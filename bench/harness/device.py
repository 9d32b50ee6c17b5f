"""JAX set-up for a run: the persistent compilation cache, the check for
a TPU (never a CPU fallback), the compile meter and the device line of
the result."""

from __future__ import annotations

import os
import sys


def setup_jax(root: str):
    """Import JAX with its persistent compilation cache at a fixed path:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<root>/.jax_cache``.
    Every program is cached, however quick its compile, so a later run
    of the cell loads each one."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def require_tpu(jax, chips: int):
    """JAX's first device, if it is a TPU and there are at least
    ``chips`` of them; otherwise exit non-zero and print no result."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run: needs a TPU; JAX's first device is on platform "
              f"{devs[0].platform!r}", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"run: the cell needs {chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[0]


class CompileMeter:
    """Counts XLA compiles, persistent-cache loads included, and their
    seconds, through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, monitoring):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration_secs

    def _event(self, event, **kwargs):
        if event == self.HIT:
            self.cache_hits += 1


def describe(jax, dev, sess) -> dict:
    out = {"platform": dev.platform, "kind": dev.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(sess.memory_peak_bytes)}
    if sess.trace is not None:
        out["busy_s"] = sess.trace.busy_s
        out["window_s"] = sess.trace.window_s
    return out
