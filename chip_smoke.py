"""Serve a few requests through the SPIN serving path on one TPU chip.

    python chip_smoke.py

Run it from the root of a checkout: it imports ``repro`` from the
``src/`` directory beside this file, and it fails unless JAX's first
device is a TPU.  Everything runs in this one process, which holds the
chip until it exits.

The models are the paper's pairing at published widths with random
bf16 weights from seed 0: a LLaMA-7B target cut to 16 of its 32 layers
(the cut layers stand for a second pipeline stage) and the LLaMA-68M,
-265M and -616M drafters whole, all on one 32000-token vocabulary.
``launch/serve.py``'s own ``build_server`` builds the engine, scheduler
and pools for 8 ``mix`` requests (capacity 4, gamma 4, LBSS, paged bf16
KV), which are served twice:

  (a) the default path, XLA attention over the paged pool;
  (b) the same requests with ``--fused-kernels on``: compiled Pallas.

Each phase prints its wall time, compile time and count, accepted
tokens, LLM verify passes and the device's peak memory, then checks that
every request finished with its ``max_new`` tokens, that every token
stream is the target's plain greedy decode up to a bf16 near tie, and
that the target, teacher-forced on each stream, scores every one of its
tokens within that near-tie tolerance of its best.  The last line of
standard output is one JSON object that names the device; any failure
raises and exits non-zero before it is printed.  These are smoke
figures, not benchmark results.
"""

import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
TARGET_LAYERS = 16
SERVE_ARGS = (
    "--dataset mix --requests 8 --capacity 4 --seed 0 --gamma 4 "
    "--selector lbss --scale 1.0 --max-slots 1000"
).split()
FUSED = ["--fused-kernels", "on"]
# A stream may leave the reference only where the reference itself
# nearly ties: the engine's token must score within TIE_TOL of the
# reference's best logit (so the top-two gap is below TIE_TOL too).  The
# logits are bf16: the top logit of 32000 unit-scale logits lies in
# [4, 8), where bf16 values are 1/32 apart, and the engine's attention
# paths round in another order than the dense reference over 16 bf16
# layers.  Prefilling the same prompts alone and in a padded batch of 8
# moved logits by up to 0.074 on the CPU (16 layers, d=1024, bf16), so
# the tolerance allows about three times that.  The comparison of that
# request stops there: the two continuations differ.
TIE_TOL = 0.25


class CompileMeter:
    """Counts XLA compiles (persistent-cache loads included) and their
    seconds through ``jax.monitoring``."""

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

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def greedy_reference(llm, reqs, streams):
    """Plain greedy decode of every request through the target's own
    ``prefill``/``decode``, batched over requests.  Returns per request
    the reference tokens and, per step, the reference's best logit minus
    its logit for the engine's token and its top-two gap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_new = max(r.max_new for r in reqs)
    S = -(-max(r.prompt_len for r in reqs) // 16) * 16
    toks = np.zeros((len(reqs), S), np.int32)
    eng = np.zeros((len(reqs), n_new), np.int32)
    for i, r in enumerate(reqs):
        toks[i, : r.prompt_len] = r.prompt
        eng[i, : r.max_new] = streams[r.rid][: r.max_new]
    lengths = jnp.asarray([r.prompt_len for r in reqs], jnp.int32)
    V = llm.cfg.vocab_size
    logits, cache = llm.prefill(jnp.asarray(toks), lengths, S + n_new)
    lg = jnp.take_along_axis(logits, (lengths - 1)[:, None, None], axis=1)
    eng = jnp.asarray(eng)
    out, gap_eng, gap_top2 = [], [], []
    for t in range(n_new):
        lg = lg[:, -1, :V].astype(jnp.float32)
        top2 = jax.lax.top_k(lg, 2)[0]
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        mine = jnp.take_along_axis(lg, eng[:, t : t + 1], axis=1)[:, 0]
        out.append(tok)
        gap_eng.append(top2[:, 0] - mine)
        gap_top2.append(top2[:, 0] - top2[:, 1])
        lg, cache = llm.decode(cache, tok[:, None], lengths + t)
    return tuple(np.asarray(jnp.stack(x, 1)) for x in (out, gap_eng, gap_top2))


def forced_gaps(llm, reqs, streams):
    """Teacher-force the target on every request's prompt and engine
    stream in one batched ``prefill``.  Returns (requests, max_new) the
    target's best logit minus its logit for the engine's token, each
    given the engine's own prefix (0 past a request's ``max_new``)."""
    import jax.numpy as jnp
    import numpy as np

    n_new = max(r.max_new for r in reqs)
    lens = [r.prompt_len + r.max_new - 1 for r in reqs]
    S = -(-max(lens) // 16) * 16
    toks = np.zeros((len(reqs), S), np.int32)
    at = np.zeros((len(reqs), n_new), np.int32)
    eng = np.zeros((len(reqs), n_new), np.int32)
    for i, r in enumerate(reqs):
        got = streams[r.rid][: r.max_new]
        toks[i, : lens[i]] = list(r.prompt) + list(got[:-1])
        at[i] = np.minimum(r.prompt_len - 1 + np.arange(n_new), lens[i] - 1)
        eng[i, : r.max_new] = got
    logits, _ = llm.prefill(jnp.asarray(toks), jnp.asarray(lens, jnp.int32), S)
    lg = jnp.take_along_axis(logits, jnp.asarray(at)[:, :, None], axis=1)
    lg = lg[:, :, : llm.cfg.vocab_size].astype(jnp.float32)
    mine = jnp.take_along_axis(lg, jnp.asarray(eng)[:, :, None], axis=2)[..., 0]
    gap = np.asarray(jnp.max(lg, -1) - mine)
    emitted = np.arange(n_new) < np.asarray([[r.max_new] for r in reqs])
    return np.where(emitted, gap, 0.0)


def check_streams(name, llm, reqs, streams):
    """Raise unless every stream equals the greedy reference up to a
    near tie, and every token of every stream scores within TIE_TOL of
    the target's best given the stream's own prefix; print the counts."""
    import numpy as np

    gap = forced_gaps(llm, reqs, streams)
    n_tok = sum(r.max_new for r in reqs)
    i, t = np.unravel_index(np.argmax(gap), gap.shape)
    if gap[i, t] > TIE_TOL:
        raise AssertionError(
            f"{name}: request {reqs[i].rid} token {t} scores {gap[i, t]:.4f} "
            f"below the target's best given the same prefix > {TIE_TOL}"
        )
    print(
        f"{name}: teacher-forced check: {n_tok} of {n_tok} tokens within "
        f"{TIE_TOL} of the target's best given their own prefix, "
        f"{int((gap > 0).sum())} below it (largest gap {gap[i, t]:.4f})"
    )
    ref, gap_eng, gap_top2 = greedy_reference(llm, reqs, streams)
    matched, ties, worst = 0, 0, 0.0
    for i, r in enumerate(reqs):
        got = streams[r.rid]
        if len(got) < r.max_new:
            raise AssertionError(
                f"{name}: request {r.rid} emitted {len(got)} of its "
                f"{r.max_new} tokens"
            )
        for t in range(r.max_new):
            if got[t] == ref[i, t]:
                continue
            if gap_eng[i, t] > TIE_TOL:
                raise AssertionError(
                    f"{name}: request {r.rid} token {t} is {got[t]}, greedy "
                    f"decoding gives {ref[i, t]}, whose logit is higher by "
                    f"{gap_eng[i, t]:.4f} > {TIE_TOL}"
                )
            ties += 1
            worst = max(worst, float(gap_top2[i, t]))
            print(
                f"{name}: request {r.rid} leaves the reference at token {t} "
                f"of {r.max_new} on a near tie (top-two gap "
                f"{gap_top2[i, t]:.4f}, engine token "
                f"{gap_eng[i, t]:.4f} below the best)"
            )
            break
        else:
            matched += 1
    print(
        f"{name}: token check: {matched} of {len(reqs)} requests match "
        f"greedy decoding in full, {ties} stop at a near tie (largest "
        f"top-two gap {worst:.4f}, tolerance {TIE_TOL})"
    )


def run_phase(name, serve, zoo, argv, meter, dev):
    """Serve the requests once through ``serve.build_server(argv)``; print
    the phase's figures and return (server, requests)."""
    import jax

    c0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    server, reqs, args = serve.build_server(argv, zoo=zoo)
    verify_passes = 0
    for _ in range(args.max_slots):
        rec = server.step()
        verify_passes += bool(rec.get("active"))
        if rec.get("done") and not server.scheduler.outstanding:
            break
    stats = server.stats()
    jax.block_until_ready(server.llm_pool.cache)
    wall = time.perf_counter() - t0
    c1, s1, h1 = meter.snapshot()
    done = sum(r.done for r in server.requests.values())
    print(
        f"{name}: wall {wall:.3f} s, compile {s1 - s0:.3f} s over "
        f"{c1 - c0} compiles ({h1 - h0} from the persistent cache), "
        f"{done}/{len(reqs)} requests done, accepted tokens "
        f"{stats['accepted_tokens']}, LLM verify passes {verify_passes}, "
        f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}"
    )
    print(
        f"{name}: kv {stats['kv_layout']} {stats['kv_dtype']}, spec "
        f"{stats['spec_shape']}, fused_kernels {stats['fused_kernels']}, "
        f"fused tile config at every site: {stats['fused_config']}"
    )
    if done != len(reqs):
        raise AssertionError(f"{name}: {len(reqs) - done} requests unfinished")
    return server, reqs


def smoke(llm_cfg, ssm_cfgs, dev):
    """Build the zoo and run both phases with their checks."""
    import jax

    from repro.kernels.ops import interpret_mode
    from repro.launch import serve

    meter = CompileMeter(jax.monitoring)
    vocab = llm_cfg.vocab_size
    t0 = time.perf_counter()
    zoo = serve.build_zoo(vocab, 0, llm_cfg=llm_cfg, ssm_cfgs=ssm_cfgs)
    models = [zoo[0], *zoo[1]]
    jax.block_until_ready([b.params for b in models])
    count, seconds, _ = meter.snapshot()
    sizes = [sum(x.size for x in jax.tree.leaves(b.params)) for b in models]
    print(
        f"chip_smoke: weights built in {time.perf_counter() - t0:.3f} s "
        f"(compile {seconds:.3f} s over {count} compiles): {sizes[0]} "
        f"target and {sum(sizes[1:])} drafter parameters, "
        f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}"
    )
    args = SERVE_ARGS + ["--vocab", str(vocab)]
    for name, extra in (("(a) default", []), ("(b) fused", FUSED)):
        server, reqs = run_phase(name, serve, zoo, args + extra, meter, dev)
        if extra and (not server.fused or interpret_mode()):
            raise AssertionError(f"{name}: the engine ran no compiled Pallas")
        streams = {r.rid: r.emitted for r in reqs}
        check_streams(name, zoo[0], reqs, streams)


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX's first device is on platform "
            f"{dev.platform!r}",
            file=sys.stderr,
        )
        return 1
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    from repro.configs import spin_llama
    from repro.launch import serve

    print(f"chip_smoke: compile cache in {serve.use_compile_cache()}")
    target = spin_llama.LLAMA_7B
    drafters = [spin_llama.LLAMA_68M, spin_llama.LLAMA_265M, spin_llama.LLAMA_616M]
    print(
        f"chip_smoke: target {target.name} d={target.d_model} "
        f"heads={target.n_heads} head_dim={target.hd} d_ff={target.d_ff} "
        f"vocab={target.vocab_size} {target.dtype}, cut to {TARGET_LAYERS} "
        f"of its {target.n_layers} layers (the cut layers stand for a second "
        f"pipeline stage); drafters {', '.join(c.name for c in drafters)} "
        f"whole; random weights from seed 0"
    )
    llm_cfg = dataclasses.replace(target, n_layers=TARGET_LAYERS)
    smoke(llm_cfg, drafters, dev)
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
