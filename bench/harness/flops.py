"""The least time the chip needs for a piece of work at the peaks of
``bench/peaks.json``.  The work itself, operations and bytes, is
counted by each architecture's module (``bench/archs/<model_type>.py``)
from its published sizes."""

from __future__ import annotations

BYTES = 2  # bf16 weights and KV: bytes per element


def least_s(flop: float, byte: float, peak: dict) -> float:
    return max(flop / peak["bf16_flops"], byte / peak["hbm_bytes_per_s"])


def verify_least_s(model, peak: dict, call) -> float:
    """Least time of the packed verify pass a traced step ``call`` (a
    ``session.Call``) made, for a ``program.Model``."""
    return least_s(*model.arch.verify_work(model.sizes, call), peak)
