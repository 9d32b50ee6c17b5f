"""Operations and bytes of the target's work, computed from its
published sizes (``harness.weights.Qwen2``), and the least time the chip
needs for them at the peaks of ``bench/peaks.json``.  bf16 weights and
KV: 2 bytes per element."""

from __future__ import annotations

import math

BYTES = 2


def layer_params(m) -> int:
    return sum(math.prod(s) for s in m.layer_shapes().values())


def matmul_params(m) -> int:
    """Parameters a token multiplies through: every layer and the head
    (the embedding lookup is a gather, not a matmul)."""
    return m.layers * layer_params(m) + m.hidden * m.vocab


def weight_bytes(m) -> int:
    """Bytes a forward pass reads of the weights: every layer, the
    head, and the final norm."""
    return BYTES * (matmul_params(m) + m.hidden)


def kv_bytes_per_token(m) -> int:
    return BYTES * 2 * m.layers * m.kv_heads * m.head_dim


def attention_flops(m, queries: int, context: int) -> float:
    """QK^T and PV of ``queries`` tokens over ``context`` cells each."""
    return 4.0 * m.layers * m.heads * m.head_dim * queries * context


def token_flops(m, context: int) -> float:
    """One token through the target at position ``context``."""
    return 2.0 * matmul_params(m) + attention_flops(m, 1, context + 1)


def prefill_flops(m, n: int) -> float:
    """A prompt of n tokens, causal attention."""
    return 2.0 * matmul_params(m) * n + attention_flops(m, 1, n * (n + 1) / 2)


def verify_work(m, rows: int, width: int, ctx_cells: int, padded_vocab: int):
    """(FLOPs, bytes) of one packed verify pass: ``rows`` verified rows
    of ``width`` + 1 query tokens each over ``ctx_cells`` cached cells in
    all: the weights read once, the rows' KV read once, the logits
    written once."""
    q = rows * (width + 1)
    f = (2.0 * matmul_params(m) * q
         + attention_flops(m, width + 1, ctx_cells)
         + attention_flops(m, rows, (width + 1) * (width + 2) / 2))
    b = (weight_bytes(m) + kv_bytes_per_token(m) * (ctx_cells + q)
         + BYTES * q * padded_vocab)
    return f, b


def least_s(flop: float, byte: float, peak: dict) -> float:
    return max(flop / peak["bf16_flops"], byte / peak["hbm_bytes_per_s"])


def verify_least_s(m, peak: dict, rows: int, width: int, ctx_cells: int,
                   padded_vocab: int = 0) -> float:
    f, b = verify_work(m, rows, width, ctx_cells, padded_vocab or m.vocab)
    return least_s(f, b, peak)
