"""Speculative decoding primitives.

One iteration = SSM drafts ``gamma`` candidate tokens (autoregressive decode
steps), then the LLM scores ``[last_token, c_1..c_gamma]`` in ONE forward
(decode_step with T=gamma+1) and accepts a prefix:

  greedy mode    accept while draft token == LLM argmax (deterministic,
                 output identical to plain LLM greedy decoding)
  sampling mode  Leviathan-style lossless accept/reject: accept c_i with
                 prob min(1, p_i(c_i)/q_i(c_i)); on first rejection resample
                 from norm(max(0, p_i - q_i)).  Output distribution provably
                 equals the LLM's.

Both verifiers return per-row accept counts so ragged batches work; caches
are rolled back by invalidating rejected slots (segment id -1) — attention
caches only, recurrent-state verifiers use snapshot+recompute (see engine).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import config as C
from repro.models import transformer as T


@dataclasses.dataclass
class Bundle:
    """A model + jitted entry points (one per (B, T) shape, cached by jit).

    Each entry point jits a function named after its method, so its
    programs carry a stable name in a profile: ``jit_prefill``,
    ``jit_decode``, ``jit_append``, ``jit_append_paged``,
    ``jit_decode_paged``, ``jit_verify_paged`` and
    ``jit_verify_paged_tree``."""
    cfg: C.ModelConfig
    params: dict

    def __post_init__(self):
        def prefill(p, toks, lengths, max_len):
            return T.prefill(p, self.cfg, tokens=toks, lengths=lengths,
                             max_len=max_len)

        def decode(p, cache, toks, lengths):
            return T.decode_step(p, self.cfg, cache, tokens=toks,
                                 lengths=lengths)

        self._prefill = jax.jit(prefill, static_argnames=("max_len",))
        self._decode = jax.jit(decode)
        # paged entry points are cached per fused_cfg (None = XLA gather
        # path; a kernels/autotune.FusedConfig = fused Pallas path) — the
        # config is static under jit, so each distinct config is its own
        # trace and flipping --fused-kernels never retraces the other path
        self._decode_paged = {}
        self._verify_paged = {}
        self._verify_paged_tree = {}
        self._append = None
        self._append_paged = {}

    def prefill(self, toks, lengths, max_len):
        return self._prefill(self.params, toks, lengths, max_len)

    def decode(self, cache, toks, lengths):
        return self._decode(self.params, cache, toks, lengths)

    def append(self, cache, toks, lengths, segments):
        """Chunked-prefill append on a batch-1 dense row cache: ingest T
        context tokens at positions lengths..lengths+T-1.  ``segments``
        marks bucket-padding tokens with -1 so their KV writes land
        invalidated and one trace serves every chunk width bucket."""
        if self._append is None:
            def append(p, c, t, l, s):
                return T.decode_step(p, self.cfg, c, tokens=t, lengths=l,
                                     segments=s)
            self._append = jax.jit(append)
        return self._append(self.params, cache, toks, lengths, segments)

    def append_paged(self, cache, toks, lengths, segments, block_tables,
                     fused_cfg=None):
        """Chunked-prefill append through a paged block pool: the (1, T)
        chunk writes straight into the row's blocks and attends its prior
        context blocks (see serving/paged.decode_step_paged)."""
        if fused_cfg not in self._append_paged:
            from repro.serving.paged import decode_step_paged

            def append_paged(p, c, t, l, s, bt):
                return decode_step_paged(
                    p, self.cfg, c, tokens=t, lengths=l, segments=s,
                    block_tables=bt, fused_cfg=fused_cfg)
            self._append_paged[fused_cfg] = jax.jit(append_paged)
        return self._append_paged[fused_cfg](self.params, cache, toks,
                                             lengths, segments, block_tables)

    def decode_paged(self, cache, toks, lengths, block_tables,
                     fused_cfg=None):
        """Decode against a paged block pool (serving/pool.PagedCachePool).
        block_tables is a *traced* argument: table contents change every
        step without retracing."""
        if fused_cfg not in self._decode_paged:
            from repro.serving.paged import decode_step_paged

            def decode_paged(p, c, t, l, bt):
                return decode_step_paged(
                    p, self.cfg, c, tokens=t, lengths=l, block_tables=bt,
                    fused_cfg=fused_cfg)
            self._decode_paged[fused_cfg] = jax.jit(decode_paged)
        return self._decode_paged[fused_cfg](self.params, cache, toks,
                                             lengths, block_tables)

    def verify_paged(self, cache, tokens, positions, segments, q_rows,
                     block_tables, block_ids, block_owner, fused_cfg=None):
        """Packed verification gathering KV fragments straight from the
        paged block pool (no flat packed copy)."""
        if fused_cfg not in self._verify_paged:
            from repro.serving.paged import verify_step_paged

            def verify_paged(p, c, t, pos, seg, qr, bt, ids, ow):
                return verify_step_paged(
                    p, self.cfg, c, tokens=t, positions=pos, segments=seg,
                    q_rows=qr, block_tables=bt, block_ids=ids,
                    block_owner=ow, fused_cfg=fused_cfg)
            self._verify_paged[fused_cfg] = jax.jit(verify_paged)
        return self._verify_paged[fused_cfg](
            self.params, cache, tokens, positions, segments, q_rows,
            block_tables, block_ids, block_owner)

    def verify_paged_tree(self, cache, tokens, positions, segments, q_rows,
                          block_tables, block_ids, block_owner, q_anc,
                          block_node, fused_cfg=None):
        """Tree-topology packed verification: like :meth:`verify_paged`
        plus the ancestor-bitmask / per-slot node-tag mask term, so one
        pass scores every root-to-leaf path of a token tree."""
        if fused_cfg not in self._verify_paged_tree:
            from repro.serving.paged import verify_step_paged

            def verify_paged_tree(p, c, t, pos, seg, qr, bt, ids, ow, anc,
                                  node):
                return verify_step_paged(
                    p, self.cfg, c, tokens=t, positions=pos, segments=seg,
                    q_rows=qr, block_tables=bt, block_ids=ids,
                    block_owner=ow, q_anc=anc, block_node=node,
                    fused_cfg=fused_cfg)
            self._verify_paged_tree[fused_cfg] = jax.jit(verify_paged_tree)
        return self._verify_paged_tree[fused_cfg](
            self.params, cache, tokens, positions, segments, q_rows,
            block_tables, block_ids, block_owner, q_anc, block_node)

    @property
    def has_recurrent_state(self) -> bool:
        kinds = set(self.cfg.unit) | set(self.cfg.tail)
        return bool(kinds & {C.MAMBA2, C.MLSTM, C.SLSTM})


def logits_to_probs(logits, temperature: float, vocab_size: int):
    logits = logits.astype(jnp.float32)
    if logits.shape[-1] > vocab_size:   # mask vocab padding
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, -1e30)
    if temperature <= 0.0:
        # one-hot argmax (greedy "distribution")
        return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                              dtype=jnp.float32)
    return jax.nn.softmax(logits / temperature, axis=-1)


def sample(probs, rng):
    return jax.random.categorical(rng, jnp.log(jnp.maximum(probs, 1e-30)))


# ------------------------------------------------------------------ draft --

def draft(ssm: Bundle, cache, last_tokens, lengths, gamma: int, rng,
          temperature: float = 0.0, collect_probs: bool = False,
          block_tables=None, fused_cfg=None):
    """Generate gamma candidates. last_tokens: (B,1) previous accepted token.
    Returns (cand (B,gamma), qprobs (B,gamma,V)|None, cache).
    block_tables routes the decode steps through the paged KV pool;
    fused_cfg additionally routes them through the fused Pallas kernel."""
    cands, qs = [], []
    tok = last_tokens
    for g in range(gamma):
        rng, k = jax.random.split(rng)
        if block_tables is not None:
            logits, cache = ssm.decode_paged(cache, tok, lengths + g,
                                             block_tables, fused_cfg)
        else:
            logits, cache = ssm.decode(cache, tok, lengths + g)
        probs = logits_to_probs(logits[:, -1], temperature,
                                ssm.cfg.vocab_size)
        tok = (jnp.argmax(probs, -1, keepdims=True) if temperature <= 0
               else sample(probs, k)[:, None]).astype(jnp.int32)
        cands.append(tok)
        if collect_probs:
            qs.append(probs)
    cand = jnp.concatenate(cands, axis=1)
    qprobs = jnp.stack(qs, axis=1) if collect_probs else None
    return cand, qprobs, cache


def draft_tree(ssm: Bundle, cache, last_tokens, lengths, gamma: int, ranks,
               block_tables=None, fused_cfg=None):
    """Greedy tree drafting: each pool row autoregressively extends ONE
    branch of a request's token tree.

    Rows of the same request share identical context (the engine forks
    their block tables copy-on-write), so their step-1 logits are
    identical; ``ranks[b]`` selects which top-k candidate row b commits to
    at the first step (rank 0 = argmax, the main chain) — after that every
    row continues greedily down its own branch.  No cross-row
    communication is needed, and with all ranks 0 (single branch) the
    emitted tokens are bitwise identical to :func:`draft` at
    temperature 0.  Returns (cand (B, gamma), cache)."""
    ranks_np = np.asarray(ranks)
    kmax = int(ranks_np.max()) + 1 if ranks_np.size else 1
    ranks = jnp.asarray(ranks_np, jnp.int32)
    cands = []
    tok = last_tokens
    for g in range(gamma):
        if block_tables is not None:
            logits, cache = ssm.decode_paged(cache, tok, lengths + g,
                                             block_tables, fused_cfg)
        else:
            logits, cache = ssm.decode(cache, tok, lengths + g)
        probs = logits_to_probs(logits[:, -1], 0.0, ssm.cfg.vocab_size)
        best = jnp.argmax(probs, -1, keepdims=True).astype(jnp.int32)
        if g == 0 and kmax > 1:
            lg = logits[:, -1].astype(jnp.float32)
            if lg.shape[-1] > ssm.cfg.vocab_size:   # mask vocab padding
                vmask = jnp.arange(lg.shape[-1]) < ssm.cfg.vocab_size
                lg = jnp.where(vmask, lg, -1e30)
            _, topi = jax.lax.top_k(lg, kmax)
            ranked = jnp.take_along_axis(topi.astype(jnp.int32),
                                         ranks[:, None], axis=1)
            # rank 0 keeps argmax's tie-breaking (== linear draft exactly)
            tok = jnp.where(ranks[:, None] == 0, best, ranked)
        else:
            tok = best
        cands.append(tok)
    return jnp.concatenate(cands, axis=1), cache


# ----------------------------------------------------------------- verify --

def verify_greedy(llm: Bundle, cache, last_tokens, cand, lengths):
    """Greedy verification.  Returns (n_accept (B,), out_tokens (B, gamma+1),
    out_len (B,), cache).  out_tokens[i, :out_len[i]] are the tokens emitted
    this iteration (accepted prefix + 1 correction/bonus token)."""
    B, gamma = cand.shape
    inp = jnp.concatenate([last_tokens, cand], axis=1)       # (B, gamma+1)
    logits, cache = llm.decode(cache, inp, lengths)
    greedy = jnp.argmax(logits.astype(jnp.float32)[..., :llm.cfg.vocab_size],
                        axis=-1).astype(jnp.int32)           # (B, gamma+1)
    # position i of `greedy` predicts the token after input i
    match = greedy[:, :gamma] == cand                        # (B, gamma)
    n_accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    # output: accepted candidates then the LLM's own next token
    idx = jnp.arange(gamma + 1)[None, :]
    out = jnp.where(idx < n_accept[:, None],
                    jnp.pad(cand, ((0, 0), (0, 1))),
                    0)
    bonus = jnp.take_along_axis(greedy, n_accept[:, None], axis=1)
    out = out.at[jnp.arange(B), n_accept].set(bonus[:, 0])
    out_len = n_accept + 1
    return n_accept, out, out_len, cache


def verify_sampling(llm: Bundle, cache, last_tokens, cand, qprobs, lengths,
                    rng, temperature: float = 1.0):
    """Lossless speculative sampling (Leviathan et al.).  qprobs: (B,g,V)."""
    B, gamma = cand.shape
    V = qprobs.shape[-1]
    inp = jnp.concatenate([last_tokens, cand], axis=1)
    logits, cache = llm.decode(cache, inp, lengths)
    p = logits_to_probs(logits, temperature, llm.cfg.vocab_size)  # (B,g+1,V)
    p_cand = p[:, :gamma]
    q_cand = qprobs
    pc = jnp.take_along_axis(p_cand, cand[..., None], -1)[..., 0]  # (B,g)
    qc = jnp.take_along_axis(q_cand, cand[..., None], -1)[..., 0]
    rng, k1, k2 = jax.random.split(rng, 3)
    u = jax.random.uniform(k1, (B, gamma))
    accept = u < jnp.minimum(1.0, pc / jnp.maximum(qc, 1e-30))
    n_accept = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), 1), 1)  # (B,)
    # residual distribution at the first rejected position
    pos = jnp.minimum(n_accept, gamma - 1)
    p_rej = jnp.take_along_axis(p_cand, pos[:, None, None].repeat(V, -1),
                                1)[:, 0]
    q_rej = jnp.take_along_axis(q_cand, pos[:, None, None].repeat(V, -1),
                                1)[:, 0]
    resid = jnp.maximum(p_rej - q_rej, 0.0)
    resid = resid / jnp.maximum(jnp.sum(resid, -1, keepdims=True), 1e-30)
    # when everything accepted: bonus sampled from p[:, gamma]
    bonus_probs = jnp.where((n_accept == gamma)[:, None], p[:, gamma], resid)
    nxt = sample(bonus_probs, k2).astype(jnp.int32)
    idx = jnp.arange(gamma + 1)[None, :]
    out = jnp.where(idx < n_accept[:, None],
                    jnp.pad(cand, ((0, 0), (0, 1))), 0)
    out = out.at[jnp.arange(B), n_accept].set(nxt)
    out_len = n_accept + 1
    return n_accept, out, out_len, cache


# --------------------------------------------------------------- rollback --

def invalidate_slots(cache, new_lengths, upper):
    """Mark attention-cache slots with new_len <= pos < upper as empty.
    Works on the whole cache tree (scan-stacked and tail entries)."""
    def fix(entry):
        if not (isinstance(entry, dict) and "seg" in entry):
            return entry
        pos, seg = entry["pos"], entry["seg"]
        nl = new_lengths[:, None]
        up = upper[:, None]
        if pos.ndim == 3:   # scan-stacked (U, B, S)
            nl, up = nl[None], up[None]
        bad = (pos >= nl) & (pos < up)
        out = dict(entry)
        out["seg"] = jnp.where(bad, -1, seg)
        return out

    out = {}
    for key, val in cache.items():
        if key == "scan":
            out["scan"] = {k: fix(v) for k, v in val.items()}
        else:
            out[key] = fix(val)
    return out


invalidate_slots_jit = jax.jit(invalidate_slots)


# ------------------------------------------------------------- iteration --

def spec_iteration(llm: Bundle, ssm: Bundle, llm_cache, ssm_cache,
                   last_tokens, lengths, gamma, rng, temperature=0.0):
    """One full speculation+verification iteration for a batch.
    Returns (out_tokens, out_len, n_accept, llm_cache, ssm_cache,
    new_lengths, new_last)."""
    sampling = temperature > 0.0
    cand, qprobs, ssm_cache = draft(ssm, ssm_cache, last_tokens, lengths,
                                    gamma, rng, temperature,
                                    collect_probs=sampling)
    if sampling:
        rng, k = jax.random.split(rng)
        n_acc, out, out_len, llm_cache = verify_sampling(
            llm, llm_cache, last_tokens, cand, qprobs, lengths, k,
            temperature)
    else:
        n_acc, out, out_len, llm_cache = verify_greedy(
            llm, llm_cache, last_tokens, cand, lengths)
    new_lengths = lengths + out_len
    # llm cache holds K/V for inputs [last, c_1..c_gamma] at positions
    # lengths..lengths+gamma: keep last + accepted prefix, drop the rest.
    # (The correction token's KV enters next iteration as the new `last`.)
    llm_cache = invalidate_slots_jit(llm_cache, lengths + 1 + n_acc,
                                     lengths + gamma + 1)
    # SSM catch-up: the draft loop never wrote c_gamma's KV (it was produced,
    # not consumed).  One batched decode_step re-feeds this iteration's
    # outputs at positions lengths+1.., filling any hole (idempotent for
    # slots already valid); rejected-slot writes are invalidated after.
    _, ssm_cache = ssm.decode(ssm_cache, out, lengths + 1)
    ssm_cache = invalidate_slots_jit(ssm_cache, new_lengths + 1,
                                     lengths + gamma + 2)
    new_last = jnp.take_along_axis(out, (out_len - 1)[:, None], axis=1)
    return out, out_len, n_acc, llm_cache, ssm_cache, new_lengths, new_last
