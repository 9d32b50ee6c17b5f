"""The one traffic generator.  A traffic mix is a JSON file of
parameters under ``bench/traffic/``; this module reads it and nothing
else decides what a request looks like.

Arrivals are an open loop.  Lengths are log-normal and clipped.  The
mix's ``sizes`` stratified quantiles of each distribution, paired once
by a fixed permutation, make a fixed set of request sizes; the same
number of stratified quantiles of the exponential make a fixed set of
gaps between arrivals (Poisson arrivals).  Both are dealt out in blocks,
each block in a fixed order, the same for every seed: a run sees only
some tens of requests, so an order drawn from the seed would give each
seed different work.  The gaps go in van der Corput order, so every run
of consecutive gaps spans the quantiles evenly and a window of any
offset is offered the mix's rate, not a stretch of long or short gaps.

A run starts at steady state: the mix's ``resident`` requests are
already in flight when traffic starts, as Little's law puts them there
(rate x mean lifetime).  A request is in flight for a time proportional
to its output, so the residents are the fixed sizes drawn in proportion
to their outputs, each with a stratified share of its output already
served: it arrives with the prompt plus those tokens as its context,
rounded down to one of the mix's prompt lengths (the shapes the warm-up
reaches), and the rest of its output to serve.

The seed draws the token ids (uniform over the vocabulary) and each
request's class label (uniform over the mix's labels).  Requests are
numbered residents first, then arrivals."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

PAIRING_SEED = 20250319


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a log-normal with the given median
    and sigma, clipped to [min, max] and rounded to whole tokens."""
    nd = NormalDist()
    mu = math.log(spec["median"])
    q = [math.exp(mu + spec["sigma"] * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)


def size_table(params: dict) -> np.ndarray:
    """(sizes, 2) prompt and output lengths: the mix's fixed multiset."""
    n = int(params["sizes"])
    prompts = lognormal_quantiles(params["prompt"], n)
    outputs = lognormal_quantiles(params["output"], n)
    perm = np.random.default_rng(PAIRING_SEED).permutation(n)
    return np.stack([prompts, outputs[perm]], axis=1)


def gap_table(params: dict) -> np.ndarray:
    """The fixed multiset of gaps between arrivals: stratified quantiles
    of the exponential, scaled so their mean is exactly 1 / rate."""
    n = int(params["sizes"])
    g = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g / g.mean() / float(params["rate_per_s"])


def balanced_order(n: int) -> np.ndarray:
    """Position k takes the quantile whose rank is that of k's binary
    radical inverse among 0..n-1 (for n a power of two, k's bits
    reversed): 0, n/2, n/4, 3n/4, ..."""
    inv = [int(format(k, "b")[::-1], 2) / 2 ** k.bit_length() if k else 0.0
           for k in range(n)]
    return np.argsort(np.argsort(inv))


def resident_table(params: dict, sizes: np.ndarray) -> np.ndarray:
    """(resident, 2) context and remaining output lengths of the
    requests in flight when traffic starts (see the module's doc)."""
    r = int(params.get("resident", 0))
    if r == 0:
        return np.zeros((0, 2), np.int64)
    prompts, outputs = sizes[:, 0], sizes[:, 1]
    cum = np.cumsum(outputs) / outputs.sum()
    pick = np.searchsorted(cum, (np.arange(r) + 0.5) / r)
    share = (np.random.default_rng([PAIRING_SEED, r]).permutation(r)
             + 0.5) / r
    served = np.floor(outputs[pick] * share).astype(np.int64)
    lengths = np.unique(prompts)
    ctx = lengths[np.searchsorted(lengths, prompts[pick] + served,
                                  side="right") - 1]
    return np.stack([ctx, outputs[pick] - served], axis=1)


class Schedule:
    """Request ``i`` of a run: its sizes, class label and tokens, and for
    an arrival the second (from the start of traffic) it falls due."""

    def __init__(self, params: dict, seed: int, vocab: int):
        self.params = params
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.sizes = size_table(params)
        self.gaps = gap_table(params)[balanced_order(int(params["sizes"]))]
        self.residents = resident_table(params, self.sizes)
        self.classes = list(params["classes"])
        self._blocks = {}
        self._due = [0.0]

    @property
    def resident(self) -> int:
        return len(self.residents)

    def _block(self, b: int):
        if b not in self._blocks:
            fixed = np.random.default_rng([PAIRING_SEED, b])
            n = len(self.sizes)
            self._blocks[b] = (fixed.permutation(n), self.gaps)
        return self._blocks[b]

    def lengths(self, i: int):
        """(prompt_len, output_len) of request i."""
        if i < self.resident:
            p, o = self.residents[i]
        else:
            a = i - self.resident
            order, _ = self._block(a // len(self.sizes))
            p, o = self.sizes[order[a % len(self.sizes)]]
        return int(p), int(o)

    def label(self, i: int) -> str:
        n = len(self.sizes)
        labels = np.random.default_rng([self.seed, 0, i // n]).integers(
            len(self.classes), size=n)
        return self.classes[int(labels[i % n])]

    def tokens(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, i])
        return rng.integers(0, self.vocab, self.lengths(i)[0]).astype(np.int32)

    def due(self, i: int) -> float:
        """Seconds from the start of traffic at which arrival i (counted
        after the residents) is due: the first at 0, then the dealt-out
        gaps."""
        n = len(self.sizes)
        while len(self._due) <= i:
            k = len(self._due) - 1
            _, gaps = self._block(k // n)
            self._due.append(self._due[-1] + float(gaps[k % n]))
        return self._due[i]
