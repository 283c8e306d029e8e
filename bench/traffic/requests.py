"""The one generator of LM request traffic; a mix file names it by
``"kind": "requests"`` and sets every parameter.

A mix holds:

* ``arrivals``: ``{"loop": "closed", "clients": n}`` (each client sends its
  next request when the last one finished) or ``{"loop": "open",
  "rate_rps": r, "gap_cv": cv}`` (gamma-distributed gaps of mean ``1/r``
  and coefficient of variation ``cv``; ``cv`` 1 is Poisson);
* ``prompt_len`` and ``output_len``: a length distribution,
  ``{"dist": "uniform", "lo": a, "hi": b}`` (inclusive) or
  ``{"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}``
  (clipped);
* ``shared`` (optional): ``{"frac": f, "prefixes": k, "prefix_len": p,
  "tail_len": <distribution>}``: a share ``f`` of requests open with one of
  ``k`` system prefixes of ``p`` tokens and go on with a unique tail;
* ``block``: requests come in blocks of this many.

Every seed gets the same work at the same times: in each block of
``block`` requests the lengths are the same ``block`` quantiles of their
distributions, the same number of requests share a prefix, and (open
loop) the gaps are the same ``block`` values, in one fixed order.  The
run's seed picks the token ids and each request's PRN seed.  (Where the
seed also chose the order, the tail of time to first token moved 10-15 %
from seed to seed with where the longest prompts fell in the window.)
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()
_ORDER_SEED = 0  # the one order of sizes, shares and gaps in each block


def _quantiles(dist: Dict, k: int) -> np.ndarray:
    """``k`` mid-point quantiles of a length distribution, as integers."""
    u = (np.arange(k) + 0.5) / k
    if dist["dist"] == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return (lo + np.floor(u * (hi - lo + 1))).astype(np.int64)
    if dist["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.round(v), dist["lo"], dist["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _gamma_gaps(mean_s: float, cv: float, k: int) -> np.ndarray:
    """``k`` gamma gaps of the given mean and coefficient of variation: the
    same fixed sample for every seed, rescaled to the exact mean."""
    shape = 1.0 / (cv * cv)
    g = np.random.default_rng(20240815).gamma(shape, 1.0, size=k)
    return g * (mean_s / g.mean())


class RequestTraffic:
    """Deterministic request stream of one mix under one seed."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.block = int(mix.get("block", 64))
        k = self.block
        sh = mix.get("shared")
        self._n_shared = int(round(sh["frac"] * k)) if sh else 0
        self._prompt_q = _quantiles(mix["prompt_len"], k - self._n_shared)
        self._out_q = _quantiles(mix["output_len"], k)
        if sh:
            self._tail_q = _quantiles(sh["tail_len"], self._n_shared)
            rng = np.random.default_rng([self.seed, 1])
            self.prefixes = [
                rng.integers(0, self.vocab, size=sh["prefix_len"]).tolist()
                for _ in range(sh["prefixes"])]
        else:
            self.prefixes = []
        arr = mix["arrivals"]
        self.closed = arr["loop"] == "closed"
        if not self.closed:
            self._gaps = _gamma_gaps(1.0 / arr["rate_rps"], arr["gap_cv"], k)
        self._blocks: Dict[int, Tuple[np.ndarray, ...]] = {}

    def _block_perms(self, b: int):
        perms = self._blocks.get(b)
        if perms is None:
            rng = np.random.default_rng([_ORDER_SEED, 2, b])
            k = self.block
            ns = self._n_shared
            perms = (rng.permutation(k), rng.permutation(k - ns),
                     rng.permutation(k), rng.permutation(max(ns, 1)),
                     rng.permutation(k))
            self._blocks[b] = perms
        return perms

    def request(self, i: int) -> Tuple[List[int], int, int]:
        """Request ``i``: (prompt token ids, tokens to generate, PRN seed)."""
        b, j = divmod(int(i), self.block)
        p_share, p_prompt, p_out, p_tail, _ = self._block_perms(b)
        rng = np.random.default_rng([self.seed, 3, i])
        max_new = int(self._out_q[p_out[j]])
        slot = int(p_share[j])
        if slot < self._n_shared:
            prefix = self.prefixes[slot % len(self.prefixes)]
            tail = int(self._tail_q[p_tail[slot]])
            prompt = prefix + rng.integers(0, self.vocab, size=tail).tolist()
        else:
            n = int(self._prompt_q[p_prompt[slot - self._n_shared]])
            prompt = rng.integers(0, self.vocab, size=n).tolist()
        req_seed = int(rng.integers(0, 2 ** 32))
        return prompt, max_new, req_seed

    def due_times(self, n: int) -> np.ndarray:
        """Scheduled send times (seconds from the window's start) of the
        first ``n`` requests of an open loop; the first is due at 0."""
        assert not self.closed, "a closed loop has no schedule"
        gaps = []
        for b in range(-(-n // self.block)):
            gaps.append(self._gaps[self._block_perms(b)[4]])
        g = np.concatenate(gaps)[:n]
        return np.concatenate([[0.0], np.cumsum(g[:-1])])


def make(mix: Dict, seed: int, vocab: int) -> RequestTraffic:
    return RequestTraffic(mix, seed, vocab)
