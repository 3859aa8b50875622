"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters (``bench/traffic/<mix>.json``):

* ``loop``: ``"closed"`` (``clients`` each send the next request when the
  last finishes) or ``"open"`` (Poisson arrivals at ``rate_per_s``; a
  mix served at each model's own knee is one file per model);
* ``prompt`` / ``output``: a length distribution, ``{"dist":
  "lognormal", "median", "sigma", "min", "max"}`` or ``{"dist":
  "uniform", "min", "max"}``;
* ``requests``: how many requests the schedule holds (closed loop);
* ``slots``, ``max_seq``, ``pool_bytes``: the engine the mix is served by;
* ``setup_prefill``: closed loop only — the first ``clients`` requests
  are admitted and prefilled during set-up, so the window starts with
  every slot decoding;
* ``check_requests``: finished requests compared with the reference.

Every seed gets the same requests and inter-arrival gaps, in an order
drawn from the seed; the prompt tokens are drawn from the seed too.  The
lengths are the quantiles of each distribution at evenly spaced
probabilities, and each output length is paired with a prompt length by
a rule that no seed changes (:func:`pairing`).  The order is stratified
(:func:`stratified_order`): the first 2**k requests of any seed hold one
output length from each of 2**k equal slices of the distribution, so the
part of the schedule that a window reaches, and not only the whole of
it, offers the same work under every seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass
class Planned:
    """One request of the schedule."""
    index: int
    prompt: np.ndarray          # (S,) int32 token ids
    max_new: int
    due_s: float                # offset from the window start (open loop)


def _quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """n lengths at probabilities (i + 0.5) / n, clipped, as ints."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in p])
        v = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        v = lo + p * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def stratified_order(n: int, rng: np.random.Generator) -> np.ndarray:
    """A seed-drawn permutation of ``range(n)`` whose every prefix is
    spread over the range: an Owen-scrambled van der Corput order.

    Over the next power of two, N = 2**b, position i takes the bit
    reversal of i, and each bit of it is flipped by a random bit chosen
    by the bits above it; indices past n are skipped.  The first 2**k
    positions then fall one into each block of N / 2**k indices."""
    b = max(0, (n - 1).bit_length())
    flips = rng.integers(0, 2, size=max(1, 1 << b), dtype=np.int64)
    out = []
    for i in range(1 << b):
        r = int(format(i, f"0{b}b")[::-1], 2) if b else 0
        idx, node = 0, 1
        for lvl in range(b - 1, -1, -1):
            bit = (r >> lvl) & 1
            idx = (idx << 1) | (bit ^ int(flips[node]))
            node = 2 * node + bit
        if idx < n:
            out.append(idx)
    return np.array(out, dtype=np.int64)


def pairing(n: int, block: int) -> np.ndarray:
    """The prompt rank of each output rank, the same for every seed.

    Output ranks come in slices of ``block`` neighbours (a closed loop's
    ``requests / clients``, so its first ``clients`` requests take one
    from each slice); each slice is matched with a slice of neighbouring
    prompt ranks by one fixed shuffle.  Prompt and output lengths are so
    independent over the schedule, and whichever member of a slice a seed
    sends, its prompt is nearly the same."""
    if block < 1 or n % block:
        block = 1
    shuffle = np.random.default_rng(0).permutation(n // block)
    j = np.arange(n)
    return block * shuffle[j // block] + j % block


def request_count(mix: Dict[str, Any], seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    return int(mix["requests"])


def schedule(mix: Dict[str, Any], seed: int, vocab: int,
             seconds: float) -> List[Planned]:
    """The requests a run offers, in the order it offers them."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    block = n // int(mix["clients"]) if mix["loop"] == "closed" else 1
    order = stratified_order(n, rng)
    prompts = _quantiles(mix["prompt"], n)[pairing(n, block)][order]
    outputs = _quantiles(mix["output"], n)[order]
    if mix["loop"] == "open":
        # exponential gaps at evenly spaced probabilities, in seed order;
        # n arrivals spread over the window at rate_per_s on average
        p = (np.arange(n) + 0.5) / n
        gaps = (-np.log1p(-p) / float(mix["rate_per_s"]))[
            stratified_order(n, rng)]
        due = np.cumsum(gaps) - gaps[0] * 0.5
        due *= seconds / (due[-1] + gaps[-1] * 0.5)
    else:
        due = np.zeros(n)
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int64)
        out.append(Planned(i, toks.astype(np.int32), int(outputs[i]),
                           float(due[i])))
    return out


def check_sample(finished: List[int], served: Dict[int, int], seed: int,
                 k: int) -> List[int]:
    """Which finished requests the reference checks: the one that served
    the most tokens, and k - 1 more drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (served[i], -i))
    rest = [i for i in sorted(finished) if i != longest]
    rng = np.random.default_rng(seed ^ 0x5EED)
    pick = list(rng.permutation(len(rest))[:max(0, k - 1)])
    return [longest] + [rest[j] for j in sorted(pick)]


def pool_pages(mix: Dict[str, Any], kv_bytes_per_token: int,
               page_size: int) -> int:
    """Pages of the KV pool: what ``pool_bytes`` holds, never more than
    every slot at ``max_seq``."""
    full = int(mix["slots"]) * math.ceil(int(mix["max_seq"]) / page_size)
    fit = int(float(mix["pool_bytes"]) // (kv_bytes_per_token * page_size))
    return min(full, fit)
