"""Whether what the timed path served is correct.

After the window, a sample of the finished requests (the one that
served the most tokens, and more drawn from the seed) is run through the
configuration's plain reference.  For every served token the gap by
which the reference's logit for it lies below the reference's best logit
at that position is read; the run is correct when each number the cell
limits (``bench/limits/<cell>.json``: the widest and the mean gap) is
within its limit.
Greedy serving picks the program's own best token, so the gap is the
program's numerical distance from the reference; a token altered where
it is produced, a stale KV page or a wrong weight opens it wide.

The control (:func:`control_gaps`) is the reference in float8: at each
of the same positions, the gap of the token float8 puts first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def served_gaps(logits: Sequence[np.ndarray],
                served: Sequence[Sequence[int]]) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    out = []
    for lg, toks in zip(logits, served):
        toks = np.asarray(toks, np.int64)
        if len(toks) != lg.shape[0] or np.any(toks < 0) or \
                np.any(toks >= lg.shape[1]):
            out.append(np.full((max(len(toks), 1),), np.inf))
            continue
        best = lg.max(axis=1)
        out.append(best - lg[np.arange(len(toks)), toks])
    return np.concatenate(out) if out else np.zeros((0,))


def control_gaps(ref_logits: Sequence[np.ndarray],
                 low_logits: Sequence[np.ndarray]) -> np.ndarray:
    """Per position: the reference's gap of the token float8 puts first."""
    return served_gaps(ref_logits, [lo.argmax(axis=1) for lo in low_logits])


def stats(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers a limit can hold: the widest gap, the mean gap, and
    the share of served tokens that are not the reference's best."""
    if gaps.size == 0 or not np.all(np.isfinite(gaps)):
        inf = float("inf")
        return {"max_logit_gap": inf, "mean_logit_gap": inf,
                "off_argmax_share": inf, "tokens_compared": int(gaps.size)}
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "off_argmax_share": float(np.mean(gaps > 0)),
            "tokens_compared": int(gaps.size)}


def compare(reference, spec: Dict, seed: int,
            requests: List[Tuple[np.ndarray, List[int]]]) -> Dict[str, float]:
    """The gap numbers over ``requests`` (prompt, served tokens)."""
    logits = reference.served_logits(spec, seed, requests)
    return stats(served_gaps(logits, [s for _, s in requests]))


def judge(got: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Correct when some tokens were compared and every number the cell
    limits is within its limit; returns the numbers beside their limits."""
    checks = {name: {"value": got[name], "limit": float(lim)}
              for name, lim in limits.items()}
    ok = bool(checks) and got["tokens_compared"] >= 1 and all(
        c["value"] <= c["limit"] for c in checks.values())
    checks["tokens_compared"] = {"value": got["tokens_compared"],
                                 "limit": 1}
    return ok, checks
