"""End-to-end numbers from the client's side of a window.

Every request the driver sends has a :class:`Record`: when it was due
(open loop: its scheduled arrival; closed loop: when its client sent
it), and the host-clock stamp of each of its tokens, taken when the
``tick()`` that produced the token returned.  The window is
``[t0, t1]``.

* TTFT counts every request due in the window.  One with no first token
  by ``t1`` enters with the time it has waited so far (censored), so a
  stall or a growing queue shows in the tail instead of dropping out.
* TBT is every gap between consecutive tokens of a request when both
  tokens fall in the window, stalls included.
* The output rate is every token stamped in the window over the
  window's whole length.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Record:
    due: float                         # host clock, seconds
    prompt_len: int
    stamps: List[float] = field(default_factory=list)
    finished: bool = False
    failed: bool = False


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def ttfts(records: Dict[int, Record], t0: float, t1: float) -> List[float]:
    out = []
    for r in records.values():
        if not (t0 <= r.due < t1) or r.failed:
            continue
        first = r.stamps[0] if r.stamps else None
        out.append((first if first is not None and first <= t1 else t1)
                   - r.due)
    return out


def gaps(records: Dict[int, Record], t0: float, t1: float) -> List[float]:
    out = []
    for r in records.values():
        s = [t for t in r.stamps if t0 <= t <= t1]
        out.extend(b - a for a, b in zip(s, s[1:]))
    return out


def tokens_in(records: Dict[int, Record], t0: float, t1: float) -> int:
    return sum(1 for r in records.values() for t in r.stamps
               if t0 <= t <= t1)


def metrics(records: Dict[int, Record], t0: float, t1: float,
            names) -> Dict[str, Optional[float]]:
    """The end-to-end metrics in ``names`` (setup_s excluded) for the
    window; None where the window holds nothing to measure."""
    out: Dict[str, Optional[float]] = {}
    g = gaps(records, t0, t1)
    for name in names:
        if name == "ttft_p90_ms":
            t = ttfts(records, t0, t1)
            out[name] = percentile(t, 90) * 1e3 if t else None
        elif name == "tbt_p50_ms":
            out[name] = percentile(g, 50) * 1e3 if g else None
        elif name == "tbt_p95_ms":
            out[name] = percentile(g, 95) * 1e3 if g else None
        elif name == "output_tok_s":
            out[name] = tokens_in(records, t0, t1) / (t1 - t0)
        else:
            raise ValueError(f"unknown end-to-end metric {name!r}")
    return out
