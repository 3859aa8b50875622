"""The program's host spans (``engine.*``) beside the benchmark's
(``bench.*``), read from the same profiler trace.

The engine names its host work inside every tick (``engine.tick`` and,
inside it, ``engine.grow``, ``engine.admit``, ``engine.prefill_chunk``,
``engine.decode``, ``engine.sample``, ``engine.readback``,
``engine.emit``; ``engine.gc`` over a collection of generation 1 or 2).
:func:`trace.load` keeps only the benchmark's spans, so :func:`load_host`
reads the host plane again and keeps both, in the same plain
``[name, start_ns, dur_ns]`` form.  A trace of a program without engine
spans gives the benchmark's spans alone, and every function here then
returns what it returns for no spans: nothing.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from harness.trace import Interval

PREFIXES = ("bench.", "engine.")
TICK, DECODE, READBACK = "engine.tick", "engine.decode", "engine.readback"
PHASES = ("engine.grow", "engine.admit", "engine.prefill_chunk",
          "engine.decode", "engine.sample", "engine.readback",
          "engine.emit", "engine.gc")


def load_host(path: str) -> List[List]:
    """Every ``bench.*`` and ``engine.*`` event on the trace's host
    planes, each ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    out: List[List] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                           for e in line.events
                           if e.name.startswith(PREFIXES))
    return out


def has_engine_spans(host: Sequence) -> bool:
    return any(n.startswith("engine.") for n, _, _ in host)


def decode_ticks(host: Sequence) -> List[Tuple[float, float, Dict[str, float]]]:
    """The ``engine.tick`` spans inside ``bench.window`` that hold an
    ``engine.decode``: (start_ns, end_ns, summed ns of each phase span
    inside the tick), in start order."""
    win = [(s, s + d) for n, s, d in host if n == "bench.window"]
    lo, hi = win[0] if win else (float("-inf"), float("inf"))
    ticks = sorted((s, s + d) for n, s, d in host
                   if n == TICK and s >= lo and s + d <= hi)
    phases = sorted((s, s + d, n) for n, s, d in host if n in PHASES)
    out = []
    j = 0
    for ts, te in ticks:
        while j < len(phases) and phases[j][0] < ts:
            j += 1
        acc: Dict[str, float] = defaultdict(float)
        k = j
        while k < len(phases) and phases[k][0] < te:
            s, e, n = phases[k]
            if e <= te:
                acc[n] += e - s
            k += 1
        if acc.get(DECODE):
            out.append((ts, te, dict(acc)))
    return out


def host_tick_ns(host: Sequence) -> List[float]:
    """Per decode tick of the window: the tick's length less the time
    the host spent blocked on a device result (``engine.readback``) —
    the host's own serial work, which the device waits for."""
    return [te - ts - acc.get(READBACK, 0.0)
            for ts, te, acc in decode_ticks(host)]


def phase_medians_ms(host: Sequence) -> Dict[str, float]:
    """Median per decode tick of the tick and of each phase, in ms."""
    ticks = decode_ticks(host)
    if not ticks:
        return {}
    out = {TICK: statistics.median(te - ts for ts, te, _ in ticks) * 1e-6}
    for name in PHASES:
        out[name] = statistics.median(
            acc.get(name, 0.0) for _, _, acc in ticks) * 1e-6
    return out


def _innermost(host: Sequence) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece named
    by the shortest span over it (``"none"`` where none is)."""
    events = []
    for i, (_, s, d) in enumerate(host):
        events.append((s, 1, i))
        events.append((s + d, 0, i))
    events.sort()
    active: Dict[int, Tuple[float, str]] = {}
    out: List[Tuple[float, float, str]] = []
    prev: Optional[float] = None
    for t, starts, i in events:
        if prev is not None and t > prev:
            name = min(active.values())[1] if active else "none"
            out.append((prev, t, name))
        prev = t
        if starts:
            active[i] = (host[i][2], host[i][0])
        else:
            active.pop(i, None)
    return out


def idle_by_span(gaps: Sequence[Interval], host: Sequence
                 ) -> List[Tuple[str, float]]:
    """Device idle time (ns) by the innermost host span over it, counted
    by interval: a gap under several spans is split among them.  Longest
    first."""
    acc: Dict[str, float] = defaultdict(float)
    segs = _innermost(host)
    j = 0
    for gs, ge in sorted(gaps):
        cur = gs
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while cur < ge and k < len(segs) and segs[k][0] < ge:
            s, e, name = segs[k]
            if s > cur:
                acc["none"] += s - cur
            lo, hi = max(s, cur), min(e, ge)
            acc[name] += hi - lo
            cur = hi
            k += 1
        if cur < ge:
            acc["none"] += ge - cur
    return sorted(acc.items(), key=lambda kv: -kv[1])
