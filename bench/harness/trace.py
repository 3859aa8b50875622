"""From a profiler trace to events, and from events to device times.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and keeps, as plain lists, what the
benchmark reads: every event on the device planes' op and module lines,
and the benchmark's own host spans (``bench.*``).  The rest of this
module works on that plain form, so a trace cut small can be checked in
and tested without a chip.

On a TPU the device plane is ``/device:TPU:<n>``; its ``XLA Modules``
line holds one event per program execution (``jit_<name>(<fingerprint>)``;
the engine's steps are jitted partials, so both are ``jit__unknown``) and
its ``XLA Ops`` line one event per operation, named by its HLO text
(``%paged_attention.64 = f32[...] custom-call(...)``), which is cut here
to the instruction name.  A Pallas kernel's instruction carries its
``pallas_call`` name (``mixed_matmul``, ``paged_attention``,
``paged_prefill``).  Control flow nests: a ``conditional`` op spans the
ops it runs.  Host and device events share one clock.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN = "bench."
_SUFFIX = re.compile(r"[.\-_](\d+|remat\d*)$")
_INSTR = re.compile(r"^%?([\w.\-]+)\s*=")

Interval = Tuple[float, float]          # (start_ns, end_ns)


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Dict:
    """Plain form: ``{"devices": {plane: {"ops": [...], "modules":
    [...]}}, "host": [...]}``, each event ``[name, start_ns, dur_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                lines[key].extend([instruction(e.name), float(e.start_ns),
                                   float(e.duration_ns)]
                                  for e in line.events)
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(HOST_SPAN))
    return out


def instruction(name: str) -> str:
    """``%paged_attention.64 = f32[...] ...`` -> ``paged_attention.64``."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def base_name(name: str) -> str:
    """An op's name without XLA's numeric and rematerialization suffixes:
    ``mixed_matmul.12`` -> ``mixed_matmul``, ``fusion.7.remat2`` ->
    ``fusion``."""
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class DeviceTrace:
    """One device plane of a trace, in nanoseconds."""

    def __init__(self, plane: Dict):
        # ops by base name, in start order
        self.ops = sorted(((base_name(n), s, s + d)
                           for n, s, d in plane["ops"]),
                          key=lambda o: (o[1], -o[2]))
        self.modules = sorted(((n, s, s + d)
                               for n, s, d in plane["modules"]),
                              key=lambda m: m[1])

    def executions(self, kernel: str) -> List[Interval]:
        """Program executions that ran ``kernel``, sorted: the decode step
        is the program that runs ``paged_attention``, the chunk step the
        one that runs ``paged_prefill``."""
        starts = [s for n, s, _ in self.ops if n == kernel]
        out = []
        for _, s, e in self.modules:
            i = _first_at_or_after(starts, s)
            if i < len(starts) and starts[i] <= e:
                out.append((s, e))
        return out

    def busy(self, lo: float, hi: float) -> List[Interval]:
        return union(clip(((s, e) for _, s, e in self.ops), lo, hi))

    def op_time_in(self, spans: Sequence[Interval]) -> List[float]:
        """Per span: the union of op time inside it."""
        starts = [s for _, s, _ in self.ops]
        out = []
        for lo, hi in spans:
            i = _first_at_or_after(starts, lo)
            j = _first_at_or_after(starts, hi)
            out.append(total(union(clip(
                ((s, e) for _, s, e in self.ops[i:j]), lo, hi))))
        return out

    def kernel_time(self, kernel: str,
                    within: Optional[Sequence[Interval]] = None) -> Tuple[float, int]:
        """(summed device ns, calls) of ``kernel``'s op events, only those
        inside ``within`` when given."""
        spans = sorted(within) if within is not None else None
        t, n = 0.0, 0
        for name, s, e in self.ops:
            if name != kernel:
                continue
            if spans is not None and not _inside(s, spans):
                continue
            t += e - s
            n += 1
        return t, n

    def top_ops(self, lo: float, hi: float, k: int = 10) -> List[Tuple[str, float]]:
        """The k operations (by base name) with the most self time in
        [lo, hi], in seconds; an op's self time leaves out the ops nested
        inside it (a ``conditional`` spans its branch's ops)."""
        acc: Dict[str, float] = defaultdict(float)
        stack: List[List] = []               # [end, name, self]
        for name, s, e in self.ops:
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            while stack and stack[-1][0] <= s:
                end, n, own = stack.pop()
                acc[n] += own
            if stack:
                stack[-1][2] -= e - s
            stack.append([e, name, e - s])
        for end, n, own in stack:
            acc[n] += own
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(n, t * 1e-9) for n, t in top]

    def gaps(self, lo: float, hi: float) -> List[Interval]:
        """Idle intervals between busy ones inside [lo, hi]."""
        out, cur = [], lo
        for s, e in self.busy(lo, hi):
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            out.append((cur, hi))
        return out


def _first_at_or_after(xs: Sequence[float], t: float) -> int:
    lo, hi = 0, len(xs)
    while lo < hi:
        mid = (lo + hi) // 2
        if xs[mid] < t:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _inside(t: float, spans: Sequence[Interval]) -> bool:
    lo, hi = 0, len(spans)
    while lo < hi:
        mid = (lo + hi) // 2
        if spans[mid][1] < t:
            lo = mid + 1
        else:
            hi = mid
    return lo < len(spans) and spans[lo][0] <= t <= spans[lo][1]


def label_gaps(gaps: Sequence[Interval], host: Sequence, k: int = 10
               ) -> List[Tuple[str, float]]:
    """The k longest idle gaps, each named by the innermost benchmark host
    span (``bench.*``) that covers its midpoint, else ``"none"``."""
    spans = [(n, s, s + d) for n, s, d in host]
    out = []
    for s, e in sorted(gaps, key=lambda g: -(g[1] - g[0]))[:k]:
        mid = (s + e) / 2
        cover = [(he - hs, n) for n, hs, he in spans if hs <= mid <= he]
        out.append((min(cover)[1] if cover else "none", (e - s) * 1e-9))
    return out
