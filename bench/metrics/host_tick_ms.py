"""Host work per decode tick, in ms: the median, over the ``engine.tick``
spans inside the traced window that hold an ``engine.decode``, of the
span's length less its ``engine.readback`` children (the host blocked on
a device result).  That is the host's own serial work per tick, which
the device waits for.

The spans are the program's own (``runtime/tracing.py``); the
benchmark's trace reduction keeps only its ``bench.*`` spans, so this
reads the run's trace file again when the reduction holds no engine
span.  A program that emits none gives no reading.  Beside the value it
notes, for the run's log, each phase's median per decode tick, the
window's device idle time by the innermost host span over it (by
interval), and the longest idle gaps named by engine spans.
"""
import os
import statistics

from harness import host_spans, trace as trace_mod
from harness.cells import BENCH_DIR

LAYER = "engine tick loop (runtime/engine.py Engine.tick)"
UNIT = "ms"
BETTER = "lower"
MOVES = "tbt_p50_ms"
TRACE_DIR = BENCH_DIR / "out" / "trace"


def _host(ctx):
    """The run's host spans, the engine's included where it emitted any."""
    host = ctx.trace["plain"]["host"]
    if host_spans.has_engine_spans(host):
        return host
    try:
        path = trace_mod.latest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return host
    full = host_spans.load_host(path)
    # the same run's trace: its window span is the reduction's, exactly
    window = [h for h in host if h[0] == "bench.window"]
    if not window or window[0] not in full:
        return host
    ctx.note("trace_bytes", str(os.path.getsize(path)))
    return full


def _explain(ctx, host, ticks):
    med = host_spans.phase_medians_ms(host)
    ctx.note("host_tick_phases_ms", ", ".join(
        f"{n} {v:.4f}" for n, v in med.items()))
    if "window_ns" not in ctx.trace:
        return
    lo, hi = ctx.trace["window_ns"]
    gaps = ctx.trace["devices"][0].gaps(lo, hi)
    idle = trace_mod.total(gaps)
    split = host_spans.idle_by_span(gaps, host)
    ctx.note("idle_by_span", f"{idle * 1e-9:.6f} s idle, " + ", ".join(
        f"{n} {t * 1e-9:.6f} s ({100 * t / idle:.2f}%)"
        for n, t in split if idle))
    client = sum(d for n, s, d in host
                 if n in ("bench.drain", "bench.submit")
                 and lo <= s and s + d <= hi)
    ctx.note("idle_per_decode_tick_ms",
             f"{idle / ticks * 1e-6:.4f} idle, {client / ticks * 1e-6:.4f} "
             f"bench.drain+bench.submit, over {ticks} decode ticks")
    ctx.note("idle_gaps_by_engine_span", ", ".join(
        f"{n} {t:.6f}" for n, t in trace_mod.label_gaps(gaps, host)))


def read(ctx):
    host = _host(ctx)
    ticks = host_spans.host_tick_ns(host)
    if not ticks:
        return None
    _explain(ctx, host, len(ticks))
    return statistics.median(ticks) * 1e-6
