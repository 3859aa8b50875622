"""paged_prefill's share of its roofline: the FLOPs and bytes the
window's prefill chunks need (``work.paged_prefill`` of each chunk's
``start`` and ``length``: causal queries over the context and the chunk,
the context's KV read and the chunk's written, every layer) over the
kernel's summed device time inside the chunk program, in percent.

Each chunk's (start, length) is what the engine put on its
``engine.prefill_chunk`` span (``runtime/tracing.py``).  The benchmark's
trace reduction keeps no span arguments, so this reads the run's trace
file again for those spans inside the window.  A program without such
spans gives no reading.  Beside the value it notes the bound, the
chunks and kernel calls counted, and the tiles the engine recorded
(``EngineMetrics.attn_ppcb``, ``prefill_bh``) where it has them.
"""
from harness import trace as trace_mod, work
from harness.cells import BENCH_DIR
from harness.roofline import CHUNK, kernel_seconds, program_spans, share

LAYER = "paged_prefill kernel (kernels/paged_prefill.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p95_ms"
TRACE_DIR = BENCH_DIR / "out" / "trace"
SPAN = "engine.prefill_chunk"


def window_chunks(path, lo, hi):
    """(start, length) of every ``engine.prefill_chunk`` span of the trace
    at ``path`` that starts inside [lo, hi] (ns), in start order."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != SPAN or not lo <= e.start_ns <= hi:
                    continue
                args = dict(e.stats)
                if "start" in args and "length" in args:
                    out.append((e.start_ns, int(args["start"]),
                                int(args["length"])))
    return [(s, n) for _, s, n in sorted(out)]


def read(ctx):
    if "window_ns" not in ctx.trace:
        return None
    secs, calls = kernel_seconds(ctx, "paged_prefill", CHUNK)
    if not calls:
        return None
    try:
        path = trace_mod.latest_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    chunks = window_chunks(path, *ctx.trace["window_ns"])
    if not chunks:
        return None
    flops = nbytes = 0.0
    for start, length in chunks:
        f, b = work.paged_prefill(ctx.dims, start, length)
        flops, nbytes = flops + f, nbytes + b
    got = share(flops, nbytes, secs, ctx.peaks)
    if got is None:
        return None
    em = ctx.engine_metrics
    ctx.note("paged_prefill_roofline", f"{got[1]}-bound, {calls} calls, "
             f"{len(program_spans(ctx, CHUNK))} chunk steps, "
             f"{len(chunks)} chunk spans, "
             f"{sum(n for _, n in chunks)} tokens, {secs:.6f} s; tiles "
             f"paged_attention ppcb {getattr(em, 'attn_ppcb', None)}, "
             f"paged_prefill bh {getattr(em, 'prefill_bh', None)}")
    return got[0]
