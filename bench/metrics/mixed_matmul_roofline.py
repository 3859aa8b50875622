"""mixed_matmul's share of its roofline on the decode path: the FLOPs and
bytes the window's decode steps need from their packed projections
(decoding rows only, weights at the recipe's size) over the kernel's
summed device time inside the decode program, in percent."""
from harness import work
from harness.roofline import DECODE, kernel_seconds, program_spans, share

LAYER = "mixed_matmul kernel (kernels/mixed_matmul.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    steps = len(program_spans(ctx, DECODE))
    rows = len(ctx.decode_rows())
    secs, calls = kernel_seconds(ctx, "mixed_matmul", DECODE)
    if not steps or not rows or not calls:
        return None
    dep = ctx.spec["deployment"]
    ratio, mult = float(dep["quant_ratio"]), int(dep["salient_multiple"])
    f1, b1 = work.decode_matmuls(ctx.dims, 1, ratio, mult)
    f0, b0 = work.decode_matmuls(ctx.dims, 0, ratio, mult)
    flops = f1 * rows
    nbytes = b0 * steps + (b1 - b0) * rows
    got = share(flops, nbytes, secs, ctx.peaks)
    if got is None:
        return None
    ctx.note("mixed_matmul_roofline", f"{got[1]}-bound, {calls} calls, "
             f"{steps} steps, {rows} rows, {secs:.6f} s")
    return got[0]
