"""mixed_matmul's share of its roofline on the decode path: the FLOPs and
bytes the traced decode steps need from their packed projections (the
family's ``decode_matmul_work``: decoding rows only, weights at the
recipe's size) over the kernel's summed device time inside the decode
program, in percent."""
from harness.roofline import DECODE, kernel_seconds, program_spans, share

LAYER = "mixed_matmul kernel (kernels/mixed_matmul.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    steps = len(program_spans(ctx, DECODE))
    rows = ctx.decode_rows(steps)
    secs, calls = kernel_seconds(ctx, "mixed_matmul", DECODE)
    if not steps or not rows or not calls:
        return None
    flops, nbytes = ctx.family.decode_matmul_work(ctx, steps, rows)
    got = share(flops, nbytes, secs, ctx.peaks)
    if got is None:
        return None
    ctx.note("mixed_matmul_roofline", f"{got[1]}-bound, {calls} calls, "
             f"{steps} steps, {len(rows)} rows "
             f"(of {len(ctx.decode_rows())} in the window), {secs:.6f} s")
    return got[0]
