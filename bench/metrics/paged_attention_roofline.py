"""paged_attention's share of its roofline: the FLOPs and live-context KV
bytes the window's decoded tokens need (one query per decoding row over
its context, every layer) over the kernel's summed device time, in
percent."""
from harness import work
from harness.roofline import DECODE, kernel_seconds, share

LAYER = "paged_attention kernel (kernels/paged_attention.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    rows = ctx.decode_rows()
    secs, calls = kernel_seconds(ctx, "paged_attention", DECODE)
    if not rows or not calls:
        return None
    flops, nbytes = work.paged_attention(ctx.dims, rows)
    got = share(flops, nbytes, secs, ctx.peaks)
    if got is None:
        return None
    ctx.note("paged_attention_roofline", f"{got[1]}-bound, {calls} calls, "
             f"{len(rows)} rows, {secs:.6f} s")
    return got[0]
