"""paged_attention's share of its roofline: the FLOPs and live-context KV
bytes the traced decode steps need (the family's
``decode_attention_work``: one query per decoding row over its context,
every layer) over the kernel's summed device time, in percent."""
from harness.roofline import DECODE, kernel_seconds, program_spans, share

LAYER = "paged_attention kernel (kernels/paged_attention.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    steps = len(program_spans(ctx, DECODE))
    rows = ctx.decode_rows(steps)
    secs, calls = kernel_seconds(ctx, "paged_attention", DECODE)
    if not rows or not calls:
        return None
    flops, nbytes = ctx.family.decode_attention_work(ctx, rows)
    got = share(flops, nbytes, secs, ctx.peaks)
    if got is None:
        return None
    ctx.note("paged_attention_roofline", f"{got[1]}-bound, {calls} calls, "
             f"{steps} steps, {len(rows)} rows, {secs:.6f} s")
    return got[0]
