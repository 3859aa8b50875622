"""The decode steps' share of the chip's peak: model FLOPs of every token
decoded in the window (2 per quantized linear and head weight, plus
attention over the token's context) over the wall time from the first
decode step's start to the last one's end, over the bf16 peak, in
percent.  It bounds every kernel's share from above on the decode path,
so a kernel taken off the path cannot hide a slower step."""
from harness import work
from harness.roofline import DECODE, program_spans

LAYER = "model step (models/model.py decode_step_paged, prefill_step_paged)"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p50_ms"


def read(ctx):
    spans = program_spans(ctx, DECODE)
    rows = ctx.decode_rows()
    if not spans or not rows:
        return None
    flops = sum(work.decode_token_flops(ctx.dims, c) for c in rows)
    wall = (spans[-1][1] - spans[0][0]) * 1e-9
    return 100.0 * flops / wall / float(ctx.peaks["bf16_flops"])
