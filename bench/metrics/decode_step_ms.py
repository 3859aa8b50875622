"""Median device time of one decode-step program execution: the union
of the device operations inside each execution of
``decode_step_paged`` in the traced window."""
import statistics

from harness.roofline import DECODE, program_spans

LAYER = "model step (models/model.py decode_step_paged, prefill_step_paged)"
UNIT = "ms"
BETTER = "lower"
MOVES = "tbt_p50_ms"


def read(ctx):
    spans = program_spans(ctx, DECODE)
    if not spans:
        return None
    times = ctx.trace["devices"][0].op_time_in(spans)
    return statistics.median(times) * 1e-6
