"""Median device time of one chunk-step program execution: the union of
the device operations inside each execution of ``prefill_step_paged``
(the program that runs ``paged_prefill``) in the traced window."""
import statistics

from harness.roofline import CHUNK, program_spans

LAYER = "model step (models/model.py decode_step_paged, prefill_step_paged)"
UNIT = "ms"
BETTER = "lower"
MOVES = "tbt_p95_ms"


def read(ctx):
    spans = program_spans(ctx, CHUNK)
    if not spans:
        return None
    times = ctx.trace["devices"][0].op_time_in(spans)
    return statistics.median(times) * 1e-6
