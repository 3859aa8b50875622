"""Share of the traced window in which no operation ran on the device,
in percent (averaged over the chips used)."""
LAYER = "device (TPU v5e)"
UNIT = "%"
BETTER = "lower"
MOVES = "tbt_p50_ms"


def read(ctx):
    busy, window = ctx.trace.get("busy_s"), ctx.trace.get("window_s")
    if not window:
        return None
    return 100.0 * (1.0 - busy / window)
