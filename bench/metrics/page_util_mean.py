"""Mean share of the KV pool's pages in use, per tick of the window
(the engine's own per-tick counter), in percent."""
LAYER = "page pool (runtime/paged_cache.py)"
UNIT = "%"
BETTER = "higher"
MOVES = "output_tok_s"


def read(ctx):
    xs = ctx.engine_metrics.page_util[ctx.window.tick_from:]
    return 100.0 * sum(xs) / len(xs) if xs else None
