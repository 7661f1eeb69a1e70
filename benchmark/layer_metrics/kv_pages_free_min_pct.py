"""Prefix cache / pages: the fewest free pages any round of the window saw,
as a share of the pool (flight recorder `kv_pages_free`). Pages the prefix
cache holds and can give back count as in use here, as the program counts
them."""


def read(ctx):
    free = [r["kv_pages_free"] for r in ctx.flight if "kv_pages_free" in r]
    total = ctx.metrics_t1[ctx.model]["serving"]["kv_pages"]["pages_total"]
    return 100.0 * min(free) / total if free and total else None
