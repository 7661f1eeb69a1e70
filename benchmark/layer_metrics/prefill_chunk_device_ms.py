"""Model step programs: device time of the prefill programs a chunk batch
run (one execution is one chunk for each of up to k admitted rows)."""
from xtrace import MODULES


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.total_s(MODULES, ctx.program("prefill")["module"])
    return secs / n * 1e3 if n else None
