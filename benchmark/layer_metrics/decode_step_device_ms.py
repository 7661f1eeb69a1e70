"""Model step programs: device time of the decode program a step (its
executions in the trace over `decode_chunk` steps each)."""
from xtrace import MODULES


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.total_s(MODULES, ctx.program("decode")["module"])
    return secs / (n * ctx.cell.serving["decode_chunk"]) * 1e3 if n else None
