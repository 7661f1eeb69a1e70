"""Model step programs: bytes a decode step must read, counted by the
configuration's family (`families/<family>/costs.py`, `decode_step_bytes`:
the weights in their served type and the KV of every live token) — over the
HBM bandwidth, over the decode program's device time a step."""
from xtrace import MODULES


def read(ctx):
    dec = ctx.traced_decode()
    if ctx.trace is None or not dec:
        return None
    secs, n = ctx.trace.total_s(MODULES, ctx.program("decode")["module"])
    if not n:
        return None
    step_s = secs / (n * ctx.cell.serving["decode_chunk"])
    need = ctx.family("costs").decode_step_bytes(ctx)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / step_s
