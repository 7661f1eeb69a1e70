"""Model step programs: bytes a decode step must read — the weights in their
served type and the KV of every live token (`costs.py`) — over the HBM
bandwidth, over the decode program's device time a step."""
import costs
from xtrace import MODULES


def read(ctx):
    dec = ctx.traced_decode()
    if ctx.trace is None or not dec:
        return None
    secs, n = ctx.trace.total_s(MODULES, ctx.program("decode")["module"])
    if not n:
        return None
    step_s = secs / (n * ctx.cell.serving["decode_chunk"])
    need = (costs.weight_bytes_per_step(ctx.cfg)
            + costs.kv_bytes_per_token(ctx.cfg) * dec["live_tokens"])
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / step_s
