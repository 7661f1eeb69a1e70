"""Kernels: the least time the chip could take for the traced calls of
`ragged_paged_attention` (the larger of operations over the bf16 peak and
bytes over the HBM bandwidth, from `kernels/ragged_paged_attention.py`) over
the time its events took in the trace."""
from xtrace import OPS


def read(ctx):
    dec = ctx.traced_decode()
    if ctx.trace is None or not dec:
        return None
    k = ctx.kernel("ragged_paged_attention")
    secs, n = ctx.trace.total_s(OPS, k.EVENT)
    if not n:
        return None
    ops, bytes_ = k.cost(ctx.cfg, dec["live_tokens"], dec["active_slots"])
    least = max(ops / ctx.peaks["bf16_flops"],
                bytes_ / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * n / secs
