"""Kernels: the least time the chip could take for the prefill attention of
the positions the traced rounds prefilled (`kernels/flash_gqa_attention.py`,
all layers) over the time the kernel's events took in the trace."""
from xtrace import OPS


def read(ctx):
    pre = ctx.traced_prefill()
    if ctx.trace is None or not pre["positions"]:
        return None
    k = ctx.kernel("flash_gqa_attention")
    secs, n = ctx.trace.total_s(OPS, k.EVENT)
    if not n:
        return None
    ops, bytes_ = k.cost(ctx.cfg, pre["attended"], pre["positions"],
                         ctx.cell.serving["prompt_bucket"])
    least = ctx.cfg["num_hidden_layers"] * max(
        ops / ctx.peaks["bf16_flops"], bytes_ / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
