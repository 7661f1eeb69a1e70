"""Model step programs: the whole step's share of the chip's bf16 peak over
the traced span. Operations the tokens of the traced rounds need — block
matmuls for every decoded and prefilled position, the head for every decoded
token and once a prefilled row, attention over the keys each position
attends to (`costs.py`) — over the span from the first to the last device
operation of the trace times the peak. Idle time inside the span counts
against it, as it does for the user."""
import costs


def read(ctx):
    if ctx.trace is None or not ctx.flight_traced:
        return None
    span = ctx.trace.span_s()
    cfg = ctx.cfg
    dec, pre = ctx.traced_decode(), ctx.traced_prefill()
    decoded = sum(r.get("emitted", 0) for r in ctx.flight_traced)
    rows = sum(len(r.get("prefix_reuse", ())) for r in ctx.flight_traced)
    if span <= 0 or decoded + pre["positions"] <= 0:
        return None
    ctx_per_token = (dec["live_tokens"] / dec["active_slots"]) if dec else 0.0
    ops = (costs.matmul_flops_per_token(cfg) * (decoded + pre["positions"])
           + costs.head_flops(cfg) * (decoded + rows)
           + costs.attention_flops(cfg, ctx_per_token) * decoded
           + costs.attention_flops(cfg, 1.0) * pre["attended"])
    return 100.0 * ops / (span * ctx.peaks["bf16_flops"])
