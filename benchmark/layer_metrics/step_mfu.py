"""Model step programs: the whole step's share of the chip's bf16 peak over
the traced span. Operations the tokens of the traced rounds need, counted by
the configuration's family (`families/<family>/costs.py`, `step_ops`: block
matmuls for every decoded and prefilled position, the head for every decoded
token and once a prefilled row, attention over the keys each position
attends to) — over the span from the first to the last device operation of
the trace times the peak. Idle time inside the span counts against it, as it
does for the user."""


def read(ctx):
    if ctx.trace is None or not ctx.flight_traced:
        return None
    span = ctx.trace.span_s()
    ops = ctx.family("costs").step_ops(ctx)
    if span <= 0 or ops is None:
        return None
    return 100.0 * ops / (span * ctx.peaks["bf16_flops"])
