"""Device: one minus the merged busy time of the device's operations over
the span from the first to the last of them in the trace."""


def read(ctx):
    if ctx.trace is None or ctx.trace.span_s() <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.span_s())
