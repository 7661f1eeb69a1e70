"""Benchmark client: how late the generator sent a request after it was due
(open loop), 90th percentile over the requests attempted."""
from layers import percentile


def read(ctx):
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx.requests if "sent" in r]
    return percentile(late, 0.9) if late else None
