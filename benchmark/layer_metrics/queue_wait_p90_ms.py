"""Service / admission: the program's own per-request `queue_wait_s` (submit
to slot admission, from its request log), 90th percentile over the requests
attempted that it logged."""
from layers import percentile


def read(ctx):
    waits = [ctx.server_log[r["request_id"]].get("queue_wait_s", 0.0) * 1e3
             for r in ctx.requests if r.get("request_id") in ctx.server_log]
    return percentile(waits, 0.9) if waits else None
