"""Scheduler: from a request's admission to a slot until its prompt is
ready to decode (the program's per-request `prefill_s`, its chunks and the
decode rounds between them), median over the requests attempted that it
logged."""
from layers import percentile


def read(ctx):
    spans = [ctx.server_log[r["request_id"]]["prefill_s"] * 1e3
             for r in ctx.requests
             if "prefill_s" in ctx.server_log.get(r.get("request_id"), ())]
    return percentile(spans, 0.5) if spans else None
