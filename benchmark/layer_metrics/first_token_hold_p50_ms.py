"""Scheduler: from a request's prompt being ready until its first token is
handed to the stream (the program's per-request `first_hold_s`: a first
token rides the harvest of a decode round), median over the requests
attempted that it logged."""
from layers import percentile


def read(ctx):
    holds = [ctx.server_log[r["request_id"]]["first_hold_s"] * 1e3
             for r in ctx.requests
             if "first_hold_s" in ctx.server_log.get(r.get("request_id"), ())]
    return percentile(holds, 0.5) if holds else None
