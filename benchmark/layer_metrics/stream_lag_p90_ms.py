"""Service / HTTP: from the scheduler's worker handing over a token until
its piece leaves the stream's generator for the chunk writer — the queue
between the two threads, the wait for the interpreter lock and the
re-decoding of the output so far. The program's per-request
`stream_lag_p90_s` (90th percentile over the request's tokens), 90th
percentile over the requests attempted that it logged."""
from layers import percentile


def read(ctx):
    lags = [ctx.server_log[r["request_id"]]["stream_lag_p90_s"] * 1e3
            for r in ctx.requests
            if "stream_lag_p90_s" in ctx.server_log.get(r.get("request_id"), ())]
    return percentile(lags, 0.9) if lags else None
