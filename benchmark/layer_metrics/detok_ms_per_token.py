"""Service / HTTP: what turning a token into text costs. The stream's
consumer decodes the whole output again for every token; the program's
per-request `detok_s` (the sum of its `stream.detok` spans) over its output
tokens, median over the requests attempted that it logged."""
from layers import percentile


def read(ctx):
    logged = (ctx.server_log.get(r.get("request_id"), {}) for r in ctx.requests)
    per = [rec["detok_s"] * 1e3 / rec["output_tokens"] for rec in logged
           if "detok_s" in rec and rec.get("output_tokens")]
    return percentile(per, 0.5) if per else None
