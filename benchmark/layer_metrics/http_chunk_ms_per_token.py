"""Service / HTTP: what handing a token's NDJSON chunk to the response
writer costs. The program's per-request `chunk_s` (the sum of its
`http.chunk` spans: each is open while the writer has the chunk) over its
output tokens, median over the requests attempted that it logged. The
record is written before the last chunk's span ends, so the sum is one
chunk short."""
from layers import percentile


def read(ctx):
    logged = (ctx.server_log.get(r.get("request_id"), {}) for r in ctx.requests)
    per = [rec["chunk_s"] * 1e3 / rec["output_tokens"] for rec in logged
           if "chunk_s" in rec and rec.get("output_tokens")]
    return percentile(per, 0.5) if per else None
