"""Scheduler: the pace of a prefill, apart from what the prefix cache
spared it. The program's per-request `prefill_s` (admission to prompt
ready: its chunk batches and the decode rounds between them) over the
prompt tokens it had to prefill (`prompt_tokens` less
`prefix_reused_tokens`), median over the requests attempted that it
logged."""
from layers import percentile


def read(ctx):
    logged = (ctx.server_log.get(r.get("request_id"), {}) for r in ctx.requests)
    per = [rec["prefill_s"] * 1e3
           / max(rec["prompt_tokens"] - rec["prefix_reused_tokens"], 1)
           for rec in logged
           if "prefix_reused_tokens" in rec and rec.get("prompt_tokens")]
    return percentile(per, 0.5) if per else None
