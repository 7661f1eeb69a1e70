"""Scheduler: the requests that share one prefill chunk batch, over the
window (`prefill_rows` over `prefill_chunks` of the round records): only
rows of one bucket batch together."""


def read(ctx):
    rounds = [r for r in ctx.flight if "prefill_chunks" in r]
    chunks = sum(r["prefill_chunks"] for r in rounds)
    return sum(r["prefill_rows"] for r in rounds) / chunks if chunks else None
