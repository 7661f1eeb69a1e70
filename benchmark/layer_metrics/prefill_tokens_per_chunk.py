"""Scheduler: the prompt tokens one prefill chunk batch carries, over the
window (`prefill_tokens` over `prefill_chunks` of the round records): a
batch has up to `prefill_kmax` rows of up to one bucket each, and every
batch costs a dispatch and a turn of the prefill/decode alternation."""


def read(ctx):
    rounds = [r for r in ctx.flight if "prefill_chunks" in r]
    chunks = sum(r["prefill_chunks"] for r in rounds)
    return sum(r["prefill_tokens"] for r in rounds) / chunks if chunks else None
