"""Scheduler: what dispatching one prefill chunk batch costs the loop on
the host's clock: the window's `sched.prefill_dispatch` time (the round
records' `host_s`) over the chunk batches dispatched (`prefill_chunks`).
With mixed rounds the chunks ride `sched.issue_mixed` and there is nothing
to read."""


def read(ctx):
    rounds = [r for r in ctx.flight if "prefill_chunks" in r]
    chunks = sum(r["prefill_chunks"] for r in rounds)
    spent = sum(r["host_s"].get("sched.prefill_dispatch", 0.0) for r in rounds)
    return spent * 1e3 / chunks if chunks and spent else None
