"""Scheduler: what the loop itself costs a round. Mean over the window's
round records of the sum of `host_s`: the loop's stages since the last
record by span name, on the host's clock, without the wait for the device
(`harvest_wait_s`) and the wait for work (`idle_s`)."""
import statistics


def read(ctx):
    busy = [sum(r["host_s"].values()) for r in ctx.flight if "host_s" in r]
    return statistics.fmean(busy) * 1e3 if busy else None
