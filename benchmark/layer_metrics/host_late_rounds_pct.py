"""Scheduler: the share of the window's rounds in which the host set the
pace. A round whose `harvest_wait_s` is under a millisecond found its
tokens ready: the host reached the harvest after the device had finished."""
LATE_S = 1e-3


def read(ctx):
    waits = [r["harvest_wait_s"] for r in ctx.flight if "harvest_wait_s" in r]
    return (100.0 * sum(w < LATE_S for w in waits) / len(waits)
            if waits else None)
