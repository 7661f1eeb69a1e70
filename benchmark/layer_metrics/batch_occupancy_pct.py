"""Scheduler: occupied slots a decode round, mean over the window's rounds,
as a share of the slots (flight recorder `occupancy`)."""
import statistics


def read(ctx):
    occ = [r["occupancy"] for r in ctx.flight if "occupancy" in r]
    return (100.0 * statistics.fmean(occ) / ctx.cell.serving["slots"]
            if occ else None)
