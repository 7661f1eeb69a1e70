"""Scheduler: what a round costs beyond its device programs. The flight
recorder's round wall, mean over the traced rounds, less the device time of
the decode and prefill programs in the trace a traced round."""
import statistics

from xtrace import MODULES


def read(ctx):
    rounds = [r for r in ctx.flight_traced if r.get("round_wall_s")]
    if ctx.trace is None or not rounds:
        return None
    dev = sum(ctx.trace.total_s(MODULES, ctx.program(p)["module"])[0]
              for p in ("decode", "prefill"))
    if dev <= 0:
        return None
    wall = statistics.fmean(r["round_wall_s"] for r in rounds)
    return (wall - dev / len(rounds)) * 1e3
