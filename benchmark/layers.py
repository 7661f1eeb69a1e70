"""What the per-layer readers are given, and how they are found: one file a
metric under `layer_metrics/`, named as the metric is in `BENCHMARK.json`,
with one function `read(ctx)` that returns the number or None. A reader that
finds nothing to read returns None and the metric is left out of the line; a
reader that raises is logged and left out the same way."""

from __future__ import annotations

import dataclasses
import os
import statistics
import traceback

import costs
import spec


@dataclasses.dataclass
class Context:
    cell: spec.Cell
    peaks: dict
    requests: list          # the client's records of the requests attempted
    server_log: dict        # request id -> the program's request-log record
    flight: list            # flight-recorder round records inside the window
    flight_traced: list     # ... of the rounds the device trace covers
    metrics_t0: dict        # /metrics at the window's two edges
    metrics_t1: dict
    trace: object = None    # trace.Trace of the traced rounds, or None
    model: str = "duckdb-nsql"

    @property
    def cfg(self) -> dict:
        return self.cell.config

    def family(self, part: str):
        """A part of the configuration's model family (`spec.family`)."""
        return spec.family(self.cfg, part)

    def program(self, name: str) -> dict:
        return spec.load_json("programs", name + ".json")

    def kernel(self, name: str):
        return spec.load_module(
            os.path.join(spec.HERE, "kernels", name + ".py"))

    def serving_delta(self, block: str, key: str) -> float:
        a = self.metrics_t0[self.model]["serving"][block][key]
        b = self.metrics_t1[self.model]["serving"][block][key]
        return b - a

    def traced_decode(self) -> dict:
        """What a decode step of the traced rounds works on, from the flight
        records: `live_tokens`, the tokens live over ALL the occupied slots
        together, and `active_slots`; means over the rounds. The record's
        `perf_ctx` is the mean context of ONE occupied slot when the round
        was issued (`ctx_sum // occupancy`), so a round's total is that
        times its `occupancy`. `live_tokens / active_slots` is then the
        context a decoded token attends to, weighted by the slots."""
        rounds = [r for r in self.flight_traced if r.get("occupancy")]
        if not rounds:
            return {}
        return {"live_tokens": statistics.fmean(
                    r["perf_ctx"] * r["occupancy"] for r in rounds),
                "active_slots": statistics.fmean(r["occupancy"] for r in rounds),
                "rounds": len(rounds)}

    def traced_prefill(self) -> dict:
        """Positions the traced rounds prefilled and the keys they attended
        to, from the flight records' per-admission `prefix_reuse`."""
        positions = attended = 0.0
        for r in self.flight_traced:
            for a in r.get("prefix_reuse", ()):
                positions += a["prefilled"]
                attended += costs.prefill_attention_positions(
                    a["reused"], a["prefilled"])
        return {"positions": positions, "attended": attended}


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, int(-(-q * len(xs) // 1)) - 1))]


def read_all(ctx: Context, metrics: list, log) -> dict:
    out = {}
    for m in metrics:
        path = os.path.join(spec.HERE, "layer_metrics", m["name"] + ".py")
        try:
            value = spec.load_module(path).read(ctx)
        except Exception:  # noqa: BLE001 — one reader never costs the run
            log(f"per-layer reader {m['name']} raised:\n{traceback.format_exc()}")
            continue
        if value is None:
            log(f"per-layer reader {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
