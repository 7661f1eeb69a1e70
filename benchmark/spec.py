"""What a run is made of, found by name: `BENCHMARK.json` names the cell,
the cell names its configuration and its traffic mix, and each of those is a
file of its own under this directory. Imports nothing but the standard
library, so the load generator's process can use it without JAX."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TOKENIZER_DIR = os.path.join(HERE, "tokenizer")


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with the files it names."""

    def __init__(self, name: str, rehearse: bool = False):
        bench = benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = int(entry["chips"])
        cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.traffic = load_json("traffic", entry["traffic"] + ".json")
        self.cell = load_json("cells", name + ".json")
        self.rehearse = rehearse
        if rehearse:
            # The same control flow at a size the CPU runs in seconds: the
            # configuration's `rehearsal` block replaces the keys it names.
            self.config = {**self.config, **self.config["rehearsal"]}
            self.traffic = {**self.traffic, **self.traffic.get("rehearsal", {})}
            self.cell = {**self.cell, **self.cell.get("rehearsal", {})}
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def serving(self) -> dict:
        return self.config["serving"]
