"""What a run is made of, found by name: `BENCHMARK.json` names the cell,
the cell names its configuration and its traffic mix, the configuration
names its model family, and each of those is a file (a family: a directory
of four) of its own under this directory. Imports nothing but the standard
library, so the load generator's process can use it without JAX."""

from __future__ import annotations

import importlib.util
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


def load_module(path: str):
    """A module of the benchmark (a per-layer reader, a kernel's counts, a
    family's part) found by its file, not by an import, and loaded once: a
    family's part keeps compiled functions, and its siblings have to meet
    the same module."""
    path = os.path.abspath(path)
    if path not in _LOADED:
        name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(".", "_")
        sp = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


_LOADED = {}

# What a family is: `families/<family>/<part>.py`, one file a part.
#   program    config(cfg) -> the program's config object;
#              served_tree(cfg, fmt, seed, emit_ids) -> the tree in the
#              program's layout, born on the device in one jitted call
#   weights    layer(cfg, fmt, key), tables(cfg, key, emit_mask): the pure
#              per-piece functions the served tree and the reference share
#   reference  logits_at(cfg, fmt, seed, emit_ids, tokens, at, control=None):
#              the plain forward pass, float32 at `highest`
#   costs      step_ops(ctx), decode_step_bytes(ctx): what `step_mfu` and
#              `decode_hbm_pct` count, given the readers' `layers.Context`
FAMILY_PARTS = ("program", "weights", "reference", "costs")


def family_file(name: str, part: str) -> str:
    return os.path.join(HERE, "families", name, part + ".py")


def family(cfg: dict, part: str):
    """The `part` of the family that the configuration `cfg` names."""
    return load_module(family_file(cfg["family"], part))


def beside(file: str, part: str):
    """For a family's own files: the sibling part in the same directory."""
    return load_module(os.path.join(os.path.dirname(file), part + ".py"))


def check_family(cfg: dict, cfg_file: str) -> None:
    """A configuration that names no family, or one that is not whole,
    stops the run with the file that is missing in the message."""
    name = cfg.get("family")
    if not name:
        raise SystemExit(f"benchmark: {cfg_file} names no `family` "
                         f"(a directory under benchmark/families/)")
    for part in FAMILY_PARTS:
        path = family_file(str(name), part)
        if not os.path.isfile(path):
            raise SystemExit(
                f"benchmark: family {name!r} of {cfg_file} lacks "
                f"{os.path.relpath(path, ROOT)}")


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
        check_family(self.config, cfg_entry["file"])
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
