"""From a profiler trace to numbers: busy and idle time, per-program and
per-kernel device time. The benchmark's own reduction (`utils/traceprof` is
the program's and is not used), checked by `tests/test_trace.py` against the
recorded trace under `recorded/`.

Reads either what `jax.profiler` wrote (`*.xplane.pb`, through
`jax.profiler.ProfileData`) or the compact form `tools/cut_trace.py` cuts
from one: `{"device": ..., "lanes": {"XLA Modules": [[name, start_ns,
dur_ns], ...], "XLA Ops": [...]}}`, gzipped JSON.

On a TPU the device's plane is `/device:TPU:<n>`; its `XLA Modules` line has
one event per executed program (`jit_decode(...)`), its `XLA Ops` line one
per operation inside, named by its HLO line (a Pallas kernel appears under
the name it was given: `%ragged_paged_attention.12 = ...`).
Busy time is the union of the `XLA Ops` intervals — nested control-flow
events (`while`) cover their bodies, so a union and not a sum.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

MODULES, OPS = "XLA Modules", "XLA Ops"


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO line (`%copy.578 =
    bf16[...] copy(...)`): keep what stands before the `=`, without `%`."""
    return name.split(" = ", 1)[0].lstrip("%")


class Trace:
    def __init__(self, lanes: dict, device: str = ""):
        self.device = device
        self.lanes = {k: sorted(((str(n), int(s), int(d)) for n, s, d in v),
                                key=lambda e: (e[1], -e[2]))
                      for k, v in lanes.items()}

    # ------------------------------------------------------------ loading

    @classmethod
    def load(cls, path: str) -> "Trace":
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no *.xplane.pb under {path}")
            path = found[-1]
        if path.endswith(".pb"):
            return cls.from_xplane(path)
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        return cls(doc["lanes"], doc.get("device", ""))

    @classmethod
    def from_xplane(cls, path: str, device_index: int = 0) -> "Trace":
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        tpu = sorted((p for p in planes if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
        if tpu:
            plane = tpu[device_index]
            lanes = {ln.name: [(short_name(e.name), e.start_ns, e.duration_ns)
                               for e in ln.events]
                     for ln in plane.lines if ln.name in (MODULES, OPS)}
            return cls(lanes, plane.name)
        # No accelerator plane (a CPU rehearsal): the CPU client's compute
        # threads stand in, so that the same code runs; their names and
        # times mean nothing for a chip.
        host = next(p for p in planes if p.name == "/host:CPU")
        ops = [(e.name, e.start_ns, e.duration_ns) for ln in host.lines
               if ln.name.startswith("tf_XLA") for e in ln.events
               if e.duration_ns > 0]
        return cls({MODULES: [], OPS: ops}, "/host:CPU")

    def compact(self, start_ns: int = 0, end_ns: int = 1 << 62) -> dict:
        return {"device": self.device, "lanes": {
            k: [[n, s, d] for n, s, d in v if start_ns <= s and s + d <= end_ns]
            for k, v in self.lanes.items()}}

    # ------------------------------------------------------------ queries

    def events(self, lane: str, pattern: str) -> list:
        """`[(start_ns, dur_ns)]` of the lane's events whose name matches."""
        rx = re.compile(pattern)
        return [(s, d) for n, s, d in self.lanes.get(lane, ()) if rx.search(n)]

    def total_s(self, lane: str, pattern: str) -> tuple:
        """(seconds, count) of the lane's events whose name matches."""
        ev = self.events(lane, pattern)
        return sum(d for _, d in ev) * 1e-9, len(ev)

    def _merged(self) -> list:
        out = []
        for _, s, d in self.lanes.get(OPS, ()):
            if d <= 0:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], s + d)
            else:
                out.append([s, s + d])
        return out

    def busy_s(self) -> float:
        return sum(b - a for a, b in self._merged()) * 1e-9

    def span_s(self) -> float:
        m = self._merged()
        return (m[-1][1] - m[0][0]) * 1e-9 if m else 0.0

    def top_ops(self, n: int = 10) -> list:
        """`[[name, seconds]]`, leaves only: an event that covers later
        events of its lane (a `while` around its body) is left out, so the
        list adds up to no more than the busy time."""
        totals, ops = {}, self.lanes.get(OPS, ())
        for i, (name, s, d) in enumerate(ops):
            if d <= 0:
                continue
            if i + 1 < len(ops) and ops[i + 1][1] < s + d:
                continue  # covers the next event: a parent, not a leaf
            key = re.sub(r"(\.remat\d*|\.clone|\.\d+)+$", "", name) or name
            totals[key] = totals.get(key, 0.0) + d * 1e-9
        return [[k, v] for k, v in sorted(totals.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """`[[what ran before the gap, seconds]]`, the longest idle gaps
        summed by the program that ended before each (the host's own spans
        are not in the trace yet: the `tracing` issue's)."""
        import bisect

        mods = sorted((s + d, re.sub(r"\(.*$", "", name))
                      for name, s, d in self.lanes.get(MODULES, ()))
        ends = [e for e, _ in mods]
        merged, totals = self._merged(), {}
        for (_, a_end), (b_start, _) in zip(merged, merged[1:]):
            i = bisect.bisect_right(ends, a_end + 1000) - 1
            key = f"after {mods[i][1] if i >= 0 else '?'}"
            totals[key] = totals.get(key, 0.0) + (b_start - a_end) * 1e-9
        return [[k, v] for k, v in sorted(totals.items(),
                                          key=lambda kv: -kv[1])[:n]]
