#!/usr/bin/env python3
"""The host's side of a run's device trace: the program's own spans
(`sched.*` of the scheduler's loop, `stream.detok`, `http.chunk`) as
`[(name, start_ns, dur_ns, stats)]`, on the clock of the device lanes that
`xtrace.Trace` reads from the same file, and what the device's idle gaps
lie under.

The program writes a span with `jax.profiler.TraceAnnotation`, so it is an
event of the `/host:CPU` plane of the capture's `*.xplane.pb`, on whichever
line its thread got (Python 3.12 passes no thread names on: spans are found
by name), with its keyword arguments as the event's stats (`round`,
`occupancy`, `emitted`, `rid`). A program without such spans (before PR 26)
leaves a plane without them and every function here returns nothing.

`load(path)` gives an `xtrace.Trace` with the spans as `.host_spans`, from
a `*.xplane.pb` (or the directory it is under) or from the compact form
`cut` writes: what `tools/cut_trace.py` writes plus `"host": [[name,
start_ns, dur_ns, stats], ...]`, and for the readers' tests the flight
records and request-log records of the same run (`"flight"`,
`"request_log"`). `xtrace.Trace.load` reads that file too and ignores what
it does not know.

    python3 benchmark/hostspans.py <run directory | xplane.pb> <out.json.gz> \\
        --rounds 3

cuts the first `--rounds` decode rounds that the capture holds whole (the
span that issued each and the span that waited for it), with every device
event and host span between, shifted so that the cut begins at 0.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import spec  # noqa: E402
import xtrace  # noqa: E402

PREFIXES = ("sched.", "stream.", "http.")
LOOP = "sched.loop"       # one pass of the loop: it covers the other spans
ISSUE, WAIT = "sched.issue_decode", "sched.harvest_wait"


def find_capture(cell_name: str) -> str | None:
    """The newest `*.xplane.pb` a traced run of the cell left under
    `benchmark_out/` (`run.py` removes it only after the readers ran)."""
    found = glob.glob(os.path.join(
        spec.ROOT, "benchmark_out", cell_name, "run-*-1", "server", "profile",
        "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def host_plane(path: str) -> list:
    """The program's spans on the `/host:CPU` plane of an `*.xplane.pb`,
    sorted by start."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append((e.name, int(e.start_ns), int(e.duration_ns),
                                  {k: v for k, v in e.stats}))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def load(path: str) -> xtrace.Trace:
    """Device lanes and host spans of one capture, one clock."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = max(found, key=os.path.getmtime)
    tr = xtrace.Trace.load(path)
    if path.endswith(".pb"):
        tr.host_spans = host_plane(path)
    else:
        with gzip.open(path, "rt") as f:
            doc = json.load(f)
        tr.host_spans = [(n, int(s), int(d), st)
                         for n, s, d, st in doc.get("host", ())]
    return tr


def of(ctx) -> list:
    """The host spans for a reader: those its trace came with (a recorded
    capture, `load`), else those of the run's own capture, read once; the
    trace remembers which file that was (`capture_path`)."""
    tr = ctx.trace
    if tr is None:
        return []
    if not hasattr(tr, "host_spans"):
        tr.capture_path = find_capture(ctx.cell.name)
        tr.host_spans = host_plane(tr.capture_path) if tr.capture_path else []
    return tr.host_spans


def run_dir(tr: xtrace.Trace) -> str | None:
    """The directory of the run whose capture `of` read the spans from
    (`<run>/server/profile/...`), for what a reader leaves beside the
    run's log; None for a recorded capture."""
    path = getattr(tr, "capture_path", None)
    marker = os.sep + os.path.join("server", "profile") + os.sep
    return path[:path.rindex(marker)] if path and marker in path else None


def rounds_of(spans: list) -> dict:
    """`{round: {span name: (start_ns, dur_ns, stats)}}` of the spans that
    carry a `round`."""
    out = {}
    for name, start, dur, stats in spans:
        if "round" in stats:
            out.setdefault(int(stats["round"]), {})[name] = (start, dur, stats)
    return out


def idle_by_span(tr: xtrace.Trace, spans: list) -> tuple:
    """`(idle_s, {span name: seconds})`: the device's idle time inside the
    traced span (the gaps between its merged busy intervals) and the part
    of it under each `sched.*` span other than the loop pass itself. The
    loop's stages follow one another on one thread, so the parts add up to
    no more than the idle time; what is left lay under no stage."""
    stages = [(s, s + d, n) for n, s, d, _ in spans
              if n.startswith("sched.") and n != LOOP and d > 0]
    stages.sort()
    starts = [s for s, _, _ in stages]
    merged = tr._merged()
    idle, under = 0, {}
    for (_, a), (b, _) in zip(merged, merged[1:]):
        idle += b - a
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(stages) and stages[i][0] < b:
            s, e, name = stages[i]
            lap = min(e, b) - max(s, a)
            if lap > 0:
                under[name] = under.get(name, 0) + lap
            i += 1
    return idle * 1e-9, {k: v * 1e-9 for k, v in under.items()}


# ------------------------------------------------------------------ cutting

def cut(src: str, rounds: int) -> dict:
    run_dir = src if os.path.isdir(src) else None
    tr = load(os.path.join(run_dir, "server", "profile") if run_dir else src)
    by = rounds_of(tr.host_spans)
    whole = sorted(r for r, spans in by.items()
                   if ISSUE in spans and WAIT in spans)
    if len(whole) < rounds:
        raise SystemExit(f"the capture holds {len(whole)} whole rounds")
    kept = whole[:rounds]
    a = by[kept[0]][ISSUE][0]
    b = sum(by[kept[-1]][WAIT][:2])
    doc = tr.compact(a, b)
    doc["lanes"] = {k: [[n, s - a, d] for n, s, d in v]
                    for k, v in doc["lanes"].items()}
    doc["host"] = [[n, s - a, d, st] for n, s, d, st in tr.host_spans
                   if a <= s and s + d <= b]
    doc["cut"] = {"rounds": kept, "from": os.path.basename(src.rstrip("/"))}
    if run_dir:
        with open(os.path.join(run_dir, "flight_traced.json")) as f:
            doc["flight"] = json.load(f)
        with open(os.path.join(run_dir, "server_requests.json")) as f:
            doc["request_log"] = json.load(f)
    return doc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src", help="a traced run's directory (kept with "
                                "BENCH_KEEP_TRACE=1), or an *.xplane.pb")
    ap.add_argument("dst")
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()
    doc = cut(args.src, args.rounds)
    with gzip.open(args.dst, "wt") as f:
        json.dump(doc, f)
    print({"rounds": doc["cut"]["rounds"], "host": len(doc["host"]),
           **{k: len(v) for k, v in doc["lanes"].items()}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
