#!/usr/bin/env python3
"""Run one cell N times as fresh processes, back to back, with the driver's
own command line, and stop at the first run that exits non-zero or whose
last line is not a well-formed result. That run's whole log directory is
kept (copied to `<out>/failure-<seed>/`).

    python3 benchmark/soak.py --workload <cell> --runs 8 [--seconds 45]
        [--trace 0] [--seeds 7,8,9 | --seed0 3000000000] [--out chiprun_out/soak]
        [--extra=--control]

One line of JSON per run on standard output (seed, exit code, wall seconds,
`correct`, attempted/failed, metrics, what was compared) and the same lines
in `<out>/<cell>.jsonl`; the exit code is 0 only if every run passed the
soak's two tests. `correct: false` does not stop the soak (it is reported):
the soak is about runs that die, the proof is about runs that are wrong.
Imports no JAX: the children hold the chip, one after another.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def one(workload: str, seed: int, seconds: float, trace: int, extra: list,
        timeout: float) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    cmd = [*command, "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    row = {"workload": workload, "seed": seed, "trace": trace, "rc": rc,
           "wall_s": round(time.time() - t0, 1), "well_formed": False}
    lines = [x for x in out.splitlines() if x.strip()]
    try:
        last = json.loads(lines[-1])
        row["well_formed"] = all(k in last for k in KEYS)
        row.update({k: last.get(k) for k in (
            "correct", "attempted", "failed", "metrics", "compared", "control")})
        row["memory_peak_gib"] = round(
            last["device"].get("memory_peak_bytes", 0) / 2**30, 2)
        for k in ("busy_s", "window_s"):
            if k in last["device"]:
                row[k] = last["device"][k]
        if "breakdown" in last:
            row["breakdown"] = last["breakdown"]
    except Exception as e:  # noqa: BLE001
        row["parse_error"] = f"{type(e).__name__}: {e}"
    row["stderr_tail"] = err[-1500:] if (rc != 0 or not row["well_formed"]) else ""
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed0", type=int, default=3_000_000_000)
    ap.add_argument("--out", default=os.path.join("benchmark_out", "soak"))
    ap.add_argument("--extra", action="append", default=[])
    ap.add_argument("--timeout", type=float, default=1300.0)
    ap.add_argument("--keep-going", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = ([int(s) for s in args.seeds.split(",") if s] if args.seeds
             else [args.seed0 + 7919 * i for i in range(args.runs)])
    out_dir = os.path.join(ROOT, args.out)
    os.makedirs(out_dir, exist_ok=True)
    ok = True
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for seed in seeds:
            row = one(args.workload, seed, seconds, args.trace, args.extra,
                      args.timeout)
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()
            if row["rc"] != 0 or not row["well_formed"]:
                ok = False
                src = os.path.join(ROOT, "benchmark_out", args.workload,
                                   f"run-{seed}-{args.trace}")
                dst = os.path.join(out_dir, f"failure-{seed}")
                shutil.rmtree(dst, ignore_errors=True)
                if os.path.isdir(src):
                    shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                        "profile", "*.pb", "*.gz"))
                if not args.keep_going:
                    break
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
