#!/usr/bin/env python3
"""Find the highest request rate an open-loop cell sustains: one sweep on the
chip inside one process (one set-up, one server), a short window a rate.

    python3 benchmark/tools/sweep.py --workload mistral-7b-int8.nl2sql \
        --rates 0.5,1,1.5,2 --seconds 30 --seed 11 [--out chiprun_out/sweep]

A rate is sustained where the requests completed inside the window are at
least 95 % of those due five seconds or more before its end and the queue wait is no longer in the window's
second half than in its first (by the program's own per-request
`queue_wait_s`). Prints a JSON row a rate: TTFT and TPOT medians and p90,
completed share, queue wait by half. No latency limit gates the sweep. The
cell's `request_rate_per_s` is then set, by hand, to four fifths of the
highest sustained rate. Not run by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", default=os.path.join("benchmark_out", "sweep"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = spec.Cell(args.workload, rehearse=args.rehearse)
    out_dir = os.path.join(ROOT, args.out)
    os.makedirs(out_dir, exist_ok=True)

    from llm_based_apache_spark_optimization_tpu.utils.jaxenv import (
        force_cpu,
        place_compile_cache,
    )

    if args.rehearse:
        force_cpu()
    place_compile_cache()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    import run
    import traffic
    from server import Server

    tok = traffic.Tok()
    table = tok.emit_table({tok.eos, cell.config["eos_token_id"]})
    srv = Server(cell, args.seed, sorted(table.values()),
                 os.path.join(out_dir, "server"))
    model = cell.traffic["model"]
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.cell["request_rate_per_s"] = rate
        schedule = traffic.build(cell, args.seed + i, args.seconds)
        sp, rp = (os.path.join(out_dir, f"{n}-{rate}.json")
                  for n in ("schedule", "results"))
        with open(sp, "w") as f:
            json.dump(schedule, f)
        seen = len(srv.request_log.records)
        client = subprocess.run(
            [sys.executable, os.path.join(HERE, "client.py"), sp, srv.host,
             str(srv.port), rp], capture_output=True, text=True,
            timeout=args.seconds + schedule["drain_s"] + 300)
        with open(rp) as f:
            played = json.load(f)
        t0, t_end = played["t0"], played["t_end"]
        _, attempted, failed, ok, have = run.end_to_end(
            cell, played["records"], t0, t_end, args.seconds)
        # A request due in the last seconds cannot end inside the window at
        # any rate: the share is of those due five seconds or more before.
        early = [r for r in played["records"] if t0 <= r["due"] < t_end - 5.0]
        inside = sum(1 for r in early if r.get("done") and r.get("end", 1e18) < t_end)
        log = {r.get("request_id"): r for r in srv.request_log.records[seen:]}
        halves = [[], []]
        for r in ok:
            qw = log.get(r["request_id"], {}).get("queue_wait_s")
            if qw is not None:
                halves[r["due"] >= (t0 + t_end) / 2].append(qw * 1e3)
        tpot = [(r["chunk_t"][-1] - r["chunk_t"][0]) / (len(r["chunk_t"]) - 1) * 1e3
                for r in ok if len(r["chunk_t"]) > 1]
        serving = srv.get("/metrics")[model]["serving"]
        row = {"rate_rps": rate, "due": attempted, "failed": failed,
               "completed_inside_share": inside / max(1, len(early)),
               "ttft_p50_ms": have["ttft_p50_ms"], "ttft_p90_ms": have["ttft_p90_ms"],
               "tpot_p50_ms": statistics.median(tpot) if tpot else None,
               "tpot_p90_ms": have["tpot_p90_ms"],
               "output_tok_s": have["output_tok_s"],
               "queue_wait_mean_ms_by_half": [
                   statistics.fmean(h) if h else None for h in halves],
               "prefix_cache": {k: serving["prefix_cache"][k] for k in (
                   "hits", "misses", "reused_tokens", "evictions")},
               "preemptions": serving["kv_pages"]["preemptions"],
               "drain_used_s": played["drain_used_s"],
               "prewarm": len(schedule["prewarm"])}
        row["sustained"] = bool(
            row["completed_inside_share"] >= 0.95 and None not in
            row["queue_wait_mean_ms_by_half"] and
            row["queue_wait_mean_ms_by_half"][1]
            <= max(1.25 * row["queue_wait_mean_ms_by_half"][0], 50.0))
        rows.append(row)
        print(json.dumps(row), flush=True)
        time.sleep(2.0)
    with open(os.path.join(out_dir, args.workload + ".json"), "w") as f:
        json.dump(rows, f, indent=1)
    srv.close()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
