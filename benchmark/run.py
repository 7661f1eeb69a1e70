#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Serves the cell's configuration through the studio's own assembly over HTTP
on localhost (`server.py`), from a thread of this process, which holds the
chip; plays the cell's traffic from a process of its own (`client.py`); then
frees the program, checks a sample of what the window served against the
plain reference (`reference.py`), and prints one JSON line last.

`--trace 0`: the line's metrics are the cell's end-to-end metrics.
`--trace 1`: a device trace of a few rounds is taken inside the window
(`/debug/profile`, beside the serving loop), and the line's metrics are the
cell's per-layer metrics.

Exit code: 0 whenever a result line was printed — a request that fails is
counted in `failed`, never in the exit code. Non-zero, and no result line,
only where no result can be had: no TPU (or fewer chips than the cell asks
for), a malformed cell, set-up failed, the server dead, the client lost.

`--rehearse` (not for the driver): the same control flow on the CPU at the
configuration's `rehearsal` size. Its numbers are not device numbers and are
printed under `rehearsal_*` names.

Everything a run writes goes under `benchmark_out/<cell>/run-<seed>-<trace>/`
in the checkout (removed at start) and the compile cache; `run.log` there
holds the whole story of the run.
"""

from __future__ import annotations

T_PROCESS_START = __import__("time").time()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import spec  # noqa: E402
from layers import percentile  # noqa: E402

NO_RESULT = 2


class Log:
    def __init__(self, path: str):
        self.f = open(path, "w", buffering=1)
        self.lock = threading.Lock()

    def __call__(self, msg: str, echo: bool = True) -> None:
        line = f"[{time.time() - T_PROCESS_START:8.2f}] {msg}"
        with self.lock:
            self.f.write(line + "\n")
            if echo:
                print(line, file=sys.stderr, flush=True)


class CompileLog:
    """Programs JAX builds (or fetches from its persistent cache), counted
    from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs, self.seconds, self.cache_hits = 0, 0.0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "compile_s": round(self.seconds, 2),
                "cache_hits": self.cache_hits}


def end_to_end(cell, records: list, t0: float, t_end: float, seconds: float,
               prefix: str = "") -> tuple:
    """(metrics, attempted, failed, ok records). A rate is over all the
    tokens and all the seconds of the window; a tail is over every request
    that was due in it and finished."""
    due = [r for r in records if t0 <= r["due"] < t_end]
    ok = [r for r in due if r.get("done") and not r.get("error")
          and r.get("chunk_t")]
    ttft = [(r["chunk_t"][0] - r["due"]) * 1e3 for r in ok]
    tpot = [(r["chunk_t"][-1] - r["chunk_t"][0]) / (len(r["chunk_t"]) - 1) * 1e3
            for r in ok if len(r["chunk_t"]) > 1]
    tokens = sum(1 for r in records for t in r.get("chunk_t", ())
                 if t0 <= t < t_end)
    have = {
        "ttft_p50_ms": percentile(ttft, 0.5) if ttft else None,
        "ttft_p90_ms": percentile(ttft, 0.9) if ttft else None,
        "tpot_p90_ms": percentile(tpot, 0.9) if tpot else None,
        "output_tok_s": tokens / seconds,
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] in have and have[m["name"]] is not None:
            out[prefix + m["name"]] = {"value": have[m["name"]], "unit": m["unit"]}
    return out, len(due), len(due) - len(ok), ok, have


def open_loop_count(records: list, t0: float, t_end: float,
                    seconds: float) -> dict:
    """What an open loop's count of tokens inside the window is made of. The
    schedule offers the tokens asked of the requests due in the window; the
    count adds the lead-in's backlog that arrives after the window opened and
    leaves out what the window's own requests deliver after it closed. Below
    the knee both spills shrink as the server gets faster, so there the count
    says what was offered and how late, not what the server can do, and
    `output_tok_s` is no metric of such a cell (`BENCHMARK.json`)."""
    return {
        "offered_tok_s": sum(r["max_new_tokens"] for r in records
                             if t0 <= r["due"] < t_end) / seconds,
        "lead_in_tokens_inside": sum(
            1 for r in records if r["due"] < t0
            for t in r.get("chunk_t", ()) if t0 <= t < t_end),
        "window_tokens_after": sum(
            1 for r in records if t0 <= r["due"] < t_end
            for t in r.get("chunk_t", ()) if t >= t_end)}


def longest_silence(records: list, t0: float) -> dict:
    """The longest time in which no chunk of any request reached the client,
    and when it began, in seconds from the window's opening. In a closed
    loop some lane is always decoding, so a silence of several rounds is a
    stall of the whole serving loop; in an open loop it may be an idle
    stretch of the schedule."""
    ts = sorted(t for r in records for t in r.get("chunk_t", ()))
    if len(ts) < 2:
        return {}
    gap, at = max((b - a, a) for a, b in zip(ts, ts[1:]))
    return {"seconds": round(gap, 3), "from_s": round(at - t0, 2)}


def within_limits(compared: dict) -> bool:
    """The comparison that decides `correct`: every number compared is at
    or under its limit. A NaN fails too."""
    return all(c["value"] <= c["limit"] for c in compared.values())


def pick_samples(cell, ok: list, schedule: dict, tok, table: dict, seed: int,
                 log) -> tuple:
    """The requests the reference reads: the longest the window finished and
    others drawn from the seed, each as (prompt ids, served ids). Also counts
    what no reference is needed to see: a chunk that is no token of the
    tokenizer, a request that did not run to the length it asked for."""
    import traffic

    by_idx = {r["idx"]: r for r in schedule["requests"]}
    unmapped = mismatch = 0
    cands = []
    for r in ok:
        ids = [table.get(t) for t in r["chunk_text"]]
        unmapped += sum(i is None for i in ids)
        mismatch += len(ids) != r["max_new_tokens"]
        if None in ids or not ids:
            continue
        req = by_idx[r["idx"]]
        prompt = traffic.prompt_ids(tok, cell.traffic, req["system"], req["prompt"])
        cands.append((len(prompt) + len(ids), r["idx"], prompt, ids))
    rows = int(cell.cell["check_rows"])
    cands.sort(key=lambda c: (-c[0], c[1]))
    chosen = cands[:1]
    rest = cands[1:]
    random.Random(int(seed)).shuffle(rest)
    chosen += rest[:rows - 1]
    log(f"check: {len(chosen)} of {len(ok)} finished requests sampled "
        f"(idx {[c[1] for c in chosen]}, lengths {[c[0] for c in chosen]})")
    return [(c[2], c[3]) for c in chosen], unmapped, mismatch


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="tools only: also read the control, the reference in "
                         "the nearest precision below the stated one")
    ap.add_argument("--skip-check", action="store_true",
                    help="tools only (the sweep): no reference pass")
    args = ap.parse_args()

    cell = spec.Cell(args.workload, rehearse=args.rehearse)
    out_dir = os.path.join(ROOT, "benchmark_out", cell.name,
                           f"run-{args.seed}-{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    log = Log(os.path.join(out_dir, "run.log"))
    # Never outlive the driver's limit: dump every thread and die.
    faulthandler.dump_traceback_later(1150, exit=True, file=log.f)
    log(f"run {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} rehearse={args.rehearse} pid={os.getpid()}")

    from llm_based_apache_spark_optimization_tpu.utils.jaxenv import (
        force_cpu,
        place_compile_cache,
    )

    if args.rehearse:
        force_cpu()
    cache_dir = place_compile_cache()
    import jax

    # Small programs too, so that a second run finds every program cached
    # and set-up is the same from run to run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        log(f"no TPU (JAX found {device}): no result")
        return NO_RESULT
    if len(devs) < cell.chips:
        log(f"the cell asks for {cell.chips} chips, JAX found {device}")
        return NO_RESULT
    device["count"] = cell.chips
    import costs

    peaks = (costs.peaks(device["kind"]) if not args.rehearse
             else costs.peaks("TPU v5 lite"))
    clog = CompileLog()
    log(f"device {device}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} entries)")

    import traffic
    from server import Server

    tok = traffic.Tok()
    stop_ids = {tok.eos, cell.config["eos_token_id"]}
    table = tok.emit_table(stop_ids)
    emit_ids = sorted(table.values())
    schedule = traffic.build(cell, args.seed, args.seconds)
    sched_path = os.path.join(out_dir, "schedule.json")
    with open(sched_path, "w") as f:
        json.dump(schedule, f)
    log(f"schedule: {len(schedule['requests'])} requests, "
        f"{len(schedule['prewarm'])} prewarm, loop={schedule['loop']}")

    # ------------------------------------------------------------ set-up
    try:
        srv = Server(cell, args.seed, emit_ids, os.path.join(out_dir, "server"))
        ready = srv.get("/readyz")
        if ready.get("state") != "ready":
            raise RuntimeError(f"/readyz says {ready}")
    except (Exception, SystemExit):  # the app's assembly exits on bad arguments
        log(f"set-up failed:\n{traceback.format_exc()}")
        return NO_RESULT
    model = cell.traffic["model"]
    kernels = srv.get("/metrics")[model]["serving"]["perf"]["kernels"]
    log(f"server ready on port {srv.port}: kernels {kernels}; "
        f"compiles so far {clog.snapshot()}")

    client_log = open(os.path.join(out_dir, "client.log"), "w")
    results_path = os.path.join(out_dir, "client_results.json")
    client = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py"), sched_path, srv.host,
         str(srv.port), results_path],
        stdout=subprocess.PIPE, stderr=client_log, text=True)
    t0 = None
    for line in client.stdout:  # blocks through the prewarm
        if line.startswith("T0 "):
            t0 = float(line.split()[1])
            break
    if t0 is None:
        client.kill()
        client.wait()
        log("the client ended before the window opened: no result")
        return NO_RESULT
    t_end = t0 + args.seconds
    setup_s = t0 - T_PROCESS_START
    at_open = clog.snapshot()
    log(f"window opens at +{setup_s:.2f} s (set-up); compiles in set-up {at_open}")

    edges, trace_state = {}, {}

    def at(t: float, fn) -> None:
        time.sleep(max(0.0, t - time.time()))
        try:
            fn()
        except Exception:  # noqa: BLE001 — an edge reading never costs the run
            log(f"edge reading failed:\n{traceback.format_exc()}")

    def arm_trace() -> None:
        spec_t = cell.cell["trace"]
        edges["arm"] = srv.get("/metrics")
        trace_state["armed"] = srv.get(
            f"/debug/profile?rounds={int(spec_t['rounds'])}&model={model}")
        log(f"trace armed: {trace_state['armed']}")

    timers = [threading.Thread(target=at, daemon=True, args=(
        t0, lambda: edges.__setitem__("t0", srv.get("/metrics")))),
        threading.Thread(target=at, daemon=True, args=(
            t_end, lambda: edges.__setitem__("t1", srv.get("/metrics"))))]
    # The trace is armed late, and the counts and host-clock readings of a
    # traced run are taken over the part of the window before it.
    trace_at = t_end - float(cell.cell["trace"]["before_end_s"])
    if args.trace:
        timers.append(threading.Thread(target=at, daemon=True, args=(
            trace_at, arm_trace)))
    for th in timers:
        th.start()
    time.sleep(max(0.0, t0 - time.time()))
    in_window_mark = clog.snapshot()
    time.sleep(max(0.0, t_end - time.time()))
    in_window = {k: round(clog.snapshot()[k] - in_window_mark[k], 2)
                 for k in in_window_mark}
    log(f"window closed; programs built inside it: {in_window['programs']} "
        f"({in_window})")
    try:
        client.wait(timeout=schedule["drain_s"] + 30)
    except subprocess.TimeoutExpired:
        client.kill()
        client.wait()
        log("the client outlived its drain and was killed")
    client_log.close()
    for th in timers:
        th.join(10)
    if not os.path.exists(results_path):
        log("the client left no results: no result")
        srv.close()
        return NO_RESULT
    with open(results_path) as f:
        played = json.load(f)

    # ----------------------------------------------- what the server saw
    health, flight, status = {}, [], {}
    try:
        m_end = srv.get("/metrics")
        serving = m_end[model]["serving"]
        health = {"supervisor": serving.get("supervisor"),
                  "watchdog": {k: serving["watchdog"].get(k) for k in (
                      "stalls_detected", "slots_retired_stalled")},
                  "preemptions": serving["kv_pages"]["preemptions"],
                  "page_waits": serving["kv_pages"]["page_waits"]}
        # Both studio roles share one scheduler; its recorder is filed
        # under the first of them.
        by_model = srv.get("/debug/flightrecorder")["models"]
        flight = by_model.get(model) or next(iter(by_model.values()))
        if args.trace:
            for _ in range(120):
                status = srv.get("/debug/profile")["captures"]
                last = next((c["last"] for c in status.values()
                             if isinstance(c, dict) and c.get("last")), None)
                if last and last.get("state") in ("done", "error", "aborted"):
                    trace_state["last"] = last
                    break
                time.sleep(0.5)
    except Exception:  # noqa: BLE001
        log(f"reading the server's state failed:\n{traceback.format_exc()}")
    log(f"health: {json.dumps(health)}")
    if played["prewarm_errors"]:
        log(f"prewarm errors: {played['prewarm_errors'][:3]}")
    mem = [d.memory_stats() or {} for d in devs[:cell.chips]]
    memory_peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    log(f"memory: peak {memory_peak / 2**30:.2f} GiB, limit "
        f"{mem[0].get('bytes_limit', 0) / 2**30:.2f} GiB")

    prefix = "rehearsal_" if args.rehearse else ""
    metrics, attempted, failed, ok, have = end_to_end(
        cell, played["records"], t0, t_end, args.seconds, prefix)
    log(f"requests due in the window {attempted}, failed {failed}; "
        f"drain used {played['drain_used_s']:.1f} s; all end-to-end readings "
        f"{json.dumps(have)}")
    if schedule["loop"] == "open":
        log("the open loop's count of tokens: "
            f"{json.dumps(open_loop_count(played['records'], t0, t_end, args.seconds))}")
    if failed:
        bad = [r for r in played["records"] if t0 <= r["due"] < t_end
               and not (r.get("done") and not r.get("error"))]
        log(f"failed requests: {[(r['idx'], r.get('error')) for r in bad[:8]]}")
    # Every run keeps its round records: about one run in ten of
    # `mistral-7b-int8.explain` holds one stall of all lanes for 1-2.5 s
    # (PERF.md section 7), and whether the device or the host held the round
    # is in the round's record and nowhere else.
    with open(os.path.join(out_dir, "flight.json"), "w") as f:
        json.dump(flight, f)
    log(f"longest silence at the client: {json.dumps(longest_silence(played['records'], t0))}")
    with open(os.path.join(out_dir, "server_requests.json"), "w") as f:
        json.dump(srv.request_log.records, f)
    server_log = {r.get("request_id"): r for r in srv.request_log.records}
    halves = [[], []]
    for r in ok:
        qw = server_log.get(r.get("request_id"), {}).get("queue_wait_s")
        if qw is not None:
            halves[r["due"] >= (t0 + t_end) / 2].append(qw * 1e3)
    log("queue wait, mean ms, by the window's halves: "
        f"{[round(sum(h) / len(h), 1) if h else None for h in halves]}")

    # --------------------------------------------------- free the program
    srv.close()
    del srv
    gc.collect()
    jax.clear_caches()
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"program freed: {live / 2**20:.1f} MiB still live on the device")

    # ------------------------------------------------------- the check
    compared, control = {}, None
    correct = True
    try:
        samples, unmapped, mismatch = pick_samples(
            cell, ok, schedule, tok, table, args.seed, log)
        compared["chunks_not_a_token"] = {"value": unmapped, "limit": 0}
        compared["requests_off_length"] = {"value": mismatch, "limit": 0}
        if args.skip_check:
            log("check skipped (--skip-check)")
        elif not samples:
            correct = False
            log("check: no finished request to compare")
        else:
            import reference

            t_ref = time.time()
            got = reference.max_logit_gap(
                cell.config, cell.serving["weights"], args.seed, emit_ids,
                samples, int(cell.cell["check_rows"]), cell.serving["max_seq"],
                int(cell.traffic["output_tokens"]["hi"]))
            log(f"reference: {json.dumps(got)} in {time.time() - t_ref:.1f} s")
            compared["max_logit_gap"] = {
                "value": got["max_logit_gap"],
                "limit": float(cell.cell["max_logit_gap_limit"])}
            if args.control:
                how = cell.config["control"]["weights"]
                low = reference.max_logit_gap(
                    cell.config, cell.serving["weights"], args.seed, emit_ids,
                    samples, int(cell.cell["check_rows"]),
                    cell.serving["max_seq"],
                    int(cell.traffic["output_tokens"]["hi"]), control=how)
                # The control is judged as a run is: the same numbers
                # against the same limits. It has to come out not correct.
                judged = {**compared, "max_logit_gap": {
                    **compared["max_logit_gap"], "value": low["max_logit_gap"]}}
                control = {"precision": how, **low, "compared": judged,
                           "correct": within_limits(judged)}
                log(f"control ({how}): {json.dumps(low)}")
                log(f"control correct: {control['correct']} (it has to be False)")
        correct = correct and within_limits(compared)
    except Exception:  # noqa: BLE001
        correct = False
        log(f"the check itself failed:\n{traceback.format_exc()}")

    # ------------------------------------------------ per-layer metrics
    breakdown = None
    if args.trace:
        import layers
        import xtrace

        tr = None
        last = trace_state.get("last") or {}
        try:
            if last.get("state") == "done":
                t_tr = time.time()
                tr = xtrace.Trace.load(last["dir"])
                device["busy_s"] = tr.busy_s()
                device["window_s"] = tr.span_s()
                breakdown = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
                log(f"trace read in {time.time() - t_tr:.1f} s: {last['dir']} "
                    f"({last.get('artifact_bytes')} bytes)")
            else:
                log(f"no finished trace: {trace_state}")
        except Exception:  # noqa: BLE001
            log(f"reading the trace failed:\n{traceback.format_exc()}")
        rounds = [r for r in flight if "round" in r]
        in_win = [r for r in rounds if t0 <= r["ts"] < trace_at]
        done_ev = next((r for r in flight if r.get("kind") == "profile_done"), None)
        traced = []
        if done_ev is not None:
            before = [r for r in rounds if r["seq"] < done_ev["seq"]]
            traced = before[-int(cell.cell["trace"]["rounds"]):]
        with open(os.path.join(out_dir, "flight_traced.json"), "w") as f:
            json.dump(traced, f)
        # An open loop's load is not the same everywhere in its window, so
        # the traced rounds need not run at the load of the window before
        # them: say how far apart.
        for what, rs in (("the window before the trace", in_win),
                         ("the traced rounds", traced)):
            occ = [r["occupancy"] for r in rs if "occupancy" in r]
            qd = [r["queued"] for r in rs if "queued" in r]
            log(f"{what}: {len(rs)} rounds, occupied slots mean "
                f"{sum(occ) / len(occ) if occ else None}, queued mean "
                f"{sum(qd) / len(qd) if qd else None}")
        ctx = layers.Context(
            cell=cell, peaks=peaks,
            requests=[r for r in played["records"]
                      if t0 <= r["due"] < trace_at - 5.0],
            server_log=server_log, flight=in_win, flight_traced=traced,
            metrics_t0=edges.get("t0") or {}, metrics_t1=edges.get("arm") or {},
            trace=tr, model=model)
        metrics = {prefix + k: v for k, v in
                   layers.read_all(ctx, cell.per_layer, log).items()}
        if not args.rehearse and not keep_trace():
            shutil.rmtree(os.path.join(out_dir, "server", "profile"),
                          ignore_errors=True)
    else:
        metrics[prefix + "setup_s"] = {"value": setup_s, "unit": "s"}

    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {**device, "memory_peak_bytes": int(memory_peak)}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True
    if control is not None:
        result["control"] = control
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    log(f"correct: {correct}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    log.f.flush()
    faulthandler.cancel_dump_traceback_later()
    return 0


def keep_trace() -> bool:
    """Tools set BENCH_KEEP_TRACE=1 to keep the raw trace for cutting."""
    return os.environ.get("BENCH_KEEP_TRACE") == "1"


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else NO_RESULT
    except BaseException:  # noqa: BLE001
        traceback.print_exc()
        code = NO_RESULT
    sys.stdout.flush()
    sys.stderr.flush()
    # Threads of the server (HTTP workers, the scheduler's loop) must not
    # keep a finished run alive.
    os._exit(code)
