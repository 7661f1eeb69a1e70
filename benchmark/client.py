#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX, so it
shares neither the server's GIL nor its chip.

    python client.py <schedule.json> <host> <port> <results.json>

Plays a schedule built by `traffic.build`: the prewarm requests one by one,
then the lead-in and the window. Prints `T0 <epoch seconds>` on its standard
output the moment the window's start is fixed (the runner reads it), and
writes every request's record to `results.json` when the drain is over.

Every request has its own deadline; nothing here raises out of a request:
whatever goes wrong is the request's `error`, and the request counts as
failed. One NDJSON chunk is one token (see `weights.py`), so a request's
record keeps the arrival time and the text of every chunk.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time
import traceback


def stream(host: str, port: int, body: dict, deadline_s: float,
           on_first=None) -> dict:
    """POST /api/generate with stream=true; never raises. `on_first` is
    called when the first chunk has arrived."""
    rec = {"sent": time.time(), "chunk_t": [], "chunk_text": [],
           "status": None, "request_id": None, "error": None, "done": False}
    conn = None
    try:
        conn = http.client.HTTPConnection(host, port, timeout=deadline_s)
        conn.request("POST", "/api/generate", json.dumps(
            {**body, "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        rec["request_id"] = resp.getheader("X-Request-Id")
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read(300)!r}"
            return rec
        end = rec["sent"] + deadline_s
        while True:
            line = resp.readline()
            if not line:
                rec["error"] = rec["error"] or "stream ended without a terminator"
                break
            now = time.time()
            msg = json.loads(line)
            if msg.get("done"):
                if "error" in msg:
                    rec["error"] = f"mid-stream: {msg['error']}"
                else:
                    rec["done"] = True
                rec["end"] = now
                break
            rec["chunk_t"].append(now)
            rec["chunk_text"].append(msg.get("response", ""))
            if on_first is not None and len(rec["chunk_t"]) == 1:
                on_first()
            if now > end:
                rec["error"] = "request deadline passed mid-stream"
                break
    except Exception as e:  # noqa: BLE001 — a failed request is a count, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if conn is not None:
            try:
                conn.close()
            except Exception:  # noqa: BLE001
                pass
    return rec


def body_of(req: dict) -> dict:
    return {"model": req["model"], "system": req["system"],
            "prompt": req["prompt"], "max_new_tokens": req["max_new_tokens"]}


def main() -> int:
    sched_path, host, port, out_path = sys.argv[1:5]
    port = int(port)
    with open(sched_path) as f:
        sched = json.load(f)
    deadline = sched["request_deadline_s"]
    seconds, lead_in = sched["seconds"], sched["lead_in_s"]
    records, lock = {}, threading.Lock()

    prewarm_errors = []
    for req in sched["prewarm"]:
        rec = stream(host, port, body_of(req), deadline)
        if not rec["done"]:
            prewarm_errors.append(rec["error"])

    t0 = time.time() + lead_in
    print(f"T0 {t0!r}", flush=True)
    t_end = t0 + seconds

    def run_one(req: dict, due: float, on_first=None) -> None:
        tag = dict(idx=req["idx"], due=due, max_new_tokens=req["max_new_tokens"])
        with lock:  # on record from the moment it is sent: never lost
            records[req["idx"]] = {**tag, "pending": True, "done": False,
                                   "error": "not finished when the drain ended"}
        rec = stream(host, port, body_of(req), deadline, on_first)
        rec.update(tag)
        with lock:
            records[req["idx"]] = rec

    threads = []
    if sched["loop"] == "open":
        for req in sched["requests"]:
            due = t0 + req["due_s"]
            wait = due - time.time()
            if wait > 0:
                time.sleep(wait)
            th = threading.Thread(target=run_one, args=(req, due), daemon=True)
            th.start()
            threads.append(th)
    else:
        lanes = {}
        for req in sched["requests"]:
            lanes.setdefault(req["client"], []).append(req)

        # Eight requests sent in one millisecond reach the server's queue in
        # whatever order its handler threads finish, and the order decides
        # which prompts share a prefill batch: the whole window then runs on
        # one of several timelines, run by run (PERF.md section 6, PR 28's
        # refusal). With `ramp_lane_gap_s` the first lane starts alone, and
        # the others one by one, that far apart, once its first chunk has
        # arrived: the server's loop is then paced by the device and has just
        # harvested a round, so all of them wait in its queue, in the order
        # they were sent, for its next pass. That is the edge a closed loop
        # stands on at every turn of a lane.
        gap = sched.get("ramp_lane_gap_s")
        go = threading.Event()

        def lane(reqs: list, nth: int) -> None:
            if gap is not None and nth:
                go.wait(lead_in)
                time.sleep(nth * gap)
            for req in sorted(reqs, key=lambda r: r["order"]):
                now = time.time()
                if now >= t_end:
                    break
                run_one(req, now, go.set)  # closed loop: due when the last one ended
            go.set()

        for nth, reqs in enumerate(lanes.values()):
            th = threading.Thread(target=lane, args=(reqs, nth), daemon=True)
            th.start()
            threads.append(th)
        time.sleep(max(0.0, t_end - time.time()))

    # Bounded drain: what was due inside the window may finish; the rest of
    # the wait is cut, and what is still open then has failed.
    drain_until = max(time.time(), t_end) + sched["drain_s"]
    for th in threads:
        th.join(max(0.0, drain_until - time.time()))
    with lock:
        done = dict(records)
    out = {"t0": t0, "t_end": t_end, "prewarm_errors": prewarm_errors,
           "records": list(done.values()),
           "drain_used_s": time.time() - t_end}
    with open(out_path, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 — the runner reads this from the log
        traceback.print_exc()
        code = 3
    sys.stdout.flush()
    sys.exit(code)
