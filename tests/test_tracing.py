"""Request-scoped tracing (utils/tracing.py): span trees, head sampling,
Chrome-trace export round-trip, and the scheduler/service integration."""

import json
import logging
import threading
import time

import pytest

from llm_based_apache_spark_optimization_tpu.utils.tracing import (
    RequestTrace,
    Tracer,
    new_request_id,
)
from llm_based_apache_spark_optimization_tpu.utils import tracing


def test_request_ids_unique_and_prefixed():
    ids = {new_request_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith("req-") for i in ids)


def test_span_tree_records_and_sorts():
    t = RequestTrace("req-x", model="m")
    with t.span("service.generate", model="m"):
        t.add_span("sched.decode", time.perf_counter() - 0.5,
                   time.perf_counter(), output_tokens=3)
    t.event("sched.error", error="Boom")
    doc = t.to_dict()
    assert doc["request_id"] == "req-x" and doc["model"] == "m"
    names = [s["name"] for s in doc["spans"]]
    # Sorted by start: the decode span started before the enclosing
    # service span's END-time recording order.
    assert set(names) == {"service.generate", "sched.decode", "sched.error"}
    decode = next(s for s in doc["spans"] if s["name"] == "sched.decode")
    assert decode["dur_s"] == pytest.approx(0.5, abs=0.05)
    assert decode["attrs"]["output_tokens"] == 3
    assert json.dumps(doc)  # JSONL-exportable


def test_spans_threadsafe_across_threads():
    t = RequestTrace("req-t")

    def worker(i):
        for j in range(50):
            t.add_span(f"lane{i}.s", 0.0, 1.0)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    [th.start() for th in threads]
    [th.join() for th in threads]
    assert len(t.to_dict()["spans"]) == 200


def test_tracer_head_sampling():
    t0 = Tracer(sample=0.0)
    assert all(t0.begin() is None for _ in range(20))
    t1 = Tracer(sample=1.0)
    assert all(t1.begin() is not None for _ in range(5))
    th = Tracer(sample=0.5, seed=0)
    picks = [th.begin() is not None for _ in range(400)]
    assert 100 < sum(picks) < 300  # genuinely sampled, not all/none


def test_tracer_finish_none_safe_and_ring():
    tr = Tracer(sample=1.0, ring=2)
    assert tr.finish(None) is None
    for i in range(4):
        t = tr.begin(model=f"m{i}")
        tr.finish(t)
    recent = tr.recent()
    assert len(recent) == 2  # ring bounded
    assert tr.stats()["exported"] == 4


def test_chrome_export_roundtrips_traceprof(tmp_path):
    """Acceptance: the exported Chrome trace loads in utils/traceprof.Trace
    (the same parser that reads jax.profiler device traces) — op time
    positive, span names preserved, device_time bounded by wall."""
    from llm_based_apache_spark_optimization_tpu.utils.traceprof import (
        Trace,
    )

    tr = Tracer(sample=1.0, export_dir=str(tmp_path))
    t = tr.begin(model="m")
    with t.span("service.generate"):
        time.sleep(0.01)
    t.add_span("sql.exec", time.perf_counter() - 0.004, time.perf_counter())
    tr.finish(t)
    # Per-request gzipped chrome file + the JSONL append both exist.
    assert (tmp_path / "requests.jsonl").exists()
    assert list(tmp_path.glob("*.trace.json.gz"))
    pt = Trace().load_dir(str(tmp_path))
    assert pt.op_time_s() > 0.0
    assert 0.0 < pt.device_time_s() <= pt.op_time_s() + 1e-9
    names = {n for n, _, _ in pt.top_ops(10)}
    assert {"service.generate", "sql.exec"} <= names


def test_span_helper_noop_without_current_trace():
    # No ambient trace: the span contextmanager must be a free no-op.
    with tracing.span("anything", attr=1):
        pass
    assert tracing.current() is None


def test_use_installs_and_restores():
    t = RequestTrace("req-ctx")
    assert tracing.current() is None
    with tracing.use(t):
        assert tracing.current() is t
        with tracing.span("sql.exec"):
            pass
    assert tracing.current() is None
    assert [s["name"] for s in t.to_dict()["spans"]] == ["sql.exec"]


def test_use_none_marks_decision_no_redraw(monkeypatch):
    """`use(None)` records made-but-UNSAMPLED: a downstream entry point
    (the service under the HTTP layer) must honor it instead of drawing
    a second sample — re-drawing would double the effective rate."""
    from llm_based_apache_spark_optimization_tpu.serve import (
        FakeBackend,
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.utils.tracing import TRACER

    assert not tracing.decided()
    with tracing.use(None):
        assert tracing.decided()
        assert tracing.current() is None
        with tracing.span("never.recorded"):  # still a free no-op
            pass
    assert not tracing.decided()

    svc = GenerationService()
    svc.register("m", FakeBackend(lambda p: "SELECT 1"))
    calls = []
    monkeypatch.setattr(
        TRACER, "begin",
        lambda *a, **k: calls.append(1) or None)
    # HTTP layer drew (unsampled) -> the service must NOT draw again...
    with tracing.use(None):
        svc.generate("m", "q")
    assert calls == []
    # ...but with no upstream decision, the service draws exactly once.
    svc.generate("m", "q")
    assert calls == [1]


def test_stream_context_never_leaks_between_yields(monkeypatch):
    """A library caller's sampled generate_stream must not leave its
    trace installed in the CALLER's context while suspended at a yield —
    generators share the thread's context, so a leaked set would record
    a second, interleaved request's spans into the first one's tree."""
    from llm_based_apache_spark_optimization_tpu.serve import (
        FakeBackend,
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.utils.tracing import TRACER

    svc = GenerationService()
    svc.register("m", FakeBackend(lambda p: "SELECT 1"))
    monkeypatch.setattr(TRACER, "sample", 1.0)  # library path draws
    g1 = svc.generate_stream("m", "one")
    next(g1)
    # Suspended mid-stream: the caller's context must be clean.
    assert tracing.current() is None
    assert not tracing.decided()
    g1.close()


def test_service_records_spans_and_request_id():
    """Driving the service directly under an ambient trace records the
    service span into it, and the GenerateResult echoes the id."""
    from llm_based_apache_spark_optimization_tpu.serve import (
        FakeBackend,
        GenerationService,
    )

    svc = GenerationService()
    svc.register("m", FakeBackend(lambda p: "SELECT 1"))
    t = RequestTrace("req-svc")
    with tracing.use(t):
        res = svc.generate("m", "q", request_id="req-svc")
    assert res.request_id == "req-svc"
    assert "service.generate" in [s["name"] for s in t.to_dict()["spans"]]


@pytest.fixture(scope="module")
def tiny_model_module():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def test_scheduler_records_request_spans(tiny_model_module):
    """The worker thread records queue-wait / prefill / decode / per-round
    spans into a submitted trace, and stamps the measured queue wait on
    the future (the Completion/metrics seam)."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    cfg, params = tiny_model_module
    t = RequestTrace("req-sched")
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, prompt_bucket=8, decode_chunk=4,
        stop_ids=(-1,),
    ) as sched:
        fut = sched.submit([1, 2, 3], max_new_tokens=6, trace=t)
        out = fut.result(timeout=120)
    assert len(out) == 6
    names = [s["name"] for s in t.to_dict()["spans"]]
    assert "sched.queue_wait" in names
    assert "sched.prefill" in names
    assert "sched.decode" in names
    assert "sched.round" in names
    assert getattr(fut, "_lsot_queue_wait") >= 0.0
    assert getattr(fut, "_lsot_replica") == "r0"
    decode = next(s for s in t.to_dict()["spans"]
                  if s["name"] == "sched.decode")
    assert decode["attrs"]["output_tokens"] == 6


def test_supervised_scheduler_forwards_trace(tiny_model_module):
    """The supervisor forwards a sampled trace to the inner attempt and
    copies the measured queue wait onto its own client-facing future."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )
    from llm_based_apache_spark_optimization_tpu.serve.supervisor import (
        SupervisedScheduler,
    )

    cfg, params = tiny_model_module

    def make():
        return ContinuousBatchingScheduler(
            cfg, params, num_slots=2, prompt_bucket=8, decode_chunk=4,
            stop_ids=(-1,),
        )

    sup = SupervisedScheduler(make, stall_min_s=0).start()
    try:
        t = RequestTrace("req-sup")
        fut = sup.submit([1, 2, 3], max_new_tokens=4, trace=t)
        fut.result(timeout=120)
        assert "sched.decode" in [s["name"] for s in t.to_dict()["spans"]]
        assert getattr(fut, "_lsot_queue_wait") >= 0.0
    finally:
        sup.shutdown()


# ------------------------------------------------- loop stages (ISSUE 26)
#
# The loop's stages belong to no request: `StageTimer` sums them on the
# host's clock (flight record, request log) and, while a /debug/profile
# capture runs, they are events of the device trace's `/host:CPU` plane.


def _host_spans(trace_dir):
    """`[(name, start_ns, dur_ns, stats)]` of the newest capture's host
    plane, read with nothing but JAX."""
    import glob
    import os

    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                          recursive=True))[-1]
    host = next(p for p in ProfileData.from_file(pb).planes
                if p.name == "/host:CPU")
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for ln in host.lines for e in ln.events]


def test_stage_timer_accumulates_across_threads_and_take_clears():
    from llm_based_apache_spark_optimization_tpu.utils.observability import (
        StageTimer,
    )

    timer = StageTimer()

    def worker():
        for _ in range(50):
            with timer.stage("sched.admit", admitted=1):
                pass
            with timer.step("sched.loop", 3):  # the trace's, not summed
                with timer.stage("sched.harvest"):
                    time.sleep(0.0002)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    [th.start() for th in threads]
    [th.join(30) for th in threads]
    assert not any(th.is_alive() for th in threads)
    spans = timer.spans
    assert set(spans) == {"sched.admit", "sched.harvest"}
    assert spans["sched.harvest"] >= 200 * 0.0002  # every pass of every thread
    assert 0.0 <= spans["sched.admit"] < spans["sched.harvest"]
    assert timer.take() == spans
    assert timer.spans == {} and timer.take() == {}
    with timer.stage("sched.idle"):
        pass
    assert set(timer.spans) == {"sched.idle"}  # sums start over


def test_stage_is_on_the_host_plane_of_a_running_capture(tmp_path):
    import jax

    from llm_based_apache_spark_optimization_tpu.utils import traceprof
    from llm_based_apache_spark_optimization_tpu.utils.observability import (
        StageTimer,
    )

    timer = StageTimer(rid="req-7")
    with timer.stage("stream.detok"):  # no capture: only the host's clock
        pass
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=traceprof.profile_options())
    try:
        with timer.step("sched.loop", 41):
            with timer.stage("http.chunk", bytes=12) as span:
                span.set(sent=True)
    finally:
        jax.profiler.stop_trace()
    by_name = {}
    for name, start, dur, stats in _host_spans(tmp_path):
        by_name.setdefault(name, []).append((start, dur, stats))
    assert "stream.detok" not in by_name  # it ran before the capture
    (start, dur, stats), = by_name["http.chunk"]
    assert stats["rid"] == "req-7" and stats["bytes"] == 12
    assert stats["sent"] in (True, 1)
    (lstart, ldur, lstats), = by_name["sched.loop"]
    assert lstats["step_num"] == 41
    assert lstart <= start and start + dur <= lstart + ldur  # nested
    # The step covers its stages: it is in the trace and in no sum.
    assert set(timer.spans) == {"stream.detok", "http.chunk"}


def _sched(cfg, params, **kw):
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("stop_ids", (-1,))
    return ContinuousBatchingScheduler(cfg, params, **kw)


def test_round_record_carries_the_loops_host_time(tiny_model_module):
    """A round's flight record says what the loop did since the last one:
    `host_s` by span, the wait for the device, the wait for work, the
    prefill dispatched — and cannot account for more than the wall
    between the two records."""
    cfg, params = tiny_model_module
    with _sched(cfg, params) as sched:
        sched.generate([[1, 5, 9, 2, 4, 4, 8, 1, 3, 3], [1, 7, 3]],
                       max_new_tokens=24)
        rounds = [r for r in sched.flight.snapshot() if "round" in r]
    assert len(rounds) >= 6
    for r in rounds:
        assert set(r["host_s"]) <= {
            "sched.upkeep", "sched.admit", "sched.prefill_dispatch",
            "sched.issue_decode", "sched.harvest"}
        assert "sched.harvest" in r["host_s"]
        assert r["harvest_wait_s"] >= 0.0 and r["idle_s"] >= 0.0
    # Two requests, one 8-token bucket: the 10-token prompt takes two
    # chunks (8 + 2 tokens), the 3-token prompt rides the first of them.
    assert sum(r["prefill_chunks"] for r in rounds) == 2
    assert sum(r["prefill_rows"] for r in rounds) == 3
    assert sum(r["prefill_tokens"] for r in rounds) == 13
    assert sum(r["host_s"].get("sched.prefill_dispatch", 0.0)
               for r in rounds) > 0.0
    for prev, r in zip(rounds, rounds[1:]):
        accounted = (sum(r["host_s"].values()) + r["harvest_wait_s"]
                     + r["idle_s"])
        # `ts` is time.time() at the record, the sums perf_counter spans
        # rounded to the microsecond.
        assert accounted <= r["ts"] - prev["ts"] + 2e-3, (prev, r)


class _RequestLog(logging.Handler):
    def __init__(self):
        super().__init__()
        self.records = []

    def emit(self, record):
        self.records.append(json.loads(record.args[0]))


def test_streamed_request_log_splits_the_ttft_into_its_waits(
        tiny_model_module):
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        SchedulerBackend,
    )
    from llm_based_apache_spark_optimization_tpu.serve.service import (
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )
    from llm_based_apache_spark_optimization_tpu.utils.observability import (
        StageTimer,
    )

    cfg, params = tiny_model_module
    backend = SchedulerBackend(_sched(cfg, params, max_seq=128),
                               ByteTokenizer(), max_new_tokens=12)
    svc = GenerationService()
    svc.register("m", backend)
    handler = _RequestLog()
    log = logging.getLogger("lsot.metrics")
    level = log.level
    log.setLevel(logging.INFO)
    log.addHandler(handler)
    try:
        stages = StageTimer(rid="req-split")
        chunks = []
        for piece in svc.generate_stream("m", "a table of orders", stages=stages,
                                         request_id="req-split"):
            with stages.stage("http.chunk"):  # what app/api.py does
                chunks.append(piece)
    finally:
        log.removeHandler(handler)
        log.setLevel(level)
        backend.shutdown()
    assert chunks
    rec, = [r for r in handler.records if r.get("request_id") == "req-split"]
    for key in ("prefill_s", "first_hold_s", "stream_lag_p90_s",
                "prefix_reused_tokens", "detok_s", "chunk_s", "ttft_s"):
        assert key in rec, (key, rec)
    assert rec["prefill_s"] > 0 and rec["first_hold_s"] > 0
    assert rec["stream_lag_p90_s"] > 0 and rec["detok_s"] > 0
    assert rec["prefix_reused_tokens"] == 0
    # The three waits are cut from one clock on one thread and end where
    # the TTFT ends; the TTFT starts a tick earlier (before submit()).
    waits = rec.get("queue_wait_s", 0.0) + rec["prefill_s"] + rec["first_hold_s"]
    assert waits <= rec["ttft_s"] + 1e-3
    assert waits >= 0.5 * rec["ttft_s"]
    # The terminal record is written before the last piece is handed on,
    # so the sum in it is of the chunks before.
    assert rec["chunk_s"] <= stages.spans["http.chunk"] + 1e-6


def test_a_result_follows_its_rounds_record(tiny_model_module):
    """Whoever waits on a request's future finds the round that finished
    it in the flight ring: the harvest writes the round's record first
    and resolves the futures of the requests it retired after."""
    cfg, params = tiny_model_module
    with _sched(cfg, params) as sched:
        for i in range(12):
            # Four tokens at decode_chunk 4: admitted and retired by one
            # round, whose record is the only one that names the request.
            fut = sched.submit([1, 5 + i, 9], max_new_tokens=4)
            assert len(fut.result(timeout=120)) == 4
            retired = [rid for r in sched.flight.snapshot()
                       for rid in r.get("retired", ())]
            assert len(retired) == len(set(retired)) == i + 1


def test_close_answers_the_clients_before_it_stops_a_capture(
        tiny_model_module, tmp_path, monkeypatch):
    """A shutdown (or crash) under a running capture fails the requests
    in flight at once and leaves the stop — tens of seconds on a TPU —
    to the writer thread; the fleet-wide guard goes when it has stopped."""
    import jax

    from llm_based_apache_spark_optimization_tpu.utils import traceprof

    stop_trace = jax.profiler.stop_trace

    def slow_stop():
        time.sleep(1.5)
        stop_trace()

    monkeypatch.setattr(jax.profiler, "stop_trace", slow_stop)
    cfg, params = tiny_model_module
    sched = _sched(cfg, params, max_seq=128).start()
    sched.generate([[1, 5, 9]], max_new_tokens=4)  # warm the programs
    fut = sched.submit([1, 2, 3], max_new_tokens=100)
    sched.profile_rounds(1000, out_dir=str(tmp_path))  # will never finish
    t0 = time.perf_counter()
    sched.shutdown(timeout=60)  # a bounded join: the supervisor's teardown
    closed_s = time.perf_counter() - t0
    assert fut.done() and (fut.exception() is not None or fut.result())
    assert closed_s < 1.0, closed_s  # not the stop's 1.5 s
    with pytest.raises(Exception):
        sched.submit([1, 2, 3], max_new_tokens=2).result(5)
    assert sched.profile_status()["state"] == "writing"
    assert traceprof.capture_owner() is not None  # the trace still runs
    deadline = time.time() + 60
    while traceprof.capture_owner() is not None and time.time() < deadline:
        time.sleep(0.02)
    st = sched.profile_status()
    assert st["state"] == "idle" and traceprof.capture_owner() is None
    assert st["last"]["state"] in ("aborted", "error")
    assert "scheduler closed" in st["last"]["error"]


def test_a_capture_that_sees_no_round_is_stopped(tiny_model_module, tmp_path,
                                                 monkeypatch):
    """`profile_rounds` starts the trace itself, so on a server with
    nothing to serve it would run without end: the idle loop stops it
    once it is `_PROFILE_IDLE_LIMIT_S` old."""
    from llm_based_apache_spark_optimization_tpu.serve import scheduler
    from llm_based_apache_spark_optimization_tpu.utils import traceprof

    monkeypatch.setattr(scheduler, "_PROFILE_IDLE_LIMIT_S", 0.3)
    cfg, params = tiny_model_module
    with _sched(cfg, params) as sched:
        assert sched.profile_rounds(4, out_dir=str(tmp_path))["state"] == "armed"
        deadline = time.time() + 60
        last = None
        while last is None and time.time() < deadline:
            time.sleep(0.02)
            last = sched.profile_status().get("last")
        assert last is not None and last["state"] == "aborted", last
        assert "no round to trace" in last["error"]
        assert last["wall_s"] >= 0.3
        assert traceprof.capture_owner() is None
        # The loop serves on, and the next capture can be taken.
        assert len(sched.generate([[1, 5, 9]], max_new_tokens=4)[0]) == 4
        assert sched.profile_rounds(1, out_dir=str(tmp_path))["state"] == "armed"


@pytest.fixture(scope="module")
def live_capture(tiny_model_module, tmp_path_factory):
    """A 6-round capture taken while requests keep arriving: the flight
    records around it, the last `profile_status`, and the host plane."""
    from llm_based_apache_spark_optimization_tpu.serve import flightrecorder

    cfg, params = tiny_model_module
    out_dir = tmp_path_factory.mktemp("capture")
    stop = threading.Event()
    flightrecorder.reconfigure(16384)  # a CPU round is a millisecond
    try:
        sched = _sched(cfg, params, max_seq=128)
    finally:
        flightrecorder.reconfigure(None)
    with sched:
        sched.generate([[1, 5, 9]], max_new_tokens=8)  # warm the programs

        def submitter(i):
            while not stop.is_set():
                sched.submit([1, 2 + i, 3], max_new_tokens=16).result(120)

        threads = [threading.Thread(target=submitter, args=(i,))
                   for i in range(3)]
        [th.start() for th in threads]
        try:
            time.sleep(0.5)
            armed = sched.profile_rounds(6, out_dir=str(out_dir))
            states, last = [armed["state"]], None
            deadline = time.time() + 120
            while time.time() < deadline:
                st = sched.profile_status()
                if st["state"] != states[-1]:
                    states.append(st["state"])
                last = st.get("last")
                if last and st["state"] == "idle":
                    break
                time.sleep(0.002)
            time.sleep(0.3)  # rounds after the capture
        finally:
            stop.set()
            [th.join(120) for th in threads]
        flight = sched.flight.snapshot()
    assert not any(th.is_alive() for th in threads)
    return {"flight": flight, "last": last, "states": states,
            "spans": _host_spans(out_dir), "rounds": 6}


def test_capture_does_not_hold_the_loop(live_capture):
    """The trace starts and stops beside the loop: around `profile_start`
    and `profile_done` no two consecutive round records are further apart
    than three median round walls (it was seconds, on a TPU)."""
    rounds = [r for r in live_capture["flight"] if "round" in r]
    gaps = sorted(b["ts"] - a["ts"] for a, b in zip(rounds, rounds[1:]))
    median = gaps[len(gaps) // 2]
    marks = {r["kind"]: r for r in live_capture["flight"]
             if r.get("kind") in ("profile_start", "profile_done")}
    assert set(marks) == {"profile_start", "profile_done"}
    for mark in marks.values():
        before = max((r for r in rounds if r["seq"] < mark["seq"]),
                     key=lambda r: r["seq"])
        after = min((r for r in rounds if r["seq"] > mark["seq"]),
                    key=lambda r: r["seq"])
        assert after["ts"] - before["ts"] <= max(3 * median, 0.25), (
            mark["kind"], after["ts"] - before["ts"], median)


def test_profile_done_directly_follows_the_last_traced_round(live_capture):
    flight = live_capture["flight"]
    start = next(r for r in flight if r.get("kind") == "profile_start")
    done = next(r for r in flight if r.get("kind") == "profile_done")
    i = flight.index(done)
    assert "round" in flight[i - 1]  # a round record, then the marker
    assert "round" in flight[i + 1]  # and the loop went on
    traced = [r for r in flight if "round" in r
              and start["seq"] < r["seq"] < done["seq"]]
    # The rounds in flight when the capture was armed are harvested
    # inside it but were issued before: they come on top of the six.
    assert live_capture["rounds"] <= len(traced) <= live_capture["rounds"] + 1
    assert done["rounds"] == live_capture["rounds"]


def test_capture_status_ends_done_with_an_xplane(live_capture):
    states, last = live_capture["states"], live_capture["last"]
    assert states[0] == "armed" and states[-1] == "idle"
    # Polled: `capturing` and `writing` are passed through in this order
    # wherever the poll caught them.
    seen = [s for s in states if s in ("capturing", "writing")]
    assert seen == sorted(seen) and "writing" in seen
    assert last["state"] == "done" and last["rounds"] == 6
    assert any(a.endswith(".xplane.pb") for a in last["artifacts"])
    assert any(a.endswith(".trace.json.gz") for a in last["artifacts"])
    assert last["artifact_bytes"] > 0
    assert last["start_s"] >= 0.0 and last["stop_s"] >= 0.0


def test_capture_pairs_issue_and_harvest_by_round(live_capture):
    """On the capture's host plane every round issued inside it has a
    `sched.issue_decode` span and, later, a `sched.harvest_wait` and a
    `sched.harvest` span of the same `round`, which is the round's number
    in its flight record."""
    by = {}
    for name, start, dur, stats in live_capture["spans"]:
        if name.startswith("sched.") and "round" in stats:
            by.setdefault(name, {})[stats["round"]] = (start, dur, stats)
    issued, waited, harvested = (by["sched.issue_decode"],
                                 by["sched.harvest_wait"], by["sched.harvest"])
    both = sorted(set(issued) & set(waited))
    assert len(both) >= live_capture["rounds"]
    # The trace may end between the wait for the last round and its harvest.
    assert set(both) - set(harvested) <= {both[-1]}
    recorded = {r["round"]: r for r in live_capture["flight"] if "round" in r}
    for rnd in sorted(set(both) & set(harvested)):
        i_start, i_dur, i_stats = issued[rnd]
        w_start, w_dur, _ = waited[rnd]
        h_start, _, h_stats = harvested[rnd]
        assert i_start + i_dur <= w_start <= w_start + w_dur <= h_start
        assert i_stats["occupancy"] == recorded[rnd]["occupancy"]
        assert h_stats["emitted"] == recorded[rnd]["emitted"]
    names = {name for name, *_ in live_capture["spans"]}
    assert {"sched.loop", "sched.admit", "sched.prefill_dispatch"} <= names
