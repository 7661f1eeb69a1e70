"""The host's spans beside the device lanes (`hostspans.py`) and the twelve
readers of PR 26, on a hand-made capture, on a recorded one, and on a
program that has no such spans.

`recorded/mistral_nl2sql_rounds.json.gz` is five decode rounds (and the
idle stretch and prefill between them) cut by `hostspans.py` from a traced chip run of PR 26
(`mistral-7b-int8.nl2sql`, one v5e): the device's two lanes, the program's
spans on the host plane of the same capture, the flight records of that
run's traced rounds and its request log. The numbers below were read off
the file by hand (`RECORDED_*`)."""
import os

import pytest

import costs
import hostspans
import layers
import spec
import xtrace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(HERE, "recorded", "mistral_nl2sql_rounds.json.gz")
NEW = ("round_host_busy_ms", "host_late_rounds_pct", "prefill_span_p50_ms",
       "first_token_hold_p50_ms", "stream_lag_p90_ms", "device_idle_named_pct",
       "http_chunk_ms_per_token", "detok_ms_per_token",
       "prefill_ms_per_new_token", "prefill_dispatch_host_ms",
       "prefill_tokens_per_chunk", "prefill_rows_per_chunk")


def _read(name, ctx):
    return spec.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py")).read(ctx)


def _ctx(cell="mistral-7b-int8.nl2sql", **kw):
    base = dict(cell=spec.Cell(cell), peaks=costs.peaks("TPU v5 lite"),
                requests=[], server_log={}, flight=[], flight_traced=[],
                metrics_t0={}, metrics_t1={}, trace=None)
    return layers.Context(**{**base, **kw})


# ------------------------------------------------------------- hand-made

US = 1000  # the tables below are in microseconds, a trace in nanoseconds


def _hand_made():
    ops = [["%fusion.1 = bf16[8] fusion(...)", 0, 100],
           ["%fusion.2 = bf16[8] fusion(...)", 100, 50],   # no gap before it
           ["%copy.3 = bf16[8] copy(...)", 250, 50],       # gap 150..250
           ["%copy.4 = bf16[8] copy(...)", 600, 100]]      # gap 300..600
    mods = [["jit_decode(1)", 0, 150], ["jit_prefill(2)", 250, 50],
            ["jit_decode(3)", 600, 100]]
    tr = xtrace.Trace({xtrace.OPS: [[n, s * US, d * US] for n, s, d in ops],
                       xtrace.MODULES: [[n, s * US, d * US] for n, s, d in mods]})
    tr.host_spans = [(n, s * US, d * US, st) for n, s, d, st in [
        ("sched.loop", 0, 700, {"step_num": 9}),           # covers the rest
        ("sched.issue_decode", 0, 20, {"round": 1, "occupancy": 2}),
        ("sched.harvest_wait", 140, 40, {"round": 1}),     # 150..180 of gap 1
        ("sched.harvest", 180, 30, {"round": 1, "emitted": 8}),  # 180..210
        ("stream.detok", 200, 300, {"rid": "req-1"}),      # another thread
        ("sched.prefill_dispatch", 230, 10, {"rows": 1}),  # 230..240
        ("sched.idle", 320, 230, {}),                      # 320..550 of gap 2
        ("sched.issue_decode", 590, 20, {"round": 2, "occupancy": 1}),
    ]]
    return tr


def test_idle_by_span_by_hand():
    tr = _hand_made()
    idle_s, under = hostspans.idle_by_span(tr, tr.host_spans)
    # Two gaps: 150..250 and 300..600, 400 us. Under a stage of the loop:
    # 30 + 30 + 10 of the first, 230 + 10 of the second; the loop pass and
    # the stream's span do not count.
    assert idle_s == pytest.approx(400e-6)
    assert {k: round(v * 1e6) for k, v in under.items()} == {
        "sched.harvest_wait": 30, "sched.harvest": 30,
        "sched.prefill_dispatch": 10, "sched.idle": 230,
        "sched.issue_decode": 10}
    assert _read("device_idle_named_pct", _ctx(trace=tr)) == \
        pytest.approx(100.0 * 310 / 400)


def test_rounds_pair_issue_and_wait():
    by = hostspans.rounds_of(_hand_made().host_spans)
    assert sorted(by) == [1, 2]
    assert set(by[1]) == {"sched.issue_decode", "sched.harvest_wait",
                          "sched.harvest"}
    assert by[1]["sched.harvest"][2]["emitted"] == 8
    assert set(by[2]) == {"sched.issue_decode"}  # not harvested in the cut


def test_a_device_that_is_never_idle_names_nothing():
    tr = xtrace.Trace({xtrace.OPS: [["%fusion.1 = f(...)", 0, 100 * US]]})
    tr.host_spans = [("sched.harvest_wait", 0, 100 * US, {"round": 1})]
    assert hostspans.idle_by_span(tr, tr.host_spans) == (0.0, {})
    assert _read("device_idle_named_pct", _ctx(trace=tr)) is None


def test_compact_form_round_trips(tmp_path):
    import gzip
    import json

    tr = _hand_made()
    doc = tr.compact()
    doc["host"] = [list(s) for s in tr.host_spans]
    path = str(tmp_path / "cut.json.gz")
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)
    back = hostspans.load(path)
    assert back.host_spans == tr.host_spans
    assert back.lanes == tr.lanes
    assert xtrace.Trace.load(path).lanes == tr.lanes  # the old reader too


# ------------------------------------------- host-clock readers, by hand

FLIGHT = [  # what the program's recorder writes a round, the new columns
    {"round": 7, "occupancy": 2, "harvest_wait_s": 0.1801, "idle_s": 0.0,
     "host_s": {"sched.admit": 0.0001, "sched.issue_decode": 0.0019,
                "sched.harvest": 0.0010},
     "prefill_chunks": 0, "prefill_rows": 0, "prefill_tokens": 0},
    {"round": 8, "occupancy": 2, "harvest_wait_s": 0.0004, "idle_s": 0.0,
     "host_s": {"sched.admit": 0.0002, "sched.prefill_dispatch": 0.0038,
                "sched.issue_decode": 0.0020, "sched.harvest": 0.0040},
     "prefill_chunks": 2, "prefill_rows": 3, "prefill_tokens": 300},
    {"round": 9, "occupancy": 1, "harvest_wait_s": 0.1650, "idle_s": 0.2500,
     "host_s": {"sched.prefill_dispatch": 0.0010, "sched.issue_decode": 0.0016,
                "sched.harvest": 0.0004},
     "prefill_chunks": 1, "prefill_rows": 1, "prefill_tokens": 100},
    {"round": 10, "occupancy": 1, "harvest_wait_s": 0.00099, "idle_s": 0.0,
     "host_s": {"sched.issue_decode": 0.0015, "sched.harvest": 0.0005},
     "prefill_chunks": 0, "prefill_rows": 0, "prefill_tokens": 0},
]
LOG = {  # request id -> the program's request-log record
    "req-a": {"queue_wait_s": 0.10, "prefill_s": 1.20, "first_hold_s": 0.21,
              "stream_lag_p90_s": 0.0011, "prefix_reused_tokens": 0,
              "prompt_tokens": 1000, "output_tokens": 20,
              "chunk_s": 0.010, "detok_s": 0.0004},
    "req-b": {"queue_wait_s": 0.02, "prefill_s": 0.40, "first_hold_s": 0.35,
              "stream_lag_p90_s": 0.0042, "prefix_reused_tokens": 512,
              "prompt_tokens": 912, "output_tokens": 40,
              "chunk_s": 0.008, "detok_s": 0.0020},
    "req-c": {"prefill_s": 2.00, "first_hold_s": 0.19,
              "stream_lag_p90_s": 0.0007, "prefix_reused_tokens": 16,
              "prompt_tokens": 816, "output_tokens": 10,
              "chunk_s": 0.009, "detok_s": 0.0001},
    "req-d": {"queue_wait_s": 0.50, "prompt_tokens": 700,
              "output_tokens": 30},           # not streamed: nothing new
    "req-late": {"prefill_s": 9.0, "first_hold_s": 9.0,
                 "stream_lag_p90_s": 9.0, "prefix_reused_tokens": 0,
                 "prompt_tokens": 9, "output_tokens": 9, "chunk_s": 9.0,
                 "detok_s": 9.0},             # due after the trace was armed
}
REQUESTS = [{"request_id": r} for r in ("req-a", "req-b", "req-c", "req-d")] \
    + [{"idx": 5}]                             # one that never got an id


def test_host_clock_readers_by_hand():
    ctx = _ctx(flight=FLIGHT, server_log=LOG, requests=REQUESTS)
    # 3.0, 10.0, 3.0 and 2.0 ms of stages a round: the waits are left out
    assert _read("round_host_busy_ms", ctx) == pytest.approx(18.0 / 4)
    # rounds 8 and 10 waited under a millisecond for the device
    assert _read("host_late_rounds_pct", ctx) == pytest.approx(50.0)
    # nearest rank of three: the second smallest
    assert _read("prefill_span_p50_ms", ctx) == pytest.approx(1200.0)
    assert _read("first_token_hold_p50_ms", ctx) == pytest.approx(210.0)
    # nearest rank, 0.9 of three: the largest
    assert _read("stream_lag_p90_ms", ctx) == pytest.approx(4.2)
    # a token: 0.5, 0.2 and 0.9 ms in the chunk writer; 0.02, 0.05 and 0.01
    # ms re-decoding the output
    assert _read("http_chunk_ms_per_token", ctx) == pytest.approx(0.5)
    assert _read("detok_ms_per_token", ctx) == pytest.approx(0.02)
    # 1,200 ms for 1,000 tokens; 400 for 912 - 512; 2,000 for 816 - 16
    assert _read("prefill_ms_per_new_token", ctx) == pytest.approx(1.2)
    # three chunk batches (two in round 8, one in 9): 3.8 + 1.0 ms of
    # dispatch, 400 tokens, 4 rows
    assert _read("prefill_dispatch_host_ms", ctx) == pytest.approx(1.6)
    assert _read("prefill_tokens_per_chunk", ctx) == pytest.approx(400 / 3)
    assert _read("prefill_rows_per_chunk", ctx) == pytest.approx(4 / 3)


# ------------------------------------------------- the recorded capture

@pytest.fixture(scope="module")
def recorded():
    import gzip
    import json

    with gzip.open(RECORDED, "rt") as f:
        doc = json.load(f)
    return hostspans.load(RECORDED), doc


ROUNDS = (171, 172, 173, 174, 175)


def test_recorded_rounds_and_their_spans(recorded):
    tr, doc = recorded
    assert tr.device == "/device:TPU:0" and doc["cut"]["rounds"] == list(ROUNDS)
    by = hostspans.rounds_of(tr.host_spans)
    # Issued in the cut, with three, two and one slot occupied; 174 and 175
    # within a millisecond of each other, after the loop had idled: the
    # first pass after an idle stretch has no round to harvest.
    assert [(r, by[r][hostspans.ISSUE][0], by[r][hostspans.ISSUE][2]["occupancy"])
            for r in ROUNDS] == [
        (171, 0, 3), (172, 183_038_354, 2), (173, 363_944_168, 1),
        (174, 927_950_318, 1), (175, 928_658_848, 1)]
    # harvested in it: 170 (its wait began in the cut) to 175
    assert sorted(r for r in by if hostspans.WAIT in by[r]) == list(range(170, 176))
    assert [by[r]["sched.harvest"][2]["emitted"] for r in (171, 172, 173, 174)] \
        == [9, 4, 0, 9]


def test_recorded_decode_programs_run_after_their_issue_span(recorded):
    """Host spans and device lanes are on one clock: the decode program of
    a round — the last to end before the host's wait for that round ends —
    starts after the start of the `sched.issue_decode` span that carries
    the round, and the wait ends 1 to 2 ms after the program does (the
    tokens' way to the host)."""
    tr, _ = recorded
    by = hostspans.rounds_of(tr.host_spans)
    decodes = tr.events(xtrace.MODULES, "^jit_decode")
    assert [s for s, _ in decodes] == [
        176_621_678, 359_570_151, 540_337_706, 1_215_135_399, 1_393_374_549]
    seen = []
    for rnd in ROUNDS:
        issue_start = by[rnd][hostspans.ISSUE][0]
        wait_end = sum(by[rnd][hostspans.WAIT][:2])
        start, dur = max((e for e in decodes if sum(e) <= wait_end), key=sum)
        assert start > issue_start
        assert 1e6 < wait_end - (start + dur) < 2e6
        seen.append(start)
    assert seen == [s for s, _ in decodes]  # one program a round, in order
    # The device ran a round behind the host: a program started when the
    # one before it ended, ~176 ms after its round was issued; round 174
    # stood behind the seven prefill chunks dispatched before it, and 175
    # behind 174.
    assert [s - by[r][hostspans.ISSUE][0] for s, r in zip(seen, ROUNDS)] == [
        176_621_678, 176_531_797, 176_393_538, 287_185_081, 464_715_701]
    assert len(tr.events(xtrace.MODULES, "^jit_prefill")) == 7


def test_recorded_idle_lies_under_the_wait_for_work(recorded):
    tr, _ = recorded
    assert tr.busy_s() == pytest.approx(1.375992771, rel=1e-9)
    assert tr.span_s() == pytest.approx(1.571604751, rel=1e-9)
    idle_s, under = hostspans.idle_by_span(tr, tr.host_spans)
    # 0.1956 s of gaps in 1.57 s: the device had nothing to run while the
    # loop waited four times (51 + 50 + 50 + 39 ms) for a request; the rest
    # are microseconds between programs.
    assert round(idle_s * 1e9) == 195_611_980
    assert [(s, d) for n, s, d, _ in tr.host_spans if n == "sched.idle"] == [
        (720_793_826, 51_009_686), (771_865_712, 50_265_665),
        (822_197_528, 50_113_975), (872_376_733, 38_849_736)]
    assert {k: round(v * 1e9) for k, v in under.items()} == {
        "sched.idle": 190_239_062, "sched.harvest_wait": 1_967_727,
        "sched.admit": 1_956_234, "sched.prefill_dispatch": 1_092_955,
        "sched.harvest": 78_389, "sched.upkeep": 20_020,
        "sched.issue_decode": 93}
    assert _read("device_idle_named_pct", _ctx(trace=tr)) == \
        pytest.approx(100.0 * 195_354_480 / 195_611_980)
    assert hostspans.run_dir(tr) is None  # a recorded capture: no run beside it


# (host_s summed, ms) of the recorded run's 16 traced rounds, 171 to 186
RECORDED_BUSY_MS = [3.6, 3.628, 0.094, 18.104, 8.187, 5.507, 5.165, 20.158,
                    1.135, 1.696, 1.792, 8.195, 498.893, 83.474, 6.909, 9.042]
RECORDED_IDS = ["req-162b-be1c78-" + x for x in (
    "17", "19", "1a", "1e", "1c", "1b", "1d", "21", "20", "1f", "24", "26")]


def test_host_clock_readers_on_the_recorded_run(recorded):
    _, doc = recorded
    flight = doc["flight"]
    assert [r["round"] for r in flight] == list(range(171, 187))
    assert [round(sum(r["host_s"].values()) * 1e3, 3) for r in flight] == \
        RECORDED_BUSY_MS
    log = {r["request_id"]: r for r in doc["request_log"]}
    ctx = _ctx(flight=flight, server_log=log,
               requests=[{"request_id": r} for r in RECORDED_IDS])
    assert _read("round_host_busy_ms", ctx) == pytest.approx(675.579 / 16)
    # round 183 alone found its tokens waiting (0.408 ms): it had spent
    # half a second admitting and dispatching eight chunk batches
    assert [r["round"] for r in flight if r["harvest_wait_s"] < 1e-3] == [183]
    assert _read("host_late_rounds_pct", ctx) == pytest.approx(100 / 16)
    # twelve requests: the sixth smallest, and for the p90 the eleventh
    assert _read("prefill_span_p50_ms", ctx) == pytest.approx(2072.879)
    assert _read("first_token_hold_p50_ms", ctx) == pytest.approx(501.931)
    assert _read("stream_lag_p90_ms", ctx) == pytest.approx(6.468)
    # the sixth smallest: 19.607 ms of chunk writing for 58 tokens, 0.507 ms
    # of decoding for 33, 2,319.015 ms of prefill for 1,156 - 48 tokens
    assert _read("http_chunk_ms_per_token", ctx) == pytest.approx(19.607 / 58)
    assert _read("detok_ms_per_token", ctx) == pytest.approx(0.507 / 33)
    assert _read("prefill_ms_per_new_token", ctx) == \
        pytest.approx(2319.015 / 1108)
    # 30 chunk batches in nine of the sixteen rounds (eight of them, with
    # 13 rows and 368 ms of dispatch, in round 183): 38 rows, 4,491 tokens
    assert sum(r["prefill_chunks"] for r in flight) == 30
    assert _read("prefill_dispatch_host_ms", ctx) == pytest.approx(503.944 / 30)
    assert _read("prefill_tokens_per_chunk", ctx) == pytest.approx(4491 / 30)
    assert _read("prefill_rows_per_chunk", ctx) == pytest.approx(38 / 30)
    # the three waits are one clock's: they end where the TTFT ends
    for rid in RECORDED_IDS:
        r = log[rid]
        waits = r["queue_wait_s"] + r["prefill_s"] + r["first_hold_s"]
        assert -2e-3 <= waits - r["ttft_s"] <= 1e-4


# -------------------------------------- a program without spans (parent)

def test_a_program_without_spans_reads_nothing():
    """The parent of PR 26 writes none of this: its flight records, request
    log and capture (the files recorded by PR 25) give every new reader
    nothing, and none raises."""
    old_rounds = spec.load_json("recorded", "flight_rounds.json")["rounds"]
    old_trace = xtrace.Trace.load(
        os.path.join(HERE, "recorded", "smollm2_decode_round.json.gz"))
    old_trace.host_spans = []  # what `host_plane` finds on such a capture
    ctx = _ctx(cell="smollm2-1.7b-bf16.explain", flight=old_rounds,
               flight_traced=old_rounds, trace=old_trace,
               server_log={"req-a": {"queue_wait_s": 0.1, "ttft_s": 1.0}},
               requests=[{"request_id": "req-a"}])
    for name in NEW:
        assert _read(name, ctx) is None, name
    assert _read("device_idle_named_pct", _ctx()) is None  # no trace at all


def test_no_capture_under_benchmark_out_is_no_spans(monkeypatch, tmp_path):
    tr = xtrace.Trace({xtrace.OPS: [["%a = f()", 0, 10], ["%b = f()", 50, 10]]})
    ctx = _ctx(trace=tr)
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    assert hostspans.find_capture("mistral-7b-int8.nl2sql") is None
    assert hostspans.of(ctx) == []
    assert _read("device_idle_named_pct", ctx) is None


def test_idle_seconds_by_span_land_in_the_runs_directory(
        monkeypatch, tmp_path, capsys):
    """The reader finds the run's capture under `benchmark_out/`, and
    leaves the idle seconds by span beside the run's log and on stderr."""
    import json

    run = tmp_path / "benchmark_out" / "mistral-7b-int8.nl2sql" / "run-7-1"
    capture = run / "server" / "profile" / "profile-1-r0" / "plugins" / "profile"
    capture.mkdir(parents=True)
    (capture / "host.xplane.pb").write_bytes(b"")
    tr = _hand_made()
    spans = tr.host_spans
    del tr.host_spans
    ctx = _ctx(trace=tr)
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(hostspans, "host_plane", lambda path: spans)
    assert hostspans.of(ctx) == spans
    assert tr.capture_path == str(capture / "host.xplane.pb")
    assert hostspans.run_dir(tr) == str(run)
    value = _read("device_idle_named_pct", ctx)
    with open(run / "idle_by_span.json") as f:
        by = json.load(f)
    assert value == pytest.approx(
        100.0 * sum(by["by_span_s"].values()) / by["idle_s"])
    assert by["idle_s"] == pytest.approx(400e-6)  # gaps 150..250, 300..600
    assert by["under_no_span_s"] == pytest.approx(
        by["idle_s"] - sum(by["by_span_s"].values()))
    assert list(by["by_span_s"])[0] == "sched.idle"  # 230 of the 400 us
    assert "by host span" in capsys.readouterr().err


def test_the_idle_share_is_the_open_loops_alone():
    """A closed loop's idle time is microseconds between programs: the
    share is read in `mistral-7b-int8.nl2sql` only."""
    for cell in spec.benchmark()["workloads"]:
        names = {m["name"] for m in spec.Cell(cell["name"]).per_layer}
        assert ("device_idle_named_pct" in names) == \
            (cell["traffic"] == "nl2sql"), cell["name"]
        assert set(NEW) - {"device_idle_named_pct"} <= names
