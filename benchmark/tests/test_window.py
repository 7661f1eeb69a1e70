"""No end-to-end metric may read worse because the server got faster.

Every cell of `BENCHMARK.json`, those that later PRs add too, has its real
schedule at the benchmark's 45 s played against a service model of two
lines: a request waits for the first of the configuration's slots to come
free, and chunk k arrives T + k*t after it got the slot. Once at a generic
(T, t) of 1.5 s and 30 ms, once at (0.75 T, 0.7 t), about what PR 27 did to
them. Both plays go through `run.end_to_end` with the cell's own list of
metrics, and every metric on the line has to be no worse on the faster play.
The slots give the model a knee (slots over a request's T + n*t: about 3
requests/s for the `nl2sql` mix at 8 slots, twice the chip's, whose prefills
share one device): a cell offered more than that reads capacity as its count
of tokens inside the window, which rises with the faster server; a cell
offered less reads the schedule's.

The case this file exists for: `output_tok_s` in `mistral-7b-int8.nl2sql`,
an open loop below its knee. What such a loop delivers is what it is
offered, plus the lead-in's backlog that arrives inside the window: 49.3
tokens/s at (2.0 s, 30.7 ms), 48.5 at (1.5 s, 21.7 ms), 45.13 from a server
that takes no time at all. PR 27 was refused on that number alone. The
metric is judged where the count is capacity; the last test but one puts it
back into the open loop's list and sees the fault."""
import heapq
import os
import types

import pytest

import run
import spec
import traffic

SECONDS = 45.0
T0 = 1_000.0
FIRST_S, GAP_S = 1.5, 0.030
WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]
OPEN_BELOW_KNEE = "mistral-7b-int8.nl2sql"


def play(schedule: dict, slots: int, first_s: float, gap_s: float) -> list:
    """The client's records of `schedule` under the service model."""
    t_end = T0 + schedule["seconds"]

    def served(req: dict, due: float, start: float) -> dict:
        n = req["max_new_tokens"]
        return {"idx": req["idx"], "due": due, "max_new_tokens": n,
                "done": True, "error": None,
                "chunk_t": [start + first_s + k * gap_s for k in range(n)]}

    records = []
    if schedule["loop"] == "open":
        free = [0.0] * slots
        for req in sorted(schedule["requests"], key=lambda r: r["due_s"]):
            due = T0 + req["due_s"]
            records.append(served(req, due, max(due, heapq.heappop(free))))
            heapq.heappush(free, records[-1]["chunk_t"][-1])
        return records
    assert schedule["clients"] <= slots  # a lane never waits for a slot
    for c in range(schedule["clients"]):
        lane = sorted((r for r in schedule["requests"] if r["client"] == c),
                      key=lambda r: r["order"])
        now = T0 - schedule["lead_in_s"]
        for req in lane:  # as client.py: due when the last one ended
            if now >= t_end:
                break
            records.append(served(req, now, now))
            now = records[-1]["chunk_t"][-1]
    return records


def readings(metrics: list, cell, schedule: dict, first_s: float,
             gap_s: float) -> dict:
    """What `run.end_to_end` prints for a cell whose list is `metrics`."""
    records = play(schedule, cell.serving["slots"], first_s, gap_s)
    line, attempted, failed, _, _ = run.end_to_end(
        types.SimpleNamespace(end_to_end=metrics), records, T0, T0 + SECONDS,
        SECONDS)
    assert attempted and not failed
    return {k: v["value"] for k, v in line.items()}


def worse_when_faster(metrics: list, cell, schedule: dict,
                      first_s: float = FIRST_S, gap_s: float = GAP_S) -> list:
    slow = readings(metrics, cell, schedule, first_s, gap_s)
    fast = readings(metrics, cell, schedule, 0.75 * first_s, 0.7 * gap_s)
    sign = {m["name"]: 1.0 if m["better"] == "lower" else -1.0 for m in metrics}
    assert set(slow) == set(fast) == set(sign) - {"setup_s"}
    return [k for k in slow if sign[k] * (fast[k] - slow[k]) > 0]


@pytest.fixture(scope="module")
def schedule_of():
    made = {}
    return lambda name: made.setdefault(
        name, traffic.build(spec.Cell(name), 11, SECONDS))


@pytest.mark.parametrize("name", WORKLOADS)
def test_a_faster_server_reads_no_worse(name, schedule_of):
    cell = spec.Cell(name)
    assert worse_when_faster(cell.end_to_end, cell, schedule_of(name)) == []


def test_the_open_loops_count_is_the_schedules(schedule_of):
    """8 lead-in requests (277 tokens) and 54 in the window (2,031): 45.13
    tokens/s offered. The count inside the window adds the lead-in's spill
    and falls towards the offered rate as the server gets faster."""
    cell, sched = spec.Cell(OPEN_BELOW_KNEE), schedule_of(OPEN_BELOW_KNEE)
    lead = [r for r in sched["requests"] if r["due_s"] < 0]
    window = [r for r in sched["requests"] if r["due_s"] >= 0]
    assert (len(lead), sum(r["max_new_tokens"] for r in lead)) == (8, 277)
    assert (len(window), sum(r["max_new_tokens"] for r in window)) == (54, 2031)
    at = {}
    for first_s, gap_s in ((2.0, 0.0307), (1.5, 0.0217), (0.0, 0.0)):
        recs = play(sched, cell.serving["slots"], first_s, gap_s)
        at[first_s] = run.open_loop_count(recs, T0, T0 + SECONDS, SECONDS)
        have = run.end_to_end(cell, recs, T0, T0 + SECONDS, SECONDS)[4]
        made = at[first_s]
        assert have["output_tok_s"] * SECONDS == pytest.approx(
            made["offered_tok_s"] * SECONDS + made["lead_in_tokens_inside"]
            - made["window_tokens_after"])
    assert all(a["offered_tok_s"] == pytest.approx(2031 / 45) for a in at.values())
    assert all(a["window_tokens_after"] == 0 for a in at.values())
    assert [at[k]["lead_in_tokens_inside"] for k in (2.0, 1.5, 0.0)] == [188, 150, 0]


def test_forced_back_into_the_open_loop_it_reads_worse_when_faster(schedule_of):
    """The fault, kept written down: with `output_tok_s` in the open loop's
    list the faster server reads lower, by more than the metric's bound."""
    cell, sched = spec.Cell(OPEN_BELOW_KNEE), schedule_of(OPEN_BELOW_KNEE)
    tok_s = next(m for m in spec.benchmark()["end_to_end"]
                 if m["name"] == "output_tok_s")
    assert tok_s not in cell.end_to_end
    forced = cell.end_to_end + [tok_s]
    assert worse_when_faster(forced, cell, sched) == ["output_tok_s"]
    assert worse_when_faster(forced, cell, sched, 2.0, 0.0307) == ["output_tok_s"]
    slow = readings(forced, cell, sched, 2.0, 0.0307)["output_tok_s"]
    fast = readings(forced, cell, sched, 1.5, 0.0217)["output_tok_s"]
    assert slow == pytest.approx(49.31, abs=0.01)
    assert fast == pytest.approx(48.47, abs=0.01)
    assert (slow - fast) / slow > tok_s["bound"]


def test_a_cell_reports_what_its_per_layer_metrics_move():
    """The contract's rule, which the fault above ran into from the other
    side: a cell that reads a per-layer metric reports the end-to-end
    metric it should move. And every entry has its reader, every reader its
    entry."""
    bench = spec.benchmark()
    for name in WORKLOADS:
        cell = spec.Cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported, (name, m["name"])
    files = {f[:-3] for f in os.listdir(os.path.join(spec.HERE, "layer_metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in bench["per_layer"]}
