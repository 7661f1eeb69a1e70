"""The per-layer readers on a recorded flight record and the recorded trace.

`recorded/flight_rounds.json` holds eight round records as the program's
flight recorder wrote them; `recorded/smollm2_decode_round.json.gz` is one
execution of the decode program on the chip (test_trace.py). Put together
as a traced run's context, the readers have to give the numbers worked out
by hand below. The point of it: a record's `perf_ctx` is the mean context
of ONE occupied slot, and what a decode step reads is the context of ALL of
them, `perf_ctx * occupancy`."""
import os

import pytest

import costs
import layers
import spec
import xtrace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUNDS = spec.load_json("recorded", "flight_rounds.json")["rounds"]
# (occupancy, perf_ctx) of the eight records, read off the file
OCC_CTX = [(1, 393), (2, 401), (2, 409), (2, 417), (4, 404), (4, 409),
           (3, 410), (2, 403)]
LIVE = (393 + 802 + 818 + 834 + 1616 + 1636 + 1230 + 806) / 8  # 1016.875
SLOTS = 20 / 8                                                  # 2.5
DECODE_S, RAGGED_S, SPAN_S = 0.186963251, 0.013791203, 0.186962577


def _ctx(rounds):
    cell = spec.Cell("smollm2-1.7b-bf16.explain")
    return layers.Context(
        cell=cell, peaks=costs.peaks("TPU v5 lite"), requests=[], server_log={},
        flight=rounds, flight_traced=rounds, metrics_t0={}, metrics_t1={},
        trace=xtrace.Trace.load(
            os.path.join(HERE, "recorded", "smollm2_decode_round.json.gz")))


def _read(name, ctx):
    return spec.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py")).read(ctx)


def test_the_file_is_what_was_read_off_it():
    assert [(r["occupancy"], r["perf_ctx"]) for r in ROUNDS] == OCC_CTX


def test_live_tokens_are_all_slots_together():
    dec = _ctx(ROUNDS).traced_decode()
    assert dec["live_tokens"] == pytest.approx(LIVE)
    assert dec["active_slots"] == pytest.approx(SLOTS)
    assert dec["rounds"] == 8


def test_decode_hbm_pct_by_hand():
    # SmolLM2 bf16: 3,422,752,768 bytes of weights a step (test_costs.py),
    # 196,608 bytes of KV a live token; the recorded decode program ran 8
    # steps in 0.186963251 s.
    need = 3_422_752_768 + 196_608 * LIVE
    assert need == pytest.approx(3_622_678_528)
    want = 100.0 * need / 819e9 / (DECODE_S / 8)
    assert want == pytest.approx(18.927, rel=1e-4)
    assert _read("decode_hbm_pct", _ctx(ROUNDS)) == pytest.approx(want, rel=1e-9)


def test_ragged_roofline_by_hand():
    # One call: K and V of every live token, 2 x (32 x 64 x 2 bytes), and the
    # queries in and the output out, 32 x 64 x 2 bytes a slot each way.
    bytes_ = 8_192 * LIVE + 8_192 * SLOTS
    assert bytes_ == pytest.approx(8_350_720)
    want = 100.0 * (bytes_ / 819e9) * 192 / RAGGED_S   # 192 calls traced
    assert want == pytest.approx(14.195, rel=1e-4)
    assert _read("ragged_paged_attention_roofline", _ctx(ROUNDS)) == \
        pytest.approx(want, rel=1e-9)


def test_step_mfu_by_hand():
    decoded, prefilled, rows = 140, 123 + 81, 2       # `emitted`, `prefix_reuse`
    attended = ((395 * 396 - 272 * 273) + (353 * 354 - 272 * 273)) / 2
    assert attended == 66_435
    ops = (3_221_225_472 * (decoded + prefilled)      # block matmuls a token
           + 201_326_592 * (decoded + rows)           # the head
           + 196_608 * (LIVE / SLOTS) * decoded       # decode attention
           + 196_608 * attended)                      # prefill attention
    want = 100.0 * ops / (SPAN_S * 197e12)
    assert want == pytest.approx(3.152, rel=1e-4)
    assert _read("step_mfu", _ctx(ROUNDS)) == pytest.approx(want, rel=1e-9)


def test_more_slots_at_one_context_read_more_kv():
    """Twice the slots at the same context a slot: twice the KV bytes. The
    fault this guards against read `perf_ctx` as the total and saw no
    difference."""
    twice = [{**r, "occupancy": 2 * r["occupancy"]} for r in ROUNDS]
    one, two = _ctx(ROUNDS), _ctx(twice)
    assert two.traced_decode()["live_tokens"] == pytest.approx(2 * LIVE)
    kv = 100.0 * 196_608 * LIVE / 819e9 / (DECODE_S / 8)
    assert _read("decode_hbm_pct", two) - _read("decode_hbm_pct", one) == \
        pytest.approx(kv, rel=1e-9)
    # ... and a decoded token still attends to one slot's context
    assert (two.traced_decode()["live_tokens"] / two.traced_decode()["active_slots"]
            == pytest.approx(LIVE / SLOTS))


def test_nothing_traced_nothing_read():
    ctx = _ctx([])
    for name in ("decode_hbm_pct", "ragged_paged_attention_roofline", "step_mfu"):
        assert _read(name, ctx) is None
