"""The trace reduction against a small recorded trace and a hand-made one.

`recorded/smollm2_decode_round.json.gz` is one execution of the decode
program (8 steps, 24 layers, 4 slots) cut by `tools/cut_trace.py` from the
first traced chip run of PR 25 (`smollm2-1.7b-bf16.explain`, one v5e); the
numbers below were read from it by hand (sums of its events)."""
import os

import pytest

import xtrace

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "recorded", "smollm2_decode_round.json.gz")


@pytest.fixture(scope="module")
def tr():
    return xtrace.Trace.load(RECORDED)


def test_recorded_programs_and_kernels(tr):
    assert tr.device == "/device:TPU:0"
    secs, n = tr.total_s(xtrace.MODULES, "^jit_decode")
    assert n == 1 and secs == pytest.approx(0.186963251, rel=1e-9)
    assert tr.total_s(xtrace.MODULES, "^jit_prefill") == (0.0, 0)
    secs, n = tr.total_s(xtrace.OPS, "^ragged_paged_attention")
    assert n == 24 * 8 and secs == pytest.approx(0.013791203, rel=1e-9)
    secs, n = tr.total_s(xtrace.OPS, "^fused_page_write")
    assert n == 24 * 8 and secs == pytest.approx(0.002721514, rel=1e-9)


def test_recorded_busy_and_idle(tr):
    assert tr.busy_s() == pytest.approx(0.186957163, rel=1e-9)
    assert tr.span_s() == pytest.approx(0.186962577, rel=1e-9)
    assert 0.0 <= 1.0 - tr.busy_s() / tr.span_s() < 1e-4
    # the `while` that wraps the eight steps covers its body: a sum of the
    # lane would count the body twice, the union does not
    lane_sum = sum(d for _, _, d in tr.lanes[xtrace.OPS]) * 1e-9
    assert lane_sum > 1.5 * tr.busy_s()


def test_recorded_top_ops_are_leaves(tr):
    top = tr.top_ops(10)
    assert top[0][0] == "slice_bitcast_fusion"
    assert top[0][1] == pytest.approx(0.10199535, rel=1e-6)
    assert "while" not in [name for name, _ in top]
    assert sum(s for _, s in tr.top_ops(1000)) <= tr.busy_s() * (1 + 1e-9)
    assert ["ragged_paged_attention", pytest.approx(0.013791203)] in top


def test_hand_made_lanes():
    ops = [["%while.1 = s32[] while(...)", 0, 100],      # parent of the next two
           ["%fusion.3 = bf16[8] fusion(...)", 10, 30],
           ["%ragged_paged_attention.7 = bf16[8] custom-call(...)", 50, 40],
           ["%copy.2 = bf16[8] copy(...)", 200, 50],      # after a gap of 100
           ["%copy.9 = bf16[8] copy(...)", 250, 50]]
    mods = [["jit_decode(123)", 0, 100], ["jit_prefill(9)", 200, 100]]
    us = 1000  # the table above is in microseconds, a trace in nanoseconds
    t = xtrace.Trace({
        xtrace.OPS: [[xtrace.short_name(n), s * us, d * us] for n, s, d in ops],
        xtrace.MODULES: [[n, s * us, d * us] for n, s, d in mods]},
        "/device:TPU:0")
    assert t.busy_s() == pytest.approx(200e-6)
    assert t.span_s() == pytest.approx(300e-6)
    assert t.total_s(xtrace.OPS, "^ragged_paged_attention") == (pytest.approx(40e-6), 1)
    assert t.total_s(xtrace.MODULES, "^jit_prefill") == (pytest.approx(100e-6), 1)
    assert t.top_ops(2) == [["copy", pytest.approx(100e-6)],
                            ["ragged_paged_attention", pytest.approx(40e-6)]]
    assert t.idle_gaps(1) == [["after jit_decode", pytest.approx(100e-6)]]


def test_xplane_names_are_shortened():
    assert xtrace.short_name(
        "%slice_bitcast_fusion.221.remat2 = bf16[213,32,64,64]{3,2,1,0} fusion(...)"
    ) == "slice_bitcast_fusion.221.remat2"
    assert xtrace.short_name("jit_decode(5979217821094033583)") == \
        "jit_decode(5979217821094033583)"
