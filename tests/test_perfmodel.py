"""Performance-attribution layer (ISSUE 12): the shared analytic roofline
model (utils/perfmodel.py), the scheduler's per-round ledger RECONCILING
with it exactly on a CPU fixture, the on-demand device-profile capture,
and the preempted/resumed trace spans.

All on the TINY config, CPU f32 (conftest forces the CPU platform)."""

import time

import pytest

from llm_based_apache_spark_optimization_tpu.utils import perfmodel, traceprof
from llm_based_apache_spark_optimization_tpu.utils.perfmodel import PerfModel


@pytest.fixture(scope="module")
def tiny_model_module():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def make_sched(cfg, params, **kw):
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("stop_ids", (-1,))
    return ContinuousBatchingScheduler(cfg, params, **kw)


# --------------------------------------------------------- analytic model


def test_peak_for_chip_table_cpu_row_and_unknown_kind():
    flops, bw = perfmodel.peak_for("TPU v5e chip", "")
    assert flops == 197.0e12 and bw == 819.0e9
    flops8, _ = perfmodel.peak_for("TPU v5e chip", "int8")
    assert flops8 == 393.0e12  # int8 rides the TOP/s column (published)
    # What a v5e chip reports as its device_kind (chip run, PR 21).
    assert perfmodel.peak_for("TPU v5 lite", "") == (flops, bw)
    # The CPU fixture is an explicit row of nominal host figures ...
    assert perfmodel.peak_for("cpu", "") == (0.2e12, 50.0e9)
    # ... and a kind the table does not know is an error, not a default:
    # no catch-all, no environment variable that invents peaks.
    with pytest.raises(ValueError, match="no peak figures"):
        perfmodel.peak_for("weird-device", "")
    with pytest.raises(ValueError, match="no peak figures"):
        perfmodel.peak_for("", "")


def test_flop_and_byte_models_match_bench_formulas(tiny_model_module):
    """The shared-model contract: perfmodel's formulas ARE bench
    `_detail`'s (2·P + 4·S·L·heads·head_dim per token; weights + KV read
    per decode step) — recomputed here from first principles so neither
    side can drift."""
    cfg, _ = tiny_model_module
    p = cfg.num_params
    attn = 4 * cfg.num_layers * cfg.num_heads * cfg.head_dim
    assert perfmodel.flops_per_token(cfg, 100) == 2 * p + attn * 100
    assert perfmodel.prefill_flops(cfg, 8, 128) == \
        8 * 128 * (2 * p + attn * 64)
    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        cache_bytes,
    )

    assert perfmodel.decode_step_bytes(cfg, 4, 100, 10 ** 6) == \
        10 ** 6 + cache_bytes(cfg, 4, 100, 2)


@pytest.mark.parametrize("kv_quant", [None, "int8"])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_perfmodel_fast_path_equals_module_functions(tiny_model_module,
                                                     kv_quant, layout):
    """The hot-path coefficients precomputed in PerfModel.__init__ must
    equal the module-level kv_bytes closed form bit for bit — across
    layouts, quants, and non-multiple-of-8 contexts."""
    cfg, _ = tiny_model_module
    pm = PerfModel(cfg, param_bytes=123456, kv_itemsize=2,
                   kv_quant=kv_quant, kv_layout=layout, page_size=16)
    for rows in (1, 3, 8):
        for ctx in (1, 7, 8, 17, 63, 64, 129):
            assert pm._kv_read_bytes(rows, ctx) == perfmodel.kv_bytes(
                cfg, rows, ctx, itemsize=2, kv_quant=kv_quant,
                kv_layout=layout, page_size=16,
            ), (rows, ctx)


def test_round_attribution_verdicts(tiny_model_module):
    """Prefill-shaped work (many tokens per weight pass) lands
    compute-bound; decode-shaped work (one token per weight pass at tiny
    batch) lands memory-bound — the BENCH_r03 asymmetry, reproduced by
    the analytic model alone."""
    cfg, _ = tiny_model_module
    # param_bytes consistent with the config (bf16 weights): the
    # flops/bytes ratio is what decides the verdict, so the two must
    # describe the same model.
    pm = PerfModel(cfg, param_bytes=2 * cfg.num_params, device_kind="v5e")
    pre = pm.round_attribution("prefill", rows=8, tokens=512, ctx=256,
                               wall_s=0.01)
    dec = pm.round_attribution("decode", rows=1, tokens=1, ctx=256,
                               wall_s=0.01)
    assert pre["bound"] == "compute-bound"
    assert dec["bound"] == "memory-bound"
    assert pre["mfu"] > pre["hbm_util"]
    assert dec["hbm_util"] > dec["mfu"]
    # Degenerate wall: zeros, never a divide-by-zero.
    z = pm.round_attribution("decode", rows=1, tokens=1, ctx=8, wall_s=0.0)
    assert z["mfu"] == 0.0 and z["hbm_util"] == 0.0


def test_phase_work_draft_and_errors(tiny_model_module):
    cfg, _ = tiny_model_module
    pm = PerfModel(cfg, param_bytes=1000)
    flops, hbm = pm.phase_work("draft", rows=4, tokens=3, ctx=64)
    assert flops == 0.0
    assert hbm == perfmodel.draft_bytes(cfg, 4, 3, 64)
    with pytest.raises(ValueError):
        pm.phase_work("warp", rows=1, tokens=1, ctx=1)


def test_observe_folds_phase_ewmas(tiny_model_module):
    cfg, _ = tiny_model_module
    pm = PerfModel(cfg, param_bytes=1000)
    for _ in range(5):
        pm.observe("decode", rows=2, tokens=4, ctx=32, wall_s=0.001)
    st = pm.stats()
    assert st["phases"]["decode"]["rounds"] == 5
    assert st["phases"]["decode"]["bound"] in ("compute-bound",
                                               "memory-bound")
    assert st["peak_tflops"] > 0 and st["peak_hbm_gbs"] > 0
    # Identical inputs -> the EWMA equals any single attribution.
    one = pm.round_attribution("decode", rows=2, tokens=4, ctx=32,
                               wall_s=0.001)
    assert st["phases"]["decode"]["mfu"] == pytest.approx(one["mfu"],
                                                          rel=1e-6)


# ------------------------------------------------- live ledger reconciles


def test_scheduler_ledger_reconciles_with_analytic_model(tiny_model_module):
    """ISSUE-12 acceptance: every flight record's mfu/hbm_util/bound
    recomputes EXACTLY through utils/perfmodel.round_attribution from
    the record's own fields (phase, perf_ctx, round_wall_s) — the ledger
    is the analytic model evaluated live, not a second implementation."""
    cfg, params = tiny_model_module
    prompts = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]
    sched = make_sched(cfg, params)
    with sched:
        sched.generate(prompts, max_new_tokens=6)
    # Read AFTER shutdown: the loop can harvest overshoot rounds between
    # the futures resolving and teardown, and the record/EWMA views must
    # be compared at the same quiesced instant.
    recs = [r for r in sched.flight.snapshot() if "mfu" in r]
    pm = sched.perf
    assert recs, "no ledger columns on flight records"
    for rec in recs:
        tokens = (sched.decode_chunk if rec["phase"] == "decode"
                  else sched._spec_draft + 1)
        att = pm.round_attribution(
            rec["phase"], rows=sched.num_slots, tokens=tokens,
            ctx=rec["perf_ctx"], wall_s=rec["round_wall_s"],
        )
        assert rec["mfu"] == att["mfu"], rec
        assert rec["hbm_util"] == att["hbm_util"], rec
        assert rec["bound"] == att["bound"], rec
    # The per-phase EWMA view is live and replica-labeled.
    st = sched.perf_stats
    assert st["replica"] == "r0"
    assert st["phases"]["decode"]["rounds"] == len(
        [r for r in recs if r["phase"] == "decode"]
    )
    # Prefill chunks were dispatched, so the prefill phase ledgered too.
    assert "prefill" in st["phases"]


def test_scheduler_ledger_spec_rounds_are_verify_phase(tiny_model_module):
    cfg, params = tiny_model_module
    with make_sched(cfg, params, speculative_draft=2) as sched:
        sched.generate([[1, 5, 9, 2], [1, 7, 3]], max_new_tokens=6)
        recs = [r for r in sched.flight.snapshot() if "mfu" in r]
        st = sched.perf_stats
    assert recs and all(r["phase"] == "verify" for r in recs)
    # Draft gathers ledger beside the verify forwards.
    assert "draft" in st["phases"] and "verify" in st["phases"]


# ------------------------------------------------- on-demand device profile


def test_profile_capture_bounded_rounds(tiny_model_module, tmp_path):
    """/debug/profile's scheduler seam: arm → capture N rounds → a
    non-empty Perfetto-loadable artifact, with the fleet-wide guard held
    for exactly the capture's lifetime (a second arm is refused, and the
    guard releases on finish)."""
    cfg, params = tiny_model_module
    with make_sched(cfg, params) as sched:
        sched.generate([[1, 5, 9]], max_new_tokens=2)  # warm compiles
        out = sched.profile_rounds(2, out_dir=str(tmp_path))
        assert out["state"] == "armed" and out["rounds"] == 2
        assert traceprof.capture_owner() is not None
        with pytest.raises(RuntimeError):
            sched.profile_rounds(2, out_dir=str(tmp_path))
        sched.generate([[1, 5, 9], [1, 7]], max_new_tokens=8)
        deadline = time.time() + 60
        last = None
        while time.time() < deadline:
            st = sched.profile_status()
            last = st.get("last")
            if last and last.get("state") in ("done", "error"):
                break
            time.sleep(0.05)
        assert last is not None and last["state"] == "done", st
        assert last["artifacts"] and last["artifact_bytes"] > 0
        assert traceprof.capture_owner() is None  # guard released
        # The artifact parses in the same reader Perfetto loads.
        tr = traceprof.Trace().load_dir(str(last["dir"]))
        assert tr.op_time_s() > 0.0
        # The capture landed as flight-recorder lifecycle events.
        kinds = {r.get("kind") for r in sched.flight.snapshot()}
        assert {"profile_start", "profile_done"} <= kinds


def test_profile_abort_on_shutdown_releases_guard(tiny_model_module,
                                                  tmp_path):
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params).start()
    sched.profile_rounds(1000, out_dir=str(tmp_path))  # will never finish
    sched.shutdown()
    assert traceprof.capture_owner() is None
    st = sched.profile_status()
    assert st["last"]["state"] in ("aborted", "done", "error")


# ---------------------------------------------- preempted/resumed spans


class _FakeTrace:
    def __init__(self):
        self.spans = []

    def add_span(self, name, t0, t1, **attrs):
        self.spans.append((name, t0, t1, attrs))


def test_flush_spans_emits_preempted_intervals():
    """ISSUE-12 satellite: a victim's trace tree carries one
    `sched.preempted` span per parked interval — closed intervals flag
    resumed=True, an interval still open at terminal time closes at
    `now` with resumed=False, so the Perfetto timeline explains the gap
    either way."""
    from concurrent.futures import Future

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        _Request,
    )

    req = _Request(ids=[1, 2], max_new=4, temperature=0.0, top_p=1.0,
                   top_k=0, seed=0, future=Future())
    req.trace = _FakeTrace()
    req.submitted_at, req.admitted_at, req.ready_at = 1.0, 2.0, 3.0
    req.preempted = 2
    req.parked = [[4.0, 5.5], [6.0, 0.0]]  # resumed once, then parked
    req.flush_spans(now=7.0)
    spans = {(n, t0, t1, a.get("resumed"))
             for n, t0, t1, a in req.trace.spans if n == "sched.preempted"}
    assert (("sched.preempted", 4.0, 5.5, True)) in spans
    assert (("sched.preempted", 6.0, 7.0, False)) in spans


@pytest.mark.chaos
def test_preempted_request_trace_has_parked_span(tiny_model_module):
    """End to end on a REAL paged scheduler: force a preemption storm
    (kv:pressure withholding an overcommitted pool — the proven
    test_paged_kv shape) with EVERY request traced, and assert each
    victim's exported span tree contains its parked interval."""
    from llm_based_apache_spark_optimization_tpu.utils.faults import FAULTS
    from llm_based_apache_spark_optimization_tpu.utils.tracing import (
        RequestTrace,
    )

    cfg, params = tiny_model_module
    prompts = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]
    sched = make_sched(
        cfg, params, num_slots=2, kv_page_size=8,
        kv_pages=9, kv_overcommit=0.25, max_seq=64, prompt_bucket=8,
    )
    traces = [RequestTrace(f"req-{i}") for i in range(len(prompts))]
    FAULTS.configure("kv:pressure:1:3", 0)
    try:
        with sched:
            futs = [
                sched.submit(p, max_new_tokens=24, trace=tr)
                for p, tr in zip(prompts, traces)
            ]
            for f in futs:
                f.result(timeout=300)
    finally:
        FAULTS.clear()
    stats = sched.page_stats
    assert stats["preemptions"] >= 1, stats
    preempt_rids = {r.get("rid") for r in sched.flight.snapshot()
                    if r.get("kind") == "preempt"}
    assert preempt_rids
    checked = 0
    for tr in traces:
        spans = tr.to_dict()["spans"]
        rids = {s.get("attrs", {}).get("rid") for s in spans}
        if rids & preempt_rids:
            checked += 1
            parked = [s for s in spans if s["name"] == "sched.preempted"]
            assert parked, f"victim trace missing parked span: {spans}"
            assert all(s["attrs"]["resumed"] for s in parked)
    assert checked >= 1  # every victim was traced, so at least one hit
