"""Disaggregated prefill/decode serving (ISSUE 13): phase roles, the
export→requeue→import KV-page handoff, the pool's phase-aware router,
and the chaos contracts.

All on the TINY config, CPU f32. The load-bearing property everywhere is
TOKEN IDENTITY: a phase-split fleet (prefill replica + decode replica,
with every request's KV migrating between pools as a host blob) must
produce exactly the outputs of a single mixed-replica control — greedy
trivially, sampled via the fold_in(key(seed), count) stream restore,
constrained via FSM replay, speculative via the history rebuild — and
`phase_role="mixed"` must reproduce the pre-disaggregation scheduler bit
for bit.
"""

import threading
import time

import numpy as np
import pytest

from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
    export_pages,
    handoff_bytes,
    import_pages,
    init_page_pool,
)
from llm_based_apache_spark_optimization_tpu.ops.sampling import SamplingParams
from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    SchedulerPool,
    parse_pool_phases,
)

PROMPTS = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]


@pytest.fixture(scope="module")
def tiny_model_module():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def make_sched(cfg, params, role="mixed", **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("stop_ids", (-1,))
    kw.setdefault("kv_page_size", 8)
    return ContinuousBatchingScheduler(cfg, params, phase_role=role, **kw)


# ------------------------------------------------------------ unit: roles


def test_parse_pool_phases():
    assert parse_pool_phases("", 3) == ["mixed"] * 3
    assert parse_pool_phases("prefill:1,decode:3", 4) == [
        "prefill", "decode", "decode", "decode"]
    assert parse_pool_phases("mixed:2", 2) == ["mixed", "mixed"]
    with pytest.raises(ValueError, match="describe 2"):
        parse_pool_phases("prefill:1,decode:1", 3)
    with pytest.raises(ValueError, match="phase role"):
        parse_pool_phases("prefil:1,decode:1", 2)
    with pytest.raises(ValueError, match="role:count"):
        parse_pool_phases("prefill", 1)
    with pytest.raises(ValueError, match="no decode/mixed"):
        parse_pool_phases("prefill:2", 2)


def test_phase_role_validation(tiny_model_module):
    cfg, params = tiny_model_module
    with pytest.raises(ValueError, match="phase_role"):
        ContinuousBatchingScheduler(cfg, params, phase_role="draft")
    # The removed layout is refused by name, whatever the role.
    with pytest.raises(ValueError, match="contiguous KV layout was removed"):
        ContinuousBatchingScheduler(cfg, params, phase_role="prefill",
                                    kv_layout="contiguous")
    # Every role builds on the one layout, with no layout argument.
    for role in ("mixed", "prefill", "decode"):
        ContinuousBatchingScheduler(cfg, params, phase_role=role)


# --------------------------------------------- wire format: export/import


def test_export_import_roundtrip_bf16_and_int8():
    """The handoff blob is a HOST COPY of the full cache tuple: int8
    scales serialize beside their values, import reproduces the page
    content exactly, and mutating the source after export cannot change
    the blob (copies, not references)."""
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY

    for quant in (None, "int8"):
        pool = init_page_pool(TINY, 6, 8, dtype=jnp.float32,
                              kv_quant=quant)
        keys = (("kp", "kps", "vp", "vps") if quant else ("kp", "vp"))
        cache = []
        for i, k in enumerate(keys):
            base = pool[k]
            fill = jnp.full(base.shape, i + 1, base.dtype)
            cache.append(fill)
        blob = export_pages(cache, [1, 3])
        assert len(blob) == len(keys)
        for arr in blob:
            assert isinstance(arr, np.ndarray)
            assert arr.shape[1] == 2  # two pages
        src_vals = [np.array(b, copy=True) for b in blob]
        # Mutate the source pool after export: the blob must not move.
        cache = [c.at[:, 1].set(0) for c in cache]
        for b, v in zip(blob, src_vals):
            np.testing.assert_array_equal(b, v)
        # Import into a DIFFERENT pool at different page ids: content
        # lands exactly (values AND scales under int8).
        dst = [jnp.zeros_like(c) if c.dtype != jnp.int8
               else jnp.zeros(c.shape, jnp.int8) for c in cache]
        out = import_pages(dst, [4, 0], blob)
        for o, b in zip(out, blob):
            got = np.asarray(o[:, [4, 0]])
            np.testing.assert_array_equal(got, b)
        assert handoff_bytes(blob) == sum(b.nbytes for b in blob)


def test_handoff_allocator_invariants_and_cow_prefix(tiny_model_module):
    """Scheduler-level wire-format property test: a phase-split pool
    serving SHARED-PREFIX traffic (the source replica's prefix cache
    shares pages by refcount) must keep BOTH allocators' free-list/
    refcount partitions intact, release every migrated request's pages
    on both sides, and export COW-shared prefix pages as copies — the
    decode side's imported content must survive the source retiring,
    evicting and reusing every page it shared."""
    cfg, params = tiny_model_module
    shared = [1, 5, 9, 2, 6, 3, 7, 4]  # one full page of shared prefix
    prompts = [shared + [10 + i] for i in range(4)]
    with make_sched(cfg, params) as ctl:
        golden = [ctl.generate([p], max_new_tokens=5)[0] for p in prompts]
    pre = make_sched(cfg, params, role="prefill")
    dec = make_sched(cfg, params, role="decode")
    pool = SchedulerPool([pre, dec])
    with pool:
        outs = [
            f.result(timeout=120)
            for f in [pool.submit(p, max_new_tokens=5) for p in prompts]
        ]
    assert outs == golden
    # Zero-copy sharing actually happened on the source (the prefix
    # cache published + hit pages by refcount before each export).
    assert pre._page_alloc.shares > 0
    for sched in (pre, dec):
        sched._page_alloc.check()  # partition invariant on both pools
        # Every slot's pages released; only prefix-cache entries (on the
        # source) may still hold references.
        assert all(not pages for pages in sched._slot_pages)
    held = sum(len(v) for v in pre._prefix_pages.values())
    assert pre._page_alloc.pages_in_use <= held
    assert dec._page_alloc.pages_in_use == 0  # importer freed everything
    hs = pool.handoff_stats
    per = {r["replica"]: r for r in hs["replicas"]}
    assert per["r0"]["exports"] == 4 and per["r1"]["imports"] == 4
    assert per["r0"]["pages_out"] == per["r1"]["pages_in"] > 0
    assert per["r0"]["bytes_out"] == per["r1"]["bytes_in"] > 0


def test_export_import_int8_scales_preserved(tiny_model_module):
    """An int8 phase-split pool hands off quantized pages + their f32
    scales; outputs must equal the int8 mixed control exactly (same
    quantize-once math, content-exact restore)."""
    cfg, params = tiny_model_module
    kw = dict(kv_quant="int8")
    with make_sched(cfg, params, **kw) as ctl:
        golden = [ctl.generate([p], max_new_tokens=5)[0] for p in PROMPTS]
    pool = SchedulerPool([make_sched(cfg, params, role="prefill", **kw),
                          make_sched(cfg, params, role="decode", **kw)])
    with pool:
        outs = [
            f.result(timeout=120)
            for f in [pool.submit(p, max_new_tokens=5) for p in PROMPTS]
        ]
    assert outs == golden
    hs = pool.handoff_stats
    assert {r["replica"]: r["imports"] for r in hs["replicas"]}["r1"] == 4


# --------------------------------------------------- parity + bit-for-bit


def test_mixed_role_default_reproduces_today_bitforbit(tiny_model_module):
    """phase_role="mixed" (the default) must be today's scheduler bit
    for bit: identical outputs, identical page accounting, no handoff
    state touched, no handoff events or columns in the flight ring."""
    import time as _t

    def drained_stats(s):
        # Page release at retire runs a harvest-beat behind the futures
        # resolving: wait for the pool to drain before snapshotting, or
        # a busy host catches one side mid-retire (flaky inequality).
        deadline = _t.monotonic() + 5.0
        while s.page_stats["pages_in_use"] and _t.monotonic() < deadline:
            _t.sleep(0.01)
        return dict(s.page_stats)

    cfg, params = tiny_model_module
    with make_sched(cfg, params) as a:
        out_a = a.generate(PROMPTS, max_new_tokens=6)
        stats_a = drained_stats(a)
        snap_a = a.flight.snapshot()
    with make_sched(cfg, params, role="mixed") as b:
        out_b = b.generate(PROMPTS, max_new_tokens=6)
        stats_b = drained_stats(b)
        snap_b = b.flight.snapshot()
        assert b.handoff_stats is None
    assert out_a == out_b
    assert stats_a == stats_b
    strip = ("ts", "round_wall_s", "cadence_s", "mfu", "hbm_util",
             "bound", "prefill_mfu", "prefill_hbm_util", "perf_ctx",
             "host_s", "harvest_wait_s", "idle_s")  # times, like the first

    def core(snap):
        return [{k: v for k, v in r.items() if k not in strip}
                for r in snap]

    assert core(snap_a) == core(snap_b)
    for rec in snap_b:
        assert "handoffs" not in rec and "pages_migrated" not in rec
        assert rec.get("kind", "") not in ("handoff_export",
                                           "handoff_import",
                                           "handoff_inplace")


def test_phase_split_parity_greedy_sampled_constrained(tiny_model_module):
    """The acceptance contract: a phase-split fleet's outputs equal a
    single mixed-replica control token for token across greedy, sampled
    and grammar-constrained traffic."""
    from llm_based_apache_spark_optimization_tpu.constrain import (
        get_constraint,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    cfg, params = tiny_model_module
    tok = ByteTokenizer()
    cm = get_constraint("spark_sql", tok, (2,))
    budget = max(16, cm.min_new_tokens)
    reqs = [
        ([1, 5, 9], SamplingParams(), None, 6),
        ([1, 7, 11], SamplingParams(temperature=0.8, top_p=0.9), None, 6),
        (tok.encode("SELECT", add_bos=True), SamplingParams(), cm, budget),
        ([1, 3, 4, 8], SamplingParams(temperature=0.5, top_k=8), None, 6),
    ]
    kw = dict(stop_ids=(2,), max_seq=96)
    with make_sched(cfg, params, **kw) as ctl:
        golden = [
            ctl.submit(ids, max_new_tokens=mn, sampling=sp, seed=40 + i,
                       constraint=c).result(timeout=120)
            for i, (ids, sp, c, mn) in enumerate(reqs)
        ]
    pool = SchedulerPool([make_sched(cfg, params, role="prefill", **kw),
                          make_sched(cfg, params, role="decode", **kw)])
    with pool:
        futs = [
            pool.submit(ids, max_new_tokens=mn, sampling=sp, seed=40 + i,
                        constraint=c)
            for i, (ids, sp, c, mn) in enumerate(reqs)
        ]
        outs = [f.result(timeout=120) for f in futs]
    assert outs == golden
    hs = pool.handoff_stats
    assert sum(r["exports"] for r in hs["replicas"]) == len(reqs)


@pytest.mark.slow
def test_phase_split_parity_speculative(tiny_model_module):
    """Speculative traffic (greedy + sampled) across the handoff: the
    importing replica rebuilds the draft history row from the committed
    prefix and restores the RNG stream index, so the split fleet's
    spec-decode emits exactly the mixed control's tokens."""
    cfg, params = tiny_model_module
    kw = dict(speculative_draft=2)
    reqs = [([1, 5, 9, 5, 9], SamplingParams(temperature=0.9, top_k=8), 11),
            ([1, 6, 2, 6, 2], SamplingParams(), 0),
            ([1, 7, 3, 7, 3], SamplingParams(temperature=0.7), 12)]
    with make_sched(cfg, params, **kw) as ctl:
        golden = [
            ctl.submit(ids, max_new_tokens=6, sampling=sp,
                       seed=sd).result(timeout=120)
            for ids, sp, sd in reqs
        ]
    pool = SchedulerPool([make_sched(cfg, params, role="prefill", **kw),
                          make_sched(cfg, params, role="decode", **kw)])
    with pool:
        futs = [pool.submit(ids, max_new_tokens=6, sampling=sp, seed=sd)
                for ids, sp, sd in reqs]
        outs = [f.result(timeout=120) for f in futs]
    assert outs == golden
    assert sum(r["imports"] for r in
               pool.handoff_stats["replicas"]) == len(reqs)


def test_lone_prefill_replica_decodes_in_place(tiny_model_module):
    """The fallback rule: a prefill-role scheduler with no handoff
    consumer (no pool) decodes in place, token-identical, and counts the
    fallback."""
    cfg, params = tiny_model_module
    with make_sched(cfg, params) as ctl:
        golden = [ctl.generate([p], max_new_tokens=6)[0] for p in PROMPTS]
    with make_sched(cfg, params, role="prefill") as lone:
        outs = [lone.submit(p, max_new_tokens=6).result(timeout=60)
                for p in PROMPTS]
        hs = lone.handoff_stats
    assert outs == golden
    assert hs["inplace_fallbacks"] == len(PROMPTS)
    assert hs["exports"] == 0


def test_streaming_and_ttft_across_handoff(tiny_model_module):
    """Streaming spans the handoff: the first token arrives from the
    prefill replica at pack time, the rest from the decode replica, in
    order, no duplicates — byte-identical to the control stream."""
    cfg, params = tiny_model_module
    with make_sched(cfg, params) as ctl:
        golden = ctl.generate([PROMPTS[0]], max_new_tokens=6)[0]
    pool = SchedulerPool([make_sched(cfg, params, role="prefill"),
                          make_sched(cfg, params, role="decode")])
    streamed = []
    with pool:
        fut = pool.submit(PROMPTS[0], max_new_tokens=6,
                          on_token=streamed.append)
        out = fut.result(timeout=120)
    assert out == golden
    assert streamed == golden


# ------------------------------------------------------- observability


def test_handoff_observability_span_columns_stats(tiny_model_module):
    """Satellite: the sched.handoff trace span (export wall, pages,
    bytes, wait-for-decode-slot) explains the between-legs gap; the
    decode replica's flight records carry pages_migrated/handoff_wait_s;
    lifecycle events land on both recorders."""
    from llm_based_apache_spark_optimization_tpu.utils.tracing import (
        RequestTrace,
    )

    cfg, params = tiny_model_module
    pre = make_sched(cfg, params, role="prefill")
    dec = make_sched(cfg, params, role="decode")
    pool = SchedulerPool([pre, dec])
    tr = RequestTrace("req-handoff")
    with pool:
        out = pool.submit(PROMPTS[2], max_new_tokens=5,
                          trace=tr).result(timeout=120)
    assert out
    spans = {s["name"]: s for s in tr.to_dict()["spans"]}
    ho = spans["sched.handoff"]
    assert ho["attrs"]["pages"] >= 1
    assert ho["attrs"]["bytes"] > 0
    assert ho["attrs"]["wait_s"] >= 0.0
    assert ho["attrs"]["src"] == "r0"
    assert "sched.handoff_export" in spans
    kinds = [r.get("kind") for r in pool.flight_snapshot()]
    assert "handoff_export" in kinds and "handoff_import" in kinds
    assert "handoff_place" in kinds  # the pool's placement decision
    mig = [r for r in dec.flight.snapshot() if "pages_migrated" in r]
    assert mig and mig[0]["pages_migrated"] >= 1
    assert mig[0]["handoff_wait_s"] >= 0.0
    # Prefill-role replicas record their own pack rounds.
    packs = [r for r in pre.flight.snapshot() if r.get("handoffs")]
    assert packs and packs[-1]["phase"] == "prefill"


def test_replica_loads_and_health_carry_phase_role(tiny_model_module):
    cfg, params = tiny_model_module
    pool = SchedulerPool([make_sched(cfg, params, role="prefill"),
                          make_sched(cfg, params, role="decode")])
    with pool:
        pool.submit(PROMPTS[0], max_new_tokens=4).result(timeout=120)
        loads = {r["replica"]: r for r in pool.replica_loads()}
        health = {r["replica"]: r for r in pool.replica_health()}
    assert loads["r0"]["phase_role"] == "prefill"
    assert loads["r1"]["phase_role"] == "decode"
    assert loads["r0"]["handoff_exports"] == 1
    assert loads["r1"]["handoff_imports"] == 1
    assert health["r0"]["phase_role"] == "prefill"


# ------------------------------------------------- router + placement


class _FakeTarget:
    """Requeue-capable fake with a scripted score/role for placement
    unit tests."""

    def __init__(self, role="decode", secs=0.0, hbm=0.0, reject=False):
        self.phase_role = role
        self.secs = secs
        self.hbm = hbm
        self.reject = reject
        self.taken = []
        self._crash = None

    def start(self):
        return self

    def shutdown(self, timeout=None):
        pass

    def backlog_score(self):
        return self.secs, 0

    @property
    def perf_stats(self):
        return {"phases": {"decode": {"hbm_util": self.hbm}}}

    def requeue(self, req):
        if self.reject:
            raise ValueError("incompatible")
        self.taken.append(req)

    def submit(self, ids, **kw):
        from concurrent.futures import Future

        f = Future()
        f.set_result(list(ids))
        return f


class _FakeReq:
    def __init__(self):
        from concurrent.futures import Future

        self.deadline = None
        self.future = Future()
        self.rid = 1
        self.handoff = {"pages": 2}


def test_place_handoff_prefers_low_pressure_decode_replica():
    src = _FakeTarget(role="prefill")
    hot = _FakeTarget(role="decode", hbm=0.9)
    cool = _FakeTarget(role="decode", hbm=0.2)
    mixed = _FakeTarget(role="mixed")
    pool = SchedulerPool([src, hot, cool, mixed])
    req = _FakeReq()
    pool._place_handoff(req, 0)
    assert cool.taken and not hot.taken and not mixed.taken


def test_place_handoff_falls_back_to_mixed_then_source():
    src = _FakeTarget(role="prefill")
    bad = _FakeTarget(role="decode", reject=True)
    mixed = _FakeTarget(role="mixed")
    pool = SchedulerPool([src, bad, mixed])
    req = _FakeReq()
    pool._place_handoff(req, 0)
    assert mixed.taken and not bad.taken
    # Every sibling refuses: the source takes it back (decode in place).
    src2, bad2 = _FakeTarget(role="prefill"), _FakeTarget(role="decode",
                                                          reject=True)
    pool2 = SchedulerPool([src2, bad2])
    req2 = _FakeReq()
    pool2._place_handoff(req2, 0)
    assert src2.taken


def test_deadline_spills_over_to_idle_decode_replicas():
    """A deadline the prefill/mixed tier cannot meet must not shed 504
    while an idle decode-role replica (full capability) can serve inside
    the budget — the phase filter yields to feasibility."""
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        DeadlineExceeded,
    )

    backed_up = _FakeTarget(role="prefill", secs=30.0)
    idle_dec = _FakeTarget(role="decode", secs=0.1)
    pool = SchedulerPool([backed_up, idle_dec])
    fut = pool.submit([1, 2], deadline_s=1.0)
    assert fut.result() == [1, 2]
    assert fut._lsot_replica == "r1"  # served by the decode spillover
    # Every tier infeasible: the typed 504 still fires.
    idle_dec.secs = 40.0
    with pytest.raises(DeadlineExceeded, match="no replica can serve"):
        pool.submit([3], deadline_s=1.0)


def test_new_requests_avoid_decode_role_replicas():
    pre = _FakeTarget(role="prefill")
    dec = _FakeTarget(role="decode", secs=0.0)
    pool = SchedulerPool([dec, pre])  # decode is index 0 AND least loaded
    fut = pool.submit([1, 2, 3])
    assert fut.result() == [1, 2, 3]
    assert fut._lsot_replica == "r1"  # placed on the prefill replica
    # With ONLY decode replicas placeable, they still serve (roles are
    # routing policy, not capability — never shed on role alone).
    pool2 = SchedulerPool([_FakeTarget(role="decode")])
    assert pool2.submit([4]).result() == [4]


@pytest.mark.chaos
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_decode_side_pressure_storm_token_identical(tiny_model_module):
    """Acceptance: a decode-side pressure storm (kv:pressure withholding
    the importing pool's pages) forces imports through _page_wait /
    preemption — and every request still completes token-identical to
    the mixed control, zero lost."""
    from llm_based_apache_spark_optimization_tpu.utils.faults import FAULTS

    cfg, params = tiny_model_module
    reqs = [([1, 5, 9], SamplingParams(), 30),
            ([1, 7, 11], SamplingParams(temperature=0.8), 31),
            ([1, 3, 4, 8], SamplingParams(), 32)]
    kw = dict(max_seq=96)
    with make_sched(cfg, params, **kw) as ctl:
        golden = [
            ctl.submit(ids, max_new_tokens=8, sampling=sp,
                       seed=sd).result(timeout=120)
            for ids, sp, sd in reqs
        ]
    # Decode pool at the one-max-request floor + overcommitted: withheld
    # pages make import allocations/top-ups fail (page_wait/preempt);
    # the prefill pool is big enough that the same withhold is harmless.
    pre = make_sched(cfg, params, role="prefill", **kw)
    dec = make_sched(cfg, params, role="decode", kv_pages=14,
                     kv_overcommit=0.25, **kw)
    pool = SchedulerPool([pre, dec])
    # Withhold 9 of the decode pool's 14 pages: 5 grantable, each import
    # needs 3 — concurrent imports are forced through _page_wait while
    # the prefill pool (24 pages) shrugs the same withhold off.
    FAULTS.configure("kv:pressure:1:9", seed=0)
    try:
        with pool:
            futs = [
                pool.submit(ids, max_new_tokens=8, sampling=sp, seed=sd)
                for ids, sp, sd in reqs
            ]
            outs = [f.result(timeout=300) for f in futs]
            stats = dict(dec.page_stats)
    finally:
        FAULTS.clear()
    assert outs == golden
    assert stats["preemptions"] > 0 or stats["page_waits"] > 0, (
        "the storm pressured nothing — the test proved nothing"
    )


@pytest.mark.chaos
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_prefill_crash_mid_handoff_zero_lost():
    """Acceptance + evalh wiring: the chaos stage drives a supervised
    phase-split fleet through a clean wave (≥1 real handoff) and a
    `sched:handoff` crash wave (prefill replica dies mid-handoff; only
    it restarts; journal re-places onto the decode sibling) — zero
    lost, token-identical to the mixed control."""
    from llm_based_apache_spark_optimization_tpu.evalh.chaos import (
        _run_disagg_stage,
    )

    report = _run_disagg_stage(0)
    assert report["lost"] == 0
    assert report["mismatched"] == 0
    assert report["handoffs"] >= 1
    assert report["crashes_injected"] >= 1
    assert report["prefill_restarts"] >= 1
    assert report["decode_restarts"] == 0


def test_drain_prefill_replica_preserves_handoffs(tiny_model_module):
    """A drained prefill replica's queued work (including anything
    parked in its handoff queue) re-places onto siblings — acknowledged
    work never sheds across a drain."""
    cfg, params = tiny_model_module
    with make_sched(cfg, params) as ctl:
        golden = [ctl.generate([p], max_new_tokens=5)[0] for p in PROMPTS]
    pre = make_sched(cfg, params, role="prefill")
    mixed = make_sched(cfg, params, role="mixed")
    pool = SchedulerPool([pre, mixed],
                         factory=lambda i: make_sched(
                             cfg, params,
                             role=["prefill", "mixed"][i]))
    with pool:
        futs = [pool.submit(p, max_new_tokens=5) for p in PROMPTS]
        pool.drain_replica("r0", deadline_s=30.0)
        outs = [f.result(timeout=120) for f in futs]
    assert outs == golden
