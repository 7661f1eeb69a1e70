"""Continuous-batching scheduler: parity with the one-shot engine, slot
reuse, concurrency, mixed sampling, and the SchedulerBackend seam.

All on the TINY config, CPU f32 (conftest.py forces the 8-virtual-device CPU
platform). Greedy decode is deterministic, so the scheduler's outputs must
equal InferenceEngine.generate()'s token-for-token regardless of batching.
"""

import threading

import pytest

from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
from llm_based_apache_spark_optimization_tpu.ops.sampling import SamplingParams
from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    SchedulerBackend,
)


PROMPTS = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]


@pytest.fixture(scope="module")
def tiny_model_module():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def make_sched(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("stop_ids", (-1,))  # random weights: don't stop early
    return ContinuousBatchingScheduler(cfg, params, **kw)


def engine_golden(cfg, params, prompts, max_new, stop_ids=(-1,)):
    eng = InferenceEngine(cfg, params, stop_ids=stop_ids, prompt_bucket=8)
    # One engine call per prompt: each sequence's greedy trajectory must not
    # depend on what else is in the batch.
    return [eng.generate([p], max_new_tokens=max_new)[0] for p in prompts]


def test_greedy_parity_with_engine(tiny_model_module):
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS, max_new=6)
    with make_sched(cfg, params) as sched:
        out = sched.generate(PROMPTS, max_new_tokens=6)
    assert out == golden


def test_slot_reuse_more_requests_than_slots(tiny_model_module):
    cfg, params = tiny_model_module
    prompts = PROMPTS * 3  # 12 requests through 2 slots
    golden = engine_golden(cfg, params, prompts, max_new=5)
    with make_sched(cfg, params) as sched:
        futs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        out = [f.result(timeout=120) for f in futs]
    assert out == golden


@pytest.mark.slow
def test_concurrent_submitters(tiny_model_module):
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS, max_new=5)
    results = {}
    with make_sched(cfg, params, num_slots=3) as sched:
        def worker(i):
            results[i] = sched.generate([PROMPTS[i]], max_new_tokens=5)[0]

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert [results[i] for i in range(len(PROMPTS))] == golden


@pytest.mark.slow
def test_stop_token_frees_slot(tiny_model_module):
    """Force a stop id that random weights hit, and check completions end there."""
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS, max_new=8, stop_ids=(-1,))
    stop = golden[0][2]  # third greedy token of prompt 0 becomes the stop id
    golden_stop = engine_golden(cfg, params, PROMPTS, max_new=8, stop_ids=(stop,))
    with make_sched(cfg, params, stop_ids=(stop,)) as sched:
        out = sched.generate(PROMPTS, max_new_tokens=8)
    # Engine includes the stop token in its output; scheduler strips it.
    stripped = [o[:-1] if o and o[-1] == stop else o for o in golden_stop]
    assert out == stripped


def test_mixed_sampling_batch(tiny_model_module):
    """Greedy and sampled requests share one batch; greedy rows stay exact."""
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, [PROMPTS[0]], max_new=6)
    with make_sched(cfg, params) as sched:
        f_greedy = sched.submit(PROMPTS[0], max_new_tokens=6)
        f_sampled = sched.submit(
            PROMPTS[1], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.9, top_p=0.9),
        )
        greedy_out = f_greedy.result(timeout=120)
        sampled_out = f_sampled.result(timeout=120)
    assert greedy_out == golden[0]
    assert 0 < len(sampled_out) <= 6
    assert all(0 <= t < cfg.vocab_size for t in sampled_out)


def test_budget_respected(tiny_model_module):
    cfg, params = tiny_model_module
    with make_sched(cfg, params) as sched:
        out = sched.generate(PROMPTS[:2], max_new_tokens=3)
    assert all(len(o) == 3 for o in out)


def test_submit_rejects_oversize(tiny_model_module):
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params)
    with pytest.raises(ValueError, match="exceeds scheduler max_seq"):
        sched.submit([1] * 8, max_new_tokens=cfg.max_seq_len)


def test_top_k_sampling_supported(tiny_model_module):
    """Runtime top-k (shape-static dynamic-gather cutoff): tokens come from
    the k most likely ids at every step. k=1 must equal greedy."""
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS[:1], max_new=6)
    with make_sched(cfg, params) as sched:
        out_k1 = sched.generate(
            PROMPTS[:1], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.8, top_k=1),
        )
        out_k5 = sched.generate(
            PROMPTS[:1], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.8, top_k=5),
        )
    assert out_k1 == golden  # top-1 == argmax regardless of temperature
    assert all(0 <= t < cfg.vocab_size for t in out_k5[0])


@pytest.mark.slow
def test_seed_reproducible_across_batch_composition(tiny_model_module):
    """A sampled request must reproduce its tokens for the same seed no
    matter what other traffic shares the batch, and differ across seeds."""
    cfg, params = tiny_model_module
    sp = SamplingParams(temperature=0.9, top_p=0.9)
    with make_sched(cfg, params, num_slots=3) as sched:
        # Run 1: alone.
        alone = sched.submit(PROMPTS[0], max_new_tokens=6, sampling=sp,
                             seed=123).result()
        # Run 2: same request sharing the batch with two other requests.
        others = [
            sched.submit(p, max_new_tokens=6, sampling=sp, seed=7 + i)
            for i, p in enumerate(PROMPTS[1:3])
        ]
        crowded = sched.submit(PROMPTS[0], max_new_tokens=6, sampling=sp,
                               seed=123).result()
        [f.result() for f in others]
        # Run 3: different seed.
        other_seed = sched.submit(PROMPTS[0], max_new_tokens=6, sampling=sp,
                                  seed=999).result()
    assert alone == crowded
    assert alone != other_seed  # overwhelmingly, in 6 tokens at T=0.9


@pytest.mark.slow
def test_multibucket_prefill(tiny_model_module):
    """Short prompts use a small prefill bucket; a long prompt still streams
    through chunked prefill — outputs stay engine-exact either way."""
    cfg, params = tiny_model_module
    long_prompt = [1] + list(range(3, 40))  # 38 tokens; prompt_bucket=16
    prompts = [PROMPTS[0], long_prompt]
    golden = engine_golden(cfg, params, prompts, max_new=5)
    with make_sched(cfg, params, prompt_bucket=16, max_seq=64) as sched:
        out = sched.generate(prompts, max_new_tokens=5)
        assert out == golden
        # Compiled prefill variants are keyed (bucket, k-bucket): buckets
        # come from the bucket table, k from the power-of-two batch widths.
        assert all(
            t in sched._buckets and kb in sched._kbuckets
            for t, kb in sched._prefill_fns
        )


@pytest.mark.slow
def test_scheduler_pool_round_robin(tiny_model_module):
    """SchedulerPool (the dp>1 story): replicas serve engine-exact greedy."""
    from llm_based_apache_spark_optimization_tpu.serve import SchedulerPool

    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS, max_new=4)
    pool = SchedulerPool([make_sched(cfg, params), make_sched(cfg, params)])
    with pool:
        out = pool.generate(PROMPTS, max_new_tokens=4)
    assert out == golden


def test_scheduler_backend_seam(tiny_model_module):
    """SchedulerBackend plugs into GenerationService like EngineBackend."""
    cfg, params = tiny_model_module
    from llm_based_apache_spark_optimization_tpu.serve import GenerationService
    from llm_based_apache_spark_optimization_tpu.tokenizer.byte import ByteTokenizer

    tok = ByteTokenizer(bos_id=cfg.bos_id, eos_id=cfg.eos_id, pad_id=cfg.pad_id)
    sched = make_sched(cfg, params, num_slots=2)
    backend = SchedulerBackend(sched, tok, max_new_tokens=4)
    svc = GenerationService()
    svc.register("duckdb-nsql", backend, template="completion")
    try:
        res = svc.generate("duckdb-nsql", prompt="SELECT", system="schema")
        assert res.output_tokens == 4
        assert isinstance(res.response, str)
    finally:
        sched.shutdown()


@pytest.mark.slow
def test_tp_sharded_scheduler(tiny_model_module):
    """TP over the virtual CPU mesh: outputs match the unsharded golden."""
    import jax

    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    cfg, params = tiny_model_module
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=5)
    with make_sched(cfg, params, mesh=mesh) as sched:
        out = sched.generate(PROMPTS[:2], max_new_tokens=5)
    assert out == golden

    dp_mesh = make_mesh(dp=2, tp=1, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dp=1"):
        ContinuousBatchingScheduler(cfg, params, mesh=dp_mesh)


@pytest.mark.slow
def test_tp_sharded_scheduler_pallas(tiny_model_module):
    """TP mesh + flash kernel (the BASELINE 4/5 serving stack): the scheduler
    must route its forward() calls through the shard_map pallas wrapper and
    still match the unsharded einsum golden token-for-token."""
    import jax

    from llm_based_apache_spark_optimization_tpu.ops.pallas import set_attention_impl
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    cfg, params = tiny_model_module
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=5)
    try:
        set_attention_impl("pallas")
        with make_sched(cfg, params, mesh=mesh) as sched:
            out = sched.generate(PROMPTS[:2], max_new_tokens=5)
    finally:
        set_attention_impl("auto")
    assert out == golden


@pytest.mark.slow
def test_scheduler_pool_skips_crashed_replica(tiny_model_module):
    """A crashed replica must not keep eating its round-robin share."""
    from llm_based_apache_spark_optimization_tpu.serve import SchedulerPool

    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=4)
    pool = SchedulerPool([make_sched(cfg, params), make_sched(cfg, params)])
    with pool:
        dead = pool.schedulers[0]
        dead._crash = RuntimeError("simulated device loss")  # as _run would
        out = pool.generate(PROMPTS[:2], max_new_tokens=4)
        assert out == golden  # both served by the healthy replica
        pool.schedulers[1]._crash = RuntimeError("second loss")
        with pytest.raises(RuntimeError, match="all scheduler replicas"):
            pool.submit(PROMPTS[0])
        for s in pool.schedulers:
            s._crash = None  # let shutdown() join cleanly


@pytest.mark.slow
def test_prefix_cache_parity_and_hits(tiny_model_module):
    """Requests sharing a schema-style prefix reuse cached K/V blocks
    (skipping that prefill work) and still match the engine token-for-token."""
    cfg, params = tiny_model_module
    shared = list(range(3, 27))  # 24-token shared "schema" prefix
    prompts = [[1] + shared + [50 + i] for i in range(4)]  # 26 tokens each
    golden = engine_golden(cfg, params, prompts, max_new=5)
    with make_sched(cfg, params, max_seq=64) as sched:  # pblock = bucket = 8
        # Sequential warm-up (concurrent admissions would race the publish):
        # prompt 1 records the prefix content, prompt 2 publishes its blocks.
        first = sched.generate(prompts[:1], max_new_tokens=5)
        second = sched.generate(prompts[1:2], max_new_tokens=5)
        # Prompts 3-4 (concurrent) both restore the 3 shared blocks.
        rest = sched.generate(prompts[2:], max_new_tokens=5)
    assert first + second + rest == golden
    stats = sched.prefix_stats
    # Publish gate: prompt 1 records the prefix content, prompt 2 publishes
    # its blocks, prompts 3-4 reuse the 3 complete shared blocks each (the
    # gate keeps one-off prompts from paying slice work for blocks nothing
    # will ever reuse).
    assert stats["hits"] >= 2
    assert stats["blocks_reused"] >= 6
    assert stats["cached_blocks"] > 0


@pytest.mark.slow
def test_prefix_cache_lru_capacity(tiny_model_module):
    cfg, params = tiny_model_module
    prompts = [[1] + list(range(3 + 30 * i, 3 + 30 * i + 30)) for i in range(3)]
    golden = engine_golden(cfg, params, prompts, max_new=4)
    with make_sched(cfg, params, max_seq=64,
                    prefix_cache_blocks=2) as sched:
        out = sched.generate(prompts, max_new_tokens=4)
    assert out == golden
    assert sched.prefix_stats["cached_blocks"] <= 2


@pytest.mark.slow
def test_prefix_cache_disabled(tiny_model_module):
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=4)
    with make_sched(cfg, params, prefix_cache_blocks=0) as sched:
        out = sched.generate(PROMPTS[:2], max_new_tokens=4)
    assert out == golden
    # Disabled cache: every counter (incl. the ISSUE-14 telemetry keys)
    # stays zeroed, and the telemetry block reports absent entirely.
    assert sched.prefix_stats == {
        "hits": 0, "misses": 0, "hit_rate": 0.0, "blocks_reused": 0,
        "reused_tokens": 0, "evictions": 0, "cached_blocks": 0,
    }
    assert sched.prefix_telemetry is None


@pytest.mark.slow
def test_prefix_cache_under_tp_mesh(tiny_model_module):
    """Sharded cache blocks restore correctly on a tp mesh."""
    import jax

    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    cfg, params = tiny_model_module
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    shared = list(range(3, 27))
    prompts = [[1] + shared + [60], [1] + shared + [61], [1] + shared + [62]]
    golden = engine_golden(cfg, params, prompts, max_new=4)
    with make_sched(cfg, params, mesh=mesh, max_seq=64) as sched:
        # Sequential: request 1 records the prefix, request 2 publishes its
        # blocks, request 3 restores them (concurrent identical admissions
        # would each prefill their own copy).
        out = []
        for p in prompts:
            out += sched.generate([p], max_new_tokens=4)
    assert out == golden
    assert sched.prefix_stats["blocks_reused"] >= 3


@pytest.mark.slow
def test_scheduler_backend_complete_batch(tiny_model_module):
    """complete_batch submits the whole batch through the slot pool and the
    greedy results match per-request engine goldens."""
    cfg, params = tiny_model_module
    from llm_based_apache_spark_optimization_tpu.tokenizer.byte import ByteTokenizer

    tok = ByteTokenizer(bos_id=cfg.bos_id, eos_id=cfg.eos_id, pad_id=cfg.pad_id)
    sched = make_sched(cfg, params, num_slots=2)
    backend = SchedulerBackend(sched, tok, max_new_tokens=4)
    prompts = ["SELECT a", "SELECT bb", "SELECT ccc"]
    try:
        outs = backend.complete_batch(prompts)
        assert len(outs) == 3
        for p, c in zip(prompts, outs):
            ids = tok.encode(p, add_bos=True)
            golden = engine_golden(cfg, params, [ids], max_new=4)[0]
            assert c.output_tokens == len(golden)
            assert c.prompt_tokens == len(ids)
    finally:
        sched.shutdown()


@pytest.mark.slow
def test_scheduler_backend_from_hf_checkpoint(tiny_model_module, tmp_path):
    """The deployment factory: HF dir -> scheduler backend, greedy parity
    with the engine path on the same checkpoint."""
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.checkpoint import (
        save_hf_checkpoint,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer.byte import ByteTokenizer

    cfg, params = tiny_model_module
    ckpt = tmp_path / "sched_ckpt"
    save_hf_checkpoint(cfg, params, ckpt)
    tok = ByteTokenizer(bos_id=cfg.bos_id, eos_id=cfg.eos_id, pad_id=cfg.pad_id)

    backend = SchedulerBackend.from_hf_checkpoint(
        str(ckpt), tok, dtype=jnp.float32, num_slots=2, decode_chunk=4,
        prompt_bucket=8, stop_ids=(-1,), max_new_tokens=4,
    )
    try:
        out = backend.complete("SELECT x")
        ids = tok.encode("SELECT x", add_bos=True)
        golden = engine_golden(cfg, params, [ids], max_new=4)[0]
        assert out.output_tokens == len(golden)
    finally:
        backend.scheduler.shutdown()


@pytest.mark.slow
def test_warmup_compiles_all_kbuckets_without_state_change(tiny_model_module):
    """warmup() builds every (bucket, k-bucket) prefill variant and runs
    them against the OOB padding slot — no VISIBLE slot/cache state
    changes, and subsequent generates stay engine-exact. The all-inactive
    decode round warmup() now also runs (compiling the decode program so
    a cold compile can't read as a watchdog wedge) writes garbage at the
    PARK row only — the last seq position, which no query can ever see
    (the cache visibility invariant); every visible row must be
    untouched."""
    import numpy as np

    cfg, params = tiny_model_module
    sched = make_sched(cfg, params, num_slots=2)
    before_k = np.asarray(sched._cache[0])
    sched.warmup()
    assert {kb for (_, kb) in sched._prefill_fns} == set(sched._kbuckets)
    after_k = np.asarray(sched._cache[0])
    np.testing.assert_array_equal(after_k[..., : sched._park, :],
                                  before_k[..., : sched._park, :])
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=4)
    with sched:
        assert sched.generate(PROMPTS[:2], max_new_tokens=4) == golden


def test_shutdown_with_in_flight_rounds_fails_futures(tiny_model_module):
    """Shutdown while rounds are still in flight (pending harvest queue
    non-empty) must fail every unresolved future with a clear error, not
    hang or leak — the async pipeline's crash-safety contract."""
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params, num_slots=2)
    sched.start()
    futs = [sched.submit([1, 5 + i], max_new_tokens=40) for i in range(6)]
    sched.shutdown()
    import concurrent.futures

    resolved, failed = 0, 0
    for f in futs:
        try:
            out = f.result(timeout=30)
            assert isinstance(out, list)
            resolved += 1
        except (RuntimeError, concurrent.futures.CancelledError):
            failed += 1
    assert resolved + failed == 6
    # And the scheduler rejects new work after shutdown.
    with pytest.raises(RuntimeError):
        sched.submit([1, 2], max_new_tokens=4)


@pytest.mark.slow
def test_scheduler_fused_matmuls_parity(tiny_model_module):
    """fuse_matmuls under the scheduler: greedy output must be exactly the
    unfused scheduler's (same dot products, wider matmuls), including with
    speculation on."""
    cfg, params = tiny_model_module
    prompts = [[1, 5, 9, 5, 9, 3], [1, 7, 2, 4]]
    ref = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, prompt_bucket=8, stop_ids=(-1,),
    )
    with ref:
        golden = ref.generate(prompts, max_new_tokens=8)
    for spec in (0, 4):
        fused = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, prompt_bucket=8, stop_ids=(-1,),
            fuse_matmuls=True, speculative_draft=spec,
        )
        with fused:
            out = fused.generate(prompts, max_new_tokens=8)
        assert out == golden, f"spec={spec}"


@pytest.mark.chaos
def test_slot_stall_retired_typed_batch_unaffected(tiny_model_module):
    """Per-slot stall retirement (serve/watchdog layer, scheduler side):
    a slot whose generation makes no progress for `slot_stall_rounds`
    harvested rounds is retired typed SlotStalled (504-family) WITHOUT
    restarting the loop — and the other slots' outputs are
    token-identical to a run without the stalled request. Injected via
    the `sched:slot_stall` chaos seam (submit-thread-scoped, so exactly
    one request wedges)."""
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        SlotStalled,
    )
    from llm_based_apache_spark_optimization_tpu.utils.faults import FAULTS

    cfg, params = tiny_model_module
    with make_sched(cfg, params, num_slots=3) as ctl:
        expected = ctl.generate([[1, 6], [1, 7]], max_new_tokens=8)

    sched = make_sched(cfg, params, num_slots=3, slot_stall_rounds=3)
    try:
        with sched:
            FAULTS.configure("sched:slot_stall:1", seed=0)
            stalled = sched.submit([1, 5], max_new_tokens=8)
            FAULTS.clear()
            others = [sched.submit([1, 6], max_new_tokens=8),
                      sched.submit([1, 7], max_new_tokens=8)]
            outs = [f.result(timeout=120) for f in others]
            with pytest.raises(SlotStalled) as exc_info:
                stalled.result(timeout=120)
            assert "no progress" in str(exc_info.value)
            # No restart happened: the SAME loop keeps serving new work.
            assert len(sched.generate([[1, 9]], max_new_tokens=4)[0]) == 4
        assert outs == expected  # neighbours token-identical to control
        assert sched.watchdog_stats["slots_retired_stalled"] == 1
    finally:
        FAULTS.clear()


# ----------------------------------------------------- fleet pool (ISSUE 9)


class _FakeReplica:
    """Host-only replica with the pool's placement surface: a scripted
    backlog score, an Overloaded switch, and instant deterministic
    results — every routing decision is inspectable without a device."""

    def __init__(self, secs=0.0, toks=0, hint=1.0):
        from concurrent.futures import Future  # noqa: F401 — used below

        from llm_based_apache_spark_optimization_tpu.serve.flightrecorder import (
            FlightRecorder,
        )

        self._crash = None
        self.flight = FlightRecorder(capacity=8)
        self.secs, self.toks, self.hint = secs, toks, hint
        self.overloaded = False
        self.submitted = []

    def start(self):
        return self

    def shutdown(self, timeout=None):
        pass

    def backlog_score(self):
        return self.secs, self.toks

    def retry_after_hint(self):
        return self.hint

    def submit(self, ids, max_new_tokens=256, sampling=None, seed=0,
               on_token=None, constraint=None, deadline_s=None, trace=None):
        from concurrent.futures import Future

        from llm_based_apache_spark_optimization_tpu.serve.resilience import (
            Overloaded,
        )

        if self.overloaded:
            raise Overloaded("fake full", retry_after_s=self.hint)
        self.submitted.append(list(ids))
        fut = Future()
        fut.set_result(list(ids))
        return fut


def _fake_pool(*replicas, **kw):
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        SchedulerPool,
    )

    return SchedulerPool(list(replicas), **kw)


def test_pool_least_loaded_routes_to_lightest_replica():
    """The router places on the replica with the smallest backlog
    estimate (queue-depth × service-time EWMA math via backlog_score),
    attributes the future, and records the placement decision in the
    pool's flight recorder."""
    heavy, light = _FakeReplica(secs=5.0), _FakeReplica(secs=0.25)
    pool = _fake_pool(heavy, light)
    fut = pool.submit([1, 2, 3])
    assert fut.result() == [1, 2, 3]
    assert light.submitted and not heavy.submitted
    assert fut._lsot_replica == "r1"
    placements = [r for r in pool.flight_snapshot()
                  if r.get("kind") == "placement"]
    assert placements and placements[-1]["to"] == "r1"
    assert placements[-1]["router"] == "least_loaded"
    # Equal seconds: the token-weighted backlog breaks the tie.
    a, b = _FakeReplica(secs=1.0, toks=500), _FakeReplica(secs=1.0, toks=3)
    pool2 = _fake_pool(a, b)
    pool2.submit([4])
    assert b.submitted and not a.submitted


def test_pool_deadline_aware_skip_and_504_when_infeasible():
    """A replica whose backlog would blow the request's deadline is
    skipped even when it is the least loaded by index order; when EVERY
    replica's backlog exceeds the deadline the pool sheds typed
    DeadlineExceeded (504) instead of burning the budget in a queue."""
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        DeadlineExceeded,
    )

    backed_up, fresh = _FakeReplica(secs=10.0), _FakeReplica(secs=0.2)
    pool = _fake_pool(backed_up, fresh)
    pool.submit([1], deadline_s=1.0)
    assert fresh.submitted and not backed_up.submitted
    backed_up.secs = fresh.secs = 30.0
    with pytest.raises(DeadlineExceeded, match="no replica can serve"):
        pool.submit([2], deadline_s=1.0)
    # Without a deadline the same backlog is simply the queue they join.
    pool.submit([3])
    assert len(backed_up.submitted) + len(fresh.submitted) == 2


def test_pool_pressure_penalty_deprioritizes_stormy_replica():
    """ISSUE 13 satellite: a replica mid-KV-pressure-storm (withheld
    pool pages — PR-10's kv_pressure signal) sorts AFTER healthy
    siblings before the least-loaded tie-break, even when its backlog
    score is strictly better; with no pressure anywhere the order is
    the pre-disagg backlog order bit for bit."""
    calm, stormy = _FakeReplica(secs=2.0), _FakeReplica(secs=0.1)
    stormy.page_stats = {"pages_withheld": 6, "pages_free": 0}
    pool = _fake_pool(stormy, calm)
    pool.submit([1, 2])
    assert calm.submitted and not stormy.submitted
    # Pressure lifted: the better backlog score wins again.
    stormy.page_stats = {"pages_withheld": 0, "pages_free": 12}
    pool.submit([3])
    assert stormy.submitted


def test_pool_slo_burning_deprioritized(monkeypatch):
    """ISSUE 13 satellite: a replica whose rolling SLO is burning sorts
    after healthy siblings before the backlog tie-break."""
    from llm_based_apache_spark_optimization_tpu.utils import slo as slo_mod

    class _Engine:
        enabled = True

        @staticmethod
        def replica_burning(label):
            return label == "r0"

    monkeypatch.setattr(slo_mod, "ENGINE", _Engine())
    burning, healthy = _FakeReplica(secs=0.1), _FakeReplica(secs=5.0)
    pool = _fake_pool(burning, healthy)
    pool.submit([1])
    assert healthy.submitted and not burning.submitted


def test_pool_all_full_sheds_with_min_retry_after():
    """One full replica no longer answers for the fleet: the pool sheds
    Overloaded only when EVERY placeable replica is at capacity, and the
    hint is the fleet's MINIMUM Retry-After, not whichever replica
    happened to shed last."""
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        Overloaded,
    )

    a, b = _FakeReplica(hint=7.0), _FakeReplica(hint=3.0)
    a.overloaded = True
    pool = _fake_pool(a, b)
    pool.submit([1])  # b has room: no shed
    assert b.submitted
    b.overloaded = True
    with pytest.raises(Overloaded) as exc_info:
        pool.submit([2])
    assert exc_info.value.retry_after_s == pytest.approx(3.0)


def test_pool_retry_after_hint_restart_aware():
    """ISSUE 9 satellite: a RESTARTING replica's stale EWMA must not
    drive the pool hint — it contributes its restart-backoff remaining
    instead, and the hint is the min over placeable replicas."""
    import time as _t

    a, b = _FakeReplica(hint=9.0), _FakeReplica(hint=0.5)
    pool = _fake_pool(a, b)
    assert pool.retry_after_hint() == pytest.approx(1.0)  # clamped floor
    b.hint = 4.0
    assert pool.retry_after_hint() == pytest.approx(4.0)
    # b restarting with 2 s of backoff left: its (stale) 4.0 estimate is
    # ignored; the hint becomes min(a's 9.0, b's backoff 2.0) = ~2.0.
    pool._states[1].state = "restarting"
    pool._states[1].restart_eta = _t.monotonic() + 2.0
    hint = pool.retry_after_hint()
    assert 1.0 <= hint <= 2.05
    # Dead replicas contribute nothing: only a's estimate remains.
    pool._states[1].state = "dead"
    assert pool.retry_after_hint() == pytest.approx(9.0)


def test_pool_health_aggregates_replica_states():
    a, b = _FakeReplica(), _FakeReplica()
    pool = _fake_pool(a, b)
    h = pool.health()
    assert h["state"] == "ready"
    assert [r["replica"] for r in h["replicas"]] == ["r0", "r1"]
    pool._states[0].state = "restarting"
    assert pool.health()["state"] == "degraded"
    pool._states[1].state = "dead"
    assert pool.health()["state"] == "restarting"
    pool._states[0].state = "dead"
    assert pool.health()["state"] == "dead"
    # A deliberately REMOVED replica stays visible but must not degrade
    # the aggregate of a healthy remainder forever.
    pool._states[0].state = "removed"
    pool._states[1].state = "ready"
    h = pool.health()
    assert h["state"] == "ready"
    assert [r["state"] for r in h["replicas"]] == ["removed", "ready"]


def test_pool_restart_refused_while_drain_owns_the_replica():
    """A racing restart_replica must not hijack a replica mid-drain (the
    drain's final state write would mark the freshly rebuilt scheduler
    drained out from under it); removed replicas are gone for good."""
    a, b = _FakeReplica(), _FakeReplica()
    pool = _fake_pool(a, b, factory=lambda i: _FakeReplica())
    pool._states[0].state = "draining"
    assert pool.restart_replica("r0") is False
    pool._states[0].state = "removed"
    assert pool.restart_replica("r0") is False


@pytest.mark.slow
def test_pool_drain_replica_replaces_queued_work(tiny_model_module):
    """Runtime drain of ONE replica: its queued requests re-place onto
    the sibling (nothing shed, outputs stay engine-exact), in-flight
    work finishes inside the grace, the replica parks `drained` and
    placement skips it — while the pool keeps serving."""
    from llm_based_apache_spark_optimization_tpu.serve import SchedulerPool

    cfg, params = tiny_model_module
    prompts = [[1, 5 + i] for i in range(6)]
    golden = engine_golden(cfg, params, prompts, max_new=4)
    pool = SchedulerPool(
        [make_sched(cfg, params, num_slots=1),
         make_sched(cfg, params, num_slots=1)],
    )
    with pool:
        futs = [pool.submit(p, max_new_tokens=4) for p in prompts]
        report = pool.drain_replica("r0", deadline_s=60.0)
        outs = [f.result(timeout=120) for f in futs]
        assert outs == golden
        assert report["state"] == "drained"
        assert pool.health()["state"] == "degraded"
        # Placement skips the drained replica from here on.
        fut = pool.submit(prompts[0], max_new_tokens=4)
        assert fut._lsot_replica == "r1"
        assert fut.result(timeout=120) == golden[0]
    ev = [r for r in pool.flight_snapshot()
          if r.get("kind") == "replica_drained"]
    assert ev and ev[-1]["replica"] == "r0"


@pytest.mark.slow
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_pool_targeted_restart_rebuilds_only_crashed_replica(
        tiny_model_module):
    """A crashed replica is rebuilt from the pool's factory (bounded
    backoff, per-replica budget) while the sibling's restart counter
    stays zero — and the rebuilt fleet serves engine-exact again."""
    import random
    import time as _t

    from llm_based_apache_spark_optimization_tpu.serve import SchedulerPool
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        RetryPolicy,
        SchedulerCrashed,
    )

    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS[:2], max_new=4)
    pool = SchedulerPool(
        [make_sched(cfg, params), make_sched(cfg, params)],
        factory=lambda i: make_sched(cfg, params),
        max_restarts=2,
        restart_policy=RetryPolicy(max_attempts=3, base_delay_s=0.001,
                                   max_delay_s=0.01),
        rng=random.Random(0),
        replica_join_s=1.0,
    )
    with pool:
        pool.schedulers[0]._crash = SchedulerCrashed("simulated device loss")
        # Placement observes the crash, serves from the sibling, and
        # kicks the targeted rebuild in the background.
        out = pool.generate(PROMPTS[:2], max_new_tokens=4)
        assert out == golden
        deadline = _t.monotonic() + 30
        while _t.monotonic() < deadline:
            reps = {r["replica"]: r for r in pool.replica_health()}
            if reps["r0"]["restarts"] >= 1 and \
                    reps["r0"]["state"] in ("ready", "degraded"):
                break
            _t.sleep(0.02)
        reps = {r["replica"]: r for r in pool.replica_health()}
        assert reps["r0"]["restarts"] == 1
        assert reps["r1"]["restarts"] == 0
        # The rebuilt replica serves again (a clean completion promotes
        # degraded back to ready).
        out2 = pool.generate(PROMPTS[:2] * 2, max_new_tokens=4)
        assert out2 == golden * 2


# --------------------------------- cache-aware + weighted routing (ISSUE 15)


def test_pool_affinity_routes_to_prefix_holder():
    """The cache-aware flip: a replica already holding the request's
    chain-prefix digests sorts FIRST — ahead of a strictly better
    backlog score — and the placement event + routing counters record
    the hit."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        prefix_chain_digests,
    )

    holder, lighter = _FakeReplica(secs=2.0), _FakeReplica(secs=0.1)
    ids = list(range(1, 20))  # 19 tokens / block 8 -> 2 chain digests
    digs = prefix_chain_digests(ids, 8)
    assert len(digs) == 2
    holder._pblock = lighter._pblock = 8
    holder.resident_digests = lambda: list(digs)
    lighter.resident_digests = lambda: []
    pool = _fake_pool(holder, lighter, affinity_routing=True)
    pool.submit(ids)
    assert holder.submitted and not lighter.submitted
    rs = pool.routing_stats()
    assert rs["affinity_checked"] == 1 and rs["affinity_hits"] == 1
    placements = [r for r in pool.flight_snapshot()
                  if r.get("kind") == "placement"]
    assert placements[-1]["to"] == "r0"
    assert placements[-1]["affinity"] == 2
    # A prompt with NO resident prefix anywhere falls back to backlog.
    pool.submit(list(range(50, 69)))
    assert lighter.submitted


def test_pool_affinity_off_reproduces_backlog_order_bit_for_bit():
    """LSOT_POOL_AFFINITY=0: no digest lookups, no affinity flight
    events, and the placement order is exactly the pre-affinity
    backlog order even when a replica holds the whole prefix."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        prefix_chain_digests,
    )

    holder, lighter = _FakeReplica(secs=2.0), _FakeReplica(secs=0.1)
    ids = list(range(1, 20))
    holder._pblock = lighter._pblock = 8
    holder.resident_digests = lambda: prefix_chain_digests(ids, 8)
    lighter.resident_digests = lambda: []
    pool = _fake_pool(holder, lighter, affinity_routing=False)
    pool.submit(ids)
    assert lighter.submitted and not holder.submitted
    kinds = {r.get("kind") for r in pool.flight_snapshot()}
    assert "prefix_affinity" not in kinds
    placements = [r for r in pool.flight_snapshot()
                  if r.get("kind") == "placement"]
    assert "affinity" not in placements[-1]
    rs = pool.routing_stats()
    assert rs["affinity_checked"] == 0 and rs["affinity_hits"] == 0


def test_pool_weights_scale_backlog_comparison():
    """Heterogeneous capacity: a replica weighted 4 takes token mass
    its raw backlog would have lost — placement compares backlog/weight
    — while all-1.0 weights keep the unweighted order (same types,
    same values)."""
    import pytest as _pytest

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        parse_replica_weights,
    )

    big, small = _FakeReplica(secs=4.0), _FakeReplica(secs=3.0)
    pool = _fake_pool(big, small, weights=[4.0, 1.0])
    pool.submit([1])
    assert big.submitted and not small.submitted  # 4/4 = 1.0 < 3.0
    big2, small2 = _FakeReplica(secs=4.0), _FakeReplica(secs=3.0)
    pool2 = _fake_pool(big2, small2)  # unweighted: raw backlog wins
    pool2.submit([1])
    assert small2.submitted and not big2.submitted
    # Weighted replicas surface their weight in the loads feed.
    loads = {r["replica"]: r for r in pool.replica_loads()}
    assert loads["r0"]["weight"] == 4.0 and "weight" not in loads["r1"]
    # Deadline feasibility stays WALL-CLOCK: the weighted ordering may
    # prefer the big replica (2.0/4 = 0.5 < 1.0), but its RAW backlog
    # blows a 1.5 s budget, so the request must land on the sibling.
    big3, small3 = _FakeReplica(secs=2.0), _FakeReplica(secs=1.0)
    pool3 = _fake_pool(big3, small3, weights=[4.0, 1.0])
    pool3.submit([2])
    assert big3.submitted  # ordering: weighted score wins
    pool3.submit([3], deadline_s=1.5)
    assert small3.submitted  # feasibility: raw seconds win
    # Spec parsing: pads with 1.0, refuses nonsense; the explicit
    # `weights=` ctor argument follows the SAME policy (no silent
    # truncation of an overlong list).
    assert parse_replica_weights("2,1", 3) == [2.0, 1.0, 1.0]
    assert parse_replica_weights("", 2) == [1.0, 1.0]
    with _pytest.raises(ValueError, match="positive"):
        parse_replica_weights("0,1", 2)
    with _pytest.raises(ValueError, match="bad replica weight"):
        parse_replica_weights("fast", 1)
    with _pytest.raises(ValueError, match="pool has"):
        parse_replica_weights("1,1,1", 2)
    with _pytest.raises(ValueError, match="pool has"):
        _fake_pool(_FakeReplica(), _FakeReplica(), weights=[1.0, 1.0, 2.0])


# ----------------------------------------------------------- multi-tenant QoS


def _mk_qos_req(ids, max_new=8, tenant="", deadline=None):
    from concurrent.futures import Future

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        _Request,
    )

    return _Request(ids=list(ids), max_new=max_new, temperature=0.0,
                    top_p=1.0, top_k=0, seed=0, future=Future(),
                    tenant=tenant, deadline=deadline)


def test_wfq_light_tenant_ahead_of_storm_backlog(tiny_model_module,
                                                 monkeypatch):
    """ISSUE 18: start-time fair queueing — a storm tenant's k-th queued
    request finishes k virtual costs out, so a light tenant's single
    request is served ahead of the storm's parked backlog (but behind
    the storm's head-of-line, which tied at the global clock first)."""
    monkeypatch.setenv("LSOT_QOS", "1")
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params)
    storm = [_mk_qos_req([1] * 8, tenant="storm") for _ in range(3)]
    light = _mk_qos_req([1] * 8, tenant="light")
    with sched._submit_lock:
        for i, r in enumerate(storm + [light]):
            r.rid = i + 1
            sched._stamp_qos_locked(r)
            sched._ready.append(r)
    order = [sched._ready_pop().tenant for _ in range(4)]
    assert order == ["storm", "light", "storm", "storm"]
    assert sched._ready_pop() is None
    # The per-tenant submit counters feed qos_stats → lsot_tenant_*.
    assert sched.qos_stats()["submitted"] == {"storm": 3, "light": 1}
    # Tenant prefix-cache namespacing: labeled requests got a salt,
    # distinct per tenant, and () is reserved for unlabeled traffic.
    assert storm[0].ns and light.ns and storm[0].ns != light.ns


def test_wfq_weights_scale_tenant_share(tiny_model_module, monkeypatch):
    """LSOT_TENANT_WEIGHTS: a weight-4 tenant's requests cost 1/4 the
    virtual time, so its whole volley finishes before an equal-sized
    weight-1 volley submitted FIRST."""
    monkeypatch.setenv("LSOT_QOS", "1")
    monkeypatch.setenv("LSOT_TENANT_WEIGHTS", "gold=4")
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params)
    reqs = ([_mk_qos_req([1] * 8, tenant="plain") for _ in range(2)]
            + [_mk_qos_req([1] * 8, tenant="gold") for _ in range(2)])
    with sched._submit_lock:
        for i, r in enumerate(reqs):
            r.rid = i + 1
            sched._stamp_qos_locked(r)
            sched._ready.append(r)
    order = [sched._ready_pop().tenant for _ in range(4)]
    assert order == ["gold", "gold", "plain", "plain"]
    assert sched.qos_stats()["weights"] == {"gold": 4.0}


def test_qos_off_reproduces_single_tenant_order_token_level(
        tiny_model_module, monkeypatch):
    """ISSUE 18 acceptance: `LSOT_QOS=0` reproduces the pre-QoS
    admission path bit-for-bit — tenant-labeled submits leave ZERO QoS
    state (FIFO queue only: empty ready pool, no vft/ns stamps, no
    stats block) and outputs reconcile token-for-token with both the
    engine golden and a QoS-on run of the same labeled workload."""
    cfg, params = tiny_model_module
    golden = engine_golden(cfg, params, PROMPTS, max_new=5)
    monkeypatch.setenv("LSOT_QOS", "0")
    with make_sched(cfg, params) as off:
        futs = [off.submit(p, max_new_tokens=5, tenant=f"t{i % 2}",
                           qos="batch")
                for i, p in enumerate(PROMPTS)]
        out_off = [f.result(timeout=120) for f in futs]
        assert off.qos_stats() is None
        assert off._ready == [] and off._wfq_vt == 0.0
        reqs = [f._lsot_request for f in futs]
        assert all(r.vft == 0.0 and r.ns == () for r in reqs)
    assert out_off == golden
    monkeypatch.setenv("LSOT_QOS", "1")
    with make_sched(cfg, params) as on:
        futs = [on.submit(p, max_new_tokens=5, tenant=f"t{i % 2}",
                          qos="batch")
                for i, p in enumerate(PROMPTS)]
        out_on = [f.result(timeout=120) for f in futs]
        assert sorted(on.qos_stats()["submitted"]) == ["t0", "t1"]
    assert out_on == golden


def test_sweep_page_wait_fails_expired_in_deadline_order(
        tiny_model_module, monkeypatch):
    """ISSUE 18 satellite (b): under WFQ the page-wait deque is no
    longer deadline-monotone — a heavy tenant's EARLIER-expiring waiter
    can sit behind a light tenant's. Expiry must still surface typed
    DeadlineExceeded in DEADLINE order (clients racing timeouts and the
    chaos loss accounting pair 504s with submit deadlines), and a
    near-expired but live waiter must survive the sweep untouched."""
    import time as _time

    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        Deadline,
        DeadlineExceeded,
    )

    monkeypatch.setenv("LSOT_QOS", "1")
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params, kv_page_size=8, kv_pages=16)
    now = _time.monotonic()
    # Parked in WFQ/service order: the light tenant's waiter expired a
    # full second LATER than the heavy tenant's sitting behind it.
    later = _mk_qos_req([1, 2], tenant="light",
                        deadline=Deadline(now - 1.0))
    earlier = _mk_qos_req([1, 2], tenant="heavy",
                          deadline=Deadline(now - 2.0))
    alive = _mk_qos_req([1, 2], tenant="heavy",
                        deadline=Deadline(now + 30.0))
    failed = []
    for tag, r in (("later", later), ("earlier", earlier),
                   ("alive", alive)):
        r.submitted_at = _time.perf_counter()
        r.future.add_done_callback(lambda f, t=tag: failed.append(t))
        sched._page_wait.append(r)
    sched._sweep_page_wait()
    assert failed == ["earlier", "later"]  # deadline order, not queue order
    for r in (earlier, later):
        with pytest.raises(DeadlineExceeded):
            r.future.result(timeout=1)
    assert list(sched._page_wait) == [alive]


# ------------------------------------------ lane-packed KV pool (ISSUE 33) --
# At a head narrower than 128 lanes the paged pool is stored f heads a row
# (engine/paged_kv.lane_pack). The packed scheduler must serve the tokens
# of the unpacked path — the same scheduler with `lane_pack` held to 1,
# which is the parent's program — through every operation that touches a
# page's bytes.


@pytest.fixture(scope="module")
def head64_model():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    cfg = dataclasses.replace(TINY, name="tiny-head64", num_heads=4,
                              num_kv_heads=4, head_dim=64)
    return cfg, init_params(cfg, jax.random.key(3), dtype=jnp.float32)


def _paged64(cfg, params, monkeypatch=None, **kw):
    """A paged scheduler at head 64: lane-packed as built, or — handed a
    `monkeypatch` — the unpacked reference path."""
    from llm_based_apache_spark_optimization_tpu.engine import paged_kv

    kw.setdefault("kv_page_size", 16)
    if monkeypatch is None:
        sched = make_sched(cfg, params, **kw)
        assert sched._cache[0].shape[2:] == (2, kw["kv_page_size"], 128)
        assert sched.page_stats["kv_pool_lane_pack"] == 2
        return sched
    with monkeypatch.context() as m:
        m.setattr(paged_kv, "lane_pack", lambda *a, **k: 1)
        sched = make_sched(cfg, params, **kw)
    assert sched._cache[0].shape[2:] == (4, kw["kv_page_size"], 64)
    assert sched.page_stats["kv_pool_lane_pack"] == 1
    return sched


_LONG = [[1] + [5 + (3 * i + j) % 40 for j in range(n)]
         for i, n in enumerate((19, 9, 26, 13))]
_SHARED = [1] + list(range(5, 28))       # 24 tokens: 3 blocks, 1.5 pages


@pytest.mark.parametrize("case", ["chunked_prefill", "prefix_hit_cow",
                                  "spill_restore", "speculative_windows",
                                  "pallas_kernels"])
def test_lane_packed_pool_serves_the_unpacked_tokens(
        head64_model, monkeypatch, case):
    from llm_based_apache_spark_optimization_tpu.utils.faults import FAULTS

    cfg, params = head64_model
    kw, pressure, max_new = {}, None, 6
    prompts = _LONG                       # prompts of 2-4 chunks of 8
    if case == "prefix_hit_cow":
        # Blocks of 8 end mid-page (pages of 16): a hit shares the full
        # page and copies the boundary page (`copy_page`) before writing.
        prompts = [_SHARED + [40 + i] for i in range(6)]
    elif case == "spill_restore":
        # A pressure storm preempts a victim, spills its pages to the
        # host (`export_pages`) and restores them (`import_pages`).
        kw = dict(max_seq=64, kv_page_size=8, kv_overcommit=0.25,
                  kv_pages=9, kv_spill=True)
        pressure, prompts, max_new = "kv:pressure:1:3", PROMPTS, 24
    elif case == "speculative_windows":
        kw = dict(speculative_draft=3)    # verify windows, T = D + 1

    def run(build, impl="auto"):
        from llm_based_apache_spark_optimization_tpu.ops.pallas import (
            set_attention_impl,
        )

        set_attention_impl(impl)          # read when a scheduler is built
        if pressure:
            FAULTS.configure(pressure, 0)
        try:
            with build() as s:
                outs = [f.result(timeout=300) for f in
                        [s.submit(p, max_new_tokens=max_new)
                         for p in prompts]]
                return outs, dict(s.page_stats), s.kernel_modes()
        finally:
            FAULTS.clear()
            set_attention_impl("auto")

    want, _, _ = run(lambda: _paged64(cfg, params, monkeypatch, **kw))
    # The packed side of "pallas_kernels" runs the interpreted kernels —
    # flash over packed row views, the fused write, the ragged read —
    # against the unpacked einsum path.
    got, stats, modes = run(
        lambda: _paged64(cfg, params, **kw),
        "pallas" if case == "pallas_kernels" else "auto")
    assert got == want
    assert modes["page_write"] == modes["decode_attention"] == (
        "pallas" if case == "pallas_kernels" else "xla")
    if case == "chunked_prefill":
        assert got == engine_golden(cfg, params, prompts, max_new)
    elif case == "prefix_hit_cow":
        assert stats["zero_copy_shares"] > 0 and stats["cow_copies"] > 0
    elif case == "spill_restore":
        assert stats["preemptions"] >= 1
        assert stats["spilled_pages"] == stats["restored_pages"] > 0


def test_lane_packed_handoff_round_trip_and_refusal(head64_model,
                                                    monkeypatch):
    """A prefill replica's exported pages (`export_pages`) restore into a
    decode replica of the same stored shape (`import_pages`) and decode
    there to the mixed scheduler's tokens; a replica whose pool is stored
    otherwise refuses the blob and names both shapes."""
    cfg, params = head64_model
    with _paged64(cfg, params) as mixed:
        want = mixed.generate([_LONG[0]], max_new_tokens=6)[0]
    ready = threading.Event()
    pre = _paged64(cfg, params, phase_role="prefill")
    pre.on_handoff = ready.set
    with pre:
        fut = pre.submit(_LONG[0], max_new_tokens=6)
        assert ready.wait(60)
        (req,) = pre.extract_handoffs()
        assert req.spilled[0].shape[2:] == (2, 16, 128)
        with _paged64(cfg, params, monkeypatch, phase_role="decode") as other:
            with pytest.raises(ValueError) as e:
                other.requeue(req)
            assert "(2, 2, 2, 16, 128)" in str(e.value)
            assert "4, 16, 64)" in str(e.value)
        with _paged64(cfg, params, phase_role="decode") as dec:
            dec.requeue(req)
            assert fut.result(timeout=120) == want
            assert dec.handoff_stats["imports"] == 1
