"""Paged KV cache (ISSUE 7): allocator invariants, ragged-paged-attention
kernel parity, engine-loop parity, and zero-copy prefix sharing through the
real scheduler.

The acceptance bar is TOKEN-IDENTICAL greedy output paged-vs-contiguous —
through the engines' one-XLA-program loops, and the continuous-batching
scheduler (which serves the page pool only) against the engine's
contiguous greedy decode on mixed constrained/speculative batches — plus
allocator stats that prove prefix hits SHARE pages (refcounts) instead of
copying them, with copy-on-write firing only at non-page-aligned
boundaries and never leaking a page.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
    cache_bytes,
    init_cache,
)
from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
    PageAccountingError,
    PageAllocator,
    init_page_pool,
    pack_prefill_pages,
    page_bytes,
    pages_for_budget,
    pages_for_tokens,
)
from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
)

PROMPTS = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]


@pytest.fixture(scope="module")
def tiny():
    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def wait_pages_drained(sched, expect_in_use=0, timeout=5.0):
    """Futures resolve BEFORE the worker frees the slot's pages (same
    ordering as the contiguous retire scatter) — poll briefly."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if sched.page_stats["pages_in_use"] <= expect_in_use:
            return sched.page_stats
        time.sleep(0.02)
    return sched.page_stats


# ------------------------------------------------------------ sizing math --


def test_cache_bytes_accounts_sublane_rounding(tiny):
    cfg, _ = tiny
    # init_cache rounds S up to a sublane multiple; cache_bytes must agree
    # (it used to under-report for non-multiple-of-8 lengths).
    assert cache_bytes(cfg, 2, 100) == cache_bytes(cfg, 2, 104)
    cache = init_cache(cfg, 2, 100, dtype=jnp.bfloat16)
    actual = cache["k"].nbytes + cache["v"].nbytes
    assert cache_bytes(cfg, 2, 100) == actual


def test_pool_sizing_roundtrip(tiny):
    cfg, _ = tiny
    pb = page_bytes(cfg, 16, itemsize=2)
    pool = init_page_pool(cfg, 5, 16, dtype=jnp.bfloat16)
    assert pool["kp"].nbytes + pool["vp"].nbytes == 5 * pb
    assert pages_for_budget(cfg, 5 * pb, 16) == 5
    assert pages_for_budget(cfg, 5 * pb - 1, 16) == 4
    assert pages_for_tokens(1, 16) == 1
    assert pages_for_tokens(16, 16) == 1
    assert pages_for_tokens(17, 16) == 2
    with pytest.raises(ValueError, match="multiple of 8"):
        init_page_pool(cfg, 4, 12)


def test_pack_prefill_pages_roundtrip(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    b, s, ps, ppr = 3, 24, 16, 4
    cache = {
        "k": jnp.asarray(rng.normal(size=(
            cfg.num_layers, b, cfg.num_kv_heads, s, cfg.head_dim
        )), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(
            cfg.num_layers, b, cfg.num_kv_heads, s, cfg.head_dim
        )), jnp.float32),
    }
    paged = pack_prefill_pages(cache, ps, ppr)
    assert paged["kp"].shape[1] == b * ppr
    from llm_based_apache_spark_optimization_tpu.ops.pallas import gather_pages

    for name, pool in (("k", paged["kp"]), ("v", paged["vp"])):
        for layer in range(cfg.num_layers):
            view = gather_pages(pool[layer], paged["ptab"])  # [B, K, NP*PS, H]
            np.testing.assert_array_equal(
                np.asarray(view[:, :, :s]),
                np.asarray(cache[name][layer]),
            )


# ------------------------------------------------- allocator property test --


def test_allocator_basic_cow_semantics():
    a = PageAllocator(4, 16)
    pages = a.alloc(2)
    assert sorted(pages) == [0, 1] and a.pages_free == 2
    a.share([pages[0]])
    assert a.is_shared(pages[0]) and a.pages_shared == 1
    # cow on a shared page: fresh exclusive page, old keeps its other ref
    fresh = a.cow(pages[0])
    assert fresh not in pages and a.refcount(pages[0]) == 1
    assert a.cow_copies == 1
    # cow on an exclusive page is the identity
    assert a.cow(pages[1]) == pages[1]
    with pytest.raises(PageAccountingError):
        a.release([fresh]); a.release([fresh])
    with pytest.raises(ValueError):
        PageAllocator(0, 16)


def test_allocator_randomized_invariants(rng):
    """Randomized admit/retire/share/cow sequences: no page leaked, no
    double free, free-list/refcount partition intact throughout."""
    a = PageAllocator(12, 8)
    live = []     # exclusively owned (slot) pages
    shared = []   # extra refs we hold (prefix-cache stand-in)
    for _ in range(600):
        op = rng.integers(0, 5)
        if op == 0:  # admit
            n = int(rng.integers(1, 4))
            got = a.alloc(n)
            if got is None:
                assert a.pages_free < n  # refused only when short
            else:
                live.extend(got)
        elif op == 1 and live:  # retire
            i = int(rng.integers(0, len(live)))
            a.release([live.pop(i)])
        elif op == 2 and live:  # publish (take a ref)
            pg = live[int(rng.integers(0, len(live)))]
            a.share([pg])
            shared.append(pg)
        elif op == 3 and shared:  # evict an entry ref
            i = int(rng.integers(0, len(shared)))
            a.release([shared.pop(i)])
        elif op == 4 and shared:  # cow a shared page
            i = int(rng.integers(0, len(shared)))
            pg = shared[i]
            if a.is_shared(pg):
                fresh = a.cow(pg)
                if fresh is not None and fresh != pg:
                    # our ref moved to the fresh page
                    shared[i] = fresh
        a.check()
        assert a.pages_free + a.pages_in_use == a.num_pages
    for pg in live + shared:
        a.release([pg])
    a.check()
    assert a.pages_free == a.num_pages  # no leak, everything drained


# -------------------------------------------------------- kernel parity ----


@pytest.mark.parametrize("ps,np_tab", [(16, 4), (8, 7)])
def test_ragged_paged_kernel_matches_reference(rng, ps, np_tab):
    from llm_based_apache_spark_optimization_tpu.ops.attention import (
        attention_mask,
        gqa_attention,
    )
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        gather_pages,
        paged_attention_reference,
        ragged_paged_attention,
    )

    b, kh, g, h, pool_pages = 3, 2, 2, 8, 11
    n = kh * g
    kp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)), jnp.float32)
    tab = np.stack([rng.permutation(pool_pages)[:np_tab] for _ in range(b)])
    tab[0, -1] = pool_pages  # unmapped sentinel past the live region
    tab = jnp.asarray(tab, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, n, h)), jnp.float32)
    s_virt = np_tab * ps
    pos = jnp.asarray([[ps // 2], [s_virt - ps - 1], [s_virt - 1]], jnp.int32)
    kvl = pos[:, 0] + 1

    out_k = ragged_paged_attention(q, kp[None], vp[None], tab, pos, 0, None,
                                   kvl)
    out_r = paged_attention_reference(q, kp, vp, tab, pos, None, kvl)
    np.testing.assert_allclose(out_k, out_r, atol=2e-6)

    # Equivalent contiguous layout: gather through the table, plain einsum.
    mask = attention_mask(pos, s_virt)
    out_c = gqa_attention(q, gather_pages(kp, tab), gather_pages(vp, tab),
                          mask)
    np.testing.assert_allclose(out_r, out_c, atol=2e-6)


def test_ragged_paged_kernel_kv_lens_truncates_and_parks(rng):
    """The kernel's output depends only on the first kv_lens[b] logical
    positions (garbage beyond is invisible), and kv_lens=0 parks a row."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        ragged_paged_attention,
    )

    b, kh, g, h, ps, np_tab, pool_pages = 2, 2, 2, 8, 8, 4, 9
    n = kh * g
    kp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)), jnp.float32)
    tab = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, n, h)), jnp.float32)
    pos = jnp.asarray([[10], [10]], jnp.int32)
    kvl = jnp.asarray([11, 11], jnp.int32)
    base = ragged_paged_attention(q, kp[None], vp[None], tab, pos, 0, None,
                                  kvl)
    # Scribble every position >= kv_lens: the wholly-dead logical pages 2-3
    # of both rows, and the in-page tail of logical page 1 (kv_lens=11 ->
    # offsets 3+ of positions 8..15 are past the live region). Output must
    # not move.
    kp2, vp2 = kp, vp
    for b_ in range(b):
        for li in (2, 3):
            pg = int(tab[b_, li])
            kp2 = kp2.at[pg].set(99.0)
            vp2 = vp2.at[pg].set(-99.0)
        pg = int(tab[b_, 1])
        kp2 = kp2.at[pg, :, 3:].set(99.0)
        vp2 = vp2.at[pg, :, 3:].set(-99.0)
    out = ragged_paged_attention(q, kp2[None], vp2[None], tab, pos, 0, None,
                                 kvl)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
    parked = ragged_paged_attention(
        q, kp[None], vp[None], tab, pos, 0, None,
        jnp.asarray([0, 11], jnp.int32)
    )
    assert float(jnp.abs(parked[0]).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(parked[1]), np.asarray(base[1]))


# ------------------------------------------------------ engine-loop parity --


def test_engine_paged_greedy_parity(tiny):
    cfg, params = tiny
    ec = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
    ep = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                         kv_layout="paged", kv_page_size=8)
    assert ep.generate(PROMPTS, max_new_tokens=6) == \
        ec.generate(PROMPTS, max_new_tokens=6)


def test_engine_paged_speculative_parity(tiny):
    cfg, params = tiny
    ec = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                         speculative_draft=4)
    ep = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                         speculative_draft=4, kv_layout="paged",
                         kv_page_size=8)
    assert ep.generate(PROMPTS, max_new_tokens=6) == \
        ec.generate(PROMPTS, max_new_tokens=6)


def test_engine_paged_rejects_bad_combos(tiny):
    """ISSUE 11 lifted the PR-7 rejections: int8 + paged and mesh + paged
    are ACCEPTED now; only genuinely invalid combos still raise."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="kv_layout"):
        InferenceEngine(cfg, params, kv_layout="sideways")
    # int8 + paged composes (the int8 page pool) — constructor accepts.
    InferenceEngine(cfg, params, kv_quant="int8", kv_layout="paged")
    # int8 + paged + speculation composes too (verify windows run the
    # int8-streaming reference gather)...
    InferenceEngine(cfg, params, kv_quant="int8", kv_layout="paged",
                    speculative_draft=4)
    # ...but int8 + speculation on the CONTIGUOUS layout stays rejected
    # (its verify loop streams the bf16 cache).
    with pytest.raises(ValueError, match="contiguous"):
        InferenceEngine(cfg, params, kv_quant="int8", speculative_draft=4)


# -------------------------------------------------- scheduler-level parity --


def contiguous_greedy(cfg, params, reqs, stop_ids, **kw):
    """The parity reference: engine/generate.py's one-program greedy
    decode on the CONTIGUOUS cache, one request at a time. `reqs` rows
    are (ids, constraint, max_new)."""
    eng = InferenceEngine(cfg, params, stop_ids=stop_ids, prompt_bucket=8,
                          **kw)
    outs = [eng.generate([ids], max_new_tokens=mn, constraint=c)[0]
            for ids, c, mn in reqs]
    # The scheduler strips the stop id; a grammar-closed engine row keeps it.
    return [o[:-1] if o and o[-1] in stop_ids else o for o in outs]


def test_scheduler_paged_greedy_parity(tiny):
    cfg, params = tiny
    golden = contiguous_greedy(
        cfg, params, [(p, None, 6) for p in PROMPTS * 2], (-1,))
    paged = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), kv_page_size=16,
    )
    with paged:
        out = paged.generate(PROMPTS * 2, max_new_tokens=6)
    assert out == golden
    stats = wait_pages_drained(paged)
    assert stats["pages_in_use"] == 0  # every retirement freed its pages


def test_scheduler_paged_mixed_constrained_speculative_parity(tiny):
    """The acceptance criterion: token-identical greedy output through the
    real scheduler on a MIXED constrained/speculative batch."""
    from llm_based_apache_spark_optimization_tpu.constrain import (
        get_constraint,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    cfg, params = tiny
    tok = ByteTokenizer()
    cm = get_constraint("spark_sql", tok, (2,))
    budget = max(30, cm.min_new_tokens)
    reqs = [
        ([1, 5, 9], None, 8),
        (tok.encode("SELECT", add_bos=True), cm, budget),
        ([1, 3, 4, 8, 10, 11, 12, 13, 14], None, 8),
        (tok.encode("SELECT c", add_bos=True), cm, budget),
    ]

    def run(**kw):
        with ContinuousBatchingScheduler(
            cfg, params, num_slots=3, decode_chunk=4, prompt_bucket=8,
            stop_ids=(2,), speculative_draft=3, **kw
        ) as s:
            futs = [s.submit(ids, max_new_tokens=mn, constraint=c)
                    for ids, c, mn in reqs]
            return [f.result(timeout=300) for f in futs]

    assert run(kv_page_size=16) == contiguous_greedy(cfg, params, reqs, (2,))


def test_scheduler_paged_prefix_sharing_zero_copy(tiny):
    """Page-aligned prefix reuse is pure sharing: zero_copy_shares rises
    with hits, cow_copies stays 0 (page size == block size), and the
    outputs equal per-request engine greedy."""
    cfg, params = tiny
    prefix = [1] + list(range(5, 28))  # 24 tokens = 3 blocks of 8
    prompts = [prefix + [40 + i] for i in range(6)]
    eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
    golden = [eng.generate([p], max_new_tokens=5)[0] for p in prompts]
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), kv_layout="paged", kv_page_size=8,
    ) as s:
        outs = [s.submit(p, max_new_tokens=5).result(timeout=300)
                for p in prompts]
        assert outs == golden
        stats = s.page_stats
        prefix_stats = s.prefix_stats
    assert prefix_stats["hits"] >= 3          # publish gate: hit from req 3 on
    assert stats["zero_copy_shares"] > 0      # hits SHARED pages...
    assert stats["cow_copies"] == 0           # ...and copied nothing


def test_scheduler_paged_cow_only_at_unaligned_boundary(tiny):
    """Blocks (8 tokens) mid-page (16-token pages): sharing still zero-copy
    for full pages, with bounded copy-on-write at the boundary — and output
    parity survives it."""
    cfg, params = tiny
    prefix = [1] + list(range(5, 28))
    prompts = [prefix + [40 + i] for i in range(6)]
    eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
    golden = [eng.generate([p], max_new_tokens=5)[0] for p in prompts]
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), kv_layout="paged", kv_page_size=16,
    ) as s:
        outs = [s.submit(p, max_new_tokens=5).result(timeout=300)
                for p in prompts]
        assert outs == golden
        stats = s.page_stats
    assert stats["zero_copy_shares"] > 0
    assert stats["cow_copies"] > 0
    # COW is bounded by boundaries touched, never per-token.
    assert stats["cow_copies"] <= 2 * len(prompts)


def test_scheduler_paged_page_pressure_waits_and_completes(tiny):
    """A pool smaller than the concurrency demand: requests wait for pages
    (all-or-nothing admission — no deadlock), every future completes with
    the unpressured output, and the pool drains to empty."""
    cfg, params = tiny
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=4, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), max_seq=48,
    ) as ref:
        golden = [f.result(timeout=300) for f in
                  [ref.submit([1, 5 + i, 9], max_new_tokens=6)
                   for i in range(6)]]
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=4, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), max_seq=48, kv_layout="paged", kv_page_size=16,
        kv_pages=3,
    ) as s:
        outs = [f.result(timeout=300) for f in
                [s.submit([1, 5 + i, 9], max_new_tokens=6)
                 for i in range(6)]]
        assert outs == golden
        stats = wait_pages_drained(s)
        assert stats["page_waits"] > 0
        assert stats["pages_in_use"] == 0
    # too-small pools are rejected up front, not deadlocked at runtime
    with pytest.raises(ValueError, match="page pool"):
        ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_seq=48, kv_layout="paged",
            kv_page_size=16, kv_pages=1,
        )


def test_scheduler_paged_rejects_bad_combos(tiny):
    """The removed layout is refused by name, bogus ones with it; the
    int8 pool (ISSUE 11) is a supported configuration and must
    construct."""
    cfg, params = tiny
    for layout in ("contiguous", "bogus"):
        with pytest.raises(ValueError, match="contiguous KV layout was "
                                             "removed"):
            ContinuousBatchingScheduler(cfg, params, kv_layout=layout)
    s = ContinuousBatchingScheduler(
        cfg, params, kv_quant="int8", num_slots=2,
    )
    assert s.page_stats["kv_quant"] == "int8"


def _tiny_backend(tiny, **kw):
    """SchedulerBackend.from_loader (what the app's --scheduler path
    builds) over TINY, at a size whose warm-up is two programs."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        SchedulerBackend,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    return SchedulerBackend.from_loader(
        lambda mesh: tiny, ByteTokenizer(), name="tiny", num_slots=2,
        prompt_bucket=16, decode_chunk=4, **kw)


def _app_parser():
    from llm_based_apache_spark_optimization_tpu.app.__main__ import (
        build_parser,
    )

    return build_parser()


def _worker_parser():
    from llm_based_apache_spark_optimization_tpu.serve.remote import (
        build_parser,
    )

    return build_parser()


@pytest.mark.parametrize("entry", ["scheduler", "loader", "app", "worker"])
def test_one_layout_default_and_refusal(tiny, entry, capsys):
    """Every entry point that still takes a layout defaults to the page
    pool and refuses any other value by name."""
    cfg, params = tiny
    gone = "contiguous KV layout was removed"
    if entry in ("app", "worker"):
        parser = _app_parser() if entry == "app" else _worker_parser()
        assert parser.parse_args([]).kv_layout == "paged"
        assert parser.parse_args(["--kv-layout", "paged"]).kv_layout == \
            "paged"
        for layout in ("contiguous", "sideways"):
            with pytest.raises(SystemExit):
                parser.parse_args(["--kv-layout", layout])
            assert gone in capsys.readouterr().err
        return
    build = ((lambda **kw: ContinuousBatchingScheduler(
                  cfg, params, num_slots=2, **kw))
             if entry == "scheduler" else
             (lambda **kw: _tiny_backend(tiny, **kw).scheduler))
    for layout in ("contiguous", "sideways"):
        with pytest.raises(ValueError, match=gone):
            build(kv_layout=layout)
    sched = build()
    try:
        assert sched.page_stats["pages_total"] == \
            sched.num_slots * sched.page_stats["pages_per_slot"]
        assert sched.perf.kv_layout == "paged"
    finally:
        sched.shutdown()


def test_layout_flag_changes_no_program(tiny):
    """A server built with no layout argument and one built from
    `--kv-layout paged` lower the same decode program."""
    def decode_text(argv):
        args = _app_parser().parse_args(argv)
        kw = {"kv_layout": args.kv_layout} if "--kv-layout" in argv else {}
        sched = _tiny_backend(tiny, **kw).scheduler
        try:
            return sched._decode_fn.lower(
                sched.params, *sched._cache, *sched._decode_warm_args()
            ).as_text()
        finally:
            sched.shutdown()

    plain = decode_text(["--scheduler"])
    assert "func.func" in plain  # a real module came back
    assert decode_text(["--scheduler", "--kv-layout", "paged"]) == plain


# ------------------------------------------------------- observability ----


def test_flight_recorder_kv_pages_column(tiny):
    cfg, params = tiny
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), kv_layout="paged", kv_page_size=16,
    ) as s:
        # Long enough that mid-flight harvests record while slots still
        # hold pages (the final round's record reads 0 — retires precede
        # the record inside one harvest).
        s.generate([[1, 5, 9], [1, 7]], max_new_tokens=12)
        # The future resolves mid-harvest, BEFORE the round record lands —
        # poll briefly for the recorder to catch up.
        deadline = time.time() + 5.0
        recs = []
        while time.time() < deadline and not recs:
            recs = [r for r in s.flight.snapshot() if "kv_pages" in r]
            time.sleep(0.02)
    assert recs, "no flight record carried the kv_pages column"
    assert any(r["kv_pages"] > 0 for r in recs)
    for r in recs:
        assert r["kv_pages"] + r["kv_pages_free"] == \
            s.page_stats["pages_total"]


def test_page_gauges_in_prometheus_exposition(tiny):
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        SchedulerBackend,
    )
    from llm_based_apache_spark_optimization_tpu.serve.service import (
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    cfg, params = tiny
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), kv_layout="paged", kv_page_size=16,
    )
    backend = SchedulerBackend(sched, ByteTokenizer(), max_new_tokens=4)
    svc = GenerationService()
    svc.register("tiny-paged", backend)
    try:
        svc.generate("tiny-paged", "hi", max_new_tokens=4)
        stats = backend.stats()
        assert stats["kv_pages"]["pages_total"] > 0
        text = svc.metrics_prometheus()
        for gauge in ("kv_pages_pages_total", "kv_pages_pages_free",
                      "kv_pages_pages_shared"):
            assert gauge in text, f"{gauge} missing from exposition"
    finally:
        svc.close()


# ------------------------------------------------ verify_cost_ratio shape --


def test_verify_cost_ratio_shape_scaling(tiny):
    from llm_based_apache_spark_optimization_tpu.engine.speculative import (
        infer_weight_bits,
        verify_cost_ratio,
    )
    from llm_based_apache_spark_optimization_tpu.models.configs import (
        BENCH_1B,
        DUCKDB_NSQL_7B,
    )

    # Backward compatible: no shape inputs -> the 1B-anchored line.
    assert verify_cost_ratio(8) == pytest.approx(1.6)
    assert verify_cost_ratio(0) == 1.0
    # The anchor shape maps to itself.
    assert verify_cost_ratio(8, cfg=BENCH_1B, weight_bits=16) == \
        pytest.approx(1.6)
    # 7B: unembed is a smaller share of the weight stream -> cheaper
    # marginal window cost -> lower ratio at the same draft.
    r7 = verify_cost_ratio(8, cfg=DUCKDB_NSQL_7B, weight_bits=16)
    assert 1.0 <= r7 < 1.6
    # int4 weights shrink the FIXED stream -> the window is relatively
    # more expensive than at bf16.
    assert verify_cost_ratio(8, cfg=DUCKDB_NSQL_7B, weight_bits=4) > r7
    # floor: never below a vanilla step
    assert verify_cost_ratio(0, cfg=DUCKDB_NSQL_7B, weight_bits=4) == 1.0

    cfg, params = tiny
    assert infer_weight_bits(params) == 32  # f32 test tree
    from llm_based_apache_spark_optimization_tpu.ops.quant import (
        quantize_params,
    )

    assert infer_weight_bits(quantize_params(params)) == 8


# ------------------------------------- pressure relief (ISSUE 10) ----------


def test_allocator_withhold_shrinks_effective_pool():
    """kv:pressure seam: withheld pages stay on the free list (partition
    invariant intact) but are not grantable; lifting the pressure returns
    them."""
    a = PageAllocator(8, 16)
    a.withhold(5)
    assert a.pages_free == 8 and a.pages_available == 3
    assert a.can_alloc(3) and not a.can_alloc(4)
    assert a.alloc(4) is None
    got = a.alloc(3)
    assert len(got) == 3 and a.pages_available == 0
    a.check()  # withheld pages never violate the free/ref partition
    a.withhold(0)
    assert a.pages_available == 5
    a.release(got)
    assert a.pages_free == 8
    with pytest.raises(ValueError):
        a.withhold(-1)
    # counters surface in stats()
    a.note_preempt()
    a.note_evictions(2)
    a.note_spill(3)
    a.note_restore(3)
    st = a.stats()
    assert st["preemptions"] == 1 and st["evictions"] == 2
    assert st["spilled_pages"] == 3 and st["restored_pages"] == 3
    assert st["pages_withheld"] == 0


def test_allocator_randomized_preempt_restore_evict_cow_cycles(rng):
    """ISSUE-10 property test: interleaved admit/preempt/restore/evict/
    COW/withhold cycles — the free-list/refcount partition holds at every
    step, no page leaks or double-frees, and refcounts come back EXACT
    after every spill-restore cycle (spilled == restored, the resumed
    slot owns exactly as many pages as it spilled)."""
    a = PageAllocator(16, 8)
    slots = {}    # slot id -> list of exclusively owned pages
    parked = {}   # preempted slot id -> page COUNT to restore (spill)
    shared = []   # prefix-cache refs
    next_slot = 0
    for _ in range(800):
        op = rng.integers(0, 7)
        if op == 0:  # admit a request
            n = int(rng.integers(1, 4))
            got = a.alloc(n)
            if got is None:
                assert a.pages_available < n
            else:
                slots[next_slot] = got
                next_slot += 1
        elif op == 1 and slots:  # retire
            sid = list(slots)[int(rng.integers(0, len(slots)))]
            a.release(slots.pop(sid))
        elif op == 2 and slots:  # preempt (spill its pages to "host")
            sid = list(slots)[int(rng.integers(0, len(slots)))]
            pages = slots.pop(sid)
            a.note_spill(len(pages))
            a.note_preempt()
            a.release(pages)
            parked[sid] = len(pages)
        elif op == 3 and parked:  # resume (restore the spilled copy)
            sid = list(parked)[int(rng.integers(0, len(parked)))]
            n = parked[sid]
            got = a.alloc(n)
            if got is not None:
                del parked[sid]
                a.note_restore(n)
                slots[sid] = got
                for pg in got:  # restored pages are exclusive
                    assert a.refcount(pg) == 1
        elif op == 4 and slots:  # publish a prefix ref
            sid = list(slots)[int(rng.integers(0, len(slots)))]
            pg = slots[sid][0]
            a.share([pg])
            shared.append(pg)
        elif op == 5 and shared:  # watermark eviction of an entry
            i = int(rng.integers(0, len(shared)))
            a.release([shared.pop(i)])
            a.note_evictions(1)
        elif op == 6:  # pressure flaps
            a.withhold(int(rng.integers(0, 6)))
        a.check()
        assert a.pages_free + a.pages_in_use == a.num_pages
    a.withhold(0)
    for pages in slots.values():
        a.release(pages)
    for pg in shared:
        a.release([pg])
    a.check()
    assert a.pages_free == a.num_pages  # no leak across the cycles
    # every COMPLETED spill-restore cycle reconciles; parked remainders
    # are spills whose restore never ran (their pages were released).
    assert a.spilled_pages == a.restored_pages + sum(parked.values())


PRESSURE_KW = dict(num_slots=2, decode_chunk=4, prompt_bucket=8,
                   stop_ids=(-1,), max_seq=64, kv_layout="paged",
                   kv_page_size=8)


def _drive(cfg, params, sampling=None, pressure=None, spec=0, **kw):
    """Submit the module PROMPTS at max_new=24 and return (outputs,
    page_stats) — the shared harness for the overcommit parity tests."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )
    from llm_based_apache_spark_optimization_tpu.utils.faults import FAULTS

    if pressure:
        FAULTS.configure(pressure, 0)
    try:
        with ContinuousBatchingScheduler(
            cfg, params, speculative_draft=spec, **PRESSURE_KW, **kw
        ) as s:
            futs = [s.submit(p, max_new_tokens=24,
                             sampling=sampling or SamplingParams(),
                             seed=41 + i)
                    for i, p in enumerate(PROMPTS)]
            out = [f.result(timeout=300) for f in futs]
            stats = dict(s.page_stats)
    finally:
        FAULTS.clear()
    return out, stats


def test_overcommit_ratio_one_reconciles_exact_envelope(tiny):
    """Acceptance: LSOT_KV_OVERCOMMIT=1.0 reproduces today's exact-
    envelope admission — identical outputs AND identical allocator
    accounting (shares/COW/waits), zero preemptions, zero top-ups —
    against a scheduler built without the knob."""
    cfg, params = tiny
    base, base_st = _drive(cfg, params)
    one, one_st = _drive(cfg, params, kv_overcommit=1.0)
    assert one == base
    assert one_st["preemptions"] == 0 and base_st["preemptions"] == 0
    # The full deterministic accounting reconciles (drop the live-pool
    # occupancy snapshot, which races retirement frees).
    for k in ("zero_copy_shares", "cow_copies", "page_waits",
              "pages_total", "spilled_pages", "restored_pages"):
        assert one_st[k] == base_st[k], k


@pytest.mark.chaos
def test_pressure_storm_preempts_and_resumes_token_identical(tiny):
    """The tentpole contract: a kv:pressure storm over an overcommitted
    pool forces >= 1 preemption, and every output — greedy and sampled —
    is token-identical to a pressure-free control (recompute resume)."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )

    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, top_p=0.95)
    for sampling in (None, samp):
        golden, _ = _drive(cfg, params, sampling=sampling)
        out, st = _drive(cfg, params, sampling=sampling,
                         pressure="kv:pressure:1:3",
                         kv_overcommit=0.25, kv_pages=9)
        assert out == golden
        assert st["preemptions"] >= 1
        assert st["pages_withheld"] == 3


@pytest.mark.chaos
def test_pressure_storm_spill_restore_token_identical(tiny):
    """LSOT_KV_SPILL=1: preemption spills host page copies and resume
    restores them instead of recomputing — same token-identical contract,
    and the spill/restore counters reconcile."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )

    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, top_p=0.95)
    golden, _ = _drive(cfg, params, sampling=samp)
    out, st = _drive(cfg, params, sampling=samp,
                     pressure="kv:pressure:1:3",
                     kv_overcommit=0.25, kv_pages=9, kv_spill=True)
    assert out == golden
    assert st["preemptions"] >= 1
    assert st["spilled_pages"] > 0
    assert st["spilled_pages"] == st["restored_pages"]


@pytest.mark.chaos
def test_pressure_storm_speculative_sampled_parity(tiny):
    """Preemption under the speculative loop: sampled + constrained-free
    spec batches preempt and resume token-identical (history rebuild +
    fold_in(key, counts) round-key restore)."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )

    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, top_p=0.95)
    golden, _ = _drive(cfg, params, sampling=samp, spec=3)
    # Spec overshoot is wider than vanilla's: a 12-page pool with 3
    # withheld leaves room for two slots' initial expected envelopes
    # (4 pages each) but not their grown ones — the top-up collision
    # that forces the preemption.
    out, st = _drive(cfg, params, sampling=samp, spec=3,
                     pressure="kv:pressure:1:3",
                     kv_overcommit=0.25, kv_pages=12)
    assert out == golden
    assert st["preemptions"] >= 1


def test_page_wait_deadline_fails_fast_and_feeds_queue_wait(tiny):
    """Satellite: a request parked on pool pages past its deadline fails
    typed DeadlineExceeded (504) instead of waiting forever, and its
    page-wait time lands on the future as queue wait (the histogram
    feed)."""
    from llm_based_apache_spark_optimization_tpu.serve.resilience import (
        DeadlineExceeded,
    )

    cfg, params = tiny
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), max_seq=48, kv_layout="paged", kv_page_size=16,
        kv_pages=3,
    ) as s:
        # One long request holds the whole 3-page pool...
        holder = s.submit([1, 5, 9], max_new_tokens=24)
        # ...and the waiter's envelope cannot be funded while it runs.
        waiter = s.submit([1, 7, 11], max_new_tokens=24, deadline_s=0.3)
        t0 = time.time()
        with pytest.raises(DeadlineExceeded):
            waiter.result(timeout=60)
        # Fail-fast: typed well before the holder finishes its budget,
        # not after.
        assert time.time() - t0 < 30
        assert getattr(waiter, "_lsot_queue_wait", 0) >= 0.25
        holder.result(timeout=300)


def test_watermark_sweep_evicts_prefix_pages_proactively(tiny):
    """Watermark satellite: cached prefix entries are evicted BEFORE an
    allocation fails — free pages recover to the high watermark and the
    evictions counter moves, with no preemption needed."""
    cfg, params = tiny
    prefix = [1] + list(range(5, 28))  # 3 blocks of 8 -> published pages
    prompts = [prefix + [40 + i] for i in range(4)]
    with ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(-1,), max_seq=64, kv_layout="paged", kv_page_size=8,
        kv_pages=8, kv_watermark_low=0.5, kv_watermark_high=0.75,
    ) as s:
        for p in prompts:
            s.submit(p, max_new_tokens=6).result(timeout=300)
        stats = wait_pages_drained(s)
    assert stats["evictions"] > 0
    assert stats["preemptions"] == 0
    # the sweep released the evicted entries' references
    assert stats["pages_in_use"] == 0


@pytest.mark.chaos
def test_chaos_pressure_stage_report_and_determinism():
    """`evalh --chaos` stage 5: the report asserts >=1 preemption, zero
    lost, zero mismatched — and the outcome fields replay exactly for a
    fixed seed (preemption counts are timing-dependent and excluded,
    like restart counts in the crash stage)."""
    from llm_based_apache_spark_optimization_tpu.evalh.chaos import (
        _run_pressure_stage,
    )

    a = _run_pressure_stage(seed=0)
    b = _run_pressure_stage(seed=0)
    assert a["lost"] == 0 and a["mismatched"] == 0
    assert a["preemptions"] >= 1 and a["pressure_fired"]

    def stable(rep):
        return {k: v for k, v in rep.items()
                if k not in ("preemptions", "page_waits", "evictions")}

    assert stable(a) == stable(b)


@pytest.mark.chaos
def test_pressure_storm_mid_prefill_victim_parity(tiny):
    """Review regression: a MID-PREFILL victim (0 generated — first in
    the fewest-generated order) preempted between chunks and re-admitted,
    possibly into its own just-freed slot, must not leave a stale prefill
    queue entry behind (the chunk would run twice and skip real prompt
    KV). Multi-chunk prompts under a storm, outputs token-identical to a
    pressure-free control."""
    cfg, params = tiny
    prompts = [[1] + list(range(5, 5 + 16 + i)) for i in range(4)]  # 3 chunks

    def run(**kw):
        from llm_based_apache_spark_optimization_tpu.utils.faults import (
            FAULTS,
        )

        pressure = kw.pop("pressure", None)
        if pressure:
            FAULTS.configure(pressure, 0)
        try:
            with ContinuousBatchingScheduler(
                cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
                stop_ids=(-1,), max_seq=64, kv_layout="paged",
                kv_page_size=8, **kw
            ) as s:
                futs = [s.submit(p, max_new_tokens=16) for p in prompts]
                out = [f.result(timeout=300) for f in futs]
                stats = dict(s.page_stats)
        finally:
            FAULTS.clear()
        return out, stats

    golden, _ = run()
    out, st = run(pressure="kv:pressure:1:3", kv_overcommit=0.25,
                  kv_pages=10)
    assert out == golden
    assert st["preemptions"] + st["page_waits"] >= 1  # pressure did bite


def test_resume_envelope_clamped_to_slot_row(tiny):
    """Review regression: a resume's prompt (original + committed tokens)
    re-rounds to the next prompt bucket, which can push the raw envelope
    past max_seq — unclamped, the allocation outgrows the device table
    row and the ptab sync crashes the loop. The clamp keeps it inside
    the per-slot virtual row."""
    from concurrent.futures import Future

    from llm_based_apache_spark_optimization_tpu.serve import (
        scheduler as sched_mod,
    )

    cfg, params = tiny
    s = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=16,
        stop_ids=(-1,), max_seq=56, kv_layout="paged", kv_page_size=8,
        kv_overcommit=0.25,
    )
    # 10-token prompt + 23 committed tokens: plen=33 re-buckets to 48,
    # and 48 + reserve + overshoot > max_seq=56 without the clamp.
    req = sched_mod._Request(
        ids=list(range(1, 11)), max_new=24, temperature=0.0, top_p=1.0,
        top_k=0, seed=0, future=Future(),
    )
    req.generated = list(range(3, 26))
    req.resume_pref = len(req.generated)
    assert s._admit(0, req)
    assert len(s._slot_pages[0]) <= s._pages_per_slot
    assert req.page_end <= s._pages_per_slot * 8
    s._free_slot_pages(0)
    s._page_alloc.check()


# ----------------------------------------------- int8 page pool (ISSUE 11) --


def test_page_bytes_prices_kv_dtype(tiny):
    """Satellite: page accounting takes the KV dtype into account — an
    int8 page costs int8-value + f32-scale bytes (not compute-dtype
    bytes), the same HBM budget buys strictly more int8 pages, and
    init_page_pool's actual device arrays reconcile the formula."""
    cfg, _ = tiny
    pb16 = page_bytes(cfg, 16, itemsize=2)
    pb8 = page_bytes(cfg, 16, itemsize=2, kv_quant="int8")
    assert pb8 < pb16
    # Exact layout: 2 sides x L x K x PS x (H int8 bytes + one f32 scale).
    assert pb8 == (2 * cfg.num_layers * cfg.num_kv_heads * 16
                   * (cfg.head_dim + 4))
    budget = 7 * pb16
    assert pages_for_budget(cfg, budget, 16, 2, "int8") > \
        pages_for_budget(cfg, budget, 16, 2)
    pool = init_page_pool(cfg, 5, 16, kv_quant="int8")
    actual = sum(pool[k].nbytes for k in ("kp", "kps", "vp", "vps"))
    assert actual == 5 * pb8
    assert pool["kp"].dtype == jnp.int8
    assert float(pool["kps"].min()) == 1.0  # unwritten scales dequant finite
    with pytest.raises(ValueError, match="kv_quant"):
        page_bytes(cfg, 16, kv_quant="fp4")


@pytest.mark.parametrize("seed", range(4))
def test_allocator_dtype_heterogeneous_page_sizing(tiny, seed):
    """Randomized property (satellite): for random (page_size, kv dtype,
    pool size) geometries, the sizing functions and the real device pool
    agree byte-for-byte, pages_for_budget inverts page_bytes, and the
    allocator's invariants hold at that geometry."""
    cfg, _ = tiny
    rng = np.random.default_rng(100 + seed)
    ps = 8 * int(rng.integers(1, 5))
    kvq = [None, "int8"][int(rng.integers(0, 2))]
    n_pages = int(rng.integers(2, 9))
    itemsize = [2, 4][int(rng.integers(0, 2))]
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    pb = page_bytes(cfg, ps, itemsize, kvq)
    pool = init_page_pool(cfg, n_pages, ps, dtype=dtype, kv_quant=kvq)
    assert sum(a.nbytes for a in pool.values()) == n_pages * pb
    assert pages_for_budget(cfg, n_pages * pb, ps, itemsize, kvq) == n_pages
    assert pages_for_budget(cfg, n_pages * pb - 1, ps, itemsize, kvq) == \
        n_pages - 1
    a = PageAllocator(n_pages, ps)
    held = []
    for _ in range(50):
        op = int(rng.integers(0, 2))
        if op == 0:
            got = a.alloc(int(rng.integers(1, 3)))
            if got is not None:
                held.extend(got)
        elif held:
            a.release([held.pop()])
        a.check()
    for pg in held:
        a.release([pg])
    a.check()
    assert a.pages_free == a.num_pages


def test_pack_prefill_pages_quantized_roundtrip(tiny):
    """pack_prefill_pages(kv_quant='int8') quantizes inside the pack:
    gather + dequantize reproduces the prefill cache within int8
    rounding, and the packed layout carries per-position scales."""
    cfg, _ = tiny
    rng = np.random.default_rng(0)
    b, s, ps, ppr = 3, 24, 16, 4
    cache = {
        "k": jnp.asarray(rng.normal(size=(
            cfg.num_layers, b, cfg.num_kv_heads, s, cfg.head_dim
        )), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(
            cfg.num_layers, b, cfg.num_kv_heads, s, cfg.head_dim
        )), jnp.float32),
    }
    paged = pack_prefill_pages(cache, ps, ppr, kv_quant="int8")
    assert paged["kp"].dtype == jnp.int8
    assert paged["kps"].shape == (cfg.num_layers, b * ppr,
                                  cfg.num_kv_heads, ps)
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        gather_page_scales,
        gather_pages,
    )

    for name, pool, scales in (("k", paged["kp"], paged["kps"]),
                               ("v", paged["vp"], paged["vps"])):
        for layer in range(cfg.num_layers):
            vals = gather_pages(pool[layer], paged["ptab"])     # int8
            sc = gather_page_scales(scales[layer], paged["ptab"])
            deq = vals.astype(np.float32) * np.asarray(sc)[..., None]
            ref = np.asarray(cache[name][layer])
            # Symmetric absmax int8: error bounded by scale/2 per element.
            bound = np.asarray(sc)[..., :s, None] / 2 + 1e-6
            assert (np.abs(deq[:, :, :s] - ref) <= bound).all(), name


@pytest.mark.parametrize("ps,np_tab", [(16, 4), (8, 7)])
def test_quantized_ragged_kernel_matches_reference(rng, ps, np_tab):
    """The int8-pool decode kernel (dequantize inside the DMA'd tiles)
    against the gather + int8-streaming-einsum reference."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        paged_attention_reference_quantized,
        ragged_paged_attention_quantized,
    )

    b, kh, g, h, pool_pages = 3, 2, 2, 8, 11
    n = kh * g
    kp = jnp.asarray(rng.integers(-127, 128, size=(pool_pages, kh, ps, h)),
                     jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, size=(pool_pages, kh, ps, h)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(pool_pages, kh, ps)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(pool_pages, kh, ps)),
                     jnp.float32)
    tab = np.stack([rng.permutation(pool_pages)[:np_tab] for _ in range(b)])
    tab[0, -1] = pool_pages  # unmapped sentinel past the live region
    tab = jnp.asarray(tab, jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, n, h)), jnp.float32)
    s_virt = np_tab * ps
    pos = jnp.asarray([[ps // 2], [s_virt - ps - 1], [s_virt - 1]],
                      jnp.int32)
    kvl = pos[:, 0] + 1
    out_k = ragged_paged_attention_quantized(
        q, kp[None], ks[None], vp[None], vs[None], tab, pos, 0, None, kvl)
    out_r = paged_attention_reference_quantized(q, kp, ks, vp, vs, tab,
                                                pos, None, kvl)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5)
    # kv_lens=0 parks a row, like the bf16 kernel.
    parked = ragged_paged_attention_quantized(
        q, kp[None], ks[None], vp[None], vs[None], tab, pos, 0, None,
        jnp.asarray([0] + [int(x) for x in kvl[1:]], jnp.int32),
    )
    assert float(jnp.abs(parked[0]).max()) == 0.0


@pytest.mark.parametrize("quant", [False, True])
def test_ragged_window_shapes_property(rng, quant):
    """ISSUE 19 satellite: randomized ragged windows — T=1 decode rows,
    verify-window and prefill-chunk rows, a parked row (q_len=0), an
    OOB-sentinel table entry, and kv_lens clamping mid-page of the last
    live page — pin kernel == ragged XLA reference == a per-row
    contiguous einsum loop, bf16-path and int8-pool variants."""
    from llm_based_apache_spark_optimization_tpu.ops.attention import (
        attention_mask,
        gqa_attention,
    )
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        gather_pages,
        paged_attention_reference,
        paged_attention_reference_quantized,
        ragged_paged_attention,
        ragged_paged_attention_quantized,
    )

    b, T, kh, g, h, ps, np_tab, pool_pages = 5, 8, 2, 2, 8, 8, 4, 24
    n = kh * g
    s_virt = np_tab * ps
    if quant:
        kp = jnp.asarray(
            rng.integers(-127, 128, size=(pool_pages, kh, ps, h)), jnp.int8
        )
        vp = jnp.asarray(
            rng.integers(-127, 128, size=(pool_pages, kh, ps, h)), jnp.int8
        )
        ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(pool_pages, kh, ps)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(pool_pages, kh, ps)),
                         jnp.float32)
        # Dequantized twins for the per-row contiguous golden loop.
        kp_f = kp.astype(jnp.float32) * ks[..., None]
        vp_f = vp.astype(jnp.float32) * vs[..., None]
    else:
        kp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)),
                         jnp.float32)
        vp = jnp.asarray(rng.normal(size=(pool_pages, kh, ps, h)),
                         jnp.float32)
        kp_f, vp_f = kp, vp

    for trial in range(2):
        tab = np.stack(
            [rng.permutation(pool_pages)[:np_tab] for _ in range(b)]
        )
        tab[1, -1] = pool_pages  # unmapped sentinel past the live region
        tab = jnp.asarray(tab, jnp.int32)
        # Mixed window shapes per trial: decode row, mid-size windows,
        # one full-T chunk, one parked row (q_len=0, kv_lens=0).
        q_lens = np.asarray(
            [1, int(rng.integers(2, T)), T, int(rng.integers(1, T + 1)), 0],
            np.int32,
        )
        starts = np.asarray(
            [int(rng.integers(0, s_virt - int(ql))) if ql else 0
             for ql in q_lens],
            np.int32,
        )
        pos = np.full((b, T), s_virt - 1, np.int32)  # dead-col junk
        for bi in range(b):
            pos[bi, : q_lens[bi]] = starts[bi] + np.arange(q_lens[bi])
        # Row 3's kv_lens clamps MID-PAGE below its own window top: the
        # kernel must stream the last live page but mask its tail.
        kvl = starts + q_lens
        kvl[3] = max(1, int(kvl[3]) - int(rng.integers(0, min(kvl[3], ps))))
        kvl[4] = 0
        pos, q_lens_j = jnp.asarray(pos), jnp.asarray(q_lens)
        kvl_j = jnp.asarray(kvl)
        q = jnp.asarray(rng.normal(size=(b, T, n, h)), jnp.float32)

        if quant:
            out_k = ragged_paged_attention_quantized(
                q, kp[None], ks[None], vp[None], vs[None], tab, pos, 0,
                None, kvl_j, q_lens_j
            )
            out_r = paged_attention_reference_quantized(
                q, kp, ks, vp, vs, tab, pos, None, kvl_j, q_lens_j
            )
            atol = 2e-5
        else:
            out_k = ragged_paged_attention(
                q, kp[None], vp[None], tab, pos, 0, None, kvl_j, q_lens_j
            )
            out_r = paged_attention_reference(
                q, kp, vp, tab, pos, None, kvl_j, q_lens_j
            )
            atol = 2e-6
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=atol)

        # Per-row contiguous golden loop: each row alone, gathered to a
        # contiguous [s_virt] layout, plain einsum over its live window.
        golden = np.zeros((b, T, n, h), np.float32)
        for bi in range(b):
            ql, kl = int(q_lens[bi]), int(kvl[bi])
            if ql == 0 or kl == 0:
                continue
            kf = gather_pages(kp_f, tab[bi : bi + 1])
            vf = gather_pages(vp_f, tab[bi : bi + 1])
            mask = attention_mask(pos[bi : bi + 1, :ql], s_virt)
            mask = mask & (jnp.arange(s_virt)[None, None, :] < kl)
            o = gqa_attention(q[bi : bi + 1, :ql], kf, vf, mask)
            golden[bi, :ql] = np.asarray(o[0])
        np.testing.assert_allclose(np.asarray(out_k), golden,
                                   atol=5e-5 if quant else 2e-6)
        # Dead columns and the parked row are EXACT zeros in both.
        for bi in range(b):
            ql = int(q_lens[bi])
            assert float(jnp.abs(out_k[bi, ql:]).max() if ql < T
                         else 0.0) == 0.0
            assert float(jnp.abs(out_r[bi, ql:]).max() if ql < T
                         else 0.0) == 0.0
        assert float(jnp.abs(out_k[4]).max()) == 0.0


# The read kernels take the STACKED pool and a layer (the write kernels'
# interface). The one-layer form they replaced is gone, so what it gave on
# `pool[layer]` is kept below as a golden of four values a case.

_STACK_L = 3
_STACK_HEADS = {"gqa32x8_h128": (32, 8, 128), "mha32_h64": (32, 32, 64)}
_STACK_PROBES = ((0, 0, 0, 0), (1, 0, 5, 3), (3, 0, 17, 11), (3, -1, -1, -1))


def _stack_case(heads, quant, ragged):
    """An L=3 stack whose layers hold different values, four rows of mixed
    age behind shuffled page tables: a young row, a row ending mid-page, a
    PARKED row (kv_lens = 0) and a row on its last page. `ragged` gives the
    rows windows of 1..T query columns (`q_lens`); else T = 1."""
    n, kh, h = _STACK_HEADS[heads]
    rng = np.random.default_rng(29)
    b, ps, np_tab, pool_pages, t = 4, 8, 4, 9, (5 if ragged else 1)
    shape = (_STACK_L, pool_pages, kh, ps, h)
    if quant:
        pools = tuple(
            jnp.asarray(a) for _ in range(2) for a in (
                rng.integers(-127, 128, size=shape).astype(np.int8),
                rng.uniform(0.002, 0.02, size=shape[:-1]).astype(np.float32)))
    else:
        pools = tuple(jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                      for _ in range(2))
    tab = np.stack([rng.permutation(pool_pages)[:np_tab] for _ in range(b)])
    tab[0, -1] = pool_pages  # unmapped sentinel past row 0's live region
    q_lens = np.asarray([1, 3, 2, t] if ragged else [1] * b, np.int32)
    kvl = np.asarray([ps // 2 + 1, 2 * ps + 3, 0, np_tab * ps], np.int32)
    pos = np.full((b, t), np_tab * ps - 1, np.int32)  # dead-column junk
    for bi in range(b):
        top = max(int(kvl[bi]), int(q_lens[bi]))
        pos[bi, :q_lens[bi]] = top - q_lens[bi] + np.arange(q_lens[bi])
    q = jnp.asarray(rng.normal(size=(b, t, n, h)), jnp.bfloat16)
    return (q, pools, jnp.asarray(tab, jnp.int32), jnp.asarray(pos),
            jnp.asarray(kvl), jnp.asarray(q_lens))


# What the parent's one-layer kernel gave on `pool[layer]` at _STACK_PROBES
# (interpret mode, this CPU); the whole outputs were compared bit for bit
# when the form changed. Key: (heads, quant, ragged, layer).
_STACK_GOLDEN = {
    ('gqa32x8_h128', False, False, 0):
        [-0.10205078125, 0.361328125, 0.046875, -0.1201171875],
    ('gqa32x8_h128', False, False, 2):
        [0.18359375, 0.921875, -0.1416015625, -0.0830078125],
    ('gqa32x8_h128', False, True, 0):
        [-0.10205078125, 0.275390625, -0.08349609375, 0.228515625],
    ('gqa32x8_h128', False, True, 2):
        [0.18359375, 0.078125, -0.294921875, 0.373046875],
    ('gqa32x8_h128', True, False, 0):
        [0.37890625, 0.2119140625, -0.2734375, 0.5390625],
    ('gqa32x8_h128', True, False, 2):
        [-0.09521484375, 0.4453125, -0.236328125, -0.33203125],
    ('gqa32x8_h128', True, True, 0):
        [0.37890625, 0.197265625, -0.11083984375, -0.197265625],
    ('gqa32x8_h128', True, True, 2):
        [-0.09521484375, 0.54296875, -0.0859375, -0.1201171875],
    ('mha32_h64', False, False, 0):
        [0.294921875, -0.0537109375, -0.039794921875, 0.095703125],
    ('mha32_h64', False, False, 2):
        [0.10546875, -0.365234375, -0.3359375, -0.1923828125],
    ('mha32_h64', False, True, 0):
        [0.294921875, -0.232421875, -0.0654296875, 0.345703125],
    ('mha32_h64', False, True, 2):
        [0.10546875, 0.1875, -0.55859375, -0.12353515625],
    ('mha32_h64', True, False, 0):
        [0.006683349609375, 0.13671875, -0.107421875, -0.07568359375],
    ('mha32_h64', True, False, 2):
        [0.515625, -0.1220703125, 0.490234375, 0.1796875],
    ('mha32_h64', True, True, 0):
        [0.006683349609375, -0.12109375, -0.1103515625, -0.1484375],
    ('mha32_h64', True, True, 2):
        [0.515625, 0.1435546875, 0.1591796875, -0.1396484375],
}


@pytest.mark.parametrize("layer", [0, _STACK_L - 1])
@pytest.mark.parametrize("ragged", [False, True], ids=["T1", "ragged"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads", sorted(_STACK_HEADS))
def test_read_kernel_on_the_stack_is_the_reference_on_the_layer(
        heads, quant, ragged, layer):
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        paged_attention_reference,
        paged_attention_reference_quantized,
        ragged_paged_attention,
        ragged_paged_attention_quantized,
    )

    q, pools, tab, pos, kvl, q_lens = _stack_case(heads, quant, ragged)
    kernel, reference = (
        (ragged_paged_attention_quantized,
         paged_attention_reference_quantized) if quant
        else (ragged_paged_attention, paged_attention_reference))
    out = np.asarray(
        kernel(q, *pools, tab, pos, layer, None, kvl, q_lens), np.float32)
    ref = np.asarray(reference(
        q, *(p[layer] for p in pools), tab, pos, None, kvl, q_lens),
        np.float32)
    # bf16 outputs of O(1) values; another layer's pool would be O(1) off.
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=2e-2)
    assert np.abs(out[2]).max() == 0.0                     # the parked row
    for bi in range(out.shape[0]):                         # dead columns
        assert np.abs(out[bi, int(q_lens[bi]):]).sum() == 0.0
    others = [l for l in range(_STACK_L) if l != layer]
    assert all(np.abs(out - np.asarray(kernel(
        q, *pools, tab, pos, l, None, kvl, q_lens), np.float32)).max() > 0.1
        for l in others)
    probes = [float(out[probe]) for probe in _STACK_PROBES]
    assert probes == _STACK_GOLDEN[heads, quant, ragged, layer]


@pytest.mark.parametrize("layer", [0, _STACK_L - 1])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_sharded_read_kernel_on_the_stack_matches_single(quant, layer):
    """The shard_map wrappers pass the stack through with the layer axis
    unsharded and the KV-head axis over tp: same output as one device."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        ragged_paged_attention,
        ragged_paged_attention_quantized,
        sharded_ragged_paged_attention,
        sharded_ragged_paged_attention_quantized,
    )
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    q, pools, tab, pos, kvl, q_lens = _stack_case("gqa32x8_h128", quant, True)
    single, sharded = (
        (ragged_paged_attention_quantized,
         sharded_ragged_paged_attention_quantized) if quant
        else (ragged_paged_attention, sharded_ragged_paged_attention))
    mesh = make_mesh(dp=1, tp=4, devices=jax.devices()[:4])
    np.testing.assert_array_equal(
        np.asarray(sharded(mesh, q, *pools, tab, pos, layer, None, kvl,
                           q_lens), np.float32),
        np.asarray(single(q, *pools, tab, pos, layer, None, kvl, q_lens),
                   np.float32))


def test_fused_page_write_matches_reference(rng):
    """The fused Pallas page-write kernel (tentpole): bit-identical to
    the XLA scatter-through-table reference — including dropped sentinel
    rows and past-the-row positions — for the bf16 and int8-quantizing
    variants."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        fused_page_write,
        fused_page_write_quantized,
        paged_write_reference,
        paged_write_reference_quantized,
    )

    L, P, kh, ps, h, b, t, np_tab = 2, 9, 2, 8, 8, 3, 3, 4
    layer = 1
    kp = jnp.asarray(rng.normal(size=(L, P, kh, ps, h)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, kh, ps, h)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
    tab = np.stack([rng.permutation(P)[:np_tab] for _ in range(b)])
    tab[2, :] = P  # row 2 fully unmapped (parked slot)
    tab = jnp.asarray(tab, jnp.int32)
    # Row 1's final position runs past the virtual row: must DROP.
    positions = jnp.asarray(
        [[0, 1, 2], [np_tab * ps - 2, np_tab * ps - 1, np_tab * ps],
         [5, 6, 7]], jnp.int32)
    okp, ovp = fused_page_write(kp, vp, k_new, v_new, positions, tab, layer)
    np.testing.assert_array_equal(
        np.asarray(okp),
        np.asarray(paged_write_reference(kp, k_new, positions, tab, layer)),
    )
    np.testing.assert_array_equal(
        np.asarray(ovp),
        np.asarray(paged_write_reference(vp, v_new, positions, tab, layer)),
    )
    # Parked row 2 wrote nothing anywhere.
    np.testing.assert_array_equal(np.asarray(okp[0]), np.asarray(kp[0]))

    kq = jnp.zeros((L, P, kh, ps, h), jnp.int8)
    ksq = jnp.ones((L, P, kh, ps), jnp.float32)
    vq = jnp.zeros((L, P, kh, ps, h), jnp.int8)
    vsq = jnp.ones((L, P, kh, ps), jnp.float32)
    outs = fused_page_write_quantized(
        kq, ksq, vq, vsq, k_new, v_new, positions, tab, layer)
    refs = paged_write_reference_quantized(
        kq, ksq, vq, vsq, k_new, v_new, positions, tab, layer)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


@pytest.mark.parametrize("case", ["leading_parked", "dead_columns",
                                  "all_parked", "page_crossing"])
def test_fused_page_write_block_runs(rng, case):
    """The write kernels move whole pages through the pipeline, and every
    grid cell — dropped ones included — maps a page block (`_block_coords`).
    The shapes of window that decide whether a dropped cell can clobber a
    fresh write: a parked row AHEAD of the first live cell, dead columns
    past `q_lens` between two live rows, a launch with no live cell at
    all, and consecutive positions that cross from one page into the
    next. Bit-identical to the reference scatter in each, both variants."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        fused_page_write,
        fused_page_write_quantized,
        paged_write_reference,
        paged_write_reference_quantized,
    )

    L, P, kh, ps, h, b, t, np_tab = 2, 16, 2, 8, 8, 4, 6, 3
    # Rows own their write pages exclusively (the scheduler's
    # copy-on-write sweep; the kernels' contract).
    tab = rng.permutation(P)[: b * np_tab].reshape(b, np_tab)
    starts, q_lens = np.array([0, 3, 9, 17]), None
    if case == "leading_parked":
        tab[0, :] = P
    elif case == "dead_columns":
        q_lens = jnp.asarray([2, 0, 6, 1], jnp.int32)
    elif case == "all_parked":
        tab[:, :] = P
    elif case == "page_crossing":
        starts = np.array([5, 6, 13, 20])  # 24 = past row 3's last page
    tab = jnp.asarray(tab, jnp.int32)
    positions = jnp.asarray(starts[:, None] + np.arange(t), jnp.int32)
    kp = jnp.asarray(rng.normal(size=(L, P, kh, ps, h)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(L, P, kh, ps, h)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
    got = fused_page_write(kp, vp, k_new, v_new, positions, tab, 1,
                           q_lens=q_lens)
    want = (paged_write_reference(kp, k_new, positions, tab, 1, q_lens),
            paged_write_reference(vp, v_new, positions, tab, 1, q_lens))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if case == "all_parked":
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(kp))

    from llm_based_apache_spark_optimization_tpu.ops.quant import quantize_kv

    k8, v8 = quantize_kv(kp), quantize_kv(vp)
    pools = (k8["q8"], k8["s"], v8["q8"], v8["s"])
    got = fused_page_write_quantized(*pools, k_new, v_new, positions, tab, 1,
                                     q_lens=q_lens)
    want = paged_write_reference_quantized(*pools, k_new, v_new, positions,
                                           tab, 1, q_lens)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_engine_paged_int8_tracks_bf16_and_matches_contiguous_int8(tiny):
    """The documented accuracy contract (tolerance grid): int8 paged
    greedy decode agrees with bf16 paged on most tokens (quant noise may
    flip near-ties; >= 0.7 agreement like the contiguous int8 grid), and
    is TOKEN-IDENTICAL to contiguous int8 — same per-position quantize
    math, different storage layout."""
    cfg, params = tiny
    golden = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                             kv_layout="paged", kv_page_size=8) \
        .generate(PROMPTS, max_new_tokens=8)
    out_q = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                            kv_layout="paged", kv_page_size=8,
                            kv_quant="int8") \
        .generate(PROMPTS, max_new_tokens=8)
    assert all(len(o) == 8 for o in out_q)
    agree = sum(a == b for go, oo in zip(golden, out_q)
                for a, b in zip(go, oo))
    total = sum(len(o) for o in golden)
    assert agree / total >= 0.7, f"only {agree}/{total} tokens agree"
    out_qc = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                             kv_quant="int8") \
        .generate(PROMPTS, max_new_tokens=8)
    assert out_q == out_qc


def test_scheduler_paged_int8_parity_mixed_constrained_speculative(tiny):
    """Acceptance: greedy paged-int8 scheduler output matches paged-bf16
    within the documented tolerance on MIXED constrained/speculative
    batches — and matches the engine's contiguous-int8 greedy decode
    exactly (same quantize math through all three programs: prefill,
    decode, spec-decode)."""
    from llm_based_apache_spark_optimization_tpu.constrain import (
        get_constraint,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    cfg, params = tiny
    tok = ByteTokenizer()
    cm = get_constraint("spark_sql", tok, (2,))
    budget = max(30, cm.min_new_tokens)
    reqs = [
        ([1, 5, 9], None, 8),
        (tok.encode("SELECT", add_bos=True), cm, budget),
        ([1, 3, 4, 8, 10, 11, 12, 13, 14], None, 8),
        (tok.encode("SELECT c", add_bos=True), cm, budget),
    ]

    def run(**kw):
        with ContinuousBatchingScheduler(
            cfg, params, num_slots=3, decode_chunk=4, prompt_bucket=8,
            stop_ids=(2,), speculative_draft=3, **kw
        ) as s:
            futs = [s.submit(ids, max_new_tokens=mn, constraint=c)
                    for ids, c, mn in reqs]
            return [f.result(timeout=300) for f in futs]

    bf16 = run(kv_page_size=16)
    q8 = run(kv_page_size=16, kv_quant="int8")
    q8c = contiguous_greedy(cfg, params, reqs, (2,), kv_quant="int8")
    assert q8 == q8c  # layout-independent quantize math, token-identical
    # Tolerance vs bf16: same-length-or-stop outputs, mostly agreeing
    # tokens (constrained rows stay inside the grammar either way).
    agree = sum(a == b for go, oo in zip(bf16, q8)
                for a, b in zip(go, oo))
    total = sum(min(len(a), len(b)) for a, b in zip(bf16, q8))
    assert agree / max(1, total) >= 0.7


@pytest.mark.chaos
def test_scheduler_paged_int8_spill_restore_token_identical(tiny):
    """Satellite: LSOT_KV_SPILL host page copies serialize the
    quantization SCALES beside the int8 pages — a preempted request's
    spill→restore resume is token-identical under an int8 pool, and the
    spill/restore counters reconcile."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )

    cfg, params = tiny
    samp = SamplingParams(temperature=0.8, top_p=0.95)
    golden, _ = _drive(cfg, params, sampling=samp, kv_quant="int8")
    out, st = _drive(cfg, params, sampling=samp,
                     pressure="kv:pressure:1:3",
                     kv_overcommit=0.25, kv_pages=9, kv_spill=True,
                     kv_quant="int8")
    assert out == golden
    assert st["preemptions"] >= 1
    assert st["spilled_pages"] > 0
    assert st["spilled_pages"] == st["restored_pages"]
    assert st["kv_quant"] == "int8"


def test_page_stats_reports_true_int8_capacity(tiny):
    """Satellite: /metrics serving.kv_pages reports the KV dtype and the
    TRUE per-page bytes, and an HBM budget buys ~2x the int8 pages."""
    cfg, params = tiny
    budget = page_bytes(cfg, 16, itemsize=4) * 8  # 8 f32 pages' worth
    kw = dict(num_slots=2, prompt_bucket=8, stop_ids=(-1,), max_seq=48,
              kv_layout="paged", kv_page_size=16,
              kv_hbm_budget_bytes=budget)
    s16 = ContinuousBatchingScheduler(cfg, params, **kw)
    s8 = ContinuousBatchingScheduler(cfg, params, kv_quant="int8", **kw)
    st16, st8 = s16.page_stats, s8.page_stats
    assert st16["kv_quant"] == "" and st8["kv_quant"] == "int8"
    assert st8["page_bytes"] < st16["page_bytes"]
    assert st8["pages_total"] > st16["pages_total"]
    # The reported page_bytes reconcile the pool's actual device arrays.
    assert st8["page_bytes"] * st8["pages_total"] == \
        sum(a.nbytes for a in s8._cache)


# ------------------------------------------- lane-packed pool (ISSUE 33) --
# A head narrower than the 128-lane tile is stored `f` heads a row
# (engine/paged_kv.lane_pack, ops/lanepack.py): [L, P, K/f, page, f*H].
# Everything below holds the packed pool to the LOGICAL one — the read to
# `paged_attention_reference` on the logical pool and to the same kernel
# on it; the writes bit for bit.

import dataclasses  # noqa: E402

from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (  # noqa: E402
    export_pages,
    import_pages,
    lane_pack,
)
from llm_based_apache_spark_optimization_tpu.ops.lanepack import (  # noqa: E402
    pack_cache,
    pack_heads,
    unpack_cache,
)


def _widths(num_heads, num_kv_heads, head_dim):
    from llm_based_apache_spark_optimization_tpu.models import TINY

    return dataclasses.replace(TINY, num_heads=num_heads,
                               num_kv_heads=num_kv_heads, head_dim=head_dim)


@pytest.mark.parametrize("heads,kv_quant,tp,want", [
    ((4, 4, 64), None, 1, 2),       # MHA at head 64: two heads a row
    ((8, 2, 64), None, 1, 2),       # GQA 8/2 at head 64
    ((32, 32, 64), None, 1, 2),     # SmolLM2-1.7B
    ((32, 8, 64), None, 4, 2),      # Llama-3.2-1B, K/f = 4 over tp = 4
    ((8, 4, 32), None, 1, 4),       # four heads of 32 a row
    ((32, 8, 128), None, 1, 1),     # Mistral-7B: a full row already
    ((4, 4, 64), "int8", 1, 1),     # an int8 pool keeps its scales a head
    ((4, 4, 16), None, 1, 1),       # the benchmark's rehearsal widths
    ((4, 2, 8), None, 1, 1),        # TINY
    ((6, 3, 64), None, 1, 1),       # an odd number of KV heads
    ((8, 4, 64), None, 4, 1),       # K/f = 2 would not divide over tp = 4
    ((8, 4, 64), None, 2, 2),
    ((4, 4, 48), None, 1, 1),       # 128 is no multiple of the head
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_lane_pack_decides_the_stored_shape(heads, kv_quant, tp, want):
    cfg = _widths(*heads)
    assert lane_pack(cfg, kv_quant, tp) == want
    pool = jax.eval_shape(
        lambda: init_page_pool(cfg, 5, 16, jnp.bfloat16, kv_quant, tp))
    kh, h = cfg.num_kv_heads, cfg.head_dim
    assert pool["kp"].shape == pool["vp"].shape == (
        cfg.num_layers, 5, kh // want, 16, want * h)
    if want == 1:  # today's pool, to the shape
        assert pool["kp"].shape == (cfg.num_layers, 5, kh, 16, h)
    if kv_quant:
        assert pool["kps"].shape == (cfg.num_layers, 5, kh, 16)
    # Packing moves bytes and buys none: the budget's pages are the same.
    assert 2 * np.prod(pool["kp"].shape) * (1 if kv_quant else 2) + (
        8 * np.prod(pool["kps"].shape) if kv_quant else 0) == 5 * page_bytes(
        cfg, 16, 2, kv_quant)


@pytest.mark.parametrize("f,kh,h", [(2, 4, 64), (2, 2, 64), (4, 8, 32),
                                    (1, 8, 128)])
def test_lane_pack_then_unpack_is_the_identity(rng, f, kh, h):
    x = jnp.asarray(rng.normal(size=(2, 3, kh, 8, h)), jnp.float32)
    packed = pack_cache(x, f)
    assert packed.shape == (2, 3, kh // f, 8, f * h)
    np.testing.assert_array_equal(np.asarray(unpack_cache(packed, f)),
                                  np.asarray(x))
    # packed[..., j, s, i*H + d] == logical[..., f*j + i, s, d]
    j, i, s, d = kh // f - 1, f - 1, 5, h - 3
    assert packed[1, 2, j, s, i * h + d] == x[1, 2, f * j + i, s, d]
    # A position's fresh K/V packs by a reshape to the same row.
    fresh = x[:, :, :, s, :]                                # [.., K, H]
    np.testing.assert_array_equal(np.asarray(pack_heads(fresh, f)),
                                  np.asarray(packed[:, :, :, s, :]))


_PACK_HEADS = {"mha4x4_h64": (4, 4, 64), "gqa8x2_h64": (8, 2, 64)}
_PACK_L, _PACK_P, _PACK_PS, _PACK_NP = 3, 20, 8, 4


def _pack_case(rng, heads, case):
    """(q, logical pools, table, positions, kv_lens, q_lens, window)."""
    n, kh, h = _PACK_HEADS[heads]
    b, t = 4, (1 if case == "T1" else 3)
    ps, np_tab = _PACK_PS, _PACK_NP
    pools = tuple(
        jnp.asarray(rng.normal(size=(_PACK_L, _PACK_P, kh, ps, h)),
                    jnp.bfloat16) for _ in range(2))
    tab = np.stack([rng.permutation(_PACK_P)[:np_tab] for _ in range(b)])
    kvl = np.asarray([ps // 2 + 1, 2 * ps + 3, 3 * ps, np_tab * ps], np.int32)
    q_lens = np.full((b,), t, np.int32)
    window = None
    if case == "ragged":        # q_lens short of T
        q_lens = np.asarray([1, 3, 2, 3], np.int32)
    elif case == "parked":      # kv_lens = 0 parks a row
        kvl[2] = 0
    elif case == "sliding":
        window = ps + 3
    elif case == "unmapped":    # a sentinel past the live pages
        tab[0, 1:] = _PACK_P
        tab[1, 3] = _PACK_P
    pos = np.full((b, t), np_tab * ps - 1, np.int32)
    for bi in range(b):
        top = max(int(kvl[bi]), int(q_lens[bi]))
        pos[bi, :q_lens[bi]] = top - q_lens[bi] + np.arange(q_lens[bi])
    q = jnp.asarray(rng.normal(size=(b, t, n, h)), jnp.bfloat16)
    return (q, pools, jnp.asarray(tab, jnp.int32), jnp.asarray(pos),
            jnp.asarray(kvl), jnp.asarray(q_lens), window)


@pytest.mark.parametrize("case", ["T1", "ragged", "parked", "sliding",
                                  "unmapped"])
@pytest.mark.parametrize("heads", sorted(_PACK_HEADS))
def test_packed_read_is_the_reference_on_the_logical_pool(rng, heads, case):
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        paged_attention_reference,
        ragged_paged_attention,
    )

    q, pools, tab, pos, kvl, q_lens, window = _pack_case(rng, heads, case)
    packed = tuple(pack_cache(p, 2) for p in pools)
    assert packed[0].shape[-1] == 128
    layer = 1
    out = np.asarray(ragged_paged_attention(
        q, *packed, tab, pos, layer, window, kvl, q_lens), np.float32)
    ref = np.asarray(paged_attention_reference(
        q, *(p[layer] for p in pools), tab, pos, window, kvl, q_lens),
        np.float32)
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=2e-2)
    # The zeros a spread query row adds to the f32 scores are exact, so
    # the packed kernel is the logical kernel up to the order of an f32
    # sum (a bf16 ulp at the most, here none); the reference handed the
    # packed pool unpacks it and is the logical reference to the bit.
    np.testing.assert_allclose(out, np.asarray(ragged_paged_attention(
        q, *pools, tab, pos, layer, window, kvl, q_lens), np.float32),
        rtol=2**-7, atol=1e-3)
    np.testing.assert_array_equal(ref, np.asarray(paged_attention_reference(
        q, *(p[layer] for p in packed), tab, pos, window, kvl, q_lens),
        np.float32))
    for bi in range(out.shape[0]):                         # dead columns
        assert np.abs(out[bi, int(q_lens[bi]):]).sum() == 0.0
    if case == "parked":
        assert np.abs(out[2]).max() == 0.0
    assert np.abs(out - np.asarray(ragged_paged_attention(
        q, *packed, tab, pos, 0, window, kvl, q_lens), np.float32)).max() > .1


@pytest.mark.parametrize("path", ["kernel", "xla"])
@pytest.mark.parametrize("heads", sorted(_PACK_HEADS))
def test_packed_write_is_the_logical_write(rng, heads, path):
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        fused_page_write,
        paged_write_reference,
    )

    _, kh, h = _PACK_HEADS[heads]
    L, P, ps, b, t, np_tab = 2, 16, 8, 4, 6, 3
    tab = rng.permutation(P)[: b * np_tab].reshape(b, np_tab)
    tab[1, :] = P                                   # a parked row
    tab = jnp.asarray(tab, jnp.int32)
    q_lens = jnp.asarray([6, 6, 2, 5], jnp.int32)   # dead columns
    positions = jnp.asarray(
        np.array([5, 0, 13, 20])[:, None] + np.arange(t), jnp.int32)
    kp, vp = (jnp.asarray(rng.normal(size=(L, P, kh, ps, h)), jnp.float32)
              for _ in range(2))
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, t, kh, h)), jnp.float32)
                    for _ in range(2))
    want = (paged_write_reference(kp, k_new, positions, tab, 1, q_lens),
            paged_write_reference(vp, v_new, positions, tab, 1, q_lens))
    pk, pv = pack_cache(kp, 2), pack_cache(vp, 2)
    if path == "kernel":
        got = fused_page_write(pk, pv, k_new, v_new, positions, tab, 1,
                               q_lens=q_lens)
    else:
        got = (paged_write_reference(pk, k_new, positions, tab, 1, q_lens),
               paged_write_reference(pv, v_new, positions, tab, 1, q_lens))
    for g, w in zip(got, want):
        assert g.shape == (L, P, kh // 2, ps, 2 * h)
        np.testing.assert_array_equal(np.asarray(unpack_cache(g, 2)),
                                      np.asarray(w))
    assert np.abs(np.asarray(want[0]) - np.asarray(kp)).max() > 0


def test_pack_prefill_pages_lane_packed(rng):
    """The engines' prefill -> pool hand-off stores what `init_page_pool`
    would: the packed pool is the plain pool, packed."""
    cache = {n: jnp.asarray(rng.normal(size=(2, 3, 4, 20, 64)), jnp.float32)
             for n in ("k", "v")}
    plain = pack_prefill_pages(cache, 8, 4)
    packed = pack_prefill_pages(cache, 8, 4, pack=2)
    assert packed["kp"].shape == (2, 12, 2, 8, 128)
    for n in ("kp", "vp"):
        np.testing.assert_array_equal(np.asarray(unpack_cache(packed[n], 2)),
                                      np.asarray(plain[n]))
    np.testing.assert_array_equal(np.asarray(packed["ptab"]),
                                  np.asarray(plain["ptab"]))


def test_import_pages_refuses_another_stored_shape(rng):
    cfg = _widths(4, 4, 64)
    packed = init_page_pool(cfg, 6, 8, jnp.float32)
    plain = {n: jnp.zeros((cfg.num_layers, 6, 4, 8, 64), jnp.float32)
             for n in ("kp", "vp")}
    assert packed["kp"].shape == (cfg.num_layers, 6, 2, 8, 128)
    src = tuple(jnp.asarray(rng.normal(size=packed["kp"].shape), jnp.float32)
                for _ in range(2))
    blob = export_pages(src, [4, 1])
    assert blob[0].shape == (cfg.num_layers, 2, 2, 8, 128)
    out = import_pages((packed["kp"], packed["vp"]), [0, 5], blob)
    np.testing.assert_array_equal(np.asarray(out[0][:, 5]),
                                  np.asarray(src[0][:, 1]))
    with pytest.raises(ValueError) as e:
        import_pages((plain["kp"], plain["vp"]), [0, 5], blob)
    assert "(2, 2, 2, 8, 128)" in str(e.value)
    assert "(2, 6, 4, 8, 64)" in str(e.value)


def test_sharded_read_kernel_takes_a_lane_packed_stack():
    """Under tp the packed head axis shards like the plain one (`lane_pack`
    packs only where K/f still divides over tp): each device reads `f` off
    its own shard, and the output is one device's."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        ragged_paged_attention,
        sharded_ragged_paged_attention,
    )
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    q, pools, tab, pos, kvl, q_lens = _stack_case("mha32_h64", False, True)
    packed = tuple(pack_cache(p, 2) for p in pools)        # K/f = 16
    mesh = make_mesh(dp=1, tp=4, devices=jax.devices()[:4])
    np.testing.assert_array_equal(
        np.asarray(sharded_ragged_paged_attention(
            mesh, q, *packed, tab, pos, 1, None, kvl, q_lens), np.float32),
        np.asarray(ragged_paged_attention(
            q, *packed, tab, pos, 1, None, kvl, q_lens), np.float32))
