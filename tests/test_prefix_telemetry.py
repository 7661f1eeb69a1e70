"""Prefix-cache telemetry (ISSUE 14): content-addressed registry,
per-request reuse attribution, eviction churn, and the cache-aware
routing feed.

Tier-1 contracts pinned here:

- RECONCILIATION: per-request `tokens_reused` attribution (flight-record
  `prefix_reuse` rows) sums EXACTLY to the scheduler's locked counter
  group (`reused_tokens` == pblock × `blocks_reused`) across a mixed
  shared-prefix batch — and, with page-aligned blocks, to the
  allocator's `zero_copy_shares` delta (hits share pages, never copy
  them).
- EVICTION CHURN: capacity-cap evictions are counted, and a key that
  comes back through publish while still on the evicted ghost counts as
  a REINSERTION (the cache-too-small signal).
- ROUTING FEED: `replica_loads()` exposes each replica's resident digest
  set + hit-rate EWMA, and `SchedulerPool.prefix_affinity(digests)`
  scores the replica that actually holds a request's schema prefix.

All on TINY / CPU f32, greedy, sequential submits (the publish gate is
order-sensitive: seen on request 1, published on 2, hit from 3 on).
"""

import pytest

from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    SchedulerPool,
    prefix_chain_digests,
    prefix_digest,
)


@pytest.fixture(scope="module")
def tiny_model_module():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def make_sched(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)  # pblock = 8
    # Page size == pblock: every cached block is exactly one page, so
    # blocks, pages and page bytes reconcile one to one.
    kw.setdefault("kv_page_size", 8)
    kw.setdefault("stop_ids", (-1,))
    return ContinuousBatchingScheduler(cfg, params, **kw)


def _drive_sequential(sched, prompts, max_new=4):
    for p in prompts:
        sched.generate([p], max_new_tokens=max_new)


def _prefix_rows(sched):
    return [row for rec in sched.flight.snapshot()
            for row in rec.get("prefix_reuse", ())]


SHARED = list(range(3, 27))  # 24 tokens = 3 pblock-8 blocks


def test_reconciliation_paged(tiny_model_module):
    """Mixed shared-prefix batch, page size == pblock so every reused
    block is exactly one page-aligned page: per-request flight
    attribution == locked counters == pblock × blocks_reused, and the
    pure-hit wave's zero_copy_shares delta == reused pages."""
    cfg, params = tiny_model_module
    shared_prompts = [[1] + SHARED + [50 + i] for i in range(4)]
    unrelated = [[2] + list(range(60, 84)) + [99]]  # a genuine miss
    with make_sched(cfg, params, max_seq=64) as sched:
        # Warm phase: request 1 records the prefix, request 2 publishes.
        _drive_sequential(sched, shared_prompts[:2])
        pre = dict(sched.prefix_stats)
        pre_shares = sched.page_stats["zero_copy_shares"]
        pre_rows = len(_prefix_rows(sched))
        # Hit wave: two full-chain hits plus one unrelated miss.
        _drive_sequential(sched, shared_prompts[2:] + unrelated)
        post = dict(sched.prefix_stats)
        post_shares = sched.page_stats["zero_copy_shares"]
        rows = _prefix_rows(sched)[pre_rows:]
        tel = sched.prefix_telemetry

    pb = 8
    d_hits = post["hits"] - pre["hits"]
    d_blocks = post["blocks_reused"] - pre["blocks_reused"]
    d_reused = post["reused_tokens"] - pre["reused_tokens"]
    assert d_hits == 2 and post["misses"] - pre["misses"] == 1
    # Counter-group reconciliation: tokens == blocks × pblock.
    assert d_reused == pb * d_blocks == 48
    # Per-request attribution reconciles exactly with the counters.
    assert sum(r["reused"] for r in rows) == d_reused
    assert [r["reused"] for r in rows] == [24, 24, 0]
    for r in rows:
        assert r["prefilled"] == (26 - r["reused"] if r["reused"] else 26)
        assert r["digest"]
    # The two hits carry the SHARED chain's digest; the miss its own.
    hit_digest = prefix_digest(([1] + SHARED)[: 3 * pb])
    assert [r["digest"] for r in rows[:2]] == [hit_digest, hit_digest]
    assert rows[2]["digest"] != hit_digest
    # Allocator reconciliation: page-aligned hits SHARE pages (one per
    # reused block at page_size == pblock), never copy them.
    assert post_shares - pre_shares == d_blocks
    # Priced savings moved with the hits, and telemetry agrees with the
    # counter group read through the same lock.
    assert tel["prefill_s_saved"] > 0.0
    assert tel["reused_tokens"] == post["reused_tokens"]
    assert tel["resident_bytes"] > 0


def test_trace_span_carries_reuse_attribution(tiny_model_module):
    """A traced request's sched.prefill span carries prefix_digest /
    tokens_reused / tokens_prefilled (the per-request half of the
    attribution contract)."""
    from llm_based_apache_spark_optimization_tpu.utils.tracing import (
        RequestTrace,
    )

    cfg, params = tiny_model_module
    prompts = [[1] + SHARED + [70 + i] for i in range(3)]
    with make_sched(cfg, params, max_seq=64) as sched:
        _drive_sequential(sched, prompts[:2])
        tr = RequestTrace("req-prefix-test")
        sched.submit(prompts[2], max_new_tokens=4,
                     trace=tr).result(timeout=120)
    spans = {s["name"]: s for s in tr.to_dict()["spans"]}
    attrs = spans["sched.prefill"]["attrs"]
    assert attrs["tokens_reused"] == 24
    assert attrs["tokens_prefilled"] == 2
    assert attrs["prefix_digest"] == prefix_digest(prompts[2][:24])


def test_eviction_churn_and_ghost_reinsertion(tiny_model_module):
    """A 2-entry cache under 3 distinct 3-block prefixes churns: cap
    evictions are counted, and re-driving an evicted prefix counts a
    ghost-list REINSERTION when it publishes again."""
    cfg, params = tiny_model_module

    def prompt(base, tail):
        return [1] + list(range(base, base + 24)) + [tail]

    with make_sched(cfg, params, max_seq=64,
                    prefix_cache_blocks=2) as sched:
        for base in (100, 200, 300):
            _drive_sequential(sched, [prompt(base, 90), prompt(base, 91)])
        st = sched.prefix_stats
        assert st["evictions"] > 0
        assert st["cached_blocks"] <= 2
        pre_reinserts = sched.prefix_telemetry["reinserts"]
        # The base=100 chain was evicted; publish it again.
        _drive_sequential(sched, [prompt(100, 92), prompt(100, 93)])
        tel = sched.prefix_telemetry
        assert tel["reinserts"] > pre_reinserts
        # Registry stays bounded and consistent with the allocator's
        # unique-page residency accounting (chained entries overlap on
        # their leading pages — bytes count UNIQUE pages, once).
        from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
            page_bytes,
        )

        reg = sched.prefix_registry()
        assert len(reg["entries"]) <= reg["capacity"]
        assert reg["resident_bytes"] == (
            sched.page_stats["prefix_resident_pages"]
            * page_bytes(cfg, 8, 4, None)
        )
        sched._page_alloc.check()


def test_registry_reuse_distance_and_topk(tiny_model_module):
    """The reuse-distance histogram fills from the admission ring (an
    immediate re-sighting lands in the le-1 bucket) and top_k bounds the
    entry list without touching the summary counters."""
    cfg, params = tiny_model_module
    prompts = [[1] + SHARED + [50 + i] for i in range(4)]
    with make_sched(cfg, params, max_seq=64) as sched:
        _drive_sequential(sched, prompts)
        reg = sched.prefix_registry()
        reg1 = sched.prefix_registry(top_k=1)
    rd = reg["reuse_distance"]
    assert rd.get("inf", 0) == 1      # first sighting inside the ring
    assert rd.get("1", 0) == 3        # back-to-back repeats
    assert len(reg["entries"]) == 3   # the 3-block chain
    # Entries are sorted by token mass; digests only, never token ids.
    assert [e["tokens"] for e in reg["entries"]] == [24, 16, 8]
    # Each entry holds its chain's pages (one per block here), priced at
    # the pool's page bytes.
    from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
        page_bytes,
    )

    assert [e["pages"] for e in reg["entries"]] == [3, 2, 1]
    assert [e["bytes"] for e in reg["entries"]] == [
        n * page_bytes(cfg, 8, 4, None) for n in (3, 2, 1)]
    assert all(isinstance(e["digest"], str) for e in reg["entries"])
    assert len(reg1["entries"]) == 1
    assert reg1["hits"] == reg["hits"]


def test_hit_digest_joins_registry_when_tail_crosses_block(tiny_model_module):
    """When the last whole prompt block crosses the schema boundary
    (tail tokens bleed into it), a HIT still stamps the MATCHED chain's
    digest — joinable against the registry and recurring in the
    reuse-distance ring — not a per-request-unique longest-prefix
    digest."""
    cfg, params = tiny_model_module
    # 34-token prompts: 24 shared + 9-token unique tails; pblock=8, so
    # the longest whole-block prefix (32 tokens) includes 7 tail tokens.
    prompts = [[1] + SHARED + [40 + i] * 9 for i in range(4)]
    with make_sched(cfg, params, max_seq=64) as sched:
        _drive_sequential(sched, prompts)
        rows = _prefix_rows(sched)
        reg = sched.prefix_registry()
    hit_rows = [r for r in rows if r["reused"]]
    assert len(hit_rows) == 2
    matched = prefix_digest(prompts[0][:24])
    assert all(r["digest"] == matched for r in hit_rows)
    assert matched in {e["digest"] for e in reg["entries"]}
    # Consecutive hits on the same schema recur in the ring (the le-1
    # arm), instead of every admission reading as a first sighting.
    assert reg["reuse_distance"].get("1", 0) >= 1


def test_pool_prefix_affinity_and_replica_loads(tiny_model_module):
    """The routing feed: a replica that served the shared prefix scores
    in prefix_affinity; its siblings (which never saw it) do not — and
    replica_loads carries the resident digest set + hit-rate EWMA."""
    cfg, params = tiny_model_module
    pool = SchedulerPool([
        make_sched(cfg, params, max_seq=64),
        make_sched(cfg, params, max_seq=64),
    ])
    prompts = [[1] + SHARED + [80 + i] for i in range(3)]
    with pool:
        # Drive the shared prefix through replica 0 ONLY (direct submits
        # bypass the router, so residency is deterministic).
        _drive_sequential(pool.schedulers[0], prompts)
        digests = prefix_chain_digests(prompts[0], 8)
        scored = pool.prefix_affinity(digests)
        assert scored and scored[0]["replica"] == "r0"
        assert scored[0]["score"] >= 1
        assert all(rec["replica"] != "r1" for rec in scored)
        # Unknown prefixes score nowhere; empty input is a no-op.
        assert pool.prefix_affinity([prefix_digest([9, 9, 9])]) == []
        assert pool.prefix_affinity([]) == []
        loads = {r["replica"]: r for r in pool.replica_loads()}
        assert set(loads["r0"].get("resident_digests", [])) >= set(digests)
        assert loads["r0"]["prefix_hit_rate"] > 0.0
        assert loads["r1"].get("resident_digests", []) == []
        # The lookup left a placement-log event in the pool flight ring.
        events = [r for r in pool._pool_flight.snapshot()
                  if r.get("kind") == "prefix_affinity"]
        assert events and events[-1]["best"] == "r0"
        # Pool prefix_stats sums counters and DERIVES the hit rate.
        st = pool.prefix_stats
        assert st["hits"] >= 1
        assert st["hit_rate"] == round(
            st["hits"] / (st["hits"] + st["misses"]), 4)
        # Pool registry / telemetry are replica-labeled.
        reg = pool.prefix_registry()
        assert {r["replica"] for r in reg["replicas"]} == {"r0", "r1"}
        tel = pool.prefix_telemetry
        assert {r["replica"] for r in tel["replicas"]} == {"r0", "r1"}


def test_prefill_saved_pricing(tiny_model_module):
    """PerfModel.prefill_saved prices a hit at the binding roof of the
    skipped one-row prefill forward — monotone in tokens, zero at zero."""
    cfg, params = tiny_model_module
    sched = make_sched(cfg, params, max_seq=64)
    try:
        assert sched.perf.prefill_saved(0) == (0.0, 0.0)
        f1, s1 = sched.perf.prefill_saved(8)
        f2, s2 = sched.perf.prefill_saved(24)
        assert 0 < f1 < f2 and 0 < s1 < s2
        from llm_based_apache_spark_optimization_tpu.utils.perfmodel import (
            prefill_flops,
        )

        assert f2 == float(prefill_flops(cfg, 1, 24))
    finally:
        sched.shutdown()


def test_digest_stability():
    """Digests are content addresses: stable across calls, sensitive to
    any token change, and chain digests prefix-extend."""
    ids = list(range(40))
    assert prefix_digest(ids) == prefix_digest(list(ids))
    assert prefix_digest(ids) != prefix_digest(ids[:-1] + [99])
    chain = prefix_chain_digests(ids, 16)
    assert chain == [prefix_digest(ids[:16]), prefix_digest(ids[:32])]
    assert prefix_chain_digests(ids[:16], 16) == []  # needs > one block
