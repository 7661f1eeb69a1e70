"""TP/DP sharding correctness on the 8-device virtual CPU mesh (SURVEY.md §4:
the standard way to test pjit/mesh code without real TPU chips)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
from llm_based_apache_spark_optimization_tpu.models import TINY, forward, init_params
from llm_based_apache_spark_optimization_tpu.parallel import (
    make_mesh,
    param_specs,
    shard_params,
    validate_tp,
)


def test_mesh_shape_and_axes():
    mesh = make_mesh(dp=4, tp=2)
    assert mesh.shape == {"dp": 4, "sp": 1, "tp": 2}
    mesh3 = make_mesh(dp=2, sp=2, tp=2)
    assert mesh3.shape == {"dp": 2, "sp": 2, "tp": 2}
    with pytest.raises(ValueError):
        make_mesh(dp=3, tp=2)


def test_validate_tp_rejects_indivisible():
    with pytest.raises(ValueError):
        validate_tp(TINY, 3)  # heads=4, kv=2 not divisible by 3
    validate_tp(TINY, 2)


def test_param_shards_are_partitioned(tiny_model):
    cfg, params = tiny_model
    mesh = make_mesh(dp=4, tp=2)
    sharded = shard_params(params, cfg, mesh)
    wq = sharded["blocks"]["wq"]
    # Column-parallel: last dim split over tp=2.
    shard_shape = wq.addressable_shards[0].data.shape
    assert shard_shape[-1] == wq.shape[-1] // 2
    # Row-parallel wo: contracted dim split.
    wo = sharded["blocks"]["wo"]
    assert wo.addressable_shards[0].data.shape[1] == wo.shape[1] // 2
    # Norms replicated.
    ln = sharded["blocks"]["ln_attn"]
    assert ln.addressable_shards[0].data.shape == ln.shape


def test_specs_tree_matches_param_tree(tiny_model):
    cfg, params = tiny_model
    from jax.sharding import PartitionSpec as P

    specs = param_specs(cfg)
    jax.tree.map(lambda x, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, P))  # raises on mismatch


def test_sharded_forward_matches_unsharded(tiny_model):
    cfg, params = tiny_model
    mesh = make_mesh(dp=4, tp=2)
    sharded = shard_params(params, cfg, mesh)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(3, cfg.vocab_size, size=(4, 8)), jnp.int32
    )
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (4, 8))
    ref, _ = forward(cfg, params, tokens, pos, None)
    got, _ = forward(cfg, sharded, tokens, pos, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_sharded_generate_matches_unsharded(tiny_model):
    cfg, params = tiny_model
    mesh = make_mesh(dp=4, tp=2)
    prompts = [[1, 5, 9], [1, 7], [1, 11, 13, 17], [1, 2, 3]]
    ref = InferenceEngine(cfg, params, prompt_bucket=8).generate(
        prompts, max_new_tokens=6
    )
    got = InferenceEngine(cfg, params, prompt_bucket=8, mesh=mesh).generate(
        prompts, max_new_tokens=6
    )
    assert got == ref


def test_sharded_generate_pads_non_divisible_batch(tiny_model):
    """3 prompts on a dp=4 mesh: batch is padded to dp and sliced back."""
    cfg, params = tiny_model
    mesh = make_mesh(dp=4, tp=2)
    prompts = [[1, 5, 9], [1, 7], [1, 11, 13]]
    ref = InferenceEngine(cfg, params, prompt_bucket=8).generate(
        prompts, max_new_tokens=5
    )
    got = InferenceEngine(cfg, params, prompt_bucket=8, mesh=mesh).generate(
        prompts, max_new_tokens=5
    )
    assert got == ref


def test_multihost_single_process_degenerates():
    """Single-process: init is a no-op, global_mesh == local mesh, primary."""
    from llm_based_apache_spark_optimization_tpu.parallel import (
        global_mesh,
        init_distributed,
        is_primary,
        process_local_batch,
    )

    assert init_distributed() is False  # no coordinator configured
    assert is_primary()
    mesh = global_mesh(dp=4, sp=1, tp=2)
    assert mesh.shape == {"dp": 4, "sp": 1, "tp": 2}
    batch = np.arange(8, dtype=np.int32).reshape(4, 2)
    arr = process_local_batch(batch, mesh)
    assert arr.shape == (4, 2)
    np.testing.assert_array_equal(np.asarray(arr), batch)
    with pytest.raises(ValueError):
        global_mesh(dp=3)


@pytest.mark.slow
def test_vocab_sharded_tables_parity(tiny_model):
    """Embed/unembed tables shard their VOCAB axis over tp
    (specs_for_params): the gather, the logits einsum and sampling must
    agree token-for-token with the single-device engine — for the bf16
    tables AND the int8 per-row quantize_unembed dicts."""
    from llm_based_apache_spark_optimization_tpu.ops.quant import (
        quantize_unembed,
    )
    from llm_based_apache_spark_optimization_tpu.parallel import (
        specs_for_params,
    )
    from jax.sharding import PartitionSpec as P

    cfg, params = tiny_model
    specs = specs_for_params(params, tp=2)
    assert specs["embed"] == P("tp", None)
    prompts = [[1, 5, 9], [1, 7, 2, 4]]
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    for tree in (params, quantize_unembed(params)):
        golden = InferenceEngine(cfg, tree, stop_ids=(-1,), prompt_bucket=8) \
            .generate(prompts, max_new_tokens=6)
        eng = InferenceEngine(cfg, tree, stop_ids=(-1,), prompt_bucket=8,
                              mesh=mesh)
        assert eng.generate(prompts, max_new_tokens=6) == golden


@pytest.mark.slow
def test_sp_sharded_decode_cache_parity(tiny_model):
    """Sequence-parallel decode cache (cache_spec shards slots over sp):
    the capacity lever for long context — an sp-way mesh holds sp x the
    context one chip fits. Greedy output must match the single-device
    engine exactly, bf16 AND int8-KV caches, through prefill (ring over
    sp) and the unrolled decode's in-place sliver writes."""
    cfg, params = tiny_model
    prompts = [[1, 5, 9, 2, 8, 4], [1, 7, 3]]
    mesh = make_mesh(dp=1, sp=2, tp=2, devices=jax.devices()[:4])
    for kvq in (None, "int8"):
        golden = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                                 kv_quant=kvq).generate(prompts,
                                                        max_new_tokens=8)
        eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                              mesh=mesh, kv_quant=kvq)
        assert eng.generate(prompts, max_new_tokens=8) == golden, kvq


def test_tp_sharded_paged_parity_engine_and_scheduler(tiny_model):
    """MULTICHIP parity for the PAGED pool (ISSUE 11), mirroring the
    contiguous tests: on a CPU tp mesh the pool's KV-head axis shards
    over tp (page tables replicated) and greedy output — engine loop AND
    continuous-batching scheduler — is token-identical to the
    single-device paged path, for bf16 and int8 pools alike."""
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    cfg, params = tiny_model
    prompts = [[1, 5, 9], [1, 7], [1, 11, 13, 17], [1, 2, 3]]
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    for kvq in (None, "int8"):
        golden = InferenceEngine(
            cfg, params, stop_ids=(-1,), prompt_bucket=8,
            kv_layout="paged", kv_page_size=8, kv_quant=kvq,
        ).generate(prompts, max_new_tokens=6)
        got = InferenceEngine(
            cfg, params, stop_ids=(-1,), prompt_bucket=8,
            kv_layout="paged", kv_page_size=8, kv_quant=kvq, mesh=mesh,
        ).generate(prompts, max_new_tokens=6)
        assert got == golden, kvq

    def sched(mesh_):
        with ContinuousBatchingScheduler(
            cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
            stop_ids=(-1,), kv_page_size=16, mesh=mesh_,
        ) as s:
            return s.generate(prompts, max_new_tokens=6)

    assert sched(mesh) == sched(None)


@pytest.mark.slow
def test_tp_sharded_paged_speculative_parity(tiny_model):
    """The spec-decode program under mesh + paged (+ int8): the verify
    window's reference gather runs over the tp-sharded pool."""
    cfg, params = tiny_model
    prompts = [[1, 5, 9], [1, 7], [1, 11, 13, 17], [1, 2, 3]]
    mesh = make_mesh(dp=1, tp=2, devices=jax.devices()[:2])
    for kvq in (None, "int8"):
        golden = InferenceEngine(
            cfg, params, stop_ids=(-1,), prompt_bucket=8,
            speculative_draft=4, kv_layout="paged", kv_page_size=8,
            kv_quant=kvq,
        ).generate(prompts, max_new_tokens=6)
        got = InferenceEngine(
            cfg, params, stop_ids=(-1,), prompt_bucket=8,
            speculative_draft=4, kv_layout="paged", kv_page_size=8,
            kv_quant=kvq, mesh=mesh,
        ).generate(prompts, max_new_tokens=6)
        assert got == golden, kvq
