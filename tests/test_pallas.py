"""Pallas flash-attention kernel vs the XLA einsum golden reference.

Runs the real kernel logic through the Pallas interpreter on CPU (same code
path the TPU compiles), comparing against `ops.attention.gqa_attention` for
prefill and decode shapes, GQA grouping, sliding windows, ragged KV blocks,
and end-to-end generate parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_based_apache_spark_optimization_tpu.ops.attention import (
    attention_mask,
    gqa_attention,
)
from llm_based_apache_spark_optimization_tpu.ops.pallas import (
    flash_gqa_attention,
    set_attention_impl,
    sharded_flash_gqa_attention,
)


def _ref_and_flash(b, t, s, n, kh, h, *, window=None, block_kv=512, seed=0):
    key = jax.random.key(seed)
    kq, kk, kv, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, t, n, h), jnp.float32)
    k = jax.random.normal(kk, (b, kh, s, h), jnp.float32)
    v = jax.random.normal(kv, (b, kh, s, h), jnp.float32)
    # Absolute positions: contiguous runs starting at a random per-batch
    # offset, like a mid-decode cache read.
    starts = jax.random.randint(kp, (b,), 0, max(1, s - t + 1))
    positions = starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    ref = gqa_attention(q, k, v, attention_mask(positions, s, window))
    out = flash_gqa_attention(
        q, k, v, positions, window, block_kv=block_kv, interpret=True
    )
    return np.asarray(ref), np.asarray(out)


@pytest.mark.parametrize(
    "b,t,s,n,kh,h",
    [
        (2, 8, 8, 4, 2, 16),     # prefill, GQA g=2
        (1, 1, 32, 4, 4, 16),    # decode, MHA
        (3, 1, 24, 8, 2, 8),     # decode, GQA g=4
        (2, 4, 20, 6, 3, 32),    # chunked prefill over longer cache
    ],
)
@pytest.mark.slow
def test_flash_matches_einsum(b, t, s, n, kh, h):
    ref, out = _ref_and_flash(b, t, s, n, kh, h)
    np.testing.assert_allclose(ref, out, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_ragged_kv_blocks():
    # S=20 with block_kv=8 -> 3 blocks, last one ragged: out-of-range slots
    # must be masked, not read as garbage.
    ref, out = _ref_and_flash(2, 2, 20, 4, 2, 16, block_kv=8)
    np.testing.assert_allclose(ref, out, rtol=2e-5, atol=2e-5)


def test_flash_multiblock_online_softmax():
    # Several full KV blocks exercise the running max/denominator rescale.
    ref, out = _ref_and_flash(1, 4, 64, 4, 2, 16, block_kv=16, seed=3)
    np.testing.assert_allclose(ref, out, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_sliding_window():
    ref, out = _ref_and_flash(2, 4, 32, 4, 2, 16, window=8, block_kv=8)
    np.testing.assert_allclose(ref, out, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 2), (2, 1)])
@pytest.mark.slow
def test_sharded_flash_matches_einsum(dp, tp):
    """shard_map-wrapped kernel under a dp×tp mesh == unsharded einsum.

    This is the TP serving path (BASELINE configs 4/5): KV heads sharded over
    tp, batch over dp, kernel running per-device in interpret mode.
    """
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    b, t, s, n, kh, h = 4, 2, 16, 8, 4, 16
    mesh = make_mesh(dp=dp, sp=1, tp=tp, devices=jax.devices()[: dp * tp])
    key = jax.random.key(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, n, h), jnp.float32)
    k = jax.random.normal(kk, (b, kh, s, h), jnp.float32)
    v = jax.random.normal(kv, (b, kh, s, h), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(4, 4 + t, dtype=jnp.int32)[None], (b, t))
    ref = gqa_attention(q, k, v, attention_mask(positions, s, None))
    out = sharded_flash_gqa_attention(mesh, q, k, v, positions, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_generate_parity_sharded_pallas_vs_xla(tiny_model):
    """Whole generate loop on a dp×tp mesh: flash == einsum token-for-token."""
    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    cfg, params = tiny_model
    mesh = make_mesh(dp=2, sp=1, tp=2, devices=jax.devices()[:4])
    prompts = [[1, 7, 11, 2], [1, 5]]
    try:
        set_attention_impl("xla")
        ref = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                              mesh=mesh).generate(prompts, max_new_tokens=6)
        set_attention_impl("pallas")
        out = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8,
                              mesh=mesh).generate(prompts, max_new_tokens=6)
    finally:
        set_attention_impl("auto")
    assert ref == out


@pytest.mark.slow
def test_generate_parity_pallas_vs_xla(tiny_model):
    """Whole generate loop: flash path produces the same tokens as einsum."""
    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine

    cfg, params = tiny_model
    prompts = [[1, 7, 11, 2], [1, 5]]
    # No cache_clear needed: the resolved impl is part of the generate-fn
    # cache key, so flipping set_attention_impl() compiles a fresh fn.
    try:
        set_attention_impl("xla")
        eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
        ref = eng.generate(prompts, max_new_tokens=6)
        set_attention_impl("pallas")
        eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
        out = eng.generate(prompts, max_new_tokens=6)
    finally:
        set_attention_impl("auto")
    assert ref == out


def test_flash_truncated_streaming_identical(monkeypatch=None):
    """The truncated-streaming invariant (VERDICT r2 next #3): with kv_lens
    bounding each row, output must be IDENTICAL whether the cache tail
    beyond kv_lens holds real data, huge garbage, or anything else — i.e.
    the kernel provably depends on nothing past the live length (the blocks
    it no longer streams)."""
    b, t, s, n, kh, h = 3, 1, 64, 4, 2, 16
    key = jax.random.key(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, n, h), jnp.float32)
    k = jax.random.normal(kk, (b, kh, s, h), jnp.float32)
    v = jax.random.normal(kv, (b, kh, s, h), jnp.float32)
    # Mixed-age decode batch: positions 5, 37, 11 -> kv_lens 6, 38, 12.
    positions = jnp.asarray([[5], [37], [11]], jnp.int32)
    kv_lens = positions[:, 0] + 1

    out_clean = flash_gqa_attention(
        q, k, v, positions, kv_lens=kv_lens, block_kv=16, interpret=True
    )
    # Poison everything beyond each row's live length with huge garbage.
    sl = jnp.arange(s)[None, None, :, None]
    poison = jnp.where(sl >= kv_lens[:, None, None, None], 1e30, 0.0)
    out_poisoned = flash_gqa_attention(
        q, k + poison, v + poison, positions, kv_lens=kv_lens,
        block_kv=16, interpret=True,
    )
    np.testing.assert_array_equal(
        np.asarray(out_clean), np.asarray(out_poisoned)
    )
    # And the bounded output equals the unbounded golden reference.
    ref = gqa_attention(q, k, v, attention_mask(positions, s, None))
    np.testing.assert_allclose(
        np.asarray(out_clean), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_flash_kv_lens_zero_parks_row():
    """kv_lens=0 (a parked continuous-batching slot) must yield zeros and
    touch nothing — the slot pays neither bandwidth nor MXU work."""
    b, t, s, n, kh, h = 2, 1, 32, 4, 2, 16
    key = jax.random.key(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, n, h), jnp.float32)
    k = jax.random.normal(kk, (b, kh, s, h), jnp.float32)
    v = jax.random.normal(kv, (b, kh, s, h), jnp.float32)
    positions = jnp.asarray([[9], [31]], jnp.int32)  # row 1 parked at S-1
    kv_lens = jnp.asarray([10, 0], jnp.int32)

    out = flash_gqa_attention(
        q, k, v, positions, kv_lens=kv_lens, block_kv=8, interpret=True
    )
    # Row 0 matches the golden reference; row 1 is exactly zero.
    ref = gqa_attention(q, k, v, attention_mask(positions, s, None))
    np.testing.assert_allclose(
        np.asarray(out)[0], np.asarray(ref)[0], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_array_equal(
        np.asarray(out)[1], np.zeros_like(np.asarray(out)[1])
    )


@pytest.mark.slow
def test_scheduler_parity_with_pallas_kv_lens(tiny_model):
    """End-to-end: the scheduler under attn impl 'pallas' (which now passes
    active-masked kv_lens) must still match the engine goldens exactly."""
    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    cfg, params = tiny_model
    prompts = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10, 2, 6], [1, 11]]
    set_attention_impl("pallas")
    try:
        golden = [
            InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=8)
            .generate([p], max_new_tokens=5)[0]
            for p in prompts
        ]
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
            stop_ids=(-1,),
        )
        with sched:
            out = sched.generate(prompts, max_new_tokens=5)
        assert out == golden
    finally:
        set_attention_impl("auto")


# ---------------------------------------------------------------------------
# int8-KV decode kernel: int8 HBM streaming stacked with kv_lens bounding.

def _quant_ref_inputs(key, b, n, kh, s, h):
    import jax

    from llm_based_apache_spark_optimization_tpu.ops.quant import quantize_kv

    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (b, 1, n, h), jnp.float32)
    k = jax.random.normal(ks[1], (b, kh, s, h), jnp.float32)
    v = jax.random.normal(ks[2], (b, kh, s, h), jnp.float32)
    kq, vq = quantize_kv(k), quantize_kv(v)
    return q, kq, vq


@pytest.mark.slow
@pytest.mark.parametrize("b,n,kh,s,h,window", [
    (2, 8, 4, 48, 16, None),
    (3, 4, 2, 64, 8, 16),
    (1, 8, 8, 24, 32, None),
])
def test_flash_quantized_matches_dequant_reference(b, n, kh, s, h, window):
    from llm_based_apache_spark_optimization_tpu.ops.attention import (
        attention_mask,
        gqa_attention,
    )
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        flash_gqa_attention_quantized,
    )

    q, kq, vq = _quant_ref_inputs(b * 7 + s, b, n, kh, s, h)
    positions = jnp.asarray([[s - 2 - i] for i in range(b)], jnp.int32)
    out = flash_gqa_attention_quantized(
        q, kq["q8"], kq["s"], vq["q8"], vq["s"], positions, window,
        block_kv=16,
    )
    k_deq = kq["q8"].astype(jnp.float32) * kq["s"][..., None]
    v_deq = vq["q8"].astype(jnp.float32) * vq["s"][..., None]
    ref = gqa_attention(q, k_deq, v_deq, attention_mask(positions, s, window))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_flash_quantized_kv_lens_bounds_streaming():
    """Output depends ONLY on the first kv_lens[b] slots (garbage — NaN! —
    beyond them must not leak), and kv_lens=0 parks a row to zeros."""
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        flash_gqa_attention_quantized,
    )

    b, n, kh, s, h = 2, 4, 2, 64, 8
    q, kq, vq = _quant_ref_inputs(11, b, n, kh, s, h)
    kv_lens = jnp.asarray([24, 0], jnp.int32)
    positions = jnp.asarray([[20], [30]], jnp.int32)
    clean = flash_gqa_attention_quantized(
        q, kq["q8"], kq["s"], vq["q8"], vq["s"], positions,
        kv_lens=kv_lens, block_kv=16,
    )
    # Poison everything at/after each row's kv_len (scales to NaN, values
    # to extreme int8) — a kernel that reads past the bound diverges.
    pos = jnp.arange(s)[None, None, :]
    dead = pos >= kv_lens[:, None, None]
    ks_p = jnp.where(dead, jnp.nan, kq["s"])
    vs_p = jnp.where(dead, jnp.nan, vq["s"])
    k8_p = jnp.where(dead[..., None], jnp.int8(127), kq["q8"])
    v8_p = jnp.where(dead[..., None], jnp.int8(-127), vq["q8"])
    poisoned = flash_gqa_attention_quantized(
        q, k8_p, ks_p, v8_p, vs_p, positions,
        kv_lens=kv_lens, block_kv=16,
    )
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))
    assert np.all(np.asarray(clean)[1] == 0.0)  # parked row: zeros


@pytest.mark.slow
def test_scheduler_kv_quant_pallas_decode_parity():
    """Force the pallas decode impl on an int8-KV scheduler: greedy output
    must equal the einsum-impl scheduler's exactly (same quantized cache
    contents; the kernel is a bandwidth reimplementation, not new math)."""
    import jax

    from llm_based_apache_spark_optimization_tpu.models import TINY, init_params
    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        set_attention_impl,
    )
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    cfg, params = TINY, init_params(TINY, jax.random.key(4), dtype=jnp.float32)
    prompts = [[1, 5, 9, 5, 9, 3], [1, 7, 2, 4], [1, 3, 4, 8, 10, 2, 6]]
    ref = ContinuousBatchingScheduler(
        cfg, params, num_slots=2, prompt_bucket=8, stop_ids=(-1,),
        kv_quant="int8",
    )
    assert ref._decode_impl == "xla"
    with ref:
        golden = ref.generate(prompts, max_new_tokens=8)
    try:
        set_attention_impl("pallas")
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, prompt_bucket=8, stop_ids=(-1,),
            kv_quant="int8",
        )
        assert sched._decode_impl == "pallas"
    finally:
        set_attention_impl("auto")
    with sched:
        out = sched.generate(prompts, max_new_tokens=8)
    assert out == golden


@pytest.mark.slow
def test_flash_quantized_sharded_matches_single(  ):
    """The shard_map wrapper over a dp×tp mesh reproduces the single-device
    kernel (heads/batch shard; scales ride their KV-head axis)."""
    import jax

    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        flash_gqa_attention_quantized,
        sharded_flash_gqa_attention_quantized,
    )
    from llm_based_apache_spark_optimization_tpu.parallel import make_mesh

    b, n, kh, s, h = 4, 8, 4, 32, 8
    q, kq, vq = _quant_ref_inputs(23, b, n, kh, s, h)
    positions = jnp.asarray([[s - 1 - i] for i in range(b)], jnp.int32)
    kv_lens = jnp.asarray([s, 20, 8, 0], jnp.int32)
    single = flash_gqa_attention_quantized(
        q, kq["q8"], kq["s"], vq["q8"], vq["s"], positions, kv_lens=kv_lens,
        block_kv=16,
    )
    mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    sharded = sharded_flash_gqa_attention_quantized(
        mesh, q, kq["q8"], kq["s"], vq["q8"], vq["s"], positions,
        kv_lens=kv_lens, block_kv=16,
    )
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(single),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("b,t,s,n,kh,h,f,window", [
    (2, 1, 32, 4, 4, 64, 2, None),     # decode grid, MHA at head 64
    (2, 8, 32, 8, 2, 64, 2, None),     # prefill grid, GQA 8/2 at head 64
    (2, 4, 40, 4, 4, 64, 2, 12),       # sliding window, ragged last block
    (1, 4, 24, 8, 4, 32, 4, None),     # four heads of 32 a row
], ids=["decode_mha_h64", "prefill_gqa_h64", "window_h64", "prefill_h32"])
def test_flash_takes_a_lane_packed_cache(b, t, s, n, kh, h, f, window):
    """Batched prefill's row views of a lane-packed pool reach the flash
    kernel as `[B, K/f, S, f*H]` (ops/lanepack.py): to the kernel a GQA
    cache of K/f heads of width 128, scored at the TRUE head's scale. The
    zeros a spread query adds are exact; what may differ from the plain
    layout is the order of an f32 sum over 128 lanes for 64."""
    from llm_based_apache_spark_optimization_tpu.ops.lanepack import (
        pack_cache,
    )

    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (b, t, n, h), jnp.float32)
    k = jax.random.normal(kk, (b, kh, s, h), jnp.float32)
    v = jax.random.normal(kv, (b, kh, s, h), jnp.float32)
    positions = (s - t - 1) + jnp.arange(t, dtype=jnp.int32)[None, :] \
        - jnp.arange(b, dtype=jnp.int32)[:, None]
    kv_lens = jnp.max(positions, axis=1) + 1
    plain = flash_gqa_attention(q, k, v, positions, window, kv_lens,
                                block_kv=16, interpret=True)
    pk, pv = pack_cache(k, f), pack_cache(v, f)
    assert pk.shape == (b, kh // f, s, f * h)
    packed = flash_gqa_attention(q, pk, pv, positions, window, kv_lens,
                                 block_kv=16, interpret=True)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(plain),
                               rtol=1e-5, atol=1e-6)
    ref = gqa_attention(q, k, v, attention_mask(positions, s, window))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(packed),
                               rtol=2e-5, atol=2e-5)
