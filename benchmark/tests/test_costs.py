"""The operation/byte functions against numbers worked by hand from the
published sizes of both configurations."""
import pytest

import costs as shared
import spec

MISTRAL = spec.load_json("configs", "mistral-7b-int8.json")
SMOL = spec.load_json("configs", "smollm2-1.7b-bf16.json")
costs = spec.family(MISTRAL, "costs")  # both are of one family


def test_mistral_by_hand():
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three of 4096x14336
    per_layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256
    assert per_layer == 218_103_808
    assert costs.block_matmul_params(MISTRAL) == per_layer
    assert costs.matmul_flops_per_token(MISTRAL) == 2 * 32 * per_layer
    assert costs.head_flops(MISTRAL) == 2 * 32000 * 4096
    # KV of one token: 32 layers x 2 x 8 heads x 128 x 2 bytes = 128 KiB
    assert costs.kv_bytes_per_token(MISTRAL) == 131_072
    assert MISTRAL["serving"]["kv_bytes_per_token"] == 131_072
    # int8 blocks 6.98 GB + f32 scales + bf16 head: 6.99 GiB of blocks (PR 21)
    blocks = 32 * per_layer
    scales = 4 * 32 * (4096 + 2 * 1024 + 4096 + 2 * 14336 + 4096)
    head = 2 * 32000 * 4096
    norms = 2 * (2 * 32 * 4096 + 4096)
    assert costs.weight_bytes_per_step(MISTRAL) == blocks + scales + head + norms
    assert blocks / 2**30 == pytest.approx(6.5, abs=0.01)
    # attention at 1000 keys: 4 x 32 layers x 32 heads x 128 x 1000
    assert costs.attention_flops(MISTRAL, 1000) == 4 * 32 * 32 * 128 * 1000


def test_smollm2_by_hand():
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 67_108_864
    assert costs.block_matmul_params(SMOL) == per_layer
    # MHA 32 x 64: 24 x 2 x 32 x 64 x 2 bytes = 192 KiB a token
    assert costs.kv_bytes_per_token(SMOL) == 196_608
    assert SMOL["serving"]["kv_bytes_per_token"] == 196_608
    w = costs.weight_bytes_per_step(SMOL)
    assert w == 2 * 24 * per_layer + 2 * 49152 * 2048 + 2 * (2 * 24 * 2048 + 2048)
    assert w / 2**30 == pytest.approx(3.19, abs=0.01)


def test_pool_tokens_are_the_budget():
    for cfg in (MISTRAL, SMOL):
        sv = cfg["serving"]
        pages = int(sv["kv_hbm_gb"] * 2**30) // (sv["kv_bytes_per_token"] * sv["page_size"])
        assert sv["pool_tokens"] == pages * sv["page_size"]


def test_prefill_attention_positions():
    # positions 1..4 attend to 1+2+3+4 keys; after 2 reused: 3+4
    assert shared.prefill_attention_positions(0, 4) == 10
    assert shared.prefill_attention_positions(2, 2) == 7


def test_kernel_costs_by_hand():
    import layers

    ctx = layers.Context(cell=None, peaks={}, requests=[], server_log={},
                         flight=[], flight_traced=[], metrics_t0={}, metrics_t1={})
    ops, by = ctx.kernel("ragged_paged_attention").cost(MISTRAL, 5000.0, 8.0)
    assert ops == 4 * 32 * 128 * 5000
    assert by == 2 * 8 * 128 * 2 * 5000 + 2 * 8 * 32 * 128 * 2
    ops, by = ctx.kernel("flash_gqa_attention").cost(SMOL, 1e6, 2000.0, 128)
    assert ops == 4 * 32 * 64 * 1e6
    assert by == 2 * 32 * 64 * 2 * 1e6 / 128 + 2 * 2000 * 32 * 64 * 2
    ops, by = ctx.kernel("fused_page_write").cost(MISTRAL, 8.0, 64)
    assert ops == 0 and by == 2 * 2 * 8 * 8 * 64 * 128 * 2


def test_unknown_device_kind_is_an_error():
    assert shared.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        shared.peaks("TPU v9")
    with pytest.raises(KeyError):
        shared.peaks("cpu")
