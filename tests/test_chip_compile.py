"""What only the chip's own compiler can say, kept as tests.

The TPU compiler is installed here and compiles for a chip that is
DESCRIBED, not attached (`/opt/skills/guides/on-chip-measurement` §2): the
Pallas kernels of the serving path, at Mistral-7B widths, either lower
through Mosaic for a v5e or raise what the chip would raise. Interpret mode
— what every other kernel test runs — cannot see a misaligned DMA slice, an
op Mosaic has no lowering for, or a kernel that outgrows VMEM; PR 11 and
PR 19 shipped kernels that passed every interpret-mode test and were refused
here. A compile that passes is not a chip run: nothing executes, so these
say nothing about results or times (`chip_smoke.py` compares results, on the
chip).

Also here: the tier-1 rehearsal of `chip_smoke.py` itself, and the compile-
cache helper every entry point shares.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from llm_based_apache_spark_optimization_tpu.models import init_params
from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
    init_page_pool,
)
from llm_based_apache_spark_optimization_tpu.models.configs import (
    MISTRAL_7B,
    LlamaConfig,
)
from llm_based_apache_spark_optimization_tpu.models.llama import forward
from llm_based_apache_spark_optimization_tpu.ops import pallas as K
from llm_based_apache_spark_optimization_tpu.ops.pallas import (
    dispatch,
    paged_attention,
)
from llm_based_apache_spark_optimization_tpu.ops.pallas.int4mm import int4_matmul
from llm_based_apache_spark_optimization_tpu.utils import jaxenv

REPO = Path(__file__).resolve().parent.parent

N, KH, H = MISTRAL_7B.num_heads, MISTRAL_7B.num_kv_heads, MISTRAL_7B.head_dim
D, F = MISTRAL_7B.hidden_size, MISTRAL_7B.intermediate_size
WINDOW = MISTRAL_7B.sliding_window
L, P, PS, NP, B = 2, 128, 64, 16, 8   # a small pool; page size = the default
BF, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
# SmolLM2-1.7B's widths (benchmark/configs/smollm2-1.7b-bf16.json): MHA
# 32/32 at head 64, whose pool is stored two heads a 128-lane row
# (engine/paged_kv.lane_pack): [L, P, 16, 64, 128].
SMOL = LlamaConfig(
    name="smollm2-widths", vocab_size=49152, hidden_size=2048,
    intermediate_size=8192, num_layers=24, num_heads=32, num_kv_heads=32,
    head_dim=64, rope_theta=130000.0, max_seq_len=2048, tie_embeddings=True)
H64, K64, W64 = SMOL.head_dim, SMOL.num_kv_heads // 2, 2 * SMOL.head_dim


@pytest.fixture(scope="module")
def chip():
    """Shape constructor for arrays on one chip of a described v5e host,
    with the persistent compile cache off around the module: such a compile
    would be written to the cache but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _read(t, quantized):
    """Ragged paged read of one layer of the stacked pool, window of T
    query rows."""
    def build(S):
        q, tab, pos = S((B, t, N, H), BF), S((B, NP), I32), S((B, t), I32)
        if not quantized:
            pool = S((L, P, KH, PS, H), BF)
            return (lambda q, k, v, tab, pos: K.ragged_paged_attention(
                q, k, v, tab, pos, 1, WINDOW, interpret=False),
                (q, pool, pool, tab, pos))
        pool, scale = S((L, P, KH, PS, H), I8), S((L, P, KH, PS), F32)
        return (lambda q, k, ks, v, vs, tab, pos:
                K.ragged_paged_attention_quantized(
                    q, k, ks, v, vs, tab, pos, 1, WINDOW, interpret=False),
                (q, pool, scale, pool, scale, tab, pos))
    return build


def _write(t, quantized):
    """Fused page write into the stacked pool at a static layer."""
    def build(S):
        new, pos, tab = S((B, t, KH, H), BF), S((B, t), I32), S((B, NP), I32)
        if not quantized:
            pool = S((L, P, KH, PS, H), BF)
            return (lambda kp, vp, k, v, pos, tab: K.fused_page_write(
                kp, vp, k, v, pos, tab, 1, interpret=False),
                (pool, pool, new, new, pos, tab))
        pool, scale = S((L, P, KH, PS, H), I8), S((L, P, KH, PS), F32)
        return (lambda kp, ks, vp, vs, k, v, pos, tab:
                K.fused_page_write_quantized(
                    kp, ks, vp, vs, k, v, pos, tab, 1, interpret=False),
                (pool, scale, pool, scale, new, new, pos, tab))
    return build


def _flash(b, t, s):
    """Flash attention over a contiguous row view (prefill / T=1 decode)."""
    def build(S):
        kv = S((b, KH, s, H), BF)
        return (lambda q, k, v, pos: K.flash_gqa_attention(
            q, k, v, pos, WINDOW, interpret=False),
            (S((b, t, N, H), BF), kv, kv, S((b, t), I32)))
    return build


def _read64(t):
    """The ragged read of a lane-packed head-64 pool (two heads a row)."""
    def build(S):
        pool = S((L, P, K64, PS, W64), BF)
        return (lambda q, k, v, tab, pos: K.ragged_paged_attention(
            q, k, v, tab, pos, 1, None, interpret=False),
            (S((B, t, N, H64), BF), pool, pool, S((B, NP), I32),
             S((B, t), I32)))
    return build


def _write64(t):
    """The fused write of head-64 slivers into the lane-packed pool."""
    def build(S):
        pool, new = S((L, P, K64, PS, W64), BF), S((B, t, N, H64), BF)
        return (lambda kp, vp, k, v, pos, tab: K.fused_page_write(
            kp, vp, k, v, pos, tab, 1, interpret=False),
            (pool, pool, new, new, S((B, t), I32), S((B, NP), I32)))
    return build


def _flash64(b, t, s):
    """Flash attention over the lane-packed row views of batched prefill."""
    def build(S):
        kv = S((b, K64, s, W64), BF)
        return (lambda q, k, v, pos: K.flash_gqa_attention(
            q, k, v, pos, None, interpret=False),
            (S((b, t, N, H64), BF), kv, kv, S((b, t), I32)))
    return build


def _int4(rows, n_in, n_out):
    def build(S):
        return (lambda x, q4, s4: int4_matmul(x, q4, s4, interpret=False),
                (S((rows, n_in), BF), S((n_in // 2, n_out), jnp.uint8),
                 S((n_in // 128, n_out), F32)))
    return build


# The largest window the kernel's own bound admits, for both head layouts
# the registry serves (GQA 32/8 and MHA 32/32 fold differently).
_T_MAX = paged_attention._MAX_QROWS // N

CASES = {
    "read_bf16_T1": _read(1, False),
    "read_bf16_T16": _read(16, False),
    "read_int8_T1": _read(1, True),
    "read_int8_T16": _read(16, True),
    "read_bf16_Tmax": _read(_T_MAX, False),
    "read_int8_Tmax": _read(_T_MAX, True),
    "write_bf16_T1": _write(1, False),
    "write_bf16_T32": _write(32, False),
    "write_int8_T1": _write(1, True),
    "write_int8_T32": _write(32, True),
    "flash_prefill_T128": _flash(8, 128, 2048),
    "flash_decode_T1": _flash(8, 1, 2048),
    "read_head64_packed_T1": _read64(1),
    "read_head64_packed_T16": _read64(16),
    "read_head64_packed_Tmax": _read64(_T_MAX),
    "write_head64_packed_T1": _write64(1),
    "write_head64_packed_T32": _write64(32),
    "flash_head64_packed_prefill_T128": _flash64(4, 128, 2048),
    "flash_head64_packed_decode_T1": _flash64(4, 1, 2048),
    "int4_matmul_gate": _int4(8, D, F),
    "int4_matmul_down": _int4(8, F, D),
    "int4_matmul_prefill_rows": _int4(512, D, D),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, args = CASES[name](chip)
    compiled = jax.jit(fn).lower(*args).compile()
    # Lowered through Mosaic, not interpreted into plain XLA ops.
    assert "tpu_custom_call" in compiled.as_text()


def test_read_window_bound_is_the_compilers(chip):
    """`_MAX_QROWS` was sized by argument (512 rows PER KV HEAD) and the
    compiler disagreed: with all KV heads folded into the cell, MHA 32/32
    runs out of VMEM long before that. The bound now counts rows over all
    heads, compiles at the bound (`read_*_Tmax` above, and here for MHA),
    and twice the bound is refused by the wrapper, not by Mosaic."""
    t = paged_attention._MAX_QROWS // N
    q, tab, pos = chip((B, t, N, H), BF), chip((B, NP), I32), chip((B, t), I32)
    pool, scale = chip((L, P, N, PS, H), I8), chip((L, P, N, PS), F32)  # MHA
    jax.jit(lambda q, k, ks, v, vs, tab, pos:
            K.ragged_paged_attention_quantized(
                q, k, ks, v, vs, tab, pos, 1, None, interpret=False)
            ).lower(q, pool, scale, pool, scale, tab, pos).compile()
    with pytest.raises(ValueError, match="folded rows"):
        K.ragged_paged_attention(
            jnp.zeros((1, 2 * t, N, H), BF), jnp.zeros((1, 4, KH, PS, H), BF),
            jnp.zeros((1, 4, KH, PS, H), BF), jnp.zeros((1, 2), I32),
            jnp.zeros((1, 2 * t), I32), 0)


@pytest.mark.parametrize("base,quantized", [
    (MISTRAL_7B, False), (MISTRAL_7B, True), (SMOL, False)],
    ids=["bf16", "int8", "smollm2_head64"])
def test_decode_step_reads_the_pool_in_place(chip, monkeypatch, base,
                                             quantized):
    """The decode path of `forward` over an L=2 paged cache at Mistral
    widths, and at SmolLM2's: both sides lower through Mosaic, and the
    program makes no copy of a layer's pool. At head 64 the pool is the
    one `init_page_pool` stores, two heads a 128-lane row; stored with a
    minor axis of 64 XLA kept it with the page axis minor and converted K
    and V whole, in and out, around the Mosaic calls (four copies of pool
    shape and 8 GiB of temporaries for a 2.5 GiB pool at 24 layers). A Mosaic call cannot take a strided view of the
    stacked loop carry, so a read kernel handed `pool[l]` made XLA
    materialize the slice, every layer of every step (a quarter to a half
    of a decode step's device time on the chip). The pool is sized like a
    served one in this: far too large for the compiler to stage in fast
    memory (at 128 pages it does, in slices of a layer), and a layer's K or
    V by far the largest buffer the program could make. So both the text
    (no result of that shape) and the temporaries (under one such buffer)
    say whether a layer is copied."""
    pages = 1024
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)  # compiled kernels
    cfg = dataclasses.replace(base, num_layers=L)
    params = jax.tree.map(
        lambda a: chip(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.key(0), dtype=BF)))
    cache = {n: chip(a.shape, a.dtype) for n, a in jax.eval_shape(
        lambda: init_page_pool(cfg, pages, PS, BF,
                               "int8" if quantized else None)).items()}
    pool = cache["kp"]
    kh, h = pool.shape[2], pool.shape[4]
    assert pool.shape == ((L, pages, 16, PS, 128) if base is SMOL
                          else (L, pages, KH, PS, H))
    cache["ptab"] = chip((B, NP), I32)

    def step(params, cache, tokens, positions, kv_lens):
        return forward(cfg, params, tokens, positions, cache,
                       attn_impl="pallas", kv_lens=kv_lens)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, chip((B, 1), I32), chip((B, 1), I32), chip((B,), I32)
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * L
    # No result of a layer's pool or of the whole pool (in any layout)
    # but the donated pool's own parameters and the aliased custom calls.
    one_layer = re.compile(rf"= \w+\[(1,)?{pages},{kh},{PS},{h}\]")
    assert not [ln[:160] for ln in text.splitlines() if one_layer.search(ln)]
    whole = re.compile(rf"= \w+\[{L},{pages},{kh},{PS},{h}\]\S* "
                       r"(copy|transpose|bitcast-convert|fusion)\(")
    assert not [ln[:160] for ln in text.splitlines() if whole.search(ln)]
    layer_bytes = pages * kh * PS * h * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes / 2


def test_chip_smoke_rehearsal():
    """`chip_smoke.py --rehearse`: the whole smoke — kernels against their
    references, the app's assembly over HTTP, every assertion — at the TINY
    shape on the CPU. And without the switch there is no CPU continuation:
    non-zero exit, no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py"),
                        "--rehearse"], env=env, capture_output=True,
                       text=True, timeout=600, cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines() if x.startswith("{")]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": lines[-1]["device"]["count"]}}
    phases = {x["phase"]: x for x in lines[:-1]}
    assert all(c["ok"] for c in phases["kernels"]["checks"].values())
    assert phases["requests"]["programs_built"] == 0
    assert phases["requests"]["prefix_cache"]["hits"] > 0

    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=str(REPO))
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_placement(monkeypatch, placed_from_outside):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache is placed from
    outside and no code sets another (JAX reads the variable itself);
    where it is not, every entry point lands on ONE fixed, git-ignored
    directory inside the checkout (a directory that moves never hits)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    if placed_from_outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert jaxenv.place_compile_cache() == "/somewhere/else"
        assert calls == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
        assert jaxenv.place_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
        ignored = (REPO / ".gitignore").read_text().splitlines()
        assert ".jax_cache/" in ignored
