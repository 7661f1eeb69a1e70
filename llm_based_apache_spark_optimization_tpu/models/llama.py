"""Llama-family transformer as a pure-functional JAX model.

This is the in-tree replacement for the GGUF models llama.cpp executes for the
reference app (reference `Flask/app.py:102-107`, `FastAPI/app.py:85-90`): one
parameterized architecture covering duckdb-nsql-7B (Llama-2 shape), Llama-3.2
1B/3B (GQA, tied embeddings, llama3 rope scaling) and Mistral-7B
(sliding window) — see `models/configs.py`.

TPU-first design decisions:

- **Params are a plain pytree** (nested dict of `jax.Array`), not a module
  object: shardings attach via `jax.tree.map` + `NamedSharding`, the same tree
  flows through `jit`/`shard_map`/checkpointing with zero framework friction.
- **Per-layer weights are stacked on a leading [L, ...] axis**. For prefill
  the block stack runs under `jax.lax.scan`: XLA traces ONE block instead of
  L copies, so compile time and program size stay flat as models deepen.
- **Decode (T == 1) unrolls the layer loop instead.** Scanning the KV cache
  through xs/ys costs ~4x the cache size in HBM traffic PER DECODE STEP:
  the xs slice reads a layer's cache, `dynamic_update_slice` copies it, and
  the ys stacking writes it back — measured on v5e (bench-1b, B=32, S=1024)
  decode ran at 17.4 ms/step when weights+cache-read explain only ~4 ms.
  The unrolled loop writes each layer's fresh K/V as a tiny sliver into the
  stacked cache at a STATIC layer index and reads the layer's cache through
  a static slice; every update kills the previous buffer (single liveness
  chain), so XLA updates the cache in place and decode streams only weights
  + live cache. Unrolling costs compile time proportional to L — decode
  traces once per (B, bucket) signature, so the price is paid once.
- **One forward for prefill and decode**: the call is "run T tokens whose
  cache-write starts at per-sequence positions"; T=prompt_len is prefill, T=1
  is decode. Static shapes per (B, T) bucket, no dynamic control flow in jit.
- Matmuls run in the params dtype (bf16 on TPU -> MXU native); softmax, norms
  and rope run in f32; logits return in f32 for stable sampling.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (
    attention_mask,
    gqa_attention,
    gqa_attention_quantized,
)
from ..ops.lanepack import pack_factor, pack_heads, unpack_cache
from ..ops.norm import rms_norm
from ..ops.pallas import (
    flash_gqa_attention,
    flash_gqa_attention_quantized,
    sharded_flash_gqa_attention,
    sharded_flash_gqa_attention_quantized,
)
from ..ops.quant import is_qtensor, mm, mm_stacked
from ..ops.ring_attention import ring_gqa_attention
from ..ops.rope import apply_rope, rope_cos_sin
from .configs import LlamaConfig

Params = Dict[str, jnp.ndarray]

# Cached forwards up to this many tokens take the unrolled layer loop (in-
# place cache slivers); longer ones (prefill) scan — the scan path's per-call
# cache restack amortizes over many tokens, and unrolling a long-T body would
# only grow the program. Covers decode (T=1) and speculative-verify windows.
_UNROLL_MAX_T = 32


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init params with the exact tree structure the weight loader fills.

    Init scale follows the standard 1/sqrt(fan_in) so random-weight smoke
    models produce finite logits at any depth.
    """
    d, f = cfg.hidden_size, cfg.intermediate_size
    nh, kh, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    keys = jax.random.split(key, 9)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    params: Params = {
        "embed": w(keys[0], (cfg.vocab_size, d), d),
        "blocks": {
            "wq": w(keys[1], (L, d, nh * hd), d),
            "wk": w(keys[2], (L, d, kh * hd), d),
            "wv": w(keys[3], (L, d, kh * hd), d),
            "wo": w(keys[4], (L, nh * hd, d), nh * hd),
            "wg": w(keys[5], (L, d, f), d),
            "wu": w(keys[6], (L, d, f), d),
            "wd": w(keys[7], (L, f, d), f),
            "ln_attn": jnp.ones((L, d), dtype),
            "ln_mlp": jnp.ones((L, d), dtype),
        },
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(keys[8], (cfg.vocab_size, d), d)
    return params


def fuse_blocks(params: Params) -> Params:
    """A params variant with same-input projections fused into one matmul:
    wq|wk|wv -> "wqkv" (MHA, equal shapes) or wk|wv -> "wkv" (GQA, where
    wq's out dim differs), and wg|wu -> "wgu".

    Prefill runs 7 medium matmuls per layer; fusing projections that share
    an input (h for QKV, h2 for gate/up) cuts kernel count and widens the
    MXU N dimension — one of the prefill-MFU levers (each output column is
    the same dot product, so results are exact: tests/test_model.py).

    Layout: the fused weight STACKS the projections on a new axis -2 —
    [L, D, C, O] — instead of concatenating out axes. Stacking is what
    makes the fusion tensor-parallel: the O axis shards over tp exactly
    like the unfused weights (parallel/sharding.param_specs) and the C
    split in forward is a device-local index, where a concatenated
    [L, D, C*O] axis would put projection boundaries mid-shard and force a
    reshard at every split. Works on bf16 trees, int8 QTensor trees
    (per-out-channel scales stack to [L, C, O]) and int4 packed trees
    (q4 [L, D/2, C, O] — the kernel flattens the contiguous (C, O) tail).
    """
    blocks = dict(params["blocks"])

    def out_dim(w):
        if is_qtensor(w):
            return w["q8"].shape[-1]
        if isinstance(w, dict) and "q4" in w:
            return w["q4"].shape[-1]
        return w.shape[-1]

    def stack(names):
        ws = [blocks.pop(n) for n in names]
        if is_qtensor(ws[0]):
            return {
                "q8": jnp.stack([w["q8"] for w in ws], axis=-2),
                "s": jnp.stack([w["s"] for w in ws], axis=-2),
            }
        if isinstance(ws[0], dict) and "q4" in ws[0]:
            return {
                "q4": jnp.stack([w["q4"] for w in ws], axis=-2),
                "s4": jnp.stack([w["s4"] for w in ws], axis=-2),
            }
        return jnp.stack(ws, axis=-2)

    if out_dim(blocks["wq"]) == out_dim(blocks["wk"]):  # MHA: one 3-stack
        blocks["wqkv"] = stack(("wq", "wk", "wv"))
    else:  # GQA: K/V share a shape, Q stays its own (wider) matmul
        blocks["wkv"] = stack(("wk", "wv"))
    blocks["wgu"] = stack(("wg", "wu"))
    out = dict(params)
    out["blocks"] = blocks
    return out


def maybe_fuse(params: Params, mesh) -> Params:
    """The engines' shared fuse_matmuls entry. The mesh argument is kept
    for call-site symmetry but no longer gates anything: the stacked fused
    layout TP-shards on its out axis (fuse_blocks docstring), so fusion
    composes with every mesh topology."""
    del mesh
    return fuse_blocks(params)


def split_blocks(params: Params) -> Params:
    """A params variant whose "blocks" is a per-layer LIST of trees (static
    slices of the stacked [L, ...] weights).

    Decode loops pass this to `forward` so the per-layer slices — and any
    layout conversions XLA decides the decode matmuls want — are anchored
    OUTSIDE the `lax.while_loop`/`lax.scan` body and run once per call
    instead of once per token (see forward's unrolled branch). Slices that
    need no layout change stay zero-copy bitcast views of the stacked
    buffer."""
    blocks = params["blocks"]
    n_layers = jax.tree.leaves(blocks)[0].shape[0]
    out = dict(params)
    out["blocks"] = [
        jax.tree.map(lambda a, _l=l: a[_l], blocks) for l in range(n_layers)
    ]
    return out


def _update_cache(cache: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray) -> jnp.ndarray:
    """Write `new` [B, T, K, H] into `cache` [B, K, S, H] at per-batch offsets.

    vmap of dynamic_update_slice lowers to an efficient batched scatter; each
    sequence writes a contiguous [T, H] block per KV head starting at its own
    position along the S axis.
    """
    return jax.vmap(
        lambda c, n, s: lax.dynamic_update_slice(c, n, (0, s, 0))
    )(cache, new.transpose(0, 2, 1, 3), start.astype(jnp.int32))


def _update_scale_layer(
    scales: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray, layer: int
) -> jnp.ndarray:
    """Write per-slot quant scales `new` [B, T, K] into the stacked scale
    tensor [L, B, K, S] at a static layer index and per-batch offsets (the
    int8-KV companion of `_update_cache_layer`; same per-row static-index
    DUS chain, same in-place reasoning)."""
    b = new.shape[0]
    upd = new.transpose(0, 2, 1)  # [B, K, T]
    start = start.astype(jnp.int32)
    for row in range(b):
        scales = lax.dynamic_update_slice(
            scales, upd[row][None, None].astype(scales.dtype),
            (layer, row, 0, start[row]),
        )
    return scales


def _update_cache_layer(
    cache: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray, layer: int
) -> jnp.ndarray:
    """Write `new` [B, T, K, H] into the STACKED cache [L, B, K, S, H] at a
    static layer index and per-batch offsets.

    Used by the unrolled decode path: the update is a tiny sliver and each
    call's result replaces the previous cache value (single liveness chain),
    so XLA performs the write in place instead of copying the layer.

    Expressed as a chain of per-row dynamic_update_slices with STATIC
    (layer, row) indices — only the slot offset is dynamic. Both batched
    alternatives copy the whole cache every call on TPU: a vmapped DUS
    transposes [L, B, ...] to batch-leading layout and back around the
    update (~32 full-cache `copy_bitcast_fusion`s per decode step), and a
    single `lax.scatter` picks a non-standard operand layout that forces a
    full-cache layout-conversion copy per layer. The static-index DUS chain
    is layout-preserving, so XLA aliases every link in place."""
    b = new.shape[0]
    upd = new.transpose(0, 2, 1, 3)[:, None, None]  # [B, 1, 1, K, T, H]
    start = start.astype(jnp.int32)
    for row in range(b):
        cache = lax.dynamic_update_slice(
            cache, upd[row].astype(cache.dtype), (layer, row, 0, start[row], 0)
        )
    return cache


# The paged write path lives in ops/pallas/paged_write.py: an XLA
# reference scatter (`paged_write_reference`, the pre-kernel path
# verbatim — bit-identical CPU/einsum serving) and the fused Pallas
# scatter-through-table kernel the T=1 pallas decode path swaps in
# (`fused_page_write` / the int8-quantizing variant).


def forward(
    cfg: LlamaConfig,
    params: Params,
    tokens: jnp.ndarray,      # [B, T] int32
    positions: jnp.ndarray,   # [B, T] int32 — absolute position of each token
    cache: Optional[Dict[str, jnp.ndarray]] = None,  # {"k","v"}: [L, B, K, S, H]
                              # or paged {"kp","vp": [L, P, K, PS, H],
                              # "ptab": [B, NP] i32} (engine/paged_kv.py);
                              # either may be lane-packed, [.., K/f, S|PS,
                              # f*H] (ops/lanepack.py)
    logit_indices: Optional[jnp.ndarray] = None,  # [B] int32 — unembed only these T-indices
    attn_impl: str = "xla",  # "xla" | "pallas" | "ring"; resolve via ops.pallas.attention_impl
    mesh=None,  # required for attn_impl="ring" (context-parallel prefill)
    kv_lens: Optional[jnp.ndarray] = None,  # [B] i32 — live KV slots per row
                                            # (pallas impl: bounds HBM
                                            # streaming; 0 parks a row)
    q_lens: Optional[jnp.ndarray] = None,   # [B] i32 — live query cols per
                                            # row (paged ragged windows:
                                            # dead cols write nothing and
                                            # read zeros)
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Run T tokens through the stack; returns (logits f32, cache').

    With `cache=None` a transient [B, T] cache is used (pure prefill-only
    forward, e.g. for scoring); with a cache dict, K/V are written at
    `positions[:, 0] + t` and attention reads the full cache buffer.

    `logit_indices=None` returns full [B, T, V] logits. Passing per-sequence
    indices [B] gathers the hidden state *before* the unembed matmul and
    returns [B, 1, V] — during prefill only the last real token's logits are
    ever sampled, and skipping the [B, T, V] unembed saves a T-times-larger
    matmul and its f32 output buffer (V=128k makes this the dominant prefill
    cost at long T).
    """
    b, t = tokens.shape
    emb = params["embed"]
    if is_qtensor(emb):  # ops/quant.quantize_unembed: per-row int8 table
        rows = jnp.take(emb["q8"], tokens, axis=0).astype(jnp.float32)
        x = (rows * jnp.take(emb["s"], tokens, axis=0)[..., None]).astype(
            params["final_norm"].dtype
        )
    else:
        x = jnp.take(emb, tokens, axis=0)  # [B, T, D]
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling)
    start = positions[:, 0]

    quant_cache = cache is not None and "k8" in cache
    paged_cache = cache is not None and "kp" in cache
    if cache is None:
        kv_size = t
    elif quant_cache:
        kv_size = cache["k8"].shape[3]
    elif paged_cache:
        # Virtual contiguous length: logical pages × page size. The table
        # maps logical position p to pool page ptab[b, p // PS], offset
        # p % PS; unmapped entries only ever sit past a row's live length,
        # where causality masks them.
        kv_size = cache["ptab"].shape[1] * cache["kp"].shape[3]
    else:
        kv_size = cache["k"].shape[3]
    # Default is the always-correct einsum path: a bare forward() cannot see
    # whether its inputs are TP-sharded, and the pallas kernel requires
    # unsharded operands (or an explicit shard_map) — callers that know the
    # placement (engine/generate.py) pass the resolved impl explicitly.
    impl = attn_impl
    if impl == "ring" and mesh is None:
        raise ValueError('attn_impl="ring" requires a mesh with an "sp" axis')
    # int8 KV cache: einsum path for any small-T window; the pallas flash
    # kernel additionally supports T=1 decode (flash_gqa_attention_quantized
    # — int8 streaming AND per-row kv_lens bounding stacked).
    if quant_cache and not (
        (impl == "xla" and t <= _UNROLL_MAX_T)
        or (impl == "pallas" and t == 1)
    ):
        raise ValueError(
            "an int8 KV cache needs the einsum impl and the unrolled "
            f"small-T path (T <= {_UNROLL_MAX_T}), or the pallas impl at "
            "T=1 (decode): the prefill scan streams bf16 caches (engine "
            "prefill fills bf16, then quantizes once — engine/generate.py)"
        )
    if paged_cache and not (
        t <= _UNROLL_MAX_T and impl in ("xla", "pallas")
    ):
        raise ValueError(
            "a paged KV cache serves the unrolled small-T path only "
            f"(T <= {_UNROLL_MAX_T}; decode, verify windows, and mixed "
            "ragged prefill+decode rounds): longer prefill runs a "
            "contiguous transient/row cache and packs or scatters its K/V "
            "into pool pages (engine/generate.py, serve/scheduler.py)."
        )
    mask = (
        attention_mask(positions, kv_size, cfg.sliding_window)
        if impl == "xla"
        else None
    )

    nh, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # Lane packing (ops/lanepack.py): a bf16/f32 cache of narrow heads may
    # be stored with f heads a 128-lane row — the paged pool, and the row
    # views batched prefill gathers from it. Its own minor axis says so;
    # f == 1 is the plain layout and every pack/unpack below the identity.
    stored = None if cache is None else cache.get("kp", cache.get("k"))
    f = 1 if stored is None else pack_factor(stored, hd)

    def qkv(p, x):
        h = rms_norm(x, p["ln_attn"], cfg.norm_eps)
        # mm()/mm_stacked() transparently handle int8 QTensors and int4
        # packed trees (ops/quant.py); mesh routes int4 through its
        # shard_map wrapper with the weight's Megatron partition.
        if "wqkv" in p:  # fused MHA tree: one stacked [D, 3, O] matmul
            fused = mm_stacked(h, p["wqkv"], mesh)  # [B, T, 3, O]
            q = fused[..., 0, :].reshape(b, t, nh, hd)
            k = fused[..., 1, :].reshape(b, t, kh, hd)
            v = fused[..., 2, :].reshape(b, t, kh, hd)
        elif "wkv" in p:  # fused GQA tree: Q alone + stacked [D, 2, KO]
            q = mm(h, p["wq"], mesh).reshape(b, t, nh, hd)
            kv = mm_stacked(h, p["wkv"], mesh)  # [B, T, 2, KO]
            k = kv[..., 0, :].reshape(b, t, kh, hd)
            v = kv[..., 1, :].reshape(b, t, kh, hd)
        else:
            q = mm(h, p["wq"], mesh).reshape(b, t, nh, hd)
            k = mm(h, p["wk"], mesh).reshape(b, t, kh, hd)
            v = mm(h, p["wv"], mesh).reshape(b, t, kh, hd)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v

    def attn_mlp(p, x, q, k_full, v_full, k_fresh, v_fresh):
        if impl == "pallas":
            if mesh is not None:
                # Per-device kernel over the tp-sharded KV heads / dp-sharded
                # batch (shard_map); single-device pallas_call otherwise.
                attn = sharded_flash_gqa_attention(
                    mesh, q, k_full, v_full, positions, cfg.sliding_window,
                    kv_lens,
                )
            else:
                attn = flash_gqa_attention(
                    q, k_full, v_full, positions, cfg.sliding_window, kv_lens
                )
        elif impl == "ring":
            # Context-parallel self-attention over the fresh K/V of this call's
            # tokens (ring over the mesh "sp" axis; sequence axis sharded).
            # Correct only for prefill-from-position-0: the cache holds nothing
            # earlier than these tokens, so self-attention == cache attention.
            # K/V are still written to the cache for later decode steps.
            attn = ring_gqa_attention(
                mesh, q, k_fresh, v_fresh, positions,
                sliding_window=cfg.sliding_window,
            )
        else:
            attn = gqa_attention(q, unpack_cache(k_full, f),
                                 unpack_cache(v_full, f), mask)
        return post_attn(p, x, attn)

    def post_attn(p, x, attn):
        x = x + mm(attn.reshape(b, t, nh * hd), p["wo"], mesh, "row")
        h2 = rms_norm(x, p["ln_mlp"], cfg.norm_eps)
        if "wgu" in p:  # fused tree: gate|up stacked in one matmul
            gu = mm_stacked(h2, p["wgu"], mesh)  # [B, T, 2, F]
            g_out, u_out = gu[..., 0, :], gu[..., 1, :]
        else:
            g_out, u_out = mm(h2, p["wg"], mesh), mm(h2, p["wu"], mesh)
        gate = jax.nn.silu(g_out.astype(jnp.float32)).astype(x.dtype)
        x = x + mm(gate * u_out, p["wd"], mesh, "row")
        return x

    def block(x, layer_in):
        p, k_cache, v_cache = layer_in
        q, k, v = qkv(p, x)
        if k_cache is None:
            # Match the cache layout: [B, T, K, H] -> [B, K, T, H].
            k_full, v_full = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            k_out = v_out = None
        else:
            k_full = _update_cache(k_cache, pack_heads(k, f), start)
            v_full = _update_cache(v_cache, pack_heads(v, f), start)
            k_out, v_out = k_full, v_full
        x = attn_mlp(p, x, q, k_full, v_full, k, v)
        return x, (k_out, v_out)

    unroll = t <= _UNROLL_MAX_T and impl != "ring" and cache is not None
    if isinstance(params["blocks"], (list, tuple)) and not unroll:
        raise ValueError(
            f"split_blocks params are only valid for the unrolled decode "
            f"path (T <= {_UNROLL_MAX_T}, cached, non-ring impl); pass the "
            f"stacked tree for prefill/ring/no-cache forwards"
        )
    if cache is None:
        # scan with no cache arrays: feed Nones via a python loop over stacked
        # params is wasteful; instead run scan with dummy empty caches.
        def block_nocache(x, p):
            y, _ = block(x, (p, None, None))
            return y, None
        x, _ = lax.scan(block_nocache, x, params["blocks"])
        new_cache = None
    elif unroll:
        # Decode (and small-T cached forwards, e.g. speculative-verify
        # windows): unrolled layer loop with in-place sliver writes into the
        # stacked cache (static layer indices). Scanning the cache through
        # xs/ys copies each layer's cache several times PER STEP — see the
        # module docstring for the measured cost.
        #
        # `params["blocks"]` may be a pre-sliced per-layer list
        # (split_blocks, used by decode loops): slicing the stacked weights
        # inside a `lax.while_loop` body leaves the layout conversions XLA
        # wants for the attention matmuls inside the loop (its invariant
        # code motion won't hoist buffers that large — profiled ~0.47
        # ms/step of repeated weight re-layout copies); pre-sliced params
        # anchor those conversions outside the loop, once per call.
        blocks = params["blocks"]
        new_cache = dict(cache)
        for l in range(cfg.num_layers):
            p = (blocks[l] if isinstance(blocks, (list, tuple))
                 else jax.tree.map(lambda a, _l=l: a[_l], blocks))
            q, k, v = qkv(p, x)
            if quant_cache:
                # int8 KV: quantize the fresh sliver (absmax over H), write
                # value+scale with the same static-index DUS chains, attend
                # with the int8-streaming einsum
                # (ops/attention.gqa_attention_quantized).
                from ..ops.quant import quantize_kv

                kq = quantize_kv(k)  # values [B, T, K, H], scales [B, T, K]
                vq = quantize_kv(v)
                new_cache["k8"] = _update_cache_layer(
                    new_cache["k8"], kq["q8"], start, l)
                new_cache["ks"] = _update_scale_layer(
                    new_cache["ks"], kq["s"], start, l)
                new_cache["v8"] = _update_cache_layer(
                    new_cache["v8"], vq["q8"], start, l)
                new_cache["vs"] = _update_scale_layer(
                    new_cache["vs"], vq["s"], start, l)
                if impl == "pallas":  # T == 1 (validated above)
                    fn = (sharded_flash_gqa_attention_quantized
                          if mesh is not None
                          else flash_gqa_attention_quantized)
                    args = (mesh,) if mesh is not None else ()
                    attn = fn(
                        *args, q, new_cache["k8"][l], new_cache["ks"][l],
                        new_cache["v8"][l], new_cache["vs"][l], positions,
                        cfg.sliding_window, kv_lens,
                    )
                else:
                    attn = gqa_attention_quantized(
                        q, new_cache["k8"][l], new_cache["ks"][l],
                        new_cache["v8"][l], new_cache["vs"][l], mask,
                    )
                x = post_attn(p, x, attn)
            elif paged_cache:
                # Paged pool: write the sliver through the page table,
                # then attend. The pallas path runs BOTH sides fused, and
                # both on the STACKED pool at layer `l`, which leads
                # their DMA index maps: the scatter-through-table
                # write kernel (K+V in one launch, DMA slivers only —
                # ops/pallas/paged_write) and the ragged-paged read kernel
                # whose index map does the gather, so a step moves the
                # touched and the live pages of each layer and never a
                # layer's pool; the xla/einsum path keeps the XLA
                # reference scatter (bit-identical to the pre-kernel
                # write) and the contiguous-view gather of `pool[l]`,
                # which XLA fuses into the gather (CPU, and windows over
                # the kernel's row bound). An int8 pool ({"kps","vps"}
                # scale arrays) quantizes the fresh sliver on the way in
                # — inside the write kernel on the pallas path — and
                # dequantizes on the way out: in the read kernel's DMA'd
                # tiles, or via
                # the int8-streaming einsum attention on the reference
                # path. Under a mesh, writes stay on the XLA scatter
                # (GSPMD partitions it over the pool's tp-sharded head
                # axis) and pallas reads go through the shard_map
                # wrappers, mirroring the contiguous branch.
                ptab = cache["ptab"]
                quant_paged = "kps" in cache
                use_write_kernel = impl == "pallas" and mesh is None
                if quant_paged:
                    if use_write_kernel:
                        from ..ops.pallas import fused_page_write_quantized

                        (new_cache["kp"], new_cache["kps"],
                         new_cache["vp"], new_cache["vps"]) = \
                            fused_page_write_quantized(
                                new_cache["kp"], new_cache["kps"],
                                new_cache["vp"], new_cache["vps"],
                                k, v, positions, ptab, l, q_lens=q_lens)
                    else:
                        from ..ops.pallas import (
                            paged_write_reference_quantized,
                        )

                        (new_cache["kp"], new_cache["kps"],
                         new_cache["vp"], new_cache["vps"]) = \
                            paged_write_reference_quantized(
                                new_cache["kp"], new_cache["kps"],
                                new_cache["vp"], new_cache["vps"],
                                k, v, positions, ptab, l, q_lens)
                else:
                    if use_write_kernel:
                        from ..ops.pallas import fused_page_write

                        new_cache["kp"], new_cache["vp"] = fused_page_write(
                            new_cache["kp"], new_cache["vp"], k, v,
                            positions, ptab, l, q_lens=q_lens)
                    else:
                        from ..ops.pallas import paged_write_reference

                        new_cache["kp"] = paged_write_reference(
                            new_cache["kp"], k, positions, ptab, l, q_lens)
                        new_cache["vp"] = paged_write_reference(
                            new_cache["vp"], v, positions, ptab, l, q_lens)
                if impl == "pallas":  # ragged windows (T·N bound validated
                                      # in the kernel wrapper)
                    # Never `pool[l]` here: as an operand of a Mosaic
                    # call it is a copy of the layer's pool.
                    from ..ops.pallas import (
                        ragged_paged_attention,
                        ragged_paged_attention_quantized,
                        sharded_ragged_paged_attention,
                        sharded_ragged_paged_attention_quantized,
                    )

                    if quant_paged:
                        fn = (sharded_ragged_paged_attention_quantized
                              if mesh is not None
                              else ragged_paged_attention_quantized)
                        pools = (new_cache["kp"], new_cache["kps"],
                                 new_cache["vp"], new_cache["vps"])
                    else:
                        fn = (sharded_ragged_paged_attention
                              if mesh is not None
                              else ragged_paged_attention)
                        pools = (new_cache["kp"], new_cache["vp"])
                    args = (mesh,) if mesh is not None else ()
                    attn = fn(
                        *args, q, *pools, ptab, positions, l,
                        cfg.sliding_window, kv_lens, q_lens,
                    )
                elif quant_paged:
                    from ..ops.pallas import gather_page_scales, gather_pages

                    attn = gqa_attention_quantized(
                        q,
                        gather_pages(new_cache["kp"][l], ptab),
                        gather_page_scales(new_cache["kps"][l], ptab),
                        gather_pages(new_cache["vp"][l], ptab),
                        gather_page_scales(new_cache["vps"][l], ptab),
                        mask,
                    )
                else:
                    from ..ops.pallas import gather_pages

                    attn = gqa_attention(
                        q,
                        unpack_cache(
                            gather_pages(new_cache["kp"][l], ptab), f),
                        unpack_cache(
                            gather_pages(new_cache["vp"][l], ptab), f),
                        mask,
                    )
                x = post_attn(p, x, attn)
            else:
                new_cache["k"] = _update_cache_layer(
                    new_cache["k"], pack_heads(k, f), start, l)
                new_cache["v"] = _update_cache_layer(
                    new_cache["v"], pack_heads(v, f), start, l)
                x = attn_mlp(p, x, q, new_cache["k"][l], new_cache["v"][l],
                             k, v)
    else:
        x, (k_new, v_new) = lax.scan(
            block, x, (params["blocks"], cache["k"], cache["v"])
        )
        new_cache = {"k": k_new, "v": v_new}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if logit_indices is not None:
        x = jnp.take_along_axis(
            x, logit_indices.astype(jnp.int32)[:, None, None], axis=1
        )  # [B, 1, D]
    unembed = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if is_qtensor(unembed):
        # int8 streams straight into the dot (never .astype the table —
        # ops/quant.py's measured rule); per-row scales rescale the logit
        # columns in the f32 epilogue.
        logits = jnp.einsum(
            "btd,vd->btv", x, unembed["q8"],
            preferred_element_type=jnp.float32,
        ) * unembed["s"][None, None, :]
    else:
        logits = jnp.einsum("btd,vd->btv", x, unembed,
                            preferred_element_type=jnp.float32)
    return logits, new_cache
