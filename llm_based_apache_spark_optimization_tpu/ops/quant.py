"""Int8 weight-only quantization for the transformer matmuls.

This is the TPU counterpart of llama.cpp's quantized serving (the
reference's models ship as Q4/Q8 GGUF blobs run by llama.cpp —
SURVEY.md §2.3). Decode throughput is HBM-bandwidth-bound: every step
streams the full weight set once, so int8 storage halves weight traffic
vs bf16 and directly buys decode tok/s. Scheme:

- Symmetric per-output-channel scaling over the contracted (input) axis:
  q8 = round(W / s), s = absmax_in(W) / 127, stored as
  {"q8": int8 [..., in, out], "s": f32 [..., out]}.
- The int8 array feeds `lax.dot_general` DIRECTLY (no `.astype` on the
  weight): XLA's native mixed-precision dot converts int8 tiles inside the
  matmul pipeline, so HBM reads stay int8 and no bf16 copy of the weight
  is ever materialized. Measured on TPU v5e (decode-shaped [8, K] @ [K, N]
  chained over 16 layers): direct mixed dot 2.37 ms vs 3.28 ms for
  `x @ q8.astype(bf16)` vs 4.30 ms bf16 — the astype form loses a third
  of the int8 win to the standalone convert, the direct form tracks the
  2x byte ratio. Accumulation is f32 (`preferred_element_type`), the
  per-channel rescale fuses into the dot epilogue.
- Only the seven block matmul weights quantize; embeddings, unembedding
  and norms stay high-precision (quality-sensitive, small share of bytes —
  the same split llama.cpp's quant presets make).

A QTensor is a plain dict, so the params tree stays a vanilla pytree:
`lax.scan` slices the stacked [L, ...] leaves per layer, `jax.tree.map`
and checkpointing traverse it, and `parallel.sharding` shards q8 like the
original weight and s by its surviving out axis.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp
from jax import lax

QUANT_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")

#: absmax -> int8 scale for K/V entries (`quantize_kv` and its in-kernel
#: twin): the f32 nearest to 1/127.
KV_SCALE_STEP = 1.0 / 127.0


def is_qtensor(w: Any) -> bool:
    return isinstance(w, dict) and "q8" in w


def is_q4tensor(w: Any) -> bool:
    return isinstance(w, dict) and "q4" in w


def tp_safe_group(n_in: int, group: int = 128) -> int:
    """Largest even quant-group <= `group` that keeps WHOLE groups inside
    every tensor-parallel shard of the contraction axis, for any tp in
    {1, 2, 4, 8} (the BASELINE topologies) that evenly shards the axis at
    even-group granularity. (If n_in/8 is odd, no even group can satisfy
    tp=8 — but such an axis cannot shard 8 ways at nibble-pair granularity
    in the first place; specs_for_params still re-checks alignment at the
    actual mesh width and fails loudly.)

    Row-parallel int4 weights (wo/wd) shard the contraction axis; the
    sharded kernel applies group scales before the tp psum
    (ops/pallas/int4mm.sharded_int4_matmul), which is only correct when no
    group straddles a shard boundary. Most dims are multiples of 128*8 and
    keep group=128; Llama-2-7B's ffn dim 11008 drops to 86 (the largest
    even divisor of 11008/8 = 1376 below 128).
    """
    base = n_in // 8 if n_in % 8 == 0 else n_in
    g = min(group, base, n_in)
    while g > 2 and (base % g or g % 2):
        g -= 1
    return max(g, 2)


def quantize_weight_int4(w: jnp.ndarray, group: int = 128) -> Dict[str, jnp.ndarray]:
    """[..., in, out] float -> {"q4": uint8 [..., in/2, out] packed nibbles,
    "s4": f32 [..., in/group, out]} — symmetric absmax int4 with one scale
    per (contraction group, out channel), the storage llama.cpp's Q4 blobs
    get at (the reference's models ship 4-bit; this is the TPU-native
    equivalent at one QUARTER of bf16's weight bytes).

    Byte b of q4 packs contraction rows 2b (LOW nibble) and 2b+1 (HIGH),
    biased by +8 into [0, 15] (value = nibble - 8). Packed uint8 on
    purpose: the kernel unpacks nibbles itself (ops/pallas/int4mm.py), so
    the bytes in HBM are exactly these, in no layout XLA may pad.
    """
    n_in = w.shape[-2]
    group = min(group, n_in)
    if n_in % group or group % 2:
        raise ValueError(f"in dim {n_in} must be a multiple of even group "
                         f"{group}")
    w32 = w.astype(jnp.float32)
    grouped = w32.reshape(*w.shape[:-2], n_in // group, group, w.shape[-1])
    s = jnp.max(jnp.abs(grouped), axis=-2) / 7.0   # [..., groups, out]
    s = jnp.where(s == 0.0, 1.0, s)
    q = jnp.clip(jnp.round(grouped / s[..., None, :]), -8, 7)
    q = q.reshape(*w.shape[:-2], n_in, w.shape[-1])
    nib = (q + 8).astype(jnp.uint8)
    pairs = nib.reshape(*w.shape[:-2], n_in // 2, 2, w.shape[-1])
    q4 = pairs[..., 0, :] | jnp.left_shift(pairs[..., 1, :], jnp.uint8(4))
    return {"q4": q4, "s4": s}


def dequantize_weight_int4(w: Dict[str, jnp.ndarray], dtype=jnp.float32) -> jnp.ndarray:
    from .pallas.int4mm import unpack_nibbles

    q = unpack_nibbles(w["q4"]).astype(jnp.float32)  # [..., in, out]
    n_in = q.shape[-2]
    groups = w["s4"].shape[-2]
    grouped = q.reshape(*q.shape[:-2], groups, n_in // groups, q.shape[-1])
    deq = grouped * w["s4"][..., None, :]
    return deq.reshape(q.shape).astype(dtype)


def quantize_params_int4(params: Dict[str, Any], group: int = 128) -> Dict[str, Any]:
    """int4-quantize the block matmul weights (same split as
    quantize_params: embeddings/unembed/norms stay high-precision).

    The per-weight group is clamped tp-safe (`tp_safe_group`) so the tree
    can later shard onto any BASELINE tensor-parallel mesh."""
    out = dict(params)
    out["blocks"] = {
        k: quantize_weight_int4(v, tp_safe_group(v.shape[-2], group))
        if k in QUANT_KEYS else v
        for k, v in params["blocks"].items()
    }
    return out


def quantize_weight(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """[..., in, out] float -> {"q8": int8, "s": f32 [..., out]}."""
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32), axis=-2) / 127.0  # [..., out]
    s = jnp.where(s == 0.0, 1.0, s)
    q8 = jnp.clip(jnp.round(w32 / s[..., None, :]), -127, 127).astype(jnp.int8)
    return {"q8": q8, "s": s}


def dequantize_weight(w: Dict[str, jnp.ndarray], dtype=jnp.float32) -> jnp.ndarray:
    return (w["q8"].astype(jnp.float32) * w["s"][..., None, :]).astype(dtype)


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize the block matmul weights of a model/checkpoint param tree."""
    out = dict(params)
    out["blocks"] = {
        k: quantize_weight(v) if k in QUANT_KEYS else v
        for k, v in params["blocks"].items()
    }
    return out


def init_params_quantized(cfg, key, dtype=jnp.bfloat16, bits: int = 8) -> Dict[str, Any]:
    """Random int8 param tree built DIRECTLY at its final size — no
    full-precision intermediate.

    Purpose: benchmarking big shapes on one chip. A 7B bf16 tree is
    13.5 GB; `init_params` + `quantize_params` would peak near 20 GB on a
    16 GB v5e before the bf16 tree is freed. Here the seven block matmuls
    are sampled straight as int8 (uniform over the full range — decode
    streams the same bytes real quantized weights would) with constant
    per-channel scales matching init_params' 1/sqrt(fan_in) magnitude, so
    logits stay finite and sampling behaves. Embeddings/unembed/norms
    follow quantize_params' split and stay in `dtype`.
    """
    import jax

    d, f = cfg.hidden_size, cfg.intermediate_size
    nh, kh, hd, L = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                     cfg.num_layers)
    keys = jax.random.split(key, 10)
    shapes = {
        "wq": (L, d, nh * hd), "wk": (L, d, kh * hd), "wv": (L, d, kh * hd),
        "wo": (L, nh * hd, d), "wg": (L, d, f), "wu": (L, d, f),
        "wd": (L, f, d),
    }
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    blocks: Dict[str, Any] = {}
    for i, (name, shape) in enumerate(shapes.items()):
        fan_in = shape[-2]
        if bits == 8:
            # jit so the PRNG runs on-device at int8 width; int8 absmax
            # 127 with scale fan_in^-0.5/127 reproduces init_params' row
            # scale.
            q8 = jax.jit(
                lambda k, s=shape: jax.random.randint(k, s, -127, 128,
                                                      jnp.int8)
            )(keys[i])
            s = jnp.full(shape[:-2] + shape[-1:], fan_in ** -0.5 / 127.0,
                         jnp.float32)
            blocks[name] = {"q8": q8, "s": s}
        else:
            # Packed random nibbles at final size (quantize_weight_int4
            # layout), absmax 7 scaling; tp-safe group like the real
            # quantizer so sharded benches see the same byte layout.
            group = tp_safe_group(fan_in)
            pshape = shape[:-2] + (fan_in // 2, shape[-1])
            q4 = jax.jit(
                lambda k, s=pshape: jax.random.randint(
                    k, s, 0, 256, jnp.int32
                ).astype(jnp.uint8)
            )(keys[i])
            s4 = jnp.full(shape[:-2] + (fan_in // group, shape[-1]),
                          fan_in ** -0.5 / 7.0, jnp.float32)
            blocks[name] = {"q4": q4, "s4": s4}
    blocks["ln_attn"] = jnp.ones((L, d), dtype)
    blocks["ln_mlp"] = jnp.ones((L, d), dtype)

    def emb(k):
        return jax.jit(
            lambda kk: (jax.random.normal(kk, (cfg.vocab_size, d),
                                          jnp.float32) * d ** -0.5)
            .astype(dtype)
        )(k)

    params: Dict[str, Any] = {
        "embed": emb(keys[7]),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = emb(keys[8])
    return params


def quantize_unembed(params: Dict[str, Any]) -> Dict[str, Any]:
    """int8-quantize the embedding/unembedding tables (per-ROW scales:
    absmax over the hidden axis, one scale per vocab entry).

    The block quantizers deliberately leave these in bf16, but at decode
    the unembed matmul streams the whole [V, D] table every step — after
    int4 blocks it is the largest remaining bf16 stream (~22% of 7B-int4
    decode bytes). llama.cpp's presets quantize output/token_embd too
    (Q6/Q8); this is the same split at int8. The embedding GATHER
    dequantizes only the looked-up rows (exact per row, negligible cost);
    the unembed feeds int8 straight into the logits einsum with the scale
    applied per vocab column after (ops/quant.mm's direct-dot rule).
    """
    def q(t: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        t32 = t.astype(jnp.float32)
        s = jnp.max(jnp.abs(t32), axis=-1) / 127.0      # [V]
        s = jnp.where(s == 0.0, 1.0, s)
        q8 = jnp.clip(jnp.round(t32 / s[:, None]), -127, 127).astype(jnp.int8)
        return {"q8": q8, "s": s}

    out = dict(params)
    out["embed"] = q(params["embed"]) if not is_qtensor(params["embed"]) \
        else params["embed"]
    if "lm_head" in params and not is_qtensor(params["lm_head"]):
        out["lm_head"] = q(params["lm_head"])
    return out


def quantize_kv(x: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Quantize K or V cache tensors [..., S, H] to int8 with one f32 scale
    per slot (absmax over the head dim).

    The TPU counterpart of llama.cpp's q8_0 KV-cache type: decode attention
    is cache-streaming-bound at long context, and int8 storage halves that
    traffic. Per-slot scaling keeps the error local to a token — attention
    applies K scales to the score row and folds V scales into the
    probabilities, so both dots stream int8 directly (ops/attention.
    gqa_attention_quantized)."""
    x32 = x.astype(jnp.float32)
    # A product, not `/ 127.0`: XLA turns a division by a constant into a
    # multiplication by its reciprocal in some fusions and not in others,
    # one ulp apart — and the fused page-write kernel must land the very
    # scales this function does (ops/pallas/paged_write.py).
    s = jnp.max(jnp.abs(x32), axis=-1) * KV_SCALE_STEP      # [..., S]
    s = jnp.where(s == 0.0, 1.0, s)
    q8 = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return {"q8": q8, "s": s}


def quantize_cache(
    k: jnp.ndarray, v: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """Quantize a K/V cache pair into the canonical int8-cache dict layout
    {"k8", "ks", "v8", "vs"} that models/llama.forward and the scheduler's
    cache-tuple threading consume (one definition of the layout; see also
    serve/scheduler._quant_window_tuple)."""
    kq, vq = quantize_kv(k), quantize_kv(v)
    return {"k8": kq["q8"], "ks": kq["s"], "v8": vq["q8"], "vs": vq["s"]}


def mm(x: jnp.ndarray, w: Any, mesh=None, partition: str = "col") -> jnp.ndarray:
    """x @ w for a plain array or a QTensor (dequant fused into the matmul).

    QTensor path: the int8 array goes straight into `dot_general` — never
    `.astype` the weight first (a standalone convert materializes VPU work
    XLA otherwise hides inside the matmul; see module docstring for the
    measured cost). f32 accumulation, rescale in the epilogue.

    `mesh`/`partition` matter only for int4 trees: the pallas kernel can't
    run on GSPMD-sharded operands, so under a mesh it routes through the
    explicit shard_map wrapper with the weight's Megatron partition ("col"
    for wq/wk/wv/wg/wu, "row" for wo/wd — the same split
    parallel/sharding.param_specs encodes). bf16/int8 dots ignore both:
    GSPMD partitions them from the operand shardings alone."""
    if is_qtensor(w):
        acc = lax.dot_general(
            x, w["q8"],
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (acc * w["s"]).astype(x.dtype)
    if is_q4tensor(w):
        return _q4_mm(x, w, mesh, partition)
    return x @ w


def _q4_mm(x: jnp.ndarray, w: Dict[str, jnp.ndarray], mesh,
           partition: str) -> jnp.ndarray:
    """Shared int4 route for mm/mm_stacked: flatten leading axes to kernel
    rows, pick the shard_map wrapper under a mesh, restore the lead."""
    from .pallas.int4mm import int4_matmul, sharded_int4_matmul

    lead = x.shape[:-1]
    rows = 1
    for d in lead:
        rows *= d
    x2 = x.reshape(rows, x.shape[-1])
    if mesh is not None:
        out = sharded_int4_matmul(mesh, x2, w["q4"], w["s4"],
                                  partition=partition)
    else:
        out = int4_matmul(x2, w["q4"], w["s4"])
    return out.reshape(*lead, *out.shape[1:])


def mm_stacked(x: jnp.ndarray, w: Any, mesh=None) -> jnp.ndarray:
    """x[..., D] @ stacked fused weight [D, C, O] -> [..., C, O].

    The fused-matmul layout (models/llama.fuse_blocks) STACKS same-shaped
    projections on a new axis instead of concatenating their out axes: the
    O axis tensor-parallel-shards exactly like the unfused weights and the
    C split is a device-local index — a concatenated out axis would put
    q/k/v boundaries mid-shard and force GSPMD to reshard every split.
    Always column-parallel. Handles bf16, int8 QTensor (s is [C, O]) and
    int4 stacked trees (q4 [D/2, C, O] — the kernel flattens the
    contiguous (C, O) tail; ops/pallas/int4mm)."""
    dn = (((x.ndim - 1,), (0,)), ((), ()))
    if is_qtensor(w):
        acc = lax.dot_general(x, w["q8"], dimension_numbers=dn,
                              preferred_element_type=jnp.float32)
        return (acc * w["s"]).astype(x.dtype)
    if is_q4tensor(w):
        return _q4_mm(x, w, mesh, "col")  # stacked trees are always col
    return lax.dot_general(x, w, dimension_numbers=dn)
