"""Ring attention: context-parallel causal GQA over a sequence-sharded mesh axis.

The reference delegates all long-context handling to llama.cpp's context
window (SURVEY.md §5 "Long-context"), capping usable sequence length at what
one device's memory holds. Here long context is first-class: the sequence
axis is sharded over the mesh's "sp" axis and attention runs as a ring —
each device computes blockwise attention against the KV shard it currently
holds, then rotates that shard to its neighbor with `jax.lax.ppermute`, so
KV blocks ride ICI neighbor links while the MXU overlaps compute. After
`sp` steps every query shard has seen every KV block.

Numerics are flash-attention style online softmax: per ring step we keep a
running row-max `m`, normalizer `l`, and unnormalized accumulator `o` in
float32, merging blocks with the standard rescale-by-`exp(m_old - m_new)`
identity — the result is bitwise-stable regardless of ring order and matches
the dense `ops.attention.gqa_attention` reference to float tolerance
(asserted in tests/test_ring.py on an 8-device virtual mesh).

Causality over the distributed sequence: each device is told which global
KV chunk it holds at step i (`(my_index - i) mod sp`) and builds the mask
from global positions, so the math is identical to the single-device causal
mask. Fully-masked blocks (KV chunk strictly right of every query position,
or — sliding window — strictly out of the window on the left) skip their
score/accumulate math entirely via `lax.cond`: the predicate is a per-device
scalar so the cond stays a real branch under shard_map, and for a from-zero
causal prefill this halves average FLOPs (the upper-triangle saving). The
`ppermute` rotation stays *outside* the cond — every device must join the
collective on every ring step or the program deadlocks.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .common import NEG_INF


def _block_scores(q5: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """[B,T,K,G,H] x [B,S,K,H] -> [B,K,G,T,S] f32 scores (MXU einsum)."""
    return jnp.einsum(
        "btkgh,bskh->bkgts", q5, k, preferred_element_type=jnp.float32
    ) * scale


def _ring_attention_sharded(
    q: jnp.ndarray,  # [B, Tq, N, H]   — this device's query shard
    k: jnp.ndarray,  # [B, Tk, K, H]   — this device's KV shard (rotates)
    v: jnp.ndarray,  # [B, Tk, K, H]
    q_positions: jnp.ndarray,  # [B, Tq] global positions of the query shard
    axis_name: str,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, tq, n, h = q.shape
    tk = k.shape[1]
    kh = k.shape[2]
    g = n // kh
    scale = h ** -0.5
    q5 = q.reshape(b, tq, kh, g, h)
    qp = q_positions.astype(jnp.int32)[:, :, None]  # [B, Tq, 1]

    perm = [(j, (j + 1) % sp) for j in range(sp)]
    qp_max = jnp.max(qp)
    qp_min = jnp.min(qp)

    def step(i, carry):
        o, m, l, k, v = carry
        # Global chunk id of the KV shard this device holds at ring step i:
        # shards rotate forward, so what started on device (my - i) is here now.
        chunk = (my - i) % sp

        def compute(o, m, l):
            kv_idx = chunk * tk + jnp.arange(tk, dtype=jnp.int32)[None, None, :]
            mask = kv_idx <= qp  # [B, Tq, Tk]
            if sliding_window is not None:
                mask = mask & (qp - kv_idx < sliding_window)
            s = _block_scores(q5, k, scale)  # [B, K, G, Tq, Tk]
            mask5 = mask[:, None, None, :, :]
            s = jnp.where(mask5, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))  # [B, K, G, Tq]
            # exp(s - m_new) is garbage (=1) where s was masked AND the whole
            # row is masked (m_new == NEG_INF, so s - m_new == 0); zero it
            # explicitly.
            p = jnp.exp(s - m_new[..., None]) * mask5  # f32 [B, K, G, Tq, Tk]
            alpha = jnp.exp(m - m_new)  # [B, K, G, Tq]
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgts,bskh->btkgh", p.astype(v.dtype), v)
            o_new = (
                o * alpha[..., None].transpose(0, 3, 1, 2, 4)
                + pv.astype(jnp.float32)
            )
            return o_new, m_new, l_new

        # Causal block skip: a KV chunk whose first global slot exceeds every
        # query position here contributes nothing; with a sliding window the
        # chunk can also fall entirely off the left edge. The predicate is a
        # per-device scalar (reduced over this shard's positions), so cond is
        # a genuine branch — skipped chunks cost zero MXU work.
        visible = chunk * tk <= qp_max
        if sliding_window is not None:
            visible = visible & (qp_min - (chunk * tk + tk - 1) < sliding_window)
        o, m, l = jax.lax.cond(
            visible, compute, lambda o, m, l: (o, m, l), o, m, l
        )
        k2, v2 = jax.lax.ppermute((k, v), axis_name, perm)
        return o, m, l, k2, v2

    o0 = jnp.zeros((b, tq, kh, g, h), jnp.float32)
    m0 = jnp.full((b, kh, g, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, tq), jnp.float32)
    o, m, l, _, _ = jax.lax.fori_loop(0, sp, step, (o0, m0, l0, k, v))
    # l == 0 only for rows with no visible key anywhere (can't happen for a
    # causal self-attention query at global position >= 0, but keep it NaN-free
    # for padded garbage rows).
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l[..., None].transpose(0, 3, 1, 2, 4)
    return out.reshape(b, tq, n, h).astype(q.dtype)


def ring_gqa_attention(
    mesh: Mesh,
    q: jnp.ndarray,  # [B, T, N, H] global, T sharded over sp
    k: jnp.ndarray,  # [B, T, K, H]
    v: jnp.ndarray,  # [B, T, K, H]
    q_positions: jnp.ndarray,  # [B, T] global positions
    sliding_window: Optional[int] = None,
    sp_axis: str = "sp",
    dp_axis: Optional[str] = "dp",
    tp_axis: Optional[str] = "tp",
) -> jnp.ndarray:
    """Causal GQA with the sequence axis sharded over `sp_axis`.

    Batch rides `dp_axis` and heads ride `tp_axis` when those axes exist in
    the mesh — context parallelism composes with TP×DP: head blocks are
    independent, so the ring runs per-(dp, tp) shard with no cross-axis
    communication. Sequence length must divide evenly by the sp axis size
    (bucketed padding upstream guarantees this; see engine/kvcache.py).
    """
    axes = dict(mesh.shape)
    dp = dp_axis if dp_axis in axes else None
    tp = tp_axis if tp_axis in axes else None
    if sp_axis not in axes:
        raise ValueError(f"mesh {tuple(axes)} has no {sp_axis!r} axis")
    if q.shape[1] % axes[sp_axis] != 0:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by sp={axes[sp_axis]}"
        )
    qkv_spec = P(dp, sp_axis, tp, None)
    pos_spec = P(dp, sp_axis)
    fn = functools.partial(
        _ring_attention_sharded, axis_name=sp_axis, sliding_window=sliding_window
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, q_positions)
