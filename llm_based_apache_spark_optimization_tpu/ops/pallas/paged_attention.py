"""Ragged paged attention over the shared KV page pool.

The paged twin of `attention.py`'s K-folded flash decode kernel: K/V live in
a shared pool `[L, P, K, page, H]` stacked over the layers
(engine/paged_kv.py) and each batch row owns a page TABLE `[NP]` mapping its
logical pages to pool pages — the layout from "Ragged Paged Attention: A
High-Performance and Flexible LLM Inference Kernel for TPU" (PAPERS.md) and
vLLM's PagedAttention.

The stored shape: where a head is narrower than the 128-lane tile the pool
is LANE-PACKED, `[L, P, K/f, page, f*H]` with f heads a row. Who decides:
`engine/paged_kv.lane_pack`, from the configuration and the pool's kind,
and nobody else — the wrappers here read `f = pool.shape[-1] // q.shape[-1]`
off the pool they are handed (ops/lanepack.py). To the grid below a packed
pool is a GQA pool of K/f heads of width f*H whose group is f*G query rows:
`ragged_paged_attention` spreads query head f*j+i over lanes [i*H, (i+1)*H)
of a zero row, passes the softmax scale of the TRUE head width, and keeps
those lanes of the row's output. The kernel bodies, the folded row count
(T*N) and the VMEM tiles are the plain layout's; the page DMA is half as
many rows of full lanes, and no program converts the pool's layout around
the call. The int8 pool (`_quantized`) is never packed.

Kernel design:

- Grid = (B, NP): the logical-page axis is innermost, so one core sweeps a
  row's pages in order and the online-softmax accumulators (shared
  `_flash_block_update`) live in VMEM scratch across the sweep. The KV-head
  axis is folded into the cell exactly like the contiguous decode kernel —
  a pool page already holds all K heads contiguously, so a page IS the
  natural DMA block.
- RAGGED QUERY WINDOWS (ISSUE 19): the query block folds BOTH the GQA
  group axis and the T query-window axis into one row axis (GT = G·T —
  identical to the decode layout at T=1), and per-row query lengths
  `q_lens[b]` ride SCALAR PREFETCH beside `kv_lens` and the page table.
  Window columns at or past a row's q_len get their query position masked
  to -1 inside the kernel, so the causal mask hides every KV position,
  their softmax weight is zero, and the finalize step emits exact zeros —
  one grid therefore serves T=1 decode rows, T=D+1 speculative verify
  windows, and multi-token prefill chunks in the SAME launch, which is
  what lets the scheduler run mixed prefill+decode rounds as one program.
- The page table rides SCALAR PREFETCH: the K/V BlockSpec index maps read
  `table[b, i]` to pick which POOL page cell (b, i) streams — the gather
  happens in the DMA engine's addressing, never as a materialized
  [B, NP*page, ...] copy (that copy is exactly what the XLA reference path
  below pays, and what this kernel exists to avoid).
- The kernels take the STACKED pool and a `layer`, like
  `paged_write.fused_page_write`: the layer is the leading coordinate of
  the same index maps, so it too enters the DMA's addressing. A Mosaic
  call cannot take a strided view of the `[L, P, K, page, H]` loop carry;
  handed `pool[layer]`, XLA materializes the slice — the whole pool read
  and written once a decode step, for K and for V. The layer rides
  SCALAR PREFETCH beside the table, as a value and not as a static
  argument: a forward pass's L calls then share one trace and one
  lowered kernel. A static layer makes L of each, every time a program
  is built, whether or not its executable is in the compile cache
  (seconds of server start at 32 layers).
- Ragged bounding: `kv_lens[b]` clamps the logical page index at the row's
  last live page — grid steps past it re-map the same pool page and Pallas
  elides the repeated DMA, so a row at position p streams
  ceil((p+1)/page) pages, not NP (parked rows with kv_lens=0 stream one
  page and compute nothing). HBM traffic therefore scales with LIVE tokens
  across a mixed-age batch — the whole point of the paged layout.
- Unmapped table entries (the `num_pages` sentinel) are clipped to a real
  pool page; they can only sit at logical positions the causal/kv_lens
  mask already hides, so the garbage never reaches the output (asserted by
  the parity tests against `paged_attention_reference`).

`paged_attention_reference` is the always-correct XLA path (gather the
row's pages into a contiguous view, run the einsum attention) with the
kernel's exact ragged contract (`q_lens` columns past a row's window
return zeros): the golden in parity tests and the CPU/interpret fallback
in `models/llama.forward`; it takes ONE layer's pool `[P, K, page, H]`
(`pool[layer]`, which XLA fuses into its gather). The kernel serves any
window with T·N <= `_MAX_QROWS` folded rows over all heads (the folded
query block must stay VMEM-resident); larger windows take the reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF
from ..lanepack import (
    gather_outputs,
    pack_factor,
    spread_queries,
    unpack_cache,
)
from .attention import _flash_block_update, _LANES
from .dispatch import resolve_interpret

# Upper bound on folded query rows the kernel serves, counted over ALL
# heads (T·N = K·G·T): the KV-head axis is folded into the cell, so the
# whole [K, G·T, H] query block plus its f32 accumulators stays
# VMEM-resident across the page sweep. Set by the compiler, not by
# argument: Mosaic for v5e accepts every variant (GQA 32/8 and MHA 32/32,
# H 64/128, pages 16/64, bf16/int8 pools) at 2048 rows and runs out of
# VMEM at 4096 (tests/test_chip_compile.py holds the bound). The
# scheduler's largest window, T=32 at N=32, is 1024. Windows above the
# bound take the XLA reference.
_MAX_QROWS = 2048


def _make_paged_decode_kernel(dequant):
    """Ragged paged kernel factory (grid = (B, NP), page axis innermost).
    `dequant(stream_refs, dtype) -> (k, v)` turns the DMA'd pool-page
    tiles into compute tiles — identity for bf16 pools, VMEM
    dequantization for int8 values + per-position scales — so the
    init/skip/finalize skeleton exists exactly once (the same factoring
    as the contiguous `_make_decode_kernel`)."""

    def kernel(
        kvlen_ref,  # [B] i32 SMEM (scalar prefetch) — live KV tokens/row
        qlen_ref,   # [B] i32 SMEM (scalar prefetch) — live query cols/row
        table_ref,  # [B, NP] i32 SMEM (scalar prefetch) — page tables
        layer_ref,  # [1] i32 SMEM (scalar prefetch) — for the index maps
        qpos_ref,   # [1, 1, GT] i32
        q_ref,      # [1, K, GT, H]
        *rest,      # stream refs (pool tiles picked by the index map),
                    # then o_ref + m/l/acc scratch
        scale: float,
        sliding_window: Optional[int],
        kv_len: int,
        window: int,
    ):
        *stream_refs, o_ref, m_ref, l_ref, acc_ref = rest
        i = pl.program_id(1)
        ps = stream_refs[0].shape[2]
        kvl = kvlen_ref[pl.program_id(0)]
        ql = qlen_ref[pl.program_id(0)]

        @pl.when(i == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # Folded row r = gi*window + ti, so r % window recovers the window
        # column. Columns at or past this row's q_len get position -1: the
        # causal mask then hides every KV position, l stays 0, and finalize
        # emits exact zeros — dead rows cost no extra pages because the
        # max-based skip below sees their position as -1, not a sentinel.
        gt = qpos_ref.shape[2]
        col = jax.lax.broadcasted_iota(jnp.int32, (gt, 1), 0)[:, 0] % window
        qp_row = jnp.where(col < ql, qpos_ref[0, 0], -1)  # [GT]

        # Same skip rule as the contiguous decode kernel: pages whose
        # first logical position exceeds every LIVE query position — or the
        # row's live length — contribute nothing (their DMA was already
        # elided by the clamped index map).
        @pl.when((i * ps <= jnp.max(qp_row)) & (i * ps < kvl))
        def _compute():
            k, v = dequant(stream_refs, q_ref.dtype)
            m_new, l_new, acc_new = _flash_block_update(
                q_ref[0], k, v, qp_row, kvl, i, ps,
                m_ref[:, :, :1], l_ref[:, :, :1], acc_ref[...],
                scale=scale, sliding_window=sliding_window, kv_len=kv_len,
            )
            acc_ref[:] = acc_new
            m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(i == pl.num_programs(1) - 1)
        def _finalize():
            l = l_ref[:, :, :1]
            out = acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = out.astype(o_ref.dtype)

    return kernel


# bf16 pool: streams are (k_page, v_page), used as-is.
_paged_decode_kernel = _make_paged_decode_kernel(
    lambda refs, dt: (refs[0][0], refs[1][0])
)


def _dequant_page_streams(refs, dt):
    """(k8, ks, v8, vs) int8 page + per-position scale tiles -> compute
    tiles. The pool streamed ~half the bytes of a bf16 pool; the dequant
    runs on the VMEM tiles only (the contract ISSUE 11 names: dequantize
    inside the kernel's DMA'd tiles)."""
    k8, ks, v8, vs = refs
    k = (k8[0].astype(jnp.float32)
         * ks[0].astype(jnp.float32)[..., None]).astype(dt)
    v = (v8[0].astype(jnp.float32)
         * vs[0].astype(jnp.float32)[..., None]).astype(dt)
    return k, v


# int8 pool: streams are (k8 [1,K,PS,H], ks [1,K,PS], v8, vs). The scale
# tiles get their trailing axis here, in VMEM: a `[..., None]` outside the
# kernel is a copy of the scale pool (its one-wide minor axis padded to
# the 128 lanes), and on the stacked pool it would be one a layer.
_paged_decode_kernel_q8 = _make_paged_decode_kernel(_dequant_page_streams)


def _run_paged_grid(kernel, q, streams, layer, page_table, q_positions,
                    sliding_window, kv_lens, q_lens, interpret, scale):
    """The ragged paged pipeline shared by the bf16 and int8 kernels:
    grid (B, NP) with the page table in SCALAR PREFETCH — every stream's
    BlockSpec index map translates the kv_lens-clamped logical page
    through the table, so the gather happens in the DMA engine's
    addressing for values and scales alike. The T query-window axis folds
    into the GQA group axis (GT = G·T — identity at T=1, the decode
    layout), and per-row `q_lens` ride prefetch so dead window columns
    zero out in-kernel. `streams` is a list of
    (array [L, P, K, PS, ...tail], tail_block_shape) pairs — (h,) for K/V
    value pools, () for per-position scale pools. A block is one page
    of `layer` (prefetched too, clipped to the stack): the layer axis is
    squeezed out of the block, so the kernel bodies see
    `[1, K, PS, ...tail]` tiles whatever the depth of the stack. `scale`
    is the softmax scale, the caller's to give: under lane packing the
    row this grid sees is wider than the head it scores."""
    b, t, n, h = q.shape
    num_layers, num_pages, kh, ps = streams[0][0].shape[:4]
    g = n // kh
    gt = g * t
    np_tab = page_table.shape[1]
    s_virt = np_tab * ps

    if kv_lens is None:
        kv_lens = jnp.max(q_positions, axis=1) + 1
    kv_lens = jnp.clip(kv_lens.astype(jnp.int32), 0, s_virt)
    if q_lens is None:
        q_lens = jnp.full((b,), t, jnp.int32)
    q_lens = jnp.clip(q_lens.astype(jnp.int32), 0, t)
    table = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    layer = jnp.clip(jnp.asarray(layer, jnp.int32), 0, num_layers - 1)

    # [B, T, N, H] -> [B, K, G·T, H]: fold the window axis under the GQA
    # group axis so folded row r = gi*t + ti (identity at T=1 — the
    # contiguous decode grid's layout).
    q5 = (
        q.reshape(b, t, kh, g, h)
        .transpose(0, 2, 3, 1, 4)
        .reshape(b, kh, gt, h)
    )
    qpos = jnp.tile(q_positions.astype(jnp.int32), (1, g))[:, None, :]

    def page_spec(tail):
        def kv_map(bi, i, kvl, ql, tab, lay):
            # Clamp at the row's last LIVE logical page, then translate
            # through its table: steps past the live region re-map the same
            # pool page and the DMA is elided — the bandwidth saving, not
            # just a compute skip. The layer leads the coordinates.
            last = jnp.maximum((kvl[bi] + ps - 1) // ps - 1, 0)
            return (lay[0], tab[bi, jnp.minimum(i, last)], 0, 0) + (
                0,) * len(tail)

        return pl.BlockSpec((None, 1, kh, ps) + tail, kv_map)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, np_tab),
        in_specs=[
            pl.BlockSpec((1, 1, gt), lambda bi, i, *_: (bi, 0, 0)),
            pl.BlockSpec((1, kh, gt, h), lambda bi, i, *_: (bi, 0, 0, 0)),
        ] + [page_spec(tail) for _, tail in streams],
        out_specs=pl.BlockSpec(
            (1, kh, gt, h), lambda bi, i, *_: (bi, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((kh, gt, _LANES), jnp.float32),
            pltpu.VMEM((kh, gt, _LANES), jnp.float32),
            pltpu.VMEM((kh, gt, h), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            kernel, scale=scale,
            sliding_window=sliding_window, kv_len=s_virt, window=t,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, gt, h), q.dtype),
        # Batch rows are independent (megacore splits them); the page axis
        # carries the online-softmax accumulators in order on one core.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(kv_lens, q_lens, table, layer.reshape(1), qpos, q5,
      *[arr for arr, _ in streams])
    return out.reshape(b, kh, g, t, h).transpose(0, 3, 1, 2, 4).reshape(
        b, t, n, h
    )


def _validate_window(q, page_size, interpret, *, quantized=False):
    """One guard for both kernel variants (bf16 and int8): reject query
    windows whose folded row count T·N exceeds `_MAX_QROWS` with ONE
    consistent message naming the always-correct fallback, and resolve +
    check the TPU sublane-alignment requirement. Returns the resolved
    `interpret` flag."""
    b, t, n, h = q.shape
    suffix = "_quantized" if quantized else ""
    if t < 1 or t * n > _MAX_QROWS:
        raise ValueError(
            f"ragged_paged_attention{suffix} serves query windows with "
            f"1 <= T*N <= {_MAX_QROWS} folded rows, got T={t} (N={n}); "
            f"larger windows take paged_attention_reference{suffix}"
        )
    interpret = resolve_interpret(interpret)
    if not interpret and page_size % 8:
        raise ValueError(
            f"pool pages must be sublane-aligned (page size multiple of 8) "
            f"on TPU, got {page_size}"
        )
    return interpret


@functools.partial(
    jax.jit, static_argnames=("sliding_window", "interpret")
)
def ragged_paged_attention(
    q: jnp.ndarray,            # [B, T, N, H] — ragged query windows
    k_pool: jnp.ndarray,       # [L, P, K, PS, H] — the stacked page pool,
    v_pool: jnp.ndarray,       # or lane-packed [L, P, K/f, PS, f*H]
    page_table: jnp.ndarray,   # [B, NP] i32 — pool page per logical page
    q_positions: jnp.ndarray,  # [B, T] i32
    layer,                     # i32 scalar: which layer of the stack
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,  # [B] i32 — live tokens per row
    q_lens: Optional[jnp.ndarray] = None,   # [B] i32 — live query cols/row
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Ragged flash attention reading K/V of one layer of the stacked pool
    through per-row page tables.

    Returns [B, T, N, H] in q's dtype. Output depends only on the first
    `kv_lens[b]` logical positions of each row (defaults to max(position)+1;
    kv_lens=0 parks a row — zero output, one elided-DMA sweep) and the
    first `q_lens[b]` window columns (defaults to T; columns past a row's
    q_len return exact zeros). One launch therefore serves T=1 decode
    rows, speculative verify windows, and prefill chunks together. The
    pool is read in place: HBM traffic is the live pages of `layer` alone,
    whatever the depth of the stack."""
    interpret = _validate_window(q, k_pool.shape[3], interpret)
    h = q.shape[3]
    # A lane-packed pool (ops/lanepack.py) is, to the grid, a GQA pool of
    # K/f heads of width f*H; its shape alone says so.
    kh, w = k_pool.shape[2], k_pool.shape[4]
    f = pack_factor(k_pool, h)
    out = _run_paged_grid(
        _paged_decode_kernel, spread_queries(q, f, kh),
        [(k_pool, (w,)), (v_pool, (w,))], layer,
        page_table, q_positions, sliding_window, kv_lens, q_lens, interpret,
        h**-0.5,
    )
    return gather_outputs(out, f, kh)


@functools.partial(
    jax.jit, static_argnames=("sliding_window", "interpret")
)
def ragged_paged_attention_quantized(
    q: jnp.ndarray,            # [B, T, N, H] — ragged query windows
    k_pool: jnp.ndarray,       # [L, P, K, PS, H] int8 — the stacked pool
    k_scale: jnp.ndarray,      # [L, P, K, PS] f32 — per-position K scales
    v_pool: jnp.ndarray,       # [L, P, K, PS, H] int8
    v_scale: jnp.ndarray,      # [L, P, K, PS] f32
    page_table: jnp.ndarray,   # [B, NP] i32
    q_positions: jnp.ndarray,  # [B, T] i32
    layer,                     # i32 scalar: which layer of the stack
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,  # [B] i32
    q_lens: Optional[jnp.ndarray] = None,   # [B] i32
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """`ragged_paged_attention` over the INT8 page pool: the table-driven
    DMA gather streams int8 value pages plus their f32 per-position scale
    columns (~half a bf16 pool's bytes), and the dequantize runs on the
    VMEM tiles inside the kernel — int8 streaming and per-row ragged
    bounding stacked, the paged twin of
    `attention.flash_gqa_attention_quantized`."""
    interpret = _validate_window(
        q, k_pool.shape[3], interpret, quantized=True
    )
    h = q.shape[3]
    return _run_paged_grid(
        _paged_decode_kernel_q8, q,
        [(k_pool, (h,)), (k_scale, ()), (v_pool, (h,)), (v_scale, ())],
        layer, page_table, q_positions, sliding_window, kv_lens, q_lens,
        interpret, h**-0.5,
    )


def sharded_ragged_paged_attention(
    mesh,
    q, k_pool, v_pool, page_table, q_positions, layer,
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,
    q_lens: Optional[jnp.ndarray] = None,
    *,
    interpret: Optional[bool] = None,
):
    """`ragged_paged_attention` under a tp mesh via `jax.shard_map`: the
    stacked pool shards its KV-HEAD axis over tp (parallel/sharding —
    every page holds all heads, each device holds its heads' slice of
    every page of every layer), page tables, positions, the layer and
    per-row lengths replicate, and the per-device body is the
    single-device kernel on local shapes — no collective inside, exactly
    like `attention.sharded_flash_gqa_attention`. The batch axis rides
    "dp" (dp=1 for the scheduler, whose slot axis never shards)."""
    from jax.sharding import PartitionSpec as P

    body = functools.partial(
        ragged_paged_attention, sliding_window=sliding_window,
        interpret=interpret,
    )
    if kv_lens is None:
        kv_lens = jnp.max(q_positions.astype(jnp.int32), axis=1) + 1
    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    pool = P(None, None, "tp", None, None)
    return jax.shard_map(
        lambda q_, k_, v_, t_, p_, y_, l_, w_: body(
            q_, k_, v_, t_, p_, y_, kv_lens=l_, q_lens=w_
        ),
        mesh=mesh,
        in_specs=(P("dp", None, "tp", None), pool, pool,
                  P("dp", None), P("dp", None), P(), P("dp"), P("dp")),
        out_specs=P("dp", None, "tp", None),
        check_vma=False,
    )(q, k_pool, v_pool, page_table, q_positions,
      jnp.asarray(layer, jnp.int32), kv_lens, q_lens)


def sharded_ragged_paged_attention_quantized(
    mesh,
    q, k_pool, k_scale, v_pool, v_scale, page_table, q_positions,
    layer,
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,
    q_lens: Optional[jnp.ndarray] = None,
    *,
    interpret: Optional[bool] = None,
):
    """The int8-pool kernel under a tp mesh (scales shard with their
    KV-head axis, like the contiguous quantized wrapper)."""
    from jax.sharding import PartitionSpec as P

    body = functools.partial(
        ragged_paged_attention_quantized, sliding_window=sliding_window,
        interpret=interpret,
    )
    if kv_lens is None:
        kv_lens = jnp.max(q_positions.astype(jnp.int32), axis=1) + 1
    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    pool, scales = P(None, None, "tp", None, None), P(None, None, "tp", None)
    return jax.shard_map(
        lambda q_, k_, ks_, v_, vs_, t_, p_, y_, l_, w_: body(
            q_, k_, ks_, v_, vs_, t_, p_, y_, kv_lens=l_, q_lens=w_
        ),
        mesh=mesh,
        in_specs=(P("dp", None, "tp", None), pool, scales, pool, scales,
                  P("dp", None), P("dp", None), P(), P("dp"), P("dp")),
        out_specs=P("dp", None, "tp", None),
        check_vma=False,
    )(q, k_pool, k_scale, v_pool, v_scale, page_table, q_positions,
      jnp.asarray(layer, jnp.int32), kv_lens, q_lens)


def gather_pages(
    pool: jnp.ndarray,        # [P, K, PS, H] — one layer of the stacked
                              # pool (`pool[l]`: XLA fuses the slice
                              # into the gather, so no layer is copied)
    page_table: jnp.ndarray,  # [B, NP] i32
) -> jnp.ndarray:
    """Materialize per-row contiguous K or V views [B, K, NP*PS, H] by
    gathering pool pages through the table (unmapped sentinel entries clip
    to a real page; their garbage sits at causally masked positions). This
    COPY is what the Pallas kernel's DMA-level gather avoids — it exists
    for the reference path, windows over the kernel's row bound, and
    prefill row views."""
    num_pages, kh, ps, h = pool.shape
    b, np_tab = page_table.shape
    safe = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    g = pool[safe]                          # [B, NP, K, PS, H]
    return g.transpose(0, 2, 1, 3, 4).reshape(b, kh, np_tab * ps, h)


def gather_page_scales(
    pool_s: jnp.ndarray,      # [P, K, PS] — one layer's per-position scales
                              # (`scales[l]` of the stack, as above)
    page_table: jnp.ndarray,  # [B, NP] i32
) -> jnp.ndarray:
    """Materialize per-row contiguous scale views [B, K, NP*PS] by
    gathering scale columns through the table — the H-less twin of
    `gather_pages`, for the int8 pool's reference/verify-window paths."""
    num_pages, kh, ps = pool_s.shape
    b, np_tab = page_table.shape
    safe = jnp.clip(page_table.astype(jnp.int32), 0, num_pages - 1)
    g = pool_s[safe]                        # [B, NP, K, PS]
    return g.transpose(0, 2, 1, 3).reshape(b, kh, np_tab * ps)


def _mask_kv_lens(mask, kv_lens, s_virt):
    kv_idx = jnp.arange(s_virt, dtype=jnp.int32)[None, None, :]
    return mask & (kv_idx < jnp.clip(
        kv_lens.astype(jnp.int32), 0, s_virt
    )[:, None, None])


def _zero_dead_qcols(out, q_lens):
    """The kernel's ragged-window contract for the XLA path: window
    columns at or past a row's q_len return exact zeros (a dead column's
    all-masked softmax would otherwise emit a uniform average)."""
    b, t = out.shape[:2]
    live = (
        jnp.arange(t, dtype=jnp.int32)[None, :]
        < jnp.clip(q_lens.astype(jnp.int32), 0, t)[:, None]
    )
    return jnp.where(live[:, :, None, None], out, jnp.zeros_like(out))


def paged_attention_reference(
    q: jnp.ndarray,            # [B, T, N, H]
    k_pool: jnp.ndarray,       # [P, K, PS, H], or lane-packed
    v_pool: jnp.ndarray,       # [P, K/f, PS, f*H]
    page_table: jnp.ndarray,   # [B, NP] i32
    q_positions: jnp.ndarray,  # [B, T] i32
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,  # [B] i32
    q_lens: Optional[jnp.ndarray] = None,   # [B] i32
) -> jnp.ndarray:
    """XLA reference with the kernel's exact ragged contract (golden in
    tests; serves any T and any per-row window, so oversized windows and
    CPU runs take this path)."""
    from ..attention import attention_mask, gqa_attention

    # A lane-packed pool gathers packed; the einsum wants logical heads.
    f = pack_factor(k_pool, q.shape[3])
    k_full = unpack_cache(gather_pages(k_pool, page_table), f)
    v_full = unpack_cache(gather_pages(v_pool, page_table), f)
    s_virt = k_full.shape[2]
    mask = attention_mask(q_positions, s_virt, sliding_window)
    if kv_lens is not None:
        mask = _mask_kv_lens(mask, kv_lens, s_virt)
    out = gqa_attention(q, k_full, v_full, mask)
    if kv_lens is not None:
        # Fully-parked rows (kv_lens=0) return zeros like the kernel, not
        # a uniform softmax over NEG_INF scores.
        out = jnp.where(
            (kv_lens > 0)[:, None, None, None], out, jnp.zeros_like(out)
        )
    if q_lens is not None:
        out = _zero_dead_qcols(out, q_lens)
    return out


def paged_attention_reference_quantized(
    q: jnp.ndarray,            # [B, T, N, H]
    k_pool: jnp.ndarray,       # [P, K, PS, H] int8
    k_scale: jnp.ndarray,      # [P, K, PS] f32
    v_pool: jnp.ndarray,       # [P, K, PS, H] int8
    v_scale: jnp.ndarray,      # [P, K, PS] f32
    page_table: jnp.ndarray,   # [B, NP] i32
    q_positions: jnp.ndarray,  # [B, T] i32
    sliding_window: Optional[int] = None,
    kv_lens: Optional[jnp.ndarray] = None,  # [B] i32
    q_lens: Optional[jnp.ndarray] = None,   # [B] i32
) -> jnp.ndarray:
    """XLA reference over the int8 pool: gather value pages AND scale
    columns through the table, then run the int8-streaming einsum
    attention (ops/attention.gqa_attention_quantized — the contiguous
    int8 cache's exact math). Serves any T and any per-row window, so
    quantized oversized windows and CPU decode run through it."""
    from ..attention import attention_mask, gqa_attention_quantized

    k_full = gather_pages(k_pool, page_table)
    v_full = gather_pages(v_pool, page_table)
    ks_full = gather_page_scales(k_scale, page_table)
    vs_full = gather_page_scales(v_scale, page_table)
    s_virt = k_full.shape[2]
    mask = attention_mask(q_positions, s_virt, sliding_window)
    if kv_lens is not None:
        mask = _mask_kv_lens(mask, kv_lens, s_virt)
    out = gqa_attention_quantized(q, k_full, ks_full, v_full, vs_full, mask)
    if kv_lens is not None:
        out = jnp.where(
            (kv_lens > 0)[:, None, None, None], out, jnp.zeros_like(out)
        )
    if q_lens is not None:
        out = _zero_dead_qcols(out, q_lens)
    return out
