"""Fused page-write: the scatter-through-table twin of the ragged read.

`ops/pallas/paged_attention.py` moved the paged READ's gather into the DMA
engine (page table in scalar prefetch, pool page picked by the index map);
this module does the same for the WRITE side — the per-layer
write-through-table scatter in `models/llama.forward`'s paged branch, the
known decode hot-path suspect opposite the already-kernelized read.

Why the XLA scatter hurts at decode: `pool.at[layer, pages, :, offs].set`
is a gather-indexed scatter over a [L, P, K, PS, H] operand — XLA lowers
it as a scatter op whose operand layout frequently forces a full-pool
layout-conversion copy per layer (the same pathology
`models/llama._update_cache_layer`'s docstring measured for the contiguous
cache), and even the good lowering re-touches whole pages to land a
[B, T, K, H] sliver. The kernel instead moves only the pages the launch
writes, straight to and from the pool the scalar-prefetched table names.

Kernel design:

- Grid = (B, T), one written position per cell, cells in order on one
  core. The (page, offset, validity) triples are tiny int math done
  OUTSIDE the kernel (`_coords`) and ride scalar prefetch; the pools alias
  their outputs, so the pages a launch does not touch never move.
- Each pool rides the Pallas pipeline as WHOLE-PAGE blocks
  `[1, 1, K, PS(, H)]` picked by the table. A one-position sliver cannot
  move alone: Mosaic DMAs only slices aligned to the pool's (8, 128)
  tiling on its minor dims (the first design's sliver DMA passed every
  interpret-mode test and was refused by the chip's compiler, as was a
  `[K, PS]` scale slab, whose lane dim the HBM layout pads to 128). The
  cell overwrites its position's row of the page block in VMEM with a
  select; consecutive cells on one page share a single fetch and a single
  write-back (the out block stays resident while its index does not
  change), so a T-token window costs one page in and out per page it
  touches, not per token.
- Unmapped / out-of-row positions carry an invalid flag and write nothing
  — the same drop semantics jax gives the XLA scatter's OOB indices, so
  parked scheduler slots and prefill padding rows are inert. A dropped
  cell still has to map SOME block: it maps its live neighbour's
  (`_block_coords`), never a block of its own, because a block fetched
  while its page is still being written back would carry stale rows.
- K and V land in one kernel launch per layer (the "fused" half: the XLA
  path dispatched two scatters per layer); the quantizing variant also
  computes the per-position absmax scale over H on the VPU and writes
  int8 values + f32 scales in the same launch.
- Contract: a row's written positions ascend, and rows own their write
  pages exclusively (the scheduler's copy-on-write sweep guarantees no
  shared page sits in a write range) — so no page is reopened after its
  write-back started. The reference scatter has no such condition; every
  caller meets it.

`paged_write_reference` / `paged_write_reference_quantized` are the XLA
goldens: bit-identical in interpret mode (parity tests) and compiled on a
v5e (`chip_smoke.py`, both variants), and the always-correct path `models/llama.forward` keeps for the einsum impl —
bf16 paged serving off-TPU is byte-for-byte what it was before this
kernel existed.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..lanepack import pack_factor, pack_heads
from ..quant import KV_SCALE_STEP
from .dispatch import resolve_interpret


def _coords(positions, page_table, page_size, num_pages, q_lens=None):
    """(pages [B, T], offs [B, T]): pool page + in-page offset per written
    position. Positions past the virtual row or through an unmapped table
    entry get page == num_pages — the kernel's skip flag and the XLA
    scatter's dropped-OOB index, one definition shared by both paths.
    `q_lens` [B] (the ragged-window contract shared with the attention
    kernel) additionally drops window columns at or past a row's live
    query length, so mixed prefill+decode launches can pad every row to
    one T without phantom writes."""
    pos = positions.astype(jnp.int32)
    np_tab = page_table.shape[1]
    page_idx = pos // page_size
    pages = jnp.take_along_axis(
        page_table.astype(jnp.int32),
        jnp.clip(page_idx, 0, np_tab - 1), axis=1,
    )
    # Past-the-row positions must DROP, not clip (a clipped lookup would
    # alias the row's last mapped page — the resumed-final-chunk overhang
    # regression the scheduler's prefill scatter documents).
    pages = jnp.where(
        (page_idx >= 0) & (page_idx < np_tab), pages, jnp.int32(num_pages)
    )
    if q_lens is not None:
        t = pos.shape[1]
        live = (
            jnp.arange(t, dtype=jnp.int32)[None, :]
            < jnp.clip(q_lens.astype(jnp.int32), 0, t)[:, None]
        )
        pages = jnp.where(live, pages, jnp.int32(num_pages))
    offs = pos % page_size
    return pages, offs


def _block_coords(pages, num_pages):
    """(blk [B, T], first [B, T]) for the kernels' page-block pipeline.

    Every grid cell maps one pool page as its in/out block, dropped cells
    included. `blk` gives a dropped cell the page of the nearest live cell
    before it in grid order (or, ahead of the first live cell, that
    cell's), so a dropped cell never opens a block of its own: a block
    fetched while its page is still being written back would return stale
    rows over the fresh ones. `first` flags the cells where the block
    changes — there the kernel seeds the out block from the in block.
    With no live cell at all, every cell maps the last page and writes it
    back unchanged."""
    flat = pages.reshape(-1)
    live = flat < num_pages
    idx = jnp.arange(flat.shape[0], dtype=jnp.int32)
    prev_live = jax.lax.cummax(jnp.where(live, idx, -1))
    src = jnp.where(prev_live >= 0, prev_live, jnp.argmax(live))
    blk = jnp.minimum(flat[src], num_pages - 1)
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32), (blk[1:] != blk[:-1]).astype(jnp.int32)])
    return blk.reshape(pages.shape), first.reshape(pages.shape)


def _put_row(out_ref, new, row):
    """Overwrite in-page position `row` of a [1, 1, K, PS, H] page block
    with `new` [K, H]: a select over the block, widened to 32 bits (a
    dynamic one-row store into a packed bf16/int8 tile does not lower,
    nor does broadcasting a packed sliver narrower than a lane tile; the
    round trip through f32/i32 is exact)."""
    page = out_ref[0, 0]
    wide = jnp.int32 if page.dtype == jnp.int8 else jnp.float32
    rows = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
    out_ref[0, 0] = jnp.where(
        rows == row, new.astype(wide)[:, None, :], page.astype(wide)
    ).astype(page.dtype)


def _put_col(out_ref, new, col):
    """The [1, 1, K, PS] scale-block twin of `_put_row`."""
    page = out_ref[0, 0]
    cols = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
    out_ref[0, 0] = jnp.where(cols == col, new[:, None], page)


def _bf16_write_kernel(blk_ref, first_ref, pages_ref, offs_ref,
                       knew_ref, vnew_ref, kp_ref, vp_ref, okp, ovp, *,
                       num_pages: int):
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(first_ref[b, t] == 1)
    def _():
        okp[...] = kp_ref[...]
        ovp[...] = vp_ref[...]

    @pl.when(pages_ref[b, t] < num_pages)
    def _():
        _put_row(okp, knew_ref[0, 0], offs_ref[b, t])
        _put_row(ovp, vnew_ref[0, 0], offs_ref[b, t])


def _quant_write_kernel(blk_ref, first_ref, pages_ref, offs_ref,
                        knew_ref, vnew_ref, kp_ref, ks_ref, vp_ref, vs_ref,
                        okp, oks, ovp, ovs, *, num_pages: int):
    b, t = pl.program_id(0), pl.program_id(1)

    def quantize(x):
        x = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(x), axis=-1) * KV_SCALE_STEP  # [K]
        s = jnp.where(s == 0.0, 1.0, s)
        return jnp.clip(jnp.round(x / s[:, None]), -127, 127), s

    @pl.when(first_ref[b, t] == 1)
    def _():
        for src, dst in ((kp_ref, okp), (ks_ref, oks),
                         (vp_ref, ovp), (vs_ref, ovs)):
            dst[...] = src[...]

    @pl.when(pages_ref[b, t] < num_pages)
    def _():
        for new_ref, oq, os_ in ((knew_ref, okp, oks), (vnew_ref, ovp, ovs)):
            q, s = quantize(new_ref[0, 0])
            _put_row(oq, q, offs_ref[b, t])
            _put_col(os_, s, offs_ref[b, t])


def _run_write(kernel, pools, k_new, v_new, positions, page_table, layer,
               q_lens, interpret, name):
    """Grid (B, T), one written position per cell, cells in order on one
    core. Each pool rides the pipeline as whole-page blocks picked by the
    scalar-prefetched `blk` table and aliases its output: consecutive
    cells on one page share a single fetch and write-back, and the pages
    a launch does not touch never move. Rows own their write pages
    exclusively and a row's positions ascend, so no page is reopened
    after its write-back started."""
    num_pages, kh, ps = pools[0].shape[1:4]
    h = k_new.shape[-1]
    interpret = resolve_interpret(interpret)
    pages, offs = _coords(positions, page_table, ps, num_pages, q_lens)
    blk, first = _block_coords(pages, num_pages)
    b, t = pages.shape
    sliver = pl.BlockSpec((1, 1, kh, h), lambda bi, ti, *_: (bi, ti, 0, 0))

    def page_spec(pool):
        tail = pool.shape[2:]
        return pl.BlockSpec(
            (1, 1) + tail,
            lambda bi, ti, blk_, *_: (layer, blk_[bi, ti]) + (0,) * len(tail),
        )

    n_prefetch = 4
    return pl.pallas_call(
        functools.partial(kernel, num_pages=num_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(b, t),
            in_specs=[sliver, sliver] + [page_spec(p) for p in pools],
            out_specs=[page_spec(p) for p in pools],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # args: prefetch + (k_new, v_new), then the pools in output order.
        input_output_aliases={n_prefetch + 2 + i: i
                              for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=name,
        interpret=interpret,
    )(blk, first, pages, offs, k_new, v_new, *pools)


@functools.partial(jax.jit, static_argnums=(6,),
                   static_argnames=("interpret",))
def fused_page_write(
    kp: jnp.ndarray,          # [L, P, K, PS, H] — shared K page pool,
    vp: jnp.ndarray,          # or lane-packed [L, P, K/f, PS, f*H]
    k_new: jnp.ndarray,       # [B, T, K, H] fresh K sliver
    v_new: jnp.ndarray,       # [B, T, K, H]
    positions: jnp.ndarray,   # [B, T] i32 absolute positions
    page_table: jnp.ndarray,  # [B, NP] i32
    layer: int,
    *,
    q_lens: Optional[jnp.ndarray] = None,  # [B] i32 live cols per row
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write K and V slivers through per-row page tables at a static layer
    index, in one kernel launch (the Pallas twin of
    `paged_write_reference`, which remains the XLA/CPU golden). Both
    pools alias their outputs: HBM traffic is the touched pages alone.
    Into a lane-packed pool (ops/lanepack.py) a position's `[K, H]` goes
    as `[K/f, f*H]`, a reshape: the same bytes, rows of full lanes."""
    f = pack_factor(kp, k_new.shape[-1])
    return _run_write(
        _bf16_write_kernel, (kp, vp), pack_heads(k_new.astype(kp.dtype), f),
        pack_heads(v_new.astype(vp.dtype), f), positions, page_table, layer,
        q_lens, interpret, "fused_page_write")


@functools.partial(jax.jit, static_argnums=(8,),
                   static_argnames=("interpret",))
def fused_page_write_quantized(
    kp: jnp.ndarray,          # [L, P, K, PS, H] int8
    kps: jnp.ndarray,         # [L, P, K, PS] f32 per-position K scales
    vp: jnp.ndarray,          # [L, P, K, PS, H] int8
    vps: jnp.ndarray,         # [L, P, K, PS] f32
    k_new: jnp.ndarray,       # [B, T, K, H] fresh bf16/f32 K sliver
    v_new: jnp.ndarray,       # [B, T, K, H]
    positions: jnp.ndarray,   # [B, T] i32
    page_table: jnp.ndarray,  # [B, NP] i32
    layer: int,
    *,
    q_lens: Optional[jnp.ndarray] = None,  # [B] i32 live cols per row
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The int8-quantizing fused write: absmax-over-H scales computed on
    the VPU inside the kernel (ops/quant.quantize_kv's exact math —
    parity-tested against `paged_write_reference_quantized`), int8 values
    + f32 scales written in the same launch."""
    return _run_write(
        _quant_write_kernel, (kp, kps, vp, vps), k_new, v_new, positions,
        page_table, layer, q_lens, interpret, "fused_page_write_quantized")


def paged_write_reference(
    pool: jnp.ndarray,        # [L, P, K, PS, H], or lane-packed
    new: jnp.ndarray,         # [B, T, K, H]
    positions: jnp.ndarray,   # [B, T] i32
    page_table: jnp.ndarray,  # [B, NP] i32
    layer: int,
    q_lens: Optional[jnp.ndarray] = None,  # [B] i32 live cols per row
) -> jnp.ndarray:
    """XLA golden for the value write (one K-or-V pool): a single scatter
    through the table whose OOB indices drop — parked/padding rows,
    past-the-row positions, and (with `q_lens`) dead window columns write
    nothing. This IS the pre-kernel write path, verbatim, so the bf16 CPU
    serving path stays bit-identical."""
    num_pages = pool.shape[1]
    ps = pool.shape[3]
    pages, offs = _coords(positions, page_table, ps, num_pages, q_lens)
    # Advanced indices at non-adjacent dims (pool page, in-page offset)
    # broadcast to the front: the update is [B, T, K, H] — exactly `new`.
    new = pack_heads(new.astype(pool.dtype), pack_factor(pool, new.shape[-1]))
    return pool.at[layer, pages, :, offs].set(new)


def paged_write_reference_quantized(
    kp: jnp.ndarray, kps: jnp.ndarray, vp: jnp.ndarray, vps: jnp.ndarray,
    k_new: jnp.ndarray, v_new: jnp.ndarray,
    positions: jnp.ndarray, page_table: jnp.ndarray, layer: int,
    q_lens: Optional[jnp.ndarray] = None,  # [B] i32 live cols per row
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """XLA golden for the quantizing write: ops/quant.quantize_kv on the
    fresh slivers, then the value scatter plus its scale twin (the scale
    pool drops the H axis; same dropped-OOB semantics)."""
    from ..quant import quantize_kv

    num_pages = kp.shape[1]
    ps = kp.shape[3]
    pages, offs = _coords(positions, page_table, ps, num_pages, q_lens)
    kq, vq = quantize_kv(k_new), quantize_kv(v_new)
    return (
        kp.at[layer, pages, :, offs].set(kq["q8"]),
        kps.at[layer, pages, :, offs].set(kq["s"]),
        vp.at[layer, pages, :, offs].set(vq["q8"]),
        vps.at[layer, pages, :, offs].set(vq["s"]),
    )
