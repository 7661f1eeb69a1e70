"""Lane packing: `f` narrow KV heads share one 128-lane row.

A KV head narrower than the TPU's 128-lane tile makes every device program
pay for the gap: XLA keeps a pool whose minor axis is 64 with another axis
minor, the Mosaic calls want it row-major, and the program converts the
whole pool in and out around them, padding each row to 128 lanes on the
way (PERF.md section 5, "What `copy` is"). So where
`engine/paged_kv.lane_pack` says `f > 1`, K and V are STORED with `f`
heads side by side in a row:

    packed[..., j, s, i*H + d] == logical[..., f*j + i, s, d]

for a pool `[L, P, K/f, page, f*H]` and for the row views `[L, k, K/f, S,
f*H]` that batched prefill gathers from it. The stored array's own shape
is the only record of `f` (`stored.shape[-1] // head_dim`); `f == 1` is
the plain layout and every function here is then the identity.

- A position's fresh K or V `[..., K, H]` is `[..., K/f, f*H]` by a
  reshape (`pack_heads`): the write side moves the same bytes.
- To an attention kernel a packed cache is a GQA cache of `K/f` heads of
  width `f*H` whose group holds `f*G` query rows. `spread_queries` places
  query head `n` (KV head `f*j + i`) in lanes `[i*H, (i+1)*H)` of a zero
  row, so the 128-lane contraction with a packed K row adds exact zeros to
  the f32 scores of head `f*j + i` alone; the PV product then carries all
  `f` heads' outputs side by side and `gather_outputs` keeps lanes
  `[i*H, (i+1)*H)` (by a select: see there why not by slices). The
  softmax scale stays that of the TRUE head width.
- `pack_cache` / `unpack_cache` move a whole `[..., K, S, H]` array
  between the two layouts (a transpose): the engines' prefill -> pool
  hand-off and the einsum reference paths, never a served decode step.
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 128  # the TPU's lane tile: the row width a stored K/V array wants


def pack_factor(stored: jnp.ndarray, head_dim: int) -> int:
    """`f` of a stored K/V array, read off its minor axis."""
    return stored.shape[-1] // head_dim


def pack_heads(new: jnp.ndarray, f: int) -> jnp.ndarray:
    """Fresh K or V `[..., K, H]` -> `[..., K/f, f*H]` (a reshape)."""
    if f == 1:
        return new
    *lead, kh, h = new.shape
    return new.reshape(*lead, kh // f, f * h)


def pack_cache(x: jnp.ndarray, f: int) -> jnp.ndarray:
    """`[..., K, S, H]` -> `[..., K/f, S, f*H]`."""
    if f == 1:
        return x
    *lead, kh, s, h = x.shape
    n = len(lead)
    return (x.reshape(*lead, kh // f, f, s, h)
            .transpose(*range(n + 1), n + 2, n + 1, n + 3)
            .reshape(*lead, kh // f, s, f * h))


def unpack_cache(x: jnp.ndarray, f: int) -> jnp.ndarray:
    """`[..., K/f, S, f*H]` -> `[..., K, S, H]`, `pack_cache`'s inverse."""
    if f == 1:
        return x
    *lead, kp, s, w = x.shape
    n = len(lead)
    return (x.reshape(*lead, kp, s, f, w // f)
            .transpose(*range(n + 1), n + 2, n + 1, n + 3)
            .reshape(*lead, kp * f, s, w // f))


def spread_queries(q: jnp.ndarray, f: int, kv_heads: int) -> jnp.ndarray:
    """`[B, T, N, H]` -> `[B, T, N, f*H]` for a cache of `kv_heads` PACKED
    heads: query head `n` of KV head `f*j + i` keeps its values in lanes
    `[i*H, (i+1)*H)`, zeros elsewhere. The head order does not change, so
    the kernels' own GQA fold (`N // kv_heads` rows a packed head) groups
    exactly the `f*G` query heads that read packed head `j`."""
    if f == 1:
        return q
    b, t, n, h = q.shape
    q6 = q.reshape(b, t, kv_heads, f, n // (kv_heads * f), h)
    rows = [
        jnp.pad(q6[:, :, :, i],
                ((0, 0),) * 4 + ((i * h, (f - 1 - i) * h),))
        for i in range(f)
    ]
    return jnp.stack(rows, axis=3).reshape(b, t, n, f * h)


def gather_outputs(out: jnp.ndarray, f: int, kv_heads: int) -> jnp.ndarray:
    """`[B, T, N, f*H]` -> `[B, T, N, H]`: of each query head's row, the
    lanes of its own KV head (`spread_queries`' inverse on the output).

    A select against a constant mask and a sum over the `f` lane groups
    (one value and zeros: exact), and on purpose not `f` static slices at
    lane offsets `i*H` stacked back together: jitted with the kernel call,
    the TPU compiler returned wrong values for every head with `i > 0`
    from that form (v5e, PR 33's chip runs; each op alone, and the
    interpreter, were right)."""
    if f == 1:
        return out
    b, t, n, w = out.shape
    own = (jnp.arange(n) // (n // (kv_heads * f))) % f       # [N]: i of n
    mask = own[:, None, None] == jnp.arange(f)[None, :, None]
    o5 = out.reshape(b, t, n, f, w // f)
    return jnp.where(mask, o5, 0).sum(axis=3).astype(out.dtype)
