"""int4 weight-only matmul as a Pallas TPU kernel.

The reference's models ship as 4-bit GGUF blobs (Q4_K) and llama.cpp serves
them at 4-bit bandwidth; the in-tree int8 path stops at half-bytes. This
kernel closes that gap for the weight-streaming-bound decode loop: weights
stream HBM→VMEM as PACKED nibbles (two 4-bit values per uint8 byte along
the contraction axis) plus one f32 scale per (group, out-channel), are
dequantized in VMEM, and feed the MXU — HBM sees one QUARTER of bf16's
weight bytes.

Layout (ops/quant.quantize_weight_int4):
    q4 : uint8 [in/2, out]    — byte b holds contraction rows 2b (low
                                nibble) and 2b+1 (high), value = nibble - 8
    s4 : f32  [in/group, out] — symmetric absmax scale per group×channel

Kernel shape choices:
- Unpacking nibbles in place would interleave rows ([IB/2, 2, OB] →
  [IB, OB], a Mosaic relayout per weight block). Instead the CALLER splits
  x once into its even/odd contraction planes (x is tiny next to the
  weight) and each cell runs two half-dots against the low/high nibble
  planes — elementwise ops + MXU dots only.
- A cell spans SEVERAL quantization groups (in-block = k·group): one cell
  per group would drown 7B shapes in per-cell dispatch overhead. Group
  scales apply via a leading-dim reshape ([k, group/2, OB] · s[k, 1, OB]),
  which merges back without touching the lane layout.
- The contraction axis runs innermost, accumulating into f32 VMEM scratch;
  each weight block is streamed exactly once per call.

Exactness: the kernel computes the same products as
x @ dequantize_weight_int4(w) with per-block f32 accumulation (asserted
against the jnp reference in tests/test_int4.py).

Packed storage, not the jnp.int4 dtype: the kernel unpacks nibbles itself,
so what streams from HBM is plain uint8/f32 with no layout XLA may pad.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import resolve_interpret


def unpack_nibbles(q4: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., in/2, out] -> int8 [..., in, out] of values in [-8, 7].

    Row 2b is byte b's LOW nibble, row 2b+1 its HIGH nibble (interleave on
    the contraction axis, matching quantize_weight_int4's packing). Host /
    reference-path helper — the kernel never materializes this layout.
    """
    lo = jnp.bitwise_and(q4, jnp.uint8(0x0F)).astype(jnp.int8) - 8
    hi = jnp.right_shift(q4, jnp.uint8(4)).astype(jnp.int8) - 8
    stacked = jnp.stack([lo, hi], axis=-2)  # [..., in/2, 2, out]
    return stacked.reshape(*q4.shape[:-2], q4.shape[-2] * 2, q4.shape[-1])


def _int4_mm_kernel(xe_ref, xo_ref, q4_ref, s4_ref, o_ref, acc_ref, *,
                    n_in_blocks, k_groups):
    """One (row-block, out-block, in-block) cell: in-block covers k_groups
    quant groups; see module docstring for the even/odd-plane
    formulation."""
    i_idx = pl.program_id(2)

    @pl.when(i_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q4 = q4_ref[...]                 # [IB/2, OB] uint8
    s4 = s4_ref[...]                 # [k_groups, OB] f32
    dt = xe_ref.dtype
    half, ob = q4.shape
    g2 = half // k_groups            # rows of a group's even (or odd) plane

    def deq(nib):
        scaled = (nib.astype(jnp.float32).reshape(k_groups, g2, ob)
                  * s4[:, None, :])
        return scaled.reshape(half, ob).astype(dt)

    # Widen before the nibble arithmetic: Mosaic has no 8-bit vector
    # subtract.
    q32 = q4.astype(jnp.int32)
    lo = jnp.bitwise_and(q32, 0x0F) - 8
    hi = jnp.right_shift(q32, 4) - 8
    dn = (((1,), (0,)), ((), ()))
    acc_ref[:] += jax.lax.dot_general(
        xe_ref[...], deq(lo), dimension_numbers=dn,
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        xo_ref[...], deq(hi), dimension_numbers=dn,
        preferred_element_type=jnp.float32,
    )

    @pl.when(i_idx == n_in_blocks - 1)
    def _finalize():
        o_ref[:] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def int4_matmul(
    x: jnp.ndarray,    # [R, IN] (bf16/f32)
    q4: jnp.ndarray,   # [IN/2, OUT] uint8 packed nibbles
    s4: jnp.ndarray,   # [IN/GROUP, OUT] f32 group scales
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """x @ dequant(q4, s4), streaming the weight at 4-bit bandwidth.

    Block sizing: the in-block is the largest ≤8-group multiple that
    divides the group count (cells must tile the axis evenly); out tiles
    at 512/256/128 lanes or runs whole when smaller. Returns [R, OUT] in
    x.dtype.
    """
    if q4.ndim == 3:
        # Stacked fused weight [IN/2, C, OUT] (models/llama.fuse_blocks):
        # the (C, OUT) tail is contiguous row-major, so flattening it to one
        # out axis is free and the kernel runs unchanged; the caller's
        # [R, C, OUT] view is the same bytes back.
        d2, c, o = q4.shape
        out = int4_matmul(x, q4.reshape(d2, c * o),
                          s4.reshape(s4.shape[0], c * o), interpret=interpret)
        return out.reshape(out.shape[0], c, o)
    r, n_in = x.shape
    n_out = q4.shape[1]
    n_groups = s4.shape[0]
    group = n_in // n_groups
    if n_in % n_groups or (n_in // 2) != q4.shape[0] or group % 2:
        raise ValueError(
            f"inconsistent int4 shapes: x in={n_in}, q4 rows={q4.shape[0]}, "
            f"groups={n_groups}"
        )
    interpret = resolve_interpret(interpret)

    k_groups = min(8, n_groups)
    while n_groups % k_groups:
        k_groups -= 1
    ib = group * k_groups
    n_in_blocks = n_in // ib
    ob = next((c for c in (512, 256, 128) if n_out % c == 0), n_out)
    # Row tiling bounds the f32 scratch and x/out blocks for prefill-shaped
    # calls (rows = batch*seq can be thousands, and an untiled scratch
    # would blow the ~16 MB/core VMEM); decode-small row counts run whole.
    # Rows that don't divide 128 pad up to the next 128 multiple (output
    # sliced back) — falling back to rb=r would rebuild exactly the untiled
    # scratch the tiling exists to bound (advisor r4 finding).
    rows = r
    rb = next((c for c in (256, 128) if r % c == 0), None)
    if rb is None:
        if r <= 256:
            rb = r
        else:
            rows = -(-r // 128) * 128
            x = jnp.pad(x, ((0, rows - r), (0, 0)))
            rb = 256 if rows % 256 == 0 else 128
    grid = (rows // rb, n_out // ob, n_in_blocks)

    # Even/odd contraction planes (module docstring): plane p holds
    # original rows 2b+p, aligned with byte b's low/high nibble. Group g's
    # even rows are CONTIGUOUS in the plane ([g*group/2, (g+1)*group/2)),
    # which is what lets the kernel scale by group with a pure reshape.
    x3 = x.reshape(rows, n_in // 2, 2)
    xe, xo = x3[:, :, 0], x3[:, :, 1]   # each [R, IN/2]

    out = pl.pallas_call(
        functools.partial(_int4_mm_kernel, n_in_blocks=n_in_blocks,
                          k_groups=k_groups),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, ib // 2), lambda ri, oi, ii: (ri, ii)),
            pl.BlockSpec((rb, ib // 2), lambda ri, oi, ii: (ri, ii)),
            pl.BlockSpec((ib // 2, ob), lambda ri, oi, ii: (ii, oi)),
            pl.BlockSpec((k_groups, ob), lambda ri, oi, ii: (ii, oi)),
        ],
        out_specs=pl.BlockSpec((rb, ob), lambda ri, oi, ii: (ri, oi)),
        out_shape=jax.ShapeDtypeStruct((rows, n_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((rb, ob), jnp.float32)],
        # Row/out-blocks are independent (megacore splits them); the
        # in-block axis accumulates through scratch and must run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xe, xo, q4, s4)
    return out[:r] if rows != r else out


def sharded_int4_matmul(
    mesh,
    x: jnp.ndarray,    # [R, IN] — rows dp-sharded (engine batch layout)
    q4: jnp.ndarray,   # [IN/2, OUT] or stacked [IN/2, C, OUT]
    s4: jnp.ndarray,   # [IN/GROUP, OUT] or [IN/GROUP, C, OUT]
    *,
    partition: str = "col",
) -> jnp.ndarray:
    """The int4 kernel under a dp×tp mesh, via `jax.shard_map`.

    A pallas_call cannot run on GSPMD-sharded operands, so each Megatron
    partition gets an explicit per-device body (the same split
    parallel/sharding.param_specs encodes for the int8/bf16 dots, where
    GSPMD does this implicitly):

    - "col" (wq/wk/wv/wg/wu and the stacked fused trees): the weight's out
      axis is tp-sharded; every device runs the kernel on its own column
      shard of replicated-activation rows — no collective. Stacked [.., C,
      OUT] weights shard the OUT axis and keep the C split device-local.
    - "row" (wo/wd): the CONTRACTION axis is tp-sharded — the packed-nibble
      axis splits at even byte boundaries and whole quant groups (tp divides
      the group count: group=128 and the head/ffn dims are multiples of
      128·tp for every supported config), each device contracts its own
      slice, and a `psum` over "tp" reduces the partial products. The group
      scales apply INSIDE the kernel, before the psum — correct because a
      group's scale multiplies only that group's products, all of which
      live on one device.

    The "sp" mesh axis is unmentioned (replicated): activations outside
    ring attention keep the sequence axis whole. check_vma=False for the
    same reason as the sharded flash kernels — the replication checker
    can't see through pallas_call.
    """
    from jax.sharding import PartitionSpec as P

    if partition == "col":
        wspec = P(None, "tp") if q4.ndim == 2 else P(None, None, "tp")
        out_spec = P("dp", "tp") if q4.ndim == 2 else P("dp", None, "tp")
        return jax.shard_map(
            lambda x_, q_, s_: int4_matmul(x_, q_, s_),
            mesh=mesh,
            in_specs=(P("dp", None), wspec, wspec),
            out_specs=out_spec,
            check_vma=False,
        )(x, q4, s4)
    if partition != "row":
        raise ValueError(f"partition must be 'col' or 'row', got {partition!r}")

    def row_body(x_, q_, s_):
        return jax.lax.psum(int4_matmul(x_, q_, s_), "tp")

    return jax.shard_map(
        row_body,
        mesh=mesh,
        in_specs=(P("dp", "tp"), P("tp", None), P("tp", None)),
        out_specs=P("dp", None),
        check_vma=False,
    )(x, q4, s4)
