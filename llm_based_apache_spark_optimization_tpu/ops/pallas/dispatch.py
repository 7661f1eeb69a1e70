"""Attention implementation selection: XLA einsum vs Pallas flash kernel.

Modes:
- "xla"    — always the einsum reference path (`ops.attention.gqa_attention`).
- "pallas" — always the flash kernel (interpreted off-TPU: the CPU tests).
- "auto"   — (default) flash kernel on TPU, einsum otherwise. Under a mesh
  the kernel runs per-device through the `shard_map` wrapper
  (`ops.pallas.attention.sharded_flash_gqa_attention`) over the tp-sharded
  KV-head axis and dp-sharded batch — the HBM-bound TP serving configs
  (BASELINE 4/5) are exactly where the kernel matters most.

Selected once per `forward` trace; override globally with
`set_attention_impl(...)` or per-process with LBASO_ATTENTION_IMPL.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_VALID = ("auto", "xla", "pallas")
_mode: Optional[str] = None


def set_attention_impl(mode: Optional[str]) -> None:
    """Force 'xla'/'pallas', or restore the default with 'auto'/None.

    'auto' clears the override entirely so the LBASO_ATTENTION_IMPL env var
    (the operator's setting) is consulted again rather than being shadowed.
    """
    global _mode
    if mode is not None and mode not in _VALID:
        raise ValueError(f"attention impl {mode!r} not in {_VALID}")
    _mode = None if mode in (None, "auto") else mode


def _resolve_mode() -> str:
    """The effective mode: 'auto', or a forced 'xla'/'pallas'."""
    mode = _mode or os.environ.get("LBASO_ATTENTION_IMPL", "auto")
    if mode not in _VALID:
        raise ValueError(f"LBASO_ATTENTION_IMPL={mode!r} not in {_VALID}")
    return mode


def on_tpu() -> bool:
    """The one platform test behind every automatic choice in this
    package: kernel or einsum, compiled or interpreted."""
    return jax.devices()[0].platform == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """`interpret=None` on a kernel wrapper: compiled by Mosaic on a TPU,
    the Pallas interpreter anywhere else (the CPU tests). Servers print
    the outcome once at start (the scheduler's `kernel_modes`), so an
    interpreted kernel can never pass for a device run."""
    return not on_tpu() if interpret is None else interpret


def attention_impl(mesh=None) -> str:
    """Resolve to 'xla' or 'pallas' for the current trace."""
    mode = _resolve_mode()
    if mode != "auto":
        return mode
    return "pallas" if on_tpu() else "xla"


# Auto-mode decode crossover: the flash kernel pays ~0.05 ms/layer of cell
# overhead at T=1 (measured v5e, K-folded grid), while the einsum path reads
# the FULL cache but fuses to zero overhead — measured faster up to at least
# a 1 GB mostly-live cache (bench-1b B=32 S=1024: einsum 4091 tok/s vs
# kernel 2779). The kernel's per-row kv_lens bounding only pays off when a
# large persistent cache is mostly DEAD (continuous-batching slots: parked
# rows, fresh requests at low positions). Assuming ~50% live occupancy,
# kernel wins when 0.5 * cache_bytes / 819 GB/s > layers * 0.05 ms, i.e.
# cache over ~1.3-2.6 GB per device; below that einsum wins outright.
_PALLAS_DECODE_MIN_CACHE_BYTES = int(1.5e9)


def decode_attention_impl(mesh=None, cache_bytes_per_device=None) -> str:
    """Resolve the T=1 (decode) attention impl.

    Honors a forced mode exactly like `attention_impl`. In auto mode decode
    prefers the XLA einsum path — uniform request-sized caches are mostly
    live, so bounded streaming saves nothing and the kernel's per-cell
    overhead is pure loss — unless the caller's persistent cache
    (`cache_bytes_per_device`) is past the measured crossover where per-row
    bounded streaming of mostly-dead slots wins (continuous-batching
    scheduler over a large window)."""
    mode = _resolve_mode()
    if mode != "auto":
        return mode
    if not on_tpu():
        return "xla"
    if (cache_bytes_per_device or 0) >= _PALLAS_DECODE_MIN_CACHE_BYTES:
        return "pallas"
    return "xla"
