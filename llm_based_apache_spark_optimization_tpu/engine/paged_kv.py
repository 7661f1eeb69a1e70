"""Paged KV cache: a shared device page pool + host-side page allocator.

The contiguous layout (engine/kvcache.py) allocates every slot its
worst-case window — `[L, slots, K, S_max, H]` — so concurrent slot count is
bounded by `slots × S_max × layer bytes` no matter how many tokens are
actually live, and the scheduler's prefix cache pays a gather-copy per hit.
The paged layout breaks both bounds (the Ragged Paged Attention / vLLM
PagedAttention design, PAPERS.md):

    pool:        {"kp": [L, P, K, page_size, H], "vp": [L, P, K, page_size, H]}
    page table:  [slots, pages_per_slot] int32 — per-slot logical->pool map

THE STORED SHAPE. Where a head is narrower than the TPU's 128-lane tile
the pool is stored LANE-PACKED, `f` heads a row:

    [L, P, K/f, page_size, f*H],  packed[l,p,j,s,i*H+d] = logical[l,p,f*j+i,s,d]

`lane_pack` below decides `f`, alone, from the configuration and the
pool's kind (`f = 128 // head_dim` for a plain bf16/f32 pool whose KV
heads divide by it; 1 — the shape above — at head 128, for an int8 pool,
for an odd number of KV heads); `init_page_pool` and `pack_prefill_pages`
ask it, and from there the pool's own shape is the only record of `f`:
every reader and writer takes `pool.shape[-1] // head_dim` (ops/lanepack.py
says how each side uses it). A minor axis of 64 would have XLA keep the
pool with another axis minor and convert it whole, in and out, around
every Mosaic call of every device program (PERF.md section 5); stored so,
no program holds a result of pool shape but the donated pool itself.
Whole-page operations (copy-on-write, spill, export/import) index the page
axis and move a page's bytes as they lie; a blob imports only into a pool
of its own stored shape.

- The pool is sized to an HBM budget (`pages_for_budget`), not to
  slots × S_max: a request holds ceil(need / page_size) pages for
  `need = bucketed prompt + max_new + overshoot` — mixed long/short traffic
  stops paying max-bucket padding, and concurrent requests scale with live
  tokens.
- `PageAllocator` is pure host bookkeeping (free list + per-page refcounts):
  page table updates are a few int32 scatters per admission, never a device
  sync. Refcounts make prefix-cache hits ZERO-COPY — a hit maps the cached
  prefix's pages into the new slot's table (refcount++) instead of
  gather-copying K/V.
- Copy-on-write: a shared page is never written in place. The only writer
  of a shared page is a slot whose write range starts INSIDE one — a
  non-page-aligned prefix boundary — and it first copies that one page
  (`PageAllocator.cow` + a one-page device copy) and remaps. Everything
  page-aligned stays zero-copy.
- The unmapped sentinel is `num_pages` (one past the pool): jax drops
  out-of-bounds scatter writes, so unmapped table entries make parked /
  padding rows' K/V writes true no-ops, and gathers clip the sentinel to a
  real page whose garbage the causal mask hides (the same
  visibility-by-causality invariant engine/kvcache.py documents).

Page size rides `LSOT_KV_PAGE_SIZE` (default 64): a multiple of 8 keeps
pool pages sublane-aligned for the Pallas ragged-paged-attention kernel
(ops/pallas/paged_attention.py), whose block grid DMAs one [K, page, H]
page per cell through the scalar-prefetched page table.

`kv_quant="int8"` (ISSUE 11) stores the pool as int8 values plus one f32
scale per (layer, page, kv-head, position) — "kps"/"vps" arrays
[L, P, K, page] beside "kp"/"vp" — so the same HBM budget holds ~2x the
live tokens. Quantization happens on the way IN (pack_prefill_pages, the
prefill windowed scatter, the fused page-write kernel) and dequantization
on the way OUT (inside the ragged read kernel's DMA'd tiles, or the
int8-streaming einsum reference); `page_bytes`/`pages_for_budget` price
the KV dtype so every capacity surface reports true bytes.

`export_pages`/`import_pages` (ISSUE 13) make KV page migration a
first-class op: a request's live pages (values + int8 scales — the full
cache tuple, generalized from the LSOT_KV_SPILL host-copy path) extract
into a portable host blob and install into ANOTHER pool's freshly
allocated pages — the page-table + page-transfer handoff that
disaggregated prefill/decode serving rides (serve/scheduler.py
`phase_role`).
"""

from __future__ import annotations

import os
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.configs import LlamaConfig
from ..ops.lanepack import LANES, pack_cache


class PageAccountingError(RuntimeError):
    """A refcount went negative or a freed page was freed again — the
    allocator's invariants are broken and the pool can no longer be
    trusted (this is a bug, not an operational condition)."""


def default_page_size() -> int:
    """LSOT_KV_PAGE_SIZE (default 64). Must be a positive multiple of 8 so
    pool pages stay sublane-aligned for the TPU kernel's block grid."""
    try:
        ps = int(os.environ.get("LSOT_KV_PAGE_SIZE", "64"))
    except ValueError:
        ps = 64
    if ps <= 0 or ps % 8:
        raise ValueError(
            f"LSOT_KV_PAGE_SIZE must be a positive multiple of 8, got {ps}"
        )
    return ps


def page_bytes(
    cfg: LlamaConfig, page_size: int, itemsize: int = 2,
    kv_quant: Optional[str] = None,
) -> int:
    """Device bytes of ONE pool page across all layers (K and V).

    `kv_quant="int8"` prices the QUANTIZED pool layout: int8 values plus
    one f32 scale per (layer, page, kv-head, position) — the KV dtype, not
    the compute dtype (`itemsize` is ignored there). Every capacity
    surface (pages_for_budget, the scheduler's HBM-budget sizing,
    /metrics serving.kv_pages, the bench accounting) must go through this
    so an int8 pool reports ~2x the true tokens per HBM byte instead of
    compute-dtype fiction."""
    per_pos = cfg.head_dim * itemsize
    if kv_quant == "int8":
        # int8 value bytes + one f32 scale per position (absmax over H).
        per_pos = cfg.head_dim * 1 + 4
    elif kv_quant is not None:
        raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
    return 2 * cfg.num_layers * cfg.num_kv_heads * page_size * per_pos


def pages_for_budget(
    cfg: LlamaConfig, budget_bytes: int, page_size: int, itemsize: int = 2,
    kv_quant: Optional[str] = None,
) -> int:
    """Pool pages an HBM budget buys (the paged twin of
    engine/kvcache.cache_bytes — same cfg, same itemsize convention;
    `kv_quant` prices the int8 page layout, so the same budget buys ~2x
    the pages)."""
    return max(
        0, int(budget_bytes) // page_bytes(cfg, page_size, itemsize, kv_quant)
    )


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages covering n_tokens positions (ceil)."""
    return -(-int(n_tokens) // int(page_size))


def lane_pack(
    cfg: LlamaConfig, kv_quant: Optional[str] = None, tp: int = 1,
) -> int:
    """How many KV heads share one stored row (module docstring, "THE
    STORED SHAPE"): `128 // head_dim` where that fills the lane tile
    exactly, the KV heads divide by it, the packed heads still divide
    over `tp` (the pool shards its head axis) and the pool holds plain
    bf16/f32 values; otherwise 1. An int8 pool stays unpacked: its scale
    per head and position would have to follow its part of the row into
    the kernels."""
    h, kh = cfg.head_dim, cfg.num_kv_heads
    if kv_quant is not None or h >= LANES or LANES % h:
        return 1
    f = LANES // h
    return f if kh % f == 0 and (kh // f) % max(1, int(tp)) == 0 else 1


def init_page_pool(
    cfg: LlamaConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
    kv_quant: Optional[str] = None, tp: int = 1,
) -> Dict[str, jnp.ndarray]:
    """Allocate the shared device page pool. Layout mirrors the contiguous
    cache with the (batch, S) axes replaced by one page axis: per
    (page, kv-head) the pool is a contiguous [page_size, H] tile — the
    MXU/Pallas-friendly trailing (sublane, lane) shape — or, lane-packed
    (`lane_pack`; `tp` is the mesh's tensor-parallel degree), a
    [page_size, f*H] tile per f heads.

    `kv_quant="int8"` stores int8 values plus f32 per-position scales
    ("kps"/"vps", [L, P, K, page_size] — the paged twin of the contiguous
    {"k8","ks","v8","vs"} layout, ops/quant.quantize_kv): the pool holds
    ~2x the live tokens per HBM byte. Scales init to 1.0 so an unwritten
    page dequantizes to harmless zeros, never NaN."""
    if page_size <= 0 or page_size % 8:
        raise ValueError(
            f"page_size must be a positive multiple of 8, got {page_size}"
        )
    f = lane_pack(cfg, kv_quant, tp)
    shape = (cfg.num_layers, num_pages, cfg.num_kv_heads // f, page_size,
             f * cfg.head_dim)
    if kv_quant == "int8":
        sshape = shape[:-1]
        return {
            "kp": jnp.zeros(shape, jnp.int8),
            "kps": jnp.ones(sshape, jnp.float32),
            "vp": jnp.zeros(shape, jnp.int8),
            "vps": jnp.ones(sshape, jnp.float32),
        }
    if kv_quant is not None:
        raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def pack_prefill_pages(
    cache: Dict[str, jnp.ndarray], page_size: int, pages_per_row: int,
    kv_quant: Optional[str] = None, pack: int = 1,
) -> Dict[str, jnp.ndarray]:
    """Contiguous prefill cache {"k","v"} [L, B, K, S, H] -> paged cache
    {"kp","vp","ptab"} with identity per-row tables (row b owns pool pages
    [b*ppr, (b+1)*ppr)).

    The engines' one-XLA-program loops use this as the prefill→decode
    handoff: prefill runs the proven contiguous scan path over a
    prompt-sized transient cache, one transpose-scatter packs its K/V into
    pool pages, and the decode `lax.while_loop` carries the pool + tables
    (models/llama.forward's paged branch). Pure jnp — runs inside jit.

    `kv_quant="int8"` QUANTIZES inside the pack (ops/quant.quantize_kv:
    int8 values + one f32 scale per position, absmax over H) and returns
    the int8 pool layout {"kp","kps","vp","vps","ptab"} — the
    prefill-fills-bf16-then-quantize-once handoff the contiguous int8
    path uses, applied per page. Unwritten pool scale entries stay 1.0 so
    unmapped-page garbage dequantizes finite.

    `pack` (the caller's `lane_pack(cfg, kv_quant, tp)`) stores the pool
    with that many heads a row, as `init_page_pool` would."""
    cache = {n: pack_cache(a, pack) for n, a in cache.items()}
    k = cache["k"]
    n_layers, b, kh, s, h = k.shape
    ppr = int(pages_per_row)
    num_pages = b * ppr
    s_pad = s + (-s % page_size)
    np0 = s_pad // page_size
    if np0 > ppr:
        raise ValueError(
            f"prefill cache ({s} positions = {np0} pages) exceeds "
            f"pages_per_row={ppr}"
        )
    ptab = (
        jnp.arange(b, dtype=jnp.int32)[:, None] * ppr
        + jnp.arange(ppr, dtype=jnp.int32)[None, :]
    )

    def pack(arr, fill=0.0):
        # Values [L, B, K, S, H] and per-position scales [L, B, K, S] both
        # land here: the scale path just drops the trailing H axis.
        has_h = arr.ndim == 5
        pad = ((0, 0), (0, 0), (0, 0), (0, s_pad - s)) + (
            ((0, 0),) if has_h else ()
        )
        a = jnp.pad(arr, pad, constant_values=fill)
        shape = (n_layers, b, kh, np0, page_size) + ((h,) if has_h else ())
        a = a.reshape(shape)
        perm = (0, 1, 3, 2, 4, 5) if has_h else (0, 1, 3, 2, 4)
        a = a.transpose(perm)  # [L, B, np0, K, PS(, H)]
        pool = jnp.full(
            (n_layers, num_pages, kh, page_size) + ((h,) if has_h else ()),
            fill, arr.dtype,
        )
        return pool.at[:, ptab[:, :np0]].set(a)

    if kv_quant == "int8":
        from ..ops.quant import quantize_kv

        kq, vq = quantize_kv(cache["k"]), quantize_kv(cache["v"])
        return {
            "kp": pack(kq["q8"]), "kps": pack(kq["s"], fill=1.0),
            "vp": pack(vq["q8"]), "vps": pack(vq["s"], fill=1.0),
            "ptab": ptab,
        }
    if kv_quant is not None:
        raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
    return {"kp": pack(cache["k"]), "vp": pack(cache["v"]), "ptab": ptab}


def export_pages(
    cache: Sequence[jnp.ndarray], page_ids: Sequence[int],
) -> Tuple[np.ndarray, ...]:
    """Extract live pool pages into a PORTABLE host-side handoff blob:
    one `[L, n, K, page_size(, H)]` numpy array per cache array, in the
    pool tuple's own order — `(kp, vp)` for a compute-dtype pool,
    `(kp, kps, vp, vps)` for the int8 pool, so the quantization scales
    always serialize beside their values and a restore reproduces the
    page content `(q8, s)` exactly. This is the LSOT_KV_SPILL host-copy
    format promoted to a first-class op: the same blob serves victim
    spill-resume on one replica AND prefill→decode page migration across
    replicas (disaggregated serving — ISSUE 13). The arrays are COPIES
    (one `device_get`): a page the source shared copy-on-write with its
    prefix cache exports as content, never as a reference, so the blob
    stays valid after the source releases, evicts or overwrites every
    page it covered."""
    idx = np.asarray(list(page_ids), np.int32)
    return jax.device_get(tuple(c[:, idx] for c in cache))


def import_pages(
    cache: Sequence[jnp.ndarray], page_ids, stacks: Sequence,
) -> Tuple[jnp.ndarray, ...]:
    """Install an `export_pages` blob into (freshly allocated, exclusive)
    pool pages: one scatter per cache array, pure jnp — callers jit it
    (the scheduler's `restore_pages` op wraps exactly this with buffer
    donation). The receiving side owns the allocation policy: the
    scheduler grants the blob's pages all-or-nothing through the same
    `_page_wait`/overcommit admission every fresh request rides, so
    migration changes no pressure semantics."""
    idx = jnp.asarray(page_ids, jnp.int32)
    for c, s in zip(cache, stacks):
        check_blob_shape(c.shape, s.shape)
    return tuple(
        c.at[:, idx].set(jnp.asarray(s)) for c, s in zip(cache, stacks)
    )


def check_blob_shape(pool_shape, blob_shape) -> None:
    """Refuse an `export_pages` array `[L, n, K/f, page(, f*H)]` that was
    not cut from a pool stored like this one: a page's bytes mean what
    the stored shape says (heads a row, page size), and another shape is
    never reinterpreted. The scheduler asks when a blob arrives
    (`requeue`), `import_pages` when it lands."""
    pool_shape, blob_shape = tuple(pool_shape), tuple(blob_shape)
    if pool_shape[:1] + pool_shape[2:] != blob_shape[:1] + blob_shape[2:]:
        raise ValueError(
            f"KV page blob of stored shape {blob_shape} "
            f"([L, n, K/f, page(, f*H)]) cannot be imported into a pool "
            f"of stored shape {pool_shape}"
        )


def handoff_bytes(stacks: Sequence[np.ndarray]) -> int:
    """Host bytes of one export_pages blob (the handoff observability
    figure: what actually crossed — or would cross — the wire)."""
    return int(sum(int(np.asarray(s).nbytes) for s in stacks))


def blob_meta(stacks: Sequence[np.ndarray]) -> dict:
    """Self-description of one `export_pages` blob — the pushed-handoff
    observability/validation record (ISSUE 17): page count + page_size
    read from the blob's own geometry, whether it carries int8 scales
    (4 arrays) or compute-dtype pages (2), and the wire bytes. The
    receiving side of a push compares `page_size` against its own pool
    BEFORE importing — a geometry mismatch is a typed rejection, not a
    scatter into the wrong page stride."""
    arrs = [np.asarray(s) for s in stacks]
    if not arrs:
        return {"arrays": 0, "pages": 0, "page_size": 0, "nbytes": 0,
                "quantized": False}
    # export_pages layout: [L, n_pages, K, page_size(, H)] per array;
    # the kps/vps scale arrays of an int8 blob share the page axes.
    lead = arrs[0]
    return {
        "arrays": len(arrs),
        "pages": int(lead.shape[1]) if lead.ndim >= 2 else 0,
        "page_size": int(lead.shape[3]) if lead.ndim >= 4 else 0,
        "nbytes": int(sum(a.nbytes for a in arrs)),
        "quantized": len(arrs) == 4,
    }


class PageAllocator:
    """Host-side page accounting: free list + per-page refcounts.

    All methods are O(pages touched); nothing here talks to the device.
    Thread-unsafe by design — the scheduler's worker thread is the only
    caller (same single-writer discipline as every other slot structure).

    Invariants (property-tested in tests/test_paged_kv.py):
    - every page is either on the free list (refcount 0) or live
      (refcount >= 1) — never both, never neither;
    - `release` on a refcount-0 page raises (double free is a bug);
    - a shared page (refcount > 1) is never handed out by `alloc`.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be positive, got {num_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free: "deque[int]" = deque(range(self.num_pages))
        self._ref = [0] * self.num_pages
        #: zero-copy shares taken (prefix publish + hit mappings): the
        #: counter that proves hits SHARED pages instead of copying them.
        self.shares = 0
        #: copy-on-write page copies (non-page-aligned boundaries only).
        self.cow_copies = 0
        #: Pages WITHHELD from allocation (still on the free list, still
        #: refcount 0 — the partition invariant is untouched): the
        #: `kv:pressure` chaos seam shrinks the effective pool through
        #: this, so allocation failure under pressure is injectable
        #: without faking device state. 0 outside pressure episodes.
        self.withheld = 0
        #: Pressure-relief lifecycle counters (ISSUE 10): victims
        #: preempted mid-decode, prefix-cache entries evicted by the
        #: watermark sweep, and pages spilled to / restored from host
        #: copies under LSOT_KV_SPILL.
        self.preemptions = 0
        self.evictions = 0
        self.spilled_pages = 0
        self.restored_pages = 0
        #: Per-page resident-PREFIX reference counts (ISSUE 14): how many
        #: live prefix-cache entries currently map each page. Chained
        #: entries overlap on their leading pages, so the scheduler's
        #: "bytes held by the prefix cache" figure needs the UNIQUE page
        #: set, not a per-entry sum — `prefix_resident_pages` counts pages
        #: with at least one entry reference, in O(1) via the nonzero
        #: tally. Distinct from `_ref` on purpose: a page can be prefix-
        #: resident and slot-mapped at once, and eviction accounting must
        #: not disturb the free-list/refcount partition invariant.
        self._prefix_ref = [0] * self.num_pages
        self._prefix_resident = 0

    # ------------------------------------------------------------- queries

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_available(self) -> int:
        """Free pages actually grantable right now: the free list minus
        the pressure-withheld reserve. What `alloc`/`can_alloc` consult."""
        return max(0, len(self._free) - self.withheld)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def pages_shared(self) -> int:
        """Pages currently mapped by more than one owner."""
        return sum(1 for r in self._ref if r > 1)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def is_shared(self, page: int) -> bool:
        return self._ref[page] > 1

    def can_alloc(self, n: int) -> bool:
        return self.pages_available >= n

    # ----------------------------------------------------------- mutations

    def withhold(self, n: int) -> None:
        """Reserve `n` free-list pages against allocation (the
        `kv:pressure` fault seam: the pool LOOKS n pages smaller until
        the pressure episode ends). Withheld pages never leave the free
        list, so the free-list/refcount partition — and `check()` — hold
        throughout; only `pages_available` shrinks. `withhold(0)` lifts
        the pressure."""
        if n < 0:
            raise ValueError(f"withhold({n})")
        self.withheld = min(int(n), self.num_pages)

    def note_preempt(self) -> None:
        """Count a mid-decode victim preemption (the scheduler released
        the victim's pages through `release` — this is the event tally
        /metrics and the bench pressure pass read)."""
        self.preemptions += 1

    def note_evictions(self, n: int) -> None:
        """Count prefix-cache entries evicted by the WATERMARK sweep
        (proactive pressure relief, distinct from `_alloc_pages`'s
        on-demand eviction which the scheduler does not tally — the
        watermark's whole point is firing before demand does)."""
        self.evictions += int(n)

    def note_spill(self, n: int) -> None:
        """Count pages copied to host at preemption (LSOT_KV_SPILL=1)."""
        self.spilled_pages += int(n)

    def note_restore(self, n: int) -> None:
        """Count spilled pages copied back at resume. A completed
        spill-resume cycle leaves spilled == restored for that request —
        the reconciliation the property tests pin."""
        self.restored_pages += int(n)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh exclusive pages, or None (all-or-nothing: a request that
        cannot fully fit must not hold a partial grab and deadlock against
        another partial holder). Withheld pages (kv:pressure) are not
        grantable."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if self.pages_available < n:
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            if self._ref[p] != 0:
                raise PageAccountingError(
                    f"free-list page {p} has refcount {self._ref[p]}"
                )
            self._ref[p] = 1
        return pages

    def share(self, pages: List[int], count: bool = True) -> None:
        """Take one additional reference on each page (zero-copy mapping:
        prefix-cache publish and hit both land here). `count=False` for
        TRANSIENT holds (e.g. pinning a matched entry across an allocation
        that may fail, or a boundary page held only until its COW copy):
        `shares` must count mappings that persist — it is the artifact's
        "sharing, not copying" proof and must not inflate under retries."""
        for p in pages:
            if self._ref[p] <= 0:
                raise PageAccountingError(
                    f"share of dead page {p} (refcount {self._ref[p]})"
                )
        for p in pages:
            self._ref[p] += 1
        if count:
            self.shares += len(pages)

    def note_shares(self, n: int) -> None:
        """Promote n transient holds (share(count=False)) to counted
        zero-copy mappings once they are known to persist."""
        self.shares += n

    def prefix_hold(self, pages: List[int]) -> None:
        """Mark pages as mapped by one more resident prefix-cache entry
        (publish). Idempotent per entry, not per page — chained entries
        legitimately hold the same leading pages more than once."""
        for p in pages:
            if self._prefix_ref[p] == 0:
                self._prefix_resident += 1
            self._prefix_ref[p] += 1

    def prefix_drop(self, pages: List[int]) -> None:
        """Drop one prefix-entry reference per page (entry eviction).
        A negative count is an accounting bug, not a recoverable state."""
        for p in pages:
            if self._prefix_ref[p] <= 0:
                raise PageAccountingError(
                    f"prefix_drop of page {p} with no prefix reference"
                )
            self._prefix_ref[p] -= 1
            if self._prefix_ref[p] == 0:
                self._prefix_resident -= 1

    @property
    def prefix_resident_pages(self) -> int:
        """UNIQUE pages currently held by at least one prefix-cache
        entry — the registry's resident-bytes numerator (× page_bytes)."""
        return self._prefix_resident

    def release(self, pages: List[int]) -> List[int]:
        """Drop one reference per page; pages reaching refcount 0 return to
        the free list. Returns the freed subset."""
        for p in pages:
            if self._ref[p] <= 0:
                raise PageAccountingError(
                    f"release of dead page {p} (refcount {self._ref[p]})"
                )
        freed = []
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed

    def cow(self, page: int) -> Optional[int]:
        """Copy-on-write: exchange one reference on a SHARED page for a
        fresh exclusive page (the caller must device-copy the old page's
        content into the returned one before writing). Returns `page`
        unchanged when it is already exclusive (no copy needed), None when
        the pool has no free page for the copy."""
        if self._ref[page] <= 0:
            raise PageAccountingError(
                f"cow of dead page {page} (refcount {self._ref[page]})"
            )
        if self._ref[page] == 1:
            return page
        fresh = self.alloc(1)
        if fresh is None:
            return None
        self.release([page])
        self.cow_copies += 1
        return fresh[0]

    def note_cow(self) -> None:
        """Count a boundary-page copy performed OUTSIDE the refcount
        exchange (admission copies a hit's partial boundary page into an
        already-allocated fresh page — same event, different bookkeeping
        path)."""
        self.cow_copies += 1

    def stats(self) -> Dict[str, int]:
        """The /metrics + flight-recorder payload: a leaked page shows up
        as pages_in_use that never returns to pages_free."""
        return {
            "page_size": self.page_size,
            "pages_total": self.num_pages,
            "pages_free": self.pages_free,
            "pages_in_use": self.pages_in_use,
            "pages_shared": self.pages_shared,
            "pages_withheld": self.withheld,
            "prefix_resident_pages": self.prefix_resident_pages,
            "zero_copy_shares": self.shares,
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "evictions": self.evictions,
            "spilled_pages": self.spilled_pages,
            "restored_pages": self.restored_pages,
        }

    def check(self) -> None:
        """Assert the free-list/refcount partition (test helper)."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise PageAccountingError("duplicate page on the free list")
        for p in range(self.num_pages):
            if (p in free) != (self._ref[p] == 0):
                raise PageAccountingError(
                    f"page {p}: refcount {self._ref[p]} vs free-list "
                    f"membership {p in free}"
                )
