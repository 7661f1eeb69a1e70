"""Continuous-batching scheduler: concurrent serving on one persistent cache.

This is the TPU-native replacement for Ollama's request queue + llama.cpp's
slot scheduler (the reference serializes everything: one blocking
`ollama.generate` per HTTP handler, reference `Flask/app.py:102-107`,
`FastAPI/app.py:85-90`). Concurrent FastAPI requests here share ONE decode
batch on the device (BASELINE.json config 5: mixed NL→SQL + error-analysis
serving), instead of queueing behind a per-backend lock.

Design (slot-based continuous batching, TPU/XLA-shaped):

- A fixed pool of `num_slots` sequence slots shares ONE persistent KV
  page pool [L, pages, K, page, H] (engine/paged_kv.py) that lives across
  jit calls, addressed through per-slot page tables [slots, pages_per_slot].
  Every jitted program donates the pool buffers, so XLA updates HBM in
  place — no per-request allocation, no growth, static shapes forever.
  Admission maps ceil(need/page) pages for the request's ACTUAL envelope
  (bucketed prompt + budget + overshoot), so concurrency is bounded by
  live tokens, not by slots x S_max worst-case rows.
- **Prefill** is one jitted fn per (prompt-length bucket, group size): gather
  each row's pages into a per-row view, run the prompt chunk through
  the stack against it, scatter ONLY the chunk's window back through the
  page table and sample the first token.
- **Decode** is one jitted fn total: a `lax.scan` of `decode_chunk` single
  token steps over the whole slot batch, reading the pool in place through
  the page tables (ops/pallas/paged_attention.py on TPU). Chunking
  amortizes the host↔device sync to 1/chunk per token; the host inspects
  tokens between chunks to retire finished sequences and admit pending
  ones into freed slots.
- Mixed sampling rides per-slot runtime arrays (ops/sampling.sample_runtime):
  greedy SQL generation and temperature/top-p/top-k error analysis share one
  compiled decode program.
- Per-request RNG streams: slot s samples token i with
  `fold_in(key(request_seed), i)` — each request owns an independent seeded
  stream, so resubmitting (prompt, seed, sampling) reproduces the same
  completion no matter what other traffic shares the batch (asserted in
  tests/test_scheduler.py).
- Free slots keep decoding garbage at a frozen position. That is safe by the
  cache-visibility invariant (engine/kvcache.py): a free slot's table row is
  unmapped (its writes drop), admission prefill overwrites positions [0, T),
  and beyond T the new sequence's own decode writes position p before p ever
  becomes visible to attention.
- **Prefix caching** (block-chained, vLLM-style at block granularity): the
  NL→SQL workload repeats one system prefix — the table schema — across
  every request for a table (reference `Flask/app.py:102-106` rebuilds the
  same system prompt per query). Completed prefix blocks of `_pblock`
  tokens are kept in an LRU keyed by the *token content* of the whole
  prefix up to that block (hash-chain semantics: a block is reusable only
  when everything before it matched too). An entry is a REFERENCE to the
  publisher's pool pages (refcounts), and admission maps matching pages
  into the slot's table zero-copy and skips their prefill entirely; the
  only copy is one page, copy-on-write, where a matched prefix ends
  mid-page. Content keys mean no invalidation is ever needed, and
  positions line up because a shared prefix occupies the same absolute
  positions [0, n) in every request. `prefix_cache_blocks` caps the LRU
  (0 disables); entries are evicted first when the pool runs short.
- Tensor parallelism: pass a mesh with dp=1 — request parallelism comes from
  slots (the batch axis stays unsharded because slots are dynamically
  indexed), TP shards heads/MLP exactly as in engine/generate.py.
- Data parallelism (dp>1) is request-level BY DESIGN: the slot axis cannot
  shard (dynamic per-slot cache indexing), so dp means independent scheduler
  replicas — each with its own params copy and tp-submesh — behind one
  `SchedulerPool`, a supervised FLEET with least-loaded deadline-aware
  placement and per-replica lifecycle (targeted restart/drain — see the
  SchedulerPool docstring). That matches the workload: serving throughput
  scales with independent replicas; there is no gradient all-reduce to
  motivate a fused dp program (inference-only framework).
- **int8 KV cache** (`kv_quant="int8"`): the pool stores int8 pages +
  per-position f32 scales (ops/quant.quantize_kv) — about half the HBM
  footprint and decode streaming, and the same HBM budget buys about
  twice the pages. Decode reads the int8 pages in place (the quantized
  ragged-paged kernel on TPU, a gather + int8-streaming einsum
  elsewhere). Chunked prefill dequantizes the gathered rows for the
  chunk forward and requantizes only its own window on scatter-back.
- **Streaming + cancellation**: `submit(on_token=...)` delivers accepted
  tokens in order from the worker thread (SchedulerBackend.complete_stream
  turns them into clean text deltas, byte-identical to the blocking path);
  `cancel(future)` retires an abandoned request at its next harvest so
  disconnected clients do not pin slots.
- **Speculative decoding** (`speculative_draft=D`): decode rounds become
  draft+verify rounds — each slot drafts D tokens by prompt lookup over an
  on-device token history (prompt tokens scattered in by the prefill fn,
  emits appended by the round itself) and one T=D+1 forward verifies the
  whole batch. Greedy slots emit their accepted chain (1..D+1 tokens per
  round, exactly vanilla-greedy output); temperature>0 slots emit their
  rejection-sampling chain (1..D+1 tokens per round: draft i accepted
  with min(1, p/q) under the target distribution — a delta q for these
  deterministic drafts — and the first rejection resampled from the
  normalized residual, engine/speculative.rejection_sample_chain), so
  sampled output is DISTRIBUTION-identical to vanilla sample_runtime
  decode and every request class gets the draft/verify speedup on ONE
  compiled program. The verify window runs the unrolled small-T einsum
  path, which also composes with the int8 KV cache. Prefix-cache reuse
  is disabled in this mode (reused tokens never reach the draft
  history). Grammar-constrained requests compose: the draft chain
  advances the slot's FSM per position (constrain.fsm_advance_chain),
  every verify logit row is masked with its own position's state BEFORE
  the accept test (so the sampled residual is grammar-renormalized and
  grammar-rejected drafts carry zero target mass), acceptance caps at
  the grammar-valid prefix, and the committed state rewinds past
  nothing — constrained+speculative greedy output is token-identical to
  constrained vanilla decode, and speculation_stats splits acceptance by
  constrained/unconstrained AND greedy/sampled class.

- **Async issue/harvest pipeline**: decode rounds, prompt chunks and
  admission scatters dispatch without waiting; per-slot state (cur/pos/
  sampling knobs/RNG counts) lives on device and chains between rounds.
  The host syncs exactly once per round — to harvest the OLDEST in-flight
  round's tokens, `_harvest_lag` rounds behind the issue frontier — so the
  transfer round-trip overlaps the next rounds' compute. This is what makes
  the loop fast over a high-latency device transport (the measured
  bottleneck was sync latency, not device FLOPs) and costs one chunk of
  retirement/admission latency.

Bounds: a request needs `bucket_len(prompt) + max_new + overshoot <= S_max`
(see the `overshoot` property: (harvest_lag+1) rounds of decode_chunk — or
of D+1 plus a verify window's write lookahead under speculation) — the
device can run past a budget or a stop token for up to that many positions
before the host notices (those tokens are discarded; their cache writes are
garbage covered by the invariant above).
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import logging
import os
import queue
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..constrain.masks import CompiledMask, trivial_tables
from ..engine.kvcache import bucket_len
from ..engine.paged_kv import (
    PageAllocator,
    check_blob_shape,
    default_page_size,
    export_pages,
    handoff_bytes,
    import_pages,
    init_page_pool,
    page_bytes,
    pages_for_budget,
    pages_for_tokens,
)
from ..models.configs import LlamaConfig
from ..models.llama import Params, forward, split_blocks
from ..ops.pallas import attention_impl, decode_attention_impl
from ..ops.sampling import (
    SamplingParams,
    apply_token_mask,
    filtered_runtime_logits,
    sample_runtime,
)
from ..parallel.sharding import shard_params, validate_tp
from ..utils import traceprof
from ..utils.faults import FAULTS, InjectedFault
from ..utils.observability import StageTimer, resilience
from ..utils.perfmodel import PerfModel
from .flightrecorder import FlightRecorder, merge_snapshots
from .resilience import (
    Deadline,
    DeadlineExceeded,
    Overloaded,
    SchedulerCrashed,
    SlotStalled,
)
from .watchdog import CombinedHeartbeat, Heartbeat

_log = logging.getLogger("lsot.scheduler")

#: Scheduler phase roles (ISSUE 13 — disaggregated prefill/decode
#: serving). "mixed" (the default) is today's behavior bit for bit; a
#: "prefill" replica runs chunked prefill to completion, packs the
#: request's KV pages into a portable handoff blob and retires it into a
#: handoff queue instead of entering its decode loop; a "decode" replica
#: is a routing preference — full mixed capability, but the pool's
#: phase-aware router sends it migrated requests and keeps fresh prompts
#: off it.
PHASE_ROLES = ("mixed", "prefill", "decode")


def require_paged_layout(kv_layout: str) -> str:
    """The serving path has one KV layout, the page pool. `kv_layout` /
    `--kv-layout` are still taken with that one value; anything else is
    refused by name."""
    if kv_layout != "paged":
        raise ValueError(
            f"kv_layout={kv_layout!r}: the contiguous KV layout was "
            f"removed from the serving path; the page pool ('paged') is "
            f"the only layout"
        )
    return kv_layout


def kv_layout_flag(value: str) -> str:
    """`type=` of the app's and the remote worker's `--kv-layout`: the
    same refusal as an argparse error."""
    try:
        return require_paged_layout(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


#: A /debug/profile capture older than this is stopped when the loop next
#: finds nothing to serve: its trace runs from the request on, rounds or
#: no rounds (`_expire_profile`).
_PROFILE_IDLE_LIMIT_S = 60.0


def parse_pool_phases(spec: str, replicas: int) -> List[str]:
    """Parse LSOT_POOL_PHASES ("prefill:1,decode:3") into a per-replica
    role list of length `replicas`. Empty/None spec means an all-"mixed"
    fleet (the pre-disaggregation behavior). Counts must sum to the
    replica count, and a fleet with any "prefill" replica must also have
    somewhere for its handoffs to decode ("decode" or "mixed") — a
    prefill-only fleet would silently fall back to decoding in place on
    every request, which is a misconfiguration, not a deployment."""
    if not spec:
        return ["mixed"] * replicas
    roles: List[str] = []
    for entry in filter(None, (s.strip() for s in spec.split(","))):
        parts = entry.split(":")
        if len(parts) != 2:
            raise ValueError(
                f"bad pool-phases entry {entry!r} (want role:count)"
            )
        role, n = parts[0].strip(), parts[1].strip()
        if role not in PHASE_ROLES:
            raise ValueError(
                f"bad phase role {role!r} (want one of {PHASE_ROLES})"
            )
        try:
            count = int(n)
        except ValueError:
            raise ValueError(f"bad replica count in {entry!r}") from None
        if count < 1:
            raise ValueError(f"replica count must be >= 1 in {entry!r}")
        roles.extend([role] * count)
    if len(roles) != replicas:
        raise ValueError(
            f"pool phases {spec!r} describe {len(roles)} replica(s) but "
            f"the pool has {replicas}"
        )
    if "prefill" in roles and not any(
            r in ("decode", "mixed") for r in roles):
        raise ValueError(
            f"pool phases {spec!r} have prefill replicas but no decode/"
            f"mixed replica to hand off to"
        )
    return roles


def normalize_replica_weights(values: Sequence[float], replicas: int,
                              label: str = "replica weights"
                              ) -> List[float]:
    """ONE pad/validate policy for replica capacity weights, shared by
    the LSOT_REPLICA_WEIGHTS spec parser and SchedulerPool's explicit
    `weights=` argument: positive floats, at most one per replica
    (more is a misconfigured fleet and raises — never a silent
    truncation), padded with 1.0."""
    out = [float(w) for w in values]
    for w in out:
        if w <= 0:
            raise ValueError(
                f"replica weights must be positive, got {w} in {label}")
    if len(out) > replicas:
        raise ValueError(
            f"{label} name {len(out)} replica(s) but the pool has "
            f"{replicas}"
        )
    return out + [1.0] * (replicas - len(out))


def parse_replica_weights(spec: str, replicas: int) -> List[float]:
    """Parse LSOT_REPLICA_WEIGHTS ("4,1,1" — one positive capacity
    multiplier per replica index) into a weight list of length
    `replicas`, padded with 1.0. A tp=4 replica weighted 4 takes
    proportionally more token mass than a tp=1 sibling: placement
    ORDERING compares backlog DIVIDED by weight (deadline feasibility
    stays wall-clock). Empty spec = all 1.0, which is bit-identical to
    the unweighted order."""
    if not spec:
        return [1.0] * replicas
    out: List[float] = []
    for entry in filter(None, (s.strip() for s in spec.split(","))):
        try:
            out.append(float(entry))
        except ValueError:
            raise ValueError(
                f"bad replica weight {entry!r} in {spec!r}") from None
    return normalize_replica_weights(out, replicas,
                                     label=f"replica weights {spec!r}")


#: Prefix-cache telemetry bounds (ISSUE 14): how many registry entries
#: /debug/prefixcache returns per replica (top-K by token mass) and how
#: many recent admissions the reuse-distance ring remembers. App-startup
#: overrides via `reconfigure_prefix_telemetry` (AppConfig.prefix_topk /
#: prefix_ring — the same wiring seam as flightrecorder.reconfigure);
#: None falls through to the LSOT_PREFIX_TOPK / LSOT_PREFIX_RING env
#: reads below.
_PREFIX_TOPK: Optional[int] = None
_PREFIX_RING: Optional[int] = None


def reconfigure_prefix_telemetry(top_k: Optional[int] = None,
                                 ring: Optional[int] = None) -> None:
    """Set the prefix-registry bounds schedulers constructed AFTER this
    call will use (app/__main__ wires AppConfig.prefix_topk/prefix_ring
    through here, so the knobs are documented config, not hidden env)."""
    global _PREFIX_TOPK, _PREFIX_RING
    _PREFIX_TOPK = int(top_k) if top_k else None
    _PREFIX_RING = int(ring) if ring else None


def _prefix_bound(configured: Optional[int], env: str, default: int) -> int:
    if configured is not None:
        return max(1, configured)
    try:
        n = int(os.environ.get(env, str(default)))
    except ValueError:
        n = default
    return max(1, n)


def prefix_digest(ids: Sequence[int]) -> str:
    """Stable content address of a token prefix: blake2b over the int32
    token ids, 16 hex chars. Deterministic across processes and replicas
    — the SAME schema prefix hashes to the SAME digest fleet-wide, which
    is what lets `SchedulerPool.prefix_affinity` compare a request's
    prefix against every replica's resident set without shipping token
    lists around (ISSUE 14)."""
    return hashlib.blake2b(
        np.asarray(ids, np.int32).tobytes(), digest_size=8
    ).hexdigest()


def prefix_chain_digests(ids: Sequence[int], block: int,
                         ns: Sequence[int] = ()) -> List[str]:
    """Digests of every whole-block prefix of a prompt (the hash-chain
    keys' content addresses): what a cache-aware router hands to
    `SchedulerPool.prefix_affinity` — a replica holding ANY chain prefix
    of the request saves that much re-prefill, so affinity matches on
    the whole chain, not just the longest prefix. `ns` is the tenant
    namespace salt (ISSUE 18): when per-tenant prefix namespacing is on,
    the router salts here exactly as admission salts its cache keys, so
    fleet-wide affinity still matches — within one tenant only."""
    base = tuple(ns)
    return [
        prefix_digest(base + tuple(ids[: (j + 1) * block]))
        for j in range(max(0, (len(ids) - 1) // block))
    ]


def _rd_buckets(ring_cap: int) -> Tuple[int, ...]:
    """Reuse-distance histogram buckets (admissions between consecutive
    sightings of the same prefix digest): le-style powers of two up to
    the ring cap, so a ring configured wider than the default still
    buckets its whole window instead of dumping the tail into "inf". A
    distance histogram bounded by the ring answers "would a cache of N
    entries have held this working set" — the capacity-planning
    readout."""
    b, buckets = 1, []
    while b < ring_cap:
        buckets.append(b)
        b *= 2
    buckets.append(ring_cap)
    return tuple(buckets)


def _first_token_timer(then: Optional[Callable[[int], None]] = None):
    """(on_token, first_at) pair for TTFT measurement: on_token records the
    worker-thread harvest time of the request's first ACCEPTED token — its
    true time-to-first-token origin (queueing + prefill + first harvest
    lag) — into the returned list, then forwards the token to `then`."""
    first_at: List[float] = []

    def on_tok(tok: int) -> None:
        if not first_at:
            first_at.append(time.perf_counter())
        if then is not None:
            then(tok)

    return on_tok, first_at


def _quant_window_tuple(d: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, ...]:
    """ops/quant.quantize_cache's dict -> the int8 pool's array order."""
    return (d["k8"], d["ks"], d["v8"], d["vs"])


def _paged_cache_dict(
    arrs: Sequence[jnp.ndarray], ptab: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """Pool tuple -> the dict form models/llama.forward takes: (kp, vp)
    for a compute-dtype pool, (kp, kps, vp, vps) for the int8 pool
    (values + per-position scales)."""
    if len(arrs) == 2:
        return {"kp": arrs[0], "vp": arrs[1], "ptab": ptab}
    return {"kp": arrs[0], "kps": arrs[1], "vp": arrs[2], "vps": arrs[3],
            "ptab": ptab}


def _paged_cache_tuple(d: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, ...]:
    if "kps" in d:
        return (d["kp"], d["kps"], d["vp"], d["vps"])
    return (d["kp"], d["vp"])


@dataclasses.dataclass
class _Request:
    ids: List[int]
    max_new: int
    temperature: float
    top_p: float
    top_k: int
    seed: int
    future: Future
    # Streaming: called from the worker thread with each ACCEPTED token id,
    # in order, before the future resolves (overshoot/stop tokens never
    # reach it). Must be fast and non-blocking; exceptions are swallowed so
    # a broken consumer cannot kill the serving loop.
    on_token: Optional[Callable[[int], None]] = None
    # Cooperative cancellation (set via Scheduler.cancel): the worker
    # retires the request at its next harvest instead of decoding the rest
    # of the budget into an abandoned consumer (client disconnects must not
    # pin slots).
    cancelled: bool = False
    # Per-request deadline (serve/resilience.Deadline), threaded submit →
    # queue → decode: expired queued requests fail fast at admission
    # (never occupying a slot); expired in-flight requests are retired at
    # the next harvest through the same path cancellation uses — either
    # way the future fails with a typed DeadlineExceeded.
    deadline: Optional[Deadline] = None
    # Grammar-constrained decoding (constrain.CompiledMask): the slot's
    # on-device DFA state starts at constraint.init_state and every decode
    # step applies the state's precomputed vocabulary mask. None = free.
    constraint: Optional[CompiledMask] = None
    # live state (set at admission)
    generated: List[int] = dataclasses.field(default_factory=list)
    # chunked-prefill progress: prompt tokens already written to the cache.
    # A slot is decode-eligible only once the whole prompt is in (`ready`).
    prefilled: int = 0
    ready: bool = False
    # Submit wall-clock origin: feeds the per-request service-time EWMA
    # behind retry_after_hint() (queue-depth-aware Retry-After).
    submitted_at: float = 0.0
    # Per-slot stall retirement: consecutive harvest rounds in which this
    # request's slot appended nothing while OTHER slots advanced. At
    # `slot_stall_rounds` the slot is retired typed (SlotStalled/504)
    # instead of occupying a decode lane forever. `stall_inject` is the
    # chaos seam (`sched:slot_stall` site, set at admission): the harvest
    # treats the slot's round output as empty, simulating a lane the
    # device produces nothing useful for.
    stall_rounds: int = 0
    stall_inject: bool = False
    # Observability (ISSUE 6): a scheduler-scope monotonic request id
    # (flight-recorder attribution: which rids a round admitted/retired),
    # the request's RequestTrace when it was head-sampled
    # (utils/tracing.py — the worker thread records queue-wait / prefill /
    # per-round decode spans into it), and the wall stamps those spans
    # are cut from.
    rid: int = 0
    trace: Optional[object] = None
    admitted_at: float = 0.0
    ready_at: float = 0.0
    # The request's waits up to its first token, cut at the first `emit`
    # (the request log's `prefill_s` and `first_hold_s`): admission →
    # prompt ready, and ready → the first token handed to the consumer (a
    # first token rides the harvest of a decode round). With the queue
    # wait they add up to the worker-side TTFT.
    prefill_s: float = 0.0
    first_hold_s: float = 0.0
    # Multi-model serving (ISSUE 16): the checkpoint this request's KV
    # was (or will be) written by — stamped at submit from the owning
    # scheduler, carried on requeue/extract wire frames so a migrated
    # request can only land on a same-model replica ("" = the
    # single-model fleet).
    model_id: str = ""
    # Page envelope: highest cache position (exclusive) this
    # request's prefill+decode can ever write — admission allocated pages
    # covering exactly [0, page_end), and the ready-time ensure-writable
    # sweep COWs any published page the decode range intersects.
    page_end: int = 0
    # Already counted in page_waits: the admission loop retries a starved
    # request every iteration, and the metric must count REQUESTS that
    # waited, not retry attempts.
    page_waited: bool = False
    # Victim preemption (ISSUE 10): `resume_pref` is how many COMMITTED
    # generated tokens are folded into the prefill prefix for the next
    # admission — resume re-runs prefill over prompt + generated-so-far
    # (recompute mode), and the continuation appends to the same
    # `generated` list, so clients never see a token twice. `rng_count`
    # mirrors the slot's on-device RNG stream index at the last harvest
    # (tokens sampled so far for vanilla decode, 1 + sampled rounds for
    # speculative) — restoring it at resume is what makes a preempted
    # SAMPLED request's continuation token-identical to an unpreempted
    # control (the fold_in(key(seed), count) contract). `spilled` holds
    # host-side page copies under LSOT_KV_SPILL=1 (restore mode skips the
    # re-prefill entirely).
    resume_pref: int = 0
    preempted: int = 0
    rng_count: int = 0
    # Parked intervals [t_preempt, t_resume-or-0.0] for the request trace
    # tree: a victim's Perfetto export shows WHERE its latency went while
    # it sat preempted off the device (flush_spans emits one
    # "sched.preempted" span per interval — ISSUE 12 satellite; PR 10
    # only emitted flight-recorder events, so a victim's timeline had an
    # unexplained hole exactly over the preemption).
    parked: List[List[float]] = dataclasses.field(default_factory=list)
    # Host page copies under LSOT_KV_SPILL=1: one array per cache array —
    # (k, v) for a compute-dtype pool, (k8, ks, v8, vs) for the int8 pool
    # (the quantization scales serialize beside the pages, so restore is
    # content-exact).
    spilled: Optional[Tuple[np.ndarray, ...]] = None
    # Prefill→decode handoff metadata (ISSUE 13): set when a prefill-role
    # replica packed this request's KV into `spilled` for migration —
    # {"t_pack", "export_s", "pages", "bytes", "src"} — and cleared by
    # the importing replica's resume, which turns it into the
    # `sched.handoff` trace span + the pages_migrated/handoff_wait_s
    # flight columns. None everywhere outside a live handoff, so the
    # spill-resume paths can tell a migrated blob from a preemption spill
    # (different counters, same restore machinery).
    handoff: Optional[Dict] = None
    # Prefix-cache reuse attribution (ISSUE 14), stamped at admission:
    # the request's schema-prefix content digest — the MATCHED chain
    # entry's digest on a hit (joinable against /debug/prefixcache and
    # the resident-digest routing feed), the longest block-aligned
    # prompt prefix on a miss (the best schema-identity guess when
    # nothing matched); same digest fleet-wide for the same token
    # prefix. Plus how many prompt tokens the hit let prefill SKIP and
    # the analytic prefill seconds that skip saved
    # (utils/perfmodel.prefill_saved). "" / 0 when the prompt is shorter
    # than one block or the cache is off.
    prefix_digest: str = ""
    tokens_reused: int = 0
    prefill_s_saved: float = 0.0
    # Multi-tenant QoS (ISSUE 18). `tenant`/`qos` ride the request from
    # the HTTP layer through pool/supervisor/remote-wire; "" = unlabeled
    # (the single-tenant shape, untouched by every QoS-off path). `vft`
    # is the WFQ virtual finish time stamped at submit; `ns` is the
    # tenant's prefix-cache namespace salt (two int32s prepended to
    # every cache key/digest — () for unlabeled traffic, so its keys
    # stay bit-for-bit identical to the shared registry).
    tenant: str = ""
    qos: str = ""
    vft: float = 0.0
    ns: Tuple[int, ...] = ()

    @property
    def full_ids(self) -> List[int]:
        """The prefill prefix: the prompt, plus — after a preemption —
        the committed generated tokens recompute must re-run (position
        of generated token j is len(ids) + j in BOTH incarnations, so
        every envelope/top-up formula can use absolute positions)."""
        if not self.resume_pref:
            return self.ids
        return self.ids + self.generated[: self.resume_pref]

    def flush_spans(self, now: float) -> None:
        """Record the request's scheduler-phase spans into its trace at
        terminal time (retire/fail): queue-wait (submit→slot), prefill
        (slot→decode-eligible), decode (eligible→terminal). One call per
        request, only when traced — zero work on the unsampled path."""
        tr = self.trace
        if tr is None:
            return
        try:
            if self.submitted_at and self.admitted_at:
                tr.add_span("sched.queue_wait", self.submitted_at,
                            self.admitted_at, rid=self.rid)
            elif self.submitted_at:
                # Never admitted (expired/cancelled while queued): its
                # whole life WAS queue wait.
                tr.add_span("sched.queue_wait", self.submitted_at, now,
                            rid=self.rid)
            if self.admitted_at:
                t_ready = self.ready_at or now
                # Reuse attribution rides the prefill span (ISSUE 14): a
                # traced request's timeline says how much of its prompt
                # the prefix cache already held and what that skip was
                # worth — beside the span whose wall it shortened.
                attrs = {"prompt_tokens": len(self.ids)}
                if self.prefix_digest:
                    attrs["prefix_digest"] = self.prefix_digest
                    attrs["tokens_reused"] = self.tokens_reused
                    attrs["tokens_prefilled"] = (
                        len(self.ids) - self.tokens_reused
                    )
                tr.add_span("sched.prefill", self.admitted_at, t_ready,
                            **attrs)
            if self.ready_at:
                tr.add_span("sched.decode", self.ready_at, now,
                            output_tokens=len(self.generated),
                            constrained=self.constraint is not None)
            # Preemption parking (ISSUE 12 satellite): one span per parked
            # interval, so a victim's exported timeline explains the gap —
            # an interval still open at terminal time (preempted, never
            # resumed: deadline burned while parked) closes at `now` with
            # resumed=False.
            for iv in self.parked:
                t0, t1 = iv[0], iv[1]
                tr.add_span("sched.preempted", t0, t1 or now,
                            rid=self.rid, resumed=bool(t1),
                            preemptions=self.preempted)
        except Exception:  # noqa: BLE001 — tracing must never kill the loop
            self.trace = None

    def emit(self, tok: int) -> None:
        if not self.first_hold_s and self.ready_at:
            self.prefill_s = self.ready_at - self.admitted_at
            self.first_hold_s = time.perf_counter() - self.ready_at
        if self.on_token is not None:
            try:
                self.on_token(tok)
            except Exception:  # noqa: BLE001 — consumer bugs must not kill serving
                self.on_token = None

    def past_deadline(self) -> bool:
        return self.deadline is not None and self.deadline.expired()

    def deadline_error(self) -> DeadlineExceeded:
        return DeadlineExceeded(
            f"request deadline exceeded with {len(self.generated)} of "
            f"{self.max_new} tokens generated"
        )


class ContinuousBatchingScheduler:
    """Admit → prefill → batched chunked decode → retire, on one device batch.

    `submit()` is thread-safe and returns a Future of generated token ids
    (stop token stripped). A daemon thread owns all device work.
    """

    #: Duck-typing flag (ISSUE 18): callers (SchedulerBackend, the
    #: supervisor, transports) only forward tenant/qos kwargs to
    #: schedulers that understand the axis — test fakes and older
    #: signatures keep working untouched.
    supports_qos = True

    def __init__(
        self,
        cfg: LlamaConfig,
        params: Params,
        num_slots: int = 8,
        max_seq: Optional[int] = None,
        decode_chunk: int = 8,
        prompt_bucket: int = 128,
        stop_ids: Optional[Sequence[int]] = None,
        mesh=None,
        prefix_cache_blocks: int = 64,
        kv_quant: Optional[str] = None,
        speculative_draft: int = 0,
        spec_ngram: int = 3,
        fuse_matmuls: bool = False,
        max_queue_depth: int = 0,
        slot_stall_rounds: int = 16,
        # The page pool is the one KV layout. The keyword is still taken,
        # with that one value, for the callers under benchmark/ that pass
        # it (ROADMAP M10 deletes it with them).
        kv_layout: str = "paged",
        kv_page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
        kv_hbm_budget_bytes: Optional[int] = None,
        kv_overcommit: Optional[float] = None,
        kv_spill: Optional[bool] = None,
        kv_watermark_low: Optional[float] = None,
        kv_watermark_high: Optional[float] = None,
        phase_role: str = "mixed",
        # Unified ragged prefill+decode (ISSUE 19): admit prefill chunks
        # and decode slots into ONE compiled mixed-round launch (per-slot
        # query-length vector; prefill rows scatter their chunk, decode
        # rows emit tokens), retiring the separate prefill pass from the
        # loop's hot path. None = read LSOT_RAGGED (default off — the
        # alternating scheduler, bit for bit). Mixed-role only.
        ragged: Optional[bool] = None,
        # Multi-model serving (ISSUE 16): which registered checkpoint
        # this replica holds. "" (the default) is the single-model
        # fleet, bit for bit — the pool only routes on model when a
        # request names one AND replicas carry ids
        # (serve/modelpool.py owns the registry; LSOT_POOL_MODELS
        # gates the routing axis like LSOT_POOL_AFFINITY gates
        # affinity).
        model_id: str = "",
    ):
        self.cfg = cfg
        self.mesh = mesh
        # Disaggregated prefill/decode serving (ISSUE 13): "mixed" (the
        # default) is today's behavior bit for bit. A "prefill" replica
        # never enters its decode loop for fresh requests: the final
        # prompt chunk's sampled first token is committed and streamed,
        # the request's KV pages export into a portable handoff blob
        # (engine/paged_kv.export_pages — the spill format), and the
        # request parks in `_handoff` for the pool's phase-aware router
        # to re-place onto a decode replica (`on_handoff` wakes it; no
        # consumer wired → the replica arms the slot and decodes in
        # place, so a lone prefill-role scheduler still serves). A
        # "decode" replica is routing policy only — full capability, but
        # the router feeds it migrated requests and keeps fresh prompts
        # off it.
        if phase_role not in PHASE_ROLES:
            raise ValueError(
                f"phase_role must be one of {PHASE_ROLES}, got "
                f"{phase_role!r}"
            )
        require_paged_layout(kv_layout)
        self.phase_role = phase_role
        self.model_id = str(model_id or "")
        # Accepted tokens over this scheduler's lifetime (ISSUE 16):
        # bumped once per harvested round; per-model throughput
        # attribution reads it (pool.model_stats / lsot_model_*).
        self._tokens_emitted_total = 0
        # The loop's stages on both clocks (ISSUE 26): every `sched.*`
        # span is summed here on the host's clock — a round's flight
        # record takes the sums since the last record — and is an event
        # of the device trace's host plane while a capture runs.
        self._stages = StageTimer()
        self._loop_passes = 0
        # Results of the requests a harvest finishes, held until the
        # round's record is written (None outside a harvest).
        self._round_results: Optional[list] = None
        # Prefill dispatched since the last round record: chunk batches,
        # real rows in them, prompt tokens they carried.
        self._round_prefill = [0, 0, 0]
        # Handoff state. `_handoff_pending` holds (slot, req, tok, epoch)
        # for final chunks whose first token is still on device;
        # `_handoff` is the packed-blob queue the pool drains. Counters
        # feed handoff_stats / the lsot_handoff_* Prometheus families.
        self._handoff: "deque[_Request]" = deque()
        self._handoff_pending: list = []
        self.on_handoff: Optional[Callable[[], None]] = None
        # Bounded in-worker handoff buffer (ISSUE 17): when the pump's
        # consumer falls behind and the packed queue reaches this depth,
        # further handoffs decode in place instead of piling up blobs
        # (each one pins exported pages' worth of host memory).
        self._pump_depth = int(os.environ.get("LSOT_PUMP_DEPTH", "32")
                               or 32)
        self._ho_backpressure = 0
        self._ho_exports = 0
        self._ho_imports = 0
        self._ho_inplace = 0
        self._ho_pages_out = 0
        self._ho_pages_in = 0
        self._ho_bytes_out = 0
        self._ho_bytes_in = 0
        self._ho_wait_sum = 0.0
        self._ho_wait_count = 0
        # Per-round migration accumulators (flushed into the flight
        # record's pages_migrated/handoff_wait_s columns at harvest).
        self._mig_pages = 0
        self._mig_wait = 0.0
        # Prefill-side backlog signal: outstanding PROMPT tokens and a
        # per-prompt-token service EWMA (submit→handoff wall), so a
        # prefill replica's backlog_score prices compute backlog instead
        # of decode budgets it will never spend.
        self._pending_prompt_tokens = 0
        self._pref_stok_ewma: Optional[float] = None
        self._last_pack_t: Optional[float] = None
        # Per-slot stall retirement: a slot that appends nothing for this
        # many consecutive harvest rounds WHILE other slots advance is
        # retired typed (SlotStalled/504) — a wedged lane must not pin a
        # batch slot until its deadline burns. 0 disables. Organically
        # impossible with the current decode programs (every active slot
        # emits per round), so this is defense-in-depth plus the
        # `sched:slot_stall` chaos seam's contract.
        self.slot_stall_rounds = int(slot_stall_rounds)
        self._slot_stalls = 0
        # Liveness stamp the event loop touches every iteration (and per
        # harvested round): the supervisor's watchdog monitor reads it to
        # tell a wedged loop (hung XLA dispatch — age grows while
        # busy) from a healthy or idle one. serve/watchdog.py.
        self.heartbeat = Heartbeat()
        # Flight recorder (serve/flightrecorder.py): one record per
        # HARVESTED round — occupancy, admitted/retired rids, emitted and
        # speculation-accepted tokens, round wall, cadence — in a bounded
        # ring. The postmortem black box a crash/stall/SIGTERM dump reads;
        # live at /debug/flightrecorder. `replica` is relabeled by
        # SchedulerPool so a pool's merged view attributes load.
        self.flight = FlightRecorder()
        # Scheduler-scope monotonic request ids for flight-recorder
        # attribution (independent of the supervisor's journal rids).
        self._rid_seq = 0
        # Rids admitted since the last harvested round's record.
        self._round_admitted: List[int] = []
        self._round_retired: List[int] = []
        # Admission control: submits beyond this many queued-not-yet-slotted
        # requests shed with a typed Overloaded (HTTP 429 upstream) instead
        # of growing the backlog without bound — under sustained overload an
        # unbounded queue turns every request into a timeout. 0 = unbounded
        # (the historical behavior, kept as default for library users).
        self.max_queue_depth = int(max_queue_depth)
        if fuse_matmuls:
            # Fewer, wider MXU matmuls for admission prefill (the phase
            # that stalls decode rounds under load).
            from ..models.llama import maybe_fuse

            params = maybe_fuse(params, mesh)
        if mesh is not None:
            if dict(mesh.shape).get("dp", 1) != 1:
                raise ValueError(
                    "scheduler mesh must have dp=1: request parallelism comes "
                    "from slots; the slot axis is dynamically indexed and "
                    "cannot shard"
                )
            validate_tp(cfg, mesh.shape["tp"])
            params = shard_params(params, cfg, mesh)
        self.params = params
        # Weight bits for the verify-cost model: immutable for this
        # scheduler's lifetime, so probe the tree ONCE instead of per
        # speculation_stats read (/metrics scrapes + bench deltas).
        from ..engine.speculative import infer_weight_bits

        self._weight_bits = infer_weight_bits(params)
        self.num_slots = num_slots
        self.max_seq = min(max_seq or cfg.max_seq_len, cfg.max_seq_len)
        self.decode_chunk = decode_chunk
        self.prompt_bucket = min(prompt_bucket, max(1, self.max_seq // 2))
        self.stop_ids = tuple(stop_ids) if stop_ids is not None else cfg.stop_ids
        self._impl = attention_impl(mesh)

        dtype = jax.tree.leaves(params)[0].dtype
        self._dtype = dtype
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
        self.kv_quant = kv_quant
        # The KV page pool (engine/paged_kv.py): a shared pool sized to an
        # HBM budget + per-slot page tables. Admission allocates
        # ceil(need/page) pages for the request's ACTUAL envelope
        # (bucketed prompt + budget + overshoot), so concurrency is
        # bounded by live tokens, mixed long/short batches stop paying
        # max-bucket padding, and prefix-cache hits map shared pages
        # zero-copy (refcounts; copy-on-write only at a non-page-aligned
        # boundary). Decode runs the ragged-paged-attention path
        # (models/llama.forward paged branch; ops/pallas/paged_attention.py
        # on TPU). Composes with kv_quant="int8" (the pool stores int8
        # pages + per-position scales — ~2x live tokens per HBM byte; page
        # accounting below prices the TRUE page bytes) and with a dp=1 tp
        # mesh (pool KV heads shard over tp; page tables replicate).
        ps = int(kv_page_size or default_page_size())
        if ps <= 0 or ps % 8:
            raise ValueError(
                f"kv_page_size must be a positive multiple of 8, got "
                f"{ps}"
            )
        self._page_size = ps
        # Logical pages per slot: enough table entries to address the
        # whole window (a slot never MAPS them all unless its request
        # actually needs max_seq).
        self._pages_per_slot = pages_for_tokens(self.max_seq, ps)
        if kv_pages:
            num_pages = int(kv_pages)
        elif kv_hbm_budget_bytes:
            # KV-dtype-aware sizing (ISSUE 11 satellite): an int8
            # pool's pages cost ~half a compute-dtype page, so the
            # same HBM budget buys ~2x the pages — capacity math must
            # price the KV dtype, not the compute dtype.
            num_pages = pages_for_budget(
                cfg, kv_hbm_budget_bytes, ps, dtype.itemsize, kv_quant
            )
        else:
            # Default budget: every slot can map a whole max_seq window
            # at once (slots x max_seq rows' worth of HBM).
            num_pages = num_slots * self._pages_per_slot
        if num_pages < self._pages_per_slot:
            raise ValueError(
                f"page pool of {num_pages} pages cannot hold one "
                f"max-length request ({self._pages_per_slot} pages of "
                f"{ps} tokens for max_seq={self.max_seq}); raise "
                f"kv_pages / kv_hbm_budget_bytes or lower max_seq"
            )
        self._page_alloc = PageAllocator(num_pages, ps)
        # Graceful degradation under page pressure (ISSUE 10).
        # Overcommit admission: reserve min(budget, max(ratio × budget,
        # EWMA of observed generation lengths)) generation tokens at
        # admission instead of the full max_new worst case — 1.0 (the
        # default) reproduces the exact-envelope admission bit for
        # bit; below 1.0, decode tops pages up at each harvest and a
        # failed top-up preempts a victim (fewest generated tokens
        # first, never the allocating slot) whose deterministic
        # resume re-prefills prompt+generated (or restores spilled
        # host page copies under kv_spill).
        if kv_overcommit is None:
            kv_overcommit = float(
                os.environ.get("LSOT_KV_OVERCOMMIT", "1.0"))
        if not 0.0 < kv_overcommit <= 1.0:
            raise ValueError(
                f"kv_overcommit must be in (0, 1], got {kv_overcommit}"
            )
        self._kv_overcommit = float(kv_overcommit)
        if kv_spill is None:
            kv_spill = os.environ.get("LSOT_KV_SPILL", "0").strip() \
                .lower() in ("1", "true", "yes", "on")
        self._kv_spill = bool(kv_spill)
        # Watermark-driven eviction: when pool free pages fall under
        # low × pages, the loop proactively evicts LRU prefix-cache
        # entries until free recovers to high × pages — steady-state
        # pressure is relieved BEFORE an allocation fails, so traffic
        # rarely needs a preemption at all. low = 0 disables (the
        # default: the on-demand eviction inside _alloc_pages remains,
        # exactly as before).
        if kv_watermark_low is None:
            kv_watermark_low = float(
                os.environ.get("LSOT_KV_WATERMARK_LOW", "0.0"))
        if kv_watermark_high is None:
            kv_watermark_high = float(
                os.environ.get("LSOT_KV_WATERMARK_HIGH", "0.0"))
        if not 0.0 <= kv_watermark_low <= 1.0 or \
                not 0.0 <= kv_watermark_high <= 1.0 or \
                kv_watermark_high < kv_watermark_low:
            raise ValueError(
                f"kv watermarks must satisfy 0 <= low <= high <= 1, "
                f"got low={kv_watermark_low} high={kv_watermark_high}"
            )
        self._wm_low_pages = int(kv_watermark_low * num_pages)
        self._wm_high_pages = max(
            self._wm_low_pages, int(kv_watermark_high * num_pages))
        # EWMA of COMPLETED requests' generation lengths: the
        # "expected generation" admission reserves under overcommit.
        self._gen_ewma: Optional[float] = None
        # Host-side per-slot page lists (the device table's mirror).
        self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        # Prefix cache: content key (token prefix) -> pool page
        # ids covering it. Entries hold REFERENCES (refcounts), not
        # copies — publish and hit are both zero-copy.
        self._prefix_pages: "OrderedDict[Tuple[int, ...], Tuple[int, ...]]" = (
            OrderedDict()
        )
        # Requests admitted to a slot but waiting for pool pages
        # (admission is all-or-nothing so partial holders can't
        # deadlock); FIFO ahead of the main queue.
        self._page_wait: "deque[_Request]" = deque()
        self._page_wait_events = 0
        # Decode impl is cost-aware: the Pallas kernels' per-row kv_lens
        # bounding (parked slots stream nothing) only beats the einsum
        # path's zero-overhead full read once the pool is large per
        # device — see ops.pallas.decode_attention_impl for the measured
        # crossover. page_bytes prices the KV dtype (int8 values +
        # scales), and the pool's head axis shards over tp.
        tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
        cache_dev_bytes = self._page_alloc.num_pages * page_bytes(
            cfg, self._page_size, dtype.itemsize, kv_quant
        ) // tp
        self._decode_impl = decode_attention_impl(mesh, cache_dev_bytes)
        # Decode anchors its per-layer weight slices outside the chunk
        # scan (models/llama.split_blocks: layout conversions once per
        # round, not per token). The compiler gives every slice that
        # enters the loop a buffer of its own, so for the length of a
        # round the device holds the block weights twice. Take that where
        # there is room for it — it was worth ~0.47 ms a step at the 1B
        # shape — and not where a 7B int8 tree on a 16 GB chip would no
        # longer fit beside its pool (the TPU compiler refuses that decode
        # program outright). A backend that reports no limit (the CPU)
        # splits, as before.
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        self._split_decode_weights = self._room_for_split(
            sum(x.nbytes for x in jax.tree.leaves(params)) // tp,
            cache_dev_bytes, limit)
        # Per-round roofline ledger (ISSUE 12, utils/perfmodel.py): the
        # SAME analytic cost model bench.py prices artifacts with, built
        # once from everything immutable — model shape, weight bytes/bits,
        # KV layout/dtype pricing, tp shard, device peaks — so every
        # harvested round can stamp achieved MFU, HBM-bandwidth
        # utilization, and a compute-vs-memory-bound verdict for a
        # handful of float multiplies (bench's _obs_overhead prices the
        # stamp against the <1% bar).
        self.perf = PerfModel(
            cfg,
            param_bytes=int(sum(x.nbytes for x in jax.tree.leaves(params))),
            weight_bits=self._weight_bits,
            kv_itemsize=dtype.itemsize,
            kv_quant=kv_quant,
            kv_layout="paged",
            page_size=self._page_size,
            tp=tp,
            device_kind=jax.devices()[0].device_kind,
        )
        self._last_harvest_t: Optional[float] = None
        # On-demand device profiling (/debug/profile): the caller's thread
        # starts the trace and arms; the WORKER flips armed to capturing
        # at the next round it issues and counts N harvested rounds; a
        # writer thread stops the trace. The process-wide guard in
        # utils/traceprof keeps at most one capture in flight fleet-wide.
        self._profile_lock = threading.Lock()
        self._profile_arm: Optional[Dict[str, object]] = None
        self._profile_active: Optional[Dict[str, object]] = None
        self._profile_last: Optional[Dict[str, object]] = None
        self._profile_writing: Optional[Dict[str, object]] = None
        self._profile_writer: Optional[threading.Thread] = None
        # The persistent cache is a TUPLE of pool arrays threaded through
        # every jitted op: (kp, vp) in the compute dtype, (kp, kps, vp,
        # vps) with int8 KV (values + per-position scales). The per-slot
        # page tables ride beside them as self._ptab, a non-donated arg
        # to every program.
        def make_cache():
            # Stored lane-packed where the heads are narrow
            # (engine/paged_kv.lane_pack decides; the pool's shape is the
            # record every program reads it from).
            pool = init_page_pool(
                cfg, self._page_alloc.num_pages, self._page_size,
                dtype=dtype, kv_quant=kv_quant, tp=tp,
            )
            return ((pool["kp"], pool["kps"], pool["vp"], pool["vps"])
                    if kv_quant else (pool["kp"], pool["vp"]))

        # Device page tables: [slots, pages_per_slot], the UNMAPPED
        # sentinel is num_pages — one past the pool, so jax drops the
        # scatter writes of parked/padding rows and gathers clip to a
        # causally-masked real page.
        self._ptab = jnp.full(
            (num_slots, self._pages_per_slot),
            self._page_alloc.num_pages, jnp.int32,
        )
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # Pages unsharded, KV heads on tp; scale tensors drop the
            # trailing axis from the spec but keep heads-over-tp —
            # [L, P, K, PS(, H)]. Born in place: made on the default
            # device and moved, a pool that fills a chip would first have
            # to fit on device 0 beside whatever lives there (its own
            # replica's weights and pool, under dp).
            arrs = jax.jit(make_cache, out_shardings=tuple(
                NamedSharding(
                    mesh,
                    P(None, None, "tp", None, None) if x.ndim == 5
                    else P(None, None, "tp", None),
                )
                for x in jax.eval_shape(make_cache)
            ))()
            # Page tables replicate: every device addresses the full
            # page axis of its own head shard.
            self._ptab = jax.device_put(
                self._ptab, NamedSharding(mesh, P(None, None))
            )
        else:
            arrs = make_cache()
        self._cache = arrs
        # The cache as stored, for /metrics and for refusing a page blob
        # of another shape: read here, once, from threads that must not
        # touch a buffer the worker has donated.
        self._kv_stored_shape = tuple(arrs[0].shape)

        # Per-slot state lives ON DEVICE and chains between rounds: decode
        # rounds and admission scatters are issued asynchronously and the
        # host syncs only to harvest sampled tokens (one transfer per round,
        # one round LATE — see _loop). Where host<->device round trips are
        # slow, per-round syncs, not device FLOPs, bound serving;
        # overlapping the round-trip with the next round's compute is the
        # fix, and on a local chip the same structure simply pipelines
        # dispatch.
        # Inactive slots "park" at the last cache slot: decode rounds write
        # garbage K/V for every slot in the batch, and a parked write lands
        # where no query can ever see it (visibility needs query position
        # >= max_seq-1, and submit() caps requests below that). This is
        # what makes chunked prefill safe: while a slot's prompt streams in
        # over several chunks, interleaved decode rounds keep scribbling at
        # the park slot, not inside the freshly written prompt region.
        self._park = self.max_seq - 1
        self._cur = jnp.full((num_slots,), cfg.pad_id, jnp.int32)
        self._pos = jnp.full((num_slots,), self._park, jnp.int32)
        self._temps = jnp.zeros(num_slots, jnp.float32)
        self._topps = jnp.ones(num_slots, jnp.float32)
        self._topks = jnp.zeros(num_slots, jnp.int32)
        # Per-request RNG: seed + tokens-sampled-so-far give slot s's key for
        # its next token as fold_in(key(seed), count) — independent of what
        # else is in the batch. counts advance on device (decode fn),
        # mirroring nothing to the host.
        self._seeds = jnp.zeros(num_slots, jnp.uint32)
        self._counts = jnp.zeros(num_slots, jnp.int32)
        # Grammar constraining: per-slot DFA state (0 = unconstrained
        # sentinel row of the installed tables) and remaining token budget
        # (drives the closing-mask switch) — both live on device and chain
        # between rounds like every other slot array. ONE grammar's tables
        # are installed at a time ([S, V] mask/next/dist/closing, passed to
        # the decode jit as regular args); mixed constrained/unconstrained
        # batches need no recompilation because "no grammar" is just state
        # 0. Installing a DIFFERENT grammar (new schema) swaps the tables
        # on the worker thread once no constrained slot is active — that is
        # one retrace per grammar, never per request.
        self._cstates = jnp.zeros(num_slots, jnp.int32)
        # crem rests at 1 for inactive slots (sentinel need is 1, so the
        # parked row is genuinely all-allowed — see park_slot).
        self._crem = jnp.ones(num_slots, jnp.int32)
        self._constraint: Optional[CompiledMask] = None
        self._ctables = trivial_tables(cfg.vocab_size)
        self._constraint_wait: "deque[_Request]" = deque()
        self._slot_req: List[Optional[_Request]] = [None] * num_slots
        # Per-slot occupancy epoch, bumped at every admission, retirement
        # and preemption: in-flight rounds/firsts are stamped with it at
        # issue, and the harvest drops rows whose epoch is stale — the
        # request-identity check alone cannot catch a request preempted
        # and re-admitted into the SAME slot between issue and harvest.
        self._slot_epoch: List[int] = [0] * num_slots
        # In-flight rounds awaiting harvest: (issue-time slot->req list,
        # issue-time slot-epoch snapshot, toks device array, n_emit device
        # array or None, firsts list of (slot, req, first_tok device,
        # epoch), issue wall stamp, mixed_meta — the unified ragged
        # round's prefill-side attribution dict, None on alternating
        # rounds — and the round's number, by which a trace pairs the
        # span that issued it with the span that harvested it).
        self._pending: "deque[Tuple[List[Optional[_Request]], List[int], jax.Array, object, list, float, Optional[dict], int]]" = deque()
        self._first_pending: list = []
        self._harvest_lag = 1  # rounds kept in flight before syncing
        (self._park_fn, self._ready_fn, self._retire_fn,
         self._resume_fn) = self._build_state_ops()
        (self._ptab_row_fn, self._copy_page_fn,
         self._restore_page_fn) = self._build_page_ops()
        # Unified ragged prefill+decode (ISSUE 19): one compiled
        # mixed-round program admits this round's prefill chunks and every
        # decode slot into the SAME launch — forward takes a per-slot
        # query-length vector (q_lens), prefill rows scatter their chunk
        # through the page table while decode rows emit tokens, and the
        # _loop hot path stops alternating a separate prefill pass with
        # decode rounds. LSOT_RAGGED=0 (the default) keeps the alternating
        # scheduler bit for bit.
        if ragged is None:
            ragged = os.environ.get("LSOT_RAGGED", "0").strip().lower() in (
                "1", "true", "yes", "on"
            )
            if ragged and self.phase_role != "mixed":
                # Env-driven opt-in degrades silently on phase-split
                # replicas: one LSOT_RAGGED=1 environment may spawn
                # heterogeneous fleets.
                ragged = False
        elif ragged and self.phase_role != "mixed":
            raise ValueError(
                "ragged mixed rounds need phase_role='mixed' (a "
                "phase-split replica has no mixed rounds to unify)"
            )
        self._ragged = bool(ragged)
        if self._ragged:
            from ..models.llama import _UNROLL_MAX_T

            # Mixed rounds run prefill chunks through forward's unrolled
            # paged path (one T for the whole batch), so chunks cap at the
            # unroll bound instead of an arbitrary prompt_bucket.
            self.prompt_bucket = min(self.prompt_bucket, _UNROLL_MAX_T)
            self._mixed_fns: Dict[int, Callable] = {}
        # Prompt-chunk buckets: powers of two up to prompt_bucket, so a short
        # prompt pays a small forward instead of a full prompt_bucket one
        # (one compiled prefill program per bucket, built lazily).
        b, buckets = min(16, self.prompt_bucket), []
        while b < self.prompt_bucket:
            buckets.append(b)
            b *= 2
        self._buckets = buckets + [self.prompt_bucket]
        # Batched prefill: up to kmax same-bucket admissions share one
        # forward (weight streaming amortizes across an admission burst).
        # Group size pads to a power-of-two k-bucket: a lone admission pays
        # a 1-row forward (low-concurrency TTFT unchanged), bursts pad at
        # most 2x, and compiled variants stay bounded at
        # len(buckets) * len(kbuckets) (built lazily).
        # kmax capped at 8: the prefill fn gathers/scatters its group's cache
        # rows through the whole stacked buffer, and larger groups also stall
        # the decode interleave for a full multi-kilotoken forward — kmax=16
        # measured 1075 tok/s vs kmax=8's 1836 on the v5e serving sweep.
        self._prefill_kmax = min(num_slots, 8)
        kb, kbuckets = 1, []
        while kb < self._prefill_kmax:
            kbuckets.append(kb)
            kb *= 2
        self._kbuckets = kbuckets + [self._prefill_kmax]

        # Speculative decoding (prompt-lookup, engine/speculative.py): when
        # speculative_draft=D > 0, decode rounds draft D tokens per slot
        # from an ON-DEVICE token history and verify them with one T=D+1
        # forward — greedy slots emit 1..D+1 tokens per round (exact
        # greedy chain), sampled slots emit 1..D+1 via rejection sampling
        # (unbiased: the emitted tokens are distributed exactly as
        # vanilla sample_runtime decode). The verify window takes the
        # unrolled small-T einsum path, which also composes with the
        # int8 KV cache.
        self._spec_draft = int(speculative_draft or 0)
        self._spec_ngram = spec_ngram
        if self._spec_draft:
            from ..models.llama import _UNROLL_MAX_T

            if not 1 <= self._spec_draft <= _UNROLL_MAX_T - 1:
                raise ValueError(
                    f"speculative_draft must be in [1, {_UNROLL_MAX_T - 1}]"
                    f" (verify window T = draft+1 must take the unrolled "
                    f"small-T path), got {self._spec_draft}"
                )
            # Prefix-cache reuse skips prefill forwards, so reused tokens
            # would never reach the on-device draft history; disable reuse
            # rather than draft from holes (both features target the same
            # copy-heavy workload — pick speculation when it's on).
            prefix_cache_blocks = 0
            # History rows are max_seq + D+1 wide: the emit scatter writes a
            # D+1 window at hlen (<= max_seq-1 by the submit bound), and the
            # extra tail absorbs it without dynamic_update_slice clamping.
            self._hist = jnp.full(
                (num_slots, self.max_seq + self._spec_draft + 1),
                cfg.pad_id, jnp.int32,
            )
            self._hlen = jnp.zeros(num_slots, jnp.int32)
            self._spec_ready_fn, self._spec_resume_fn = \
                self._build_spec_ready()
            # Acceptance accounting (VERDICT r4 next #5): without a counter
            # the bench could never say whether speculation PAYS — breakeven
            # is ~1.6 accepted tokens per verify round (the measured cost of
            # a T=D+1 verify vs a T=1 step, engine/speculative.py). Counted
            # at harvest for every emitting slot. The *_con pair counts the
            # CONSTRAINED subset of the totals: grammar-masked traffic has
            # a different acceptance profile (forced keyword/identifier
            # runs accept whole chains; branch points reject), and an
            # operator deciding whether speculation pays for the NL→SQL
            # hot path needs ITS tokens/round, not a blend with
            # unconstrained traffic. The *_samp pair counts the SAMPLED
            # (temperature>0) subset the same way: rejection-sampling
            # acceptance (u < target mass) runs systematically below
            # greedy's argmax-match acceptance, and the sampled class's
            # tokens/round is the go/no-go number for speculating on
            # sampled traffic (speculation_stats splits both axes;
            # /metrics carries all of it).
            self._spec_rounds = 0
            self._spec_tokens = 0
            self._spec_rounds_con = 0
            self._spec_tokens_con = 0
            self._spec_rounds_samp = 0
            self._spec_tokens_samp = 0

        # Prefix cache: block size = the smallest bucket, so chunk boundaries
        # always land on block boundaries. `_prefix_pages` (above) is the
        # LRU of content-keyed page references.
        self._pblock = self._buckets[0]
        self._prefix_cache_blocks = max(0, prefix_cache_blocks)
        # Publish gate: a block's pages are shared with the cache only
        # once its content key has been SEEN before (second occurrence
        # onward). A shared system/schema prefix repeats across requests,
        # so it gets published on request 2 and hit from request 3 on;
        # one-off prompts (every block unique) take no references and
        # never crowd the LRU.
        self._prefix_seen: "OrderedDict[Tuple[int, ...], None]" = OrderedDict()
        self._prefix_hits = 0
        self._prefix_blocks_reused = 0
        # --- Prefix-cache telemetry (ISSUE 14). Counters move as a group
        # under _submit_lock (the PR-1 speculation-counter pattern) so
        # /metrics scrapes and bench's pre/post delta bracketing never
        # read a torn (hits, misses, reused_tokens) triple; the worker
        # thread is the only writer.
        self._prefix_misses = 0
        self._prefix_evictions = 0
        self._prefix_reinserts = 0
        self._prefix_reused_tokens = 0
        self._prefix_flops_saved = 0.0
        self._prefix_s_saved = 0.0
        # Hit-rate EWMA over admissions (1.0 hit / 0.0 miss, alpha 0.2):
        # the live per-replica routing signal replica_loads() exports —
        # a ratio of lifetime counters would take hours to reflect a
        # cold cache after a restart.
        self._prefix_hit_ewma: Optional[float] = None
        # Content-addressed registry: per-entry live metadata keyed by
        # the same chain keys as the caches (digest, token length, hit
        # count, insert/last-hit round). Pages/bytes/refcounts are read
        # off the live structures at registry() time, never duplicated.
        self._prefix_meta: Dict[Tuple[int, ...], Dict[str, object]] = {}
        # Eviction-churn ghost: keys evicted from the cache, bounded like
        # _prefix_seen — a publish that finds its key here is a
        # REINSERTION (the cache was too small for the working set), the
        # churn signal the ring-size knob acts on.
        self._prefix_evicted_ghost: "OrderedDict[Tuple[int, ...], None]" = (
            OrderedDict()
        )
        # Reuse-distance ring: the last N admissions' schema-prefix
        # digests. distance = admissions since the same digest last
        # appeared, computed O(1) off a digest -> admission-seq map
        # (bounded: stale entries older than the ring window are swept
        # when the map doubles — a linear deque scan was the measured
        # hog of the admission stamp). Histogram buckets are powers of
        # two plus an overflow arm ("inf" = first sighting inside the
        # ring window).
        self._prefix_ring_cap = _prefix_bound(
            _PREFIX_RING, "LSOT_PREFIX_RING", 256)
        self._prefix_topk = _prefix_bound(
            _PREFIX_TOPK, "LSOT_PREFIX_TOPK", 32)
        self._prefix_adm_seq = 0
        self._prefix_ring_seq: Dict[str, int] = {}
        self._prefix_rd_buckets = _rd_buckets(self._prefix_ring_cap)
        self._prefix_rd_hist: Dict[str, int] = {}
        # Digest memo (chain key -> digest), LRU-bounded: packing a
        # Python token list into hashable bytes is the measured hog of
        # the admission stamp (~6µs/256 tokens), and steady-state traffic
        # repeats the SAME schema prefix — so the hot path is a tuple +
        # dict probe, and blake2b runs once per distinct prefix.
        self._prefix_digest_memo: "OrderedDict[Tuple[int, ...], str]" = (
            OrderedDict()
        )
        # Per-round reuse attribution, flushed into the flight record at
        # the next harvest ({rid, digest, reused, prefilled} per admitted
        # request that went through the prefix-match path).
        self._round_prefix: List[Dict[str, object]] = []

        # Recent per-request service time (EWMA of completed requests'
        # submit→retire wall): the backpressure estimate behind
        # retry_after_hint(). None until the first completion — the static
        # 1s floor serves until there is something to estimate from.
        self._svc_ewma: Optional[float] = None
        # Token-weighted backlog: sum of outstanding requests' max_new
        # (queued + slotted; += at submit/requeue, -= at terminal), and a
        # per-TOKEN service-time EWMA beside the per-request one. The
        # pool's least-loaded router scores replicas by
        # pending_tokens × sec/token / slots: the same service-time-EWMA
        # family as the Retry-After math, refined to token resolution —
        # request COUNTS tie constantly under a submit burst and say
        # nothing about skewed prompt lengths; outstanding token mass is
        # the signal that actually differs, and pricing it in seconds
        # keeps the score comparable to a request's deadline.
        self._pending_new_tokens = 0
        self._stok_ewma: Optional[float] = None

        # Multi-tenant QoS (ISSUE 18): weighted-fair queueing at admission
        # and _page_wait. `LSOT_QOS=0` switches every QoS path off — the
        # FIFO admission order, prefix-cache key shapes, and preemption
        # victim choice then reproduce the pre-QoS scheduler bit-for-bit
        # (reconciliation-tested at the token level). With QoS on, the
        # worker drains the submit queue into `_ready` and serves the
        # smallest virtual finish time: vft = max(global virtual time,
        # tenant's last vft) + (prompt+budget tokens)/weight — start-time
        # fair queueing, so a storm tenant's backlog inflates only its
        # OWN virtual clock and cannot head-of-line-block a light tenant.
        # `_ready` and the WFQ ledgers are touched only under
        # `_submit_lock` (extract_queued races the worker during drains).
        from .qos import (parse_tenant_weights as _ptw,
                          prefix_tenant_ns_enabled as _pns,
                          qos_enabled as _qen)
        self._qos = _qen()
        self._tenant_weights: Dict[str, float] = (
            _ptw(os.environ.get("LSOT_TENANT_WEIGHTS", ""))
            if self._qos else {}
        )
        self._prefix_tenant_ns = self._qos and _pns()
        self._wfq_vt = 0.0
        self._wfq_last: Dict[str, float] = {}
        self._ready: List[_Request] = []
        self._tenant_submitted: Dict[str, float] = {}
        self._tenant_preempted: Dict[str, float] = {}

        self._queue: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._prefill_q: "deque[Tuple[int, _Request]]" = deque()
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._crash: Optional[BaseException] = None
        # Guards the closed-check+enqueue in submit() against the final queue
        # drain in _close(): a request either lands before the drain starts
        # (and is drained) or submit() observes _closed and raises.
        self._submit_lock = threading.Lock()
        self._closed = False
        self._prefill_fns: Dict[Tuple[int, int], object] = {}
        self._decode_fn = (self._build_spec_decode() if self._spec_draft
                           else self._build_decode())
        self._warmed = False  # a full warmup() has run on this instance

    @staticmethod
    def _room_for_split(param_bytes: int, cache_bytes: int,
                        limit: Optional[int]) -> bool:
        """Whether a device of `limit` bytes holds the weights twice
        beside the cache, with a tenth to spare for the round's other
        buffers (see `_split_decode_weights`)."""
        return limit is None or 2 * param_bytes + cache_bytes < 0.9 * limit

    # ---------------------------------------------------------------- jitted

    def _build_state_ops(self):
        """Async per-slot state scatters (no host sync; ~bytes of traffic).

        park: point a freshly reserved slot's decode writes at the parking
        position before its prompt starts streaming in.
        ready: arm a slot for decode — first sampled token (still a device
        value from the prefill program), true position, sampling knobs, RNG
        stream (count=1: the prefill sample consumed fold index 0)."""
        park = self._park
        pad = self.cfg.pad_id

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def park_slot(cur, pos, cstates, crem, slot):
            # A freshly reserved slot also drops any previous occupant's
            # grammar state: parked garbage decode must run the sentinel
            # (all-allowed) row, not a stale budget-starved one. crem
            # parks at 1 — the sentinel row's need is 1, so `need <= crem`
            # genuinely allows everything (crem=0 would mask the whole
            # vocabulary: harmless for output, which is discarded, but the
            # inverse of the invariant); it never decrements while the
            # slot is inactive.
            return (
                cur.at[slot].set(pad),
                pos.at[slot].set(park),
                cstates.at[slot].set(0),
                crem.at[slot].set(1),
            )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def retire_slot(temps, topps, topks, cstates, slot):
            # Reset the sampling knobs so a retired sampled request doesn't
            # leave temperature > 0 behind: sample_runtime's all-greedy
            # lax.cond fast path keys on EVERY slot's temperature, and one
            # stale hot slot would force the full vocab-sort path on all
            # subsequent rounds of an otherwise greedy workload. The
            # grammar state resets for the same hygiene (a stale
            # constrained state would keep masking the slot's parked
            # garbage decode).
            return (
                temps.at[slot].set(0.0),
                topps.at[slot].set(1.0),
                topks.at[slot].set(0),
                cstates.at[slot].set(0),
            )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
        def ready_slot(cur, pos, temps, topps, topks, seeds, counts,
                       cstates, crem, next_t, slot,
                       tok, pos_val, temp, topp, topk, seed, cinit, cbudget):
            # The first sampled token (still on device) advances the
            # grammar FSM here: cinit is the grammar start state (0 for
            # unconstrained requests — row 0 of next_t self-loops, so the
            # same scatter serves both).
            return (
                cur.at[slot].set(tok[0]),
                pos.at[slot].set(pos_val),
                temps.at[slot].set(temp),
                topps.at[slot].set(topp),
                topks.at[slot].set(topk),
                seeds.at[slot].set(seed),
                counts.at[slot].set(1),
                cstates.at[slot].set(next_t[cinit, tok[0]]),
                crem.at[slot].set(cbudget - 1),
            )

        @partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 8))
        def resume_slot(cur, pos, temps, topps, topks, seeds, counts,
                        cstates, crem, slot, tok, pos_val, temp, topp, topk,
                        seed, count0, cstate0, crem0):
            # Arm a PREEMPTION-RESUMED slot from host scalars: no fresh
            # sample — `tok` is the last COMMITTED token (already
            # delivered), fed again at its own position so decode
            # continues exactly where the victim stopped. counts/cstate/
            # crem restore the committed RNG stream index, the replayed
            # FSM state, and the remaining grammar budget — the whole
            # determinism contract in one scatter.
            return (
                cur.at[slot].set(tok),
                pos.at[slot].set(pos_val),
                temps.at[slot].set(temp),
                topps.at[slot].set(topp),
                topks.at[slot].set(topk),
                seeds.at[slot].set(seed),
                counts.at[slot].set(count0),
                cstates.at[slot].set(cstate0),
                crem.at[slot].set(crem0),
            )

        return park_slot, ready_slot, retire_slot, resume_slot

    def _build_page_ops(self):
        """Jitted paged-KV bookkeeping ops (async scatters, ~bytes of
        traffic), generic over the pool tuple — (kp, vp) compute-dtype or
        (kp, kps, vp, vps) int8 values + per-position scales:

        set_row: replace one slot's device page-table row (admission,
        retirement, copy-on-write remaps). Driven at the OOB slot index
        during warmup — jax drops the scatter, a true no-op.
        copy_page: one-page device copy for copy-on-write (a shared page
        about to be partially overwritten at a non-page-aligned boundary
        is copied into a fresh exclusive page first; the prefix-cache
        entry keeps the original). Under int8 the SCALES copy with their
        values — a page's content is (q8, s) pairs.
        restore_pages: spill-resume scatter; int8 spills restore values
        AND scales (the spill serialized both)."""
        nc = len(self._cache)

        @partial(jax.jit, donate_argnums=(0,))
        def set_row(ptab, slot, row):
            return ptab.at[slot].set(row)

        @partial(jax.jit, donate_argnums=tuple(range(nc)))
        def copy_page(*args):
            cache, (dst, src) = args[:nc], args[nc:]
            out = []
            for c in cache:
                head = (c.shape[0], 1) + c.shape[2:]
                zeros = (0,) * (c.ndim - 2)
                pg = lax.dynamic_slice(c, (0, src) + zeros, head)
                out.append(
                    lax.dynamic_update_slice(c, pg, (0, dst) + zeros)
                )
            return tuple(out)

        @partial(jax.jit, donate_argnums=tuple(range(nc)))
        def restore_pages(*args):
            # Spill-resume (LSOT_KV_SPILL) and handoff import (ISSUE 13):
            # write the host page copies [L, n, K, page(, H)] back into
            # freshly allocated pool pages in ONE scatter per array (one
            # dispatch + one transfer per resume, not per page; retraces
            # per distinct page count, bounded by pages_per_slot). The
            # scatter itself is engine/paged_kv.import_pages — the
            # first-class migration op — wrapped here with donation.
            cache, idx, stacks = args[:nc], args[nc], args[nc + 1:]
            return import_pages(cache, idx, stacks)

        return set_row, copy_page, restore_pages

    # ---------------------------------------------------- paged-KV host side

    def _sync_ptab_row(self, slot: int) -> None:
        """Mirror a slot's host page list into the device table (async
        scatter; unmapped tail entries carry the OOB sentinel)."""
        row = np.full(
            (self._pages_per_slot,), self._page_alloc.num_pages, np.int32
        )
        pages = self._slot_pages[slot]
        row[: len(pages)] = pages
        self._ptab = self._ptab_row_fn(
            self._ptab, jnp.int32(slot), jnp.asarray(row)
        )

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """All-or-nothing page grab, evicting LRU prefix-cache entries
        under pressure: cached prefixes are a perf win funded by SPARE
        pages, never a reason to make a live request wait."""
        while not self._page_alloc.can_alloc(n) and self._prefix_pages:
            key, pages = self._prefix_pages.popitem(last=False)
            self._prefix_note_evict(key, pages=pages)
            self._page_alloc.release(list(pages))
        return self._page_alloc.alloc(n)

    def _free_slot_pages(self, slot: int) -> None:
        """Retirement: drop the slot's page references (pages still held
        by prefix-cache entries survive for future hits) and unmap its
        device row."""
        if self._slot_pages[slot]:
            self._page_alloc.release(self._slot_pages[slot])
            self._slot_pages[slot] = []
            self._sync_ptab_row(slot)

    def _evict_entries_with(self, page: int) -> None:
        """Drop every prefix-cache entry referencing `page` (the
        copy-on-write fallback when the pool has no free page for the
        copy: un-publishing makes the page exclusive again, so the write
        can proceed in place without ever touching shared content)."""
        for key in [k for k, v in self._prefix_pages.items() if page in v]:
            pages = self._prefix_pages.pop(key)
            self._prefix_note_evict(key, pages=pages)
            self._page_alloc.release(list(pages))

    def _ensure_writable(self, slot: int, start_tok: int, end_tok: int) -> None:
        """Copy-on-write sweep before writing cache positions
        [start_tok, end_tok): any SHARED page in the range is either
        copied into a fresh exclusive page (content preserved, table
        remapped — the prefix-cache entry keeps the original) or, if the
        pool can't fund the copy, un-published until exclusive. Shared
        pages are never written in place — the invariant the allocator
        property tests pin. Page-aligned traffic never triggers this
        (full prefix pages sit below every write range); the only
        organic trigger is a non-page-aligned prefix boundary."""
        ps = self._page_size
        pages = self._slot_pages[slot]
        hi = min(pages_for_tokens(end_tok, ps), len(pages))
        for pi in range(start_tok // ps, hi):
            pg = pages[pi]
            if not self._page_alloc.is_shared(pg):
                continue
            fresh = self._alloc_pages(1)
            if fresh is None:
                # No page for a copy: un-publish instead. Slot-to-slot
                # sharing only ever covers FULL prefix pages below any
                # write range, so after entry eviction the page is ours.
                self._evict_entries_with(pg)
                if self._page_alloc.is_shared(pg):
                    raise RuntimeError(
                        f"page {pg} still shared inside a write range "
                        f"after un-publishing (slot {slot})"
                    )
                continue
            self._cache = self._copy_page_fn(
                *self._cache, jnp.int32(fresh[0]), jnp.int32(pg)
            )
            self._page_alloc.note_cow()
            self._page_alloc.release([pg])
            pages[pi] = fresh[0]
            self._sync_ptab_row(slot)

    # ------------------------------------------- pressure relief (ISSUE 10)

    def _reserve_new(self, req: _Request) -> int:
        """Generation tokens the admission envelope RESERVES for `req`.

        Exact mode (kv_overcommit = 1.0, the default): the full remaining
        budget — bit-for-bit the pre-overcommit envelope. Overcommitted:
        min(budget, max(ratio × budget, expected remaining generation)),
        where expected = EWMA of completed requests' generation lengths
        minus what this request already generated — the vLLM-style bet
        that most requests stop far short of max_new, with the ratio as
        the guaranteed floor. Decode tops up at each harvest; a failed
        top-up preempts (the overcommit's safety valve)."""
        remaining = max(0, req.max_new - len(req.generated))
        r = self._kv_overcommit
        if r >= 1.0 or remaining == 0:
            return remaining
        floor = -int(-remaining * r // 1)  # ceil
        expect = 0
        if self._gen_ewma is not None:
            expect = max(0, -int(-self._gen_ewma // 1)
                         - len(req.generated))
        return min(remaining, max(1, floor, expect))

    def _sample_pressure(self) -> None:
        """Chaos seam: the value-valued `kv:pressure` site withholds part
        of the pool (a fraction when the value < 1, absolute pages
        otherwise) for every loop iteration it fires — allocation and
        top-up failures become injectable, which is how the chaos stage
        forces a deterministic preemption storm. Pressure lifts the
        moment the site stops firing."""
        if not FAULTS.active:
            if self._page_alloc.withheld:
                self._page_alloc.withhold(0)
            return
        v = FAULTS.value("kv:pressure")
        if v is None:
            self._page_alloc.withhold(0)
            return
        total = self._page_alloc.num_pages
        self._page_alloc.withhold(
            int(v * total) if v < 1.0 else int(v)
        )

    def _watermark_sweep(self) -> None:
        """Proactive LRU eviction of prefix-cache pages: when available
        pages fall under the LOW watermark, evict entries until the HIGH
        watermark recovers (or the cache is empty) — pressure is relieved
        BEFORE an allocation fails, so steady-state traffic rarely needs
        a preemption. Disabled at low = 0 (the on-demand eviction inside
        _alloc_pages still backstops allocation)."""
        if not self._wm_low_pages or \
                self._page_alloc.pages_available >= self._wm_low_pages:
            return
        evicted = 0
        while self._prefix_pages and \
                self._page_alloc.pages_available < self._wm_high_pages:
            key, pages = self._prefix_pages.popitem(last=False)
            self._prefix_note_evict(key, pages=pages)
            self._page_alloc.release(list(pages))
            evicted += 1
        if evicted:
            self._page_alloc.note_evictions(evicted)
            resilience.inc("kv_evictions")
            self.flight.event("kv_evict", entries=evicted,
                              free=self._page_alloc.pages_free)

    def _sweep_page_wait(self) -> None:
        """Deadline enforcement for page-starved requests: a request
        parked on pool pages past its deadline fails fast with the typed
        DeadlineExceeded (504) instead of waiting forever — page-wait
        starvation is queue wait, and the same _observe_terminal path
        feeds the queue-wait span + histogram. Cancelled waiters resolve
        with whatever they had (the cancel contract)."""
        if not self._page_wait:
            return
        keep: "deque[_Request]" = deque()
        expired: List[_Request] = []
        while self._page_wait:
            req = self._page_wait.popleft()
            if req.cancelled:
                self._observe_terminal(req)
                req.future.set_result(req.generated)
            elif req.past_deadline():
                expired.append(req)
            else:
                keep.append(req)
        self._page_wait = keep
        # Expiry surfaces in DEADLINE order even when WFQ reorders the
        # SERVICE order (ISSUE 18 satellite): under QoS the deque is no
        # longer deadline-monotone — a heavy tenant's earlier-expiring
        # waiter can sit behind a light tenant's — and anything pairing
        # 504s with submit deadlines (clients racing timeouts, the chaos
        # harness's loss accounting) relies on earliest-first failure.
        expired.sort(key=lambda r: (r.deadline.expires_at
                                    if r.deadline is not None else 0.0))
        for req in expired:
            resilience.inc("deadline_expired")
            self._observe_terminal(req, error="DeadlineExceeded")
            req.future.set_exception(req.deadline_error())

    def _preempt_slot(self, slot: int) -> None:
        """Victim preemption: release the slot's pages and park the
        request for a DETERMINISTIC resume. Recompute mode re-runs
        prefill over prompt + committed tokens at re-admission; spill
        mode (LSOT_KV_SPILL=1) copies the committed pages to host first
        and restores them instead of recomputing. Either way the client
        keeps every delivered token and the continuation is
        token-identical to an unpreempted control: greedy trivially,
        sampled because `rng_count` restores the per-slot
        fold_in(key(seed), count) stream index, constrained because the
        FSM state is re-derived from the committed tokens."""
        req = self._slot_req[slot]
        if self._kv_spill and req.generated:
            plen = len(req.ids) + len(req.generated)
            npg = min(pages_for_tokens(plen, self._page_size),
                      len(self._slot_pages[slot]))
            # Syncs in-flight rounds; their uncommitted writes beyond the
            # committed positions ride along as garbage the resumed
            # decode overwrites before any read can see it (the same
            # write-before-read invariant every freed-page reuse relies
            # on). EVERY cache array spills — under an int8 pool the
            # quantization scales serialize beside the int8 pages, so a
            # restore reproduces the page content (q8, s) exactly and the
            # resumed output stays token-identical. export_pages is the
            # same first-class op the prefill→decode handoff ships.
            req.spilled = export_pages(
                self._cache, self._slot_pages[slot][:npg]
            )
            self._page_alloc.note_spill(int(npg))
        req.resume_pref = len(req.generated)
        req.preempted += 1
        req.ready = False
        req.prefilled = 0
        self._slot_req[slot] = None
        self._slot_epoch[slot] += 1
        if self._prefill_q:
            # Purge the victim's queued prefill entries NOW: a mid-prefill
            # victim re-admitted into the SAME slot would otherwise leave
            # a stale (slot, req) pair that _prefill_step's identity check
            # cannot tell from the fresh one — the chunk would prefill
            # twice and `prefilled` would advance two chunks for one
            # chunk's KV.
            self._prefill_q = deque(
                (s, r) for (s, r) in self._prefill_q if r is not req
            )
        # Same hygiene as retirement: a lingering temperature > 0 would
        # defeat the all-greedy fast path for every later round.
        self._temps, self._topps, self._topks, self._cstates = \
            self._retire_fn(self._temps, self._topps, self._topks,
                            self._cstates, jnp.int32(slot))
        self._free_slot_pages(slot)
        self._page_alloc.note_preempt()
        resilience.inc("kv_preemptions")
        if self._qos:
            from .qos import bounded_bump
            with self._submit_lock:
                bounded_bump(self._tenant_preempted, req.tenant)
        # Open a parked interval for the request trace tree (closed at
        # resume; flush_spans exports it as a "sched.preempted" span).
        req.parked.append([time.perf_counter(), 0.0])
        self.flight.event(
            "preempt", slot=slot, rid=req.rid,
            generated=len(req.generated), spill=req.spilled is not None,
        )
        # Victims resume ahead of never-admitted waiters: they were
        # admitted first and already hold delivered tokens.
        self._page_wait.appendleft(req)

    def _preempt_for(self, n_pages: int, protect: int) -> Optional[List[int]]:
        """Fund a failed mid-decode allocation by preempting victims —
        fewest generated tokens first (cheapest recompute), never the
        allocating slot — until the grab succeeds or no victim remains."""
        while True:
            got = self._alloc_pages(n_pages)
            if got is not None:
                return got
            victims = [
                (len(r.generated), i)
                for i, r in enumerate(self._slot_req)
                if r is not None and i != protect
            ]
            if not victims:
                return None
            if self._qos:
                # QoS enforcement arm (ISSUE 18): prefer evicting the
                # tenant holding the most WEIGHTED slot share — the one
                # over its fair allocation — before falling back to the
                # cheapest-recompute tie-break. QoS off keeps the exact
                # pre-QoS (fewest-generated, lowest-slot) choice.
                share: Dict[str, float] = {}
                for r in self._slot_req:
                    if r is not None:
                        t = r.tenant
                        share[t] = share.get(t, 0.0) + 1.0 / self._wfq_weight(t)
                victims.sort(key=lambda v: (
                    -share.get(self._slot_req[v[1]].tenant, 0.0), v[0], v[1]))
            else:
                victims.sort()
            self._preempt_slot(victims[0][1])

    def _topup_pages(self) -> None:
        """Keep every decoding slot's mapped pages ahead of the device's
        write frontier: at each harvest the committed position is
        len(ids) + len(generated), and in-flight + next-issued rounds can
        write at most `overshoot` further before the next harvest tops up
        again — so covering committed + overshoot here means the device
        NEVER writes through an unmapped (silently dropped) table entry.
        Exact-envelope admission (kv_overcommit = 1.0) prepaid the whole
        budget, so this pass allocates nothing there. A failed top-up
        preempts a victim; if even that cannot fund it (pressure
        withholding the pool), the needing slot preempts ITSELF — parked
        with a deterministic resume beats silent KV loss."""
        overshoot, ps = self.overshoot, self._page_size
        for i in range(self.num_slots):
            req = self._slot_req[i]
            if req is None or not req.ready:
                continue
            target = len(req.ids) + len(req.generated) + overshoot
            need = pages_for_tokens(target, ps) - len(self._slot_pages[i])
            if need <= 0:
                continue
            got = self._alloc_pages(need)
            if got is None:
                got = self._preempt_for(need, i)
            if got is None:
                self._preempt_slot(i)
                continue
            self._slot_pages[i].extend(got)
            self._sync_ptab_row(i)
            req.page_end = max(req.page_end,
                               len(self._slot_pages[i]) * ps)

    def _resume_ready(self, slot: int, req: _Request,
                      mode: str = "recompute") -> None:
        """Arm a preemption-resumed slot: the last COMMITTED token is fed
        again at its own position (its KV rewrite is value-identical),
        the RNG stream index restores from the host mirror, and the
        grammar FSM state is re-derived by replaying the committed tokens
        through the compiled tables — after this scatter the slot's
        device state equals the unpreempted control's at the same commit
        frontier, which is the whole token-identical-resume contract."""
        ids = req.full_ids
        plen = len(ids)
        cstate0 = 0
        if req.constraint is not None:
            cstate0 = req.constraint.walk(req.generated)
            if cstate0 is None:
                # Committed tokens came out of the masked decode, so a
                # dead replay means corrupted state — fail typed, never
                # resume into a wrong grammar row.
                raise RuntimeError(
                    f"resume FSM replay left the grammar after "
                    f"{len(req.generated)} committed tokens (rid {req.rid})"
                )
        crem0 = max(0, req.max_new - len(req.generated))
        (self._cur, self._pos, self._temps, self._topps, self._topks,
         self._seeds, self._counts, self._cstates,
         self._crem) = self._resume_fn(
            self._cur, self._pos, self._temps, self._topps, self._topks,
            self._seeds, self._counts, self._cstates, self._crem,
            jnp.int32(slot), jnp.int32(req.generated[-1]),
            jnp.int32(plen - 1),
            jnp.float32(req.temperature), jnp.float32(req.top_p),
            jnp.int32(req.top_k), jnp.uint32(req.seed & 0xFFFFFFFF),
            jnp.int32(req.rng_count), jnp.int32(cstate0),
            jnp.int32(crem0),
        )
        if self._spec_draft:
            row = np.full((self._hist.shape[1],), self.cfg.pad_id,
                          np.int32)
            row[:plen] = ids
            self._hist, self._hlen = self._spec_resume_fn(
                self._hist, self._hlen, jnp.int32(slot),
                jnp.asarray(row), jnp.int32(plen),
            )
        req.ready = True
        req.ready_at = time.perf_counter()
        if req.parked and not req.parked[-1][1]:
            # Close the parked interval: the trace span now bounds
            # exactly preempt → re-armed.
            req.parked[-1][1] = req.ready_at
        ho = req.handoff
        if ho is not None:
            # Prefill→decode migration landed (ISSUE 13): close the
            # handoff interval — pack wall, page/byte volume, and the
            # wait for a decode slot — into the request trace (the
            # `sched.handoff` span that explains the Perfetto gap
            # between prefill and first decode token), the per-round
            # flight columns, and the lsot_handoff_* counters.
            wait = max(0.0, req.ready_at - float(ho["t_pack"]))
            self._ho_wait_sum += wait
            self._ho_wait_count += 1
            self._mig_pages += int(ho["pages"])
            self._mig_wait += wait
            if req.trace is not None:
                try:
                    req.trace.add_span(
                        "sched.handoff", float(ho["t_pack"]),
                        req.ready_at, rid=req.rid,
                        pages=int(ho["pages"]), bytes=int(ho["bytes"]),
                        export_s=float(ho["export_s"]),
                        wait_s=round(wait, 6), src=ho.get("src", ""),
                    )
                except Exception:  # noqa: BLE001 — tracing must never kill the loop
                    req.trace = None
            self.flight.event("handoff_import", slot=slot, rid=req.rid,
                              pages=int(ho["pages"]),
                              wait_s=round(wait, 6), src=ho.get("src", ""))
            req.handoff = None
        # Decode re-writes [plen - 1, page_end): COW any page the
        # re-prefill's publish shared before the slot goes
        # decode-eligible (spill resumes never published — no-op there).
        self._ensure_writable(slot, max(0, plen - 1), req.page_end)
        self.flight.event("resume", slot=slot, rid=req.rid,
                          generated=len(req.generated), mode=mode)

    def _restore_spilled(self, slot: int, req: _Request) -> None:
        """Spill-resume (LSOT_KV_SPILL=1): write the host page copies —
        values AND, under an int8 pool, their quantization scales — back
        into the freshly allocated pages and arm the slot directly; no
        re-prefill forward at all."""
        parts = req.spilled
        n = parts[0].shape[1]
        idx = jnp.asarray(self._slot_pages[slot][:n], jnp.int32)
        self._cache = self._restore_page_fn(
            *self._cache, idx, *(jnp.asarray(p) for p in parts),
        )
        if req.handoff is None:
            self._page_alloc.note_restore(int(n))
        else:
            # A MIGRATED blob, not a preemption spill: counted in the
            # handoff families so the spill path's spilled == restored
            # reconciliation stays exact per pool.
            self._ho_imports += 1
            self._ho_pages_in += int(n)
            self._ho_bytes_in += handoff_bytes(parts)
        mode = "import" if req.handoff is not None else "spill"
        req.spilled = None
        req.prefilled = len(req.full_ids)
        self._resume_ready(slot, req, mode=mode)

    # ----------------------------- prefill→decode handoff (ISSUE 13)

    def _pack_handoffs(self) -> None:
        """Prefill-role terminal step: sync the parked first tokens of
        every just-completed prompt (one device_get for the whole
        group), run the same stop/budget/cancel/deadline checks a
        harvest would, commit + stream the first token, and either
        export the request's pages into a handoff blob for the pool's
        router (`on_handoff` wired) or arm the slot to decode in place
        (no consumer — a lone prefill-role scheduler still serves)."""
        if not self._handoff_pending:
            return
        pending, self._handoff_pending = self._handoff_pending, []
        vals = jax.device_get([t for (_, _, t, _) in pending])
        emitted = 0
        packed = 0
        for (slot, req, _, epoch), fv in zip(pending, vals):
            # _append_first IS the first-token commit sequence (identity/
            # epoch guard, cancel, deadline, stop-id, append+emit, budget
            # retire) — sharing it keeps the prefill-role path bit-
            # identical to the mixed harvest's, which the token-identity
            # contract depends on. Return 1 with the slot still held
            # means "committed and mid-generation": the handoff case.
            emitted += self._append_first(slot, req,
                                          int(np.asarray(fv)[0]),
                                          epoch=epoch)
            if req is not self._slot_req[slot]:
                continue  # terminal (retired/failed/budget-exhausted)
            # Chaos seam: `sched:handoff` kills the prefill loop exactly
            # here — first token committed and possibly already streamed,
            # blob never shipped. The supervisor must re-prefill on a
            # sibling with the delivered prefix suppressed (the
            # crash-mid-handoff chaos tests).
            FAULTS.check("sched:handoff")
            if self.on_handoff is None:
                self._arm_inplace(slot, req)
                continue
            if self._pump_depth and len(self._handoff) >= self._pump_depth:
                # Bounded buffer: the pump's consumer is behind by a full
                # window of packed blobs — decoding in place is cheaper
                # than pinning more exported pages on the host.
                self._ho_backpressure += 1
                self._arm_inplace(slot, req)
                continue
            self._export_handoff(slot, req)
            packed += 1
        if packed:
            cb = self.on_handoff
            try:
                cb()
            except Exception:  # noqa: BLE001 — a broken pump must not strand work
                _log.exception("on_handoff pump failed; decoding in place")
                # Reclaim whatever the pump left behind: requeue to
                # ourselves — re-admission restores the blob and decodes
                # here (the fall-back-to-in-place rule, applied late).
                for req in self.extract_handoffs():
                    self.requeue(req)
        self._record_prefill_round(emitted, packed)

    def _export_handoff(self, slot: int, req: _Request) -> None:
        """Pack one request's live KV into a portable blob and park it in
        the handoff queue: pages covering the committed positions
        (prompt + the first token — whose KV the importer's first decode
        round writes, exactly like a preemption resume rewrites its last
        committed token) extract via export_pages, and the request's
        resume state (`resume_pref`, `rng_count`) is staged so the
        importing replica's spill-restore machinery arms a slot
        device-state-identical to a mixed replica's post-prefill arm —
        the token-identity contract."""
        t0 = time.perf_counter()
        ps = self._page_size
        committed = len(req.ids) + len(req.generated)
        npg = min(pages_for_tokens(committed, ps),
                  len(self._slot_pages[slot]))
        blob = export_pages(self._cache, self._slot_pages[slot][:npg])
        wall = time.perf_counter() - t0
        nbytes = handoff_bytes(blob)
        req.spilled = blob
        req.resume_pref = len(req.generated)
        req.rng_count = 1  # the prefill sample consumed fold index 0
        req.handoff = {
            "t_pack": time.perf_counter(), "export_s": round(wall, 6),
            "pages": int(npg), "bytes": nbytes,
            "src": self.flight.replica,
        }
        self._ho_exports += 1
        self._ho_pages_out += int(npg)
        self._ho_bytes_out += nbytes
        # Prefill service EWMA: submit→pack wall per prompt token — the
        # compute-backlog price backlog_score quotes the router.
        if req.submitted_at > 0.0:
            pstok = (time.perf_counter() - req.submitted_at) \
                / max(1, len(req.ids))
            prev = self._pref_stok_ewma
            self._pref_stok_ewma = (pstok if prev is None
                                    else 0.2 * pstok + 0.8 * prev)
        # The request leaves this replica's backlog (the importing side's
        # requeue re-adds it there); its rid reads as retired in THIS
        # replica's flight attribution.
        with self._submit_lock:
            self._pending_new_tokens = max(
                0, self._pending_new_tokens - req.max_new)
            self._pending_prompt_tokens = max(
                0, self._pending_prompt_tokens - len(req.ids))
        self._round_retired.append(req.rid)
        if req.trace is not None:
            try:
                req.trace.event("sched.handoff_export", rid=req.rid,
                                pages=int(npg), bytes=nbytes)
            except Exception:  # noqa: BLE001 — tracing must never kill the loop
                req.trace = None
        self.flight.event("handoff_export", slot=slot, rid=req.rid,
                          pages=int(npg), bytes=nbytes,
                          export_s=round(wall, 6))
        self._release_slot(slot)
        self._handoff.append(req)

    def _arm_inplace(self, slot: int, req: _Request) -> None:
        """Fallback when no handoff consumer exists (bare prefill-role
        scheduler, or the pool pump failed): decode in place. The resume
        machinery arms the slot exactly as a mixed replica's ready path
        would — cur = the committed first token at its own position,
        counts = 1, FSM replayed, budget decremented — so the output is
        token-identical either way."""
        self._ho_inplace += 1
        req.resume_pref = len(req.generated)
        req.rng_count = 1
        req.prefilled = len(req.full_ids)
        self.flight.event("handoff_inplace", slot=slot, rid=req.rid)
        self._resume_ready(slot, req, mode="inplace")

    def _record_prefill_round(self, emitted: int, handoffs: int) -> None:
        """Prefill-role round bookkeeping: a pure prefill replica never
        harvests a decode round, so the flight record, heartbeat cadence
        and prefill roofline attribution land here — one record per pack
        pass that concluded at least one request (handoff, in-place arm
        or terminal)."""
        if not (emitted or handoffs or self._round_retired
                or self._round_admitted):
            return
        self.heartbeat.round_done()
        now = time.perf_counter()
        prev, self._last_pack_t = self._last_pack_t, now
        interval = round(now - prev, 6) if prev is not None else 0.0
        ewma = self.heartbeat.expected_round_s()
        rec = {
            "round": self.heartbeat.rounds,
            "occupancy": sum(1 for r in self._slot_req if r is not None),
            "queued": self._queue.qsize(),
            "admitted": self._round_admitted,
            "retired": self._round_retired,
            "emitted": emitted,
            "handoffs": handoffs,
            "round_wall_s": interval,
            "cadence_s": round(ewma, 6) if ewma is not None else None,
            "phase": "prefill",
        }
        if prev is not None:
            # First pack pass has no interval origin: leave the banked
            # FLOPs for the next record instead of attributing a real
            # wall of work over a degenerate denominator (the inflated
            # MFU would pollute the EWMA and bench --compare's gates).
            pre = self.perf.flush_prefill(interval)
            if pre is not None:
                rec["prefill_mfu"] = pre["mfu"]
                rec["prefill_hbm_util"] = pre["hbm_util"]
        rec["kv_pages"] = self._page_alloc.pages_in_use
        rec["kv_pages_free"] = self._page_alloc.pages_free
        rec["kv_pressure"] = self._page_alloc.withheld
        self._host_columns(rec)
        self.flight.record(**rec)
        self._round_admitted = []
        self._round_retired = []

    @property
    def handoff_stats(self) -> Optional[Dict[str, object]]:
        """Disaggregation observability (None for a mixed replica that
        never touched a handoff): export/import/fallback counters, page
        and byte volumes, and the summed wait for a decode slot — the
        lsot_handoff_* Prometheus families and the /metrics
        serving.handoff payload."""
        if self.phase_role == "mixed" and not (
                self._ho_exports or self._ho_imports or self._ho_inplace):
            return None
        return {
            "replica": self.flight.replica,
            "phase_role": self.phase_role,
            "exports": self._ho_exports,
            "imports": self._ho_imports,
            "inplace_fallbacks": self._ho_inplace,
            "pages_out": self._ho_pages_out,
            "pages_in": self._ho_pages_in,
            "bytes_out": self._ho_bytes_out,
            "bytes_in": self._ho_bytes_in,
            "wait_s_sum": round(self._ho_wait_sum, 6),
            "wait_count": self._ho_wait_count,
            "queued_handoffs": len(self._handoff),
            "backpressure": self._ho_backpressure,
        }

    @property
    def page_stats(self) -> Dict[str, int]:
        """Page-pool observability: pool occupancy and sharing counters —
        `zero_copy_shares` rising with prefix hits while `cow_copies`
        stays at boundary-only counts is the
        "sharing, not copying" proof the bench artifact records; a leaked
        page shows up as pages_in_use that never drains. The pressure
        block (preemptions/evictions/spilled/withheld + watermarks) is
        the graceful-degradation dashboard."""
        out = self._page_alloc.stats()
        out["pages_per_slot"] = self._pages_per_slot
        out["page_waits"] = self._page_wait_events
        out["overcommit"] = self._kv_overcommit
        out["spill"] = int(self._kv_spill)
        out["watermark_low_pages"] = self._wm_low_pages
        out["watermark_high_pages"] = self._wm_high_pages
        # KV-dtype-aware capacity (ISSUE 11 satellite): the TRUE device
        # bytes of one page — int8 pools report ~half a compute-dtype
        # page — so /metrics serving.kv_pages, watermark ratios and
        # overcommit dashboards act on real bytes, not compute-dtype
        # fiction.
        out["kv_quant"] = self.kv_quant or ""
        out["page_bytes"] = page_bytes(
            self.cfg, self._page_size, self._dtype.itemsize, self.kv_quant
        )
        # The pool as stored: KV heads a 128-lane row (engine/paged_kv
        # .lane_pack; 1 is the plain [L, P, K, page, H]) and the shape.
        pool = self._kv_stored_shape
        out["kv_pool_lane_pack"] = pool[-1] // self.cfg.head_dim
        out["kv_pool_shape"] = "x".join(str(d) for d in pool)
        return out

    # --------------------------------------------------- performance ledger

    @property
    def perf_stats(self) -> Dict[str, object]:
        """The `serving.perf` /metrics payload: the analytic model's
        pricing assumptions + per-phase EWMAs of the live roofline
        position (prefill/decode/draft/verify MFU, HBM util, binding
        roof), replica-labeled for the Prometheus gauges."""
        return {"replica": self.flight.replica,
                "kernels": self.kernel_modes(), **self.perf.stats()}

    def kernel_modes(self) -> Dict[str, str]:
        """What this scheduler's automatic choices resolved to: whether
        Pallas kernels are compiled for the device or interpreted (the
        CPU tests), and which implementation prefill attention, decode
        attention and the paged write take. Servers print it once at
        start; `chip_smoke.py` asserts it, so a run that fell back to the
        einsum path or the interpreter cannot pass for a device run."""
        from ..ops.pallas.dispatch import resolve_interpret

        # models/llama.forward: the write kernel needs the pallas impl
        # and no mesh (GSPMD partitions the XLA scatter).
        write = ("pallas" if self._decode_impl == "pallas"
                 and self.mesh is None else "xla")
        return {
            "pallas": "interpreted" if resolve_interpret(None)
            else "compiled",
            "prefill_attention": self._impl,
            "decode_attention": self._decode_impl,
            "page_write": write,
        }

    # ------------------------------------------------ on-demand profiling

    def _profile_owner(self) -> str:
        return f"sched:{self.flight.replica}:{id(self):x}"

    def profile_rounds(self, rounds: Optional[int] = None,
                       out_dir: Optional[str] = None) -> Dict[str, object]:
        """Take a bounded `jax.profiler` device trace of the next `rounds`
        scheduler rounds (the /debug/profile seam). The trace is started
        HERE, on the caller's thread, and stopped on a thread of its own,
        beside the serving loop: stopping one takes half a minute to a
        minute on a TPU (the profiler collects and converts the device's
        events) and the loop keeps serving through it. The worker only
        flips the armed capture to `capturing` at the next round it
        issues and counts N harvested rounds; the writer thread then
        stops the trace and lists the artifacts (`*.xplane.pb` and the
        Perfetto-loadable `*.trace.json.gz`), which land under `out_dir`
        — default: next to the tracer's export dir
        (utils/traceprof.profile_defaults). The trace runs from this call
        on: on a server with nothing to serve it is stopped after
        `_PROFILE_IDLE_LIMIT_S` (`_expire_profile`). Raises RuntimeError
        when ANY capture is already in flight fleet-wide (the
        process-wide guard) or the profiler will not start."""
        import tempfile

        d_def, r_def = traceprof.profile_defaults()
        # None -> the configured default; an EXPLICIT 0 must be a clear
        # request error, never a silent default-8 capture that takes the
        # fleet-wide slot nobody asked for.
        n = r_def if rounds is None else int(rounds)
        if n < 1:
            raise ValueError(f"rounds must be >= 1, got {n}")
        owner = self._profile_owner()
        if not traceprof.try_acquire_capture(owner):
            raise RuntimeError(
                f"a device profile capture is already in flight "
                f"(owner {traceprof.capture_owner()}); one at a time "
                f"fleet-wide"
            )
        base = out_dir or d_def
        try:
            if base:
                d = os.path.join(
                    base, f"profile-{int(time.time() * 1000)}-"
                          f"{self.flight.replica}"
                )
                os.makedirs(d, exist_ok=True)
            else:
                d = tempfile.mkdtemp(prefix="lsot_profile_")
            started = time.time()
            jax.profiler.start_trace(
                d, profiler_options=traceprof.profile_options())
        except Exception as e:  # noqa: BLE001 — the guard must not leak
            traceprof.release_capture(owner)
            if isinstance(e, OSError):
                raise
            raise RuntimeError(f"the profiler did not start: {e}") from e
        start_s = round(time.time() - started, 3)
        with self._profile_lock:
            self._profile_arm = {"rounds": n, "dir": d, "owner": owner,
                                 "started": started, "start_s": start_s}
        return {"state": "armed", "rounds": n, "dir": d,
                "start_s": start_s, "replica": self.flight.replica}

    def profile_status(self) -> Dict[str, object]:
        """Live capture state: armed (the trace runs; the worker has not
        issued a round since) → capturing (rounds left) → writing (the
        writer thread is stopping the trace) → idle, with the last
        finished capture under `last` (state done / error / aborted, the
        artifact list) — what the smoke script and the benchmark poll."""
        with self._profile_lock:
            arm, active, last = (self._profile_arm, self._profile_active,
                                 self._profile_last)
            writing = self._profile_writing
            out: Dict[str, object] = {"replica": self.flight.replica}
            if active is not None:
                out.update({"state": "capturing",
                            "rounds_left": active["rounds_left"],
                            "dir": active["dir"]})
            elif arm is not None:
                out.update({"state": "armed", "rounds": arm["rounds"],
                            "dir": arm["dir"]})
            elif writing is not None:
                out.update({"state": "writing", "dir": writing["dir"]})
            else:
                out["state"] = "idle"
            if last is not None:
                out["last"] = dict(last)
        return out

    def _maybe_start_profile(self) -> None:
        """Worker thread, before it issues a round: flip the armed
        capture (its trace already runs) to capturing, so that the rounds
        counted are rounds issued inside the trace."""
        with self._profile_lock:
            arm = self._profile_arm
            if arm is None or self._profile_active is not None:
                return
            self._profile_arm = None
            self._profile_active = {
                **arm, "rounds_left": arm["rounds"],
                # Rounds already in flight were ISSUED before the flip:
                # their harvests must not count toward the capture, or a
                # lag-deep pipeline under live traffic brackets only N-1
                # (or zero) complete in-trace rounds.
                "skip": len(self._pending),
            }
        self.flight.event("profile_start", rounds=arm["rounds"],
                          dir=arm["dir"])

    def _profile_round_done(self) -> None:
        with self._profile_lock:
            st = self._profile_active
            if st is None:
                return
            if st["skip"] > 0:
                st["skip"] -= 1  # pre-trace round draining the pipeline
                return
            st["rounds_left"] -= 1
            if st["rounds_left"] > 0:
                return
            self._profile_active = None
            self._profile_writing = st
        # The marker sits right after the last traced round's record and
        # before the next one's: readers find the traced rounds by it.
        # Stopping the trace takes tens of seconds on a TPU and is not
        # the loop's to wait for.
        self.flight.event("profile_done", rounds=st["rounds"], dir=st["dir"])
        self._start_profile_writer(st)

    def _start_profile_writer(self, st: Dict[str, object], **outcome) -> None:
        self._profile_writer = threading.Thread(
            target=self._finish_profile, args=(st,), kwargs=outcome,
            name=f"lsot-profile-writer-{self.flight.replica}", daemon=True)
        self._profile_writer.start()

    def _finish_profile(self, st: Dict[str, object],
                        error: Optional[str] = None,
                        state: Optional[str] = None) -> None:
        """The writer thread: stop the trace, list what it wrote, publish
        the outcome as `profile_status()["last"]` and release the
        fleet-wide guard."""
        stopping = time.time()
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — a failed stop is still a finish
            error = error or str(e)[:200]
        arts = traceprof.find_profile_artifacts(str(st["dir"]))
        out: Dict[str, object] = {
            "state": "done" if arts else "error",
            "dir": st["dir"],
            "rounds": st["rounds"],
            "artifacts": arts,
            "artifact_bytes": sum(
                os.path.getsize(a) for a in arts if os.path.exists(a)
            ),
            "start_s": st["start_s"],
            "stop_s": round(time.time() - stopping, 3),
            "wall_s": round(time.time() - float(st["started"]), 3),
        }
        if error:
            out["error"] = error
            out["state"] = state or "error"
        with self._profile_lock:
            self._profile_last = out
            if self._profile_writing is st:
                self._profile_writing = None
        traceprof.release_capture(str(st["owner"]))

    def _abort_profile(self, reason: str) -> None:
        """Shutdown/crash hygiene, and the bound on a capture that sees
        no rounds: an armed or mid-flight capture must not leak the
        fleet-wide guard (or a running jax trace) past the loop that
        owned it. Its trace is stopped on the writer thread like any
        other — the closing loop does not wait the tens of seconds that
        takes on a TPU — and the guard goes when it has stopped."""
        with self._profile_lock:
            arm, self._profile_arm = self._profile_arm, None
            active, self._profile_active = self._profile_active, None
            st = arm or active
            if st is None:
                return
            self._profile_writing = st
        self._start_profile_writer(
            st, error=reason, state="aborted" if arm is not None else "error")

    def _expire_profile(self) -> None:
        """Worker thread, with nothing to serve: a capture armed on a
        server that issues no round (or whose traffic ended before its
        N-th) would trace without end. Once it is `_PROFILE_IDLE_LIMIT_S`
        old it is stopped and reported `aborted` (never flipped) or
        `error` (cut short)."""
        st = self._profile_arm or self._profile_active
        if st is not None and \
                time.time() - float(st["started"]) > _PROFILE_IDLE_LIMIT_S:
            self._abort_profile(
                f"no round to trace within {_PROFILE_IDLE_LIMIT_S:.0f} s")

    def _build_prefill(self, t_bucket: int, k: int):
        cfg, impl, mesh = self.cfg, self._impl, self.mesh
        quant, dtype = self.kv_quant, self._dtype
        nc = len(self._cache)
        spec = bool(self._spec_draft)
        ps, np_tab = self._page_size, self._pages_per_slot
        num_pages = self._page_alloc.num_pages

        # Speculative mode appends the on-device draft history as one more
        # donated arg: the chunk's tokens scatter into hist rows at the
        # same positions their K/V land at (drafting needs the prompt text,
        # and it is already on device for the forward anyway).
        # The device page tables come LAST (non-donated: tables are tiny
        # and in-flight rounds must keep reading the version they were
        # issued with).
        donate = tuple(range(1, 1 + nc)) + ((12 + nc,) if spec else ())

        @partial(jax.jit, donate_argnums=donate)
        def prefill(params, *args):
            """One prompt chunk for EACH of k slots in one forward — prefill
            is MXU-bound and weight streaming amortizes across the batch
            (admission bursts would otherwise pay a full weight pass per
            B=1 request). Row i's tokens occupy absolute positions
            [starts[i], starts[i]+lengths[i]); its last real logit samples
            with the request's own stream at fold index 0 (used only on
            final chunks).

            Padding rows carry slot index num_slots (out of bounds): the
            gather clamps harmlessly and the scatter DROPS their cache
            writes (jax scatter OOB semantics), so a partially filled
            k-batch is safe without duplicate-slot scatters.

            With kv_quant, the gathered rows dequantize to the compute
            dtype for the chunk forward, but only THIS chunk's window
            [start, start+t) requantizes and scatters back: a full-row
            scatter would round-trip earlier chunks' entries
            int8→bf16→int8 once per subsequent chunk, and bf16 rounding
            of q8·s can flip int8 LSBs each pass — drift would accumulate
            over a long multi-chunk prompt. Windowed, every entry is
            quantized exactly once (scales are per-position, so the
            window owns its scales too).
            """
            cache = args[:nc]
            (tokens, lengths, slots, starts, temps, topps, topks,
             seeds, cinits, cbudgets) = args[nc:nc + 10]
            g_need = args[nc + 10]
            hist = args[nc + 11] if spec else None
            ptab = args[-1]
            # Per-row page tables: OOB padding slots get an all-sentinel
            # row (mode="fill"), so BOTH their gather garbage is causally
            # masked and their scatter-back below drops — a clamped
            # gather would alias a real slot's pages and the scatter
            # would corrupt them.
            tab = jnp.take(
                ptab, slots, axis=0, mode="fill", fill_value=num_pages
            )  # [k, NP]
            safe = jnp.clip(tab, 0, num_pages - 1)

            def rowview(pool):
                # [L, P, K, ps(, H)] -> per-row view [L, k, K, NP*ps(, H)]
                # in forward's {"k", "v"} form for the chunk forward (the
                # scale arrays of an int8 pool drop the H axis). A
                # lane-packed pool [L, P, K/f, ps, f*H] gives packed row
                # views [L, k, K/f, NP*ps, f*H]: forward reads f off
                # them, writes the chunk packed, and the window scatter
                # below moves rows as they lie.
                g = pool[:, safe]  # [L, k, NP, K, ps(, H)]
                perm = ((0, 1, 3, 2, 4, 5) if pool.ndim == 5
                        else (0, 1, 3, 2, 4))
                shape = (pool.shape[0], safe.shape[0], pool.shape[2],
                         np_tab * ps) + (
                    (pool.shape[4],) if pool.ndim == 5 else ())
                return g.transpose(perm).reshape(shape)

            if quant:
                # int8 pool: dequantize the gathered rows for the chunk
                # forward (q8 × per-position scale) — the scatter-back
                # below requantizes ONLY this chunk's window, so every
                # entry quantizes exactly once.
                row_cache = {
                    "k": (rowview(cache[0]).astype(dtype)
                          * rowview(cache[1])[..., None].astype(dtype)),
                    "v": (rowview(cache[2]).astype(dtype)
                          * rowview(cache[3])[..., None].astype(dtype)),
                }
            else:
                row_cache = {"k": rowview(cache[0]),
                             "v": rowview(cache[1])}
            positions = (
                starts[:, None] + jnp.arange(t_bucket, dtype=jnp.int32)[None, :]
            )
            logits, new = forward(
                cfg, params, tokens, positions, row_cache,
                logit_indices=lengths - 1, attn_impl=impl, mesh=mesh,
            )
            # Scatter ONLY this chunk's window through the page tables,
            # by (page, offset): other pages of the row may be SHARED
            # prefix pages that must never be written (the host's
            # ensure-writable sweep guarantees the window's own pages are
            # exclusive). Advanced indices at non-adjacent dims broadcast
            # to the FRONT: windows come out [k, t, L, K(, H)] — the
            # layout the scatter expects.
            pos_idx = positions  # [k, t] = starts[:, None] + arange(t)
            row_ar = jnp.arange(pos_idx.shape[0], dtype=jnp.int32)
            wk = new["k"][:, row_ar[:, None], :, pos_idx]  # [k,t,L,K,H]
            wv = new["v"][:, row_ar[:, None], :, pos_idx]
            page_idx = pos_idx // ps
            pages = jnp.take_along_axis(
                tab, jnp.clip(page_idx, 0, np_tab - 1), axis=1
            )  # [k, t]; sentinel rows/entries drop their writes
            # Positions past the virtual row (a resumed prompt's final
            # chunk bucket can overhang it) must DROP, not clip: the
            # clipped lookup would alias the row's LAST mapped page and
            # overwrite real KV at matching offsets.
            pages = jnp.where(page_idx < np_tab, pages,
                              jnp.int32(num_pages))
            offs = pos_idx % ps
            if quant:
                # int8 pool: requantize the chunk's window (values +
                # per-position scales) and scatter both through the table
                # — windowed, so earlier chunks' entries never round-trip
                # int8→bf16→int8.
                from ..ops.quant import quantize_cache

                wins = _quant_window_tuple(quantize_cache(wk, wv))
                cache = tuple(
                    c.at[:, pages, :, offs].set(w)
                    for c, w in zip(cache, wins)
                )
            else:
                cache = (
                    cache[0].at[:, pages, :, offs].set(wk),
                    cache[1].at[:, pages, :, offs].set(wv),
                )
            keys = jax.vmap(
                lambda s: jax.random.fold_in(jax.random.key(s), 0)
            )(seeds)
            # Constrained rows sample their FIRST token under the grammar
            # start-state's budget-aware mask, computed ON DEVICE from the
            # installed need table and per-row (init state, budget) scalars
            # — the host ships 2*k ints per round, not a [k, vocab] bool
            # array. Unconstrained/padding rows carry state 0 (need 1):
            # all-allowed.
            first_logits = apply_token_mask(
                logits[:, 0], g_need[cinits] <= cbudgets[:, None]
            )
            toks = sample_runtime(first_logits, temps, topps, topks, keys)
            if spec:
                # OOB padding slots drop their history writes too.
                hist = hist.at[slots[:, None], positions].set(tokens)
                return (*cache, hist, toks)
            return (*cache, toks)

        return prefill

    def _build_decode(self):
        cfg, impl, chunk = self.cfg, self._decode_impl, self.decode_chunk
        mesh, split_weights = self.mesh, self._split_decode_weights
        pad_id = cfg.pad_id
        nc = len(self._cache)

        @partial(jax.jit,
                 donate_argnums=tuple(range(1, 3 + nc))
                 + (8 + nc, 9 + nc, 10 + nc))
        def decode(params, *args):
            cache = args[:nc]
            (cur, pos, active, temps, topps, topks, seeds,
             counts, cstates, crem, g_next, g_need) = args[nc:nc + 12]
            ptab = args[nc + 12]
            # Per-layer slices outside the chunk scan: decode-matmul layout
            # conversions run once per round, not per token (split_blocks)
            # — where the device has room for the copies it costs.
            if split_weights:
                params = split_blocks(params)

            def step(carry, i):
                cache, cur, pos, cstates, crem = carry
                logits, new_cache = forward(
                    cfg, params, cur[:, None], pos[:, None],
                    _paged_cache_dict(cache, ptab), attn_impl=impl,
                    mesh=mesh,
                    # Parked slots (decoding garbage at the park position)
                    # stream ZERO KV blocks; live slots stream only up to
                    # their own position — without this every decode step
                    # pays S_max bandwidth per slot.
                    kv_lens=jnp.where(active, pos + 1, 0),
                )
                # Grammar masking: ONE table gather + compare per step, no
                # host involvement and no per-token vocab iteration. A
                # token is allowed iff the tokens it commits to (itself +
                # shortest completion + stop id, the precomputed `need`
                # table) fit the slot's remaining budget — so constrained
                # completions always parse, never truncate. cstate 0 is
                # the all-allowed sentinel row (need 1), so mixed
                # constrained/unconstrained batches share this one
                # program.
                step_logits = apply_token_mask(
                    logits[:, 0], g_need[cstates] <= crem[:, None]
                )
                # Slot s's i-th token of this chunk is sample number
                # counts[s]+i of its request's stream — reproducible across
                # any batch composition.
                keys = jax.vmap(
                    lambda s, c: jax.random.fold_in(jax.random.key(s), c)
                )(seeds, counts + i)
                nxt = sample_runtime(step_logits, temps, topps, topks, keys)
                nxt = jnp.where(active, nxt, pad_id)
                cstates = jnp.where(active, g_next[cstates, nxt], cstates)
                crem = jnp.where(active, crem - 1, crem)
                pos = jnp.where(active, pos + 1, pos)
                return (_paged_cache_tuple(new_cache), nxt, pos, cstates,
                        crem), nxt

            (cache, cur, pos, cstates, crem), toks = lax.scan(
                step, (cache, cur, pos, cstates, crem), jnp.arange(chunk)
            )
            # RNG stream bookkeeping advances on device too: every active
            # slot consumed `chunk` samples.
            counts = jnp.where(active, counts + chunk, counts)
            # toks: [slots, chunk]
            return (*cache, cur, pos, counts, cstates, crem, toks.T)

        return decode

    def _build_spec_ready(self):
        """Jitted history arm for a freshly prefilled slot: the first
        sampled token lands at position plen and the valid length becomes
        plen + 1 (the prompt tokens themselves were scattered into the
        history by the prefill fn, chunk by chunk)."""

        @partial(jax.jit, donate_argnums=(0, 1))
        def spec_ready(hist, hlen, slot, tok, plen):
            return hist.at[slot, plen].set(tok[0]), hlen.at[slot].set(plen + 1)

        @partial(jax.jit, donate_argnums=(0, 1))
        def spec_resume(hist, hlen, slot, row, plen):
            # Preemption resume: rewrite the slot's WHOLE history row
            # (prompt + committed generated tokens, pad beyond) and set
            # hlen to the committed length — the ngram draft source is
            # then byte-identical to the unpreempted control's, which the
            # sampled-speculative determinism contract needs. Serves both
            # recompute (prefill re-scattered the same tokens; this
            # overwrite is a content no-op that also scrubs any stale
            # previous-occupant tail) and spill-restore (no prefill ran,
            # so this IS the history rebuild).
            return hist.at[slot].set(row), hlen.at[slot].set(plen)

        return spec_ready, spec_resume

    def _build_spec_decode(self):
        """One speculative round for the whole slot batch: draft D tokens
        per slot by prompt lookup over the on-device history, verify with a
        single T=D+1 forward, emit the accepted chain. Greedy slots verify
        by exact argmax (token-identical to vanilla greedy decode);
        temperature>0 slots verify by REJECTION SAMPLING
        (engine/speculative.rejection_sample_chain): draft token i is
        accepted iff a uniform draw lands under its mass in the target
        distribution (grammar-masked, temperature/top-k/top-p-filtered —
        softmax of ops.sampling.filtered_runtime_logits, the same
        distribution a vanilla sample_runtime step draws from), and the
        round's final token comes from the normalized residual (first
        rejection) or the target itself (all accepted) — so sampled slots
        emit 1..D+1 tokens per round, distribution-identical to vanilla
        sampling. Both classes ride this ONE compiled program: greedy vs
        sampled is a per-row `temps <= 0` select, and an all-greedy round
        skips the window-wide sort/softmax via lax.cond (mirroring
        sample_runtime's fast path). Per-slot state — history, length,
        position, RNG counts, grammar FSM state and budget — advances on
        device; the host harvests (emitted [slots, D+1], n_emit [slots]) a
        lag late, exactly like vanilla rounds.

        Sampled determinism: slot s's round keys derive as
        fold_in(key(seed), counts) with counts advancing by one per
        harvested sampled round, so a (seed, request) pair reproduces the
        same tokens whatever other traffic shares the batch — the
        contract crash-replay token suppression (serve/supervisor.py)
        depends on.

        Grammar constraining composes per position: each slot's draft
        chain advances its FSM (constrain.fsm_advance_chain — drafts stop
        counting at the first grammar-rejected token), every verify
        position's logits are masked with its OWN per-position state's
        budget-aware row before argmax, acceptance is capped at the
        grammar-valid prefix, and the committed `cstate` is the state
        after the accepted prefix — rejected drafts never advance it (the
        FSM twin of the rejected-K/V rewind the cache-visibility invariant
        already covers). Unconstrained slots sit at the sentinel state 0
        (need 1 = all-allowed), so mixed constrained/unconstrained batches
        ride this ONE compiled program, exactly like vanilla decode.

        Attention runs the einsum impl: the verify window needs the
        unrolled small-T path (which is also the only int8-KV path), and
        the pallas decode kernel is a T=1 specialization. Parked slots
        verify garbage at the parking position — their cache writes clamp
        into their own row's tail, which the visibility invariant covers —
        and emit nothing (n_emit=0); their history write is routed past
        max_seq so a slot mid-chunked-prefill cannot have its freshly
        scattered prompt history punched by pad writes at a stale hlen."""
        from ..constrain.masks import fsm_advance_chain
        from ..engine.speculative import (
            emit_chain,
            ngram_draft,
            rejection_sample_chain,
        )

        cfg, mesh = self.cfg, self.mesh
        D, ngram = self._spec_draft, self._spec_ngram
        d1 = D + 1
        pad_id = cfg.pad_id
        nc = len(self._cache)

        @partial(jax.jit,
                 donate_argnums=tuple(range(1, nc + 5))
                 + (nc + 10, nc + 11, nc + 12))
        def spec_decode(params, *args):
            cache = args[:nc]
            (hist, hlen, cur, pos, active, temps, topps, topks, seeds,
             counts, cstates, crem, g_next, g_need) = args[nc:nc + 14]
            ptab = args[nc + 14]
            params = split_blocks(params)
            drafts = ngram_draft(hist, hlen, D, ngram)           # [S, D]
            verify = jnp.concatenate([cur[:, None], drafts], 1)  # [S, D+1]
            jd = jnp.arange(d1, dtype=jnp.int32)[None, :]
            vpos = pos[:, None] + jd
            logits, new_cache = forward(
                cfg, params, verify, vpos,
                _paged_cache_dict(cache, ptab),
                attn_impl="xla", mesh=mesh,
            )
            # Per-position grammar masking: pstates[:, j] is the slot's
            # FSM state after accepting drafts[:, :j], vlen the longest
            # grammar-valid draft prefix under the per-position budget
            # (crem - j — the exact mask a vanilla round would apply at
            # that step). Masked argmax at position j therefore IS the
            # vanilla constrained greedy token there, which is what makes
            # constrained+speculative output token-identical to
            # constrained vanilla decode.
            pstates, vlen = fsm_advance_chain(
                g_next, g_need, cstates, drafts, crem
            )                                                    # [S,D+1],[S]
            logits = apply_token_mask(
                logits, g_need[pstates] <= (crem[:, None] - jd)[:, :, None]
            )
            preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, D+1]
            # preds[j] is the true greedy token after verify[j] iff every
            # draft before j was accepted; accept the longest such chain —
            # capped at the grammar-valid prefix (a rejected draft must
            # not be accepted even where the masked model would agree).
            eq = ((drafts == preds[:, :D])
                  & (jd[:, :D] < vlen[:, None])).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)         # [S]
            greedy = temps <= 0.0
            keys = jax.vmap(
                lambda s, c: jax.random.fold_in(jax.random.key(s), c)
            )(seeds, counts)
            ns = preds.shape[0]

            def rejection_path(_):
                # Filtered target logits at EVERY verify position: the
                # grammar mask was applied above (exactly where a vanilla
                # round applies it), so softmax(filt[:, j]) is the
                # distribution vanilla sample_runtime would draw token j
                # from, grammar-rejected drafts carry zero target mass
                # (auto-reject, capping acceptance at the valid prefix),
                # and the rejection residual is grammar-renormalized for
                # free. One [S, D+1, V] sort per round.
                filt = filtered_runtime_logits(
                    logits, temps[:, None], topps[:, None], topks[:, None],
                )
                return rejection_sample_chain(filt, drafts, keys)

            # All-greedy rounds (the NL→SQL common case) skip the
            # window-wide sort/softmax/draws entirely — the same fast
            # path sample_runtime keys on, lifted to the whole window.
            acc_s, extra = lax.cond(
                jnp.all(greedy),
                lambda _: (jnp.zeros((ns,), jnp.int32),
                           jnp.zeros((ns,), jnp.int32)),
                rejection_path, None,
            )
            emitted_s = emit_chain(drafts, acc_s, extra, pad_id)
            n_emit = jnp.where(
                active, jnp.where(greedy, acc + 1, acc_s + 1), 0
            )
            emitted = jnp.where(greedy[:, None], preds, emitted_s)
            emitted = jnp.where(jd < n_emit[:, None], emitted, pad_id)
            # Inactive rows write past max_seq (clamped into the history's
            # spare tail), never at their stale hlen.
            write_at = jnp.where(
                active, hlen, jnp.int32(hist.shape[1])
            )
            hist = jax.vmap(
                lambda h, e, s: lax.dynamic_update_slice(h, e, (s,))
            )(hist, emitted, write_at)
            cur = jax.vmap(
                lambda e, n, c: jnp.where(n > 0, e[jnp.maximum(n - 1, 0)], c)
            )(emitted, n_emit, cur)
            # Commit the FSM to the state after the accepted prefix: the
            # last emitted token advances from ITS per-position state
            # (for accepted drafts emitted[j] == drafts[j] in BOTH
            # classes, so this lands exactly on the chain state; a
            # sampled row's residual/bonus token advances from the state
            # after its accepted prefix). n_emit == 0 rows freeze —
            # rejected drafts never move the committed state (rewind by
            # construction).
            idx = jnp.maximum(n_emit - 1, 0)
            last_s = jnp.take_along_axis(pstates, idx[:, None], 1)[:, 0]
            last_t = jnp.take_along_axis(emitted, idx[:, None], 1)[:, 0]
            cstates = jnp.where(n_emit > 0, g_next[last_s, last_t], cstates)
            crem = crem - n_emit
            pos = pos + n_emit
            hlen = hlen + n_emit
            # Sampled slots consumed one stream index per ROUND (the
            # round key fans out into the window's accept/residual draws
            # inside rejection_sample_chain); greedy argmax consumed
            # none. Round count per request is deterministic — drafting
            # reads only the row's own history — so (seed, request)
            # reproduces the same tokens under any batch mix.
            counts = counts + jnp.where(active & ~greedy, 1, 0)
            return (*_paged_cache_tuple(new_cache), hist, hlen, cur, pos,
                    counts, cstates, crem, emitted, n_emit)

        return spec_decode

    def _build_mixed(self, t_bucket: int):
        """One compiled MIXED round (LSOT_RAGGED=1, ISSUE 19): this
        iteration's prompt chunks and the decode round ride a single
        [S, t_bucket] ragged launch instead of alternating programs.
        Prefill rows (is_pref) carry their whole chunk and scatter it
        through their page tables; decode rows carry their current token
        in column 0 with dead padding beyond — the per-row q_lens vector
        routes dead columns' K/V writes to the sentinel page and (pallas)
        zeroes their attention output, so neither class perturbs the
        other. Step 0 is ONE ragged forward; chunk steps 1..chunk-1 reuse
        _build_decode's T=1 step body verbatim under lax.scan (prefill
        rows are inactive there: not yet armed, `active` gates every
        advance). Sampling stays per-row deterministic: prefill rows
        sample their first token at fold 0 under the grammar start
        state's budget mask (== _build_prefill), decode rows sample chunk
        token i at fold counts+i under their committed state (==
        _build_decode) — so each request's token stream is identical to
        the alternating control's; only round BOUNDARIES shift (a slot
        finishing prefill here decodes starting next round)."""
        cfg, mesh = self.cfg, self.mesh
        impl, dimpl = self._impl, self._decode_impl
        chunk = self.decode_chunk
        pad_id = cfg.pad_id
        nc = len(self._cache)
        t = t_bucket
        ps, np_tab = self._page_size, self._pages_per_slot
        s_virt = np_tab * ps  # dead-col sentinel position (write drops)

        @partial(jax.jit,
                 donate_argnums=tuple(range(1, 3 + nc))
                 + (8 + nc, 9 + nc, 10 + nc))
        def mixed(params, *args):
            cache = args[:nc]
            (cur, pos, active, temps, topps, topks, seeds,
             counts, cstates, crem) = args[nc:nc + 10]
            (p_tokens, p_lengths, p_starts, is_pref, p_temps, p_topps,
             p_topks, p_seeds, p_cinits, p_cbudgets) = args[nc + 10:nc + 20]
            g_next, g_need = args[nc + 20:nc + 22]
            ptab = args[nc + 22]
            params = split_blocks(params)
            col = jnp.arange(t, dtype=jnp.int32)[None, :]
            tokens0 = jnp.where(
                is_pref[:, None], p_tokens,
                jnp.where(col == 0, cur[:, None], pad_id),
            )
            # Dead decode columns sit at the virtual-row position: their
            # page lookup lands on the sentinel (write drops) and the
            # causal mask over kv_lens keeps their garbage logits finite.
            pos0 = jnp.where(
                is_pref[:, None], p_starts[:, None] + col,
                jnp.where(col == 0, pos[:, None], jnp.int32(s_virt)),
            )
            q_lens_v = jnp.where(is_pref, t, 1).astype(jnp.int32)
            kv0 = jnp.where(
                is_pref, jnp.clip(p_starts + t, 0, s_virt),
                jnp.where(active, pos + 1, 0),
            ).astype(jnp.int32)
            logit_idx = jnp.where(is_pref, p_lengths - 1, 0)
            logits, new_cache = forward(
                cfg, params, tokens0, pos0,
                _paged_cache_dict(cache, ptab),
                logit_indices=logit_idx, attn_impl=impl, mesh=mesh,
                kv_lens=kv0, q_lens=q_lens_v,
            )
            cache = _paged_cache_tuple(new_cache)
            # Combined first sample, per-row knobs: prefill rows fold 0
            # of THEIR seed under (init state, full budget); decode rows
            # fold counts under (committed state, remaining budget).
            m_states = jnp.where(is_pref, p_cinits, cstates)
            m_rem = jnp.where(is_pref, p_cbudgets, crem)
            m_seeds = jnp.where(is_pref, p_seeds, seeds)
            m_counts = jnp.where(is_pref, 0, counts)
            m_temps = jnp.where(is_pref, p_temps, temps)
            m_topps = jnp.where(is_pref, p_topps, topps)
            m_topks = jnp.where(is_pref, p_topks, topks)
            keys = jax.vmap(
                lambda s, c: jax.random.fold_in(jax.random.key(s), c)
            )(m_seeds, m_counts)
            logits0 = apply_token_mask(
                logits[:, 0], g_need[m_states] <= m_rem[:, None]
            )
            toks0 = sample_runtime(logits0, m_temps, m_topps, m_topks, keys)
            firsts = toks0
            # Decode rows commit chunk token 0 (prefill rows arm on the
            # host AFTER this launch, so `active` excludes them here).
            d_nxt = jnp.where(active, toks0, pad_id)
            cstates = jnp.where(active, g_next[cstates, d_nxt], cstates)
            crem = jnp.where(active, crem - 1, crem)
            pos = jnp.where(active, pos + 1, pos)
            cur = d_nxt

            def step(carry, i):
                # _build_decode's step body, verbatim (T=1 per row).
                cache, cur, pos, cstates, crem = carry
                logits, new_cache = forward(
                    cfg, params, cur[:, None], pos[:, None],
                    _paged_cache_dict(cache, ptab), attn_impl=dimpl,
                    mesh=mesh, kv_lens=jnp.where(active, pos + 1, 0),
                )
                step_logits = apply_token_mask(
                    logits[:, 0], g_need[cstates] <= crem[:, None]
                )
                keys = jax.vmap(
                    lambda s, c: jax.random.fold_in(jax.random.key(s), c)
                )(seeds, counts + i)
                nxt = sample_runtime(step_logits, temps, topps, topks, keys)
                nxt = jnp.where(active, nxt, pad_id)
                cstates = jnp.where(active, g_next[cstates, nxt], cstates)
                crem = jnp.where(active, crem - 1, crem)
                pos = jnp.where(active, pos + 1, pos)
                return (_paged_cache_tuple(new_cache), nxt, pos, cstates,
                        crem), nxt

            # chunk == 1 leaves an empty scan: toks is just step 0's
            # column. Fold indices continue at counts+1 where step 0
            # (fold counts) left off — the control's i=1..chunk-1 steps.
            (cache, cur, pos, cstates, crem), toks_rest = lax.scan(
                step, (cache, cur, pos, cstates, crem),
                jnp.arange(1, chunk),
            )
            toks = jnp.concatenate([d_nxt[None], toks_rest], 0).T
            counts = jnp.where(active, counts + chunk, counts)
            return (*cache, cur, pos, counts, cstates, crem, toks, firsts)

        return mixed

    def _build_mixed_spec(self, t_bucket: int):
        """Speculative twin of _build_mixed: decode rows run their verify
        window (T = D+1) and prefill rows their chunk (T = t_bucket) in
        the SAME ragged launch — the window is padded to
        max(t_bucket, D+1) columns and q_lens tells the kernel which
        prefix of each row is real. The verify math (draft, per-position
        grammar masking, greedy/rejection acceptance, history commit) is
        _build_spec_decode's, applied to the window's first D+1 columns;
        prefill rows additionally scatter their chunk into the draft
        history (== _build_prefill's hist write) and sample their first
        token from the chunk's last real logit at fold 0."""
        from ..constrain.masks import fsm_advance_chain
        from ..engine.speculative import (
            emit_chain,
            ngram_draft,
            rejection_sample_chain,
        )

        cfg, mesh, impl = self.cfg, self.mesh, self._impl
        D, ngram = self._spec_draft, self._spec_ngram
        d1 = D + 1
        t = t_bucket
        T = max(t, d1)
        pad_id = cfg.pad_id
        nc = len(self._cache)
        ps, np_tab = self._page_size, self._pages_per_slot
        s_virt = np_tab * ps

        @partial(jax.jit,
                 donate_argnums=tuple(range(1, nc + 5))
                 + (nc + 10, nc + 11, nc + 12))
        def mixed_spec(params, *args):
            cache = args[:nc]
            (hist, hlen, cur, pos, active, temps, topps, topks, seeds,
             counts, cstates, crem) = args[nc:nc + 12]
            (p_tokens, p_lengths, p_starts, is_pref, p_temps, p_topps,
             p_topks, p_seeds, p_cinits, p_cbudgets) = args[nc + 12:nc + 22]
            g_next, g_need = args[nc + 22:nc + 24]
            ptab = args[nc + 24]
            params = split_blocks(params)
            drafts = ngram_draft(hist, hlen, D, ngram)           # [S, D]
            verify = jnp.concatenate([cur[:, None], drafts], 1)  # [S, D+1]
            jd = jnp.arange(d1, dtype=jnp.int32)[None, :]
            vpos = pos[:, None] + jd
            col = jnp.arange(T, dtype=jnp.int32)[None, :]
            if T > d1:
                verify = jnp.pad(verify, ((0, 0), (0, T - d1)),
                                 constant_values=pad_id)
                vpos = jnp.pad(vpos, ((0, 0), (0, T - d1)),
                               constant_values=s_virt)
            pt = p_tokens
            if T > t:
                pt = jnp.pad(pt, ((0, 0), (0, T - t)),
                             constant_values=pad_id)
            p_pos = jnp.where(col < t, p_starts[:, None] + col,
                              jnp.int32(s_virt))
            tokens0 = jnp.where(is_pref[:, None], pt, verify)
            pos0 = jnp.where(is_pref[:, None], p_pos, vpos)
            q_lens_v = jnp.where(is_pref, t, d1).astype(jnp.int32)
            kv0 = jnp.where(
                is_pref, jnp.clip(p_starts + t, 0, s_virt),
                jnp.where(active, pos + d1, 0),
            ).astype(jnp.int32)
            logits, new_cache = forward(
                cfg, params, tokens0, pos0,
                _paged_cache_dict(cache, ptab),
                attn_impl=impl, mesh=mesh, kv_lens=kv0, q_lens=q_lens_v,
            )
            # ----- verify math: _build_spec_decode, on the first D+1
            # columns (mid-prefill slots sit at temps=0/state park, same
            # values the alternating control's spec round sees).
            vlogits = logits[:, :d1]
            pstates, vlen = fsm_advance_chain(
                g_next, g_need, cstates, drafts, crem
            )                                                    # [S,D+1],[S]
            vlogits = apply_token_mask(
                vlogits, g_need[pstates] <= (crem[:, None] - jd)[:, :, None]
            )
            preds = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)
            eq = ((drafts == preds[:, :D])
                  & (jd[:, :D] < vlen[:, None])).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)         # [S]
            greedy = temps <= 0.0
            keys = jax.vmap(
                lambda s, c: jax.random.fold_in(jax.random.key(s), c)
            )(seeds, counts)
            ns = preds.shape[0]

            def rejection_path(_):
                filt = filtered_runtime_logits(
                    vlogits, temps[:, None], topps[:, None], topks[:, None],
                )
                return rejection_sample_chain(filt, drafts, keys)

            acc_s, extra = lax.cond(
                jnp.all(greedy),
                lambda _: (jnp.zeros((ns,), jnp.int32),
                           jnp.zeros((ns,), jnp.int32)),
                rejection_path, None,
            )
            emitted_s = emit_chain(drafts, acc_s, extra, pad_id)
            n_emit = jnp.where(
                active, jnp.where(greedy, acc + 1, acc_s + 1), 0
            )
            emitted = jnp.where(greedy[:, None], preds, emitted_s)
            emitted = jnp.where(jd < n_emit[:, None], emitted, pad_id)
            write_at = jnp.where(
                active, hlen, jnp.int32(hist.shape[1])
            )
            hist = jax.vmap(
                lambda h, e, s: lax.dynamic_update_slice(h, e, (s,))
            )(hist, emitted, write_at)
            cur = jax.vmap(
                lambda e, n, c: jnp.where(n > 0, e[jnp.maximum(n - 1, 0)], c)
            )(emitted, n_emit, cur)
            idx = jnp.maximum(n_emit - 1, 0)
            last_s = jnp.take_along_axis(pstates, idx[:, None], 1)[:, 0]
            last_t = jnp.take_along_axis(emitted, idx[:, None], 1)[:, 0]
            cstates = jnp.where(n_emit > 0, g_next[last_s, last_t], cstates)
            crem = crem - n_emit
            pos = pos + n_emit
            hlen = hlen + n_emit
            counts = counts + jnp.where(active & ~greedy, 1, 0)
            # ----- prefill rows: chunk into the draft history (row S is
            # the OOB drop for everyone else — disjoint from the emitted
            # write above, whose prefill rows landed in the spare tail)
            # and the first token from the chunk's last real logit.
            rows = jnp.where(
                is_pref, jnp.arange(is_pref.shape[0], dtype=jnp.int32),
                jnp.int32(is_pref.shape[0]),
            )
            hist = hist.at[
                rows[:, None],
                p_starts[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :],
            ].set(p_tokens)
            fl = jnp.take_along_axis(
                logits, jnp.clip(p_lengths - 1, 0, T - 1)[:, None, None],
                axis=1,
            )[:, 0]
            fl = apply_token_mask(fl, g_need[p_cinits] <= p_cbudgets[:, None])
            p_keys = jax.vmap(
                lambda s: jax.random.fold_in(jax.random.key(s), 0)
            )(p_seeds)
            firsts = sample_runtime(fl, p_temps, p_topps, p_topks, p_keys)
            out_cache = _paged_cache_tuple(new_cache)
            return (*out_cache, hist, hlen, cur, pos, counts,
                    cstates, crem, emitted, n_emit, firsts)

        return mixed_spec

    # ------------------------------------------------------------- lifecycle

    def warmup(self, prompt_len: Optional[int] = None) -> None:
        """Pre-compile (and execute once) every program the loop can
        issue: each (bucket, k-bucket) prefill variant — or, given
        `prompt_len`, only the bucket a prompt of that length takes —
        the DECODE program, the mixed-round programs of a ragged
        scheduler, and the per-slot state scatters. Deterministic, unlike
        warming through generate() (concurrent admission groups race, so
        some k-buckets can stay uncompiled and stall a later request with
        an XLA compile). Every prefill row targets the out-of-bounds
        padding slot: the scatter drops all writes, so no slot or cache
        state changes. Decode runs one all-inactive round (every write
        lands at the park position, which no query can see); the state
        scatters are driven at the out-of-bounds slot (jax drops OOB
        scatter writes, so they are true no-ops). Call before start() (or
        while the loop is idle). A second full warm-up is a no-op.

        Liveness note: an unwarmed loop blocks its own thread on each
        cold XLA compile, which the watchdog (serve/watchdog.py) cannot
        tell from a genuine wedge once the first round is harvested — a
        32-layer program compiles for longer than the stall floor, so the
        second bucket's compile would read as a stall and the restart
        would compile again. Every deployment path warms through this
        method before it serves; so does the supervisor's restart driver,
        while the monitor is quiet."""
        if prompt_len is None:
            if self._warmed:
                return
            buckets = self._buckets
        else:
            buckets = [next((b for b in self._buckets if b >= prompt_len),
                            self.prompt_bucket)]
        for t in buckets:
            for kb in self._kbuckets:
                self._warm_prefill(t, kb)
            if self._ragged:
                self._warm_mixed(t)
        self._warm_state_ops()
        self._warm_decode()
        self._warmed = prompt_len is None

    def _prefill_warm_args(self, t: int, kb: int) -> list:
        """One (bucket, k-bucket) prefill call's arguments after params
        and cache, every row at the out-of-bounds padding slot."""
        pad = self.cfg.pad_id
        args = [
            jnp.full((kb, t), pad, jnp.int32),
            jnp.ones(kb, jnp.int32),
            jnp.full((kb,), self.num_slots, jnp.int32),  # all OOB
            jnp.zeros(kb, jnp.int32),
            jnp.zeros(kb, jnp.float32),
            jnp.ones(kb, jnp.float32),
            jnp.zeros(kb, jnp.int32),
            jnp.zeros(kb, jnp.uint32),
            jnp.zeros(kb, jnp.int32),   # cinits: sentinel state
            jnp.ones(kb, jnp.int32),    # cbudgets: need<=1 all-True
            self._ctables["need"],
        ]
        if self._spec_draft:
            args.append(self._hist)
        args.append(self._ptab)
        return args

    def _warm_prefill(self, t: int, kb: int) -> None:
        if (t, kb) not in self._prefill_fns:
            self._prefill_fns[(t, kb)] = self._build_prefill(t, kb)
        out = self._prefill_fns[(t, kb)](
            self.params, *self._cache, *self._prefill_warm_args(t, kb))
        nc = len(self._cache)
        self._cache = out[:nc]
        if self._spec_draft:
            self._hist = out[nc]
        # _prefill_step hands each row's first token on as a static
        # slice, one tiny program per row index.
        for i in range(kb):
            out[-1][i : i + 1]

    def _warm_state_ops(self) -> None:
        """Compile the per-slot state scatters at the OOB padding slot
        (index num_slots): jax drops out-of-bounds scatter writes, so
        these executions change nothing while caching the compiled
        programs the first admission would otherwise block the loop on."""
        oob = jnp.int32(self.num_slots)
        self._cur, self._pos, self._cstates, self._crem = self._park_fn(
            self._cur, self._pos, self._cstates, self._crem, oob
        )
        self._temps, self._topps, self._topks, self._cstates = \
            self._retire_fn(self._temps, self._topps, self._topks,
                            self._cstates, oob)
        (self._cur, self._pos, self._temps, self._topps, self._topks,
         self._seeds, self._counts, self._cstates,
         self._crem) = self._ready_fn(
            self._cur, self._pos, self._temps, self._topps, self._topks,
            self._seeds, self._counts, self._cstates, self._crem,
            self._ctables["next"], oob,
            jnp.full((1,), self.cfg.pad_id, jnp.int32), jnp.int32(self._park),
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
            jnp.uint32(0), jnp.int32(0), jnp.int32(1),
        )
        (self._cur, self._pos, self._temps, self._topps, self._topks,
         self._seeds, self._counts, self._cstates,
         self._crem) = self._resume_fn(
            self._cur, self._pos, self._temps, self._topps, self._topks,
            self._seeds, self._counts, self._cstates, self._crem,
            oob, jnp.int32(self.cfg.pad_id), jnp.int32(self._park),
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
            jnp.uint32(0), jnp.int32(1), jnp.int32(0), jnp.int32(1),
        )
        if self._spec_draft:
            self._hist, self._hlen = self._spec_ready_fn(
                self._hist, self._hlen, oob,
                jnp.full((1,), self.cfg.pad_id, jnp.int32), jnp.int32(0),
            )
            self._hist, self._hlen = self._spec_resume_fn(
                self._hist, self._hlen, oob,
                jnp.full((self._hist.shape[1],), self.cfg.pad_id,
                         jnp.int32),
                jnp.int32(0),
            )
        # Table-row scatter at the OOB slot (dropped) and a page-0
        # self-copy (content no-op): compiles the page bookkeeping ops so
        # the first admission doesn't block the loop on them.
        self._ptab = self._ptab_row_fn(
            self._ptab, oob,
            jnp.full((self._pages_per_slot,),
                     self._page_alloc.num_pages, jnp.int32),
        )
        self._cache = self._copy_page_fn(
            *self._cache, jnp.int32(0), jnp.int32(0)
        )

    def _decode_warm_args(self) -> tuple:
        """A decode call's arguments after params and cache, every slot
        inactive."""
        t = self._ctables
        hist = (self._hist, self._hlen) if self._spec_draft else ()
        return (
            *hist, self._cur, self._pos,
            jnp.zeros(self.num_slots, jnp.bool_), self._temps, self._topps,
            self._topks, self._seeds, self._counts, self._cstates,
            self._crem, t["next"], t["need"], self._ptab,
        )

    def _warm_decode(self) -> None:
        """Compile (and execute once) the decode program with every slot
        inactive: parked-position garbage writes only — the same rounds
        free slots run between requests anyway, covered by the cache
        visibility invariant."""
        nc = len(self._cache)
        out = self._decode_fn(self.params, *self._cache,
                              *self._decode_warm_args())
        self._cache = out[:nc]
        if self._spec_draft:
            (self._hist, self._hlen, self._cur, self._pos, self._counts,
             self._cstates, self._crem, _, _) = out[nc:]
        else:
            (self._cur, self._pos, self._counts, self._cstates, self._crem,
             _) = out[nc:]

    def _warm_mixed(self, t: int) -> None:
        """Compile (and execute once) the ragged mixed-round program of
        bucket `t` with no prefill row and every slot inactive — the
        decode warm-up's parked writes, in the mixed program."""
        if t not in self._mixed_fns:
            self._mixed_fns[t] = (
                self._build_mixed_spec(t) if self._spec_draft
                else self._build_mixed(t)
            )
        S = self.num_slots
        dec = self._decode_warm_args()
        tail = 3  # g_next, g_need, ptab: the mixed program takes them last
        p_args = (
            jnp.full((S, t), self.cfg.pad_id, jnp.int32),
            jnp.ones(S, jnp.int32), jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.bool_), jnp.zeros(S, jnp.float32),
            jnp.ones(S, jnp.float32), jnp.zeros(S, jnp.int32),
            jnp.zeros(S, jnp.uint32), jnp.zeros(S, jnp.int32),
            jnp.ones(S, jnp.int32),
        )
        nc = len(self._cache)
        out = self._mixed_fns[t](self.params, *self._cache, *dec[:-tail],
                                 *p_args, *dec[-tail:])
        self._cache = out[:nc]
        if self._spec_draft:
            (self._hist, self._hlen, self._cur, self._pos, self._counts,
             self._cstates, self._crem, _, _, firsts) = out[nc:]
        else:
            (self._cur, self._pos, self._counts, self._cstates, self._crem,
             _, firsts) = out[nc:]
        for i in range(S):  # _issue_mixed's per-slot first-token slices
            firsts[i : i + 1]

    def _crash_error(self) -> SchedulerCrashed:
        """The typed "engine dead" error for this scheduler's crash (HTTP
        503 upstream, vs a per-request 500): carries the loop's original
        traceback so every rejected submit points at the real device
        failure, not just its own stack."""
        if isinstance(self._crash, SchedulerCrashed):
            return self._crash
        return SchedulerCrashed.from_exception(self._crash)

    def start(self) -> "ContinuousBatchingScheduler":
        if self._thread is None:
            if self._crash is not None:
                raise self._crash_error()
            # Re-sync every device table row from the host mirror: a
            # previous _close released abandoned slots' pages host-side
            # only, and a stale row would route the freed slots' parked
            # writes into pages a future occupant owns. No-op cost on
            # first start (rows are already the unmapped sentinel).
            for i in range(self.num_slots):
                self._sync_ptab_row(i)
            self._stop_evt.clear()
            with self._submit_lock:
                self._closed = False
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
        return self

    def shutdown(self, timeout: Optional[float] = None) -> None:
        """Stop the event loop. `timeout` bounds the join: a WEDGED loop
        (hung XLA dispatch — the case the watchdog escalates) would block
        an unbounded join forever, so the supervisor's teardown passes a
        bound and ABANDONS the daemon thread if it doesn't exit in time.
        An abandoned zombie exits at its next top-of-loop check once it
        unwedges; its futures are superseded by the supervisor's replay
        (bare-scheduler callers should keep the default blocking join —
        abandonment leaves inner futures unresolved)."""
        if self._thread is not None:
            self._stop_evt.set()
            self._queue.put(None)  # wake the loop
            self._thread.join(timeout)
            if self._thread.is_alive():
                with self._submit_lock:
                    self._closed = True
                _log.warning(
                    "scheduler loop did not join within %.2fs; abandoning "
                    "wedged worker thread (it exits when it unwedges)",
                    timeout,
                )
            self._thread = None
        writer = self._profile_writer
        if writer is not None and timeout is None:
            # A capture cut short by this shutdown: let its trace stop
            # before the process may exit under the profiler.
            writer.join(120.0)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()

    # ---------------------------------------------------------------- client

    def submit(
        self,
        ids: Sequence[int],
        max_new_tokens: int = 256,
        sampling: SamplingParams = SamplingParams(),
        # Honored: the request samples from its own fold_in(key(seed), i)
        # stream, so (ids, sampling, seed, max_new) reproduces the same
        # tokens regardless of concurrent traffic. (Note the stream indexing
        # differs from InferenceEngine's shared-batch keys, so scheduler and
        # engine agree token-for-token on greedy but not on sampled runs.)
        seed: int = 0,
        # Streaming consumer: called with each accepted token id in order
        # from the worker thread (see _Request.on_token).
        on_token: Optional[Callable[[int], None]] = None,
        # Grammar constraining (constrain.CompiledMask): the request's
        # tokens are masked to the compiled language; the slot's FSM state
        # rides the decode program on device. Requests with and without a
        # constraint share the batch; a request with a DIFFERENT grammar
        # than the installed one waits for constrained slots to drain, then
        # swaps the tables (one retrace per grammar, never per request).
        constraint: Optional[CompiledMask] = None,
        # Per-request latency budget in seconds (serve/resilience.Deadline):
        # the request fails with a typed DeadlineExceeded — fast at
        # admission if it expired while queued, or at the next harvest once
        # in flight. None = no deadline.
        deadline_s: Optional[float] = None,
        # Request-scoped tracing (utils/tracing.RequestTrace): when the
        # request was head-sampled, the worker thread records queue-wait /
        # prefill / per-round decode spans into this tree. None (the
        # unsampled fast path) costs nothing anywhere in the loop.
        trace=None,
        # Multi-model serving (ISSUE 16): the model the request wants.
        # "" accepts (single-model callers never name one); a non-empty
        # id must match THIS replica's checkpoint — a mismatch is the
        # caller's routing bug and fails typed instead of decoding the
        # prompt against the wrong weights.
        model_id: str = "",
        # Multi-tenant QoS (ISSUE 18): the tenant the request bills to
        # and its service class (interactive|batch|replay). "" = the
        # unlabeled single-tenant shape; with LSOT_QOS=0 both are
        # carried but never consulted.
        tenant: str = "",
        qos: str = "",
    ) -> "Future[List[int]]":
        if not ids:
            raise ValueError("empty prompt")
        if model_id and model_id != self.model_id:
            from .modelpool import UnknownModel

            raise UnknownModel(
                f"request names model {model_id!r} but this replica "
                f"serves {self.model_id or '<unset>'!r}"
            )
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        if constraint is not None:
            if max_new_tokens < constraint.min_new_tokens:
                raise ValueError(
                    f"max_new_tokens={max_new_tokens} cannot hold a "
                    f"complete constrained parse (grammar needs >= "
                    f"{constraint.min_new_tokens} tokens incl. the stop id)"
                )
        # Overshoot bound: the device can run (harvest_lag + 1) rounds past
        # a budget or stop token before the host notices (rounds are
        # harvested one lag late); those tokens are discarded but their
        # cache writes must stay inside the window.
        overshoot = self.overshoot
        need = bucket_len(len(ids), self.prompt_bucket) + max_new_tokens + overshoot
        if need > self.max_seq - 1:  # the last cache slot is the parking spot
            raise ValueError(
                f"prompt ({len(ids)} tokens, bucketed) + max_new_tokens "
                f"({max_new_tokens}) + overshoot ({overshoot}) "
                f"= {need} exceeds scheduler max_seq={self.max_seq}"
            )
        req = _Request(
            ids=list(ids), max_new=max_new_tokens,
            temperature=sampling.temperature, top_p=sampling.top_p,
            top_k=sampling.top_k, seed=seed,
            future=Future(), on_token=on_token, constraint=constraint,
            deadline=(Deadline.after(deadline_s)
                      if deadline_s is not None else None),
            trace=trace,
            model_id=model_id or self.model_id,
            tenant=str(tenant or ""), qos=str(qos or ""),
        )
        req.future._lsot_request = req  # cancel() handle
        try:
            # Chaos seam: mark THIS request's slot as a silently
            # no-progress lane (its harvest rows read empty) — the
            # per-slot stall retirement's injectable trigger. Checked on
            # the SUBMITTING thread so a test can scope the spec to
            # exactly the requests it wants wedged, deterministically.
            FAULTS.check("sched:slot_stall")
        except InjectedFault:
            req.stall_inject = True
        with self._submit_lock:
            if self._closed:
                if self._crash is not None:
                    raise self._crash_error()
                raise RuntimeError("scheduler has shut down")
            if self._thread is None:
                raise RuntimeError(
                    "scheduler not started — call start() or use it as a "
                    "context manager (a queued Future would never resolve)"
                )
            # Admission control: shed instead of queueing without bound.
            # qsize() counts requests not yet pulled into slots/prefill —
            # the true backlog a new request would wait behind.
            if self.max_queue_depth and \
                    self._queue.qsize() + len(self._ready) \
                    >= self.max_queue_depth:
                resilience.inc("shed")
                raise Overloaded(
                    f"scheduler queue at capacity "
                    f"({self.max_queue_depth} waiting requests)",
                    # Backpressure hint: current queue depth × the recent
                    # per-request service time (retry_after_hint), with a
                    # 1s floor until the first completion seeds the EWMA.
                    retry_after_s=self.retry_after_hint(),
                )
            self._rid_seq += 1
            req.rid = self._rid_seq
            req.future._lsot_replica = self.flight.replica
            req.submitted_at = time.perf_counter()
            if self._qos:
                self._stamp_qos_locked(req)
            self._pending_new_tokens += req.max_new
            self._pending_prompt_tokens += len(req.ids)
            self._queue.put(req)
        return req.future

    def generate(
        self,
        prompts: List[List[int]],
        max_new_tokens: int = 256,
        sampling: SamplingParams = SamplingParams(),
        seed: int = 0,
    ) -> List[List[int]]:
        """Synchronous batch helper (engine-compatible signature)."""
        futs = [
            self.submit(p, max_new_tokens=max_new_tokens, sampling=sampling, seed=seed)
            for p in prompts
        ]
        return [f.result() for f in futs]

    @staticmethod
    def cancel(future: "Future[List[int]]") -> None:
        """Cooperatively cancel a submitted request: the worker retires it
        (resolving the future with whatever was generated) at its next
        harvest instead of decoding the remaining budget for an abandoned
        consumer. Safe on finished/foreign futures (no-op). A REMOTE
        request's `_Request` lives in another process — its future
        carries an `_lsot_cancel` callable instead (serve/remote.py),
        which ships the cancel over the wire."""
        req = getattr(future, "_lsot_request", None)
        if req is not None:
            req.cancelled = True
            return
        cb = getattr(future, "_lsot_cancel", None)
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — cancel of the unreachable is moot
                pass

    # ------------------------------------------------------ multi-tenant WFQ

    def _wfq_weight(self, tenant: str) -> float:
        """WFQ weight for a tenant (LSOT_TENANT_WEIGHTS; 1.0 default —
        including the unlabeled "" tenant, which competes as one tenant)."""
        w = self._tenant_weights.get(tenant, 1.0)
        return w if w > 0 else 1.0

    def _stamp_qos_locked(self, req: _Request) -> None:
        """Stamp the WFQ virtual finish time and the tenant's prefix
        namespace salt (callers hold _submit_lock; QoS on only).

        Start-time fair queueing: a request starts at max(global virtual
        time, its tenant's last finish) and finishes cost/weight later,
        cost = prompt + budget tokens. A tenant submitting a storm only
        advances its OWN clock — its k-th queued request finishes k
        virtual-costs out, while a light tenant's next request starts at
        the global clock and is served ahead of the whole backlog."""
        from .qos import bounded_bump, tenant_salt
        cost = (len(req.ids) + req.max_new) / self._wfq_weight(req.tenant)
        req.vft = max(self._wfq_vt, self._wfq_last.get(req.tenant, 0.0)) + cost
        self._wfq_last[req.tenant] = req.vft
        if len(self._wfq_last) > 128:
            # Idle-tenant ledger hygiene: a finish time at/behind the
            # global clock no longer orders anything.
            self._wfq_last = {t: v for t, v in self._wfq_last.items()
                              if v > self._wfq_vt}
        if self._prefix_tenant_ns and req.tenant:
            req.ns = tenant_salt(req.tenant)
        bounded_bump(self._tenant_submitted, req.tenant)

    def _drain_ready(self) -> None:
        """Move every queued submit into the WFQ ready pool (worker
        thread; QoS on only). queue.Queue hands each item to exactly one
        consumer, so this never duplicates against extract_queued."""
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            if req is None:
                continue
            with self._submit_lock:
                self._ready.append(req)

    def _ready_pop(self) -> Optional[_Request]:
        """Serve the smallest virtual finish time (rid tie-break keeps
        same-tenant FIFO and determinism) and advance the global virtual
        clock to it."""
        with self._submit_lock:
            if not self._ready:
                return None
            i = min(range(len(self._ready)),
                    key=lambda j: (self._ready[j].vft, self._ready[j].rid))
            req = self._ready.pop(i)
            self._wfq_vt = max(self._wfq_vt, req.vft)
            return req

    def _page_wait_pop(self) -> _Request:
        """Next page-starved waiter to re-try admission. QoS off: FIFO
        popleft — the pre-QoS order bit-for-bit. QoS on: preempted
        victims still resume ahead of never-admitted waiters (they were
        admitted first and hold delivered tokens), then smallest virtual
        finish time — a storm tenant's parked backlog cannot
        head-of-line-block a light tenant's waiter."""
        pw = self._page_wait
        if not self._qos or len(pw) == 1:
            return pw.popleft()
        best = min(range(len(pw)),
                   key=lambda i: (0 if pw[i].preempted else 1,
                                  pw[i].vft, i))
        if best == 0:
            req = pw.popleft()
        else:
            pw.rotate(-best)
            req = pw.popleft()
            pw.rotate(best)
        self._wfq_vt = max(self._wfq_vt, req.vft)
        return req

    def qos_stats(self) -> Optional[Dict[str, object]]:
        """Per-tenant WFQ/admission counters for /metrics (the
        lsot_tenant_* families): None when QoS is off — the pre-QoS
        payload byte-for-byte."""
        if not self._qos:
            return None
        with self._submit_lock:
            backlog: Dict[str, int] = {}
            for r in self._ready:
                key = r.tenant or "default"
                backlog[key] = backlog.get(key, 0) + 1
            out: Dict[str, object] = {
                "virtual_time": round(self._wfq_vt, 3),
                "ready": len(self._ready),
                "page_wait": len(self._page_wait),
                "submitted": dict(self._tenant_submitted),
                "preempted": dict(self._tenant_preempted),
            }
            if self._tenant_weights:
                out["weights"] = dict(self._tenant_weights)
            if backlog:
                out["backlog"] = backlog
            return out

    @property
    def overshoot(self) -> int:
        """Max tokens/positions the device can run past a budget or stop
        before the host notices: pending rounds × max tokens per round,
        plus (speculatively) one verify window of cache-write lookahead
        beyond the last emitted position."""
        if self._spec_draft:
            d1 = self._spec_draft + 1
            return (self._harvest_lag + 1) * d1 + self._spec_draft
        return (self._harvest_lag + 1) * self.decode_chunk

    @property
    def speculation_stats(self) -> Optional[Dict[str, float]]:
        """Speculative-decoding acceptance (None when speculation is off):
        verify rounds and tokens emitted across every emitting slot,
        tokens/round (1.0 = no draft ever accepted .. D+1 = every draft
        accepted), and the estimated speedup vs vanilla decode given the
        measured ~1.6x verify-round cost (engine/speculative.py breakeven
        math) — the go/no-go number for --speculative on a given
        workload. `by_class` splits the same acceptance figures by
        constrained vs unconstrained requests: grammar-masked NL→SQL
        traffic accepts differently (forced keyword/identifier runs vs
        free text). `by_sampling` splits them by greedy vs sampled
        (temperature>0) requests: rejection-sampling acceptance (u <
        target mass) runs systematically below greedy's argmax match, so
        the sampled class prices its own speedup instead of hiding in a
        blend (/metrics carries both splits)."""
        if not self._spec_draft:
            return None
        from ..engine.speculative import (
            VERIFY_COST_CALIBRATION,
            verify_cost_ratio,
        )

        # Copy the counters under the scheduler's lock: the harvest thread
        # updates them as a group under it, so this read can never see a
        # half-applied round (ADVICE.md r5 #2).
        with self._submit_lock:
            rounds, toks = self._spec_rounds, self._spec_tokens
            rounds_con, toks_con = (self._spec_rounds_con,
                                    self._spec_tokens_con)
            rounds_samp, toks_samp = (self._spec_rounds_samp,
                                      self._spec_tokens_samp)
        # The verify cost scales with THIS scheduler's draft length
        # (ADVICE r5 #3: a D=4 deployment's breakeven is not D=8's) — the
        # per-D linear model replaces the old single 1.6 constant — and
        # with its MODEL SHAPE (ROADMAP carried-over: the 1B-anchored
        # slope mispriced 7B/int4 configs; unembed-marginal over
        # weight-stream-fixed rescales it). Weight bits were probed once
        # at construction.
        ratio = verify_cost_ratio(
            self._spec_draft, cfg=self.cfg, weight_bits=self._weight_bits,
        )

        def acceptance(r: int, t: int) -> Dict[str, float]:
            tpr = t / r if r else 0.0
            return {
                "verify_rounds": r,
                "tokens_emitted": t,
                "tokens_per_round": round(tpr, 3),
                "est_speedup_vs_vanilla": round(tpr / ratio, 3) if r else 0.0,
            }

        return {
            **acceptance(rounds, toks),
            # The estimate's denominator, at this config's draft length,
            # plus where the model's anchors were measured — a 7B/int4/TP
            # serving config can still sit meaningfully off it.
            "verify_cost_ratio": round(ratio, 3),
            "est_speedup_calibration": VERIFY_COST_CALIBRATION,
            "by_class": {
                "constrained": acceptance(rounds_con, toks_con),
                "unconstrained": acceptance(rounds - rounds_con,
                                            toks - toks_con),
            },
            "by_sampling": {
                "greedy": acceptance(rounds - rounds_samp,
                                     toks - toks_samp),
                "sampled": acceptance(rounds_samp, toks_samp),
            },
        }

    def retry_after_hint(self) -> float:
        """Queue-depth-aware Retry-After (ROADMAP follow-up): a shed client
        should wait roughly until the current backlog has drained through
        the slot pool — queue depth × recent per-request service time /
        concurrent lanes — not a static constant. Clamped to [1, 60]s:
        the floor keeps retry storms decorrelated when the estimate is
        tiny (or not yet seeded), the ceiling keeps one pathological slow
        request from telling everyone to come back in an hour. Shared by
        the 429 shed path and the drain-mode 503.

        Lock-free read ON PURPOSE: submit() calls this while HOLDING
        _submit_lock (the Overloaded raise), so taking the lock here
        would self-deadlock; a float attribute read is atomic under the
        GIL and a one-update-stale estimate is still an estimate."""
        ewma = self._svc_ewma
        if ewma is None:
            return 1.0
        # The retry waits behind itself too; under QoS the WFQ ready pool
        # is backlog the queue alone no longer counts.
        depth = self._queue.qsize() + len(self._ready) + 1
        return float(min(60.0, max(1.0, depth * ewma / max(1, self.num_slots))))

    def backlog_score(self) -> Tuple[float, int]:
        """Placement score for the pool's least-loaded router:
        `(estimated backlog seconds, pending new tokens)`, compared
        lexicographically. The seconds estimate is the Retry-After
        hint's service-time-EWMA math refined to TOKEN resolution —
        outstanding token mass × measured sec/token / slots — unclamped
        (a router comparing replicas needs the raw estimate, not the
        [1, 60] s client courtesy). Token-weighted on purpose: under a
        submit burst, request COUNTS tie constantly and a per-request
        EWMA degenerates into count-balancing, which on skewed prompt
        lengths reproduces round-robin's pathology (all the long
        requests stack one replica); token mass is the load that
        actually differs, and pricing it in seconds keeps the score
        comparable against a request's deadline. Until the first
        completion seeds the EWMA the estimate is 0.0 and the raw token
        tie-break carries the routing. Lock-free read like
        retry_after_hint (atomic attribute reads; a hair-stale estimate
        is still an estimate)."""
        if self.phase_role == "prefill":
            # A prefill replica's backlog is COMPUTE backlog: outstanding
            # prompt tokens priced by the measured submit→handoff wall
            # per prompt token — the decode budgets it will never spend
            # say nothing about how long a new prompt waits here.
            toks = int(self._pending_prompt_tokens)
            stok = self._pref_stok_ewma
            secs = (toks * stok / max(1, self.num_slots)
                    if stok is not None else 0.0)
            return float(secs), toks
        stok = self._stok_ewma
        toks = int(self._pending_new_tokens)
        secs = (toks * stok / max(1, self.num_slots)
                if stok is not None else 0.0)
        return float(secs), toks

    def extract_queued(self) -> List[_Request]:
        """Pull every queued-not-yet-admitted request OUT of this
        scheduler (the pool's drain-one-replica re-placement seam).
        Safe against the live worker: `queue.Queue` hands each item to
        exactly one consumer, so a request is either extracted here or
        admitted there, never both — requests the worker already pulled
        finish on this replica during the drain grace. Wake sentinels
        (None) are dropped; the loop's 50 ms poll re-arms them."""
        out: List[_Request] = []
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                out.append(req)
        with self._submit_lock:
            # Under QoS the worker stages queued submits in the WFQ ready
            # pool — those are still queued-not-yet-admitted and must
            # leave with the drain (the lock serializes against the
            # worker's own _drain_ready/_ready_pop).
            if self._ready:
                out.extend(self._ready)
                self._ready.clear()
            if out:
                self._pending_new_tokens = max(
                    0, self._pending_new_tokens
                    - sum(r.max_new for r in out)
                )
                self._pending_prompt_tokens = max(
                    0, self._pending_prompt_tokens
                    - sum(len(r.ids) for r in out)
                )
        return out

    def extract_handoffs(self) -> List[_Request]:
        """Drain the packed-handoff queue (the pool's placement pump and
        the drain-replica re-placement both come through here). Each
        request carries its portable KV blob (`spilled` + `handoff`
        metadata), so any paged sibling can `requeue()` it and resume
        decode without re-prefilling. Backlog accounting already left
        this replica at pack time — no decrement here."""
        out: List[_Request] = []
        while True:
            try:
                out.append(self._handoff.popleft())
            except IndexError:
                break
        return out

    def requeue(self, req: _Request) -> None:
        """Accept a request extracted from a sibling replica (pool
        re-placement on drain/remove): fresh rid + replica restamp, then
        straight into the queue. BYPASSES max_queue_depth on purpose —
        the request was already admitted (acknowledged) once; shedding
        acknowledged work because it had to move replicas would turn a
        drain into data loss."""
        if req.spilled is not None:
            # A migrated/spilled blob can only restore into a COMPATIBLE
            # pool: same page size (blob pages are [L, n, K, ps(, H)]
            # slices of the source pool). The pool's handoff placement
            # treats this ValueError as "target can't take it" and tries
            # the next sibling.
            if req.spilled[0].shape[3] != self._page_size:
                raise ValueError(
                    f"handoff blob page size {req.spilled[0].shape[3]} "
                    f"!= this pool's {self._page_size}"
                )
            # ... and the same stored shape (heads a row).
            check_blob_shape(self._kv_stored_shape, req.spilled[0].shape)
        with self._submit_lock:
            if self._closed:
                if self._crash is not None:
                    raise self._crash_error()
                raise RuntimeError("scheduler has shut down")
            if self._thread is None:
                raise RuntimeError("scheduler not started")
            self._rid_seq += 1
            req.rid = self._rid_seq
            req.future._lsot_replica = self.flight.replica
            if self._qos:
                # Re-placed requests re-enter THIS replica's virtual
                # clock (vft from another replica's clock is meaningless
                # here) and re-derive the prefix namespace locally.
                self._stamp_qos_locked(req)
            self._pending_new_tokens += req.max_new
            self._pending_prompt_tokens += len(req.ids)
            self._queue.put(req)

    def _record_service_time(self, req: _Request) -> None:
        """EWMA of submit→retire wall for COMPLETED requests (failures and
        cancels say nothing about healthy service time — a disconnect-heavy
        streaming workload retiring fractional decodes would otherwise
        drag the estimate down and tell shed clients to retry too soon).
        Under the submit lock: retry_after_hint reads it from HTTP
        threads."""
        if req.submitted_at <= 0.0 or req.cancelled:
            return
        wall = time.perf_counter() - req.submitted_at
        stok = wall / max(1, len(req.generated))
        with self._submit_lock:
            prev = self._svc_ewma
            self._svc_ewma = wall if prev is None else 0.2 * wall + 0.8 * prev
            prev_t = self._stok_ewma
            self._stok_ewma = (stok if prev_t is None
                               else 0.2 * stok + 0.8 * prev_t)
            # Observed generation length: what overcommit admission
            # reserves instead of the worst-case budget. Completed
            # requests only (a cancelled fraction says nothing about how
            # long requests RUN).
            g = float(len(req.generated))
            prev_g = self._gen_ewma
            self._gen_ewma = (g if prev_g is None
                              else 0.2 * g + 0.8 * prev_g)

    # ------------------------------------- prefix-cache telemetry (ISSUE 14)

    def _digest_for(self, key: Tuple[int, ...]) -> str:
        """Memoized content digest of a chain key (worker thread only;
        see _prefix_digest_memo for why)."""
        memo = self._prefix_digest_memo
        d = memo.get(key)
        if d is None:
            d = prefix_digest(key)
            memo[key] = d
            bound = 4 * max(self._prefix_topk,
                            self._prefix_cache_blocks or 1)
            while len(memo) > bound:
                memo.popitem(last=False)
        else:
            memo.move_to_end(key)
        return d

    def _prefix_note_publish(self, key: Tuple[int, ...]) -> None:
        """Register a freshly published cache entry: content digest +
        live metadata, and the eviction-churn check — a key coming back
        through publish while still on the evicted ghost means the cache
        was too small for the working set (reinsertion, the signal the
        capacity knob acts on). Worker thread only; the lock is for
        registry/metrics readers."""
        digest = self._digest_for(key)
        with self._submit_lock:
            if key in self._prefix_evicted_ghost:
                del self._prefix_evicted_ghost[key]
                self._prefix_reinserts += 1
            self._prefix_meta[key] = {
                "digest": digest,
                "tokens": len(key),
                "hits": 0,
                "insert_round": self.heartbeat.rounds,
                "last_hit_round": None,
            }

    def _prefix_note_evict(self, key: Tuple[int, ...],
                           pages: Tuple[int, ...]) -> None:
        """Entry left the cache (capacity cap, allocation pressure,
        watermark sweep, or COW un-publish): count it, drop its registry
        metadata, remember the key on the churn ghost, and release the
        allocator's per-page resident-prefix accounting."""
        self._page_alloc.prefix_drop(list(pages))
        with self._submit_lock:
            self._prefix_evictions += 1
            self._prefix_meta.pop(key, None)
            self._prefix_evicted_ghost[key] = None
            while len(self._prefix_evicted_ghost) > \
                    4 * self._prefix_cache_blocks:
                self._prefix_evicted_ghost.popitem(last=False)

    def _prefix_note_admission(self, req: _Request, ids: Sequence[int],
                               reuse: int, blocks: int) -> None:
        """Per-request reuse attribution, at the one instant admission
        knows both the request and the match: stamp the request (digest,
        tokens_reused, analytic prefill seconds saved), move the
        hit/miss counter group under the scheduler lock, feed the
        reuse-distance ring, and queue the {rid, digest, reused,
        prefilled} row for the next flight record. `reuse` is in tokens
        (always a whole number of pblock blocks), `blocks` = reuse //
        pblock."""
        pb = self._pblock
        max_blocks = (len(ids) - 1) // pb
        hit = reuse > 0
        # HIT: the digest is the MATCHED chain entry's (ids[:reuse]) —
        # joinable against /debug/prefixcache and the resident-digest
        # sets, and stable across requests whose tails differ. MISS: the
        # longest whole-block prompt prefix is the best schema-identity
        # guess available (there is no match to name); once the prefix
        # publishes and hits, later admissions converge on the matched
        # digest, so the reuse-distance ring sees the recurrence.
        # `req.ns` (the tenant namespace salt, ISSUE 18) prefixes every
        # key/digest exactly as the cache-key sites do: a tenant's digest
        # only ever joins against its own namespace. () for unlabeled
        # traffic — the shared-registry digests, unchanged.
        if hit:
            digest = self._digest_for(req.ns + tuple(ids[:reuse]))
        elif max_blocks:
            digest = self._digest_for(req.ns + tuple(ids[: max_blocks * pb]))
        else:
            digest = ""
        flops = secs = 0.0
        if hit:
            flops, secs = self.perf.prefill_saved(reuse)
        req.prefix_digest = digest
        req.tokens_reused = reuse
        req.prefill_s_saved = secs
        # Reuse distance BEFORE this admission joins the ring: admissions
        # since the same schema-prefix digest last appeared, from the
        # O(1) digest -> seq map; a sighting older than the ring window
        # counts as absent (the "inf" histogram arm).
        bucket = None
        if digest:
            seq = self._prefix_adm_seq
            last = self._prefix_ring_seq.get(digest)
            dist = (seq - last
                    if last is not None
                    and seq - last <= self._prefix_ring_cap else None)
            bucket = "inf"
            if dist is not None:
                # dist <= ring cap by the window check above, and the
                # bucket list tops out AT the ring cap — next() always
                # finds an arm, however wide the ring is configured.
                bucket = str(next(b for b in self._prefix_rd_buckets
                                  if dist <= b))
        with self._submit_lock:
            if hit:
                self._prefix_hits += 1
                self._prefix_blocks_reused += blocks
                self._prefix_reused_tokens += reuse
                self._prefix_flops_saved += flops
                self._prefix_s_saved += secs
                meta = self._prefix_meta.get(req.ns + tuple(ids[:reuse]))
                if meta is not None:
                    meta["hits"] += 1
                    meta["last_hit_round"] = self.heartbeat.rounds
            elif digest:
                # CACHEABLE admissions only: a prompt shorter than one
                # block (digest == "") can never hit, and counting it as
                # a miss would deflate hit_rate / the EWMA routing signal
                # on short-query traffic the cache was never for.
                self._prefix_misses += 1
            if digest:
                x = 1.0 if hit else 0.0
                prev = self._prefix_hit_ewma
                self._prefix_hit_ewma = (x if prev is None
                                         else 0.2 * x + 0.8 * prev)
            if bucket is not None:
                self._prefix_rd_hist[bucket] = \
                    self._prefix_rd_hist.get(bucket, 0) + 1
                self._prefix_ring_seq[digest] = self._prefix_adm_seq
                self._prefix_adm_seq += 1
                if len(self._prefix_ring_seq) > 2 * self._prefix_ring_cap:
                    # Amortized sweep of sightings older than the window.
                    cutoff = self._prefix_adm_seq - self._prefix_ring_cap
                    self._prefix_ring_seq = {
                        d: s for d, s in self._prefix_ring_seq.items()
                        if s >= cutoff
                    }
        if digest:
            self._round_prefix.append({
                "rid": req.rid,
                "digest": digest,
                "reused": reuse,
                "prefilled": len(ids) - reuse,
            })

    def _prefix_snapshot(self) -> Dict[str, object]:
        """ONE-acquisition copy of the whole telemetry counter group (the
        PR-1 speculation-counter pattern, widened): every field a reader
        pairs — hits/misses/reused beside the priced savings and the
        EWMA — comes from the same instant, so /metrics scrapes and
        bench's pre/post delta bracketing can never see a hits delta
        inconsistent with its prefill_s_saved delta."""
        with self._submit_lock:
            return {
                "hits": self._prefix_hits,
                "misses": self._prefix_misses,
                "blocks_reused": self._prefix_blocks_reused,
                "reused_tokens": self._prefix_reused_tokens,
                "evictions": self._prefix_evictions,
                "reinserts": self._prefix_reinserts,
                "flops_saved": self._prefix_flops_saved,
                "s_saved": self._prefix_s_saved,
                "hit_ewma": self._prefix_hit_ewma,
                "resident_entries": len(self._prefix_meta),
            }

    @staticmethod
    def _prefix_stats_from(snap: Dict[str, object]) -> Dict[str, object]:
        total = int(snap["hits"]) + int(snap["misses"])
        return {
            "hits": snap["hits"],
            "misses": snap["misses"],
            "hit_rate": (round(int(snap["hits"]) / total, 4) if total
                         else 0.0),
            "blocks_reused": snap["blocks_reused"],
            "reused_tokens": snap["reused_tokens"],
            "evictions": snap["evictions"],
        }

    @property
    def prefix_stats(self) -> Dict[str, object]:
        """Prefix-cache observability: requests that reused any blocks vs
        requests the match path came up empty for (`hit_rate` =
        hits/(hits+misses)), total blocks and TOKENS reused (each block
        is a skipped pblock-token prefill), entries evicted, and the
        current LRU size (entries are zero-copy page references;
        page_stats carries the sharing counters). The counter
        group is copied under the scheduler lock in ONE acquisition so a
        /metrics scrape or bench's pre/post delta bracketing never
        observes a torn (hits, blocks_reused) pair."""
        return {
            **self._prefix_stats_from(self._prefix_snapshot()),
            "cached_blocks": len(self._prefix_pages),
        }

    @property
    def prefix_telemetry(self) -> Optional[Dict[str, object]]:
        """The `serving.prefix` /metrics block (ISSUE 14): the counter
        group plus churn, the live hit-rate EWMA, the priced value of the
        hits (analytic prefill FLOPs/seconds saved —
        utils/perfmodel.prefill_saved), and what the cache currently
        HOLDS (entries / tokens / device bytes; residency comes from the
        allocator's unique-page accounting, so chained entries are not
        double-counted). None when the cache is off
        (prefix_cache_blocks=0 — including speculative schedulers, which
        disable reuse by design). The whole block derives from ONE locked
        snapshot, so no field pairs across a concurrent admission."""
        if not self._prefix_cache_blocks:
            return None
        snap = self._prefix_snapshot()
        st = self._prefix_stats_from(snap)
        st["cached_blocks"] = len(self._prefix_pages)
        reinserts = snap["reinserts"]
        flops = float(snap["flops_saved"])
        secs = float(snap["s_saved"])
        ewma = snap["hit_ewma"]
        entries = int(snap["resident_entries"])
        # Residency counts what the cache HOLDS, deduped: chained entries
        # overlap on their leading pages, so tokens/bytes come from the
        # allocator's unique-page accounting (summing per-entry chain
        # lengths would overstate residency ~2x on deep chains).
        resident_pages = self._page_alloc.prefix_resident_pages
        tokens = resident_pages * self._page_size
        resident_bytes = resident_pages * page_bytes(
            self.cfg, self._page_size, self._dtype.itemsize,
            self.kv_quant,
        )
        return {
            "replica": self.flight.replica,
            **st,
            "reinserts": reinserts,
            "hit_rate_ewma": round(ewma, 4) if ewma is not None else 0.0,
            "prefill_flops_saved": round(flops, 1),
            "prefill_s_saved": round(secs, 6),
            "resident_entries": entries,
            "resident_tokens": tokens,
            "resident_bytes": resident_bytes,
        }

    def resident_digests(self, limit: Optional[int] = None) -> List[str]:
        """Hottest-K resident prefix digests (by hit count, then token
        mass): the bounded per-replica residency set `replica_loads()`
        exports and `SchedulerPool.prefix_affinity` matches a request's
        chain digests against — the cache-aware routing feed the
        multi-host ROADMAP item consumes."""
        k = limit if limit and limit > 0 else self._prefix_topk
        with self._submit_lock:
            metas = sorted(
                self._prefix_meta.values(),
                key=lambda m: (int(m["hits"]), int(m["tokens"])),
                reverse=True,
            )[:k]
        return [str(m["digest"]) for m in metas]

    def prefix_registry(self, top_k: Optional[int] = None
                        ) -> Dict[str, object]:
        """The /debug/prefixcache payload for this replica: top-K
        resident entries by token mass (digest, token length, pages +
        device bytes held, live share refcount, hit count,
        insert/last-hit round), the reuse-distance histogram over the
        bounded admission ring, and the eviction-churn counters. Bounded
        by `top_k` (default LSOT_PREFIX_TOPK) so a huge cache never turns
        a debug scrape into a token-list dump — entries carry digests,
        never token ids."""
        k = top_k if top_k and top_k > 0 else self._prefix_topk
        tel = self.prefix_telemetry
        # Snapshot metadata, page tuples AND refcounts under ONE lock
        # acquisition: read piecemeal, an entry evicted mid-iteration
        # could have its freed page reallocated to another slot, and the
        # registry would report the unrelated slot's refcount as the
        # entry's share count.
        with self._submit_lock:
            rd = dict(self._prefix_rd_hist)
            metas = []
            for key, m in self._prefix_meta.items():
                pages = self._prefix_pages.get(key)
                shares = (self._page_alloc.refcount(pages[-1])
                          if pages else None)
                metas.append((m, pages, shares))
        entries: List[Dict[str, object]] = []
        for m, pages, shares in metas:
            if pages is None:
                continue  # evicted between its meta pop and page pop
            entries.append({
                "digest": m["digest"],
                "tokens": m["tokens"],
                "hits": m["hits"],
                "insert_round": m["insert_round"],
                "last_hit_round": m["last_hit_round"],
                "pages": len(pages),
                "bytes": len(pages) * page_bytes(
                    self.cfg, self._page_size, self._dtype.itemsize,
                    self.kv_quant,
                ),
                # How many owners the chain's DEEPEST page had at the
                # snapshot (1 = resident but unmapped by any slot).
                "shares": shares,
            })
        entries.sort(key=lambda e: (int(e["tokens"]), int(e["hits"])),
                     reverse=True)
        return {
            "replica": self.flight.replica,
            "enabled": bool(self._prefix_cache_blocks),
            "block_tokens": self._pblock,
            "capacity": self._prefix_cache_blocks,
            "ring": self._prefix_ring_cap,
            "top_k": k,
            "entries": entries[:k],
            "reuse_distance": rd,
            **({k2: v for k2, v in tel.items() if k2 != "replica"}
               if tel else {}),
        }

    @property
    def watchdog_stats(self) -> Dict[str, object]:
        """Liveness observability for /metrics: the loop's heartbeat (age,
        busy, rounds, measured cadence) and per-slot stall retirements.
        The supervisor layers its stall-detection counters on top."""
        return {
            "heartbeat": self.heartbeat.snapshot(),
            "slots_retired_stalled": self._slot_stalls,
        }

    # ------------------------------------------------------------ event loop

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _constrained_busy(self) -> bool:
        return any(
            r is not None and r.constraint is not None for r in self._slot_req
        )

    def _grammar_matches(self, c: CompiledMask) -> bool:
        """Is `c` servable by the INSTALLED tables? Content identity
        (fingerprint + stop ids), not object identity: the constrain-side
        compile cache is LRU-bounded, so the same schema can legitimately
        arrive as a fresh CompiledMask object after an eviction — a
        spurious drain+reinstall for identical tables would serialize the
        batch for nothing."""
        inst = self._constraint
        return inst is not None and (
            c is inst
            or (c.fingerprint == inst.fingerprint
                and c.eos_ids == inst.eos_ids)
        )

    def _install_constraint(self, compiled: CompiledMask) -> None:
        """Swap in a grammar's precompiled device tables (worker thread
        only; callers guarantee no constrained slot is active, so no live
        FSM state can index into the wrong table). Tables are compiled and
        cached by constrain.get_constraint — installing is a device_put of
        existing arrays plus ONE decode retrace when the state count
        changes; per-request admissions with the already-installed grammar
        touch nothing."""
        self._constraint = compiled
        self._ctables = compiled.device_tables(self.cfg.vocab_size)
        self.flight.event("grammar_swap",
                          fingerprint=str(getattr(compiled, "fingerprint",
                                                  ""))[:16])

    def _admit(self, slot: int, req: _Request) -> bool:
        """Reserve `slot` for `req`: allocate its page envelope, map any
        cached prefix ZERO-COPY (shared pages by refcount; one-page
        copy-on-write only when the matched prefix ends mid-page) and
        queue the prompt for chunked prefill. Returns False — with no
        side effects — when the pool cannot fund the envelope right now
        (the loop parks the request in _page_wait until retirements free
        pages; all-or-nothing, so partial holders can never deadlock each
        other)."""
        if req.cancelled:  # cancelled while queued: never occupy a slot
            self._observe_terminal(req)
            req.future.set_result(req.generated)
            return True
        if req.past_deadline():
            # Expired while queued: fail fast with the typed error before
            # ever occupying a slot — under overload, prefilling work whose
            # caller already gave up only steals device time from requests
            # that can still make their deadlines. Terminal bookkeeping
            # still runs: the trace gets its queue-wait span (the one span
            # that explains a 504-from-queue) and the flight record lists
            # the rid as retired.
            resilience.inc("deadline_expired")
            self._observe_terminal(req, error="DeadlineExceeded")
            req.future.set_exception(req.deadline_error())
            return True
        ps, pb = self._page_size, self._pblock
        s_virt = self._pages_per_slot * ps
        ids = req.full_ids  # prompt + committed tokens after a preemption
        plen = len(ids)
        n = 0
        # Spill resumes restore page CONTENT into fresh exclusive pages —
        # a shared prefix mapping would be overwritten, so they skip the
        # prefix cache entirely (the pages already hold the prefix).
        if self._prefix_cache_blocks and req.spilled is None:
            # Every lookup keys through the request's tenant namespace
            # salt (`req.ns`, ISSUE 18): a tenant can only ever match —
            # or evict — entries its own admissions published. () for
            # unlabeled traffic keeps the shared-registry keys exact.
            max_blocks = (plen - 1) // pb
            while n < max_blocks and \
                    req.ns + tuple(ids[: (n + 1) * pb]) in self._prefix_pages:
                n += 1
            # Cap reuse so the chunk envelope stays inside the virtual
            # row: a block-aligned (not bucket-aligned) reuse offset
            # shifts every chunk start, and the final chunk's bucket
            # (which can exceed the tokens left) must still land inside
            # it. n=0 restores the un-reused geometry.
            while n and self._chunk_end(n * pb, plen) > s_virt:
                n -= 1
        reuse = n * pb
        # The envelope admission must cover: every position chunked
        # prefill writes, plus decode through the RESERVED generation
        # budget + overshoot. Exact mode (kv_overcommit=1.0) reserves the
        # full remaining budget — today's envelope bit for bit;
        # overcommit reserves the expected generation and decode tops up
        # at each harvest (_topup_pages). Clamped to the per-slot virtual
        # row: a RESUME's prompt (original + committed tokens) re-rounds
        # to the next prompt bucket, which can push the raw formula past
        # max_seq even though every real write stays below it (submit's
        # bound) — unclamped, the allocation could outgrow the device
        # table row. Fresh admissions never hit the clamp (submit's bound
        # keeps their envelope inside the row), so exact-envelope
        # accounting is untouched.
        need_end = min(s_virt, max(
            self._chunk_end(reuse, plen),
            bucket_len(plen, self.prompt_bucket)
            + self._reserve_new(req) + self.overshoot,
        ))
        need_pages = pages_for_tokens(need_end, ps)
        full = reuse // ps
        entry = (self._prefix_pages.get(req.ns + tuple(ids[:reuse]))
                 if reuse else None)
        shared = list(entry[:full]) if entry else []
        boundary_src = entry[full] if (entry and reuse % ps) else None
        # Take the refs BEFORE allocating: _alloc_pages evicts LRU prefix
        # entries under pressure, and the matched entry must survive it.
        # count=False: these holds are transient until admission succeeds
        # (released on the shortage path below, and the boundary hold only
        # lives until its COW copy) — the shares counter must track
        # mappings that PERSIST, not per-retry churn.
        self._page_alloc.share(shared, count=False)
        if boundary_src is not None:
            self._page_alloc.share([boundary_src], count=False)
        fresh = self._alloc_pages(need_pages - full)
        if fresh is None:
            self._page_alloc.release(shared)
            if boundary_src is not None:
                self._page_alloc.release([boundary_src])
            if not req.page_waited:
                # Count REQUESTS that waited, not per-round retries.
                req.page_waited = True
                self._page_wait_events += 1
            return False
        if boundary_src is not None:
            # Copy-on-write at the non-page-aligned boundary: ONE page
            # copy; prefill resumes mid-page inside the private copy
            # while the cache entry keeps the original.
            self._cache = self._copy_page_fn(
                *self._cache, jnp.int32(fresh[0]), jnp.int32(boundary_src)
            )
            self._page_alloc.note_cow()
            self._page_alloc.release([boundary_src])
        self._slot_pages[slot] = shared + fresh
        self._sync_ptab_row(slot)
        # The full-page mappings are now permanent for this request's
        # lifetime: count them as the zero-copy shares they are (the
        # boundary page was a COW copy, already counted as one).
        self._page_alloc.note_shares(len(shared))
        req.page_end = need_end
        if reuse:
            req.prefilled = reuse
            for j in range(n):  # LRU touch along the matched chain
                key = req.ns + tuple(ids[: (j + 1) * pb])
                if key in self._prefix_pages:
                    self._prefix_pages.move_to_end(key)
        if self._prefix_cache_blocks and req.spilled is None:
            # Reuse attribution at the one instant admission knows both
            # the request and the match (counters move inside, under the
            # scheduler lock — ISSUE 14).
            self._prefix_note_admission(req, ids, reuse, n)
        if not req.admitted_at:
            # Resumes keep their ORIGINAL admission stamp: the queue-wait
            # span/histogram measure submit → first slot, not decode time
            # an earlier incarnation already spent.
            req.admitted_at = time.perf_counter()
        self._round_admitted.append(req.rid)
        self._slot_req[slot] = req
        # Per-slot incarnation epoch: rounds and prefill first-tokens
        # harvested later carry the epoch they were issued under, so a
        # slot preempted and re-occupied (even by the SAME request —
        # identity checks can't see that) never commits a stale round's
        # tokens.
        self._slot_epoch[slot] += 1
        # Park the slot's decode writes before its prompt starts streaming in
        # (it may still be frozen at the previous occupant's position).
        # Async scatter — no host sync.
        self._cur, self._pos, self._cstates, self._crem = self._park_fn(
            self._cur, self._pos, self._cstates, self._crem, jnp.int32(slot)
        )
        if req.spilled is not None:
            # Spill resume: restore the host page copies and arm the slot
            # directly — no re-prefill, no first-token sample.
            self._restore_spilled(slot, req)
            return True
        self._prefill_q.append((slot, req))
        return True

    def _next_bucket(self, req: _Request) -> int:
        remaining = len(req.full_ids) - req.prefilled
        return next(
            (b for b in self._buckets if b >= remaining), self.prompt_bucket
        )

    def _chunk_end(self, start: int, total: int) -> int:
        """Highest cache position (exclusive) the chunked prefill of tokens
        [start, total) will WRITE — the final chunk writes its whole bucket,
        which can exceed the tokens left. Mirrors _next_bucket's chunking."""
        end = start
        while start < total:
            remaining = total - start
            t = next(
                (b for b in self._buckets if b >= remaining),
                self.prompt_bucket,
            )
            end = start + t
            start += min(t, remaining)
        return end

    def _prefill_step(self, span) -> None:
        """Run ONE prompt chunk for up to `_prefill_kmax` waiting requests
        in a single batched forward (Sarathi-style chunked prefill, batched
        over admissions): long prompts interleave with decode rounds instead
        of stalling every active slot (SURVEY.md §7 'without starving
        either'), and admission bursts amortize the weight stream across the
        batch instead of paying a full pass per request. The chunk size is
        the smallest power-of-two bucket covering what's left of the prompt;
        only same-bucket entries batch together (one compiled program per
        (bucket, k-bucket) pair, built lazily). `span` is the caller's open
        `sched.prefill_dispatch` stage, which learns what was dispatched."""
        group: List[Tuple[int, _Request]] = []
        deferred = []
        t = 0
        while self._prefill_q and len(group) < self._prefill_kmax:
            s, r = self._prefill_q.popleft()
            if self._slot_req[s] is not r:
                # Preempted while queued for prefill (its pages are gone
                # and the slot may belong to someone else): the request
                # re-admits from _page_wait, this stale entry just drops.
                continue
            if not group:
                t = self._next_bucket(r)
                group.append((s, r))
            elif self._next_bucket(r) == t:
                group.append((s, r))
            else:
                deferred.append((s, r))
        for item in reversed(deferred):  # keep arrival order for next passes
            self._prefill_q.appendleft(item)
        if not group:
            return

        kb = next(b for b in self._kbuckets if b >= len(group))
        if (t, kb) not in self._prefill_fns:
            self._prefill_fns[(t, kb)] = self._build_prefill(t, kb)
        # Copy-on-write sweep over each chunk's write window: a page the
        # publisher shared with the prefix cache last chunk must not be
        # written in place this chunk (only non-page-aligned block
        # boundaries ever trigger it).
        for slot, req in group:
            self._ensure_writable(slot, req.prefilled, req.prefilled + t)

        tokens, lengths, slots, starts = [], [], [], []
        temps, topps, topks, seeds, chunk_lens = [], [], [], [], []
        # First-token grammar state/budget per row: the grammar start
        # state on FINAL chunks of constrained requests (admission
        # guarantees the request's grammar IS the installed one), state 0
        # (the all-allowed sentinel) everywhere else. The prefill fn turns
        # these into a budget-aware mask on device — 2 ints per row cross
        # the host boundary, never a [k, vocab] array.
        cinits, cbudgets = [], []
        for slot, req in group:
            full = req.full_ids
            chunk_ids = full[req.prefilled : req.prefilled + t]
            tokens.append(chunk_ids + [self.cfg.pad_id] * (t - len(chunk_ids)))
            lengths.append(len(chunk_ids))
            chunk_lens.append(len(chunk_ids))
            slots.append(slot)
            starts.append(req.prefilled)
            temps.append(req.temperature)
            topps.append(req.top_p)
            topks.append(req.top_k)
            seeds.append(req.seed & 0xFFFFFFFF)
            final = req.prefilled + len(chunk_ids) >= len(full)
            # Resumed rows discard the prefill's sampled token (the next
            # input is the last COMMITTED token, re-armed by
            # _resume_ready), so they ride the unconstrained sentinel.
            con = (req.constraint is not None and final
                   and not req.resume_pref)
            cinits.append(req.constraint.init_state if con else 0)
            cbudgets.append(req.max_new if con else 1)
        # Padding rows: OOB slot index (writes dropped), positions [0, t)
        # over the clamped gather row — finite garbage, output discarded.
        for _ in range(kb - len(group)):
            tokens.append([self.cfg.pad_id] * t)
            lengths.append(1)
            slots.append(self.num_slots)
            starts.append(0)
            temps.append(0.0)
            topps.append(1.0)
            topks.append(0)
            seeds.append(0)
            cinits.append(0)
            cbudgets.append(1)

        # numpy at the exact dtype: jnp.asarray of a Python list converts
        # on the device, one tiny compiled program per (k-bucket, bucket)
        # shape, inside the serving loop.
        call_args = [
            np.asarray(tokens, np.int32), np.asarray(lengths, np.int32),
            np.asarray(slots, np.int32), np.asarray(starts, np.int32),
            np.asarray(temps, np.float32), np.asarray(topps, np.float32),
            np.asarray(topks, np.int32), np.asarray(seeds, np.uint32),
            np.asarray(cinits, np.int32), np.asarray(cbudgets, np.int32),
            self._ctables["need"],
        ]
        if self._spec_draft:
            call_args.append(self._hist)
        call_args.append(self._ptab)
        out = self._prefill_fns[(t, kb)](self.params, *self._cache, *call_args)
        # Roofline ledger: bank this chunk batch's analytic work; the
        # next harvested round attributes the pile over the measured
        # inter-harvest wall — chunks dispatch asynchronously, so there
        # is no honest per-chunk device wall outside /debug/profile.
        # rows = kb, the PADDED k-bucket: the device computes every
        # padding row's FLOPs too (finite garbage, writes dropped) —
        # the same every-row convention the decode ledger uses
        # (rows = num_slots), so prefill MFU is not understated vs
        # decode's on small admission groups. ctx is the real group's
        # mean attention context (padding rows attend over [0, t)).
        avg_start = sum(starts[: len(group)]) // len(group)
        self.perf.note_prefill(rows=kb, tokens=t,
                               ctx=avg_start + t // 2)
        self._note_prefill_dispatch(span, len(group), t, sum(chunk_lens))
        nc = len(self._cache)
        self._cache, toks = out[:nc], out[-1]
        if self._spec_draft:
            self._hist = out[nc]

        for i, (slot, req) in enumerate(group):
            chunk_start = req.prefilled
            req.prefilled += chunk_lens[i]
            full = req.full_ids
            if self._prefix_cache_blocks:
                self._publish_pages(slot, req, chunk_start)
            if req.prefilled < len(full):
                self._prefill_q.append((slot, req))
                continue
            if req.resume_pref:
                # Preemption resume (recompute mode): the KV is rebuilt;
                # arm the slot from the COMMITTED state — the prefill's
                # sampled token is discarded (the continuation's first
                # token comes from the next decode round, exactly where
                # the unpreempted control would produce it).
                self._resume_ready(slot, req)
                continue
            if self.phase_role == "prefill":
                # Disaggregation (ISSUE 13): don't arm the slot for
                # decode — park the final chunk's still-on-device first
                # token; _pack_handoffs (called right after this step)
                # syncs it, commits/streams it, and exports the slot's
                # pages into the handoff blob. The ready/spec-ready
                # scatters are skipped on purpose: the importing replica
                # arms everything through the resume machinery.
                self._handoff_pending.append(
                    (slot, req, toks[i : i + 1], self._slot_epoch[slot])
                )
                continue
            # No sync: arm the slot with the still-on-device first token and
            # attach it to the next round's harvest. Stop-token / budget
            # checks on the first token happen there, one round late — the
            # slot may decode a round of garbage first, which the
            # visibility invariant absorbs and submit()'s overshoot bound
            # accounts for.
            req.ready = True
            req.ready_at = time.perf_counter()
            # Decode writes [len(ids), page_end): the final chunk's
            # publish may have shared the page holding the prompt tail —
            # COW it before the slot goes decode-eligible, so decode
            # never writes a shared page in place.
            self._ensure_writable(slot, len(req.ids), req.page_end)
            tok = toks[i : i + 1]
            cinit = (req.constraint.init_state if req.constraint is not None
                     else 0)
            (self._cur, self._pos, self._temps, self._topps, self._topks,
             self._seeds, self._counts, self._cstates,
             self._crem) = self._ready_fn(
                self._cur, self._pos, self._temps, self._topps, self._topks,
                self._seeds, self._counts, self._cstates, self._crem,
                self._ctables["next"], jnp.int32(slot), tok,
                jnp.int32(len(req.ids)),
                jnp.float32(req.temperature), jnp.float32(req.top_p),
                jnp.int32(req.top_k), jnp.uint32(req.seed & 0xFFFFFFFF),
                jnp.int32(cinit), jnp.int32(req.max_new),
            )
            # The host mirror of the slot's on-device RNG stream index
            # (ready_slot set counts = 1: the prefill sample consumed
            # fold index 0) — what a later preemption restores.
            req.rng_count = 1
            if self._spec_draft:
                self._hist, self._hlen = self._spec_ready_fn(
                    self._hist, self._hlen, jnp.int32(slot), tok,
                    jnp.int32(len(req.ids)),
                )
            self._first_pending.append(
                (slot, req, tok, self._slot_epoch[slot])
            )

    def _note_prefill_dispatch(self, span, rows: int, bucket: int,
                               tokens: int) -> None:
        """One chunk batch went to the device: the open span's arguments,
        and the next round record's `prefill_*` columns."""
        span.set(rows=rows, bucket=bucket, tokens=tokens)
        self._round_prefill[0] += 1
        self._round_prefill[1] += rows
        self._round_prefill[2] += tokens

    def _next_round(self) -> int:
        """The number the round about to be issued will have in its
        flight record (`heartbeat.rounds` when it is harvested): rounds
        are harvested in the order they were issued, one count each."""
        return self.heartbeat.rounds + len(self._pending) + 1

    def _publish_pages(self, slot: int, req: _Request,
                       chunk_start: int) -> None:
        """Publish the chunk's completed prefix blocks (chunk_start is
        always block-aligned: reuse stops on block boundaries and every
        non-final chunk is a bucket = multiple of pblock). An entry is a
        REFERENCE to the publisher's pages (refcount++), not a copy —
        zero data movement. The publisher itself COWs before its next
        write into a page it just shared (_ensure_writable), so entry
        content is immutable from here on."""
        pb, ps = self._pblock, self._page_size
        ids = req.full_ids
        for b0 in range(chunk_start // pb, req.prefilled // pb):
            key = req.ns + tuple(ids[: (b0 + 1) * pb])
            if key in self._prefix_pages:
                self._prefix_pages.move_to_end(key)
                continue
            if key not in self._prefix_seen:
                # First sighting: remember the content, share nothing.
                self._prefix_seen[key] = None
                while len(self._prefix_seen) > 4 * self._prefix_cache_blocks:
                    self._prefix_seen.popitem(last=False)
                continue
            covered = (b0 + 1) * pb
            pages = tuple(
                self._slot_pages[slot][: pages_for_tokens(covered, ps)]
            )
            self._page_alloc.share(list(pages))
            self._page_alloc.prefix_hold(list(pages))
            self._prefix_pages[key] = pages
            self._prefix_note_publish(key)
            while len(self._prefix_pages) > self._prefix_cache_blocks:
                old_key, old = self._prefix_pages.popitem(last=False)
                self._prefix_note_evict(old_key, pages=old)
                self._page_alloc.release(list(old))

    def _issue_decode(self, span) -> None:
        """Dispatch one decode round asynchronously: state chains on device,
        nothing syncs here. The round's tokens are harvested `_harvest_lag`
        rounds later so the transfer round-trip overlaps later compute.
        `span` is the caller's open `sched.issue_decode` stage."""
        # Chaos seam (utils/faults.py): a `sched:decode` fault simulates a
        # device/loop failure mid-round — the loop dies, _run wraps it in
        # SchedulerCrashed, and every client future must fail typed, never
        # hang (asserted by the chaos tests).
        FAULTS.check("sched:decode")
        # Duration-valued hang seam: `sched:hang:p:secs` SLEEPS here —
        # the wedge that never raises (hung XLA dispatch, stuck device
        # transport).
        # The heartbeat was stamped at the loop top, so its age grows for
        # the whole sleep and the supervisor's watchdog must detect and
        # escalate it (SchedulerStalled → restart/replay).
        FAULTS.check("sched:hang")
        if FAULTS.active:
            # Replica-ADDRESSABLE seam (`sched:wedge_r1:p[:secs]`): wedge
            # (duration form) or crash (raising form) exactly ONE pool
            # replica by its label, leaving siblings untouched — the
            # fleet chaos stage's targeted-restart trigger. Gated on
            # FAULTS.active so the idle path never builds the site string.
            FAULTS.check(f"sched:wedge_{self.flight.replica}")
        rnd = self._next_round()
        active = np.asarray(
            [r is not None and r.ready for r in self._slot_req]
        )
        span.set(round=rnd, occupancy=int(active.sum()))
        issue_reqs = [
            self._slot_req[i] if active[i] else None
            for i in range(self.num_slots)
        ]
        nc = len(self._cache)
        if self._spec_draft:
            t = self._ctables
            out = self._decode_fn(
                self.params, *self._cache, self._hist, self._hlen,
                self._cur, self._pos, jnp.asarray(active), self._temps,
                self._topps, self._topks, self._seeds, self._counts,
                self._cstates, self._crem, t["next"], t["need"],
                self._ptab,
            )
            self._cache = out[:nc]
            (self._hist, self._hlen, self._cur, self._pos, self._counts,
             self._cstates, self._crem, toks, n_emit) = out[nc:]
        else:
            t = self._ctables
            out = self._decode_fn(
                self.params, *self._cache, self._cur, self._pos,
                jnp.asarray(active), self._temps, self._topps, self._topks,
                self._seeds, self._counts, self._cstates, self._crem,
                t["next"], t["need"], self._ptab,
            )
            self._cache = out[:nc]
            (self._cur, self._pos, self._counts, self._cstates, self._crem,
             toks) = out[nc:]
            n_emit = None
        self._pending.append((issue_reqs, list(self._slot_epoch), toks,
                              n_emit, self._first_pending,
                              time.perf_counter(), None, rnd))
        self._first_pending = []

    def _issue_mixed(self, span) -> bool:
        """LSOT_RAGGED=1 hot path (ISSUE 19): ONE compiled launch admits
        this iteration's prompt chunks AND the decode round — no phase
        alternation, no off-phase idle. Same group selection as
        _prefill_step (one bucket per round, arrival order), same host
        tail (publish / requeue / arm), same async pending/harvest
        plumbing as _issue_decode — the round just carries a mixed_meta
        so harvest attributes both phases' analytic work over one wall.
        Returns False (caller falls back to the alternating path for
        this iteration) when every queued entry was stale. `span` is the
        caller's open `sched.issue_mixed` stage."""
        group: List[Tuple[int, _Request]] = []
        deferred = []
        t = 0
        while self._prefill_q and len(group) < self._prefill_kmax:
            s, r = self._prefill_q.popleft()
            if self._slot_req[s] is not r:
                continue  # preempted while queued; re-admits via _page_wait
            if not group:
                t = self._next_bucket(r)
                group.append((s, r))
            elif self._next_bucket(r) == t:
                group.append((s, r))
            else:
                deferred.append((s, r))
        for item in reversed(deferred):  # keep arrival order for next passes
            self._prefill_q.appendleft(item)
        if not group:
            return False
        # Chaos seams: the mixed round IS the decode round, so the same
        # crash/hang/wedge sites fire here (chaos contracts hold with
        # ragged on).
        FAULTS.check("sched:decode")
        FAULTS.check("sched:hang")
        if FAULTS.active:
            FAULTS.check(f"sched:wedge_{self.flight.replica}")
        if t not in self._mixed_fns:
            self._mixed_fns[t] = (
                self._build_mixed_spec(t) if self._spec_draft
                else self._build_mixed(t)
            )
        # COW sweep over each chunk's write window.
        for slot, req in group:
            self._ensure_writable(slot, req.prefilled, req.prefilled + t)

        # S-wide prefill-row vectors: non-group rows carry the inert
        # defaults (is_pref=False routes them to the decode lane; the
        # rest are never read for such rows).
        S = self.num_slots
        p_tokens = [[self.cfg.pad_id] * t for _ in range(S)]
        p_lengths = [1] * S
        p_starts = [0] * S
        is_pref = [False] * S
        p_temps = [0.0] * S
        p_topps = [1.0] * S
        p_topks = [0] * S
        p_seeds = [0] * S
        p_cinits = [0] * S
        p_cbudgets = [1] * S
        chunk_lens: Dict[int, int] = {}
        for slot, req in group:
            full = req.full_ids
            chunk_ids = full[req.prefilled : req.prefilled + t]
            p_tokens[slot] = (
                chunk_ids + [self.cfg.pad_id] * (t - len(chunk_ids))
            )
            p_lengths[slot] = len(chunk_ids)
            chunk_lens[slot] = len(chunk_ids)
            p_starts[slot] = req.prefilled
            is_pref[slot] = True
            p_temps[slot] = req.temperature
            p_topps[slot] = req.top_p
            p_topks[slot] = req.top_k
            p_seeds[slot] = req.seed & 0xFFFFFFFF
            final = req.prefilled + len(chunk_ids) >= len(full)
            con = (req.constraint is not None and final
                   and not req.resume_pref)
            p_cinits[slot] = req.constraint.init_state if con else 0
            p_cbudgets[slot] = req.max_new if con else 1

        active = np.asarray(
            [r is not None and r.ready for r in self._slot_req]
        )
        issue_reqs = [
            self._slot_req[i] if active[i] else None
            for i in range(self.num_slots)
        ]
        nc = len(self._cache)
        tab = self._ctables
        p_args = (  # numpy at the exact dtype: see _prefill_step
            np.asarray(p_tokens, np.int32),
            np.asarray(p_lengths, np.int32),
            np.asarray(p_starts, np.int32),
            np.asarray(is_pref, np.bool_),
            np.asarray(p_temps, np.float32),
            np.asarray(p_topps, np.float32),
            np.asarray(p_topks, np.int32),
            np.asarray(p_seeds, np.uint32),
            np.asarray(p_cinits, np.int32),
            np.asarray(p_cbudgets, np.int32),
        )
        if self._spec_draft:
            out = self._mixed_fns[t](
                self.params, *self._cache, self._hist, self._hlen,
                self._cur, self._pos, jnp.asarray(active), self._temps,
                self._topps, self._topks, self._seeds, self._counts,
                self._cstates, self._crem, *p_args, tab["next"],
                tab["need"], self._ptab,
            )
            self._cache = out[:nc]
            (self._hist, self._hlen, self._cur, self._pos, self._counts,
             self._cstates, self._crem, toks, n_emit, firsts) = out[nc:]
        else:
            out = self._mixed_fns[t](
                self.params, *self._cache, self._cur, self._pos,
                jnp.asarray(active), self._temps, self._topps, self._topks,
                self._seeds, self._counts, self._cstates, self._crem,
                *p_args, tab["next"], tab["need"], self._ptab,
            )
            self._cache = out[:nc]
            (self._cur, self._pos, self._counts, self._cstates, self._crem,
             toks, firsts) = out[nc:]
            n_emit = None
        # Both phases' analytic work attributes over THIS round's wall at
        # harvest (perfmodel.observe_mixed) — no note_prefill banking.
        avg_start = sum(p_starts[s] for s, _ in group) // len(group)
        mixed_meta = {
            "pre_rows": len(group),
            "pre_tokens": t,
            "pre_ctx": avg_start + t // 2,
        }
        rnd = self._next_round()
        span.set(round=rnd, occupancy=int(active.sum()))
        self._note_prefill_dispatch(span, len(group), t,
                                    sum(chunk_lens.values()))

        # Host tail for the chunk rows: _prefill_step's, minus the
        # prefill-role handoff branch (ragged requires phase_role=mixed).
        for slot, req in group:
            chunk_start = req.prefilled
            req.prefilled += chunk_lens[slot]
            full = req.full_ids
            if self._prefix_cache_blocks:
                self._publish_pages(slot, req, chunk_start)
            if req.prefilled < len(full):
                self._prefill_q.append((slot, req))
                continue
            if req.resume_pref:
                self._resume_ready(slot, req)
                continue
            req.ready = True
            req.ready_at = time.perf_counter()
            self._ensure_writable(slot, len(req.ids), req.page_end)
            tok = firsts[slot : slot + 1]
            cinit = (req.constraint.init_state if req.constraint is not None
                     else 0)
            (self._cur, self._pos, self._temps, self._topps, self._topks,
             self._seeds, self._counts, self._cstates,
             self._crem) = self._ready_fn(
                self._cur, self._pos, self._temps, self._topps, self._topks,
                self._seeds, self._counts, self._cstates, self._crem,
                self._ctables["next"], jnp.int32(slot), tok,
                jnp.int32(len(req.ids)),
                jnp.float32(req.temperature), jnp.float32(req.top_p),
                jnp.int32(req.top_k), jnp.uint32(req.seed & 0xFFFFFFFF),
                jnp.int32(cinit), jnp.int32(req.max_new),
            )
            req.rng_count = 1
            if self._spec_draft:
                self._hist, self._hlen = self._spec_ready_fn(
                    self._hist, self._hlen, jnp.int32(slot), tok,
                    jnp.int32(len(req.ids)),
                )
            self._first_pending.append(
                (slot, req, tok, self._slot_epoch[slot])
            )
        self._pending.append((issue_reqs, list(self._slot_epoch), toks,
                              n_emit, self._first_pending,
                              time.perf_counter(), mixed_meta, rnd))
        self._first_pending = []
        return True

    def _retire(self, slot: int, req: _Request, result: List[int]) -> None:
        """Resolve a finished request, free its slot, and reset the slot's
        on-device sampling knobs (a lingering temperature > 0 would defeat
        sample_runtime's all-greedy fast path for every later round)."""
        self._record_service_time(req)
        self._observe_terminal(req)
        if self._round_results is not None:
            self._round_results.append((req.future, result))
        else:
            req.future.set_result(result)
        self._release_slot(slot)

    def _fail_slot(self, slot: int, req: _Request, exc: Exception) -> None:
        """Retire a slot with a typed FAILURE (deadline expiry): same slot
        release as _retire, but the future carries the error."""
        self._observe_terminal(req, error=type(exc).__name__)
        req.future.set_exception(exc)
        self._release_slot(slot)

    def _observe_terminal(self, req: _Request,
                          error: Optional[str] = None) -> None:
        """Per-request terminal bookkeeping BEFORE the future resolves
        (the client reads these right after result()): flush the trace's
        scheduler spans, stamp the measured queue wait on the future (the
        Completion/metrics seam), and log the rid as retired for this
        round's flight record."""
        now = time.perf_counter()
        req.flush_spans(now)
        if req.trace is not None and error is not None:
            req.trace.event("sched.error", error=error, rid=req.rid)
        if req.admitted_at and req.submitted_at:
            req.future._lsot_queue_wait = req.admitted_at - req.submitted_at
        elif req.submitted_at:
            # Never admitted (expired/cancelled while queued or parked on
            # pool pages): its whole life WAS queue wait — page-wait
            # starvation must show up in the queue-wait span + histogram,
            # not vanish because the request never reached a slot.
            req.future._lsot_queue_wait = now - req.submitted_at
        if req.first_hold_s:
            # The rest of the worker-side TTFT, and what the prefix cache
            # spared its prefill (RequestMetrics' fields of these names).
            req.future._lsot_waits = {
                "prefill_s": req.prefill_s,
                "first_hold_s": req.first_hold_s,
                "prefix_reused_tokens": req.tokens_reused,
            }
        self._round_retired.append(req.rid)
        with self._submit_lock:
            self._pending_new_tokens = max(
                0, self._pending_new_tokens - req.max_new
            )
            self._pending_prompt_tokens = max(
                0, self._pending_prompt_tokens - len(req.ids)
            )

    def _release_slot(self, slot: int) -> None:
        self._slot_req[slot] = None
        self._slot_epoch[slot] += 1
        self._temps, self._topps, self._topks, self._cstates = self._retire_fn(
            self._temps, self._topps, self._topks, self._cstates,
            jnp.int32(slot)
        )
        # In-flight overshoot rounds still write through the page-table
        # version they were issued with; device program order puts those
        # writes BEFORE any new occupant's prefill of the freed pages, so
        # the garbage is overwritten before it can become visible.
        self._free_slot_pages(slot)

    def _append_first(self, slot: int, req: _Request, first: int,
                      epoch: Optional[int] = None) -> int:
        """Apply a harvested prefill first-token: stop/budget checks run
        here, one round late (the slot may have decoded a garbage round
        meanwhile — absorbed by the visibility invariant and submit()'s
        overshoot bound). Returns tokens appended (0/1) so the harvest's
        flight record counts prefill firsts in its emitted tally."""
        if req is not self._slot_req[slot]:
            return 0  # cleared by shutdown/crash path meanwhile
        if epoch is not None and epoch != self._slot_epoch[slot]:
            return 0  # preempted + re-admitted: a fresh arm supersedes this
        if req.cancelled:
            self._retire(slot, req, req.generated)
            return 0
        if req.past_deadline():
            # In-flight expiry rides the cancel path's timing (next
            # harvest) but fails the future with the typed error.
            resilience.inc("deadline_expired")
            self._fail_slot(slot, req, req.deadline_error())
            return 0
        if first in self.stop_ids or req.max_new < 1:
            self._retire(slot, req, req.generated)
            return 0
        req.generated.append(first)
        req.emit(first)
        if len(req.generated) >= req.max_new:
            self._retire(slot, req, req.generated)
        return 1

    def _harvest_round(self) -> None:
        """Sync the OLDEST in-flight round: one device_get brings down its
        chunk tokens plus any prefill first-tokens attached to it; retire
        finished requests and free their slots."""
        # Chaos seam (utils/faults.py): `sched:crash` kills the loop
        # MID-BATCH — rounds issued, tokens possibly already streamed to
        # clients, slots occupied. The supervisor (serve/supervisor.py)
        # must restart the loop and replay every acknowledged request
        # without duplicating delivered tokens (chaos tests assert zero
        # lost, zero double-streamed).
        FAULTS.check("sched:crash")
        pending = self._pending.popleft()
        _, _, toks_dev, n_emit_dev, firsts, _, _, rnd = pending
        # The wait for the device is a span of its own: where it is
        # near zero the host reached the harvest after the device had
        # finished, and the host set that round's pace.
        with self._stages.stage("sched.harvest_wait", round=rnd):
            fetched = jax.device_get(
                (toks_dev, n_emit_dev, [t for (_, _, t, _) in firsts])
            )
        # A request that ends in this round learns so once the round's
        # record is written: whoever waits on its future finds the round
        # that finished it in the flight ring.
        self._round_results = []
        try:
            with self._stages.stage("sched.harvest", round=rnd) as span:
                rec = self._commit_round(pending, *fetched)
                span.set(emitted=rec["emitted"])
            self._host_columns(rec)
            self.flight.record(**rec)
        finally:
            results, self._round_results = self._round_results, None
            for fut, result in results:
                fut.set_result(result)
        self._round_admitted = []
        self._round_retired = []
        if self._profile_active is not None:
            self._profile_round_done()

    def _host_columns(self, rec: Dict[str, object]) -> None:
        """What the loop did since the last round record, on the host's
        clock: `host_s` by span name (`self._stages`, taken and cleared),
        the wait for the device and the wait for work beside it — the
        three add up to the wall between two records but for the loop's
        own glue — and the prefill dispatched."""
        spans = self._stages.take()
        rec["harvest_wait_s"] = round(spans.pop("sched.harvest_wait", 0.0), 6)
        rec["idle_s"] = round(spans.pop("sched.idle", 0.0), 6)
        rec["host_s"] = {k: round(v, 6) for k, v in spans.items()}
        (rec["prefill_chunks"], rec["prefill_rows"],
         rec["prefill_tokens"]) = self._round_prefill
        self._round_prefill = [0, 0, 0]

    def _commit_round(self, pending: tuple, toks, n_emit,
                      first_vals) -> Dict[str, object]:
        """The host's work on a fetched round: append and stream its
        tokens, retire what finished, and build the round's flight
        record, which it returns."""
        (issue_reqs, epochs, _, _, firsts, t_issue, mixed_meta,
         _) = pending
        toks = np.asarray(toks)
        t_harvest = time.perf_counter()
        occupancy = sum(1 for r in issue_reqs if r is not None)
        round_emitted = 0
        # Two independent splits of the same per-round emission totals:
        # constrained/unconstrained (grammar class) and greedy/sampled
        # (sampling class — the rejection-sampling path's acceptance is
        # separately observable in the flight recorder).
        spec_emitted = {"constrained": 0, "unconstrained": 0,
                        "greedy": 0, "sampled": 0}
        # Firsts precede the round's chunk tokens in every stream: their
        # ready-scatter was dispatched before the round was issued.
        for (slot, req, _, fep), fv in zip(firsts, first_vals):
            round_emitted += self._append_first(slot, req,
                                                int(np.asarray(fv)[0]),
                                                epoch=fep)
        # Per-slot progress this round: a slot "advanced" if it appended a
        # token or reached a terminal state. A slot that advanced nothing
        # in a HARVESTED round accrues a stall round (sweep after the
        # loop): reaching harvest accounting at all proves the loop is
        # alive — a genuinely wedged loop blocks inside a jax call and is
        # the watchdog's case (stale heartbeat), never this one. The
        # common signature is one frozen lane while its batch neighbours
        # advance; a LONE frozen slot must retire too, or it pins its
        # lane until the client's deadline burns.
        advanced: List[int] = []
        no_progress: List[Tuple[int, _Request]] = []
        for i, req in enumerate(issue_reqs):
            if req is None or req is not self._slot_req[i] \
                    or epochs[i] != self._slot_epoch[i]:
                continue  # inactive at issue, retired, or preempted since
            # Mirror the slot's on-device RNG stream advance for this
            # COMMITTED round (what a preemption resume restores): vanilla
            # rounds consume one fold index per chunk token for every
            # active slot; speculative rounds consume one per SAMPLED
            # round (greedy argmax draws nothing).
            if n_emit is None:
                req.rng_count += self.decode_chunk
            elif req.temperature > 0.0:
                req.rng_count += 1
            if req.cancelled:
                self._retire(i, req, req.generated)
                advanced.append(i)
                continue
            if req.past_deadline():
                resilience.inc("deadline_expired")
                self._fail_slot(i, req, req.deadline_error())
                advanced.append(i)
                continue
            # Speculative rounds emit a variable number of accepted tokens
            # per slot; vanilla rounds emit the whole chunk row.
            if n_emit is None:
                row = toks[i]
            else:
                ne = int(n_emit[i])
                row = toks[i][:ne]
                sampled_req = req.temperature > 0.0
                cls = ("constrained" if req.constraint is not None
                       else "unconstrained")
                spec_emitted[cls] += ne
                spec_emitted["sampled" if sampled_req else "greedy"] += ne
                if ne > 0:
                    # All counters move under the scheduler's lock so
                    # speculation_stats (HTTP/metrics threads) and
                    # bench.py's pre/post delta bracketing always read a
                    # COHERENT (rounds, tokens) pair — unlocked, a reader
                    # could see rounds bumped but tokens not yet
                    # (ADVICE.md r5 #2).
                    with self._submit_lock:
                        self._spec_rounds += 1
                        self._spec_tokens += ne
                        if req.constraint is not None:
                            # Per-class splits: each pair is the named
                            # subset of the totals (the complement class
                            # is total - subset).
                            self._spec_rounds_con += 1
                            self._spec_tokens_con += ne
                        if sampled_req:
                            self._spec_rounds_samp += 1
                            self._spec_tokens_samp += ne
            if req.stall_inject:
                # Injected lane wedge (`sched:slot_stall`): the device
                # "produced nothing useful" for this slot this round.
                row = row[:0]
            before = len(req.generated)
            done = False
            for tok in row:
                tok = int(tok)
                if tok in self.stop_ids:
                    done = True
                    break
                req.generated.append(tok)
                req.emit(tok)
                if len(req.generated) >= req.max_new:
                    done = True
                    break
            appended = len(req.generated) - before
            round_emitted += appended
            if req.trace is not None:
                # One span per harvested round for sampled requests: where
                # decode time went, round by round — with the speculation
                # acceptance and grammar-mask attrs a latency regression
                # investigation starts from.
                attrs = {"emitted": appended, "rid": req.rid}
                if req.constraint is not None:
                    attrs["grammar_mask"] = True
                if n_emit is not None:
                    attrs["spec_accepted"] = int(n_emit[i])
                req.trace.add_span("sched.round", t_issue, t_harvest,
                                   **attrs)
            if done:
                self._retire(i, req, req.generated)
                advanced.append(i)
            elif len(req.generated) > before:
                req.stall_rounds = 0
                advanced.append(i)
            else:
                no_progress.append((i, req))
        if self.slot_stall_rounds and no_progress:
            for i, req in no_progress:
                if req is not self._slot_req[i]:
                    continue
                req.stall_rounds += 1
                if req.stall_rounds >= self.slot_stall_rounds:
                    self._slot_stalls += 1
                    resilience.inc("slot_stalls")
                    _log.warning(
                        "slot %d made no progress for %d harvested rounds "
                        "(%d other slot(s) advanced this round); retiring "
                        "typed", i, req.stall_rounds, len(advanced),
                    )
                    self._fail_slot(i, req, SlotStalled(
                        f"slot {i} made no progress for {req.stall_rounds} "
                        f"harvested decode rounds while the loop stayed "
                        f"live ({len(req.generated)} of {req.max_new} "
                        f"tokens generated before the lane wedged)"
                    ))
        # Overcommit's safety valve: retirements above just freed pages;
        # extend every live slot's mapping past the committed frontier +
        # overshoot BEFORE the next round can write through an unmapped
        # entry. Allocation failure preempts here (never silently drops
        # KV).
        self._topup_pages()
        self.heartbeat.round_done()
        # Flight-recorder round record (the postmortem black box): what
        # this round DID — occupancy at issue, admission/retirement churn
        # since the last record, tokens emitted (speculation split by
        # class when on), round wall (issue→harvest, pipeline lag
        # included), and the heartbeat's measured cadence. One bounded
        # append; bench prices it.
        ewma = self.heartbeat.expected_round_s()
        round_wall = round(t_harvest - t_issue, 6)
        # Monotonic accepted-token counter (ISSUE 16): the per-model
        # tok/s feed — one int add on the harvest path, read by the
        # pool's model_stats() and the lsot_model_tokens_total family.
        self._tokens_emitted_total += round_emitted
        rec = {
            "round": self.heartbeat.rounds,
            "occupancy": occupancy,
            "queued": self._queue.qsize(),
            "admitted": self._round_admitted,
            "retired": self._round_retired,
            "emitted": round_emitted,
            "round_wall_s": round_wall,
            "cadence_s": round(ewma, 6) if ewma is not None else None,
        }
        if n_emit is not None:
            rec["spec_emitted"] = spec_emitted
        if self._round_prefix:
            # Per-request reuse attribution for admissions since the last
            # record (ISSUE 14): {rid, digest, reused, prefilled} per
            # admitted request with at least one full prompt block —
            # present only on rounds that admitted such requests, so
            # records elsewhere stay byte-identical to pre-telemetry.
            rec["prefix_reuse"] = self._round_prefix
            self._round_prefix = []
        # Roofline ledger columns (ISSUE 12): this round's achieved MFU /
        # HBM-bandwidth utilization / binding-roof verdict from the shared
        # analytic model — computed from the ROUNDED wall that lands in
        # the record, so a reader (and the tier-1 reconciliation test) can
        # recompute the exact same numbers from the record alone.
        # `rows` is num_slots: the device computes EVERY slot row, parked
        # lanes included (occupancy is the goodput column beside it);
        # `perf_ctx` is the active rows' mean committed context. Spec
        # rounds are the VERIFY phase (one T=D+1 forward); the draft
        # gather is ledgered separately into the phase EWMAs.
        phase = "decode" if n_emit is None else "verify"
        tokens = (self.decode_chunk if n_emit is None
                  else self._spec_draft + 1)
        ctx_sum = sum(
            len(r.ids) + len(r.generated)
            for r in issue_reqs if r is not None
        )
        perf_ctx = max(1, ctx_sum // max(1, occupancy))
        if mixed_meta is not None:
            # Unified ragged round (LSOT_RAGGED=1): one launch did both
            # phases' work, so ONE attribution covers decode/verify rows
            # AND the chunk rows over the same wall. The record keeps the
            # chunk-side inputs so the reconciliation test can recompute
            # the ledger columns from the record alone (ragged-off
            # records never carry these keys — byte-identical to the
            # alternating control).
            phase = "mixed"
            att = self.perf.observe_mixed(
                rows=self.num_slots, dec_tokens=tokens, dec_ctx=perf_ctx,
                pre_rows=mixed_meta["pre_rows"],
                pre_tokens=mixed_meta["pre_tokens"],
                pre_ctx=mixed_meta["pre_ctx"], wall_s=round_wall,
            )
            rec["pre_rows"] = mixed_meta["pre_rows"]
            rec["pre_tokens"] = mixed_meta["pre_tokens"]
            rec["pre_ctx"] = mixed_meta["pre_ctx"]
        else:
            att = self.perf.observe(phase, rows=self.num_slots,
                                    tokens=tokens, ctx=perf_ctx,
                                    wall_s=round_wall)
        rec["phase"] = phase
        rec["perf_ctx"] = perf_ctx
        rec["mfu"] = att["mfu"]
        rec["hbm_util"] = att["hbm_util"]
        rec["bound"] = att["bound"]
        if n_emit is not None and self._spec_draft:
            self.perf.observe("draft", rows=self.num_slots,
                              tokens=self._spec_draft,
                              ctx=int(self._hist.shape[1]),
                              wall_s=round_wall)
        # Prefill chunks dispatched since the last harvest attribute over
        # the inter-harvest wall (the live prefill-vs-decode asymmetry
        # signal the disaggregation ROADMAP item needs per replica).
        interval = round(
            t_harvest - (self._last_harvest_t
                         if self._last_harvest_t is not None else t_issue),
            6,
        )
        self._last_harvest_t = t_harvest
        pre = self.perf.flush_prefill(interval)
        if pre is not None:
            rec["prefill_mfu"] = pre["mfu"]
            rec["prefill_hbm_util"] = pre["hbm_util"]
        # Page-pool occupancy per round: the flight-recorder column a
        # leaked page shows up in (pages_in_use that never drains while
        # occupancy does). kv_pressure is the injected withheld reserve
        # (kv:pressure chaos site) — the column a preemption storm
        # postmortem reads next to the preempt/resume events.
        rec["kv_pages"] = self._page_alloc.pages_in_use
        rec["kv_pages_free"] = self._page_alloc.pages_free
        rec["kv_pressure"] = self._page_alloc.withheld
        if self._mig_pages:
            # Handoff columns (ISSUE 13 satellite): pages imported since
            # the last record and the decode-slot wait they carried —
            # present only on rounds that actually imported, so a mixed
            # replica's records stay byte-identical to pre-disagg.
            rec["pages_migrated"] = self._mig_pages
            rec["handoff_wait_s"] = round(self._mig_wait, 6)
            self._mig_pages = 0
            self._mig_wait = 0.0
        return rec

    def _harvest_firsts(self) -> None:
        """Drain path: ready slots whose first token never rode a round."""
        if not self._first_pending:
            return
        firsts, self._first_pending = self._first_pending, []
        with self._stages.stage("sched.harvest_wait"):  # of no round
            vals = jax.device_get([t for (_, _, t, _) in firsts])
        for (slot, req, _, fep), fv in zip(firsts, vals):
            self._append_first(slot, req, int(np.asarray(fv)[0]), epoch=fep)

    def _run(self) -> None:
        try:
            self._loop()
            self._close(RuntimeError("scheduler shut down mid-request"))
        except BaseException as exc:  # noqa: BLE001 — a dead loop must not hang clients
            # Fail everything with the TYPED crash error (original
            # traceback attached): callers distinguish "engine dead" (503,
            # breaker-relevant) from a per-request failure (500).
            wrapped = SchedulerCrashed.from_exception(exc)
            self._crash = wrapped
            # Black-box marker: the postmortem dump shows the crash beside
            # the rounds that led up to it.
            self.flight.event("crash", error=str(exc)[:200],
                              error_type=type(exc).__name__)
            self._close(wrapped)
            raise

    def _close(self, exc: BaseException) -> None:
        """Fail every in-flight and queued request; reject future submits."""
        with self._submit_lock:
            self._closed = True
            self._pending_new_tokens = 0
            ready, self._ready = self._ready, []
        for req in ready:  # staged in the WFQ pool when the loop died
            req.future.set_exception(exc)
        self._prefill_q.clear()  # their requests fail via the slot sweep below
        self._pending.clear()    # in-flight rounds: futures fail below
        self._first_pending = []
        self._handoff_pending = []  # still slot-held: the sweep covers them
        for req in self._handoff:
            # Parked in the handoff queue when the loop died: the blob is
            # lost with this replica — fail typed so the supervisor's
            # journal re-prefills the request on a sibling.
            req.future.set_exception(exc)
        self._handoff.clear()
        for req in self._constraint_wait:  # waiting on a grammar swap
            req.future.set_exception(exc)
        self._constraint_wait.clear()
        for req in self._page_wait:  # waiting on pool pages
            req.future.set_exception(exc)
        self._page_wait.clear()
        for i, req in enumerate(self._slot_req):
            if req is not None:
                req.future.set_exception(exc)
                self._slot_req[i] = None
                if self._slot_pages[i]:
                    # Host-side release only — no device work on a possibly
                    # wedged path. The device table rows go stale; start()
                    # re-syncs them before the loop serves again.
                    self._page_alloc.release(self._slot_pages[i])
                    self._slot_pages[i] = []
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.future.set_exception(exc)
        # Last, with every client answered: an armed/mid-flight
        # /debug/profile capture must not leak the fleet-wide guard past
        # the loop that owned it.
        self._abort_profile(f"scheduler closed: {type(exc).__name__}")

    def _busy_now(self) -> bool:
        """Work anywhere in the pipeline: the busy flag the event loop
        stamps into the heartbeat each iteration. A method (not inlined
        in `_loop`) so bench's `_watchdog_overhead` can time the FULL
        per-iteration liveness cost — this scan plus the stamp — instead
        of the stamp alone."""
        return bool(
            self._prefill_q or self._pending or self._constraint_wait
            or self._handoff or self._handoff_pending
            or self._page_wait
            or any(r is not None for r in self._slot_req)
            or not self._queue.empty()
            or self._ready
        )

    def _loop(self) -> None:
        while not self._stop_evt.is_set():
            # One pass is the profiler's step: a round's issue and its
            # harvest are `_harvest_lag` passes apart, so a round cannot
            # be one.
            self._loop_passes += 1
            with StageTimer.step("sched.loop", self._loop_passes):
                self._loop_pass()

    def _loop_pass(self) -> None:
        """One pass of the serving loop: upkeep, admission, at most one
        prompt chunk batch, one decode round issued and the oldest one
        harvested — or, with nothing to issue, a drain and a wait for
        work. Each stage is a span of `self._stages`."""
        # Liveness stamp FIRST, so a wedge anywhere below (a hung XLA
        # dispatch in prefill/decode, a stuck device_get in harvest)
        # leaves a stale busy stamp for the watchdog to age. Idle
        # iterations stamp busy=False every <=50ms (the queue.get
        # timeout below), so an idle loop never looks wedged.
        self.heartbeat.stamp(busy=self._busy_now())
        # Pressure-relief upkeep, every iteration (cheap int math when
        # nothing is happening): sample the kv:pressure chaos site, evict
        # prefix pages down to the high watermark when free pages dip
        # under the low one, and fail page-starved waiters whose deadline
        # burned (they would otherwise wait forever while slots stay
        # busy).
        with self._stages.stage("sched.upkeep"):
            self._sample_pressure()
            self._watermark_sweep()
            self._sweep_page_wait()
        # Admit pending requests into every free slot, then issue one
        # prompt chunk and one decode round — all asynchronously — and
        # harvest the oldest round once the pipeline is `_harvest_lag`
        # deep. When fully idle, drain and block for work. Requests
        # whose grammar differs from the installed one wait in
        # `_constraint_wait` until the constrained slots drain (the
        # table swap must not move live FSM states between grammars),
        # then install and admit in arrival order. Fairness: while
        # waiters exist, NEW constrained requests also queue behind
        # them (even for the currently installed grammar) — otherwise
        # a steady same-grammar stream keeps _constrained_busy() true
        # forever and a different-grammar waiter starves. Waiters
        # matching the installed grammar admit immediately (no drain
        # needed); unconstrained traffic always flows directly.
        with self._stages.stage("sched.admit") as span:
            admitted = len(self._round_admitted)
            while self._free_slots():
                wait = self._constraint_wait
                if wait and self._grammar_matches(wait[0].constraint):
                    req = wait.popleft()
                elif wait and not self._constrained_busy():
                    req = wait.popleft()
                    self._install_constraint(req.constraint)
                else:
                    if self._page_wait:
                        # Page-starved requests re-admit ahead of the
                        # queue the moment retirements free pages — FIFO
                        # with QoS off, WFQ order (victims first) with it
                        # on. They already passed grammar routing once,
                        # and re-routing below keeps them correct if the
                        # installed grammar changed meanwhile.
                        req = self._page_wait_pop()
                    elif self._qos:
                        # WFQ admission (ISSUE 18): stage every queued
                        # submit in the ready pool, serve the smallest
                        # virtual finish time. QoS off takes the exact
                        # pre-QoS get_nowait path below.
                        self._drain_ready()
                        req = self._ready_pop()
                        if req is None:
                            break
                    else:
                        try:
                            req = self._queue.get_nowait()
                        except queue.Empty:
                            break
                        if req is None:
                            continue
                    c = req.constraint
                    if c is not None and (not self._grammar_matches(c)
                                          or wait):
                        if self._constrained_busy() or wait:
                            wait.append(req)
                            continue
                        self._install_constraint(c)
                if not self._admit(self._free_slots()[0], req):
                    # The pool cannot hold this request's envelope
                    # until live slots retire — park it at the FRONT of
                    # the page-wait line (admission order preserved) and
                    # stop admitting; decode/harvest below keep the pipe
                    # moving and will free pages.
                    self._page_wait.appendleft(req)
                    break
            span.set(admitted=len(self._round_admitted) - admitted,
                     queued=self._queue.qsize())
        # Unified ragged round (LSOT_RAGGED=1, ISSUE 19): fold this
        # iteration's prompt chunks INTO the decode launch — one
        # compiled program, no phase alternation, the off-phase
        # never idles. Falls through to the alternating path when
        # every queued prefill entry was stale, so decode never
        # stalls behind an empty mix.
        if self._ragged and self._prefill_q:
            if self._profile_arm is not None:
                self._maybe_start_profile()
            with self._stages.stage("sched.issue_mixed") as span:
                issued = self._issue_mixed(span)
            if issued:
                if len(self._pending) > self._harvest_lag:
                    self._harvest_round()
                return
        # Fair interleave: at most one prompt chunk per decode round —
        # admission work is bounded, so active slots never wait longer
        # than one prompt_bucket forward.
        if self._prefill_q:
            with self._stages.stage("sched.prefill_dispatch") as span:
                self._prefill_step(span)
        if self._handoff_pending:
            # Prefill-role terminal step: commit first tokens, pack
            # blobs, wake the pool's placement pump (mixed/decode
            # replicas never queue anything here).
            self._pack_handoffs()
        if any(r is not None and r.ready for r in self._slot_req):
            if self._profile_arm is not None:
                # Armed /debug/profile capture: count the next N rounds.
                self._maybe_start_profile()
            with self._stages.stage("sched.issue_decode") as span:
                self._issue_decode(span)
            if len(self._pending) > self._harvest_lag:
                self._harvest_round()
        elif not self._prefill_q:
            # Nothing left to issue: drain in-flight rounds and any
            # unridden first tokens, then wait for new requests.
            while self._pending:
                self._harvest_round()
            self._harvest_firsts()
            if self._prefill_q or self._constraint_wait or any(
                r is not None for r in self._slot_req
            ) or self._page_wait or self._ready:
                return  # harvests freed work — go admit/issue again
            try:
                with self._stages.stage("sched.idle"):
                    req = self._queue.get(timeout=0.05)
            except queue.Empty:
                if self._profile_arm or self._profile_active:
                    self._expire_profile()
                return
            if req is not None:
                with self._stages.stage("sched.admit", admitted=1, queued=0):
                    # Fully idle here (no slots, no waiters), so a new
                    # grammar can install immediately.
                    c = req.constraint
                    if c is not None and not self._grammar_matches(c):
                        self._install_constraint(c)
                    if not self._admit(self._free_slots()[0], req):
                        # Fully idle: can only mean the pool
                        # itself is smaller than one request envelope
                        # after eviction — park it like the loop does.
                        self._page_wait.appendleft(req)


@dataclasses.dataclass
class _ReplicaState:
    """One replica's supervision state inside a SchedulerPool fleet.

    `state` lifecycle: ready → (crash/stall) → restarting → ready |
    degraded | dead, plus the runtime-ops states draining (drain_replica
    in progress) / drained (drained, restartable) / removed
    (remove_replica: permanently out of the fleet). Placement considers
    only ready/degraded replicas; `degraded` means "restarted, not yet
    proven by a clean completion" and clears on the next success placed
    there."""

    label: str
    state: str = "ready"
    restarts: int = 0
    stalls: int = 0
    placements: int = 0
    restart_eta: Optional[float] = None
    last_crash: Optional[str] = None
    #: Multi-model axis (ISSUE 16) beside phase_role: which registered
    #: checkpoint this replica holds ("" = the single-model fleet).
    #: Captured at wiring time so placement can filter on it even while
    #: the scheduler object is mid-restart-swap.
    model_id: str = ""

    #: States a replica can take new work in.
    PLACEABLE = ("ready", "degraded")


class SchedulerPool:
    """dp>1 for continuous batching: a supervised FLEET of independent
    scheduler replicas behind one `submit()`.

    The slot axis can't shard over a mesh "dp" axis (slots are dynamically
    indexed per request), so data parallelism is request-level: each replica
    owns its own params placement — typically a disjoint tp-submesh of the
    same slice. This is the scale-out story SURVEY.md §2.4 calls "DP /
    request-level parallelism", played by scheduler replicas instead of
    Ollama instances.

    Fleet semantics (ISSUE 9 — what turns "a scheduler" into "a fleet"):

    - **Load-aware placement.** `submit()` routes each request to the
      least-loaded placeable replica, scored by the SAME queue-depth ×
      service-time EWMA the Retry-After hint quotes shed clients
      (`backlog_score()`: unclamped seconds estimate, token-weighted
      backlog as the tie-break). Replicas that are restarting, draining,
      dead, or crashed are skipped; replicas whose backlog estimate would
      blow the request's own deadline are skipped too. A request is shed
      typed — Overloaded/429 or DeadlineExceeded/504 — only when NO
      replica can serve it, with the honest minimum Retry-After across
      the fleet (one full replica no longer rejects while a sibling has
      room). `router="round_robin"` keeps the pre-fleet blind rotation
      (the bench's comparison baseline).
    - **Per-replica lifecycle.** With a `factory` (index → fresh replica),
      each replica carries its own supervision state (`_ReplicaState`):
      a crash or watchdog-flagged stall escalates to a TARGETED restart —
      bounded-backoff rebuild of that one replica under a per-replica
      restart budget — while siblings keep serving uninterrupted. Budget
      exhausted marks only that replica `dead`. The `on_replica_restart`/
      `on_replica_drained` callbacks are the supervisor's replay seam:
      a SupervisedScheduler wrapping this pool re-places ONLY the wedged
      replica's journaled requests (serve/supervisor.py), so one bad
      replica no longer restarts — and replays — the whole fleet.
    - **Runtime drain/remove.** `drain_replica()` takes one replica out
      of rotation at runtime: its queued-not-yet-admitted requests
      re-place onto the least-loaded siblings (never shed), in-flight
      work gets a bounded grace, then the replica shuts down. SIGTERM
      semantics at the POOL level are unchanged — `shutdown()`/the
      supervisor's drain still govern whole-process exit.
    - **Observable.** Placement decisions and replica lifecycle events
      land in a pool-level flight recorder (merged into
      `flight_snapshot()`), per-replica health in `health()` /
      `replica_loads()` (Prometheus picks the numeric fields up under
      the shared `r{i}` label vocabulary), and per-replica stall
      verdicts in `heartbeat.verdicts()` / `stalled_replicas()`.
    """

    #: Duck-typing flag the supervisor keys targeted restart/replay on.
    @property
    def supports_replica_restart(self) -> bool:
        return self._factory is not None

    def __init__(
        self,
        schedulers: Sequence[ContinuousBatchingScheduler],
        factory: Optional[Callable] = None,
        max_restarts: int = 5,
        restart_policy=None,
        rng=None,
        sleep: Callable[[float], None] = time.sleep,
        router: str = "least_loaded",
        replica_join_s: float = 1.0,
        # Cache-aware routing (ISSUE 15): consume `prefix_affinity` in
        # the placement order — affinity → pressure penalty → weighted
        # least-loaded tie-break. None reads LSOT_POOL_AFFINITY (default
        # ON); 0/False reproduces the pre-affinity order bit for bit
        # (no digest lookups, no affinity flight events).
        affinity_routing: Optional[bool] = None,
        # Heterogeneous replica weights: replica i's serving capacity
        # relative to its siblings (a tp=4 replica takes proportionally
        # more token mass than a tp=1 sibling — its backlog is DIVIDED
        # by its weight before comparison). None reads
        # LSOT_REPLICA_WEIGHTS ("4,1,1" by index); all-1.0 (the default)
        # is bit-identical to the unweighted order.
        weights: Optional[Sequence[float]] = None,
        # Remote-replica lease (serve/remote.py): ping every transport
        # replica each `lease_s`; `lease_misses` consecutive failures
        # expire the lease — the replica is declared unreachable and its
        # journaled work re-places on siblings. None reads LSOT_LEASE_S /
        # LSOT_LEASE_MISSES; lease_s <= 0 disables the monitor.
        lease_s: Optional[float] = None,
        lease_misses: Optional[int] = None,
        # Multi-model routing (ISSUE 16): requests naming a model_id are
        # placed only on replicas carrying that checkpoint (model →
        # affinity → pressure → weighted least-loaded). None reads
        # LSOT_POOL_MODELS (default ON); 0/False — or requests that
        # never name a model — reproduce the single-model placement
        # order bit for bit.
        model_routing: Optional[bool] = None,
    ):
        if not schedulers:
            raise ValueError("SchedulerPool needs at least one scheduler")
        if router not in ("least_loaded", "round_robin"):
            raise ValueError(
                f"router must be 'least_loaded' or 'round_robin', got "
                f"{router!r}"
            )
        import random as _random

        from .resilience import RetryPolicy

        self.schedulers = list(schedulers)
        self._rr = 0
        self._lock = threading.Lock()
        self._closed = False
        self.router = router
        # Targeted-restart machinery: `factory` builds replacement replica
        # i on demand — either `factory(i)` (per-replica meshes/placement)
        # or `factory()` when it takes no required argument. None disables
        # per-replica restart (a crashed replica is marked dead and
        # skipped, the pre-fleet behavior).
        self._factory = factory
        self._factory_takes_index = False
        if factory is not None:
            import inspect

            try:
                params = inspect.signature(factory).parameters.values()
                self._factory_takes_index = any(
                    p.default is inspect.Parameter.empty
                    and p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                    for p in params
                )
            except (TypeError, ValueError):
                self._factory_takes_index = False
        self.max_restarts = int(max_restarts)
        self._restart_policy = restart_policy or RetryPolicy(
            max_attempts=self.max_restarts + 1, base_delay_s=0.1,
            max_delay_s=5.0,
        )
        self._rng = rng if rng is not None else _random.Random()
        self._sleep = sleep
        # Bounded join for a wedged replica's teardown: a targeted restart
        # must not block its driver for the length of the hang it is
        # recovering from (the abandoned daemon zombie exits when it
        # unwedges — same contract as the supervisor's teardown).
        self._replica_join_s = float(replica_join_s)
        # Replay seams for a wrapping SupervisedScheduler: called with the
        # replica LABEL after a targeted restart swap / a drain shutdown,
        # so the supervisor re-places exactly that replica's journaled
        # requests onto the (now current) fleet.
        self.on_replica_restart: Optional[Callable[[str], None]] = None
        self.on_replica_drained: Optional[Callable[[str], None]] = None
        # Attribute each replica's flight records: a pool's merged
        # postmortem/debug view must say WHICH replica's rounds these were
        # (the load-signal feed the multi-replica ROADMAP item needs).
        # "r{i}" matches the single-scheduler recorder default ("r0") and
        # the Prometheus exposition's per-replica label scheme, so the
        # histogram and serving-gauge families join on `replica`.
        self._states: List[_ReplicaState] = []
        for i, s in enumerate(self.schedulers):
            label = f"r{i}"
            fl = getattr(s, "flight", None)
            if fl is not None:
                fl.replica = label
            self._states.append(_ReplicaState(
                label=label, model_id=self._model_id(s)))
            # Disaggregation (ISSUE 13): a prefill-role replica's packed
            # handoffs drain through the pool's phase-aware placement
            # pump (re-wired after every restart swap).
            self._wire_handoff(i, s)
        # Pool-level black box: placement decisions + replica lifecycle
        # events (restart/drain/dead), merged into flight_snapshot() so
        # the postmortem timeline shows WHERE every request went and what
        # the fleet did about failures.
        self._pool_flight = FlightRecorder(capacity=256, replica="pool")
        # Cache-aware routing flip (ISSUE 15): ON by default — the PR-14
        # feed (resident digests + hit-rate EWMAs) is now consumed by
        # submit(); LSOT_POOL_AFFINITY=0 restores the pre-affinity
        # placement order bit for bit.
        if affinity_routing is None:
            affinity_routing = os.environ.get(
                "LSOT_POOL_AFFINITY", "1").strip().lower() not in (
                    "0", "false", "no", "off")
        self._affinity = bool(affinity_routing)
        self._aff_checked = 0
        self._aff_hits = 0
        # Multi-model routing flip (ISSUE 16): ON by default, but inert
        # until a request names a model_id — LSOT_POOL_MODELS=0 makes
        # even named requests fall through to the model-blind order.
        if model_routing is None:
            model_routing = os.environ.get(
                "LSOT_POOL_MODELS", "1").strip().lower() not in (
                    "0", "false", "no", "off")
        self._model_routing = bool(model_routing)
        # Per-model throughput attribution (model_stats): last observed
        # (wall, tokens_total) per model, so successive scrapes read a
        # live tok/s without a sampling thread.
        self._model_rate: Dict[str, Tuple[float, int]] = {}
        # Heterogeneous replica weights: capacity multipliers by index
        # (missing entries default 1.0; weights must be positive).
        if weights is None:
            self._weights = parse_replica_weights(
                os.environ.get("LSOT_REPLICA_WEIGHTS", ""),
                len(self.schedulers),
            )
        else:
            # Same pad/validate policy as the env-spec path — an
            # overlong explicit list raises instead of silently
            # truncating a misconfigured fleet.
            self._weights = normalize_replica_weights(
                list(weights), len(self.schedulers))
        # Remote-replica lease monitor (serve/remote.py): started lazily
        # at start() when any replica exposes the lease surface.
        self._lease_s = (float(os.environ.get("LSOT_LEASE_S", "2.0"))
                         if lease_s is None else float(lease_s))
        self._lease_misses = (int(os.environ.get("LSOT_LEASE_MISSES", "3"))
                              if lease_misses is None
                              else int(lease_misses))
        self._lease_stop = threading.Event()
        self._lease_thread: Optional[threading.Thread] = None
        # Live targeted-restart driver threads: shutdown() joins them so
        # a pool teardown racing a rebuild does not leave a daemon
        # thread inside an XLA compile when the process exits (a C++
        # abort at interpreter teardown, seen in the chaos suites).
        self._restart_threads: List[threading.Thread] = []
        # Elastic fleet membership (ISSUE 17): lifecycle counters +
        # push-handoff latency ledger behind fleet_stats()/lsot_fleet_*,
        # plus the constraint-resolver seam a pushed constrained handoff
        # needs when its target is a remote transport (the wire carries
        # the spec; the receiving client rebuilds the matcher).
        self.constraint_resolver: Optional[Callable] = None
        self._fleet_joins = 0
        self._fleet_retires = 0
        self._fleet_drain_s_sum = 0.0
        self._fleet_drain_count = 0
        self._push_lat = deque(maxlen=4096)
        # Indices the autoscaler added — only these are eligible for
        # scale-down, so an operator-configured replica never retires.
        self._elastic: set = set()
        # Startup handshake (ISSUE 17): a remote joiner whose page
        # geometry / model set cannot co-serve this fleet is marked dead
        # BEFORE placement can route a request into it.
        for i, s in enumerate(self.schedulers):
            self._validate_join(i, s)

    # Admission-arithmetic surface, so SchedulerBackend can wrap a pool the
    # same way it wraps one scheduler (replicas are homogeneous: same cfg,
    # window, chunking — submit() re-validates on the chosen replica).
    @property
    def cfg(self):
        return self.schedulers[0].cfg

    @property
    def max_seq(self) -> int:
        return self.schedulers[0].max_seq

    @property
    def decode_chunk(self) -> int:
        return self.schedulers[0].decode_chunk

    @property
    def stop_ids(self):
        return self.schedulers[0].stop_ids

    @property
    def _spec_draft(self) -> int:
        # Replicas are homogeneous; SchedulerBackend's constrain guard
        # reads this through the pool exactly like a single scheduler.
        return self.schedulers[0]._spec_draft

    @property
    def prompt_bucket(self) -> int:
        return self.schedulers[0].prompt_bucket

    @property
    def _harvest_lag(self) -> int:
        return self.schedulers[0]._harvest_lag

    @property
    def overshoot(self) -> int:
        return self.schedulers[0].overshoot

    def retry_after_hint(self) -> float:
        """Soonest-available replica's hint, restart-aware: min over
        PLACEABLE replicas' queue-drain estimates, with a RESTARTING
        replica contributing its restart-backoff remaining instead of
        its stale EWMA over a frozen queue (the per-replica twin of the
        PR-5 supervisor clamp — before this fix a restarting replica's
        frozen estimate could drive the pool-wide minimum). Draining,
        dead, and removed replicas contribute nothing: they are never
        coming back for this client."""
        now = time.monotonic()
        hints: List[float] = []
        for st, s in self._replica_items():
            if st.state in _ReplicaState.PLACEABLE:
                if getattr(s, "_crash", None) is not None:
                    continue
                hint = getattr(s, "retry_after_hint", None)
                try:
                    hints.append(hint() if callable(hint) else 1.0)
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    hints.append(1.0)
            elif st.state == "restarting":
                eta = st.restart_eta
                rem = (eta - now) if eta is not None else 1.0
                hints.append(float(min(60.0, max(1.0, rem))))
        if not hints:
            return 1.0
        # Same [1, 60] s clamp as the per-scheduler estimate, so a
        # duck-typed replica's raw hint can't quote sub-second retries.
        return float(min(60.0, max(1.0, min(hints))))

    def warmup(self, prompt_len=None) -> None:
        for s in self.schedulers:
            warm = getattr(s, "warmup", None)
            if callable(warm):
                warm(prompt_len)

    @property
    def heartbeat(self) -> CombinedHeartbeat:
        """Monitor view over the replicas' heartbeats: one wedged replica
        reads stale (oldest busy age) even while its siblings stamp, so
        the supervisor's watchdog covers pools with the same code path.
        Labeled with the replica vocabulary, so `verdicts()` (and the
        snapshot's replicas list) attribute staleness to the replica
        that went quiet — the targeted-restart feed."""
        hbs, labels = [], []
        for st, s in zip(self._states, self.schedulers):
            hb = getattr(s, "heartbeat", None)
            if hb is not None:
                hbs.append(hb)
                labels.append(st.label)
        if not hbs:
            # All-duck-typed fleet with no liveness stamps: None, so the
            # supervisor's `getattr(inner, "heartbeat", None)` callers
            # degrade to no-monitoring instead of a ValueError from an
            # empty CombinedHeartbeat.
            return None
        return CombinedHeartbeat(hbs, labels=labels)

    @property
    def watchdog_stats(self) -> Dict[str, object]:
        hb = self.heartbeat
        return {
            "heartbeat": hb.snapshot() if hb is not None else None,
            "slots_retired_stalled": sum(
                getattr(s, "_slot_stalls", 0) for s in self.schedulers
            ),
        }

    @property
    def page_stats(self) -> Optional[Dict[str, int]]:
        """Summed page-pool stats across replicas (None when no replica
        reports one: duck-typed stand-ins) — each replica owns an
        independent pool, so totals add."""
        per = [s.page_stats for s in self.schedulers
               if getattr(s, "page_stats", None)]
        if not per:
            return None
        out: Dict[str, int] = {}
        for st in per:
            for k, v in st.items():
                if isinstance(v, str):
                    continue  # non-numeric knobs (kv_quant) keep-first below
                out[k] = out.get(k, 0) + int(v)
        # Ratios/sizes/knobs/thresholds don't sum: keep the first
        # replica's values (homogeneous fleets; heterogeneous knobs show
        # per replica in replica_loads — a summed watermark compared
        # against summed free pages would misread per-pool pressure).
        for k in ("page_size", "overcommit", "spill", "kv_quant",
                  "page_bytes", "watermark_low_pages",
                  "watermark_high_pages", "kv_pool_lane_pack",
                  "kv_pool_shape"):
            if k in per[0]:
                out[k] = per[0][k]
        return out

    @property
    def perf_stats(self) -> Optional[Dict[str, object]]:
        """Per-replica roofline ledgers (utils/perfmodel.py), labeled —
        the Prometheus lsot_mfu/lsot_hbm_util gauges render phase ×
        replica from this list. None when no replica ledgers (duck-typed
        toy fleets)."""
        per = []
        for st, s in self._replica_items():
            p = getattr(s, "perf_stats", None)
            if isinstance(p, dict):
                rec = dict(p)
                rec["replica"] = st.label
                per.append(rec)
        return {"replicas": per} if per else None

    def profile_rounds(self, rounds: Optional[int] = None,
                       out_dir: Optional[str] = None,
                       replica: Optional[str] = None) -> Dict[str, object]:
        """Arm an on-demand device capture on ONE replica (the named one,
        else the first placeable) — the process-wide guard in
        utils/traceprof already enforces at most one capture in flight
        across the whole fleet."""
        for st, s in self._replica_items():
            if replica is not None and st.label != replica:
                continue
            fn = getattr(s, "profile_rounds", None)
            if callable(fn) and (replica is not None
                                 or st.state in _ReplicaState.PLACEABLE):
                return fn(rounds, out_dir)
        raise ValueError(
            f"no {'replica ' + replica if replica else 'placeable replica'}"
            f" exposes device profiling"
        )

    def profile_status(self) -> Dict[str, object]:
        out: Dict[str, object] = {}
        for st, s in self._replica_items():
            fn = getattr(s, "profile_status", None)
            if callable(fn):
                out[st.label] = fn()
        return out

    @property
    def flight(self):
        """First replica's recorder (single-scheduler duck typing);
        flight_snapshot() is the merged pool view."""
        return self.schedulers[0].flight

    def flight_snapshot(self, last: Optional[int] = None) -> List[Dict]:
        """All replicas' flight records merged in time order — each
        record carries its replica label, so the pool view attributes
        every round to the replica that ran it. The pool's own recorder
        (placement decisions, replica restart/drain/dead lifecycle) rides
        the merge under the "pool" label."""
        return merge_snapshots([self._pool_flight, *self.schedulers], last)

    def flight_stats(self) -> Dict[str, Dict]:
        """Per-replica ring occupancy for /metrics: without this seam the
        backend's duck-typed `.flight` read would surface replica 0's
        counters only, hiding r1..rN's fill/overwrite on a dp>1 pool."""
        out: Dict[str, Dict] = {}
        for i, s in enumerate(self.schedulers):
            fl = getattr(s, "flight", None)
            if fl is not None:
                out[getattr(fl, "replica", f"r{i}")] = fl.stats()
        return out

    def replica_loads(self) -> List[Dict[str, object]]:
        """Per-replica load + lifecycle attribution (queue depth, live
        slots, round cadence, supervision state, restart/stall/placement
        counters, the live placement score): the feed the least-loaded
        router consumes, exported per replica under the shared `r{i}`
        label vocabulary (numeric fields become Prometheus gauges)."""
        out = []
        for st, s in self._replica_items():
            hb = getattr(s, "heartbeat", None)
            hb_snap = hb.snapshot() if hb is not None else {}
            secs, toks = self._score(s)
            q = getattr(s, "_queue", None)
            slot_req = getattr(s, "_slot_req", None) or []
            rec: Dict[str, object] = {
                "replica": st.label,
                "state": st.state,
                "num_slots": getattr(s, "num_slots", 0),
                "expected_round_s": hb_snap.get("expected_round_s"),
                "crashed": getattr(s, "_crash", None) is not None,
                "restarts": st.restarts,
                "stalls": st.stalls,
                "placements": st.placements,
                "backlog_s": round(secs, 4),
                "pending_new_tokens": toks,
            }
            # Queue depth / live slots: read in-process when the replica
            # is local; a socket transport has neither attribute, so the
            # keys stay unset here and the loads-digest merge below fills
            # them from the worker's piggybacked numbers — the elastic
            # autoscaler's queue-EWMA signal (serve/elastic.py) must see
            # REMOTE decode backlog, not a shadowing local zero.
            if q is not None:
                rec["queued"] = q.qsize()
            if slot_req:
                rec["active_slots"] = sum(
                    1 for r in slot_req if r is not None)
            hint = getattr(s, "retry_after_hint", None)
            if callable(hint) and st.state in _ReplicaState.PLACEABLE:
                try:
                    rec["retry_after_s"] = round(hint(), 3)
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    pass
            # Paged-KV pressure gauges under the shared r{i} label
            # vocabulary (numeric fields become per-replica Prometheus
            # gauges): which replica is preempting/evicting, and how
            # close each pool is to its watermarks.
            pstats = getattr(s, "page_stats", None)
            if pstats:
                rec["kv_pages_free"] = pstats["pages_free"]
                rec["kv_pages_withheld"] = pstats["pages_withheld"]
                rec["kv_preemptions"] = pstats["preemptions"]
                rec["kv_evictions"] = pstats["evictions"]
                rec["kv_spilled_pages"] = pstats["spilled_pages"]
                rec["kv_watermark_low_pages"] = \
                    pstats["watermark_low_pages"]
                rec["kv_watermark_high_pages"] = \
                    pstats["watermark_high_pages"]
            # Prefix-cache residency feed (ISSUE 14): the replica's live
            # hit-rate EWMA (a numeric gauge under the shared r{i} label
            # vocabulary) and its hottest-K resident digest set (JSON
            # only — strings never become Prometheus samples). This is
            # the per-replica half of the cache-aware routing feed;
            # prefix_affinity() is the lookup over it.
            ptel = getattr(s, "prefix_telemetry", None)
            if isinstance(ptel, dict):
                rec["prefix_hit_rate"] = ptel.get("hit_rate_ewma", 0.0)
                rec["prefix_resident_entries"] = \
                    ptel.get("resident_entries", 0)
            digs = getattr(s, "resident_digests", None)
            if callable(digs):
                try:
                    # No explicit limit: the replica's own configured
                    # top-K bound applies, so this export and
                    # prefix_affinity() see the SAME resident set.
                    rec["resident_digests"] = digs()
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    pass
            # Disaggregation (ISSUE 13): which phase this replica serves
            # and its handoff traffic — the router's placement feed and
            # the per-replica lsot_serving_* gauges.
            rec["phase_role"] = self._phase_role(s)
            # Multi-model axis (ISSUE 16): which checkpoint the replica
            # holds — the model router's placement feed, carried beside
            # phase_role in loads/health views (and across the remote
            # transport via describe_scheduler's digest).
            rec["model_id"] = st.model_id or self._model_id(s)
            ho = getattr(s, "handoff_stats", None)
            if isinstance(ho, dict):
                rec["handoff_exports"] = ho["exports"]
                rec["handoff_imports"] = ho["imports"]
                rec["handoff_queued"] = ho["queued_handoffs"]
            # Roofline + SLO placement signals (ISSUE 12): the replica's
            # live decode roofline position and whether its rolling SLO
            # is burning — the columns the phase-aware router consumes
            # (decode_hbm_util is _decode_pressure's feed), exported per
            # replica like every other numeric field here.
            perf = getattr(s, "perf_stats", None)
            if isinstance(perf, dict):
                dec = (perf.get("phases") or {}).get("decode")
                if dec:
                    rec["decode_mfu"] = dec.get("mfu")
                    rec["decode_hbm_util"] = dec.get("hbm_util")
            try:
                from ..utils import slo as _slo

                if _slo.ENGINE.enabled:
                    rec["slo_burning"] = bool(
                        _slo.ENGINE.replica_burning(st.label)
                    )
            except Exception:  # noqa: BLE001 — placement view best-effort
                pass
            # Remote replicas (ISSUE 15): a socket transport has no
            # in-process attributes to read — merge its cached loads
            # digest (refreshed by every lease ping / rpc ack) without
            # overwriting anything read directly above.
            ld = getattr(s, "loads_digest", None)
            if callable(ld):
                try:
                    for k, v in ld().items():
                        rec.setdefault(k, v)
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    pass
            # Key-presence contract: every record carries the load pair
            # even when neither the local read nor the digest had it.
            rec.setdefault("queued", 0)
            rec.setdefault("active_slots", 0)
            # Transport attribution: which wire this replica is behind
            # and how it is behaving (rpc/retry/timeout totals, lease
            # state) — the per-replica half of serving.transport.
            ts = getattr(s, "transport_stats", None)
            if callable(ts):
                try:
                    rec["transport"] = self._transport_summary(ts())
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    pass
            idx = next((j for j, x in enumerate(self._states) if x is st),
                       -1)
            if 0 <= idx < len(self._weights) \
                    and self._weights[idx] != 1.0:
                rec["weight"] = self._weights[idx]
            out.append(rec)
        return out

    @staticmethod
    def _transport_summary(t: Dict[str, object]) -> Dict[str, object]:
        """Flatten one transport's stats into the compact per-replica
        block replica_loads()/replica_health()//healthz carry."""
        eps = t.get("endpoints") or {}
        total = {"rpcs": 0, "retries": 0, "timeouts": 0, "errors": 0}
        for rec in eps.values():
            for k in total:
                total[k] += int(rec.get(k, 0))
        return {
            "kind": t.get("kind", "transport"),
            "unreachable": bool(t.get("unreachable", False)),
            "lease_misses": int(t.get("lease_misses", 0)),
            "lease_expiries": int(t.get("lease_expiries", 0)),
            "reconnects": int(t.get("reconnects", 0)),
            **total,
        }

    @property
    def transport_stats(self) -> Optional[Dict[str, object]]:
        """Per-replica transport counters, labeled (the serving.transport
        payload the lsot_transport_* Prometheus families render). None
        when no replica is behind a transport — in-process fleets pay
        nothing."""
        per = []
        for st, s in self._replica_items():
            fn = getattr(s, "transport_stats", None)
            if not callable(fn):
                continue
            try:
                rec = dict(fn())
            except Exception:  # noqa: BLE001 — a dying replica mid-read
                continue
            rec["replica"] = st.label
            per.append(rec)
        return {"replicas": per} if per else None

    def start(self) -> "SchedulerPool":
        with self._lock:
            self._closed = False
        for st, s in zip(self._states, self.schedulers):
            if st.state != "removed":
                s.start()
        self._maybe_start_lease()
        return self

    # ------------------------------------------------ remote-replica lease

    @staticmethod
    def _leaseable(s) -> bool:
        return bool(getattr(s, "supports_lease", False)) and callable(
            getattr(s, "ping", None))

    def _maybe_start_lease(self) -> None:
        """Spawn the lease monitor iff any replica is a transport
        (serve/remote.py): in-process scheduler fleets have the
        watchdog's heartbeat as their liveness authority and pay
        nothing here."""
        if self._lease_s <= 0 or self._lease_thread is not None:
            return
        if not any(self._leaseable(s) for s in self.schedulers):
            return
        self._lease_stop = threading.Event()
        self._lease_thread = threading.Thread(
            target=self._lease_loop, daemon=True, name="lsot-pool-lease",
        )
        self._lease_thread.start()

    def _lease_loop(self) -> None:
        """Per-replica heartbeat LEASE over the transports: ping each
        placeable transport replica every `lease_s`; `lease_misses`
        consecutive failures expire the lease — the transport is marked
        unreachable (pending futures fail typed, streams gate shut) and
        `_note_replica_crash` kicks the targeted restart, whose
        `on_replica_restart` callback re-places the journaled work on
        siblings via the supervisor's existing fleet replay. A dead or
        partitioned host loses zero acknowledged requests."""
        while not self._lease_stop.wait(self._lease_s):
            with self._lock:
                if self._closed:
                    return
                items = [(i, st, self.schedulers[i])
                         for i, st in enumerate(self._states)
                         if st.state in _ReplicaState.PLACEABLE]
            for i, st, s in items:
                if not self._leaseable(s):
                    continue
                try:
                    s.ping(timeout=self._lease_s)
                except Exception as e:  # noqa: BLE001 — any failure is a miss
                    miss_fn = getattr(s, "lease_miss", None)
                    misses = (miss_fn() if callable(miss_fn)
                              else self._lease_misses)
                    self._pool_flight.event("lease_miss", replica=st.label,
                                            misses=misses)
                    if misses < self._lease_misses:
                        continue
                    exc = None
                    mark = getattr(s, "mark_unreachable", None)
                    if callable(mark):
                        exc = mark(
                            f"lease expired after {misses} missed "
                            f"beat(s): {e}"
                        )
                    if exc is None:
                        from .remote import ReplicaUnreachable

                        exc = ReplicaUnreachable(
                            f"replica {st.label} lease expired after "
                            f"{misses} missed beat(s): {e}"
                        )
                    resilience.inc("lease_expiries")
                    self._pool_flight.event("lease_expired",
                                            replica=st.label,
                                            misses=misses)
                    _log.warning("replica %s lease expired (%d misses)",
                                 st.label, misses)
                    self._note_replica_crash(i, exc)
                else:
                    ok_fn = getattr(s, "lease_ok", None)
                    if callable(ok_fn):
                        ok_fn()

    def shutdown(self, timeout: Optional[float] = None) -> None:
        # _closed stops any in-flight replica-restart driver from swapping
        # a fresh replica into a pool that is going away.
        with self._lock:
            self._closed = True
        self._lease_stop.set()
        for st, s in zip(self._states, self.schedulers):
            if s is None:
                continue
            try:
                s.shutdown(timeout=timeout)
            except Exception:  # noqa: BLE001 — one corpse must not wedge the rest
                _log.exception("replica %s shutdown failed", st.label)
        # Join in-flight restart drivers: `_closed` makes each exit at
        # its next checkpoint (discarding any fresh replica it built),
        # but a driver can be seconds deep in a rebuild's XLA compiles —
        # abandoning it leaves a daemon thread inside native code when a
        # short-lived process (tests, the chaos harness) exits. The same
        # `timeout` bound callers pass for replica teardown applies; a
        # driver that cannot finish inside it is abandoned like a wedged
        # replica join.
        with self._lock:
            drivers = list(self._restart_threads)
        for t in drivers:
            if t is not threading.current_thread():
                t.join(timeout)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()

    # ------------------------------------------------------------ placement

    @staticmethod
    def _score(s) -> Tuple[float, int]:
        """A replica's placement score `(backlog seconds, pending
        tokens)` — the scheduler's own Retry-After math via
        `backlog_score()`, with a queue-depth-only fallback for
        duck-typed replicas (the chaos harness's toy)."""
        fn = getattr(s, "backlog_score", None)
        if callable(fn):
            try:
                secs, toks = fn()
                return float(secs), int(toks)
            except Exception:  # noqa: BLE001 — a dying replica mid-read
                return 0.0, 0
        q = getattr(s, "_queue", None)
        return 0.0, (q.qsize() if q is not None else 0)

    def _wscore(self, i: int, s) -> Tuple[float, float]:
        """Weighted placement ORDERING score: replica i's backlog
        divided by its capacity weight, so a tp=4 replica weighted 4
        looks a quarter as loaded per unit of capacity and takes
        proportionally more token mass. Ordering only — deadline
        feasibility and Retry-After hints always compare the RAW
        seconds estimate (a replica's real backlog is wall-clock no
        matter its capacity weight). Weight 1.0 (the default) returns
        `_score` UNCHANGED — same types, same values — keeping the
        unweighted placement order bit for bit."""
        secs, toks = self._score(s)
        return self._wkey(i, secs, toks)

    def _wkey(self, i: int, secs: float, toks):
        w = self._weights[i] if i < len(self._weights) else 1.0
        if w == 1.0:
            return secs, toks
        return secs / w, toks / w

    def _affinity_scores(self, ids, tenant: str = "") -> Dict[str, int]:
        """The cache-aware routing lookup for one submit (ISSUE 15):
        the request's chain-prefix digests scored against every
        placeable replica's resident set via `prefix_affinity`. Empty
        when routing is off, the prompt is shorter than one block, or
        nobody holds anything — every one of which leaves the placement
        sort exactly where it was. With per-tenant prefix namespacing on
        (ISSUE 18), the lookup salts its digests with the request's
        tenant exactly as replica admission salts its cache keys —
        affinity keeps matching, within one tenant only."""
        block = int(getattr(self.schedulers[0], "_pblock", 0) or 0)
        if not block:
            return {}
        ns: Tuple[int, ...] = ()
        if tenant:
            from .qos import (prefix_tenant_ns_enabled, qos_enabled,
                              tenant_salt)
            if qos_enabled() and prefix_tenant_ns_enabled():
                ns = tenant_salt(tenant)
        digests = prefix_chain_digests(ids, block, ns)
        if not digests:
            return {}
        scored = self.prefix_affinity(digests)
        if not scored:
            return {}
        with self._lock:
            self._aff_checked += 1
        return {str(r["replica"]): int(r["score"]) for r in scored}

    def routing_stats(self) -> Dict[str, object]:
        """The placement layer's own counters (the bench `fleet_routing`
        affinity pass cites these): how many submits had a non-empty
        affinity lookup and how many landed on a best-affinity holder."""
        with self._lock:
            return {
                "router": self.router,
                "affinity_routing": self._affinity,
                "model_routing": self._model_routing,
                "weights": list(self._weights),
                "placements": sum(st.placements for st in self._states),
                "affinity_checked": self._aff_checked,
                "affinity_hits": self._aff_hits,
            }

    def model_stats(self) -> Optional[Dict[str, object]]:
        """Per-model serving aggregation (ISSUE 16): queue depth, live
        slots, accepted-token throughput and KV pages held, summed over
        every replica carrying each model_id — the `serving.models`
        payload behind the `lsot_model_*` Prometheus families. None for
        single-model fleets (no replica carries an id), which keeps the
        pre-multi-model /metrics byte-identical."""
        per: Dict[str, Dict[str, object]] = {}
        for st, s in self._replica_items():
            mid = st.model_id or self._model_id(s)
            if not mid:
                continue
            rec = per.setdefault(mid, {
                "model": mid, "replicas": 0, "placeable": 0,
                "queued": 0, "active_slots": 0,
                "pending_new_tokens": 0, "backlog_s": 0.0,
                "placements": 0, "tokens_total": 0,
                "kv_pages_total": 0, "kv_pages_in_use": 0,
            })
            rec["replicas"] += 1
            if st.state in _ReplicaState.PLACEABLE:
                rec["placeable"] += 1
            secs, toks = self._score(s)
            rec["backlog_s"] = round(rec["backlog_s"] + secs, 4)
            rec["pending_new_tokens"] += toks
            q = getattr(s, "_queue", None)
            rec["queued"] += q.qsize() if q is not None else 0
            slot_req = getattr(s, "_slot_req", None) or []
            rec["active_slots"] += sum(
                1 for r in slot_req if r is not None)
            rec["placements"] += st.placements
            rec["tokens_total"] += int(
                getattr(s, "_tokens_emitted_total", 0) or 0)
            pstats = getattr(s, "page_stats", None)
            if isinstance(pstats, dict):
                rec["kv_pages_total"] += int(
                    pstats.get("pages_total", 0) or 0)
                rec["kv_pages_in_use"] += int(
                    pstats.get("pages_in_use", 0) or 0)
            # Remote carriers: the cached loads digest stands in for
            # the attribute reads a socket transport cannot offer.
            ld = getattr(s, "loads_digest", None)
            if callable(ld):
                try:
                    d = ld()
                    rec["queued"] += int(d.get("queued", 0) or 0)
                    rec["active_slots"] += int(
                        d.get("active_slots", 0) or 0)
                    rec["tokens_total"] += int(
                        d.get("tokens_total", 0) or 0)
                except Exception:  # noqa: BLE001 — a dying replica
                    pass
        if not per:
            return None
        # Scrape-to-scrape tok/s: delta of the monotonic accepted-token
        # counter over the wall between calls (first call reports 0.0).
        now = time.monotonic()
        with self._lock:
            for mid, rec in per.items():
                prev = self._model_rate.get(mid)
                total = int(rec["tokens_total"])
                tok_s = 0.0
                if prev is not None and now > prev[0]:
                    tok_s = max(0.0, (total - prev[1]) / (now - prev[0]))
                self._model_rate[mid] = (now, total)
                rec["tok_s"] = round(tok_s, 3)
        return {"models": sorted(per.values(),
                                 key=lambda r: r["model"])}

    def _replica_items(self, states: Optional[Sequence[str]] = None
                       ) -> List[Tuple["_ReplicaState", object]]:
        """Locked (state, scheduler) snapshot of the fleet, optionally
        filtered by lifecycle state — the ONE place the
        iterate-the-fleet lock discipline lives (retry_after_hint,
        replica_loads, stalled_replicas, replica_health)."""
        with self._lock:
            return [(st, self.schedulers[i])
                    for i, st in enumerate(self._states)
                    if states is None or st.state in states]

    def _placeable(self, exclude: Optional[set] = None) -> List[Tuple[int, "_ReplicaState", object]]:
        """Replicas that can take new work right now: ready/degraded and
        not crashed. Observing a crash here kicks the replica's targeted
        restart (or marks it dead when the pool has no factory) — the
        bare-pool self-healing path; under a supervisor the inner-future
        failure notices it too."""
        out = []
        with self._lock:
            items = [(i, st) for i, st in enumerate(self._states)
                     if st.state in _ReplicaState.PLACEABLE
                     and (exclude is None or i not in exclude)]
            scheds = list(self.schedulers)
        for i, st in items:
            s = scheds[i]
            crash = getattr(s, "_crash", None)
            if crash is not None:
                self._note_replica_crash(i, crash)
                continue
            out.append((i, st, s))
        return out

    @staticmethod
    def _phase_role(s) -> str:
        return getattr(s, "phase_role", "mixed") or "mixed"

    @staticmethod
    def _model_id(s) -> str:
        return str(getattr(s, "model_id", "") or "")

    #: Duck-typing flag: callers (SchedulerBackend, the supervisor) only
    #: forward a model_id to schedulers that understand the axis.
    supports_model_routing = True
    #: Same duck-typing for the tenant/qos axis (ISSUE 18).
    supports_qos = True

    def _wire_handoff(self, idx: int, s) -> None:
        """Point a prefill-role replica's handoff queue at the pool's
        placement pump (idempotent; called at construction and after
        every targeted-restart swap)."""
        if self._phase_role(s) == "prefill" and hasattr(s, "on_handoff"):
            s.on_handoff = partial(self._pump_handoffs, idx)
        # Pushed constrained handoffs (ISSUE 17): the wire carries only
        # the constraint SPEC — the receiving transport rebuilds the
        # matcher through the pool's resolver seam (set by
        # SchedulerBackend; raw fleets may set pool.constraint_resolver
        # directly).
        if (getattr(s, "is_remote", False)
                and getattr(s, "constraint_resolver", "absent") is None):
            s.constraint_resolver = self._fleet_constraint

    def _fleet_constraint(self, spec):
        """Resolver seam for constrained requests re-materialized from
        the wire (pushed handoffs): delegates to whatever the owning
        backend installed, failing typed when nothing did."""
        fn = self.constraint_resolver
        if fn is None:
            raise ValueError(
                "pushed constrained handoff needs a constraint resolver: "
                "set pool.constraint_resolver (SchedulerBackend does this "
                "automatically)"
            )
        return fn(spec)

    def _join_compat(self, s) -> Optional[str]:
        """Startup-handshake compatibility check for a REMOTE joiner
        (ISSUE 17): a pushed KV blob's pages must be importable by every
        decode target, so a joiner whose page geometry disagrees with
        the fleet's — or whose checkpoint no local sibling carries —
        cannot be made placeable. Returns a reason string, or None when
        compatible. Local replicas are trusted: they were built by the
        same factory that built the fleet."""
        if not getattr(s, "is_remote", False):
            return None
        try:
            ref = None
            for other in self.schedulers:
                if other is not s and not getattr(other, "is_remote",
                                                  False):
                    ref = other
                    break
            if ref is None:
                return None  # all-remote fleet: nothing to disagree with
            r_ps = int(getattr(s, "_page_size", 0) or 0)
            l_ps = int(getattr(ref, "_page_size", 0) or 0)
            if r_ps and l_ps and r_ps != l_ps:
                return f"page_size={r_ps} vs fleet page_size={l_ps}"
            want = str(getattr(s, "model_id", "") or "")
            have = {str(self._model_id(other) or "")
                    for other in self.schedulers if other is not s}
            have.discard("")
            if want and have and want not in have:
                return (f"model_id={want!r} not served by this fleet "
                        f"({sorted(have)})")
        except Exception as e:  # noqa: BLE001 — unreachable joiner
            return f"handshake read failed: {e!r}"
        return None

    def _validate_join(self, idx: int, s) -> bool:
        """Run the join handshake for replica `idx`; an incompatible
        joiner is marked dead (never placeable) with the reason in its
        crash slot and a flight event — the pool keeps serving on the
        rest of the fleet."""
        reason = self._join_compat(s)
        if reason is None:
            return True
        st = self._states[idx]
        with self._lock:
            st.state = "dead"
            st.last_crash = f"join rejected: {reason}"
        self._pool_flight.event(
            "replica_join_rejected", replica=st.label, reason=reason)
        return False

    def _penalty(self, st: "_ReplicaState", s) -> int:
        """Pressure-aware placement (ISSUE 13 satellite): deprioritize a
        replica mid-KV-pressure-storm (withheld pool pages — PR-10's
        `kv_pressure` signal) or mid-SLO-burn BEFORE the least-loaded
        tie-break — backlog scores say nothing about a replica that is
        busy preempting victims or already blowing its latency budget.
        Additive, so a replica with both problems sorts after one with
        either; 0 everywhere in a healthy fleet, which keeps the
        pre-disagg placement order bit for bit."""
        pen = 0
        try:
            pstats = getattr(s, "page_stats", None)
            if pstats and int(pstats.get("pages_withheld", 0) or 0) > 0:
                pen += 1
        except Exception:  # noqa: BLE001 — a dying replica mid-read
            pass
        try:
            from ..utils import slo as _slo

            if _slo.ENGINE.enabled \
                    and _slo.ENGINE.replica_burning(st.label):
                pen += 1
        except Exception:  # noqa: BLE001 — placement view best-effort
            pass
        return pen

    @staticmethod
    def _decode_pressure(s) -> float:
        """The live decode-side placement signal (ISSUE 13): the
        replica's decode-phase HBM-bandwidth utilization EWMA from the
        per-round roofline ledger (PR 12) — the closer to the roof, the
        less headroom a migrated request's decode leg has there. 0.0
        for replicas without a ledger (duck-typed fakes)."""
        try:
            perf = getattr(s, "perf_stats", None)
            if isinstance(perf, dict):
                dec = (perf.get("phases") or {}).get("decode")
                if dec and dec.get("hbm_util") is not None:
                    return float(dec["hbm_util"])
        except Exception:  # noqa: BLE001 — a dying replica mid-read
            pass
        return 0.0

    def _pump_handoffs(self, src_idx: int) -> None:
        """Drain one prefill replica's packed handoffs and place each
        onto a decode-capable sibling. Runs on the prefill replica's
        worker thread the moment a blob is packed — placement is a lock
        plus a queue put, so the pump costs the prefill loop
        microseconds, and there is no polling thread to fall behind."""
        src = self.schedulers[src_idx]
        # One drain path (ISSUE 17): a push-capable transport buffers
        # blobs the worker streamed to us — drain that buffer directly.
        # extract_handoffs survives only as the legacy pull RPC for
        # pre-push workers and the drain/reconcile sweep.
        ex = getattr(src, "drain_pushed_handoffs", None)
        if not callable(ex):
            ex = getattr(src, "extract_handoffs", None)
        if not callable(ex):
            return
        for req in ex():
            self._place_handoff(req, src_idx)

    def _place_handoff(self, req, src_idx: int) -> None:
        """Phase-aware placement of ONE migrated request: decode
        replicas first — ordered by the pressure penalty, the live
        decode-phase HBM utilization, then backlog — mixed siblings
        next, the originating prefill replica last (the documented
        "fall back to decoding in place" rule: a decode-side placement
        failure must never lose the request). The remaining deadline is
        budgeted across the handoff: a target whose backlog estimate
        already exceeds it is skipped, so TTFT accounting spans both
        legs."""
        src = self.schedulers[src_idx]
        remaining = (req.deadline.remaining()
                     if req.deadline is not None else None)
        cands = self._placeable()
        # Multi-model fleets (ISSUE 16): a migrated request's KV pages
        # were written by the SOURCE model's weights — a cross-model
        # sibling would decode them into garbage. Same-model targets
        # only; the in-place fallback (the source itself) always
        # matches.
        src_model = self._model_id(src)
        if self._model_routing and src_model:
            cands = [c for c in cands
                     if (c[1].model_id or self._model_id(c[2]))
                     == src_model]

        def ordered(role):
            # Score once per candidate (decorate-sort): backlog_score /
            # penalty reads run on the prefill worker thread, and the
            # sort key must not re-invoke them per comparison pass.
            decorated = []
            for (i, st, s) in cands:
                if self._phase_role(s) != role or s is src:
                    continue
                secs, toks = self._wscore(i, s)
                decorated.append((self._penalty(st, s),
                                  self._decode_pressure(s),
                                  secs, toks, i, st, s))
            decorated.sort(key=lambda t: t[:5])
            return [(i, st, s) for (*_k, i, st, s) in decorated]

        targets = ordered("decode") + ordered("mixed") + [
            (src_idx, self._states[src_idx], src)
        ]
        # Snapshot the event fields BEFORE the target can race us: the
        # importing replica's worker may restore the blob (clearing
        # req.handoff) and requeue reassigns rid the moment rq(req)
        # returns.
        pages = (req.handoff or {}).get("pages", 0)
        t_recv = (req.handoff or {}).get("t_recv")
        rid = req.rid
        starved = 0
        for i, st, s in targets:
            if remaining is not None and s is not src:
                secs, _ = self._score(s)
                if secs >= remaining:
                    continue  # its backlog alone would burn the deadline
            if s is not src:
                # Page-starved targets (ISSUE 17): a decode sibling with
                # zero free pages would park this blob in its page-wait
                # queue — behind the very storm that starved it. Skip it;
                # if EVERY target is starved the failure below is typed
                # Overloaded, not a crash.
                try:
                    pstats = getattr(s, "page_stats", None)
                    if (pstats
                            and int(pstats.get("pages_free", 1) or 0) <= 0):
                        starved += 1
                        continue
                except Exception:  # noqa: BLE001 — dying replica mid-read
                    pass
            rq = getattr(s, "requeue", None)
            if not callable(rq):
                continue
            try:
                rq(req)
            except Exception:  # noqa: BLE001 — crashed/incompatible target
                continue
            with self._lock:
                st.placements += 1
                # Pushed-handoff latency ledger (ISSUE 17): the receiving
                # transport stamps t_recv the moment the blob leaves the
                # wire; placement closes the window lsot_fleet_push
                # latency summaries render.
                if t_recv is not None:
                    try:
                        self._push_lat.append(
                            max(0.0, time.perf_counter() - float(t_recv)))
                    except (TypeError, ValueError):
                        pass
            self._pool_flight.event(
                "handoff_place", to=st.label,
                src=self._states[src_idx].label, rid=rid,
                pages=pages, inplace=s is src,
            )
            return
        if starved:
            # Capacity exhaustion, not a crash: every decode target is
            # page-waiting AND the source could not take it back. Typed
            # backpressure tells the client to retry after the storm.
            req.future.set_exception(Overloaded(
                "every decode target is page-waiting; prefill→decode "
                "handoff rejected under KV pressure"
            ))
            return
        # Not even the (live — we are on its worker thread) source could
        # take it back: fail typed so the supervisor's journal replays it
        # instead of a client hanging on a parked future.
        req.future.set_exception(SchedulerCrashed(
            "no replica could accept a prefill→decode handoff"
        ))

    @property
    def handoff_stats(self) -> Optional[Dict[str, object]]:
        """Per-replica handoff counters (None when no replica has any) —
        the pool-level serving.handoff payload the lsot_handoff_*
        Prometheus families render."""
        per = []
        for st, s in self._replica_items():
            h = getattr(s, "handoff_stats", None)
            if isinstance(h, dict):
                rec = dict(h)
                rec["replica"] = st.label
                per.append(rec)
        return {"replicas": per} if per else None

    def qos_stats(self) -> Optional[Dict[str, object]]:
        """Per-replica WFQ/admission counters (ISSUE 18): None when no
        replica runs QoS — the pre-QoS payload byte-for-byte."""
        per = []
        for st, s in self._replica_items():
            fn = getattr(s, "qos_stats", None)
            if not callable(fn):
                continue
            try:
                qs = fn()
            except Exception:  # noqa: BLE001 — a churning fleet mid-read
                continue
            if qs:
                rec = dict(qs)
                rec["replica"] = st.label
                per.append(rec)
        return {"replicas": per} if per else None

    def submit(self, ids, max_new_tokens: int = 256,
               sampling: SamplingParams = SamplingParams(), seed: int = 0,
               on_token=None, constraint=None, deadline_s=None, trace=None,
               model_id: str = "", tenant: str = "", qos: str = ""):
        """Least-loaded, deadline-aware placement (router="round_robin"
        keeps the pre-fleet rotation): score every placeable replica,
        skip the ones whose backlog would blow this request's deadline,
        and fail over on Overloaded/crash races. A request is shed typed
        only when NO replica can serve it — Overloaded (429) with the
        fleet's minimum Retry-After when placeable replicas are all at
        capacity, DeadlineExceeded (504) when every placeable replica's
        backlog exceeds the deadline, Overloaded-with-backoff when the
        whole fleet is mid-restart, and SchedulerCrashed only when the
        fleet is truly gone.

        Multi-model placement (ISSUE 16): a request naming `model_id`
        considers ONLY replicas carrying that checkpoint — ahead of the
        phase filter, the affinity sort and the load tie-break. Naming a
        model nobody registered fails typed `UnknownModel` (ValueError →
        a 4xx at the API layer, never a scheduler crash); a model whose
        replicas are all mid-drain/restart sheds retryable Overloaded.
        `model_id=""` (all pre-existing callers) or LSOT_POOL_MODELS=0
        skips every model check — the single-model placement order, bit
        for bit."""
        want_model = model_id if (self._model_routing and model_id) else ""
        if want_model:
            with self._lock:
                carriers = [st.state for st in self._states
                            if st.model_id == want_model]
            if not carriers:
                from .modelpool import UnknownModel

                raise UnknownModel(
                    f"no replica in the fleet serves model "
                    f"{want_model!r} (models: "
                    f"{sorted({st.model_id for st in self._states if st.model_id}) or ['<unset>']})"
                )
        last_overloaded: Optional[Overloaded] = None
        deadline_blocked: Optional[float] = None
        tried: set = set()
        while True:
            cands = self._placeable(exclude=tried)
            if want_model:
                cands = [c for c in cands
                         if (c[1].model_id or self._model_id(c[2]))
                         == want_model]
            if not cands:
                break
            # Phase-aware routing (ISSUE 13): NEW requests are prefill
            # work — keep them off decode-role replicas while any
            # prefill/mixed replica can take them (all-decode leftovers
            # still serve rather than shed: roles are routing policy,
            # not capability). All-mixed fleets filter nothing. The
            # filtered-out decode replicas are kept as the deadline
            # spillover tier below.
            spill: List = []
            front = [c for c in cands if self._phase_role(c[2]) != "decode"]
            if front and len(front) < len(cands):
                spill = [c for c in cands
                         if self._phase_role(c[2]) == "decode"]
                cands = front
            if self.router == "round_robin":
                aff: Dict[str, int] = {}
                with self._lock:
                    pick = self._rr % len(cands)
                    self._rr += 1
                order = cands[pick:] + cands[:pick]
                scored = [(self._score(s), i, st, s)
                          for (i, st, s) in order]
            else:
                # Cache-aware, pressure-aware, weighted least-loaded
                # (ISSUE 15): a replica already holding the request's
                # schema-prefix pages sorts FIRST (zero-copy hit instead
                # of a re-prefill — at fleet scale the schema-prefix
                # working set IS the traffic shape), then replicas
                # mid-KV-pressure-storm or mid-SLO-burn sort after
                # healthy ones, then the weighted backlog tie-break.
                # With LSOT_POOL_AFFINITY=0 (no lookup, no events) and
                # all-1.0 weights this is the pre-affinity order bit
                # for bit.
                aff = (self._affinity_scores(ids, tenant)
                       if self._affinity else {})
                # Scores stay RAW (deadline feasibility + the 504 hint
                # below compare wall-clock backlog); the capacity weight
                # applies only inside the ordering key.
                scored = sorted(
                    ((self._score(s), i, st, s) for (i, st, s) in cands),
                    key=lambda t: (-aff.get(t[2].label, 0),
                                   self._penalty(t[2], t[3]),
                                   *self._wkey(t[1], t[0][0], t[0][1]),
                                   t[1]),
                )
            if deadline_s is not None:
                feasible = [t for t in scored if t[0][0] < deadline_s]
                if not feasible and spill:
                    # The prefill/mixed tier can't meet the deadline, but
                    # the decode-role replicas the phase filter set aside
                    # are FULL-capability — serving there beats shedding
                    # a request that still fits its budget somewhere.
                    spilled = sorted(
                        ((self._score(s), i, st, s)
                         for (i, st, s) in spill),
                        key=lambda t: (-aff.get(t[2].label, 0),
                                       self._penalty(t[2], t[3]),
                                       *self._wkey(t[1], t[0][0],
                                                   t[0][1]),
                                       t[1]),
                    )
                    feasible = [t for t in spilled if t[0][0] < deadline_s]
                    scored = scored + spilled
                if not feasible:
                    # Every placeable replica's backlog estimate already
                    # exceeds the budget: admitting anywhere would burn
                    # the deadline in queue. Shed 504 below (unless a
                    # not-yet-tried replica frees up — there is none:
                    # the estimate only grows with this submit).
                    deadline_blocked = min(t[0][0] for t in scored)
                    break
                scored = feasible
            (secs, toks), i, st, sched = scored[0]
            try:
                # The model kwarg rides only model-named submits: every
                # pre-existing replica (and the test fleet's duck-typed
                # fakes) keeps its exact signature on the "" path. Same
                # for the tenant/qos axis (ISSUE 18): forwarded only to
                # replicas that declare `supports_qos`.
                extra = {"model_id": want_model} if want_model else {}
                if (tenant or qos) and getattr(sched, "supports_qos",
                                               False):
                    extra["tenant"] = tenant
                    extra["qos"] = qos
                fut = sched.submit(
                    ids, max_new_tokens=max_new_tokens, sampling=sampling,
                    seed=seed, on_token=on_token, constraint=constraint,
                    deadline_s=deadline_s, trace=trace, **extra,
                )
            except ValueError:
                # Request-shape rejection (oversize prompt): identical on
                # every replica — re-raise rather than spinning the ring.
                raise
            except Overloaded as e:
                # This replica's queue is full; another may have room. Shed
                # (429) only when EVERY placeable replica is at capacity.
                if (last_overloaded is None
                        or e.retry_after_s < last_overloaded.retry_after_s):
                    last_overloaded = e
                tried.add(i)
                continue
            except RuntimeError:
                # Failover only for genuine crashes that landed between the
                # placeable check and submit(); lifecycle misuse ("not
                # started", "has shut down" without a crash) is the
                # caller's bug and its accurate error must propagate.
                crash = getattr(sched, "_crash", None)
                if crash is None:
                    raise
                self._note_replica_crash(i, crash)
                tried.add(i)
                continue
            # Replica attribution for the metrics label set: which
            # replica actually served this submit. Real schedulers
            # already stamped their own label under the submit lock —
            # only fill the gap for duck-typed replicas, so a handoff
            # requeue that migrated the request in the microseconds
            # since submit() returned is never overwritten with the
            # prefill replica's label.
            if getattr(fut, "_lsot_replica", None) is None:
                fut._lsot_replica = st.label
            with self._lock:
                st.placements += 1
                if aff and aff.get(st.label, 0) > 0 \
                        and aff[st.label] == max(aff.values()):
                    # The request landed on a best-affinity holder: the
                    # zero-copy prefix hit the router was built to buy.
                    self._aff_hits += 1
            if st.state == "degraded":
                # A clean completion proves the restarted replica serves.
                def _prove(f, st=st):
                    if f.exception() is None:
                        with self._lock:
                            if st.state == "degraded":
                                st.state = "ready"
                fut.add_done_callback(_prove)
            # Placement decision into the pool black box: where the
            # request went and what the router saw (bounded ring append).
            ev: Dict[str, object] = dict(
                to=st.label, router=self.router,
                backlog_s=round(secs, 4), pending_new_tokens=toks,
                considered=len(cands),
            )
            if aff:
                ev["affinity"] = aff.get(st.label, 0)
            if want_model:
                ev["model"] = want_model
            self._pool_flight.event("placement", **ev)
            return fut
        if want_model and last_overloaded is None \
                and deadline_blocked is None:
            # The model IS registered (the pre-loop check passed) but no
            # carrier is placeable right now: a drain/restart in flight
            # is retryable backpressure; all-dead is the model-scoped
            # fleet death. Re-snapshot — the loop's crash handling may
            # have moved carriers since the pre-loop check.
            with self._lock:
                carriers = [st.state for st in self._states
                            if st.model_id == want_model]
            if any(s in ("restarting", "draining", "drained")
                   for s in carriers):
                raise Overloaded(
                    f"every replica serving model {want_model!r} is "
                    f"draining or restarting",
                    retry_after_s=self.retry_after_hint(),
                )
            raise SchedulerCrashed(
                f"every replica serving model {want_model!r} has "
                f"crashed or left the fleet"
            )
        if last_overloaded is not None:
            # Min Retry-After across the full fleet (restart-aware), not
            # whichever replica happened to shed last.
            raise Overloaded(
                "every scheduler replica is at capacity",
                retry_after_s=min(last_overloaded.retry_after_s,
                                  self.retry_after_hint()),
            )
        if deadline_blocked is not None:
            resilience.inc("deadline_infeasible")
            raise DeadlineExceeded(
                f"no replica can serve within the {deadline_s:.3f}s "
                f"deadline: minimum fleet backlog estimate "
                f"{deadline_blocked:.3f}s"
            )
        with self._lock:
            restarting = any(st.state == "restarting" for st in self._states)
        if restarting:
            # The fleet is mid-restart with nothing placeable: retryable
            # backpressure (the hint carries the backoff remaining), NOT a
            # crash — a supervisor must not tear the whole pool down while
            # its replicas are already being rebuilt.
            raise Overloaded(
                "every scheduler replica is restarting",
                retry_after_s=self.retry_after_hint(),
            )
        # Typed (not a bare RuntimeError): every replica holds a
        # SchedulerCrashed (or is dead/removed), the pool just summarizes
        # — and the supervisor classifies crashes by TYPE, so the
        # fleet-wide death must carry it (a message-string contract would
        # silently break recovery on rewording). Subclasses RuntimeError:
        # existing handlers keep working.
        raise SchedulerCrashed(
            "all scheduler replicas have crashed or left the fleet"
        )

    cancel = staticmethod(ContinuousBatchingScheduler.cancel)

    # --------------------------------------------------- replica lifecycle

    def _resolve_idx(self, replica) -> int:
        if isinstance(replica, int):
            if not 0 <= replica < len(self._states):
                raise ValueError(f"no replica index {replica}")
            return replica
        for i, st in enumerate(self._states):
            if st.label == replica:
                return i
        raise ValueError(f"unknown replica {replica!r}")

    def _note_replica_crash(self, idx: int, exc: BaseException) -> None:
        """A replica's loop died: kick its targeted restart (factory
        pools), or mark it dead and skip it forever (factory-less pools —
        the pre-fleet behavior, now visible in health()). Idempotent per
        episode."""
        with self._lock:
            st = self._states[idx]
            if self._closed or st.state not in _ReplicaState.PLACEABLE:
                return
            st.last_crash = str(exc)[:200]
            if self._factory is None:
                st.state = "dead"
                self._pool_flight.event("replica_dead", replica=st.label,
                                        error=st.last_crash)
                return
            st.state = "restarting"
        resilience.inc("replica_crashes")
        self._pool_flight.event("replica_crash", replica=st.label,
                                error=st.last_crash)
        _log.warning("replica %s crashed; pool restarting it: %s",
                     st.label, exc)
        self._spawn_restart(idx)

    def notice_replica_crash(self, replica, exc: BaseException) -> None:
        """Public crash-notice seam (the supervisor calls it when one of
        its journaled requests' inner futures fails typed with a crash):
        kicks the replica's targeted restart, idempotent per episode."""
        try:
            idx = self._resolve_idx(replica)
        except ValueError:
            return
        self._note_replica_crash(idx, exc)

    def restart_replica(self, replica, reason: str = "manual") -> bool:
        """Targeted restart of ONE replica (the watchdog's stall
        escalation and the operator's manual kick): tear it down with a
        bounded join — a WEDGED loop never joins; the zombie daemon is
        abandoned — and rebuild it from the factory under the replica's
        own bounded-backoff restart budget, while every sibling keeps
        serving untouched. A `drained` replica restarts back into the
        fleet (the re-add path). Returns False when the replica is
        already restarting, mid-drain (the drain owns its fate),
        removed, the pool is closed, or there is no factory."""
        idx = self._resolve_idx(replica)
        with self._lock:
            st = self._states[idx]
            if (self._closed or self._factory is None
                    or st.state in ("restarting", "draining", "removed")):
                return False
            st.state = "restarting"
            if reason == "stalled":
                st.stalls += 1
            st.last_crash = reason
        if reason == "stalled":
            resilience.inc("replica_stalls")
        self._pool_flight.event("replica_restart_requested",
                                replica=st.label, reason=reason)
        _log.warning("replica %s restart requested (%s)", st.label, reason)
        self._spawn_restart(idx)
        return True

    def _spawn_restart(self, idx: int) -> None:
        t = threading.Thread(
            target=self._restart_driver, args=(idx,), daemon=True,
            name=f"lsot-pool-restart-{self._states[idx].label}",
        )
        with self._lock:
            # Prune finished episodes so the list tracks live drivers.
            self._restart_threads = [
                x for x in self._restart_threads if x.is_alive()
            ]
            self._restart_threads.append(t)
        t.start()

    def _build_replica(self, idx: int):
        return (self._factory(idx) if self._factory_takes_index
                else self._factory())

    def _restart_driver(self, idx: int) -> None:
        """One thread per replica restart episode: bounded teardown of
        the corpse, backoff under the per-replica budget, rebuild + warm
        + swap. Budget exhausted (or rebuild failures burning it) marks
        only THIS replica dead — siblings carry the fleet."""
        st = self._states[idx]
        while True:
            old = self.schedulers[idx]
            try:
                if old is not None:
                    old.shutdown(timeout=self._replica_join_s)
            except Exception:  # noqa: BLE001 — a broken corpse must not stop the rebuild
                _log.exception("replica %s teardown failed; continuing",
                               st.label)
            with self._lock:
                if self._closed:
                    return
                if st.restarts >= self.max_restarts:
                    st.state = "dead"
                    st.restart_eta = None
                    self._pool_flight.event("replica_dead",
                                            replica=st.label,
                                            restarts=st.restarts)
                    _log.error(
                        "replica %s dead: restart budget exhausted "
                        "(%d/%d)", st.label, st.restarts, self.max_restarts,
                    )
                    return
                attempt = st.restarts
                st.restarts += 1
            resilience.inc("replica_restarts")
            delay = self._restart_policy.delay_s(attempt, self._rng)
            with self._lock:
                # Published for retry_after_hint: hints quoted while this
                # replica is down promise at least the backoff remaining.
                st.restart_eta = time.monotonic() + delay
            self._sleep(delay)
            with self._lock:
                if self._closed:
                    # The pool died during the backoff: don't start a
                    # rebuild nobody will use (shutdown() is joining us).
                    return
            try:
                fresh = self._build_replica(idx)
                # Warm BEFORE serving, like the supervisor's restart
                # driver: a rebuilt scheduler's cold XLA compiles block
                # its loop exactly like the wedge this restart may be
                # recovering from.
                warm = getattr(fresh, "warmup", None)
                if callable(warm):
                    warm()
                fresh.start()
            except Exception:  # noqa: BLE001 — rebuild failure burns one credit
                _log.exception("replica %s rebuild failed (restart %d/%d)",
                               st.label, attempt + 1, self.max_restarts)
                continue
            with self._lock:
                if self._closed or st.state != "restarting":
                    # Pool going away, or a drain/remove raced the
                    # rebuild and owns the replica now: don't swap a
                    # fresh scheduler into a slot someone else decided
                    # the fate of.
                    fresh.shutdown()
                    return
                fl = getattr(fresh, "flight", None)
                if fl is not None:
                    fl.replica = st.label
                self.schedulers[idx] = fresh
                # A rebuilt prefill-role replica needs its handoff pump
                # re-pointed at the pool (the corpse took the wiring).
                self._wire_handoff(idx, fresh)
                # Re-capture the model axis: the factory may rebuild the
                # replica with (or without) a checkpoint id, and stale
                # model routing would misplace every named request.
                st.model_id = self._model_id(fresh)
                # Degraded until a clean completion lands on it (the
                # submit-path done-callback promotes it back to ready).
                st.state = "degraded"
                st.restart_eta = None
            self._pool_flight.event("replica_restart", replica=st.label,
                                    attempt=st.restarts)
            _log.info("replica %s restarted (%d/%d)", st.label,
                      st.restarts, self.max_restarts)
            cb = self.on_replica_restart
            if cb is not None:
                try:
                    cb(st.label)
                except Exception:  # noqa: BLE001 — replay hook must not kill the driver
                    _log.exception("on_replica_restart(%s) failed", st.label)
            return

    def drain_replica(self, replica, deadline_s: Optional[float] = None,
                      remove: bool = False) -> Dict[str, object]:
        """Runtime drain of ONE replica: stop placing on it, RE-PLACE its
        queued-not-yet-admitted requests onto the least-loaded siblings
        (acknowledged work is never shed by a drain), give in-flight
        work up to `deadline_s` to finish (None = wait; <= 0 = none),
        then shut the replica down with a bounded join. `remove=True`
        marks it permanently out of the fleet; otherwise it parks as
        `drained` and `restart_replica()` can bring it back. SIGTERM
        semantics at the pool level are untouched — this is the
        one-replica twin of the supervisor's drain."""
        idx = self._resolve_idx(replica)
        t_drain0 = time.perf_counter()
        with self._lock:
            st = self._states[idx]
            if st.state in ("draining", "removed"):
                return {"replica": st.label, "state": st.state,
                        "replaced": 0}
            st.state = "draining"
            sched = self.schedulers[idx]
        self._pool_flight.event("replica_drain", replica=st.label,
                                deadline_s=deadline_s, remove=remove)
        # Re-place queued work BEFORE waiting on in-flight: the queue
        # would otherwise drain into the replica we are emptying.
        replaced = 0
        pulls = []
        extract = getattr(sched, "extract_queued", None)
        if callable(extract):
            pulls.extend(extract())
        # Packed handoffs waiting on this replica drain too: each carries
        # its portable KV blob, so a sibling restores and decodes it
        # without a re-prefill (acknowledged work never sheds).
        exh = getattr(sched, "extract_handoffs", None)
        if callable(exh):
            pulls.extend(exh())
        if pulls:
            # Multi-model fleets (ISSUE 16): a draining replica's queued
            # work can only re-place onto siblings holding the SAME
            # checkpoint — a cross-model sibling would decode with the
            # wrong weights. Draining the ONLY replica of a model keeps
            # the lone-replica degenerate path below: the work stays on
            # the draining replica and serves out inside the grace.
            drain_model = st.model_id or self._model_id(sched)
            for req in pulls:
                target = None
                cands = self._placeable()
                if self._model_routing and drain_model:
                    cands = [c for c in cands
                             if (c[1].model_id or self._model_id(c[2]))
                             == drain_model]
                if cands:
                    target = min(
                        ((self._wscore(i, s), self._penalty(_st, s), i, s)
                         for (i, _st, s) in cands),
                        key=lambda t: (t[1], t[0][0], t[0][1], t[2]),
                    )[3]
                if target is not None and callable(
                        getattr(target, "requeue", None)):
                    try:
                        target.requeue(req)
                        replaced += 1
                        continue
                    except Exception:  # noqa: BLE001 — incompatible/racing target
                        pass
                # No sibling can take it: leave it on the draining
                # replica — it serves out its queue inside the grace
                # (a lone-replica drain degenerates to a plain drain).
                sched.requeue(req)
        if replaced:
            self._pool_flight.event("replica_drain_replaced",
                                    replica=st.label, replaced=replaced)
        # Bounded grace for in-flight + whatever stayed queued.
        busy = getattr(sched, "_busy_now", None)
        deadline = (Deadline.after(deadline_s)
                    if deadline_s is not None and deadline_s > 0 else None)
        wait_all = deadline_s is None
        finished = True
        while callable(busy):
            try:
                if not busy():
                    break
            except Exception:  # noqa: BLE001 — a dying replica mid-read
                break
            if not wait_all and (deadline is None
                                 or deadline.remaining() <= 0):
                finished = False
                break
            time.sleep(0.01)
        try:
            sched.shutdown(timeout=self._replica_join_s)
        except Exception:  # noqa: BLE001 — a wedged corpse must not fail the drain
            _log.exception("replica %s drain shutdown failed", st.label)
        with self._lock:
            # Only finalize if the drain still owns the slot: a racing
            # restart_replica is refused while state == "draining", so
            # anything else here means someone else took over — don't
            # mark a live replica drained out from under them.
            if st.state == "draining":
                st.state = "removed" if remove else "drained"
            # Fleet drain ledger (ISSUE 17): scale-down rides this path,
            # so its cost shows up as lsot_fleet_drain_seconds.
            self._fleet_drain_s_sum += time.perf_counter() - t_drain0
            self._fleet_drain_count += 1
        self._pool_flight.event("replica_drained", replica=st.label,
                                replaced=replaced, finished=finished,
                                removed=remove)
        cb = self.on_replica_drained
        if cb is not None:
            try:
                # The supervisor's re-placement seam: journaled requests
                # still attributed to this replica (in-flight work the
                # grace did not finish) re-place onto the fleet.
                cb(st.label)
            except Exception:  # noqa: BLE001 — replay hook best-effort
                _log.exception("on_replica_drained(%s) failed", st.label)
        return {"replica": st.label,
                "state": "removed" if remove else "drained",
                "replaced": replaced, "finished": finished}

    def remove_replica(self, replica,
                       deadline_s: Optional[float] = None) -> Dict[str, object]:
        """Drain + permanently remove one replica from the fleet."""
        return self.drain_replica(replica, deadline_s=deadline_s,
                                  remove=True)

    # ------------------------------------------- elastic membership (17)

    def add_replica(self, scheduler, label: Optional[str] = None,
                    weight: float = 1.0, elastic: bool = True) -> str:
        """Join ONE replica to a LIVE fleet: append + wire the handoff
        pump and constraint seam, run the startup handshake, and (if the
        joiner brought a lease surface) make sure the lease monitor is
        running. Returns the new replica's label. A joiner failing the
        page-geometry/model handshake stays visible in /healthz as dead
        with the reason — it is never placeable, and the fleet keeps
        serving. `elastic=True` marks it retirable by scale-down;
        operator-configured replicas never retire."""
        with self._lock:
            if self._closed:
                raise RuntimeError("cannot add a replica to a closed pool")
            idx = len(self.schedulers)
            lbl = label or f"r{idx}"
            fl = getattr(scheduler, "flight", None)
            if fl is not None:
                fl.replica = lbl
            self.schedulers.append(scheduler)
            self._states.append(_ReplicaState(
                label=lbl, model_id=self._model_id(scheduler)))
            self._weights.append(max(1e-9, float(weight)))
            if elastic:
                self._elastic.add(idx)
            self._fleet_joins += 1
        self._wire_handoff(idx, scheduler)
        ok = self._validate_join(idx, scheduler)
        self._pool_flight.event(
            "replica_join", replica=lbl, elastic=bool(elastic),
            accepted=ok, phase_role=self._phase_role(scheduler))
        # The lease monitor tolerates list growth (it snapshots the state
        # list under the lock each tick) — (re)arm it in case the joiner
        # is the fleet's first remote.
        self._maybe_start_lease()
        return lbl

    def retire_replica(self, replica=None,
                       deadline_s: Optional[float] = None
                       ) -> Optional[Dict[str, object]]:
        """Scale-down: drain-and-remove ONE autoscaler-added replica —
        drain → re-place → remove rides drain_replica, so acknowledged
        work re-places onto siblings and ZERO requests are lost. With
        `replica=None`, picks the least-loaded serving elastic replica.
        Returns the drain report, or None when nothing is retirable
        (operator-configured replicas are never eligible)."""
        if replica is not None:
            idx = self._resolve_idx(replica)
            if idx not in self._elastic:
                return None
        else:
            with self._lock:
                cands = [i for i in self._elastic
                         if self._states[i].state
                         in _ReplicaState.PLACEABLE]
            if not cands:
                return None
            idx = min(cands, key=lambda i: (
                self._wscore(i, self.schedulers[i]), i))
        out = self.drain_replica(idx, deadline_s=deadline_s, remove=True)
        with self._lock:
            self._elastic.discard(idx)
            self._fleet_retires += 1
        self._pool_flight.event("replica_retire",
                                replica=out.get("replica"),
                                replaced=out.get("replaced"))
        return out

    def fleet_stats(self) -> Dict[str, object]:
        """The `fleet` block in /healthz and /metrics (lsot_fleet_*):
        live membership, join/retire/drain lifecycle counters, and the
        pushed-handoff ledger (depth, bytes, wire→placement latency)."""
        with self._lock:
            states = [st.state for st in self._states]
            out: Dict[str, object] = {
                "size": len(states),
                "serving": sum(1 for s in states
                               if s in _ReplicaState.PLACEABLE),
                "elastic": len(self._elastic),
                "joins": self._fleet_joins,
                "retires": self._fleet_retires,
                "drain_s_sum": round(self._fleet_drain_s_sum, 6),
                "drain_count": self._fleet_drain_count,
            }
            lat = sorted(self._push_lat)
        out.update({"pushed": 0, "push_bytes": 0, "pump_depth": 0,
                    "push_placed": len(lat)})
        if lat:
            out["push_place_p50_ms"] = round(
                lat[int(0.50 * (len(lat) - 1))] * 1e3, 3)
            out["push_place_p95_ms"] = round(
                lat[int(0.95 * (len(lat) - 1))] * 1e3, 3)
        for s in self.schedulers:
            pp = getattr(s, "push_pump_stats", None)
            if isinstance(pp, dict):
                out["pushed"] += int(pp.get("pushed", 0) or 0)
                out["push_bytes"] += int(pp.get("push_bytes", 0) or 0)
                out["pump_depth"] += int(pp.get("depth", 0) or 0)
                w = pp.get("worker")
                if isinstance(w, dict):
                    out["pump_depth"] += int(w.get("window", 0) or 0)
        return out

    def stalled_replicas(self, factor: float, floor_s: float) -> List[str]:
        """Labels of SERVING replicas whose busy heartbeat has gone stale
        past their own stall threshold — the supervisor's watchdog feed
        for targeted restarts. Replicas already restarting/draining/dead
        are excluded (their stale corpses are being handled)."""
        from .watchdog import stall_threshold

        out: List[str] = []
        for st, s in self._replica_items(_ReplicaState.PLACEABLE):
            hb = getattr(s, "heartbeat", None)
            if hb is None or not hb.busy:
                continue
            if hb.age() > stall_threshold(hb, factor, floor_s):
                out.append(st.label)
        return out

    # ----------------------------------------------------------- health

    def replica_health(self) -> List[Dict[str, object]]:
        """Per-replica lifecycle for /healthz + /readyz + /metrics:
        state, restart/stall budgets, crash flag, restart ETA."""
        now = time.monotonic()
        out = []
        for st, s in self._replica_items():
            rec: Dict[str, object] = {
                "replica": st.label,
                "state": st.state,
                "phase_role": self._phase_role(s),
                "model_id": st.model_id or self._model_id(s),
                "restarts": st.restarts,
                "max_restarts": self.max_restarts,
                "stalls": st.stalls,
                "crashed": getattr(s, "_crash", None) is not None,
            }
            # Transport-backed replicas (ISSUE 15): the /healthz fleet
            # view says which wire the replica is behind and whether its
            # lease is healthy — one probe answers "is r2 down or just
            # partitioned from us".
            ts = getattr(s, "transport_stats", None)
            if callable(ts):
                try:
                    rec["transport"] = self._transport_summary(ts())
                except Exception:  # noqa: BLE001 — a dying replica mid-read
                    pass
            if st.last_crash:
                rec["last_crash"] = st.last_crash
            if st.restart_eta is not None:
                rec["restart_eta_s"] = round(max(0.0, st.restart_eta - now),
                                             3)
            out.append(rec)
        return out

    def health(self) -> Dict[str, object]:
        """Aggregate fleet state, shaped like the supervisor's health()
        payload (/readyz consumes either): `ready` — every replica
        serving clean; `degraded` — serving, but some replica is
        restarting/drained/dead or not yet proven after a restart;
        `restarting` — NO replica serving but at least one rebuild in
        flight; `dead` — the fleet is gone. Plus the per-replica list."""
        reps = self.replica_health()
        # Removed replicas LEFT the fleet (a deliberate scale-down): they
        # stay visible in the replicas list but must not degrade the
        # aggregate forever.
        states = [r["state"] for r in reps if r["state"] != "removed"]
        serving = [s for s in states if s in _ReplicaState.PLACEABLE]
        if serving:
            state = ("ready" if all(s == "ready" for s in states)
                     else "degraded")
        elif "restarting" in states:
            state = "restarting"
        else:
            state = "dead"
        return {
            "state": state,
            "replicas": reps,
            "restarts": sum(int(r["restarts"]) for r in reps),
            "stalls": sum(int(r["stalls"]) for r in reps),
            # Elastic membership view (ISSUE 17): size/joins/retires/
            # drain ledger + the pushed-handoff pump depth, so one
            # /healthz probe answers "did the fleet actually scale".
            "fleet": self.fleet_stats(),
        }

    @property
    def prefix_stats(self) -> Dict[str, object]:
        """Summed prefix-cache stats across replicas (SchedulerBackend
        duck typing — each replica owns an independent cache). Counters
        sum; `hit_rate` is DERIVED from the summed hits/misses — summing
        or averaging per-replica ratios would misweight replicas with
        different traffic shares."""
        out: Dict[str, object] = {
            "hits": 0, "misses": 0, "blocks_reused": 0,
            "reused_tokens": 0, "evictions": 0, "cached_blocks": 0,
        }
        for s in self.schedulers:
            st = getattr(s, "prefix_stats", None)
            if isinstance(st, dict):
                for k in out:
                    out[k] += int(st.get(k, 0))
        total = out["hits"] + out["misses"]
        out["hit_rate"] = (round(out["hits"] / total, 4) if total
                           else 0.0)
        return out

    @property
    def prefix_telemetry(self) -> Optional[Dict[str, object]]:
        """Per-replica prefix-cache telemetry, labeled (the serving.prefix
        payload the lsot_prefix_* Prometheus families render). None when
        no replica has an enabled cache."""
        per = []
        for st, s in self._replica_items():
            t = getattr(s, "prefix_telemetry", None)
            if isinstance(t, dict):
                rec = dict(t)
                rec["replica"] = st.label
                per.append(rec)
        return {"replicas": per} if per else None

    def prefix_registry(self, top_k: Optional[int] = None
                        ) -> Dict[str, object]:
        """Per-replica content-addressed registries (the
        /debug/prefixcache payload for a fleet), labeled with the pool's
        replica vocabulary."""
        per = []
        for st, s in self._replica_items():
            fn = getattr(s, "prefix_registry", None)
            if not callable(fn):
                continue
            try:
                reg = fn(top_k)
            except Exception:  # noqa: BLE001 — a dying replica mid-read
                continue
            if isinstance(reg, dict):
                reg = dict(reg)
                reg["replica"] = st.label
                per.append(reg)
        return {"replicas": per}

    def prefix_affinity(self, digests: Sequence[str]
                        ) -> List[Dict[str, object]]:
        """Cache-aware routing feed (ISSUE 14): score every placeable
        replica by how many of `digests` (a request's chain-prefix
        digests — `prefix_chain_digests(ids, block)`) it currently holds
        resident. Returns [{replica, score}] sorted best-first, scoring
        replicas only (no score-0 noise); empty when nobody holds any.
        CONSUMED BY PLACEMENT (ISSUE 15): submit() sorts candidates by
        this lookup's scores ahead of the pressure penalty and the
        weighted least-loaded tie-break whenever affinity routing is on
        (the default; LSOT_POOL_AFFINITY=0 restores the pure
        observability role) — changing the scoring here changes where
        requests LAND. Each non-empty lookup drops a `prefix_affinity`
        event into the pool flight ring so placement postmortems can
        see what the router knew."""
        want = {d for d in digests if d}
        if not want:
            return []
        scored: List[Dict[str, object]] = []
        for _i, st, s in self._placeable():
            fn = getattr(s, "resident_digests", None)
            if not callable(fn):
                continue
            try:
                score = len(want & set(fn()))
            except Exception:  # noqa: BLE001 — a dying replica mid-read
                continue
            if score:
                scored.append({"replica": st.label, "score": score})
        scored.sort(key=lambda r: -int(r["score"]))
        if scored:
            self._pool_flight.event(
                "prefix_affinity", best=scored[0]["replica"],
                score=scored[0]["score"], digests=len(want),
                holders=len(scored),
            )
        return scored

    @property
    def speculation_stats(self) -> Optional[Dict[str, float]]:
        """First replica's acceptance view (replicas are homogeneous;
        None when speculation is off) — SchedulerBackend duck typing."""
        return getattr(self.schedulers[0], "speculation_stats", None)

    def generate(self, prompts, max_new_tokens: int = 256,
                 sampling: SamplingParams = SamplingParams(), seed: int = 0):
        futs = [
            self.submit(p, max_new_tokens=max_new_tokens, sampling=sampling,
                        seed=seed)
            for p in prompts
        ]
        return [f.result() for f in futs]


class SchedulerBackend:
    """`serve.GenerationService`-compatible backend over the scheduler.

    Drop-in for `EngineBackend` (same `.complete()` seam, backends.py): N
    HTTP handler threads calling `complete()` concurrently share one decode
    batch instead of serializing on a lock.
    """

    #: GenerationService checks this before forwarding a `constrain=` spec.
    supports_constrain = True
    #: GenerationService checks this before forwarding a `deadline_s`: the
    #: scheduler can actually retire an in-flight request at harvest time,
    #: unlike the one-XLA-program engine.
    supports_deadline = True

    def __init__(
        self,
        scheduler: ContinuousBatchingScheduler,
        tokenizer,
        max_new_tokens: int = 256,
        sampling: SamplingParams = SamplingParams(),
        stop_texts: Sequence[str] = (),
        add_bos: bool = True,
        deadline_s: Optional[float] = None,
        model_id: str = "",
    ):
        self.scheduler = scheduler.start()
        self.tokenizer = tokenizer
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.stop_texts = tuple(stop_texts)
        self.add_bos = add_bos
        # Default per-request deadline (None = no deadline); a request's
        # own deadline_s overrides it.
        self.deadline_s = deadline_s
        # Multi-model serving (ISSUE 16): every submit through this
        # backend names its registered model so a model-aware pool
        # routes it to the right co-resident checkpoint. "" (the
        # default) submits model-blind — the single-model fleet's exact
        # call shape — and the kwarg is forwarded only to schedulers
        # that understand the axis (duck-typed, like idempotency).
        self.model_id = str(model_id or "")
        self._routes_models = bool(
            getattr(scheduler, "supports_model_routing", False)
        ) and bool(self.model_id)
        # Idempotency keys need a journal to dedupe against — only the
        # supervised wrapper (serve/supervisor.py) has one.
        self.supports_idempotency = bool(
            getattr(scheduler, "supports_idempotency", False)
        )
        # Multi-tenant QoS (ISSUE 18): tenant/qos kwargs are forwarded
        # only to schedulers that understand the axis — duck-typed like
        # model routing, so fakes and older signatures stay untouched.
        self.supports_qos = bool(getattr(scheduler, "supports_qos", False))
        # Journal-spill recovery happens HERE, the one seam every
        # deployment path (tiny, HF, GGUF, dp pool) funnels through: a
        # previous process's drained-but-unfinished requests resubmit so
        # retried idempotency keys find their results. The backend owns
        # the tokenizer, so it is also the one that can recompile a
        # spilled constraint SPEC back into device tables — point the
        # supervisor's resolver here BEFORE recovery runs.
        if hasattr(scheduler, "constraint_resolver"):
            scheduler.constraint_resolver = self._resolve_constraint
        recover = getattr(scheduler, "recover", None)
        if callable(recover) and getattr(scheduler, "spill_path", None):
            recover()

    def shutdown(self) -> None:
        """Stop the scheduler's event loop (idempotent; safe on shared
        schedulers — GenerationService.close() dedupes by backend, and
        ContinuousBatchingScheduler.shutdown is itself idempotent)."""
        self.scheduler.shutdown()

    def health(self) -> Optional[Dict[str, object]]:
        """Supervisor lifecycle state (ready/restarting/degraded/dead +
        restart counters) for /readyz; None for a bare scheduler (always
        'ready or crashed' — the crash already answers 503 per request)."""
        h = getattr(self.scheduler, "health", None)
        return h() if callable(h) else None

    def drain(self, deadline_s: Optional[float] = None) -> None:
        """Graceful-shutdown seam (SIGTERM path): supervised schedulers
        stop admitting, finish in-flight up to the deadline, and journal
        the rest; bare schedulers just stop."""
        d = getattr(self.scheduler, "drain", None)
        if callable(d):
            d(deadline_s)
        else:
            self.scheduler.shutdown()

    def retry_after_hint(self) -> float:
        hint = getattr(self.scheduler, "retry_after_hint", None)
        return hint() if callable(hint) else 1.0

    def stats(self) -> Dict[str, object]:
        """Serving-layer observability beyond per-request metrics: prefix
        cache reuse, (when --speculative is on) draft acceptance, and
        (when supervised) the crash-recovery lifecycle — merged into the
        app's /metrics payload per model."""
        out: Dict[str, object] = {"prefix_cache": self.scheduler.prefix_stats}
        # Prefix-cache telemetry (ISSUE 14): the per-replica counter/
        # residency/priced-savings block the lsot_prefix_* Prometheus
        # families render — beside (not replacing) the flat prefix_cache
        # sums above, whose lsot_serving_prefix_cache_* gauges dashboards
        # already scrape.
        ptel = getattr(self.scheduler, "prefix_telemetry", None)
        if ptel:
            out["prefix"] = ptel
        spec = self.scheduler.speculation_stats
        if spec is not None:
            out["speculation"] = spec
        # Page-pool occupancy + sharing counters:
        # pages_total/pages_free/pages_shared become Prometheus gauges via
        # the nested-serving-stats renderer (utils/prometheus.py), so a
        # leaked page is a flat-lining pages_free on a dashboard.
        pages = getattr(self.scheduler, "page_stats", None)
        if pages:
            out["kv_pages"] = pages
        # Per-round roofline ledger (ISSUE 12, utils/perfmodel.py): the
        # live per-phase MFU / HBM-util / binding-roof view under
        # `serving.perf` — the Prometheus renderer turns it into the
        # lsot_mfu / lsot_hbm_util gauges labeled phase × replica.
        perf = getattr(self.scheduler, "perf_stats", None)
        if perf:
            out["perf"] = perf
        # Prefill→decode handoff traffic (ISSUE 13): exports/imports/
        # fallbacks, page+byte volume, decode-slot wait — rendered as
        # the lsot_handoff_* Prometheus families (utils/prometheus.py).
        ho = getattr(self.scheduler, "handoff_stats", None)
        if ho:
            out["handoff"] = ho
        # Replica-transport traffic (ISSUE 15): per-replica rpc/retry/
        # timeout counters + lease state for remote fleets — rendered as
        # the lsot_transport_* families (utils/prometheus.py).
        tr = getattr(self.scheduler, "transport_stats", None)
        if tr:
            out["transport"] = tr
        # Cache-aware placement counters (ISSUE 15): how often affinity
        # had an opinion and how often the router took it.
        rt = getattr(self.scheduler, "routing_stats", None)
        if callable(rt):
            try:
                routing = rt()
            except Exception:  # noqa: BLE001 — a churning fleet mid-read
                routing = None
            if routing:
                out["routing"] = routing
        # Liveness view (serve/watchdog.py): heartbeat age/cadence, slots
        # retired for per-lane stalls, and — when supervised — whole-loop
        # stalls detected + the active stall threshold.
        wd = getattr(self.scheduler, "watchdog_stats", None)
        if wd is not None:
            out["watchdog"] = wd
        # Flight-recorder occupancy (counts only — the records themselves
        # live at /debug/flightrecorder, too hot-path-adjacent for every
        # /metrics scrape to serialize). Prefer the flight_stats() seam:
        # a SupervisedScheduler's own `.flight` is the sparse lifecycle
        # ring, not the per-round ring an operator monitors.
        fs = getattr(self.scheduler, "flight_stats", None)
        if callable(fs):
            out["flight_recorder"] = fs()
        else:
            fl = getattr(self.scheduler, "flight", None)
            if fl is not None:
                out["flight_recorder"] = fl.stats()
        # Per-replica load attribution (SchedulerPool): queue depth ×
        # cadence per replica, the placement-score feed.
        loads = getattr(self.scheduler, "replica_loads", None)
        if callable(loads):
            out["replicas"] = loads()
        # Per-model serving aggregation (ISSUE 16): queue depth, tok/s
        # and KV pages held per co-resident checkpoint — the
        # lsot_model_* Prometheus families. None (single-model fleets)
        # adds nothing, keeping the pre-multi-model payload intact.
        ms = getattr(self.scheduler, "model_stats", None)
        if callable(ms):
            try:
                models = ms()
            except Exception:  # noqa: BLE001 — a churning fleet mid-read
                models = None
            if models:
                out["models"] = models
        # Multi-tenant QoS (ISSUE 18): per-tenant WFQ/admission counters
        # — the lsot_tenant_* families. None (QoS off, or a scheduler
        # without the seam) adds nothing: the pre-QoS payload intact.
        qs = getattr(self.scheduler, "qos_stats", None)
        if callable(qs):
            try:
                qos_block = qs()
            except Exception:  # noqa: BLE001 — a churning fleet mid-read
                qos_block = None
            if qos_block:
                out["qos"] = qos_block
        # Elastic fleet membership (ISSUE 17): size/joins/retires/drain
        # ledger + pushed-handoff depth/bytes/latency — rendered as the
        # lsot_fleet_* families (utils/prometheus.py).
        fs2 = getattr(self.scheduler, "fleet_stats", None)
        if callable(fs2):
            try:
                fleet = fs2()
            except Exception:  # noqa: BLE001 — a churning fleet mid-read
                fleet = None
            if fleet:
                out["fleet"] = fleet
        sup = self.health()
        if sup is not None:
            out["supervisor"] = sup
        return out

    @classmethod
    def from_loader(
        cls,
        load: Callable[[object], Tuple[LlamaConfig, Params]],
        tokenizer,
        *,
        name: str,
        mesh=None,
        num_slots: int = 8,
        prompt_bucket: int = 128,
        stop_ids: Optional[Sequence[int]] = None,
        kv_quant: Optional[str] = None,
        kv_layout: str = "paged",
        kv_page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
        kv_hbm_budget_bytes: Optional[int] = None,
        kv_overcommit: Optional[float] = None,
        kv_spill: Optional[bool] = None,
        kv_watermark_low: Optional[float] = None,
        kv_watermark_high: Optional[float] = None,
        max_seq: Optional[int] = None,
        decode_chunk: int = 8,
        speculative_draft: int = 0,
        max_queue_depth: int = 0,
        supervise: bool = False,
        max_restarts: int = 5,
        max_entry_replays: int = 0,
        journal_spill: Optional[str] = None,
        stall_factor: float = 16.0,
        stall_min_s: float = 10.0,
        stall_warmup_s: float = 0.0,
        **kwargs,
    ) -> "SchedulerBackend":
        """Deployment path for concurrent serving: `load(mesh) -> (cfg,
        params)` — a checkpoint reader (`from_hf_checkpoint`, `from_gguf`)
        or seeded weights at a registered shape (`chip_smoke.py`) —
        straight into a WARM continuous-batching scheduler (the product's
        `--scheduler` flag, app/__main__.py). The mesh (if any) must be
        dp=1 — request parallelism comes from slots. With `supervise=True`
        the scheduler runs under a crash supervisor (serve/supervisor.py):
        the params stay loaded, and a decode-loop crash tears down +
        rebuilds the scheduler and replays journaled requests instead of
        503ing until a human restarts the process."""
        from .backends import resolve_stop_ids

        cfg, params = load(mesh)

        def make_sched():
            # Factory, not instance: the supervisor rebuilds from the SAME
            # loaded (and possibly quantized/sharded) params after a crash
            # — one load per process, not per restart. Warm before the
            # loop starts: see warmup()'s liveness note.
            sched = ContinuousBatchingScheduler(
                cfg, params, num_slots=num_slots, max_seq=max_seq,
                decode_chunk=decode_chunk, prompt_bucket=prompt_bucket,
                stop_ids=stop_ids if stop_ids is not None
                else resolve_stop_ids(cfg, tokenizer),
                mesh=mesh, kv_quant=kv_quant,
                kv_layout=kv_layout, kv_page_size=kv_page_size,
                kv_pages=kv_pages,
                kv_hbm_budget_bytes=kv_hbm_budget_bytes,
                kv_overcommit=kv_overcommit, kv_spill=kv_spill,
                kv_watermark_low=kv_watermark_low,
                kv_watermark_high=kv_watermark_high,
                speculative_draft=speculative_draft,
                max_queue_depth=max_queue_depth,
            )
            sched.warmup()
            return sched

        if supervise:
            from .supervisor import SupervisedScheduler

            return cls(SupervisedScheduler(
                make_sched, max_restarts=max_restarts,
                max_entry_replays=max_entry_replays,
                spill_path=journal_spill,
                stall_factor=stall_factor, stall_min_s=stall_min_s,
                warmup_grace_s=stall_warmup_s,
                name=f"scheduler:{name}",
            ), tokenizer, **kwargs)
        return cls(make_sched(), tokenizer, **kwargs)

    @classmethod
    def from_hf_checkpoint(
        cls, ckpt_dir: str, tokenizer, mesh=None, dtype=None,
        quantize_int8: bool = False, quantize_int4: bool = False,
        quantize_unembed8: bool = False, **opts,
    ) -> "SchedulerBackend":
        """`from_loader` over an HF checkpoint directory. Mirrors
        `EngineBackend.from_hf_checkpoint` incl. int8/int4 weight-only
        quantization (`kv_quant="int8"` for the persistent KV cache rides
        `opts` — halves the serving window's HBM footprint and decode
        streaming)."""
        from ..checkpoint import load_and_quantize, load_hf_checkpoint

        return cls.from_loader(
            lambda m: load_and_quantize(
                lambda m_: load_hf_checkpoint(
                    ckpt_dir, dtype=dtype or jnp.bfloat16, mesh=m_),
                m, quantize_int8=quantize_int8, quantize_int4=quantize_int4,
                quantize_unembed8=quantize_unembed8),
            tokenizer, name=os.path.basename(ckpt_dir.rstrip("/")),
            mesh=mesh, **opts)

    @classmethod
    def from_gguf(
        cls, gguf_path: str, tokenizer, cfg=None, mesh=None, dtype=None,
        quantize_int8: bool = False, quantize_int4: bool = False,
        quantize_unembed8: bool = False, **opts,
    ) -> "SchedulerBackend":
        """`from_loader` over a GGUF blob (C++ parse + dequant,
        native/src/gguf.cpp). `quantize_int8`/`quantize_int4` re-quantize
        the dequantized blob into the in-tree serving formats (a Q4 blob
        served with quantize_int4 stays 4-bit end to end)."""
        from ..checkpoint import load_and_quantize, load_gguf_checkpoint

        return cls.from_loader(
            lambda m: load_and_quantize(
                lambda m_: load_gguf_checkpoint(
                    gguf_path, cfg=cfg, dtype=dtype, mesh=m_),
                m, quantize_int8=quantize_int8, quantize_int4=quantize_int4,
                quantize_unembed8=quantize_unembed8),
            tokenizer, name=os.path.basename(gguf_path), mesh=mesh, **opts)

    def _rclass(self, constrain) -> str:
        """The request-class label for the metrics histograms: grammar
        constraining and speculation have distinct latency profiles, and
        an operator pricing the NL→SQL hot path needs ITS numbers."""
        parts = []
        if constrain is not None:
            parts.append("constrained")
        if getattr(self.scheduler, "_spec_draft", 0):
            parts.append("speculative")
        return "+".join(parts)

    def flight_snapshot(self, last: Optional[int] = None):
        """Live flight-recorder view (per-round records; pool-merged and
        replica-labeled for dp>1) — the /debug/flightrecorder payload."""
        return merge_snapshots([self.scheduler], last)

    def prefix_registry(self, top_k: Optional[int] = None
                        ) -> Optional[Dict[str, object]]:
        """Content-addressed prefix-cache registry (ISSUE 14) — the
        /debug/prefixcache payload: top-K resident digests with live
        metadata, reuse-distance histogram, churn counters; pool-shaped
        ({"replicas": [...]}) for fleets. None for schedulers without
        the seam (duck-typed fakes)."""
        fn = getattr(self.scheduler, "prefix_registry", None)
        return fn(top_k) if callable(fn) else None

    def profile_rounds(self, rounds: Optional[int] = None,
                       out_dir: Optional[str] = None) -> Dict[str, object]:
        """On-demand device capture seam (the /debug/profile POST body):
        arm a bounded jax.profiler trace around the scheduler's next N
        rounds. Raises ValueError for backends whose scheduler has no
        profiling seam (duck-typed fakes)."""
        fn = getattr(self.scheduler, "profile_rounds", None)
        if not callable(fn):
            raise ValueError("backend scheduler does not support device "
                             "profiling")
        return fn(rounds, out_dir)

    def profile_status(self) -> Optional[Dict[str, object]]:
        fn = getattr(self.scheduler, "profile_status", None)
        return fn() if callable(fn) else None

    def check_budget(self, prompt: str,
                     max_new_tokens: Optional[int] = None,
                     constraint=None) -> None:
        """Raise ValueError if `prompt` leaves no decode room in the serving
        window — the same rejection complete()/complete_stream() would make,
        runnable BEFORE a streaming handler puts 200 headers on the wire
        (after which a request-shape error can only be a mid-stream line).
        With a compiled `constraint`, also checks that the CLAMPED budget
        (what submit() will actually receive after the decode-room clamp,
        not the raw requested value) can hold a complete parse."""
        ids = self.tokenizer.encode(prompt, add_bos=self.add_bos)
        budget = self._budget(len(ids), max_new_tokens)
        if constraint is not None and budget < constraint.min_new_tokens:
            raise ValueError(
                f"decode budget {budget} (after the serving-window clamp) "
                f"cannot hold a complete constrained parse (grammar needs "
                f">= {constraint.min_new_tokens} tokens incl. the stop id)"
            )

    def _resolve_constraint(self, constrain):
        # Constrained requests ride the speculative scheduler too: the
        # verify window evaluates the grammar mask at every draft position
        # (scheduler._build_spec_decode), so there is nothing to reject
        # here anymore — the resolver's only job is compiling the spec.
        from .backends import resolve_constraint

        return resolve_constraint(constrain, self.tokenizer,
                                  self.scheduler.stop_ids)

    def _constraint_kwargs(self, constrain) -> Dict[str, object]:
        """submit() kwargs for a constraint: the compiled tables always,
        plus the raw serializable SPEC when the scheduler is supervised
        (its journal spill writes the spec and recompiles it at
        recovery — serve/supervisor.py; a bare scheduler has no journal
        and no constraint_spec parameter)."""
        kwargs: Dict[str, object] = {
            "constraint": self._resolve_constraint(constrain)
        }
        if constrain is not None and hasattr(self.scheduler,
                                             "constraint_resolver"):
            kwargs["constraint_spec"] = constrain
        return kwargs

    def _model_kwargs(self) -> Dict[str, object]:
        """submit() kwargs for the model axis: present only when this
        backend is model-scoped AND the scheduler routes on models —
        bare schedulers and test fakes keep their exact signatures."""
        return {"model_id": self.model_id} if self._routes_models else {}

    def _qos_kwargs(self, tenant: str, qos: str) -> Dict[str, object]:
        """submit() kwargs for the tenant/qos axis (ISSUE 18): present
        only for labeled requests on a QoS-capable scheduler — the
        unlabeled path keeps the exact pre-QoS call shape."""
        if (tenant or qos) and self.supports_qos:
            return {"tenant": tenant, "qos": qos}
        return {}

    def _budget(self, n_prompt_tokens: int, max_new_tokens: Optional[int]) -> int:
        sched = self.scheduler
        overshoot = sched.overshoot
        room = sched.max_seq - 1 - overshoot - bucket_len(
            n_prompt_tokens, sched.prompt_bucket
        )
        if room < 1:
            raise ValueError(
                f"prompt ({n_prompt_tokens} tokens) leaves no room in the "
                f"{sched.max_seq}-token scheduler window of {sched.cfg.name}"
            )
        return min(max_new_tokens or self.max_new_tokens, room)

    def complete_stream(self, prompt: str,
                        max_new_tokens: Optional[int] = None,
                        sampling: Optional[SamplingParams] = None,
                        seed: int = 0,
                        stats_out: Optional[dict] = None,
                        constrain=None,
                        deadline_s: Optional[float] = None,
                        tenant: str = "", qos: str = "",
                        stages: Optional[StageTimer] = None):
        """Stream the completion as text chunks while it decodes — the
        capability Ollama's `stream=true` API exposes and the reference
        never used. Token ids arrive from the scheduler's per-request
        callback; text is re-decoded incrementally and emitted as clean
        deltas (a chunk is held back while the byte-level decode of a
        partial multi-byte sequence would surface U+FFFD, and the last
        `longest stop text - 1` chars stay held so a stop spanning chunk
        boundaries never leaks — streamed text equals blocking text).

        Each token re-decodes the accumulated ids (O(n^2) over the
        completion) ON PURPOSE: prefix-decode is not compositional for
        BPE/sentencepiece boundaries, the cost is host-side microseconds
        per token against human-reading-rate output, and exactness vs the
        blocking path is the contract the tests pin. What it does cost is
        the `stream.detok` span of `stages`, the stream's StageTimer (the
        caller's, which reads the sum and may add spans of its own).
        `stats_out` also gets `stream_lag_p90_s`: per token,
        from the worker's `emit` — which stamps the time beside the token
        — to the piece leaving this generator."""
        from ..utils import tracing
        from .backends import trim_stop_texts

        ids = self.tokenizer.encode(prompt, add_bos=self.add_bos)
        if stats_out is not None:
            # Accounting seam for GenerationService.generate_stream: the
            # prompt is tokenized here anyway, and chunk counts are not
            # token counts (holdbacks merge many tokens into one chunk).
            stats_out["prompt_tokens"] = len(ids)
        toks: "queue.Queue[Tuple[int, float]]" = queue.Queue()
        trace = tracing.current()
        stages = stages if stages is not None else StageTimer()
        lags: List[float] = []
        t_submit = time.perf_counter()
        on_tok, first_at = _first_token_timer(
            lambda tok: toks.put((tok, time.perf_counter())))
        fut = self.scheduler.submit(
            ids, max_new_tokens=self._budget(len(ids), max_new_tokens),
            sampling=sampling or self.sampling, seed=seed,
            on_token=on_tok, **self._constraint_kwargs(constrain),
            deadline_s=deadline_s if deadline_s is not None
            else self.deadline_s,
            trace=trace, **self._model_kwargs(),
            **self._qos_kwargs(tenant, qos),
        )
        out_ids: List[int] = []
        emitted = ""
        hold = max((len(s) for s in self.stop_texts), default=1) - 1

        try:
            done = False
            while not done:
                try:
                    tok, t_emit = toks.get(timeout=0.05)
                except queue.Empty:
                    done = fut.done()
                    continue
                out_ids.append(tok)
                with stages.stage("stream.detok"):
                    text = self.tokenizer.decode(out_ids)
                    trimmed = trim_stop_texts(text, self.stop_texts)
                if trimmed != text:  # a stop text landed: flush and end
                    if len(trimmed) > len(emitted):
                        yield trimmed[len(emitted):]
                    # Stop texts are host-side only (the scheduler knows stop
                    # IDS, not stop strings): without a cancel the slot keeps
                    # decoding the full remaining budget for text that is
                    # already final, delaying the terminal chunk and the
                    # slot's release. Cancel retires it at the next harvest;
                    # the future then resolves with what was generated, so
                    # result() still surfaces scheduler errors.
                    self.scheduler.cancel(fut)
                    fut.result()
                    return
                # Emit up to the holdback horizon, minus any trailing
                # partial multi-byte replacement char.
                safe = text[: len(text) - hold if hold else len(text)]
                delta = safe[len(emitted):]
                if delta and not delta.endswith("�"):
                    emitted += delta
                    lags.append(time.perf_counter() - t_emit)
                    yield delta
            fut.result()  # propagate errors; also syncs the token list
            while not toks.empty():
                out_ids.append(toks.get_nowait()[0])
            text = trim_stop_texts(
                self.tokenizer.decode(out_ids), self.stop_texts
            )
            if len(text) > len(emitted):
                yield text[len(emitted):]
        finally:
            # Consumer gone mid-stream (GeneratorExit lands on a yield):
            # cancel so the slot stops decoding an abandoned request.
            if not fut.done():
                self.scheduler.cancel(fut)
                if trace is not None:
                    # Traced abandon: the worker flushes the sched.* spans
                    # at the retiring harvest, but the HTTP layer exports
                    # the trace the moment this generator closes — without
                    # a bounded wait the artifact for exactly the
                    # abandoned/stuck streams being diagnosed would carry
                    # stream.deliver and zero scheduler spans. One harvest
                    # normally lands in milliseconds; the cap keeps a
                    # wedged loop from hanging disconnect cleanup.
                    try:
                        fut.result(timeout=2.0)
                    except Exception:  # noqa: BLE001 — export best-effort
                        pass
            if trace is not None:
                # The delivery window: first submit to last chunk handed
                # to the consumer — what the CLIENT experienced, beside
                # the scheduler-side decode spans.
                trace.add_span("stream.deliver", t_submit,
                               time.perf_counter(), chunks=len(out_ids))
            if stats_out is not None:
                stats_out["output_tokens"] = len(out_ids)
                if first_at:
                    stats_out["ttft_s"] = first_at[0] - t_submit
                qw = getattr(fut, "_lsot_queue_wait", 0.0)
                if qw:
                    stats_out["queue_wait_s"] = qw
                stats_out.update(getattr(fut, "_lsot_waits", {}))
                if lags:
                    lags.sort()
                    stats_out["stream_lag_p90_s"] = lags[
                        min(len(lags) - 1, int(0.9 * len(lags)))]
                stats_out["rclass"] = self._rclass(constrain)
                stats_out["replica"] = getattr(fut, "_lsot_replica", "")

    def complete(self, prompt: str, max_new_tokens: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None, seed: int = 0,
                 constrain=None, deadline_s: Optional[float] = None,
                 idempotency_key: Optional[str] = None,
                 tenant: str = "", qos: str = ""):
        from .backends import Completion, trim_stop_texts

        from ..utils import tracing

        ids = self.tokenizer.encode(prompt, add_bos=self.add_bos)
        t_submit = time.perf_counter()
        on_tok, first_at = _first_token_timer()
        kwargs = {}
        if idempotency_key is not None:
            # Only the supervised scheduler takes the key (journal dedup);
            # GenerationService gates on supports_idempotency before
            # forwarding, so a bare scheduler never sees the kwarg.
            kwargs["idempotency_key"] = idempotency_key
        fut = self.scheduler.submit(
            ids, max_new_tokens=self._budget(len(ids), max_new_tokens),
            sampling=sampling or self.sampling, seed=seed, on_token=on_tok,
            **self._constraint_kwargs(constrain),
            deadline_s=deadline_s if deadline_s is not None
            else self.deadline_s,
            trace=tracing.current(),
            **kwargs, **self._model_kwargs(),
            **self._qos_kwargs(tenant, qos),
        )
        out = fut.result()
        text = trim_stop_texts(self.tokenizer.decode(out), self.stop_texts)
        return Completion(text=text, output_tokens=len(out),
                          prompt_tokens=len(ids),
                          ttft_s=(first_at[0] - t_submit) if first_at else 0.0,
                          queue_wait_s=getattr(fut, "_lsot_queue_wait", 0.0),
                          rclass=self._rclass(constrain),
                          replica=getattr(fut, "_lsot_replica", ""))

    def complete_batch(
        self, prompts: Sequence[str], max_new_tokens: Optional[int] = None,
        sampling: Optional[SamplingParams] = None, seed: int = 0,
        constrain=None, deadline_s: Optional[float] = None,
        tenant: str = "", qos: str = "",
    ):
        """Submit the whole batch at once: the scheduler interleaves the
        prompts through its slot pool, so this IS continuous batching —
        unlike EngineBackend's single padded program, raggedness costs
        nothing beyond bucketing."""
        from .backends import Completion, trim_stop_texts

        constraint_kwargs = self._constraint_kwargs(constrain)
        effective_deadline = (deadline_s if deadline_s is not None
                              else self.deadline_s)
        ids_list = [
            self.tokenizer.encode(p, add_bos=self.add_bos) for p in prompts
        ]
        t_submit = time.perf_counter()
        timers = [_first_token_timer() for _ in ids_list]
        futs = [
            self.scheduler.submit(
                ids, max_new_tokens=self._budget(len(ids), max_new_tokens),
                sampling=sampling or self.sampling, seed=seed,
                on_token=on_tok, **constraint_kwargs,
                deadline_s=effective_deadline, **self._model_kwargs(),
                **self._qos_kwargs(tenant, qos),
            )
            for ids, (on_tok, _) in zip(ids_list, timers)
        ]
        firsts = [fl for _, fl in timers]
        completions = []
        for ids, fut, fl in zip(ids_list, futs, firsts):
            out = fut.result()
            text = trim_stop_texts(self.tokenizer.decode(out), self.stop_texts)
            completions.append(Completion(
                text=text, output_tokens=len(out), prompt_tokens=len(ids),
                ttft_s=(fl[0] - t_submit) if fl else 0.0,
                queue_wait_s=getattr(fut, "_lsot_queue_wait", 0.0),
                rclass=self._rclass(constrain),
                replica=getattr(fut, "_lsot_replica", ""),
            ))
        return completions
