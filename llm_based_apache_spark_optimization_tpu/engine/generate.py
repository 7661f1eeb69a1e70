"""Autoregressive generation: batched prefill + `lax.while_loop` decode.

This module is the heart of the in-tree engine that replaces the reference's
`ollama.generate(...)` calls (reference `Flask/app.py:102-107,160-166`,
`FastAPI/app.py:85-90,105-111`). One jit-compiled function per
(batch, prompt-bucket, max_new, sampling) signature does:

    prefill (all prompt tokens at once, MXU-bound)
      -> sample first token from each sequence's last real logit
      -> while_loop decode (one token/step, HBM-bandwidth-bound)
         with per-sequence stop-token handling and early exit when
         every sequence is done.

TPU/XLA notes:
- The whole generate call is ONE XLA program: no host round-trip per token.
  The while_loop carries the KV cache; XLA keeps it in HBM and updates it
  in place.
- Early exit is real: the loop condition is `step < max_new & ~all(done)`,
  so a batch of short SQL answers doesn't pay for the longest possible
  completion.
- Prompt lengths are bucketed (engine/kvcache.bucket_len) so the number of
  distinct compilations stays small; compiled fns are cached per signature.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.configs import LlamaConfig
from ..models.llama import Params, forward, split_blocks
from ..ops.pallas import attention_impl, decode_attention_impl
from ..ops.sampling import SamplingParams, apply_token_mask, sample
from ..parallel.sharding import constrain_cache, shard_batch, shard_params
from .kvcache import bucket_len, init_cache


def _is_stop(tok: jnp.ndarray, stop_ids: Tuple[int, ...]) -> jnp.ndarray:
    hit = jnp.zeros(tok.shape, jnp.bool_)
    for s in stop_ids:
        hit = hit | (tok == s)
    return hit


def make_generate_fn(
    cfg: LlamaConfig,
    max_new: int,
    sampling: SamplingParams,
    stop_ids: Tuple[int, ...],
    mesh=None,
    attn_impl: Optional[str] = None,
    kv_quant: Optional[str] = None,
    constrained: bool = False,
    kv_layout: str = "contiguous",
    kv_page_size: Optional[int] = None,
):
    """Resolve the attention impl *outside* the cache boundary so a
    set_attention_impl() flip between calls maps to a different cache key
    (and thus a fresh compilation) even for callers that omit attn_impl.

    `max_new` here is the compile-time CAP (output buffer width / cache
    allocation); the returned fn takes a traced `budget` argument that bounds
    the decode loop at runtime, so callers can serve any budget <= cap from
    one compilation (serving backends bucket the cap — see
    InferenceEngine.new_bucket — instead of compiling per distinct budget).

    Prefill and decode resolve their impls separately: the engine's cache is
    request-sized and mostly live, so auto-mode decode takes the XLA einsum
    path (`ops.pallas.decode_attention_impl`) — the flash kernel's bounded
    streaming has nothing to bound there and its per-cell overhead is pure
    loss (measured: einsum decode 2160 vs kernel 1978 tok/s at B=8, 4091 vs
    2779 at B=32 on v5e). An explicit `attn_impl` forces both phases.

    `kv_quant="int8"` stores the decode-time KV cache as int8 with per-slot
    scales: prefill fills the normal bf16 cache, one pass quantizes it
    (ops/quant.quantize_kv), and every decode step streams half the cache
    bytes (decode is cache-streaming-bound at long context). Decodes via
    the einsum impl (auto default) or, when forced, the int8-streaming
    flash kernel (flash_gqa_attention_quantized).

    `constrained=True` returns a fn taking two extra traced arguments —
    `(next, need)` grammar tables from
    constrain.CompiledMask.device_tables, plus `init_states [B]` — and
    runs the grammar FSM ON DEVICE: every step gathers the state's
    precomputed tokens-to-finish row, masks out entries that no longer
    fit the remaining budget, and advances the state by one
    [state, token] gather. No host round-trip, no per-token Python over
    the vocab, still ONE XLA program.

    `kv_layout="paged"` swaps the decode loop's cache for the paged pool
    (engine/paged_kv.py): prefill still runs the contiguous scan path over
    a PROMPT-sized transient cache, one transpose-scatter packs it into
    pool pages with identity per-row tables, and every decode step
    reads/writes K/V through the page table — the same paged programs the
    continuous-batching scheduler serves with, parity-tested here where
    the loop is a single jit. Page size rides `kv_page_size` /
    LSOT_KV_PAGE_SIZE.
    """
    if kv_layout not in ("contiguous", "paged"):
        raise ValueError(
            f"kv_layout must be 'contiguous' or 'paged', got {kv_layout!r}"
        )
    page_size = 0
    if kv_layout == "paged":
        from .paged_kv import default_page_size

        page_size = int(kv_page_size or default_page_size())
        # kv_quant="int8" + paged (ISSUE 11): the pool stores int8 pages
        # + per-position scales — quantized inside pack_prefill_pages,
        # dequantized in the ragged read kernel's DMA'd tiles / the
        # int8-streaming reference path. A mesh shards the pool's KV-head
        # axis over tp like the contiguous cache (constrain_cache's paged
        # branch); page tables replicate.
    return _make_generate_fn(
        cfg, max_new, sampling, stop_ids, mesh,
        attn_impl or attention_impl(mesh),
        attn_impl or decode_attention_impl(mesh),
        kv_quant,
        constrained,
        kv_layout,
        page_size,
    )


@functools.lru_cache(maxsize=64)
def _make_generate_fn(
    cfg: LlamaConfig,
    max_new: int,
    sampling: SamplingParams,
    stop_ids: Tuple[int, ...],
    mesh,
    attn_impl: str,
    decode_impl: str,
    kv_quant: Optional[str] = None,
    constrained: bool = False,
    kv_layout: str = "contiguous",
    page_size: int = 0,
):
    """Build + jit a generate function for a fixed decode-budget cap and sampler.

    Returned fn: (params, tokens [B,T] i32, lengths [B] i32, budget [] i32,
    key) -> (out_tokens [B, max_new] i32, gen_lens [B] i32), with the loop
    stopping at the traced `budget` (<= max_new cap). Cached so repeated
    calls with the same signature reuse the compiled executable.

    With a `jax.sharding.Mesh`, the KV cache allocated inside the program is
    pinned to the TP×DP×SP layout (parallel/sharding.cache_spec — KV heads
    over tp, batch over dp, cache SLOTS over sp, so an sp-way mesh fits
    sp× the context); params/tokens carry their own NamedShardings in, and
    GSPMD lays the collectives.
    """
    pad_id = cfg.pad_id
    impl = attn_impl
    # With a sequence-parallel axis in the mesh, prefill runs ring attention
    # (sequence sharded over sp, KV blocks rotating on ICI); decode keeps the
    # resolved single-block impl — its T=1 queries have nothing to shard.
    sp = dict(mesh.shape).get("sp", 1) if mesh is not None else 1
    prefill_impl = "ring" if sp > 1 else impl
    if kv_quant not in (None, "int8"):
        raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
    if sp > 1 and decode_impl == "pallas":
        # The flash decode kernel's shard_map expects S-replicated K/V;
        # against the sp-sharded cache (parallel/sharding.cache_spec) GSPMD
        # would all-gather the whole cache every step — OOM at exactly the
        # long-context sizes sp exists to serve. The einsum path IS the sp
        # decode impl (flash-decoding-style partial combines).
        raise ValueError(
            "attn_impl='pallas' decode cannot run on an sp>1 mesh: the "
            "sequence-sharded cache would be all-gathered every step; use "
            "the auto/einsum decode impl"
        )
    if kv_quant and decode_impl not in ("xla", "pallas"):
        # "xla" is the auto default (uniform engine caches are mostly live
        # — ops.pallas.decode_attention_impl); a forced "pallas" runs the
        # int8-streaming flash decode kernel
        # (flash_gqa_attention_quantized). Ring has no quantized path.
        raise ValueError(
            "kv_quant='int8' decodes through the einsum impl (auto "
            f"default) or the pallas flash kernel; resolved to "
            f"{decode_impl!r}"
        )

    def gen(
        params: Params,
        tokens: jnp.ndarray,
        lengths: jnp.ndarray,
        budget: jnp.ndarray,
        key: jax.Array,
        grammar=None,       # (next [S,V] i32, need [S,V] i32) device tables
        init_states=None,   # [B] int32 DFA start states (0 = unconstrained)
    ):
        b, t = tokens.shape
        # The output buffer and cache are sized for the compile-time cap; a
        # caller-passed budget beyond it would silently corrupt both, so
        # clamp (InferenceEngine always passes budget <= cap, but this fn is
        # exported for direct use).
        budget = jnp.minimum(budget, max_new)
        paged = kv_layout == "paged"
        # Paged mode prefills a PROMPT-sized transient cache (packed into
        # pool pages after the prefill forward); contiguous allocates the
        # whole prompt+completion window up front.
        cache = init_cache(cfg, b, t if paged else t + max_new,
                           dtype=params["final_norm"].dtype)
        if mesh is not None:
            cache = constrain_cache(cache, mesh)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        # Unembed only each sequence's last real position: sampling never looks
        # at the other T-1 logits, and skipping them drops the [B, T, V]
        # prefill unembed to [B, 1, V].
        logits, cache = forward(
            cfg, params, tokens, positions, cache,
            logit_indices=lengths - 1, attn_impl=prefill_impl, mesh=mesh,
        )
        first_logits = logits[:, 0]
        if constrained:
            g_next, g_need = grammar
            # The first token is constrained too (otherwise one free token
            # breaks the guarantee): a token is allowed iff the tokens it
            # commits to — itself, the shortest completion after it, the
            # stop id — fit the whole budget (g_need table, masks.py).
            first_logits = apply_token_mask(
                first_logits, g_need[init_states] <= budget
            )
        first = sample(first_logits, sampling, jax.random.fold_in(key, 0))
        cstate = g_next[init_states, first] if constrained else None
        done = _is_stop(first, stop_ids)
        out = jnp.full((b, max_new), pad_id, jnp.int32)
        out = out.at[:, 0].set(first)
        # Per-layer weight slices anchored OUTSIDE the decode loop: layout
        # conversions for the decode matmuls run once per call, not per
        # token (split_blocks docstring). Only the unrolled decode branch
        # accepts pre-sliced params — a forced ring impl scans instead.
        dec_params = params if decode_impl == "ring" else split_blocks(params)

        if paged:
            # Prefill→decode handoff: pack the prompt K/V into pool pages
            # with identity per-row tables; the while_loop below carries
            # the pool, and forward's paged branch reads/writes through
            # the table every step (the same paged decode program shape
            # the scheduler serves with). kv_quant="int8" quantizes
            # INSIDE the pack (int8 pages + per-position scales) — the
            # same prefill-bf16-then-quantize-once handoff as the
            # contiguous int8 path, per page.
            from .paged_kv import lane_pack, pack_prefill_pages

            ppr = -(-(t + max_new) // page_size)
            tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
            cache = pack_prefill_pages(
                cache, page_size, ppr, kv_quant=kv_quant,
                pack=lane_pack(cfg, kv_quant, tp))
            if mesh is not None:
                cache = constrain_cache(cache, mesh)
        elif kv_quant:
            # One-pass cache quantization between prefill and decode: the
            # loop carries int8 values + f32 per-slot scales and every step
            # streams ~half the cache bytes (ops/quant.quantize_kv).
            from ..ops.quant import quantize_cache

            cache = quantize_cache(cache["k"], cache["v"])
            if mesh is not None:
                cache = constrain_cache(cache, mesh)

        def cond(carry):
            done, step = carry[3], carry[5]
            return (step < budget) & ~jnp.all(done)

        def body(carry):
            out, cur, pos, done, cache, step = carry[:6]
            logits, cache = forward(
                cfg, dec_params, cur[:, None], pos[:, None], cache,
                attn_impl=decode_impl, mesh=mesh,
            )
            step_logits = logits[:, 0]
            if constrained:
                cstate = carry[6]
                # A token is allowed iff its completion still fits the
                # remaining budget (need table): tokens that merely keep
                # the DFA alive but can no longer close in time drop out
                # exactly when that becomes true, so the guarantee holds
                # for any budget >= the grammar's shortest parse. One
                # gather + one compare per step.
                rem = budget - step
                step_logits = apply_token_mask(
                    step_logits, g_need[cstate] <= rem
                )
            nxt = sample(step_logits, sampling, jax.random.fold_in(key, step))
            nxt = jnp.where(done, pad_id, nxt)
            tail = ()
            if constrained:
                # Finished rows freeze their state (their pad fill must not
                # walk the FSM); live rows advance one [state, token]
                # gather — the whole per-step grammar cost.
                tail = (jnp.where(done, cstate, g_next[cstate, nxt]),)
            done = done | _is_stop(nxt, stop_ids)
            out = lax.dynamic_update_slice(out, nxt[:, None], (0, step))
            return (out, nxt, pos + 1, done, cache, step + 1) + tail

        carry = (out, first, lengths.astype(jnp.int32), done, cache,
                 jnp.int32(1))
        if constrained:
            carry = carry + (cstate,)
        final = lax.while_loop(cond, body, carry)
        out, done = final[0], final[3]

        stops = _is_stop(out, stop_ids)
        gen_lens = jnp.where(
            jnp.any(stops, axis=1),
            jnp.argmax(stops, axis=1).astype(jnp.int32) + 1,
            budget.astype(jnp.int32),
        )
        return out, gen_lens

    return jax.jit(gen)


class InferenceEngine:
    """Convenience host-side wrapper: ragged python prompts -> ragged outputs.

    Pads/buckets prompts, dispatches to the cached jitted generate fn, and
    slices per-sequence completions. This is the object the serve/ registry
    holds per model name.
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        params: Params,
        stop_ids: Optional[Sequence[int]] = None,
        prompt_bucket: int = 128,
        mesh=None,
        new_bucket: int = 64,
        speculative_draft: int = 0,
        speculative_ngram: int = 3,
        kv_quant: Optional[str] = None,
        fuse_matmuls: bool = False,
        kv_layout: str = "contiguous",
        kv_page_size: Optional[int] = None,
    ):
        self.cfg = cfg
        self.mesh = mesh
        # Fused wqkv/wgu matmuls (models/llama.fuse_blocks): fewer, wider
        # MXU calls — a prefill-throughput lever.
        if fuse_matmuls:
            from ..models.llama import maybe_fuse

            params = maybe_fuse(params, mesh)
        # "int8": decode streams an int8 KV cache (half the cache bytes;
        # make_generate_fn docstring). Greedy/sampled both supported. The
        # CONTIGUOUS speculative path has no int8-KV variant (its verify
        # loop streams the bf16 cache), and silently dropping a requested
        # memory/bandwidth mode would misattribute results — so that
        # combination stays rejected; the PAGED pool's verify windows run
        # the int8-streaming reference gather, so int8 + paged +
        # speculative composes.
        if kv_quant and speculative_draft and kv_layout != "paged":
            raise ValueError(
                "kv_quant and speculative_draft cannot combine on the "
                "contiguous layout: the speculative verify loop streams "
                "the bf16 cache (use kv_layout='paged')"
            )
        self.kv_quant = kv_quant
        # "paged": decode loops carry the shared page pool + per-row page
        # tables instead of a contiguous cache (engine/paged_kv.py) —
        # greedy-parity-tested against the contiguous layout, and the
        # engine-side proof of the programs the scheduler serves with.
        # Composes with kv_quant="int8" (int8 pages + per-position
        # scales) and with a mesh (pool KV heads shard over tp).
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"kv_layout must be 'contiguous' or 'paged', got "
                f"{kv_layout!r}"
            )
        self.kv_layout = kv_layout
        self.kv_page_size = kv_page_size
        # Prompt-lookup speculative decoding (engine/speculative.py):
        # requests draft `speculative_draft` tokens per round by n-gram
        # lookup over prompt+history and verify them in one forward. 0
        # disables. Greedy requests verify by exact argmax; sampled
        # requests verify by rejection sampling (unbiased).
        self.speculative_draft = speculative_draft
        self.speculative_ngram = speculative_ngram
        # Diagnostics from the last speculative generate: verify rounds vs
        # tokens emitted (rounds << tokens means drafts were accepted).
        self.last_spec_rounds: Optional[int] = None
        if mesh is not None:
            params = shard_params(params, cfg, mesh)
        self.params = params
        self.stop_ids = tuple(stop_ids) if stop_ids is not None else cfg.stop_ids
        # A bucket as large as the whole context would leave no decode room
        # after bucketing even a short prompt; cap at half the context.
        self.prompt_bucket = min(prompt_bucket, max(1, cfg.max_seq_len // 2))
        # Decode budgets are bucketed the same way prompts are: the compiled
        # program's cap rounds up to a multiple of new_bucket and the loop
        # stops at the traced budget, so serving backends that clamp
        # max_new to per-prompt context room (serve/backends.py) don't
        # compile one program per distinct budget value.
        self.new_bucket = max(1, new_bucket)

    def padded_prompt_len(self, n: int) -> int:
        """Device-side prompt length for an n-token prompt: bucketed, then —
        on an sp mesh — padded so ring prefill gives each device an equal
        sequence block. Callers budgeting decode room against max_seq_len
        (serve/backends.py) must use this, not bucket_len alone."""
        t = bucket_len(n, self.prompt_bucket)
        if self.mesh is not None:
            t += -t % dict(self.mesh.shape).get("sp", 1)
        return t

    def generate(
        self,
        prompts: List[List[int]],
        max_new_tokens: int = 256,
        sampling: SamplingParams = SamplingParams(),
        seed: int = 0,
        constraint=None,  # constrain.CompiledMask: grammar-masked decode
    ) -> List[List[int]]:
        assert prompts and all(len(p) >= 1 for p in prompts), "empty prompt"
        b = len(prompts)
        if constraint is not None and max_new_tokens < constraint.min_new_tokens:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} cannot hold a complete "
                f"constrained parse (grammar needs >= "
                f"{constraint.min_new_tokens} tokens incl. the stop id)"
            )
        t = self.padded_prompt_len(max(len(p) for p in prompts))
        if t + max_new_tokens > self.cfg.max_seq_len:
            raise ValueError(
                f"bucketed prompt ({t}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds model context max_seq_len={self.cfg.max_seq_len}"
            )
        padded = list(prompts)
        if self.mesh is not None:
            # The batch axis shards over dp; pad with dummy rows to a multiple
            # of dp (sliced off after decode) so any request count works.
            dp = self.mesh.shape["dp"]
            padded += [[self.cfg.bos_id]] * (-b % dp)
        tokens = jnp.asarray(
            [p + [self.cfg.pad_id] * (t - len(p)) for p in padded], jnp.int32
        )
        lengths = jnp.asarray([len(p) for p in padded], jnp.int32)
        if self.mesh is not None:
            tokens, lengths = shard_batch((tokens, lengths), self.mesh)
        cap = min(bucket_len(int(max_new_tokens), self.new_bucket),
                  self.cfg.max_seq_len - t)
        if self.speculative_draft > 0:
            # Constrained requests speculate too: the verify window
            # evaluates the grammar mask at every draft position
            # (constrain.fsm_advance_chain threads per-position FSM states
            # through the chain), so drafted tokens cannot bypass the mask
            # and greedy output stays token-identical to constrained
            # vanilla decode. Sampled requests run rejection-sampling
            # verification (engine/speculative.rejection_sample_chain):
            # distribution-identical to the vanilla sampled loop, not
            # token-identical — the RNG consumption pattern differs.
            from .speculative import make_speculative_generate_fn

            fn = make_speculative_generate_fn(
                self.cfg, cap, self.stop_ids, self.mesh,
                self.speculative_draft, self.speculative_ngram,
                constrained=constraint is not None,
                kv_layout=self.kv_layout, kv_page_size=self.kv_page_size,
                kv_quant=self.kv_quant,
                sampling=sampling,
            )
            args = [
                self.params, tokens, lengths, jnp.int32(max_new_tokens),
                # key: unused by the greedy verify, drives the
                # accept/residual draws in sampled mode.
                None if sampling.is_greedy else jax.random.key(seed),
            ]
            if constraint is not None:
                tabs = constraint.device_tables(self.cfg.vocab_size)
                args += [
                    (tabs["next"], tabs["need"]),
                    jnp.full((tokens.shape[0],), constraint.init_state,
                             jnp.int32),
                ]
            out, gen_lens, rounds = fn(*args)
            self.last_spec_rounds = int(jax.device_get(rounds))
        else:
            self.last_spec_rounds = None  # this call ran no speculation
            fn = make_generate_fn(
                self.cfg, cap, sampling, self.stop_ids, self.mesh,
                kv_quant=self.kv_quant,
                constrained=constraint is not None,
                kv_layout=self.kv_layout, kv_page_size=self.kv_page_size,
            )
            args = [
                self.params, tokens, lengths, jnp.int32(max_new_tokens),
                jax.random.key(seed),
            ]
            if constraint is not None:
                tabs = constraint.device_tables(self.cfg.vocab_size)
                args += [
                    (tabs["next"], tabs["need"]),
                    jnp.full((tokens.shape[0],), constraint.init_state,
                             jnp.int32),
                ]
            out, gen_lens = fn(*args)
        out, gen_lens = jax.device_get(out), jax.device_get(gen_lens)
        return [list(map(int, out[i, : gen_lens[i]])) for i in range(b)]
