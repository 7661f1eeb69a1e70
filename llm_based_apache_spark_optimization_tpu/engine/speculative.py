"""Prompt-lookup speculative decoding: n-gram drafts, one-forward verify.

NL→SQL output is dominated by tokens COPIED from the prompt — column names,
the table name, literals from the question (the reference's whole workload:
schema + question in, SQL over that schema out, reference
`Flask/app.py:98-107`). Prompt-lookup decoding exploits that: draft the next
`draft_len` tokens by finding the most recent occurrence of the current
n-gram in (prompt + generated-so-far) and copying what followed it, then
verify all drafts with ONE forward pass of T = draft_len + 1. Greedy
verification is exact: the emitted tokens are identical to vanilla greedy
decode token-for-token (asserted in tests/test_speculative.py), whatever the
drafts were — bad drafts only cost speed, never correctness. No draft model,
no extra weights.

TPU-first shape of the idea:

- The whole loop stays ONE XLA program (`lax.while_loop`), like the vanilla
  engine: drafting is a handful of vectorized compares over the token
  history, and verification is a T=draft_len+1 cached forward — the same
  weight stream a T=1 step pays, so a round that accepts `a` drafts divides
  decode's HBM-bound cost by (a+1) at ~zero marginal FLOP cost (the MXU is
  >97% idle at T=1; T=9 is still tiny).
- Verify windows take the unrolled small-T decode path in models/llama.py
  (in-place cache sliver writes), not the prefill scan.
- Rejected drafts leave garbage K/V beyond the accepted point; the next
  round's verify window starts at the first unverified position, so its
  cache writes overwrite exactly that garbage before attention can see it —
  the same visibility invariant engine/kvcache.py documents.
- Sampled requests (temperature > 0) get the SAME draft/verify speedup via
  standard rejection sampling (`rejection_sample_chain`): each drafted
  token is accepted with min(1, p/q) under the target distribution — a
  delta q for these deterministic drafts, so accept iff a uniform draw
  lands under the draft's target mass — and the first rejection resamples
  from the normalized residual max(0, p − q). The emitted tokens are
  exactly a sample from vanilla `sample_runtime`'s distribution (the
  property tests' acceptance bar), while greedy requests keep the exact
  argmax verify (token-identical to vanilla greedy, as before).

Measured cost model (v5e, bench-1b, B=8, D=8): a verify round runs ~1.6x a
vanilla decode step (same weight stream; wider unembed + draft/accept
bookkeeping), so speculation breaks even around ~1.6 accepted tokens per
round and wins above it. Random-weight smoke models accept ~0-1.5 (nothing
real to copy), hence the engine default is OFF; enable it for real
checkpoints on copy-heavy workloads (NL→SQL over a schema is the
archetype — published prompt-lookup results and the reference's own
workload shape put acceptance at 3-6+).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..constrain.masks import fsm_advance_chain
from ..models.configs import LlamaConfig
from ..models.llama import _UNROLL_MAX_T, forward, split_blocks
from ..ops.pallas import attention_impl, decode_attention_impl
from ..ops.sampling import (
    SamplingParams,
    apply_token_mask,
    filtered_runtime_logits,
    sample,
)
from ..parallel.sharding import constrain_cache
from .kvcache import init_cache

# Cost of one T=D+1 verify round relative to a T=1 decode step: the single
# source for every est_speedup_vs_vanilla figure (scheduler
# speculation_stats, bench speculative block) — re-measure here, and both
# surfaces move together. ADVICE r5 #3: the old single 1.6 constant was
# measured at ONE draft length (D=8) and silently mispriced every other
# config, so the cost is now a LINEAR MODEL in draft length, fit at two
# anchor shapes:
#   D=0: ratio 1.0 by construction — a T=1 "verify" IS a vanilla decode
#        step (same forward, argmax instead of sample).
#   D=8: ratio 1.6 measured (v5e, bench-1b, B=8 — module docstring).
# Linearity is the right first-order model because the verify forward pays
# the same weight stream at any small T (the MXU is >97% idle at T=1) and
# the extra cost — wider unembed, draft/accept bookkeeping — scales with
# the window width. At other SHAPES (7B, int8/int4, TP meshes) the whole
# line can shift, so /metrics labels the estimate with its calibration
# instead of presenting it as universal.
VERIFY_COST_ANCHORS = ((0, 1.0), (8, 1.6))
VERIFY_COST_CALIBRATION = (
    "linear in draft length, anchored at D=0 (=1.0 by construction) and "
    "D=8 (=1.6 measured: v5e, bench-1b, B=8, bf16); other shapes scale "
    "the slope by (unembed marginal / weight-stream fixed) cost relative "
    "to that anchor"
)


def _param_count(cfg) -> int:
    """Approximate parameter count from the architecture shape — the
    decode step's fixed cost is streaming these bytes."""
    d, f, n_layers = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nh, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = d * nh * hd + 2 * d * kh * hd + nh * hd * d
    mlp = 3 * d * f
    emb = cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2)
    return emb + n_layers * (attn + mlp + 2 * d) + d


def infer_weight_bits(params) -> int:
    """Weight bits/param of a params tree: 4 for int4-packed trees, 8 for
    int8 QTensor trees, else the leaf dtype width — the shape input
    `verify_cost_ratio` prices the fixed weight stream with."""
    import jax

    blocks = params.get("blocks", params)
    sample = blocks[0] if isinstance(blocks, (list, tuple)) else blocks
    if isinstance(sample, dict):
        if any(isinstance(v, dict) and "q4" in v for v in sample.values()):
            return 4
        if any(isinstance(v, dict) and "q8" in v for v in sample.values()):
            return 8
    return jax.tree.leaves(params)[0].dtype.itemsize * 8


def verify_cost_ratio(draft_len: int, cfg=None, weight_bits: int = 16,
                      tp: int = 1) -> float:
    """verify(T=draft_len+1) / decode(T=1) cost under the anchored linear
    model, scaled to the caller's MODEL SHAPE (ROADMAP carried-over item:
    the old signature priced every config at the 1B bench shape).

    The slope — marginal cost per extra window position — is dominated by
    the unembed (a V×D dot and a V-wide f32 logit row per position; the
    block matmuls are MXU-idle at small T), while the round's fixed cost
    is streaming the weight bytes. So the slope scales with
    (vocab·hidden) / weight_bytes relative to the anchor shape (bench-1b
    bf16), where `weight_bits` prices int8/int4 trees (fewer fixed bytes →
    a verify window is relatively MORE expensive → higher breakeven).
    `tp` cancels to first order — each device streams 1/tp of the weights
    AND computes 1/tp of the unembed — and is accepted so callers can
    record their topology; only the collective overhead it adds is
    unmodeled. Floors at 1.0: a verify round can never be cheaper than
    the vanilla step it replaces."""
    del tp  # cancels: fixed and marginal costs shard identically
    (d0, r0), (d1, r1) = VERIFY_COST_ANCHORS
    slope = (r1 - r0) / (d1 - d0)
    if cfg is not None:
        from ..models.configs import BENCH_1B

        def marg_over_fixed(c, bits):
            return (c.vocab_size * c.hidden_size) / (
                _param_count(c) * bits / 8
            )

        slope *= marg_over_fixed(cfg, weight_bits) / marg_over_fixed(
            BENCH_1B, 16
        )
    return max(1.0, r0 + slope * (draft_len - d0))


#: Backward-compatible single-number view: the D=8 anchor (the bench's
#: historical default draft). Prefer verify_cost_ratio(D) — this constant
#: only prices D=8 correctly.
VERIFY_COST_RATIO = verify_cost_ratio(8)


def ngram_draft(
    hist: jnp.ndarray,      # [B, HT] i32 token history (prompt + generated)
    hist_len: jnp.ndarray,  # [B] i32 — tokens valid in hist (incl. current)
    draft_len: int,
    ngram: int,
) -> jnp.ndarray:
    """Draft [B, draft_len] tokens by prompt lookup.

    For each row: take the trailing `ngram` tokens of the history (the
    current context suffix), find an earlier occurrence, and copy the
    `draft_len` tokens that followed it. Occurrence choice: the LATEST
    match whose whole draft window is already-written history (recency
    predicts best), else the EARLIEST match — a late match near the tail
    has almost no written continuation to copy (a pure-repetition loop
    would cap at ~period tokens per round), while the earliest match
    maximizes it. No occurrence -> returns whatever sits at the history
    tail (padding); those drafts simply fail verification. All comparisons
    are static-shape; per-row starts ride dynamic slices.
    """
    b, ht = hist.shape
    nw = ht - ngram + 1  # number of n-gram windows

    def row(h, hlen):
        suffix = lax.dynamic_slice(h, (hlen - ngram,), (ngram,))
        match = jnp.ones((nw,), jnp.bool_)
        for j in range(ngram):
            match = match & (lax.slice(h, (j,), (j + nw,)) == suffix[j])
        idx = jnp.arange(nw, dtype=jnp.int32)
        # Strictly before the suffix's own occurrence at hlen - ngram.
        valid = match & (idx < hlen - ngram)
        full = valid & (idx <= hlen - ngram - draft_len)
        found = jnp.any(valid)
        last_full = (nw - 1) - jnp.argmax(full[::-1]).astype(jnp.int32)
        first_any = jnp.argmax(valid).astype(jnp.int32)
        m = jnp.where(jnp.any(full), last_full, first_any)
        start = jnp.where(found, m + ngram, hlen)
        # dynamic_slice clamps start so the read stays in bounds; a clamped
        # window only shifts WHICH tokens get drafted — still just a draft.
        out = lax.dynamic_slice(h, (start,), (draft_len,))
        # Stale-memory guard: the copy window can cross hlen (an earliest
        # match's continuation, or the no-match fallback at the tail),
        # and beyond hlen sits whatever a PREVIOUS occupant of this
        # history row left there (scheduler slots are reused across
        # requests). Greedy verification never cared — drafts change
        # round counts, never output — but SAMPLED rejection
        # verification's realized tokens depend on the drafts (accept
        # iff u < p(draft)), so reading stale memory would break
        # (seed, request) reproducibility across batch compositions and
        # scheduler incarnations — the crash-replay suppression
        # contract. Pin past-hlen positions to token 0: any FIXED value
        # is a valid junk draft.
        pos = start + jnp.arange(draft_len, dtype=jnp.int32)
        return jnp.where(pos < hlen, out, 0)

    return jax.vmap(row)(hist, hist_len.astype(jnp.int32))


def rejection_sample_chain(
    filt: jnp.ndarray,    # [B, D+1, V] filtered target logits (see below)
    drafts: jnp.ndarray,  # [B, D] i32 deterministic prompt-lookup drafts
    keys: jax.Array,      # [B] typed PRNG keys, one per row per round
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Standard speculative rejection sampling (Leviathan et al.; Chen et
    al.) specialized to DETERMINISTIC drafts — the shared accept/resample
    core of both one-XLA-program speculative loops (this module's
    `lax.while_loop` and the scheduler's spec-decode program).

    `filt` must be `ops.sampling.filtered_runtime_logits` output over the
    verify window's logits, grammar-masked BEFORE filtering exactly where
    vanilla decode masks (per-position budget-aware state rows):
    `softmax(filt[:, j])` is then the EXACT distribution p_j(·) a vanilla
    sampled step would draw token j from.

    The general scheme accepts draft token x_i ~ q(·) with probability
    min(1, p(x_i)/q(x_i)) and resamples the first rejection from the
    normalized residual max(0, p - q). Prompt-lookup drafts are not
    model-sampled — q is a DELTA at the drafted token d (q(d) = 1) — so
    the scheme degenerates cleanly:

      accept:    min(1, p(d)/1) = p(d) — accept iff u < p(d), i.e. iff
                 the drafted token has enough TARGET mass. (p(d) = 0 for
                 a grammar-masked draft, so invalid drafts auto-reject.)
      residual:  max(0, p - δ_d) is p with d zeroed (p(d) <= 1 always),
                 renormalized — which is exactly `categorical` over filt
                 with d's logit dropped to NEG_INF. The residual stays
                 grammar-renormalized for free: masked tokens were
                 already at NEG_INF in filt.

    Unbiasedness at one position: P(emit t) = p(d)·1[t=d] +
    (1-p(d))·p(t)·1[t≠d]/(1-p(d)) = p(t). Chained over positions with
    the standard longest-accepted-prefix rule, plus the bonus draw from
    p_D itself when every draft accepts, the emitted tokens are exactly
    a sample from the target process — property-tested against vanilla
    `sample_runtime` output distributions in tests/test_speculative.py.
    (The p(d)=1 corner where the residual would be empty is unreachable:
    u ~ U[0,1) < 1 accepts with certainty there.)

    Returns (acc [B], extra [B]): `acc` is the accepted draft prefix
    length in [0, D], `extra` the token sampled at position `acc` — the
    residual draw when acc < D, the bonus target sample when acc == D.
    Callers emit drafts[:acc] + [extra], i.e. acc + 1 tokens (see
    `emit_chain`)."""
    from ..ops.common import NEG_INF

    b, d1, v = filt.shape
    d = d1 - 1
    p = jax.nn.softmax(filt, axis=-1)
    # Dead-row guard: a FULLY-masked position (possible only past the
    # budget horizon) must reject with certainty. NEG_INF is a finite
    # -1e30, so softmax over an all-masked row degenerates to UNIFORM
    # (exp(0)/V), not NaN — without this clamp a past-horizon draft
    # would accept with probability ~1/V and inflate acceptance
    # counters with tokens the loops discard anyway. Partially-masked
    # rows are unaffected: a masked token's mass underflows to exactly
    # 0 against any finite max, so grammar-rejected drafts still
    # auto-reject through p_draft == 0 alone.
    alive = (jnp.max(filt, axis=-1) > NEG_INF * 0.5)     # [B, D+1]
    p_draft = jnp.take_along_axis(
        p[:, :d], drafts[..., None], axis=-1
    )[..., 0] * alive[:, :d]                             # [B, D]
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    u = jax.vmap(lambda k: jax.random.uniform(k, (d,)))(ks[:, 0])
    accept = (u < p_draft).astype(jnp.int32)
    acc = jnp.sum(jnp.cumprod(accept, axis=1), axis=1)   # [B] in [0, D]
    final = jnp.take_along_axis(filt, acc[:, None, None], axis=1)[:, 0]
    rej = jnp.take_along_axis(
        jnp.concatenate([drafts, drafts[:, :1]], axis=1),  # pad col unused
        acc[:, None], axis=1,
    )[:, 0]
    final = jnp.where(
        (acc < d)[:, None] & (jnp.arange(v, dtype=jnp.int32)[None, :]
                              == rej[:, None]),
        NEG_INF, final,
    )
    extra = jax.vmap(jax.random.categorical)(ks[:, 1], final).astype(jnp.int32)
    return acc, extra


def emit_chain(drafts: jnp.ndarray, acc: jnp.ndarray, extra: jnp.ndarray,
               pad_id: int) -> jnp.ndarray:
    """Materialize `rejection_sample_chain`'s (acc, extra) contract as the
    emitted window [B, D+1]: the accepted draft prefix, then the
    residual/bonus token at position `acc`, pad beyond — the ONE place
    the emission indexing lives for both one-XLA-program loops."""
    b, d = drafts.shape
    jd = jnp.arange(d + 1, dtype=jnp.int32)[None, :]
    chain = jnp.concatenate(
        [drafts, jnp.full((b, 1), pad_id, jnp.int32)], axis=1
    )
    return jnp.where(
        jd < acc[:, None], chain,
        jnp.where(jd == acc[:, None], extra[:, None], pad_id),
    )


def make_speculative_generate_fn(
    cfg: LlamaConfig,
    max_new: int,
    stop_ids: Tuple[int, ...],
    mesh=None,
    draft_len: int = 8,
    ngram: int = 3,
    attn_impl: Optional[str] = None,
    constrained: bool = False,
    kv_layout: str = "contiguous",
    kv_page_size: Optional[int] = None,
    kv_quant: Optional[str] = None,
    sampling: Optional["SamplingParams"] = None,
):
    """Generate with prompt-lookup speculation (greedy or sampled).

    Same contract as `make_generate_fn` (bucketed cap, traced budget) plus a
    third output: `rounds` — the number of verify forwards the batch ran.
    rounds < total emitted tokens means speculation paid off; equality means
    every draft missed (the worst case, which still emits one token per
    round like vanilla decode, paying only the wider verify unembed).

    `sampling` (static, default greedy): greedy mode verifies by exact
    argmax — output token-identical to vanilla greedy decode. A
    temperature>0 `sampling` runs rejection-sampling verification
    (`rejection_sample_chain`): per round, each drafted token is accepted
    iff a uniform draw lands under its mass in the target distribution
    (temperature/top-k/top-p-filtered, grammar-masked when constrained),
    and the round's final token is drawn from the residual (first
    rejection) or the target itself (all accepted) — output
    DISTRIBUTION-identical to the vanilla sampled loop, not
    token-identical (the RNG consumption pattern differs). The traced
    `key` argument is required in sampled mode; round r derives per-row
    keys as fold_in(fold_in(key, r+1), row), so a (seed, request) pair is
    reproducible whatever the drafts accepted.

    `constrained=True` returns a fn taking two extra traced arguments —
    `(next, need)` grammar tables from constrain.CompiledMask.device_tables
    plus `init_states [B]` — and evaluates the grammar mask AT EVERY DRAFT
    POSITION: the draft chain advances the FSM per position
    (constrain.fsm_advance_chain) and truncates at the first
    grammar-rejected token (so acceptance doesn't crater on junk drafts),
    every verify-window logit row is masked with ITS position's
    budget-aware state row before argmax, and the committed FSM state is
    the one after the ACCEPTED prefix — rejected drafts never advance it
    (the same rewind-by-construction the rejected-K/V garbage relies on).
    Greedy parity is the contract: constrained+speculative output is
    token-identical to the constrained vanilla loop, drafts only change
    how many forwards it takes.
    """
    if not 1 <= draft_len <= _UNROLL_MAX_T - 1:
        raise ValueError(
            f"draft_len must be in [1, {_UNROLL_MAX_T - 1}] (the verify "
            f"window T = draft_len + 1 must take the unrolled small-T "
            f"decode path), got {draft_len}"
        )
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    if kv_layout not in ("contiguous", "paged"):
        raise ValueError(
            f"kv_layout must be 'contiguous' or 'paged', got {kv_layout!r}"
        )
    page_size = 0
    decode = attn_impl or decode_attention_impl(mesh)
    if kv_quant not in (None, "int8"):
        raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
    if kv_quant and kv_layout != "paged":
        raise ValueError(
            "kv_quant='int8' speculation needs kv_layout='paged': the "
            "contiguous verify loop streams the bf16 cache, the paged "
            "pool's verify windows run the int8-streaming reference gather"
        )
    if kv_layout == "paged":
        from .paged_kv import default_page_size

        page_size = int(kv_page_size or default_page_size())
        # The verify window is T=D+1: since the ragged-paged kernel takes
        # per-row query lengths, a resolved-pallas mode runs verify windows
        # through the kernel grid; the auto resolution still lands on the
        # reference gather path off-TPU. A mesh shards the pool's KV-head
        # axis over tp (constrain_cache's paged branch); page tables
        # replicate.
    return _make_speculative_generate_fn(
        cfg, max_new, stop_ids, mesh, draft_len, ngram,
        attn_impl or attention_impl(mesh),
        decode,
        constrained,
        kv_layout,
        page_size,
        kv_quant,
        sampling or SamplingParams(),
    )


@functools.lru_cache(maxsize=64)
def _make_speculative_generate_fn(
    cfg: LlamaConfig,
    max_new: int,
    stop_ids: Tuple[int, ...],
    mesh,
    draft_len: int,
    ngram: int,
    prefill_impl: str,
    decode_impl: str,
    constrained: bool = False,
    kv_layout: str = "contiguous",
    page_size: int = 0,
    kv_quant: Optional[str] = None,
    sampling: SamplingParams = SamplingParams(),
):
    from .generate import _is_stop as _is_stop_ids

    pad_id = cfg.pad_id
    d1 = draft_len + 1
    sp = dict(mesh.shape).get("sp", 1) if mesh is not None else 1
    pre_impl = "ring" if sp > 1 else prefill_impl
    if sp > 1 and decode_impl == "pallas":
        # Same hazard as generate.py's guard: the flash kernel's shard_map
        # expects S-replicated K/V, and against the sp-sharded cache
        # (parallel/sharding.cache_spec) every verify round would
        # all-gather the whole cache.
        raise ValueError(
            "attn_impl='pallas' verify/decode cannot run on an sp>1 mesh: "
            "the sequence-sharded cache would be all-gathered every round; "
            "use the auto/einsum impl"
        )

    def _is_stop(tok):
        return _is_stop_ids(tok, stop_ids)

    sampled = not sampling.is_greedy

    def gen(params, tokens, lengths, budget, key=None,
            grammar=None,       # (next [S,V] i32, need [S,V] i32) tables
            init_states=None):  # [B] int32 DFA start states
        b, t = tokens.shape
        budget = jnp.minimum(budget, max_new)
        lengths = lengths.astype(jnp.int32)
        paged = kv_layout == "paged"
        # Cache spans prompt + completion + one verify window of overshoot
        # (paged mode prefills a prompt-sized transient cache and packs it
        # into pool pages covering the same span — verify windows write
        # through the page table, spanning page boundaries freely).
        cache = init_cache(cfg, b, t if paged else t + max_new + d1,
                           dtype=params["final_norm"].dtype)
        if mesh is not None:
            cache = constrain_cache(cache, mesh)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        logits, cache = forward(
            cfg, params, tokens, positions, cache,
            logit_indices=lengths - 1, attn_impl=pre_impl, mesh=mesh,
        )
        first_logits = logits[:, 0]
        if constrained:
            g_next, g_need = grammar
            # First token constrained exactly like the vanilla loop: a
            # token is allowed iff itself + shortest completion + stop id
            # fit the whole budget (masks.py need table).
            first_logits = apply_token_mask(
                first_logits, g_need[init_states] <= budget
            )
        if sampled:
            # Vanilla-identical first draw: the same grammar-masked logits,
            # the same static sampler, fold index 0 of the batch key.
            first = sample(first_logits, sampling, jax.random.fold_in(key, 0))
        else:
            first = jnp.argmax(first_logits, axis=-1).astype(jnp.int32)
        cstate = g_next[init_states, first] if constrained else None
        if paged:
            from .paged_kv import lane_pack, pack_prefill_pages

            ppr = -(-(t + max_new + d1) // page_size)
            tp = dict(mesh.shape).get("tp", 1) if mesh is not None else 1
            cache = pack_prefill_pages(
                cache, page_size, ppr, kv_quant=kv_quant,
                pack=lane_pack(cfg, kv_quant, tp))
            if mesh is not None:
                cache = constrain_cache(cache, mesh)

        # History = prompt tokens + generated, contiguous per row (generated
        # tokens land at hlen, after the row's REAL prompt; the pad gap up
        # to the bucket boundary never sits inside an n-gram window that
        # can win: drafts sourced from it fail verification).
        ht = t + max_new + d1
        hist = jnp.concatenate(
            [tokens, jnp.full((b, max_new + d1), pad_id, jnp.int32)], axis=1
        )
        hist = jax.vmap(
            lambda h, f, s: lax.dynamic_update_slice(h, f[None], (s,))
        )(hist, first, lengths)

        out = jnp.full((b, max_new + d1), pad_id, jnp.int32)
        out = out.at[:, 0].set(first)
        done = _is_stop(first) | (budget <= 1)
        glen = jnp.ones((b,), jnp.int32)
        hlen = lengths + 1
        dec_params = params if decode_impl == "ring" else split_blocks(params)
        jd = jnp.arange(d1, dtype=jnp.int32)[None, :]

        def cond(carry):
            return ~jnp.all(carry[4])

        def body(carry):
            hist, hlen, out, glen, done, cache, cur, pos, rounds = carry[:9]
            drafts = ngram_draft(hist, hlen, draft_len, ngram)  # [B, D]
            verify = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, D+1]
            vpos = pos[:, None] + jd
            logits, cache = forward(
                cfg, dec_params, verify, vpos, cache,
                attn_impl=decode_impl, mesh=mesh,
            )
            if constrained:
                # The draft chain advances the FSM per position; drafts
                # stop counting at the first grammar-rejected token
                # (vlen), and EVERY verify position's logits are masked
                # with its own state's budget-aware row — the masked
                # argmax at position j is exactly the token vanilla
                # constrained decode would emit there, which is what makes
                # greedy parity hold whatever the drafts were.
                cstate = carry[9]
                rem0 = budget - glen                         # [B]
                pstates, vlen = fsm_advance_chain(
                    g_next, g_need, cstate, drafts, rem0
                )                                            # [B,D+1], [B]
                pos_rem = rem0[:, None] - jd                 # [B, D+1]
                logits = apply_token_mask(
                    logits, g_need[pstates] <= pos_rem[:, :, None]
                )
            if sampled:
                # Rejection-sampling verification: the filtered target
                # logits at every window position (softmax = the EXACT
                # per-position distribution vanilla sample_runtime draws
                # from — grammar-masked above, so grammar-rejected drafts
                # carry zero target mass and auto-reject, capping
                # acceptance at the valid prefix without a separate
                # clamp). Per-row keys derive from (key, round, row), so
                # the whole run is reproducible per (seed, batch).
                filt = filtered_runtime_logits(
                    logits, jnp.float32(sampling.temperature),
                    jnp.float32(sampling.top_p), jnp.int32(sampling.top_k),
                )
                round_key = jax.random.fold_in(key, rounds + 1)
                rkeys = jax.vmap(
                    lambda i: jax.random.fold_in(round_key, i)
                )(jnp.arange(b, dtype=jnp.int32))
                acc, extra = rejection_sample_chain(filt, drafts, rkeys)
                preds = emit_chain(drafts, acc, extra, pad_id)
            else:
                preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, D+1]
                # preds[j] is the TRUE greedy token after verify[j] iff all
                # drafts before j were accepted; accept the longest such
                # chain.
                eq = (drafts == preds[:, :draft_len]).astype(jnp.int32)
                if constrained:
                    # A grammar-rejected draft can never be accepted even
                    # if the (masked-out) model would have agreed:
                    # acceptance is capped at the valid prefix, so the
                    # committed chain only ever walks live FSM
                    # transitions.
                    eq = eq * (jd[:, :draft_len]
                               < vlen[:, None]).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)  # [B] in [0, D]
            emit_mask = jd <= acc[:, None]
            stops = _is_stop(preds)
            # Keep through the FIRST stop, nothing after it.
            stops_before = jnp.cumsum(stops.astype(jnp.int32), axis=1) - stops
            emit_mask = emit_mask & (stops_before == 0)
            emit_mask = emit_mask & (jd < (budget - glen)[:, None])
            emit_mask = emit_mask & ~done[:, None]
            n_emit = jnp.sum(emit_mask, axis=1).astype(jnp.int32)
            emitted = jnp.where(emit_mask, preds, pad_id)

            out = jax.vmap(
                lambda o, e, s: lax.dynamic_update_slice(o, e, (s,))
            )(out, emitted, glen)
            hist = jax.vmap(
                lambda h, e, s: lax.dynamic_update_slice(h, e, (s,))
            )(hist, emitted, hlen)

            cur = jax.vmap(
                lambda e, n, c: jnp.where(n > 0, e[jnp.maximum(n - 1, 0)], c)
            )(emitted, n_emit, cur)
            tail = ()
            if constrained:
                # Commit the state AFTER the accepted prefix: the last
                # emitted token advances from ITS per-position state
                # (pstates[n_emit-1] — for accepted drafts that is the
                # chain state, and emitted[j] == drafts[j] there).
                # Rejected drafts never touch the committed state, the
                # FSM twin of the rejected-K/V rewind. n_emit == 0 rows
                # (done / budget-exhausted) freeze.
                idx = jnp.maximum(n_emit - 1, 0)
                last_s = jnp.take_along_axis(pstates, idx[:, None], 1)[:, 0]
                last_t = jnp.take_along_axis(emitted, idx[:, None], 1)[:, 0]
                tail = (jnp.where(n_emit > 0, g_next[last_s, last_t],
                                  cstate),)
            glen = glen + n_emit
            hlen = hlen + n_emit
            pos = pos + n_emit
            done = done | jnp.any(stops & emit_mask, axis=1) | (glen >= budget)
            return (hist, hlen, out, glen, done, cache, cur, pos,
                    rounds + 1) + tail

        carry = (hist, hlen, out, glen, done, cache, first, lengths,
                 jnp.int32(0))
        if constrained:
            carry = carry + (cstate,)
        final = lax.while_loop(cond, body, carry)
        out, rounds = final[2], final[8]

        out = out[:, :max_new]
        stops = _is_stop(out)
        gen_lens = jnp.where(
            jnp.any(stops, axis=1),
            jnp.argmax(stops, axis=1).astype(jnp.int32) + 1,
            budget.astype(jnp.int32),
        )
        return out, gen_lens, rounds

    return jax.jit(gen)
