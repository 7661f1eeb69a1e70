"""Completion backends: the text→text seam under the generation service.

`EngineBackend` is the real path (tokenizer + in-tree TPU engine).
`FakeBackend` makes the whole app/eval stack hermetically testable without
weights — the capability the reference never had (its only 'test' needed a
live Ollama server, SURVEY.md §4).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, List, Optional, Sequence

from ..engine.generate import InferenceEngine
from ..ops.sampling import SamplingParams
from ..tokenizer.base import Tokenizer


def resolve_stop_ids(cfg, tokenizer) -> tuple:
    """Union of the checkpoint config's stop list and every stop token the
    tokenizer's vocabulary declares (HFTokenizer.eos_ids). Either source
    alone under-stops llama-3.x chat models: the config may carry only
    <|end_of_text|> while the turn actually ends at <|eot_id|>."""
    ids = list(cfg.stop_ids)
    for i in getattr(tokenizer, "eos_ids", ()):
        if i not in ids:
            ids.append(i)
    return tuple(ids)


@dataclasses.dataclass
class Completion:
    text: str
    output_tokens: int
    prompt_tokens: int = 0
    # Time to first token, when the backend has a first-token seam (the
    # continuous-batching scheduler); 0.0 = not measured.
    ttft_s: float = 0.0
    # Queue wait (submit -> slot admission) on the scheduler path: the
    # backlog share of latency. 0.0 = not measured.
    queue_wait_s: float = 0.0
    # Request class ("constrained"/"speculative"/both/"") and serving
    # replica — the label set the Prometheus histograms slice by.
    rclass: str = ""
    replica: str = ""


def resolve_constraint(constrain, tokenizer, stop_ids):
    """Spec ("spark_sql" / {"table","columns"} / CompiledMask) -> compiled
    grammar tables for a backend's tokenizer + stop ids; None passes
    through. get_constraint caches per triple, so repeated requests reuse
    the same precomputed masks. Shared by EngineBackend and
    SchedulerBackend — one resolution path, not two drifting copies."""
    if constrain is None:
        return None
    from ..constrain import get_constraint

    return get_constraint(constrain, tokenizer, stop_ids)


def stok_seed_from_bench(path: str) -> Optional[float]:
    """Seconds-per-output-token seed from the last committed bench
    artifact line (bench.py emits one JSON artifact per line; the last
    parseable line is the richest). The artifact's headline is AGGREGATE
    output tok/s at batch B, and decode is weight-streaming bound, so the
    wall of one decode step — which is what a serving request pays per
    token regardless of its own batch size — is ~B / value; B is parsed
    from the metric string (falls back to 1, which UNDER-estimates
    s/token and therefore under-clamps: a conservative failure mode, the
    request may overrun its deadline but is never spuriously rejected).
    Returns None when the file is missing/unparseable — callers degrade
    to the unseeded (unclamped-first-request) behavior."""
    import json
    import re

    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return None
    obj = None
    for ln in reversed(text.splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                obj = json.loads(ln)
            except json.JSONDecodeError:
                continue
            break
    if not isinstance(obj, dict):
        return None
    value = obj.get("value")
    if not isinstance(value, (int, float)) or value <= 0:
        return None
    m = re.search(r"B=(\d+)", str(obj.get("metric", "")))
    batch = int(m.group(1)) if m else 1
    return batch / float(value)


def trim_stop_texts(text: str, stop_texts: Sequence[str]) -> str:
    """Cut the completion at the first occurrence of any stop string."""
    for stop in stop_texts:
        cut = text.find(stop)
        if cut != -1:
            text = text[:cut]
    return text


class EngineBackend:
    """Tokenize → engine.generate → detokenize. Thread-safe: one lock per
    backend serializes device work (the continuous-batching scheduler
    replaces this lock for concurrent serving)."""

    #: GenerationService checks this before forwarding a `constrain=` spec.
    supports_constrain = True
    #: Deadline enforcement, smallest slice (ROADMAP follow-up): the
    #: one-XLA-program decode cannot retire mid-flight like the scheduler,
    #: but the STEP BUDGET can be clamped at issue time from the request's
    #: remaining deadline × the measured per-token service rate — so a
    #: nearly-expired request occupies the device for roughly its budget,
    #: not a full max-tokens decode. An already-expired deadline fails
    #: typed before any device work.
    supports_deadline = True

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: Tokenizer,
        max_new_tokens: int = 256,
        sampling: SamplingParams = SamplingParams(),
        stop_texts: Sequence[str] = (),
        add_bos: bool = True,
        sec_per_tok_seed: Optional[float] = None,
    ):
        """Set `add_bos=False` for chat templates whose rendered prompt
        already begins with the BOS string (e.g. llama3-chat's
        <|begin_of_text|>) — otherwise the model sees BOS twice, an
        off-distribution prompt that silently degrades output quality.

        `sec_per_tok_seed` primes the deadline-clamp s/token EWMA at
        startup (LSOT_STOK_SEED, or stok_seed_from_bench over the last
        bench artifact): without it the FIRST request after boot runs
        unclamped because there is nothing to exchange a deadline against
        (ROADMAP PR-3 follow-up). The seed is a prior, not a pin — real
        completions EWMA-blend it away at the usual 0.2 rate."""
        self.engine = engine
        self.tokenizer = tokenizer
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.stop_texts = tuple(stop_texts)
        self.add_bos = add_bos
        self._lock = threading.Lock()
        # EWMA of seconds-per-output-token over completed requests (wall /
        # tokens, prefill amortized in): the deadline→step-budget exchange
        # rate. The FIRST completion of each program shape (batch size ×
        # padded prompt length) is discarded — its wall is dominated by
        # that shape's one-time XLA compilation, orders of magnitude off
        # steady state, and would poison the exchange rate into spurious
        # DeadlineExceeded for affordable requests. Until a real sample
        # exists, requests run unclamped (a guessed rate would silently
        # truncate output); shapes the key doesn't capture (budget
        # buckets) can still land one inflated sample, which the 0.2 EWMA
        # bounds (ROADMAP notes the follow-up).
        self._sec_per_tok: Optional[float] = (
            float(sec_per_tok_seed)
            if sec_per_tok_seed is not None and sec_per_tok_seed > 0
            else None
        )
        self._rate_warm_shapes: set = set()

    @classmethod
    def from_hf_checkpoint(
        cls,
        ckpt_dir: str,
        tokenizer: Tokenizer,
        mesh=None,
        dtype=None,
        prompt_bucket: int = 128,
        stop_ids: Optional[Sequence[int]] = None,
        quantize_int8: bool = False,
        quantize_int4: bool = False,
        quantize_unembed8: bool = False,
        speculative_draft: int = 0,
        kv_quant=None,
        **kwargs,
    ) -> "EngineBackend":
        """Stand up a backend straight from an HF-format checkpoint directory
        (the deployment path: weights land pre-sharded on the mesh).

        `quantize_int8=True` converts the block matmul weights to int8
        QTensors before placement (ops/quant.py) — halves weight HBM
        traffic for bandwidth-bound decode; `quantize_int4=True` packs
        them to 4-bit nibbles served by the pallas int4 matmul kernel
        (one quarter of bf16's weight bytes; TP-shards like the other
        quantized layouts — parallel/sharding.specs_for_params).
        `speculative_draft=N` turns on prompt-lookup speculative decoding
        for greedy requests (engine/speculative.py — the NL→SQL
        copy-heavy workload is its sweet spot)."""
        import jax.numpy as jnp

        from ..checkpoint import load_and_quantize, load_hf_checkpoint

        cfg, params = load_and_quantize(
            lambda m: load_hf_checkpoint(
                ckpt_dir, dtype=dtype or jnp.bfloat16, mesh=m),
            mesh, quantize_int8=quantize_int8, quantize_int4=quantize_int4,
            quantize_unembed8=quantize_unembed8,
        )
        engine = InferenceEngine(
            cfg, params, mesh=mesh, prompt_bucket=prompt_bucket,
            stop_ids=stop_ids if stop_ids is not None
            else resolve_stop_ids(cfg, tokenizer),
            speculative_draft=speculative_draft, kv_quant=kv_quant,
        )
        return cls(engine, tokenizer, **kwargs)

    @classmethod
    def from_gguf(
        cls,
        gguf_path: str,
        tokenizer: Tokenizer,
        cfg=None,
        mesh=None,
        dtype=None,
        prompt_bucket: int = 128,
        stop_ids: Optional[Sequence[int]] = None,
        quantize_int8: bool = False,
        quantize_int4: bool = False,
        quantize_unembed8: bool = False,
        speculative_draft: int = 0,
        kv_quant=None,
        **kwargs,
    ) -> "EngineBackend":
        """Stand up a backend from a GGUF blob — the exact file format the
        reference's Ollama models ship as (parsed + dequantized by the
        in-tree C++ core, native/src/gguf.cpp). The loader dequantizes the
        blob's own quantization to the compute dtype; `quantize_int8` /
        `quantize_int4` then re-quantize into the in-tree serving formats
        (a Q4 blob served with quantize_int4 stays 4-bit end to end)."""
        from ..checkpoint import load_and_quantize, load_gguf_checkpoint

        cfg, params = load_and_quantize(
            lambda m: load_gguf_checkpoint(
                gguf_path, cfg=cfg, dtype=dtype, mesh=m),
            mesh, quantize_int8=quantize_int8, quantize_int4=quantize_int4,
            quantize_unembed8=quantize_unembed8,
        )
        engine = InferenceEngine(
            cfg, params, mesh=mesh, prompt_bucket=prompt_bucket,
            speculative_draft=speculative_draft, kv_quant=kv_quant,
            stop_ids=stop_ids if stop_ids is not None
            else resolve_stop_ids(cfg, tokenizer),
        )
        return cls(engine, tokenizer, **kwargs)

    def check_budget(self, prompt: str,
                     max_new_tokens: Optional[int] = None,
                     constraint=None) -> None:
        """Raise ValueError if `prompt` leaves no decode room — the same
        rejection complete() would make, runnable BEFORE any response
        bytes go on the wire (streaming handlers must turn request-shape
        errors into 400s, which is impossible once 200 headers are sent).
        With a compiled `constraint`, also checks the CLAMPED budget
        (after the context-room clamp complete() applies) against the
        grammar's shortest complete parse."""
        ids = self.tokenizer.encode(prompt, add_bos=self.add_bos)
        room = self._room(len(ids))
        if constraint is not None:
            budget = min(max_new_tokens or self.max_new_tokens, room)
            if budget < constraint.min_new_tokens:
                raise ValueError(
                    f"decode budget {budget} (after the context-room "
                    f"clamp) cannot hold a complete constrained parse "
                    f"(grammar needs >= {constraint.min_new_tokens} tokens)"
                )

    def _room(self, n_prompt_tokens: int) -> int:
        cfg = self.engine.cfg
        room = cfg.max_seq_len - self.engine.padded_prompt_len(n_prompt_tokens)
        if room < 1:
            raise ValueError(
                f"prompt ({n_prompt_tokens} tokens) leaves no room in the "
                f"{cfg.max_seq_len}-token context of {cfg.name}"
            )
        return room

    def _resolve_constraint(self, constrain):
        return resolve_constraint(constrain, self.tokenizer,
                                  self.engine.stop_ids)

    @staticmethod
    def _make_deadline(deadline_s: Optional[float]):
        """Stamp the deadline at REQUEST ENTRY: the exchange below runs
        inside the backend lock, so time queued behind another decode on
        this serialized engine is charged against the budget too."""
        if deadline_s is None:
            return None
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be positive, got {deadline_s}")
        from .resilience import Deadline

        return Deadline.after(deadline_s)

    def _deadline_budget(self, budget: int, deadline) -> int:
        """Exchange the REMAINING deadline for a step budget: tokens the
        request can afford at the measured rate. Expired (or unaffordable
        even for one token) fails typed DeadlineExceeded BEFORE the device
        is touched — the engine has no mid-decode retirement, so issue
        time is the only enforcement point (smallest slice)."""
        if deadline is None:
            return budget
        from ..utils.observability import resilience
        from .resilience import DeadlineExceeded

        remaining = deadline.remaining()
        if remaining <= 0:
            resilience.inc("deadline_expired")
            raise DeadlineExceeded(
                "request deadline expired before issue (burned queueing "
                "behind the serialized engine)"
            )
        rate = self._sec_per_tok
        if rate is None or rate <= 0:
            return budget
        afford = int(remaining / rate)
        if afford < 1:
            resilience.inc("deadline_expired")
            raise DeadlineExceeded(
                f"remaining deadline of {remaining:.3f}s cannot afford one "
                f"token at the measured {rate:.4f}s/token — not issued"
            )
        if afford < budget:
            resilience.inc("deadline_clamps")
            return afford
        return budget

    def _record_rate(self, wall_s: float, output_tokens: int,
                     shape: tuple) -> None:
        if output_tokens < 1 or wall_s <= 0:
            return
        if shape not in self._rate_warm_shapes:
            # First completion at this program shape: wall includes that
            # shape's jit compile — discard.
            self._rate_warm_shapes.add(shape)
            return
        rate = wall_s / output_tokens
        prev = self._sec_per_tok
        self._sec_per_tok = rate if prev is None else 0.2 * rate + 0.8 * prev

    def complete(self, prompt: str, max_new_tokens: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None, seed: int = 0,
                 constrain=None,
                 deadline_s: Optional[float] = None) -> Completion:
        import time

        deadline = self._make_deadline(deadline_s)
        ids = self.tokenizer.encode(prompt, add_bos=self.add_bos)
        # Clamp the decode budget to what fits the model context after the
        # bucketed (and sp-padded, on a sequence-parallel mesh) prompt: a
        # serving backend degrades to a shorter completion instead of
        # erroring (the engine itself raises on overflow).
        room = self._room(len(ids))
        budget = min(max_new_tokens or self.max_new_tokens, room)
        # Resolve (and first-use compile) the grammar OUTSIDE the timed
        # window: a one-off token-mask precompute inside it would poison
        # the s/token rate the deadline exchange runs on.
        constraint = self._resolve_constraint(constrain)
        with self._lock:
            # Inside the lock: the wait behind another decode has already
            # been charged against the deadline by the time we exchange
            # what REMAINS for a step budget.
            budget = self._deadline_budget(budget, deadline)
            t0 = time.perf_counter()
            out = self.engine.generate(
                [ids],
                max_new_tokens=budget,
                sampling=sampling or self.sampling,
                seed=seed,
                constraint=constraint,
            )[0]
            self._record_rate(time.perf_counter() - t0, len(out),
                              (1, self.engine.padded_prompt_len(len(ids))))
        # Strip the stop token itself from the text.
        if out and out[-1] in self.engine.stop_ids:
            out = out[:-1]
        text = trim_stop_texts(self.tokenizer.decode(out), self.stop_texts)
        return Completion(text=text, output_tokens=len(out), prompt_tokens=len(ids))

    def complete_batch(
        self, prompts: Sequence[str], max_new_tokens: Optional[int] = None,
        sampling: Optional[SamplingParams] = None, seed: int = 0,
        constrain=None, deadline_s: Optional[float] = None,
    ) -> List[Completion]:
        """One batched device program for many prompts (BASELINE config 4:
        batch=32 Spider questions) — amortizes weight streaming across the
        whole batch instead of paying it per request. A `deadline_s` clamps
        the SHARED step budget (the batch decodes in lockstep, so the
        deadline is the batch's, not per member)."""
        import time

        deadline = self._make_deadline(deadline_s)
        ids = [self.tokenizer.encode(p, add_bos=self.add_bos) for p in prompts]
        room = self.engine.cfg.max_seq_len - self.engine.padded_prompt_len(
            max(len(i) for i in ids)
        )
        if room < 1:
            raise ValueError("longest prompt leaves no decode room")
        budget = min(max_new_tokens or self.max_new_tokens, room)
        constraint = self._resolve_constraint(constrain)  # outside the timer
        with self._lock:
            budget = self._deadline_budget(budget, deadline)
            t0 = time.perf_counter()
            outs = self.engine.generate(
                ids, max_new_tokens=budget,
                sampling=sampling or self.sampling, seed=seed,
                constraint=constraint,
            )
            self._record_rate(
                time.perf_counter() - t0,
                max(len(o) for o in outs) if outs else 0,
                (len(prompts), self.engine.padded_prompt_len(
                    max(len(i) for i in ids))),
            )
        completions = []
        for prompt_ids, out in zip(ids, outs):
            if out and out[-1] in self.engine.stop_ids:
                out = out[:-1]
            text = trim_stop_texts(self.tokenizer.decode(out), self.stop_texts)
            completions.append(Completion(
                text=text, output_tokens=len(out),
                prompt_tokens=len(prompt_ids),
            ))
        return completions


class FakeBackend:
    """Deterministic canned backend: `fn(prompt) -> text`."""

    def __init__(self, fn: Callable[[str], str]):
        self.fn = fn
        self.calls: List[str] = []

    def complete(self, prompt: str, max_new_tokens: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None, seed: int = 0) -> Completion:
        self.calls.append(prompt)
        text = self.fn(prompt)
        return Completion(
            text=text,
            output_tokens=len(text.split()),
            prompt_tokens=len(prompt.split()),
        )

    def complete_batch(self, prompts, max_new_tokens=None, sampling=None,
                       seed: int = 0) -> List[Completion]:
        return [self.complete(p, max_new_tokens, sampling, seed) for p in prompts]
