"""Model registry + multi-model serving assembly (ISSUE 16).

The source paper runs TWO models — `duckdb-nsql` writes the SQL and
`llama3.2` explains Spark stack traces — but until this subsystem the
fleet served exactly one checkpoint and the explainer leg aliased the
SQL model's weights. This module owns:

- `ModelSpec` / `parse_models_spec`: the `LSOT_MODELS` env spec — which
  checkpoints are co-resident, where each loads from (`tiny` random
  weights for tests, `hf` safetensors, `gguf`), what share of the paged
  KV arena each may hold, and which chat template wraps its prompts.
- `partition_pages`: split ONE page budget between co-resident
  checkpoints proportional to their `hbm` fractions — the two models
  live in one process and must not size their arenas independently
  against the same HBM.
- `ModelRegistry`: id → spec lookup with the typed `UnknownModel`
  error the scheduler pool raises when a request names a model no
  replica carries (api.py maps ValueError → 400, so a bad model id is
  a client error, never a scheduler crash).
- `build_tiny_model_service`: the proof-harness assembly — one
  scheduler per registered tiny model, all in ONE `SchedulerPool`
  routing on `model_id`, one `SchedulerBackend` per model sharing that
  pool. Tests, `scripts/multimodel_smoke.sh` and the bench
  `multi_model` leg all stand their fleets up through this.

Routing itself lives in `scheduler.SchedulerPool` (the `model_id` axis
beside `phase_role`, flag-gated by `LSOT_POOL_MODELS`); this module is
the registry + assembly layer above it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence


class UnknownModel(ValueError):
    """A request named a model_id no replica in the fleet carries.

    Subclasses ValueError so the API layer's existing `except ValueError
    → 400` mapping turns it into a typed client error instead of a 500
    (or worse, a SchedulerCrashed shed) — the "unregistered model"
    failure mode is the requester's bug, not the fleet's.
    """


_SOURCES = ("tiny", "hf", "gguf")


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """One registered model: identity, checkpoint source, HBM share."""

    model_id: str
    #: "tiny" (random test weights, no path), "hf" (safetensors dir),
    #: "gguf" (single-file checkpoint).
    source: str = "tiny"
    #: Checkpoint location; required for hf/gguf, ignored for tiny.
    path: str = ""
    #: Share of the co-resident paged KV arena (0 < f <= 1). Specs in a
    #: fleet are normalized together — see `partition_pages`.
    hbm_fraction: float = 0.0
    #: Chat template name for GenerationService.register ("" = raw
    #: completion prompt — the duckdb-nsql shape).
    template: str = ""
    #: Replicas of this model in the pool.
    replicas: int = 1
    #: Whether the backend prepends BOS (llama3-chat renders its own).
    add_bos: bool = True

    def validate(self) -> "ModelSpec":
        if not self.model_id:
            raise ValueError("model spec needs a non-empty model id")
        if self.source not in _SOURCES:
            raise ValueError(
                f"model {self.model_id!r}: unknown source "
                f"{self.source!r} (expected one of {_SOURCES})"
            )
        if self.source in ("hf", "gguf") and not self.path:
            raise ValueError(
                f"model {self.model_id!r}: source {self.source!r} "
                f"needs a checkpoint path (model=source:/path)"
            )
        if self.hbm_fraction < 0 or self.hbm_fraction > 1:
            raise ValueError(
                f"model {self.model_id!r}: hbm fraction must be in "
                f"(0, 1], got {self.hbm_fraction}"
            )
        if self.replicas < 1:
            raise ValueError(
                f"model {self.model_id!r}: replicas must be >= 1, "
                f"got {self.replicas}"
            )
        return self


def parse_models_spec(spec: str) -> List[ModelSpec]:
    """Parse `LSOT_MODELS` — the multi-model fleet description.

    Format: `;`-separated entries, each
    `model_id=source[:path][,hbm=F][,template=T][,replicas=N][,add_bos=B]`

        LSOT_MODELS="duckdb-nsql=tiny,hbm=0.75;llama3.2=tiny,hbm=0.25,template=llama3-chat,add_bos=0"
        LSOT_MODELS="sql=hf:/ckpts/nsql,hbm=0.8;explainer=gguf:/ckpts/tiny.gguf,hbm=0.2"

    `tiny` needs no path. Omitted `hbm` fractions split whatever the
    explicit ones left over, equally. Explicit fractions summing past
    1.0 are a config error (two models cannot both hold 80% of one
    arena). Duplicate ids are a config error.
    """
    out: List[ModelSpec] = []
    seen: set = set()
    for raw in (spec or "").split(";"):
        raw = raw.strip()
        if not raw:
            continue
        if "=" not in raw:
            raise ValueError(
                f"LSOT_MODELS entry {raw!r}: expected "
                f"model_id=source[:path][,k=v...]"
            )
        model_id, rest = raw.split("=", 1)
        model_id = model_id.strip()
        parts = [p.strip() for p in rest.split(",")]
        src = parts[0]
        source, _, path = src.partition(":")
        source = source.strip().lower()
        kw: Dict[str, object] = {}
        for opt in parts[1:]:
            if not opt:
                continue
            if "=" not in opt:
                raise ValueError(
                    f"LSOT_MODELS entry {model_id!r}: option {opt!r} "
                    f"is not k=v"
                )
            k, v = (x.strip() for x in opt.split("=", 1))
            if k == "hbm":
                kw["hbm_fraction"] = float(v)
            elif k == "template":
                kw["template"] = v
            elif k == "replicas":
                kw["replicas"] = int(v)
            elif k == "add_bos":
                kw["add_bos"] = v.lower() in ("1", "true", "yes", "on")
            else:
                raise ValueError(
                    f"LSOT_MODELS entry {model_id!r}: unknown option "
                    f"{k!r} (expected hbm/template/replicas/add_bos)"
                )
        ms = ModelSpec(model_id=model_id, source=source,
                       path=path.strip(), **kw).validate()
        if ms.model_id in seen:
            raise ValueError(
                f"LSOT_MODELS: duplicate model id {ms.model_id!r}"
            )
        seen.add(ms.model_id)
        out.append(ms)
    if not out:
        return out
    # Normalize the HBM shares: explicit fractions must leave room for
    # every unspecified model; the leftovers split equally.
    explicit = sum(m.hbm_fraction for m in out if m.hbm_fraction > 0)
    free = [m for m in out if m.hbm_fraction <= 0]
    if explicit > 1.0 + 1e-9:
        raise ValueError(
            f"LSOT_MODELS: hbm fractions sum to {explicit:.3f} > 1.0"
        )
    if free:
        remaining = max(0.0, 1.0 - explicit)
        if remaining <= 1e-9:
            raise ValueError(
                "LSOT_MODELS: explicit hbm fractions leave no arena "
                f"for {[m.model_id for m in free]}"
            )
        share = remaining / len(free)
        out = [dataclasses.replace(m, hbm_fraction=share)
               if m.hbm_fraction <= 0 else m for m in out]
    return out


def partition_pages(total_pages: int,
                    specs: Sequence[ModelSpec]) -> Dict[str, int]:
    """Split one paged-KV arena budget between co-resident models.

    Proportional to `hbm_fraction`, floored, remainder to the largest
    share — and every model gets at least one page when the budget can
    hold one per model (a 5%-share explainer beside a 6-slot SQL model
    must still be able to admit a request).
    """
    if total_pages < len(specs):
        raise ValueError(
            f"page budget {total_pages} cannot hold one page per "
            f"model ({len(specs)} registered)"
        )
    shares = {m.model_id: int(total_pages * m.hbm_fraction)
              for m in specs}
    for mid in shares:
        shares[mid] = max(1, shares[mid])
    # Hand the rounding remainder (or claw back an over-allocation from
    # the minimum-1 floor) to/from the largest-share models.
    order = sorted(specs, key=lambda m: -m.hbm_fraction)
    spare = total_pages - sum(shares.values())
    i = 0
    while spare != 0 and order:
        mid = order[i % len(order)].model_id
        if spare > 0:
            shares[mid] += 1
            spare -= 1
        elif shares[mid] > 1:
            shares[mid] -= 1
            spare += 1
        i += 1
        if i > 4 * len(order) * max(1, abs(spare)):
            break  # degenerate budget; shares are as close as they get
    return shares


class ModelRegistry:
    """id → ModelSpec lookup for one fleet."""

    def __init__(self, specs: Sequence[ModelSpec] = ()):
        self._specs: Dict[str, ModelSpec] = {}
        for m in specs:
            if m.model_id in self._specs:
                raise ValueError(f"duplicate model id {m.model_id!r}")
            self._specs[m.model_id] = m.validate()

    def __contains__(self, model_id: str) -> bool:
        return model_id in self._specs

    def __len__(self) -> int:
        return len(self._specs)

    def ids(self) -> List[str]:
        return list(self._specs)

    def get(self, model_id: str) -> ModelSpec:
        try:
            return self._specs[model_id]
        except KeyError:
            raise UnknownModel(
                f"model {model_id!r} is not registered "
                f"(registered: {sorted(self._specs)})"
            ) from None

    def specs(self) -> List[ModelSpec]:
        return list(self._specs.values())


def build_tiny_model_service(
    specs: Sequence[ModelSpec],
    *,
    num_slots: int = 2,
    max_seq: int = 512,
    decode_chunk: int = 4,
    prompt_bucket: int = 8,
    kv_page_size: int = 8,
    total_pages: int = 0,
    max_new_tokens: int = 48,
    supervise: bool = False,
    seed: int = 0,
):
    """Stand up a co-resident multi-model fleet on tiny random weights.

    One paged `ContinuousBatchingScheduler` per (model, replica) — each
    stamped with its `model_id` and sized to its `partition_pages`
    share of ONE arena budget — all in ONE `SchedulerPool` that routes
    on model, plus one `SchedulerBackend` per model submitting through
    that shared pool. Returns `(service, pool, registry)`; shutting
    down the pool shuts down every scheduler.

    This is the test/smoke/bench harness for the multi-model subsystem:
    production fleets assemble through `app/__main__.py`'s checkpoint
    path with real `hf`/`gguf` sources instead.
    """
    import jax
    import jax.numpy as jnp

    from ..models import TINY, init_params
    from ..tokenizer import ByteTokenizer
    from .scheduler import (
        ContinuousBatchingScheduler,
        SchedulerBackend,
        SchedulerPool,
    )
    from .service import GenerationService

    specs = [m.validate() for m in specs]
    if not specs:
        raise ValueError("need at least one model spec")
    for m in specs:
        if m.source != "tiny":
            raise ValueError(
                f"build_tiny_model_service only builds tiny sources; "
                f"{m.model_id!r} is {m.source!r}"
            )
    n_replicas = sum(m.replicas for m in specs)
    # Default arena: enough for every replica to hold a full slot
    # complement, partitioned by the models' HBM fractions.
    pages_per_slot = -(-max_seq // kv_page_size)
    if total_pages <= 0:
        total_pages = n_replicas * num_slots * pages_per_slot
    shares = partition_pages(total_pages, specs)

    # TINY's CI context is smaller than a schema prompt; a longer
    # context costs nothing (rope tables are computed on the fly).
    cfg = dataclasses.replace(TINY, max_seq_len=max(TINY.max_seq_len,
                                                    2 * max_seq))
    tok = ByteTokenizer()
    scheds = []
    for idx, m in enumerate(specs):
        # Distinct seed per model: two checkpoints, not one aliased.
        # Derived from the spec POSITION, never hash(model_id) — str
        # hashing is salted per process (PYTHONHASHSEED), which made
        # fleet weights differ run to run and could even collide two
        # models onto ONE set of weights.
        params = init_params(
            cfg, jax.random.key(seed + idx + 1), dtype=jnp.float32,
        )
        per_replica = max(1, shares[m.model_id] // m.replicas)
        for _ in range(m.replicas):
            scheds.append(ContinuousBatchingScheduler(
                cfg, params, num_slots=num_slots,
                decode_chunk=decode_chunk, prompt_bucket=prompt_bucket,
                stop_ids=(2,), max_seq=max_seq,
                kv_page_size=kv_page_size,
                kv_pages=per_replica,
                model_id=m.model_id,
            ))
    pool = SchedulerPool(scheds)
    sched_like = pool
    if supervise:
        from .supervisor import SupervisedScheduler

        sched_like = SupervisedScheduler(pool)
    svc = GenerationService()
    for m in specs:
        backend = SchedulerBackend(
            sched_like, tok, max_new_tokens=max_new_tokens,
            add_bos=m.add_bos, model_id=m.model_id,
        )
        svc.register(m.model_id, backend,
                     template=m.template or "completion")
    return svc, sched_like, ModelRegistry(specs)
