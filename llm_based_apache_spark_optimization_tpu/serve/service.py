"""Generation service: the in-process replacement for the Ollama sidecar.

The reference calls `ollama.generate(model=..., system=..., prompt=...)` over
HTTP to a separate Go server and reads `res.response` (reference
`Flask/app.py:102-107,160-166`; `FastAPI/app.py:85-90,105-111`). Here the
same call shape is a method on an in-process registry of TPU engines — no
sidecar, no socket, and per-request metrics built in (SURVEY.md §5
observability: per-request tok/s and latency counters).
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
from typing import Dict, Optional

from ..ops.sampling import SamplingParams
from ..utils import tracing
from ..utils.observability import (
    MetricsRegistry,
    RequestMetrics,
    StageTimer,
    resilience,
)
from ..utils.tracing import TRACER
from .templates import TEMPLATES, Template

log = logging.getLogger("lsot.service")


@dataclasses.dataclass(frozen=True)
class GenerateResult:
    """Mirror of the ollama response surface the reference touches: only
    `.response` is read there; the rest is in-tree observability."""

    response: str
    model: str
    latency_s: float
    output_tokens: int
    # Per-request latency decomposition (scheduler-path backends; 0.0 =
    # not measured): TTFT and queue wait — the evalh report's "where
    # latency lives" columns read these.
    ttft_s: float = 0.0
    queue_wait_s: float = 0.0
    # Trace-correlation id when the request ran under one.
    request_id: str = ""

    @property
    def tok_per_s(self) -> float:
        return self.output_tokens / self.latency_s if self.latency_s > 0 else 0.0


@dataclasses.dataclass
class ModelEntry:
    name: str
    backend: object  # EngineBackend | FakeBackend (duck-typed .complete)
    template: Template


class GenerationService:
    """Named-model registry + generate() — the Ollama capability surface."""

    def __init__(self):
        self._models: Dict[str, ModelEntry] = {}
        self._lock = threading.Lock()
        self.stats: Dict[str, Dict[str, float]] = {}
        self.metrics = MetricsRegistry()
        # Drain mode (SIGTERM path): once set, the HTTP layers answer new
        # work with 503 + Retry-After while in-flight requests finish.
        self._draining = False
        # Per-tenant model routing (ISSUE 20, LSOT_TENANT_MODELS): tenant
        # → model_id atop the multi-model pool. Resolved at every
        # generate front door; unknown tenants (and tenants pinned to a
        # model that never registered) fall through to the request's own
        # model, warned once per tenant.
        import os

        from .qos import parse_tenant_models

        self._tenant_models: Dict[str, str] = parse_tenant_models(
            os.environ.get("LSOT_TENANT_MODELS", ""))
        self._tenant_model_warned: set = set()

    def set_tenant_models(self, spec: str) -> None:
        """Install a tenant → model_id routing map from its spec string
        (config wiring; replaces the env-derived map wholesale)."""
        from .qos import parse_tenant_models

        with self._lock:
            self._tenant_models = parse_tenant_models(spec)
            self._tenant_model_warned = set()

    def resolve_model(self, model: str, tenant: str) -> str:
        """Apply per-tenant model routing: a listed tenant's requests ride
        its pinned model_id; everything else — no tenant, unlisted
        tenant, pinned model not (yet) registered — falls through to the
        request's own `model` untouched."""
        if not tenant:
            return model
        with self._lock:
            pinned = self._tenant_models.get(tenant)
            if pinned is None:
                return model
            if pinned not in self._models:
                if tenant not in self._tenant_model_warned:
                    self._tenant_model_warned.add(tenant)
                    log.warning(
                        "tenant %r pins model %r which is not registered "
                        "(available: %s); falling through to %r",
                        tenant, pinned, sorted(self._models), model,
                    )
                return model
        return pinned

    def register(self, name: str, backend, template: str = "completion") -> None:
        if template not in TEMPLATES:
            raise ValueError(f"unknown template {template!r}; choices {sorted(TEMPLATES)}")
        with self._lock:
            self._models[name] = ModelEntry(name, backend, TEMPLATES[template])
            self.stats.setdefault(
                name, {"requests": 0, "total_latency_s": 0.0, "total_tokens": 0}
            )

    def models(self):
        return sorted(self._models)

    def _entry(self, model: str) -> ModelEntry:
        entry = self._models.get(model)
        if entry is None:
            raise KeyError(
                f"model {model!r} is not registered; available: {self.models()}"
            )
        return entry

    def backend_stats(self) -> Dict[str, Dict]:
        """Per-model serving-layer stats from backends exposing .stats()
        (SchedulerBackend: prefix-cache reuse, speculation acceptance —
        split by constrained/unconstrained class under
        speculation.by_class, since the grammar-masked hot path prices
        its speedup separately) — the /metrics endpoint merges these
        beside the request aggregates."""
        out: Dict[str, Dict] = {}
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            fn = getattr(e.backend, "stats", None)
            if callable(fn):
                out[e.name] = fn()
        return out

    def metrics_snapshot(self) -> Dict[str, Dict]:
        """The /metrics payload: per-model request aggregates with each
        model's serving-layer stats merged under "serving" — ONE
        definition for the web and headless-API endpoints. Process-wide
        fault-tolerance counters (retries, sheds, deadline expiries,
        breaker trips, supervisor restart/replay/lost counts —
        serve/resilience.py, serve/supervisor.py) ride under the reserved
        "resilience" key whenever any fired — or any breaker is live:
        under load these numbers ARE the serving story, and an operator
        reading only per-model aggregates would see throughput without
        the sheds that bought it. Per-dependency breaker state (ollama,
        sql backend, each supervised scheduler's restart breaker) rides
        beside them under "breakers" — WHICH dependency is open, not just
        that some trip counter moved; owners unregister their breakers at
        teardown so the view tracks live dependencies."""
        from .resilience import breaker_states

        snap = self.metrics.snapshot()
        for model, extra in self.backend_stats().items():
            snap.setdefault(model, {})["serving"] = extra
        counters = resilience.snapshot()
        breakers = breaker_states()
        if any(counters.values()) or breakers:
            snap["resilience"] = dict(counters)
            if breakers:
                snap["resilience"]["breakers"] = breakers
        # Rolling SLO view (utils/slo.py) under the reserved "slo" key
        # when objectives are configured: burn rates ARE the serving
        # story under load, and the Prometheus renderer turns this into
        # the lsot_slo_* families.
        from ..utils import slo as slo_mod

        if slo_mod.ENGINE.enabled:
            snap["slo"] = slo_mod.ENGINE.report()
        # Multi-tenant front door (ISSUE 18) under the reserved "qos"
        # key: per-tenant admit/shed counters and live bucket levels —
        # the lsot_tenant_* Prometheus families. Empty (key absent) for
        # a quiet single-tenant deployment.
        from .qos import ADMISSION

        qos_block = ADMISSION.snapshot()
        if qos_block:
            snap["qos"] = qos_block
        # Self-healing SQL (ISSUE 20) under the reserved "repair" key:
        # repair_rounds/repaired/unrepairable + per-class diagnosed
        # counters and the last few repair flight rows — the
        # lsot_repair_* Prometheus families. Empty (key absent) until a
        # repair loop has actually run.
        from ..app.repair import repair_metrics_block

        repair_block = repair_metrics_block()
        if repair_block:
            snap["repair"] = repair_block
        return snap

    def metrics_prometheus(self) -> str:
        """The same payload in Prometheus exposition text
        (`/metrics?format=prometheus`), plus the registry's fixed-bucket
        TTFT/TPOT/queue-wait/latency histograms — which aggregate across
        scrapes and replicas where windowed percentiles cannot."""
        from ..utils.prometheus import render_prometheus

        return render_prometheus(self.metrics_snapshot(),
                                 self.metrics.histograms)

    def flight_snapshot(self, last: Optional[int] = None) -> Dict[str, list]:
        """Per-model flight-recorder records (backends exposing the
        seam; replica-labeled, lifecycle events merged for supervised
        schedulers) — the /debug/flightrecorder payload. Backends are
        deduped by underlying scheduler like health()/drain(), so a
        shared scheduler's ring is not reported twice."""
        out: Dict[str, list] = {}
        with self._lock:
            entries = list(self._models.values())
        seen = set()
        for e in entries:
            fn = getattr(e.backend, "flight_snapshot", None)
            if not callable(fn):
                continue
            key = id(getattr(e.backend, "scheduler", e.backend))
            if key in seen:
                continue
            seen.add(key)
            out[e.name] = fn(last)
        return out

    def recent_traces(self, n: Optional[int] = None) -> list:
        """Last head-sampled request traces (the /debug/traces payload)."""
        return TRACER.recent(n)

    def prefix_registry(self, top_k: Optional[int] = None) -> Dict[str, Dict]:
        """Per-model content-addressed prefix-cache registries (ISSUE 14)
        — the /debug/prefixcache payload: resident digests with live
        metadata (token mass, bytes held, shares, hit counts), the
        reuse-distance histogram over recent admissions, and the
        eviction-churn counters. Deduped by underlying scheduler like
        flight_snapshot(), so a shared scheduler's registry is not
        reported twice; backends without the seam (fakes, engines) are
        skipped."""
        out: Dict[str, Dict] = {}
        with self._lock:
            entries = list(self._models.values())
        seen = set()
        for e in entries:
            fn = getattr(e.backend, "prefix_registry", None)
            if not callable(fn):
                continue
            key = id(getattr(e.backend, "scheduler", e.backend))
            if key in seen:
                continue
            seen.add(key)
            reg = fn(top_k)
            if reg:
                out[e.name] = reg
        return out

    def slo_report(self) -> Dict[str, object]:
        """The /debug/slo payload: the process SLO engine's rolling
        report (objectives, per-replica quantiles + burn rates, fleet
        merge) — populated even with no objective configured, so the
        quantile sketches are inspectable before alerting is wired."""
        from ..utils import slo as slo_mod

        return slo_mod.ENGINE.report()

    def profile_capture(self, rounds: Optional[int] = None,
                        model: Optional[str] = None) -> Dict[str, object]:
        """Arm an on-demand device-trace capture (the /debug/profile
        trigger) on the first backend exposing the seam — or `model`'s.
        Raises LookupError when no registered backend can profile
        (fake/demo backends), RuntimeError when a capture is already in
        flight fleet-wide (the endpoint's 409)."""
        with self._lock:
            entries = [e for e in self._models.values()
                       if model is None or e.name == model]
        seen = set()
        for e in entries:
            key = id(getattr(e.backend, "scheduler", e.backend))
            if key in seen:
                continue
            seen.add(key)
            fn = getattr(e.backend, "profile_rounds", None)
            if callable(fn):
                out = dict(fn(rounds))
                out["model"] = e.name
                return out
        raise LookupError(
            f"no {'backend for model ' + repr(model) if model else 'registered backend'}"
            f" supports device profiling"
        )

    def profile_status(self) -> Dict[str, object]:
        """Per-model capture state (armed/capturing/last artifact) —
        what the smoke script polls after arming."""
        out: Dict[str, object] = {}
        with self._lock:
            entries = list(self._models.values())
        seen = set()
        for e in entries:
            key = id(getattr(e.backend, "scheduler", e.backend))
            if key in seen:
                continue
            seen.add(key)
            fn = getattr(e.backend, "profile_status", None)
            if callable(fn):
                st = fn()
                if st:
                    out[e.name] = st
        return out

    # ------------------------------------------------------------- lifecycle

    @property
    def draining(self) -> bool:
        return self._draining

    def health(self) -> Dict[str, object]:
        """Aggregate lifecycle state for /readyz: the WORST state across
        backends exposing a health() seam (the supervised scheduler's
        ready | restarting | degraded | dead), plus per-model detail and
        summed restart counters. Backends without the seam (engine,
        fakes) are 'ready' by construction — their failures are
        per-request, not lifecycle."""
        order = {"ready": 0, "degraded": 1, "restarting": 2, "dead": 3}
        worst = "ready"
        models: Dict[str, Dict] = {}
        # `stalls` counts watchdog-detected wedges (serve/watchdog.py): a
        # stalled loop surfaces as `restarting` here the moment the
        # monitor escalates it — /readyz must stop saying ready while
        # requests silently sit on a wedged device.
        totals = {"restarts": 0, "replayed": 0, "lost": 0, "stalls": 0}
        with self._lock:
            entries = list(self._models.values())
        seen = set()
        for e in entries:
            hfn = getattr(e.backend, "health", None)
            h = hfn() if callable(hfn) else None
            if not h:
                continue
            models[e.name] = h
            state = h.get("state", "ready")
            if order.get(state, 0) > order[worst]:
                worst = state
            # Dedupe by the underlying SCHEDULER, not the backend wrapper:
            # the shared-weights aliasing rule (serve/factory.py) wraps
            # one supervisor in two SchedulerBackends, and double-counting
            # its restarts would make /readyz report phantom instability.
            key = id(getattr(e.backend, "scheduler", e.backend))
            if key not in seen:
                seen.add(key)
                for k in totals:
                    totals[k] += int(h.get(k, 0) or 0)
        out: Dict[str, object] = {
            "state": worst,
            "draining": self._draining,
            "models": models,
            **totals,
        }
        # Rolling SLO (utils/slo.py): a replica BURNING a configured
        # objective (multi-window burn rate > 1 on both arms) marks the
        # instance degraded — still serving (200 from /readyz), but
        # flagged for operators and visibly worse than 'ready'. Crash/
        # restart states stay strictly worse: a burning SLO never
        # downgrades 'restarting'/'dead' information.
        from ..utils import slo as slo_mod

        if slo_mod.ENGINE.enabled:
            # ONE report per probe: readiness polls every few seconds,
            # and `burning` + `state` must come from the same snapshot
            # (two calls could straddle a window-slice rollover).
            rep = slo_mod.ENGINE.report()
            out["slo"] = {"state": rep["state"],
                          "burning": rep["burning"]}
            if rep["burning"] and out["state"] == "ready":
                out["state"] = "degraded"
        return out

    def fleet_health(self) -> Dict[str, list]:
        """Per-replica lifecycle per model, for backends serving from a
        replica fleet (SchedulerPool / a supervisor wrapping one):
        {model: [{replica, state, phase_role, restarts, ...}]} — a
        disaggregated fleet (ISSUE 13) shows each replica's prefill/
        decode/mixed role beside its lifecycle state, so one probe says
        both WHICH replica is restarting/dead and which phase lost
        capacity. Empty for single-scheduler and engine backends.
        Surfaced on /healthz, and deduped by underlying scheduler like
        health() (shared-weights aliasing)."""
        out: Dict[str, list] = {}
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            sched = getattr(e.backend, "scheduler", None)
            fn = getattr(sched, "replica_health", None)
            if callable(fn):
                try:
                    reps = fn()
                except Exception:  # noqa: BLE001 — a churning fleet mid-read
                    continue
                if reps:
                    out[e.name] = reps
        return out

    def fleet_membership(self) -> Dict[str, Dict[str, object]]:
        """Elastic-membership view per model (ISSUE 17): the pool's
        fleet_stats() — size/serving/elastic counts, join/retire/drain
        lifecycle counters, pushed-handoff pump depth/bytes/latency —
        beside the per-replica lifecycle above. Empty for backends
        without a fleet. Surfaced on /healthz."""
        out: Dict[str, Dict[str, object]] = {}
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            sched = getattr(e.backend, "scheduler", None)
            fn = getattr(sched, "fleet_stats", None)
            if callable(fn):
                try:
                    stats = fn()
                except Exception:  # noqa: BLE001 — a churning fleet mid-read
                    continue
                if stats:
                    out[e.name] = stats
        return out

    def supports_idempotency(self, model: str) -> bool:
        """Can `model`'s backend dedupe an idempotency key against a
        journal? The drain gate uses this to decide whether a keyed
        request during shutdown is a safe journal lookup (let through) or
        plain new work wearing a key (refused like any other)."""
        with self._lock:
            entry = self._models.get(model)
        return bool(entry and getattr(entry.backend, "supports_idempotency",
                                      False))

    def retry_after_hint(self, default: float = 1.0) -> float:
        """Backpressure hint for drain-mode 503s / readiness failures: the
        largest queue-drain estimate across backends exposing one (the
        scheduler's queue-depth × service-time estimate)."""
        hints = []
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            fn = getattr(e.backend, "retry_after_hint", None)
            if callable(fn):
                hints.append(fn())
        return max(hints) if hints else default

    def drain(self, deadline_s: Optional[float] = None) -> None:
        """Graceful shutdown (SIGTERM): stop admitting — the HTTP drain
        gate answers 503 + Retry-After from here on — then let each
        backend finish in-flight work up to the shared drain deadline
        (supervised schedulers journal-and-exit what is left), then close
        everything."""
        from .resilience import Deadline

        self._draining = True
        # deadline_s <= 0 means "journal-and-exit NOW", never "wait
        # forever": a 0-configured drain must not block on a wedged loop.
        deadline = (Deadline.after(deadline_s)
                    if deadline_s is not None and deadline_s > 0 else None)
        immediate = deadline_s is not None and deadline_s <= 0
        seen = set()
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            d = getattr(e.backend, "drain", None)
            # Same scheduler-level dedupe as health(): two wrappers over
            # one supervisor must drain (and spill) it exactly once.
            key = id(getattr(e.backend, "scheduler", e.backend))
            if d is None or key in seen:
                continue
            seen.add(key)
            remaining = deadline.remaining() if deadline is not None else None
            if immediate or (remaining is not None and remaining <= 0):
                remaining = 0.0  # burned: backends spill without waiting
            d(remaining)
        self.close()

    def close(self) -> None:
        """Shut down owned backend resources (scheduler threads, slot-pool
        caches). Idempotent; shared backends (one scheduler behind two
        model names) shut down once."""
        seen = set()
        with self._lock:
            entries = list(self._models.values())
        for e in entries:
            shutdown = getattr(e.backend, "shutdown", None)
            if shutdown is not None and id(e.backend) not in seen:
                seen.add(id(e.backend))
                shutdown()

    @staticmethod
    def _constrain_kwargs(entry: ModelEntry, constrain) -> Dict:
        """`constrain` is opt-in per request ("spark_sql", or a schema dict
        {"table", "columns"}): forwarded only to backends that declare
        `supports_constrain`; anything else is a clear request-shape error
        rather than a silently unconstrained completion."""
        if constrain is None:
            return {}
        if not getattr(entry.backend, "supports_constrain", False):
            raise ValueError(
                f"model {entry.name!r} backend does not support "
                f"constrained decoding"
            )
        return {"constrain": constrain}

    @staticmethod
    def _deadline_kwargs(entry: ModelEntry, deadline_s) -> Dict:
        """Per-request deadline (seconds), forwarded only to backends that
        can actually enforce one (`supports_deadline`: the scheduler
        retires in-flight work at harvest; the one-XLA-program engine
        clamps its step budget at issue time from the remaining deadline
        and the measured per-token rate). Backends without the seam —
        fakes — silently ignore it: a deadline is best-effort latency
        control, not a correctness contract, and failing the request over
        an unenforceable hint would be worse than serving it."""
        if deadline_s is None or not getattr(
                entry.backend, "supports_deadline", False):
            return {}
        return {"deadline_s": deadline_s}

    @staticmethod
    def _idempotency_kwargs(entry: ModelEntry, idempotency_key) -> Dict:
        """Client-suppliable idempotency key, forwarded only to backends
        with a journal to dedupe against (`supports_idempotency`: the
        supervised scheduler). Elsewhere it is silently dropped — the key
        is a retry-safety hint, and a backend that cannot honor it still
        serves the request correctly once."""
        if idempotency_key is None or not getattr(
                entry.backend, "supports_idempotency", False):
            return {}
        return {"idempotency_key": idempotency_key}

    @staticmethod
    def _qos_kwargs(entry: ModelEntry, tenant: str, qos: str) -> Dict:
        """Tenant/qos labels (ISSUE 18), forwarded only to backends that
        understand the axis (`supports_qos`: the scheduler path, where
        WFQ ordering and per-tenant prefix namespaces live). Elsewhere
        the labels were still charged at admission — they are a
        fairness/accounting hint, not a correctness contract."""
        if not (tenant or qos) or not getattr(entry.backend,
                                              "supports_qos", False):
            return {}
        return {"tenant": tenant, "qos": qos}

    def _admit_qos(self, tenant: str, qos: str,
                   deadline_s: Optional[float]) -> Optional[float]:
        """Front-door admission (ISSUE 18): consume one bucket token for
        (tenant, class) — raises TenantShed (→ HTTP 429) with a
        bucket-aware Retry-After when the tenant is over budget — and
        apply the class's default deadline when the request carries none
        (interactive gets the tighter budget the deadline machinery
        already honors). No-op with `LSOT_QOS=0`."""
        from .qos import ADMISSION

        if not ADMISSION.enabled:
            return deadline_s
        ADMISSION.admit(tenant, qos, fleet_hint=self.retry_after_hint())
        if deadline_s is None:
            return ADMISSION.default_deadline(qos)
        return deadline_s

    def generate(
        self,
        model: str,
        prompt: str,
        system: str = "",
        max_new_tokens: Optional[int] = None,
        sampling: Optional[SamplingParams] = None,
        seed: int = 0,
        constrain=None,
        deadline_s: Optional[float] = None,
        idempotency_key: Optional[str] = None,
        request_id: Optional[str] = None,
        tenant: str = "",
        qos: str = "",
    ) -> GenerateResult:
        model = self.resolve_model(model, tenant)
        entry = self._entry(model)
        deadline_s = self._admit_qos(tenant, qos, deadline_s)
        rendered = entry.template(system, prompt)
        # Request-scoped tracing: honor the HTTP layer's sampling
        # decision when one exists, else head-sample here — the shared
        # entry-point dance (tracing.begin_or_ambient).
        tr, own, rid = tracing.begin_or_ambient(request_id, model)
        t0 = time.perf_counter()
        try:
            with tracing.use(tr) if own is not None else contextlib.nullcontext():
                with tracing.span("service.generate", model=model,
                                  constrained=constrain is not None):
                    completion = entry.backend.complete(
                        rendered, max_new_tokens=max_new_tokens,
                        sampling=sampling, seed=seed,
                        **self._constrain_kwargs(entry, constrain),
                        **self._deadline_kwargs(entry, deadline_s),
                        **self._idempotency_kwargs(entry, idempotency_key),
                        **self._qos_kwargs(entry, tenant, qos),
                    )
        finally:
            TRACER.finish(own)
        latency = time.perf_counter() - t0
        with self._lock:
            s = self.stats[model]
            s["requests"] += 1
            s["total_latency_s"] += latency
            s["total_tokens"] += completion.output_tokens
        self.metrics.record(RequestMetrics(
            model=model,
            prompt_tokens=completion.prompt_tokens,
            output_tokens=completion.output_tokens,
            latency_s=latency,
            ttft_s=getattr(completion, "ttft_s", 0.0),
            queue_wait_s=getattr(completion, "queue_wait_s", 0.0),
            rclass=getattr(completion, "rclass", ""),
            replica=getattr(completion, "replica", ""),
            request_id=rid,
        ))
        return GenerateResult(
            response=completion.text,
            model=model,
            latency_s=latency,
            output_tokens=completion.output_tokens,
            ttft_s=getattr(completion, "ttft_s", 0.0),
            queue_wait_s=getattr(completion, "queue_wait_s", 0.0),
            request_id=rid,
        )

    def validate(
        self,
        model: str,
        prompt: str,
        system: str = "",
        max_new_tokens: Optional[int] = None,
        constrain=None,
    ) -> None:
        """Raise the same KeyError/ValueError generate() would raise for a
        bad model name, an oversize prompt, or a bad `constrain` spec —
        WITHOUT generating. Streaming handlers call this before sending
        response headers: a request-shape error must become a 400/404
        status, which is impossible once the NDJSON stream's 200 is on the
        wire. Backends without a budget seam (fakes) validate trivially.

        `constrain` checks mirror the generate path: unsupported backend
        (ValueError here, not a mid-stream line), an uncompilable schema
        spec (e.g. no usable identifiers — the compile runs here and is
        cached for the actual request), and a budget below the grammar's
        shortest complete parse.

        The check tokenizes the rendered prompt a second time (the
        generate call re-encodes it); that is host-side microseconds per
        kilotoken against a device TTFT of tens of milliseconds, and
        keeping validate() stateless beats threading encoded ids through
        the service/backend seam."""
        entry = self._entry(model)
        self._constrain_kwargs(entry, constrain)  # supports check
        compiled = None
        if constrain is not None:
            resolve = getattr(entry.backend, "_resolve_constraint", None)
            if resolve is not None:
                compiled = resolve(constrain)  # compile errors become 400s
        check = getattr(entry.backend, "check_budget", None)
        if check is not None:
            # The backend checks its CLAMPED budget (what generate will
            # actually run with after the decode-room clamp) against the
            # grammar's shortest complete parse — the raw requested value
            # can pass while the clamp still makes the parse impossible.
            check(entry.template(system, prompt), max_new_tokens,
                  constraint=compiled)

    def generate_stream(
        self,
        model: str,
        prompt: str,
        system: str = "",
        max_new_tokens: Optional[int] = None,
        sampling: Optional[SamplingParams] = None,
        seed: int = 0,
        constrain=None,
        deadline_s: Optional[float] = None,
        request_id: Optional[str] = None,
        tenant: str = "",
        qos: str = "",
        stages: Optional[StageTimer] = None,
    ):
        """Yield the completion as text chunks while it decodes (Ollama's
        `stream=true` surface). Backends without a `complete_stream` seam
        (the one-XLA-program engine, fakes) degrade to a single chunk.
        Metrics record the request exactly like generate(), with the sums
        of the stream's own spans: `stages` is the stream's StageTimer —
        the HTTP layer passes the one its `http.chunk` spans go to, the
        backend adds `stream.detok`. Front-door
        admission (ISSUE 18) runs on the generator's FIRST step — the
        HTTP layer primes the stream before sending headers, so a shed
        still answers a real 429."""
        model = self.resolve_model(model, tenant)
        entry = self._entry(model)
        deadline_s = self._admit_qos(tenant, qos, deadline_s)
        ckw = self._constrain_kwargs(entry, constrain)
        ckw.update(self._deadline_kwargs(entry, deadline_s))
        ckw.update(self._qos_kwargs(entry, tenant, qos))
        rendered = entry.template(system, prompt)
        # Tracing: the BACKEND generator reads tracing.current() at its
        # first step (the scheduler's complete_stream captures it before
        # submit), which runs inside THIS generator's frame. The shared
        # entry-point dance decides the sample (tracing.begin_or_ambient);
        # when this call drew it (`own`), the context is entered only
        # around backend ADVANCEMENT, never across our own yields — a
        # contextvar set held across a yield leaks into the caller's
        # frame between steps (generators don't isolate contextvars), so
        # a library caller interleaving two sampled streams would record
        # request B's spans into request A's tree.
        tr, own, rid = tracing.begin_or_ambient(request_id, model)

        def _ctx():
            return tracing.use(tr) if own is not None \
                else contextlib.nullcontext()

        if stages is None:
            stages = StageTimer(rid=rid)
        t0 = time.perf_counter()
        out_tokens = prompt_tokens = 0
        stream_stats: dict = {}
        try:
            streamer = getattr(entry.backend, "complete_stream", None)
            if streamer is None:
                with _ctx():
                    completion = entry.backend.complete(
                        rendered, max_new_tokens=max_new_tokens,
                        sampling=sampling, seed=seed, **ckw,
                    )
                out_tokens, prompt_tokens = (completion.output_tokens,
                                             completion.prompt_tokens)
                if completion.text:
                    yield completion.text
            else:
                # The backend fills real token counts through stats_out
                # (chunk counts are not token counts; re-encoding here
                # would tokenize the prompt twice).
                inner = streamer(
                    rendered, max_new_tokens=max_new_tokens,
                    sampling=sampling, seed=seed, stats_out=stream_stats,
                    stages=stages, **ckw,
                )
                try:
                    # tracing.stepwise: the backend advances under the
                    # trace context, which is never held across our own
                    # yields (the generator/contextvar hazard). Only
                    # needed when this call drew the sample; the HTTP
                    # path advances plain.
                    src = tracing.stepwise(inner, tr) \
                        if own is not None else inner
                    for chunk in src:
                        yield chunk
                finally:
                    # Deterministically unwind the backend generator
                    # (its finally cancels the scheduler request and
                    # fills stats_out) BEFORE the accounting below
                    # reads it — a disconnect would otherwise leave it
                    # to the GC. No trace context needed: the backend
                    # captured its trace object at its first step.
                    inner.close()
        finally:
            # Record even when the client disconnects mid-stream (the WSGI
            # server close()s the generator -> GeneratorExit lands here):
            # disconnect-heavy streaming must not vanish from the serving
            # metrics. The backend's own finally has filled stats_out by
            # the time the generator unwinds.
            TRACER.finish(own)
            out_tokens = stream_stats.get("output_tokens", out_tokens)
            prompt_tokens = stream_stats.get("prompt_tokens", prompt_tokens)
            latency = time.perf_counter() - t0
            spans = stages.spans
            with self._lock:
                s = self.stats[model]
                s["requests"] += 1
                s["total_latency_s"] += latency
                s["total_tokens"] += out_tokens
            self.metrics.record(RequestMetrics(
                model=model,
                prompt_tokens=prompt_tokens,
                output_tokens=out_tokens,
                latency_s=latency,
                ttft_s=stream_stats.get("ttft_s", 0.0),
                queue_wait_s=stream_stats.get("queue_wait_s", 0.0),
                prefill_s=stream_stats.get("prefill_s", 0.0),
                first_hold_s=stream_stats.get("first_hold_s", 0.0),
                prefix_reused_tokens=stream_stats.get(
                    "prefix_reused_tokens", 0),
                stream_lag_p90_s=stream_stats.get("stream_lag_p90_s", 0.0),
                detok_s=spans.get("stream.detok", 0.0),
                chunk_s=spans.get("http.chunk", 0.0),
                rclass=stream_stats.get("rclass", ""),
                replica=stream_stats.get("replica", ""),
                request_id=rid,
            ))

    def generate_batch(
        self,
        model: str,
        prompts: "list[str]",
        system: str = "",
        max_new_tokens: Optional[int] = None,
        sampling: Optional[SamplingParams] = None,
        seed: int = 0,
        constrain=None,
        tenant: str = "",
        qos: str = "",
    ) -> "list[GenerateResult]":
        """Batched twin of generate(): one device program for all prompts.

        Latency reported per result is the batch wall-clock (that IS each
        request's latency when submitted together); tok/s aggregates across
        the batch in the metrics registry.
        """
        model = self.resolve_model(model, tenant)
        entry = self._entry(model)
        # One admission token per batch MEMBER: a storm tenant cannot
        # dodge its budget by folding the storm into one batch call.
        for _ in prompts:
            self._admit_qos(tenant, qos, None)
        rendered = [entry.template(system, p) for p in prompts]
        t0 = time.perf_counter()
        completions = entry.backend.complete_batch(
            rendered, max_new_tokens=max_new_tokens, sampling=sampling,
            seed=seed, **self._constrain_kwargs(entry, constrain),
            **self._qos_kwargs(entry, tenant, qos),
        )
        latency = time.perf_counter() - t0
        with self._lock:
            s = self.stats[model]
            s["requests"] += len(prompts)
            # total_latency_s is DISTINCT wall-clock in both paths: the
            # sequential path adds each request's own wall; here the batch
            # wall counts once, not once per member.
            s["total_latency_s"] += latency
            s["total_tokens"] += sum(c.output_tokens for c in completions)
        for c in completions:
            self.metrics.record(RequestMetrics(
                model=model, prompt_tokens=c.prompt_tokens,
                output_tokens=c.output_tokens, latency_s=latency,
                wall_share_s=latency / len(completions),
                ttft_s=getattr(c, "ttft_s", 0.0),
            ))
        return [
            GenerateResult(
                response=c.text, model=model, latency_s=latency,
                output_tokens=c.output_tokens,
            )
            for c in completions
        ]
