"""Health-gated serving: /healthz, /readyz, and the drain gate.

Kubernetes-shaped lifecycle endpoints for both HTTP frontends (the
headless JSON API and the web UI register the same routes — one
definition, app/api.py + app/web.py):

- `GET /healthz` — LIVENESS: the process is up and the WSGI loop answers.
  Always 200 while the process serves; a dead supervisor does NOT fail
  liveness (restarting the pod would throw away the journal a human might
  still want to inspect — readiness already pulls it out of rotation).
  Fleet deployments (SchedulerPool) also carry per-replica lifecycle in
  the body (`fleet`: {model: [{replica, state, restarts, stalls, ...}]}),
  so one probe attributes a restart/drain to the replica it hit.
- `GET /readyz` — READINESS: should this instance receive traffic?
  Aggregates the supervised schedulers' lifecycle
  (`ready | restarting | degraded | dead`, serve/supervisor.py) through
  `GenerationService.health()`:

      ready       200 — serving normally
      degraded    200 — serving, but the last restart dropped work
                  (capacity restored, flagged for operators)
      restarting  503 + Retry-After — the loop is being rebuilt; traffic
                  should go elsewhere and retry. A loop the WATCHDOG
                  caught wedged (stale busy heartbeat, serve/watchdog.py)
                  lands here too the moment it is escalated — a stalled
                  loop must stop reading `ready` while requests silently
                  sit on a hung device; the Retry-After includes the
                  restart backoff remaining
      dead        503 — restart budget exhausted; pull the instance
      draining    503 + Retry-After — SIGTERM received, shutting down

  The body carries the full health payload (per-model states, restart/
  replay/lost/stall counters) so `/readyz` doubles as the crash-recovery
  dashboard.
- **Drain gate** — a `before_request` hook: once `service.drain()` has
  been triggered (SIGTERM, app/__main__.py), every new mutating request
  (POST) answers 503 + Retry-After while in-flight work finishes. GETs
  (health probes, /metrics, result pages) stay up so operators can watch
  the drain. The Retry-After is the queue-depth-aware estimate
  (scheduler service-time EWMA), shared with the 429 shed path.
"""

from __future__ import annotations

import math

from ..serve.service import GenerationService
from .wsgi import App, Request, Response

__all__ = ["add_debug_routes", "add_health_routes", "install_drain_gate",
           "metrics_response"]


def metrics_response(service: GenerationService, req: "Request") -> "Response":
    """The shared `/metrics` body for BOTH frontends (app/api.py and
    app/web.py): JSON by default, `?format=prometheus` renders the
    exposition text, anything else is a 400 — one place for the format
    contract, so the two routes cannot drift (content-type, compression,
    auth all land here once)."""
    fmt = req.query.get("format", "json")
    if fmt == "prometheus":
        from ..utils.prometheus import CONTENT_TYPE

        return Response(
            body=service.metrics_prometheus().encode(),
            headers=[("Content-Type", CONTENT_TYPE)],
        )
    if fmt != "json":
        return Response.json(
            {"error": "'format' must be json or prometheus"}, status=400)
    return Response.json(service.metrics_snapshot())

#: readiness state → (HTTP status, include Retry-After)
_READY_STATUS = {
    "ready": (200, False),
    "degraded": (200, False),
    "restarting": (503, True),
    "dead": (503, False),
}


def _retry_after(seconds: float) -> list:
    return [("Retry-After", str(max(1, int(math.ceil(seconds)))))]


def add_health_routes(app: App, service: GenerationService) -> None:
    """Register /healthz + /readyz on an App (both frontends call this)."""

    @app.route("/healthz")
    def healthz(req: Request) -> Response:
        # Liveness stays liveness: always 200 while the process serves.
        # Fleet deployments (SchedulerPool replicas) additionally carry
        # the per-replica lifecycle here — one probe answers WHICH
        # replica is restarting/drained/dead, without flipping liveness
        # (readiness already pulls degraded instances out of rotation).
        body: dict = {"status": "ok"}
        fleet = service.fleet_health()
        if fleet:
            body["fleet"] = fleet
        # Elastic membership (ISSUE 17): size/joins/retires/drain +
        # pushed-handoff pump ledger per model, so the same probe
        # answers "did the fleet actually scale" without /metrics.
        membership = service.fleet_membership()
        if membership:
            body["fleet_membership"] = membership
        return Response.json(body)

    @app.route("/readyz")
    def readyz(req: Request) -> Response:
        health = service.health()
        if service.draining:
            return Response.json(
                {**health, "state": "draining"}, status=503,
                headers=_retry_after(service.retry_after_hint()),
            )
        status, hint = _READY_STATUS.get(health["state"], (503, False))
        headers = (_retry_after(service.retry_after_hint())
                   if status != 200 and hint else None)
        return Response.json(health, status=status, headers=headers)


def add_debug_routes(app: App, service: GenerationService) -> None:
    """Register the observability debug surface on an App (both
    frontends, like the health routes):

    - `GET /debug/flightrecorder[?last=N]` — the scheduler flight
      recorder's live ring per model: per-harvested-round records
      (occupancy, admitted/retired rids, emitted/speculation tokens,
      round wall, cadence) merged with supervisor lifecycle events and
      replica-labeled for pools (serve/flightrecorder.py). The same
      records a crash/stall/SIGTERM postmortem dumps to disk — this
      route answers "what is the scheduler doing RIGHT NOW".
    - `GET /debug/traces[?last=N]` — the most recent head-sampled
      request traces (utils/tracing.py): span trees with queue-wait /
      prefill / per-round decode / SQL-exec timing, plus the tracer's
      sampling config.
    - `GET /debug/slo` — the rolling SLO engine's report (utils/slo.py):
      per-replica + fleet quantile sketches over TTFT/TPOT/queue-wait,
      burn rates per window arm, and which replicas are burning.
    - `GET /debug/prefixcache[?top=K]` — the content-addressed
      prefix-cache registry per model (ISSUE 14): top-K resident
      entries by token mass (digest, tokens, pages/bytes held, live
      shares, hit counts, insert/last-hit round), the reuse-distance
      histogram over a bounded ring of recent admissions, and the
      eviction-churn counters (evictions, ghost-list reinsertions).
      Replica-labeled for fleets; entries carry digests, never token
      ids.
    - `GET /debug/profile[?rounds=N[&model=M]]` — on-demand device
      profiling: with `rounds`, take a bounded jax.profiler capture of
      the scheduler's next N rounds (409 when a capture is already in
      flight fleet-wide or the profiler will not start). THIS request's
      thread starts the trace and answers `armed`; the scheduler's
      worker only counts the rounds, and a writer thread stops the
      trace — tens of seconds on a TPU, during which the serving loop
      keeps serving — and lists the artifacts (`*.xplane.pb` and the
      Perfetto-loadable `*.trace.json.gz`, next to the per-request
      trace exports). Without `rounds`, poll the capture state: `armed`
      → `capturing` (rounds left) → `writing` → `idle`, the finished
      capture under `last` (`done` / `error` / `aborted`, artifact
      list, `start_s`, `stop_s`). The trace runs from the request on,
      rounds or no rounds: a capture a minute old on a server with
      nothing to serve is stopped and reported `aborted` (no round was
      issued) or `error` (cut short), as is one the scheduler's
      shutdown or crash cuts short — after the clients have been
      answered, on the writer thread too. The host's side of the trace
      is the loop's own spans (`sched.*`, `stream.detok`, `http.chunk`:
      utils/tracing.py), not the Python tracer, which is off."""

    @app.route("/debug/flightrecorder")
    def flightrecorder(req: Request) -> Response:
        try:
            last = int(req.query.get("last", "0")) or None
        except ValueError:
            return Response.json({"error": "'last' must be an integer"},
                                 status=400)
        return Response.json({"models": service.flight_snapshot(last)})

    @app.route("/debug/traces")
    def traces(req: Request) -> Response:
        from ..utils.tracing import TRACER

        try:
            last = int(req.query.get("last", "0")) or None
        except ValueError:
            return Response.json({"error": "'last' must be an integer"},
                                 status=400)
        return Response.json({
            "tracer": TRACER.stats(),
            "traces": service.recent_traces(last),
        })

    @app.route("/debug/slo")
    def slo(req: Request) -> Response:
        return Response.json(service.slo_report())

    @app.route("/debug/prefixcache")
    def prefixcache(req: Request) -> Response:
        try:
            top = int(req.query.get("top", "0")) or None
        except ValueError:
            return Response.json({"error": "'top' must be an integer"},
                                 status=400)
        if top is not None and top < 1:
            # A negative K would flow into list slicing as a from-the-end
            # slice — a near-unbounded payload instead of a bound.
            return Response.json({"error": "'top' must be >= 1"},
                                 status=400)
        return Response.json({"models": service.prefix_registry(top)})

    @app.route("/debug/profile")
    def profile(req: Request) -> Response:
        rounds = req.query.get("rounds")
        if rounds is None:
            # Poll: the armed/capturing/last-artifact state per model.
            return Response.json({"captures": service.profile_status()})
        try:
            n = int(rounds)
        except ValueError:
            return Response.json({"error": "'rounds' must be an integer"},
                                 status=400)
        model = req.query.get("model") or None
        try:
            return Response.json(service.profile_capture(n, model=model))
        except LookupError as e:
            # No registered backend can profile (fake/demo backends).
            return Response.json({"error": str(e)}, status=400)
        except RuntimeError as e:
            # The fleet-wide single-capture guard: one at a time.
            return Response.json({"error": str(e)}, status=409)
        except ValueError as e:
            return Response.json({"error": str(e)}, status=400)


def install_drain_gate(app: App, service: GenerationService) -> None:
    """Refuse NEW mutating work during drain with 503 + Retry-After.

    Exception: a `/api/generate` POST carrying an `idempotency_key` for
    a model whose backend can actually DEDUPE it (a supervised
    scheduler's journal) is let through: the supervisor serves an
    already-journaled result from its cache even while draining (the
    "retry with the same key is safe" contract — the result may only
    exist in THIS process) and answers a typed `Draining` 503 itself
    when the key is unknown. A key aimed at a backend without a journal
    is just new work wearing a key — refused like any other."""

    @app.before_request
    def drain_gate(req: Request):
        if req.method != "POST" or not service.draining:
            return None
        if req.path == "/api/generate":
            try:
                body = req.json()
                if isinstance(body.get("idempotency_key"), str) and \
                        service.supports_idempotency(body.get("model", "")):
                    return None  # the journal, not the gate, answers
            except Exception:  # noqa: BLE001 — malformed body: no key to
                pass           # honor, so it gets the drain 503 below
        return Response.json(
            {"error": "server draining: not accepting new requests"},
            status=503,
            headers=_retry_after(service.retry_after_hint()),
        )
