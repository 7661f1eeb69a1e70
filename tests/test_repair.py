"""Self-healing SQL (ISSUE 20): classification, bounded repair loop, pipeline
wiring, per-tenant model routing, metrics surfaces, and the evalh
executable%-after-k leg.

The loop's chaos contract (bounded typed termination under per-class
injection, LSOT_REPAIR=0 bit-parity, clean traffic untouched) also runs
as `evalh --chaos` stage 10; these tests pin the unit-level semantics the
stage builds on.
"""

import time

import pytest

from llm_based_apache_spark_optimization_tpu.app import repair as repair_mod
from llm_based_apache_spark_optimization_tpu.app.repair import (
    REPAIR_CLASSES,
    REPAIRABLE_CLASSES,
    RepairEngine,
    build_repair_prompt,
    classify_sql_error,
    repair_metrics_block,
)
from llm_based_apache_spark_optimization_tpu.serve.flightrecorder import (
    FlightRecorder,
)
from llm_based_apache_spark_optimization_tpu.serve.resilience import (
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    Overloaded,
)
from llm_based_apache_spark_optimization_tpu.utils.faults import (
    InjectedFault,
    InjectedSQLError,
    SQL_FAULT_ERRORS,
)
from llm_based_apache_spark_optimization_tpu.utils.observability import (
    CounterSet,
)


@pytest.fixture()
def counters(monkeypatch):
    """Fresh repair counters + flight ring per test: the production
    objects are process-global singletons, so asserting absolutes needs
    isolation (delta-math everywhere else would hide double counting)."""
    fresh = CounterSet()
    monkeypatch.setattr(repair_mod, "repair_counters", fresh)
    monkeypatch.setattr(repair_mod, "REPAIR_FLIGHT",
                        FlightRecorder(replica="repair"))
    return fresh


# ----------------------------------------------------------- classification


def test_injected_sites_classify_by_site_name():
    for site, (exc_cls, message) in SQL_FAULT_ERRORS.items():
        expect = site.rpartition(":")[2]
        assert classify_sql_error(exc_cls(site, message)) == expect


def test_classify_message_shapes():
    cases = {
        "no such column: total_amout": "schema",
        "Table or view not found: trips": "schema",
        "cannot resolve 'fare' given input columns": "schema",
        "datatype mismatch: cannot cast string to int": "type",
        "invalid input syntax for type integer": "type",
        "out of memory": "resource",
        "disk I/O error": "resource",
        'near "FORM": syntax error': "syntax",
        "ParseException: mismatched input 'SELEC'": "syntax",
        "something entirely novel": "syntax",  # broadest default
    }
    for message, expect in cases.items():
        assert classify_sql_error(Exception(message)) == expect, message


def test_classify_typed_capacity_sheds_are_resource():
    assert classify_sql_error(CircuitOpen("sql backend down")) == "resource"
    assert classify_sql_error(Overloaded("queue full")) == "resource"


def test_classify_transient_infra():
    assert classify_sql_error(
        InjectedFault("sql:transient", "database is locked")) == "transient"
    assert classify_sql_error(ConnectionError("peer reset")) == "transient"


def test_error_class_vocabulary_is_fixed():
    assert set(REPAIRABLE_CLASSES) < set(REPAIR_CLASSES)
    assert "resource" not in REPAIRABLE_CLASSES


def test_build_repair_prompt_carries_question_sql_and_error():
    p = build_repair_prompt("How many rows?", "SELEC 1", "syntax error")
    assert "How many rows?" in p
    assert "SELEC 1" in p
    assert "failed with this error" in p
    assert "syntax error" in p


# -------------------------------------------------------- repair loop


def _fail_times(n, exc=None):
    """execute() that raises `exc` for the first n calls, then returns a
    sentinel result."""
    exc = exc or InjectedSQLError("sql:syntax", 'near "FORM": syntax error')
    calls = []

    def execute(sql):
        calls.append(sql)
        if len(calls) <= n:
            raise exc
        return {"rows": 1, "sql": sql}

    execute.calls = calls
    return execute


def test_repaired_after_one_round(counters):
    execute = _fail_times(0)  # first re-execute succeeds
    regen = []

    def regenerate(error_text, failed_sql, remaining):
        regen.append((error_text, failed_sql, remaining))
        return "SELECT 1"

    first = InjectedSQLError("sql:syntax", 'near "FORM": syntax error')
    out = RepairEngine(max_rounds=2, backoff_s=0.0).run(
        first, "SELEC 1", execute=execute, regenerate=regenerate)
    assert out.ok and out.repaired and out.rounds == 1
    assert out.sql == "SELECT 1"
    assert out.result == {"rows": 1, "sql": "SELECT 1"}
    assert len(out.attempts) == 1
    assert out.attempts[0].error_class == "syntax"
    # The regenerate saw the ORIGINAL error + failed SQL.
    assert regen == [('near "FORM": syntax error', "SELEC 1", None)]
    assert counters.snapshot() == {"repair_rounds": 1, "repaired": 1}


def test_rounds_exhausted_is_typed_and_bounded(counters):
    always = InjectedSQLError("sql:syntax", 'near "FORM": syntax error')
    execute = _fail_times(99, exc=always)
    out = RepairEngine(max_rounds=2, backoff_s=0.0).run(
        always, "SELEC 1", execute=execute,
        regenerate=lambda e, s, r: "SELEC 1 AGAIN")
    assert not out.ok
    assert out.degraded == "rounds_exhausted"
    assert out.rounds == 2 and len(out.attempts) == 2
    assert out.error_class == "syntax"
    assert len(execute.calls) == 2  # one re-execute per round, no more
    snap = counters.snapshot()
    assert snap["repair_rounds"] == 2
    assert snap["unrepairable"] == 1 and snap["diagnosed_syntax"] == 1
    assert "repaired" not in snap


def test_resource_errors_degrade_immediately(counters):
    regen = []
    out = RepairEngine(max_rounds=2).run(
        Exception("out of memory"), "SELECT big",
        execute=lambda s: None,
        regenerate=lambda e, s, r: regen.append(1) or "x")
    assert not out.ok and out.degraded == "unrepairable"
    assert out.rounds == 0 and out.error_class == "resource"
    assert regen == []  # rewriting SQL cannot fix the engine's state
    assert counters.get("diagnosed_resource") == 1


def test_mid_loop_reclassify_to_unrepairable_stops(counters):
    """A repair round whose re-execute fails with a RESOURCE error must
    stop there — not burn the remaining rounds replaying it."""
    def execute(sql):
        raise MemoryError("out of memory")

    first = InjectedSQLError("sql:syntax", 'near "FORM": syntax error')
    out = RepairEngine(max_rounds=3, backoff_s=0.0).run(
        first, "SELEC 1", execute=execute, regenerate=lambda e, s, r: "S2")
    assert not out.ok and out.degraded == "unrepairable"
    assert out.rounds == 1 and out.error_class == "resource"


def test_max_rounds_zero_is_straight_diagnosis(counters):
    out = RepairEngine(max_rounds=0).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=lambda s: None, regenerate=lambda e, s, r: "x")
    assert not out.ok and out.degraded == "unrepairable" and out.rounds == 0


def test_open_breaker_skips_the_loop(counters):
    breaker = CircuitBreaker("sql repair", failure_threshold=1,
                             reset_after_s=60.0)
    breaker.record_failure()
    regen = []
    out = RepairEngine(max_rounds=2, breaker=breaker).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=lambda s: None,
        regenerate=lambda e, s, r: regen.append(1) or "x")
    assert not out.ok and out.degraded == "breaker_open"
    assert regen == []
    assert counters.get("breaker_skips") == 1


def test_typed_repair_generate_failure_counts_into_breaker(counters):
    """Overloaded/CircuitOpen from the repair generate degrade THIS
    request typed and, after the threshold, open the breaker so the next
    request skips straight to diagnosis."""
    breaker = CircuitBreaker("sql repair", failure_threshold=2,
                             reset_after_s=60.0)
    engine = RepairEngine(max_rounds=2, backoff_s=0.0, breaker=breaker)

    def shed(e, s, r):
        raise Overloaded("queue full")

    first = InjectedSQLError("sql:syntax", "syntax error")
    for _ in range(2):
        out = engine.run(first, "S", execute=lambda s: None, regenerate=shed)
        assert not out.ok and out.degraded == "repair_failed"
        assert out.rounds == 1
    out = engine.run(first, "S", execute=lambda s: None, regenerate=shed)
    assert out.degraded == "breaker_open"
    assert counters.get("breaker_skips") == 1


def test_expired_deadline_stops_before_regenerating(counters):
    expired = Deadline(time.monotonic() - 1.0)
    regen = []
    out = RepairEngine(max_rounds=2).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=lambda s: None,
        regenerate=lambda e, s, r: regen.append(1) or "x",
        deadline=expired)
    assert not out.ok and out.degraded == "deadline" and out.rounds == 0
    assert regen == []
    assert counters.get("deadline_stops") == 1


def test_remaining_deadline_is_threaded_to_regenerate(counters):
    deadline = Deadline.after(60.0)
    seen = []

    def regenerate(e, s, remaining):
        seen.append(remaining)
        return "SELECT 1"

    out = RepairEngine(max_rounds=2, backoff_s=0.0).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=_fail_times(0), regenerate=regenerate, deadline=deadline)
    assert out.ok
    assert len(seen) == 1 and 0 < seen[0] <= 60.0


def test_backoff_is_exponential_between_rounds(counters):
    sleeps = []
    always = InjectedSQLError("sql:syntax", "syntax error")
    RepairEngine(max_rounds=3, backoff_s=0.1,
                 sleep=sleeps.append).run(
        always, "S", execute=_fail_times(99, exc=always),
        regenerate=lambda e, s, r: "S2")
    # Round 1 fires immediately; rounds 2 and 3 wait b, 2b.
    assert sleeps == [0.1, 0.2]


def test_run_never_raises_on_arbitrary_exec_errors(counters):
    """The bounded-termination contract: whatever execute throws, the
    caller gets a typed outcome, not an escape."""
    out = RepairEngine(max_rounds=1, backoff_s=0.0).run(
        Exception("?"), "S",
        execute=_fail_times(99, exc=ValueError("no such column: x")),
        regenerate=lambda e, s, r: "S2")
    assert not out.ok and out.degraded == "rounds_exhausted"
    assert out.error_class == "schema"  # reclassified from the re-execute


# ---------------------------------------------------- metrics surfaces


def test_metrics_block_empty_until_loop_runs(counters):
    assert repair_metrics_block() == {}
    RepairEngine(max_rounds=1, backoff_s=0.0).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=_fail_times(0), regenerate=lambda e, s, r: "SELECT 1")
    block = repair_metrics_block()
    assert block["repaired"] == 1 and block["repair_rounds"] == 1
    assert isinstance(block["recent"], list) and block["recent"]


def test_prometheus_families_render_from_repair_block():
    from llm_based_apache_spark_optimization_tpu.utils.prometheus import (
        render_prometheus,
    )

    snap = {"repair": {
        "repair_rounds": 5, "repaired": 3, "unrepairable": 2,
        "breaker_skips": 1, "deadline_stops": 1,
        "diagnosed_syntax": 1, "diagnosed_resource": 1,
        "recent": [{"round": 1}],
    }}
    text = render_prometheus(snap)
    assert "lsot_repair_rounds_total 5" in text
    assert "lsot_repair_repaired_total 3" in text
    assert "lsot_repair_unrepairable_total 2" in text
    assert "lsot_repair_breaker_skips_total 1" in text
    assert "lsot_repair_deadline_stops_total 1" in text
    assert 'lsot_repair_errors_total{class="syntax"} 1' in text
    assert 'lsot_repair_errors_total{class="resource"} 1' in text
    # The reserved block never leaks as a bare lsot_repair gauge.
    assert "lsot_repair " not in text


def test_service_metrics_snapshot_carries_repair_block(counters):
    from llm_based_apache_spark_optimization_tpu.serve.backends import (
        FakeBackend,
    )
    from llm_based_apache_spark_optimization_tpu.serve.service import (
        GenerationService,
    )

    svc = GenerationService()
    svc.register("m", FakeBackend(lambda p: "x"))
    assert "repair" not in svc.metrics_snapshot()  # loop never ran
    RepairEngine(max_rounds=1, backoff_s=0.0).run(
        InjectedSQLError("sql:syntax", "syntax error"), "S",
        execute=_fail_times(0), regenerate=lambda e, s, r: "SELECT 1")
    snap = svc.metrics_snapshot()
    assert snap["repair"]["repaired"] == 1


# ------------------------------------------------------ pipeline wiring


BROKEN = "SELEC * FORM temp_view"
GOOD = "SELECT COUNT(*) FROM temp_view"
MARKER = "failed with this error"


def _pipeline(tmp_path, sql_fn, **cfg_overrides):
    from llm_based_apache_spark_optimization_tpu.app.config import AppConfig
    from llm_based_apache_spark_optimization_tpu.app.pipeline import Pipeline
    from llm_based_apache_spark_optimization_tpu.evalh.fixtures import (
        write_taxi_fixture_csv,
    )
    from llm_based_apache_spark_optimization_tpu.serve.backends import (
        FakeBackend,
    )
    from llm_based_apache_spark_optimization_tpu.serve.service import (
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.sql.sqlite_backend import (
        SQLiteBackend,
    )

    csv = str(tmp_path / "taxi.csv")
    write_taxi_fixture_csv(csv)
    (tmp_path / "out").mkdir(exist_ok=True)
    svc = GenerationService()
    sqlgen = FakeBackend(sql_fn)
    svc.register("duckdb-nsql", sqlgen)
    svc.register("llama3.2", FakeBackend(lambda p: "Check the schema."))
    cfg_kw = dict(repair_backoff_s=0.0, output_dir=str(tmp_path / "out"),
                  history_db=":memory:")
    cfg_kw.update(cfg_overrides)
    pipe = Pipeline(svc, SQLiteBackend, None, AppConfig(**cfg_kw))
    return pipe, csv, svc, sqlgen


def test_pipeline_repairs_broken_sql(tmp_path, counters):
    from llm_based_apache_spark_optimization_tpu.app.pipeline import (
        ST_GEN_OK,
        ST_REPAIR,
    )

    pipe, csv, _, sqlgen = _pipeline(
        tmp_path, lambda p: GOOD if MARKER in p else BROKEN)
    statuses = []
    res = pipe.run(csv, "How many rows are there?",
                   status=lambda s, m: statuses.append(m))
    assert res.ok and res.sql_query == GOOD
    assert res.output_file
    assert statuses.count(ST_GEN_OK) == 2  # initial + repaired
    assert ST_REPAIR in statuses
    assert len(sqlgen.calls) == 2
    # The repair prompt rides the ORIGINAL system prompt + question.
    assert "How many rows are there?" in sqlgen.calls[1]
    assert MARKER in sqlgen.calls[1]


def test_pipeline_repair_off_is_the_pre_repair_path(tmp_path, counters):
    from llm_based_apache_spark_optimization_tpu.app.pipeline import ST_REPAIR

    pipe, csv, _, sqlgen = _pipeline(
        tmp_path, lambda p: GOOD if MARKER in p else BROKEN, repair=False)
    statuses = []
    res = pipe.run(csv, "How many rows are there?",
                   status=lambda s, m: statuses.append(m))
    assert not res.ok
    assert res.sql_query == BROKEN
    assert "syntax error" in res.error_message
    assert res.error_solution == "Check the schema."
    assert ST_REPAIR not in statuses
    assert len(sqlgen.calls) == 1  # no repair generate
    assert counters.snapshot() == {}  # zero counter movement


def test_pipeline_repair_rides_replay_qos_under_tenant(tmp_path, counters):
    pipe, csv, svc, _ = _pipeline(
        tmp_path, lambda p: GOOD if MARKER in p else BROKEN)
    seen = []
    inner = svc.generate

    def spy(model, prompt, **kw):
        seen.append((kw.get("tenant"), kw.get("qos")))
        return inner(model, prompt, **kw)

    svc.generate = spy
    res = pipe.run(csv, "How many rows are there?", tenant="acme")
    assert res.ok
    # initial generate: tenant threaded, default class; repair round:
    # same tenant, the replay backfill class.
    assert seen[0] == ("acme", None)
    assert seen[1] == ("acme", "replay")


def test_pipeline_unregistered_repair_model_falls_back(tmp_path, counters,
                                                       caplog):
    pipe, csv, _, sqlgen = _pipeline(
        tmp_path, lambda p: GOOD if MARKER in p else BROKEN,
        repair_model="not-registered")
    with caplog.at_level("WARNING", logger="lsot.pipeline"):
        res = pipe.run(csv, "How many rows are there?")
    assert res.ok and res.sql_query == GOOD
    assert len(sqlgen.calls) == 2  # repaired via the SQL model
    assert any("not registered" in r.message for r in caplog.records)


# ----------------------------------------------- tenant model routing


def test_parse_tenant_models():
    from llm_based_apache_spark_optimization_tpu.serve.qos import (
        parse_tenant_models,
    )

    assert parse_tenant_models("") == {}
    assert parse_tenant_models("a=m1,b=m2") == {"a": "m1", "b": "m2"}
    assert parse_tenant_models(" a = m1 , b = m2 ") == {"a": "m1", "b": "m2"}
    # Malformed fragments are dropped, not fatal.
    assert parse_tenant_models("a=,=m,noequals,b=m2") == {"b": "m2"}


def test_tenant_model_routing_resolves_and_falls_through():
    from llm_based_apache_spark_optimization_tpu.serve.backends import (
        FakeBackend,
    )
    from llm_based_apache_spark_optimization_tpu.serve.service import (
        GenerationService,
    )

    a, b = FakeBackend(lambda p: "A"), FakeBackend(lambda p: "B")
    svc = GenerationService()
    svc.register("model-a", a)
    svc.register("model-b", b)
    svc.set_tenant_models("acme=model-b,ghost=no-such-model")

    assert svc.resolve_model("model-a", "") == "model-a"
    assert svc.resolve_model("model-a", "unlisted") == "model-a"
    assert svc.resolve_model("model-a", "acme") == "model-b"
    # Pinned-but-unregistered falls through to the request's own model.
    assert svc.resolve_model("model-a", "ghost") == "model-a"

    # End to end: the pinned tenant's generate lands on model-b.
    res = svc.generate("model-a", "hi", tenant="acme")
    assert res.response == "B"
    assert len(b.calls) == 1 and a.calls == []
    res = svc.generate("model-a", "hi", tenant="other")
    assert res.response == "A"


# ------------------------------------------------- evalh repair leg


def test_evalh_repair_leg_injected_k2_beats_one_shot(counters):
    """The acceptance gate: on the injected suite, executable% after
    k=2 strictly exceeds one-shot (0% by construction)."""
    from llm_based_apache_spark_optimization_tpu.app.__main__ import (
        make_oracle_service,
    )
    from llm_based_apache_spark_optimization_tpu.evalh.repair import (
        run_repair_leg,
    )
    from llm_based_apache_spark_optimization_tpu.evalh.spider import (
        SPIDER_SMOKE,
    )

    svc = make_oracle_service()
    model = svc.models()[0]
    cases = SPIDER_SMOKE[:6]
    injected = run_repair_leg(svc, model, cases=cases, max_rounds=2,
                              inject=True)
    assert injected["suite"] == "injected"
    assert injected["executable_after"][0] == 0.0
    assert injected["executable_after"][2] > injected["executable_after"][0]
    assert injected["executable_after"][2] == 1.0

    clean = run_repair_leg(svc, model, cases=cases, max_rounds=2,
                           inject=False)
    assert clean["suite"] == "clean"
    assert clean["executable_after"][0] == 1.0  # oracle SQL executes


def test_evalh_repair_summary_formats(counters):
    from llm_based_apache_spark_optimization_tpu.evalh.repair import (
        format_repair_summary,
    )

    text = format_repair_summary({
        "suite": "injected", "model": "m", "cases": 3, "max_rounds": 2,
        "executable_after": {0: 0.0, 1: 2 / 3, 2: 2 / 3},
        "per_case": [{"success_round": None, "error_class": "syntax",
                      "nl": "q", "sql": "s", "error": "e"}],
    })
    assert "one-shot" in text and "0.0%" in text
    assert "unrepairable: 1" in text
