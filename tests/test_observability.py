"""Metrics/timing observability: registry aggregates and endpoint surface."""

import pytest

import json

from llm_based_apache_spark_optimization_tpu.utils.observability import (
    MetricsRegistry,
    RequestMetrics,
    StageTimer,
)


def test_stage_timer_accumulates():
    t = StageTimer()
    with t.stage("prefill"):
        pass
    with t.stage("decode"):
        pass
    with t.stage("decode"):
        pass
    spans = t.spans
    assert set(spans) == {"prefill", "decode"}
    assert all(v >= 0 for v in spans.values())


def test_registry_aggregates():
    reg = MetricsRegistry()
    for i in range(10):
        reg.record(RequestMetrics(
            model="duckdb-nsql", prompt_tokens=50, output_tokens=20,
            latency_s=0.1 * (i + 1),
        ))
    snap = reg.snapshot()["duckdb-nsql"]
    assert snap["requests"] == 10
    assert snap["output_tokens"] == 200
    assert 0.4 <= snap["p50_latency_s"] <= 0.7
    assert snap["p95_latency_s"] >= snap["p50_latency_s"]
    assert snap["avg_decode_tok_s"] > 0


def test_registry_window_bounds_memory():
    reg = MetricsRegistry(window=4)
    for i in range(20):
        reg.record(RequestMetrics("m", 1, 1, 0.01))
    assert reg.snapshot()["m"]["requests"] == 20
    assert len(reg._recent["m"]) == 4


def test_decode_tok_s_prefers_decode_stage():
    m = RequestMetrics("m", 10, 30, latency_s=3.0, stages={"decode": 1.5})
    assert m.decode_tok_s == 20.0
    m2 = RequestMetrics("m", 10, 30, latency_s=3.0)
    assert m2.decode_tok_s == 10.0


def test_service_records_metrics():
    from llm_based_apache_spark_optimization_tpu.serve import (
        FakeBackend,
        GenerationService,
    )

    svc = GenerationService()
    svc.register("m", FakeBackend(lambda p: "SELECT 1"))
    svc.generate("m", "question", system="schema")
    snap = svc.metrics.snapshot()
    assert snap["m"]["requests"] == 1
    assert json.dumps(snap)  # JSON-serializable for the /metrics endpoint


def test_metrics_endpoint():
    from llm_based_apache_spark_optimization_tpu.app.api import create_api_app
    from llm_based_apache_spark_optimization_tpu.app.config import AppConfig
    from llm_based_apache_spark_optimization_tpu.history import SQLiteHistory
    from llm_based_apache_spark_optimization_tpu.serve import (
        FakeBackend,
        GenerationService,
    )
    from llm_based_apache_spark_optimization_tpu.sql import default_backend

    svc = GenerationService()
    svc.register("duckdb-nsql", FakeBackend(lambda p: "SELECT 1"))
    svc.register("llama3.2", FakeBackend(lambda p: "fix it"))
    cfg = AppConfig(history_db=":memory:")
    app = create_api_app(svc, default_backend, SQLiteHistory(":memory:"), cfg)
    client = app.test_client()
    res = client.request("GET", "/metrics")
    assert res.status == 200
    body = json.loads(res.body)
    # The reserved "resilience"/"qos"/"repair" keys carry PROCESS-GLOBAL
    # counters (serve/resilience.py, serve/qos.ADMISSION,
    # utils/observability.repair) — other tests in the same process may
    # legitimately have moved them; per-model metrics must still be empty.
    for reserved in ("resilience", "qos", "repair"):
        body.pop(reserved, None)
    assert body == {}
    svc.generate("duckdb-nsql", "q")
    res = client.request("GET", "/metrics")
    assert json.loads(res.body)["duckdb-nsql"]["requests"] == 1


@pytest.mark.slow
def test_device_trace_reads_real_op_time():
    """traceprof parses jax.profiler's chrome trace into device-op time:
    a matmul loop's device_time_s must be positive, bounded by wall, and
    the hot op list non-empty."""
    import time

    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.utils.traceprof import (
        device_trace,
    )

    x = jnp.ones((512, 512), jnp.float32)

    @jax.jit
    def step(a):
        for _ in range(8):
            a = a @ a / 512.0
        return a

    step(x).block_until_ready()  # compile outside the trace
    t0 = time.perf_counter()
    with device_trace() as tr:
        step(x).block_until_ready()
    wall = time.perf_counter() - t0
    assert tr.op_time_s() > 0.0
    assert 0.0 < tr.device_time_s() <= wall + 0.5
    assert tr.top_ops(3) and tr.top_ops(3)[0][1] > 0.0
