"""Unified ragged prefill+decode rounds (ISSUE 19, LSOT_RAGGED).

The tentpole contract, executable:

- LSOT_RAGGED=0 (the default) keeps the ALTERNATING scheduler
  bit-for-bit: its flight records carry no mixed-round keys and every
  ledger column recomputes through `round_attribution` exactly as
  before (the rest of the tier-1 suite pins its tokens against the
  engine golden, unchanged).
- LSOT_RAGGED=1 is token-identical to that control across
  greedy/sampled/constrained/speculative on mixed prefill+decode
  batches — per-request RNG streams and grammar FSMs ride per-row, so
  folding prompt chunks into the decode launch moves round BOUNDARIES
  but never a request's tokens.
- Mixed rounds ledger through `PerfModel.mixed_attribution` (both
  phases' analytic work over one wall) and their records carry the
  chunk-side inputs needed to recompute it.

All on the TINY config, CPU f32, paged KV (ragged requires the page
tables — prefill rows scatter their chunks through them).
"""

import pytest

from llm_based_apache_spark_optimization_tpu.ops.sampling import SamplingParams
from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
)

PROMPTS = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 10], [1, 11, 12, 13]]


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import (
        TINY,
        init_params,
    )

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def make_sched(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prompt_bucket", 8)
    kw.setdefault("stop_ids", (-1,))
    kw.setdefault("kv_page_size", 16)
    return ContinuousBatchingScheduler(cfg, params, **kw)


# ------------------------------------------------------------ construction


def test_ragged_requires_paged_mixed(tiny):
    cfg, params = tiny
    # The removed layout is refused by name before ragged is looked at;
    # with no layout argument ragged rounds build on the page pool.
    with pytest.raises(ValueError, match="contiguous KV layout was removed"):
        ContinuousBatchingScheduler(
            cfg, params, num_slots=2, ragged=True, kv_layout="contiguous"
        )
    assert ContinuousBatchingScheduler(
        cfg, params, num_slots=2, ragged=True)._ragged
    with pytest.raises(ValueError, match="mixed"):
        make_sched(cfg, params, ragged=True, phase_role="prefill")


def test_ragged_env_knob(tiny, monkeypatch):
    cfg, params = tiny
    monkeypatch.setenv("LSOT_RAGGED", "1")
    with make_sched(cfg, params) as s:
        assert s._ragged
    # A phase-split replica: the env knob silently stays off (explicit
    # ragged=True raises instead — tested above).
    with make_sched(cfg, params, phase_role="decode") as s:
        assert not s._ragged
    monkeypatch.delenv("LSOT_RAGGED")
    with make_sched(cfg, params) as s:
        assert not s._ragged


# ------------------------------------------------------------ token parity


def _run(cfg, params, ragged, *, spec=0, sampled=False, prompts=None,
         max_new=6):
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import init_params

    # Fresh params per run: the scheduler donates them into jit buffers.
    p = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    kw = {"ragged": ragged}
    if spec:
        kw["speculative_draft"] = spec
    prompts = prompts if prompts is not None else PROMPTS * 3
    with make_sched(cfg, p, **kw) as s:
        if sampled:
            futs = [
                s.submit(pr, max_new_tokens=max_new, seed=42 + i,
                         sampling=SamplingParams(temperature=0.9,
                                                 top_p=0.9))
                for i, pr in enumerate(prompts)
            ]
            return [f.result(timeout=300) for f in futs]
        futs = [s.submit(pr, max_new_tokens=max_new) for pr in prompts]
        return [f.result(timeout=300) for f in futs]


def test_ragged_greedy_parity(tiny):
    """12 requests through 2 slots: admissions force prompt chunks into
    live decode rounds — the mixed launch's bread and butter."""
    cfg, params = tiny
    assert _run(cfg, params, True) == _run(cfg, params, False)


def test_ragged_sampled_parity(tiny):
    cfg, params = tiny
    assert _run(cfg, params, True, sampled=True) == \
        _run(cfg, params, False, sampled=True)


def test_ragged_speculative_parity(tiny):
    cfg, params = tiny
    assert _run(cfg, params, True, spec=3) == _run(cfg, params, False,
                                                   spec=3)
    assert _run(cfg, params, True, spec=3, sampled=True) == \
        _run(cfg, params, False, spec=3, sampled=True)


def test_ragged_constrained_spec_parity(tiny):
    """Mixed constrained/unconstrained + speculative batch, ragged vs
    alternating — the full acceptance matrix in one fixture."""
    from llm_based_apache_spark_optimization_tpu.constrain import (
        get_constraint,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import init_params

    cfg, _ = tiny
    tok = ByteTokenizer()
    cm = get_constraint("spark_sql", tok, (2,))
    budget = max(30, cm.min_new_tokens)
    reqs = [
        ([1, 5, 9], None, 8),
        (tok.encode("SELECT", add_bos=True), cm, budget),
        ([1, 3, 4, 8, 10, 11, 12, 13, 14], None, 8),
        (tok.encode("SELECT c", add_bos=True), cm, budget),
    ]

    def run(ragged):
        p = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
        with ContinuousBatchingScheduler(
            cfg, p, num_slots=3, decode_chunk=4, prompt_bucket=8,
            stop_ids=(2,), speculative_draft=3,
            kv_page_size=16, ragged=ragged,
        ) as s:
            futs = [s.submit(ids, max_new_tokens=mn, constraint=c)
                    for ids, c, mn in reqs]
            return [f.result(timeout=300) for f in futs]

    assert run(True) == run(False)


# --------------------------------------------------------- flight records


def test_ragged_off_records_stay_alternating(tiny):
    """The control's flight records are untouched by this PR: no
    mixed-round keys, phases are the alternating pair, and every ledger
    column still recomputes through round_attribution."""
    cfg, params = tiny
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import init_params

    p = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    sched = make_sched(cfg, p, ragged=False)
    with sched:
        sched.generate(PROMPTS, max_new_tokens=6)
    recs = [r for r in sched.flight.snapshot() if "mfu" in r]
    assert recs
    for rec in recs:
        assert rec["phase"] in ("decode", "verify")
        assert "pre_rows" not in rec and "pre_tokens" not in rec
        att = sched.perf.round_attribution(
            rec["phase"], rows=sched.num_slots,
            tokens=sched.decode_chunk, ctx=rec["perf_ctx"],
            wall_s=rec["round_wall_s"],
        )
        assert rec["mfu"] == att["mfu"], rec
        assert rec["bound"] == att["bound"], rec
    assert "mixed" not in sched.perf_stats["phases"]


def test_ragged_mixed_records_reconcile(tiny):
    """Ragged rounds ledger as phase 'mixed' and recompute EXACTLY
    through PerfModel.mixed_attribution from the record's own fields —
    the live ledger stays the analytic model evaluated live."""
    cfg, params = tiny
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import init_params

    p = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    sched = make_sched(cfg, p, ragged=True)
    with sched:
        sched.generate(PROMPTS * 3, max_new_tokens=6)
    recs = [r for r in sched.flight.snapshot() if "mfu" in r]
    mixed = [r for r in recs if r["phase"] == "mixed"]
    assert mixed, "no mixed rounds harvested under LSOT_RAGGED=1"
    for rec in mixed:
        assert rec["pre_rows"] >= 1
        att = sched.perf.mixed_attribution(
            rows=sched.num_slots, dec_tokens=sched.decode_chunk,
            dec_ctx=rec["perf_ctx"], pre_rows=rec["pre_rows"],
            pre_tokens=rec["pre_tokens"], pre_ctx=rec["pre_ctx"],
            wall_s=rec["round_wall_s"],
        )
        assert rec["mfu"] == att["mfu"], rec
        assert rec["hbm_util"] == att["hbm_util"], rec
        assert rec["bound"] == att["bound"], rec
    assert sched.perf_stats["phases"]["mixed"]["rounds"] == len(mixed)
