"""Incremental benchmark: aggregate output tok/s of the in-tree engine.

Prints one JSON line per completed stage on stdout — each line is a COMPLETE,
self-contained artifact (a superset of the previous one), so the driver's
"take the last JSON line" capture always gets the richest result that
finished, even if the process is killed mid-run (an all-or-nothing design
prints nothing until every sub-benchmark finished, and one stuck stage then
nulls the whole artifact).

Structure:
  1. The parent process NEVER imports jax. A chip belongs to one process
     at a time: a parent that had touched jax would hold it, and the legs —
     each a subprocess of its own, run one after another — would fail or
     hang. The parent runs the CORE leg once, with a hard timeout. A
     measurement path that finds no chip fails: the leg refuses any
     platform but `tpu` (BENCH_FORCE_CPU=1 asks for the CPU lane the tests
     use, whose numbers say what the program counts, never a device time),
     and the parent then exits non-zero without printing an artifact.
  2. Each optional leg (int8 / scheduler / long-context / 7b / 7b_sched)
     then runs as its OWN subprocess with its OWN timeout slice; after each
     one the merged artifact is re-emitted. A leg that hangs or dies burns
     only its slice and is recorded in the "legs" status map — the
     already-emitted numbers survive.
  3. The core leg itself emits its primary measurement BEFORE the detail
     pass, so even a mid-detail kill leaves a headline number.

What it measures: batched greedy decode throughput (output tokens/second,
summed over the batch) for an NL→SQL-shaped workload — a schema-sized prompt
prefill followed by a SQL-sized completion. The detail breakdown (prefill vs
decode split, decode MFU vs the chip's peak, HBM bandwidth utilization —
decode is weight+cache streaming bound) rides the core leg; the optional
legs fold into the same JSON line:
  "int8":         int8 weight-only quant at B=8 (speedup vs the bf16
                  primary, decode-only split, and a trace-parsed per-op
                  account of where the decode device time goes) and B=32
  "scheduler":    continuous-batching scheduler driven by 4×slots
                  concurrent submitter threads — the serving path's number
                  (the component that replaces Ollama's queue; reference
                  serializes requests, `FastAPI/app.py:85-90`)
  "long_context": B=16 prompt=1024 — the shape where KV-cache bytes rival
                  weight bytes — stacking int8 weights and the int8 KV cache
  "7b":           the FLAGSHIP shape — duckdb-nsql-7b (Llama-2-7B arch),
                  int8 weights + int8 KV on one chip, B=8 and B=32: the
                  BASELINE north star is denominated in this model class
  "7b_sched":     the flagship shape through the continuous-batching
                  scheduler (BASELINE config 4 is "duckdb-nsql-7B batch=32
                  Spider TP=4" — serving-path tok/s + TTFT at 7B, not just
                  the engine loop; VERDICT r4 next #7)
(BENCH_INT8=0 / BENCH_SCHED=0 / BENCH_LONG=0 / BENCH_7B=0 / BENCH_7B_SCHED=0
skip them; they default off on the CPU lane, where their compile+run
time would blow the watchdog budget.)

Baseline derivation (BASELINE.md): the reference's best model (DuckDB-NSQL via
Ollama) averages 8.05 s per NL→SQL query over its four-query suite for
completions of roughly 50 tokens — an effective ~6.2 output tok/s, single
request, CPU-class Ollama (measuring instrument:
reference `Model_Evaluation_&_Comparision.py:42-44`). vs_baseline = value/6.2.

Weights are random (no checkpoint assets in this environment) — throughput is
architecture+shape-bound, not weight-bound, so random weights measure the same
thing the loaded model would.

Knobs (env): BENCH_CONFIG (model registry name, default bench-1b), BENCH_BATCH,
BENCH_PROMPT, BENCH_NEW (auto-clamped to the config's max_seq_len),
BENCH_QUANT=int8|int4 (int4: packed-nibble weights through the pallas
int4 matmul kernel), BENCH_FUSE=1 (fused wqkv/wgu A/B), BENCH_7B_BITS=4|8,
BENCH_REPS, BENCH_DETAIL=1, BENCH_FORCE_CPU=1, BENCH_CORE_TIMEOUT /
BENCH_LEG_TIMEOUT_<LEG> (s), BENCH_SPEC_CONSTRAIN=0 (skip the constrained
speculative pass).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REFERENCE_TOKS_PER_S = 6.2  # 50-token SQL / 8.05 s avg latency (BASELINE.md)

# Peak specs by TPU generation for MFU / bandwidth accounting: moved
# IN-TREE (ISSUE 12) to utils/perfmodel.py — the live scheduler's
# per-round roofline ledger and this bench price with the SAME table and
# the SAME FLOP/byte models, so the two can never disagree (a tier-1
# reconciliation test pins it). Re-exported here for artifact diffing.
from llm_based_apache_spark_optimization_tpu.utils.perfmodel import (  # noqa: E402
    PEAKS,
    peak_for,
)


#: Every _emit'd artifact line, in order (last = richest). The --compare
#: gate reads the final line after a fresh run.
_EMITTED: "list[dict]" = []


def _emit(obj: dict) -> None:
    _EMITTED.append(obj)
    print(json.dumps(obj), flush=True)


def _last_json(text: str) -> dict | None:
    """Last parseable JSON-object line of a (possibly truncated) stdout."""
    for ln in reversed((text or "").splitlines()):
        ln = ln.strip()
        if ln.startswith("{"):
            try:
                obj = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict):
                return obj
    return None


def _load_artifact(path: str) -> dict | None:
    """Load a committed BENCH artifact in either on-disk shape: the
    bench's own stdout JSONL (last line = richest), or the CI capture
    wrapper that pretty-prints `{"n", "cmd", "rc", "tail", "parsed"}`
    with the artifact under "parsed" (BENCH_r01..r05's shape — a
    multi-line document the line-oriented _last_json cannot see into)."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = _last_json(text)
    if not isinstance(obj, dict):
        return None
    if isinstance(obj.get("parsed"), dict):
        return obj["parsed"]
    if "parsed" in obj and "tail" in obj:
        # Wrapper whose parse failed at capture time (parsed: null) —
        # salvage from the tail.
        return _last_json(obj.get("tail") or "")
    return obj


# --------------------------------------------------------------------------
# Outer orchestration: core leg with retries, then per-leg subprocesses
# --------------------------------------------------------------------------

# (leg id, result key, enable env var, default timeout slice in seconds).
# Slices are sized for a healthy v5e run (compiles included); a leg that
# hangs burns one slice, not the round.
_LEGS = (
    ("int8", "int8", "BENCH_INT8", 360),
    ("sched", "scheduler", "BENCH_SCHED", 700),
    ("long", "long_context", "BENCH_LONG", 420),
    ("7b", "7b", "BENCH_7B", 780),
    ("int4", "int4", "BENCH_INT4", 420),
    ("7b4", "7b_int4", "BENCH_7B4", 600),
    ("7b_sched", "7b_sched", "BENCH_7B_SCHED", 780),
    ("fuse", "fused", "BENCH_FUSED", 600),
    # Kernel-level microbench lane (paged-attention read, fused page
    # write vs XLA scatter, mask gather — ns/op per leg).
    ("micro", "kernels", "BENCH_MICRO", 300),
    # Multi-model routing (ISSUE 16): two co-resident tiny checkpoints
    # in ONE model-routing pool under concurrent mixed traffic. Its
    # tok_s keys enter the --compare gate like every other leg's.
    ("multi_model", "multi_model", "BENCH_MULTI_MODEL", 420),
)


def _run_sub(leg: str, timeout_s: int, extra_env: dict) -> tuple[dict | None, str]:
    """Run one inner leg as a subprocess; return (last JSON line, error).

    Per-leg watchdog: the leg runs in its OWN process group and a hung
    leg gets the whole group SIGKILLed at timeout — subprocess.run's
    kill only reaches the direct child, so a leg that spawned helpers
    (a scheduler pool's worker, a wedged compile) used to hold the
    stdout pipe open and wedge the OUTER process until CI's `timeout`
    killed the whole run rc=124, losing every completed leg's numbers.
    Now the watchdog fires, the partial artifact is salvaged from
    whatever the leg printed, and the caller records the leg as
    `timed_out` in the BENCH JSON instead of the round dying."""
    env = dict(os.environ)
    env["BENCH_INNER"] = "1"
    env["BENCH_LEG"] = leg
    env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    try:
        stdout, stderr = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        import signal

        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            stdout, stderr = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover - pipe wedge
            stdout, stderr = "", ""
        sys.stderr.write((stderr or "")[-4000:])
        # The core leg flushes its primary line early for exactly this
        # case — salvage it.
        return _last_json(stdout or ""), f"timed_out after {timeout_s}s"
    sys.stderr.write((stderr or "")[-4000:])
    parsed = _last_json(stdout)
    if proc.returncode != 0:
        tail = (stderr or "").strip().splitlines()
        return parsed, f"rc={proc.returncode}: " + (tail[-1][-300:] if tail else "no stderr")
    if parsed is None:
        return None, f"printed no JSON: {(stdout or '')[:200]!r}"
    return parsed, ""


def outer() -> int:
    on_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    core_timeout = int(os.environ.get("BENCH_CORE_TIMEOUT", "700"))
    print(f"bench[outer]: core leg (timeout {core_timeout}s)",
          file=sys.stderr)
    result, err = _run_sub("core", core_timeout, {})
    if result is None or "value" not in result:
        # No chip, no number: nothing is printed on stdout, and the exit
        # code says the measurement did not happen.
        print(f"bench[outer]: core leg failed: "
              f"{err or 'no parseable output'}", file=sys.stderr)
        return 1
    if err:
        # Partial core (e.g. killed mid-detail): keep the headline.
        result.setdefault("legs", {})["core"] = f"partial: {err}"

    _emit(result)  # first flush: the core artifact stands on its own

    # Focused primary modes measure ONE variant; their legs would silently
    # re-quantize/reshape the wrong tree (see inner_core notes), so skip.
    focused = (os.environ.get("BENCH_QUANT")
               or os.environ.get("BENCH_FUSE") == "1"
               or os.environ.get("BENCH_UNEMBED8") == "1")
    if focused:
        # Say so loudly: BENCH_FUSE=1 (focused primary) is one character
        # from BENCH_FUSED=1 (the fused A/B leg) and silently skipping all
        # legs would look like a bug to someone who meant the latter.
        print("bench[outer]: focused primary mode "
              "(BENCH_QUANT/BENCH_FUSE/BENCH_UNEMBED8) — default-on legs "
              "skipped (explicitly enabled ones still run); the fused A/B "
              "*leg* is BENCH_FUSED=1", file=sys.stderr)
    legs_status = result.setdefault("legs", {})
    for leg, key, env_var, default_to in _LEGS:
        want = os.environ.get(env_var)
        if want == "0" or (want is None and (on_cpu or focused)):
            continue
        timeout_s = int(os.environ.get(f"BENCH_LEG_TIMEOUT_{leg.upper()}",
                                       str(default_to)))
        print(f"bench[outer]: leg {leg} (timeout {timeout_s}s)",
              file=sys.stderr)
        extra = {"BENCH_PRIMARY_TOKS": str(result.get("value", 0.0)),
                 "BENCH_PRIMARY_PREFILL": str(result.get("prefill_s", 0.0))}
        t0 = time.time()
        parsed, err = _run_sub(leg, timeout_s, extra)
        timed_out = err.startswith("timed_out")
        if parsed is not None and key in parsed:
            result[key] = parsed[key]
            # A timed-out leg that still printed its result dict keeps
            # the numbers but is MARKED: a partial measurement must not
            # read as a clean one in the committed artifact.
            legs_status[leg] = (f"timed_out after {timeout_s}s (partial)"
                                if timed_out
                                else f"ok ({time.time() - t0:.0f}s)")
        else:
            legs_status[leg] = err or "no result"
        _emit(result)  # re-flush after every leg: last line = richest
    return 0


# --------------------------------------------------------------------------
# Inner measurement (BENCH_INNER=1; BENCH_LEG picks the stage)
# --------------------------------------------------------------------------

def _peak_for(device_kind: str, quant: str):
    """Bench-side peak lookup over the shared in-tree table. On the CPU
    lane it returns (None, None): an artifact omits utilization figures
    there rather than print the table's nominal host row under a device
    metric's name. A TPU kind the table lacks raises (perfmodel)."""
    if "cpu" in device_kind.lower():
        return None, None
    return peak_for(device_kind, quant)


def _param_bytes(params) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(params))


def _paged_accounting(cfg, *, slots_rows, max_seq, max_new,
                      overshoot, mix_lens, page_size=64, itemsize=2,
                      prompt_bucket=128, kv_quant=None):
    """Slots-at-fixed-HBM: how many concurrent requests of a mixed-length
    traffic sample the page pool admits inside the HBM that
    `slots_rows` worst-case rows of max_seq tokens take. Pure host math
    over the same sizing functions the scheduler allocates with
    (engine/kvcache.cache_bytes, engine/paged_kv.page_bytes), so the
    artifact's numbers reconcile by construction — a tier-1 test asserts
    it (tests/test_bench.py): pages_used never exceeds pages_total, and
    `next_request_pages` records exactly why admission stopped (no silent
    cap)."""
    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        bucket_len,
        cache_bytes,
    )
    from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
        page_bytes,
        pages_for_tokens,
    )

    # kv_quant prices the pool's KV dtype (engine/paged_kv.page_bytes):
    # an int8 pool's pages cost ~half a compute-dtype page, so the SAME
    # bf16 worst-case-rows HBM budget buys ~2x the pages — the slots-at-fixed-
    # HBM lever ISSUE 11 ships (int8 strictly more slots than bf16,
    # asserted by the tier-1 reconciliation test).
    budget = cache_bytes(cfg, slots_rows, max_seq, itemsize)
    pages_total = budget // page_bytes(cfg, page_size, itemsize, kv_quant)
    needs = []
    for ln in mix_lens:
        need_tokens = bucket_len(ln, prompt_bucket) + max_new + overshoot
        if need_tokens > max_seq - 1:
            # The real scheduler's submit() rejects this envelope (the
            # last cache slot is the parking spot) — counting it as an
            # admitted paged slot would fabricate concurrency the system
            # cannot serve. Loud failure beats a silently-wrong artifact.
            raise ValueError(
                f"mix length {ln}: envelope {need_tokens} tokens exceeds "
                f"max_seq-1={max_seq - 1} — this request is unservable at "
                f"this window, fix the mix or max_seq"
            )
        needs.append(pages_for_tokens(need_tokens, page_size))
    used, admitted, i = 0, [], 0
    next_request_pages = 0
    while True:
        need = needs[i % len(needs)]
        if used + need > pages_total:
            next_request_pages = need
            break
        used += need
        admitted.append(need)
        i += 1
    return {
        "page_size": page_size,
        "hbm_budget_bytes": budget,
        "pages_total": pages_total,
        "slots_rows": slots_rows,
        "slots_paged": len(admitted),
        "pages_used": used,
        "pages_per_request": admitted,
        "next_request_pages": next_request_pages,
        "mix_lens": list(mix_lens),
        "max_new": max_new,
        "overshoot": overshoot,
        "prompt_bucket": prompt_bucket,
        "max_seq": max_seq,
        "kv_quant": kv_quant or "",
        "slots_ratio": (round(len(admitted) / slots_rows, 2)
                        if slots_rows else 0.0),
    }


def _mk_prompts(cfg, n, length, rng):
    """Random NL->SQL-shaped prompts (one definition: the workload's token
    distribution must be identical across every sub-benchmark)."""
    return [
        [int(x) for x in rng.integers(3, cfg.vocab_size, size=length)]
        for _ in range(n)
    ]


def _workload(cfg):
    """Shared workload shape so every leg measures the same distribution.

    Clamped to the model's context: prompt to half the context (the
    engine's own bucket cap), completion to the room left. Round-1 bug:
    BENCH_CONFIG=tiny crashed because 128+64 > tiny's 128."""
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt_len = min(int(os.environ.get("BENCH_PROMPT", "128")),
                     cfg.max_seq_len // 2)
    max_new = min(int(os.environ.get("BENCH_NEW", "64")),
                  cfg.max_seq_len - prompt_len)
    return batch, prompt_len, max_new


def _setup_jax():
    """Platform and compile cache for an inner leg. A leg measures a TPU or
    nothing: BENCH_FORCE_CPU=1 is the CPU lane the tests use (counts and
    control flow, never a device time); without it any other platform is
    refused — there is no fallback that hides the device."""
    from llm_based_apache_spark_optimization_tpu.utils.jaxenv import (
        force_cpu,
        place_compile_cache,
    )

    forced = os.environ.get("BENCH_FORCE_CPU") == "1"
    if forced:
        force_cpu()
    place_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not forced:
        sys.exit(f"bench: JAX found platform {platform!r}, not a TPU; a "
                 f"benchmark leg does not fall back (BENCH_FORCE_CPU=1 "
                 f"runs the CPU lane the tests use)")
    return jax


def inner() -> int:
    leg = os.environ.get("BENCH_LEG", "core")
    if leg == "core":
        return inner_core()
    return inner_leg(leg)


def inner_leg(leg: str) -> int:
    jax = _setup_jax()
    import jax.numpy as jnp  # noqa: F401

    from llm_based_apache_spark_optimization_tpu.models import REGISTRY, init_params

    dev = jax.devices()[0]
    device_kind = dev.device_kind
    if leg == "7b":
        _emit({"7b": _bench_7b(device_kind, dev)})
        return 0
    if leg == "7b4":
        # The 4-bit bandwidth story at the FLAGSHIP shape (VERDICT r4 next
        # #3): the 7b leg with the packed-nibble tree through the compiled
        # pallas kernel; B=8 only — the leg exists to prove the compiled
        # kernel + its bandwidth, not to re-sweep batch sizes.
        os.environ["BENCH_7B_BITS"] = "4"
        os.environ.setdefault("BENCH_7B_BATCH2", "0")
        _emit({"7b_int4": _bench_7b(device_kind, dev)})
        return 0
    if leg == "7b_sched":
        _emit({"7b_sched": _bench_7b_sched(device_kind)})
        return 0
    if leg == "micro":
        # Needs no params tree — pure kernel shapes.
        _emit({"kernels": _bench_micro(device_kind)})
        return 0
    if leg == "multi_model":
        # Builds its own two-checkpoint fleet — no shared params tree.
        _emit({"multi_model": _bench_multi_model(device_kind)})
        return 0

    cfg = REGISTRY[os.environ.get("BENCH_CONFIG", "bench-1b")]
    batch, prompt_len, max_new = _workload(cfg)
    on_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    dtype = jnp.float32 if on_cpu else jnp.bfloat16
    params = init_params(cfg, jax.random.key(0), dtype=dtype)
    print(f"bench[{leg}]: {cfg.name} on {dev.platform} ({device_kind}), "
          f"B={batch} prompt={prompt_len} new={max_new}", file=sys.stderr)

    primary = float(os.environ.get("BENCH_PRIMARY_TOKS", "0") or 0)
    if leg == "int8":
        _emit({"int8": _bench_int8(cfg, params, prompt_len, max_new, batch,
                                   primary or None, device_kind)})
    elif leg == "sched":
        _emit({"scheduler": _bench_scheduler(cfg, params, prompt_len,
                                             max_new, batch)})
    elif leg == "long":
        _emit({"long_context": _bench_long(cfg, params)})
    elif leg == "int4":
        _emit({"int4": _bench_int4(cfg, params, prompt_len, max_new, batch,
                                   primary or None, device_kind)})
    elif leg == "fuse":
        # Fuse HERE and rebind, dropping the unfused wq/wk/wv/wg/wu leaves
        # before the engine builds — holding both copies would double
        # weight residency (the OOM hazard inner_core's BENCH_FUSE path
        # documents).
        from llm_based_apache_spark_optimization_tpu.models.llama import (
            fuse_blocks,
        )

        params = fuse_blocks(params)
        _emit({"fused": _bench_fused(cfg, params, prompt_len, max_new,
                                     batch, primary or None, device_kind)})
    else:
        print(f"bench: unknown BENCH_LEG={leg!r}", file=sys.stderr)
        return 2
    return 0


def inner_core() -> int:
    jax = _setup_jax()
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.models import REGISTRY, init_params

    cfg_name = os.environ.get("BENCH_CONFIG", "bench-1b")
    if cfg_name not in REGISTRY:
        print(f"bench: unknown BENCH_CONFIG={cfg_name!r}; "
              f"choices: {sorted(REGISTRY)}", file=sys.stderr)
        return 2
    cfg = REGISTRY[cfg_name]
    batch, prompt_len, max_new = _workload(cfg)
    # Detail (prefill/decode split + roofline) is always on unless disabled:
    # the committed artifact must prove the roofline position by itself
    # (VERDICT r2 weak #1), not leave MFU/HBM-util to judge arithmetic.
    detail = os.environ.get("BENCH_DETAIL", "1") == "1"
    on_cpu = os.environ.get("BENCH_FORCE_CPU") == "1"
    dtype = jnp.float32 if on_cpu else jnp.bfloat16

    dev = jax.devices()[0]
    platform, device_kind = dev.platform, dev.device_kind
    print(f"bench: {cfg_name} on {platform} ({device_kind}), "
          f"B={batch} prompt={prompt_len} new={max_new}", file=sys.stderr)

    params = init_params(cfg, jax.random.key(0), dtype=dtype)
    quant = os.environ.get("BENCH_QUANT", "")
    if quant == "int8":
        from llm_based_apache_spark_optimization_tpu.ops import quantize_params

        params = quantize_params(params)
    elif quant == "int4":
        # Focused primary: the packed-nibble tree through the pallas int4
        # matmul kernel (the optional legs are skipped by the outer — they
        # (re)quantize by int8/bf16 leaf shapes and would crash on q4).
        from llm_based_apache_spark_optimization_tpu.ops import (
            quantize_params_int4,
        )

        params = quantize_params_int4(params)
    if os.environ.get("BENCH_UNEMBED8", "0") == "1":
        # Per-row int8 embed/unembed tables: after int4 blocks the bf16
        # unembed is the largest remaining decode stream. Focused A/B.
        from llm_based_apache_spark_optimization_tpu.ops import quantize_unembed

        params = quantize_unembed(params)
        quant = (quant + "+ue8") if quant else "ue8"
    # stop_ids=(-1,): never stops — random weights would otherwise emit eos at
    # arbitrary points and under-count the decode work.
    # BENCH_FUSE=1: fused wqkv/wgu matmuls (models/llama.fuse_blocks) for
    # prefill A/B runs. Fuse the tree HERE and drop the unfused leaves —
    # letting the engine fuse would keep both full copies resident for the
    # whole run (an OOM at exactly the sizes where prefill MFU matters).
    fuse = os.environ.get("BENCH_FUSE", "0") == "1"
    if fuse:
        from llm_based_apache_spark_optimization_tpu.models.llama import (
            fuse_blocks,
        )

        params = fuse_blocks(params)
    eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=prompt_len)
    rng = __import__("numpy").random.default_rng(0)
    prompts = _mk_prompts(cfg, batch, prompt_len, rng)

    t0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=max_new)  # warmup incl. compile
    compile_s = time.perf_counter() - t0
    print(f"bench: warmup+compile {compile_s:.1f}s", file=sys.stderr)

    reps = int(os.environ.get("BENCH_REPS", "3"))
    best_tok_s, best_dt = 0.0, float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = eng.generate(prompts, max_new_tokens=max_new)
        dt = time.perf_counter() - t0
        toks = sum(len(o) for o in out)
        if toks / dt > best_tok_s:
            best_tok_s, best_dt = toks / dt, dt

    result = {
        "metric": f"aggregate greedy decode throughput ({cfg_name}"
                  f"{'-' + quant if quant else ''}, B={batch}, "
                  f"prompt={prompt_len}, new={max_new})",
        "value": round(best_tok_s, 1),
        "unit": "output tok/s",
        "vs_baseline": round(best_tok_s / REFERENCE_TOKS_PER_S, 2),
        "platform": platform,
        "device_kind": device_kind,
        "compile_s": round(compile_s, 1),
    }
    if fuse:
        result["fused_matmuls"] = True
    _emit(result)  # pre-detail flush: a mid-detail kill keeps the headline

    if detail:
        result.update(_detail(
            cfg, eng, prompts, prompt_len, max_new, batch, best_dt,
            params, quant, device_kind,
        ))
        _emit(result)
    return 0


def _bench_7b(device_kind, dev) -> dict:
    """Flagship-shape leg: duckdb-nsql-7b (the Llama-2-7B architecture the
    reference's headline model fine-tunes — BASELINE.md north star) on ONE
    chip, int8 weights + int8 KV cache. bf16 7B is 13.5 GB of weights
    alone; on a 16 GB v5e the serving configuration IS the quantized one,
    so that is what this measures: decode tok/s at B=8 and B=32, the HBM
    roofline position, compile time, and the resident HBM footprint.
    Weights are random int8 (ops/quant.init_params_quantized — built
    directly at final size; no 13.5 GB intermediate): throughput is
    shape/byte-bound, not value-bound. BENCH_7B_BITS=4 swaps in the
    packed-nibble int4 tree (pallas int4 matmul, quarter weight bytes)."""
    import time as _t

    import jax
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        cache_bytes,
    )
    from llm_based_apache_spark_optimization_tpu.models import REGISTRY
    from llm_based_apache_spark_optimization_tpu.ops.quant import (
        init_params_quantized,
    )

    cfg = REGISTRY[os.environ.get("BENCH_7B_CONFIG", "duckdb-nsql-7b")]
    bits = int(os.environ.get("BENCH_7B_BITS", "8"))
    batch = int(os.environ.get("BENCH_7B_BATCH", "8"))
    prompt_len = min(int(os.environ.get("BENCH_7B_PROMPT", "128")),
                     cfg.max_seq_len // 2)
    max_new = min(int(os.environ.get("BENCH_7B_NEW", "64")),
                  cfg.max_seq_len - prompt_len)
    out: dict = {"config": cfg.name, "quant": f"int{bits}+kv8",
                 "prompt": prompt_len, "new": max_new}

    params = init_params_quantized(cfg, jax.random.key(0), bits=bits)
    if os.environ.get("BENCH_7B_UNEMBED8", "0") == "1":
        from llm_based_apache_spark_optimization_tpu.ops.quant import (
            quantize_unembed,
        )

        params = quantize_unembed(params)
        out["quant"] += "+ue8"
    out["param_bytes"] = _param_bytes(params)
    rng = np.random.default_rng(3)

    def prompts_for(b):
        return _mk_prompts(cfg, b, prompt_len, rng)

    eng = InferenceEngine(cfg, params, stop_ids=(-1,), prompt_bucket=prompt_len,
                          kv_quant="int8")
    peak_flops, peak_bw = _peak_for(device_kind, "int8")

    def measure(b):
        ps = prompts_for(b)
        t0 = _t.perf_counter()
        eng.generate(ps, max_new_tokens=max_new)  # warmup+compile
        compile_s = _t.perf_counter() - t0
        best = 0.0
        for _ in range(2):
            t0 = _t.perf_counter()
            res = eng.generate(ps, max_new_tokens=max_new)
            best = max(best, sum(len(o) for o in res)
                       / (_t.perf_counter() - t0))
        # Prefill probe for the decode-only split.
        eng.generate(ps, max_new_tokens=1)
        t_pre = float("inf")
        for _ in range(2):
            t0 = _t.perf_counter()
            eng.generate(ps, max_new_tokens=1)
            t_pre = min(t_pre, _t.perf_counter() - t0)
        decode_dt = max(b * max_new / best - t_pre, 1e-9)
        decode_tok_s = b * (max_new - 1) / decode_dt
        block = {"tok_s": round(best, 1), "compile_s": round(compile_s, 1),
                 "decode_tok_s": round(decode_tok_s, 1),
                 "prefill_s": round(t_pre, 4)}
        if peak_bw:
            s_avg = prompt_len + max_new // 2
            # int8 KV values + f32 per-position scales (1 + 4/head_dim
            # bytes per element).
            kv = cache_bytes(cfg, b, s_avg, 1)
            kv += cache_bytes(cfg, b, s_avg, 4) // cfg.head_dim
            bytes_per_step = out["param_bytes"] + kv
            block["decode_hbm_util"] = round(
                bytes_per_step * (decode_tok_s / b) / peak_bw, 4
            )
        return block

    out[f"b{batch}"] = measure(batch)
    b2 = int(os.environ.get("BENCH_7B_BATCH2", "32"))
    if b2 and b2 != batch:
        out[f"b{b2}"] = measure(b2)
    # Resident HBM with the flagship engine live (weights + caches +
    # programs). bytes_in_use, not the allocator's process-lifetime peak —
    # the peak would report whatever the earlier legs high-watered.
    ms = dev.memory_stats() or {}
    if "bytes_in_use" in ms:
        out["hbm_resident_gb"] = round(ms["bytes_in_use"] / 1e9, 2)
    return out


def _bench_7b_sched(device_kind) -> dict:
    """Flagship shape through the SERVING stack (VERDICT r4 next #7):
    continuous-batching scheduler at 7B int8+kv8 — BASELINE config 4
    ("duckdb-nsql-7B batch=32 Spider TP=4") is denominated at this model
    class, and before round 5 the scheduler had only ever been benched at
    bench-1b. Reports aggregate tok/s, per-request latency and TTFT
    percentiles under full contention."""
    import jax

    from llm_based_apache_spark_optimization_tpu.models import REGISTRY
    from llm_based_apache_spark_optimization_tpu.ops.quant import (
        init_params_quantized,
    )

    cfg = REGISTRY[os.environ.get("BENCH_7B_CONFIG", "duckdb-nsql-7b")]
    prompt_len = min(int(os.environ.get("BENCH_7B_PROMPT", "128")),
                     cfg.max_seq_len // 2)
    max_new = min(int(os.environ.get("BENCH_7B_NEW", "64")),
                  cfg.max_seq_len - prompt_len)
    slots = int(os.environ.get("BENCH_7B_SLOTS", "16"))
    params = init_params_quantized(cfg, jax.random.key(0), bits=8)
    out = _bench_scheduler(
        cfg, params, prompt_len, max_new, batch=slots // 2,
        kv_quant="int8", reps=1, n_req=2 * slots, spec_draft=0,
    )
    out["config"] = cfg.name
    out["quant"] = "int8+kv8"
    return out


def _bench_long(cfg, params) -> dict:
    """Long-context leg: B=16, prompt=1024, new=512 — the shape where the
    KV cache rivals the weights for decode bytes. Three variants stack the
    quantization levers: bf16, int8 weights, int8 weights + int8 KV cache
    (ops/quant.quantize_kv). Lean on purpose (1 timed rep each) to stay
    inside the leg's watchdog slice."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.ops import quantize_params

    b = int(os.environ.get("BENCH_LONG_BATCH", "16"))
    p = min(int(os.environ.get("BENCH_LONG_PROMPT", "1024")),
            cfg.max_seq_len // 2)
    n = min(int(os.environ.get("BENCH_LONG_NEW", "512")),
            cfg.max_seq_len - p)
    rng = np.random.default_rng(2)
    prompts = _mk_prompts(cfg, b, p, rng)
    out = {"batch": b, "prompt": p, "new": n}
    params8 = quantize_params(params)
    for key, ps, kvq in (
        ("bf16_tok_s", params, None),
        ("int8_tok_s", params8, None),
        ("int8_kv8_tok_s", params8, "int8"),
    ):
        eng = InferenceEngine(cfg, ps, stop_ids=(-1,), prompt_bucket=p,
                              kv_quant=kvq)
        eng.generate(prompts, max_new_tokens=n)  # warmup+compile
        t0 = _t.perf_counter()
        res = eng.generate(prompts, max_new_tokens=n)
        out[key] = round(sum(len(o) for o in res) / (_t.perf_counter() - t0), 1)
        del eng
    out["int8_kv8_speedup_vs_bf16"] = round(
        out["int8_kv8_tok_s"] / out["bf16_tok_s"], 2
    )
    if os.environ.get("BENCH_PAGED", "1") == "1":
        out["paged"] = _bench_long_paged(cfg, params, p, n)
    return out


def _bench_long_paged(cfg, params, p, n) -> dict:
    """The page pool at FIXED HBM (ISSUE 7 acceptance leg):

    - `accounting`: slots-at-fixed-HBM for a mixed-length traffic sample
      (half full-length, half quarter-length prompts) — how many
      requests the pool admits inside the HBM that `slots_rows`
      worst-case max_seq rows would take, reconciled by a tier-1 test.
    - `paged`: the same mixed workload with a shared schema prefix
      driven through a real scheduler capped at that HBM via
      kv_hbm_budget_bytes, recording tok/s plus the allocator counters
      that prove prefix hits SHARED pages (zero_copy_shares) instead of
      copying them (cow_copies stays at boundary counts)."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        cache_bytes,
    )
    from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
        default_page_size,
    )
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    slots_c = int(os.environ.get("BENCH_PAGED_SLOTS", "4"))
    max_new = min(n, 128)
    decode_chunk = 8
    overshoot = 2 * decode_chunk  # (harvest_lag + 1) * decode_chunk
    pb = min(128, p)
    # 2*pb floor keeps the scheduler's prompt-bucket clamp (max_seq // 2)
    # from shrinking the bucket below the prompt at small test shapes.
    max_seq = min(cfg.max_seq_len,
                  max(p + max_new + overshoot + 8, 2 * pb))
    ps = default_page_size()
    mix = [p, max(32, p // 4)]
    acct = _paged_accounting(
        cfg, slots_rows=slots_c, max_seq=max_seq, max_new=max_new,
        overshoot=overshoot, mix_lens=mix, page_size=ps,
        prompt_bucket=pb,
    )
    # Slots-at-fixed-HBM for the INT8 pool (ISSUE 11 acceptance): the
    # same bf16 worst-case-rows budget, priced at int8 page bytes — strictly
    # more admitted slots than the bf16 pool (tier-1 reconciles).
    acct8 = _paged_accounting(
        cfg, slots_rows=slots_c, max_seq=max_seq, max_new=max_new,
        overshoot=overshoot, mix_lens=mix, page_size=ps,
        prompt_bucket=pb, kv_quant="int8",
    )
    out = {"accounting": acct, "accounting_int8": acct8,
           "int8_slots_vs_bf16": (round(
               acct8["slots_paged"] / acct["slots_paged"], 2)
               if acct["slots_paged"] else 0.0)}

    # Real mixed workload: shared schema prefix (hits from request 3 on —
    # publish gate), then per-request divergence; lengths alternate
    # long/short so the paged pool's live-token packing shows up.
    rng = np.random.default_rng(7)
    n_reqs = 2 * slots_c + 2
    schema = [int(x) for x in rng.integers(3, cfg.vocab_size, size=p // 4)]
    prompts = []
    for i in range(n_reqs):
        want = mix[i % len(mix)]
        tail = [int(x) for x in
                rng.integers(3, cfg.vocab_size, size=max(1, want - p // 4))]
        prompts.append((schema + tail)[:want])

    def drive(sched, reps=2):
        sched.warmup(pb)
        best = 0.0
        with sched:
            sched.generate(prompts[:2], max_new_tokens=max_new)  # compile
            # Best-of-reps, like every other scheduler leg: wave 1 can
            # still eat stragglers' cold compiles (short-prompt buckets).
            for _ in range(reps):
                t0 = _t.perf_counter()
                futs = [sched.submit(pr, max_new_tokens=max_new)
                        for pr in prompts]
                toks = sum(len(f.result()) for f in futs)
                dt = _t.perf_counter() - t0
                best = max(best, toks / dt if dt > 0 else 0.0)
        return best

    sched_p = ContinuousBatchingScheduler(
        cfg, params, num_slots=max(1, min(acct["slots_paged"], 4 * slots_c)),
        max_seq=max_seq, prompt_bucket=pb, decode_chunk=decode_chunk,
        stop_ids=(-1,), kv_page_size=ps,
        kv_hbm_budget_bytes=cache_bytes(cfg, slots_c, max_seq),
    )
    out["paged"] = {
        "slots": sched_p.num_slots,
        "tok_s": round(drive(sched_p), 1),
        "prefix": dict(sched_p.prefix_stats),
        "kv_pages": dict(sched_p.page_stats),
    }
    del sched_p
    # The INT8 pool through a real scheduler at the SAME HBM budget: the
    # kv-dtype-aware sizing grants ~2x the pages, so strictly more slots
    # fit (mirrors accounting_int8 with live traffic; 1 rep — the pass
    # exists to prove capacity, the tok/s story is the paged pass above,
    # which is why the throughput key is tok_s_1rep: a 1-rep number must
    # NOT enter the --compare gate's tracked tok_s metrics, or ordinary
    # cold-compile variance reads as a regression).
    sched_q = ContinuousBatchingScheduler(
        cfg, params, num_slots=max(1, min(acct8["slots_paged"],
                                          4 * slots_c)),
        max_seq=max_seq, prompt_bucket=pb, decode_chunk=decode_chunk,
        stop_ids=(-1,), kv_page_size=ps,
        kv_quant="int8",
        kv_hbm_budget_bytes=cache_bytes(cfg, slots_c, max_seq),
    )
    out["paged_int8"] = {
        "slots": sched_q.num_slots,
        "tok_s_1rep": round(drive(sched_q, reps=1), 1),
        "kv_pages": dict(sched_q.page_stats),
    }
    del sched_q
    # Graceful-degradation leg (ISSUE 10): overcommit-vs-exact admission
    # at a pool sized to TWO worst-case envelopes of a generation-heavy
    # mixed fixture — the shape where reserving max_new up front forfeits
    # the pool's live-token concurrency.
    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        bucket_len as _bl,
    )
    from llm_based_apache_spark_optimization_tpu.engine.paged_kv import (
        pages_for_tokens as _pft,
    )

    pmix = [pb, max(32, pb // 4)]
    p_need = _pft(_bl(pmix[0], pb) + max_new + overshoot, ps)
    p_seq = min(cfg.max_seq_len,
                _bl(pmix[0], pb) + max_new + overshoot + 8)
    out["kv_pressure"] = _bench_kv_pressure(
        cfg, params, slots=slots_c, max_new=max_new,
        prompt_bucket=pb, decode_chunk=decode_chunk, mix_lens=pmix,
        page_size=ps, pool_pages=max(2 * p_need, _pft(p_seq, ps)),
        max_seq=p_seq,
    )
    return out


def _bench_kv_pressure(cfg, params, *, slots, max_new, prompt_bucket,
                       decode_chunk, mix_lens, page_size, pool_pages,
                       max_seq, overcommit=0.25, n_reqs=None) -> dict:
    """Overcommitted-vs-exact-envelope admission at FIXED HBM (ISSUE 10
    acceptance leg): the same page pool and the same mixed-length
    fixture, driven through two real schedulers — exact admission
    (kv_overcommit=1.0) reserves every request's worst-case envelope
    all-or-nothing, overcommit reserves the expected envelope and
    preempts victims when mid-decode top-ups fail. Records PEAK
    concurrent occupancy (the flight recorder's per-round occupancy
    column — the concurrency the pool actually sustained), tok/s, and
    the preemption rate overcommit paid for it. A tier-1 test reconciles
    the pass on the tiny config: overcommit must sustain STRICTLY more
    concurrency than exact at the same HBM (tests/test_bench.py)."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    rng = np.random.default_rng(11)
    n_reqs = n_reqs or 2 * slots
    prompts = [
        _mk_prompts(cfg, 1, mix_lens[i % len(mix_lens)], rng)[0]
        for i in range(n_reqs)
    ]

    def drive(ratio):
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_seq=max_seq,
            prompt_bucket=prompt_bucket, decode_chunk=decode_chunk,
            stop_ids=(-1,), kv_page_size=page_size,
            kv_pages=pool_pages, kv_overcommit=ratio,
        )
        sched.warmup(prompt_bucket)
        with sched:
            t0 = _t.perf_counter()
            futs = [sched.submit(pr, max_new_tokens=max_new)
                    for pr in prompts]
            # Running max over the flight ring's tail while the wave
            # drains: a long leg outruns the bounded ring, and a single
            # end-of-run read would silently report only the drain-phase
            # occupancy (the repo's no-silent-caps bench rule).
            occ = 0
            while not all(f.done() for f in futs):
                occ = max(occ, max(
                    (r.get("occupancy", 0)
                     for r in sched.flight.snapshot(64)), default=0))
                _t.sleep(0.02)
            toks = sum(len(f.result()) for f in futs)
            dt = _t.perf_counter() - t0
            occ = max(occ, max(
                (r.get("occupancy", 0)
                 for r in sched.flight.snapshot(64)), default=0))
            stats = dict(sched.page_stats)
        return {
            "overcommit": ratio,
            "tok_s": round(toks / dt, 1) if dt > 0 else 0.0,
            "peak_occupancy": int(occ),
            "preemptions": stats["preemptions"],
            "page_waits": stats["page_waits"],
        }

    exact = drive(1.0)
    over = drive(overcommit)
    out = {
        "pool_pages": pool_pages,
        "slots": slots,
        "requests": n_reqs,
        "max_new": max_new,
        "mix_lens": list(mix_lens),
        "exact": exact,
        "overcommitted": over,
        # The cost side of the ledger: preemptions per served request.
        "preemption_rate": round(over["preemptions"] / max(1, n_reqs), 3),
    }
    if exact["tok_s"]:
        out["tok_s_ratio"] = round(over["tok_s"] / exact["tok_s"], 2)
    return out


def _bench_micro(device_kind: str = "") -> dict:
    """Kernel-level microbench lane (ISSUE 11 satellite, FlashInfer-Bench
    posture): ns/op for each hot-path kernel leg vs its XLA twin, so a
    hot-path PR cites before/after numbers in-PR. Legs:

    - paged_read:        ragged paged attention kernel vs the gather+einsum
                         reference (the PR-7 read side)
    - page_write:        fused Pallas page-write kernel vs the XLA
                         scatter-through-table (this PR's write side)
    - page_write_int8:   the quantizing variants of the same pair
    - mask_gather:       the grammar need-table gather + compare + mask
                         (the per-step constrained-decode cost)

    Numbers are honest per-platform: off-TPU the Pallas kernels run in
    interpreter mode and will lose to XLA — the committed artifact records
    device_kind so a CPU lane is never misread as a chip capture. Shapes
    ride BENCH_MICRO_* (tiny defaults keep the tier-1 reconciliation test
    cheap); reps ride BENCH_MICRO_REPS."""
    import time as _t

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.ops.pallas import (
        fused_page_write,
        fused_page_write_quantized,
        paged_attention_reference,
        paged_write_reference,
        paged_write_reference_quantized,
        ragged_paged_attention,
    )
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        apply_token_mask,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    reps = int(os.environ.get("BENCH_MICRO_REPS", "20" if on_tpu else "3"))
    b = int(os.environ.get("BENCH_MICRO_BATCH", "8"))
    kh = int(os.environ.get("BENCH_MICRO_KV_HEADS", "4"))
    g = int(os.environ.get("BENCH_MICRO_GROUP", "4"))
    h = int(os.environ.get("BENCH_MICRO_HEAD_DIM", "64"))
    ps = int(os.environ.get("BENCH_MICRO_PAGE", "16"))
    np_tab = int(os.environ.get("BENCH_MICRO_PAGES_PER_ROW", "8"))
    n_layers = int(os.environ.get("BENCH_MICRO_LAYERS", "2"))
    n_states = int(os.environ.get("BENCH_MICRO_STATES", "64"))
    vocab = int(os.environ.get("BENCH_MICRO_VOCAB", "512"))
    pool_pages = b * np_tab + 1
    n = kh * g
    rng = np.random.default_rng(5)

    def ns_per_op(fn, *args):
        out = fn(*args)  # warmup + compile
        jax.block_until_ready(out)
        t0 = _t.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return int((_t.perf_counter() - t0) / reps * 1e9)

    # Stacked [L, P, ...] pools, read and written at one layer like the
    # serving path does.
    kp_l = jnp.asarray(
        rng.normal(size=(n_layers, pool_pages, kh, ps, h)), jnp.float32)
    vp_l = jnp.asarray(
        rng.normal(size=(n_layers, pool_pages, kh, ps, h)), jnp.float32)
    tab = jnp.asarray(
        np.stack([rng.permutation(pool_pages - 1)[:np_tab]
                  for _ in range(b)]), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, 1, n, h)), jnp.float32)
    pos = jnp.asarray(
        rng.integers(ps, np_tab * ps, size=(b, 1)), jnp.int32)
    kvl = pos[:, 0] + 1

    out: dict = {
        "device_kind": device_kind, "reps": reps,
        "shape": {"b": b, "kv_heads": kh, "group": g, "head_dim": h,
                  "page": ps, "pages_per_row": np_tab,
                  "layers": n_layers},
        "paged_read": {
            "kernel_ns": ns_per_op(
                ragged_paged_attention, q, kp_l, vp_l, tab, pos, 0, None,
                kvl),
            "xla_ns": ns_per_op(
                jax.jit(lambda q_, k_, v_, *a: paged_attention_reference(
                    q_, k_[0], v_[0], *a)),
                q, kp_l, vp_l, tab, pos, None, kvl),
        },
    }

    # Write side: one decode sliver per row through the table.
    knew = jnp.asarray(rng.normal(size=(b, 1, kh, h)), jnp.float32)
    vnew = jnp.asarray(rng.normal(size=(b, 1, kh, h)), jnp.float32)

    @jax.jit
    def xla_write(kp_, vp_, k_, v_, pos_, tab_):
        return (paged_write_reference(kp_, k_, pos_, tab_, 0),
                paged_write_reference(vp_, v_, pos_, tab_, 0))

    out["page_write"] = {
        "fused_ns": ns_per_op(
            lambda *a: fused_page_write(*a, 0), kp_l, vp_l, knew, vnew,
            pos, tab),
        "xla_ns": ns_per_op(xla_write, kp_l, vp_l, knew, vnew, pos, tab),
    }

    kq = jnp.zeros((n_layers, pool_pages, kh, ps, h), jnp.int8)
    ksq = jnp.ones((n_layers, pool_pages, kh, ps), jnp.float32)
    vq = jnp.zeros((n_layers, pool_pages, kh, ps, h), jnp.int8)
    vsq = jnp.ones((n_layers, pool_pages, kh, ps), jnp.float32)

    out["page_write_int8"] = {
        "fused_ns": ns_per_op(
            lambda *a: fused_page_write_quantized(*a, 0),
            kq, ksq, vq, vsq, knew, vnew, pos, tab),
        "xla_ns": ns_per_op(
            jax.jit(lambda *a: paged_write_reference_quantized(*a, 0)),
            kq, ksq, vq, vsq, knew, vnew, pos, tab),
    }

    # Grammar mask gather: the per-step constrained-decode cost — one
    # need-table row gather + budget compare + mask apply per slot.
    need = jnp.asarray(
        rng.integers(1, 8, size=(n_states, vocab)), jnp.int32)
    states = jnp.asarray(rng.integers(0, n_states, size=(b,)), jnp.int32)
    rem = jnp.asarray(rng.integers(1, 32, size=(b,)), jnp.int32)
    logits = jnp.asarray(rng.normal(size=(b, vocab)), jnp.float32)

    @jax.jit
    def mask_gather(lg, nd, st, rm):
        return apply_token_mask(lg, nd[st] <= rm[:, None])

    out["mask_gather"] = {
        "xla_ns": ns_per_op(mask_gather, logits, need, states, rem),
    }

    # Ragged mixed-round legs (ISSUE 19): ONE ragged launch serving
    # prefill rows (q_len=T) and decode rows (q_len=1) together vs the
    # alternating structure's per-phase pair of launches over the same
    # rows — the kernel-level version of the dispatch the unified
    # scheduler deletes. Swept at several prefill:decode row mixes so
    # the artifact shows where raggedness pays (decode-heavy mixes pad
    # the most dead columns; prefill-heavy mixes are nearly dense).
    t_rag = int(os.environ.get("BENCH_MICRO_RAGGED_T",
                               str(min(8, (np_tab - 1) * ps))))
    s_virt = np_tab * ps
    mixes_out = []
    seen_mix = set()
    for n_pref in (1, b // 2, b - 1):
        n_dec = b - n_pref
        if n_pref < 1 or n_dec < 1 or (n_pref, n_dec) in seen_mix:
            continue
        seen_mix.add((n_pref, n_dec))
        posm = np.full((b, t_rag), s_virt - 1, np.int32)
        qlm = np.empty((b,), np.int32)
        kvm = np.empty((b,), np.int32)
        for r in range(b):
            if r < n_pref:
                st = int(rng.integers(0, (np_tab - 1) * ps - t_rag + 1))
                posm[r] = st + np.arange(t_rag)
                qlm[r], kvm[r] = t_rag, st + t_rag
            else:
                p0 = int(rng.integers(ps, np_tab * ps - 1))
                posm[r, 0] = p0
                qlm[r], kvm[r] = 1, p0 + 1
        qm = jnp.asarray(rng.normal(size=(b, t_rag, n, h)), jnp.float32)
        posm_d = jnp.asarray(posm)
        qlm_d, kvm_d = jnp.asarray(qlm), jnp.asarray(kvm)
        # Per-phase twin: the SAME rows as two dense launches — prefill
        # rows at their full T, decode rows at T=1 — i.e. what the
        # alternating scheduler dispatches for this traffic. Two real
        # dispatches on purpose: the launch boundary IS the cost under
        # measurement, so the pair must not be fused under one jit.
        qp, pp_ = qm[:n_pref], posm_d[:n_pref]
        kvp, tp = kvm_d[:n_pref], tab[:n_pref]
        qd, pd = qm[n_pref:, :1], posm_d[n_pref:, :1]
        kvd, td = kvm_d[n_pref:], tab[n_pref:]

        def per_phase(qp_, pp2, kvp_, tp_, qd_, pd_, kvd_, td_):
            a = ragged_paged_attention(qp_, kp_l, vp_l, tp_, pp2, 0, None,
                                       kvp_)
            d = ragged_paged_attention(qd_, kp_l, vp_l, td_, pd_, 0, None,
                                       kvd_)
            return a, d

        rag_ns = ns_per_op(ragged_paged_attention, qm, kp_l, vp_l, tab,
                           posm_d, 0, None, kvm_d, qlm_d)
        pp_ns = ns_per_op(per_phase, qp, pp_, kvp, tp, qd, pd, kvd, td)
        mixes_out.append({
            "prefill_rows": n_pref, "decode_rows": n_dec,
            "ragged_ns": rag_ns, "per_phase_ns": pp_ns,
            "per_phase_over_ragged": round(pp_ns / rag_ns, 2)
            if rag_ns else 0.0,
        })
    out["ragged_mix"] = {"t": t_rag, "mixes": mixes_out}

    for leg in ("paged_read", "page_write", "page_write_int8"):
        ref = out[leg].get("xla_ns", 0)
        ker = out[leg].get("kernel_ns", out[leg].get("fused_ns", 0))
        if ker:
            out[leg]["xla_over_kernel"] = round(ref / ker, 2)
    return out


def _bench_int8(cfg, params, prompt_len, max_new, batch, bf16_tok_s,
                device_kind) -> dict:
    """int8 weight-only quant: B=8 for the apples-to-apples speedup vs the
    bf16 primary (decode streams half the weight bytes), B=32 for the
    throughput headline (BASELINE config 4's batch size) — with a bf16
    B=32 control so the B=32 ratio is also apples-to-apples (at small
    batch decode is attention/overhead-bound and int8's weight saving
    barely shows; at B=32 weight streaming amortizes differently).

    `bf16_tok_s` (the primary leg's number, handed through the outer via
    BENCH_PRIMARY_TOKS) may be None when the primary was skipped/failed —
    the speedup ratio is then omitted rather than invented.

    Also commits the trace-parsed per-op account of the B=batch decode
    (VERDICT r3 weak #3 / r4 next #6: the measured 0.34 HBM util at B=8
    was promised an itemized device-time breakdown): prefill-trace op
    sums are subtracted from full-run op sums, so the table is
    decode-only, hottest first.

    NOTE for readers diffing against BENCH_r03: decode_hbm_util is now
    decode-denominated (the shared _decode_split_and_util protocol);
    r03's 0.3382 divided the same bytes by AGGREGATE steps/s and so
    understated the decode loop's bandwidth position."""
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.ops import quantize_params

    rng = np.random.default_rng(0)

    def make_prompts(b):
        return _mk_prompts(cfg, b, prompt_len, rng)

    params8 = quantize_params(params)
    pbytes8 = _param_bytes(params8)
    eng8 = InferenceEngine(cfg, params8, stop_ids=(-1,), prompt_bucket=prompt_len)
    out = {"quant": "int8"}
    for b in sorted({batch, 32}):
        out[f"b{b}_tok_s"] = _measure_tok_s(eng8, cfg, b, prompt_len,
                                            max_new, rng)
    if bf16_tok_s:
        out["speedup_vs_bf16"] = round(out[f"b{batch}_tok_s"] / bf16_tok_s, 2)
    out.update(_decode_split_and_util(
        eng8, cfg, batch, prompt_len, max_new, out[f"b{batch}_tok_s"],
        pbytes8, device_kind, rng,
    ))
    peak_flops, peak_bw = _peak_for(device_kind, "int8")
    bytes_per_step = _step_bytes(cfg, batch, prompt_len, max_new, pbytes8)
    # Trace-parsed decode breakdown (see docstring). Op names are XLA
    # fusion labels — `fusion`/`copy`* families; counts show the per-step
    # repetition. Never fatal: profiling must not kill the leg.
    if os.environ.get("BENCH_INT8_TRACE", "1") == "1" and max_new >= 8:
        try:
            from llm_based_apache_spark_optimization_tpu.utils.traceprof import (
                device_trace,
            )

            ps = make_prompts(batch)
            with device_trace() as tr_pre:
                eng8.generate(ps, max_new_tokens=1)
            with device_trace() as tr_full:
                eng8.generate(ps, max_new_tokens=max_new)
            pre_ops = {n: s for n, s, _ in tr_pre.top_ops(10 ** 6)}
            rows = [
                (n, s - pre_ops.get(n, 0.0), c)
                for n, s, c in tr_full.top_ops(10 ** 6)
            ]
            rows = sorted((r for r in rows if r[1] > 1e-5),
                          key=lambda r: -r[1])[:12]
            dev_decode = tr_full.device_time_s() - tr_pre.device_time_s()
            trace: dict = {
                "decode_device_s": round(max(dev_decode, 0.0), 4),
                "top_ops": [[n[:100], round(s, 4), c] for n, s, c in rows],
            }
            if peak_bw and dev_decode > 0 and bytes_per_step:
                trace["decode_device_hbm_util"] = round(
                    bytes_per_step * (max_new - 1) / dev_decode / peak_bw, 4
                )
            out[f"b{batch}_trace"] = trace
        except Exception as e:
            out[f"b{batch}_trace"] = {"error": str(e)[:200]}
    # Free the int8 tree before building the bf16 control engine: holding
    # both would triple resident state and can OOM a near-capacity chip
    # during the control measurement.
    del eng8, params8
    if 32 != batch:
        eng16 = InferenceEngine(cfg, params, stop_ids=(-1,),
                                prompt_bucket=prompt_len)
        out["bf16_b32_tok_s"] = _measure_tok_s(eng16, cfg, 32, prompt_len,
                                               max_new, rng)
        out["b32_speedup_vs_bf16"] = round(
            out["b32_tok_s"] / out["bf16_b32_tok_s"], 2
        )
    return out


def _measure_tok_s(eng, cfg, b, prompt_len, max_new, rng) -> float:
    """Best-of-2 aggregate tok/s (warmup+compile first) — the one
    measurement protocol every engine leg shares."""
    import time as _t

    ps = _mk_prompts(cfg, b, prompt_len, rng)
    eng.generate(ps, max_new_tokens=max_new)  # warmup incl. compile
    best = 0.0
    for _ in range(2):
        t0 = _t.perf_counter()
        res = eng.generate(ps, max_new_tokens=max_new)
        best = max(best, sum(len(o) for o in res) / (_t.perf_counter() - t0))
    return round(best, 1)


def _step_bytes(cfg, b, prompt_len, max_new, param_bytes,
                cache_itemsize=2) -> int:
    """HBM bytes one decode step streams: full weights + the KV cache read
    at the mid-run context length — the SHARED model
    (utils/perfmodel.decode_step_bytes), so bench and the live ledger
    can never disagree on what a step costs."""
    from llm_based_apache_spark_optimization_tpu.utils.perfmodel import (
        decode_step_bytes,
    )

    return decode_step_bytes(cfg, b, prompt_len + max_new // 2, param_bytes,
                             itemsize=cache_itemsize)


def _decode_split_and_util(eng, cfg, b, prompt_len, max_new, agg_tok_s,
                           param_bytes, device_kind, rng) -> dict:
    """Decode-only split via the max_new=1 prefill probe, plus decode HBM
    util from DECODE-ONLY tok/s (one formula across the bf16/int8/int4
    legs — mixing aggregate- and decode-denominated utils would make the
    cross-quant bandwidth comparison apples-to-oranges). Bandwidth only:
    this helper deliberately has no FLOPs/quant plumbing, so no caller
    can silently compute MFU against the wrong peak (_detail owns MFU).
    Empty when max_new is too small for the split to be signal."""
    import time as _t

    out: dict = {}
    if max_new < 8:
        return out
    ps = _mk_prompts(cfg, b, prompt_len, rng)
    eng.generate(ps, max_new_tokens=1)
    t_pre = float("inf")
    for _ in range(2):
        t0 = _t.perf_counter()
        eng.generate(ps, max_new_tokens=1)
        t_pre = min(t_pre, _t.perf_counter() - t0)
    out["prefill_s"] = round(t_pre, 4)
    decode_dt = max(b * max_new / agg_tok_s - t_pre, 1e-9)
    out["decode_tok_s"] = round(b * (max_new - 1) / decode_dt, 1)
    _, peak_bw = _peak_for(device_kind, "")
    if peak_bw:
        bps = _step_bytes(cfg, b, prompt_len, max_new, param_bytes)
        out["decode_hbm_util"] = round(
            bps * (out["decode_tok_s"] / b) / peak_bw, 4
        )
    return out


def _bench_int4(cfg, params, prompt_len, max_new, batch, bf16_tok_s,
                device_kind) -> dict:
    """Compiled int4 pallas-kernel leg (VERDICT r4 next #3: every int4
    parity test runs interpret mode on CPU, and no committed artifact had
    ever executed the COMPILED kernel on a real chip).

    Three pieces of on-chip evidence:
    1. `kernel_max_abs_err`: one decode-shaped int4_matmul, compiled,
       against the pure-jnp dequantized reference — a nonzero-but-tiny
       value proves the compiled kernel (packed uint8 nibbles, unpacked
       in VMEM) computes the same products as interpret mode.
    2. Engine throughput at B=batch and B=32 on the int4 tree, with the
       decode-only split.
    3. `decode_hbm_util` against the 4-bit byte ceiling — THE number that
       says whether 4-bit storage actually bought 4-bit bandwidth.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine
    from llm_based_apache_spark_optimization_tpu.ops import (
        dequantize_weight_int4,
        quantize_params_int4,
        quantize_weight_int4,
    )
    from llm_based_apache_spark_optimization_tpu.ops.pallas.int4mm import (
        int4_matmul,
    )

    out: dict = {"quant": "int4"}

    # 1. Compiled-kernel parity spot-check on a decode-shaped matmul.
    w = params["blocks"]["wq"][0]  # [D, N*H] — a real weight, layer 0
    q = quantize_weight_int4(w)
    x = jax.random.normal(jax.random.key(7), (batch, w.shape[0]), w.dtype)
    got = np.asarray(int4_matmul(x, q["q4"], q["s4"]))
    ref = np.asarray(x.astype(jnp.float32) @ dequantize_weight_int4(q))
    out["kernel_max_abs_err"] = float(np.max(np.abs(got - ref)))
    out["kernel_ref_scale"] = float(np.max(np.abs(ref)))

    # 2./3. Engine throughput + roofline on the int4 tree (shared
    # protocol: _measure_tok_s / _decode_split_and_util).
    params4 = quantize_params_int4(params)
    pbytes4 = _param_bytes(params4)
    out["param_bytes"] = pbytes4
    eng4 = InferenceEngine(cfg, params4, stop_ids=(-1,),
                           prompt_bucket=prompt_len)
    rng = np.random.default_rng(0)
    for b in sorted({batch, 32}):
        out[f"b{b}_tok_s"] = _measure_tok_s(eng4, cfg, b, prompt_len,
                                            max_new, rng)
    if bf16_tok_s:
        out["speedup_vs_bf16"] = round(out[f"b{batch}_tok_s"] / bf16_tok_s, 2)
    out.update(_decode_split_and_util(
        eng4, cfg, batch, prompt_len, max_new, out[f"b{batch}_tok_s"],
        pbytes4, device_kind, rng,
    ))
    return out


def _bench_fused(cfg, params, prompt_len, max_new, batch,
                 bf16_tok_s, device_kind) -> dict:
    """Fused-matmul A/B (stacked wkv/wqkv + wgu, models/llama.fuse_blocks;
    the caller passes an ALREADY-FUSED tree so the unfused leaves are
    gone): the prefill-MFU lever, measured against the unfused primary.
    Reports aggregate tok/s, the decode split/HBM util (expected ~flat:
    decode moves the same bytes either way — the util number is here to
    CONFIRM that), and the prefill probe, which BENCH_PRIMARY_PREFILL
    (the core leg's prefill_s, handed through by the outer) turns into a
    committed speedup ratio."""
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.engine import InferenceEngine

    rng = np.random.default_rng(0)
    eng = InferenceEngine(cfg, params, stop_ids=(-1,),
                          prompt_bucket=prompt_len)
    out: dict = {"quant": "bf16+fused"}
    out[f"b{batch}_tok_s"] = _measure_tok_s(eng, cfg, batch, prompt_len,
                                            max_new, rng)
    if bf16_tok_s:
        out["speedup_vs_unfused"] = round(
            out[f"b{batch}_tok_s"] / bf16_tok_s, 2
        )
    out.update(_decode_split_and_util(
        eng, cfg, batch, prompt_len, max_new, out[f"b{batch}_tok_s"],
        _param_bytes(params), device_kind, rng,
    ))
    base_pre = float(os.environ.get("BENCH_PRIMARY_PREFILL", "0") or 0)
    if base_pre > 0 and out.get("prefill_s"):
        out["prefill_speedup_vs_unfused"] = round(
            base_pre / out["prefill_s"], 2
        )
    return out


def _watchdog_overhead(n: int = 50_000, sched=None) -> dict:
    """Measured cost of the liveness layer on the scheduler hot path
    (per-ns): the busy-flag scan + one heartbeat stamp per event-loop
    iteration plus one round_done per harvested round
    (serve/watchdog.py). The stamp/round_done are timed on a throwaway
    Heartbeat so the live scheduler's state is untouched; the busy scan
    (`_busy_now` — an O(num_slots) sweep plus a queue-mutex peek, which
    can dominate the stamp itself on wide batches) is timed on the real
    `sched` when one is passed, since its cost depends on the live slot
    count. The scheduler leg records it so the watchdog's tax is a
    number in the artifact, not an assumption."""
    import time as _t

    from llm_based_apache_spark_optimization_tpu.serve.watchdog import (
        Heartbeat,
    )

    hb = Heartbeat()
    t0 = _t.perf_counter()
    for _ in range(n):
        hb.stamp(True)
    stamp_ns = (_t.perf_counter() - t0) / n * 1e9
    t0 = _t.perf_counter()
    for _ in range(n):
        hb.round_done()
    round_ns = (_t.perf_counter() - t0) / n * 1e9
    busy_ns = 0.0
    busy_now = getattr(sched, "_busy_now", None)
    if callable(busy_now):
        t0 = _t.perf_counter()
        for _ in range(n):
            busy_now()
        busy_ns = (_t.perf_counter() - t0) / n * 1e9
    out = {
        "stamp_ns": round(stamp_ns, 1),
        "round_done_ns": round(round_ns, 1),
        # One loop iteration ≈ one busy scan + one stamp + one round_done
        # at steady state.
        "per_round_ns": round(busy_ns + stamp_ns + round_ns, 1),
    }
    if callable(busy_now):
        out["busy_scan_ns"] = round(busy_ns, 1)
    return out


def _obs_overhead(n: int = 50_000, sched=None) -> dict:
    """Measured cost of the ISSUE-6 observability layer on the scheduler
    hot path, sampling OFF (the always-on configuration): one flight-
    recorder record per harvested round, plus the no-op tracing span
    (contextvar read) and the unsampled per-request tracer draw. Timed on
    throwaway objects so the live scheduler's ring is untouched. The leg
    divides the per-round cost by the measured round cadence so the
    artifact carries overhead as a PERCENTAGE of decode wall, not just
    nanoseconds — the <1% acceptance bar is checked against it.

    Every component takes the BEST of three trial loops: the figure
    claims what the stamps COST, and a single-trial mean on a loaded
    host (a full-suite CI run, sibling compiles) measures scheduler
    contention instead — the best-of floor is the standard microbench
    answer and is what the <1% bar should gate."""
    import time as _t

    from llm_based_apache_spark_optimization_tpu.serve.flightrecorder import (
        FlightRecorder,
    )
    from llm_based_apache_spark_optimization_tpu.utils import tracing
    from llm_based_apache_spark_optimization_tpu.utils.tracing import Tracer

    def best_ns(loop, iters, trials=3):
        best = None
        for _ in range(trials):
            t0 = _t.perf_counter()
            loop(iters)
            dt = (_t.perf_counter() - t0) / iters * 1e9
            best = dt if best is None else min(best, dt)
        return best

    fl = FlightRecorder(capacity=256)

    def _rec_loop(k):
        for i in range(k):
            fl.record(round=i, occupancy=8, queued=0, admitted=(),
                      retired=(), emitted=8, round_wall_s=0.001,
                      cadence_s=0.001)

    record_ns = best_ns(_rec_loop, n)

    def _span_loop(k):
        for _ in range(k):
            with tracing.span("bench.noop"):
                pass

    span_off_ns = best_ns(_span_loop, n)
    # A vanishingly small (but nonzero) sample rate exercises the real
    # unsampled fast path — the RNG draw and the compare — without ever
    # paying RequestTrace construction, which is what an unsampled
    # request actually costs and what this figure claims to be.
    tracer = Tracer(sample=1e-12, seed=0)

    def _begin_loop(k):
        for _ in range(k):
            tracer.begin()  # sample draw; never a real trace

    begin_ns = best_ns(_begin_loop, n)
    # Roofline-ledger stamp (ISSUE 12): one PerfModel.observe per
    # harvested round — a handful of float multiplies + an EWMA fold.
    # Timed on a THROWAWAY model cloned from the live scheduler's pricing
    # when one is passed (same cost profile, but 50k fake observations
    # must not pollute the live per-phase EWMAs the artifact commits);
    # the acceptance bar counts it inside the same <1%-of-cadence budget.
    from llm_based_apache_spark_optimization_tpu.utils.perfmodel import (
        PerfModel,
    )

    live = getattr(sched, "perf", None)
    if live is not None:
        perf = PerfModel(live.cfg, param_bytes=live.param_bytes,
                         weight_bits=live.weight_bits,
                         kv_itemsize=live.kv_itemsize,
                         kv_quant=live.kv_quant, kv_layout=live.kv_layout,
                         page_size=live.page_size, tp=live.tp,
                         device_kind=live.device_kind)
    else:
        from llm_based_apache_spark_optimization_tpu.models import TINY

        perf = PerfModel(TINY, param_bytes=10 ** 6)
    def _ledger_loop(k):
        for _ in range(k):
            perf.observe("decode", rows=8, tokens=8, ctx=128, wall_s=0.001)

    ledger_ns = best_ns(_ledger_loop, n)
    # Prefix-reuse admission stamp (ISSUE 14): the memoized content
    # digest of a schema-sized prefix + the O(1) reuse-distance map
    # probe + the priced-savings floats — the telemetry cost ONE
    # admission pays in STEADY STATE (the same schema prefix repeats, so
    # the digest is a tuple + dict probe; blake2b runs once per DISTINCT
    # prefix, amortized to ~nothing on the serving pattern the cache
    # exists for). Folded into the per-round figure below as if every
    # round admitted, which overstates it — the <1% bar is checked
    # against the overstatement.
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        prefix_digest,
    )

    ids = list(range(256))
    memo = {tuple(ids): prefix_digest(ids)}
    ring_seq = {prefix_digest([i]): i for i in range(256)}

    def _prefix_loop(k):
        for _ in range(k):
            d = memo.get(tuple(ids))  # the admission path's memoized digest
            ring_seq.get(d)           # ...and its distance probe
            perf.prefill_saved(256)

    prefix_ns = best_ns(_prefix_loop, max(1, n // 10))
    per_round = record_ns + span_off_ns + ledger_ns
    out = {
        "flight_record_ns": round(record_ns, 1),
        "span_unsampled_ns": round(span_off_ns, 1),
        "tracer_begin_ns": round(begin_ns, 1),
        "ledger_ns": round(ledger_ns, 1),
        # Per ADMISSION, not per round: the prefix stamp runs once per
        # admitted request on the path that also runs a multi-ms prefill
        # forward, so it carries its own figure and its own <1%-of-a-1ms-
        # round bar in the test instead of inflating the per-round sum
        # (a request's admission amortizes over its whole decode life).
        "prefix_stamp_ns": round(prefix_ns, 1),
        # One harvested round pays ONE flight record + ONE ledger stamp;
        # spans are per request-terminal, not per round.
        "per_round_ns": round(per_round, 1),
    }
    hb = getattr(sched, "heartbeat", None)
    cadence = hb.expected_round_s() if hb is not None else None
    if cadence:
        out["pct_of_round"] = round(
            100.0 * per_round * 1e-9 / cadence,
            4,
        )
    return out


def _bench_pool_routing(cfg, params, n_long: int = 4, n_short: int = 4,
                        long_prompt: int = 24, short_prompt: int = 6,
                        long_new: int = 48, short_new: int = 4,
                        reps: int = 2) -> dict:
    """Round-robin vs least-loaded pool placement under SKEWED prompt
    lengths/budgets (ISSUE 9): two 1-slot replicas serve an alternating
    long/short submit wave. Blind round-robin anti-correlates with the
    arrival pattern — every long request lands on replica 0, serializing
    ~long_new×n_long tokens behind one slot while replica 1 idles — and
    the least-loaded router (queue-depth × service-time EWMA, token-
    weighted tie-break) balances the token mass. Two committed figures:
    `max_replica_share` (routing quality — provable anywhere, including
    this CPU pass where both replicas contend for the same cores and
    the wall barely moves with balance) and the tok/s `speedup`, which
    is what the chip capture (disjoint submeshes, truly parallel
    replicas) turns into a real throughput win on the workload shape
    the reference actually serves (short lookups interleaved with long
    schema-heavy generations). Fresh replicas per router so EWMAs and
    caches can't leak between the passes."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        SchedulerPool,
    )

    decode_chunk = 4
    bucket = max(long_prompt, 16)
    max_seq = min(bucket + long_new + 3 * decode_chunk + 8, cfg.max_seq_len)
    rng = np.random.default_rng(5)
    longs = _mk_prompts(cfg, n_long, long_prompt, rng)
    shorts = _mk_prompts(cfg, n_short, short_prompt, rng)
    # Alternating arrival: the pattern round-robin pairs worst with.
    wave = []
    for i in range(max(n_long, n_short)):
        if i < n_long:
            wave.append((longs[i], long_new))
        if i < n_short:
            wave.append((shorts[i], short_new))

    def make_replica(i=0):
        return ContinuousBatchingScheduler(
            cfg, params, num_slots=1, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=0,
        )

    def drive(router):
        pool = SchedulerPool([make_replica(), make_replica()],
                             router=router)
        for s in pool.schedulers:
            s.warmup(long_prompt)
            s.warmup(short_prompt)
        best = None
        with pool:
            # Compile each replica's decode program and seed each EWMA
            # SYMMETRICALLY (a pool-level warm call would seed only the
            # replica it lands on and bias the router's first picks).
            for s in pool.schedulers:
                s.generate([wave[0][0]], max_new_tokens=2)
            # Best-of-reps, like every other scheduler pass: wave walls
            # at this size carry host-scheduling noise either router
            # would absorb at production scale.
            for _ in range(reps):
                toks_by_replica: dict = {}
                t0 = _t.perf_counter()
                futs = [
                    pool.submit(ids, max_new_tokens=mn)
                    for ids, mn in wave
                ]
                total = 0
                for fut in futs:
                    n = len(fut.result())
                    total += n
                    rep = getattr(fut, "_lsot_replica", "")
                    toks_by_replica[rep] = toks_by_replica.get(rep, 0) + n
                wall = _t.perf_counter() - t0
                if best is None or total / wall > best["tok_s"]:
                    split = dict(sorted(toks_by_replica.items()))
                    best = {
                        "tok_s": total / wall,
                        "wall_s": round(wall, 3),
                        "tokens_by_replica": split,
                        # Routing quality, independent of the host: the
                        # hottest replica's share of the wave's tokens
                        # (0.5 = perfectly balanced on 2 replicas; 1.0 =
                        # everything stacked on one). On a shared-compute
                        # CPU host the wall barely moves with balance
                        # (both replicas contend for the same cores), so
                        # THIS is the figure the CPU pass proves; the
                        # tok/s delta is what the chip capture (disjoint
                        # submeshes, truly parallel replicas) commits.
                        "max_replica_share": round(
                            max(split.values()) / max(1, total), 3),
                    }
        best["tok_s"] = round(best["tok_s"], 1)
        return best

    rr = drive("round_robin")
    ll = drive("least_loaded")
    return {
        "requests": len(wave),
        "long": {"n": n_long, "prompt": long_prompt, "max_new": long_new},
        "short": {"n": n_short, "prompt": short_prompt,
                  "max_new": short_new},
        "round_robin": rr,
        "least_loaded": ll,
        "speedup": round(ll["tok_s"] / rr["tok_s"], 3) if rr["tok_s"]
        else 0.0,
        # Cache-aware routing flip (ISSUE 15): affinity-on vs
        # affinity-off over shared-schema-prefix traffic — the flip
        # cites its own number.
        "affinity": _bench_pool_affinity(cfg, params),
    }


def _bench_pool_affinity(cfg, params, n_per_schema: int = 4,
                         block: int = 8, max_new: int = 4) -> dict:
    """Affinity-on vs affinity-off placement over SHARED-SCHEMA-PREFIX
    traffic (ISSUE 15): two schema families A and B — every request in
    a family shares its first `block` tokens (the schema prefix the
    NL→SQL workload repeats per table) — warmed onto OPPOSITE replicas
    from where the blind tie-break would send the follow-up wave. With
    `prefix_affinity` consumed in the placement order the wave lands on
    the replica already holding its schema's pages (zero-copy hits);
    with LSOT_POOL_AFFINITY=0 the least-loaded order scatters the
    families and re-prefills. Committed figures: the wave's
    `prefix_hit_rate` per mode (`--compare`-gated — a routing
    regression shows up as the ON rate collapsing toward OFF) and the
    ON pass's placement-hit share (affinity_hits / affinity_checked
    from the pool's own routing counters)."""
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        SchedulerPool,
    )

    rng = np.random.default_rng(7)
    vocab = cfg.vocab_size
    schema_a = [int(t) for t in rng.integers(3, vocab, size=block)]
    schema_b = [int(t) for t in rng.integers(3, vocab, size=block)]
    while schema_b[:block] == schema_a[:block]:
        schema_b = [int(t) for t in rng.integers(3, vocab, size=block)]

    def prompts(schema):
        return [schema + [int(t) for t in rng.integers(3, vocab, size=4)]
                for _ in range(n_per_schema)]

    wave_a, wave_b = prompts(schema_a), prompts(schema_b)

    def make_replica(i=0):
        return ContinuousBatchingScheduler(
            cfg, params, num_slots=1, max_seq=64, prompt_bucket=block,
            stop_ids=(-1,), decode_chunk=4, prefix_cache_blocks=8,
            # One schema block a page: the pool of a one-slot replica is
            # too small to keep a 64-token page resident beside its COW
            # copy.
            kv_page_size=block,
        )

    def drive(affinity: bool) -> dict:
        pool = SchedulerPool([make_replica(), make_replica()],
                             affinity_routing=affinity, lease_s=0.0)
        with pool:
            for s in pool.schedulers:
                s.warmup(block + 4)
            # Seed each schema's pages on the replica OPPOSITE to where
            # the blind tie-break sends the wave's first requests —
            # only content-aware placement can exploit the residency.
            # Twice per schema: the prefix cache publishes a block on
            # its SECOND sighting (first sighting only records content).
            for warm in (wave_a[0], wave_a[1]):
                pool.schedulers[1].submit(
                    warm, max_new_tokens=max_new).result()
            for warm in (wave_b[0], wave_b[1]):
                pool.schedulers[0].submit(
                    warm, max_new_tokens=max_new).result()
            before = pool.prefix_stats
            futs = []
            for pa, pb in zip(wave_a, wave_b):
                futs.append(pool.submit(pa, max_new_tokens=max_new))
                futs.append(pool.submit(pb, max_new_tokens=max_new))
            for f in futs:
                f.result()
            after = pool.prefix_stats
            routing = pool.routing_stats()
        hits = after["hits"] - before["hits"]
        misses = after["misses"] - before["misses"]
        total = hits + misses
        checked = routing["affinity_checked"]
        return {
            "hits": hits,
            "misses": misses,
            "prefix_hit_rate": round(hits / total, 4) if total else 0.0,
            "placement_hit_share": round(
                routing["affinity_hits"] / checked, 4) if checked else 0.0,
        }

    on = drive(True)
    off = drive(False)
    return {
        "requests": 2 * n_per_schema,
        "schema_prefix_tokens": block,
        "affinity_on": on,
        "affinity_off": off,
        "hit_rate_delta": round(
            on["prefix_hit_rate"] - off["prefix_hit_rate"], 4),
    }


def _bench_disagg(cfg, params, n_long: int = 3, n_short: int = 3,
                  long_prompt: int = 24, short_prompt: int = 6,
                  long_new: int = 4, short_new: int = 24,
                  reps: int = 2) -> dict:
    """Mixed fleet vs phase-split fleet at EQUAL replica count (ISSUE
    13) over a bimodal workload: long-prompt-short-gen (the schema-heavy
    NL→SQL lookup — prefill-dominated) interleaved with
    short-prompt-long-gen (free-text generation — decode-dominated).
    The mixed fleet runs two mixed paged replicas; the split fleet runs
    one prefill + one decode replica, with every request's KV migrating
    through the export→requeue→import handoff. Committed figures: TTFT/
    TPOT percentiles and decode tok/s per fleet shape, plus the split
    fleet's handoff tally (proof the disaggregated path actually
    served, not the in-place fallback). On a shared-core CPU host the
    two fleets contend for the same silicon, so the structural figures
    (handoffs fired, both shapes complete, token counts equal) are what
    the CPU pass proves; the tok/s and latency DELTAS are owed to the
    chip capture where prefill and decode replicas hold disjoint
    submeshes."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        SchedulerPool,
    )

    decode_chunk = 4
    bucket = max(long_prompt, 16)
    max_seq = min(bucket + max(long_new, short_new) + 3 * decode_chunk + 8,
                  cfg.max_seq_len)
    rng = np.random.default_rng(7)
    longs = _mk_prompts(cfg, n_long, long_prompt, rng)
    shorts = _mk_prompts(cfg, n_short, short_prompt, rng)
    wave = []
    for i in range(max(n_long, n_short)):
        if i < n_long:
            wave.append((longs[i], long_new))
        if i < n_short:
            wave.append((shorts[i], short_new))

    def make_replica(role):
        return ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=0,
            kv_page_size=8, phase_role=role,
        )

    def drive(roles):
        pool = SchedulerPool([make_replica(r) for r in roles])
        for s in pool.schedulers:
            s.warmup(long_prompt)
            s.warmup(short_prompt)
        best = None
        with pool:
            # Compile every replica's decode + restore programs outside
            # the timed wave (a prefill replica's warm request migrates
            # to its decode sibling, compiling the import scatter too).
            for s in pool.schedulers:
                s.generate([wave[0][0]], max_new_tokens=2)
            for _ in range(reps):
                stamps = [[] for _ in wave]
                t0 = _t.perf_counter()
                futs = [
                    pool.submit(ids, max_new_tokens=mn,
                                on_token=(lambda _t_, ss=ss:
                                          ss.append(_t.perf_counter())))
                    for (ids, mn), ss in zip(wave, stamps)
                ]
                total = sum(len(f.result()) for f in futs)
                wall = _t.perf_counter() - t0
                ttfts = [s[0] - t0 for s in stamps if s]
                tpots = [
                    (s[-1] - s[0]) / (len(s) - 1)
                    for s in stamps if len(s) > 1
                ]
                if best is None or total / wall > best["decode_tok_s"]:
                    best = {
                        "decode_tok_s": total / wall,
                        "wall_s": round(wall, 3),
                        "tokens": total,
                        "ttft_p50_s": round(
                            float(np.percentile(ttfts, 50)), 4),
                        "ttft_p95_s": round(
                            float(np.percentile(ttfts, 95)), 4),
                        "tpot_p50_s": round(
                            float(np.percentile(tpots, 50)), 5),
                        "tpot_p95_s": round(
                            float(np.percentile(tpots, 95)), 5),
                    }
            ho = pool.handoff_stats
        best["decode_tok_s"] = round(best["decode_tok_s"], 1)
        if ho:
            best["handoffs"] = sum(
                int(r.get("exports", 0)) for r in ho["replicas"]
            )
            # The "no silent fallback" proof: a split-fleet request that
            # decoded in place instead of migrating counts here.
            best["inplace_fallbacks"] = sum(
                int(r.get("inplace_fallbacks", 0)) for r in ho["replicas"]
            )
            best["handoff_wait_s"] = round(sum(
                float(r.get("wait_s_sum", 0.0)) for r in ho["replicas"]
            ), 4)
        return best

    mixed = drive(["mixed", "mixed"])
    split = drive(["prefill", "decode"])
    return {
        "requests": len(wave),
        "long": {"n": n_long, "prompt": long_prompt, "max_new": long_new},
        "short": {"n": n_short, "prompt": short_prompt,
                  "max_new": short_new},
        "mixed_fleet": mixed,
        "split_fleet": split,
        "speedup": round(
            split["decode_tok_s"] / mixed["decode_tok_s"], 3
        ) if mixed["decode_tok_s"] else 0.0,
    }


def _bench_qos(cfg, params, n_batch: int = 4, n_inter: int = 3,
               batch_prompt: int = 24, inter_prompt: int = 6,
               batch_new: int = 16, inter_new: int = 8,
               reps: int = 2) -> dict:
    """Multi-tenant QoS pass (ISSUE 18): one WFQ scheduler serving a
    storm tenant's `batch`-class long-prompt wave concurrently with an
    interactive tenant's short probes — the front-door workload the
    weighted-fair queue exists for. Committed figures: TTFT/TPOT p50/p95
    PER QOS CLASS plus aggregate tok/s (`--compare`-gated via the nested
    tok_s leaf). The structural claim on a shared-core CPU host is that
    both classes complete and the interactive class's TTFT does not
    inherit the batch backlog wholesale; the absolute latency deltas
    are owed to the chip capture like the disagg passes."""
    import os as _os
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    decode_chunk = 4
    bucket = max(batch_prompt, 16)
    max_seq = min(bucket + max(batch_new, inter_new) + 3 * decode_chunk + 8,
                  cfg.max_seq_len)
    rng = np.random.default_rng(18)
    batch_reqs = _mk_prompts(cfg, n_batch, batch_prompt, rng)
    inter_reqs = _mk_prompts(cfg, n_inter, inter_prompt, rng)
    wave = ([("bulk", "batch", ids, batch_new) for ids in batch_reqs]
            + [("fg", "interactive", ids, inter_new) for ids in inter_reqs])

    # The scheduler latches LSOT_QOS at __init__ — force the QoS path on
    # for this pass regardless of the harness environment.
    saved = _os.environ.get("LSOT_QOS")
    _os.environ["LSOT_QOS"] = "1"
    try:
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=0,
            kv_page_size=8,
        )
    finally:
        if saved is None:
            _os.environ.pop("LSOT_QOS", None)
        else:
            _os.environ["LSOT_QOS"] = saved
    sched.warmup(batch_prompt)
    sched.warmup(inter_prompt)

    def pct(vals, q, nd):
        return round(float(np.percentile(vals, q)), nd) if vals else 0.0

    best = None
    with sched:
        sched.generate([wave[0][2]], max_new_tokens=2)  # decode program
        for _ in range(reps):
            stamps = [[] for _ in wave]
            t0 = _t.perf_counter()
            futs = [
                sched.submit(ids, max_new_tokens=mn, tenant=tenant,
                             qos=qos,
                             on_token=(lambda _tok, ss=ss:
                                       ss.append(_t.perf_counter())))
                for (tenant, qos, ids, mn), ss in zip(wave, stamps)
            ]
            total = sum(len(f.result()) for f in futs)
            wall = _t.perf_counter() - t0
            by_class = {}
            for (tenant, qos, _ids, _mn), ss in zip(wave, stamps):
                cls = by_class.setdefault(qos, {"ttft": [], "tpot": []})
                if ss:
                    cls["ttft"].append(ss[0] - t0)
                if len(ss) > 1:
                    cls["tpot"].append((ss[-1] - ss[0]) / (len(ss) - 1))
            if best is None or total / wall > best["tok_s"]:
                best = {
                    "tok_s": total / wall,
                    "wall_s": round(wall, 3),
                    "tokens": total,
                    "classes": {
                        qos: {
                            "ttft_p50_s": pct(c["ttft"], 50, 4),
                            "ttft_p95_s": pct(c["ttft"], 95, 4),
                            "tpot_p50_s": pct(c["tpot"], 50, 5),
                            "tpot_p95_s": pct(c["tpot"], 95, 5),
                        }
                        for qos, c in sorted(by_class.items())
                    },
                }
        qstats = sched.qos_stats()
    best["tok_s"] = round(best["tok_s"], 1)
    best["requests"] = {"batch": n_batch, "interactive": n_inter}
    if qstats:
        best["tenants"] = sorted(qstats.get("submitted", {}))
    return best


def _bench_repair(cfg, params, n_req: int = 6, prompt_len: int = 32,
                  max_new: int = 8, reps: int = 2) -> dict:
    """Repair-wave pass (ISSUE 20): the self-healing loop's serving
    shape. A failed request's repair rounds reuse the ORIGINAL system
    prompt verbatim (app/repair.build_repair_prompt's contract) with a
    short unique tail (error text + question), ride QoS class `replay`
    under the requesting tenant, and arrive as a correlated wave — the
    near-total-prefix-reuse short-gen traffic the ISSUE names as a
    routing/prefix-cache/QoS stress unlike any prior fixture. Committed
    figures: the wave's TTFT p50/p95, tok/s, and its prefix_hit_rate
    (per-wave prefix_stats delta) — a repair wave that stops hitting the
    schema prefix re-pays full prefill exactly when the fleet is already
    dealing with failures."""
    import os as _os
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    decode_chunk = 4
    bucket = max(prompt_len, 16)
    # Room for the bucketed prompt + generation + harvest overshoot (the
    # admission check prices the NEXT bucket up for block-aligned
    # prefix-cache admissions, hence 2x the prompt bucket).
    max_seq = min(2 * bucket + max_new + 3 * decode_chunk + 8,
                  cfg.max_seq_len)
    # The scheduler latches LSOT_QOS at __init__ — force the QoS path on
    # so the wave's tenant/replay-class submits take the front-door path.
    saved = _os.environ.get("LSOT_QOS")
    _os.environ["LSOT_QOS"] = "1"
    try:
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=256,
        )
    finally:
        if saved is None:
            _os.environ.pop("LSOT_QOS", None)
        else:
            _os.environ["LSOT_QOS"] = saved
    sched.warmup(prompt_len)
    pblock = sched._pblock
    shared_len = max(pblock, (prompt_len // 2) // pblock * pblock)
    tail_len = prompt_len - shared_len
    if tail_len > 0:
        # Repair admissions prefill only the tail bucket — warm it too
        # or the timed wave compiles mid-flight.
        sched.warmup(tail_len)
    rng = np.random.default_rng(27)
    shared = _mk_prompts(cfg, 1, shared_len, rng)[0]

    def pct(vals, q):
        return round(float(np.percentile(vals, q)), 4) if vals else 0.0

    def submit_wave(prompts, stamps):
        t0 = _t.perf_counter()
        futs = [
            # tenant="repair" on every submit INCLUDING the publisher:
            # prefix namespaces are tenant-salted (ISSUE 18), so the
            # wave only re-hits blocks published under its own tenant —
            # exactly as production repair rounds reuse their own
            # request's schema prefix.
            sched.submit(ids, max_new_tokens=max_new, tenant="repair",
                         qos="replay",
                         on_token=(lambda _tok, ss=ss:
                                   ss.append(_t.perf_counter())))
            for ids, ss in zip(prompts, stamps)
        ]
        total = sum(len(f.result()) for f in futs)
        return total, _t.perf_counter() - t0, t0

    best = None
    with sched:
        sched.generate([shared[:decode_chunk]], max_new_tokens=2)  # decode program
        # The "original request": publishes the schema prefix the repair
        # wave then re-hits (publish gate needs two sightings).
        warm = [shared + t for t in _mk_prompts(cfg, 2, tail_len, rng)]
        submit_wave(warm, [[] for _ in warm])
        for _ in range(reps):
            # Fresh unique tails per rep (error text differs per repair
            # round); resubmitting identical prompts would measure
            # full-prompt replay caching, not the schema-prefix pattern.
            prompts = [shared + t
                       for t in _mk_prompts(cfg, n_req, tail_len, rng)]
            stamps = [[] for _ in prompts]
            pre = dict(sched.prefix_stats)
            total, wall, t0 = submit_wave(prompts, stamps)
            post = dict(sched.prefix_stats)
            dstats = {k: post[k] - pre[k]
                      for k in ("hits", "misses", "blocks_reused",
                                "reused_tokens")}
            ttfts = [ss[0] - t0 for ss in stamps if ss]
            hm = dstats["hits"] + dstats["misses"]
            cand = {
                "tok_s": total / wall if wall > 0 else 0.0,
                "wall_s": round(wall, 3),
                "requests": n_req,
                "shared_prefix_tokens": shared_len,
                **({"ttft_p50_s": pct(ttfts, 50),
                    "ttft_p95_s": pct(ttfts, 95)} if ttfts else {}),
                **dstats,
                "prefix_hit_rate": round(dstats["hits"] / hm, 4) if hm
                else 0.0,
            }
            if best is None or cand["tok_s"] > best["tok_s"]:
                best = cand
    best["tok_s"] = round(best["tok_s"], 1)
    return best


def _bench_disagg_remote(cfg, params, n_long: int = 3, n_short: int = 3,
                         long_prompt: int = 24, short_prompt: int = 6,
                         long_new: int = 4, short_new: int = 24,
                         reps: int = 2) -> dict:
    """Elastic remote disaggregation (ISSUE 17): a remote-PREFILL fleet
    — a real worker scheduler behind a `ReplicaServer` on a loopback
    socket, PUSHING each packed KV blob to the pool the moment
    `_pack_handoffs` retires it — against the same worker serving
    decode-in-place (mixed role, no migration), over the PR-13 bimodal
    fixture. Committed figures per shape: TTFT/TPOT percentiles +
    decode tok/s (`--compare`-gated), plus the remote shape's push
    ledger: pushed handoffs and bytes, wire→placement p50/p95 ms, and
    the in-place fallback tally — ZERO on a clean wave is the
    structural tier-1 assertion (tests/test_bench.py): a remote-prefill
    request that silently decoded on the worker instead of migrating
    is the bug this pass exists to price. On a shared-core CPU host
    both shapes contend for the same silicon AND the same loopback, so
    the TTFT delta is owed to the chip capture; the structural figures
    are what the CPU pass proves."""
    import time as _t

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.remote import (
        ReplicaServer,
        SocketTransport,
    )
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
        SchedulerPool,
    )

    decode_chunk = 4
    bucket = max(long_prompt, 16)
    max_seq = min(bucket + max(long_new, short_new) + 3 * decode_chunk + 8,
                  cfg.max_seq_len)
    rng = np.random.default_rng(7)
    longs = _mk_prompts(cfg, n_long, long_prompt, rng)
    shorts = _mk_prompts(cfg, n_short, short_prompt, rng)
    wave = []
    for i in range(max(n_long, n_short)):
        if i < n_long:
            wave.append((longs[i], long_new))
        if i < n_short:
            wave.append((shorts[i], short_new))

    def make_replica(role):
        return ContinuousBatchingScheduler(
            cfg, params, num_slots=2, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=0,
            kv_page_size=8, phase_role=role,
        )

    def drive(worker_role, local_role):
        wsched = make_replica(worker_role)
        wsched.start()
        srv = ReplicaServer(wsched)
        local = make_replica(local_role)
        local.warmup(long_prompt)
        local.warmup(short_prompt)
        pool = SchedulerPool(
            [SocketTransport(srv.address, label="r0", rpc_timeout_s=30.0),
             local],
        )
        best = None
        try:
            with pool:
                # Compile both sides outside the timed wave: a remote-
                # prefill warm request pushes through the wire and
                # compiles the local import scatter too. Submitted
                # concurrently so least-loaded placement touches BOTH
                # replicas, not twice the idle one.
                prime = [pool.submit(ids, max_new_tokens=2)
                         for ids, _mn in wave[:2]]
                for f in prime:
                    f.result(timeout=600)
                for _ in range(reps):
                    stamps = [[] for _ in wave]
                    t0 = _t.perf_counter()
                    futs = [
                        pool.submit(ids, max_new_tokens=mn,
                                    on_token=(lambda _t_, ss=ss:
                                              ss.append(_t.perf_counter())))
                        for (ids, mn), ss in zip(wave, stamps)
                    ]
                    total = sum(len(f.result(timeout=600)) for f in futs)
                    wall = _t.perf_counter() - t0
                    ttfts = [s[0] - t0 for s in stamps if s]
                    tpots = [(s[-1] - s[0]) / (len(s) - 1)
                             for s in stamps if len(s) > 1]
                    if best is None or total / wall > best["decode_tok_s"]:
                        best = {
                            "decode_tok_s": total / wall,
                            "wall_s": round(wall, 3),
                            "tokens": total,
                            "ttft_p50_s": round(
                                float(np.percentile(ttfts, 50)), 4),
                            "ttft_p95_s": round(
                                float(np.percentile(ttfts, 95)), 4),
                            "tpot_p50_s": round(
                                float(np.percentile(tpots, 50)), 5),
                            "tpot_p95_s": round(
                                float(np.percentile(tpots, 95)), 5),
                        }
                fl = pool.fleet_stats()
                wh = wsched.handoff_stats or {}
                pump = dict(srv._pump_stats)
        finally:
            srv.close()
            wsched.shutdown()
        best["decode_tok_s"] = round(best["decode_tok_s"], 1)
        if worker_role == "prefill":
            # The push ledger: handoffs streamed through the wire, the
            # wire→placement latency the pump adds on top of the blob
            # pack, and the "no silent fallback" tally — worker-side
            # decode-in-place absorptions, whether at the scheduler
            # (no decode sibling visible) or at the pump (overflow /
            # backpressure). ZERO on a clean wave is the structural
            # contract.
            best["pushed"] = int(fl.get("pushed", 0))
            best["push_bytes"] = int(fl.get("push_bytes", 0))
            best["push_place_p50_ms"] = fl.get("push_place_p50_ms", 0.0)
            best["push_place_p95_ms"] = fl.get("push_place_p95_ms", 0.0)
            best["inplace_fallbacks"] = int(pump.get("inplace", 0)) \
                + int(wh.get("inplace_fallbacks", 0) or 0)
        return best

    remote = drive("prefill", "decode")
    inplace = drive("mixed", "mixed")
    return {
        "requests": len(wave),
        "long": {"n": n_long, "prompt": long_prompt, "max_new": long_new},
        "short": {"n": n_short, "prompt": short_prompt,
                  "max_new": short_new},
        "remote_prefill": remote,
        "inplace": inplace,
        # The headline the chip capture owes: how much TTFT the remote
        # prefill tier buys the decode tier (positive = remote wins).
        "ttft_delta_p50_s": round(
            inplace["ttft_p50_s"] - remote["ttft_p50_s"], 4),
        "speedup": round(
            remote["decode_tok_s"] / inplace["decode_tok_s"], 3
        ) if inplace["decode_tok_s"] else 0.0,
    }


def _bench_multi_model(device_kind) -> dict:
    """Multi-model routing throughput (ISSUE 16): two tiny checkpoints
    co-resident in ONE model-routing SchedulerPool, mixed traffic
    alternating between them from concurrent submitters. Records
    aggregate tok/s plus the per-model split the lsot_model_* families
    export — placements, tokens, and each model's partitioned share of
    the page arena. Random weights, so the number is a ROUTING+SCHEDULER
    overhead figure, not a model-quality one; the leg exists to price
    what co-residency costs versus the single-model scheduler leg."""
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    from llm_based_apache_spark_optimization_tpu.serve.modelpool import (
        ModelSpec,
        build_tiny_model_service,
    )

    n_req = int(os.environ.get("BENCH_MM_REQS", "8"))
    max_new = int(os.environ.get("BENCH_MM_NEW", "24"))
    specs = [ModelSpec("sql", hbm_fraction=0.75),
             ModelSpec("explainer", hbm_fraction=0.25)]
    svc, pool, _reg = build_tiny_model_service(
        specs, num_slots=4, max_new_tokens=max_new,
    )
    try:
        prompt = "SELECT something from the bench table please"
        t0 = _t.perf_counter()

        def one(i):
            model = "sql" if i % 2 == 0 else "explainer"
            return svc.generate(model=model, prompt=f"{prompt} {i}")

        with ThreadPoolExecutor(max_workers=min(8, 2 * n_req)) as ex:
            outs = list(ex.map(one, range(2 * n_req)))
        wall = _t.perf_counter() - t0
        toks = sum(o.output_tokens for o in outs)
        stats = pool.model_stats() or {"models": []}
        per = {
            rec["model"]: {
                "tok_s": round(rec["tokens_total"] / max(wall, 1e-9), 1),
                "placements": rec["placements"],
                "kv_pages_total": rec["kv_pages_total"],
            }
            for rec in stats["models"]
        }
        return {
            "tok_s": round(toks / max(wall, 1e-9), 1),
            "wall_s": round(wall, 2),
            "requests": 2 * n_req,
            "models": per,
            "platform": device_kind,
        }
    finally:
        pool.shutdown()


def _bench_ragged(cfg, params, *, slots, decode_chunk) -> dict:
    """Unified ragged serving A/B (ISSUE 19): the SAME mixed
    prefill+decode traffic through the paged scheduler twice — once with
    phase alternation (the LSOT_RAGGED=0 control) and once through the
    one-launch mixed-round program (ragged=True) — recording TTFT
    p50/p95 and aggregate tok/s per arm. Full-contention submit waves
    keep admissions landing while slots decode, which is exactly the
    alternation tax the ragged program deletes: under alternation every
    admission stalls all live decode rows for a prefill round; under
    ragged the chunk rides the decode launch. Token parity between the
    arms is pinned by tier-1 (tests/test_ragged_sched.py) — this pass
    prices it. `mixed_rounds` proves the ragged arm actually served
    mixed launches rather than degenerating to alternation."""
    import math
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    prompt_len = int(os.environ.get("BENCH_RAGGED_PROMPT", "64"))
    max_new = int(os.environ.get("BENCH_RAGGED_NEW", "32"))
    n_req = int(os.environ.get("BENCH_RAGGED_REQS", str(4 * slots)))
    # The ragged program unrolls prompt chunks into the decode launch,
    # so its prompt_bucket caps at the kernel unroll window (32). Give
    # the CONTROL the same bucket: otherwise the arms chunk prompts
    # differently and the A/B measures admission policy, not launch
    # structure.
    bucket = min(32, prompt_len, max(1, cfg.max_seq_len // 2))
    max_seq = min(cfg.max_seq_len,
                  prompt_len + max_new + 4 * decode_chunk + 2 * bucket)
    rng = np.random.default_rng(7)
    reqs = _mk_prompts(cfg, n_req, prompt_len, rng)

    def pctile(vals, q):
        return round(vals[min(len(vals) - 1,
                              max(0, math.ceil(q * len(vals)) - 1))], 3)

    def arm(ragged: bool) -> dict:
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_seq=max_seq,
            prompt_bucket=bucket, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=0,
            ragged=ragged,
        )
        sched.warmup(prompt_len)
        ttfts: list = []

        def one(r):
            s0 = _t.perf_counter()
            first: list = []

            def on_tok(_tok):
                if not first:
                    first.append(_t.perf_counter())

            res = sched.submit(r, max_new_tokens=max_new,
                               on_token=on_tok).result()
            if first:
                ttfts.append(first[0] - s0)
            return len(res)

        with sched:
            # Pre-wave: compiles the decode program and (ragged arm) the
            # mixed-round variants the timed wave's chunk sizes form.
            sched.generate(reqs[:2], max_new_tokens=max_new)
            ttfts.clear()
            t0 = _t.perf_counter()
            with ThreadPoolExecutor(max_workers=n_req) as pool:
                total = sum(pool.map(one, reqs))
            dt = _t.perf_counter() - t0
        mixed_rounds = ((sched.perf_stats or {}).get("phases", {})
                        .get("mixed", {}).get("rounds", 0))
        res = {"tok_s": round(total / dt, 1), "wall_s": round(dt, 2),
               "mixed_rounds": mixed_rounds}
        if ttfts:
            ttfts.sort()
            res["ttft_p50_s"] = pctile(ttfts, 0.5)
            res["ttft_p95_s"] = pctile(ttfts, 0.95)
        return res

    out = {"requests": n_req, "prompt": prompt_len, "new": max_new,
           "prompt_bucket": bucket, "slots": slots,
           "alternating": arm(False), "ragged": arm(True)}
    alt_ts = out["alternating"]["tok_s"]
    if alt_ts:
        out["ragged_speedup"] = round(out["ragged"]["tok_s"] / alt_ts, 3)
    return out


def _bench_scheduler(cfg, params, prompt_len, max_new, batch,
                     kv_quant=None, reps=None, n_req=None,
                     spec_draft=None) -> dict:
    """Continuous-batching scheduler throughput: n_req requests from
    concurrent submitter threads share one persistent-cache decode batch —
    the number BENCH_r02 never recorded (VERDICT r2 missing #4). Also the
    shared engine for the 7b_sched leg (kv_quant/reps/n_req kwargs).

    A second pass with speculative_draft=BENCH_SCHED_SPEC (default 4, 0
    disables) reruns the same greedy workload on a speculative scheduler
    and records tok/s plus the acceptance counters (VERDICT r4 next #5) —
    random-weight prompts accept ~nothing, so the committed number is the
    instrument proof and the overhead floor; real SQL checkpoints are
    where tokens_per_round > 1.6 should appear."""
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    from llm_based_apache_spark_optimization_tpu.engine.kvcache import bucket_len

    # Serving-tuned defaults, swept on v5e (bench-1b, 128/64 workload):
    # slots = 2x the engine batch — decode is weight-streaming-bound, so
    # doubling the shared batch nearly doubles aggregate tok/s (1157 ->
    # 1918) while p50 latency under full contention grows ~40%; past 4x
    # the latency cost outweighs the gain for this workload.
    slots = int(os.environ.get("BENCH_SCHED_SLOTS", str(2 * batch)))
    n_req = n_req or 4 * slots
    # Throughput-leaning chunk: each decode round costs one host<->device
    # sync, amortized over chunk*slots tokens; 32 measured best at saturation (and better p50
    # than 16 — fewer sync stalls) vs the scheduler's latency-leaning
    # interactive default of 8.
    decode_chunk = int(os.environ.get("BENCH_SCHED_CHUNK", "32"))
    # >= 2*prompt so the scheduler's internal prompt_bucket = min(bucket,
    # max_seq//2) clamp doesn't double-bucket the prompt and reject requests.
    max_seq = min(max(2 * prompt_len, prompt_len + max_new + 3 * decode_chunk),
                  cfg.max_seq_len)
    # prefix_cache_blocks=0: best-of-reps resubmits the same prompts, and a
    # warm prefix cache would skip their prefills in later reps — the bench
    # must measure cold-path scheduler throughput, not cache reuse.
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=slots, max_seq=max_seq,
        prompt_bucket=prompt_len, stop_ids=(-1,), decode_chunk=decode_chunk,
        prefix_cache_blocks=0, kv_quant=kv_quant,
    )
    # Derive the admissible budget from the scheduler's OWN bound (its
    # resolved prompt_bucket and harvest lag), not a hand-mirrored copy.
    overshoot = sched.overshoot
    max_new = min(
        max_new,
        sched.max_seq - 1 - overshoot - bucket_len(prompt_len,
                                                   sched.prompt_bucket),
    )
    if max_new < 1:
        return {"skipped": f"no decode room at prompt={prompt_len} in "
                           f"max_seq={sched.max_seq}"}
    rng = np.random.default_rng(1)
    reqs = _mk_prompts(cfg, n_req, prompt_len, rng)
    reps = reps or int(os.environ.get("BENCH_SCHED_REPS", "2"))

    def timed_wave(s, wave_reqs):
        """One full-contention submit wave: (toks, wall_s, sorted lats,
        sorted ttfts). ONE definition for the vanilla/speculative/prefix
        passes — a measurement fix must apply to all three or their
        cross-comparison skews."""
        lats: list = []
        ttfts: list = []

        def one(r):
            s0 = _t.perf_counter()
            first: list = []

            def on_tok(_tok):
                if not first:
                    first.append(_t.perf_counter())

            res = s.submit(r, max_new_tokens=max_new,
                           on_token=on_tok).result()
            lats.append(_t.perf_counter() - s0)
            if first:
                ttfts.append(first[0] - s0)
            return res

        t0 = _t.perf_counter()
        with ThreadPoolExecutor(max_workers=len(wave_reqs)) as pool:
            toks = sum(len(r) for r in pool.map(one, wave_reqs))
        return toks, _t.perf_counter() - t0, sorted(lats), sorted(ttfts)

    best_tok_s, best_dt = 0.0, 0.0
    # Deterministically compile every (bucket, k-bucket) prefill variant the
    # timed run can form (admission bursts group up to kmax; retirement
    # waves re-admit in smaller groups) — warming through generate() races
    # the worker's grouping and can leave variants to compile mid-timing.
    sched.warmup(prompt_len)
    with sched:
        sched.generate(reqs[:2], max_new_tokens=max_new)  # decode program
        # Best-of-reps: host-clock timings vary from run to run.
        best_lats: list = []
        best_ttfts: list = []
        for _ in range(reps):
            toks, dt, lats, ttfts = timed_wave(sched, reqs)
            if toks / dt > best_tok_s:
                best_tok_s, best_dt = toks / dt, dt
                best_lats, best_ttfts = lats, ttfts
    # Per-request end-to-end latency under full contention (submit ->
    # result, queueing included): the metric BASELINE.json's north star is
    # denominated in alongside aggregate tok/s.
    out = {
        "tok_s": round(best_tok_s, 1),
        "requests": n_req,
        "slots": slots,
        "wall_s": round(best_dt, 2),
    }
    import math

    def pctile(vals, q):
        # Nearest-rank percentiles (ceil(q*n)-1), clamped for tiny n.
        return round(vals[min(len(vals) - 1,
                              max(0, math.ceil(q * len(vals)) - 1))], 3)

    if best_lats:
        out["p50_latency_s"] = pctile(best_lats, 0.5)
        out["p95_latency_s"] = pctile(best_lats, 0.95)
    # Time-to-first-token under full contention: queueing + admission
    # prefill + first harvest — the latency streaming clients actually feel.
    if best_ttfts:
        out["ttft_p50_s"] = pctile(best_ttfts, 0.5)
        out["ttft_p95_s"] = pctile(best_ttfts, 0.95)
    # Liveness tax: per-round heartbeat cost (ns) beside the rounds the
    # timed run actually harvested — nanoseconds against multi-ms rounds.
    out["watchdog"] = {
        **_watchdog_overhead(sched=sched),
        "rounds_harvested": sched.heartbeat.rounds,
    }
    # Observability tax (ISSUE 6): flight-recorder append + unsampled
    # tracing cost per round, as ns AND as % of this run's measured round
    # cadence — the acceptance bar is <1% with sampling off (the ISSUE-12
    # roofline-ledger stamp now counts inside the same budget).
    out["observability"] = _obs_overhead(sched=sched)
    # Per-round roofline ledger (ISSUE 12, utils/perfmodel.py): the
    # scheduler's OWN per-phase attribution over the run just measured —
    # the same numbers serving.perf exports live, committed beside the
    # tok/s they explain (decode MFU / HBM-util enter the --compare
    # regression gate via the `mfu`/`hbm_util` leaf keys).
    perf_view = getattr(sched, "perf_stats", None)
    if perf_view:
        out["perf"] = perf_view

    draft = (int(os.environ.get("BENCH_SCHED_SPEC", "4"))
             if spec_draft is None else spec_draft)
    if draft > 0:
        spec_sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_seq=max_seq,
            prompt_bucket=prompt_len, stop_ids=(-1,),
            decode_chunk=decode_chunk, kv_quant=kv_quant,
            speculative_draft=draft,
        )
        from llm_based_apache_spark_optimization_tpu.engine.speculative import (
            verify_cost_ratio,
        )

        spec_sched.warmup(prompt_len)
        spec_tok_s, rounds, toks_sp = 0.0, 0, 0
        with spec_sched:
            spec_sched.generate(reqs[:2], max_new_tokens=max_new)
            # Same best-of-reps protocol as the vanilla pass above — a
            # single run would bias the spec-vs-vanilla comparison either
            # way. Counter deltas bracket
            # exactly the best rep's window (the warmup generate also
            # harvests verify rounds, so lifetime totals would overcount).
            for _ in range(reps):
                pre = dict(spec_sched.speculation_stats or {})
                stoks, sdt, _, _ = timed_wave(spec_sched, reqs)
                post = dict(spec_sched.speculation_stats or {})
                if stoks / sdt > spec_tok_s:
                    spec_tok_s = stoks / sdt
                    rounds = (post.get("verify_rounds", 0)
                              - pre.get("verify_rounds", 0))
                    toks_sp = (post.get("tokens_emitted", 0)
                               - pre.get("tokens_emitted", 0))
        tpr = toks_sp / rounds if rounds else 0.0
        # Cost model priced at THIS run's draft length (ADVICE r5 #3) AND
        # model shape/weight bits (ROADMAP carried-over: the 1B-anchored
        # slope mispriced 7B/int4 drafts), not the old D=8-only constant.
        from llm_based_apache_spark_optimization_tpu.engine.speculative import (
            infer_weight_bits,
        )

        ratio = verify_cost_ratio(draft, cfg=cfg,
                                  weight_bits=infer_weight_bits(params))
        out["speculative"] = {
            "draft": draft,
            "tok_s": round(spec_tok_s, 1),
            "verify_rounds": rounds,
            "tokens_emitted": toks_sp,
            "tokens_per_round": round(tpr, 3),
            "verify_cost_ratio": round(ratio, 3),
            "est_speedup_vs_vanilla": round(tpr / ratio, 3),
        }
        if (os.environ.get("BENCH_SPEC_CONSTRAIN", "1") == "1"
                and cfg.vocab_size >= 259):
            # Constrained fixture traffic through a speculative scheduler:
            # the ISSUE-4 acceptance number. Random-token prompts cannot
            # say anything about the grammar-masked hot path (the mask
            # forces identifier/keyword runs that prompt lookup can copy
            # from the DDL), so this pass drives byte-tokenized fixture
            # SQL + schema prompts under the schema-locked taxi grammar
            # and reports the CONSTRAINED class's tokens/round from the
            # per-class speculation counters. Instrument pass, never
            # fatal to the leg.
            try:
                out["speculative"]["constrained"] = _spec_constrained_pass(
                    cfg, params, slots, max_seq, prompt_len, decode_chunk,
                    kv_quant, draft, ratio,
                )
            except Exception as e:  # noqa: BLE001 — keep the leg's numbers
                out["speculative"]["constrained"] = {"error": str(e)[:200]}
        if (os.environ.get("BENCH_SPEC_SAMPLED", "1") == "1"
                and cfg.vocab_size >= 259):
            # Sampled fixture traffic through the same speculative
            # scheduler: the ISSUE-8 acceptance number. temperature>0
            # requests ride the rejection-sampling verify path, and the
            # SAMPLED class of the per-class speculation counters prices
            # whether speculating on sampled traffic pays. Instrument
            # pass, never fatal to the leg.
            try:
                out["speculative"]["sampled"] = _spec_sampled_pass(
                    cfg, params, slots, max_seq, prompt_len, decode_chunk,
                    kv_quant, draft, ratio,
                )
            except Exception as e:  # noqa: BLE001 — keep the leg's numbers
                out["speculative"]["sampled"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_POOL", "1") == "1" and kv_quant is None:
        # Fleet-routing pass (ISSUE 9): round-robin vs least-loaded pool
        # tok/s under skewed prompt lengths — the committed proof that
        # load-aware placement beats the blind rotation on the workload
        # shape it was built for. Instrument pass, never fatal to the
        # leg. (Skipped under kv_quant to keep the 7b_sched slice lean,
        # like the prefix pass.)
        try:
            out["fleet_routing"] = _bench_pool_routing(cfg, params)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["fleet_routing"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_DISAGG", "1") == "1" and kv_quant is None:
        # Disaggregated-serving pass (ISSUE 13): mixed fleet vs
        # phase-split fleet at equal replica count over a bimodal
        # long-prompt-short-gen / short-prompt-long-gen fixture — TTFT/
        # TPOT percentiles + decode tok/s per shape, handoff tally as
        # the proof the split path served. Instrument pass, never fatal
        # to the leg; --compare gates its decode_tok_s keys like every
        # tracked metric.
        try:
            out["disagg"] = _bench_disagg(cfg, params)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["disagg"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_DISAGG_REMOTE", "1") == "1" \
            and kv_quant is None:
        # Elastic remote disaggregation pass (ISSUE 17): remote-PREFILL
        # worker behind a real loopback ReplicaServer pushing packed KV
        # blobs to a local decode replica, vs the same worker serving
        # decode-in-place — TTFT/TPOT percentiles + decode tok/s per
        # shape, push ledger (count/bytes/wire→placement p50/p95) and
        # the zero-in-place-fallback proof. Instrument pass, never
        # fatal; --compare gates its decode_tok_s keys like every
        # tracked metric.
        try:
            out["disagg_remote"] = _bench_disagg_remote(cfg, params)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["disagg_remote"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_QOS", "1") == "1" and kv_quant is None:
        # Multi-tenant QoS pass (ISSUE 18): WFQ scheduler serving a
        # batch-class storm beside interactive probes — per-class TTFT/
        # TPOT p50/p95 + aggregate tok/s, riding --compare via the
        # nested tok_s leaf. Instrument pass, never fatal to the leg;
        # skipped under kv_quant to keep the 7b_sched slice lean.
        try:
            out["qos"] = _bench_qos(cfg, params)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["qos"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_RAGGED", "1") == "1" and kv_quant is None:
        # Unified-ragged A/B pass (ISSUE 19): mixed prefill+decode
        # traffic through one-launch mixed rounds vs the alternating
        # control — TTFT p50/p95 + tok/s per arm, riding --compare via
        # the nested tok_s leaves. Instrument pass, never fatal to the
        # leg; skipped under kv_quant to keep the 7b_sched slice lean.
        try:
            out["ragged"] = _bench_ragged(cfg, params, slots=slots,
                                          decode_chunk=decode_chunk)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["ragged"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_REPAIR", "1") == "1" and kv_quant is None:
        # Repair-wave pass (ISSUE 20): correlated short-gen requests
        # sharing the failed request's schema prefix, riding tenant
        # "repair" / QoS class `replay` — TTFT p50/p95 + prefix-hit-rate
        # of the self-healing loop's serving shape. Instrument pass,
        # never fatal to the leg; skipped under kv_quant to keep the
        # 7b_sched slice lean.
        try:
            out["repair"] = _bench_repair(cfg, params)
        except Exception as e:  # noqa: BLE001 — keep the leg's numbers
            out["repair"] = {"error": str(e)[:200]}

    if os.environ.get("BENCH_SCHED_PREFIX", "1") == "1" and kv_quant is None:
        # Warm-prefix pass: the reference's ACTUAL serving pattern is the
        # same schema/system prompt on every request (SURVEY §2.2's
        # NL→SQL contract), which is exactly what the prefix cache exists
        # for — and it had no committed number. Requests share a
        # block-aligned prefix with unique tails; within one wave the
        # publish gate sees request 1, publishes on request 2, and 3..n
        # skip their shared-prefix prefills. Reported against the cold
        # main run's ttft/tok_s above. (Skipped under kv_quant only to
        # keep the 7b_sched slice lean — the cache composes with int8 KV.)
        psched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_seq=max_seq,
            prompt_bucket=prompt_len, stop_ids=(-1,),
            decode_chunk=decode_chunk, prefix_cache_blocks=256,
        )
        psched.warmup(prompt_len)
        pblock = psched._pblock
        shared_len = max(pblock, (prompt_len // 2) // pblock * pblock)
        # Reused-prefix admissions prefill only the TAIL, whose smaller
        # bucket has its own compiled variants — warm those too or the
        # timed wave compiles mid-flight and reads slower than cold.
        if prompt_len - shared_len > 0:
            psched.warmup(prompt_len - shared_len)
        rng2 = np.random.default_rng(9)
        shared = _mk_prompts(cfg, 1, shared_len, rng2)[0]

        def fresh_wave():
            # FRESH unique tails every rep: resubmitting identical prompts
            # would let the publish gate cache the tails too from rep 2 on,
            # and the "shared-prefix" number would silently measure
            # full-prompt replay caching instead of the schema-prefix
            # serving pattern it claims to model.
            tails = _mk_prompts(cfg, n_req, prompt_len - shared_len, rng2)
            return [shared + t for t in tails]

        ptok_s, best_ttfts2 = 0.0, []
        best_stats = {"hits": 0, "misses": 0, "blocks_reused": 0,
                      "reused_tokens": 0}
        best_saved = 0.0
        warm2 = [shared + t for t in
                 _mk_prompts(cfg, 2, prompt_len - shared_len, rng2)]
        with psched:
            psched.generate(warm2, max_new_tokens=max_new)
            # Best-of-reps like every other pass (one definition:
            # timed_wave); the shared prefix is published by the generate
            # above, so every rep measures the steady warm state. Counters
            # are per-rep deltas so they describe the reported wave —
            # incl. the ISSUE-14 telemetry (misses, reused tokens, priced
            # prefill savings), all read through the locked prefix_stats/
            # prefix_telemetry snapshots so the brackets are coherent.
            for _ in range(reps):
                pre = dict(psched.prefix_stats)
                pre_saved = (psched.prefix_telemetry
                             or {}).get("prefill_s_saved", 0.0)
                ptoks, pdt, _, ttfts2 = timed_wave(psched, fresh_wave())
                post = dict(psched.prefix_stats)
                post_saved = (psched.prefix_telemetry
                              or {}).get("prefill_s_saved", 0.0)
                if ptoks / pdt > ptok_s:
                    ptok_s, best_ttfts2 = ptoks / pdt, ttfts2
                    best_stats = {
                        k: post[k] - pre[k]
                        for k in ("hits", "misses", "blocks_reused",
                                  "reused_tokens")
                    }
                    best_saved = post_saved - pre_saved
        hm = best_stats["hits"] + best_stats["misses"]
        out["prefix_cache"] = {
            "shared_prefix_tokens": shared_len,
            "tok_s": round(ptok_s, 1),
            **({"ttft_p50_s": pctile(best_ttfts2, 0.5),
                "ttft_p95_s": pctile(best_ttfts2, 0.95)}
               if best_ttfts2 else {}),
            **best_stats,
            # The --compare-gated cache-health figure (ISSUE 14): the
            # reported wave's hit rate. A cache regression (publish gate
            # broken, eviction storm, digest churn) drops this loudly
            # even when tok/s hides it behind host noise.
            "prefix_hit_rate": round(best_stats["hits"] / hm, 4) if hm
            else 0.0,
            "prefill_s_saved": round(best_saved, 6),
        }
    return out


def _spec_class_wave(cfg, params, slots, max_seq, prompt_len, decode_chunk,
                     kv_quant, draft, ratio, *, stop_ids, class_path,
                     submit_kw, min_new=1) -> dict:
    """Shared machinery of the per-class speculative fixture waves
    (`_spec_constrained_pass` / `_spec_sampled_pass`): copy-heavy
    fixture-shaped prompts (byte-tokenized taxi DDL + the case's
    expected SQL, so prompt lookup has real identifiers to copy), a
    warm-then-timed full-contention wave, and a pre/post delta of ONE
    class of the speculation counters. `class_path` walks
    speculation_stats to the class (e.g. ("by_class", "constrained"));
    `submit_kw(i)` yields the per-request submit kwargs that define the
    class. The first two requests run OUTSIDE the timed window so
    class-specific compiles (a constrained admission installs the
    grammar tables, which retraces the decode program) never land
    mid-wave."""
    import time as _t
    from concurrent.futures import ThreadPoolExecutor

    from llm_based_apache_spark_optimization_tpu.engine.kvcache import (
        bucket_len,
    )
    from llm_based_apache_spark_optimization_tpu.evalh.fixtures import (
        FOUR_QUERY_SUITE,
        TAXI_DDL_SYSTEM,
    )
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    tok = ByteTokenizer()
    # Room check BEFORE constructing the scheduler (whose __init__
    # allocates the slots x max_seq KV cache): mirrors the speculative
    # overshoot property ((harvest_lag+1)*(D+1) + D, lag 1) and the
    # prompt-bucket clamp — keep in sync with serve/scheduler.py.
    overshoot = 2 * (draft + 1) + draft
    pbucket = min(prompt_len, max(1, max_seq // 2))
    room = max_seq - 1 - overshoot - bucket_len(prompt_len, pbucket)
    max_new = max(min_new, min(64, room))
    if max_new > room:
        return {"skipped": f"no decode room (need {min_new}, have {room})"}
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=slots, max_seq=max_seq,
        prompt_bucket=prompt_len, stop_ids=stop_ids,
        decode_chunk=decode_chunk, kv_quant=kv_quant,
        speculative_draft=draft,
    )
    prompts = []
    for case in FOUR_QUERY_SUITE * max(1, (2 * slots) // 4):
        text = (TAXI_DDL_SYSTEM + " " + case.expected_sql + "\nSQL: ")
        prompts.append(tok.encode(text, add_bos=True)[-prompt_len:])

    def cls_stats() -> dict:
        node = dict(sched.speculation_stats or {})
        for key in class_path:
            node = dict(node.get(key, {}) or {})
        return node

    sched.warmup(prompt_len)
    with sched:
        for f in [sched.submit(p, max_new_tokens=max_new, **submit_kw(i))
                  for i, p in enumerate(prompts[:2])]:
            f.result()
        pre = cls_stats()
        t0 = _t.perf_counter()
        with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
            toks_out = sum(len(r) for r in pool.map(
                lambda ip: sched.submit(
                    ip[1], max_new_tokens=max_new, **submit_kw(ip[0])
                ).result(),
                enumerate(prompts),
            ))
        dt = _t.perf_counter() - t0
        post = cls_stats()
    rounds = post.get("verify_rounds", 0) - pre.get("verify_rounds", 0)
    toks_sp = post.get("tokens_emitted", 0) - pre.get("tokens_emitted", 0)
    tpr = toks_sp / rounds if rounds else 0.0
    return {
        "requests": len(prompts),
        "tok_s": round(toks_out / dt, 1) if dt > 0 else 0.0,
        "verify_rounds": rounds,
        "tokens_emitted": toks_sp,
        "tokens_per_round": round(tpr, 3),
        "est_speedup_vs_vanilla": round(tpr / ratio, 3),
    }


def _spec_constrained_pass(cfg, params, slots, max_seq, prompt_len,
                           decode_chunk, kv_quant, draft, ratio) -> dict:
    """Grammar-constrained speculative wave: fixture NL→SQL traffic
    decoded under the schema-locked grammar on a speculative scheduler.
    Returns the constrained class's acceptance (tokens/round is the
    go/no-go number for --speculative on the constrained hot path).
    Requires cfg.vocab_size >= the byte tokenizer's 259 (every bench
    config satisfies this)."""
    from llm_based_apache_spark_optimization_tpu.constrain import (
        get_constraint,
    )
    from llm_based_apache_spark_optimization_tpu.evalh.fixtures import (
        TAXI_COLUMNS,
    )
    from llm_based_apache_spark_optimization_tpu.tokenizer import (
        ByteTokenizer,
    )

    tok = ByteTokenizer()
    # The scheduler must KNOW the stop id: constrained completions close
    # with eos, and an unstopped slot would spin at the accepting state
    # for the whole budget.
    cm = get_constraint({"table": "taxi", "columns": list(TAXI_COLUMNS)},
                        tok, (tok.eos_id,))
    return _spec_class_wave(
        cfg, params, slots, max_seq, prompt_len, decode_chunk, kv_quant,
        draft, ratio, stop_ids=(tok.eos_id,),
        class_path=("by_class", "constrained"),
        submit_kw=lambda i: {"constraint": cm},
        min_new=cm.min_new_tokens,
    )


def _spec_sampled_pass(cfg, params, slots, max_seq, prompt_len,
                       decode_chunk, kv_quant, draft, ratio) -> dict:
    """Sampled-traffic speculative wave (ISSUE 8): the same copy-heavy
    fixture prompts decoded at temperature>0 through the
    rejection-sampling verify path. Reports the SAMPLED class's
    acceptance — tokens/round > 1 means drafted tokens are clearing the
    accept test (u < target mass) and sampled traffic is getting real
    multi-token rounds. Random weights put acceptance near the floor (a
    draft's target mass is ~uniform); real checkpoints on copy-heavy
    NL→SQL traffic are where the number climbs toward greedy's."""
    from llm_based_apache_spark_optimization_tpu.ops.sampling import (
        SamplingParams,
    )

    # Moderate temperature: enough entropy to be genuinely sampled,
    # sharp enough that copy-heavy drafts keep non-trivial target mass.
    sp = SamplingParams(temperature=0.7)
    out = _spec_class_wave(
        cfg, params, slots, max_seq, prompt_len, decode_chunk, kv_quant,
        draft, ratio, stop_ids=(-1,),
        class_path=("by_sampling", "sampled"),
        submit_kw=lambda i: {"sampling": sp, "seed": i},
    )
    if "skipped" not in out:
        out["temperature"] = sp.temperature
    return out


def _detail(cfg, eng, prompts, prompt_len, max_new, batch, full_dt,
            params, quant, device_kind) -> dict:
    """Prefill/decode split + roofline placement.

    Prefill time is approximated by a generate call with max_new_tokens=1
    (prefill + first-token sample, zero decode-loop steps); decode time is
    the remainder of the full run. FLOP model: 2·P per token for the dense
    matmuls plus 4·S·L·heads·head_dim for attention score/value contractions.
    Decode HBM traffic per step: the full weight set streamed once plus the
    K/V cache read at the current context length.
    """
    eng.generate(prompts, max_new_tokens=1)  # compile the prefill-only variant
    t_pre = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        eng.generate(prompts, max_new_tokens=1)
        t_pre = min(t_pre, time.perf_counter() - t0)
    decode_dt = max(full_dt - t_pre, 1e-9)
    decode_steps = max_new - 1
    decode_tok_s = batch * decode_steps / decode_dt

    # Shared analytic models (utils/perfmodel.py): the SAME formulas the
    # live scheduler ledger stamps rounds with — factored out in ISSUE 12
    # so bench artifacts and serving.perf can never disagree.
    from llm_based_apache_spark_optimization_tpu.utils import perfmodel

    s_avg = prompt_len + max_new // 2
    flops_per_tok = perfmodel.flops_per_token(cfg, s_avg)
    prefill_flops = perfmodel.prefill_flops(cfg, batch, prompt_len)

    pbytes = _param_bytes(params)
    itemsize = 2  # bf16 cache
    bytes_per_step = perfmodel.decode_step_bytes(cfg, batch, s_avg, pbytes,
                                                 itemsize=itemsize)

    peak_flops, peak_bw = _peak_for(device_kind, quant)
    out = {
        "prefill_s": round(t_pre, 4),
        "decode_s": round(decode_dt, 4),
        "decode_tok_s": round(decode_tok_s, 1),
        "prefill_tok_s": round(batch * prompt_len / t_pre, 1),
        "param_bytes": pbytes,
        "quant": quant or "bf16",
    }
    decode_flop_s = batch * decode_steps * flops_per_tok / decode_dt
    prefill_flop_s = prefill_flops / t_pre
    decode_bw = bytes_per_step * decode_steps / decode_dt
    out["decode_achieved_tflop_s"] = round(decode_flop_s / 1e12, 3)
    out["prefill_achieved_tflop_s"] = round(prefill_flop_s / 1e12, 3)
    out["decode_hbm_gb_s"] = round(decode_bw / 1e9, 1)
    if peak_flops:
        out["decode_mfu"] = round(decode_flop_s / peak_flops, 4)
        out["prefill_mfu"] = round(prefill_flop_s / peak_flops, 4)
        out["decode_hbm_util"] = round(decode_bw / peak_bw, 4)

    # Device-time variants (trace-parsed): the wall numbers above include a
    # per-call host<->device dispatch+sync floor that dominates short
    # programs. jax.profiler's chrome trace records the real device op
    # timeline; utils/traceprof parses it directly (the tensorboard
    # converter is broken in this image).
    try:
        from llm_based_apache_spark_optimization_tpu.utils.traceprof import (
            device_trace,
        )

        with device_trace() as tr:
            eng.generate(prompts, max_new_tokens=1)
        prefill_dev = tr.device_time_s()
        with device_trace() as tr2:
            eng.generate(prompts, max_new_tokens=max_new)
        full_dev = tr2.device_time_s()
        # Guard against silently empty/partial traces (load_dir returns 0
        # rather than raising): a 0 or inverted pair would otherwise turn
        # decode_dev into 1e-9 and emit an astronomical util.
        if prefill_dev > 0 and full_dev > prefill_dev:
            decode_dev = full_dev - prefill_dev
            out["prefill_device_s"] = round(prefill_dev, 4)
            out["decode_device_s"] = round(decode_dev, 4)
            if peak_flops:
                out["prefill_device_mfu"] = round(
                    prefill_flops / prefill_dev / peak_flops, 4
                )
                out["decode_device_hbm_util"] = round(
                    bytes_per_step * decode_steps / decode_dev / peak_bw, 4
                )
        else:
            out["trace_error"] = (
                f"empty/partial device trace (prefill {prefill_dev:.4f}s, "
                f"full {full_dev:.4f}s)"
            )
    except Exception as e:  # profiling must never kill the artifact
        out["trace_error"] = str(e)[:200]
    return out


# --------------------------------------------------------------------------
# Regression gate: bench.py --compare LAST.json [NEW.json]
# --------------------------------------------------------------------------

#: Higher-is-better metric keys the compare gate tracks wherever they
#: appear in an artifact: decode/aggregate throughputs, speculative
#: acceptance, and (ISSUE 12) the roofline-ledger utilization figures —
#: a decode-MFU or HBM-util drop at flat tok/s means the analytic model
#: or the hardware placement regressed, and the gate must say so. The
#: scheduler leg's warm-prefix `prefix_hit_rate` (ISSUE 14) rides the
#: same gate: a cache regression fails loudly beside tok/s.
#: Matched by full path, so "scheduler.tok_s" only ever compares against
#: "scheduler.tok_s" and "perf.phases.decode.mfu" against itself.
_COMPARE_KEYS = ("value", "tok_s", "decode_tok_s", "tokens_per_round",
                 "mfu", "hbm_util", "decode_mfu", "decode_hbm_util",
                 "prefix_hit_rate")


def _collect_compare_metrics(obj, path="") -> "dict[str, float]":
    """Flatten an artifact to {dotted.path: value} for every numeric leaf
    whose key is a tracked metric (lists index numerically)."""
    out: "dict[str, float]" = {}
    if isinstance(obj, dict):
        items = obj.items()
    elif isinstance(obj, list):
        items = ((str(i), v) for i, v in enumerate(obj))
    else:
        return out
    for k, v in items:
        p = f"{path}.{k}" if path else str(k)
        if isinstance(v, (dict, list)):
            out.update(_collect_compare_metrics(v, p))
        elif k in _COMPARE_KEYS and isinstance(v, (int, float)):
            out[p] = float(v)
    return out


def compare_artifacts(old: dict, new: dict,
                      tolerance: float = 0.10) -> "list[str]":
    """Regressions: tracked metrics present in BOTH artifacts where the
    new value dropped more than `tolerance` below the old. Metrics only
    one side has (new legs, skipped legs) are not regressions — the gate
    flags decay, not coverage drift. A metric that COLLAPSED to zero in
    the new artifact (e.g. a failed leg that emitted {"value": 0.0,
    "error": ...}) is decay, not a skipped leg — it must fail the gate,
    which is why the new side keeps non-positive values."""
    olds = _collect_compare_metrics(old)
    news = _collect_compare_metrics(new)
    regressions = []
    for p, ov in sorted(olds.items()):
        nv = news.get(p)
        if ov <= 0 or nv is None or nv >= (1.0 - tolerance) * ov:
            continue
        regressions.append(
            f"{p}: {ov:g} -> {nv:g} ({(nv / ov - 1.0) * 100:+.1f}%)"
        )
    return regressions


def compare_main(argv: "list[str]") -> int:
    """`bench.py --compare LAST.json [NEW.json]`: the FlashInfer-Bench
    regression gate — exits NON-ZERO when any tracked decode-throughput
    or speculative-acceptance metric regresses more than
    BENCH_COMPARE_TOL (default 10%) vs the LAST committed artifact.

    With one file, runs the bench NOW (outer orchestration) and gates
    its final artifact; with two files, pure offline compare — a CI lane
    needs no chip at all. Artifacts are the bench's own stdout JSONL
    (last line = richest) or a CI capture wrapper (_load_artifact reads
    both)."""
    args = [a for a in argv[1:] if a != "--compare"]
    if not args:
        print("usage: bench.py --compare LAST.json [NEW.json]",
              file=sys.stderr)
        return 2
    tol = float(os.environ.get("BENCH_COMPARE_TOL", "0.10"))
    old = _load_artifact(args[0])
    if old is None:
        print(f"bench[compare]: no JSON artifact in {args[0]}",
              file=sys.stderr)
        return 2
    if len(args) > 1:
        new = _load_artifact(args[1])
        if new is None:
            print(f"bench[compare]: no JSON artifact in {args[1]}",
                  file=sys.stderr)
            return 2
    else:
        rc = inner() if os.environ.get("BENCH_INNER") == "1" else outer()
        if rc != 0 or not _EMITTED:
            print("bench[compare]: fresh run produced no artifact",
                  file=sys.stderr)
            return rc or 2
        new = _EMITTED[-1]
    # Same-environment guard: a CPU-lane artifact gated against a chip
    # baseline reads as a ~99% "regression" when the real difference is
    # the platform. Both artifacts carry the platform they measured on —
    # a mismatch is an environment problem, reported as its own exit code
    # so CI can tell it from decay.
    oplat, nplat = old.get("platform"), new.get("platform")
    if oplat and nplat and oplat != nplat:
        print(f"bench[compare]: environment mismatch — baseline measured "
              f"on {oplat!r}, new artifact on {nplat!r} (the CPU lane?); "
              f"refusing to gate throughput across "
              f"platforms", file=sys.stderr)
        return 3
    regressions = compare_artifacts(old, new, tol)
    if regressions:
        print(f"bench[compare]: {len(regressions)} regression(s) past "
              f"{tol:.0%}:", file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        return 1
    print(f"bench[compare]: no tracked metric regressed past {tol:.0%}",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    if "--compare" in sys.argv:
        sys.exit(compare_main(sys.argv))
    if os.environ.get("BENCH_INNER") == "1":
        sys.exit(inner())
    sys.exit(outer())
