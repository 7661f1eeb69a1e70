#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # one host with four: tp=4 and dp=4
                                      # against the one-chip scheduler
    python chip_smoke.py --rehearse   # CPU rehearsal at the TINY shape

Drives the main serving path once, through the entry points a user calls:
the assembly `python -m …app --api --backend checkpoint` builds
(`app.__main__.build_app`: checkpoint service → SchedulerBackend →
SupervisedScheduler → GenerationService → JSON API), served over HTTP on
localhost from a thread of this process, for Mistral-7B as registered — all
32 layers, int8 weights made on the device from `--seed` (the chip machine
has no network and the repo holds no checkpoint), paged KV. It also runs
each Pallas kernel on that path against its XLA reference, compiled, at the
same widths.

One process holds the chip. Every phase prints one JSON line; any failed
assertion, exception or non-200 ends the run with a non-zero exit code
before the last line. The last line of a run that passed is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without `--rehearse` there is no platform but the TPU: where JAX finds none,
the script exits non-zero and prints no result. Everything it writes goes
under `chiprun_out/smoke/` (the directory the chip tool copies back) and
the compile cache; nothing lands in a tracked file. Sizes were chosen with
`scripts/chip_rehearsal.py`, which compiles the same programs for a
described chip without one.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import faulthandler
import gc
import json
import os
import shutil
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "smoke")
TOKENIZER = os.path.join(REPO, "tests", "golden", "sql_bpe")
CSV = os.path.join(REPO, "data", "input", "taxi.csv")

MODEL = "mistral-7b"
#: One v5e chip (16 GB): int8 weights take 7.5 GB; the batched prefill's
#: whole-window row views and the KV pool share the rest. The pool budget is
#: what `scripts/chip_rehearsal.py` shows the largest program leaves, and is
#: past ops/pallas/dispatch's crossover, so auto-dispatch picks the kernels.
ONE_CHIP = dict(slots=8, max_seq=2048, prompt_bucket=128, kv_hbm_gb=3.5,
                max_new_tokens=24)
#: Four chips: the same model on every layout compared. Few slots, a short
#: window and one small bucket keep it to three programs a scheduler — six
#: schedulers are built — at four times the cost a second. A chip that holds
#: the whole model (the one-chip reference, each dp replica) has room for a
#: 4 GB pool beside the prefill scatter's copy of half of it; under tp=4
#: the pool must be larger to be past the crossover PER DEVICE, so that
#: auto-dispatch picks the shard_map kernels there too.
FOUR_CHIP = dict(slots=2, max_seq=512, prompt_bucket=16, kv_hbm_gb=4.0,
                 tp_kv_hbm_gb=6.5, max_new_tokens=16)
#: `--rehearse`: the same control flow at a size the CPU runs in seconds.
REHEARSAL = dict(slots=4, max_seq=512, prompt_bucket=32, kv_hbm_gb=0.004,
                 tp_kv_hbm_gb=0.004, max_new_tokens=8)

SCHEMA_SYSTEM = (
    "Table name is temp_view. The structure of the table is:\n"
    "VendorID INT, tpep_pickup_datetime STRING, tpep_dropoff_datetime "
    "STRING, passenger_count INT, trip_distance DOUBLE, RatecodeID INT, "
    "store_and_fwd_flag STRING, PULocationID INT, DOLocationID INT, "
    "payment_type INT, fare_amount DOUBLE, extra DOUBLE, mta_tax DOUBLE, "
    "tip_amount DOUBLE, tolls_amount DOUBLE, improvement_surcharge DOUBLE, "
    "total_amount DOUBLE, congestion_surcharge DOUBLE"
)
QUESTIONS = [
    "What is the average fare amount per passenger count?",
    "How many trips had more than two passengers?",
    "Show the total fare amount for each vendor.",
    "Which vendor has the highest average tip amount?",
    "List the ten longest trips by distance.",
    "What is the total amount collected per payment type?",
    "How many trips were paid with payment type 2?",
    "What is the average trip distance for each rate code?",
]


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------ bookkeeping


class CompileLog:
    """Counts what JAX compiles (or fetches from its persistent cache) and
    for how long, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, mark) -> dict:
        return {"programs_built": self.programs - mark[0],
                "compile_s": round(self.seconds - mark[1], 1),
                "cache_hits": self.cache_hits - mark[2]}


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def device_memory() -> list:
    """Per device: bytes in use now and at the peak, in GB (None where the
    backend keeps no such figures, as the CPU's does not)."""
    import jax

    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append({k: round(st[k] / 2**30, 2) if k in st else None
                    for k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit")})
    return out


# ------------------------------------------------------------ kernel phase


def check_kernels(seed: int, rehearse: bool) -> None:
    """Each Pallas kernel on the serving path against its XLA reference, in
    this process, on seeded inputs at the model's widths. On the chip the
    kernels run compiled (`interpret=False` is explicit here: a kernel that
    Mosaic refuses fails the phase, it does not drop to the interpreter)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.models.configs import REGISTRY
    from llm_based_apache_spark_optimization_tpu.ops import pallas as K
    from llm_based_apache_spark_optimization_tpu.ops.attention import (
        attention_mask,
        gqa_attention,
    )
    from llm_based_apache_spark_optimization_tpu.ops.lanepack import (
        pack_cache,
        unpack_cache,
    )
    from llm_based_apache_spark_optimization_tpu.ops.quant import quantize_kv

    cfg = REGISTRY["tiny" if rehearse else MODEL]
    n, kh, h = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.sliding_window
    interpret = rehearse
    if rehearse:
        L, P, ps, np_tab, b, s_flash, t_flash = 2, 40, 8, 6, 5, 64, 16
    else:
        L, P, ps, np_tab, b, s_flash, t_flash = 2, 96, 64, 8, 8, 2048, 128
    t_chunk = 16
    dt = jnp.float32 if rehearse else jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(seed), 32))

    def rnd(shape, dtype=dt):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    rng = np.random.default_rng(seed)
    # Page tables: every row owns its pages; the last row is parked.
    tab = rng.permutation(P)[: b * np_tab].reshape(b, np_tab).astype(np.int32)
    tab[-1, :] = P
    tab = jnp.asarray(tab)
    kp, vp = rnd((L, P, kh, ps, h)), rnd((L, P, kh, ps, h))
    k8, v8 = quantize_kv(kp), quantize_kv(vp)
    # A head-64 pool is stored two heads a 128-lane row (engine/paged_kv
    # .lane_pack): the same three kernels through their packed wrappers,
    # against the references on the LOGICAL pool. Compiled is what counts:
    # the interpreter passed a form of the wrapper that the TPU compiler,
    # jitted around the kernel call, turned into wrong values (PR 33).
    n6, h6 = (4, 64) if rehearse else (32, 64)      # MHA, as SmolLM2-1.7B
    lk, lv = rnd((L, P, n6, ps, h6)), rnd((L, P, n6, ps, h6))
    pk, pv = pack_cache(lk, 2), pack_cache(lv, 2)
    results = {}

    def close(name, got, want, atol):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ref = float(np.mean(np.abs(want)))
        results[name] = {
            "max_abs_err": float(f"{err:.3g}"), "atol": atol,
            "mean_abs_ref": float(f"{ref:.3g}"),
            # finite, a reference that is not all zero, within tolerance
            "ok": bool(np.all(np.isfinite(got)) and ref > 1e-3
                       and err <= atol)}

    def same(name, got, want):
        bad = sum(int(np.sum(np.asarray(g) != np.asarray(w)))
                  for g, w in zip(got, want))
        results[name] = {"differing_elements": bad, "ok": bad == 0}

    atol = 2e-5 if rehearse else 2e-2
    for t in (1, t_chunk):
        # Ragged windows: rows at different ages, some shorter than T.
        starts = rng.integers(0, np_tab * ps - t, size=b).astype(np.int32)
        pos = jnp.asarray(starts[:, None] + np.arange(t, dtype=np.int32))
        q_lens = jnp.asarray(rng.integers(1, t + 1, size=b).astype(np.int32))
        kv_lens = jnp.asarray(starts + t).at[-1].set(0)
        q = rnd((b, t, n, h))
        close(f"ragged_read_bf16_T{t}",
              K.ragged_paged_attention(q, kp, vp, tab, pos, 1, window,
                                       kv_lens, q_lens, interpret=interpret),
              K.paged_attention_reference(q, kp[1], vp[1], tab, pos, window,
                                          kv_lens, q_lens), atol)
        close(f"ragged_read_int8_T{t}",
              K.ragged_paged_attention_quantized(
                  q, k8["q8"], k8["s"], v8["q8"], v8["s"], tab,
                  pos, 1, window, kv_lens, q_lens, interpret=interpret),
              K.paged_attention_reference_quantized(
                  q, k8["q8"][1], k8["s"][1], v8["q8"][1], v8["s"][1], tab,
                  pos, window, kv_lens, q_lens), atol)
        # Writes: the same windows, consecutive positions sharing pages,
        # dead columns past q_lens, the parked row, and one row running
        # past its virtual end (all of which must drop).
        wpos = pos.at[0].set(np_tab * ps - t // 2 - 1 + jnp.arange(t))
        k_new, v_new = rnd((b, t, kh, h)), rnd((b, t, kh, h))
        same(f"page_write_bf16_T{t}",
             K.fused_page_write(kp, vp, k_new, v_new, wpos, tab, 1,
                                q_lens=q_lens, interpret=interpret),
             (K.paged_write_reference(kp, k_new, wpos, tab, 1, q_lens),
              K.paged_write_reference(vp, v_new, wpos, tab, 1, q_lens)))
        same(f"page_write_int8_T{t}",
             K.fused_page_write_quantized(
                 k8["q8"], k8["s"], v8["q8"], v8["s"], k_new, v_new, wpos,
                 tab, 1, q_lens=q_lens, interpret=interpret),
             K.paged_write_reference_quantized(
                 k8["q8"], k8["s"], v8["q8"], v8["s"], k_new, v_new, wpos,
                 tab, 1, q_lens))
        q, k_new, v_new = (rnd((b, t, n6, h6)) for _ in range(3))
        close(f"ragged_read_head64_packed_T{t}",
              K.ragged_paged_attention(q, pk, pv, tab, pos, 1, window,
                                       kv_lens, q_lens, interpret=interpret),
              K.paged_attention_reference(q, lk[1], lv[1], tab, pos, window,
                                          kv_lens, q_lens), atol)
        same(f"page_write_head64_packed_T{t}",
             [unpack_cache(x, 2) for x in K.fused_page_write(
                 pk, pv, k_new, v_new, wpos, tab, 1, q_lens=q_lens,
                 interpret=interpret)],
             (K.paged_write_reference(lk, k_new, wpos, tab, 1, q_lens),
              K.paged_write_reference(lv, v_new, wpos, tab, 1, q_lens)))

    # Flash prefill over a contiguous row view, one chunk in mid-window.
    bq = 2
    q = rnd((bq, t_flash, n, h))
    kc, vc = rnd((bq, kh, s_flash, h)), rnd((bq, kh, s_flash, h))
    pos = jnp.asarray(
        np.array([[s_flash // 2], [0]], np.int32)
        + np.arange(t_flash, dtype=np.int32))
    close("flash_prefill",
          K.flash_gqa_attention(q, kc, vc, pos, window, interpret=interpret),
          gqa_attention(q, kc, vc, attention_mask(pos, s_flash, window)),
          atol)
    q = rnd((bq, t_flash, n6, h6))
    kc, vc = rnd((bq, n6, s_flash, h6)), rnd((bq, n6, s_flash, h6))
    close("flash_prefill_head64_packed",
          K.flash_gqa_attention(q, pack_cache(kc, 2), pack_cache(vc, 2), pos,
                                window, interpret=interpret),
          gqa_attention(q, kc, vc, attention_mask(pos, s_flash, window)),
          atol)
    emit("kernels", widths={"heads": n, "kv_heads": kh, "head_dim": h,
                            "page_size": ps},
         mode="interpreted" if interpret else "compiled", checks=results)
    failed = [name for name, r in results.items() if not r["ok"]]
    assert not failed, f"kernels that disagree with their reference: {failed}"


# ------------------------------------------------------------- serve phase


class SeededWeights:
    """`load_weights` for `app.__main__.build_app`: int8 weights of a
    REGISTRY shape, made on the device from a seed. `keep` holds on to the
    placed tree (for the logits comparison of the four-chip run); it must
    be off where the app parks the tree on the host (dp replicas), or
    device 0 would keep a second copy."""

    def __init__(self, seed: int, max_seq: int, keep: bool = False,
                 min_kv_heads: int = 0):
        self.seed, self.max_seq, self.keep = seed, max_seq, keep
        self.min_kv_heads = min_kv_heads
        self.cfg = self.params = None

    def __call__(self, name, mesh, *, quantize_int8=False,
                 quantize_int4=False, quantize_unembed8=False):
        import jax

        from llm_based_apache_spark_optimization_tpu.models.configs import REGISTRY
        from llm_based_apache_spark_optimization_tpu.ops.quant import (
            init_params_quantized,
        )

        if not quantize_int8 or quantize_int4 or quantize_unembed8:
            raise ValueError("seeded weights are int8 (pass --int8 alone)")
        cfg = REGISTRY[name]
        # TINY's registered window is a CI size; rope is computed, not
        # learned, so a longer one costs nothing (app make_tiny_service).
        cfg = dataclasses.replace(
            cfg, max_seq_len=max(cfg.max_seq_len, self.max_seq),
            # ... and its two KV heads do not divide over tp=4.
            num_kv_heads=max(cfg.num_kv_heads, self.min_kv_heads))
        params = init_params_quantized(cfg, jax.random.key(self.seed))
        if mesh is not None:
            from llm_based_apache_spark_optimization_tpu.parallel.sharding import (
                shard_params,
            )

            params = shard_params(params, cfg, mesh)
        if self.keep:
            self.cfg, self.params = cfg, params
        return cfg, params


class Server:
    """The app's own assembly, served from a thread of this process."""

    def __init__(self, shape: dict, model: str, loader: SeededWeights,
                 tag: str, extra_args=()):
        from llm_based_apache_spark_optimization_tpu.app.__main__ import (
            build_app,
            build_parser,
        )
        from llm_based_apache_spark_optimization_tpu.app.config import AppConfig

        self.dir = os.path.join(OUT, tag)
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(os.path.join(self.dir, "input"))
        shutil.copy(CSV, os.path.join(self.dir, "input", "taxi.csv"))
        args = build_parser().parse_args([
            "--api", "--backend", "checkpoint",
            "--sql-model-path", f"{model}:{TOKENIZER}",
            "--int8", "--kv-layout", "paged",
            "--kv-hbm-gb", str(shape["kv_hbm_gb"]),
            "--slots", str(shape["slots"]),
            "--max-seq", str(shape["max_seq"]),
            "--prompt-bucket", str(shape["prompt_bucket"]),
            "--max-new-tokens", str(shape["max_new_tokens"]),
            *extra_args,
        ])
        cfg = AppConfig.from_env(
            input_dir=os.path.join(self.dir, "input"),
            output_dir=os.path.join(self.dir, "output"),
            history_db=os.path.join(self.dir, "history.db"),
            journal_spill=os.path.join(self.dir, "journal"),
            profile_dir=os.path.join(self.dir, "profile"),
            max_new_tokens=shape["max_new_tokens"],
            port=0,
        )
        self.app, self.service = build_app(args, cfg, load_weights=loader)
        self.httpd = self.app.serve(cfg.host, 0, background=True)
        self.base = f"http://{cfg.host}:{self.httpd.server_port}"

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.service.close()

    def get(self, path: str):
        with urllib.request.urlopen(self.base + path, timeout=600) as r:
            assert r.status == 200, f"GET {path}: HTTP {r.status}"
            return json.loads(r.read())

    def post(self, path: str, body: dict):
        req = urllib.request.Request(
            self.base + path, json.dumps(body).encode(),
            {"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as r:
            assert r.status == 200, f"POST {path}: HTTP {r.status}"
            return r.read()

    def generate(self, prompt: str, model: str = "duckdb-nsql",
                 system: str = SCHEMA_SYSTEM) -> str:
        out = json.loads(self.post("/api/generate", {
            "model": model, "prompt": prompt, "system": system}))
        assert out["done"] is True and out["model"] == model, out
        assert isinstance(out["response"], str) and out["request_id"], out
        return out["response"]

    def scheduler(self):
        """The live scheduler (or pool) under the SQL model's supervisor,
        for what HTTP does not expose: a program's text, the mesh."""
        return self.service._entry("duckdb-nsql").backend.scheduler._inner

    def scheduler_stats(self) -> dict:
        """`serving` block of /metrics for the SQL model."""
        return self.get("/metrics")["duckdb-nsql"]["serving"]


def ledgers(serving: dict) -> list:
    """Per-replica perf ledgers of a /metrics `serving` block."""
    perf = serving["perf"]
    return perf.get("replicas", [perf])


def assert_device_path(serving: dict, rehearse: bool, mesh: bool) -> dict:
    """Auto-dispatch itself must have picked the kernels (on the chip)."""
    kernels = ledgers(serving)[0]["kernels"]
    if not rehearse:
        want = {"pallas": "compiled", "prefill_attention": "pallas",
                "decode_attention": "pallas",
                "page_write": "xla" if mesh else "pallas"}
        assert kernels == want, f"kernel modes {kernels}, expected {want}"
    return kernels


def assert_healthy(serving: dict) -> None:
    sup = serving["supervisor"]
    assert sup["state"] == "ready", sup
    for key in ("restarts", "stalls", "lost", "quarantined"):
        assert sup[key] == 0, f"supervisor counted {key}={sup[key]}"
    assert serving["watchdog"].get("slot_stalls", 0) == 0, serving["watchdog"]


def serve_one_chip(seed: int, rehearse: bool, clog: CompileLog) -> None:
    shape = REHEARSAL if rehearse else ONE_CHIP
    model = "tiny" if rehearse else MODEL
    mark = clog.mark()
    t0 = time.time()
    srv = Server(shape, model, SeededWeights(seed, shape["max_seq"]),
                 "one_chip")
    startup = {"startup_s": round(time.time() - t0, 1), **clog.since(mark)}
    try:
        ready = srv.get("/readyz")
        assert ready["state"] == "ready", ready
        serving = srv.scheduler_stats()
        kernels = assert_device_path(serving, rehearse, mesh=False)
        led = ledgers(serving)[0]
        emit("server_ready", model=model, layout="paged", weights="int8",
             kernels=kernels, device_kind=led["device_kind"],
             param_gb=round(led["param_bytes"] / 2**30, 2),
             kv_pages=serving["kv_pages"]["pages_total"],
             **startup, memory=device_memory())
        if not rehearse:
            # The decode program the server warmed: the kernels must be
            # IN it, not merely selected.
            sched = srv.scheduler()
            text = sched._decode_fn.lower(
                sched.params, *sched._cache, *sched._decode_warm_args()
            ).as_text()
            calls = text.count("tpu_custom_call")
            assert calls > 0, "no tpu_custom_call in the decode program"
            emit("decode_program", decode_impl=sched._decode_impl,
                 tpu_custom_calls=calls)

        mark = clog.mark()
        window_t0 = time.time()
        models = srv.get("/models")
        assert {"duckdb-nsql", "llama3.2"} <= set(models["models"]), models

        # Greedy determinism, and the second occurrence publishes the
        # shared schema prefix for the wave below to hit.
        first = srv.generate(QUESTIONS[0])
        again = srv.generate(QUESTIONS[0])
        assert first == again, "the same greedy prompt gave different text"

        # A profile capture of a few rounds, armed before the wave.
        armed = srv.get("/debug/profile?rounds=2&model=duckdb-nsql")
        assert armed["state"] == "armed" and armed["rounds"] == 2, armed

        # A concurrent wave, so that a decode batch really forms.
        with concurrent.futures.ThreadPoolExecutor(len(QUESTIONS)) as pool:
            wave = list(pool.map(srv.generate, QUESTIONS))
        assert wave[0] == first, "batched greedy output differs from solo"

        # One streamed request: NDJSON chunks, then the terminator.
        lines = [json.loads(x) for x in srv.post("/api/generate", {
            "model": "llama3.2", "prompt": "Explain: no such column",
            "stream": True}).splitlines() if x.strip()]
        assert lines[-1]["done"] is True and "error" not in lines[-1], lines
        assert all(not x["done"] for x in lines[:-1]), lines

        # The studio's own request: CSV + question -> SQL -> execute; the
        # random model's SQL fails, so diagnose and repair turns run too.
        out = json.loads(srv.post("/process-data/", {
            "input_text": QUESTIONS[0], "file_name": "taxi.csv"}))
        assert ("message" in out and out["output_file"]) or (
            out.get("error") == "SQL execution failed"
            and "sql_query" in out and "error_details" in out), out

        window = {"window_s": round(time.time() - window_t0, 1),
                  **clog.since(mark)}
        # The capture is stopped on a thread of its own, beside the
        # serving loop, and that takes 30-55 s on a TPU: wait for its
        # outcome, not for a time.
        profile_deadline = time.time() + 180.0
        while True:
            prof = srv.get("/debug/profile")
            done = [c["last"] for c in prof["captures"].values()
                    if isinstance(c, dict) and c.get("last")]
            if done or time.time() > profile_deadline:
                break
            time.sleep(1.0)
        assert done, f"the armed capture never finished: {prof}"

        metrics = srv.get("/metrics")
        serving = metrics["duckdb-nsql"]["serving"]
        tokens = {m: metrics[m]["output_tokens"]
                  for m in ("duckdb-nsql", "llama3.2")}
        assert all(v > 0 for v in tokens.values()), tokens
        rounds = sum(p["rounds"] for p in ledgers(serving)[0]["phases"].values())
        assert rounds > 0, "no round was harvested"
        prefix = serving["prefix_cache"]
        assert prefix["hits"] > 0 and prefix["blocks_reused"] > 0, prefix
        pages = serving["kv_pages"]
        assert pages["zero_copy_shares"] > 0, pages
        assert pages["prefix_resident_pages"] > 0, pages
        assert_healthy(serving)
        assert window["programs_built"] == 0, (
            f"{window['programs_built']} programs were compiled inside the "
            f"request window; the server must warm them before it is ready")
        registry = srv.get("/debug/prefixcache")["models"]
        assert registry["duckdb-nsql"], registry

        last = done[0]
        assert last["state"] == "done" and last["artifacts"], last
        from llm_based_apache_spark_optimization_tpu.utils.traceprof import Trace

        ops = Trace().load_dir(last["dir"]).top_ops(3)
        assert ops, "the profile capture names no device operation"
        emit("requests", answered=len(wave) + 4, tokens_generated=tokens,
             rounds=rounds, prefix_cache=prefix,
             pages={k: pages[k] for k in (
                 "pages_total", "pages_free", "zero_copy_shares",
                 "prefix_resident_pages", "cow_copies")},
             process_data="executed" if "message" in out else "diagnosed",
             profile={"artifact_bytes": last["artifact_bytes"],
                      "top_ops": [o[0] for o in ops]},
             **window, memory=device_memory())
    finally:
        srv.close()


# ---------------------------------------------------------- four-chip phase


def prefill_logits(loader: SeededWeights, mesh):
    """Prefill logits of one fixed prompt from the tree the server holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_based_apache_spark_optimization_tpu.models.llama import forward

    t = 32
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(3, 300, size=(1, t)), jnp.int32)
    positions = jnp.arange(t, dtype=jnp.int32)[None, :]
    logits, _ = jax.jit(
        lambda p: forward(loader.cfg, p, tokens, positions, mesh=mesh)
    )(loader.params)
    return np.asarray(logits[0], np.float32)


def run_layout(tag: str, seed: int, shape: dict, model: str, extra_args,
               keep: bool, clog: CompileLog):
    """Build one layout through the app's assembly, answer the fixed
    prompts, take its logits and memory picture, release everything."""
    import jax

    mark = clog.mark()
    loader = SeededWeights(seed, shape["max_seq"], keep=keep,
                           min_kv_heads=4 if model == "tiny" else 0)
    srv = Server(shape, model, loader, tag, extra_args)
    try:
        assert srv.get("/readyz")["state"] == "ready"
        with concurrent.futures.ThreadPoolExecutor(len(QUESTIONS)) as pool:
            texts = list(pool.map(srv.generate, QUESTIONS))
        serving = srv.scheduler_stats()
        assert_healthy(serving)
        memory = device_memory()
        logits = None
        if keep:
            logits = prefill_logits(loader, srv.scheduler().mesh)
        served = [r["replica"] for r in ledgers(serving)
                  if any(p["rounds"] for p in r["phases"].values())]
        emit(tag, kernels=[r["kernels"] for r in ledgers(serving)],
             replicas_that_served=served, memory=memory, **clog.since(mark))
    finally:
        srv.close()
        loader.cfg = loader.params = None
    del srv, loader
    gc.collect()
    jax.clear_caches()
    # Released means released: the next layout needs the room, and no
    # device may ever hold two copies.
    live = sum(a.nbytes for a in jax.live_arrays())
    assert live < 64 * 2**20, f"{live / 2**30:.2f} GB still live after {tag}"
    return texts, logits, memory, serving


def serve_four_chips(seed: int, rehearse: bool, clog: CompileLog) -> None:
    import numpy as np

    shape = REHEARSAL if rehearse else FOUR_CHIP
    model = "tiny" if rehearse else MODEL
    ref_text, ref_logits, _, _ = run_layout(
        "one_chip_reference", seed, shape, model, (), True, clog)

    dp_text, _, dp_mem, dp_serving = run_layout(
        "dp4", seed, shape, model, ("--dp", "4"), False, clog)
    assert dp_text == ref_text, "dp=4 greedy output differs from one chip"
    assert len(ledgers(dp_serving)) == 4, "the pool does not hold 4 replicas"
    if not rehearse:
        weights_gb = ledgers(dp_serving)[0]["param_bytes"] / 2**30
        for i, m in enumerate(dp_mem):
            assert m["bytes_in_use"] >= weights_gb, (
                f"device {i} holds {m['bytes_in_use']} GB under dp=4: less "
                f"than one replica's weights ({weights_gb:.2f} GB)")

    tp_text, tp_logits, tp_mem, tp_serving = run_layout(
        "tp4", seed, {**shape, "kv_hbm_gb": shape["tp_kv_hbm_gb"]}, model,
        ("--tp", "4"), True, clog)
    assert_device_path(tp_serving, rehearse, mesh=True)
    err = float(np.max(np.abs(tp_logits - ref_logits)))
    top1 = float(np.mean(tp_logits.argmax(-1) == ref_logits.argmax(-1)))
    # Row-parallel matmuls sum four partial products in another order, in
    # bf16 activations, 32 layers deep: 8 bf16 ulps of the largest logit.
    tol = float(8 * 2.0 ** (np.floor(np.log2(np.abs(ref_logits).max())) - 7))
    assert err <= tol, f"tp=4 prefill logits off by {err} (> {tol})"
    assert ref_logits[-1, tp_logits[-1].argmax()] >= ref_logits[-1].max() - tol, \
        "tp=4 picks another first token than one chip (beyond a near-tie)"
    if not rehearse:
        used = [m["bytes_in_use"] for m in tp_mem]
        assert max(used) <= 1.25 * min(used), (
            f"tp=4 memory is not spread evenly over the devices: {used} GB")
    emit("tp4_vs_one_chip", max_abs_logit_err=round(err, 5), tolerance=tol,
         top1_agreement=round(top1, 4), max_abs_logit=float(np.abs(ref_logits).max()),
         greedy_text_identical=sum(a == b for a, b in zip(tp_text, ref_text)),
         of=len(ref_text))


# ----------------------------------------------------------------- driver


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip layouts and what they "
                         "are compared with")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the TINY shape (kernels "
                         "interpreted; --chips 4 wants four virtual CPU "
                         "devices); proves control flow, not the chip")
    args = ap.parse_args()
    # Never outlive the driver's limit: dump every thread and die.
    faulthandler.dump_traceback_later(1150, exit=True)

    from llm_based_apache_spark_optimization_tpu.utils.jaxenv import (
        force_cpu,
        place_compile_cache,
    )

    if args.rehearse:
        force_cpu()
    cache_dir = place_compile_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device}); there is no CPU "
              f"continuation — see --rehearse", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {device}",
              file=sys.stderr)
        return 2

    from llm_based_apache_spark_optimization_tpu import native
    from llm_based_apache_spark_optimization_tpu.utils.perfmodel import peak_for

    peak_for(dev.device_kind)  # a kind the peaks table lacks raises here
    # No silent drop to the pure-Python twins: the C++ core must build.
    assert native.load_native() is not None, "the native library is missing"
    entries_before = cache_entries(cache_dir)
    emit("device", **device, compile_cache=cache_dir,
         cache_entries_before=entries_before,
         native_library_built_in_this_run=native.built_in_this_process())
    clog = CompileLog()
    if args.chips == 4:
        serve_four_chips(args.seed, args.rehearse, clog)
    else:
        mark = clog.mark()
        check_kernels(args.seed, args.rehearse)
        emit("kernels_compile", **clog.since(mark))
        serve_one_chip(args.seed, args.rehearse, clog)
    emit("compile_cache", dir=cache_dir, entries_before=entries_before,
         entries_after=cache_entries(cache_dir), cache_hits=clog.cache_hits,
         programs_built=clog.programs, compile_s=round(clog.seconds, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
