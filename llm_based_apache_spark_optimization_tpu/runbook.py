"""One-command real-weight runbook: weights in, comparison report out.

The reference's headline artifact is its model-comparison report measured
over live Ollama models (`Model_Comparision_Report.docx`, SURVEY.md §6).
This module is that workflow as ONE command against real checkpoints:

    python -m llm_based_apache_spark_optimization_tpu.runbook \
        --sql-model /weights/duckdb-nsql-7b \
        --error-model /weights/llama3.2-3b \
        --mistral-model /weights/mistral-7b.gguf \
        --tp 4 -o EVAL.md

per model: HF safetensors dir or GGUF blob -> scanned param tree ->
orbax native cache (first run converts, every later run restores the
pre-stacked tree straight to the mesh) -> continuous-batching scheduler
backend -> the eval harness's four-query suite + five BASELINE configs ->
markdown report in the reference's own table shapes.

THE DAY REAL WEIGHTS ARRIVE (this image ships none — VERDICT r4 missing
#1; the suite to reproduce is the reference's
`Model_Evaluation_&_Comparision.py:86-158`):

1. Cheap smoke first — one query, no config table, ~one prefill+decode
   per model, proving tokenizer/template/stop-ids before the full run:

       python -m llm_based_apache_spark_optimization_tpu.runbook \
           --sql-model /weights/duckdb-nsql-7b --limit-cases 1 -o SMOKE.md

2. Then the full report at the serving configuration (one v5e chip fits
   7B only quantized — pick --int8 or --int4, and kv-int8 for headroom):

       python -m llm_based_apache_spark_optimization_tpu.runbook \
           --sql-model /weights/duckdb-nsql-7b \
           --error-model /weights/llama3.2-3b \
           --int8 --kv-int8 --speculative 4 -o EVAL.md

   The report's exact-match / edit-distance / latency columns then read
   against BASELINE.md's 50% / 21.5 / 8.05 s reference row, and
   /metrics' serving.speculation block says whether --speculative paid
   (tokens_per_round > 1.6 = yes).

Model path syntax: `PATH[:TOKENIZER_DIR]` — the tokenizer.json defaults to
living inside an HF checkpoint dir; GGUF blobs usually need the explicit
`:TOKDIR`.

Serving the same weights afterwards:
    python -m llm_based_apache_spark_optimization_tpu.app \
        --backend checkpoint --sql-model-path ... [--scheduler is default]
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import sys
import time
from pathlib import Path
from typing import Optional, Tuple

from .models.configs import LlamaConfig
from .ops.rope import RopeFreqFactors, RopeScaling

__all__ = ["load_or_convert", "build_service", "main"]


# --------------------------------------------------------------------- config
# LlamaConfig <-> json for the cache sidecar (orbax stores only the tree).

def _cfg_dump(cfg: LlamaConfig) -> dict:
    d = dataclasses.asdict(cfg)
    if cfg.rope_scaling is not None:
        d["rope_scaling"] = {
            "kind": type(cfg.rope_scaling).__name__,
            **dataclasses.asdict(cfg.rope_scaling),
        }
    return d


def _cfg_load(d: dict) -> LlamaConfig:
    d = dict(d)
    rs = d.get("rope_scaling")
    if rs:
        rs = dict(rs)
        kind = rs.pop("kind")
        d["rope_scaling"] = (
            RopeFreqFactors(tuple(rs["factors"]))
            if kind == "RopeFreqFactors" else RopeScaling(**rs)
        )
    d["extra_stop_ids"] = tuple(d.get("extra_stop_ids") or ())
    return LlamaConfig(**d)


# ---------------------------------------------------------------- conversion

def _cache_key(path: Path, dtype_name: str) -> str:
    # Identity = the files whose contents land in the tree: for HF dirs,
    # config.json plus every weight file's (name, mtime, size) — replacing
    # safetensors in place (re-download, fine-tune) must invalidate, or the
    # cache silently serves stale params. For GGUF blobs, the file itself.
    if path.is_dir():
        probes = [path / "config.json"] + sorted(path.glob("*.safetensors"))
    else:
        probes = [path]
    parts = [str(path.resolve()), dtype_name]
    for p in probes:
        st = p.stat()
        parts.append(f"{p.name}|{st.st_mtime_ns}|{st.st_size}")
    h = hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]
    return f"{path.name}-{h}"


def load_or_convert(
    src: str,
    cache_dir: str | Path,
    dtype=None,
    mesh=None,
    log=print,
) -> Tuple[LlamaConfig, dict, Optional[str]]:
    """(cfg, params, tokenizer_dir) for `PATH[:TOKDIR]`, via the orbax cache.

    First run converts the HF/GGUF source and persists the stacked tree;
    later runs restore it directly into the mesh's NamedShardings without
    re-reading the source (checkpoint/cache.py — the resume subsystem).
    """
    import jax.numpy as jnp

    from .checkpoint import (
        load_gguf_checkpoint,
        load_hf_checkpoint,
        load_native,
        save_native,
    )

    if dtype is None:
        dtype = jnp.bfloat16
    path_s, tok_dir = (
        (src.split(":", 1) + [None])[:2] if ":" in src else (src, None)
    )
    path = Path(path_s)
    if not path.exists():
        sys.exit(f"runbook: model path {path} does not exist")
    cache = Path(cache_dir) / _cache_key(path, jnp.dtype(dtype).name)
    cfg_file = cache / "config.json"

    t0 = time.perf_counter()
    if cfg_file.exists():
        cfg = _cfg_load(json.loads(cfg_file.read_text()))
        params = load_native(cfg, cache / "params", dtype=dtype, mesh=mesh)
        log(f"runbook: {path.name}: restored native cache in "
            f"{time.perf_counter() - t0:.1f}s ({cache})")
    else:
        if path.is_file() and path.suffix == ".gguf":
            cfg, params = load_gguf_checkpoint(path, dtype=dtype, mesh=mesh)
        else:
            cfg, params = load_hf_checkpoint(path, dtype=dtype, mesh=mesh)
        cache.mkdir(parents=True, exist_ok=True)
        save_native(params, cache / "params")
        cfg_file.write_text(json.dumps(_cfg_dump(cfg), indent=2))
        log(f"runbook: {path.name}: converted + cached in "
            f"{time.perf_counter() - t0:.1f}s ({cache})")
    return cfg, params, tok_dir or (str(path) if path.is_dir() else None)


# ------------------------------------------------------------------- service

def build_service(args, log=print):
    """The three-model generation service from checkpoint paths, through the
    cache, on scheduler backends (or locked engines with --no-scheduler).
    Registry shape and shared-weights aliasing come from
    serve.factory.assemble_reference_service (shared with the product CLI)."""
    from .serve import EngineBackend
    from .serve.backends import resolve_stop_ids
    from .serve.factory import assemble_reference_service
    from .serve.scheduler import ContinuousBatchingScheduler, SchedulerBackend
    from .tokenizer import HFTokenizer

    if getattr(args, "int4", False) and args.int8:
        sys.exit("runbook: pick one of --int8 / --int4")
    if (getattr(args, "kv_int8", False) and getattr(args, "speculative", 0)
            and not args.scheduler):
        # Same up-front guard as the app CLI: the ENGINE's speculative
        # verify loop streams a bf16 cache; only the scheduler path
        # composes speculation with the int8 KV cache.
        sys.exit("runbook: --kv-int8 cannot combine with --speculative on "
                 "--no-scheduler (the engine's verify loop streams the "
                 "bf16 cache); drop one, or use the scheduler path")
    mesh = None
    if args.tp > 1:
        from .parallel import make_mesh

        mesh = make_mesh(dp=1, sp=1, tp=args.tp)

    def build(src: str, add_bos: bool = True):
        cfg, params, tok_dir = load_or_convert(
            src, args.cache_dir, mesh=mesh, log=log
        )
        if getattr(args, "max_seq", None):
            # Context override — mainly for tiny smoke fixtures whose
            # declared context can't fit a schema prompt (rope tables are
            # computed on the fly, so extending costs nothing).
            cfg = dataclasses.replace(cfg, max_seq_len=args.max_seq)
        if tok_dir is None:
            sys.exit(f"runbook: {src}: GGUF blobs need an explicit "
                     "tokenizer dir — pass PATH.gguf:TOKDIR")
        tok = HFTokenizer(tok_dir)
        stop_ids = resolve_stop_ids(cfg, tok)
        if args.int8:
            from .ops.quant import quantize_params

            params = quantize_params(params)
        elif getattr(args, "int4", False):
            from .ops.quant import quantize_params_int4

            params = quantize_params_int4(params)
        if getattr(args, "int8_unembed", False):
            from .ops.quant import quantize_unembed

            params = quantize_unembed(params)
        kv_quant = "int8" if getattr(args, "kv_int8", False) else None
        spec = getattr(args, "speculative", 0)
        if args.scheduler:
            sched = ContinuousBatchingScheduler(
                cfg, params, num_slots=args.slots, stop_ids=stop_ids,
                mesh=mesh, kv_quant=kv_quant, speculative_draft=spec,
            )
            return SchedulerBackend(
                sched, tok, max_new_tokens=args.max_new_tokens,
                add_bos=add_bos,
            )
        from .engine import InferenceEngine

        eng = InferenceEngine(cfg, params, stop_ids=stop_ids, mesh=mesh,
                              kv_quant=kv_quant, speculative_draft=spec)
        return EngineBackend(
            eng, tok, max_new_tokens=args.max_new_tokens, add_bos=add_bos
        )

    return assemble_reference_service(
        build, args.sql_model, args.error_model, args.mistral_model,
        max_new_tokens=args.max_new_tokens,
    )


# ----------------------------------------------------------------------- cli

def build_parser() -> argparse.ArgumentParser:
    """The runbook CLI surface, separately constructible so the documented
    real-weight invocations stay dry-runnable in CI (tests parse them
    without loading any weights — tests/test_runbook.py)."""
    ap = argparse.ArgumentParser(
        prog="llm_based_apache_spark_optimization_tpu.runbook",
        description="weights in -> model-comparison report out (one command)",
    )
    ap.add_argument("--sql-model", required=True,
                    metavar="DIR_OR_GGUF[:TOKDIR]",
                    help="duckdb-nsql weights (NL->SQL role)")
    ap.add_argument("--error-model", metavar="DIR_OR_GGUF[:TOKDIR]",
                    help="llama3.2 weights; defaults to --sql-model")
    ap.add_argument("--mistral-model", metavar="DIR_OR_GGUF[:TOKDIR]",
                    help="optional third comparison model")
    ap.add_argument("--cache-dir", default="data/ckpt_cache",
                    help="orbax native-cache root (convert once, restore after)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--int4", action="store_true",
                    help="4-bit packed weights via the pallas int4 matmul "
                         "kernel (composes with --tp; pick one of "
                         "--int8/--int4)")
    ap.add_argument("--int8-unembed", action="store_true",
                    help="per-row int8 embed/unembed tables (composes with "
                         "--int8/--int4)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache (per-slot scales): halves the "
                         "serving window's HBM footprint and cache traffic")
    ap.add_argument("--speculative", type=int, default=0, metavar="N",
                    help="prompt-lookup speculative decoding, draft N "
                         "tokens/round (greedy requests; NL→SQL's "
                         "copy-heavy completions are the sweet spot)")
    ap.add_argument("--scheduler", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=None,
                    help="override the model's context window (smoke fixtures)")
    ap.add_argument("--limit-cases", type=int, default=None, metavar="N",
                    help="smoke mode: score only the first N suite queries "
                         "and skip the BASELINE config table — makes the "
                         "FIRST run over a new checkpoint cheap (one "
                         "prefill+decode per model at N=1) before "
                         "committing to the full report")
    ap.add_argument("-o", "--out", default="EVAL.md")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU jax (hermetic smoke)")
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.limit_cases is not None and args.limit_cases < 1:
        # 0 would run the FULL suite (falsy = no limit downstream) while
        # still skipping the config table — an expensive half-smoke nobody
        # means; negatives would silently slice from the end.
        sys.exit("runbook: --limit-cases must be >= 1")

    from .utils.jaxenv import force_cpu, place_compile_cache

    if args.cpu:
        force_cpu()
    place_compile_cache()

    import datetime

    from .evalh import report as report_mod

    svc = build_service(args)
    try:
        text = report_mod.generate(
            svc,
            backend_desc=(
                f"real checkpoints via runbook (tp={args.tp}, "
                f"{'int8, ' if args.int8 else ''}"
                f"{'scheduler' if args.scheduler else 'engine'} backends)"
            ),
            max_new_tokens=args.max_new_tokens,
            quality_meaningful=True,
            timestamp=datetime.datetime.now().strftime("%Y-%m-%d %H:%M"),
            # The service owns its mesh: report config rows with the mesh
            # that actually serves them, not a tp=1 default.
            service_mesh=f"tp={args.tp}",
            limit_cases=args.limit_cases,
            with_configs=args.limit_cases is None,
        )
    finally:
        svc.close()
    Path(args.out).write_text(text)
    print(f"runbook: wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
