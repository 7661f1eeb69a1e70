#!/usr/bin/env python3
"""Compile a configuration's largest prefill program and its decode program
for a DESCRIBED v5e, no chip attached (on-chip-measurement guide, section 2,
third rehearsal; after `scripts/chip_rehearsal.py`).

    JAX_PLATFORMS=cpu python benchmark/tools/compile_rehearsal.py \
        --config smollm2-1.7b-bf16 [--kv-hbm-gb 3.0] [--only p128x8,decode]

Prints what the TPU compiler says for each program: refusal or success,
`tpu_custom_call` present, and `memory_analysis()` against 16 GB. The pool
and the weights are arguments and are inside `argument` bytes. A compile
that passes is not a chip run. The numbers it prints are copied by hand into
the configuration file's `rehearsal_compile`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import spec  # noqa: E402

HBM_BYTES = 16 * 2**30


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--kv-hbm-gb", type=float, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--only", default="",
                    help="comma list of programs (p<bucket>x<k>, decode); "
                         "default: the largest prefill and decode")
    args = ap.parse_args()
    cfg = spec.load_json("configs", args.config + ".json")
    spec.check_family(cfg, f"benchmark/configs/{args.config}.json")
    program = spec.family(cfg, "program")
    sv = cfg["serving"]
    gb = args.kv_hbm_gb if args.kv_hbm_gb is not None else sv["kv_hbm_gb"]
    slots = args.slots or sv["slots"]

    from llm_based_apache_spark_optimization_tpu.ops.pallas import dispatch
    from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
        ContinuousBatchingScheduler,
    )

    dispatch.on_tpu = lambda: True  # the one place the program asks
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    abstract = jax.eval_shape(
        lambda: program.served_tree(cfg, sv["weights"], 0, [2, 3]))
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
    sched = ContinuousBatchingScheduler(
        program.config(cfg), params, num_slots=slots,
        prompt_bucket=sv["prompt_bucket"], kv_layout="paged",
        kv_hbm_budget_bytes=int(gb * 2**30))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    sched._split_decode_weights = sched._room_for_split(
        param_bytes, sum(c.nbytes for c in sched._cache), HBM_BYTES)
    sched._decode_fn = sched._build_decode()
    print(json.dumps({"config": args.config, "kv_hbm_gb": gb, "slots": slots,
                      "kernels": sched.kernel_modes(),
                      "split_decode_weights": sched._split_decode_weights,
                      "param_gb": round(param_bytes / 2**30, 2),
                      "pages": sched._page_alloc.num_pages,
                      "page_size": sched._page_size}), flush=True)

    def describe(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    programs = [(f"p{t}x{kb}", lambda t=t, kb=kb: (
        sched._build_prefill(t, kb), sched._prefill_warm_args(t, kb)))
        for t in sched._buckets for kb in sched._kbuckets]
    programs.append(("decode", lambda: (sched._decode_fn,
                                        sched._decode_warm_args())))
    only = set(filter(None, args.only.split(","))) or {
        f"p{sched._buckets[-1]}x{sched._kbuckets[-1]}", "decode"}
    ok = True
    for name, make in programs:
        if only and name not in only:
            continue
        fn, tail = make()
        t0 = time.time()
        try:
            compiled = fn.lower(describe(params),
                                *describe(list(sched._cache)),
                                *describe(list(tail))).compile()
        except Exception as e:  # noqa: BLE001 — report and go on
            ok = False
            print(json.dumps({"program": name, "ok": False,
                              "error": str(e)[:2000]}), flush=True)
            continue
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        ok = ok and total < HBM_BYTES
        print(json.dumps({
            "program": name, "ok": True,
            "compile_s": round(time.time() - t0, 1),
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            "argument_gb": round(m.argument_size_in_bytes / 2**30, 2),
            "temp_gb": round(m.temp_size_in_bytes / 2**30, 2),
            "output_gb": round(m.output_size_in_bytes / 2**30, 2),
            "alias_gb": round(m.alias_size_in_bytes / 2**30, 2),
            "total_gb": round(total / 2**30, 2),
            "fits_16gb": total < HBM_BYTES}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
