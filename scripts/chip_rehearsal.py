#!/usr/bin/env python3
"""Compile the scheduler's whole step programs for a DESCRIBED v5e, no chip
attached (on-chip-measurement guide §2, third rehearsal).

    JAX_PLATFORMS=cpu python scripts/chip_rehearsal.py            # one chip
    JAX_PLATFORMS=cpu python scripts/chip_rehearsal.py --tp 4     # 2x2 mesh
    JAX_PLATFORMS=cpu python scripts/chip_rehearsal.py --four-chip-shape

Builds the scheduler `chip_smoke.py` serves (same model, slots, window,
bucket and pool budget; zero weights of the real shapes, held on the host),
then lowers every prefill (bucket, k-bucket) program and the decode program
with their arguments described as living on the chip(s), and prints what
the TPU compiler says: refusal or success, `tpu_custom_call` present or
not, and `memory_analysis()` bytes against the chip's 16 GB. It counts one
program at a time, not what else the process keeps on the device, so the
pool and the weights — which are arguments — are inside `argument` bytes.

A compile that passes here is not a chip run, and says nothing about
results or times. This script steers the code under test (which asks JAX
for the platform and would take its CPU branch) from here, not through an
option of the program.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from llm_based_apache_spark_optimization_tpu.models.configs import REGISTRY  # noqa: E402
from llm_based_apache_spark_optimization_tpu.ops.pallas import dispatch  # noqa: E402
from llm_based_apache_spark_optimization_tpu.ops.quant import init_params_quantized  # noqa: E402
from llm_based_apache_spark_optimization_tpu.parallel import make_mesh  # noqa: E402
from llm_based_apache_spark_optimization_tpu.parallel.sharding import specs_for_params  # noqa: E402
from llm_based_apache_spark_optimization_tpu.serve.scheduler import ContinuousBatchingScheduler  # noqa: E402

HBM_BYTES = 16 * 2**30


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4))
    ap.add_argument("--four-chip-shape", action="store_true",
                    help="with --tp 1: one replica of the four-chip run "
                         "(its one-chip reference and each dp replica)")
    ap.add_argument("--only", default="",
                    help="comma list of program names (p<bucket>x<k>, decode)")
    args = ap.parse_args()
    shape = (chip_smoke.FOUR_CHIP if args.tp > 1 or args.four_chip_shape
             else chip_smoke.ONE_CHIP)
    cfg = dataclasses.replace(REGISTRY[chip_smoke.MODEL],
                              max_seq_len=shape["max_seq"])

    # The code under test asks "is this a TPU?" in one place.
    dispatch.on_tpu = lambda: True

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    abstract = jax.eval_shape(
        lambda: init_params_quantized(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), abstract)
    mesh = None
    if args.tp > 1:
        mesh = make_mesh(dp=1, sp=1, tp=args.tp,
                         devices=topo.devices[:args.tp])
        # The scheduler places params with device_put and allocates its
        # pool through a jit with out_shardings; a described device can
        # take neither. Leave the arrays on the host while it is built,
        # and describe their placement to the compiler below.
        real_put, real_jit = jax.device_put, jax.jit
        jax.device_put = lambda x, *a, **k: x
        jax.jit = lambda fn, *a, out_shardings=None, **k: (
            fn if out_shardings is not None else real_jit(fn, *a, **k))
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=shape["slots"],
        prompt_bucket=shape["prompt_bucket"], kv_layout="paged", mesh=mesh,
        kv_hbm_budget_bytes=int(
            shape["tp_kv_hbm_gb" if mesh is not None else "kv_hbm_gb"]
            * 2**30),
    )
    if mesh is not None:
        jax.device_put, jax.jit = real_put, real_jit
    # ... and how much memory the device has in another.
    sched._split_decode_weights = sched._room_for_split(
        sum(x.nbytes for x in jax.tree.leaves(params)) // args.tp,
        sum(c.nbytes for c in sched._cache) // args.tp, HBM_BYTES)
    sched._decode_fn = sched._build_decode()
    print(json.dumps({"kernels": sched.kernel_modes(),
                      "split_decode_weights": sched._split_decode_weights,
                      "pages": sched._page_alloc.num_pages,
                      "page_size": sched._page_size}), flush=True)

    if mesh is None:
        one = SingleDeviceSharding(topo.devices[0])
        param_sh = jax.tree.map(lambda _: one, params)
        cache_sh = [one] * len(sched._cache)
        rep = one
    else:
        param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                specs_for_params(params, tp=args.tp))
        cache_sh = [NamedSharding(mesh, PartitionSpec(
            None, None, "tp", *([None] * (c.ndim - 3))))
            for c in sched._cache]
        rep = NamedSharding(mesh, PartitionSpec())

    def describe(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    def rest(xs):
        return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep)
                for a in xs]

    programs = [(f"p{t}x{kb}", lambda t=t, kb=kb: (
        sched._build_prefill(t, kb), sched._prefill_warm_args(t, kb)))
        for t in sched._buckets for kb in sched._kbuckets]
    programs.append(("decode", lambda: (sched._decode_fn,
                                        sched._decode_warm_args())))
    only = set(filter(None, args.only.split(",")))
    ok = True
    for name, make in programs:
        if only and name not in only:
            continue
        fn, tail = make()
        t0 = time.time()
        try:
            compiled = fn.lower(
                describe(params, param_sh),
                *describe(list(sched._cache), cache_sh), *rest(tail),
            ).compile()
        except Exception as e:  # noqa: BLE001 — report and go on to the next
            ok = False
            print(json.dumps({"program": name, "ok": False,
                              "error": str(e)[:2000]}), flush=True)
            continue
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        fits = total < HBM_BYTES
        ok = ok and fits
        print(json.dumps({
            "program": name, "ok": True, "compile_s": round(time.time() - t0, 1),
            "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            "argument_gb": round(m.argument_size_in_bytes / 2**30, 2),
            "temp_gb": round(m.temp_size_in_bytes / 2**30, 2),
            "output_gb": round(m.output_size_in_bytes / 2**30, 2),
            "alias_gb": round(m.alias_size_in_bytes / 2**30, 2),
            "total_gb": round(total / 2**30, 2), "fits_16gb": fits,
        }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
