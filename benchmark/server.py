"""The system under test, assembled exactly as `python -m …app --api
--backend checkpoint --kv-layout paged` assembles it, and served over HTTP
from a thread of this process on port 0 (`chip_smoke.Server`'s pattern,
copied: the benchmark keeps its own).

From the program this takes `build_app`, `build_parser`, `AppConfig` and
the process-level JAX settings; the program's config object and the weights
come from the configuration's family (`families/<family>/program.py`).
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import urllib.request

import spec


class SeededWeights:
    """`load_weights` for `build_app`: `(cfg, params)` from the seed."""

    def __init__(self, cfg: dict, seed: int, emit_ids):
        self.cfg, self.seed, self.emit_ids = cfg, seed, emit_ids

    def __call__(self, name, mesh, *, quantize_int8=False,
                 quantize_int4=False, quantize_unembed8=False):
        fmt = self.cfg["serving"]["weights"]
        if quantize_int4 or quantize_unembed8 or quantize_int8 != (fmt == "int8"):
            raise ValueError(f"seeded weights are {fmt}; the server's "
                             f"arguments ask for another format")
        if mesh is not None:
            raise ValueError("one-chip cells only: no mesh")
        program = spec.family(self.cfg, "program")
        return program.config(self.cfg), program.served_tree(
            self.cfg, fmt, self.seed, self.emit_ids)


class RequestLog(logging.Handler):
    """Keeps the program's per-request log records (`lsot.metrics`: request
    id, queue wait, the server's own TTFT) for the per-layer readers."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.records = []

    def emit(self, record):
        try:
            if record.args:
                self.records.append(json.loads(record.args[0]))
        except Exception:  # noqa: BLE001 — a log line is never worth a run
            pass


class Server:
    def __init__(self, cell: spec.Cell, seed: int, emit_ids, scratch: str):
        from llm_based_apache_spark_optimization_tpu.app.__main__ import (
            build_app,
            build_parser,
        )
        from llm_based_apache_spark_optimization_tpu.app.config import AppConfig

        sv = cell.serving
        self.dir = scratch
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(os.path.join(self.dir, "input"))
        # Deployment settings the configuration states reach the assembly
        # the way an operator sets them: LSOT_* in the environment.
        for k, v in sv.get("env", {}).items():
            os.environ[k] = str(v)
        argv = [
            "--api", "--backend", "checkpoint",
            "--sql-model-path", f"{cell.config['name']}:{spec.TOKENIZER_DIR}",
            "--kv-layout", "paged", "--kv-hbm-gb", str(sv["kv_hbm_gb"]),
            "--slots", str(sv["slots"]), "--max-seq", str(sv["max_seq"]),
            "--prompt-bucket", str(sv["prompt_bucket"]),
            "--max-new-tokens", str(sv["max_new_tokens"]),
            *(["--int8"] if sv["weights"] == "int8" else []),
            *sv.get("extra_args", []),
        ]
        args = build_parser().parse_args(argv)
        self.request_log = RequestLog()
        log = logging.getLogger("lsot.metrics")
        log.setLevel(logging.INFO)
        log.propagate = False
        log.addHandler(self.request_log)
        cfg = AppConfig.from_env(
            input_dir=os.path.join(self.dir, "input"),
            output_dir=os.path.join(self.dir, "output"),
            history_db=os.path.join(self.dir, "history.db"),
            journal_spill=os.path.join(self.dir, "journal"),
            profile_dir=os.path.join(self.dir, "profile"),
            max_new_tokens=sv["max_new_tokens"],
            flight_rounds=8192,
            request_log=1.0, port=0)
        self.app, self.service = build_app(
            args, cfg, load_weights=SeededWeights(cell.config, seed, emit_ids))
        self.httpd = self.app.serve(cfg.host, 0, background=True)
        self.host, self.port = cfg.host, self.httpd.server_port
        self.base = f"http://{self.host}:{self.port}"

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.service.close()
        logging.getLogger("lsot.metrics").removeHandler(self.request_log)
        self.app = self.service = self.httpd = None

    def get(self, path: str, timeout: float = 120.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return json.loads(r.read())
