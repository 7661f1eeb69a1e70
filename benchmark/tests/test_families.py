"""The seam between the harness and a model family.

1. A family, a configuration and a cell are added to a temporary copy of
   `benchmark/` and `BENCHMARK.json` as NEW files and entries only (the
   family is `tests/fixture_family/`: the Llama block with a narrower
   feed-forward on layer 0, which the served tree, the reference and the
   counts each have to honour), the cell's traced rehearsal runs there on
   the CPU to a last line with `correct` true, and no file that was in the
   copy has changed. What this shows is discovery: the four files are found
   by the name and their answers reach `max_logit_gap` and the two readers.
   It does not show an independent forward pass: the fixture reuses the
   Llama family's `_make_fns` and `step_ops`, so the first real family, with
   a layer function and `Context`-reading counts of its own, should expect
   to find what `Context` and `max_logit_gap` still lack for it.
2. A configuration that names no family, or a family that lacks one of its
   four files, stops `spec.Cell` with the file in the message.
3. The Llama family's served trees, one full-width layer and the reference's
   logits are what they were before the code moved under `families/llama/`
   (digests computed at the parent commit, PR 29's tree), to the bit.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import spec
import weights

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FIXTURE = os.path.join(HERE, "tests", "fixture_family")
NEW = "narrow0-bf16"


def _files(top: str) -> dict:
    out = {}
    for d, _, names in os.walk(top):
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_family_a_configuration_and_a_cell_are_new_files_only(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _files(tmp_path / "benchmark")
    with open(tmp_path / "BENCHMARK.json") as f:
        bench_before = json.load(f)

    # ---- what a PR that brings a family adds: files ...
    shutil.copytree(FIXTURE, tmp_path / "benchmark" / "families" / "llama_narrow0")
    cfg = spec.load_json("configs", "smollm2-1.7b-bf16.json")
    cfg.update(name=NEW, family="llama_narrow0", first_intermediate_size=4096)
    cfg["rehearsal"]["first_intermediate_size"] = 64
    new_cfg = f"benchmark/configs/{NEW}.json"
    with open(tmp_path / new_cfg, "w") as f:
        json.dump(cfg, f)
    new_cell = spec.load_json("cells", "smollm2-1.7b-bf16.explain.json")
    # The rehearsal's closed loop is over within 1-3 s of a 4 s window, by
    # the CPU's mood: arm the trace as the window opens, for two rounds.
    new_cell["rehearsal"]["trace"] = {"rounds": 2, "before_end_s": 3.95}
    with open(tmp_path / "benchmark" / "cells" / f"{NEW}.explain.json", "w") as f:
        json.dump(new_cell, f)
    # ... and entries
    bench = json.loads(json.dumps(bench_before))
    bench["configs"].append({
        "name": NEW, "source": cfg["source"], "file": new_cfg,
        "reduced": ["max_position_embeddings", "eos_token_id"],
        "why": "test only: layer 0 with a narrower feed-forward"})
    bench["workloads"].append({
        "name": f"{NEW}.explain", "config": NEW, "traffic": "explain",
        "chips": 1, "why": "test only: a cell of a family that is not llama"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONDONTWRITEBYTECODE": "1",
           "PYTHONPATH": ROOT}  # the package; `benchmark/` is the copy's own
    done = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
         f"{NEW}.explain", "--seed", str(2**31 + 30), "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, done.stderr[-3000:]
    assert line["attempted"] > 0
    gap = line["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    # `step_mfu` counted through the new family's `costs.py` (a CPU trace
    # has no lane of modules for `decode_hbm_pct` to read: the next test)
    assert line["metrics"].get("rehearsal_step_mfu", {}).get("value", 0) > 0, \
        done.stderr[-6000:]

    after = _files(tmp_path / "benchmark")
    assert {k: after.get(k) for k in before} == before
    added = sorted(set(after) - set(before))
    assert added == sorted(
        [f"cells/{NEW}.explain.json", f"configs/{NEW}.json"]
        + [f"families/llama_narrow0/{p}.py" for p in spec.FAMILY_PARTS])
    for key, was in bench_before.items():  # every old entry is still there
        assert bench[key][:len(was)] == was if isinstance(was, list) \
            else bench[key] == was


def test_the_new_family_counts_less_than_llama():
    """The fixture's counts on the recorded rounds, by hand: layer 0 spares
    3 x hidden x (8192 - 4096) weights a token."""
    import costs
    import layers
    import xtrace

    rounds = spec.load_json("recorded", "flight_rounds.json")["rounds"]
    cell = spec.Cell("smollm2-1.7b-bf16.explain")
    ctx = layers.Context(
        cell=cell, peaks=costs.peaks("TPU v5 lite"), requests=[], server_log={},
        flight=rounds, flight_traced=rounds, metrics_t0={}, metrics_t1={},
        trace=xtrace.Trace.load(os.path.join(
            HERE, "recorded", "smollm2_decode_round.json.gz")))
    llama = spec.family(cell.config, "costs")
    cell.config = {**cell.config, "first_intermediate_size": 4096}
    narrow = spec.load_module(os.path.join(FIXTURE, "costs.py"))
    spared = 3 * 2048 * 4096
    assert llama.decode_step_bytes(ctx) - narrow.decode_step_bytes(ctx) == 2 * spared
    assert llama.step_ops(ctx) - narrow.step_ops(ctx) == \
        pytest.approx(2 * spared * (140 + 123 + 81))  # test_readers.py's tokens


@pytest.mark.parametrize("family, missing", [
    (None, "names no `family`"),
    ("half", "benchmark/families/half/costs.py"),
    ("nowhere", "benchmark/families/nowhere/program.py")])
def test_a_family_that_is_not_whole_stops_the_cell(tmp_path, monkeypatch,
                                                   family, missing):
    (tmp_path / "benchmark" / "families" / "half").mkdir(parents=True)
    for part in spec.FAMILY_PARTS[:-1]:
        (tmp_path / "benchmark" / "families" / "half" / f"{part}.py").touch()
    cfg = {"name": "c"} if family is None else {"name": "c", "family": family}
    (tmp_path / "benchmark" / "c.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "benchmark/c.json"}],
        "workloads": [{"name": "c.t", "config": "c", "traffic": "t", "chips": 1}]}))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(spec, "HERE", str(tmp_path / "benchmark"))
    with pytest.raises(SystemExit) as stop:
        spec.Cell("c.t")
    assert missing in str(stop.value) and "benchmark/c.json" in str(stop.value)


# ------------------------------------------------ what moved, to the bit
EMIT = list(range(2, 200))
SEED = 2**31 + 5
PINNED = {  # computed on PR 29's tree, before anything moved (PERF.md, PR 30)
    "mistral-7b-int8": {
        "served_tree": "8dedd7ba200bd003c689675a47672ebb31bcc3b8384efc561c31207d41393a68",
        "layer": "ee7414404403c671fd17f68e3365b446a1b0de5314178a15eafd4a179cfe3b9c",
        "logits": "1b8d7e9104043420cd19b04429fa2cd17ec195efe239eb76351ff81c769ab7b8",
        "logits_control": "f8c4b56e0654603967073a26fad8e8bdd5887c27f27807a98cdb077e5b88ee90"},
    "smollm2-1.7b-bf16": {
        "served_tree": "0b4a26f1e909e88407cf72bdd8e82c5bd867e874029e0b73d1d459c25ffa0617",
        "layer": "4af4faaf6bd5d063cd89e10752ad8440b63e4bd8b7798bb371880cf880911735",
        "logits": "a64372b210df30816bd058dfa83113da90b4810e11e97cfa0158b3721327482d",
        "logits_control": "1b429cd055167c8dc666ad34381a670869afa2a7870318bc8f49aa323d874ecc"}}


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda p: jax.tree_util.keystr(p[0])):
        a = np.ascontiguousarray(np.asarray(leaf))
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes() if a.dtype == np.bool_ else a.view(np.uint8).tobytes())
    return h.hexdigest()


def _made(name: str, what: str):
    full = spec.load_json("configs", name + ".json")
    tiny = {**full, **full["rehearsal"]}
    fmt = full["serving"]["weights"]
    if what == "served_tree":  # at the rehearsal size
        return spec.family(tiny, "program").served_tree(tiny, fmt, SEED, EMIT)
    if what == "layer":        # one layer at the published widths
        _, k_l = weights.keys_for(SEED, full["num_hidden_layers"])
        layer = spec.family(full, "weights").layer
        return jax.jit(lambda k: layer(full, fmt, k))(k_l[3])
    tokens = np.random.default_rng(7).choice(EMIT, size=(2, 48)).astype(np.int32)
    at = np.array([[10, 20, 47], [5, 30, 40]], np.int32)
    control = full["control"]["weights"] if what == "logits_control" else None
    return np.asarray(spec.family(tiny, "reference").logits_at(
        tiny, fmt, SEED, EMIT, tokens, at, control=control))


@pytest.mark.parametrize("what", ["served_tree", "layer", "logits", "logits_control"])
@pytest.mark.parametrize("name", sorted(PINNED))
def test_what_moved_is_the_parents_to_the_bit(name, what):
    assert _digest(_made(name, what)) == PINNED[name][what]
