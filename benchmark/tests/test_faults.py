"""The rest of a run with the timed path broken underneath: the harness's
look for a chip is skipped (`--rehearse`: the CPU, the configuration's
rehearsal size), everything else is the run the driver makes — server,
client process, window, drain, reference — and `correct` has to come out
false when a token is altered where it is produced, true when not. (A served
model has this one fault of the contract's four: no training step, no batch
mean, no exchange between chips.)"""
import json
import sys

import pytest

import run

CELLS = ["smollm2-1.7b-bf16.explain", "mistral-7b-int8.nl2sql"]


def _run(capsys, monkeypatch, cell, seed):
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", cell, "--seed", str(seed), "--seconds", "5",
        "--trace", "0", "--rehearse"])
    assert run.main() == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, monkeypatch, cell):
    line = _run(capsys, monkeypatch, cell, 2**31 + 77)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["compared"]["max_logit_gap"]["value"] <= \
        line["compared"]["max_logit_gap"]["limit"]
    assert all(k.startswith("rehearsal_") for k in line["metrics"])


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(capsys, monkeypatch, cell):
    from llm_based_apache_spark_optimization_tpu.serve import scheduler

    import spec
    import traffic

    # The altered id is another of the ids the cell's head emits (`run.main`
    # builds the same list), so it prints as a token, every altered request
    # can still be sampled, and it is the reference that has to see it.
    tok = traffic.Tok()
    config = spec.Cell(cell, rehearse=True).config
    emit_ids = sorted(tok.emit_table({tok.eos, config["eos_token_id"]}).values())
    real = scheduler._Request.emit

    def emit(self, tok):  # the 4th token of every request, where it is produced
        n = self.__dict__["_bench_n"] = self.__dict__.get("_bench_n", 0) + 1
        if n == 4:
            at = emit_ids.index(tok) if tok in emit_ids else 0
            tok = emit_ids[(at + len(emit_ids) // 2) % len(emit_ids)]
        return real(self, tok)

    monkeypatch.setattr(scheduler._Request, "emit", emit)
    line = _run(capsys, monkeypatch, cell, 2**31 + 78)
    assert line["correct"] is False
    assert line["compared"]["chunks_not_a_token"]["value"] == 0
    c = line["compared"]["max_logit_gap"]
    assert c["value"] > c["limit"]
