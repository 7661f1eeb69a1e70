"""The traffic generators: the same seed gives the same schedule, another
seed the same work under other texts — the same lengths to the token and
the same prefix blocks shared —, lengths stay inside their ranges, the
open loop's rate is the cell's by construction, and the share of prompt
tokens that sessions share is what the mix says."""
import statistics

import pytest

import spec
import traffic

CELLS = ["mistral-7b-int8.nl2sql", "smollm2-1.7b-bf16.explain",
         "mistral-7b-int8.explain"]


@pytest.fixture(scope="module")
def tok():
    return traffic.Tok()


def _lens(tok, sched):
    return [(len(tok.encode(r["system"])), len(tok.encode(r["prompt"])),
             r["max_new_tokens"]) for r in sched["requests"]]


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_schedule(name):
    cell = spec.Cell(name)
    assert traffic.build(cell, 7, 20.0) == traffic.build(cell, 7, 20.0)


@pytest.mark.parametrize("name", CELLS)
def test_other_seed_same_work_other_text(name, tok):
    cell = spec.Cell(name)
    a, b = traffic.build(cell, 7, 20.0), traffic.build(cell, 2**31 + 11, 20.0)
    assert len(a["requests"]) == len(b["requests"])
    assert [r.get("due_s") for r in a["requests"]] == \
        [r.get("due_s") for r in b["requests"]]
    key = "session" if "session" in a["requests"][0] else "order"
    assert sorted((r[key], r["max_new_tokens"]) for r in a["requests"]) == \
        sorted((r[key], r["max_new_tokens"]) for r in b["requests"])
    assert all(x["prompt"] != y["prompt"]
               for x, y in zip(a["requests"], b["requests"]))
    # to the token: one more carries a prompt over a chunk boundary
    mix = cell.traffic
    assert [len(traffic.prompt_ids(tok, mix, r["system"], r["prompt"]))
            for r in a["requests"]] == \
        [len(traffic.prompt_ids(tok, mix, r["system"], r["prompt"]))
         for r in b["requests"]]


def _shared_blocks(tok, cell, seed, seconds=45.0, block=16):
    """For every request in the order it is due, the blocks of 16 tokens its
    prompt has in common with the nearest earlier prompt: what a prefix
    cache keyed by content can reuse at most."""
    sched = traffic.build(cell, seed, seconds)
    order = sched["prewarm"] + sorted(sched["requests"], key=lambda r: r["due_s"])
    seen, out = [], []
    for r in order:
        ids = traffic.prompt_ids(tok, cell.traffic, r["system"], r["prompt"])
        best = 0
        for other in seen:
            n = 0
            while n < min(len(ids), len(other)) and ids[n] == other[n]:
                n += 1
            best = max(best, n)
        out.append((len(ids), best // block))
        seen.append(ids)
    return out


def test_nl2sql_no_seed_moves_a_shared_prefix_block(tok):
    """The program reuses prefixes in blocks of 16 tokens. Where two prompts
    part must not hang on their wording: on one seed of five two questions
    of a session began alike, one block more matched, and the cell read
    another `tpot_p90_ms`, run after run (PERF.md section 6)."""
    cell = spec.Cell("mistral-7b-int8.nl2sql")
    want = _shared_blocks(tok, cell, 7)
    assert sum(b for _, b in want) > 100
    for seed in (2**31 + 11, 4100000011, 4500000013, 12345678901 % (2**31 + 2**20)):
        assert _shared_blocks(tok, cell, seed) == want


@pytest.mark.parametrize("name", CELLS[1:])
def test_explain_no_seed_moves_a_shared_prefix_block(name, tok):
    """The same in the closed loop, where the order of arrival is the
    server's: whichever two traces meet in its cache, they share the
    instruction, the template and the fixed opening of the exception — 17
    blocks of 16 tokens — and part at the tag, 5 tokens short of the next
    block's edge. Before PR 30 they parted inside the first column's name:
    on seed 2147490101 four requests of `mistral-7b-int8.explain` reused 288
    tokens for 272 and the cell read `ttft_p50_ms` 933 for 955, run after
    run (PERF.md section 2)."""
    cell = spec.Cell(name)
    for seed in (2147490101, 7, 2**31 + 11, 4100000011):
        prompts = [traffic.prompt_ids(tok, cell.traffic, r["system"], r["prompt"])
                   for r in traffic.build(cell, seed, 45.0)["requests"]]
        for i, ids in enumerate(prompts):
            common = max(
                next(n for n in range(len(ids) + 1)
                     if n >= min(len(ids), len(o)) or ids[n] != o[n])
                for j, o in enumerate(prompts) if j != i)
            assert 272 + 8 <= common < 288 - 3, (seed, i, common)


def test_text_kinds_are_data(tok):
    import random
    for kind in ("schema", "question", "spark_trace"):
        a = traffic._text(kind, tok, random.Random(5), 120, tag=3)
        assert a == traffic._text(kind, tok, random.Random(5), 120, tag=3)
        assert a != traffic._text(kind, tok, random.Random(6), 120, tag=3)
        assert len(tok.encode(a)) == pytest.approx(120, abs=2)
    assert traffic._text("question", tok, random.Random(5), 30, tag=27).startswith("bb) ")
    assert traffic._tag(0) == "aa" and traffic._tag(25) == "za"
    assert len({traffic._tag(i) for i in range(26 * 26)}) == 26 * 26


def test_closed_loop_with_a_number_of_clients():
    cell = spec.Cell("mistral-7b-int8.explain", rehearse=True)
    cell.traffic = {**cell.traffic, "clients": 2}
    sched = traffic.build(cell, 5, 10.0)
    assert sched["clients"] == 2
    assert {r["client"] for r in sched["requests"]} == {0, 1}


def test_nl2sql_lengths_and_sharing(tok):
    cell = spec.Cell("mistral-7b-int8.nl2sql")
    mix = cell.traffic
    sched = traffic.build(cell, 3, 45.0)
    lens = _lens(tok, sched)
    assert len(lens) >= 20
    for s, q, n in lens:
        assert mix["system"]["tokens"]["lo"] - 2 <= s <= mix["system"]["tokens"]["hi"] + 2
        assert mix["prompt"]["tokens"]["lo"] - 2 <= q <= mix["prompt"]["tokens"]["hi"] + 2
        assert mix["output_tokens"]["lo"] <= n <= mix["output_tokens"]["hi"]
    assert all(-mix["lead_in_s"] <= r["due_s"] < 45.0 for r in sched["requests"])
    # Shared prompt tokens: every request's schema but the first of each
    # session the schedule has not seen (sessions under way are prewarmed).
    warm = {p["system"] for p in sched["prewarm"]}
    seen, shared, total = set(warm), 0, 0
    for r, (s, q, _) in zip(sched["requests"], lens):
        total += s + q
        shared += s if r["system"] in seen else 0
        seen.add(r["system"])
    assert 0.6 < shared / total < 0.9  # 0.70 for the cell's schedule
    assert len(sched["prewarm"]) == 2 * len(warm)


@pytest.mark.parametrize("rate,seconds", [(1.2, 45.0), (0.5, 30.0), (2.0, 30.0)])
def test_nl2sql_rate_is_the_cells(rate, seconds):
    """By construction: the requests due in the window number rate x seconds."""
    cell = spec.Cell("mistral-7b-int8.nl2sql")
    cell.cell["request_rate_per_s"] = rate
    rows = traffic._open_structure(cell.traffic, rate, seconds)
    assert sum(0.0 <= due < seconds for _, due, _, _ in rows) == round(rate * seconds)
    assert all(-cell.traffic["lead_in_s"] <= due < seconds for _, due, _, _ in rows)
    assert any(under_way for *_, under_way in rows)


@pytest.mark.parametrize("name", CELLS[1:])
def test_explain_lengths_and_clients(name, tok):
    cell = spec.Cell(name)
    mix = cell.traffic
    sched = traffic.build(cell, 5, 45.0)
    assert sched["clients"] == cell.serving["slots"]
    assert {r["client"] for r in sched["requests"]} == set(range(sched["clients"]))
    for r, (_, p, n) in zip(sched["requests"], _lens(tok, sched)):
        assert mix["prompt"]["tokens"]["lo"] - 2 <= p <= mix["prompt"]["tokens"]["hi"] + 2
        assert n <= mix["output_tokens"]["hi"]
        assert r["order"] == 0 or n >= mix["output_tokens"]["lo"]
    assert len({r["prompt"] for r in sched["requests"]}) == len(sched["requests"])
    assert len({r["system"] for r in sched["requests"]}) == 1
    firsts = [r["max_new_tokens"] for r in sched["requests"] if r["order"] == 0]
    assert statistics.pstdev(firsts) > 0  # staggered: the slots do not retire in step


@pytest.mark.parametrize("name", CELLS)
def test_pool_headroom_rule(name, tok):
    """slots x (longest prompt + longest output) <= 80 % of the pool."""
    cell = spec.Cell(name)
    sv, mix = cell.serving, cell.traffic
    template_tokens = 130  # the chat template's own tokens, an upper bound
    longest_prompt = (mix["prompt"]["tokens"]["hi"] + template_tokens
                      + (mix["system"].get("tokens", {}).get("hi")
                         or len(tok.encode(mix["system"].get("text", "")))))
    bucket = sv["prompt_bucket"]
    need = (-(-longest_prompt // bucket) * bucket + mix["output_tokens"]["hi"]
            + sv["decode_chunk"])
    assert need <= sv["max_seq"] - 1
    assert sv["slots"] * need <= 0.8 * sv["pool_tokens"]


def test_emit_table_is_one_token_a_chunk(tok):
    table = tok.emit_table({tok.eos})
    assert len(table) > 100 and tok.eos not in table.values()
    ids = list(table.values())[:50]
    assert tok.decode(ids) == "".join(tok.decode([i]) for i in ids)
    assert all(table[tok.decode([i])] == i for i in ids)
