"""The one traffic generator: a mix is a data file under `traffic/`, a cell's
own numbers (its rate) sit under `cells/`, and this turns both and `--seed`
into a schedule of requests for the client. No JAX here.

What the seed decides and what it does not: the structure of a schedule —
arrival instants, sessions, how many questions each asks, every length — is
drawn from the mix's own `schedule_seed`, so every run of a cell offers the
same work at the same instants in the same order: a closed loop at full
slots and a server near its knee both feel the order, and a seed that
reordered the work would be measured as noise. `--seed` writes every text
(and so the weights' reply to it) and deals the closed loop's lanes to its
clients, so no two seeds send the same prompt.

A text kind is a file of its own, `traffic/texts/<kind>.json`: a `head`,
`pieces` appended until the text is long enough, and the word lists their
fields draw from (`{ident}`, `{n}`, `{<list>}`, and `{tag}`, below). The
template that frames system and prompt is the mix's `template`.

Lengths are in tokens of the served tokenizer (`benchmark/tokenizer`).
"""

from __future__ import annotations

import math
import os
import random
import re

import spec


class Tok:
    """The served tokenizer, read by the benchmark's own hand."""

    def __init__(self):
        from tokenizers import Tokenizer

        self.t = Tokenizer.from_file(
            os.path.join(spec.TOKENIZER_DIR, "tokenizer.json"))
        self.bos = self.t.token_to_id("<s>")
        self.eos = self.t.token_to_id("</s>")

    def encode(self, text: str) -> list:
        return self.t.encode(text, add_special_tokens=False).ids

    def decode(self, ids) -> str:
        return self.t.decode(list(ids), skip_special_tokens=True)

    def emit_table(self, stop_ids) -> dict:
        """text -> id for every id that prints as plain ASCII, alone and
        unambiguously, and is no stop id: the ids the seeded head emits."""
        by_text = {}
        for i in range(self.t.get_vocab_size()):
            s = self.decode([i])
            if i in stop_ids or not s or any(not 0x20 <= ord(c) <= 0x7E for c in s):
                continue
            by_text.setdefault(s, []).append(i)
        return {s: ids[0] for s, ids in by_text.items() if len(ids) == 1}


def _draw(rng: random.Random, d: dict) -> int:
    lo, hi = d["lo"], d["hi"]
    if d["dist"] == "loguniform":
        return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
    if d["dist"] == "uniform":
        return rng.randint(lo, hi)
    raise ValueError(f"unknown distribution {d['dist']!r}")


def _fit(tok: Tok, make_piece, n_tokens: int, head: str = "") -> str:
    """Text of `n_tokens` tokens: pieces until long enough, cut at a token."""
    text = head
    ids = tok.encode(text)
    while len(ids) < n_tokens:
        text += make_piece()
        ids = tok.encode(text)
    return tok.decode(ids[:n_tokens])


def _exact(count, text: str, target: int) -> str:
    """`text` trimmed or padded by single characters until `count(text)` is
    exactly `target`. Lengths have to be exact, not near: one token more
    carries a prompt over a chunk boundary, and a seed whose texts happened
    to do that ran 7 % slower on the chip, run after run."""
    for _ in range(256):
        n = count(text)
        if n == target:
            return text
        text = text[:-1] if n > target else text + "x"
    raise ValueError(f"cannot reach {target} tokens")


def _tag(i: int) -> str:
    """Two letters for a number, the fastest-changing first. A `{tag}` opens
    the part of a text that differs from request to request, so two prompts
    part at a token that no seed moves. Without it they part wherever their
    wording happens to: the program reuses a prefix in blocks of 16 tokens,
    and on one seed of five two questions of one session began alike,
    matched one block more, and split a prefill group in two (`tpot_p90_ms`
    -4 %, `ttft_p90_ms` -2 %, run after run)."""
    a = "abcdefghijklmnopqrstuvwxyz"
    return a[i % 26] + a[i // 26 % 26]


_FIELD = re.compile(r"\{(\w+)\}")


def _text(kind: str, tok: Tok, rng: random.Random, n: int, tag: int = 0) -> str:
    """`n` tokens of the text kind `traffic/texts/<kind>.json`."""
    k = spec.load_json("traffic", "texts", kind + ".json")

    def field(m) -> str:
        f = m.group(1)
        if f == "ident":
            return ("_".join(rng.sample(k["words"], rng.randint(1, 3)))
                    + str(rng.randint(0, 99)))
        if f == "n":
            return str(rng.randint(1, 999))
        if f == "tag":
            return _tag(tag)
        return rng.choice(k["lists"][f])

    def piece() -> str:
        ps = k["pieces"]
        return _FIELD.sub(field, ps[0] if len(ps) == 1 else rng.choice(ps))

    return _fit(tok, piece, n, _FIELD.sub(field, k["head"]))


def _open_structure(mix: dict, rate: float, seconds: float) -> list:
    """`[(session, due_s, schema length, under way at the start)]`, due
    relative to the window's start, from `-lead_in_s` to `seconds`.

    Sessions begin at uniform instants — which is what a Poisson process
    is, given how many arrived — early enough that some are under way when
    the schedule begins; each asks a geometric number of questions, every
    one due an exponential think time after the one before it was due. They
    are added one by one from the mix's own random stream until the
    requests due in the window number `rate * seconds`, and the last
    session is cut there: sessions of six questions are bursty, so in tens
    of seconds a free realization's rate is anywhere within half of the
    nominal one, and a cell fixed at "four fifths of the knee" would sit
    above it or far below it by luck. Only the count is fixed; when the
    requests fall inside the window is as the stream has it."""
    rng = random.Random(mix["schedule_seed"])
    ses = mix["session"]
    p = 1.0 / ses["questions_mean"]
    target = int(round(rate * seconds))
    horizon = ses["questions_max"] * ses["think_mean_s"] * 1.5
    begin = -float(mix["lead_in_s"])
    out, sid, inside = [], 0, 0
    while inside < target:
        t = rng.uniform(-horizon, seconds)
        asks = min(ses["questions_max"],
                   1 + int(math.log(1.0 - rng.random()) / math.log(1.0 - p)))
        schema_len = _draw(rng, mix["system"]["tokens"])
        due = t
        for _ in range(asks):
            if due >= seconds or inside >= target:
                break
            if due >= begin:
                out.append((sid, due, schema_len, t < begin))
                inside += due >= 0.0
            due += rng.expovariate(1.0 / ses["think_mean_s"])
        sid += 1
    out.sort(key=lambda r: r[1])
    return out


def build(cell: spec.Cell, seed: int, seconds: float) -> dict:
    """The schedule the client plays: `prewarm` (sent one by one before
    anything is timed), then `requests`, each due at `due_s` from the
    window's start (negative: lead-in, not measured) or, in a closed loop,
    queued on one of `clients` clients."""
    mix, tok = cell.traffic, Tok()
    structure = random.Random(mix["schedule_seed"])
    rng = random.Random(int(seed))
    model = mix["model"]

    def fitted(kind: str, n: int, tag: int, system: str) -> str:
        """A prompt of `n` tokens for `system`, to the token."""
        frame = len(prompt_ids(tok, mix, system, ""))
        return _exact(lambda t: len(prompt_ids(tok, mix, system, t)),
                      _text(kind, tok, rng, n, tag), frame + n)

    requests, prewarm = [], []
    if mix["loop"] == "open":
        rows = _open_structure(mix, float(cell.cell["request_rate_per_s"]), seconds)
        pairs = [(_draw(structure, mix["prompt"]["tokens"]),
                  _draw(structure, mix["output_tokens"])) for _ in rows]
        schemas, asked = {}, {}
        for (sid, due, schema_len, under_way), (q_len, n_out) in zip(rows, pairs):
            if sid not in schemas:
                schemas[sid] = _exact(
                    lambda t: len(tok.encode(t)),
                    _text(mix["system"]["kind"], tok, rng, schema_len, sid),
                    schema_len)
                asked[sid] = 0
                if under_way:
                    # A session already under way has asked before: its
                    # schema is resident. Twice, because the program
                    # publishes a prefix on its second sighting; under tags
                    # that no question of the window has.
                    for k in (24, 25):
                        prewarm.append({"model": model, "system": schemas[sid],
                                        "prompt": fitted(mix["prompt"]["kind"], 16,
                                                         k, schemas[sid]),
                                        "max_new_tokens": 1})
            requests.append({"due_s": due, "session": sid, "model": model,
                             "system": schemas[sid],
                             "prompt": fitted(mix["prompt"]["kind"], q_len,
                                              asked[sid], schemas[sid]),
                             "max_new_tokens": n_out})
            asked[sid] += 1
        clients = 0
    elif mix["loop"] == "closed":
        clients = (cell.serving["slots"] if mix["clients"] == "slots"
                   else int(mix["clients"]))
        per_client = int(mix["requests_per_client"])
        pairs = [(_draw(structure, mix["prompt"]["tokens"]),
                  _draw(structure, mix["output_tokens"]))
                 for _ in range(clients * per_client)]
        # Start stationary: each lane's first request (the lead-in's) is cut
        # to a share of its output, so that the slots do not retire in step.
        # The seed deals the lanes to the clients, which changes nothing a
        # closed loop can feel: the order of the work inside a lane, which it
        # does feel, is the mix's.
        deal = list(range(clients))
        rng.shuffle(deal)
        system = mix["system"]["text"]
        for i, (p_len, n_out) in enumerate(pairs):
            lane, k = i % clients, i // clients
            if k == 0:
                n_out = max(8, int(n_out * (lane + 0.5) / clients))
            requests.append({"client": deal[lane], "order": k, "model": model,
                             "system": system,
                             "prompt": fitted(mix["prompt"]["kind"], p_len, i,
                                              system),
                             "max_new_tokens": n_out})
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for i, r in enumerate(requests):
        r["idx"] = i
    return {"loop": mix["loop"], "clients": clients, "seconds": seconds,
            "lead_in_s": float(mix["lead_in_s"]),
            "drain_s": float(mix["drain_s"]),
            "request_deadline_s": float(mix["request_deadline_s"]),
            "ramp_lane_gap_s": mix.get("ramp_lane_gap_s"),
            "prewarm": prewarm, "requests": requests}


def prompt_ids(tok: Tok, mix: dict, system: str, prompt: str) -> list:
    """The ids the model is meant to see: the mix's `template`, which is the
    studio's own for the role the mix addresses (`serve/templates.py` as
    `serve/factory.assemble_reference_service` registers it), written out
    again as data — the reference tokenizes this, not what the program made
    of the request."""
    t = mix["template"]
    ids = tok.encode(t["text"].replace("{system}", system)
                     .replace("{prompt}", prompt))
    return [tok.bos] + ids if t["bos"] else ids
