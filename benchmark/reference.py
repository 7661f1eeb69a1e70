"""The comparison that decides `correct`, and what every family's plain
reference shares with it.

The forward pass itself is the family's (`families/<family>/reference.py`,
`logits_at`, found through the configuration's `family`): float32 at
`highest` precision, no kernel, no cache, importing nothing of the program
and making its own weights from the seed, one layer at a time.

What is compared: for a sample of the requests the window finished, the
prompt as the studio's template and the served tokenizer make it, followed
by the tokens that were served. The reference runs once over each and reads,
at every served position, how far the served token's logit lies below the
reference's best: the widest such gap is the number, `max_logit_gap`. A
greedy path in the stated precision stays within rounding of 0; a token
altered anywhere, a wrong page, a stale prefix or a lower precision does not.

The control (`control=...`) is the same reference with its weights rounded
to the nearest precision below the stated one (and, for float8, what goes
into each weight matmul rounded too, as a float8 matmul takes it); it need
not decode: at each position it reads the gap of the token the lower
precision puts first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import spec

HI = jax.lax.Precision.HIGHEST


def _f32(w) -> jnp.ndarray:
    if isinstance(w, dict):  # int8 with per-output-channel scales
        return w["q8"].astype(jnp.float32) * w["s"][None, :]
    return w.astype(jnp.float32)


def _fp8_rows(x):
    """Activations as a float8 matmul would take them: rounded to e4m3 with
    one scale a row (a token)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def degrade(w, how: str):
    """One matrix in the nearest precision below the stated one."""
    if how == "int4":  # stated int8: 4 bits per weight, scale per channel
        q = jnp.clip(jnp.round(w["q8"].astype(jnp.float32) * (7.0 / 127.0)), -7, 7)
        return q * (w["s"] * (127.0 / 7.0))[None, :]
    if how == "fp8_e4m3":  # stated bf16: float8 with a scale per channel
        w32 = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(w32), axis=0, keepdims=True) / 448.0
        return (w32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {how!r}")


def pack(samples: list, rows: int, width: int, n_out: int):
    """`samples`: [(prompt ids, served ids)] -> tokens [rows, width], the
    positions whose logits choose each served token [rows, n_out], the
    served tokens [rows, n_out] and which of them are real."""
    tokens = np.zeros((rows, width), np.int32)
    at = np.zeros((rows, n_out), np.int32)
    served = np.zeros((rows, n_out), np.int32)
    real = np.zeros((rows, n_out), bool)
    for r, (prompt, out) in enumerate(samples[:rows]):
        seq = (list(prompt) + list(out))[:width]
        tokens[r, :len(seq)] = seq
        n = min(len(out), n_out, width - len(prompt))
        at[r, :n] = len(prompt) - 1 + np.arange(n)
        served[r, :n] = out[:n]
        real[r, :n] = True
    return tokens, at, served, real


def max_logit_gap(cfg: dict, fmt: str, seed: int, emit_ids, samples: list,
                  rows: int, width: int, n_out: int, control=None) -> dict:
    """The comparison. Without `control`: the widest gap by which a served
    token's logit lies below the reference's best. With it: the widest gap
    of the token the lower precision puts first at the same positions."""
    logits_at = spec.family(cfg, "reference").logits_at
    tokens, at, served, real = pack(samples, rows, width, n_out)
    ref = logits_at(cfg, fmt, seed, emit_ids, tokens, at)
    if control:
        low = logits_at(cfg, fmt, seed, emit_ids, tokens, at, control=control)
        chosen = jnp.argmax(low, axis=-1)
        del low
    else:
        chosen = jnp.asarray(served)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    gap = np.asarray(jnp.where(jnp.asarray(real), best - got, 0.0))
    agree = np.asarray((jnp.argmax(ref, axis=-1) == chosen) & jnp.asarray(real))
    spread = float(jnp.std(jnp.where(jnp.asarray(real)[..., None], ref, 0.0)))
    return {"max_logit_gap": float(gap.max()) if real.any() else float("nan"),
            "tokens_compared": int(real.sum()),
            "argmax_agree": int(agree.sum()),
            "logit_std": spread,
            "per_row_gap": [float(g) for g in gap.max(axis=1)]}
