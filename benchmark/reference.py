"""The plain reference, and the comparison that decides `correct`.

A Llama-family forward pass in straightforward `jax.numpy`, float32 with
`highest` matmul precision, no kernel, no cache, no batching tricks: RMSNorm,
rotary embedding (the published rotate-half form), grouped-query attention
under a causal (and, where the configuration has one, sliding-window) mask,
SwiGLU, the head. It imports nothing of the program and makes its own
weights from the seed (`weights.py`), one layer at a time, so that a 7B model
in float32 fits beside nothing else on the chip.

What is compared: for a sample of the requests the window finished, the
prompt as the studio's template and the served tokenizer make it, followed
by the tokens that were served. The reference runs once over each and reads,
at every served position, how far the served token's logit lies below the
reference's best: the widest such gap is the number, `max_logit_gap`. A
greedy path in the stated precision stays within rounding of 0; a token
altered anywhere, a wrong page, a stale prefix or a lower precision does not.

The control (`control=...`) is this same reference with its weights rounded
to the nearest precision below the stated one (and, for float8, what goes
into each weight matmul rounded too, as a float8 matmul takes it); it need
not decode: at each position it reads the gap of the token the lower
precision puts first.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

import weights

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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv  # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_row(cfg: dict, w: dict, x, act=None):
    """One layer over one sequence `x [T, D]`. `act` (the control's) rounds
    what goes into each weight matmul."""
    t = x.shape[0]
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, pos = cfg["rms_norm_eps"], jnp.arange(t)
    def mm(a, b):
        return jnp.matmul(act(a) if act else a, b, precision=HI)

    h = _rms(x, w["ln_attn"], eps)
    q = _rope(mm(h, w["wq"]).reshape(t, nh, hd), pos, cfg["rope_theta"])
    k = _rope(mm(h, w["wk"]).reshape(t, kh, hd), pos, cfg["rope_theta"])
    v = mm(h, w["wv"]).reshape(t, kh, hd)
    k, v = (jnp.repeat(a, nh // kh, axis=1) for a in (k, v))
    s = jnp.einsum("qnh,knh->nqk", q, k, precision=HI) * hd ** -0.5
    keep = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        keep &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("nqk,knh->qnh", p, v, precision=HI).reshape(t, nh * hd)
    x = x + mm(a, w["wo"])
    h = _rms(x, w["ln_mlp"], eps)
    return x + mm(jax.nn.silu(mm(h, w["wg"])) * mm(h, w["wu"]), w["wd"])


_FNS = {}


def _make_fns(cfg: dict, fmt: str, control):
    key = (json.dumps({k: cfg[k] for k in sorted(cfg) if k != "serving"},
                      sort_keys=True, default=str), fmt, control)
    if key not in _FNS:
        _FNS[key] = _build_fns(cfg, fmt, control)
    return _FNS[key]


def _build_fns(cfg: dict, fmt: str, control):
    # A float8 path rounds both operands of a matmul; an int4 path is
    # weight-only, as the stated int8 path is (`ops/quant.mm`).
    act = _fp8_rows if control == "fp8_e4m3" else None

    @jax.jit
    def layer_fn(key, x):  # x [R, T, D]; the layer's weights are made here
        raw = weights.layer(cfg, fmt, key)
        w = {n: (degrade(raw[n], control) if control else _f32(raw[n]))
             for n in weights.MATRICES}
        w.update({n: raw[n].astype(jnp.float32) for n in ("ln_attn", "ln_mlp")})
        return jax.lax.map(lambda row: _layer_row(cfg, w, row, act), x)

    @jax.jit
    def embed_fn(key, mask, tokens):
        t = weights.tables(cfg, key, mask)
        return jnp.take(t["embed"].astype(jnp.float32), tokens, axis=0)

    @jax.jit
    def head_fn(key, mask, x, at):  # x [R, T, D]; at [R, N] positions
        t = weights.tables(cfg, key, mask)
        xs = jnp.take_along_axis(x, at[:, :, None], axis=1)
        xs = _rms(xs, t["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
        head = t["embed"] if cfg["tie_word_embeddings"] else t["lm_head"]
        return jnp.einsum("rnd,vd->rnv", xs, head.astype(jnp.float32),
                          precision=HI)

    return layer_fn, embed_fn, head_fn


def logits_at(cfg: dict, fmt: str, seed: int, emit_ids, tokens: np.ndarray,
              at: np.ndarray, control=None) -> jnp.ndarray:
    """Reference logits `[R, N, V]` at positions `at [R, N]` of `tokens [R, T]`."""
    layer_fn, embed_fn, head_fn = _make_fns(cfg, fmt, control)
    k_t, k_l = weights.keys_for(seed, cfg["num_hidden_layers"])
    mask = jnp.asarray(weights.emit_mask(cfg, emit_ids))
    x = embed_fn(k_t, mask, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        x = layer_fn(k_l[l], x)
    return head_fn(k_t, mask, x, jnp.asarray(at, jnp.int32))


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
