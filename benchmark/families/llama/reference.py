"""The Llama family's plain reference: a forward pass in straightforward
`jax.numpy`, float32 with `highest` matmul precision, no kernel, no cache,
no batching tricks: RMSNorm, rotary embedding (the published rotate-half
form), grouped-query attention under a causal (and, where the configuration
has one, sliding-window) mask, SwiGLU, the head. It imports nothing of the
program and makes its own weights from the seed (`weights.py` beside this
file), one layer at a time, so that a 7B model in float32 fits beside
nothing else on the chip. What is compared, and the control's precisions,
are every family's: `benchmark/reference.py`.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

import spec
import weights as shared  # benchmark/weights.py: seeds, keys, the emit mask
from reference import HI, _f32, _fp8_rows, degrade

weights = spec.beside(__file__, "weights")


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
    k_t, k_l = shared.keys_for(seed, cfg["num_hidden_layers"])
    mask = jnp.asarray(shared.emit_mask(cfg, emit_ids))
    x = embed_fn(k_t, mask, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        x = layer_fn(k_l[l], x)
    return head_fn(k_t, mask, x, jnp.asarray(at, jnp.int32))
