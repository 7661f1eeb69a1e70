"""The Llama family's weights from a key: one function per piece (`layer`,
`tables`), each a pure function of the configuration and a key. The served
tree (`program.py` beside this file) is the same functions mapped over the
layer keys inside ONE jitted call, born on the device in the type it is
served in; the plain reference (`reference.py` beside it) calls them again,
one layer at a time, and so takes nothing the program has touched. Seeds,
keys and the mask of the ids that print are every family's
(`benchmark/weights.py`).

Formats (`serving.weights` in the configuration file):

- `int8`: the seven block matrices are int8 `[in, out]` with one float32
  scale per output channel (random in 0.75..1.25 of `sqrt(3 / fan_in) / 127`,
  so the stated weights have bf16's deviation and a path that dropped or
  transposed the scales cannot pass); embeddings,
  head and norms bfloat16. The stated weights ARE `q8 * scale`.
- `bf16`: every matrix bfloat16, normal with deviation `fan_in**-0.5`.

The head's rows outside `emit_ids` are scaled by `HEAD_DAMP`
(`benchmark/weights.py` says why). Norm weights are random about 1.
`init.qk_gain` in the configuration file multiplies the query and key
matrices; the file says why.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import HEAD_DAMP

MATRICES = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return {"wq": (d, nh * hd), "wk": (d, kh * hd), "wv": (d, kh * hd),
            "wo": (nh * hd, d), "wg": (d, f), "wu": (d, f), "wd": (f, d)}


def layer(cfg: dict, fmt: str, key) -> dict:
    """One layer's weights in the served types."""
    out = {}
    keys = jax.random.split(key, 2 * len(MATRICES) + 2)
    qk_gain = cfg.get("init", {}).get("qk_gain", 1.0)
    gains = {"wq": qk_gain, "wk": qk_gain}
    for i, (name, (n_in, n_out)) in enumerate(shapes(cfg).items()):
        gain = float(gains.get(name, 1.0))
        if fmt == "int8":
            q = jax.lax.bitcast_convert_type(
                jax.random.bits(keys[2 * i], (n_in, n_out), jnp.uint8), jnp.int8)
            q = jnp.maximum(q, jnp.int8(-127))
            # uniform int8 has deviation 127/sqrt(3): the scale makes the
            # stated weight's deviation gain * fan_in**-0.5, as bf16's is
            s = (gain * (3.0 / n_in) ** 0.5 / 127.0) * jax.random.uniform(
                keys[2 * i + 1], (n_out,), jnp.float32, 0.75, 1.25)
            out[name] = {"q8": q, "s": s}
        elif fmt == "bf16":
            out[name] = (jax.random.normal(keys[2 * i], (n_in, n_out), jnp.float32)
                         * (gain * n_in ** -0.5)).astype(jnp.bfloat16)
        else:
            raise ValueError(f"unknown weight format {fmt!r}")
    d = cfg["hidden_size"]
    for j, name in enumerate(("ln_attn", "ln_mlp")):
        out[name] = jax.random.uniform(keys[-2 + j], (d,), jnp.float32,
                                       0.8, 1.2).astype(jnp.bfloat16)
    return out


def tables(cfg: dict, key, emit_mask) -> dict:
    """Embedding, head and final norm. `emit_mask` is a bool `[vocab]`."""
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    k_e, k_h, k_n = jax.random.split(key, 3)
    damp = jnp.where(emit_mask, 1.0, HEAD_DAMP)[:, None]

    def table(k, damped):
        t = jax.random.normal(k, (v, d), jnp.float32) * d ** -0.5
        return (t * damp if damped else t).astype(jnp.bfloat16)

    tied = bool(cfg["tie_word_embeddings"])
    out = {"embed": table(k_e, tied),
           "final_norm": jax.random.uniform(k_n, (d,), jnp.float32, 0.8,
                                            1.2).astype(jnp.bfloat16)}
    if not tied:
        out["lm_head"] = table(k_h, True)
    return out
