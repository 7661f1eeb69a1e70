"""The fixture family as the program runs it: the program has one
feed-forward width, so layer 0's narrower matrices are padded with nought to
it (`silu(0) * 0` adds nothing) and stacked with the others."""
import jax
import jax.numpy as jnp

import spec
import weights as shared

weights = spec.beside(__file__, "weights")
config = spec.load_module(spec.family_file("llama", "program")).config


def served_tree(cfg: dict, fmt: str, seed: int, emit_ids):
    if fmt != "bf16":
        raise ValueError("the fixture family is served in bf16 only")
    k_t, k_l = shared.keys_for(seed, cfg["num_hidden_layers"])
    mask = jnp.asarray(shared.emit_mask(cfg, emit_ids))
    more = cfg["intermediate_size"] - cfg["first_intermediate_size"]
    pad = {"wg": ((0, 0), (0, more)), "wu": ((0, 0), (0, more)),
           "wd": ((0, more), (0, 0))}

    @jax.jit
    def make(k_t, k_l, mask):
        first = weights.layer(cfg, fmt, k_l[0], first=True)
        first = {n: jnp.pad(w, pad[n]) if n in pad else w
                 for n, w in first.items()}
        rest = jax.lax.map(lambda k: weights.layer(cfg, fmt, k), k_l[1:])
        blocks = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b]),
                              first, rest)
        return {**weights.tables(cfg, k_t, mask), "blocks": blocks}

    return make(k_t, k_l, mask)
