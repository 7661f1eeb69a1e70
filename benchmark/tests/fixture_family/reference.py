"""The fixture family's plain reference: the Llama family's layer, embedding
and head functions, layer 0 made at its own, narrower width."""
import jax.numpy as jnp

import spec
import weights as shared

weights = spec.beside(__file__, "weights")
llama = spec.load_module(spec.family_file("llama", "reference"))


def logits_at(cfg, fmt, seed, emit_ids, tokens, at, control=None):
    layer_fn, embed_fn, head_fn = llama._make_fns(cfg, fmt, control)
    first_fn = llama._make_fns(weights.narrowed(cfg), fmt, control)[0]
    k_t, k_l = shared.keys_for(seed, cfg["num_hidden_layers"])
    mask = jnp.asarray(shared.emit_mask(cfg, emit_ids))
    x = embed_fn(k_t, mask, jnp.asarray(tokens, jnp.int32))
    for l in range(cfg["num_hidden_layers"]):
        x = (layer_fn if l else first_fn)(k_l[l], x)
    return head_fn(k_t, mask, x, jnp.asarray(at, jnp.int32))
