"""The Llama family as the program runs it: the program's config object for
a configuration file, and the served tree in the program's layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

import spec
import weights as shared  # benchmark/weights.py: seeds, keys, the emit mask

weights = spec.beside(__file__, "weights")


def config(cfg: dict):
    """The program's `LlamaConfig` for a configuration file."""
    from llm_based_apache_spark_optimization_tpu.models.configs import LlamaConfig

    return LlamaConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        sliding_window=cfg.get("sliding_window"),
        bos_id=cfg["bos_token_id"], eos_id=cfg["eos_token_id"],
        pad_id=cfg.get("pad_token_id", 0))


def served_tree(cfg: dict, fmt: str, seed: int, emit_ids):
    """The whole tree in the program's layout (`models/llama.init_params`:
    blocks stacked on a leading layer axis), in one jitted call."""
    k_t, k_l = shared.keys_for(seed, cfg["num_hidden_layers"])
    mask = jnp.asarray(shared.emit_mask(cfg, emit_ids))

    @jax.jit
    def make(k_t, k_l, mask):
        blocks = jax.lax.map(lambda k: weights.layer(cfg, fmt, k), k_l)
        return {**weights.tables(cfg, k_t, mask), "blocks": blocks}

    return make(k_t, k_l, mask)
