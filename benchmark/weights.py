"""Weights from `--seed`, made by the benchmark and by nothing else: what
every model family shares. The pieces themselves (`layer`, `tables`) and
the tree in the program's layout are the family's
(`families/<family>/weights.py`, `program.py`), found through the
configuration's `family` (`spec.family`).

The served tokenizer (`benchmark/tokenizer`) has 320 entries, the models
32,000 and 49,152. A random head would put nearly every greedy token outside
the tokenizer, where it prints as nothing and the stream would carry no
chunk to time. So a family scales the head's rows outside `emit_ids` (the
ids that print as plain ASCII and are no stop id) by `HEAD_DAMP`: every
greedy token then prints, one NDJSON chunk per token, and the full-width
head is still computed.
"""

from __future__ import annotations

import jax
import numpy as np

HEAD_DAMP = 1.0 / 64.0


def keys_for(seed: int, num_layers: int):
    """(key of the tables, keys of the layers). The seed may be wider than
    32 signed bits; `jax.random.key` takes a Python int of 64."""
    root = jax.random.key(int(seed))
    k_t, k_l = jax.random.split(root)
    return k_t, jax.random.split(k_l, num_layers)


def emit_mask(cfg: dict, emit_ids) -> np.ndarray:
    mask = np.zeros(cfg["vocab_size"], bool)
    mask[np.asarray(list(emit_ids), np.int64)] = True
    return mask
