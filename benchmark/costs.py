"""Operations and bytes the model needs, from its shapes alone. Kept with
the benchmark so that no later PR can change what a share of the peak is a
share of. `utils/perfmodel`'s `2 * num_params` is not used.

A multiply-add is two operations. Weights served as int8 are converted to
bfloat16 inside the matmul (`ops/quant.mm`: weight-only quantization, bf16
activations), so the arithmetic both configurations use is bfloat16 and the
peak they are held against is the bf16 one; int8 only changes the bytes.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"benchmark/peaks.json with its source")
    return table[device_kind]


def block_matmul_params(cfg: dict) -> int:
    """Weights of the seven matrices of one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return d * nh * hd + 2 * d * kh * hd + nh * hd * d + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def matmul_flops_per_token(cfg: dict) -> int:
    """Block matmuls of all layers for one token (no head, no attention)."""
    return 2 * cfg["num_hidden_layers"] * block_matmul_params(cfg)


def head_flops(cfg: dict) -> int:
    """The head for one position (prefill computes it for a row's last
    position only; decode for every token)."""
    return 2 * head_params(cfg)


def attention_flops(cfg: dict, context: float) -> float:
    """QK and PV of all layers for one query position over `context` keys."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def kv_bytes_per_token(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_dtype_bytes)


def weight_bytes_per_step(cfg: dict) -> int:
    """Bytes of weights one decode step must read: every block matrix in its
    served type (int8: one byte and a float32 scale per output channel), the
    head in bfloat16, the norms; the embedding is a gather of a few rows."""
    fmt = cfg["serving"]["weights"]
    per = {"int8": 1, "bf16": 2}[fmt]
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    blocks = L * block_matmul_params(cfg) * per
    if fmt == "int8":
        f = cfg["intermediate_size"]
        nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        blocks += 4 * L * (nh * hd + 2 * kh * hd + d + 2 * f + d)
    return blocks + 2 * head_params(cfg) + 2 * (2 * L * d + d)


def prefill_attention_positions(reused: int, prefilled: int) -> float:
    """Sum over the prefilled positions of how many keys each attends to."""
    end = reused + prefilled
    return (end * (end + 1) - reused * (reused + 1)) / 2.0
