"""Operations and bytes a Llama-family model needs. Kept with the benchmark
so that no later PR can change what a share of the peak is a share of.
`utils/perfmodel`'s `2 * num_params` is not used.

A multiply-add is two operations. Weights served as int8 are converted to
bfloat16 inside the matmul (`ops/quant.mm`: weight-only quantization, bf16
activations), so the arithmetic both configurations use is bfloat16 and the
peak they are held against is the bf16 one; int8 only changes the bytes.

`step_ops` and `decode_step_bytes` are what the two whole-step readers
(`layer_metrics/step_mfu.py`, `decode_hbm_pct.py`) ask a family for. They
are given the readers' `layers.Context` and not only the shapes: a dense
block needs the same of every token, but what a sparse or windowed step must
read depends on what the traced rounds did. The shape functions above them
serve the kernel counts (`kernels/*.py` count their own) and the tests.
"""

from __future__ import annotations


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


def step_ops(ctx):
    """Operations the tokens of the traced rounds need: block matmuls for
    every decoded and prefilled position, the head for every decoded token
    and once a prefilled row, attention over the keys each position attends
    to. None where the rounds decoded and prefilled nothing."""
    cfg = ctx.cfg
    dec, pre = ctx.traced_decode(), ctx.traced_prefill()
    decoded = sum(r.get("emitted", 0) for r in ctx.flight_traced)
    rows = sum(len(r.get("prefix_reuse", ())) for r in ctx.flight_traced)
    if decoded + pre["positions"] <= 0:
        return None
    ctx_per_token = (dec["live_tokens"] / dec["active_slots"]) if dec else 0.0
    return (matmul_flops_per_token(cfg) * (decoded + pre["positions"])
            + head_flops(cfg) * (decoded + rows)
            + attention_flops(cfg, ctx_per_token) * decoded
            + attention_flops(cfg, 1.0) * pre["attended"])


def decode_step_bytes(ctx):
    """Bytes one decode step of the traced rounds must read: the weights in
    their served type and the KV of every live token of every slot."""
    return (weight_bytes_per_step(ctx.cfg)
            + kv_bytes_per_token(ctx.cfg) * ctx.traced_decode()["live_tokens"])
