"""`flash_gqa_attention[.N]` (ops/pallas/attention.py): prefill's attention
over a contiguous row view, one call per layer and chunk batch.

Needs, for the positions a traced span prefilled: QK and PV of each position
over the keys before it, and K and V of a row's context read once per chunk
of `chunk` positions (bf16), the queries in and the output out."""

EVENT = r"^flash_gqa_attention"


def cost(cfg: dict, attended: float, positions: float, chunk: int) -> tuple:
    """(operations, bytes) of ONE layer over the traced span's prefill:
    `attended` = sum over prefilled positions of the keys each attends to."""
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = 4.0 * nh * hd * attended
    bytes_ = 2.0 * kh * hd * 2 * attended / chunk + 2.0 * positions * nh * hd * 2
    return ops, bytes_
