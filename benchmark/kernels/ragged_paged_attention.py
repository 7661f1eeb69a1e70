"""`ragged_paged_attention[.N]` (ops/pallas/paged_attention.py): decode's
read of the paged KV pool, one call per layer and step.

Needs: K and V of every live token of every active slot, once (bf16), the
queries in and the output out; QK and PV for one query position a slot."""

EVENT = r"^ragged_paged_attention"


def cost(cfg: dict, live_tokens: float, active_slots: float) -> tuple:
    """(operations, bytes) of ONE call: one layer, one step."""
    nh, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    ops = 4.0 * nh * hd * live_tokens
    bytes_ = 2.0 * kh * hd * 2 * live_tokens + 2.0 * active_slots * nh * hd * 2
    return ops, bytes_
