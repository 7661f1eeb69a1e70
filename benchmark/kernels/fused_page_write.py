"""`fused_page_write[.N]` (ops/pallas/paged_write.py): decode's write of one
fresh K and V row a slot into its page, one call per layer and step. Moves
whole pages through VMEM: a page of K and of V in and out for each slot.
No metric yet (0.18 ms of a 22 ms step, PERF.md section 5): it has its
operation/byte function and its line in `breakdown`."""

EVENT = r"^fused_page_write"


def cost(cfg: dict, active_slots: float, page_size: int) -> tuple:
    kh, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    return 0.0, 2.0 * 2 * active_slots * kh * page_size * hd * 2
