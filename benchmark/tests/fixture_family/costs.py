"""The fixture family's counts: the Llama family's, less what layer 0's
narrower feed-forward spares a token (operations) and a step (bytes)."""
import spec

llama = spec.load_module(spec.family_file("llama", "costs"))


def spared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * (cfg["intermediate_size"]
                                     - cfg["first_intermediate_size"])


def step_ops(ctx):
    ops = llama.step_ops(ctx)
    if ops is None:
        return None
    tokens = (sum(r.get("emitted", 0) for r in ctx.flight_traced)
              + ctx.traced_prefill()["positions"])
    return ops - 2 * spared_params(ctx.cfg) * tokens


def decode_step_bytes(ctx):
    return llama.decode_step_bytes(ctx) - 2 * spared_params(ctx.cfg)
