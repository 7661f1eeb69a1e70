"""Prefix cache / pages: prompt tokens the prefix cache supplied, as a share
of all prompt tokens admitted, over the window (`/metrics` `prefix_cache`
reused tokens at the window's two edges; prompt tokens from the flight
records' per-admission `reused + prefilled`)."""


def read(ctx):
    prompt = sum(a["reused"] + a["prefilled"] for r in ctx.flight
                 for a in r.get("prefix_reuse", ()))
    if not prompt:
        return None
    return 100.0 * ctx.serving_delta("prefix_cache", "reused_tokens") / prompt
