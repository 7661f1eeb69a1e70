"""A family for `test_families.py` only, never committed under `families/`:
the Llama block, but layer 0's feed-forward is `first_intermediate_size`
wide. Every part has to honour the one difference: the served tree (the
program runs one width, so layer 0 is padded with columns of nought), the
reference (which makes layer 0 at its own width) and the counts."""
import spec

llama = spec.load_module(spec.family_file("llama", "weights"))
MATRICES = llama.MATRICES
tables = llama.tables


def narrowed(cfg: dict) -> dict:
    return {**cfg, "intermediate_size": cfg["first_intermediate_size"]}


def layer(cfg: dict, fmt: str, key, first: bool = False) -> dict:
    return llama.layer(narrowed(cfg) if first else cfg, fmt, key)
