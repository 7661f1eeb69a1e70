"""Checkpoint layer: HF safetensors -> sharded JAX param trees, plus a native
resharded cache.

This is the TPU build's equivalent of the reference stack's weight handling —
there, GGUF blobs are downloaded and memory-mapped by Ollama/llama.cpp
("locally downloaded Ollama model", reference Project Report ch.3); here the
framework owns the loading path end-to-end (SURVEY.md §5 "Checkpoint /
resume"): read HF-format safetensors, map tensor names onto the
`models.llama.init_params` tree, stack per-layer weights for the scanned
block, cast to the serving dtype, and place directly onto a TP×DP mesh.
"""

from .hf import config_from_hf, load_hf_checkpoint, save_hf_checkpoint  # noqa: F401
from .cache import load_native, save_native  # noqa: F401
from .gguf import config_from_gguf, load_gguf_checkpoint, write_gguf  # noqa: F401


def load_and_quantize(load_raw, mesh=None, *, quantize_int8=False,
                      quantize_int4=False, quantize_unembed8=False):
    """`load_raw(mesh) -> (cfg, params)` plus the serving quantization,
    placed on `mesh`: the one load path behind every backend factory.

    A tree that is to be quantized loads unplaced, quantizes, and only then
    goes to the mesh — what ships to the devices (and what a supervisor's
    rebuild closure keeps alive) is the quantized, placed tree, never the
    full-precision one."""
    if quantize_int8 and quantize_int4:
        raise ValueError("pick one of quantize_int8 / quantize_int4")
    if not (quantize_int8 or quantize_int4 or quantize_unembed8):
        return load_raw(mesh)
    from ..ops.quant import (
        quantize_params,
        quantize_params_int4,
        quantize_unembed,
    )

    cfg, params = load_raw(None)
    if quantize_int4:
        params = quantize_params_int4(params)
    elif quantize_int8:
        params = quantize_params(params)
    if quantize_unembed8:
        # Per-row int8 embed/unembed tables (composes with either block
        # quantization — or none).
        params = quantize_unembed(params)
    if mesh is not None:
        from ..parallel.sharding import shard_params

        params = shard_params(params, cfg, mesh)
    return cfg, params
