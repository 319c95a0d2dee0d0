"""Architecture registry + per-(arch x shape) input specs.

``get_config(arch_id)`` returns the exact published configuration;
``input_specs(cfg, shape)`` returns weak-type-correct ShapeDtypeStruct
stand-ins for every model input of that cell (never allocates device
memory — the dry-run pattern)."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.shapes import SHAPES, ShapeSpec, applicable_shapes  # noqa: F401
from repro.models.config import ModelConfig

_MODULES = {
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "zamba2-1.2b": "zamba2_1p2b",
    "musicgen-medium": "musicgen_medium",
    "deepseek-7b": "deepseek_7b",
    "stablelm-12b": "stablelm_12b",
    "minicpm3-4b": "minicpm3_4b",
    "granite-34b": "granite_34b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "xlstm-1.3b": "xlstm_1p3b",
    "deepseek-v3": "deepseek_v3",
    "gigachat3.5-432b-a28b": "gigachat35_432b_a28b",
    "glm-5.2": "glm52",
}

ARCH_IDS = list(_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.config


def effective_microbatches(cfg: ModelConfig, shape: ShapeSpec, dp_size: int = 16) -> int:
    """Microbatch count adapted to the mesh: each microbatch's global batch
    must stay divisible by the DP width (a 2-pod mesh doubles DP, so the
    per-pod microbatch count halves while per-device activations stay
    constant)."""
    if shape.kind != "train":
        return 1
    n = min(cfg.train_microbatches, max(1, shape.global_batch // dp_size))
    while shape.global_batch % n:
        n -= 1
    return max(1, n)


def input_specs(cfg: ModelConfig, shape: ShapeSpec | str, dp_size: int = 16) -> dict:
    """ShapeDtypeStructs for the step inputs of one (arch x shape) cell.

    train:   {"tokens"/"codes"/"embeds"(+positions), "labels"}
    prefill: model inputs for the full prompt (no cache)
    decode:  one new token + "cur_index"; the cache struct comes from
             :func:`cache_specs`."""
    if isinstance(shape, str):
        shape = SHAPES[shape]
    B = shape.global_batch
    S = 1 if shape.kind == "decode" else shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32

    # training batches arrive pre-split into microbatches: (N, B/N, ...)
    N = effective_microbatches(cfg, shape, dp_size)
    if N > 1:
        assert B % N == 0, (B, N)
        lead: tuple = (N, B // N)
    else:
        lead = (B,)

    specs: dict = {}
    if cfg.frontend == "audio_codes":
        specs["codes"] = jax.ShapeDtypeStruct((*lead, S, cfg.n_codebooks), i32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((*lead, S, cfg.n_codebooks), i32)
    elif cfg.frontend == "vision_embeds":
        specs["embeds"] = jax.ShapeDtypeStruct((*lead, S, cfg.d_model), dt)
        if shape.kind == "train":
            specs["positions"] = jax.ShapeDtypeStruct((N, 3, B // N, S), i32) \
                if N > 1 else jax.ShapeDtypeStruct((3, B, S), i32)
            specs["labels"] = jax.ShapeDtypeStruct((*lead, S), i32)
        else:
            specs["positions"] = jax.ShapeDtypeStruct((3, B, S), i32)
    else:
        specs["tokens"] = jax.ShapeDtypeStruct((*lead, S), i32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((*lead, S), i32)
    if shape.kind == "decode":
        specs["cur_index"] = jax.ShapeDtypeStruct((), i32)
        if cfg.frontend == "vision_embeds":
            specs["positions"] = jax.ShapeDtypeStruct((3, B, 1), i32)
    return specs


def cache_specs(cfg: ModelConfig, shape: ShapeSpec | str):
    """ShapeDtypeStruct pytree for the decode cache of one cell."""
    from repro.models.transformer import init_cache

    if isinstance(shape, str):
        shape = SHAPES[shape]
    assert shape.kind == "decode"
    return jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
