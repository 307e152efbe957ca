"""Public model API + dry-run input specs.

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
input of the step function selected by the shape kind (train_step /
prefill_step / serve_step), weak-type-correct and shardable, with no device
allocation.  [audio]/[vlm] train/prefill inputs are precomputed frontend
embeddings (the modality frontend is a stub per the assignment).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tf
from repro.models.transformer import (  # re-exports (public API)
    init_params, forward, loss_fn, decode_step, prefill, init_cache,
)

__all__ = [
    "init_params", "init_params_jit", "forward", "loss_fn", "decode_step",
    "prefill", "init_cache", "input_specs", "param_specs", "cache_specs",
]

# ``init_params`` as ONE compiled program, for the entry points: each
# leaf's f32 normal draw fuses with its bf16 cast, so no f32 copy of a
# whole stacked leaf ever lands on the device — what lets a full-width
# config initialise on the one chip that holds its bf16 weights.
init_params_jit = jax.jit(tf.init_params, static_argnums=1)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def param_specs(cfg: ModelConfig) -> Any:
    """Shape/dtype tree of the parameters (no allocation)."""
    return jax.eval_shape(lambda k: tf.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> Any:
    return jax.eval_shape(
        lambda: tf.init_cache(cfg, batch, max_seq))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Inputs for the step function implied by ``shape.kind``.

    train   -> {"batch": {tokens|embeds, labels}}
    prefill -> {"batch": {tokens|embeds}}
    decode  -> {"cache": <tree>, "tokens": (B,1), "index": ()}
    """
    B, S = shape.global_batch, shape.seq_len
    stub = cfg.frontend != "none"
    if shape.kind == "train":
        if stub:
            batch = {"embeds": _sds((B, S, cfg.d_model), jnp.bfloat16),
                     "labels": _sds((B, S), jnp.int32)}
        else:
            batch = {"tokens": _sds((B, S), jnp.int32),
                     "labels": _sds((B, S), jnp.int32)}
        return {"batch": batch}
    if shape.kind == "prefill":
        if stub:
            return {"batch": {"embeds": _sds((B, S, cfg.d_model), jnp.bfloat16)}}
        return {"batch": {"tokens": _sds((B, S), jnp.int32)}}
    if shape.kind == "decode":
        cache = jax.tree.map(
            lambda x: _sds(x.shape, x.dtype), cache_specs(cfg, B, S))
        return {
            "cache": cache,
            "tokens": _sds((B, 1), jnp.int32),
            "index": _sds((), jnp.int32),
        }
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# Step functions lowered by the dry-run / drivers
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig):
    """Gradient-only step (optimizer handled by repro.train); returns
    (loss, grads) — the canonical object the dry-run lowers for `train`."""
    def train_step(params, batch):
        loss, grads = jax.value_and_grad(tf.loss_fn)(params, batch, cfg)
        return loss, grads
    return train_step


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return tf.prefill(params, batch, cfg)
    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens, index):
        return tf.decode_step(params, cache, tokens, index, cfg)
    return serve_step
