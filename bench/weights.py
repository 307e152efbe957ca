"""Seeded random weights for the leaves a configuration's module lists.

The benchmark makes the weights, hands them to the program in the
layout its serve path takes, and regenerates them leaf by leaf, layer by
layer, for the module's plain reference, which so never reads an array
the program holds.  A module's ``leaf_specs(c)`` lists the leaves as
``(path, shape of one layer's leaf, std, stacked over layers)``; the
index of an entry is its leaf id.  Every leaf is drawn from its own key,
``fold_in(fold_in(run_key, leaf), layer)``, so the whole-model draw (one
jitted call on the device, in bfloat16, the type the weights are served
in) and the one-layer draw produce the same values.  The run key is an
argument of the compiled program, so one compilation serves every seed.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16


def run_key(seed: int) -> np.ndarray:
    """Raw threefry key of a seed of any width up to 64 bits."""
    s = int(seed) & ((1 << 64) - 1)
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


Spec = Tuple[str, Tuple[int, ...], float, bool]


def _draw(key, leaf: int, layer, shape, std):
    k = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(DTYPE)


def _put(tree: Dict, path: str, value) -> None:
    *head, last = path.split("/")
    for p in head:
        tree = tree.setdefault(p, {})
    tree[last] = value


@functools.lru_cache(maxsize=None)
def _make_fn(specs: Tuple[Spec, ...], layers: int):
    def make(key):
        tree: Dict = {}
        for i, (path, shape, std, stacked) in enumerate(specs):
            if stacked:
                v = jax.vmap(lambda l, i=i, shape=shape, std=std:
                             _draw(key, i, l, shape, std))(
                                 jnp.arange(layers, dtype=jnp.uint32))
            else:
                v = _draw(key, i, 0, shape, std)
            _put(tree, path, v)
        return tree

    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _leaf_fn(spec: Spec, leaf: int):
    _, shape, std, _ = spec
    return jax.jit(lambda key, l: _draw(key, leaf, l, shape, std))


def make_params(specs: Sequence[Spec], layers: int, seed: int) -> Dict:
    """The whole model's weights in the serve layout: every leaf of
    ``specs``, the stacked ones over ``layers`` layers."""
    return _make_fn(tuple(specs), layers)(jnp.asarray(run_key(seed)))


def leaf(specs: Sequence[Spec], seed: int, path: str,
         layer: int = 0) -> jax.Array:
    """One leaf (one layer's slice of a stacked leaf), bit for bit the
    slice ``make_params`` holds."""
    for i, spec in enumerate(specs):
        if spec[0] == path:
            return _leaf_fn(tuple(spec), i)(jnp.asarray(run_key(seed)),
                                            jnp.uint32(layer))
    raise KeyError(path)


def layer_paths(specs: Sequence[Spec]) -> List[str]:
    return [p for p, _, _, stacked in specs if stacked]
