"""Seeded random weights for a dense GQA configuration.

The benchmark makes the weights, hands them to the program in the
layout its serve path takes, and regenerates them leaf by leaf, layer by
layer, for the plain reference (``bench/reference.py``), which so never
reads an array the program holds.  Every leaf is drawn from its own key,
``fold_in(fold_in(run_key, leaf), layer)``, so the whole-model draw (one
jitted call on the device, in bfloat16, the type the weights are served
in) and the one-layer draw produce the same values.  The run key is an
argument of the compiled program, so one compilation serves every seed.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16
NORM_SCALE = 0.1          # norm gains are 1 + N(0, NORM_SCALE^2)


def run_key(seed: int) -> np.ndarray:
    """Raw threefry key of a seed of any width up to 64 bits."""
    s = int(seed) & ((1 << 64) - 1)
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


def padded_vocab(c: Dict) -> int:
    return -(-c["vocab_size"] // 256) * 256


def leaf_specs(c: Dict) -> List[Tuple[str, Tuple[int, ...], float, bool]]:
    """(path, shape of one layer's leaf, std, stacked over layers).  The
    index of an entry is its leaf id in the key derivation."""
    d, H, K = c["d_model"], c["num_heads"], c["num_kv_heads"]
    hd, ff, V = c["head_dim"], c["d_ff"], padded_vocab(c)
    return [
        ("embed", (V, d), 0.02, False),
        ("head", (d, V), 1.0 / math.sqrt(d), False),
        ("final_norm", (d,), NORM_SCALE, False),
        ("blocks/attn/wq", (d, H * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wk", (d, K * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wv", (d, K * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wo", (H * hd, d), 1.0 / math.sqrt(H * hd), True),
        ("blocks/ffn/w_gate", (d, ff), 1.0 / math.sqrt(d), True),
        ("blocks/ffn/w_up", (d, ff), 1.0 / math.sqrt(d), True),
        ("blocks/ffn/w_down", (ff, d), 1.0 / math.sqrt(ff), True),
        ("blocks/norm1", (d,), NORM_SCALE, True),
        ("blocks/norm2", (d,), NORM_SCALE, True),
    ]


def _draw(key, leaf: int, layer, shape, std):
    k = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(DTYPE)


def _put(tree: Dict, path: str, value) -> None:
    *head, last = path.split("/")
    for p in head:
        tree = tree.setdefault(p, {})
    tree[last] = value


def _frozen(c: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _make_fn(frozen: Tuple):
    c = dict(frozen)
    L = c["num_layers"]
    specs = leaf_specs(c)

    def make(key):
        tree: Dict = {}
        for i, (path, shape, std, stacked) in enumerate(specs):
            if stacked:
                v = jax.vmap(lambda l, i=i, shape=shape, std=std:
                             _draw(key, i, l, shape, std))(
                                 jnp.arange(L, dtype=jnp.uint32))
            else:
                v = _draw(key, i, 0, shape, std)
            _put(tree, path, v)
        return tree

    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _leaf_fn(frozen: Tuple, leaf: int):
    c = dict(frozen)
    _, shape, std, _ = leaf_specs(c)[leaf]
    return jax.jit(lambda key, l: _draw(key, leaf, l, shape, std))


def make_params(c: Dict, seed: int) -> Dict:
    """The whole model's weights in the serve layout (norm leaves hold
    the gain minus one, as the program's norms add the one)."""
    return _make_fn(_frozen(c))(jnp.asarray(run_key(seed)))


def leaf(c: Dict, seed: int, path: str, layer: int = 0) -> jax.Array:
    """One leaf (one layer's slice of a stacked leaf), bit for bit the
    slice ``make_params`` holds."""
    for i, (p, _, _, _) in enumerate(leaf_specs(c)):
        if p == path:
            return _leaf_fn(_frozen(c), i)(jnp.asarray(run_key(seed)),
                                            jnp.uint32(layer))
    raise KeyError(path)


def layer_paths(c: Dict) -> List[str]:
    return [p for p, _, _, stacked in leaf_specs(c) if stacked]
