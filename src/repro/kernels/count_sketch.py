"""Pallas TPU kernel: count-sketch apply as a blocked signed-one-hot MXU
matmul.

TPUs have no efficient random scatter; the TPU-native formulation of
CS(x)_j = sum_{h(i)=j} s(i) x(i) is y = x @ O with O[i, j] = s(i)*[h(i)=j].
The kernel builds each (bI, bJ) one-hot tile IN VMEM from the hash tables
(broadcasted-iota compare + sign multiply) and immediately contracts it on
the MXU with the (bB, bI) input tile, accumulating f32 partials in the
(bB, bJ) output tile.  The one-hot matrix never exists in HBM, so HBM
traffic is O(B*I + B*J + I) per sketch instead of O(I*J).

Grid: (J/bJ, B/bB, I/bI) — I is the innermost (reduction) axis so the
output tile revisits stay in VMEM (TPU grids iterate minor-most fastest).
Block sizes default to MXU-aligned (128, 128) multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cs_kernel(x_ref, h_ref, s_ref, o_ref, *, bJ: int):
    j0 = pl.program_id(0) * bJ

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = h_ref[...]                                   # (bI,) int32
    s = s_ref[...]                                   # (bI,) f32
    cols = j0 + jax.lax.broadcasted_iota(jnp.int32, (h.shape[0], bJ), 1)
    onehot = jnp.where(cols == h[:, None], s[:, None], 0.0)
    x = x_ref[...]                                   # (bB, bI)
    o_ref[...] += jax.lax.dot(x.astype(jnp.float32), onehot,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("J", "bB", "bI", "bJ",
                                             "interpret"))
def count_sketch(x: jax.Array, h: jax.Array, s: jax.Array, J: int,
                 bB: int = 128, bI: int = 512, bJ: int = 256,
                 interpret: bool = False) -> jax.Array:
    """x: (B, I) -> (B, J) count sketch with shared hash (h, s).

    Compiled for the TPU unless ``interpret`` asks for interpret mode
    (kernel body in Python — bit-identical block semantics).
    """
    B, I = x.shape
    bB = min(bB, B)
    bI = min(bI, I)
    bJ = min(bJ, J)
    padB, padI, padJ = (-B) % bB, (-I) % bI, (-J) % bJ
    if padB or padI:
        x = jnp.pad(x, ((0, padB), (0, padI)))
    if padI:
        h = jnp.pad(h, (0, padI), constant_values=J + padJ + 1)  # out of range
        s = jnp.pad(s, (0, padI))
    Jp = J + padJ
    grid = (Jp // bJ, x.shape[0] // bB, x.shape[1] // bI)
    out = pl.pallas_call(
        functools.partial(_cs_kernel, bJ=bJ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bI), lambda j, b, i: (b, i)),
            pl.BlockSpec((bI,), lambda j, b, i: (i,)),
            pl.BlockSpec((bI,), lambda j, b, i: (i,)),
        ],
        out_specs=pl.BlockSpec((bB, bJ), lambda j, b, i: (b, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], Jp), jnp.float32),
        interpret=interpret,
    )(x, h, s.astype(jnp.float32))
    return out[:B, :J].astype(x.dtype)
