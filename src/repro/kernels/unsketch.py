"""Pallas TPU kernel: count-sketch decompress (gather) as a blocked one-hot
MXU matmul.

out[b, i] = s(i) * y[b, h(i)] — the transpose access pattern of the apply
kernel.  Each (bI, bJ) signed one-hot tile is built in VMEM and contracted
as y_tile @ onehot_tile^T, accumulating over J blocks (each row of onehot
has its single 1 in exactly one J block, so accumulation is exact)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unsketch_kernel(y_ref, h_ref, s_ref, o_ref, *, bJ: int):
    j0 = pl.program_id(2) * bJ

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    h = h_ref[...]                                   # (bI,)
    s = s_ref[...]
    cols = j0 + jax.lax.broadcasted_iota(jnp.int32, (h.shape[0], bJ), 1)
    onehot = jnp.where(cols == h[:, None], s[:, None], 0.0)   # (bI, bJ)
    y = y_ref[...].astype(jnp.float32)               # (bB, bJ)
    o_ref[...] += jax.lax.dot_general(
        y, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bB, bI)


@functools.partial(jax.jit, static_argnames=("bB", "bI", "bJ", "interpret"))
def unsketch(y: jax.Array, h: jax.Array, s: jax.Array,
             bB: int = 128, bI: int = 512, bJ: int = 256,
             interpret: bool = False) -> jax.Array:
    """y: (B, J), hash tables over I entries -> (B, I) estimates.

    Compiled for the TPU unless ``interpret`` asks for interpret mode.
    """
    B, J = y.shape
    I = h.shape[0]
    bB = min(bB, B)
    bI = min(bI, I)
    bJ = min(bJ, J)
    padB, padI, padJ = (-B) % bB, (-I) % bI, (-J) % bJ
    if padB or padJ:
        y = jnp.pad(y, ((0, padB), (0, padJ)))
    if padI:
        h = jnp.pad(h, (0, padI), constant_values=J + padJ + 1)
        s = jnp.pad(s, (0, padI))
    grid = (y.shape[0] // bB, (I + padI) // bI, y.shape[1] // bJ)
    out = pl.pallas_call(
        functools.partial(_unsketch_kernel, bJ=bJ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bJ), lambda b, i, j: (b, j)),
            pl.BlockSpec((bI,), lambda b, i, j: (i,)),
            pl.BlockSpec((bI,), lambda b, i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((bB, bI), lambda b, i, j: (b, i)),
        out_shape=jax.ShapeDtypeStruct((y.shape[0], I + padI), jnp.float32),
        interpret=interpret,
    )(y, h, s.astype(jnp.float32))
    return out[:B, :I].astype(y.dtype)
