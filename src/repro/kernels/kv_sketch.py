"""Pallas TPU kernels: KV tail-table fold and query score estimation.

serve/kv_sketch.py expresses the long-context tail math as jnp einsums
against a precomputed (rows, T, cols) signed position one-hot — fine at
serve geometry, but the one-hot is T*cols floats per hash row.  These
kernels are the bandwidth-honest formulation, following
kernels/sketch_update.py: hashes are evaluated ON THE FLY per tile
(uint32 multiply-add + murmur finalize from sketch/hashing.py) and the
signed one-hot only ever exists as a (block, block) VMEM tile feeding an
MXU contraction.

  tail_fold   : rows (N, D) at absolute positions (N,) accumulate into a
                (Z, C, D) tail table — grid (C/bC, N/bN), reduction axis
                innermost so each table tile is revisited consecutively.
  tail_scores : per-query bucket products q @ tail_k[z]^T gathered back
                to per-position estimates, median-combined over hash rows
                in-kernel — grid (N/bN, T/bT), bucket products computed
                once per query block and parked in VMEM scratch.

Both compile for the TPU unless ``interpret=True`` asks for interpret
mode, and are validated against kernels/ref.py oracles that
delegate to serve/kv_sketch.py — kernel and serve path share
sketch/hashing.py, so the hash arithmetic matches bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sketch_update import _median_rows
from repro.sketch.hashing import bucket_hash, sign_hash


def _fold_kernel(p_ref, x_ref, t_ref, c_ref, o_ref, *,
                 bN: int, bC: int, C: int, Z: int):
    n_blk = pl.program_id(1)

    @pl.when(n_blk == 0)
    def _init():
        o_ref[...] = t_ref[...]

    idx = p_ref[...].astype(jnp.uint32)                       # (bN,)
    x = x_ref[...].astype(jnp.float32)                        # (bN, D)
    c0 = pl.program_id(0) * bC
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, (bN, bC), 1)
    for z in range(Z):
        bk = bucket_hash(idx, c_ref[z, 0], c_ref[z, 1], C)
        sg = sign_hash(idx, c_ref[z, 2], c_ref[z, 3])
        onehot = jnp.where(cols == bk[:, None], sg[:, None], 0.0)
        # (bC, bN) @ (bN, D): each bucket column sums its rows' signed hits
        o_ref[z, :, :] += jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _scores_kernel(q_ref, t_ref, c_ref, o_ref, qa_ref, *,
                   bN: int, bT: int, C: int, Z: int):
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _products():
        q = q_ref[...].astype(jnp.float32)                    # (bN, D)
        for z in range(Z):
            # bucket products: one (bN, C) row of q . tail_k[z, c] per z
            qa_ref[z, :, :] = jax.lax.dot_general(
                q, t_ref[z, :, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    idx = (t_blk * bT
           + jax.lax.broadcasted_iota(jnp.int32, (bT,), 0)).astype(
               jnp.uint32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bT, C), 1)
    est = []
    for z in range(Z):
        bk = bucket_hash(idx, c_ref[z, 0], c_ref[z, 1], C)
        sg = sign_hash(idx, c_ref[z, 2], c_ref[z, 3])
        onehot = jnp.where(cols == bk[:, None], sg[:, None], 0.0)
        # gather each position's bucket estimate: (bN, C) @ (bT, C)^T
        est.append(jax.lax.dot_general(
            qa_ref[z, :, :], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))
    o_ref[...] = _median_rows(est)


@functools.partial(jax.jit, static_argnames=("bN", "bC", "interpret"))
def tail_fold(rows: jax.Array, positions: jax.Array, tail: jax.Array,
              coeffs: jax.Array, *, bN: int = 256, bC: int = 256,
              interpret: bool = False) -> jax.Array:
    """Accumulate ``rows`` (N, D) at absolute ``positions`` (N,) int32
    into ``tail`` (Z, C, D) f32.  Returns the new (Z, C, D) table.
    D is the flattened feature axis (K * head_dim for KV rows)."""
    N, D = rows.shape
    Z, C, _ = tail.shape
    bN = min(bN, N)
    bC = min(bC, C)
    padN, padC = (-N) % bN, (-C) % bC
    if padN:
        # zero rows contribute nothing, whatever their padded position
        rows = jnp.pad(rows, ((0, padN), (0, 0)))
        positions = jnp.pad(positions, (0, padN))
    if padC:
        # hashes land in [0, C): padded columns are never hit
        tail = jnp.pad(tail, ((0, 0), (0, padC), (0, 0)))
    Cp = C + padC
    nN, nC = rows.shape[0] // bN, Cp // bC
    out = pl.pallas_call(
        functools.partial(_fold_kernel, bN=bN, bC=bC, C=C, Z=Z),
        grid=(nC, nN),
        in_specs=[
            pl.BlockSpec((bN,), lambda c, n: (n,)),
            pl.BlockSpec((bN, D), lambda c, n: (n, 0)),
            pl.BlockSpec((Z, bC, D), lambda c, n: (0, c, 0)),
            pl.BlockSpec((Z, 4), lambda *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Z, bC, D), lambda c, n: (0, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Z, Cp, D), jnp.float32),
        interpret=interpret,
    )(positions, rows, tail, coeffs)
    return out[:, :C, :]


@functools.partial(jax.jit, static_argnames=("T", "bN", "bT", "interpret"))
def tail_scores(q: jax.Array, tail_k: jax.Array, coeffs: jax.Array, *,
                T: int, bN: int = 128, bT: int = 256,
                interpret: bool = False) -> jax.Array:
    """Median-of-rows tail score estimates: (N, T) where
    out[n, t] ~= q[n] . key_row(t) for folded positions t.  q: (N, D);
    tail_k: (Z, C, D); unscaled and unmasked — the caller applies the
    softmax scale and the fold_base live mask."""
    N, D = q.shape
    Z, C, _ = tail_k.shape
    bN = min(bN, N)
    bT = min(bT, T)
    padN, padT = (-N) % bN, (-T) % bT
    if padN:
        q = jnp.pad(q, ((0, padN), (0, 0)))
    Tp = T + padT
    nN, nT = q.shape[0] // bN, Tp // bT
    out = pl.pallas_call(
        functools.partial(_scores_kernel, bN=bN, bT=bT, C=C, Z=Z),
        grid=(nN, nT),
        in_specs=[
            pl.BlockSpec((bN, D), lambda n, t: (n, 0)),
            pl.BlockSpec((Z, C, D), lambda *_: (0, 0, 0)),
            pl.BlockSpec((Z, 4), lambda *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bN, bT), lambda n, t: (n, t)),
        out_shape=jax.ShapeDtypeStruct((q.shape[0], Tp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Z, bN, C), jnp.float32)],
        interpret=interpret,
    )(q, tail_k, coeffs)
    return out[:N, :T]
