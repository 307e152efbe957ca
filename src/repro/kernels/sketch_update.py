"""Pallas TPU kernel: fused sketched-moment update-retrieve.

The sketched optimizer (repro/sketch/optimizer.py) keeps AdamW's (m, v) in
count-sketch / count-min tables.  Per leaf and step it needs

  new_m = b1 * m_table + (1-b1) * CS(g)          scatter-accumulate
  new_v = b2 * v_table + (1-b2) * CMS(g^2)
  m_hat[i] = median_r  s_r(i) * new_m[r, h_r(i)]  gather-estimate
  v_hat[i] = min_r     new_v[r, h2_r(i)]

TPUs have no efficient random scatter/gather, so both halves reuse the
signed-one-hot MXU formulation of kernels/count_sketch.py: each (bI, bC)
one-hot tile is built in VMEM from hashes evaluated ON THE FLY (uint32
multiply-add + murmur finalize from sketch/hashing.py — tabulated hashes
would cost 8 bytes/element/row and erase the memory win) and contracted on
the MXU.  Dense (m, v) never exist; HBM traffic per step is
O(n + rows*cols), tables touched once per pass.

The op is one fused update-retrieve: a scatter-accumulate pass over grid
(C/bC, I/bI) (reduction axis innermost so table tiles stay resident in
VMEM), then a gather-estimate pass over grid (I/bI, C/bC) (each row's
single hit lands in exactly one C block, so accumulation over C blocks is
exact; the median/min combine runs in-kernel at the last C block via a
static odd-even sorting network over the rows).  The retrieve reads the
freshly written tables — the strict accumulate->query dependency makes a
single-grid formulation impossible without violating Pallas's
consecutive-output-revisit rule.

Compiled for the TPU unless ``interpret=True`` asks for interpret mode.
``kernels/ref.py:sketch_update_ref`` is the pure-jnp oracle (bit-matching
hash arithmetic — both paths share sketch/hashing.py).
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.sketch.hashing import bucket_hash, sign_hash


def _median_rows(rows: List[jax.Array]) -> jax.Array:
    """Median across a static list of equal-shape vectors via an odd-even
    transposition network (TPU-safe: only elementwise min/max)."""
    rows = list(rows)
    R = len(rows)
    for p in range(R):
        for j in range(p % 2, R - 1, 2):
            lo = jnp.minimum(rows[j], rows[j + 1])
            hi = jnp.maximum(rows[j], rows[j + 1])
            rows[j], rows[j + 1] = lo, hi
    if R % 2:
        return rows[R // 2]
    return 0.5 * (rows[R // 2 - 1] + rows[R // 2])


def _acc_kernel(g_ref, m_ref, v_ref, cm_ref, cv_ref, om_ref, ov_ref, *,
                bI: int, bC: int, C: int, R: int, b1: float, b2: float):
    i_blk = pl.program_id(1)

    @pl.when(i_blk == 0)
    def _init():
        om_ref[...] = b1 * m_ref[...]
        ov_ref[...] = b2 * v_ref[...]

    idx = (i_blk * bI
           + jax.lax.broadcasted_iota(jnp.int32, (bI,), 0)).astype(jnp.uint32)
    g = g_ref[...].astype(jnp.float32)
    c0 = pl.program_id(0) * bC
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, (bI, bC), 1)
    for r in range(R):
        bk = bucket_hash(idx, cm_ref[r, 0], cm_ref[r, 1], C)
        sg = sign_hash(idx, cm_ref[r, 2], cm_ref[r, 3])
        onehot = jnp.where(cols == bk[:, None], sg[:, None], 0.0)
        om_ref[r:r + 1, :] += (1.0 - b1) * jax.lax.dot(
            g[None, :], onehot, preferred_element_type=jnp.float32)
        bkv = bucket_hash(idx, cv_ref[r, 0], cv_ref[r, 1], C)
        onehot_v = jnp.where(cols == bkv[:, None], 1.0, 0.0)
        ov_ref[r:r + 1, :] += (1.0 - b2) * jax.lax.dot(
            (g * g)[None, :], onehot_v, preferred_element_type=jnp.float32)


def _ret_kernel(m_ref, v_ref, cm_ref, cv_ref, mh_ref, vh_ref, em_ref, ev_ref,
                *, bI: int, bC: int, C: int, R: int, nC: int):
    i_blk = pl.program_id(0)
    c_blk = pl.program_id(1)

    @pl.when(c_blk == 0)
    def _init():
        em_ref[...] = jnp.zeros_like(em_ref)
        ev_ref[...] = jnp.zeros_like(ev_ref)

    idx = (i_blk * bI
           + jax.lax.broadcasted_iota(jnp.int32, (bI,), 0)).astype(jnp.uint32)
    c0 = c_blk * bC
    cols = c0 + jax.lax.broadcasted_iota(jnp.int32, (bI, bC), 1)
    for r in range(R):
        bk = bucket_hash(idx, cm_ref[r, 0], cm_ref[r, 1], C)
        sg = sign_hash(idx, cm_ref[r, 2], cm_ref[r, 3])
        onehot = jnp.where(cols == bk[:, None], sg[:, None], 0.0)
        em_ref[r:r + 1, :] += jax.lax.dot_general(
            m_ref[r:r + 1, :], onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        bkv = bucket_hash(idx, cv_ref[r, 0], cv_ref[r, 1], C)
        onehot_v = jnp.where(cols == bkv[:, None], 1.0, 0.0)
        ev_ref[r:r + 1, :] += jax.lax.dot_general(
            v_ref[r:r + 1, :], onehot_v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(c_blk == nC - 1)
    def _emit():
        em = em_ref[...]
        ev = ev_ref[...]
        mh_ref[...] = _median_rows([em[r] for r in range(R)])
        vh_ref[...] = functools.reduce(jnp.minimum,
                                       [ev[r] for r in range(R)])


@functools.partial(jax.jit, static_argnames=("b1", "b2", "bI", "bC",
                                             "interpret"))
def sketch_update(g: jax.Array, m_table: jax.Array, v_table: jax.Array,
                  coeffs_m: jax.Array, coeffs_v: jax.Array, *,
                  b1: float = 0.9, b2: float = 0.95,
                  bI: int = 512, bC: int = 256,
                  interpret: bool = False):
    """Fused moment update + estimate for one flat gradient leaf.

    g: (n,) — any float dtype, accumulated in f32.
    m_table / v_table: (R, C) f32; coeffs_*: (R, 4) uint32.
    Returns (new_m (R, C), new_v (R, C), m_hat (n,), v_hat (n,)).
    """
    n = g.shape[0]
    R, C = m_table.shape
    bI = min(bI, n)
    bC = min(bC, C)
    padI, padC = (-n) % bI, (-C) % bC
    if padI:
        g = jnp.pad(g, (0, padI))        # zero grads: no-op contributions
    if padC:
        m_table = jnp.pad(m_table, ((0, 0), (0, padC)))
        v_table = jnp.pad(v_table, ((0, 0), (0, padC)))
    Cp = C + padC
    nI, nC = g.shape[0] // bI, Cp // bC

    coeff_spec = pl.BlockSpec((R, 4), lambda *_: (0, 0))
    new_m, new_v = pl.pallas_call(
        functools.partial(_acc_kernel, bI=bI, bC=bC, C=C, R=R, b1=b1, b2=b2),
        grid=(nC, nI),
        in_specs=[
            pl.BlockSpec((bI,), lambda c, i: (i,)),
            pl.BlockSpec((R, bC), lambda c, i: (0, c)),
            pl.BlockSpec((R, bC), lambda c, i: (0, c)),
            coeff_spec, coeff_spec,
        ],
        out_specs=[pl.BlockSpec((R, bC), lambda c, i: (0, c)),
                   pl.BlockSpec((R, bC), lambda c, i: (0, c))],
        out_shape=[jax.ShapeDtypeStruct((R, Cp), jnp.float32),
                   jax.ShapeDtypeStruct((R, Cp), jnp.float32)],
        interpret=interpret,
    )(g, m_table, v_table, coeffs_m, coeffs_v)

    m_hat, v_hat = pl.pallas_call(
        functools.partial(_ret_kernel, bI=bI, bC=bC, C=C, R=R, nC=nC),
        grid=(nI, nC),
        in_specs=[
            pl.BlockSpec((R, bC), lambda i, c: (0, c)),
            pl.BlockSpec((R, bC), lambda i, c: (0, c)),
            coeff_spec, coeff_spec,
        ],
        out_specs=[pl.BlockSpec((bI,), lambda i, c: (i,)),
                   pl.BlockSpec((bI,), lambda i, c: (i,))],
        out_shape=[jax.ShapeDtypeStruct((g.shape[0],), jnp.float32),
                   jax.ShapeDtypeStruct((g.shape[0],), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R, bI), jnp.float32),
                        pltpu.VMEM((R, bI), jnp.float32)],
        interpret=interpret,
    )(new_m, new_v, coeffs_m, coeffs_v)

    return new_m[:, :C], new_v[:, :C], m_hat[:n], v_hat[:n]
