"""Flash-decode paged attention: one Pallas pass over a slot's block table.

The serve path (models/layers.py paged modes) currently gathers a slot's
pool blocks into a dense (B, S, K, hd) buffer with ``jnp.take`` and then
runs a full masked softmax.  This kernel attends IN ONE PASS instead: the
grid walks (slot, logical block), the block table rides as a
scalar-prefetch operand so each step's BlockSpec index map fetches the
slot's next physical KV block directly from the pool, and f32 online-
softmax statistics (running max / weight sum / weighted value
accumulator) merge the blocks — the dense gathered copy never exists.

One kernel covers the three serve shapes (they differ only in the query
geometry):

  decode   q: (B, 1, K, R, hd),  start = per-slot position (B,)
  verify   q: (B, C, K, R, hd),  start = per-slot first position (B,)
  chunk    q: (1, C, K, R, hd),  start = traced chunk offset (1,)

Masking matches the jnp paths row for row: query i of slot b sees key
position p iff ``fold_base[b] <= p <= start[b] + i`` and p lies in a
block whose table entry is a real pool id (entries >= num_blocks mark
unallocated / invalidated rows — the whole block is masked dead and the
index map clamps the fetch, so retired slots read nothing).  With
``fold_base == 0`` the lower bound is vacuous and the statistics cover
the full causal span; with ``fold_base > 0`` they cover exactly the
two-span exact window, merge-ready against ``serve/kv_sketch.py``'s
``tail_attend`` output via ``merge_spans``.

Precision follows the repo's flash idiom (layers._flash_attention):
scores and running statistics are f32 (``preferred_element_type``); the
per-block weight tile is cast back to the value dtype for the weighted-
value MXU pass.  ``kernels/ref.py:paged_attention_ref`` mirrors the
block loop op for op, so interpret mode reproduces it bitwise.

Returns raw statistics, not normalized output: (m, l, acc) shaped
(B, K, R, Sq) / (B, K, R, Sq) / (B, K, R, Sq, hd), all f32.  Callers
normalize ``acc / max(l, eps)`` or merge with a sketched tail first.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _attend_block(tbl_ref, st_ref, fb_ref, q_ref, k_ref, v_ref,
                  m_ref, l_ref, acc_ref, *, bs, Sq, K, NQ, NB, scale):
    """Grid (B, nb_slot): fold pool block ``tbl[b, j]`` into slot b's
    running statistics.  NQ = R * Sq query rows per kv head; row r*Sq+i
    is query position i of q-head replica r."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = tbl_ref[b, j] < NB
    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (NQ, bs), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (NQ, bs), 0) % Sq
    live = ((kpos <= st_ref[b] + qi) & (kpos >= fb_ref[b])) & valid
    for z in range(K):
        qz = q_ref[0, z]                              # (NQ, hd)
        kz = k_ref[0, :, z, :]                        # (bs, hd)
        vz = v_ref[0, :, z, :]
        s = jax.lax.dot_general(qz, kz, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, -1e30)
        m_prev = m_ref[0, z]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # exp(-1e30 - (-1e30)) == 1 on fully-dead rows: re-zero after exp
        p = jnp.where(live, jnp.exp(s - m_cur[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_cur)
        m_ref[0, z] = m_cur
        l_ref[0, z] = l_ref[0, z] * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.astype(vz.dtype), vz,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[0, z] = acc_ref[0, z] * corr[:, None] + pv


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    tables: jax.Array, start: jax.Array,
                    fold_base: jax.Array, *,
                    interpret: bool = False):
    """Flash-decode attention through per-slot block tables.

    q: (B, Sq, K, R, hd); k_pool/v_pool: (NB, bs, K, hd) shared pool;
    tables: (B, nb_slot) int32 physical block ids (>= NB = dead row);
    start: (B,) int32 per-slot position of query row 0; fold_base: (B,)
    int32 lower visibility bound (zeros when no span is folded).

    Compiled for the TPU unless ``interpret`` asks for interpret mode.
    Returns f32 (m, l, acc): (B, K, R, Sq) x2 and (B, K, R, Sq, hd).
    """
    B, Sq, K, R, hd = q.shape
    NB, bs = k_pool.shape[0], k_pool.shape[1]
    nb_slot = tables.shape[1]
    NQ = R * Sq
    scale = 1.0 / math.sqrt(hd)
    # (B, K, R*Sq, hd): kv-head-major rows so each head's queries are one
    # contiguous MXU tile inside the kernel
    qt = q.transpose(0, 2, 3, 1, 4).reshape(B, K, NQ, hd)

    def _kv_map(b, j, tbl, st, fb):
        # dead entries (>= NB) still need an in-range fetch; the kernel
        # masks the whole block so the clamped read is never used
        return (jnp.minimum(tbl[b, j], NB - 1), 0, 0, 0)

    kv_spec = pl.BlockSpec((1, bs, K, hd), _kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb_slot),
        in_specs=[
            pl.BlockSpec((1, K, NQ, hd), lambda b, j, *_: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, K, NQ), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, K, NQ), lambda b, j, *_: (b, 0, 0)),
            pl.BlockSpec((1, K, NQ, hd), lambda b, j, *_: (b, 0, 0, 0)),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_attend_block, bs=bs, Sq=Sq, K=K, NQ=NQ,
                          NB=NB, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, K, NQ), jnp.float32),
            jax.ShapeDtypeStruct((B, K, NQ), jnp.float32),
            jax.ShapeDtypeStruct((B, K, NQ, hd), jnp.float32),
        ],
        interpret=interpret,
    )(tables.astype(jnp.int32), start.astype(jnp.int32),
      fold_base.astype(jnp.int32), qt, k_pool, v_pool)
    return (m.reshape(B, K, R, Sq), l.reshape(B, K, R, Sq),
            acc.reshape(B, K, R, Sq, hd))
