"""Pure-jnp oracles for the Pallas kernels."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import tile_blocks, tile_range
from repro.sketch.csvec import CSVec, accumulate, query


def count_sketch_ref(x: jax.Array, h: jax.Array, s: jax.Array,
                     J: int) -> jax.Array:
    """Batched signed bucket-accumulate.
    x: (B, I); h: (I,) int32 in [0, J); s: (I,) +-1.  -> (B, J)."""
    onehot = jax.nn.one_hot(h, J, dtype=x.dtype) * s[:, None].astype(x.dtype)
    return x @ onehot


def unsketch_ref(y: jax.Array, h: jax.Array, s: jax.Array) -> jax.Array:
    """Batched decompress: out[b, i] = s[i] * y[b, h[i]].
    y: (B, J); h: (I,); s: (I,).  -> (B, I)."""
    return y[:, h] * s[None, :].astype(y.dtype)


def kv_tail_fold_ref(rows: jax.Array, positions: jax.Array,
                     tail: jax.Array, coeffs: jax.Array) -> jax.Array:
    """Oracle for kernels/kv_sketch.py:tail_fold, delegating to the serve
    math (serve/kv_sketch.py:fold_rows) so the kernel is checked against
    exactly what the engine computes.  rows: (N, D); positions: (N,);
    tail: (Z, C, D).  -> new (Z, C, D) table."""
    from repro.serve.kv_sketch import fold_rows
    C = tail.shape[1]
    # fold_rows speaks (B, n, K, hd): view D as K with hd == 1
    r4 = rows[None, :, :, None]
    acc = fold_rows(r4, r4, positions, coeffs, C)["k"][0, :, :, :, 0]
    return tail + acc


def kv_tail_scores_ref(q: jax.Array, tail_k: jax.Array, coeffs: jax.Array,
                       T: int) -> jax.Array:
    """Oracle for kernels/kv_sketch.py:tail_scores via the serve path's
    precomputed signed position one-hot (serve/kv_sketch.py:pos_onehot —
    same in-graph hashes as the kernel's on-the-fly tiles).
    q: (N, D); tail_k: (Z, C, D).  -> (N, T) median-of-rows estimates."""
    from repro.serve.kv_sketch import pos_onehot
    C = tail_k.shape[1]
    onehot = pos_onehot(coeffs, T, C)                       # (Z, T, C)
    qa = jnp.einsum("nd,zcd->znc", q.astype(jnp.float32),
                    tail_k.astype(jnp.float32))
    est = jnp.einsum("znc,ztc->znt", qa, onehot)
    return jnp.median(est, axis=0)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        tables: jax.Array, start: jax.Array,
                        fold_base: jax.Array):
    """Oracle for kernels/paged_attention.py: the same online-softmax
    tile walk in plain jnp — a lax.scan over the slot's key tiles of
    ``tile_blocks`` table entries, fetching each tile's physical blocks
    through the table (dead entries >= NB clamp the fetch and mask their
    keys) and keeping a tile's update only where the kernel walks it (the
    tile meets the slot's ``tile_range`` and holds a real entry), with
    op-for-op the kernel's update equations and dtypes, so interpret mode
    reproduces it bitwise.  Shapes/returns match ``paged_attention``: q
    (B,Sq,K,R,hd), pools (NB,bs,K,hd), tables (B,nb) int32,
    start/fold_base (B,) -> f32 (m, l, acc): (B,K,R,Sq) x2 and
    (B,K,R,Sq,hd)."""
    B, Sq, K, R, hd = q.shape
    NB, bs = k_pool.shape[0], k_pool.shape[1]
    nb_slot = tables.shape[1]
    NQ = R * Sq
    P = tile_blocks(bs, K, hd, nb_slot, NQ, k_pool.dtype.itemsize)
    W = P * bs
    T = -(-nb_slot // P)
    scale = 1.0 / math.sqrt(hd)
    qt = q.transpose(0, 2, 3, 1, 4).reshape(B, K, NQ, hd)
    st = start.astype(jnp.int32)
    fb = fold_base.astype(jnp.int32)
    lo, hi = tile_range(st, fb, Sq, W)
    qi = jax.lax.broadcasted_iota(jnp.int32, (NQ, W), 0) % Sq

    def tile(carry, t):
        m, l, acc = carry                # (B,K,NQ) x2, (B,K,NQ,hd)
        j = t * P + jnp.arange(P, dtype=jnp.int32)
        entry = tables[:, jnp.minimum(j, nb_slot - 1)]           # (B, P)
        valid = (j < nb_slot)[None] & (entry < NB)
        walk = (t >= lo) & (t <= hi) & jnp.any(valid, axis=1)    # (B,)
        blk = jnp.minimum(entry, NB - 1)
        kj = jnp.take(k_pool, blk, axis=0).reshape(B, W, K, hd)
        vj = jnp.take(v_pool, blk, axis=0).reshape(B, W, K, hd)
        kpos = t * W + jax.lax.broadcasted_iota(jnp.int32, (NQ, W), 1)
        live = ((kpos[None] <= st[:, None, None] + qi[None])
                & (kpos[None] >= fb[:, None, None])
                & jnp.repeat(valid, bs, axis=1)[:, None])  # (B, NQ, W)
        live = live[:, None]             # (B, 1, NQ, W)
        s = jnp.einsum("bknh,bwkh->bknw", qt, kj,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, -1e30)
        m_cur = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(live, jnp.exp(s - m_cur[..., None]), 0.0)
        corr = jnp.exp(m - m_cur)
        l_cur = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bknw,bwkh->bknh", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_cur = acc * corr[..., None] + pv
        keep = walk[:, None, None]
        return (jnp.where(keep, m_cur, m), jnp.where(keep, l_cur, l),
                jnp.where(keep[..., None], acc_cur, acc)), None

    m0 = jnp.full((B, K, NQ), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, NQ), jnp.float32)
    a0 = jnp.zeros((B, K, NQ, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(tile, (m0, l0, a0),
                                  jnp.arange(T, dtype=jnp.int32))
    return (m.reshape(B, K, R, Sq), l.reshape(B, K, R, Sq),
            acc.reshape(B, K, R, Sq, hd))


def sketch_update_ref(g: jax.Array, m_table: jax.Array, v_table: jax.Array,
                      coeffs_m: jax.Array, coeffs_v: jax.Array,
                      b1: float, b2: float):
    """Fused optimizer update-retrieve on sketched (m, v) moments.

    g: (n,) f32 gradient; m_table/v_table: (R, C) count-sketch/count-min
    tables; coeffs: (R, 4) uint32 hash coefficients (sketch/hashing.py).

      new_m = b1 * m_table + (1-b1) * CS(g)        (signed)
      new_v = b2 * v_table + (1-b2) * CMS(g^2)     (unsigned)
      m_hat = median-of-rows query of new_m at all n coordinates
      v_hat = min-of-rows query of new_v

    Returns (new_m, new_v, m_hat, v_hat).  Expressed through the CSVec
    container ops so the oracle and repro.sketch share one copy of the
    accumulate/query math."""
    n = g.shape[0]
    gf = g.astype(jnp.float32)
    cs_g = accumulate(CSVec(table=jnp.zeros_like(m_table), coeffs=coeffs_m,
                            d=n, signed=True), gf)
    cs_g2 = accumulate(CSVec(table=jnp.zeros_like(v_table), coeffs=coeffs_v,
                             d=n, signed=False), gf * gf)
    new_m = b1 * m_table + (1.0 - b1) * cs_g.table
    new_v = b2 * v_table + (1.0 - b2) * cs_g2.table
    idx = jnp.arange(n, dtype=jnp.int32)
    m_hat = query(CSVec(table=new_m, coeffs=coeffs_m, d=n, signed=True),
                  idx)
    v_hat = query(CSVec(table=new_v, coeffs=coeffs_v, d=n, signed=False),
                  idx)
    return new_m, new_v, m_hat, v_hat
