"""Sketched long-context KV: per-slot, per-layer FCS tail tables.

The paged pool (serve/scheduler.py) bounds a slot's KV by its RESERVED
blocks — fine for mixed-length streams, but a long document still needs
ceil(context / block_size) live blocks.  This module decouples context
length from pool blocks the same way sketch/optimizer.py decouples
optimizer state from parameter count: when a block ages past the recent
window (``cfg.serve.kv_sketch_window``), its key and value rows are
count-sketched ALONG THE SEQUENCE AXIS into a per-slot, per-layer
(rows, cols, K, hd) tail table and the block returns to the free list.
Sketches are linear, so folding is a batched signed bucket-accumulate
(the CS half of the paper's FCS, hashes from sketch/hashing.py evaluated
on the fly), and it rides inside the compiled decode chunk — the
scheduler's one-compilation contract is untouched.

Decode attention becomes TWO-SPAN:

  exact span   — paged attention over [fold_base, pos], bit-identical
                 ops to the pre-sketch path (the regression anchor: when
                 nothing has folded the engine selects this output
                 verbatim, so window >= context runs are bitwise equal
                 to a sketch-free engine's);
  sketch tail  — scores against folded positions j < fold_base are
                 estimated per hash row as q . tail_k[r, h_r(j)] * s_r(j)
                 (one MXU contraction against a precomputed signed
                 position-one-hot), median-combined over rows; the
                 softmax weight vector w over the tail is then itself
                 count-sketched per row (CS is linear: sum_j w_j v_j =
                 <CS_r(w), tail_v[r]> exactly, up to collisions) and the
                 weighted value sum is median-combined the same way.

The two spans merge with online-softmax (m, l, acc) statistics, exactly
like kernels-level flash attention — an empty tail contributes weight
zero, so the merge is total.

Everything here is dependency-light (configs + sketch.hashing only) so
models/layers.py can import it; kernels/kv_sketch.py carries the Pallas
fold+query kernels with kernels/ref.py oracles delegating to this math.

Fold points are PUMP-STEP BOUNDARIES: the scheduler plans each chunk's
fold lengths from its host position mirrors (``_plan_folds``, at
dispatch), the chunk folds at its head, and the freed blocks leave the
slot's table at dispatch time too (``_finish_folds`` — the sentinel
writes enqueue after the chunk in device-stream order).  Only COMMITTED
rows ever fold, so the async pump (serve/frontend.py) can cancel or
preempt a sketched slot at any boundary: the tail tables are per-slot
state, zeroed lazily at the next admission, and the fold frontier
resets with the slot.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ServeConfig
from repro.sketch.hashing import cached_coeffs, row_buckets_signs

# tail-table hash seeds derive from the serve seed but never collide with
# the prefix cache's count-min seed (same hashing family)
_SEED_SALT = 0x4B56AD  # "KV"-flavoured salt


def tail_seed(sv: ServeConfig) -> int:
    return (int(sv.seed) ^ _SEED_SALT) & 0x7FFFFFFF


def tail_coeffs(sv: ServeConfig) -> jax.Array:
    """(rows, 4) uint32 hash coefficients for the tail tables."""
    return cached_coeffs(tail_seed(sv), sv.kv_sketch_rows)


def tail_cols(max_seq: int, ratio: int) -> int:
    """Tail-table columns: ~max_seq / ratio, rounded UP to a multiple of
    16 (lane alignment + 16-way model-axis shardability), at least 16."""
    c = -(-max_seq // max(1, ratio))
    return max(16, -(-c // 16) * 16)


def pos_domain(max_seq: int, block_size: int) -> int:
    """Hashed position domain T: every foldable absolute position lives
    in [0, T) — whole blocks only, so round max_seq up to blocks."""
    return -(-max_seq // block_size) * block_size


def pos_onehot(coeffs: jax.Array, T: int, cols: int) -> jax.Array:
    """(rows, T, cols) signed position one-hot: onehot[z, j, c] =
    s_z(j) * [h_z(j) == c].  Shared by fold (accumulate = x @ onehot) and
    query (estimate gather = table-products @ onehot^T); both sides use
    the same in-graph hashes, so fold and query can never disagree."""
    idx = jnp.arange(T, dtype=jnp.int32)
    bk, sg = row_buckets_signs(coeffs, idx, cols, signed=True)   # (Z, T)
    cols_iota = jnp.arange(cols, dtype=jnp.int32)
    return jnp.where(cols_iota[None, None, :] == bk[:, :, None],
                     sg[:, :, None], 0.0).astype(jnp.float32)


def init_tail(cfg: ModelConfig, batch: int, rows: int, cols: int
              ) -> Dict[str, jax.Array]:
    """Per-slot, per-layer tail tables: {"k","v"} of
    (L, B, rows, cols, K, hd) f32 zeros.  f32 because folds accumulate
    hundreds of signed bf16 rows per bucket."""
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, rows, cols, cfg.num_kv_heads, hd)
    # two distinct buffers — donation of a state pytree holding the SAME
    # zeros array twice is an XLA error ("donate the same buffer twice")
    return {"k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32) + 0.0}


def tail_state_bytes(tail: Any) -> int:
    return sum(int(a.size) * int(a.dtype.itemsize)
               for a in jax.tree.leaves(tail))


# ---------------------------------------------------------------------------
# Fold: pool blocks -> tail tables (linear accumulate, in-graph)
# ---------------------------------------------------------------------------


@jax.named_scope("fcs_fold")
def fold_pool(pool: Dict[str, jax.Array], tail: Dict[str, jax.Array],
              tables: jax.Array, fold_from: jax.Array, fold_len: jax.Array,
              coeffs: jax.Array, fold_cap: int) -> Dict[str, jax.Array]:
    """Fold each slot's next ``fold_len[b]`` aged KV rows into its tail.

    pool: {"k","v"} (L, NB, bs, K, hd) — the paged block pool; the rows
    being folded are still table-mapped (the host frees their blocks only
    after this runs).  tail: {"k","v"} (L, B, Z, C, K, hd).  tables:
    (B, blocks_per_slot) int32.  fold_from: (B,) first absolute position
    to fold (the slot's current fold_base; block-aligned).  fold_len:
    (B,) rows to fold, a multiple of the block size, <= ``fold_cap``
    (static).  All arrays traced — one compilation covers every fold.
    """
    NB, bs = pool["k"].shape[1], pool["k"].shape[2]
    B = tables.shape[0]
    F = int(fold_cap)
    if F == 0:
        return tail
    p = fold_from[:, None] + jnp.arange(F, dtype=jnp.int32)[None, :]  # (B,F)
    valid = (jnp.arange(F, dtype=jnp.int32)[None, :]
             < fold_len[:, None]).astype(jnp.float32)                 # (B,F)
    blk = jnp.clip(p // bs, 0, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, blk, axis=1)                   # (B,F)
    phys = jnp.clip(phys, 0, NB - 1)      # invalid rows are masked by valid
    off = p % bs
    Z = tail["k"].shape[2]
    C = tail["k"].shape[3]
    bk, sg = row_buckets_signs(coeffs, p.reshape(-1), C, signed=True)
    bk = bk.reshape(Z, B, F)
    sg = sg.reshape(Z, B, F) * valid[None, :, :]
    cols_iota = jnp.arange(C, dtype=jnp.int32)
    onehot = jnp.where(cols_iota[None, None, None, :] == bk[..., None],
                       sg[..., None], 0.0)                       # (Z,B,F,C)

    def one(pool_a, tail_a):
        rows = pool_a[:, phys, off].astype(jnp.float32)          # (L,B,F,K,hd)
        return tail_a + jnp.einsum("zbfc,lbfkh->lbzckh", onehot, rows)

    return {"k": one(pool["k"], tail["k"]),
            "v": one(pool["v"], tail["v"])}


# ---------------------------------------------------------------------------
# Query: online-softmax statistics of the sketched tail span
# ---------------------------------------------------------------------------


@jax.named_scope("fcs_tail")
def tail_attend(q: jax.Array, tail_k: jax.Array, tail_v: jax.Array,
                onehot: jax.Array, fold_base: jax.Array, scale: float
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Approximate attention statistics over the folded span [0, fold_base).

    q: (B, Sq, K, R, hd) f32 queries; tail_k/tail_v: (B, Z, C, K, hd) f32
    (one layer's tables); onehot: (Z, T, C) from ``pos_onehot``;
    fold_base: (B,) int32.  Every query position is >= fold_base (folded
    rows are strictly older than the exact window), so the whole tail is
    causally visible — no per-query mask, only the live-span mask.

    Returns flash-style (m, l, acc): (B, K, R, Sq), same, and
    (B, K, R, Sq, hd) — merge-ready against the exact span's statistics.
    An empty tail (fold_base == 0) yields m = -1e30, l = 0, acc = 0, so
    the merge degenerates to the exact span exactly.
    """
    T = onehot.shape[1]
    qf = q.astype(jnp.float32)
    tk = tail_k.astype(jnp.float32)
    # per-row bucket products, then gather each position's bucket estimate
    qa = jnp.einsum("bqkrh,bzckh->bzkrqc", qf, tk)
    est = jnp.einsum("bzkrqc,ztc->bzkrqt", qa, onehot)     # (B,Z,K,R,Sq,T)
    s = jnp.median(est, axis=1) * scale                    # (B,K,R,Sq,T)
    live = (jnp.arange(T, dtype=jnp.int32)[None, :]
            < fold_base[:, None])                          # (B,T)
    lm = live[:, None, None, None, :]
    s = jnp.where(lm, s, -1e30)
    m = jnp.max(s, axis=-1)                                # (B,K,R,Sq)
    w = jnp.exp(s - m[..., None])
    # exp(-1e30 - (-1e30)) == 1 when the span is empty: kill dead weights
    w = jnp.where(lm, w, 0.0)
    l = jnp.sum(w, axis=-1)
    # CS is linear: sum_j w_j * v_j  ~=  < CS_z(w), tail_v[z] > per row
    cw = jnp.einsum("bkrqt,ztc->bzkrqc", w, onehot)
    acc = jnp.median(jnp.einsum("bzkrqc,bzckh->bzkrqh", cw,
                                tail_v.astype(jnp.float32)), axis=1)
    return m, l, acc


def merge_spans(m_e: jax.Array, l_e: jax.Array, acc_e: jax.Array,
                m_t: jax.Array, l_t: jax.Array, acc_t: jax.Array
                ) -> jax.Array:
    """Online-softmax merge of exact-window and sketch-tail statistics.
    All f32; shapes (B,K,R,Sq) / (B,K,R,Sq,hd).  Returns (B,K,R,Sq,hd).
    The exact span is never empty (a live query always sees its own
    position), so the denominator is positive."""
    m = jnp.maximum(m_e, m_t)
    a_e = jnp.exp(m_e - m)
    a_t = jnp.exp(m_t - m)
    num = acc_e * a_e[..., None] + acc_t * a_t[..., None]
    den = l_e * a_e + l_t * a_t
    return num / jnp.maximum(den, 1e-30)[..., None]


def exact_span_stats(s: jax.Array, v: jax.Array, live: jax.Array
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """f32 online-softmax statistics of an exact masked score tensor.
    s: (B, K, R, Sq, Sk) with dead positions already at -1e30; ``live``
    is the bool mask that produced them (exp(-1e30 - (-1e30)) == 1, so
    dead weights must be re-zeroed after the exp); v: (B, Sk, K, hd).
    Returns (m, l, acc) matching tail_attend."""
    m = jnp.max(s, axis=-1)
    p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkrqs,bskh->bkrqh", p, v.astype(jnp.float32))
    return m, l, acc


# ---------------------------------------------------------------------------
# Dense oracle (tests / benchmarks)
# ---------------------------------------------------------------------------


def dense_tail_stats(q: jax.Array, k: jax.Array, v: jax.Array,
                     fold_base: jax.Array, scale: float):
    """Exact (m, l, acc) over the folded span — what tail_attend
    approximates.  k/v: (B, T, K, hd) the TRUE rows at absolute
    positions [0, T)."""
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bqkrh,bskh->bkrqs", qf,
                   k.astype(jnp.float32)) * scale
    T = k.shape[1]
    live = (jnp.arange(T)[None, :] < fold_base[:, None]
            )[:, None, None, None, :]
    s = jnp.where(live, s, -1e30)
    m = jnp.max(s, axis=-1)
    w = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(w, axis=-1)
    acc = jnp.einsum("bkrqs,bskh->bkrqh", w, v.astype(jnp.float32))
    return m, l, acc


# ---------------------------------------------------------------------------
# Fidelity probe (observability): per-row estimate spread
# ---------------------------------------------------------------------------


def tail_row_spread(tail: Dict[str, jax.Array]) -> jax.Array:
    """Per-slot relative spread of the Z independent hash-row tail
    estimates — the live collision-variance proxy behind the ROADMAP's
    error-adaptive folding ("monitor the tail's median-estimate
    spread").

    Each hash row z holds an independent count-sketch of the SAME
    folded rows, so its total energy e_z = sum over (L, C, K, hd) of
    tail_k^2 + tail_v^2 equals sum_j ||k_j||^2 + ||v_j||^2 exactly when
    no two folded positions collide in row z, and picks up
    2 * s_i s_j <x_i, x_j> cross terms when they do.  Rows that agree
    mean the median-of-rows estimates the engine decodes with are
    trustworthy; rows that diverge mean collisions are corrupting the
    tail and the slot is a candidate for a wider exact window or a
    re-fold.

    tail: {"k","v"} of (L, B, Z, C, K, hd).  Returns (B,) f32:
    (max_z e - min_z e) / median_z e, 0 for an empty (all-zero) tail.

    Observability contract: this is HOST-OPT-IN telemetry — the
    scheduler calls it (jitted) only at its configured probe cadence
    and only at the ``collect()`` boundary where the round's sync
    already happened; it is never traced into the compiled decode
    chunk.
    """
    e = (jnp.sum(jnp.square(tail["k"]), axis=(0, 3, 4, 5)) +
         jnp.sum(jnp.square(tail["v"]), axis=(0, 3, 4, 5)))   # (B, Z)
    med = jnp.median(e, axis=1)
    spread = jnp.max(e, axis=1) - jnp.min(e, axis=1)
    return jnp.where(med > 0.0, spread / jnp.maximum(med, 1e-30), 0.0)


def fold_rows(k: jax.Array, v: jax.Array, positions: jax.Array,
              coeffs: jax.Array, cols: int):
    """Reference fold of explicit rows (no pool/tables): k/v
    (B, n, K, hd) at absolute ``positions`` (n,) -> tail {"k","v"}
    (B, Z, cols, K, hd).  Shares row_buckets_signs with fold_pool, so the
    two folds agree bitwise for the same rows."""
    bk, sg = row_buckets_signs(coeffs, positions.astype(jnp.int32), cols,
                               signed=True)                       # (Z, n)
    cols_iota = jnp.arange(cols, dtype=jnp.int32)
    onehot = jnp.where(cols_iota[None, None, :] == bk[:, :, None],
                       sg[:, :, None], 0.0)                       # (Z,n,C)
    fold = lambda x: jnp.einsum("znc,bnkh->bzckh", onehot,
                                x.astype(jnp.float32))
    return {"k": fold(k), "v": fold(v)}
