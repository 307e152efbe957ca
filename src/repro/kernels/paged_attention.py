"""Flash-decode paged attention: one Pallas pass over each slot's live keys.

The serve path (models/layers.py paged modes) attends straight through
the block tables instead of gathering a slot's pool blocks into a dense
(B, S, K, hd) buffer.  The grid walks (slot, key tile): a key tile is
``P`` consecutive table entries (``P * bs`` keys), and f32 online-softmax
statistics (running max / weight sum / weighted value accumulator) merge
the tiles — the dense gathered copy never exists.

The walk visits only LIVE tiles: those that hold a key of the slot's
visible range ``[fold_base[b], start[b] + Sq - 1]`` and at least one real
pool block.  The pools stay in HBM; each live tile's ``P`` blocks are
gathered through the table by manual async copies into a VMEM tile, and
the copies of the next live tile (the next slot's first one included)
start before the current tile is computed, so the fetch of one tile
overlaps the arithmetic of the other.  A tile off the range runs no copy
and no arithmetic, which is bitwise neutral: its statistics update would
be ``corr = 1``, ``p = 0``.  The bound is computed in the kernel from the
scalar-prefetched ``start`` / ``fold_base``, so it follows a traced
decode position inside a scanned chunk.  ``tile_blocks`` (the tile
width) and ``tile_range`` (the bound) are shared with the scheduler's
``attn_tiles_*`` counters, so the two cannot disagree.

One kernel covers the three serve shapes (they differ only in the query
geometry):

  decode   q: (B, 1, K, R, hd),  start = per-slot position (B,)
  verify   q: (B, C, K, R, hd),  start = per-slot first position (B,)
  chunk    q: (1, C, K, R, hd),  start = traced chunk offset (1,)

Decode and verify pick the same tile width (their score tiles are far
below the VMEM budget), which keeps a verify row bitwise equal to the
decode step at its position; a prefill chunk's many query rows may pick a
narrower tile.

Masking matches the jnp paths row for row: query i of slot b sees key
position p iff ``fold_base[b] <= p <= start[b] + i`` and p lies in a
block whose table entry is a real pool id (entries >= num_blocks mark
unallocated / invalidated rows — the whole block is masked dead and its
fetch is clamped to block ``num_blocks - 1``, so retired slots read
nothing).  With ``fold_base == 0`` the lower bound is vacuous and the
statistics cover the full causal span; with ``fold_base > 0`` they cover
exactly the two-span exact window, merge-ready against
``serve/kv_sketch.py``'s ``tail_attend`` output via ``merge_spans``.

Precision follows the repo's flash idiom (layers._flash_attention):
scores and running statistics are f32 (``preferred_element_type``); the
per-tile weight matrix is cast back to the value dtype for the weighted-
value MXU pass.  ``kernels/ref.py:paged_attention_ref`` mirrors the tile
walk op for op, so interpret mode reproduces it bitwise.

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

# a tile's live table entries travel as the bits of one int32
_MAX_TILE_BLOCKS = 32
_MAX_TILE_KEYS = 512
# VMEM the tile width may claim (pool tiles, score temporaries, the
# query and accumulator blocks), and the compiler's default scoped limit
_VMEM_BUDGET = 24 << 20
_SCOPED_VMEM = 16 << 20


def _rows(n: int, itemsize: int) -> int:
    """``n`` rounded up to the sublane tile of a dtype of ``itemsize``."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-n // sub) * sub


def _row_block(NQ: int) -> int:
    """Query rows per kv head that one pass of the tile arithmetic takes:
    all of them, or 256 (128) at a time for a prefill chunk's many rows,
    which bounds the score temporaries and the kernel's code size."""
    for rows in (256, 128):
        if NQ > rows and NQ % rows == 0:
            return rows
    return NQ


def _vmem_bytes(P: int, bs: int, K: int, hd: int, NQ: int,
                itemsize: int) -> int:
    """VMEM of one kernel instance at tile width ``P``: the double-buffered
    K and V tiles, three f32 score temporaries of one row block, and the
    double-buffered query and f32 accumulator blocks."""
    tiles = 2 * 2 * P * bs * _rows(K, itemsize) * hd * itemsize
    scores = 3 * _rows(_row_block(NQ), 4) * P * bs * 4
    blocks = 2 * K * hd * (_rows(NQ, itemsize) * itemsize
                           + _rows(NQ, 4) * 4)
    return tiles + scores + blocks


def tile_blocks(bs: int, K: int, hd: int, nb_slot: int, NQ: int,
                itemsize: int = 2) -> int:
    """Table entries per key tile for a call with ``NQ = R * Sq`` query
    rows per KV head: the widest power-of-two tile of at most
    ``_MAX_TILE_KEYS`` keys (and ``_MAX_TILE_BLOCKS`` entries, no wider
    than the table) whose working set fits the VMEM budget."""
    P = 1
    while (2 * P <= min(nb_slot, _MAX_TILE_BLOCKS, _MAX_TILE_KEYS // bs)
           and _vmem_bytes(2 * P, bs, K, hd, NQ, itemsize) <= _VMEM_BUDGET):
        P *= 2
    return P


def tile_range(start, fold_base, Sq: int, tile_keys: int):
    """First and last key tile holding a key of ``[fold_base, start + Sq
    - 1]``, the keys some query row of the call sees.  The last may lie
    past the table's last tile, and the range is empty when first >
    last.  Works on Python ints, NumPy arrays and traced scalars alike."""
    return fold_base // tile_keys, (start + Sq - 1) // tile_keys


def _walk(tbl_ref, st_ref, fb_ref, q_ref, k_hbm, v_hbm,
          m_ref, l_ref, acc_ref, kbuf, vbuf, sem, nxt_ref, *,
          B, T, P, bs, Sq, K, NQ, NB, nb_slot, scale):
    """Grid (B, T): fold key tile t of slot b into the slot's running
    statistics when it is live.  NQ = R * Sq query rows per kv head; row
    r*Sq+i is query position i of q-head replica r.  ``nxt_ref`` (SMEM)
    carries across grid steps the next live tile (slot, tile), the VMEM
    buffer its copies land in, and the bit mask of its real entries."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    W = P * bs

    def entry(bb, j):
        """Table entry j of slot bb, and whether it names a pool block
        (entries past the table's end are dead)."""
        e = tbl_ref[bb, jnp.minimum(j, nb_slot - 1)]
        return e, (j < nb_slot) & (e < NB)

    def entry_bits(bb, tt):
        return jax.lax.fori_loop(
            0, P, lambda i, bits: bits | jnp.where(
                entry(bb, tt * P + i)[1], jnp.left_shift(1, i), 0),
            jnp.int32(0))

    def next_live(bb, tt):
        """The first live tile at or after (bb, tt) in grid order, with
        its entry mask; (B, 0, 0) when none is left."""
        def body(c):
            bb, tt, _ = c
            lo, hi = tile_range(st_ref[bb], fb_ref[bb], Sq, W)
            hi = jnp.minimum(hi, T - 1)
            tt = jnp.maximum(tt, lo)
            bits = jnp.where(tt <= hi, entry_bits(bb, tt), 0)
            stay = (bits != 0) | (tt < hi)
            found = bits != 0
            return (jnp.where(stay, bb, bb + 1),
                    jnp.where(found, tt, jnp.where(stay, tt + 1, 0)), bits)

        return jax.lax.while_loop(lambda c: (c[0] < B) & (c[2] == 0), body,
                                  (bb, tt, jnp.int32(0)))

    def tile_copies(bb, tt, buf, wait):
        """Start (or wait for) the copies of tile tt's P pool blocks into
        VMEM buffer ``buf``; all 2P copies signal that buffer's semaphore."""
        def go(i, c):
            # dead entries still need an in-range fetch; the tile's entry
            # mask drops their keys, so the clamped read is never used
            blk = jnp.minimum(entry(bb, tt * P + i)[0], NB - 1)
            for hbm, vmem in ((k_hbm, kbuf), (v_hbm, vbuf)):
                cp = pltpu.make_async_copy(
                    hbm.at[blk], vmem.at[buf, pl.ds(i * bs, bs)],
                    sem.at[buf])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return c
        jax.lax.fori_loop(0, P, go, 0)

    @pl.when((b == 0) & (t == 0))
    def _first():
        nb_, nt_, bits = next_live(jnp.int32(0), jnp.int32(0))
        nxt_ref[0] = nb_
        nxt_ref[1] = nt_
        nxt_ref[2] = 0
        nxt_ref[3] = bits

        @pl.when(nb_ < B)
        def _():
            tile_copies(nb_, nt_, 0, wait=False)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((b == nxt_ref[0]) & (t == nxt_ref[1]))
    def _live():
        buf = nxt_ref[2]
        bits = nxt_ref[3]
        nb_, nt_, nbits = next_live(b, t + 1)
        nxt_ref[0] = nb_
        nxt_ref[1] = nt_
        nxt_ref[2] = 1 - buf
        nxt_ref[3] = nbits

        @pl.when(nb_ < B)
        def _():
            tile_copies(nb_, nt_, 1 - buf, wait=False)

        tile_copies(b, t, buf, wait=True)
        QB = _row_block(NQ)
        col = jax.lax.broadcasted_iota(jnp.int32, (QB, W), 1)
        kpos = t * W + col
        real = (jnp.right_shift(bits, col // bs) & 1) == 1
        seen = (kpos >= fb_ref[b]) & real

        def rows(rb, c):
            r0 = rb * QB
            rs = pl.ds(r0 if NQ == QB else pl.multiple_of(r0, QB), QB)
            qi = (r0 + jax.lax.broadcasted_iota(jnp.int32, (QB, W), 0)) % Sq
            live = (kpos <= st_ref[b] + qi) & seen
            # one load of the whole tile, then a head per slice: slicing
            # the head out of the VMEM ref compiled 7-12x slower at K = 4
            # and ran slower on a v5e
            kt = kbuf[buf]                                # (W, K, hd)
            vt = vbuf[buf]
            for z in range(K):
                qz = q_ref[0, z, rs, :]                   # (QB, hd)
                kz = kt[:, z, :]                          # (W, hd)
                vz = vt[:, z, :]
                s = jax.lax.dot_general(
                    qz, kz, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(live, s, -1e30)
                m_prev = m_ref[0, z, rs]
                m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                # exp(-1e30 - (-1e30)) == 1 on fully-dead rows: re-zero
                p = jnp.where(live, jnp.exp(s - m_cur[:, None]), 0.0)
                corr = jnp.exp(m_prev - m_cur)
                m_ref[0, z, rs] = m_cur
                l_ref[0, z, rs] = l_ref[0, z, rs] * corr + jnp.sum(p, -1)
                pv = jax.lax.dot_general(p.astype(vz.dtype), vz,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                acc_ref[0, z, rs] = acc_ref[0, z, rs] * corr[:, None] + pv
            return c

        if NQ == QB:
            rows(0, 0)
        else:
            jax.lax.fori_loop(0, NQ // QB, rows, 0)


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
    itemsize = k_pool.dtype.itemsize
    P = tile_blocks(bs, K, hd, nb_slot, NQ, itemsize)
    T = -(-nb_slot // P)
    scale = 1.0 / math.sqrt(hd)
    # (B, K, R*Sq, hd): kv-head-major rows so each head's queries are one
    # contiguous MXU tile inside the kernel
    qt = q.transpose(0, 2, 3, 1, 4).reshape(B, K, NQ, hd)
    vmem = _vmem_bytes(P, bs, K, hd, NQ, itemsize)

    row = lambda b, t, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, K, NQ, hd), lambda b, t, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, K, NQ), row),
            pl.BlockSpec((1, K, NQ), row),
            pl.BlockSpec((1, K, NQ, hd), lambda b, t, *_: (b, 0, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, P * bs, K, hd), k_pool.dtype),
            pltpu.VMEM((2, P * bs, K, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((4,), jnp.int32),
        ],
    )
    m, l, acc = pl.pallas_call(
        functools.partial(_walk, B=B, T=T, P=P, bs=bs, Sq=Sq, K=K, NQ=NQ,
                          NB=NB, nb_slot=nb_slot, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, K, NQ), jnp.float32),
            jax.ShapeDtypeStruct((B, K, NQ), jnp.float32),
            jax.ShapeDtypeStruct((B, K, NQ, hd), jnp.float32),
        ],
        # the walk carries its prefetch across grid steps: strictly in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(None if vmem <= _SCOPED_VMEM
                              else vmem + vmem // 2)),
        interpret=interpret,
    )(tables.astype(jnp.int32), start.astype(jnp.int32),
      fold_base.astype(jnp.int32), qt, k_pool, v_pool)
    return (m.reshape(B, K, R, Sq), l.reshape(B, K, R, Sq),
            acc.reshape(B, K, R, Sq, hd))
