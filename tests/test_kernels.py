"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles.  Every kernel defaults to its compiled TPU form, so each call
here asks for interpret mode, which executes the kernel body on CPU.

The CI kernel-oracle matrix job selects one kernel family per matrix
entry with ``pytest tests/test_kernels.py -k <family>`` — every
kernel/ref pair in ``kernels/ref.py`` has at least one test here whose
name contains its family (count_sketch, unsketch, sketch_update,
kv_tail, paged_attention), so no kernel can drift from its oracle
unexercised."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.count_sketch import count_sketch
from repro.kernels.ops import (count_sketch_op, kernel_mode,
                               paged_attention_op, unsketch_op)
from repro.kernels.paged_attention import (paged_attention, tile_blocks,
                                           tile_range)
from repro.kernels.unsketch import unsketch

SHAPES = [(1, 64, 32), (4, 1000, 256), (2, 300, 64), (8, 4096, 512),
          (1, 50, 300), (3, 128, 128)]
BLOCKS = [(2, 128, 128), (4, 256, 64)]


def _inputs(B, I, J, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (B, I)).astype(dtype)
    h = jax.random.randint(ks[1], (I,), 0, J)
    s = (1.0 - 2.0 * jax.random.randint(ks[2], (I,), 0, 2)
         ).astype(jnp.float32)
    return x, h, s


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("blocks", BLOCKS)
def test_count_sketch_matches_ref_f32(shape, blocks):
    B, I, J = shape
    bB, bI, bJ = blocks
    x, h, s = _inputs(B, I, J, jnp.float32)
    out = count_sketch(x, h, s, J, bB=bB, bI=bI, bJ=bJ, interpret=True)
    np.testing.assert_allclose(out, ref.count_sketch_ref(x, h, s, J),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
def test_unsketch_matches_ref_f32(shape):
    B, I, J = shape
    _, h, s = _inputs(B, I, J, jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(9), (B, J))
    out = unsketch(y, h, s, bB=2, bI=128, bJ=128, interpret=True)
    np.testing.assert_allclose(out, ref.unsketch_ref(y, h, s),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 3e-2),
                                       (jnp.float32, 2e-5)])
def test_count_sketch_dtypes(dtype, tol):
    x, h, s = _inputs(4, 512, 128, dtype)
    out = count_sketch(x, h, s, 128, interpret=True)
    refv = ref.count_sketch_ref(x.astype(jnp.float32), h, s, 128)
    np.testing.assert_allclose(out.astype(jnp.float32), refv,
                               rtol=tol, atol=tol)


def test_roundtrip_unbiased_entries():
    """unsketch(count_sketch(x)) has the right diagonal (each entry
    contains its own value plus zero-mean collision noise)."""
    B, I, J = 1, 256, 4096
    x, h, s = _inputs(B, I, J, jnp.float32, seed=3)
    y = count_sketch(x, h, s, J, interpret=True)
    xhat = unsketch(y, h, s, interpret=True)
    err = jnp.abs(xhat - x)
    # J >> I: expected collision-free fraction ~ (1 - 1/J)^(I-1) ~ 94%
    frac_exact = float(jnp.mean(err < 1e-4))
    assert frac_exact > 0.85, frac_exact
    assert float(jnp.median(err)) < 1e-5


def test_kernel_mode_resolution():
    """The one compiled-or-interpreted decision: a TPU runs the kernel
    compiled; elsewhere the kernel runs only when asked for, and then
    interpreted."""
    on_tpu = jax.default_backend() == "tpu"
    assert kernel_mode(None) == ("compiled" if on_tpu else None)
    assert kernel_mode(True) == ("compiled" if on_tpu else "interpret")
    assert kernel_mode(False) is None


def test_ops_dispatch():
    x, h, s = _inputs(2, 200, 64, jnp.float32)
    a = count_sketch_op(x, h, s, 64, use_pallas=True)
    b = count_sketch_op(x, h, s, 64, use_pallas=False)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 64))
    a = unsketch_op(y, h, s, use_pallas=True)
    b = unsketch_op(y, h, s, use_pallas=False)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sketch_update / kv_tail kernels vs ref (pair coverage for the CI matrix;
# deeper sweeps live in test_sketch_opt.py / test_kv_sketch.py)
# ---------------------------------------------------------------------------


def test_sketch_update_matches_ref():
    from repro.kernels.sketch_update import sketch_update
    from repro.sketch.hashing import cached_coeffs

    rng = np.random.RandomState(0)
    n, R, C = 700, 3, 128
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    m_t = jnp.asarray(rng.randn(R, C).astype(np.float32))
    v_t = jnp.abs(jnp.asarray(rng.randn(R, C).astype(np.float32)))
    cm, cv = cached_coeffs(3, R), cached_coeffs(5, R)
    got = sketch_update(g, m_t, v_t, cm, cv, b1=0.9, b2=0.95,
                        interpret=True)
    want = ref.sketch_update_ref(g, m_t, v_t, cm, cv, 0.9, 0.95)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_kv_tail_fold_matches_ref():
    from repro.kernels import kv_sketch as kk
    from repro.sketch.hashing import cached_coeffs

    rng = np.random.RandomState(2)
    Z, C, D, N, T = 3, 32, 48, 90, 160
    coeffs = cached_coeffs(7, Z)
    rows = jnp.asarray(rng.randn(N, D).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, T, (N,)).astype(np.int32))
    tail = jnp.asarray(rng.randn(Z, C, D).astype(np.float32))
    got = kk.tail_fold(rows, pos, tail, coeffs, bN=32, bC=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.kv_tail_fold_ref(rows, pos, tail,
                                                         coeffs)),
        atol=1e-4)


def test_kv_tail_scores_matches_ref():
    from repro.kernels import kv_sketch as kk
    from repro.sketch.hashing import cached_coeffs

    rng = np.random.RandomState(3)
    Z, C, D, N, T = 3, 32, 48, 20, 130
    coeffs = cached_coeffs(11, Z)
    q = jnp.asarray(rng.randn(N, D).astype(np.float32))
    tail_k = jnp.asarray(rng.randn(Z, C, D).astype(np.float32))
    got = kk.tail_scores(q, tail_k, coeffs, T=T, bN=16, bT=64,
                         interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.kv_tail_scores_ref(q, tail_k,
                                                           coeffs, T)),
        atol=1e-4)


# ---------------------------------------------------------------------------
# paged attention (flash-decode over block tables)
# ---------------------------------------------------------------------------

# (B, Sq, K, R, hd, NB, bs, nb): decode-, verify- and chunk-shaped cases;
# the last four walk tables of several key tiles
PAGED_SHAPES = [
    (3, 1, 2, 3, 16, 12, 8, 4),     # single-token decode, GQA
    (2, 4, 2, 2, 32, 10, 4, 5),     # speculative verify (C = 4)
    (1, 16, 1, 4, 16, 8, 8, 6),     # chunked prefill (one slot), MQA
    (4, 3, 3, 1, 8, 20, 16, 3),     # R == 1 (MHA-as-GQA degenerate)
    (2, 1, 2, 3, 16, 400, 4, 160),  # decode, 5 tiles of 128 keys
    (3, 4, 2, 2, 16, 300, 8, 96),   # verify, 3 tiles of 256 keys
    (1, 16, 2, 2, 16, 200, 8, 96),  # chunk, 3 tiles of 256 keys
    (1, 256, 1, 2, 16, 200, 8, 96),  # chunk in two blocks of 256 rows
]


def _paged_inputs(B, Sq, K, R, hd, NB, bs, nb, seed=0,
                  dtype=jnp.bfloat16):
    """Ragged per-slot geometry: every slot gets its own start position
    (some mid-block, some spanning several blocks), slot 0 gets an
    invalidated table row, and fold_base mixes zero / nonzero."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Sq, K, R, hd), dtype)
    kp = jnp.asarray(rng.randn(NB, bs, K, hd), dtype)
    vp = jnp.asarray(rng.randn(NB, bs, K, hd), dtype)
    tables = jnp.asarray(
        rng.permutation(NB)[:B * nb].reshape(B, nb)
        if B * nb <= NB else rng.randint(0, NB, (B, nb)), jnp.int32)
    tables = tables.at[0, nb - 1].set(NB)          # invalidated row
    start = jnp.asarray(rng.randint(0, nb * bs - Sq, (B,)), jnp.int32)
    fb = jnp.asarray([0] * (B - B // 2) + [bs] * (B // 2), jnp.int32)
    fb = jnp.minimum(fb, start)    # window always contains the query row
    return q, kp, vp, tables, start, fb


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_attention_matches_ref(shape):
    """Interpret-mode kernel vs the jnp online-softmax oracle: the block
    loop is op-for-op identical, so the statistics agree to ~bitwise
    (asserted at rtol 1e-5, well below the acceptance bar)."""
    got = paged_attention(*_paged_inputs(*shape), interpret=True)
    want = ref.paged_attention_ref(*_paged_inputs(*shape))
    for name, a, b in zip("m l acc".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def _assert_dense_oracle(q, kp, vp, tables, start, fb, stats):
    """Normalized kernel output vs a full-softmax f32 oracle computed per
    slot over the gathered span (per-row causal bound, fold_base lower
    bound, dead blocks)."""
    m, l, acc = stats
    B, Sq, K, R, hd = q.shape
    NB, bs = kp.shape[0], kp.shape[1]
    nb = tables.shape[1]
    out = np.asarray(acc / jnp.maximum(l, 1e-30)[..., None])
    S = nb * bs
    kt = np.asarray(jnp.take(kp, tables, axis=0, mode="fill",
                             fill_value=0), np.float32).reshape(B, S, K, hd)
    vt = np.asarray(jnp.take(vp, tables, axis=0, mode="fill",
                             fill_value=0), np.float32).reshape(B, S, K, hd)
    qf = np.asarray(q, np.float32)
    kpos = np.arange(S)
    blk_ok = np.repeat(np.asarray(tables) < NB, bs).reshape(B, S)
    scale = 1.0 / np.sqrt(hd)
    for b in range(B):
        for i in range(Sq):
            live = ((kpos <= int(start[b]) + i)
                    & (kpos >= int(fb[b])) & blk_ok[b])
            for z in range(K):
                for r in range(R):
                    s = kt[b, :, z] @ qf[b, i, z, r] * scale
                    s = np.where(live, s, -1e30)
                    w = np.where(live, np.exp(s - s.max()), 0.0)
                    o = (w @ vt[b, :, z]) / max(w.sum(), 1e-30)
                    np.testing.assert_allclose(out[b, z, r, i], o,
                                               rtol=2e-2, atol=2e-2)


def test_paged_attention_ragged_lengths_dense_oracle():
    """Normalized kernel output vs a full-softmax f32 oracle computed
    per slot over the gathered span — checks the mask semantics (per-row
    causal bound, fold_base lower bound, dead blocks) rather than the
    update equations."""
    B, Sq, K, R, hd, NB, bs, nb = 2, 4, 2, 2, 16, 10, 4, 5
    args = _paged_inputs(B, Sq, K, R, hd, NB, bs, nb, seed=5)
    _assert_dense_oracle(*args, paged_attention(*args, interpret=True))


# slot 0's geometry in a table of 160 blocks of 4 rows, 5 tiles of 128
# keys (slot 1 stays an ordinary mid-table slot): (Sq, start, fold_base,
# entries of slot 0 marked dead, slot 0 retired)
W_EDGE = 128
EDGE_CASES = {
    "ends_in_first_tile": (1, 5, 0, (), False),
    "verify_ends_in_first_tile": (4, W_EDGE - 4, 0, (), False),
    "reaches_last_entry": (4, 160 * 4 - 4, 0, (), False),
    "fold_base_tiles_in": (1, 4 * W_EDGE + 20, 3 * W_EDGE + 5, (), False),
    "dead_entry_in_live_tile": (4, 2 * W_EDGE + 10, 0, (33, 47), False),
    "retired_slot": (1, 300, 0, tuple(range(160)), True),
}


@pytest.mark.parametrize("case", sorted(EDGE_CASES))
def test_paged_attention_tile_walk_edges(case):
    """Edges of the live-tile walk, bitwise against the oracle's tile walk
    and close to the dense full-softmax oracle: a range that ends inside
    the first tile, one that reaches the table's last entry, a fold_base
    several tiles in (the tiles below it are skipped), a dead entry inside
    a live tile, and a retired slot (every entry dead, a stale start)."""
    Sq, st0, fb0, dead, retired = EDGE_CASES[case]
    B, K, R, hd, NB, bs, nb = 2, 2, 3, 16, 400, 4, 160
    assert tile_blocks(bs, K, hd, nb, R * Sq) * bs == W_EDGE
    q, kp, vp, tables, start, fb = _paged_inputs(B, Sq, K, R, hd, NB, bs,
                                                 nb, seed=21)
    tables = tables.at[0, np.asarray(dead, np.int32)].set(NB)
    start = start.at[0].set(st0)
    fb = fb.at[0].set(fb0).at[1].set(0)
    args = (q, kp, vp, tables, start, fb)
    got = paged_attention(*args, interpret=True)
    want = ref.paged_attention_ref(*args)
    for name, a, b in zip("m l acc".split(), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    _assert_dense_oracle(*args, got)
    if retired:
        np.testing.assert_array_equal(np.asarray(got[1][0]), 0.0)
        np.testing.assert_array_equal(np.asarray(got[2][0]), 0.0)


@pytest.mark.parametrize("shape", PAGED_SHAPES[4:])
def test_paged_attention_skips_dead_tiles(shape):
    """A tile outside a slot's live range is never fetched: poisoning with
    NaN every pool block that lies only in such tiles leaves the
    statistics finite and bitwise unchanged.  A walk over every block of
    the table fails this (0 * NaN in p @ v)."""
    B, Sq, K, R, hd, NB, bs, nb = shape
    q, kp, vp, tables, start, fb = _paged_inputs(*shape, seed=17)
    tables = tables.at[0, nb - 1].set(tables[B - 1, 0])  # every entry real
    fb = fb.at[B - 1].set(start[B - 1] // 2)           # tiles below skipped
    base = paged_attention(q, kp, vp, tables, start, fb, interpret=True)
    P = tile_blocks(bs, K, hd, nb, R * Sq)
    lo, hi = tile_range(np.asarray(start), np.asarray(fb), Sq, P * bs)
    tile_of = np.arange(nb) // P
    walked = (tile_of[None] >= lo[:, None]) & (tile_of[None] <= hi[:, None])
    tbl = np.asarray(tables)
    poison = sorted(set(tbl[~walked].tolist()) - set(tbl[walked].tolist()))
    assert poison, "fixture must leave whole tiles unwalked"
    kp2, vp2 = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    kp2[poison] = np.nan
    vp2[poison] = np.nan
    got = paged_attention(q, jnp.asarray(kp2, kp.dtype),
                          jnp.asarray(vp2, vp.dtype), tables, start, fb,
                          interpret=True)
    for name, a, b in zip("m l acc".split(), base, got):
        assert np.isfinite(np.asarray(b)).all(), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_paged_attention_invalid_rows_drop():
    """Pool blocks behind invalidated table entries (>= NB) contribute
    nothing: scribbling huge values into every block the tables do NOT
    reference — including the block an invalidated entry would clamp to
    — leaves the statistics unchanged."""
    B, Sq, K, R, hd, NB, bs, nb = 2, 2, 2, 2, 16, 12, 4, 3
    q, kp, vp, tables, start, fb = _paged_inputs(B, Sq, K, R, hd, NB, bs,
                                                 nb, seed=7)
    ref_out = paged_attention(q, kp, vp, tables, start, fb, interpret=True)
    used = set(np.asarray(tables)[np.asarray(tables) < NB].tolist())
    unused = [j for j in range(NB) if j not in used]
    assert unused, "fixture must leave unreferenced pool blocks"
    kp2, vp2 = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    kp2[unused] = 1e4
    vp2[unused] = -1e4
    got = paged_attention(q, jnp.asarray(kp2, kp.dtype),
                          jnp.asarray(vp2, vp.dtype), tables, start, fb,
                          interpret=True)
    for a, b in zip(ref_out, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_attention_decode_rows_bitwise_match_verify():
    """A single-token decode call at position start + i reproduces row i
    of the multi-query verify call BITWISE — the kernel-side anchor that
    keeps greedy speculative decode identical to plain greedy decode."""
    B, Sq, K, R, hd, NB, bs, nb = 2, 4, 2, 2, 16, 10, 4, 5
    q, kp, vp, tables, start, fb = _paged_inputs(B, Sq, K, R, hd, NB, bs,
                                                 nb, seed=9)
    mv, lv, av = paged_attention(q, kp, vp, tables, start, fb,
                                 interpret=True)
    for i in range(Sq):
        m1, l1, a1 = paged_attention(q[:, i:i + 1], kp, vp, tables,
                                     start + i, fb, interpret=True)
        np.testing.assert_array_equal(np.asarray(m1[..., 0]),
                                      np.asarray(mv[..., i]))
        np.testing.assert_array_equal(np.asarray(l1[..., 0]),
                                      np.asarray(lv[..., i]))
        np.testing.assert_array_equal(np.asarray(a1[..., 0, :]),
                                      np.asarray(av[..., i, :]))


@pytest.mark.parametrize("R,C", [(8, 5), (3, 5), (2, 4)])
def test_paged_attention_decode_and_verify_share_tile_width(R, C):
    """Decode (Sq = 1) and speculative verify (Sq = C) pick one tile
    width at both cells' shapes and the tests', so a verify row folds its
    keys in the same tiles as the decode step at its position."""
    for bs, K, hd, nb in [(16, 4, 128, 256), (16, 8, 128, 64),
                          (4, 2, 32, 5), (8, 2, 16, 96)]:
        assert tile_blocks(bs, K, hd, nb, R) == tile_blocks(bs, K, hd, nb,
                                                            R * C)


def test_paged_attention_ops_dispatch():
    shape = PAGED_SHAPES[1]
    args = _paged_inputs(*shape, seed=11)
    got = paged_attention_op(*args, kernels="interpret")
    want = paged_attention_op(*args, kernels=None)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_paged_attention_traced_start():
    """chunk_attention passes a TRACED start offset — one compilation
    must serve every offset, so the kernel has to accept start/fold_base
    as runtime values."""
    shape = PAGED_SHAPES[2]
    q, kp, vp, tables, start, fb = _paged_inputs(*shape, seed=13)

    calls = jax.jit(lambda s: paged_attention(q, kp, vp, tables, s, fb,
                                              interpret=True))
    a = calls(start)
    b = calls(start + 4)
    assert calls._cache_size() == 1
    assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
