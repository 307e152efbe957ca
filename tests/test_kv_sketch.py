"""Sketched long-context KV subsystem (serve/kv_sketch.py): bitwise
short-context regression, fold-then-query fidelity across compression
ratios, fold-through long-context decode past the pool's row capacity,
slot lifecycle with live tails, speculative identity, pspecs coverage,
Pallas kernels vs oracles, and the freed-block prefix-cache guard."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import model as M
from repro.serve import kv_sketch as kvs
from repro.serve.scheduler import Request, SlotScheduler


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, **kw):
    base = dict(max_batch=2, max_seq=128, decode_chunk=4,
                prefill_bucket=16)
    base.update(kw)
    return dataclasses.replace(cfg.serve, **base)


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _run(cfg, params, serve, reqs):
    sched = SlotScheduler(cfg, params, serve=serve)
    return sched, {c.rid: list(c.tokens) for c in sched.run(reqs)}


# ---------------------------------------------------------------------------
# Bitwise regression + engine contracts
# ---------------------------------------------------------------------------


def test_short_context_bitwise_regression(gemma):
    """The regression anchor: a sketch engine whose window covers every
    context decodes BITWISE identically to a sketch-free engine (the
    two-span select picks the unchanged exact-path output), while decode
    and prefill still compile exactly once."""
    cfg, params = gemma
    lens = [5, 21, 13, 30]
    reqs = lambda: [Request(rid=i, tokens=p, max_new=4)
                    for i, p in enumerate(_prompts(cfg, lens))]
    _, ref = _run(cfg, params, _serve(cfg), reqs())
    s, got = _run(cfg, params,
                  _serve(cfg, kv_sketch_window=128), reqs())
    assert got == ref
    assert s.decode_compilations == 1
    assert s.prefill_compilations == 1


def test_opt_out_request_stays_exact(gemma):
    """Per-request kv_sketch=False keeps that request's whole context
    exact even on an engine with a small window — its tokens match a
    sketch-free engine's bitwise."""
    cfg, params = gemma
    (p,) = _prompts(cfg, [60], seed=3)
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=1, kv_sketch_window=2 * bs)
    _, got = _run(cfg, params, sv,
                  [Request(rid=0, tokens=p, max_new=4, kv_sketch=False)])
    _, ref = _run(cfg, params, _serve(cfg, max_batch=1),
                  [Request(rid=0, tokens=p, max_new=4)])
    assert got == ref


def test_long_context_past_pool_capacity(gemma):
    """The tentpole claim: a slot decodes a context >= 4x the pool's row
    capacity — impossible for the exact paged path, which must reserve
    every block of the context — because aged blocks fold into the tail
    and return to the pool."""
    cfg, params = gemma
    bs = cfg.serve.kv_block_size
    nb = 9
    sv = _serve(cfg, max_batch=1, max_seq=1024, num_kv_blocks=nb,
                kv_sketch_window=4 * bs, admit_threshold=1 << 30)
    S = 4 * nb * bs + 20
    (p,) = _prompts(cfg, [S], seed=7)
    sched, done = _run(cfg, params, sv,
                       [Request(rid=0, tokens=p, max_new=6)])
    assert len(done[0]) == 6
    assert sched.decode_compilations == 1
    assert sched.prefill_compilations == 1
    assert sched.kv_sketch_tail_bytes() > 0
    # everything returned to the pool after retirement
    assert sched.alloc.reserved_bytes() == 0


def test_fold_fidelity_improves_with_ratio_and_rows(gemma):
    """Fold-then-query accuracy: the tail span's softmax output tracks
    the dense oracle better as compression relaxes (smaller ratio ->
    more cols) and as hash rows are added — the count-sketch variance
    contract, measured end-to-end through fold_rows + tail_attend."""
    cfg, params = gemma
    rng = np.random.RandomState(0)
    K, hd, R = 2, 16, 2
    T, bsz = 96, 16
    kr = jnp.asarray(rng.randn(1, T, K, hd).astype(np.float32))
    vr = jnp.asarray(rng.randn(1, T, K, hd).astype(np.float32))
    q = jnp.asarray(rng.randn(1, 1, K, R, hd).astype(np.float32))
    fb = jnp.asarray([T], jnp.int32)
    scale = 1.0 / float(np.sqrt(hd))
    _, l_o, acc_o = kvs.dense_tail_stats(q, kr, vr, fb, scale)
    oracle = (acc_o / l_o[..., None]).reshape(-1)

    def cos(ratio, rows):
        sv = dataclasses.replace(cfg.serve, kv_sketch_ratio=ratio,
                                 kv_sketch_rows=rows)
        coeffs = kvs.tail_coeffs(sv)
        C = kvs.tail_cols(T, ratio)
        onehot = kvs.pos_onehot(coeffs, kvs.pos_domain(T, bsz), C)
        tail = kvs.fold_rows(kr, vr, jnp.arange(T, dtype=jnp.int32),
                             coeffs, C)
        _, l_t, acc_t = kvs.tail_attend(q, tail["k"], tail["v"], onehot,
                                        fb, scale)
        out = (acc_t / jnp.maximum(l_t, 1e-30)[..., None]).reshape(-1)
        return float(jnp.vdot(out, oracle)
                     / (jnp.linalg.norm(out) * jnp.linalg.norm(oracle)))

    by_ratio = [cos(r, 3) for r in (8, 4, 1)]
    assert by_ratio == sorted(by_ratio), by_ratio
    assert by_ratio[-1] > 0.7, by_ratio
    assert cos(2, 5) > cos(2, 1)


def test_fold_pool_matches_fold_rows(gemma):
    """The in-chunk pool fold (block tables, traced lengths) and the
    reference explicit-row fold accumulate bitwise-identical tables for
    the same rows — they share row_buckets_signs."""
    cfg, params = gemma
    rng = np.random.RandomState(1)
    L, NB, bs, K, hd = 2, 6, 8, 2, 16
    Z, C = 3, 32
    sv = dataclasses.replace(cfg.serve, kv_sketch_rows=Z)
    coeffs = kvs.tail_coeffs(sv)
    pool = {"k": jnp.asarray(rng.randn(L, NB, bs, K, hd).astype(np.float32)),
            "v": jnp.asarray(rng.randn(L, NB, bs, K, hd).astype(np.float32))}
    # slot 0 holds physical blocks [4, 1, 2]; fold its first 2 blocks
    tables = jnp.asarray([[4, 1, 2, NB]], jnp.int32)
    tail0 = {"k": jnp.zeros((L, 1, Z, C, K, hd), jnp.float32),
             "v": jnp.zeros((L, 1, Z, C, K, hd), jnp.float32)}
    got = kvs.fold_pool(pool, tail0, tables,
                        jnp.asarray([0], jnp.int32),
                        jnp.asarray([2 * bs], jnp.int32), coeffs,
                        fold_cap=3 * bs)
    rows_k = jnp.concatenate([pool["k"][:, 4], pool["k"][:, 1]],
                             axis=1)              # (L, 2*bs, K, hd)
    rows_v = jnp.concatenate([pool["v"][:, 4], pool["v"][:, 1]], axis=1)
    ref = kvs.fold_rows(rows_k, rows_v,
                        jnp.arange(2 * bs, dtype=jnp.int32), coeffs, C)
    np.testing.assert_array_equal(np.asarray(got["k"][:, 0]),
                                  np.asarray(ref["k"]))
    np.testing.assert_array_equal(np.asarray(got["v"][:, 0]),
                                  np.asarray(ref["v"]))


# ---------------------------------------------------------------------------
# Lifecycle: retire / reuse / fork / speculative
# ---------------------------------------------------------------------------


def test_slot_reuse_after_sketched_retire(gemma):
    """A slot that served a folded long request is clean for its next
    occupant: the tail is re-zeroed at admission, so a short request
    decodes bitwise as on a fresh engine."""
    cfg, params = gemma
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=1, max_seq=256, num_kv_blocks=24,
                kv_sketch_window=2 * bs)
    long_p, short_p = _prompts(cfg, [150, 11], seed=5)
    sched = SlotScheduler(cfg, params, serve=sv)
    sched.run([Request(rid=0, tokens=long_p, max_new=3)])
    assert sched._slot_first_lblk[0] == 0        # reset at retirement
    got = {c.rid: list(c.tokens)
           for c in sched.run([Request(rid=1, tokens=short_p, max_new=4)])}
    _, ref = _run(cfg, params, sv,
                  [Request(rid=1, tokens=short_p, max_new=4)])
    assert got == ref
    assert sched.decode_compilations == 1


def test_sketched_stream_mixed_with_exact(gemma):
    """Sketched and opted-out requests share one engine, one compiled
    chunk: the exact request's tokens match a sketch-free engine's,
    and everything completes."""
    cfg, params = gemma
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=2, max_seq=256, num_kv_blocks=24,
                kv_sketch_window=2 * bs)
    pl, pe = _prompts(cfg, [140, 17], seed=9)
    reqs = [Request(rid=0, tokens=pl, max_new=4),
            Request(rid=1, tokens=pe, max_new=4, kv_sketch=False)]
    sched, got = _run(cfg, params, sv, reqs)
    assert set(got) == {0, 1} and all(len(v) == 4 for v in got.values())
    _, ref = _run(cfg, params, _serve(cfg, max_batch=1, max_seq=256,
                                      num_kv_blocks=24),
                  [Request(rid=1, tokens=pe, max_new=4)])
    assert got[1] == ref[1]
    assert sched.decode_compilations == 1


def test_speculative_sketch_identity_and_long_context(gemma):
    """Speculative engines compose: with window >= context the sketched
    spec engine's greedy output is bitwise a plain spec engine's; with a
    small window a long prompt still decodes (draft pool and tail fold
    in lockstep), one compilation each."""
    cfg, params = gemma
    sv0 = _serve(cfg, max_batch=2, max_seq=96, decode_chunk=2, spec_k=2)
    reqs = lambda: [Request(rid=i, tokens=p, max_new=5)
                    for i, p in enumerate(_prompts(cfg, [9, 18], seed=2))]
    _, ref = _run(cfg, params, sv0, reqs())
    s1, got = _run(cfg, params,
                   dataclasses.replace(sv0, kv_sketch_window=96), reqs())
    assert got == ref
    assert s1.decode_compilations == 1
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=1, max_seq=256, decode_chunk=2, spec_k=2,
                num_kv_blocks=14, kv_sketch_window=4 * bs)
    (p,) = _prompts(cfg, [180], seed=4)
    s2, done = _run(cfg, params, sv, [Request(rid=0, tokens=p, max_new=6)])
    assert len(done[0]) == 6
    assert s2.decode_compilations == 1


def test_reseed_leaves_inflight_sketch_state(gemma):
    """reseed() swaps the base sampling key only — a queued sketched
    request admitted after the reseed still folds and completes."""
    cfg, params = gemma
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=1, max_seq=256, num_kv_blocks=24,
                kv_sketch_window=2 * bs)
    (p,) = _prompts(cfg, [100], seed=6)
    sched = SlotScheduler(cfg, params, serve=sv)
    sched.submit(Request(rid=0, tokens=p, max_new=3, temperature=0.7,
                         top_k=4))
    sched.reseed(jax.random.PRNGKey(42))
    done = []
    while sched.pending:
        done.extend(sched.step())
    assert len(done) == 1 and len(done[0].tokens) == 3


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------


def test_sketched_state_pspecs(gemma):
    """serve_state_pspecs covers the new state: tail tables put their
    bucket-column axis on the split-KV ("model") axis, fold_base rides
    the batch axis, and the spec tree matches the state tree."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.shardings import serve_state_pspecs
    from repro.models.sharding import decode_rules

    cfg, params = gemma
    sv = _serve(cfg, kv_sketch_window=128, spec_k=2)
    sched = SlotScheduler(cfg, params, serve=sv)
    rules = decode_rules(multi_pod=False, long_context=False)
    specs = serve_state_pspecs(cfg, sched.state, rules)
    b = rules["batch"]
    assert specs.cache["tail"]["k"] == P(None, b, None, "model", None,
                                         None)
    assert specs.cache["draft"]["tail"]["k"] == \
        P(None, b, None, "model", None, None)
    assert specs.fold_base == P(b)
    # the spec tree must mirror the state tree exactly — a missing field
    # would silently replicate that array under shard_map
    assert (jax.tree.structure(sched.state)
            == jax.tree.structure(
                specs, is_leaf=lambda x: x is None or isinstance(x, P)))


# ---------------------------------------------------------------------------
# Pallas kernels vs oracles
# ---------------------------------------------------------------------------


def test_tail_fold_kernel_matches_oracle():
    from repro.kernels import kv_sketch as kk
    from repro.kernels import ref
    from repro.sketch.hashing import cached_coeffs

    rng = np.random.RandomState(0)
    Z, C, D, N, T = 3, 48, 64, 150, 200
    coeffs = cached_coeffs(7, Z)
    rows = jnp.asarray(rng.randn(N, D).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, T, (N,)).astype(np.int32))
    tail = jnp.asarray(rng.randn(Z, C, D).astype(np.float32))
    got = kk.tail_fold(rows, pos, tail, coeffs, bN=64, bC=32, interpret=True)
    want = ref.kv_tail_fold_ref(rows, pos, tail, coeffs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


def test_tail_scores_kernel_matches_oracle():
    from repro.kernels import kv_sketch as kk
    from repro.kernels import ref
    from repro.sketch.hashing import cached_coeffs

    rng = np.random.RandomState(1)
    Z, C, D, N, T = 3, 32, 48, 20, 130
    coeffs = cached_coeffs(11, Z)
    q = jnp.asarray(rng.randn(N, D).astype(np.float32))
    tail_k = jnp.asarray(rng.randn(Z, C, D).astype(np.float32))
    got = kk.tail_scores(q, tail_k, coeffs, T=T, bN=16, bT=64,
                         interpret=True)
    want = ref.kv_tail_scores_ref(q, tail_k, coeffs, T)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# Prefix-cache guard (bugfix)
# ---------------------------------------------------------------------------


def test_prefix_admit_rejects_freed_blocks(gemma):
    """The freed-block guard: admitting a prefix whose blocks have been
    returned to the pool must fail loudly — a ref on a freed block would
    resurrect it while the allocator hands the same block elsewhere."""
    cfg, params = gemma
    sched = SlotScheduler(cfg, params, serve=_serve(cfg))
    bs = sched.block_size
    ids = sched.alloc.alloc(2)
    prompt = np.arange(2 * bs, dtype=np.int32)
    sched.alloc.unref(ids)                      # freed: rc back to 0
    with pytest.raises(AssertionError, match="freed block"):
        sched.prefix_cache.admit(prompt, 2 * bs, tuple(ids))


def test_folded_prefix_never_admitted(gemma):
    """A sketched request whose qualifying prefix folded (and freed its
    leading blocks) must not register a prefix-cache entry — the entry
    would map prompt tokens to re-allocatable block ids."""
    cfg, params = gemma
    bs = cfg.serve.kv_block_size
    sv = _serve(cfg, max_batch=1, max_seq=256, num_kv_blocks=24,
                kv_sketch_window=2 * bs, admit_threshold=1)
    (p,) = _prompts(cfg, [120], seed=8)
    sched = SlotScheduler(cfg, params, serve=sv)
    for rid in range(3):
        sched.run([Request(rid=rid, tokens=p, max_new=2)])
    st = sched.prefix_cache.stats
    assert st.admitted == 0, st
