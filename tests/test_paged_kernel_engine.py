"""End-to-end engine contracts with the flash-decode paged-attention
Pallas kernels enabled (``ServeConfig.paged_kernels=True``, interpret
mode on CPU).

The kernel and the jnp gather path are numerically equivalent but not
bitwise-identical (online-softmax block order, bf16 p@v), so with
random-init weights greedy argmax can legitimately flip between the
implementations — cross-implementation checks therefore compare LOGITS
with tolerance (decode_step / verify_step on identical caches), while
the engine-level token assertions are the structural contracts that ARE
bitwise on the kernel path: speculative == plain, sketched anchor ==
sketch-free, run-to-run determinism, one decode compilation."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import model as M
from repro.models import transformer as tf
from repro.serve.scheduler import Request, SlotScheduler


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, **kw):
    base = dict(max_batch=2, max_seq=64, decode_chunk=4,
                prefill_bucket=16)
    base.update(kw)
    return dataclasses.replace(cfg.serve, **base)


def _reqs(cfg, lens, max_new=6, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size, (n,)).astype(
                        np.int32),
                    max_new=max_new)
            for i, n in enumerate(lens)]


def _run(cfg, params, serve, reqs):
    sched = SlotScheduler(cfg, params, serve=serve)
    return sched, {c.rid: list(c.tokens) for c in sched.run(reqs)}


def test_kernel_logits_match_jnp(gemma):
    """decode_step and verify_step on the SAME prefilled paged cache:
    interpret-mode kernel logits agree with jnp gather-path logits to
    bf16-level tolerance across every slot and verify row."""
    cfg, params = gemma
    B, bs, nper = 2, 16, 4
    tables = jnp.arange(B * nper, dtype=jnp.int32).reshape(B, nper)
    cache = tf.init_paged_cache(cfg, B * nper, bs)
    rng = np.random.RandomState(0)
    lens = [17, 30]
    for b, n in enumerate(lens):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :n] = rng.randint(0, cfg.vocab_size, (n,))
        for s in (0, 16):
            cache = tf.prefill_chunk(
                params, cache, jnp.asarray(toks[:, s:s + 16]),
                tables[b], jnp.int32(s), cfg)
    cur = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    lg_k, _ = tf.decode_step(params, dict(cache), cur, pos, cfg,
                             tables=tables, kernels="interpret")
    lg_j, _ = tf.decode_step(params, dict(cache), cur, pos, cfg,
                             tables=tables, kernels=None)
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_j),
                               rtol=5e-2, atol=5e-2)
    vt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 3)), jnp.int32)
    vg_k, _ = tf.verify_step(params, dict(cache), vt, pos, cfg,
                             tables=tables, kernels="interpret")
    vg_j, _ = tf.verify_step(params, dict(cache), vt, pos, cfg,
                             tables=tables, kernels=None)
    np.testing.assert_allclose(np.asarray(vg_k), np.asarray(vg_j),
                               rtol=5e-2, atol=5e-2)


def test_smoke_logit_check_flags_planted_fault():
    """chip_smoke.py's kernel-vs-jnp logit check, on a GQA model with a
    ragged batch: the intact kernel stays within every slot's bound and
    flips no decided top-1, while the kernel with a planted fault (each
    slot's first visible key dropped) breaks the bound on every slot."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = reduced_config("minitron-4b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, bs, nper = 4, 16, 4
    tables = jnp.arange(B * nper, dtype=jnp.int32).reshape(B, nper)
    cache = tf.init_paged_cache(cfg, B * nper, bs)
    rng = np.random.RandomState(0)
    lens = [9, 23, 32, 40]
    for b, n in enumerate(lens):
        toks = np.zeros((1, 48), np.int32)
        toks[0, :n] = rng.randint(0, cfg.vocab_size, (n,))
        for s in range(0, n, 16):
            cache = tf.prefill_chunk(
                params, cache, jnp.asarray(toks[:, s:s + 16]),
                tables[b], jnp.int32(s), cfg)
    cur = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 1)), jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    exact = np.zeros(B, bool)
    lg_k, _ = tf.decode_step(params, dict(cache), cur, pos, cfg,
                             tables=tables, kernels="interpret")
    lg_j, _ = tf.decode_step(params, dict(cache), cur, pos, cfg,
                             tables=tables, kernels=None)
    rel, bound, _, flips, _ = smoke.logit_readings(lg_k, lg_j,
                                                   cfg.vocab_size, exact)
    assert (rel <= bound).all() and not flips.any(), rel
    with smoke.first_key_dropped():
        lg_f, _ = tf.decode_step(params, dict(cache), cur, pos, cfg,
                                 tables=tables, kernels="interpret")
    rel, bound, _, _, _ = smoke.logit_readings(lg_f, lg_j, cfg.vocab_size,
                                               exact)
    assert (rel > bound).all(), rel


def test_kernel_engine_deterministic_and_compiles_once(gemma):
    """A mixed-length stream (short prompts + chunk-prefilled prompts)
    through the kernel engine: every request completes with its full
    budget, decode and chunked prefill each compile exactly once, and a
    second identical run reproduces the tokens bitwise."""
    cfg, params = gemma
    lens = [5, 17, 9, 26]
    s, got = _run(cfg, params, _serve(cfg, paged_kernels=True),
                  _reqs(cfg, lens))
    assert s.use_kernels and s.kernels == "interpret"
    assert sorted(got) == list(range(len(lens)))
    assert all(len(t) == 6 for t in got.values())
    assert s.decode_compilations == 1
    assert s.prefill_compilations == 1
    _, again = _run(cfg, params, _serve(cfg, paged_kernels=True),
                    _reqs(cfg, lens))
    assert again == got


def test_kernel_spec_greedy_identity(gemma):
    """Greedy speculative decode with kernels on emits token-for-token
    what the plain kernel engine emits — the verify kernel's rows are
    bitwise its single-token decode rows, so acceptance only changes
    speed, never tokens."""
    cfg, params = gemma
    lens = [5, 14, 22]
    _, plain = _run(cfg, params,
                    _serve(cfg, paged_kernels=True), _reqs(cfg, lens))
    s, spec = _run(cfg, params,
                   _serve(cfg, paged_kernels=True, spec_k=2,
                          draft_depth=1), _reqs(cfg, lens))
    assert s.use_kernels
    assert spec == plain
    assert s.decode_compilations == 1


def test_kernel_sketched_anchor_and_fold(gemma):
    """Sketched engines on the kernel path: a window covering every
    context is bitwise the sketch-free kernel engine (the fold_base==0
    select picks pure kernel output), and a genuinely folding window
    (exact kernel window + sketched tail merged in one chunk) runs clean,
    deterministically, in one decode compilation."""
    cfg, params = gemma
    lens = [5, 19, 28]
    _, ref = _run(cfg, params, _serve(cfg, paged_kernels=True),
                  _reqs(cfg, lens))
    s, got = _run(cfg, params,
                  _serve(cfg, paged_kernels=True, kv_sketch_window=64),
                  _reqs(cfg, lens))
    assert s.use_kernels
    assert got == ref
    assert s.decode_compilations == 1
    sv_fold = dict(kv_sketch_window=16, max_seq=64, paged_kernels=True)
    reqs = lambda: _reqs(cfg, [40, 12], max_new=5, seed=1)
    sf, fold = _run(cfg, params, _serve(cfg, **sv_fold), reqs())
    assert sf.use_kernels
    assert all(len(t) == 5 for t in fold.values())
    assert sf.decode_compilations == 1
    _, fold2 = _run(cfg, params, _serve(cfg, **sv_fold), reqs())
    assert fold2 == fold


def test_paged_kernels_resolution(gemma):
    """paged_kernels=None resolves exactly once at construction, through
    kops.kernel_mode: the compiled kernel on TPU, the jnp path elsewhere
    (no kernel on CPU), and the flag is ignored for engines without a
    paged KV pool."""
    cfg, params = gemma
    s = SlotScheduler(cfg, params, serve=_serve(cfg))
    on_tpu = jax.default_backend() == "tpu"
    assert s.use_kernels == on_tpu
    assert s.kernels == ("compiled" if on_tpu else None)
    xcfg = reduced_config("xlstm-1.3b")
    xp = M.init_params(jax.random.PRNGKey(0), xcfg)
    sx = SlotScheduler(
        xcfg, xp, serve=dataclasses.replace(
            xcfg.serve, max_batch=2, max_seq=48, decode_chunk=4,
            paged_kernels=True))
    assert not sx.use_kernels


def _tiles_meeting(lo_key, hi_key, W, T):
    """Tiles of ``W`` keys, of ``T``, that hold a key of [lo_key, hi_key],
    by brute force over each tile's keys."""
    return sum(1 for t in range(T)
               if set(range(t * W, (t + 1) * W))
               & set(range(lo_key, hi_key + 1)))


def test_attn_tile_counters_match_kernel_bound(gemma):
    """EngineStats.attn_tiles_* of a small kernel-path run equal what the
    kernel's tile width and live bound admit for every call, counted here
    by brute force from the known geometry (two slots, one reaching its
    table's second tile), and merge as counters."""
    from repro.kernels.paged_attention import tile_blocks
    from repro.serve.scheduler import EngineStats
    cfg, params = gemma
    bs, max_seq, bucket, chunk, max_new = 4, 160, 16, 4, 6
    lens = [140, 20]
    s, got = _run(cfg, params,
                  _serve(cfg, paged_kernels=True, kv_block_size=bs,
                         max_seq=max_seq, prefill_bucket=bucket,
                         decode_chunk=chunk),
                  _reqs(cfg, lens, max_new=max_new))
    assert all(len(t) == max_new for t in got.values())
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    R, nb = cfg.num_heads // K, max_seq // bs
    walked = grid = 0
    P = tile_blocks(bs, K, hd, nb, R * bucket)
    T = -(-nb // P)
    for S in lens:                       # prefill chunks at 0, 16, ...
        for off in range(0, S, bucket):
            walked += _tiles_meeting(0, off + bucket - 1, P * bs, T)
            grid += T
    P = tile_blocks(bs, K, hd, nb, R)
    T = -(-nb // P)
    assert T == 2
    for c in range(-(-max_new // chunk)):  # decode chunks, both slots
        for j in range(chunk):
            for S in lens:
                pos = S - 1 + c * chunk + min(j, max_new - c * chunk)
                walked += _tiles_meeting(0, pos, P * bs, T)
            grid += len(lens) * T
    st = s.stats()
    assert (st.attn_tiles_walked, st.attn_tiles_grid) == (walked, grid)
    assert 0 < st.attn_tile_share < 1
    assert "tile_share" in st.format()
    both = EngineStats.merge([st, st])
    assert (both.attn_tiles_walked, both.attn_tiles_grid) == (2 * walked,
                                                              2 * grid)
    assert both.attn_tile_share == st.attn_tile_share
