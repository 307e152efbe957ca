"""Continuous-batching engine: slot-cache decode correctness against
per-request full-context recompute (all families), chunked prefill,
per-request sampling, single decode compilation for mixed request
streams, and count-min gated prefix caching."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.registry import reduced_config
from repro.models import layers as ly
from repro.models import model as M
from repro.models import transformer as tf
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request, SlotScheduler
from repro.sketch import csvec


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _oracle_continuation(cfg, params, prompt: np.ndarray, n: int):
    """Teacher-forced greedy continuation via full-context recompute."""
    seq = jnp.asarray(prompt, jnp.int32)[None, :]
    out = []
    for _ in range(n):
        y, _, _ = tf.forward(params, tf.embed_inputs(
            params, {"tokens": seq}, cfg), cfg, mode="train")
        lg = ly.logits_fn(params, y[:, -1:], cfg)[:, 0, :cfg.vocab_size]
        nxt = int(jnp.argmax(lg, axis=-1)[0])
        out.append(nxt)
        seq = jnp.concatenate(
            [seq, jnp.full((1, 1), nxt, jnp.int32)], axis=1)
    return out


def test_mixed_length_stream_matches_recompute_and_compiles_once(gemma):
    """The tentpole contract: a stream of mixed-length, mixed-budget
    requests through the chunk-prefilled slot cache decodes
    token-for-token identically to per-request full-context recompute,
    while decode AND chunked prefill each compile exactly once."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=3, max_seq=96,
                                decode_chunk=4, prefill_bucket=16)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(0)
    lens = [5, 16, 9, 23, 31, 12]
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size, (n,)).astype(
                        np.int32),
                    max_new=3 + i % 3)
            for i, n in enumerate(lens)]
    done = {c.rid: c for c in sched.run(list(reqs))}
    assert len(done) == len(reqs)
    for r in reqs:
        ref = _oracle_continuation(cfg, params, r.tokens, r.max_new)
        np.testing.assert_array_equal(done[r.rid].tokens, ref,
                                      err_msg=f"rid {r.rid}")
    assert sched.decode_compilations == 1
    assert sched.prefill_compilations == 1


@pytest.mark.parametrize("arch", ["xlstm-1.3b", "zamba2-2.7b"])
def test_recurrent_slot_stream_matches_recompute(arch):
    """ssm / hybrid requests ride the slot scheduler (no synchronized
    fallback): mixed-length streams — including a 1-token prompt, which
    exercises the zero-state slot reset — match full-context recompute
    token-for-token, with one decode compilation."""
    cfg = reduced_config(arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=48,
                                decode_chunk=4)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size, (n,)).astype(
                        np.int32),
                    max_new=3)
            for i, n in enumerate([6, 11, 1, 9])]
    done = {c.rid: c for c in sched.run(list(reqs))}
    for r in reqs:
        ref = _oracle_continuation(cfg, params, r.tokens, r.max_new)
        np.testing.assert_array_equal(done[r.rid].tokens, ref,
                                      err_msg=f"{arch} rid {r.rid}")
    assert sched.decode_compilations == 1


def test_chunked_prefill_hit_matches_miss_multi_bucket(gemma):
    """A cached-prefix hit whose uncached suffix spans MULTIPLE prefill
    buckets is chunk-prefilled against the slot cache and reproduces the
    cold-miss output token-for-token; decode and prefill each stay at one
    compilation."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96,
                                prefill_bucket=8, prefix_block=16,
                                admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(1)
    prompt = np.concatenate([
        rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32),   # prefix
        rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)])  # suffix
    assert len(prompt) - 16 > serve.prefill_bucket   # suffix > 1 bucket
    outs = []
    for i in range(4):
        done = sched.run([Request(rid=i, tokens=prompt, max_new=5)])
        outs.append(done[0])
    st = sched.prefix_cache.stats
    assert st.admitted >= 1 and st.hits >= 1
    assert outs[-1].prefix_hit and not outs[0].prefix_hit
    for o in outs[1:]:
        np.testing.assert_array_equal(o.tokens, outs[0].tokens)
    np.testing.assert_array_equal(
        outs[0].tokens, _oracle_continuation(cfg, params, prompt, 5))
    assert sched.decode_compilations == 1
    assert sched.prefill_compilations == 1


def test_prefix_cache_respects_byte_budget(gemma):
    """LRU eviction keeps cache-held pool-block bytes at or under the
    configured budget no matter how many prefixes qualify for admission,
    and the refcount books balance: once every slot has retired, the only
    reserved pool blocks are the ones the prefix cache holds."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96,
                                prefill_bucket=16, prefix_block=16,
                                kv_block_size=16, admit_threshold=1,
                                prefix_cache_bytes=6 * 1024)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(2)
    for i in range(6):
        prompt = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        sched.run([Request(rid=i, tokens=prompt, max_new=2)])
    st = sched.prefix_cache.stats
    assert st.admitted >= 2
    assert st.evicted >= 1
    assert st.bytes <= serve.prefix_cache_bytes
    # byte counter == unique held blocks, and allocator agrees: with all
    # slots retired, reserved pool blocks are exactly the cache's holds
    held = sched.prefix_cache.held_blocks()
    assert st.bytes == held * sched.alloc.block_bytes
    assert sched.alloc.reserved == held
    assert sched.alloc.free_count == sched.num_blocks - held


def test_exact_length_prefill_still_hits(gemma):
    """prefill_bucket=1 (exact-length chunks, the documented moe setting)
    must not disable prefix-cache hits — chunked prefill degenerates to
    token-by-token but the hit path still works."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96,
                                prefill_bucket=1, prefix_block=8,
                                kv_block_size=8, admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab_size, (13,)).astype(np.int32)
    outs = [sched.run([Request(rid=i, tokens=prompt, max_new=4)])[0]
            for i in range(4)]
    assert sched.prefix_cache.stats.hits >= 1
    assert outs[-1].prefix_hit
    for o in outs[1:]:
        np.testing.assert_array_equal(o.tokens, outs[0].tokens)
    np.testing.assert_array_equal(
        outs[0].tokens, _oracle_continuation(cfg, params, prompt, 4))


def test_mixed_per_request_sampling_one_compilation(gemma):
    """Greedy and sampled requests share one compiled chunk: a mixed
    temperature/top-k batch compiles decode once, its greedy slots
    bitwise-match a solo all-greedy run, and a fixed per-request seed
    reproduces the sampled stream regardless of rid / slot placement."""
    cfg, params = gemma
    rng = np.random.RandomState(4)
    prompts = jnp.asarray(rng.randint(0, cfg.vocab_size, (3, 12)),
                          jnp.int32)
    eng = ServeEngine(cfg, params, max_seq=96)
    mixed = eng.generate(prompts, max_new=6,
                         temperature=[0.0, 0.8, 0.0], top_k=[0, 4, 0])
    assert eng.decode_compilations == 1
    solo = ServeEngine(cfg, params, max_seq=96).generate(
        prompts, max_new=6, temperature=0.0)
    got, ref = np.asarray(mixed.tokens), np.asarray(solo.tokens)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[2], ref[2])
    # sampled tokens stay in-vocab
    assert int(np.max(got[1])) < cfg.vocab_size
    # per-request seed → reproducible sampling, independent of rid
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96)
    prompt = np.asarray(prompts[0])
    r1 = SlotScheduler(cfg, params, serve=serve).run(
        [Request(rid=0, tokens=prompt, max_new=5, temperature=0.9,
                 seed=7)])[0]
    r2 = SlotScheduler(cfg, params, serve=serve).run(
        [Request(rid=99, tokens=prompt, max_new=5, temperature=0.9,
                 seed=7)])[0]
    np.testing.assert_array_equal(r1.tokens, r2.tokens)


def test_mixed_family_stream_single_compilation():
    """One engine per family, mixed greedy/sampled requests in the same
    stream: the decode chunk still compiles exactly once per engine for
    recurrent families too (the acceptance-criteria contract)."""
    cfg = reduced_config("xlstm-1.3b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=48,
                                decode_chunk=4)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(5)
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size, (n,)).astype(
                        np.int32),
                    max_new=3,
                    temperature=0.8 if i % 2 else 0.0,
                    top_k=4 if i % 2 else 0, seed=i)
            for i, n in enumerate([6, 9, 4, 11])]
    done = {c.rid: c for c in sched.run(list(reqs))}
    assert len(done) == len(reqs)
    assert sched.decode_compilations == 1
    # greedy requests still match the oracle in the mixed stream
    for r in reqs:
        if r.temperature == 0.0:
            ref = _oracle_continuation(cfg, params, r.tokens, r.max_new)
            np.testing.assert_array_equal(done[r.rid].tokens, ref)


def test_param_swap_invalidates_schedulers(gemma):
    """Swapping engine.params (checkpoint load) must rebuild schedulers:
    the old ones closed over stale weights and cached stale prefix KV."""
    cfg, _ = gemma
    p1 = M.init_params(jax.random.PRNGKey(10), cfg)
    p2 = M.init_params(jax.random.PRNGKey(11), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0,
                                 cfg.vocab_size)
    engine = ServeEngine(cfg, p1, max_seq=64)
    engine.generate(prompts, max_new=4)
    engine.params = p2
    swapped = engine.generate(prompts, max_new=4).tokens
    fresh = ServeEngine(cfg, p2, max_seq=64).generate(
        prompts, max_new=4).tokens
    np.testing.assert_array_equal(np.asarray(swapped), np.asarray(fresh))


def test_generate_temperature_without_key(gemma):
    """Regression: temperature > 0 with key=None used to crash in
    jax.random.split(None); per-slot keys must fall back to seeded
    derivation.  An explicit key must be honored AND deterministic:
    the same key reproduces the same sampled tokens across calls (keys
    fold in the batch row, not the ever-growing engine rid)."""
    cfg, params = gemma
    engine = ServeEngine(cfg, params, max_seq=64)
    prompts = jnp.ones((2, 8), jnp.int32)
    res = engine.generate(prompts, max_new=4, temperature=0.7)
    assert res.tokens.shape == (2, 4)
    assert int(res.tokens.max()) < cfg.vocab_size
    k = jax.random.PRNGKey(3)
    res2 = engine.generate(prompts, max_new=4, temperature=0.7, key=k)
    res3 = engine.generate(prompts, max_new=4, temperature=0.7, key=k)
    np.testing.assert_array_equal(np.asarray(res2.tokens),
                                  np.asarray(res3.tokens))


def test_recurrent_engine_no_temperature_crash():
    cfg = reduced_config("xlstm-1.3b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_seq=32)
    res = engine.generate(jnp.ones((2, 6), jnp.int32), max_new=3,
                          temperature=0.9)
    assert res.tokens.shape == (2, 3)
    assert engine.decode_compilations == 1


def test_countmin_decay_ages_counts():
    """decay() halves count-min estimates (floored to keep integer-count
    semantics) and preserves the one-sided overestimate."""
    sk = csvec.csvec_zeros(1 << 16, cols=64, rows=4, signed=False)
    idx = np.arange(10, dtype=np.int32)
    for _ in range(4):
        sk = csvec.accumulate_coords(sk, idx, np.ones(10, np.float32))
    before = np.asarray(csvec.query(sk, idx))
    assert (before >= 4).all()          # overestimate: never undercounts
    aged = csvec.decay(sk, 0.5)
    after = np.asarray(csvec.query(aged, idx))
    assert (after <= before // 2 + 1).all() and (after >= 2).all()
    # a once-seen coordinate decays to exactly zero, not dust
    one = csvec.accumulate_coords(
        csvec.csvec_zeros(1 << 16, cols=64, rows=4, signed=False),
        np.array([7], np.int32), np.ones(1, np.float32))
    for _ in range(2):
        one = csvec.decay(one, 0.5)
    assert float(csvec.query(one, np.array([7], np.int32))[0]) == 0.0


@pytest.mark.parametrize("arch", ["gemma-2b", "xlstm-1.3b"])
def test_serve_state_pspecs(arch):
    """Slot-state decode specs: the paged KV pool's block axis takes the
    split-KV role over model (blocks are interchangeable) with block
    tables replicated; recurrent leaves per cache_pspecs; per-slot
    bookkeeping and sampling state on the batch axis."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.shardings import serve_state_pspecs
    from repro.models.sharding import decode_rules

    cfg = reduced_config(arch)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=64)
    sched = SlotScheduler(cfg, params, serve=serve)
    rules = decode_rules(multi_pod=False, long_context=False)
    specs = serve_state_pspecs(cfg, sched.state, rules)
    b = rules["batch"]
    if arch == "gemma-2b":
        # pool (L, NB, bs, K, hd): block axis split-KV over model
        assert specs.cache["kv"]["k"] == P(None, "model", None, None, None)
        assert specs.tables == P(None, None)
    else:
        # compare specs, not raw entries: PartitionSpec normalises a
        # one-axis tuple entry to the bare axis name
        assert P(specs.cache["mlstm"]["C"][1]) == P(b)
        assert specs.tables == P(b, None)
    assert specs.pos == P(b)
    assert specs.temp == P(b)
    assert specs.top_k == P(b)
    assert specs.keys == P(b, None)


def test_paged_pool_reserves_blocks_not_max_seq(gemma):
    """The paged-KV contract: a request reserves ceil((S + max_new) /
    kv_block_size) pool blocks, not max_seq dense rows — short requests
    in a big-max_seq engine cut reserved KV bytes by >= 4x — and every
    block returns to the free list once its slot retires."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=4, max_seq=256,
                                prefill_bucket=16, kv_block_size=16,
                                admit_threshold=100)   # no admission noise
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(6)
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size,
                                       (12 + i,)).astype(np.int32),
                    max_new=4)
            for i in range(8)]
    done = sched.run(list(reqs))
    assert len(done) == 8
    assert sched.kv_peak_reserved_bytes() * 4 <= sched.kv_dense_equiv_bytes()
    assert sched.alloc.reserved == 0          # all blocks back on the list
    assert sched.alloc.free_count == sched.num_blocks
    assert sched.decode_compilations == 1


def test_pool_pressure_defers_admission(gemma):
    """A pool smaller than max_batch's worth of requests must defer
    admissions until retirements free blocks — never corrupt KV or drop
    requests: everything completes and matches the oracle."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=4, max_seq=64,
                                prefill_bucket=16, kv_block_size=16,
                                num_kv_blocks=4, admit_threshold=100)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(7)
    # each request needs 2 of the 4 pool blocks: at most 2 in flight
    reqs = [Request(rid=i,
                    tokens=rng.randint(0, cfg.vocab_size,
                                       (20,)).astype(np.int32),
                    max_new=4)
            for i in range(4)]
    done = {c.rid: c for c in sched.run(list(reqs))}
    assert len(done) == 4
    for r in reqs:
        ref = _oracle_continuation(cfg, params, r.tokens, r.max_new)
        np.testing.assert_array_equal(done[r.rid].tokens, ref,
                                      err_msg=f"rid {r.rid}")
    assert sched.alloc.peak_reserved <= 4
    assert sched.alloc.reserved == 0
    assert sched.decode_compilations == 1
    # a request the pool can NEVER serve is rejected at submit time —
    # not left to head-of-line-block the queue and crash mid-stream
    small = dataclasses.replace(serve, num_kv_blocks=2)
    s2 = SlotScheduler(cfg, params, serve=small)
    with pytest.raises(AssertionError, match="KV blocks"):
        s2.submit(Request(rid=99,
                          tokens=rng.randint(0, cfg.vocab_size,
                                             (36,)).astype(np.int32),
                          max_new=4))                # 3 blocks > pool 2


def test_deferred_admission_counts_request_once(gemma):
    """A request stuck behind pool pressure is retried every scheduler
    round, but must feed the count-min tracker (and lookup stats) exactly
    ONCE — otherwise a one-shot prompt accrues one count per retry,
    spuriously crosses admit_threshold, and a cold prefix evicts real
    heavy hitters."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=64,
                                prefill_bucket=16, prefix_block=16,
                                kv_block_size=16, num_kv_blocks=3,
                                admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(13)
    # A occupies 2 of 3 blocks for 2 decode chunks; B (2 blocks) must wait
    a = Request(rid=0, tokens=rng.randint(0, cfg.vocab_size,
                                          (20,)).astype(np.int32),
                max_new=12)
    b = Request(rid=1, tokens=rng.randint(0, cfg.vocab_size,
                                          (20,)).astype(np.int32),
                max_new=4)
    done = sched.run([a, b])
    assert len(done) == 2
    st = sched.prefix_cache.stats
    assert st.lookups == 2, "retries re-counted lookups"
    # B was observed once: its 16-token prefix has count 1 < threshold 2,
    # so nothing may have been admitted off the back of retry inflation
    assert len(sched.prefix_cache) == 0
    assert st.admitted == 0


def test_pool_pressure_never_wipes_busy_entries(gemma):
    """Pool-pressure eviction must stop at entries whose blocks live
    slots still reference: removing them frees nothing (the blocks stay
    reserved), so a transient spike must not wipe hot cached prefixes."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=64,
                                prefill_bucket=16, prefix_block=16,
                                kv_block_size=16, num_kv_blocks=4,
                                admit_threshold=1)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(14)
    pre = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    # admit the prefix (threshold 1), slot retires, cache holds 1 block
    sched.run([Request(rid=0, tokens=pre, max_new=4)])
    assert len(sched.prefix_cache) == 1
    # B shares the cached block and holds the pool for 2 decode chunks;
    # C (2 blocks, 1 free) must defer — and must NOT evict B's busy entry
    b = Request(rid=1,
                tokens=np.concatenate(
                    [pre, rng.randint(0, cfg.vocab_size,
                                      (16,)).astype(np.int32)]),
                max_new=12)
    c = Request(rid=2, tokens=rng.randint(0, cfg.vocab_size,
                                          (16,)).astype(np.int32),
                max_new=4)
    done = {x.rid: x for x in sched.run([b, c])}
    assert len(done) == 2 and done[1].prefix_hit
    assert tuple(int(t) for t in pre) in sched.prefix_cache._entries, (
        "pool pressure wiped a busy (still-referenced) cache entry")
    for r in (b, c):
        np.testing.assert_array_equal(
            done[r.rid].tokens,
            _oracle_continuation(cfg, params, r.tokens, r.max_new))


def test_prefix_hit_is_zero_copy(gemma):
    """A prefix-cache hit installs the cached entry's PHYSICAL block ids
    into the slot's table (no KV rows move) and bumps their refcount;
    slot retirement releases the reference, the cache keeps its own."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96,
                                prefill_bucket=16, prefix_block=16,
                                kv_block_size=16, admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
    for i in range(2):
        sched.run([Request(rid=i, tokens=prompt, max_new=4)])
    # threshold 2: the LONGEST qualifying prefix (the full 32-token
    # prompt, 2 blocks) is admitted on the second observation
    key = tuple(int(t) for t in prompt)
    ids = sched.prefix_cache._entries[key].block_ids
    assert len(ids) == 2
    assert int(sched.alloc.rc[ids[0]]) == 1           # cache hold only
    # third request hits; keep it in flight to observe the shared ref
    sched.submit(Request(rid=2, tokens=prompt, max_new=12))
    done = sched.step()                               # decode_chunk=8 < 12
    assert not done and sched._slot_hit[0]
    assert sched._slot_blocks[0][0] == ids[0]         # shared by reference
    assert int(sched.alloc.rc[ids[0]]) == 2           # cache + slot
    out = sched.run()
    assert len(out) == 1 and out[0].prefix_hit
    assert int(sched.alloc.rc[ids[0]]) == 1           # slot ref released


def test_hit_extends_cached_prefix(gemma):
    """Regression (hot prompt starved of its long prefix): hits feed the
    admission path too, so a prompt that keeps hitting a short cached
    prefix eventually gets its LONGEST block-multiple prefix admitted and
    served — with outputs bitwise-stable throughout."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=96,
                                prefill_bucket=16, prefix_block=16,
                                kv_block_size=16, admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(9)
    pre = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    # two different-tailed prompts get the SHORT 16-token prefix admitted
    for i in range(2):
        tail = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
        sched.run([Request(rid=i, tokens=np.concatenate([pre, tail]),
                           max_new=3)])
    assert tuple(int(t) for t in pre) in sched.prefix_cache._entries
    # now a hot prompt whose longest prefix (its full 48 tokens) only
    # accrues count-min frequency through HITS on the short prefix
    prompt = np.concatenate([pre, rng.randint(0, cfg.vocab_size,
                                              (32,)).astype(np.int32)])
    outs = [sched.run([Request(rid=10 + i, tokens=prompt, max_new=4)])[0]
            for i in range(4)]
    assert outs[1].prefix_hit
    long_key = tuple(int(t) for t in prompt)          # 48 = 3 blocks
    assert long_key in sched.prefix_cache._entries, (
        "hit path never extended the cached prefix")
    # the last run serves the full-prompt prefix (plen == S: decode
    # resumes inside a shared block — idempotent rewrite) bitwise-equal
    for o in outs[1:]:
        np.testing.assert_array_equal(o.tokens, outs[0].tokens)
    np.testing.assert_array_equal(
        outs[0].tokens, _oracle_continuation(cfg, params, prompt, 4))


def test_chunk_prefill_hit_matches_miss_nondividing_max_seq(gemma):
    """Regression for the tail clamp: with prefill_bucket not dividing
    max_seq, chunk starts must stay absolute bucket multiples on both the
    cold-miss and the cached-prefix-hit paths (the old clamp shifted the
    tail chunk to max_seq - bucket), keeping hit == miss bitwise."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=88,
                                prefill_bucket=32, prefix_block=16,
                                kv_block_size=16, admit_threshold=2)
    assert serve.max_seq % serve.prefill_bucket != 0
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(11)
    # prompt reaches into the non-dividing tail: S=80 > max_seq - bucket
    prompt = rng.randint(0, cfg.vocab_size, (80,)).astype(np.int32)
    outs = [sched.run([Request(rid=i, tokens=prompt, max_new=6)])[0]
            for i in range(4)]
    assert outs[-1].prefix_hit and not outs[0].prefix_hit
    for o in outs[1:]:
        np.testing.assert_array_equal(o.tokens, outs[0].tokens)
    np.testing.assert_array_equal(
        outs[0].tokens, _oracle_continuation(cfg, params, prompt, 6))
    assert sched.decode_compilations == 1
    assert sched.prefill_compilations == 1


def test_prefix_cache_lru_refresh_and_rejected_stats():
    """Satellite regressions on SketchPrefixCache bookkeeping: (a)
    re-admitting a present key refreshes LRU recency (the old early
    return left eviction order stale); (b) observe() counts a prompt
    whose longest qualifying prefix is already cached in stats.rejected
    instead of silently returning None."""
    import dataclasses as dc

    from repro.configs.base import ServeConfig
    from repro.serve.prefix_cache import SketchPrefixCache
    from repro.serve.scheduler import BlockAllocator

    sv = dc.replace(ServeConfig(), prefix_block=4, admit_threshold=1,
                    prefix_cache_bytes=128)            # 2 x 64-byte blocks
    alloc = BlockAllocator(num_blocks=8, block_bytes=64)
    cache = SketchPrefixCache(sv, allocator=alloc, block_size=4)
    a = np.arange(0, 4, dtype=np.int32)
    b = np.arange(4, 8, dtype=np.int32)
    c = np.arange(8, 12, dtype=np.int32)
    ids = {}
    for name, toks in (("a", a), ("b", b)):
        blk = alloc.alloc(1)
        cache.admit(toks, 4, tuple(blk))
        alloc.unref(blk)                               # "slot" retires
        ids[name] = blk[0]
    cache.admit(a, 4, (ids["a"],))                     # refresh, not no-op
    blk = alloc.alloc(1)
    cache.admit(c, 4, tuple(blk))                      # over budget: evict
    alloc.unref(blk)
    assert cache.stats.evicted == 1
    assert cache.lookup(a) is not None, "refreshed entry was evicted"
    assert cache.lookup(b) is None, "stale-LRU entry survived"
    # rc books: evicted b's block went back to the free list
    assert int(alloc.rc[ids["b"]]) == 0
    # (b): longest qualifying prefix cached -> rejected must count it
    rej0 = cache.stats.rejected
    assert cache.observe(a) is None
    assert cache.stats.rejected == rej0 + 1


def test_reseed_only_affects_unadmitted(gemma):
    """SlotScheduler.reseed(): in-flight slots keep the sampling keys
    they were admitted with (per-slot keys are engine state resolved at
    admission); requests admitted AFTER a reseed derive from the new base
    key, reproducibly across schedulers."""
    cfg, params = gemma
    serve = dataclasses.replace(cfg.serve, max_batch=2, max_seq=64,
                                decode_chunk=2)
    rng = np.random.RandomState(12)
    prompt = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)

    def mk(rid):
        return Request(rid=rid, tokens=prompt.copy(), max_new=6,
                       temperature=0.9)                # base-key derived

    ctrl = SlotScheduler(cfg, params, serve=serve).run([mk(0)])[0]
    s2 = SlotScheduler(cfg, params, serve=serve)
    s2.submit(mk(0))
    done = s2.step()                                   # in flight (2 of 6)
    assert not done
    s2.reseed(jax.random.PRNGKey(999))                 # mid-flight reseed
    out = s2.run()[0]
    np.testing.assert_array_equal(out.tokens, ctrl.tokens)
    # post-reseed requests are reproducible: same reseed key + same rid
    # on a fresh scheduler gives the same sampled stream
    s3 = SlotScheduler(cfg, params, serve=serve)
    s3.reseed(jax.random.PRNGKey(999))
    r2 = s2.run([mk(7)])[0]
    r3 = s3.run([mk(7)])[0]
    np.testing.assert_array_equal(r2.tokens, r3.tokens)


def test_rtpm_nan_safe_selection():
    """A NaN/inf candidate can no longer hijack best-of-inits selection."""
    from repro.cpd.rtpm import _nan_safe_argmax
    vals = jnp.array([1.0, jnp.nan, 3.0, jnp.inf, 2.0])
    assert int(_nan_safe_argmax(vals)) == 2
    assert int(_nan_safe_argmax(jnp.array([jnp.nan, jnp.nan]))) == 0


def _allocator_program(num_blocks: int, seed: int, steps: int) -> None:
    """Drive one random alloc/ref/unref/fork/cancel program against a
    BlockAllocator and assert its books after every operation:

      * conservation: reserved + free == num_blocks, always
      * no leaks: every block with refcount > 0 is reserved (off the
        free list), every refcount-0 block is ON the free list
      * no double-frees: the free list never holds duplicates
      * fork: the forked-from block keeps its other holders, the fork
        target is exclusively held
      * cancel: a mid-flight cancellation releases a "request's" whole
        block group in one bulk unref (the async front-end's cancel /
        expire / preempt path) — the books must balance immediately,
        with every other group's references untouched

    Every reference is tagged with the group ("request") that created
    it, so a cancel is a realistic storm primitive: groups die in random
    order, interleaved with allocs, shares and forks from survivors.
    """
    from repro.serve.scheduler import BlockAllocator

    rng = np.random.RandomState(seed)
    alloc = BlockAllocator(num_blocks, block_bytes=64)
    held: list = []            # (block, gid): one entry per reference
    next_gid = 0

    def check():
        assert alloc.reserved + alloc.free_count == alloc.num_blocks
        free = alloc._free
        assert len(set(free)) == len(free), "double-freed block"
        for b in range(alloc.num_blocks):
            rc = int(alloc.rc[b])
            assert rc >= 0
            assert (rc == 0) == (b in free), (b, rc)
        assert sorted(b for b, _ in held) == sorted(
            b for b in range(alloc.num_blocks)
            for _ in range(int(alloc.rc[b]))), "leaked or lost reference"

    for _ in range(steps):
        op = rng.randint(5)
        if op == 0:                                    # alloc (new group)
            n = int(rng.randint(1, 4))
            ids = alloc.alloc(n)
            if ids is None:
                assert n > alloc.free_count
            else:
                held.extend((b, next_gid) for b in ids)
                next_gid += 1
        elif op == 1 and held:                         # ref (share)
            b, g = held[rng.randint(len(held))]
            alloc.ref([b])
            held.append((b, g))
        elif op == 2 and held:                         # unref
            b, _ = held.pop(rng.randint(len(held)))
            alloc.unref([b])
        elif op == 3 and held:                         # fork (CoW)
            i = rng.randint(len(held))
            b, g = held[i]
            rc_before = int(alloc.rc[b])
            nb = alloc.fork(b)
            if nb is None:
                assert alloc.free_count == 0 and rc_before > 1
            else:
                held[i] = (nb, g)
                assert int(alloc.rc[nb]) >= 1
                if nb != b:
                    assert rc_before > 1
                    assert int(alloc.rc[b]) == rc_before - 1
                    assert int(alloc.rc[nb]) == 1
                else:
                    assert rc_before == 1
        elif op == 4 and held:                         # cancel one group
            gids = {g for _, g in held}
            victim = sorted(gids)[rng.randint(len(gids))]
            freed = [b for b, g in held if g == victim]
            held = [(b, g) for b, g in held if g != victim]
            alloc.unref(freed)                         # bulk, mid-flight
        check()
    # teardown as a full cancel storm: every surviving group goes down
    # in one bulk release each, in random order
    while held:
        gids = sorted({g for _, g in held})
        victim = gids[rng.randint(len(gids))]
        freed = [b for b, g in held if g == victim]
        held = [(b, g) for b, g in held if g != victim]
        alloc.unref(freed)
        check()
    assert alloc.reserved == 0 and alloc.free_count == num_blocks


@settings(max_examples=25, deadline=None)
@given(num_blocks=st.integers(1, 12), seed=st.integers(0, 1 << 16),
       steps=st.integers(1, 120))
def test_block_allocator_fuzz(num_blocks, seed, steps):
    _allocator_program(num_blocks, seed, steps)
