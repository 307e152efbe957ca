"""Continuous-batching serve benchmark: per-family tok/s, prefix-cache hit
rate, paged-KV reserved-vs-used bytes, chunked-prefill hit latency, and
speculative-decode speedup over mixed-length request streams with shared
system prefixes.

Attention families run at a big ``kv_max_seq`` to measure the paged pool:
the row reports peak RESERVED KV bytes (allocated blocks), peak USED KV
bytes ((S + max_new) rows of live requests), and the dense
max_batch * max_seq equivalent — the mixed-length stream must show a
>= 4x reserved-bytes reduction over the dense cache (asserted), since
reservations scale with allocated blocks, not engine geometry.

One row per served family — transformer (dense) vs recurrent (ssm /
hybrid) — so the slot scheduler's two state layouts are measured
separately, plus a ``prefill_hit`` row timing a cached-prefix request
whose uncached suffix spans multiple prefill buckets (the chunked-prefill
path) against the equivalent cold miss, and an ``async_stream`` row
driving the async front-end open-loop (Poisson arrivals, streamed
tokens, mid-stream cancellations) to report TTFT and p50/p99
inter-token latency with a zero-leaked-blocks assert at drain.

Reports steady-state decode throughput (compile excluded via a warmup
drain) and asserts the engine's contracts: one decode compilation for the
whole stream (and one chunked-prefill compilation for attention
families), and cached KV bytes never above the configured budget.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.registry import reduced_config
from repro.launch.serve import make_request_stream
from repro.models import model as M
from repro.serve.scheduler import KV_FAMILIES, Request, SlotScheduler


def _stream(arch: str, n_requests: int, n_prefixes: int, prefix_len: int,
            max_tail: int, max_new: int, max_batch: int, max_seq: int,
            sampled_frac: float) -> None:
    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    serve = dataclasses.replace(
        cfg.serve, max_batch=max_batch, max_seq=max_seq,
        prefix_block=prefix_len, admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(0)

    # recurrent families compile prefill per distinct prompt length, so
    # the compile warmup must cover EVERY length the stream can emit —
    # otherwise fresh compilations land inside the timed region and get
    # reported as family tok/s.  Attention families compile prefill once
    # (offset-traced chunks): the stream warmup below suffices.
    if cfg.family not in KV_FAMILIES:
        rng_w = np.random.RandomState(99)
        sched.run([Request(rid=20_000 + t,
                           tokens=rng_w.randint(
                               0, cfg.vocab_size,
                               (prefix_len + t,)).astype(np.int32),
                           max_new=max_new)
                   for t in range(1, max_tail + 1)])
    # stream warmup: lets the count-min tracker see the shared prefixes
    sched.run(make_request_stream(cfg, rng, max_batch, n_prefixes,
                                  prefix_len, max_tail, max_new,
                                  rid0=10_000, sampled_frac=sampled_frac))

    reqs = make_request_stream(cfg, rng, n_requests, n_prefixes, prefix_len,
                               max_tail, max_new,
                               sampled_frac=sampled_frac)
    t0 = time.time()
    done = sched.run(reqs)
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    assert sched.decode_compilations == 1, sched.decode_compilations
    derived = (f"family={cfg.family};tok_s={toks/dt:.1f};"
               f"decode_compiles={sched.decode_compilations};"
               f"decode_steps={sched.decode_steps};"
               f"prefill_compiles={sched.prefill_compilations}")
    if cfg.family in KV_FAMILIES:
        st = sched.prefix_cache.stats
        assert sched.prefill_compilations == 1, sched.prefill_compilations
        assert st.bytes <= serve.prefix_cache_bytes, (
            st.bytes, serve.prefix_cache_bytes)
        reserved = sched.kv_peak_reserved_bytes()
        used = sched.kv_peak_used_bytes()
        dense = sched.kv_dense_equiv_bytes()
        reduction = dense / max(reserved, 1)
        # the paged-pool contract: reservations scale with allocated
        # blocks, not max_batch * max_seq
        assert reduction >= 4.0, (reserved, dense)
        derived += (f";hit_rate={st.hit_rate:.2f};cached_bytes={st.bytes};"
                    f"budget={serve.prefix_cache_bytes};"
                    f"tracker_bytes={sched.prefix_cache.tracker_bytes()};"
                    f"kv_pool_bytes={sched.kv_cache_bytes()};"
                    f"kv_peak_reserved_bytes={reserved};"
                    f"kv_peak_used_bytes={used};"
                    f"kv_dense_equiv_bytes={dense};"
                    f"kv_reduction={reduction:.1f}")
    emit(f"serve/continuous_batch/{arch}", dt / max(toks, 1), derived)


def _speculative(arch: str, n_requests: int, prompt_len: int, max_new: int,
                 max_seq: int, spec_k: int, target_layers: int,
                 draft_depth: int) -> None:
    """Speculative vs plain greedy decode on the dense family.

    Measures the MECHANICS of the speculative path at a controlled
    acceptance rate: the target is a ``target_layers``-deep reduced
    model whose layers above ``draft_depth`` have their residual outputs
    zeroed, so the truncated draft agrees with the target and acceptance
    sits near the ceiling (a trained draft's acceptance is a model
    property this random-init bench can't measure).  Reports
    accepted-tokens/s for both paths, the speedup, the acceptance rate
    and the mean accepted-run length; asserts the speculative stream is
    faster and token-for-token identical to plain greedy decode.
    """
    cfg = dataclasses.replace(reduced_config(arch),
                              num_layers=target_layers)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    params["blocks"]["attn"]["wo"] = \
        params["blocks"]["attn"]["wo"].at[draft_depth:].set(0)
    params["blocks"]["ffn"]["w_down"] = \
        params["blocks"]["ffn"]["w_down"].at[draft_depth:].set(0)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]

    def run(k):
        serve = dataclasses.replace(
            cfg.serve, max_batch=4, max_seq=max_seq, prefill_bucket=16,
            admit_threshold=1 << 30, spec_k=k, draft_depth=draft_depth)
        sched = SlotScheduler(cfg, params, serve=serve)
        # compile warmup: fill every slot once
        sched.run([Request(rid=10_000 + i, tokens=p, max_new=max_new)
                   for i, p in enumerate(prompts[:4])])
        reqs = [Request(rid=i, tokens=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.time()
        done = sched.run(reqs)
        dt = time.time() - t0
        toks = sum(len(c.tokens) for c in done)
        assert sched.decode_compilations == 1, sched.decode_compilations
        return toks / dt, sched, {c.rid: c.tokens for c in done}

    plain_tok_s, _, ref = run(0)
    spec_tok_s, sched, out = run(spec_k)
    for rid, toks in ref.items():
        np.testing.assert_array_equal(
            out[rid], toks,
            err_msg=f"speculative greedy diverged from plain (rid {rid})")
    speedup = spec_tok_s / plain_tok_s
    # the latency win the paged pool + verify step were built for: at
    # spec_k >= 4 with a healthy acceptance rate the speculative stream
    # must beat plain decode on accepted-tokens/s
    assert speedup > 1.0, (spec_tok_s, plain_tok_s)
    emit(f"serve/speculative/{arch}", 1.0 / spec_tok_s,
         f"family={cfg.family};spec_k={spec_k};draft_depth={draft_depth};"
         f"target_layers={target_layers};spec_tok_s={spec_tok_s:.1f};"
         f"plain_tok_s={plain_tok_s:.1f};spec_speedup={speedup:.2f}x;"
         f"accept_rate={sched.acceptance_rate:.2f};"
         f"mean_accepted_run={sched.mean_accepted_run:.2f}")


def _long_context(arch: str, context: int, max_new: int, max_seq: int,
                  window: int, ratio: int, num_blocks: int) -> None:
    """Sketched long-context serve: one prompt of ``context`` tokens
    decoded through a pool of ``num_blocks`` blocks — the context is
    >= 4x the pool's row capacity (asserted), which the exact paged path
    cannot serve at all.  Reports steady-state tok/s, the exact-window /
    sketched-tail / dense-equivalent byte split, and the tail span's
    cosine fidelity against a full-context oracle at the bench geometry
    (same fold + query math the engine compiles, on known random rows).
    """
    from repro.serve import kv_sketch as kvs

    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    bs = cfg.serve.kv_block_size
    serve = dataclasses.replace(
        cfg.serve, max_batch=1, max_seq=max_seq, num_kv_blocks=num_blocks,
        admit_threshold=1 << 30, kv_sketch_window=window,
        kv_sketch_ratio=ratio)
    sched = SlotScheduler(cfg, params, serve=serve)
    pool_rows = num_blocks * bs
    assert context >= 4 * pool_rows, (context, pool_rows)
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, (context,)).astype(np.int32)
    # compile warmup (prefill chunks + fold + decode chunk)
    sched.run([Request(rid=10_000, tokens=prompt, max_new=max_new)])
    t0 = time.time()
    done = sched.run([Request(rid=0, tokens=prompt, max_new=max_new)])
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    assert toks == max_new, toks
    assert sched.decode_compilations == 1, sched.decode_compilations
    tail_b = sched.kv_sketch_tail_bytes()
    reserved = sched.kv_peak_reserved_bytes()
    dense = sched.kv_dense_equiv_bytes()

    # tail fidelity at this geometry: fold known random rows, query the
    # sketch, cosine against the exact softmax over the same rows
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    R = cfg.num_heads // K
    Tf = context - window                       # the folded span
    coeffs = kvs.tail_coeffs(serve)
    C = kvs.tail_cols(max_seq, ratio)
    dom = kvs.pos_domain(max_seq, bs)
    onehot = kvs.pos_onehot(coeffs, dom, C)
    kr = jnp.asarray(rng.randn(1, Tf, K, hd).astype(np.float32))
    vr = jnp.asarray(rng.randn(1, Tf, K, hd).astype(np.float32))
    q = jnp.asarray(rng.randn(1, 1, K, R, hd).astype(np.float32))
    tail = kvs.fold_rows(kr, vr, jnp.arange(Tf, dtype=jnp.int32), coeffs, C)
    fb = jnp.asarray([Tf], jnp.int32)
    scale = 1.0 / float(np.sqrt(hd))
    _, l_t, acc_t = kvs.tail_attend(q, tail["k"], tail["v"], onehot, fb,
                                    scale)
    _, l_o, acc_o = kvs.dense_tail_stats(q, kr, vr, fb, scale)
    out_t = (acc_t / jnp.maximum(l_t, 1e-30)[..., None]).reshape(-1)
    out_o = (acc_o / jnp.maximum(l_o, 1e-30)[..., None]).reshape(-1)
    cos = float(jnp.vdot(out_t, out_o)
                / jnp.maximum(jnp.linalg.norm(out_t)
                              * jnp.linalg.norm(out_o), 1e-30))
    emit(f"serve/long_context/{arch}", dt / max(toks, 1),
         f"family={cfg.family};context={context};window={window};"
         f"ratio={ratio};pool_rows={pool_rows};tok_s={toks/dt:.1f};"
         f"kv_peak_reserved_bytes={reserved};kv_tail_bytes={tail_b};"
         f"kv_dense_equiv_bytes={dense};"
         f"kv_reduction={dense / max(reserved + tail_b, 1):.1f};"
         f"tail_cosine={cos:.3f};"
         f"decode_compiles={sched.decode_compilations}")


def _paged_kernel(arch: str, n_requests: int, prompt_len: int,
                  max_new: int, max_seq: int) -> None:
    """Flash-decode paged-attention kernel vs the jnp gather path.

    Runs the same greedy stream through two engines — one with
    ``paged_kernels=True`` (Pallas; interpret mode off-TPU) and one with
    the jnp oracle path — and times one compiled multi-query verify step
    on each implementation over an identically prefilled pool.  Reports
    decode tok/s for both, the kernel/jnp speedup, and the verify-step
    latencies.  No speedup floor is asserted: on CPU the kernel runs
    interpreted, so the ratio only becomes a win on TPU — the row exists
    to put the number on the trend line either way.
    """
    import jax.numpy as jnp

    from repro.kernels.ops import kernel_mode
    from repro.models import transformer as tf

    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]

    def run_engine(pk):
        serve = dataclasses.replace(
            cfg.serve, max_batch=2, max_seq=max_seq, decode_chunk=4,
            prefill_bucket=16, admit_threshold=1 << 30, paged_kernels=pk)
        sched = SlotScheduler(cfg, params, serve=serve)
        sched.run([Request(rid=10_000 + i, tokens=p, max_new=max_new)
                   for i, p in enumerate(prompts[:2])])   # compile warmup
        reqs = [Request(rid=i, tokens=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        t0 = time.time()
        done = sched.run(reqs)
        dt = time.time() - t0
        assert sched.decode_compilations == 1, sched.decode_compilations
        return sum(len(c.tokens) for c in done) / dt

    kernel_tok_s = run_engine(True)
    jnp_tok_s = run_engine(False)

    # one compiled verify step (spec_max + 1 = 4 rows/slot), same cache
    from benchmarks.common import timeit
    B, bs, nper = 2, 16, max_seq // 16
    tables = jnp.arange(B * nper, dtype=jnp.int32).reshape(B, nper)
    cache = tf.init_paged_cache(cfg, B * nper, bs)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 16)), jnp.int32)
    for b in range(B):
        cache = tf.prefill_chunk(params, cache, toks, tables[b],
                                 jnp.int32(0), cfg)
    vt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, 4)), jnp.int32)
    pos = jnp.full((B,), 16, jnp.int32)

    def verify_fn(pk):
        return jax.jit(lambda c, t, i: tf.verify_step(
            params, c, t, i, cfg, tables=tables,
            kernels=kernel_mode(pk))[0])

    t_k = timeit(verify_fn(True), cache, vt, pos)
    t_j = timeit(verify_fn(False), cache, vt, pos)
    emit(f"serve/paged_kernel/{arch}", 1.0 / max(kernel_tok_s, 1e-9),
         f"family={cfg.family};kernel_tok_s={kernel_tok_s:.1f};"
         f"jnp_tok_s={jnp_tok_s:.1f};"
         f"paged_kernel_speedup={kernel_tok_s / jnp_tok_s:.2f}x;"
         f"verify_us_kernel={t_k * 1e6:.1f};"
         f"verify_us_jnp={t_j * 1e6:.1f};"
         f"backend={jax.default_backend()}")


def _async_stream(arch: str, n_requests: int, n_prefixes: int,
                  prefix_len: int, max_tail: int, max_new: int,
                  max_batch: int, max_seq: int, rate: float,
                  cancel_frac: float) -> None:
    """Open-loop async serving latency: Poisson arrivals through the
    ``AsyncServeEngine`` pump, tokens streamed per decode chunk, a
    fraction of clients hanging up mid-stream.

    Reports time-to-first-token and p50/p99 inter-token latency (per
    streamed token, wall clock — tokens inside one delivered chunk are
    near-zero apart, the p99 is the chunk cadence) alongside tok/s.
    Asserts the pump's contracts: still ONE decode compilation across
    admission / cancellation / drain, at least one cancellation actually
    landed mid-flight, and ZERO leaked pool blocks at drain — every
    reserved block is accounted to the prefix cache once all slots
    retire."""
    import asyncio

    from repro.serve.frontend import AsyncServeEngine

    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    serve = dataclasses.replace(
        cfg.serve, max_batch=max_batch, max_seq=max_seq,
        prefix_block=prefix_len, admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(0)
    # compile warmup (closed batch) — the async pump runs the same chunk
    sched.run(make_request_stream(cfg, rng, max_batch, n_prefixes,
                                  prefix_len, max_tail, max_new,
                                  rid0=10_000))
    # greedy stream: cancellation must land between chunk deliveries, so
    # budgets span several decode chunks
    assert max_new > 2 * serve.decode_chunk, (max_new, serve.decode_chunk)
    reqs = make_request_stream(cfg, rng, n_requests, n_prefixes,
                               prefix_len, max_tail, max_new)
    front = AsyncServeEngine(scheduler=sched)
    rng_arr = np.random.RandomState(11)

    async def go():
        ttfts, itls, done = [], [], []

        async def consume(handle, t_submit, cancel_after):
            n, prev = 0, 0.0
            async for _tok in handle.stream():
                now = time.monotonic()
                if n == 0:
                    ttfts.append(now - t_submit)
                else:
                    itls.append(now - prev)
                prev = now
                n += 1
                if cancel_after is not None and n >= cancel_after:
                    handle.cancel()
            done.append(handle.completion)

        tasks = []
        for r in reqs:
            h = await front.submit(r.tokens, max_new=r.max_new, rid=r.rid)
            cancel_after = (max(1, r.max_new // 2)
                            if rng_arr.rand() < cancel_frac else None)
            tasks.append(asyncio.ensure_future(
                consume(h, time.monotonic(), cancel_after)))
            await asyncio.sleep(float(rng_arr.exponential(1.0 / rate)))
        await asyncio.gather(*tasks)
        await front.drain()
        return ttfts, itls, done

    t0 = time.time()
    ttfts, itls, done = asyncio.run(go())
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    st = sched.stats()
    assert st.decode_compilations == 1, st.decode_compilations
    n_cancel = sum(1 for c in done if c.status == "cancelled")
    assert n_cancel >= 1, "no cancellation landed mid-flight"
    assert toks < n_requests * max_new, "cancelled clients got full budgets"
    # zero-leak contract: with every slot retired, reserved pool blocks
    # are exactly the prefix cache's holdings (free + held == pool)
    held = sched.prefix_cache.held_blocks()
    leaked = sched.num_blocks - sched.alloc.free_count - held
    assert leaked == 0, (sched.alloc.free_count, held, sched.num_blocks)
    emit(f"serve/async_stream/{arch}", dt / max(toks, 1),
         f"family={cfg.family};arrival_rate={rate};tok_s={toks/dt:.1f};"
         f"ttft_p50_ms={np.percentile(ttfts, 50)*1e3:.1f};"
         f"ttft_p99_ms={np.percentile(ttfts, 99)*1e3:.1f};"
         f"itl_p50_ms={np.percentile(itls, 50)*1e3:.2f};"
         f"itl_p99_ms={np.percentile(itls, 99)*1e3:.1f};"
         f"cancelled={n_cancel};served={len(done)};"
         f"blocks_leaked={leaked};"
         f"decode_compiles={st.decode_compilations}")


def _obs_overhead(arch: str, n_requests: int, n_prefixes: int,
                  prefix_len: int, max_tail: int, max_new: int,
                  max_batch: int, max_seq: int,
                  metrics_jsonl=None) -> None:
    """Observability overhead: the same closed-batch greedy stream
    through a bare engine and one carrying a full ``ServeObserver``
    (tracing at sample_rate=1, metrics flushed EVERY decode round —
    the worst case; the probe needs kv sketching and is off here).

    The primary ``us_per_call`` is the tracing-OFF run, so the spread
    gate in compare.py keeps guarding baseline serve throughput; the
    tracing-on ratio is reported (and bounded) separately.  Asserts the
    two runs' tokens are bitwise identical and each compiled the decode
    chunk exactly once — observability must never touch the compiled
    path."""
    from repro.obs import ServeObserver, Tracer

    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)

    def run_once(obs):
        serve = dataclasses.replace(
            cfg.serve, max_batch=max_batch, max_seq=max_seq,
            prefix_block=prefix_len, admit_threshold=2)
        sched = SlotScheduler(cfg, params, serve=serve, obs=obs)
        rng = np.random.RandomState(0)
        # compile warmup with the observer already attached: hooks run
        # host-side only, so the compiled chunk is identical either way
        sched.run(make_request_stream(cfg, rng, max_batch, n_prefixes,
                                      prefix_len, max_tail, max_new,
                                      rid0=10_000))
        reqs = make_request_stream(cfg, rng, n_requests, n_prefixes,
                                   prefix_len, max_tail, max_new)
        t0 = time.time()
        done = sched.run(reqs)
        dt = time.time() - t0
        assert sched.decode_compilations == 1, sched.decode_compilations
        return dt, sum(len(c.tokens) for c in done), \
            {c.rid: np.asarray(c.tokens) for c in done}

    t_off, toks_off, out_off = run_once(None)
    obs = ServeObserver(tracer=Tracer(sample_rate=1.0),
                        metrics_path=metrics_jsonl,
                        metrics_interval=0.0)
    t_on, toks_on, out_on = run_once(obs)
    obs.close()
    for rid, ref in out_off.items():
        np.testing.assert_array_equal(
            out_on[rid], ref,
            err_msg=f"observer changed greedy tokens (rid {rid})")
    ratio = t_on / t_off
    # host-side hooks on a pump that blocks on a device chunk per round:
    # a 1.5x wall-clock ceiling is generous — regressions that sneak a
    # sync or per-token work into the hooks blow well past it
    assert ratio <= 1.5, (t_on, t_off)
    assert len(obs.tracer) > 0 and len(obs.windows) > 0
    emit(f"serve/obs_overhead/{arch}", t_off / max(toks_off, 1),
         f"family={cfg.family};tok_s={toks_off/t_off:.1f};"
         f"tok_s_on={toks_on/t_on:.1f};obs_overhead={ratio:.3f};"
         f"trace_events={len(obs.tracer)};windows={len(obs.windows)}")


def _hit_latency(arch: str, prefix_len: int, suffix_len: int, max_new: int,
                 max_seq: int) -> None:
    """Cached-prefix request latency (suffix chunk-prefilled, spanning
    multiple buckets) vs the equivalent cold miss."""
    cfg = reduced_config(arch)
    k_params, _ = jax.random.split(jax.random.PRNGKey(0))
    params = M.init_params(k_params, cfg)
    serve = dataclasses.replace(
        cfg.serve, max_batch=1, max_seq=max_seq, prefix_block=prefix_len,
        admit_threshold=2)
    sched = SlotScheduler(cfg, params, serve=serve)
    rng = np.random.RandomState(1)
    prefix = rng.randint(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)

    def req(rid):
        tail = rng.randint(0, cfg.vocab_size, (suffix_len,)).astype(np.int32)
        return Request(rid=rid, tokens=np.concatenate([prefix, tail]),
                       max_new=max_new)

    # warm: compile + push the shared prefix over the admission threshold
    for i in range(3):
        sched.run([req(i)])
    t0 = time.time()
    hit = sched.run([req(100)])[0]
    t_hit = time.time() - t0
    assert hit.prefix_hit, "prefix should be cached by now"
    t0 = time.time()
    cold = sched.run([Request(
        rid=101,
        tokens=rng.randint(0, cfg.vocab_size,
                           (prefix_len + suffix_len,)).astype(np.int32),
        max_new=max_new)])[0]
    t_cold = time.time() - t0
    assert not cold.prefix_hit
    n_buckets = -(-suffix_len // cfg.serve.prefill_bucket)
    emit(f"serve/prefill_hit/{arch}", t_hit,
         f"cold_miss_s={t_cold:.4f};speedup={t_cold/max(t_hit,1e-9):.2f}x;"
         f"suffix_tokens={suffix_len};suffix_buckets={n_buckets};"
         f"decode_compiles={sched.decode_compilations}")


def run(archs=("gemma-2b", "xlstm-1.3b", "zamba2-2.7b"),
        n_requests: int = 24, n_prefixes: int = 3, prefix_len: int = 32,
        max_tail: int = 12, max_new: int = 8, max_batch: int = 4,
        max_seq: int = 128, kv_max_seq: int = 512,
        sampled_frac: float = 0.25, hit_suffix: int = 48,
        spec_k: int = 4, spec_max_new: int = 48,
        metrics_jsonl=None) -> None:
    for arch in archs:
        # attention families get the big-max_seq geometry: the paged pool
        # makes sequence capacity nearly free (blocks are reserved per
        # request), while recurrent families still preallocate dense
        # per-slot state and stay at the small max_seq
        fam_seq = (kv_max_seq if reduced_config(arch).family in KV_FAMILIES
                   else max_seq)
        _stream(arch, n_requests, n_prefixes, prefix_len, max_tail,
                max_new, max_batch, fam_seq, sampled_frac)
    # open-loop async serving: Poisson arrivals, streamed tokens, mid-
    # stream hangups; TTFT + inter-token latency + zero-leak at drain
    _async_stream("gemma-2b", n_requests=12, n_prefixes=n_prefixes,
                  prefix_len=prefix_len, max_tail=max_tail, max_new=24,
                  max_batch=max_batch, max_seq=kv_max_seq, rate=50.0,
                  cancel_frac=0.5)
    # observability overhead: identical greedy stream, observer on/off;
    # tracing-off tok/s is the gated number
    _obs_overhead("gemma-2b", n_requests=min(n_requests, 12),
                  n_prefixes=n_prefixes, prefix_len=prefix_len,
                  max_tail=max_tail, max_new=max_new, max_batch=max_batch,
                  max_seq=kv_max_seq, metrics_jsonl=metrics_jsonl)
    # chunked-prefill hit latency: suffix spans multiple prefill buckets
    _hit_latency("gemma-2b", prefix_len=prefix_len, suffix_len=hit_suffix,
                 max_new=max_new, max_seq=max_seq)
    # speculative decode: dense family, acceptance-ceiling draft
    _speculative("gemma-2b", n_requests=8, prompt_len=16,
                 max_new=spec_max_new, max_seq=kv_max_seq, spec_k=spec_k,
                 target_layers=6, draft_depth=1)
    # sketched long-context: context >= 4x the pool's row capacity
    _long_context("gemma-2b", context=580, max_new=max_new, max_seq=1024,
                  window=64, ratio=8, num_blocks=9)
    # flash-decode paged-attention kernel vs the jnp gather path
    _paged_kernel("gemma-2b", n_requests=4, prompt_len=12,
                  max_new=max_new, max_seq=64)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
