"""Serving driver: a mixed-length request stream through the
continuous-batching engine, with prefix-cache hit stats.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b \
      --requests 24 --max-new 16

The stream mimics production traffic: a handful of shared "system prompt"
prefixes with random per-request tails of mixed lengths, so the count-min
admission filter has real heavy hitters to find.  By default requests are
served closed-batch (submit all, drain); ``--arrival-rate R`` switches to
an OPEN-LOOP Poisson arrival process through the async front-end
(serve/frontend.py): requests arrive at R req/s on average, tokens
stream back per decode chunk, ``--cancel-frac`` hangs up a fraction of
clients mid-stream, and ``--deadline-s`` arms a per-request SLO (expired
requests surface partial output).  Either way the driver exits with the
engine's unified ``EngineStats`` snapshot.  Every family rides the
slot scheduler — attention families through chunked prefill + the prefix
cache, recurrent families (ssm/hybrid) through slot-inserted state.  Part
of the stream can be sampled (``--sampled-frac``) to exercise mixed
greedy/sampled decoding in the one compiled chunk, and ``--spec-k`` turns
on speculative decoding (a truncated / count-sketch-compressed draft
proposes, the target verifies in one multi-query step; acceptance rate
and mean accepted-run length are reported).  ``--kv-sketch-window N``
turns on sketched long-context KV: each slot keeps the most recent N
rows exact and folds older blocks into per-slot FCS tail tables
(``--long-context S`` appends one S-token demo prompt; the exact-window
vs sketched-tail byte split is printed).  ``--trace-out trace.json``
records the full request lifecycle + pump phases as Chrome trace-event
JSON (load in Perfetto), ``--metrics-jsonl metrics.jsonl`` streams
windowed metrics snapshots, and ``--fidelity-every N`` samples the
sketch-fidelity probe for folded slots every N decode rounds.  Runs on
the reduced config by default; pass ``--full`` for the full
architecture.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.obs import ServeObserver, Tracer
from repro.serve.frontend import AsyncServeEngine
from repro.serve.scheduler import KV_FAMILIES, Request, SlotScheduler


def make_request_stream(cfg, rng: np.random.RandomState, n_requests: int,
                        n_prefixes: int, prefix_len: int, max_tail: int,
                        max_new: int, rid0: int = 0,
                        sampled_frac: float = 0.0, temperature: float = 0.8,
                        top_k: int = 8):
    """Mixed-length prompts: each request samples one of ``n_prefixes``
    shared system prefixes and appends a random-length random tail; a
    ``sampled_frac`` fraction of requests asks for seeded top-k sampling
    instead of greedy decoding.  The canonical heavy-tailed workload
    generator — the CLI driver and benchmarks/bench_serve.py both use it."""
    prefixes = rng.randint(0, cfg.vocab_size,
                           (n_prefixes, prefix_len)).astype(np.int32)
    reqs = []
    for i in range(n_requests):
        p = prefixes[rng.randint(n_prefixes)]
        tail = rng.randint(0, cfg.vocab_size,
                           size=rng.randint(1, max_tail + 1)).astype(np.int32)
        sampled = rng.rand() < sampled_frac
        reqs.append(Request(
            rid=rid0 + i, tokens=np.concatenate([p, tail]), max_new=max_new,
            temperature=temperature if sampled else 0.0,
            top_k=top_k if sampled else 0,
            seed=int(rng.randint(1 << 30)) if sampled else None))
    return reqs


def long_context_request(cfg, rng: np.random.RandomState, rid: int,
                         prompt_len: int, max_new: int) -> Request:
    """One long random prompt: with a sketched KV window shorter than
    it, prefill folds blocks into the slot's FCS tail and decode attends
    exact window + sketched tail."""
    return Request(
        rid=rid,
        tokens=rng.randint(0, cfg.vocab_size, (prompt_len,)).astype(np.int32),
        max_new=max_new)


def init_serve_params(cfg, seed: int):
    """The serve drivers' random weights: the seed's first split key (the
    second is left for data), initialised as one compiled program so a
    full-width config fits the chip that holds its bf16 weights."""
    # independent keys: reusing the params-init key for prompt generation
    # correlates weights with data (and made every run's prompts identical
    # to its init) — split once, use each stream exactly once.
    k_params, _ = jax.random.split(jax.random.PRNGKey(seed))
    return M.init_params_jit(k_params, cfg)


async def stream_poisson(front: AsyncServeEngine, reqs, rate: float,
                         cancel_frac: float, deadline_s: float,
                         rng: np.random.RandomState,
                         priority_frac: float = 0.0):
    """Open-loop Poisson driver: submit ``reqs`` with exponential
    inter-arrival gaps (mean 1/rate s), stream every response, and hang
    up on a ``cancel_frac`` fraction of clients midway through their
    budget.  A ``priority_frac`` fraction of requests submits at
    priority 1 — under slot pressure those preempt running priority-0
    requests, so traces show preempt + re-admission continuations.
    Returns (completions, first_token_latencies) — arrival pacing is
    wall-clock real, so TTFT numbers here include genuine queueing
    delay, not just compute."""
    results = []
    ttfts = []

    async def consume(handle, t_submit, cancel_after):
        n = 0
        async for _tok in handle.stream():
            if n == 0:
                ttfts.append(time.monotonic() - t_submit)
            n += 1
            if cancel_after is not None and n >= cancel_after:
                handle.cancel()
        results.append(handle.completion)

    tasks = []
    for r in reqs:
        h = await front.submit(
            r.tokens, max_new=r.max_new, temperature=r.temperature,
            top_k=r.top_k, seed=r.seed,
            priority=(1 if rng.rand() < priority_frac else 0),
            deadline_s=(deadline_s if deadline_s > 0 else 0),
            rid=r.rid)
        cancel_after = (max(1, r.max_new // 2)
                        if rng.rand() < cancel_frac else None)
        tasks.append(asyncio.ensure_future(
            consume(h, time.monotonic(), cancel_after)))
        if rate > 0:
            await asyncio.sleep(float(rng.exponential(1.0 / rate)))
    await asyncio.gather(*tasks)
    return results, ttfts


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-2b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prefixes", type=int, default=3,
                    help="distinct shared system prefixes in the stream")
    ap.add_argument("--prefix-len", type=int, default=32)
    ap.add_argument("--max-tail", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--admit-threshold", type=int, default=2)
    ap.add_argument("--sampled-frac", type=float, default=0.25,
                    help="fraction of requests decoded with top-k sampling")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="temperature for the sampled fraction")
    ap.add_argument("--top-k", type=int, default=8,
                    help="top-k cutoff for the sampled fraction")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft tokens per verify round "
                         "(0 = plain decode; attention families only)")
    ap.add_argument("--draft-depth", type=int, default=1,
                    help="layers kept in the derived draft proposer")
    ap.add_argument("--draft-sketch-ratio", type=int, default=0,
                    help="count-sketch-compress the draft weights at this "
                         "ratio (0 = dense truncated draft)")
    ap.add_argument("--kv-sketch-window", type=int, default=0,
                    help="exact recent-window rows per slot; older blocks "
                         "fold into per-slot FCS tail tables and free "
                         "(0 = whole context exact; attention families)")
    ap.add_argument("--kv-sketch-ratio", type=int, default=8,
                    help="seq-axis compression of the tail tables "
                         "(cols ~ max_seq / ratio)")
    ap.add_argument("--long-context", type=int, default=0,
                    help="append one demo request with a prompt of this "
                         "many tokens (exercises fold-through prefill "
                         "and two-span decode; needs --kv-sketch-window)")
    ap.add_argument("--paged-kernels", choices=["auto", "on", "off"],
                    default="auto",
                    help="Pallas flash-decode paged attention on the serve "
                         "path (auto = TPU only; 'on' forces the kernels — "
                         "interpret mode on CPU, slow but exact)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="open-loop Poisson arrivals at this rate "
                         "(req/s) through the async front-end; 0 = "
                         "closed-batch (submit all, drain)")
    ap.add_argument("--cancel-frac", type=float, default=0.0,
                    help="fraction of streamed clients that hang up "
                         "halfway through their budget (open-loop only)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request SLO deadline in seconds; expired "
                         "requests surface partial output (open-loop "
                         "only; 0 = none)")
    ap.add_argument("--priority-frac", type=float, default=0.0,
                    help="fraction of open-loop requests submitted at "
                         "priority 1 (may preempt running priority-0 "
                         "requests under slot pressure)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (load in "
                         "Perfetto / chrome://tracing) of the request "
                         "lifecycle and pump phases to this path")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="fraction of requests traced (deterministic "
                         "by rid); engine-level events always record")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append windowed metrics snapshots (counter "
                         "deltas/rates, latency quantiles, gauges) as "
                         "JSON lines to this path")
    ap.add_argument("--metrics-interval", type=float, default=0.5,
                    help="seconds between metrics windows")
    ap.add_argument("--fidelity-every", type=int, default=2,
                    help="sketch-fidelity probe cadence in decode "
                         "rounds (0 = off; needs --kv-sketch-window; "
                         "runs only at chunk boundaries)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true",
                    help="run the full architecture (default: reduced)")
    args = ap.parse_args()

    cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
    params = init_serve_params(cfg, args.seed)
    serve = dataclasses.replace(
        cfg.serve, max_batch=args.max_batch, max_seq=args.max_seq,
        admit_threshold=args.admit_threshold, prefix_block=args.prefix_len,
        spec_k=args.spec_k, draft_depth=args.draft_depth,
        draft_sketch_ratio=args.draft_sketch_ratio,
        kv_sketch_window=args.kv_sketch_window,
        kv_sketch_ratio=args.kv_sketch_ratio,
        paged_kernels={"auto": None, "on": True, "off": False}[
            args.paged_kernels])
    if args.spec_k and cfg.family not in KV_FAMILIES:
        print(f"note: --spec-k needs an attention family; {cfg.family!r} "
              f"decodes plainly")
    sched = SlotScheduler(cfg, params, serve=serve)
    obs = None
    if args.trace_out or args.metrics_jsonl:
        obs = ServeObserver(
            tracer=(Tracer(sample_rate=args.trace_sample)
                    if args.trace_out else None),
            metrics_path=args.metrics_jsonl,
            metrics_interval=args.metrics_interval,
            fidelity_every=(args.fidelity_every
                            if args.kv_sketch_window > 0 else 0))
        sched.set_observer(obs)
    reqs = make_request_stream(cfg, np.random.RandomState(args.seed + 1),
                               args.requests, args.prefixes,
                               args.prefix_len, args.max_tail, args.max_new,
                               sampled_frac=args.sampled_frac,
                               temperature=args.temperature,
                               top_k=args.top_k)
    if args.long_context:
        assert args.kv_sketch_window > 0, "--long-context needs a window"
        S = min(args.long_context, args.max_seq - args.max_new)
        reqs.append(long_context_request(
            cfg, np.random.RandomState(args.seed + 2), len(reqs), S,
            args.max_new))

    t0 = time.time()
    if args.arrival_rate > 0:
        front = AsyncServeEngine(scheduler=sched)
        done, ttfts = asyncio.run(stream_poisson(
            front, reqs, args.arrival_rate, args.cancel_frac,
            args.deadline_s, np.random.RandomState(args.seed + 3),
            priority_frac=args.priority_frac))
    else:
        done = sched.run(reqs)
        ttfts = []
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    n_sampled = sum(1 for r in reqs if (r.temperature or 0) > 0)
    print(f"served {len(done)} requests ({n_sampled} sampled) / {toks} "
          f"tokens in {dt:.2f}s ({toks/dt:.1f} tok/s incl. compile)")
    if ttfts:
        print(f"open loop: arrival_rate={args.arrival_rate}/s, "
              f"ttft p50={np.percentile(ttfts, 50)*1e3:.0f}ms "
              f"p99={np.percentile(ttfts, 99)*1e3:.0f}ms "
              f"over {len(ttfts)} first tokens")
    if cfg.family in KV_FAMILIES:
        attn = (f"{sched.kernels} pallas kernel" if sched.use_kernels
                else "jnp")
        print(f"paged attention: {attn} "
              f"(--paged-kernels {args.paged_kernels})")
        if sched.sketch_on:
            print(f"kv sketch: window={serve.kv_sketch_window} rows "
                  f"(ratio={serve.kv_sketch_ratio}, "
                  f"rows={serve.kv_sketch_rows}, "
                  f"cols={sched.tail_cols}) — exact-window "
                  f"{sched.kv_sketch_exact_bytes()}B live + sketched-tail "
                  f"{sched.kv_sketch_tail_bytes()}B fixed")
    else:
        print(f"recurrent family ({cfg.family}): slot-scheduled state, "
              f"prefix cache n/a")
    # the unified observability snapshot — queue/slots, pool occupancy,
    # prefix-cache hit rate, fold counts, speculative acceptance
    print(sched.stats().format())
    print("first completions:",
          [(c.rid, c.status, c.tokens[:6].tolist()) for c in done[:2]])
    if obs is not None:
        obs.close(stats=sched.stats(), trace_path=args.trace_out)
        if args.trace_out:
            n_ev = len(obs.tracer)
            print(f"trace: {n_ev} events -> {args.trace_out} "
                  f"(open in https://ui.perfetto.dev)")
        if args.metrics_jsonl:
            print(f"metrics: {len(obs.windows)} windows -> "
                  f"{args.metrics_jsonl}")


if __name__ == "__main__":
    main()
