"""Chip smoke: serve minitron-4b at its published widths on one TPU.

  python chip_smoke.py [--seed N]

One process drives the real serve stack — ``SlotScheduler`` behind
``AsyncServeEngine``, fed and streamed by ``launch/serve.py``'s own
request stream and driver — with random weights made from ``--seed``:

  A  plain paged serve: 8 requests (3 shared 32-token prefixes, 1-12
     token tails, a quarter sampled), max_new 16, through the compiled
     paged-attention kernel.
  B  sketched long context: kv_sketch_window 64, max_seq 512, one
     384-token prompt and 3 short ones, so blocks fold into the FCS tail
     and decode merges the kernel's exact window with the tail.

After each serve the phase checks, teacher-forced on one cache, that the
compiled kernel's logits agree with the jnp gather path: on every slot
the largest logit difference over the largest logit stays within the
slot's bound (``BOUND_EXACT``, or ``BOUND_FOLDED`` once its FCS tail
holds folded rows), and top-1 agrees on every row whose top-2 margin
exceeds ``TOP1_MARGIN`` (a folded slot's: its bound).  The same check
must flag a planted fault, the compiled kernel fed each slot's lower
visibility bound plus one so it drops the slot's first visible key.  On
the engine's own pool, in the first and last layer, the kernel alone
must also match its op-for-op oracle (``kernels/ref.py``) within
``KERNEL_BOUND``.  Times printed here are a smoke, not a benchmark.
Any failed check raises; only a run that passes every check prints, as
its last line, ``{"ok": true, "device": {...}}``.  Needs a TPU: on any
other platform it exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "minitron-4b"
# max|d logit| / max|logit| on one slot, compiled kernel vs the jnp
# gather path.  The logits are bf16 values (spacing 2**-8 .. 2**-7 of the
# largest) and the two paths round differently inside each of the 32
# layers (block-wise online softmax in the kernel, one softmax over the
# gathered span in jnp), which on a v5e reads 1.35-1.74% (2.4-2.8
# spacings) on a slot with nothing folded and 3.73-4.19% on a slot whose
# FCS tail holds folded rows: the tail's median-of-rows estimates amplify
# what reaches them.  Keeping the jnp scores and p@v in f32 does not move
# these readings, so they are the rounding floor, not a fault.
BOUND_EXACT = 3e-2
BOUND_FOLDED = 6e-2
# top-1 must agree on rows whose top-2 margin exceeds this share of the
# largest logit; a folded slot's rows use its bound instead
TOP1_MARGIN = 1e-2
KERNEL_BOUND = 1e-3   # max|d out| / max|out|, kernel vs its oracle
TF_STEPS = 4          # teacher-forced decode steps compared per phase
PREFILL_CHUNK = 32


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def logit_readings(lk, lj, vocab: int, folded):
    """Per slot: max|lk - lj| / max|lj|, its bound, whether its top-2
    margin in ``lj`` exceeds the slot's top-1 margin, and whether ``lk``
    then picks another top-1.  lk/lj: (B, V) logits; folded: (B,) bool."""
    import numpy as np
    lk = np.asarray(lk, np.float32)[:, :vocab]
    lj = np.asarray(lj, np.float32)[:, :vocab]
    folded = np.asarray(folded, bool)
    scale = float(np.abs(lj).max())
    rel = np.abs(lk - lj).max(-1) / scale
    bound = np.where(folded, BOUND_FOLDED, BOUND_EXACT)
    margin = np.where(folded, BOUND_FOLDED, TOP1_MARGIN)
    top2 = np.partition(lj, -2, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) / scale > margin
    flips = decided & (lk.argmax(-1) != lj.argmax(-1))
    return rel, bound, decided, flips, scale


def check_logits(failures: list, phase: str, what: str, lk, lj,
                 vocab: int, folded) -> None:
    import numpy as np
    rel, bound, decided, flips, scale = logit_readings(lk, lj, vocab, folded)
    # logits are bf16 values: their resolution is the bf16 spacing at the
    # largest one, 2**-8 .. 2**-7 of it
    ulps = float(rel.max() * scale / 2.0 ** (np.floor(np.log2(scale)) - 7))
    print(f"[{phase}] {what}: max|dlogit|/max|logit| per slot "
          f"{rel.tolist()} (bounds {bound.tolist()}; largest "
          f"{ulps!r} bf16 spacings of the largest logit); top-1 flips "
          f"{int(flips.sum())} of {int(decided.sum())} decided rows")
    if (rel > bound).any() or flips.any():
        failures.append(f"{phase} {what}: deltas {rel.tolist()}, "
                        f"{int(flips.sum())} top-1 flips")


@contextlib.contextmanager
def first_key_dropped():
    """Plant a fault in the compiled kernel for code traced inside: every
    slot's lower visibility bound moves up by one, so the kernel drops
    the slot's first visible key (position 0, or a folded slot's first
    window row) — the smallest fault its masking can make."""
    from repro.kernels import ops as kops
    real = kops.paged_attention_op

    def drop_first_key(q, k_pool, v_pool, tables, start, fold_base,
                       kernels=None):
        return real(q, k_pool, v_pool, tables, start, fold_base + 1,
                    kernels=kernels)

    kops.paged_attention_op = drop_first_key
    try:
        yield
    finally:
        kops.paged_attention_op = real


def check_fault_caught(failures: list, phase: str, cfg, kern, params,
                       cache, cur, pos, lj, folded, **kw) -> None:
    """One decode step through the kernel with ``first_key_dropped``
    against the jnp logits ``lj`` of the same step: the logit check must
    flag it."""
    import jax

    from repro.models import transformer as tf
    with first_key_dropped():
        lf, _ = jax.jit(functools.partial(tf.decode_step, cfg=cfg,
                                          kernels=kern))(
            params, cache, cur, pos, **kw)
    rel, bound, decided, flips, _ = logit_readings(lf, lj, cfg.vocab_size,
                                                   folded)
    print(f"[{phase}] planted fault (first visible key dropped): "
          f"max|dlogit|/max|logit| per slot {rel.tolist()} (bounds "
          f"{bound.tolist()}); top-1 flips {int(flips.sum())} of "
          f"{int(decided.sum())} decided rows")
    if not ((rel > bound).any() or flips.any()):
        failures.append(f"{phase} planted fault not caught: deltas "
                        f"{rel.tolist()}")


def check_kernel_vs_oracle(failures: list, phase: str, cfg, kern, pool,
                           tables, start, fold_base, seed: int) -> None:
    """The kernel alone against its op-for-op jnp oracle
    (kernels/ref.py) on the engine's own pool, tables and positions, in
    the first and last layer, with seeded random queries: normalized
    outputs within KERNEL_BOUND of the oracle's largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import paged_attention_op

    B, K = tables.shape[0], cfg.num_kv_heads
    q = jax.random.normal(jax.random.PRNGKey(seed), (
        B, 1, K, cfg.num_heads // K, cfg.resolved_head_dim), jnp.bfloat16)
    run = {k: jax.jit(functools.partial(paged_attention_op, kernels=k))
           for k in (kern, None)}
    for layer in (0, cfg.num_layers - 1):
        outs = []
        for k in (kern, None):
            _, l, acc = run[k](q, pool["k"][layer], pool["v"][layer],
                               tables, start, fold_base)
            outs.append(np.asarray(acc / l[..., None], np.float32))
        rel = float(np.abs(outs[0] - outs[1]).max()
                    / np.abs(outs[1]).max())
        print(f"[{phase}] kernel vs oracle, layer {layer}: "
              f"max|do|/max|o| = {rel!r} (bound {KERNEL_BOUND})")
        if not rel <= KERNEL_BOUND:
            failures.append(f"{phase} kernel vs oracle layer {layer}: "
                            f"{rel!r}")


def serve_stream(phase: str, sched, reqs, seed: int):
    """Serve ``reqs`` through AsyncServeEngine with launch/serve.py's
    streaming driver (rate 0: all submitted at once) and check every
    request completes in full with no pool block leaked."""
    import numpy as np

    from repro.launch.serve import stream_poisson
    from repro.serve.frontend import AsyncServeEngine

    t0 = time.perf_counter()
    done, _ = asyncio.run(stream_poisson(
        AsyncServeEngine(scheduler=sched), reqs, 0.0, 0.0, 0.0,
        np.random.RandomState(seed)))
    dt = time.perf_counter() - t0
    want = {r.rid: r.max_new for r in reqs}
    check(sorted(c.rid for c in done) == sorted(want),
          f"{phase}: completions {sorted(c.rid for c in done)}")
    for c in done:
        check(c.status == "ok", f"{phase}: rid {c.rid} status {c.status}")
        check(len(c.tokens) == want[c.rid],
              f"{phase}: rid {c.rid} {len(c.tokens)} of {want[c.rid]} tokens")
    held = sched.prefix_cache.held_blocks()
    leaked = sched.num_blocks - sched.alloc.free_count - held
    check(leaked == 0, f"{phase}: {leaked} pool blocks leaked at drain")
    toks = sum(len(c.tokens) for c in done)
    return toks, dt


def check_engine(phase: str, sched) -> None:
    print(f"[{phase}] paged attention: kernels={sched.kernels!r}, "
          f"decode compilations {sched.decode_compilations}, "
          f"prefill compilations {sched.prefill_compilations}")
    check(sched.decode_compilations == 1, f"{phase}: decode compilations")
    check(sched.prefill_compilations == 1, f"{phase}: prefill compilations")


def phase_a(cfg, params, seed: int, failures: list) -> None:
    """Plain paged serve, then kernel vs jnp teacher-forced on a cache
    both paths prefill from the same prompts; numeric comparisons that
    break their bound go to ``failures``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import make_request_stream
    from repro.models import transformer as tf
    from repro.serve.scheduler import SlotScheduler

    serve = dataclasses.replace(
        cfg.serve, max_batch=4, max_seq=128, prefix_block=32,
        admit_threshold=2, paged_kernels=None)
    sched = SlotScheduler(cfg, params, serve=serve)
    kern = sched.kernels
    check(sched.use_kernels and kern == "compiled",
          f"A: paged attention resolved to {kern!r}, want the compiled "
          f"kernel")

    def stream(rid0):
        return make_request_stream(
            cfg, np.random.RandomState(seed + 1), 8, 3, 32, 12, 16,
            rid0=rid0, sampled_frac=0.25)

    reqs = stream(0)
    n_sampled = sum(r.temperature > 0 for r in reqs)
    check(n_sampled > 0, "A: the stream has no sampled request")
    toks, dt = serve_stream("A", sched, reqs, seed + 3)
    print(f"[A] cold serve (compiles included): {toks} tokens in {dt!r} s "
          f"({n_sampled} of {len(reqs)} requests sampled)")
    toks, dt = serve_stream("A", sched, stream(100), seed + 3)
    print(f"[A] warm serve, smoke not a benchmark: {toks / dt!r} tok/s "
          f"({toks} tokens in {dt!r} s)")
    check_engine("A", sched)

    # teacher-forced: prefill the first 4 prompts once per path, compare
    # the jnp decode logits on the two caches (prefill delta), then feed
    # both paths the same cache and tokens for TF_STEPS decode steps
    B, bs = serve.max_batch, sched.block_size
    nb = serve.max_seq // bs
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    prompts = [r.tokens for r in reqs[:B]]
    pre = {k: jax.jit(functools.partial(tf.prefill_chunk, cfg=cfg,
                                        kernels=k)) for k in (kern, None)}
    dec = {k: jax.jit(functools.partial(tf.decode_step, cfg=cfg,
                                        kernels=k)) for k in (kern, None)}
    caches = {}
    for k in (kern, None):
        cache = tf.init_paged_cache(cfg, B * nb, bs)
        for b, p in enumerate(prompts):
            for off in range(0, len(p), PREFILL_CHUNK):
                seg = np.zeros((1, PREFILL_CHUNK), np.int32)
                part = p[off:off + PREFILL_CHUNK]
                seg[0, :len(part)] = part
                cache = pre[k](params, cache, jnp.asarray(seg), tables[b],
                               jnp.int32(off))
        caches[k] = cache
    cur = jnp.asarray([[p[-1]] for p in prompts], jnp.int32)
    pos = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)
    exact = np.zeros(B, bool)
    lk, _ = dec[None](params, caches[kern], cur, pos, tables=tables)
    lj, cache = dec[None](params, caches[None], cur, pos, tables=tables)
    check_logits(failures, "A", "prefill (kernel vs jnp cache)", lk, lj,
                 cfg.vocab_size, exact)
    check_kernel_vs_oracle(failures, "A", cfg, kern, cache["kv"], tables,
                           pos, jnp.zeros_like(pos), seed)
    for t in range(TF_STEPS):
        lk, _ = dec[kern](params, cache, cur, pos, tables=tables)
        lj, nxt = dec[None](params, cache, cur, pos, tables=tables)
        check_logits(failures, "A", f"decode step {t}", lk, lj,
                     cfg.vocab_size, exact)
        if t == 0:
            check_fault_caught(failures, "A", cfg, kern, params, cache, cur,
                               pos, lj, exact, tables=tables)
        cache = nxt
        cur = jnp.argmax(lj[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
        pos = pos + 1


def phase_b(cfg, params, seed: int, failures: list) -> None:
    """Sketched long context: serve, then kernel + FCS tail vs the jnp
    two-span path, teacher-forced on the engine's own admitted state;
    numeric comparisons that break their bound go to ``failures``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import long_context_request, make_request_stream
    from repro.serve.scheduler import SlotScheduler

    W, long_len = 64, 384
    serve = dataclasses.replace(
        cfg.serve, max_batch=4, max_seq=512, kv_sketch_window=W,
        paged_kernels=None)
    sched = SlotScheduler(cfg, params, serve=serve)
    kern = sched.kernels
    check(kern == "compiled" and sched.sketch_on,
          f"B: sketched engine on {kern!r}, want the compiled kernel")
    shorts = make_request_stream(cfg, np.random.RandomState(seed + 4), 3,
                                 3, 32, 12, 32)
    reqs = shorts + [long_context_request(
        cfg, np.random.RandomState(seed + 5), 3, long_len, 32)]
    toks, dt = serve_stream("B", sched, reqs, seed + 6)
    # prefill folds (long_len - W) // bs blocks; any more folded inside
    # the compiled decode chunk
    prefill_rows = (long_len - W) // sched.block_size * sched.block_size
    print(f"[B] serve, smoke not a benchmark: {toks} tokens in {dt!r} s; "
          f"folded {sched.fold_rows_total} rows ({prefill_rows} in prefill)")
    check(sched.fold_rows_total > prefill_rows,
          "B: no block folded inside the compiled decode chunk")
    check_engine("B", sched)

    # teacher-forced on the engine's state right after admission: the
    # long prompt folded during prefill and keeps TF_STEPS rows of room
    # in its last block; the short ones reserve their whole context
    from repro.models import transformer as tf
    bs = sched.block_size
    tf_len = long_len - bs + (bs - TF_STEPS)
    for r in shorts:
        sched.submit(dataclasses.replace(r, rid=r.rid + 100,
                                         kv_sketch=False))
    sched.submit(long_context_request(
        cfg, np.random.RandomState(seed + 5), 103, tf_len, 32))
    check(sched.admit_pending() == 4, "B: teacher-forced admission")
    st = sched.state
    fold_base = np.asarray(st.fold_base)
    print(f"[B] teacher-forced fold_base per slot: {fold_base.tolist()}")
    check(int(fold_base.max()) > 0, "B: no slot folded")
    check_kernel_vs_oracle(failures, "B", cfg, kern, st.cache["kv"],
                           st.tables, st.pos, st.fold_base, seed)
    sk = {"fold_base": st.fold_base, "onehot": sched.tail_onehot}
    dec = {k: jax.jit(functools.partial(tf.decode_step, cfg=cfg,
                                        kernels=k)) for k in (kern, None)}
    cache, cur, pos = st.cache, st.cur, st.pos
    folded = fold_base > 0
    for t in range(TF_STEPS):
        lk, _ = dec[kern](params, cache, cur, pos, tables=st.tables,
                          sketch=sk)
        lj, nxt = dec[None](params, cache, cur, pos, tables=st.tables,
                            sketch=sk)
        check_logits(failures, "B", f"two-span decode step {t}", lk, lj,
                     cfg.vocab_size, folded)
        if t == 0:
            check_fault_caught(failures, "B", cfg, kern, params, cache, cur,
                               pos, lj, folded, tables=st.tables, sketch=sk)
        cache = nxt
        cur = jnp.argmax(lj[:, :cfg.vocab_size], -1).astype(jnp.int32)[:, None]
        pos = pos + 1
    for r in range(100, 104):
        sched.cancel(r)
    check(sched.alloc.free_count + sched.prefix_cache.held_blocks()
          == sched.num_blocks, "B: pool blocks leaked after cancel")


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request streams")
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devs)} compile_cache={cache_dir}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform!r}")

    from repro.configs.registry import get_config
    from repro.launch.serve import init_serve_params

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_serve_params(cfg, args.seed))
    print(f"[init] {ARCH} at published widths, jitted init incl. compile: "
          f"{time.perf_counter() - t0!r} s")
    # every phase runs and prints its deltas before a numeric failure
    # raises, so one run shows them all
    failures: list = []
    for name, phase in (("A", phase_a), ("B", phase_b)):
        t0 = time.perf_counter()
        phase(cfg, params, args.seed, failures)
        print(f"[{name}] phase ran in {time.perf_counter() - t0!r} s")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use={peak}")
    check(not failures, f"numeric comparisons: {failures}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
