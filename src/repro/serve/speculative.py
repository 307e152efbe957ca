"""Speculative decoding rounds: draft-propose -> verify-all -> commit.

This module builds the compiled chunk a speculative ``SlotScheduler``
runs instead of the plain one-token-per-step scan.  One round, per slot:

  1. the DRAFT model (``models/draft.py`` — a truncated and/or
     count-sketch-compressed copy of the served weights) runs K+1 paged
     decode micro-steps through the slot's block table against its own
     shallow pool: the first K produce greedy proposals d_1..d_K, the
     last only writes the draft KV row for d_K so the draft pool stays in
     lockstep with whatever prefix ends up committed;
  2. the TARGET scores all K+1 positions in ONE multi-query decode
     (``transformer.verify_step``) — its logits at position pos+i are
     bitwise what a plain decode step would produce after committing the
     first i+1 tokens;
  3. the longest verified prefix commits, plus the target's correction /
     bonus token: the slot emits n+1 tokens where n is the count of
     leading proposals matching the target's greedy choice (clipped to
     the slot's spec_k, its remaining token budget, and forced to 0 for
     sampled slots, which instead draw their one token with their own
     key).  Rejection is positional rollback — the slot's position
     simply doesn't advance past the accepted prefix, and the rejected
     rows above it are rewritten by the next round before any causal
     mask can expose them.

Greedy speculative output is therefore token-for-token identical to
plain greedy decode — acceptance rate changes HOW FAST tokens commit,
never WHICH tokens — and slots with spec_k == 0 ride the same
compilation as one-verified-token-per-round participants, so mixed
spec / non-spec / sampled batches keep the engine's
one-compilation-per-lifetime contract.

Pump-step boundaries: each round clamps its emission to the slot's
remaining budget (``e = min(n + 1, remaining)``), so the committed
position and token count advance in lockstep — the scheduler's HOST
mirrors stay exact without reading device state, which is what lets the
async pump (serve/frontend.py) cancel, preempt or retire a speculating
slot at any chunk boundary: rejected overhang rows sit above the
committed position and are never observable by a successor occupant
(its table row is sentineled before the blocks free).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.serve import kv_sketch as kvs


def round_accounting(spec_k: int, emitted: int):
    """Host-side accounting for ONE step-row of a speculating slot:
    given the slot's proposal budget and the tokens that committed this
    round, return ``(rounds, proposed, accepted)``.

    The round emits the accepted draft prefix PLUS the target's
    correction/bonus token (step 3 above), so ``emitted - 1`` of the
    ``spec_k`` proposals survived verification.  A slot with no
    proposal budget — or a round that emitted nothing (budget already
    spent) — contributes no accounting.  Centralised here, next to the
    round semantics it mirrors, because both the scheduler's cumulative
    counters and the observer's windowed ``spec.*`` series consume it
    and must never disagree."""
    if spec_k <= 0 or emitted <= 0:
        return 0, 0, 0
    return 1, spec_k, emitted - 1


def build_spec_chunk(cfg: ModelConfig, draft_cfg: ModelConfig,
                     decode_chunk: int, spec_max: int, sample,
                     sketch=None, kernels=None):
    """Build the speculative decode chunk: ``decode_chunk`` rounds of
    propose/verify/commit over all slots, ONE compilation for the
    engine's lifetime.  ``sample`` is the scheduler's per-slot sampler
    (greedy when temp == 0, keyed top-k otherwise).  The returned
    ``spec_chunk_fn(params, draft_params, state)`` maps a DecodeState to
    (new_state, toks, emits) with toks/emits shaped
    (decode_chunk, B, spec_max + 1) — emitted tokens are the leading
    True-masked entries of each round's row, in order.

    ``sketch`` (sketched engines only) is the static fold geometry
    ``{"onehot", "coeffs", "fold_cap"}``; the returned fn then takes a
    4th argument ``fold_len`` (B,) and, at the chunk head, folds the
    aged exact-window rows of BOTH pools into the per-slot tail tables
    (speculation only ever folds COMMITTED rows: fold_base advances
    through positions the scheduler has already verified past).  Rounds
    then run two-span attention — draft propose and target verify both
    see exact window + sketched tail.

    ``kernels`` (static, a resolved ``kops.kernel_mode``) routes draft
    micro-steps and the target verify through the flash-decode paged
    Pallas kernels (kernels/paged_attention.py); the kernel's verify rows
    are bitwise the kernel's single-token decode rows, so greedy spec
    identity holds on either implementation — but only when plain and
    speculative engines resolve the SAME choice, which the scheduler
    guarantees.
    """
    K = spec_max
    V = cfg.vocab_size

    def spec_chunk_fn(params, draft_params, state, fold_len=None):
        temp, top_k = state.temp, state.top_k
        spec_k = jnp.minimum(state.spec_k, K)
        tables = state.tables
        sk = None
        if sketch is not None:
            tail = kvs.fold_pool(state.cache["kv"], state.cache["tail"],
                                 tables, state.fold_base, fold_len,
                                 sketch["coeffs"], sketch["fold_cap"])
            dtail = kvs.fold_pool(state.cache["draft"]["kv"],
                                  state.cache["draft"]["tail"], tables,
                                  state.fold_base, fold_len,
                                  sketch["coeffs"], sketch["fold_cap"])
            fold_base = state.fold_base + fold_len
            sk = {"fold_base": fold_base, "onehot": sketch["onehot"]}

        def round_fn(carry, _):
            kv, dkv, cur, pos, remaining, keys = carry

            # -- draft: K proposals in K+1 micro-steps ----------------
            def dbody(c, i):
                dkv, tok = c
                lg, dkv = tf.decode_step(draft_params, dkv, tok,
                                         pos + i, draft_cfg,
                                         tables=tables, sketch=sk,
                                         kernels=kernels)
                nxt = jnp.argmax(lg[:, :V].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                return (dkv, nxt[:, None]), tok[:, 0]

            (dkv, _), fed = jax.lax.scan(dbody, (dkv, cur),
                                         jnp.arange(K + 1))
            vtok = jnp.swapaxes(fed, 0, 1)           # (B, K+1)

            # -- target: verify all K+1 positions at once -------------
            logits, kv = tf.verify_step(params, kv, vtok, pos, cfg,
                                        tables=tables, sketch=sk,
                                        kernels=kernels)
            lg = logits[..., :V].astype(jnp.float32)  # (B, K+1, V)
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

            # -- accept the longest verified prefix -------------------
            drafts = vtok[:, 1:]                      # (B, K): d_1..d_K
            match = (drafts == greedy[:, :-1]).astype(jnp.int32)
            eligible = ((jnp.arange(K)[None, :] < spec_k[:, None])
                        & (temp[:, None] == 0.0)).astype(jnp.int32)
            n = jnp.sum(jnp.cumprod(match * eligible, axis=1),
                        axis=1).astype(jnp.int32)     # (B,)
            # sampled slots draw their one token with their own key
            keys, tok0 = sample(keys, lg[:, 0], temp, top_k)
            out = greedy.at[:, 0].set(tok0)           # (B, K+1)
            e = jnp.minimum(n + 1, remaining)         # emitted count
            emit = jnp.arange(K + 1)[None, :] < e[:, None]
            last = jnp.take_along_axis(
                out, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
            cur = jnp.where(e > 0, last, cur[:, 0])[:, None]
            pos = pos + e
            remaining = remaining - e
            return (kv, dkv, cur, pos, remaining, keys), (out, emit)

        if sketch is not None:
            kv0 = {"kv": state.cache["kv"], "tail": tail}
            dkv0 = {"kv": state.cache["draft"]["kv"], "tail": dtail}
        else:
            kv0 = {"kv": state.cache["kv"]}
            dkv0 = state.cache["draft"]
        carry = (kv0, dkv0, state.cur, state.pos, state.remaining,
                 state.keys)
        (kv, dkv, cur, pos, remaining, keys), (toks, emits) = \
            jax.lax.scan(round_fn, carry, None, length=decode_chunk)
        if sketch is not None:
            new_cache = {"kv": kv["kv"], "tail": kv["tail"], "draft": dkv}
            new_state = state._replace(
                cache=new_cache, cur=cur, pos=pos, remaining=remaining,
                keys=keys, fold_base=fold_base)
        else:
            new_state = state._replace(
                cache={"kv": kv["kv"], "draft": dkv},
                cur=cur, pos=pos, remaining=remaining, keys=keys)
        return new_state, toks, emits    # toks/emits: (chunk, B, K+1)

    return spec_chunk_fn
