"""Continuous-batching slot scheduler over preallocated per-slot state.

The engine owns ``max_batch`` slots.  For attention families the KV cache
is PAGED: one shared (L, num_kv_blocks, kv_block_size, K, hd) block pool
plus a per-slot (blocks_per_slot,) int32 block table — a request reserves
ceil((S + max_new) / kv_block_size) pool blocks from a host-side free-list
allocator instead of max_seq dense rows, so long-tail requests stop
reserving sequence capacity they never touch.  For recurrent families
(ssm / hybrid) the slot state is the family's per-layer recurrent state
stacked on the slot axis ((L, max_batch, ...) leaves, plus the hybrid
shared-KV rows), exactly as before.  Decode runs as ONE jitted function
for the engine's lifetime: a ``jax.lax.scan`` of single-token steps over
fixed shapes, with per-slot position / active masks, per-slot sampling
parameters, and (for attention) per-slot block tables doing the work that
used to require per-request shapes.  Requests of arbitrary (mixed) prompt
lengths, families and sampling settings are admitted into free slots
between chunks and retired when their token budget is spent; the decode
step therefore compiles exactly once per engine (see
``decode_compilations``).

Prefill:

  * attention families (dense / moe / audio / vlm) use CHUNKED prefill:
    the prompt is fed through ``tf.prefill_chunk`` in ``prefill_bucket``-
    sized chunks scattered through the slot's block table into the pool,
    each chunk attending against everything below it.  Chunk starts are
    absolute multiples of the bucket — never clamped — so a prefix-cache
    hit resuming at ``plen`` replays exactly the chunk boundaries a cold
    miss used (the overlap recompute is idempotent) and the two paths
    produce bitwise-identical cache rows and tokens; rows a tail chunk
    would write past the request's reserved blocks map to the invalid
    table sentinel and are dropped by the scatter.  Table and offset are
    traced, so prefill compiles exactly once too, for any prompt length.
  * recurrent families prefill the first S-1 prompt tokens exactly (no
    padding — trailing pad tokens would corrupt a recurrence) and insert
    the resulting state wholesale into the slot (the slot "reset"); the
    last prompt token is fed through the first decode step, which advances
    the state and samples the first output in-graph.  Prefill compiles per
    distinct prompt length, as the synchronized fallback always did.

Slot-uniform decode semantics (all shape-static):

  * every slot decodes every step; inactive slots mutate nothing: a
    retired slot's block-table row is reset to the invalid sentinel, so
    its idle KV write is dropped by the scatter — pool blocks are safe to
    free and reuse the moment their refcount hits zero.  Recurrent slot
    state is replaced wholesale at the next admit, so junk there is never
    observed.
  * a freshly admitted attention-family request resumes at
    ``pos = S - 1`` by re-feeding its last prompt token: the recomputed KV
    row is bit-identical (it depends only on that token's residual stream)
    and the resulting logits sample the first output token in-graph —
    prefill logits never cross the host boundary.  When the whole prompt
    was a cached prefix the rewrite lands in a SHARED block; it is
    idempotent, so concurrent readers of that block see unchanged bits.
  * sampling is per-slot: temperature / top-k / PRNG key live in (B,)
    engine state set at admission, so greedy and sampled requests (and
    different seeds) share the one compiled chunk.  A greedy slot's tokens
    are bitwise-independent of its neighbours.

Prefix reuse (attention families only — a recurrent state at a prefix
boundary is not recoverable from an end-of-prompt prefill) is gated by the
count-min admission filter in serve/prefix_cache.py and is ZERO-COPY: a
hit writes the cached entry's physical block ids into the new slot's
table and bumps their refcounts; no KV rows move.  Admission donates the
admitting slot's own prefill blocks to the cache the same way.

Copy-on-write: in a SPECULATIVE engine a slot never decodes into a block
whose refcount is above one.  After hit installation and admission
donation, any shared block the slot's decode region [S - 1, ...) reaches
is forked — a fresh pool block is allocated, the rows are copied
device-side (target and draft pools alike), the table entry is rebound
and the shared block loses one reference (``_ensure_exclusive``).  Plain
engines skip the fork: their only shared-block write is the idempotent
last-prompt-token rewrite.  Speculative verify writes draft proposals
that may be REJECTED, so there the fork rule is what makes "a cached
prefix entry's blocks are immutable while cached" hold.

Speculative decoding (``serve.spec_k > 0`` / per-request
``Request.spec_k``, attention families): a derived draft model
(``models/draft.py`` — truncated and/or count-sketch-compressed) runs a
K-token greedy proposal loop per slot inside the SAME compiled chunk,
writing its own shallow paged pool through the slot's block table, and
the target verifies all K+1 positions in one multi-query decode
(``tf.verify_step``).  The accepted prefix commits (per-slot position
advance), rejection rolls the slot back simply by not advancing —
rejected rows sit above the slot's position and are overwritten by the
next round before any query can attend them.  Greedy speculative output
is token-for-token identical to plain greedy decode; sampled slots fall
back to one verified token per round drawn with their own key.  A spec
engine reserves ``spec_k`` extra rows per slot so overhang writes stay
inside the slot's own blocks.

The host loop is decomposed into PUMP PHASES — ``admit_pending`` /
``dispatch`` / ``collect``, plus ``cancel`` / ``preempt`` /
``expire_deadlines`` at pump boundaries.  ``step()`` composes them
synchronously (the classic closed-batch round); serve/frontend.py's
``AsyncServeEngine`` drives them as an always-on pump instead:
``dispatch`` launches the compiled chunk asynchronously (jax returns
futures), host-side admission and chunked prefill run while the device
crunches, and ``collect`` is the round's single host-device sync point.
Retirement and fold planning read HOST mirrors of the per-slot position
and budget — never device arrays — so the overlap is real.  SLO
scheduling rides the same machinery: per-request ``priority`` orders
the admission queue, ``deadline`` expires requests (queued or
mid-flight, surfacing partial output), and a full engine preempts a
strictly lower-priority slot by requeueing it as a continuation whose
prompt carries the tokens already served.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.kernels import ops as kops
from repro.kernels import paged_attention as kpa
from repro.models import transformer as tf
from repro.models.draft import Draft, make_draft
from repro.obs import span
from repro.serve import kv_sketch as kvs
from repro.serve.prefix_cache import SketchPrefixCache
from repro.serve.speculative import build_spec_chunk
from repro.serve.speculative import round_accounting as \
    spec_round_accounting

KV_FAMILIES = ("dense", "moe", "audio", "vlm")
RECURRENT_FAMILIES = ("ssm", "hybrid")


def _named(name: str, fn):
    """``fn`` under ``name``.  JAX names a jitted program after its
    function (``jit_<name>`` in a profiler trace); a ``functools.partial``
    has no name of its own and would show up as ``jit__unknown``."""
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return call


@dataclass
class Request:
    rid: int
    tokens: np.ndarray           # (S,) int32 prompt
    max_new: int
    # per-request sampling: None temperature falls back to the scheduler
    # default; top_k == 0 disables top-k filtering.  The slot PRNG key is
    # ``key`` when given, else PRNGKey(seed), else derived from the
    # scheduler's base key and the rid.
    temperature: Optional[float] = None
    top_k: int = 0
    seed: Optional[int] = None
    key: Optional[jax.Array] = None
    # speculative tokens per round: None -> the engine default
    # (cfg.serve.spec_k); clamped to the engine max; 0 = plain decode for
    # this request even inside a speculative engine.
    spec_k: Optional[int] = None
    # sketched long-context KV (serve/kv_sketch.py): None follows the
    # engine (on when cfg.serve.kv_sketch_window > 0); False opts this
    # request out — it reserves full exact coverage and never folds.
    kv_sketch: Optional[bool] = None
    # SLO scheduling (serve/frontend.py): higher priority admits first
    # and may preempt strictly lower-priority running slots when the
    # engine is full (cfg.serve.preemption); ``deadline`` is an absolute
    # time.monotonic() timestamp past which the request is expired —
    # dropped from the queue, or retired mid-flight with whatever tokens
    # it has (Completion.status == "expired").
    priority: int = 0
    deadline: Optional[float] = None


@dataclass
class Completion:
    rid: int
    prompt_len: int
    tokens: np.ndarray           # (<= max_new,) int32 generated
    prefix_hit: bool
    # "ok" — full budget served; "cancelled" — caller cancelled
    # mid-flight; "expired" — deadline passed (tokens hold the partial
    # output in both non-ok cases).  Preemption never surfaces here: a
    # preempted request is requeued as a continuation and completes "ok".
    status: str = "ok"


def _stat(kind: str) -> Any:
    """An ``EngineStats`` field tagged with its merge/metrics KIND:

      "counter"  monotonic event count — merges by SUM, and windowed
                 metric deltas of it sum back to the cumulative total;
      "gauge"    instantaneous level over resources the scheduler OWNS
                 (its queue, its slots, its pool blocks) — schedulers
                 in one engine own disjoint resources, so a merged
                 engine-level gauge is the sum of the per-scheduler
                 gauges (a documented disjoint-sum, not double
                 counting);
      "peak"     high-water mark — merges by MAX (summing peaks of
                 independently-peaking schedulers would report a
                 moment that never existed);
      "geometry" a configuration constant — merges by MAX so the
                 merged snapshot stays printable.

    The same tags drive ``repro.obs.MetricsRegistry.update_from_stats``,
    so merge semantics and metrics semantics can never drift apart.
    """
    return dataclasses.field(default=0, metadata={"kind": kind})


@dataclass
class EngineStats:
    """One flat observability snapshot of a scheduler (or, merged, of a
    whole engine): queue pressure, slot occupancy, pool high-water
    marks, prefix-cache effectiveness, sketch folding and speculative
    acceptance — everything launch/serve.py prints at exit and the
    async front-end exposes for monitoring.  ``merge`` combines
    snapshots across schedulers per-field by each field's tagged kind
    (counters sum, gauges disjoint-sum, peaks max — see ``_stat``);
    ratio properties recompute from the merged counts.

    ``queue_depth`` never double-counts: each scheduler owns exactly
    one admission queue, and the async front-end
    (``AsyncServeEngine``) wraps exactly ONE scheduler — its bounded
    queue IS that scheduler's queue.  ``ServeEngine`` keeps one
    scheduler per batch-size family, each with its own (disjoint)
    queue, so the merged depth is the true number of waiting requests
    across the engine."""
    queue_depth: int = _stat("gauge")
    active_slots: int = _stat("gauge")
    max_batch: int = _stat("gauge")           # total slots across parts
    completed: int = _stat("counter")   # all statuses, incl. the below
    cancelled: int = _stat("counter")
    expired: int = _stat("counter")
    preempted: int = _stat("counter")   # preemptions (requests requeued)
    decode_steps: int = _stat("counter")
    decode_compilations: int = _stat("counter")
    prefill_compilations: int = _stat("counter")
    pool_blocks: int = _stat("gauge")         # pool sizes are disjoint
    block_size: int = _stat("geometry")
    blocks_reserved: int = _stat("gauge")
    blocks_free: int = _stat("gauge")
    blocks_peak: int = _stat("peak")
    kv_reserved_bytes: int = _stat("gauge")
    kv_peak_reserved_bytes: int = _stat("peak")
    kv_peak_used_bytes: int = _stat("peak")
    kv_dense_equiv_bytes: int = _stat("gauge")
    prefix_lookups: int = _stat("counter")
    prefix_hits: int = _stat("counter")
    prefix_admitted: int = _stat("counter")
    prefix_evicted: int = _stat("counter")
    prefix_cached_bytes: int = _stat("gauge")
    fold_rows: int = _stat("counter")   # exact rows folded into tails
    kv_sketch_tail_bytes: int = _stat("gauge")
    spec_rounds: int = _stat("counter")
    spec_proposed: int = _stat("counter")
    spec_accepted: int = _stat("counter")
    # key tiles the paged-attention kernel walks, of those its grid holds,
    # per decode step and prefill chunk of the target model (one layer's
    # call: every layer walks the same tiles)
    attn_tiles_walked: int = _stat("counter")
    attn_tiles_grid: int = _stat("counter")

    @property
    def prefix_hit_rate(self) -> float:
        return self.prefix_hits / max(self.prefix_lookups, 1)

    @property
    def acceptance_rate(self) -> float:
        return self.spec_accepted / max(self.spec_proposed, 1)

    @property
    def mean_accepted_run(self) -> float:
        return ((self.spec_accepted + self.spec_rounds)
                / max(self.spec_rounds, 1))

    @property
    def attn_tile_share(self) -> float:
        """Share of the kernel's grid that the live bound lets through."""
        return self.attn_tiles_walked / max(self.attn_tiles_grid, 1)

    @staticmethod
    def field_kinds() -> Dict[str, str]:
        """field name -> kind tag ("counter" / "gauge" / "peak" /
        "geometry"); the single source of truth shared by ``merge`` and
        the metrics registry's EngineStats bridge."""
        return {f.name: f.metadata.get("kind", "counter")
                for f in dataclasses.fields(EngineStats)}

    @staticmethod
    def merge(parts: Sequence["EngineStats"]) -> "EngineStats":
        """Merge per-scheduler snapshots kind-correctly: counters and
        gauges sum (gauges measure disjoint resources — see the class
        docstring), peaks and geometry take the max (each scheduler's
        high-water mark happened at its own moment; summing them would
        fabricate a combined peak that never occurred)."""
        out = EngineStats()
        for p in parts:
            for f in dataclasses.fields(EngineStats):
                kind = f.metadata.get("kind", "counter")
                if kind in ("peak", "geometry"):
                    setattr(out, f.name,
                            max(getattr(out, f.name), getattr(p, f.name)))
                else:
                    setattr(out, f.name,
                            getattr(out, f.name) + getattr(p, f.name))
        return out

    def format(self) -> str:
        """Human-readable multi-line report (the launch driver's exit
        summary)."""
        lines = [
            f"queue={self.queue_depth} active={self.active_slots}/"
            f"{self.max_batch} completed={self.completed} "
            f"(cancelled={self.cancelled} expired={self.expired} "
            f"preemptions={self.preempted})",
            f"decode: steps={self.decode_steps} "
            f"compilations={self.decode_compilations} "
            f"(prefill: {self.prefill_compilations})",
        ]
        if self.pool_blocks:
            lines.append(
                f"paged KV: {self.pool_blocks} blocks x "
                f"{self.block_size} tokens, reserved="
                f"{self.blocks_reserved} (peak {self.blocks_peak}, "
                f"free {self.blocks_free}) — "
                f"{self.kv_peak_reserved_bytes}B peak vs dense "
                f"{self.kv_dense_equiv_bytes}B")
            lines.append(
                f"prefix cache: hit_rate={self.prefix_hit_rate:.2f} "
                f"({self.prefix_hits}/{self.prefix_lookups}), "
                f"admitted={self.prefix_admitted}, "
                f"evicted={self.prefix_evicted}, "
                f"cached_bytes={self.prefix_cached_bytes}")
        if self.fold_rows or self.kv_sketch_tail_bytes:
            lines.append(
                f"kv sketch: folded_rows={self.fold_rows}, "
                f"tail_bytes={self.kv_sketch_tail_bytes}")
        if self.spec_rounds:
            lines.append(
                f"speculative: acceptance={self.acceptance_rate:.2f} "
                f"({self.spec_accepted}/{self.spec_proposed}), "
                f"mean_run={self.mean_accepted_run:.2f} over "
                f"{self.spec_rounds} rounds")
        if self.attn_tiles_grid:
            lines.append(
                f"paged attention: tile_share={self.attn_tile_share:.3f} "
                f"({self.attn_tiles_walked}/{self.attn_tiles_grid} key "
                f"tiles walked)")
        return "\n".join(lines)


class BlockAllocator:
    """Host-side free-list allocator over the paged KV block pool.

    Every pool block has a refcount: 1 for the slot that allocated it,
    +1 per prefix-cache entry holding it, +1 per additional slot sharing
    it through a prefix hit.  A block returns to the free list exactly
    when its count reaches zero — zero-copy sharing with no
    use-after-free, no matter how admission, hits and evictions
    interleave.  ``peak_reserved`` records the high-water mark of
    allocated blocks (the paged analogue of the dense cache's
    max_batch * max_seq reservation).
    """

    def __init__(self, num_blocks: int, block_bytes: int):
        self.num_blocks = num_blocks
        self.block_bytes = block_bytes
        self._free = list(range(num_blocks - 1, -1, -1))   # pop() -> 0,1,..
        self.rc = np.zeros((num_blocks,), np.int64)
        self.peak_reserved = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def reserved(self) -> int:
        return self.num_blocks - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` blocks (refcount 1 each); None if not enough free."""
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self.rc[ids] += 1
        self.peak_reserved = max(self.peak_reserved, self.reserved)
        return ids

    def ref(self, ids: Sequence[int]) -> None:
        for b in ids:
            self.rc[b] += 1

    def unref(self, ids: Sequence[int]) -> None:
        for b in ids:
            self.rc[b] -= 1
            assert self.rc[b] >= 0, f"block {b} over-unreffed"
            if self.rc[b] == 0:
                self._free.append(b)

    def fork(self, b: int) -> Optional[int]:
        """Copy-on-write preparation for one holder of block ``b``: when
        the caller is the sole holder (refcount 1) the block is returned
        unchanged; otherwise a fresh block is taken (refcount 1), the
        caller's reference on ``b`` is dropped, and the new id returned —
        the caller then copies the rows device-side and rebinds its
        table.  None when the pool has no free block (caller defers)."""
        assert self.rc[b] >= 1, f"fork of unheld block {b}"
        if self.rc[b] == 1:
            return b
        ids = self.alloc(1)
        if ids is None:
            return None
        self.rc[b] -= 1          # rc > 1: never reaches the free list here
        return ids[0]

    def reserved_bytes(self) -> int:
        return self.reserved * self.block_bytes

    def peak_reserved_bytes(self) -> int:
        return self.peak_reserved * self.block_bytes


class DecodeState(NamedTuple):
    """All device-resident engine state (a pytree; see
    launch.shardings.serve_state_pspecs for its mesh placement)."""
    cache: Dict[str, Any]        # KV block pool / recurrent slot state
                                 # (+ "draft" sub-pool in a spec engine)
    tables: jax.Array            # (B, blocks_per_slot) int32 block tables
    cur: jax.Array               # (B, 1) next token to feed per slot
    pos: jax.Array               # (B,)  write/attend position per slot
    remaining: jax.Array         # (B,)  output tokens still owed per slot
    temp: jax.Array              # (B,)  sampling temperature per slot
    top_k: jax.Array             # (B,)  top-k cutoff per slot (0 = off)
    keys: jax.Array              # (B, 2) per-slot sampling PRNG keys
    spec_k: jax.Array            # (B,)  speculative proposals per round
    fold_base: jax.Array         # (B,)  rows folded into the slot's FCS
                                 # tail (0 = nothing folded, pure exact)


class SlotScheduler:
    def __init__(self, cfg: ModelConfig, params: Any,
                 serve: Optional[ServeConfig] = None,
                 temperature: float = 0.0,
                 draft: Optional[Draft] = None,
                 obs: Any = None):
        if cfg.family not in KV_FAMILIES + RECURRENT_FAMILIES:
            raise ValueError(f"unknown family {cfg.family!r}")
        self.cfg = cfg
        self.params = params
        self.serve = serve if serve is not None else cfg.serve
        self.temperature = float(temperature)   # default for requests
        self.is_kv = cfg.family in KV_FAMILIES
        sv = self.serve
        # paged-attention implementation, resolved ONCE by
        # kops.kernel_mode ("compiled" / "interpret" Pallas kernel, or
        # None for the jnp gather path) and baked into every compiled
        # chunk below as a static — layers never re-detect, so the
        # chunks stay one-compilation-per-engine
        self.kernels = (kops.kernel_mode(sv.paged_kernels)
                        if self.is_kv else None)
        B = sv.max_batch
        # speculative decode: an explicit draft wins; else derive one per
        # the serve knobs (None when spec_k == 0 or the family has no KV)
        self.draft = (draft if draft is not None
                      else make_draft(params, cfg, sv))
        self.spec_max = (int(sv.spec_k)
                         if self.is_kv and self.draft is not None else 0)
        if self.draft is not None and not self.is_kv:
            raise ValueError("speculative decode needs a kv-cache family")
        self.spec_rounds = 0       # verify rounds run by speculating slots
        self.spec_proposed = 0     # draft tokens proposed in those rounds
        self.spec_accepted = 0     # draft tokens verified-and-emitted
        self.attn_tiles_walked = 0  # see EngineStats.attn_tiles_*
        self.attn_tiles_grid = 0
        self._queue: List[Request] = []
        self._slot_req: List[Optional[Request]] = [None] * B
        self._slot_out: List[List[int]] = [[] for _ in range(B)]
        self._slot_hit: List[bool] = [False] * B
        self._slot_blocks: List[List[int]] = [[] for _ in range(B)]
        self._slot_spec: List[int] = [0] * B
        # rid -> pending admit_plen: set on a request's FIRST admission
        # attempt so pool-pressure retries don't re-feed the count-min
        # tracker (a queued one-shot prompt must not accrue one count per
        # scheduler round and spuriously cross admit_threshold)
        self._admit_memo: Dict[int, Optional[int]] = {}
        self._slot_rows: List[int] = [0] * B
        # host mirrors of device per-slot state, maintained at admission
        # and collect(): the pump phases (fold planning, retirement,
        # preemption) never read device arrays, so host bookkeeping for
        # the next round overlaps the in-flight chunk instead of
        # serializing on it
        self._slot_pos: List[int] = [0] * B
        self._slot_admit_seq: List[int] = [0] * B
        self._admit_seq = 0
        # rid -> (original prompt_len, tokens emitted before preemption,
        # prefix_hit so far): a preempted slot's progress, folded back
        # into its Completion when the requeued continuation retires
        self._preempted: Dict[int, Tuple[int, List[int], bool]] = {}
        # in-flight decode chunk (device futures) between dispatch() and
        # collect(); exactly one chunk may be outstanding
        self._inflight: Optional[Tuple[Any, Any]] = None
        self.cancellations = 0
        self.expirations = 0
        self.preemptions = 0
        self.fold_rows_total = 0
        # sketched long-context KV bookkeeping (host mirrors of the
        # device fold_base): first live logical block per slot, and
        # whether the slot's request opted into folding
        self._slot_first_lblk: List[int] = [0] * B
        self._slot_use_sketch: List[bool] = [False] * B
        self._used_rows = 0
        self.peak_used_rows = 0
        self.decode_steps = 0
        self.completed: List[Completion] = []
        self._base_key = jax.random.PRNGKey(sv.seed)
        # observability (repro.obs.ServeObserver or None).  Every hook
        # site guards with ``if self.obs is not None`` and passes only
        # host-side values, so obs off costs one attribute check and
        # obs on adds no device syncs; ``_round_idx`` paces the opt-in
        # sketch-fidelity probe (see ``_probe_fidelity``).
        self.obs: Any = None
        self._round_idx = 0

        if self.is_kv:
            # no max_seq clamp: a block larger than max_seq just means one
            # partially-used block per slot, while clamping could
            # manufacture a size that breaks the divisibility contract
            self.block_size = max(1, sv.kv_block_size)
            assert sv.prefix_block % self.block_size == 0, (
                f"kv_block_size {self.block_size} must divide prefix_block "
                f"{sv.prefix_block} so cached prefixes share whole blocks")
            # a spec engine's verify/draft writes overhang the committed
            # sequence by up to spec_max rows — every slot (even ones
            # decoding plainly: the verify step is batch-wide) reserves
            # them so overhang writes land in its own blocks, not drop
            self.spec_overhang = self.spec_max
            self.blocks_per_slot = -(-(sv.max_seq + self.spec_overhang)
                                     // self.block_size)
            nb = sv.num_kv_blocks or B * self.blocks_per_slot
            self.num_blocks = nb
            cache = tf.init_paged_cache(cfg, nb, self.block_size)
            if self.draft is not None:
                # the draft's shallow pool mirrors the target pool block
                # for block (same ids, same tables, same refcounts), so
                # prefix sharing, CoW forks and frees cover both for free
                cache = dict(cache)
                cache["draft"] = tf.init_paged_cache(
                    self.draft.cfg, nb, self.block_size)
            # block_bytes derive from the POOL leaves only — the FCS tail
            # tables added below are per-slot constant state, not paged
            pool_bytes = sum(int(a.size) * int(a.dtype.itemsize)
                             for a in jax.tree.leaves(cache))
            self._pool_itemsize = cache["kv"]["k"].dtype.itemsize
            self.alloc = BlockAllocator(nb, pool_bytes // nb)
            self.prefix_cache = SketchPrefixCache(
                sv, allocator=self.alloc, block_size=self.block_size)
            tables0 = jnp.full((B, self.blocks_per_slot), nb, jnp.int32)
            self.sketch_on = bool(sv.kv_sketch_window)
            if self.sketch_on:
                W = int(sv.kv_sketch_window)
                bs = self.block_size
                assert W % bs == 0 and W >= bs, (
                    f"kv_sketch_window {W} must be a positive multiple of "
                    f"kv_block_size {bs}")
                self.kv_window = W
                Z = max(1, int(sv.kv_sketch_rows))
                ratio = max(1, int(sv.kv_sketch_ratio))
                T = kvs.pos_domain(sv.max_seq, bs)
                C = kvs.tail_cols(sv.max_seq, ratio)
                self.tail_rows, self.tail_cols, self.tail_domain = Z, C, T
                self.tail_coeffs = kvs.tail_coeffs(sv)
                self.tail_onehot = kvs.pos_onehot(self.tail_coeffs, T, C)
                # max committed-position advance per decode chunk; the
                # in-chunk fold cap keeps pace with it (+1 block of slack
                # so a lagging slot catches up instead of drifting)
                self.adv_max = sv.decode_chunk * (self.spec_max + 1)
                self.fold_cap = bs * (-(-self.adv_max // bs) + 1)
                bucket = max(1, min(sv.prefill_bucket, sv.max_seq))
                self.prefill_fold_cap = bs * (bucket // bs + 1)
                cache = dict(cache)
                cache["tail"] = kvs.init_tail(cfg, B, Z, C)
                if self.draft is not None:
                    cache["draft"] = dict(cache["draft"])
                    cache["draft"]["tail"] = kvs.init_tail(
                        self.draft.cfg, B, Z, C)
        else:
            # prefix reuse / paging are KV-cache concepts; a recurrent
            # scheduler gets neither (and misuse fails loudly on None)
            self.block_size = 0
            self.blocks_per_slot = 0
            self.num_blocks = 0
            self.spec_overhang = 0
            self.alloc = None
            self.prefix_cache = None
            self.sketch_on = False    # recurrent state never pages or folds
            cache = tf.init_cache(cfg, B, sv.max_seq)
            tables0 = jnp.zeros((B, 0), jnp.int32)

        self._state = DecodeState(
            cache=cache,
            tables=tables0,
            cur=jnp.zeros((B, 1), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            remaining=jnp.zeros((B,), jnp.int32),
            temp=jnp.zeros((B,), jnp.float32),
            top_k=jnp.zeros((B,), jnp.int32),
            keys=jnp.zeros((B, 2), jnp.uint32),
            spec_k=jnp.zeros((B,), jnp.int32),
            fold_base=jnp.zeros((B,), jnp.int32),
        )
        if self.spec_max > 0:
            self._chunk_fn = jax.jit(self._make_spec_chunk(),
                                     donate_argnums=(2,))
        else:
            self._chunk_fn = jax.jit(self._make_chunk(),
                                     donate_argnums=(1,))
        if self.is_kv:
            if self.sketch_on:
                # every slot of a sketch engine prefills through the
                # sketched chunk (fold_base == 0 reproduces the exact
                # graph bitwise), so prefill still compiles exactly once
                self._prefill_chunk = self._make_sketch_prefill(cfg, False)
                if self.draft is not None:
                    self._draft_prefill_chunk = self._make_sketch_prefill(
                        self.draft.cfg, True)
                self._fold_fn = jax.jit(self._make_fold(),
                                        donate_argnums=(0,))
                self._zero_tail = jax.jit(self._make_zero_tail(),
                                          donate_argnums=(0,))
                # opt-in fidelity probe (observability): jitted once,
                # invoked only at the collect() boundary at the
                # observer's cadence — never inside the decode chunk
                self._spread_fn = jax.jit(kvs.tail_row_spread)
            else:
                self._prefill_chunk = jax.jit(
                    _named("prefill_chunk", functools.partial(
                        tf.prefill_chunk, cfg=cfg, kernels=self.kernels)),
                    donate_argnums=(1,))
                if self.draft is not None:
                    self._draft_prefill_chunk = jax.jit(
                        _named("draft_prefill_chunk", functools.partial(
                            tf.prefill_chunk, cfg=self.draft.cfg,
                            kernels=self.kernels)),
                        donate_argnums=(1,))

            # copy-on-write block fork: copy one physical block's rows
            # (target AND draft pools) to a fresh block, device-side
            def copy_block(c, src, dst):
                return jax.tree.map(
                    lambda a: a.at[:, dst].set(a[:, src]), c)

            self._copy_block = jax.jit(copy_block, donate_argnums=(0,))
        else:
            self._insert_fn = jax.jit(
                _named("insert_slot_state", self._insert_state),
                donate_argnums=(0,))
            self._prefill = jax.jit(_named(
                "recurrent_prefill", functools.partial(tf.prefill, cfg=cfg)))
            # slot "reset" block: zero state inserted before (or instead
            # of, for 1-token prompts) the prefilled state
            self._zero_block = tf.init_cache(cfg, 1, sv.max_seq)
        if obs is not None:
            self.set_observer(obs)

    def set_observer(self, obs: Any) -> None:
        """Attach (or detach, with None) a ``repro.obs.ServeObserver``:
        the scheduler and its prefix cache report into it from every
        pump phase.  Safe to call at any pump boundary."""
        self.obs = obs
        if self.prefix_cache is not None:
            self.prefix_cache.obs = obs

    # ------------------------------------------------------------------
    # Compiled pieces
    # ------------------------------------------------------------------

    @staticmethod
    def _make_sampler():
        @jax.named_scope("lm_head")
        def sample(key, lg, temp, top_k):
            """Per-slot next token: greedy when temp == 0, else top-k
            filtered temperature sampling with the slot's own key.  The
            whole filter/sort/categorical branch is skipped in-graph
            (lax.cond) when every slot is greedy, so greedy-only chunks
            pay pure argmax while mixed chunks share the compilation."""
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

            def do_sample(args):
                key, lg = args
                V = lg.shape[-1]
                srt = jnp.sort(lg, axis=-1)[:, ::-1]
                kth = jnp.take_along_axis(
                    srt, jnp.clip(top_k - 1, 0, V - 1)[:, None],
                    axis=1)[:, 0]
                keep = (top_k <= 0)[:, None] | (lg >= kth[:, None])
                filt = jnp.where(keep, lg, -jnp.inf)
                scaled = filt / jnp.maximum(temp, 1e-6)[:, None]
                split = jax.vmap(jax.random.split)(key)      # (B, 2, 2)
                key, ks = split[:, 0], split[:, 1]
                sampled = jax.vmap(jax.random.categorical)(ks, scaled)
                return key, jnp.where(temp > 0.0,
                                      sampled.astype(jnp.int32), greedy)

            def do_greedy(args):
                key, _ = args
                return key, greedy

            return jax.lax.cond(jnp.any(temp > 0.0), do_sample, do_greedy,
                                (key, lg))

        return sample

    def _make_chunk(self):
        cfg = self.cfg
        chunk = self.serve.decode_chunk
        is_kv = self.is_kv
        sample = self._make_sampler()
        sketch_on = self.sketch_on
        kernels = self.kernels
        if sketch_on:
            onehot, coeffs = self.tail_onehot, self.tail_coeffs
            fold_cap = self.fold_cap

        def chunk_fn(params, state: DecodeState):
            temp, top_k = state.temp, state.top_k
            # block tables are fixed for the chunk (admission happens
            # between chunks on the host), so they ride outside the carry
            tables = state.tables if is_kv else None

            def step(carry, _):
                cache, cur, pos, remaining, keys = carry
                running = remaining > 0
                logits, cache = tf.decode_step(params, cache, cur, pos, cfg,
                                               tables=tables,
                                               kernels=kernels)
                lg = logits[:, :cfg.vocab_size].astype(jnp.float32)
                keys, nxt = sample(keys, lg, temp, top_k)
                nxt = nxt.astype(jnp.int32)
                pos = pos + running.astype(jnp.int32)
                remaining = remaining - running.astype(jnp.int32)
                return (cache, nxt[:, None], pos, remaining, keys), \
                    (nxt, running)

            carry = (state.cache, state.cur, state.pos, state.remaining,
                     state.keys)
            (cache, cur, pos, remaining, keys), (toks, emits) = \
                jax.lax.scan(step, carry, None, length=chunk)
            new_state = DecodeState(cache=cache, tables=state.tables,
                                    cur=cur, pos=pos, remaining=remaining,
                                    temp=temp, top_k=top_k, keys=keys,
                                    spec_k=state.spec_k,
                                    fold_base=state.fold_base)
            return new_state, toks, emits        # toks/emits: (chunk, B)

        def sketched_chunk_fn(params, state: DecodeState, fold_len):
            """Sketch-engine chunk: fold aged blocks into the FCS tails
            ONCE at chunk start (fold_len (B,) rows per slot, decided by
            the host from committed positions), then run the usual scan
            with two-span decode.  Folded positions sit strictly below
            the window every in-chunk query keeps exact, so folding
            before the steps is equivalent to folding between them —
            and it keeps the fold out of the scan body."""
            temp, top_k = state.temp, state.top_k
            tables = state.tables
            cache = state.cache
            tail = kvs.fold_pool(cache["kv"], cache["tail"], tables,
                                 state.fold_base, fold_len, coeffs,
                                 fold_cap)
            cache = {**cache, "tail": tail}
            fold_base = state.fold_base + fold_len
            sk = {"fold_base": fold_base, "onehot": onehot}

            def step(carry, _):
                cache, cur, pos, remaining, keys = carry
                running = remaining > 0
                logits, cache = tf.decode_step(params, cache, cur, pos, cfg,
                                               tables=tables, sketch=sk,
                                               kernels=kernels)
                lg = logits[:, :cfg.vocab_size].astype(jnp.float32)
                keys, nxt = sample(keys, lg, temp, top_k)
                nxt = nxt.astype(jnp.int32)
                pos = pos + running.astype(jnp.int32)
                remaining = remaining - running.astype(jnp.int32)
                return (cache, nxt[:, None], pos, remaining, keys), \
                    (nxt, running)

            carry = (cache, state.cur, state.pos, state.remaining,
                     state.keys)
            (cache, cur, pos, remaining, keys), (toks, emits) = \
                jax.lax.scan(step, carry, None, length=chunk)
            new_state = DecodeState(cache=cache, tables=state.tables,
                                    cur=cur, pos=pos, remaining=remaining,
                                    temp=temp, top_k=top_k, keys=keys,
                                    spec_k=state.spec_k,
                                    fold_base=fold_base)
            return new_state, toks, emits

        return sketched_chunk_fn if sketch_on else chunk_fn

    def _make_spec_chunk(self):
        """Speculative decode chunk (serve/speculative.py): rounds of
        draft-propose -> verify-all -> accept/rollback, ONE compilation
        for the engine's lifetime; mixed spec / non-spec / sampled slots
        share it."""
        sketch = None
        if self.sketch_on:
            sketch = {"onehot": self.tail_onehot,
                      "coeffs": self.tail_coeffs,
                      "fold_cap": self.fold_cap}
        return build_spec_chunk(self.cfg, self.draft.cfg,
                                self.serve.decode_chunk, self.spec_max,
                                self._make_sampler(), sketch=sketch,
                                kernels=self.kernels)

    def _make_sketch_prefill(self, model_cfg: ModelConfig, is_draft: bool):
        """Jitted sketched prefill chunk: the legacy chunk plus the
        slot's tail slice and fold offset, so prompts longer than the
        window attend their already-folded span.  slot / fold_base are
        traced — one compilation covers every slot and fold state; with
        fold_base == 0 the produced pool rows are bitwise the legacy
        chunk's (the two-span select picks the exact output and the KV
        scatter is untouched)."""
        onehot = self.tail_onehot
        kernels = self.kernels

        def sketched_prefill_chunk(params, pool, tail_full, tok, table,
                                   start, slot, fold_base):
            tail = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                tail_full)
            sk = {"fold_base": fold_base[None], "onehot": onehot}
            nc = tf.prefill_chunk(params, {"kv": pool, "tail": tail}, tok,
                                  table, start, model_cfg, sketch=sk,
                                  kernels=kernels)
            return nc["kv"]

        name = ("draft_prefill_chunk" if is_draft
                else "sketched_prefill_chunk")
        return jax.jit(_named(name, sketched_prefill_chunk),
                       donate_argnums=(1,))

    def _make_fold(self):
        """Jitted out-of-chunk fold (prefill fold-through): fold the next
        ``fold_len`` aged rows of ONE slot — target and draft pools alike
        — into its tail tables.  Separate from the decode chunk (and
        compiled once), because prefill folds happen between prefill
        chunks, before the slot ever decodes."""
        coeffs = self.tail_coeffs
        cap = self.prefill_fold_cap

        def fold_fn(cache, row, fold_from, fold_len, slot):
            ff, fl = fold_from[None], fold_len[None]       # (1,)

            def one(pool, tail_full):
                t1 = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1,
                                                           axis=1),
                    tail_full)
                t1 = kvs.fold_pool(pool, t1, row[None], ff, fl, coeffs,
                                   cap)
                return jax.tree.map(
                    lambda full, s: jax.lax.dynamic_update_slice_in_dim(
                        full, s, slot, axis=1), tail_full, t1)

            out = {**cache, "tail": one(cache["kv"], cache["tail"])}
            if "draft" in cache:
                d = cache["draft"]
                out["draft"] = {**d, "tail": one(d["kv"], d["tail"])}
            return out

        return fold_fn

    def _make_zero_tail(self):
        """Jitted per-slot tail reset (slot admission): a new occupant
        must never attend the previous request's folded content."""
        def zero_tail(cache, slot):
            z = lambda t: jax.tree.map(
                lambda a: a.at[:, slot].set(0.0), t)
            out = {**cache, "tail": z(cache["tail"])}
            if "draft" in cache:
                out["draft"] = {**cache["draft"],
                                "tail": z(cache["draft"]["tail"])}
            return out

        return zero_tail

    @staticmethod
    def _insert_state(cache, block, slot):
        """Write a per-request recurrent prefill block (leaves (X, 1, ...))
        into slot ``slot`` of the preallocated slot state (leaves
        (X, B, ...)): equal-shape leaves are replaced wholesale — the slot
        'reset' that makes any stale state from the slot's previous
        occupant unobservable."""
        def one(c, b):
            return jax.lax.dynamic_update_slice(
                c, b.astype(c.dtype), (0, slot) + (0,) * (c.ndim - 2))
        return jax.tree.map(one, cache, block)

    # ------------------------------------------------------------------
    # Scheduling
    # ------------------------------------------------------------------

    def submit(self, req: Request) -> None:
        sv = self.serve
        S = len(req.tokens)
        assert req.max_new >= 1, "requests must ask for at least one token"
        assert S >= 1, "empty prompt"
        # the last write lands at position S - 1 + max_new
        assert S + req.max_new <= sv.max_seq, (
            f"prompt {S} + max_new {req.max_new} exceeds max_seq "
            f"{sv.max_seq}")
        if self.is_kv:
            # reject up front what the pool can never serve — otherwise
            # the impossible request head-of-line-blocks the FIFO queue
            # and only fails once every in-flight slot has drained
            bs = self.block_size
            need = -(-(S + req.max_new + self.spec_overhang) // bs)
            if self.sketch_on and req.kv_sketch is not False:
                # a sketched request never holds its whole context: its
                # peak is the exact window + one prefill bucket of write
                # frontier + one chunk of decode lookahead
                bucket = max(1, min(sv.prefill_bucket, sv.max_seq))
                peak = (self.kv_window // bs + -(-bucket // bs)
                        + -(-(self.adv_max + self.spec_overhang) // bs)
                        + 2)
                need = min(need, peak)
            assert need <= self.num_blocks, (
                f"request needs {need} KV blocks of {bs}, "
                f"pool has {self.num_blocks} (raise "
                f"cfg.serve.num_kv_blocks)")
        self._enqueue(req, front=False)
        if self.obs is not None:
            self.obs.request_queued(req.rid, S, req.priority)

    def _enqueue(self, req: Request, front: bool) -> None:
        """Priority-ordered queue insertion (descending priority, stable
        FIFO within a band — default priority 0 is a plain FIFO).
        ``front`` inserts at the HEAD of the request's priority band:
        used for preempted continuations, which are the oldest work in
        their band and must not lose their turn to later arrivals."""
        pr = req.priority
        if front:
            i = 0
            while i < len(self._queue) and self._queue[i].priority > pr:
                i += 1
        else:
            i = len(self._queue)
            while i > 0 and self._queue[i - 1].priority < pr:
                i -= 1
        self._queue.insert(i, req)

    def reseed(self, key: jax.Array) -> None:
        """Replace the base sampling key: per-slot keys for requests
        without an explicit seed derive from it (folded with the rid).
        Only NOT-YET-ADMITTED requests are affected — in-flight slots
        keep the keys they were admitted with (per-slot keys are engine
        state, resolved once at admission)."""
        self._base_key = key

    def _request_key(self, req: Request) -> jax.Array:
        if req.key is not None:
            return req.key
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(self._base_key, req.rid)

    def _prefill_one(self, cache, tok: jax.Array, table: jax.Array,
                     off: int, slot: int, fold_base: int):
        """One prefill chunk through the target (and lockstep draft)
        pool.  In a sketch engine the sketched chunk is used for EVERY
        slot — with fold_base == 0 it writes bitwise the legacy rows —
        so prefill keeps compiling exactly once per engine."""
        if self.sketch_on:
            kv = self._prefill_chunk(self.params, cache["kv"],
                                     cache["tail"], tok, table,
                                     jnp.int32(off), jnp.int32(slot),
                                     jnp.int32(fold_base))
            cache = {**cache, "kv": kv}
            if self.draft is not None:
                dkv = self._draft_prefill_chunk(
                    self.draft.params, cache["draft"]["kv"],
                    cache["draft"]["tail"], tok, table, jnp.int32(off),
                    jnp.int32(slot), jnp.int32(fold_base))
                cache = {**cache, "draft": {**cache["draft"], "kv": dkv}}
            return cache
        kv = self._prefill_chunk(self.params, {"kv": cache["kv"]},
                                 tok, table, jnp.int32(off))
        cache = {**cache, "kv": kv["kv"]}
        if self.draft is not None:
            # the draft pool prefills in lockstep through the same
            # table, so cached-prefix blocks hold BOTH models' rows
            dkv = self._draft_prefill_chunk(
                self.draft.params, cache["draft"], tok, table,
                jnp.int32(off))
            cache = {**cache, "draft": dkv}
        return cache

    def _chunk_prefill_loop(self, cache, prompt: np.ndarray,
                            table: jax.Array, start_off: int,
                            slot: int = 0):
        """Feed prompt rows [start_off, S) through bucket-sized prefill
        chunks.  Starts are ALWAYS absolute bucket multiples — no tail
        clamp — so the chunk boundaries (and hence the cache rows) are
        identical whether the loop starts at 0 (cold miss) or at a cached-
        prefix boundary (hit), for any max_seq; overlap rows recompute to
        the values they already hold, and tail rows mapping past the
        request's reserved blocks are dropped by the paged scatter."""
        sv = self.serve
        S = len(prompt)
        if start_off >= S:
            return cache
        bucket = max(1, min(sv.prefill_bucket, sv.max_seq))
        off = (start_off // bucket) * bucket
        while off < S:
            seg = prompt[off:off + bucket]
            tok = np.zeros((1, bucket), np.int32)
            tok[0, :len(seg)] = seg
            with span("prefill_chunk", self.obs, slot=slot, off=off,
                      rows=len(seg)) as sp:
                cache = self._prefill_one(cache, jnp.asarray(tok), table,
                                          off, slot, 0)
            self._count_attn_tiles([off], [0], bucket, 1)
            if self.obs is not None:
                self.obs.prefill_span(slot, off, len(seg), sp.dur_s)
            off += bucket
        return cache

    def _sketch_prefill_admit(self, slot: int, cache, prompt: np.ndarray,
                              shared: List[int], start_off: int):
        """Fold-through chunked prefill for a SKETCHED request: blocks
        allocate lazily just ahead of the write frontier, and blocks that
        age fully past the exact window fold into the slot's tail tables
        and return to the pool — a prompt's peak block hold is the window
        plus one prefill bucket, independent of its length.

        Returns (cache, slot_ids, first_lblk, ok); ``slot_ids`` are the
        blocks still held (logical blocks [first_lblk, ...)), already
        unreffed on failure (ok False -> caller defers the admission).
        """
        sv = self.serve
        bs = self.block_size
        W = self.kv_window
        S = len(prompt)
        NB = self.num_blocks
        bucket = max(1, min(sv.prefill_bucket, sv.max_seq))
        row = np.full((self.blocks_per_slot,), NB, np.int32)
        slot_ids = list(shared)
        row[:len(slot_ids)] = slot_ids
        first_lblk = 0
        fold_base = 0
        off = (start_off // bucket) * bucket
        while off < S:
            seg = prompt[off:off + bucket]
            end = off + len(seg)                   # prompt rows fed so far
            need_end = (end - 1) // bs             # last logical block hit
            have_end = first_lblk + len(slot_ids) - 1
            if need_end > have_end:
                ids = self._take_blocks(need_end - have_end)
                if ids is None:
                    self.alloc.unref(slot_ids)
                    return cache, [], 0, False
                row[have_end + 1:need_end + 1] = ids
                slot_ids.extend(ids)
            tok = np.zeros((1, bucket), np.int32)
            tok[0, :len(seg)] = seg
            # jnp.array copies ``row``: the loop rewrites it in place
            # while the dispatched chunk may still read it, and on the CPU
            # jnp.asarray of an aligned numpy array aliases its memory
            with span("prefill_chunk", self.obs, slot=slot, off=off,
                      rows=len(seg)) as sp:
                cache = self._prefill_one(cache, jnp.asarray(tok),
                                          jnp.array(row), off, slot,
                                          fold_base)
            self._count_attn_tiles([off], [fold_base], bucket, 1)
            if self.obs is not None:
                self.obs.prefill_span(slot, off, len(seg), sp.dur_s)
            # fold whole blocks that aged past the window ([0, end) keeps
            # >= W exact rows; the decode resume row S-1 always stays
            # exact because fold_base <= S - W <= S - 1)
            n_elig = max(0, (end - W) // bs) - first_lblk
            while n_elig > 0:
                k = min(n_elig, self.prefill_fold_cap // bs)
                cache = self._fold_fn(cache, jnp.array(row),
                                      jnp.int32(fold_base),
                                      jnp.int32(k * bs), jnp.int32(slot))
                # sentinel the folded entries BEFORE freeing: a freed
                # block may be re-allocated (e.g. as a CoW fork target)
                # while this row is still live
                row[first_lblk:first_lblk + k] = NB
                dead = slot_ids[:k]
                del slot_ids[:k]
                self.alloc.unref(dead)
                first_lblk += k
                fold_base += k * bs
                self.fold_rows_total += k * bs
                if self.obs is not None:
                    self.obs.fold(slot, k * bs)
                n_elig -= k
            off += bucket
        return cache, slot_ids, first_lblk, True

    def _take_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pool blocks, evicting IDLE prefix-cache entries
        under pressure (evicting busy ones frees nothing — their blocks
        stay reserved by the referencing slots — so hot prefixes are
        never wiped for a transient spike); None when the pool genuinely
        can't serve it now."""
        ids = self.alloc.alloc(n)
        while ids is None and self.prefix_cache.evict_one(idle_only=True):
            ids = self.alloc.alloc(n)
        return ids

    def _ensure_exclusive(self, slot: int, slot_ids: List[int], cache,
                          first_write: int, first_lblk: int = 0):
        """Copy-on-write fork: make every block of ``slot`` that decode
        can write — logical blocks covering positions >= ``first_write``
        — exclusively held (refcount 1).  Shared blocks (prefix-cache
        entries / other slots referencing them) are forked: a fresh pool
        block is allocated (evicting idle cache entries under pressure),
        the rows are copied device-side in BOTH the target and draft
        pools, ``slot_ids`` is rebound in place and the shared block
        loses this slot's reference.  Returns (cache, ok); ok False when
        the pool can't supply a fork target right now (caller unwinds
        and defers the admission).  ``first_lblk`` is the logical block
        index of ``slot_ids[0]`` — nonzero for sketched slots whose
        leading blocks already folded into the tail and were freed."""
        bs = self.block_size
        for i in range(max(0, first_write // bs - first_lblk),
                       len(slot_ids)):
            b = slot_ids[i]
            nb = self.alloc.fork(b)
            while nb is None and self.prefix_cache.evict_one(
                    idle_only=True):
                nb = self.alloc.fork(b)
            if nb is None:
                return cache, False
            if nb != b:      # was shared: copy rows into the fresh block
                cache = self._copy_block(cache, jnp.int32(b),
                                         jnp.int32(nb))
                slot_ids[i] = nb
        return cache, True

    def _admit(self, slot: int, req: Request) -> bool:
        """Try to admit ``req`` into ``slot``; False when the block pool
        can't currently reserve the request's KV (the caller leaves the
        request queued until blocks free up)."""
        prompt = np.asarray(req.tokens, np.int32)
        S = len(prompt)
        st = self._state
        hit = None
        fold_rows = 0
        if self.is_kv:
            bs = self.block_size
            use_sketch = self.sketch_on and req.kv_sketch is not False
            first = req.rid not in self._admit_memo
            with span("prefix_lookup"):
                # a pool-pressure retry was counted on its first attempt:
                # re-resolve the hit statelessly (the entry may have been
                # evicted or admitted meanwhile, so stats reflect the
                # first attempt while Completion.prefix_hit reflects how
                # the request was actually served) and reuse the
                # memoized admission decision
                hit = (self.prefix_cache.lookup(prompt) if first
                       else self.prefix_cache.peek(prompt))
            if first:
                # hits feed the admission path too: a hot prompt that
                # keeps hitting a short cached prefix must still get its
                # longer qualifying prefix admitted eventually
                self._admit_memo[req.rid] = self.prefix_cache.observe(
                    prompt)
            admit_plen = self._admit_memo[req.rid]
            shared: List[int] = []
            start_off = 0
            if hit is not None:
                plen, ids = hit
                shared = list(ids)
                start_off = plen
                # pin the shared blocks BEFORE any allocation below can
                # pressure the cache into evicting (and freeing) them
                self.alloc.ref(shared)
            if admit_plen is not None and admit_plen <= start_off:
                admit_plen = None    # nothing beyond what we already share
            first_lblk = 0
            if use_sketch:
                # fold-through prefill: the tail must be zeroed FIRST
                # (the slot lane may hold a retired occupant's sums)
                cache0 = self._zero_tail(st.cache, jnp.int32(slot))
                st = st._replace(cache=cache0)
                cache, slot_ids, first_lblk, ok_pf = \
                    self._sketch_prefill_admit(slot, cache0, prompt,
                                               shared, start_off)
                if not ok_pf:
                    # blocks already unreffed; the prefill chunks donated
                    # the old pool buffers, so the threaded cache must
                    # land back in engine state before deferring
                    self._state = st._replace(cache=cache)
                    if not any(r is not None for r in self._slot_req):
                        raise RuntimeError(
                            f"kv pool ({self.num_blocks} blocks of {bs}) "
                            f"too small for sketched prompt {S} with "
                            f"window {self.kv_window}")
                    return False
            else:
                n_total = -(-(S + req.max_new + self.spec_overhang) // bs)
                new_ids = self._take_blocks(n_total - len(shared))
                if new_ids is None:
                    if hit is not None:
                        self.alloc.unref(shared)
                    if not any(r is not None for r in self._slot_req):
                        raise RuntimeError(
                            f"kv pool ({self.num_blocks} blocks of {bs}) "
                            f"too small for prompt {S} + max_new "
                            f"{req.max_new}")
                    return False
                slot_ids = shared + new_ids
                row = np.full((self.blocks_per_slot,), self.num_blocks,
                              np.int32)
                row[:len(slot_ids)] = slot_ids
                table = jnp.asarray(row)
                st = st._replace(tables=st.tables.at[slot].set(table))
                cache = self._chunk_prefill_loop(st.cache, prompt, table,
                                                 start_off, slot)
            self._slot_blocks[slot] = slot_ids
            if admit_plen is not None and first_lblk > 0:
                # fold-through freed leading prompt blocks — the prefix's
                # block run no longer exists, and admitting the surviving
                # suffix would register freed (re-allocatable) block ids
                # as live cache entries
                admit_plen = None
            if admit_plen is not None:
                self.prefix_cache.admit(prompt, admit_plen,
                                        tuple(slot_ids[:admit_plen // bs]))
            # copy-on-write (speculative engines): fork any block the
            # slot's decode region [S-1, ...) reaches that is still
            # shared (prefix hit with plen == S, or the donation above).
            # Plain decode's only shared-block write is the idempotent
            # last-prompt-token rewrite, but a verify step writes draft
            # proposals that may be REJECTED — a speculating slot must
            # never write a block with refcount > 1.
            if self.spec_max:
                cache, ok = self._ensure_exclusive(slot, slot_ids, cache,
                                                   S - 1, first_lblk)
            else:
                ok = True
            if not ok:
                # pool exhausted mid-fork: unwind the slot's references
                # (the cache keeps any entry admitted above — its blocks
                # now hold valid prefix rows) and leave the request
                # queued; the memo records that admission already
                # happened so a retry won't re-count or re-admit
                self._state = st._replace(
                    cache=cache,
                    tables=st.tables.at[slot].set(
                        jnp.full((self.blocks_per_slot,), self.num_blocks,
                                 jnp.int32)))
                self.alloc.unref(slot_ids)
                self._slot_blocks[slot] = []
                self._slot_use_sketch[slot] = False
                self._slot_first_lblk[slot] = 0
                self._admit_memo[req.rid] = None
                return False
            row = np.full((self.blocks_per_slot,), self.num_blocks,
                          np.int32)
            row[first_lblk:first_lblk + len(slot_ids)] = slot_ids
            st = st._replace(tables=st.tables.at[slot].set(
                jnp.asarray(row)))
            fold_rows = first_lblk * bs
            self._slot_first_lblk[slot] = first_lblk
            self._slot_use_sketch[slot] = use_sketch
            # used-rows tracks DEMAND: every row a live request attends,
            # shared prefix rows counted per referencing request — so
            # demand exceeding reserved is the zero-copy sharing win
            # made visible, not an accounting error
            self._slot_rows[slot] = S + req.max_new
            self._used_rows += self._slot_rows[slot]
            self.peak_used_rows = max(self.peak_used_rows, self._used_rows)
            self._admit_memo.pop(req.rid, None)
        else:
            # recurrent: exact-length prefill of all but the last token
            # (decode applies it — a recurrent step is not idempotent, so
            # unlike KV rows the last token must be consumed exactly once)
            if S > 1:
                _, pre = self._prefill(
                    self.params, {"tokens": jnp.asarray(prompt[None, :-1])})
            else:
                pre = self._zero_block        # fresh state, reset only
            cache = self._insert_fn(st.cache, pre, jnp.int32(slot))
        temp = (self.temperature if req.temperature is None
                else float(req.temperature))
        eff_spec = 0
        if self.spec_max:
            eff_spec = (self.serve.spec_k if req.spec_k is None
                        else int(req.spec_k))
            eff_spec = max(0, min(eff_spec, self.spec_max))
        # each eager ``.at[].set`` here dispatches about five programs:
        # behind the prefill chunks still in flight they reach the
        # runtime's limit (32 programs in flight on a v5e), and the host
        # then waits for the prefill to end before dispatching the rest
        st = st._replace(
            cache=cache,
            cur=st.cur.at[slot, 0].set(int(prompt[S - 1])),
            pos=st.pos.at[slot].set(S - 1),
            remaining=st.remaining.at[slot].set(req.max_new),
            temp=st.temp.at[slot].set(temp),
            top_k=st.top_k.at[slot].set(int(req.top_k)),
            keys=st.keys.at[slot].set(self._request_key(req)),
            spec_k=st.spec_k.at[slot].set(eff_spec),
            fold_base=st.fold_base.at[slot].set(fold_rows),
        )
        self._state = st
        self._slot_req[slot] = req
        self._slot_out[slot] = []
        self._slot_hit[slot] = hit is not None
        # host mirror of the device position: decode resumes at S - 1 and
        # collect() advances the mirror by the emitted count per round
        # (the chunk advances pos by exactly the tokens it emits), so
        # fold planning / retirement never read device arrays
        self._slot_pos[slot] = S - 1
        self._slot_admit_seq[slot] = self._admit_seq
        self._admit_seq += 1
        # host-side mirror for acceptance accounting: sampled slots never
        # accept proposals in-graph, so they don't count as speculating
        self._slot_spec[slot] = eff_spec if temp == 0.0 else 0
        if self.obs is not None:
            self.obs.request_admitted(req.rid, slot, hit is not None)
        return True

    def _complete(self, slot: int, status: str) -> Completion:
        """Build the Completion for ``slot``'s occupant, folding in any
        output the request emitted before an earlier preemption (a
        preempted request is requeued as a continuation whose prompt is
        the original prompt + the tokens already served — its Completion
        reports the ORIGINAL prompt_len and the full output)."""
        req = self._slot_req[slot]
        out = list(self._slot_out[slot][:req.max_new])
        hit = self._slot_hit[slot]
        plen = len(req.tokens)
        stash = self._preempted.pop(req.rid, None)
        if stash is not None:
            plen, prior, hit0 = stash
            out = prior + out
            hit = hit or hit0
        return Completion(rid=req.rid, prompt_len=plen,
                          tokens=np.asarray(out, np.int32),
                          prefix_hit=hit, status=status)

    def _complete_queued(self, req: Request, status: str) -> Completion:
        """Completion for a request leaving the QUEUE (cancelled or
        expired before admission); a preempted continuation surfaces the
        tokens it emitted before eviction."""
        plen, prior, hit = self._preempted.pop(
            req.rid, (len(req.tokens), [], False))
        self._admit_memo.pop(req.rid, None)
        return Completion(rid=req.rid, prompt_len=plen,
                          tokens=np.asarray(prior, np.int32),
                          prefix_hit=hit, status=status)

    def _release_slot_state(self, freed: List[int],
                            deactivate: bool = False) -> None:
        """Release every slot in ``freed`` — device tables, pool blocks,
        host mirrors — shared by retirement, cancellation, expiry and
        preemption.  ``deactivate`` additionally zeroes the device
        ``remaining`` (mid-flight evictions; a naturally retired slot's
        budget already reached zero on device)."""
        if not freed:
            return
        if deactivate:
            self._state = self._state._replace(
                remaining=self._state.remaining.at[
                    np.asarray(freed)].set(0))
        if self.is_kv:
            # invalidate the slots' table rows BEFORE their blocks can
            # be freed/reused: an idle slot still executes the decode
            # write every step, and only the sentinel makes it a no-op
            # (one batched row-scatter, not one update per slot)
            tables = self._state.tables.at[np.asarray(freed)].set(
                self.num_blocks)
            self._state = self._state._replace(tables=tables)
            if self.sketch_on:
                # a leaving slot's fold frontier resets with it; the tail
                # sums themselves are zeroed lazily at the NEXT admission
                self._state = self._state._replace(
                    fold_base=self._state.fold_base.at[
                        np.asarray(freed)].set(0))
            for s in freed:
                self.alloc.unref(self._slot_blocks[s])
                self._slot_blocks[s] = []
                self._used_rows -= self._slot_rows[s]
                self._slot_rows[s] = 0
                self._slot_first_lblk[s] = 0
                self._slot_use_sketch[s] = False
        for s in freed:
            self._slot_req[s] = None
            self._slot_out[s] = []
            self._slot_spec[s] = 0
            self._slot_pos[s] = 0

    def _retire(self) -> List[Completion]:
        """Retire every slot whose token budget is spent.  Purely
        host-side: a slot is done exactly when its collected output
        reached ``max_new`` (the chunk clamps emission to the remaining
        budget, so this coincides with device ``remaining == 0``)."""
        done: List[Completion] = []
        freed: List[int] = []
        for s, req in enumerate(self._slot_req):
            if req is not None and len(self._slot_out[s]) >= req.max_new:
                done.append(self._complete(s, "ok"))
                freed.append(s)
        self._release_slot_state(freed)
        self.completed.extend(done)
        if self.obs is not None:
            for c in done:
                self.obs.request_finished(c.rid, c.status, len(c.tokens))
        return done

    def cancel(self, rid: int, status: str = "cancelled"
               ) -> Optional[Completion]:
        """Cancel a queued or in-flight request mid-stream: a queued
        request just leaves the queue; an admitted one is evicted — its
        table row sentineled, its pool blocks unreffed (target and draft
        pools share refcounts, so both free together) — and the slot is
        immediately admittable again.  Returns the Completion (partial
        ``tokens``, ``status`` as given) or None for an unknown rid.
        Must run at a pump boundary: never between dispatch() and
        collect()."""
        assert self._inflight is None, (
            "cancel() between dispatch() and collect()")
        comp = None
        for i, r in enumerate(self._queue):
            if r.rid == rid:
                self._queue.pop(i)
                comp = self._complete_queued(r, status)
                break
        if comp is None:
            for s, r in enumerate(self._slot_req):
                if r is not None and r.rid == rid:
                    comp = self._complete(s, status)
                    self._release_slot_state([s], deactivate=True)
                    break
        if comp is None:
            return None
        if status == "expired":
            self.expirations += 1
        else:
            self.cancellations += 1
        self.completed.append(comp)
        if self.obs is not None:
            self.obs.request_finished(comp.rid, comp.status,
                                      len(comp.tokens))
        return comp

    def expire_deadlines(self, now: Optional[float] = None
                         ) -> List[Completion]:
        """Expire every request whose deadline has passed: queued ones
        drop with empty output, in-flight ones retire with whatever
        tokens they have (status "expired" either way)."""
        if now is None:
            now = time.monotonic()
        late = [r.rid for r in self._queue
                if r.deadline is not None and r.deadline <= now]
        late += [r.rid for r in self._slot_req
                 if r is not None and r.deadline is not None
                 and r.deadline <= now]
        return [c for c in (self.cancel(rid, "expired") for rid in late)
                if c is not None]

    def preempt(self, slot: int) -> Request:
        """Evict a RUNNING slot and requeue its request as a
        continuation: prompt extended by the tokens already emitted,
        budget reduced by the same count, reinserted at the head of its
        priority band.  The request later completes "ok" with its
        original prompt_len and its full output — preemption changes
        when it runs, not what it returns.  Sampled slots carry their
        advanced per-slot PRNG key so the continuation keeps drawing
        from the same stream.  Must run at a pump boundary."""
        assert self._inflight is None, (
            "preempt() between dispatch() and collect()")
        req = self._slot_req[slot]
        assert req is not None, f"preempt of empty slot {slot}"
        out = list(self._slot_out[slot])
        plen0, prior, hit0 = self._preempted.get(
            req.rid, (len(req.tokens), [], False))
        self._preempted[req.rid] = (plen0, prior + out,
                                    hit0 or self._slot_hit[slot])
        temp = (self.temperature if req.temperature is None
                else float(req.temperature))
        cont = dataclasses.replace(
            req,
            tokens=np.concatenate([np.asarray(req.tokens, np.int32),
                                   np.asarray(out, np.int32)]),
            max_new=req.max_new - len(out),
            key=(jnp.asarray(self._state.keys[slot]) if temp > 0
                 else req.key))
        self._release_slot_state([slot], deactivate=True)
        self.preemptions += 1
        if self.obs is not None:
            self.obs.request_preempted(req.rid, slot, len(out))
        # the continuation must not re-feed the count-min tracker (its
        # prefix was counted at first admission): memo None keeps hit
        # lookups stateless and suppresses re-admission of the extended
        # prompt, while a cached prefix the original admission donated
        # still gives the continuation a zero-copy resume
        self._admit_memo[req.rid] = None
        self._enqueue(cont, front=True)
        return cont

    def _preempt_for(self, req: Request) -> Optional[int]:
        """Preemption policy for a full engine: evict the lowest-priority
        running slot STRICTLY below ``req``'s priority (ties broken
        toward the most recently admitted — least sunk work), returning
        the freed slot, or None when preemption is off / no slot
        qualifies (equal-priority traffic is never preempted, so plain
        FIFO streams keep their old head-of-line behaviour)."""
        if not self.serve.preemption:
            return None
        best = None
        for s, r in enumerate(self._slot_req):
            if r is None or r.priority >= req.priority:
                continue
            rank = (r.priority, -self._slot_admit_seq[s])
            if best is None or rank < best[0]:
                best = (rank, s)
        if best is None:
            return None
        self.preempt(best[1])
        return best[1]

    def _plan_folds(self) -> np.ndarray:
        """Pre-chunk bookkeeping for sketched slots: allocate the blocks
        the coming chunk can write (lazy lookahead — a sketched slot
        never reserves its whole context) and decide how many rows each
        slot folds into its tail at the chunk head.  Returns the per-slot
        fold length (rows, block multiples) passed into the compiled
        chunk; the matching host-side frees happen in ``_finish_folds``
        AFTER the chunk consumed the folded blocks.  Positions come from
        the HOST mirror (``_slot_pos``), so planning the next chunk never
        synchronizes on the previous one."""
        bs = self.block_size
        W = self.kv_window
        fold = np.zeros((self.serve.max_batch,), np.int32)
        tables = self._state.tables
        dirty = False
        for s, req in enumerate(self._slot_req):
            if req is None or not self._slot_use_sketch[s]:
                continue
            p = self._slot_pos[s]
            first = self._slot_first_lblk[s]
            held = self._slot_blocks[s]
            # the chunk writes rows up to p + adv_max (+ rejected
            # speculative writes); clamp to the request's own demand
            last = min(p + self.adv_max, self._slot_rows[s] - 1) \
                + self.spec_overhang
            need_end = min(last // bs, self.blocks_per_slot - 1)
            have_end = first + len(held) - 1
            if need_end > have_end:
                ids = self._take_blocks(need_end - have_end)
                if ids is None:
                    raise RuntimeError(
                        f"kv pool exhausted extending sketched slot {s} "
                        f"(pool {self.num_blocks} blocks of {bs}; raise "
                        f"cfg.serve.num_kv_blocks or shrink "
                        f"kv_sketch_window)")
                tables = tables.at[s, have_end + 1:need_end + 1].set(
                    jnp.asarray(np.asarray(ids, np.int32)))
                held.extend(ids)
                dirty = True
            # fold whole blocks aged past the exact window, at most one
            # chunk's worth (the compiled fold span is fold_cap rows)
            n = min(max(0, (p + 1 - W) // bs - first), self.fold_cap // bs,
                    len(held))
            fold[s] = n * bs
        if dirty:
            self._state = self._state._replace(tables=tables)
        return fold

    def _finish_folds(self, fold: np.ndarray) -> None:
        """Post-chunk half of a fold: the chunk already accumulated the
        folded rows into the tails and advanced ``fold_base``; here the
        blocks leave the slot — sentinel the table entries FIRST (a freed
        block can be re-allocated immediately), then drop the refs."""
        tables = self._state.tables
        dead: List[int] = []
        dirty = False
        for s in range(self.serve.max_batch):
            n = int(fold[s]) // self.block_size
            if n == 0:
                continue
            first = self._slot_first_lblk[s]
            tables = tables.at[s, first:first + n].set(self.num_blocks)
            dirty = True
            dead.extend(self._slot_blocks[s][:n])
            del self._slot_blocks[s][:n]
            self._slot_first_lblk[s] = first + n
            self.fold_rows_total += n * self.block_size
            if self.obs is not None:
                self.obs.fold(s, n * self.block_size)
        if dirty:
            # sentinel the rows BEFORE the unref makes the blocks
            # re-allocatable (nothing allocates between these two lines,
            # so no other slot's table can claim a stale-mapped block)
            self._state = self._state._replace(tables=tables)
            self.alloc.unref(dead)

    def _count_attn_tiles(self, start, fold_base, Sq: int,
                          slots: int) -> None:
        """Account kernel calls of ``Sq`` query rows over ``slots`` slot
        rows of the grid, of which the slots at ``start`` / ``fold_base``
        are live (a retired slot's dead table walks no tile).  Tile width
        and bound come from the kernel's own ``tile_blocks`` /
        ``tile_range``, so the counters cannot disagree with its walk."""
        if not self.kernels:
            return
        cfg = self.cfg
        K = cfg.num_kv_heads
        nb = self.blocks_per_slot
        P = kpa.tile_blocks(self.block_size, K, cfg.resolved_head_dim, nb,
                            cfg.num_heads // K * Sq, self._pool_itemsize)
        T = -(-nb // P)
        lo, hi = kpa.tile_range(np.asarray(start), np.asarray(fold_base),
                                Sq, P * self.block_size)
        self.attn_tiles_walked += int(
            np.maximum(np.minimum(hi, T - 1) - lo + 1, 0).sum())
        self.attn_tiles_grid += slots * T

    def _count_decode_tiles(self) -> None:
        """The dispatched chunk's decode steps from the host mirrors: a
        slot's step j attends from its position advanced by the steps it
        has run (a verify round counts one committed token, a lower bound)."""
        live = [s for s, r in enumerate(self._slot_req) if r is not None]
        if not self.kernels or not live:
            return
        chunk = self.serve.decode_chunk
        pos = np.asarray([self._slot_pos[s] for s in live])
        left = np.asarray([self._slot_req[s].max_new
                           - len(self._slot_out[s]) for s in live])
        fb = np.asarray([self._slot_first_lblk[s] for s in live]) \
            * self.block_size
        steps = np.arange(chunk)[:, None]
        self._count_attn_tiles(pos + np.minimum(steps, left),
                               np.broadcast_to(fb, (chunk, fb.size)),
                               self.spec_max + 1,
                               chunk * self.serve.max_batch)

    @property
    def use_kernels(self) -> bool:
        """True when paged attention runs the Pallas kernel (compiled or
        interpreted), False on the jnp gather path."""
        return self.kernels is not None

    @property
    def pending(self) -> bool:
        """True while any request is queued or occupying a slot — the
        public drain condition (``while sched.pending: sched.step()``)."""
        return bool(self._queue) or any(
            r is not None for r in self._slot_req)

    @property
    def queue_len(self) -> int:
        """Requests waiting for admission (the backpressure signal the
        async front-end bounds against cfg.serve.queue_depth)."""
        return len(self._queue)

    def progress(self) -> Dict[int, List[int]]:
        """rid -> tokens emitted so far, for every request still queued
        or in flight (a preempted request's pre-eviction output counts).
        The async front-end reads this after each collect() to stream
        per-token deltas without touching scheduler internals."""
        out: Dict[int, List[int]] = {}
        for rid, (_, prior, _) in self._preempted.items():
            out[rid] = list(prior)
        for s, r in enumerate(self._slot_req):
            if r is not None:
                out[r.rid] = out.get(r.rid, []) + list(self._slot_out[s])
        for r in self._queue:
            out.setdefault(r.rid, [])
        return out

    # ------------------------------------------------------------------
    # Pump phases — the building blocks of one scheduler round.  The
    # synchronous ``step()`` composes them back-to-back; the async pump
    # (serve/frontend.py) interleaves them so host-side admission and
    # chunked prefill overlap the in-flight device chunk:
    #
    #   expire_deadlines / cancel   (pump boundary only)
    #   admit_pending               (before OR during the chunk)
    #   dispatch                    (launch the chunk; returns futures)
    #       ... more admit_pending: prefill ops enqueue AFTER the chunk
    #       in device-stream order, and the chunk read pre-admission
    #       state (an idle slot emits nothing and its sentinel table row
    #       drops the KV write), so overlapped admission is invisible to
    #       the in-flight chunk ...
    #   collect                     (materialize tokens; retire)
    # ------------------------------------------------------------------

    def admit_pending(self) -> int:
        """Admission phase: move queued requests into free slots until
        the queue empties, the engine fills, or the block pool can't
        serve the head request (it stays queued — FIFO order within a
        priority band is preserved, so pool pressure never starves the
        head).  A full engine may PREEMPT a strictly lower-priority slot
        for a high-priority head (``cfg.serve.preemption``).  Safe to
        call while a chunk is in flight.  Returns the admission count."""
        admitted = 0
        while self._queue:
            head = self._queue[0]
            slot = next((s for s, r in enumerate(self._slot_req)
                         if r is None), None)
            if slot is None:
                slot = self._preempt_for(head) \
                    if self._inflight is None else None
            if slot is None:
                break                # full: wait
            with span("admit", self.obs, rid=int(head.rid),
                      slot=slot) as sp:
                ok = self._admit(slot, head)
                sp.set(ok=ok)
            if not ok:
                if self.obs is not None:
                    # a free slot existed but the pool couldn't serve
                    # the head request right now — a deferral stall
                    self.obs.admission_deferred(head.rid)
                break                # pool pressure: wait
            self._queue.pop(0)
            admitted += 1
        return admitted

    def dispatch(self) -> bool:
        """Decode phase, launch half: run one compiled decode chunk
        ASYNCHRONOUSLY — jax dispatch returns futures, so the host keeps
        working (admission, prefill, stream delivery) while the device
        crunches; ``collect()`` materializes the result.  Returns False
        when no slot is active (nothing to run)."""
        assert self._inflight is None, "one decode chunk may be in flight"
        if not any(r is not None for r in self._slot_req):
            return False
        with span("dispatch", self.obs):
            fold_host = None
            if self.sketch_on:
                fold_host = self._plan_folds()
            if self.spec_max > 0:
                if self.sketch_on:
                    self._state, toks, emits = self._chunk_fn(
                        self.params, self.draft.params, self._state,
                        jnp.asarray(fold_host))
                else:
                    self._state, toks, emits = self._chunk_fn(
                        self.params, self.draft.params, self._state)
            elif self.sketch_on:
                self._state, toks, emits = self._chunk_fn(
                    self.params, self._state, jnp.asarray(fold_host))
            else:
                self._state, toks, emits = self._chunk_fn(self.params,
                                                          self._state)
            if fold_host is not None:
                # the fold's host half runs at dispatch time: the table
                # sentinels enqueue AFTER the chunk in device-stream order,
                # and any re-allocation's prefill writes enqueue later still
                self._finish_folds(fold_host)
            self._count_decode_tiles()
            self.decode_steps += self.serve.decode_chunk
            self._inflight = (toks, emits)
        return True

    def collect(self) -> List[Completion]:
        """Decode phase, collect half: materialize the in-flight chunk's
        tokens (this is the ONE host-device sync point of a round),
        account them to their slots, advance the host position mirrors,
        and retire every request whose budget is spent.  Slots admitted
        while the chunk was in flight emitted nothing (their ``remaining``
        was 0 when the chunk launched), so overlap never misattributes
        tokens."""
        assert self._inflight is not None, "collect() without dispatch()"
        with span("collect", self.obs):
            toks, emits = self._inflight
            self._inflight = None
            with span("collect.wait"):
                toks = np.asarray(toks)
                emits = np.asarray(emits)
            if toks.ndim == 2:               # plain chunk: one token per step
                toks = toks[:, :, None]
                emits = emits[:, :, None]
            round_tokens = 0
            for t in range(toks.shape[0]):
                for s in range(toks.shape[1]):
                    if self._slot_req[s] is None:
                        continue
                    e = int(emits[t, s].sum())
                    if e == 0:
                        continue
                    self._slot_out[s].extend(
                        int(x) for x in toks[t, s][emits[t, s]])
                    self._slot_pos[s] += e
                    round_tokens += e
                    # one verify round: slot proposed spec_k tokens and
                    # e - 1 of them survived verification
                    rr, pp, aa = spec_round_accounting(self._slot_spec[s], e)
                    if rr:
                        self.spec_rounds += rr
                        self.spec_proposed += pp
                        self.spec_accepted += aa
                        if self.obs is not None:
                            self.obs.spec_round(self._slot_req[s].rid, pp, aa)
            with span("retire"):
                done = self._retire()
            if self.obs is not None:
                self._round_idx += 1
                self.obs.chunk_collected(
                    round_tokens, len(self._queue),
                    sum(r is not None for r in self._slot_req))
                if (self.sketch_on and self.obs.fidelity_every > 0
                        and self._round_idx % self.obs.fidelity_every == 0):
                    self._probe_fidelity()
                self.obs.maybe_flush(self.stats)
        return done

    def _probe_fidelity(self) -> None:
        """Opt-in sketch-fidelity probe: per-slot relative spread of the
        Z independent hash-row tail estimates (``kv_sketch.
        tail_row_spread``), emitted as a gauge for every slot with
        folded content.  Runs ONLY here — at the ``collect()`` boundary,
        where the round's host-device sync just happened and the tail
        tables are already materialized engine state — and only at the
        observer's ``fidelity_every`` cadence, so the compiled chunk and
        the sync discipline of the hot path are untouched."""
        sp = np.asarray(self._spread_fn(self._state.cache["tail"]))
        for s, req in enumerate(self._slot_req):
            if req is None or not self._slot_use_sketch[s]:
                continue
            folded = self._slot_first_lblk[s] * self.block_size
            if folded <= 0:
                continue
            self.obs.fidelity(s, req.rid, folded, float(sp[s]))

    def step(self) -> List[Completion]:
        """One SYNCHRONOUS scheduler round — the closed-batch
        composition of the pump phases: expire deadlines, admit into
        free slots, run one compiled decode chunk and immediately
        collect it.  Returns the requests completed this round."""
        self.expire_deadlines()
        self.admit_pending()
        if not self.dispatch():
            return []
        return self.collect()

    def drain(self) -> List[Completion]:
        """Step until every queued and in-flight request completed."""
        done: List[Completion] = []
        while self.pending:
            done.extend(self.step())
        return done

    def run(self, requests: Optional[List[Request]] = None
            ) -> List[Completion]:
        """Closed-batch convenience: submit ``requests`` (if given) and
        drain."""
        for r in requests or []:
            self.submit(r)
        return self.drain()

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def decode_compilations(self) -> int:
        """Number of times the chunked decode step has been compiled —
        the engine's contract is that this is 1 for its whole lifetime,
        regardless of the request mix (lengths, families, sampling)."""
        return self._chunk_fn._cache_size()

    @property
    def prefill_compilations(self) -> int:
        """Attention families: 1 for the engine's lifetime (the chunked
        prefill step is offset-traced).  Recurrent families: one per
        distinct prompt length (exact-length prefill)."""
        if self.is_kv:
            return self._prefill_chunk._cache_size()
        return self._prefill._cache_size()

    @property
    def state(self) -> DecodeState:
        return self._state

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals that survived verification, over
        every verify round run by speculating (greedy, spec_k > 0)
        slots.  0.0 when nothing speculated."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    @property
    def mean_accepted_run(self) -> float:
        """Mean tokens emitted per verify round by speculating slots
        (accepted draft tokens + the verified correction/bonus token) —
        the per-round decode advance; 1.0 means speculation never helps,
        spec_k + 1 is the ceiling."""
        return ((self.spec_accepted + self.spec_rounds)
                / max(self.spec_rounds, 1))

    def kv_cache_bytes(self) -> int:
        """Total bytes of the slot cache (the whole pool for attention
        families, the stacked recurrent state otherwise)."""
        return sum(int(a.size) * int(a.dtype.itemsize)
                   for a in jax.tree.leaves(self._state.cache))

    def kv_reserved_bytes(self) -> int:
        """Bytes of pool blocks currently allocated (slots + prefix
        cache) — what the engine actually reserves, vs the dense
        max_batch * max_seq equivalent."""
        return self.alloc.reserved_bytes() if self.is_kv else \
            self.kv_cache_bytes()

    def kv_peak_reserved_bytes(self) -> int:
        """High-water mark of reserved pool bytes over the engine's
        lifetime (the honest paged analogue of the dense reservation)."""
        return self.alloc.peak_reserved_bytes() if self.is_kv else \
            self.kv_cache_bytes()

    def kv_peak_used_bytes(self) -> int:
        """High-water mark of the KV row DEMAND of concurrently live
        requests ((S + max_new) per active slot; rows of a shared prefix
        count once per referencing request).  Reserved minus demand,
        when positive, bounds internal fragmentation (< one block per
        slot) plus idle cached prefixes; demand ABOVE reserved is memory
        zero-copy prefix sharing deduplicated away."""
        if not self.is_kv:
            return self.kv_cache_bytes()
        row_bytes = self.alloc.block_bytes / self.block_size
        return int(row_bytes * self.peak_used_rows)

    def kv_dense_equiv_bytes(self) -> int:
        """Bytes the old dense (L, max_batch, max_seq, K, hd) slot cache
        would have reserved for the same engine geometry."""
        if not self.is_kv:
            return self.kv_cache_bytes()
        row_bytes = self.alloc.block_bytes / self.block_size
        return int(row_bytes * self.serve.max_seq * self.serve.max_batch)

    def kv_sketch_tail_bytes(self) -> int:
        """Bytes of the per-slot FCS tail tables (target + draft) — the
        FIXED cost that replaces unbounded exact-KV growth past the
        window.  0 when the engine runs without sketching."""
        if not (self.is_kv and self.sketch_on):
            return 0
        total = kvs.tail_state_bytes(self._state.cache["tail"])
        if self.draft is not None:
            total += kvs.tail_state_bytes(
                self._state.cache["draft"]["tail"])
        return total

    def kv_sketch_exact_bytes(self) -> int:
        """Bytes of pool blocks currently held by SKETCHED slots — the
        exact recent-window span of the two-span cache."""
        if not (self.is_kv and self.sketch_on):
            return 0
        bb = self.alloc.block_bytes
        return sum(len(self._slot_blocks[s]) * bb
                   for s in range(self.serve.max_batch)
                   if self._slot_use_sketch[s])

    def stats(self) -> EngineStats:
        """The unified observability snapshot (see ``EngineStats``)."""
        st = EngineStats(
            queue_depth=len(self._queue),
            active_slots=sum(r is not None for r in self._slot_req),
            max_batch=self.serve.max_batch,
            completed=len(self.completed),
            cancelled=self.cancellations,
            expired=self.expirations,
            preempted=self.preemptions,
            decode_steps=self.decode_steps,
            decode_compilations=self.decode_compilations,
            prefill_compilations=self.prefill_compilations,
            fold_rows=self.fold_rows_total,
            kv_sketch_tail_bytes=self.kv_sketch_tail_bytes(),
            spec_rounds=self.spec_rounds,
            spec_proposed=self.spec_proposed,
            spec_accepted=self.spec_accepted,
            attn_tiles_walked=self.attn_tiles_walked,
            attn_tiles_grid=self.attn_tiles_grid,
        )
        if self.is_kv:
            st.pool_blocks = self.num_blocks
            st.block_size = self.block_size
            st.blocks_reserved = self.alloc.reserved
            st.blocks_free = self.alloc.free_count
            st.blocks_peak = self.alloc.peak_reserved
            st.kv_reserved_bytes = self.kv_reserved_bytes()
            st.kv_peak_reserved_bytes = self.kv_peak_reserved_bytes()
            st.kv_peak_used_bytes = self.kv_peak_used_bytes()
            st.kv_dense_equiv_bytes = self.kv_dense_equiv_bytes()
            pc = self.prefix_cache.stats
            st.prefix_lookups = pc.lookups
            st.prefix_hits = pc.hits
            st.prefix_admitted = pc.admitted
            st.prefix_evicted = pc.evicted
            st.prefix_cached_bytes = pc.bytes
        return st
