"""Sketch-gated prefix KV cache: count-min admission over prompt prefixes,
holding refcounted paged-pool block ids (zero-copy prefix sharing).

Production prompt streams are heavy-tailed — a few system/template prefixes
recur across millions of requests while the long tail is unique.  Caching
every prefill's KV would blow the budget on one-shot prompts, and tracking
exact per-prefix frequencies needs state proportional to unique-prompt
cardinality.  This module uses the same O(table)-storage hash machinery the
paper builds CS/FCS on (and that HCS motivates for multi-dimensional
lookups): prefix hashes are counted in a CSVec count-min table
(sketch/csvec.py, ``signed=False``), and a prefix is admitted to the
bounded cache only once its estimated frequency clears
``admit_threshold``.  Count-min's one-sided overestimate makes admission
*safe* — a hot prefix is never starved, a cold one is at worst admitted a
little early — while the tracker stays O(rows * cols) forever.

Granularity: block-multiple prefixes.  Every observed prompt increments the
count of each of its block-multiple prefixes in one batched
``accumulate_coords`` call, so two long prompts sharing a 32-token system
preamble both feed the same prefix keys even when their total lengths
differ.  Admission picks the LONGEST prefix over threshold.  Counts are
periodically aged (``decay``) TinyLFU-style so stale heavy hitters fade.

Storage: an admitted entry is a tuple of PHYSICAL POOL BLOCK IDS (the
slot's own prefill blocks, refcounted via the scheduler's BlockAllocator),
not a host copy — a hit writes the ids into the new slot's block table and
the prefix KV is shared by reference.  Eviction is LRU under a hard byte
budget counted in pool blocks, preferring entries no live slot still
references; an evicted entry's blocks return to the free list only when
their refcount reaches zero, so in-flight readers are never pulled out
from under.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.configs.base import ServeConfig
from repro.obs import span
from repro.sketch import csvec

# count-min key domain: prefix hashes land in [0, CM_DOMAIN)
CM_DOMAIN = 1 << 20


def prefix_key(tokens: np.ndarray) -> int:
    """Stable 64-bit hash of a token prefix (process-salt-free)."""
    h = hashlib.blake2b(np.ascontiguousarray(tokens, np.int32).tobytes(),
                        digest_size=8)
    return int.from_bytes(h.digest(), "little")


@dataclass
class PrefixCacheStats:
    lookups: int = 0
    hits: int = 0
    misses: int = 0
    admitted: int = 0
    evicted: int = 0
    rejected: int = 0            # observed prompts yielding no new admission
    bytes: int = 0               # unique cache-held pool blocks * block size

    @property
    def hit_rate(self) -> float:
        return self.hits / max(self.lookups, 1)


@dataclass
class _Entry:
    plen: int                    # cached prefix length in tokens
    block_ids: Tuple[int, ...]   # physical pool blocks covering [0, plen)


@dataclass
class SketchPrefixCache:
    """``allocator`` is the scheduler's BlockAllocator: the cache holds one
    reference per (entry, block) and the allocator arbitrates frees.
    ``block_size`` is the paged-KV page size in tokens — admitted prefix
    lengths are multiples of it (whole shared blocks only: a partially
    filled block would expose rows another slot later rewrites)."""
    cfg: ServeConfig
    allocator: Any = None
    block_size: int = 0
    stats: PrefixCacheStats = field(default_factory=PrefixCacheStats)
    # optional repro.obs.ServeObserver: hit / miss / admit / evict /
    # defer outcomes stream into its windowed ``prefix.*`` counters
    # (``stats`` above stays the cumulative source of truth)
    obs: Any = None

    def __post_init__(self):
        # whole-block sharing needs admitted prefix lengths (multiples of
        # prefix_block) to be block-aligned; assert here so the cache's
        # own arithmetic may rely on it, not just the scheduler's check
        assert self.block_size > 0, "paged prefix cache needs a block size"
        assert self.cfg.prefix_block % self.block_size == 0, (
            self.cfg.prefix_block, self.block_size)
        self._cm = csvec.csvec_zeros(
            CM_DOMAIN, cols=self.cfg.cm_cols, rows=self.cfg.cm_rows,
            seed=self.cfg.seed, signed=False)
        self._entries: "OrderedDict[Tuple[int, ...], _Entry]" = OrderedDict()
        self._held: Dict[int, int] = {}      # block id -> # entries holding
        self._observed = 0

    # -- read path ---------------------------------------------------------
    def _find(self, tokens: np.ndarray
              ) -> Optional[Tuple[Tuple[int, ...], _Entry]]:
        """Longest cached block-multiple prefix (key, entry) of
        ``tokens``, no side effects."""
        block = self.cfg.prefix_block
        n = len(tokens)
        for m in range(n // block, 0, -1):
            # block-aligned by the __post_init__ divisibility invariant
            key = tuple(int(t) for t in tokens[:m * block])
            ent = self._entries.get(key)
            if ent is not None:
                return key, ent
        return None

    def peek(self, tokens: np.ndarray
             ) -> Optional[Tuple[int, Tuple[int, ...]]]:
        """Like ``lookup`` but WITHOUT touching stats or LRU recency —
        for retrying a deferred admission (pool pressure): the request
        was already counted on its first attempt, and counting retries
        would inflate frequencies/hit rates per scheduler round."""
        found = self._find(tokens)
        return None if found is None else (found[1].plen,
                                           found[1].block_ids)

    def lookup(self, tokens: np.ndarray
               ) -> Optional[Tuple[int, Tuple[int, ...]]]:
        """Longest cached block-multiple prefix of ``tokens``.  Returns
        (prefix_len, pool block ids) and refreshes LRU recency; the caller
        installs the ids into the slot's block table and takes its own
        allocator reference (zero-copy hit — no KV rows move)."""
        self.stats.lookups += 1
        found = self._find(tokens)
        if found is not None:
            key, ent = found
            self._entries.move_to_end(key)
            self.stats.hits += 1
            if self.obs is not None:
                self.obs.prefix_event("hit")
            return ent.plen, ent.block_ids
        self.stats.misses += 1
        if self.obs is not None:
            self.obs.prefix_event("miss")
        return None

    # -- write path --------------------------------------------------------
    def _count(self, tokens: np.ndarray) -> Optional[np.ndarray]:
        """Increment the count-min frequency of every block-multiple
        prefix of ``tokens`` (one batched accumulate) and return the
        estimated counts, aging the table on the decay cadence."""
        block = self.cfg.prefix_block
        n_blocks = len(tokens) // block
        if n_blocks == 0:
            return None
        keys = np.array(
            [prefix_key(tokens[:m * block]) % CM_DOMAIN
             for m in range(1, n_blocks + 1)], np.int32)
        # about 66 single-op programs (hashing, accumulate, query), each
        # dispatched on its own: on a v5e the host's dispatch takes the
        # time, with the device idle, not the read-back; behind programs
        # still in flight the dispatches wait too (32 in flight at most)
        with span("prefix_count"):
            self._cm = csvec.accumulate_coords(
                self._cm, keys, np.ones(len(keys), np.float32))
            counts = np.asarray(csvec.query(self._cm, keys))
        self._observed += 1
        if self._observed % self.cfg.cm_decay_every == 0:
            self._cm = csvec.decay(self._cm, self.cfg.cm_decay)
        return counts

    def observe(self, tokens: np.ndarray) -> Optional[int]:
        """Count an observed prompt — hits AND misses: classic TinyLFU
        counts every access, and a hot prompt that keeps hitting a short
        cached prefix must still be able to get its longer qualifying
        prefix admitted — and return the longest (kv-block-aligned) prefix
        length whose estimated frequency clears the admission threshold
        and is not already cached.  The caller should then ``admit`` the
        slot's pool blocks covering it.  Returns None (counting the prompt
        in ``stats.rejected``) when nothing new qualifies."""
        counts = self._count(tokens)
        if counts is None:           # sub-block prompt: nothing can ever
            self.stats.rejected += 1  # qualify, but the observation counts
            if self.obs is not None:
                self.obs.prefix_event("defer")
            return None
        block = self.cfg.prefix_block
        n_blocks = len(counts)
        for m in range(n_blocks, 0, -1):
            if counts[m - 1] >= self.cfg.admit_threshold:
                plen = m * block     # block-aligned by the init invariant
                key = tuple(int(t) for t in tokens[:plen])
                if key not in self._entries:
                    return plen
                # longest qualifying prefix already cached: nothing to
                # admit, but the observation still counts as rejected —
                # otherwise hot-and-cached prompts vanish from the stats
                break
        self.stats.rejected += 1
        if self.obs is not None:
            self.obs.prefix_event("defer")
        return None

    def admit(self, tokens: np.ndarray, plen: int,
              block_ids: Tuple[int, ...]) -> None:
        """Hold a reference on the pool blocks covering ``tokens[:plen]``
        (zero-copy: they are the admitting slot's own prefill blocks),
        then evict LRU entries until under the byte budget.  Re-admitting
        a present key refreshes its LRU recency instead of silently
        returning — eviction order must reflect real access order."""
        assert plen % self.block_size == 0, (plen, self.block_size)
        assert len(block_ids) == plen // self.block_size
        key = tuple(int(t) for t in tokens[:plen])
        if key in self._entries:
            self._entries.move_to_end(key)
            return
        bb = self.allocator.block_bytes
        if len(block_ids) * bb > self.cfg.prefix_cache_bytes:
            return                   # one entry can never fit: don't thrash
        # every admitted block must still be LIVE (held by the admitting
        # slot): a freed block id would be ref'd back to life here while
        # the allocator hands the same block to someone else — the cache
        # would then serve rows another slot is overwriting.  Sketched
        # slots fold-and-free leading prompt blocks, so the scheduler
        # must skip admission for them rather than trip this.
        rc = self.allocator.rc
        assert all(int(rc[b]) >= 1 for b in block_ids), (
            "prefix-cache admit of freed block(s): "
            f"{[b for b in block_ids if int(rc[b]) < 1]}")
        self.allocator.ref(block_ids)
        for b in block_ids:
            self._held[b] = self._held.get(b, 0) + 1
        self._entries[key] = _Entry(plen=plen, block_ids=tuple(block_ids))
        self.stats.bytes = len(self._held) * bb
        self.stats.admitted += 1
        if self.obs is not None:
            self.obs.prefix_event("admit")
        while self.stats.bytes > self.cfg.prefix_cache_bytes:
            if not self.evict_one():
                break

    # -- eviction ----------------------------------------------------------
    def _entry_busy(self, ent: _Entry) -> bool:
        """True if any live slot still references the entry's blocks
        (allocator refcount above the cache's own holds)."""
        rc = self.allocator.rc
        return any(int(rc[b]) > self._held.get(b, 0)
                   for b in ent.block_ids)

    def _remove(self, key: Tuple[int, ...]) -> None:
        ent = self._entries.pop(key)
        for b in ent.block_ids:
            self._held[b] -= 1
            if self._held[b] == 0:
                del self._held[b]
        self.allocator.unref(ent.block_ids)
        self.stats.bytes = len(self._held) * self.allocator.block_bytes
        self.stats.evicted += 1
        if self.obs is not None:
            self.obs.prefix_event("evict")

    def evict_one(self, idle_only: bool = False) -> bool:
        """Evict one entry in LRU order, preferring entries whose blocks
        no live slot references (those actually free pool blocks).
        ``idle_only`` stops there — the pool-pressure caller gains
        nothing from evicting busy entries (their blocks stay reserved by
        the referencing slots), so wiping hot cached prefixes would be
        pure loss.  The byte-budget caller falls back to the absolute LRU
        entry: its blocks return to the free list when the last
        referencing slot retires, which is what the budget needs.
        Returns False when nothing (eligible) remains."""
        if not self._entries:
            return False
        for key, ent in self._entries.items():
            if not self._entry_busy(ent):
                self._remove(key)
                return True
        if idle_only:
            return False
        self._remove(next(iter(self._entries)))
        return True

    # -- introspection -----------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    def held_blocks(self) -> int:
        """Unique pool blocks currently held by the cache."""
        return len(self._held)

    def tracker_bytes(self) -> int:
        """Bytes held by the count-min frequency tracker (O(table),
        independent of how many unique prompts were observed)."""
        return csvec.state_bytes(self._cm)
