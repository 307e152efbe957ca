"""Shared layers: norms, RoPE, GQA/MQA attention (chunked causal + decode),
GLU FFN, embeddings, (optionally FCS-sketched) LM head.

All layers are pure functions over explicit param pytrees; init_* builders
mirror the apply functions.  Weights are bf16; softmax / norms / losses
accumulate in f32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models.sharding import shard

PDTYPE = jnp.bfloat16  # parameter dtype


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def init_rms_norm(d: int) -> jax.Array:
    return jnp.zeros((d,), PDTYPE)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, n, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos, sin = cos[..., None, :], sin[..., None, :]  # add head axis
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA / MQA)
# ---------------------------------------------------------------------------


def init_attention(key: jax.Array, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sd = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(k1, (d, H * hd)) * sd).astype(PDTYPE),
        "wk": (jax.random.normal(k2, (d, K * hd)) * sd).astype(PDTYPE),
        "wv": (jax.random.normal(k3, (d, K * hd)) * sd).astype(PDTYPE),
        "wo": (jax.random.normal(k4, (H * hd, d)) / math.sqrt(H * hd)).astype(PDTYPE),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), PDTYPE)
        p["bk"] = jnp.zeros((K * hd,), PDTYPE)
        p["bv"] = jnp.zeros((K * hd,), PDTYPE)
    return p


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = shard(q.reshape(B, S, H, hd), "batch", "seq", "heads", None)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    return q, k, v


def _sketched_two_span(o, qg, kt, vt, win, tail, sketch, scale):
    """Two-span long-context attention (serve/kv_sketch.py).

    ``o`` is the exact-path attention output (B, Sq, K, R, hd) computed
    with the legacy full causal mask; ``win`` is the exact-window
    visibility mask (folded positions excluded), broadcastable to the
    (B, K, R, Sq, Sk) score tensor.  Computes f32 online-softmax
    statistics over the window span, queries the slot's FCS tail tables
    for the folded span, merges the two, and selects the merged output
    ONLY for slots that have folded anything — slots with fold_base == 0
    keep ``o`` verbatim (elementwise where), which is the bitwise
    regression anchor: window >= context runs match a sketch-free engine
    exactly.  The window span always contains the query's own position,
    so its statistics are never empty."""
    # deferred: serve/__init__ -> engine -> scheduler -> transformer ->
    # moe -> layers would otherwise cycle at import time
    from repro.serve import kv_sketch as _kvs
    fold_base = sketch["fold_base"]
    sw = jnp.einsum("bqkrh,bskh->bkrqs", qg, kt).astype(jnp.float32) * scale
    sw = jnp.where(win, sw, -1e30)
    m_e, l_e, acc_e = _kvs.exact_span_stats(sw, vt, win)
    m_t, l_t, acc_t = _kvs.tail_attend(qg, tail["k"], tail["v"],
                                       sketch["onehot"], fold_base, scale)
    merged = _kvs.merge_spans(m_e, l_e, acc_e, m_t, l_t, acc_t)
    merged = merged.transpose(0, 3, 1, 2, 4).astype(o.dtype)  # (B,Sq,K,R,hd)
    sel = (fold_base > 0)[:, None, None, None, None]
    return jnp.where(sel, merged, o)


def _kernel_paged_attention(qg, k, v, tables, start, tail, sketch,
                            kernels):
    """Flash-decode kernel path shared by the three paged shapes
    (kernels/paged_attention.py): attend straight through the block
    table — the dense gathered KV copy never materializes.  ``kernels``
    is the resolved ``kops.kernel_mode`` ("compiled" / "interpret").  qg:
    (B, Sq, K, R, hd); k/v: the updated (NB, bs, K, hd) pools; start:
    (B,) position of each slot's query row 0.  With ``tail``/``sketch``
    the kernel covers the exact window [fold_base, start + i] and the
    FCS tail supplies the folded span, merged with online-softmax
    statistics; slots with fold_base == 0 keep the pure kernel output
    bitwise (same anchor as _sketched_two_span).  Returns
    (B, Sq, K, R, hd) in qg's dtype."""
    B = qg.shape[0]
    fb = (sketch["fold_base"] if tail is not None
          else jnp.zeros((B,), jnp.int32))
    m_e, l_e, acc_e = kops.paged_attention_op(qg, k, v, tables, start, fb,
                                              kernels=kernels)
    o = acc_e / jnp.maximum(l_e, 1e-30)[..., None]      # (B,K,R,Sq,hd)
    if tail is not None:
        from repro.serve import kv_sketch as _kvs
        scale = 1.0 / math.sqrt(qg.shape[-1])
        m_t, l_t, acc_t = _kvs.tail_attend(qg, tail["k"], tail["v"],
                                           sketch["onehot"], fb, scale)
        merged = _kvs.merge_spans(m_e, l_e, acc_e, m_t, l_t, acc_t)
        o = jnp.where((fb > 0)[:, None, None, None, None], merged, o)
    return o.transpose(0, 3, 1, 2, 4).astype(qg.dtype)  # (B,Sq,K,R,hd)


def _gqa_scores_softmax_out(q, k, v, mask, scale):
    """q: (B,Sq,K,R,hd); k,v: (B,Sk,K,hd); mask: bool, broadcastable to
    the (B,K,R,Sq,Sk) score tensor, or None.
    Grouped form used on the decode path (reads each KV head once)."""
    s = jnp.einsum("bqkrh,bskh->bkrqs", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkrqs,bskh->bqkrh", p, v)


def causal_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                     positions: jax.Array, kv_chunk: int = 1024) -> jax.Array:
    """Full-sequence causal attention: online-softmax (flash-style) over KV
    chunks, scanned.  Query rows stay fully data/context-sharded — every
    device participates in every KV-chunk iteration (KV is replicated /
    all-gathered, which is cheap for GQA), so context sharding of the
    sequence never serializes the scan.  Per-chunk bodies rematerialize in
    the backward."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    R = H // K
    q, k, v = _project_qkv(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # replicate KV at the (small) K-head stage: the context-sharded seq dim
    # is all-gathered here, BEFORE the R-fold head broadcast
    k = shard(k, "batch", "kv_seq", None, None)
    v = shard(v, "batch", "kv_seq", None, None)
    if R > 1:
        k = jnp.repeat(k, R, axis=2)
        v = jnp.repeat(v, R, axis=2)
    o = _flash_attention(q, k, v, min(kv_chunk, S))
    o = o.reshape(B, S, H * hd)
    o = shard(o, "batch", "seq", None)
    return jnp.einsum("bsh,hd->bsd", o, p["wo"])


def _flash_attention(q, k, v, kc):
    """Online-softmax causal attention.  q,k,v: (B,S,H,hd) (kv already
    expanded to H heads).  Scans KV chunks of size kc; the causal mask is
    applied per chunk.  f32 running (max, sum, acc) statistics."""
    B, S, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    pad = (-S) % kc
    kp, vp = k, v
    if pad:  # padded keys are masked out by the causal test below
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = (S + pad) // kc
    kg = kp.reshape(B, nk, kc, H, hd).transpose(1, 0, 2, 3, 4)
    vg = vp.reshape(B, nk, kc, H, hd).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(S)

    def _pin(m, l, acc):  # keep scan carries on the q/context sharding
        m = shard(m, "batch", None, "seq")
        l = shard(l, "batch", None, "seq")
        acc = shard(acc, "batch", "seq", None, None)
        return m, l, acc

    @jax.checkpoint
    def chunk_fn(carry, inp):
        m, l, acc = carry                       # (B,H,S), (B,H,S), (B,S,H,hd)
        cj, kj, vj = inp
        k_pos = cj * kc + jnp.arange(kc)
        mask = q_pos[:, None] >= k_pos[None, :]              # (S, kc)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kj).astype(jnp.float32) * scale
        s = jnp.where(mask[None, None], s, -1e30)
        s = shard(s, "batch", None, "seq", None)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(vj.dtype), vj).astype(jnp.float32)
        return _pin(m_new, l, acc), None

    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(chunk_fn, _pin(m0, l0, a0),
                                  (jnp.arange(nk), kg, vg))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _project_qkv_rope(p: dict, x: jax.Array, cfg: ModelConfig,
                      positions: jax.Array):
    """Shared decode/chunk-prefill QKV block: project (+bias), split
    heads, rope q and k at ``positions`` ((S,) or (B, S)).  One home for
    this math keeps the chunked-prefill path bit-identical to decode."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    kn = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    vn = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, kn, vn = q + p["bq"], kn + p["bk"], vn + p["bv"]
    q = rope(q.reshape(B, S, H, hd), positions, cfg.rope_theta)
    kn = rope(kn.reshape(B, S, K, hd), positions, cfg.rope_theta)
    return q, kn, vn.reshape(B, S, K, hd)


def decode_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                     cache: dict, index: jax.Array,
                     tables: Optional[jax.Array] = None,
                     tail: Optional[dict] = None,
                     sketch: Optional[dict] = None,
                     kernels: Optional[str] = None
                     ) -> Tuple[jax.Array, dict]:
    """Single-token decode against a KV cache.

    Dense mode (``tables=None``): cache {"k": (B, S_max, K, hd), "v": ...};
    ``index`` is the current position — a scalar (whole batch at the same
    position, the classic synchronized-decode path) or a (B,) vector of
    per-slot positions (the continuous-batching path: every slot writes
    its KV row at its own position and attends under its own causal mask).

    Paged mode (``tables`` given): cache is the shared block pool
    {"k": (num_blocks, block_size, K, hd), "v": ...} and ``tables`` is the
    (B, blocks_per_slot) int32 block table mapping each slot's logical
    block index to a physical pool block (entries == num_blocks are
    unallocated).  Each slot's new KV row scatters into
    table[pos // bs][pos % bs] (out-of-range physical ids are dropped, so
    retired slots with invalidated tables write nowhere), and the slot
    attends over its gathered blocks under the same per-slot causal mask.
    Returns (out (B,1,d), updated cache).
    """
    if tables is not None:
        return _paged_decode_attention(p, x, cfg, cache, index, tables,
                                       tail, sketch, kernels)
    B, one, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    R = H // K
    per_slot = jnp.ndim(index) == 1
    pos = (index[:, None].astype(jnp.int32) if per_slot
           else jnp.full((1,), index, jnp.int32))
    q, kn, vn = _project_qkv_rope(p, x, cfg, pos)
    if per_slot:
        slots = jnp.arange(B, dtype=jnp.int32)
        k = cache["k"].at[slots, index].set(kn[:, 0].astype(cache["k"].dtype))
        v = cache["v"].at[slots, index].set(vn[:, 0].astype(cache["v"].dtype))
    else:
        k = jax.lax.dynamic_update_slice(
            cache["k"], kn.astype(cache["k"].dtype), (0, index, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache["v"], vn.astype(cache["v"].dtype), (0, index, 0, 0))
    k = shard(k, "batch", "kv_seq", "kv_heads", None)
    v = shard(v, "batch", "kv_seq", "kv_heads", None)
    S = k.shape[1]
    qg = q.reshape(B, 1, K, R, hd)
    if per_slot:
        mask = (jnp.arange(S)[None, :] <= index[:, None]
                )[:, None, None, None, :]                # (B,1,1,1,S)
    else:
        mask = (jnp.arange(S) <= index)[None, :]         # (1,S) -> broadcast
    o = _gqa_scores_softmax_out(qg, k, v, mask, 1.0 / math.sqrt(hd))
    o = o.reshape(B, 1, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, {"k": k, "v": v}


def _paged_decode_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                            cache: dict, index: jax.Array,
                            tables: jax.Array,
                            tail: Optional[dict] = None,
                            sketch: Optional[dict] = None,
                            kernels: Optional[str] = None
                            ) -> Tuple[jax.Array, dict]:
    """Paged single-token decode: scatter each slot's new KV row through
    its block table, then attend — in one flash-decode Pallas pass over
    the table (``kernels`` = "compiled" / "interpret", a resolved
    ``kops.kernel_mode``) or, with ``kernels`` None, by gathering the
    slot's blocks dense and softmaxing in jnp (the oracle path).  See
    decode_attention.
    With ``tail``/``sketch`` (serve/kv_sketch.py) the attention becomes
    two-span: exact over [fold_base, index], sketched over [0, fold_base)."""
    B, _, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    R = H // K
    NB, bs = cache["k"].shape[0], cache["k"].shape[1]
    nb_slot = tables.shape[1]
    pos = index[:, None].astype(jnp.int32)
    q, kn, vn = _project_qkv_rope(p, x, cfg, pos)
    blk = (index // bs).astype(jnp.int32)
    phys = jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0]  # (B,)
    off = (index % bs).astype(jnp.int32)
    # unallocated/invalidated table entries hold NB: the scatter drops the
    # write, so an inactive slot's idle decode step mutates nothing — pool
    # blocks can be freed and reused the moment their refcount hits zero.
    k = cache["k"].at[phys, off].set(
        kn[:, 0].astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[phys, off].set(
        vn[:, 0].astype(cache["v"].dtype), mode="drop")
    k = shard(k, "kv_blocks", None, "kv_heads", None)
    v = shard(v, "kv_blocks", None, "kv_heads", None)
    qg = q.reshape(B, 1, K, R, hd)
    scale = 1.0 / math.sqrt(hd)
    if kernels:
        o = _kernel_paged_attention(qg, k, v, tables,
                                    index.astype(jnp.int32), tail, sketch,
                                    kernels)
    else:
        # gather the slot's logical KV row; invalid blocks read as zeros
        # and sit at positions the per-slot causal mask never exposes
        kt = jnp.take(k, tables, axis=0, mode="fill", fill_value=0)
        vt = jnp.take(v, tables, axis=0, mode="fill", fill_value=0)
        S = nb_slot * bs
        kt = shard(kt.reshape(B, S, K, hd), "batch", "kv_seq", "kv_heads",
                   None)
        vt = shard(vt.reshape(B, S, K, hd), "batch", "kv_seq", "kv_heads",
                   None)
        mask = (jnp.arange(S)[None, :] <= index[:, None]
                )[:, None, None, None, :]                # (B,1,1,1,S)
        o = _gqa_scores_softmax_out(qg, kt, vt, mask, scale)
        if tail is not None:
            win = mask & (jnp.arange(S)[None, :] >=
                          sketch["fold_base"][:, None])[:, None, None,
                                                        None, :]
            o = _sketched_two_span(o, qg, kt, vt, win, tail, sketch, scale)
    o = o.reshape(B, 1, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, {"k": k, "v": v}


def verify_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                     cache: dict, index: jax.Array, tables: jax.Array,
                     tail: Optional[dict] = None,
                     sketch: Optional[dict] = None,
                     kernels: Optional[str] = None
                     ) -> Tuple[jax.Array, dict]:
    """Multi-query paged decode (speculative verify).

    x: (B, C, d) — every slot feeds C tokens (its last committed token
    followed by C-1 draft proposals) at absolute positions
    index[b] .. index[b] + C - 1.  Structurally this is ``chunk_attention``
    batched over slots: each slot's C new KV rows scatter through its own
    block-table row (out-of-range or invalidated physical ids drop, so
    retired slots and overhang rows mutate nothing), then each of its C
    queries attends causally — key row j visible to query i iff
    j <= index[b] + i — over the slot's gathered blocks.  The per-row
    projections and masks match single-token paged decode exactly, so a
    verified-and-accepted position produces the same logits a plain
    decode step at that position would.  Returns (out (B, C, d), pool).
    """
    B, C, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    R = H // K
    NB, bs = cache["k"].shape[0], cache["k"].shape[1]
    nb_slot = tables.shape[1]
    positions = (index[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
                 ).astype(jnp.int32)                     # (B, C)
    q, kn, vn = _project_qkv_rope(p, x, cfg, positions)
    blk = positions // bs
    phys = jnp.take_along_axis(tables, jnp.minimum(blk, nb_slot - 1),
                               axis=1)
    # rows past the slot's table (speculative overhang at max_seq) must
    # DROP, not clamp into the last reserved block
    phys = jnp.where(blk < nb_slot, phys, NB)
    off = positions % bs
    k = cache["k"].at[phys, off].set(
        kn.astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[phys, off].set(
        vn.astype(cache["v"].dtype), mode="drop")
    k = shard(k, "kv_blocks", None, "kv_heads", None)
    v = shard(v, "kv_blocks", None, "kv_heads", None)
    qg = q.reshape(B, C, K, R, hd)
    scale = 1.0 / math.sqrt(hd)
    if kernels:
        # kernel row i of slot b sees key positions <= index[b] + i —
        # identical per-row math to a single-token decode at that
        # position, the bitwise spec-identity anchor
        o = _kernel_paged_attention(qg, k, v, tables,
                                    index.astype(jnp.int32), tail, sketch,
                                    kernels)
    else:
        kt = jnp.take(k, tables, axis=0, mode="fill", fill_value=0)
        vt = jnp.take(v, tables, axis=0, mode="fill", fill_value=0)
        S = nb_slot * bs
        kt = shard(kt.reshape(B, S, K, hd), "batch", "kv_seq", "kv_heads",
                   None)
        vt = shard(vt.reshape(B, S, K, hd), "batch", "kv_seq", "kv_heads",
                   None)
        mask = (jnp.arange(S)[None, None, :] <= positions[:, :, None]
                )[:, None, None]                         # (B,1,1,C,S)
        o = _gqa_scores_softmax_out(qg, kt, vt, mask, scale)
        if tail is not None:
            win = mask & (jnp.arange(S)[None, :] >=
                          sketch["fold_base"][:, None])[:, None, None,
                                                        None, :]
            o = _sketched_two_span(o, qg, kt, vt, win, tail, sketch, scale)
    o = o.reshape(B, C, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, {"k": k, "v": v}


def chunk_attention(p: dict, x: jax.Array, cfg: ModelConfig,
                    cache: dict, table: jax.Array, start: jax.Array,
                    tail: Optional[dict] = None,
                    sketch: Optional[dict] = None,
                    kernels: Optional[str] = None
                    ) -> Tuple[jax.Array, dict]:
    """Multi-token chunk against the paged slot KV (chunked prefill).

    x: (1, C, d) — one prompt chunk for one slot.  ``cache`` is the shared
    block pool {"k": (num_blocks, block_size, K, hd), "v": ...} and
    ``table`` the slot's (blocks_per_slot,) block-table row.  KV rows for
    absolute positions [start, start + C) scatter into the slot's blocks
    (rows mapping to unallocated table entries — e.g. tail-chunk zero
    padding beyond the request's reserved blocks — are dropped), then every
    chunk query attends causally against the slot's gathered blocks, so a
    chunk at offset ``start`` sees both earlier chunks and any shared
    prefix blocks referenced by the table.  ``table`` and ``start`` are
    traced — one compilation serves every slot and offset.
    Returns (out (1, C, d), updated pool).
    """
    _, C, _ = x.shape
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    R = H // K
    NB, bs = cache["k"].shape[0], cache["k"].shape[1]
    nb_slot = table.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    q, kn, vn = _project_qkv_rope(p, x, cfg, positions)
    phys = jnp.take(table, positions // bs, mode="fill", fill_value=NB)
    k = cache["k"].at[phys, positions % bs].set(
        kn[0].astype(cache["k"].dtype), mode="drop")
    v = cache["v"].at[phys, positions % bs].set(
        vn[0].astype(cache["v"].dtype), mode="drop")
    # same placement pin decode applies: the pool layout from
    # serve_state_pspecs must survive the chunked-prefill update
    k = shard(k, "kv_blocks", None, "kv_heads", None)
    v = shard(v, "kv_blocks", None, "kv_heads", None)
    qg = q.reshape(1, C, K, R, hd)
    scale = 1.0 / math.sqrt(hd)
    if kernels:
        o = _kernel_paged_attention(qg, k, v, table[None],
                                    jnp.reshape(start, (1,)).astype(
                                        jnp.int32), tail, sketch, kernels)
    else:
        ks = jnp.take(k, table, axis=0, mode="fill", fill_value=0)
        vs = jnp.take(v, table, axis=0, mode="fill", fill_value=0)
        S = nb_slot * bs
        ks = ks.reshape(1, S, K, hd)
        vs = vs.reshape(1, S, K, hd)
        # causal over absolute positions: key row j visible to chunk
        # query i iff j <= start + i (earlier chunks / shared prefix
        # blocks included)
        mask = (jnp.arange(S)[None, :] <= positions[:, None]
                )[None, None, None]
        o = _gqa_scores_softmax_out(qg, ks, vs, mask, scale)
        if tail is not None:
            win = mask & (jnp.arange(S)[None, :] >=
                          sketch["fold_base"][:, None])[:, None, None,
                                                        None, :]
            o = _sketched_two_span(o, qg, ks, vs, win, tail, sketch, scale)
    o = o.reshape(1, C, H * hd)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"])
    return out, {"k": k, "v": v}


def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype=jnp.bfloat16) -> dict:
    hd = cfg.resolved_head_dim
    K = cfg.num_kv_heads
    z = jnp.zeros((batch, max_seq, K, hd), dtype)
    return {"k": z, "v": z}


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                        dtype=jnp.bfloat16) -> dict:
    """Per-layer paged KV block pool: physical blocks are position-free
    storage; a slot's block table gives them logical order."""
    hd = cfg.resolved_head_dim
    K = cfg.num_kv_heads
    z = jnp.zeros((num_blocks, block_size, K, hd), dtype)
    return {"k": z, "v": z}


# ---------------------------------------------------------------------------
# GLU FFN
# ---------------------------------------------------------------------------


def init_glu_ffn(key: jax.Array, d: int, ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": (jax.random.normal(k1, (d, ff)) / math.sqrt(d)).astype(PDTYPE),
        "w_up": (jax.random.normal(k2, (d, ff)) / math.sqrt(d)).astype(PDTYPE),
        "w_down": (jax.random.normal(k3, (ff, d)) / math.sqrt(ff)).astype(PDTYPE),
    }


def _act(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def glu_ffn(p: dict, x: jax.Array, act: str) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = _act(act)(g) * u
    h = shard(h, "batch", "seq", "ff")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding + LM head (dense or FCS-sketched)
# ---------------------------------------------------------------------------


def init_embedding(key: jax.Array, cfg: ModelConfig) -> jax.Array:
    return (jax.random.normal(key, (cfg.padded_vocab, cfg.d_model)) * 0.02
            ).astype(PDTYPE)


def embed_tokens(emb: jax.Array, tokens: jax.Array) -> jax.Array:
    x = jnp.take(emb, tokens, axis=0)
    return shard(x, "batch", "seq", "embed")


def head_sketch_len(cfg: ModelConfig) -> int:
    return cfg.sketch.head_hash_len or cfg.d_model // 4


def init_head(key: jax.Array, cfg: ModelConfig) -> Optional[jax.Array]:
    if cfg.sketch.sketched_head:
        # FCS-sketched LM head (paper Section 4.2, CP-TRL): the projection is
        # trained directly in the J~-dim sketch space; activations are
        # count-sketched per token (FCS degenerates to CS for order-1
        # activations).  CR = d_model / J~.
        J = head_sketch_len(cfg)
        return (jax.random.normal(key, (J, cfg.padded_vocab))
                / math.sqrt(J)).astype(PDTYPE)
    if cfg.tie_embeddings:
        return None
    return (jax.random.normal(key, (cfg.d_model, cfg.padded_vocab))
            / math.sqrt(cfg.d_model)).astype(PDTYPE)


@functools.lru_cache(maxsize=None)
def _head_hash_tables(seed: int, d: int, J: int):
    """Host-side (trace-safe) 2-wise-independent hash tables."""
    import numpy as np
    from repro.core.hashes import PRIME
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    ah, bh = rng.randint(1, PRIME, dtype=np.int64), rng.randint(0, PRIME, dtype=np.int64)
    as_, bs = rng.randint(1, PRIME, dtype=np.int64), rng.randint(0, PRIME, dtype=np.int64)
    idx = np.arange(d, dtype=np.int64)
    h = (((ah * idx + bh) % PRIME) % J).astype(np.int32)
    sg = (1.0 - 2.0 * (((as_ * idx + bs) % PRIME) % 2)).astype(np.float32)
    return h, sg


def _head_io(params: dict, x: jax.Array, cfg: ModelConfig):
    """Returns (x_eff, W_eff) for the vocab projection, applying the FCS
    activation sketch when the sketched head is enabled."""
    if cfg.sketch.sketched_head:
        J = head_sketch_len(cfg)
        h, sg = _head_hash_tables(cfg.sketch.seed, cfg.d_model, J)
        onehot = (jax.nn.one_hot(h, J, dtype=x.dtype)
                  * sg[:, None].astype(x.dtype))
        xs = jnp.einsum("bsd,dj->bsj", x, onehot)
        return xs, params["head"]
    head = params["head"] if params.get("head") is not None \
        else params["embed"].T
    return x, head


@jax.named_scope("lm_head")
def logits_fn(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final-hidden -> vocab logits (f32)."""
    x, head = _head_io(params, x, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)
    return shard(logits, "batch", "seq", "vocab")


def cross_entropy(params: dict, x: jax.Array, labels: jax.Array,
                  cfg: ModelConfig, vocab_chunk: int = 8192) -> jax.Array:
    """Vocab-chunked online-logsumexp CE: the (B, S, V) f32 logits never
    fully materialize, and the chunked axis is the (replicated) vocab, so
    data/context sharding of tokens never serializes the scan.  Padded vocab
    rows carry random-init weights; they only add a handful of terms to the
    logsumexp (trained to -inf naturally) and are never produced as labels."""
    B, S, _ = x.shape
    x, head = _head_io(params, x, cfg)
    V = head.shape[-1]
    vc = min(vocab_chunk, V)
    pad = (-V) % vc
    if pad:
        head = jnp.pad(head, ((0, 0), (0, pad)))
    nv = (V + pad) // vc
    hg = head.reshape(-1, nv, vc).transpose(1, 0, 2)    # (nv, d, vc)

    @jax.checkpoint
    def chunk(carry, inp):
        m, l, gold = carry                              # (B,S),(B,S),(B,S)
        cj, hj = inp
        logits = jnp.einsum("bsd,dv->bsv", x, hj).astype(jnp.float32)
        if pad:  # mask out padded columns in the final chunk
            col = cj * vc + jnp.arange(vc)
            logits = jnp.where(col[None, None, :] < V, logits, -1e30)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1)
        idx = labels - cj * vc
        valid = (idx >= 0) & (idx < vc)
        g = jnp.take_along_axis(logits, jnp.clip(idx, 0, vc - 1)[..., None],
                                axis=-1)[..., 0]
        gold = gold + jnp.where(valid, g, 0.0)
        return (m_new, l, gold), None

    m0 = jnp.full((B, S), -1e30, jnp.float32)
    z0 = jnp.zeros((B, S), jnp.float32)
    (m, l, gold), _ = jax.lax.scan(chunk, (m0, z0, z0), (jnp.arange(nv), hg))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return jnp.mean(lse - gold)
