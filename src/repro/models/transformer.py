"""Model assembly: scan-over-layers stacks for all five families, with
train / prefill / decode paths sharing the same per-block code.

Param tree layout (family-dependent "blocks" subtree; all per-layer leaves
stacked on a leading L axis so the stack lowers to one lax.scan):

  {"embed": (Vp, d), "head": (d, Vp)|None, "final_norm": (d,), "blocks": ...}

Caches:
  dense/moe/audio/vlm : {"kv": {"k": (L,B,Smax,K,hd), "v": ...}} (dense) or
                        {"kv": {"k": (L,NB,bs,K,hd), "v": ...}} block pool
                        indexed through per-slot block tables (paged serve)
  ssm (xlstm)         : {"mlstm": <stacked states>, "slstm": <stacked states>}
  hybrid (zamba2)     : {"mamba": <stacked>, "shared_kv": (G,B,Smax,K,hd)x2}
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as ly
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.sharding import shard

Params = Dict[str, Any]


def _stack_init(key: jax.Array, n: int, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    params: Params = {
        "embed": ly.init_embedding(k_emb, cfg),
        "head": ly.init_head(k_head, cfg),
        "final_norm": ly.init_rms_norm(cfg.d_model),
        "blocks": _init_blocks(k_blocks, cfg),
    }
    return params


def _init_blocks(key: jax.Array, cfg: ModelConfig) -> Params:
    L, d = cfg.num_layers, cfg.d_model
    if cfg.family in ("dense", "audio", "vlm"):
        k1, k2 = jax.random.split(key)
        return {
            "attn": _stack_init(k1, L, lambda k: ly.init_attention(k, cfg)),
            "ffn": _stack_init(k2, L, lambda k: ly.init_glu_ffn(k, d, cfg.d_ff)),
            "norm1": jnp.zeros((L, d), ly.PDTYPE),
            "norm2": jnp.zeros((L, d), ly.PDTYPE),
        }
    if cfg.family == "moe":
        k1, k2 = jax.random.split(key)
        return {
            "attn": _stack_init(k1, L, lambda k: ly.init_attention(k, cfg)),
            "moe": _stack_init(k2, L, lambda k: moe_mod.init_moe(k, cfg)),
            "norm1": jnp.zeros((L, d), ly.PDTYPE),
            "norm2": jnp.zeros((L, d), ly.PDTYPE),
        }
    if cfg.family == "ssm":
        x = cfg.xlstm
        G = L // (x.m_per_group + x.s_per_group)
        n_m, n_s = G * x.m_per_group, G * x.s_per_group
        k1, k2 = jax.random.split(key)
        return {
            "mlstm": _stack_init(k1, n_m, lambda k: ssm_mod.init_mlstm(k, cfg)),
            "slstm": _stack_init(k2, n_s, lambda k: ssm_mod.init_slstm(k, cfg)),
        }
    if cfg.family == "hybrid":
        hb = cfg.hybrid
        G = L // hb.mamba_per_group
        k1, k2, k3 = jax.random.split(key, 3)

        def init_shared(k):
            ka, kf = jax.random.split(k)
            return {
                "attn": ly.init_attention(ka, cfg),
                "ffn": ly.init_glu_ffn(kf, d, cfg.d_ff),
                "norm1": jnp.zeros((d,), ly.PDTYPE),
                "norm2": jnp.zeros((d,), ly.PDTYPE),
            }

        return {
            "mamba": _stack_init(k1, L, lambda k: ssm_mod.init_mamba2(k, cfg)),
            "shared": _stack_init(k2, hb.num_shared_blocks, init_shared),
        }
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Block application helpers
# ---------------------------------------------------------------------------


def _dense_block(p_l, x, cfg: ModelConfig, positions, cache_l, index, mode,
                 tables=None, tail_l=None, sketch=None, kernels=None):
    """One attention+FFN (or attention+MoE) block. Returns (x, aux, cache).
    ``tail_l``/``sketch``: per-layer FCS tail tables + fold state for
    two-span long-context decode (serve/kv_sketch.py); read-only here.
    ``kernels``: paged modes — a resolved ``kops.kernel_mode``:
    "compiled" / "interpret" route attention through the flash-decode
    Pallas kernel, None through the jnp gather path."""
    with jax.named_scope("attention"):
        h = ly.rms_norm(x, p_l["norm1"], cfg.norm_eps)
        new_cache = None
        if mode == "decode":
            a, new_cache = ly.decode_attention(p_l["attn"], h, cfg, cache_l,
                                               index, tables=tables,
                                               tail=tail_l, sketch=sketch,
                                               kernels=kernels)
        elif mode == "verify":
            a, new_cache = ly.verify_attention(p_l["attn"], h, cfg, cache_l,
                                               index, tables, tail=tail_l,
                                               sketch=sketch,
                                               kernels=kernels)
        elif mode == "chunk":
            a, new_cache = ly.chunk_attention(p_l["attn"], h, cfg, cache_l,
                                              tables, index, tail=tail_l,
                                              sketch=sketch,
                                              kernels=kernels)
        else:
            a = ly.causal_attention(p_l["attn"], h, cfg, positions)
            if mode == "prefill":
                # re-derive roped k/v for the cache (cheap vs attention
                # itself)
                q, k, v = ly._project_qkv(p_l["attn"], h, cfg)
                del q
                k = ly.rope(k, positions, cfg.rope_theta)
                new_cache = {"k": k, "v": v}
    x = x + a
    with jax.named_scope("mlp"):
        h = ly.rms_norm(x, p_l["norm2"], cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if cfg.family == "moe":
            f, aux = moe_mod.moe_ffn(p_l["moe"], h, cfg)
        else:
            f = ly.glu_ffn(p_l["ffn"], h, cfg.act)
    return x + f, aux, new_cache


# ---------------------------------------------------------------------------
# Forward (all modes)
# ---------------------------------------------------------------------------


def forward(params: Params, x: jax.Array, cfg: ModelConfig,
            mode: str = "train", cache: Optional[dict] = None,
            index: Optional[jax.Array] = None,
            tables: Optional[jax.Array] = None,
            sketch: Optional[dict] = None,
            kernels: Optional[str] = None
            ) -> Tuple[jax.Array, jax.Array, Optional[dict]]:
    """x: embedded inputs (B, S, d).  Returns (hidden, aux_loss, cache).

    ``sketch`` (attention families, paged modes only): {"fold_base": (B,)
    int32, "onehot": (Z, T, C)} — enables two-span decode against the
    cache's "tail" FCS tables (serve/kv_sketch.py).

    ``kernels`` (paged modes only): static attention implementation, a
    resolved ``kops.kernel_mode`` — "compiled" runs the flash-decode
    paged Pallas kernel (kernels/paged_attention.py), "interpret" the
    same kernel in interpret mode (CPU validation), None the jnp
    gather-then-softmax path.

    Modes: "train" / "prefill" (full-sequence), "decode" (single token per
    slot against the cache — paged through per-slot block ``tables`` when
    given, dense otherwise), "chunk" (multi-token prompt chunk written
    into the paged pool through the slot's (blocks_per_slot,) ``tables``
    row at offset ``index`` — the chunked prefill building block;
    attention families only), "verify" (speculative decode: every slot
    feeds S tokens at per-slot start positions ``index`` ((B,)) through
    its block-table row — multi-query paged decode; attention families
    only).
    """
    B, S, d = x.shape
    if mode not in ("decode", "chunk", "verify"):
        x = shard(x, "batch", "residual", None)
    if mode == "verify":
        positions = None     # per-slot (B,) starts; handled in-layer
    else:
        positions = (jnp.arange(S) if index is None
                     else jnp.arange(S) + index)
    fam = cfg.family
    if fam in ("dense", "audio", "vlm", "moe"):
        y, aux, new_cache = _forward_attn_stack(params, x, cfg, positions,
                                                mode, cache, index, tables,
                                                sketch, kernels)
    elif mode in ("chunk", "verify"):
        raise ValueError(f"mode {mode!r} needs a kv-cache family, "
                         f"got {fam!r}")
    elif fam == "ssm":
        y, aux, new_cache = _forward_xlstm(params, x, cfg, mode, cache)
    elif fam == "hybrid":
        y, aux, new_cache = _forward_zamba(params, x, cfg, positions, mode,
                                           cache, index)
    else:
        raise ValueError(fam)
    with jax.named_scope("lm_head"):
        y = ly.rms_norm(y, params["final_norm"], cfg.norm_eps)
    return y, aux, new_cache


def _forward_attn_stack(params, x, cfg, positions, mode, cache, index,
                        tables=None, sketch=None, kernels=None):
    blocks = params["blocks"]

    if mode in ("decode", "chunk", "verify"):
        sketched = sketch is not None and "tail" in (cache or {})

        def body(carry, xs):
            h, aux = carry
            p_l, c_l = xs[0], xs[1]
            t_l = xs[2] if sketched else None
            h, a, nc = _dense_block(p_l, h, cfg, positions, c_l, index, mode,
                                    tables, tail_l=t_l, sketch=sketch,
                                    kernels=kernels)
            return (h, aux + a), nc

        xs = ((blocks, cache["kv"], cache["tail"]) if sketched
              else (blocks, cache["kv"]))
        (y, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    xs)
        new_cache = {"kv": kv}
        if "tail" in (cache or {}):
            # tail tables are read-only inside the stack (folds happen in
            # the serve chunk, outside forward) — reattach unchanged
            new_cache["tail"] = cache["tail"]
        return y, aux, new_cache

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(carry, p_l):
        h, aux = carry
        h, a, nc = _dense_block(p_l, h, cfg, positions, None, index, mode)
        h = shard(h, "batch", "residual", None)
        return (h, aux + a), nc

    (y, aux), kv = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    new_cache = {"kv": kv} if mode == "prefill" else None
    return y, aux, new_cache


def _forward_xlstm(params, x, cfg, mode, cache):
    xl = cfg.xlstm
    G = cfg.num_layers // (xl.m_per_group + xl.s_per_group)
    m_per, s_per = xl.m_per_group, xl.s_per_group
    blocks = params["blocks"]
    # reshape stacked (n_m, ...) -> (G, m_per, ...)
    ml = jax.tree.map(lambda a: a.reshape(G, m_per, *a.shape[1:]),
                      blocks["mlstm"])
    sl = jax.tree.map(lambda a: a.reshape(G, s_per, *a.shape[1:]),
                      blocks["slstm"])
    want_state = mode in ("prefill", "decode")
    m_state = s_state = None
    if mode == "decode":
        m_state = jax.tree.map(
            lambda a: a.reshape(G, m_per, *a.shape[1:]), cache["mlstm"])
        s_state = jax.tree.map(
            lambda a: a.reshape(G, s_per, *a.shape[1:]), cache["slstm"])

    def group(carry, xs):
        h = carry
        p_m, p_s = xs[0], xs[1]
        st_m = xs[2] if mode == "decode" else None
        st_s = xs[3] if mode == "decode" else None

        def m_body(hh, mxs):
            p_i = mxs[0]
            st_i = mxs[1] if mode == "decode" else None
            out, ns = ssm_mod.mlstm_block(
                p_i, hh, cfg, state=st_i, q_chunk=512,
                want_state=(mode == "prefill"))
            return hh + out, ns

        def s_body(hh, sxs):
            p_i = sxs[0]
            st_i = sxs[1] if mode == "decode" else None
            out, ns = ssm_mod.slstm_block(
                p_i, hh, cfg, state=st_i, want_state=(mode == "prefill"))
            return hh + out, ns

        if mode == "train":
            m_body = jax.checkpoint(m_body)
            s_body = jax.checkpoint(s_body)
        h, m_ns = jax.lax.scan(m_body, h,
                               (p_m, st_m) if mode == "decode" else (p_m,))
        h, s_ns = jax.lax.scan(s_body, h,
                               (p_s, st_s) if mode == "decode" else (p_s,))
        if mode != "decode":
            h = shard(h, "batch", "residual", None)
        return h, (m_ns, s_ns)

    xs = (ml, sl) if mode != "decode" else (ml, sl, m_state, s_state)
    y, (m_ns, s_ns) = jax.lax.scan(group, x, xs)
    new_cache = None
    if want_state and m_ns is not None:
        flat = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), t)
        new_cache = {"mlstm": flat(m_ns), "slstm": flat(s_ns)}
    aux = jnp.zeros((), jnp.float32)
    return y, aux, new_cache


def _forward_zamba(params, x, cfg, positions, mode, cache, index):
    hb = cfg.hybrid
    G = cfg.num_layers // hb.mamba_per_group
    blocks = params["blocks"]
    mamba = jax.tree.map(
        lambda a: a.reshape(G, hb.mamba_per_group, *a.shape[1:]),
        blocks["mamba"])
    shared = blocks["shared"]
    m_state = None
    if mode == "decode":
        m_state = jax.tree.map(
            lambda a: a.reshape(G, hb.mamba_per_group, *a.shape[1:]),
            cache["mamba"])

    def group(carry, xs):
        h = carry
        gi = xs[0]
        p_m = xs[1]
        st_m = xs[2] if mode == "decode" else None
        kv_g = xs[3] if mode == "decode" else None

        def m_body(hh, mxs):
            p_i = mxs[0]
            st_i = mxs[1] if mode == "decode" else None
            out, ns = ssm_mod.mamba2_block(
                p_i, hh, cfg, state=st_i, want_state=(mode == "prefill"))
            return hh + out, ns

        if mode == "train":
            m_body = jax.checkpoint(m_body)
        h, m_ns = jax.lax.scan(m_body, h,
                               (p_m, st_m) if mode == "decode" else (p_m,))
        # shared transformer block, round-robin over the distinct blocks
        sel = gi % hb.num_shared_blocks
        p_s = jax.tree.map(lambda a: a[sel], shared)

        def shared_apply(p_b, hh):
            out, _, kv = _dense_block(p_b, hh, cfg, positions, kv_g, index,
                                      mode)
            return out, kv

        if mode == "train":
            shared_apply = jax.checkpoint(
                shared_apply, policy=jax.checkpoint_policies.nothing_saveable)
        h, kv_ns = shared_apply(p_s, h)
        if mode != "decode":
            h = shard(h, "batch", "residual", None)
        return h, (m_ns, kv_ns)

    gidx = jnp.arange(G)
    xs = ((gidx, mamba) if mode != "decode"
          else (gidx, mamba, m_state, cache["shared_kv"]))
    y, (m_ns, kv_ns) = jax.lax.scan(group, x, xs)
    new_cache = None
    if mode in ("prefill", "decode") and m_ns is not None:
        flat = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), m_ns)
        new_cache = {"mamba": flat, "shared_kv": kv_ns}
    aux = jnp.zeros((), jnp.float32)
    return y, aux, new_cache


# ---------------------------------------------------------------------------
# Embedding of inputs, losses, public step functions
# ---------------------------------------------------------------------------


def embed_inputs(params: Params, batch: dict, cfg: ModelConfig) -> jax.Array:
    """[audio]/[vlm] train/prefill batches carry precomputed frontend
    embeddings ("embeds"); everything else carries token ids ("tokens")."""
    if "embeds" in batch:
        return shard(batch["embeds"].astype(ly.PDTYPE), "batch", "seq", "embed")
    return ly.embed_tokens(params["embed"], batch["tokens"])


def loss_fn(params: Params, batch: dict, cfg: ModelConfig) -> jax.Array:
    x = embed_inputs(params, batch, cfg)
    y, aux, _ = forward(params, x, cfg, mode="train")
    ce = ly.cross_entropy(params, y, batch["labels"], cfg)
    return ce + aux


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> dict:
    L = cfg.num_layers
    if cfg.family in ("dense", "audio", "vlm", "moe"):
        kv = ly.init_kv_cache(cfg, batch, max_seq, dtype)
        stack = lambda t: jnp.broadcast_to(t, (L, *t.shape))
        return {"kv": jax.tree.map(stack, kv)}
    if cfg.family == "ssm":
        xl = cfg.xlstm
        G = L // (xl.m_per_group + xl.s_per_group)
        rep = lambda t, n: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n, *a.shape)), t)
        return {
            "mlstm": rep(ssm_mod.init_mlstm_state(cfg, batch, dtype),
                         G * xl.m_per_group),
            "slstm": rep(ssm_mod.init_slstm_state(cfg, batch),
                         G * xl.s_per_group),
        }
    if cfg.family == "hybrid":
        hb = cfg.hybrid
        G = L // hb.mamba_per_group
        rep = lambda t, n: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n, *a.shape)), t)
        kv = ly.init_kv_cache(cfg, batch, max_seq, dtype)
        return {
            "mamba": rep(ssm_mod.init_mamba2_state(cfg, batch, dtype), L),
            "shared_kv": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (G, *a.shape)), kv),
        }
    raise ValueError(cfg.family)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> dict:
    """Paged slot cache for attention families: one shared
    (L, num_blocks, block_size, K, hd) KV block pool; slots reference
    blocks through their block tables (serve.scheduler.BlockAllocator)."""
    if cfg.family not in ("dense", "audio", "vlm", "moe"):
        raise ValueError(f"paged KV needs an attention family, "
                         f"got {cfg.family!r}")
    kv = ly.init_paged_kv_cache(cfg, num_blocks, block_size)
    stack = lambda t: jnp.broadcast_to(t, (cfg.num_layers, *t.shape))
    return {"kv": jax.tree.map(stack, kv)}


def decode_step(params: Params, cache: dict, tokens: jax.Array,
                index: jax.Array, cfg: ModelConfig,
                tables: Optional[jax.Array] = None,
                sketch: Optional[dict] = None,
                kernels: Optional[str] = None
                ) -> Tuple[jax.Array, dict]:
    """tokens: (B, 1) int32.  Returns (logits (B, Vp) f32, new cache).
    ``tables``: optional (B, blocks_per_slot) block tables — paged-KV
    decode for attention families (dense slot cache otherwise).
    ``sketch``: optional two-span long-context state (see forward).
    ``kernels``: static paged-attention implementation switch (see
    forward)."""
    x = ly.embed_tokens(params["embed"], tokens)
    y, _, new_cache = forward(params, x, cfg, mode="decode", cache=cache,
                              index=index, tables=tables, sketch=sketch,
                              kernels=kernels)
    logits = ly.logits_fn(params, y, cfg)[:, 0]
    return logits, new_cache


def verify_step(params: Params, cache: dict, tokens: jax.Array,
                index: jax.Array, cfg: ModelConfig, tables: jax.Array,
                sketch: Optional[dict] = None,
                kernels: Optional[str] = None
                ) -> Tuple[jax.Array, dict]:
    """Speculative-decode verification: score C tokens per slot in ONE
    compiled multi-query decode against the paged pool.

    tokens: (B, C) int32 — each slot's last committed token followed by
    its C-1 draft proposals; index: (B,) per-slot start positions (the
    slot's current decode position).  Rows for positions
    index[b] .. index[b]+C-1 scatter through the slot's block table
    exactly as C successive decode steps would, and the returned logits
    (B, C, Vp) f32 at position index[b]+i match what a plain decode step
    would produce after committing tokens[:i+1] — the property that makes
    greedy speculative decode bitwise-identical to plain greedy decode.
    Rejected rows beyond the accepted prefix are overwritten by the next
    round's writes before any query can attend them.
    """
    x = ly.embed_tokens(params["embed"], tokens)
    y, _, new_cache = forward(params, x, cfg, mode="verify", cache=cache,
                              index=index, tables=tables, sketch=sketch,
                              kernels=kernels)
    logits = ly.logits_fn(params, y, cfg)
    return logits, new_cache


def prefill(params: Params, batch: dict, cfg: ModelConfig
            ) -> Tuple[jax.Array, dict]:
    """Full-sequence prefill producing (last-token logits, cache)."""
    x = embed_inputs(params, batch, cfg)
    y, _, cache = forward(params, x, cfg, mode="prefill")
    logits = ly.logits_fn(params, y[:, -1:], cfg)[:, 0]
    return logits, cache


def prefill_chunk(params: Params, cache: dict, tokens: jax.Array,
                  table: jax.Array, start: jax.Array, cfg: ModelConfig,
                  sketch: Optional[dict] = None,
                  kernels: Optional[str] = None) -> dict:
    """Chunked prefill step: write KV rows for absolute positions
    [start, start + C) into the paged pool through the slot's
    (blocks_per_slot,) block-table row ``table``, attending the chunk
    against everything the table already references below it (earlier
    chunks, shared prefix blocks).

    tokens: (1, C) int32 — one bucket-sized chunk of one prompt (the tail
    chunk is zero-padded; pad rows mapping past the request's reserved
    blocks are dropped by the scatter, the rest sit at positions no query
    attends before decode rewrites them).  No logits are produced: the
    scheduler resumes decode at the last prompt position, which recomputes
    that row's logits in-graph.  ``table``/``start`` are traced, so one
    compilation serves every slot and offset — the engine's prefill
    compile count is 1 regardless of prompt lengths.
    """
    x = ly.embed_tokens(params["embed"], tokens)
    _, _, new_cache = forward(params, x, cfg, mode="chunk", cache=cache,
                              index=start, tables=table, sketch=sketch,
                              kernels=kernels)
    return new_cache
