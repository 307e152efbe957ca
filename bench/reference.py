"""Plain float32 reference of a dense GQA decoder, as a configuration
file states it.

No kernels, no cache, no batching tricks: embed, then per layer
RMSNorm (gain ``1 + g``), rotary q/k (half-split rotation, base
``rope_theta``), causal grouped-query softmax attention, output
projection, residual, RMSNorm, SiLU-gated MLP, residual; then the final
RMSNorm and the LM head.  Weights are regenerated from the seed by
``bench/weights.py`` one layer at a time, so the reference imports
nothing of the program and fits beside nothing: it runs after the
program's state is freed.  Matrix products run at
``Precision.HIGHEST`` (a TPU computes float32 products in bfloat16
otherwise).

``mode="fp8"`` is the lower-precision control: every linear layer's
weights (per output channel) and inputs (per token), and the cached
keys and values (per token and head), rounded to float8 e4m3 with an
absmax scale, the step below the bfloat16 the configurations serve in.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
Q_CHUNK = 512          # query rows per attention block
PAD = 1024             # sequence lengths are padded to a multiple of this


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(a, w, mode):
    if mode == "fp8":
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _norm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, theta):
    """x: (N, S, n, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen: Tuple, mode: str):
    c = dict(frozen)
    H, K, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    R = H // K
    eps, theta = c["norm_eps"], c["rope_theta"]

    def layer(x, w):
        N, S, _ = x.shape
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        h = _norm(x, w["blocks/norm1"], eps)
        q = _rope(_mm(h, w["blocks/attn/wq"], mode).reshape(N, S, H, hd),
                  theta).reshape(N, S, K, R, hd)
        k = _rope(_mm(h, w["blocks/attn/wk"], mode).reshape(N, S, K, hd),
                  theta)
        v = _mm(h, w["blocks/attn/wv"], mode).reshape(N, S, K, hd)
        if mode == "fp8":
            k, v = _fp8(k, -1), _fp8(v, -1)
        outs = []
        for i0 in range(0, S, Q_CHUNK):
            i1 = min(S, i0 + Q_CHUNK)
            s = jnp.einsum("nqkrh,nskh->nkrqs", q[:, i0:i1], k[:, :i1],
                           precision=HIGHEST) / np.sqrt(hd)
            mask = (jnp.arange(i1)[None, :]
                    <= jnp.arange(i0, i1)[:, None])
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("nkrqs,nskh->nqkrh", p, v[:, :i1],
                                   precision=HIGHEST))
        o = jnp.concatenate(outs, axis=1).reshape(N, S, H * hd)
        x = x + _mm(o, w["blocks/attn/wo"], mode)
        h = _norm(x, w["blocks/norm2"], eps)
        g = _mm(h, w["blocks/ffn/w_gate"], mode)
        u = _mm(h, w["blocks/ffn/w_up"], mode)
        return x + _mm(jax.nn.silu(g) * u, w["blocks/ffn/w_down"], mode)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(frozen: Tuple, mode: str):
    c = dict(frozen)
    V = c["vocab_size"]

    def head(x, rows_n, rows_t, final_norm, head_w, served):
        h = _norm(x[rows_n, rows_t], final_norm.astype(jnp.float32),
                  c["norm_eps"])
        lg = _mm(h, head_w.astype(jnp.float32), mode)[:, :V]
        best = jnp.max(lg, axis=-1)
        at = jnp.take_along_axis(lg, served, axis=-1)
        return {"best": best, "std": jnp.std(lg, axis=-1), "at": at,
                "argmax": jnp.argmax(lg, axis=-1).astype(jnp.int32)}

    return jax.jit(head)


def logit_rows(c: Dict, seed: int, seqs: Sequence[np.ndarray],
               rows: Sequence[Tuple[int, int]], tokens: np.ndarray,
               mode: str = "f32") -> Dict[str, np.ndarray]:
    """Run the reference over ``seqs`` (token arrays, each from position
    0) and read the logits of ``rows``, (sequence, position) pairs: the
    logits at ``position`` score the next token.  ``tokens`` (rows, m)
    names m tokens per row to read.  Returns per row the best logit, the
    logits' standard deviation, the logits of ``tokens`` (``at``, rows x
    m) and the argmax."""
    frozen = W._frozen(c)
    S = max(len(s) for s in seqs)
    S = -(-S // PAD) * PAD
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        emb = W.leaf(c, seed, "embed")
        x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
        del emb
        layer = _layer_fn(frozen, mode)
        for l in range(c["num_layers"]):
            w = {p: W.leaf(c, seed, p, l) for p in W.layer_paths(c)}
            x = layer(x, w)
            del w
        rn = jnp.asarray(np.asarray([r[0] for r in rows], np.int32))
        rt = jnp.asarray(np.asarray([r[1] for r in rows], np.int32))
        served = jnp.asarray(np.asarray(tokens, np.int32).reshape(
            len(rows), -1))
        out = _head_fn(frozen, mode)(x, rn, rt,
                                     W.leaf(c, seed, "final_norm"),
                                     W.leaf(c, seed, "head"), served)
        return {k: np.asarray(v) for k, v in out.items()}


def gaps(ref: Dict[str, np.ndarray], col: int = 0) -> np.ndarray:
    """Per row, how far the reference's logit of token column ``col``
    lies below its best, in standard deviations of that row's logits."""
    return (ref["best"] - ref["at"][:, col]) / ref["std"]


def served_rows(samples: List[Tuple[np.ndarray, np.ndarray]]
                ) -> Tuple[List[np.ndarray], List[Tuple[int, int]],
                           np.ndarray]:
    """(prompt, served tokens) pairs -> reference sequences, the rows
    that score each served token (served token j of a prompt of length
    S is scored by the logits at position S - 1 + j) and those tokens."""
    seqs, rows, toks = [], [], []
    for n, (prompt, out) in enumerate(samples):
        seqs.append(np.concatenate([prompt, out[:-1]]).astype(np.int32))
        S = len(prompt)
        rows.extend((n, S - 1 + j) for j in range(len(out)))
        toks.extend(int(t) for t in out)
    return seqs, rows, np.asarray(toks, np.int32)
