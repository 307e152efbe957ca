"""The Llama block as a configuration module of the benchmark, with its
plain float32 reference.

A configuration file names its module under ``"reference"``;
``bench/run.py`` loads it by path and takes from it everything that
depends on the block:

- ``model(config)``: the configuration in the names the module's other
  functions use; the shared code reads ``num_layers``, ``d_model``,
  ``num_heads``, ``num_kv_heads``, ``head_dim`` and ``vocab_size``
  (traffic ids, attention counts) from it;
- ``program_config(config, serve)``: the program's ``ModelConfig``, or
  an exit for a configuration the program cannot run as this block;
- ``leaf_specs(c)``: the serve-layout leaves, in leaf-id order, that
  ``bench/weights.py`` draws;
- ``logit_rows(c, seed, seqs, rows, tokens, mode)``: the reference;
- ``block_params(c)``: the matrix parameters one token passes through in
  one layer (``bench/counts.py``).

The reference: no kernels, no cache, no batching tricks: embed, then per
layer RMSNorm (gain ``1 + g``), rotary q/k (half-split rotation, base
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
import math
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
NORM_SCALE = 0.1       # norm gains are 1 + N(0, NORM_SCALE^2)

# model keys of a configuration file (public config.json names) -> the
# names of model(): the program's ModelConfig fields
MODEL_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "norm_eps": "norm_eps",
    "hidden_act": "act",
}
# the program's dense block; a configuration file states its block in
# these keys, and one that states another is not this block
BLOCK = {"norm": "rmsnorm", "partial_rotary_factor": 1.0, "mlp_gated": True,
         "hidden_act": "silu"}


def model(config: Dict) -> Dict:
    """The configuration in the names the reference and counts use."""
    return {v: config[k] for k, v in MODEL_KEYS.items() if k in config}


def program_config(config: Dict, serve):
    """The program's ``ModelConfig`` of ``config``; exits where the file
    states a block other than this one.  ``serve``, the cell's
    ``ServeConfig``, is for a block whose program config depends on it;
    this one's does not."""
    from repro.configs.base import ModelConfig
    for k, v in BLOCK.items():
        if config.get(k, v) != v:
            raise SystemExit(f"{config['name']}: {k} {config[k]!r}, but "
                             f"the program's dense block has {v!r}")
    m = model(config)
    return ModelConfig(name=config["name"], family="dense",
                       num_layers=m["num_layers"], d_model=m["d_model"],
                       num_heads=m["num_heads"],
                       num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
                       vocab_size=m["vocab_size"], head_dim=m["head_dim"],
                       act="silu", rope_theta=float(m["rope_theta"]),
                       norm_eps=float(m["norm_eps"]))


def padded_vocab(c: Dict) -> int:
    return -(-c["vocab_size"] // 256) * 256


def leaf_specs(c: Dict) -> List[W.Spec]:
    """(path, shape of one layer's leaf, std, stacked over layers).  The
    index of an entry is its leaf id in the key derivation; norm leaves
    hold the gain minus one, as the program's norms add the one."""
    d, H, K = c["d_model"], c["num_heads"], c["num_kv_heads"]
    hd, ff, V = c["head_dim"], c["d_ff"], padded_vocab(c)
    return [
        ("embed", (V, d), 0.02, False),
        ("head", (d, V), 1.0 / math.sqrt(d), False),
        ("final_norm", (d,), NORM_SCALE, False),
        ("blocks/attn/wq", (d, H * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wk", (d, K * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wv", (d, K * hd), 1.0 / math.sqrt(d), True),
        ("blocks/attn/wo", (H * hd, d), 1.0 / math.sqrt(H * hd), True),
        ("blocks/ffn/w_gate", (d, ff), 1.0 / math.sqrt(d), True),
        ("blocks/ffn/w_up", (d, ff), 1.0 / math.sqrt(d), True),
        ("blocks/ffn/w_down", (ff, d), 1.0 / math.sqrt(ff), True),
        ("blocks/norm1", (d,), NORM_SCALE, True),
        ("blocks/norm2", (d,), NORM_SCALE, True),
    ]


def block_params(c: Dict) -> int:
    """Matrix parameters of one decoder layer (attention + gated MLP)."""
    d, H, K = c["d_model"], c["num_heads"], c["num_kv_heads"]
    hd, ff = c["head_dim"], c["d_ff"]
    return d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff


def _frozen(c: Dict) -> Tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


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
def _layer_fn(frozen: Tuple, mode: str, act):
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
        return x + _mm(act(g) * u, w["blocks/ffn/w_down"], mode)

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
               mode: str = "f32", act=jax.nn.silu) -> Dict[str, np.ndarray]:
    """Run the reference over ``seqs`` (token arrays, each from position
    0) and read the logits of ``rows``, (sequence, position) pairs: the
    logits at ``position`` score the next token.  ``tokens`` (rows, m)
    names m tokens per row to read.  Returns per row the best logit, the
    logits' standard deviation, the logits of ``tokens`` (``at``, rows x
    m) and the argmax.  ``act`` is the gated MLP's activation, for a
    module that differs from this block in nothing else."""
    frozen, specs = _frozen(c), leaf_specs(c)
    S = max(len(s) for s in seqs)
    S = -(-S // PAD) * PAD
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        emb = W.leaf(specs, seed, "embed")
        x = jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
        del emb
        layer = _layer_fn(frozen, mode, act)
        for l in range(c["num_layers"]):
            w = {p: W.leaf(specs, seed, p, l) for p in W.layer_paths(specs)}
            x = layer(x, w)
            del w
        rn = jnp.asarray(np.asarray([r[0] for r in rows], np.int32))
        rt = jnp.asarray(np.asarray([r[1] for r in rows], np.int32))
        served = jnp.asarray(np.asarray(tokens, np.int32).reshape(
            len(rows), -1))
        out = _head_fn(frozen, mode)(x, rn, rt,
                                     W.leaf(specs, seed, "final_norm"),
                                     W.leaf(specs, seed, "head"), served)
        return {k: np.asarray(v) for k, v in out.items()}

