"""Operations and bytes that the served work needs, from a
configuration's shapes and the live context lengths.

These count what exact attention over the live positions needs (in a
sketched cell, the exact window), not what a kernel's grid walks, so a
kernel that skips dead blocks shows as a gain and not as a changed
yardstick.  A multiply-add is two operations.  Weights, keys and values
are bfloat16 (2 bytes).  The matrices of one layer are the
configuration's module's count (``block_params``); attention is counted
as the GQA paged kernel computes it.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

BF16 = 2
F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown device is an
    error, not a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def attn_flops(c: Dict, keys: float) -> float:
    """Attention operations of one query row over ``keys`` keys, all
    layers: q.k and p.v, each ``2 * head_dim`` per head and key."""
    return c["num_layers"] * 4.0 * c["num_heads"] * c["head_dim"] * keys


def attn_bytes(c: Dict, keys: float, rows: int) -> float:
    """Bytes one attention call over ``keys`` keys for ``rows`` query
    rows of one sequence moves, all layers: the keys and values read
    once, the queries read, and the f32 output statistics (running max,
    sum and weighted values) written."""
    H, K, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    per_layer = (keys * K * hd * BF16 * 2
                 + rows * (H * hd * BF16 + H * hd * F32 + 2 * H * F32))
    return c["num_layers"] * per_layer


def decode_flops(block, c: Dict, tokens: int, key_sum: float) -> float:
    """``tokens`` decoded tokens whose queries attend ``key_sum`` keys in
    all: the layers' matrices (``block``, the configuration's module),
    the LM head over the vocabulary, and attention."""
    return (2.0 * tokens * (c["num_layers"] * block.block_params(c)
                            + c["d_model"] * c["vocab_size"])
            + attn_flops(c, key_sum))


def prefill_flops(block, c: Dict, rows: int, key_sum: float) -> float:
    """``rows`` prompt rows through the layers (no logits: prefill
    produces none), attending ``key_sum`` keys in all."""
    return (2.0 * rows * c["num_layers"] * block.block_params(c)
            + attn_flops(c, key_sum))


def chunk_key_sum(off: int, rows: int) -> float:
    """Keys a causal chunk of ``rows`` rows at offset ``off`` attends:
    row i sees positions 0 .. off + i."""
    return rows * off + rows * (rows + 1) / 2.0


def decode_key_sum(ctx: int, emitted: int) -> float:
    """Keys of ``emitted`` successive decode steps of one slot whose
    first step's query sits at position ``ctx - 1`` (it attends ``ctx``
    keys), each later step one more."""
    return emitted * ctx + emitted * (emitted - 1) / 2.0
