"""Operation and byte counts against hand counts, the layers' matrices
through the Llama block's module; the peak table."""
import json
from pathlib import Path

import pytest

from bench import counts as C
from bench import reference as ref

ROOT = Path(__file__).resolve().parents[2]
# a small dense GQA shape: 2 layers, d 8, 4 query heads and 2 kv heads of
# 2, MLP 16, vocabulary 10
SMALL = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_block_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 x3
    assert ref.block_params(SMALL) == 64 + 32 + 32 + 64 + 3 * 128


def test_key_sums_by_brute_force():
    off, rows = 37, 11
    assert C.chunk_key_sum(off, rows) == sum(off + i + 1
                                             for i in range(rows))
    ctx, emitted = 100, 8
    assert C.decode_key_sum(ctx, emitted) == sum(ctx + j
                                                 for j in range(emitted))


def test_decode_and_prefill_operations_by_hand():
    # one token attending 5 keys: 2 * (2 layers * 576 + 8 * 10) matrix
    # operations, plus per layer and head 2*2 for q.k and 2*2 for p.v
    # over 5 keys
    want = 2 * (2 * 576 + 80) + 2 * 4 * (4 + 4) * 5
    assert C.decode_flops(ref, SMALL, 1, 5) == want
    # 3 prompt rows at offset 0: keys 1 + 2 + 3, no LM head
    assert C.prefill_flops(ref, SMALL, 3, C.chunk_key_sum(0, 3)) == \
        2 * 3 * 2 * 576 + 2 * 4 * 8 * 6


def test_attention_bytes_by_hand():
    # 5 keys: k and v rows of 2 heads x 2 bf16 each; one query row: q in
    # bf16, the f32 accumulator and two f32 statistics per head
    per_layer = 5 * 2 * 2 * 2 * 2 + (4 * 2 * 2 + 4 * 2 * 4 + 2 * 4 * 4)
    assert C.attn_bytes(SMALL, 5, 1) == 2 * per_layer


def test_peaks_are_keyed_by_device_kind_with_a_source():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    p = C.peak("TPU v5 lite")
    assert (p["flops_per_s"], p["bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        C.peak("a chip nobody measured")
