"""Traffic: stratified blocks make the offered work seed-invariant."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
SEEDS = (7, 2**31 + 11)


def _cell(mix):
    # the cell file of some workload that uses this mix
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w["name"] for w in spec["workloads"] if w["traffic"] == mix)
    return json.loads((ROOT / "bench" / "cells" / f"{wl}.json").read_text())


def _blocks(mix, seed, n):
    tr = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    return tr, T.take(T.stream(tr, _cell(mix), seed, 1000), n * T.BLOCK)


@pytest.mark.parametrize("mix", MIXES)
def test_whole_blocks_offer_the_same_work_for_every_seed(mix):
    n = 4
    (tr, a), (_, b) = _blocks(mix, SEEDS[0], n), _blocks(mix, SEEDS[1], n)
    for k in range(n):
        blk = slice(k * T.BLOCK, (k + 1) * T.BLOCK)
        for key in (lambda r: len(r.tokens), lambda r: r.max_new):
            assert sorted(map(key, a[blk])) == sorted(map(key, b[blk]))
        if tr["arrivals"] != "closed":
            # every block ends at the same time: same offered load
            assert a[blk][-1].due_s == pytest.approx(b[blk][-1].due_s)
    assert T.offered(a) == pytest.approx(T.offered(b))
    # the seed still draws the order and the token ids
    assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]
    assert not np.array_equal(a[-1].tokens[-8:], b[-1].tokens[-8:])


@pytest.mark.parametrize("mix", MIXES)
def test_blocks_hold_the_stated_quantiles(mix):
    tr, reqs = _blocks(mix, 3, 2)
    blk = reqs[T.BLOCK:]         # block 0 of a closed batch is the head
    sys_len = tr.get("system_prompts", {}).get("length", 0)
    assert sorted(len(r.tokens) - sys_len for r in blk) == sorted(
        T.block_lengths(tr["prompt"]).tolist())
    assert sorted(r.max_new for r in blk) == sorted(
        T.block_lengths(tr["output"]).tolist())
    if sys_len:
        # each shared system prompt opens a quarter of the block
        heads = [tuple(r.tokens[:sys_len]) for r in blk]
        assert sorted(heads.count(x) for x in set(heads)) == [4] * 4
    lo, hi = tr["prompt"]["min"], tr["prompt"]["max"]
    q = T.block_lengths(tr["prompt"])
    assert lo <= q.min() and q.max() <= hi
    assert q.max() > 3 * np.median(q)          # a heavy tail


def test_quantiles_of_a_lognormal_by_hand():
    spec = {"kind": "lognormal", "median": 100, "sigma": 1.0,
            "min": 1, "max": 10000}
    q = T.block_lengths(spec, 2)
    # u = 1/4 and 3/4: z = -/+0.6745
    assert q.tolist() == [round(100 * np.exp(-0.6744897501960817)),
                          round(100 * np.exp(0.6744897501960817))]
    gaps = T.block_gaps(2.0, 2)
    assert gaps.tolist() == pytest.approx([-np.log(0.75) / 2,
                                           -np.log(0.25) / 2])


def test_residual_life_of_the_first_batch():
    x = np.asarray([10, 30])
    r = T.residual_lengths(x, 4)
    # F_R(r) = E[min(X, r)] / E[X]: a Monte-Carlo residual life agrees
    rng = np.random.default_rng(0)
    pick = rng.choice(x, 200000, p=x / x.sum())
    mc = np.ceil(rng.random(200000) * pick)
    want = np.quantile(mc, [(i + 0.5) / 4 for i in range(4)])
    assert np.abs(r - want).max() <= 1
    tr = {"arrivals": "closed", "residual_first": True,
          "prompt": {"kind": "lognormal", "median": 40, "sigma": 1.0,
                     "min": 4, "max": 400},
          "output": {"kind": "lognormal", "median": 16, "sigma": 0.8,
                     "min": 4, "max": 64}}
    reqs = T.take(T.stream(tr, {}, 1, 50), T.BLOCK)
    out = T.block_lengths(tr["output"])
    assert sorted(r.max_new for r in reqs) == sorted(
        T.residual_lengths(out, T.BLOCK).tolist())
    assert sorted(len(r.tokens) for r in reqs) == sorted(
        T.block_lengths(tr["prompt"]).tolist())


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3])
def test_each_quarter_of_a_block_spans_the_distribution(seed):
    o = T.order(np.random.default_rng(seed))
    assert sorted(o.tolist()) == list(range(T.BLOCK))
    q = T.BLOCK // T.SUB
    for i in range(0, T.BLOCK, q):
        assert sorted(o[i:i + q] // q) == list(range(T.SUB))
