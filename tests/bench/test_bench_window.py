"""Window arithmetic: whole pump rounds, on the host clock."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import run as R
from bench.traffic import BLOCK

ROOT = Path(__file__).resolve().parents[2]


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Loop:
    def call_soon_threadsafe(self, fn):
        fn()


def _recorder(closed, seconds=1.0, **cell_kw):
    cell = R.Cell(
        workload="w", config={}, end_to_end=[], per_layer=[],
        traffic={"arrivals": "closed" if closed else "poisson"},
        cell={"serve": {"max_batch": 4, "decode_chunk": 8},
              "warm_rounds": 2, "warm_s": 0.5, **cell_kw})
    clock, cpu = Clock(), Clock()
    rec = R.Recorder(cell, seconds, False, 0.0, clock=clock, cpu=cpu)
    rec.cpu_clock = cpu
    rec.loop = Loop()
    rec.shut = []
    rec.on_close = lambda: rec.shut.append(clock.t)
    return rec, clock


def _round(rec, clock, t, tokens, cpu_s=0.25, admits=0):
    for k in range(admits):
        rec.request_admitted(1000 + len(rec.rounds) * 100 + k, k, False)
    clock.t = t
    rec.cpu_clock.t += cpu_s
    rec.chunk_collected(tokens, 0, 4)


def test_window_spans_whole_rounds_after_warm_up():
    rec, clock = _recorder(closed=True, seconds=1.0)
    for rid in range(BLOCK):
        rec.request_admitted(rid, rid % 4, False)
    # rounds every 0.3 s; nothing has finished before t = 0.9
    times = [0.3 * (i + 1) for i in range(12)]
    for i, t in enumerate(times):
        if i == 2:
            rec.request_finished(0, "ok", 8)
        _round(rec, clock, t, tokens=10 + i)
    w = rec.window()
    # warm: >= 2 rounds and one finished -> starts at the boundary of
    # round 2 (t = 0.9); the last boundary within 1 s of it is t = 1.8
    assert (w["a"], w["b"]) == (2, 5)
    assert (w["t_a"], w["t_b"]) == pytest.approx((0.9, 1.8))
    assert w["tokens"] == sum(10 + i for i in (3, 4, 5))
    assert rec.shut == pytest.approx([2.1])      # closed at the next one
    ctx = R._Ctx({"window": w, "rec": rec,
                  "serve": {"max_batch": 4, "decode_chunk": 8}})
    assert R.reader("tok_s")(ctx) == pytest.approx(w["tokens"] / 0.9)
    assert R.reader("slot_occupancy.tput")(ctx) == pytest.approx(
        100.0 * w["tokens"] / (3 * 8 * 4))
    # each round: 0.3 s of wall time, 0.25 s of the pump thread's CPU
    assert R.reader("pump_host_ms.tput")(ctx) == pytest.approx(250.0)


def test_window_is_trimmed_to_whole_admission_cycles():
    rec, clock = _recorder(closed=True, seconds=4.6)
    for rid in range(BLOCK):
        rec.request_admitted(rid, rid % 4, False)
    rec.request_finished(0, "ok", 8)
    # decode rounds of 0.3 s; a round that admits takes 1.2 s
    admits = [0, 0, 0, 0, 1, 0, 0, 1, 0, 0, 0, 1, 0, 0]
    t = 0.0
    for i, n in enumerate(admits):
        t += 1.2 if n else 0.3
        _round(rec, clock, t, tokens=8, admits=n)
    assert rec.win_a == 1                   # warm: 2 rounds, one finished
    w = rec.window()
    # the last boundary in time is the one before round 11, which
    # admits: whole cycles run from boundary 3 (round 4 admits) to it
    assert rec.win_b == 10
    assert (w["a"], w["b"]) == (3, 10)
    assert w["tokens"] == 8 * 7
    assert w["t_warm"] == pytest.approx(0.6)
    assert w["t_b"] - w["t_a"] == pytest.approx(1.2 * 2 + 0.3 * 5)


def test_window_without_two_cycle_edges_keeps_its_rounds():
    rec, clock = _recorder(closed=True, seconds=1.0)
    for rid in range(BLOCK):
        rec.request_admitted(rid, rid % 4, False)
    rec.request_finished(0, "ok", 8)
    for i in range(8):
        _round(rec, clock, 0.3 * (i + 1), tokens=8, admits=int(i == 3))
    assert rec.cycles(rec.win_a, rec.win_b) == (rec.win_a, rec.win_b)


def test_window_needs_a_block_admitted_and_open_loop_warm_seconds():
    rec, clock = _recorder(closed=False, seconds=0.5)
    rec.request_finished(0, "ok", 8)
    for i in range(4):
        _round(rec, clock, 0.2 * (i + 1), 8)
    assert rec.win_a is None            # fewer than a block admitted
    for rid in range(BLOCK):
        rec.request_admitted(rid, 0, False)
    _round(rec, clock, 1.0, 8)          # past warm_s = 0.5: starts here
    assert rec.win_a == 4
    for t in (1.2, 1.4, 1.6):
        _round(rec, clock, t, 8)
    w = rec.window()
    assert (w["t_a"], w["t_b"]) == pytest.approx((1.0, 1.4))
    assert rec.shut == pytest.approx([1.6])


def test_latency_reader_counts_only_the_window():
    w = {"t_a": 10.0, "t_b": 20.0}
    recs = {
        0: {"due": 9.0, "times": [9.5, 10.5, 10.6]},     # starts before
        1: {"due": 10.0, "times": [11.0, 11.2, 11.2, 19.0]},
        2: {"due": 19.0, "times": [19.9, 20.5]},
    }
    ctx = R._Ctx({"window": w, "recs": recs})
    gaps = [1000.0, 100.0, 200.0, 0.0, 7800.0]   # later token inside
    assert R.reader("itl_mean_ms")(ctx) == pytest.approx(
        float(np.mean(gaps)))
    # first tokens inside: requests 1 (due 10.0) and 2 (due 19.0)
    assert R.reader("ttft_p90_ms")(ctx) == pytest.approx(
        float(np.percentile([1000.0, 900.0], 90)))
    rec = R._Ctx({"admitted": {0: 9.2, 1: 10.4, 2: 19.5, 3: 20.5}})
    ctx = R._Ctx({"window": w, "recs": recs, "rec": rec})
    assert R.reader("queue_wait_p90_ms.ttft")(ctx) == pytest.approx(
        float(np.percentile([400.0, 500.0], 90)))


def test_every_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(R.reader(m["name"]))
