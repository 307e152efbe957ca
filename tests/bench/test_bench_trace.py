"""The reduction from a profiler trace to busy time, idle gaps, op and
module times: on hand-made events, and on a small trace recorded on a
TPU v5e (``bench/testdata/``) against a brute-force recount."""
from pathlib import Path

import numpy as np
import pytest

from bench import devtrace as D

DATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"


def _ev(line, name, start, dur):
    return {"line": line, "name": name, "start_ns": float(start),
            "dur_ns": float(dur)}


def test_reduce_by_hand():
    ev = [_ev(D.OPS_LINE, "fusion.1", 0, 10),
          _ev(D.OPS_LINE, "fusion.22", 5, 15),       # overlaps: union 0-20
          _ev(D.OPS_LINE, "paged_attention.3", 30, 10),
          _ev(D.MODULES_LINE, "jit_chunk_fn(7)", 0, 20),
          _ev(D.MODULES_LINE, "jit_prefill_chunk", 30, 10),
          _ev("host", "bench.collect", 18, 10),       # covers 25
          _ev("host", "bench.admit", 41, 20)]         # covers 50
    r = D.reduce(ev, 0.0, 60.0)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["window_s"] == pytest.approx(60e-9)
    assert r["ops"] == pytest.approx({"fusion": 25e-9,
                                      "paged_attention": 10e-9})
    assert D.time_of(r, "paged_attention") == pytest.approx(10e-9)
    assert D.module_time(r, r"chunk_fn") == pytest.approx(
        {"s": 20e-9, "n": 1})
    assert r["idle_gaps"] == [["admit", pytest.approx(20e-9)],
                              ["collect", pytest.approx(10e-9)]]
    assert D.op_kind("bitcast_add_fusion.4.remat") == \
        "bitcast_add_fusion.4.remat"
    assert D.op_kind("copy.12") == "copy"


def _recorded():
    paths = sorted(DATA.glob("*.json.gz"))
    if not paths:
        pytest.fail("no recorded trace under bench/testdata")
    return D.load(str(paths[0]))


def test_recorded_trace_against_a_brute_force_recount():
    tr = _recorded()
    ev = tr["events"]
    span = tr["span_ns"]
    r = D.reduce(ev, 0.0, span)
    ops = [e for e in ev if e["line"] == D.OPS_LINE
           and e["start_ns"] + e["dur_ns"] <= span]
    assert ops, "the recorded trace holds device ops"
    # busy time on a 1 us grid
    grid = np.zeros(int(span // 1000) + 2, bool)
    for e in ops:
        grid[int(e["start_ns"] // 1000):
             int(-(-(e["start_ns"] + e["dur_ns"]) // 1000))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02,
                                        abs=2e-5 * len(ops) ** 0.5)
    assert 0 < r["busy_s"] <= r["window_s"]
    # ops on the line nest (a while loop spans its body): self times
    # tile the busy time
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    assert r["device_ops"][0][1] == max(r["ops"].values())
    # the decode chunk program and the kernel are in it, named
    assert D.module_time(r, r"chunk_fn")["n"] >= 1
    assert D.time_of(r, "paged_attention") > 0
