"""The per-layer metrics read from the program's ``serve.*`` spans
(``bench/spans.py``) and the prefill chunk's device time: on hand-made
spans with the values worked out by hand, on a profile written on the
CPU, and on a slice recorded on a TPU v5e (``bench/testdata/``)."""
from pathlib import Path

import jax
import pytest

from bench import devtrace
from bench import run as R
from bench import spans as S

DATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"
US = 1e5      # ns per unit of the hand-made timeline: 1 unit = 0.1 ms


def _sp(name, tid, a, b):
    return {"name": name, "tid": tid, "start_ns": a * US,
            "dur_ns": (b - a) * US, "args": {}}


# two rounds: the loop thread "L" dispatches, admits a request while the
# chunk is in flight (tracker 20 units), the worker "W" waits for the
# chunk; after it the loop delivers, crosses the boundary, admits again
# (tracker 10) and dispatches the next chunk; the last read-back has no
# dispatch after it in the window.  The device runs a decode chunk 5-50
# (the first admission's host work up to 50 hides behind it), the
# admitted prefill 58-100, and decode chunks 190-240 and 275-330
HAND = [
    _sp("dispatch", "L", 0, 10),
    _sp("admit", "L", 20, 60),
    _sp("prefix_lookup", "L", 21, 24),
    _sp("prefix_count", "L", 25, 45),
    _sp("prefill_chunk", "L", 46, 58),
    _sp("collect", "W", 70, 120),
    _sp("collect.wait", "W", 70, 100),
    _sp("prefix_count", "W", 101, 104),     # not inside any admission
    _sp("retire", "W", 105, 115),
    _sp("deliver", "L", 125, 130),
    _sp("yield", "L", 130, 132),
    _sp("boundary", "L", 132, 135),
    _sp("admit", "L", 136, 176),
    _sp("prefix_count", "L", 140, 150),
    _sp("dispatch", "L", 180, 190),
    _sp("collect", "W", 200, 260),
    _sp("collect.wait", "W", 200, 240),
    _sp("dispatch", "L", 270, 275),
    _sp("collect.wait", "W", 300, 330),
]
BUSY = [[5 * US, 50 * US], [58 * US, 100 * US], [190 * US, 240 * US],
        [275 * US, 330 * US]]


def _hand(lo=0, hi=400):
    return S.view({"spans": HAND, "ops": BUSY, "programs": BUSY,
                   "window_ns": [lo * US, hi * US]})


def _ms(units):
    return units * US * 1e-6


def test_readers_by_hand():
    sp = _hand()
    # tracker: (20 + 10) / 2 admissions; the worker's prefix_count is in
    # no admission
    assert S.tracker_wait_ms(sp) == pytest.approx(_ms(15))
    # admission outside its tracker with no program on the device: the
    # first, 20-60, is hidden behind the decode chunk up to 50 and idle
    # 50-58 (8); the second, 136-176, idle throughout less its tracker
    # (40 - 10); (8 + 30) / 2
    assert [a["exposed_ns"] for a in S.admissions(sp)] == [
        pytest.approx(8 * US), pytest.approx(30 * US)]
    assert S.admit_host_ms(sp) == pytest.approx(_ms(19))
    # stretches: 100 -> 190 less the admission 136-176 (50), 240 -> 275
    # (35); the read-back ending at 330 has no dispatch after it
    st = S.stretches(sp)
    assert [(s["from_ns"], s["to_ns"], s["admit_ns"]) for s in st] == [
        (100 * US, 190 * US, 40 * US), (240 * US, 275 * US, 0.0)]
    assert S.boundary_host_ms(sp) == pytest.approx(_ms(42.5))


def test_window_and_a_program_without_spans():
    # the window drops what does not lie wholly inside it
    sp = _hand(15, 250)
    assert [s["name"] for s in sp["spans"]][:2] == ["admit",
                                                    "prefix_lookup"]
    assert sp["programs"][0] == [15 * US, 50 * US]
    assert sp["programs"][-1] == [190 * US, 240 * US]
    assert S.tracker_wait_ms(sp) == pytest.approx(_ms(15))
    assert [s["to_ns"] for s in S.stretches(sp)] == [190 * US]
    assert S.view({"spans": HAND, "window_ns": None})["spans"] == []
    # the parent program writes no serve.* span: every reader is silent
    for name in ("tracker_wait_ms.ttft", "admit_host_ms.tput",
                 "boundary_host_ms.itl", "prefill_chunk_ms.tput"):
        ctx = R._Ctx({"trace": {"modules": {"jit__unknown(1)":
                                            {"s": 1.0, "n": 3}}},
                      "serve_spans": S.view({})})
        assert R.reader(name)(ctx) is None, name


def test_readers_found_by_name_read_the_cached_spans():
    ctx = R._Ctx({"trace": {"modules": {}}, "serve_spans": _hand()})
    assert R.reader("tracker_wait_ms.tput")(ctx) == pytest.approx(_ms(15))
    assert R.reader("admit_host_ms.ttft")(ctx) == pytest.approx(_ms(19))
    assert R.reader("boundary_host_ms.tput")(ctx) == \
        pytest.approx(_ms(42.5))


def test_prefill_module_names():
    mods = {"jit_prefill_chunk(11)": {"s": 0.3, "n": 2},
            "jit_sketched_prefill_chunk(12)": {"s": 0.1, "n": 2},
            "jit_draft_prefill_chunk(13)": {"s": 5.0, "n": 1},
            "jit_chunk_fn(14)": {"s": 9.0, "n": 1}}
    ctx = R._Ctx({"trace": {"modules": mods}})
    assert R.reader("prefill_chunk_ms.ttft")(ctx) == pytest.approx(100.0)
    # the decode chunk's reader still reads the decode chunk alone
    assert devtrace.module_time({"modules": mods}, r"chunk_fn") == \
        pytest.approx({"s": 9.0, "n": 1})


def test_extract_reads_a_profile(tmp_path):
    """On a profile written here (no device ops: no window), the spans
    come back with their thread and arguments, names without prefix."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("serve.admit", rid=3, slot=1):
            with jax.profiler.TraceAnnotation("serve.prefix_count"):
                pass
        with jax.profiler.TraceAnnotation("bench.collect"):
            pass
    found = S.extract(str(tmp_path))
    assert found["window_ns"] is None and found["ops"] == []
    by = {s["name"]: s for s in found["spans"]}
    assert set(by) == {"admit", "prefix_count"}
    assert by["admit"]["args"] == {"rid": 3, "slot": 1}
    assert by["admit"]["tid"] == by["prefix_count"]["tid"]
    assert by["admit"]["start_ns"] <= by["prefix_count"]["start_ns"]
    assert S.view(found)["spans"] == []


def test_idle_cover_by_hand():
    """Device idle time by the innermost span over it: gaps 0-5, 50-58,
    100-190, 240-275 and 330-400 (208 units), 90 of them under no span
    (120-125, 135-136, 176-180, 260-270 and the trace's end after the
    last read-back)."""
    c = S.idle_cover(_hand())
    assert c["idle_ns"] == pytest.approx(208 * US)
    assert c["covered_ns"] == pytest.approx(118 * US)
    want = {"none": 90, "admit": 30, "collect": 27, "dispatch": 20,
            "prefix_count": 13, "retire": 10, "prefill_chunk": 8,
            "deliver": 5, "boundary": 3, "yield": 2}
    assert c["by_ns"] == {k: pytest.approx(v * US) for k, v in
                          want.items()}
    assert S.idle_cover(S.view({})) is None


# slices of two traced runs on a TPU v5e (``python3 bench/spans.py``);
# the values were worked out by hand from the spans and device programs
# the slice holds (ms, to the microsecond): per admission, its length,
# its tracker child and the time in it, outside the tracker, with no
# program on the device (counted on a 1 us grid); per round, read-back
# end to the next dispatch's end and the admission inside; the device's
# idle time in the slice's window and the part under a span (same grid).
# Neither run admitted while a decode chunk was in flight: both cells
# admit at the pump boundary (the hand-made timeline above has the
# overlapping case)
RECORDED = {
    "v5e_minitron_chat_spans": {
        "admits": [(120.629, 24.739, 10.543), (119.143, 22.783, 11.061),
                   (206.081, 22.830, 14.088), (120.311, 22.871, 12.240),
                   (120.521, 24.001, 11.245)],
        "stretches": [(120.696, 119.143), (332.253, 326.392),
                      (125.906, 120.521)],
        "idle": (201.427, 196.555),
        "prefill_ms": 84.55},
    "v5e_yi_batch_spans": {
        "admits": [(296.495, 23.044, 13.290)],
        "stretches": [(2.318, 0.0), (302.116, 296.495), (2.245, 0.0)],
        "idle": (54.759, 52.948),
        "prefill_ms": 260.11},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_slice_by_hand(name):
    want = RECORDED[name]
    sl = S.load(str(DATA / f"{name}.json.gz"))
    v = S.view(sl)
    sp = v["spans"]
    ctx = R._Ctx({"trace": devtrace.reduce(sl["events"]),
                  "serve_spans": v})
    adm = want["admits"]
    assert [(a["dur_ns"] * 1e-6, a["count_ns"] * 1e-6,
             a["exposed_ns"] * 1e-6) for a in S.admissions(v)] == [
        (pytest.approx(d, abs=2e-3), pytest.approx(c, abs=2e-3),
         pytest.approx(x, abs=5e-3)) for d, c, x in adm]
    assert R.reader("tracker_wait_ms.tput")(ctx) == pytest.approx(
        sum(c for _, c, _ in adm) / len(adm), abs=2e-3)
    # an admission lasts its prefill chunks' device time and more; the
    # metric keeps only what the device waited for
    assert R.reader("admit_host_ms.ttft")(ctx) == pytest.approx(
        sum(x for _, _, x in adm) / len(adm), abs=5e-3)
    assert all(d - c - x > want["prefill_ms"] for d, c, x in adm)
    st = want["stretches"]
    assert [((s["to_ns"] - s["from_ns"]) * 1e-6, s["admit_ns"] * 1e-6)
            for s in S.stretches(v)] == [
        (pytest.approx(t, abs=2e-3), pytest.approx(a, abs=2e-3))
        for t, a in st]
    assert any(a > 0 for _, a in st)     # a round with an admission
    assert R.reader("boundary_host_ms.itl")(ctx) == pytest.approx(
        sum(t - a for t, a in st) / len(st), abs=2e-3)
    assert R.reader("prefill_chunk_ms.tput")(ctx) == pytest.approx(
        want["prefill_ms"], abs=0.01)
    # every admission's prefill chunk and tracker nest in it
    for s in sp:
        if s["name"] in ("prefill_chunk", "prefix_count"):
            assert any(a["tid"] == s["tid"]
                       and a["start_ns"] <= s["start_ns"]
                       and s["start_ns"] + s["dur_ns"]
                       <= a["start_ns"] + a["dur_ns"]
                       for a in sp if a["name"] == "admit"), s


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_slice_idle_cover(name):
    idle, covered = RECORDED[name]["idle"]
    sl = S.load(str(DATA / f"{name}.json.gz"))
    c = S.idle_cover(S.view(sl))
    assert c["idle_ns"] * 1e-6 == pytest.approx(idle, abs=0.02)
    assert c["covered_ns"] * 1e-6 == pytest.approx(covered, abs=0.02)
    # the idle share the benchmark reports reads the same idle time
    t = devtrace.reduce(sl["events"])
    assert c["idle_ns"] * 1e-9 == pytest.approx(
        t["window_s"] - t["busy_s"], abs=2e-5)
