"""The harness finds a configuration, a traffic mix, a cell and a metric
reader that a later change adds, by name, with no file of ours edited."""
import hashlib
import json
import shutil
from pathlib import Path

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]


def _digest(root):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digest(tmp_path)
    # what a later change would add: files only, and entries
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "hidden_size": 64,
                    "num_hidden_layers": 2}))
    (tmp_path / "bench" / "traffic" / "bursty.json").write_text(
        json.dumps({"arrivals": "poisson",
                    "prompt": {"kind": "lognormal", "median": 8,
                               "sigma": 0.5, "min": 2, "max": 32},
                    "output": {"kind": "lognormal", "median": 4,
                               "sigma": 0.5, "min": 1, "max": 16}}))
    (tmp_path / "bench" / "cells" / "new-model.bursty.json").write_text(
        json.dumps({"serve": {"max_batch": 2}, "rate_per_s": 3.0}))
    (tmp_path / "bench" / "metrics" / "tail_ms.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.window['t_b']\n")
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new-model.bursty",
                              "config": "new-model", "traffic": "bursty",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "tail_ms.bursty", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "x", "moves": "tok_s",
                              "workloads": ["new-model.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = R.load_cell(tmp_path, "new-model.bursty")
    assert cell.model == {"d_model": 64, "num_layers": 2}
    assert cell.traffic["arrivals"] == "poisson"
    assert cell.cell["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["tail_ms.bursty"]
    # metrics listed for all cells (no "workloads") reach it too
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    read = R.reader("tail_ms.bursty", root=tmp_path)
    assert read(R._Ctx({"window": {"t_b": 4.0}})) == 8.0
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
