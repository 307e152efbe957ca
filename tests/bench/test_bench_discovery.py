"""The harness finds a configuration, a traffic mix, a cell and a metric
reader that a later change adds, by name, with no file of ours edited;
a configuration with a block of its own brings the module that serves
and checks it."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

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
                    "num_hidden_layers": 2,
                    "reference": "bench/reference.py"}))
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


# a block the harness has no code for: the dense block with a
# GELU-gated MLP, which the program runs through ModelConfig.act;
# ``{act}`` is the reference's activation
GELU_MODULE = '''"""The dense GQA block with a GELU-gated MLP."""
import dataclasses

import jax

from bench import reference as dense

model = dense.model
leaf_specs = dense.leaf_specs
block_params = dense.block_params


def program_config(config, serve):
    cfg = dense.program_config(dict(config, hidden_act="silu"), serve)
    return dataclasses.replace(cfg, act=config["hidden_act"])


def logit_rows(c, seed, seqs, rows, tokens, mode="f32"):
    return dense.logit_rows(c, seed, seqs, rows, tokens, mode, act={act})
'''
TINY_GELU = {"name": "tiny-gelu", "hidden_size": 64,
             "intermediate_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16,
             "num_hidden_layers": 2, "vocab_size": 512,
             "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
             "hidden_act": "gelu", "reference": "bench/blocks/gelu.py"}
# at this size the program reads 0.00-0.05 and the float8 control
# 0.24-0.52 (tests/bench/test_bench_control.py)
LIMIT = 0.15
TINY_CELL = {"serve": {"max_batch": 4, "max_seq": 256, "decode_chunk": 4,
                       "prefill_bucket": 16, "kv_block_size": 8,
                       "prefix_block": 16, "queue_depth": 16},
             "warm_rounds": 2,
             "check": {"sample_tokens": 128, "max_sequences": 8,
                       "limits": {"logit_gap": LIMIT}}}
TINY_BATCH = {"arrivals": "closed", "residual_first": True,
              "prompt": {"kind": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 8, "max": 96},
              "output": {"kind": "lognormal", "median": 12, "sigma": 0.7,
                         "min": 4, "max": 48}}


@pytest.mark.parametrize("act,correct", [("jax.nn.gelu", True),
                                         ("jax.nn.silu", False)],
                         ids=["gelu", "silu_reference"])
def test_new_block_is_served_and_checked_by_its_module(tmp_path, act,
                                                       correct):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digest(tmp_path)
    bench = tmp_path / "bench"
    (bench / "configs" / "tiny-gelu.json").write_text(json.dumps(TINY_GELU))
    (bench / "blocks").mkdir()
    (bench / "blocks" / "gelu.py").write_text(GELU_MODULE.format(act=act))
    (bench / "traffic" / "tiny-batch.json").write_text(
        json.dumps(TINY_BATCH))
    (bench / "cells" / "tiny-gelu.tiny-batch.json").write_text(
        json.dumps(TINY_CELL))
    spec["configs"].append({"name": "tiny-gelu", "source": "x",
                            "file": "bench/configs/tiny-gelu.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny-gelu.tiny-batch",
                              "config": "tiny-gelu",
                              "traffic": "tiny-batch", "chips": 1,
                              "why": "x"})
    spec["end_to_end"].append({"name": "tok_s.gelu", "unit": "tokens/s",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["tiny-gelu.tiny-batch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = R.load_cell(tmp_path, "tiny-gelu.tiny-batch")
    assert cell.block.program_config(cell.config, None).act == "gelu"
    res = R.run(cell, 2**31 + 977, 1.0, False, require_chip=False)
    gap = res["compared"]["logit_gap"]["value"]
    assert res["correct"] is correct, res["compared"]
    if not correct:
        # a SiLU reference against the GELU the program runs
        assert gap > 3 * LIMIT, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"tok_s.gelu", "setup_s"}
    after = _digest(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
