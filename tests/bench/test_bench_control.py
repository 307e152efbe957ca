"""The comparison that decides ``correct``, at a size a test run holds:
the harness drives a tiny dense GQA model through the program's serve
path on the CPU (the look for a chip skipped), the float32 reference
scores a sample of what it served, the float8 control reads above the
limit, and each fault the serving cells can have turns ``correct``
false."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import run as R
from repro.models import transformer as tf
from repro.serve.scheduler import SlotScheduler

ROOT = Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "hidden_act": "silu"}
# at this size the program reads 0.00-0.05 and the float8 control
# 0.24-0.52 (standard deviations of the reference's logits)
LIMIT = 0.15
SERVE = {"max_batch": 4, "max_seq": 256, "decode_chunk": 4,
         "prefill_bucket": 16, "kv_block_size": 8, "prefix_block": 16,
         "prefix_cache_bytes": 1 << 22, "queue_depth": 16}
CHECK = {"sample_tokens": 128, "max_sequences": 8,
         "limits": {"logit_gap": LIMIT}}
TRAFFIC = {
    "batch": {"arrivals": "closed", "residual_first": True,
              "prompt": {"kind": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 8, "max": 96},
              "output": {"kind": "lognormal", "median": 12, "sigma": 0.7,
                         "min": 4, "max": 48}},
    "chat": {"arrivals": "poisson",
             "system_prompts": {"count": 4, "length": 32},
             "prompt": {"kind": "lognormal", "median": 16, "sigma": 1.0,
                        "min": 4, "max": 64},
             "output": {"kind": "lognormal", "median": 10, "sigma": 0.8,
                        "min": 4, "max": 32}},
}


def _cell(mix):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w["name"] for w in spec["workloads"] if w["traffic"] == mix)
    return R.Cell(
        workload=wl, config=TINY, traffic=TRAFFIC[mix],
        cell={"serve": SERVE, "warm_rounds": 2, "warm_s": 1.0,
              "rate_per_s": 8.0, "check": CHECK},
        end_to_end=[m for m in spec["end_to_end"]
                    if wl in m.get("workloads", [wl])],
        per_layer=[])


@pytest.mark.parametrize("mix", sorted(TRAFFIC))
def test_program_passes_and_control_fails(mix):
    res = R.run(_cell(mix), 5, 1.0, False, require_chip=False,
                control=True)
    gap = res["compared"]["logit_gap"]["value"]
    assert res["correct"] and gap <= LIMIT, res["compared"]
    assert res["control"]["logit_gap"] > LIMIT, res["control"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   _cell(mix).end_to_end}


def _state_unchanged(monkeypatch):
    # every decode step hands back the cache it was given: no KV row of
    # a decoded token is ever written
    real = tf.decode_step
    monkeypatch.setattr(tf, "decode_step", lambda p, cache, *a, **k: (
        real(p, cache, *a, **k)[0], cache))


def _token_altered(monkeypatch):
    # the sampler shifts about one token in seven where it is produced
    real = SlotScheduler._make_sampler

    def make():
        sample = real()

        def altered(key, lg, temp, top_k):
            key, nxt = sample(key, lg, temp, top_k)
            return key, jnp.where(nxt % 7 == 3, (nxt + 1) % lg.shape[-1],
                                  nxt)
        return altered

    monkeypatch.setattr(SlotScheduler, "_make_sampler", staticmethod(make))


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_planted_fault_makes_the_run_incorrect(fault, monkeypatch):
    fault(monkeypatch)
    res = R.run(_cell("batch"), 6, 1.0, False, require_chip=False)
    assert not res["correct"], res["compared"]
    assert res["compared"]["logit_gap"]["value"] > 3 * LIMIT
