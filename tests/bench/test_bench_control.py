"""The comparison that decides ``correct``, at a size a test run holds:
the harness drives a tiny dense GQA model through the program's serve
path on the CPU (the look for a chip skipped), the float32 reference
scores a sample of what it served, the float8 control reads above the
limit, and each fault the serving cells can have turns ``correct``
false.  The Llama block's module is pinned: its weights and reference
logits at the tiny size, and its refusal of another block."""
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as ref
from bench import run as R
from bench import weights as W
from repro.models import transformer as tf
from repro.serve.scheduler import SlotScheduler

ROOT = Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "hidden_act": "silu",
        "reference": "bench/reference.py"}
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


# recorded from the harness before the Llama block became a module of
# its own (bench/run.py's MODEL_KEYS, bench/weights.py's leaves): the
# bfloat16 bits of every leaf, in path order, and reference logits
PIN_SEED = 3000000019
PIN_DIGEST = ("7c8c2d9024d51df86c744dc8b950eb37"
              "893240257aa7d92e9b3e7601604d59a1")
PIN_ROWS = [(0, 3), (0, 39), (1, 0), (1, 24)]
PIN_TOKENS = [[5, 9], [100, 7], [511, 0], [42, 300]]
PIN = {
    "f32": {"argmax": [152, 283, 462, 354],
            "best": [3.2861244678497314, 3.4624621868133545,
                     3.5233657360076904, 3.6112468242645264],
            "std": [1.0360673666000366, 0.9555171728134155,
                    1.037347435951233, 1.0535295009613037],
            "at": [[0.6702260375022888, -1.903191089630127],
                   [-1.4732763767242432, 0.7091878056526184],
                   [-1.4436956644058228, 0.9563623070716858],
                   [-0.6307436227798462, -0.06685248017311096]]},
    "fp8": {"argmax": [152, 283, 462, 354],
            "best": [3.52229642868042, 3.43513560295105,
                     3.6035683155059814, 3.6468915939331055]},
}


def test_llama_block_weights_and_reference_are_pinned():
    c = ref.model(TINY)
    params = W.make_params(ref.leaf_specs(c), c["num_layers"], PIN_SEED)
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(params)[0],
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == PIN_DIGEST
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 512, 40).astype(np.int32),
            rng.integers(0, 512, 25).astype(np.int32)]
    for mode, want in PIN.items():
        got = ref.logit_rows(c, PIN_SEED, seqs, PIN_ROWS,
                             np.asarray(PIN_TOKENS, np.int32), mode=mode)
        for k, v in want.items():
            # float32 on the CPU: only the order of a sum may differ
            # (threads), a few units in the last place; another weight
            # or block moves these by tenths
            np.testing.assert_allclose(got[k], v, rtol=1e-5, atol=0,
                                       err_msg=f"{mode} {k}")


@pytest.mark.parametrize("key,value", [("hidden_act", "relu2"),
                                       ("norm", "layernorm1p"),
                                       ("partial_rotary_factor", 0.5),
                                       ("mlp_gated", False)])
def test_llama_block_refuses_another_block(key, value):
    # Minitron-4B's published block, key by key: run as the Llama block
    # on both sides, it would pass ``correct`` while measuring another
    # model
    ref.program_config(TINY, None)
    with pytest.raises(SystemExit, match=key):
        ref.program_config(dict(TINY, **{key: value}), None)
