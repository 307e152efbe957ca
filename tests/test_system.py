"""End-to-end behaviour: a reduced model trains, checkpoints, serves, and
the sketched-head / grad-compression variants run through the same loop."""
import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SketchConfig
from repro.configs.registry import reduced_config
from repro.models import model as M
from repro.serve.engine import ServeEngine
from repro.train.loop import train


def test_train_then_serve():
    cfg = reduced_config("gemma-2b")
    h = train(cfg, steps=20, batch=2, seq=32, lr=1e-3, log_every=1000,
              log_fn=lambda *_: None)
    assert all(jnp.isfinite(jnp.float32(l)) for l in h.losses)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_seq=48)
    out = engine.generate(jnp.ones((2, 8), jnp.int32), max_new=4)
    assert out.tokens.shape == (2, 4)
    assert int(out.tokens.max()) < cfg.vocab_size


def test_sketched_head_trains():
    cfg = dataclasses.replace(
        reduced_config("minitron-4b"),
        sketch=SketchConfig(sketched_head=True, head_hash_len=32))
    h = train(cfg, steps=40, batch=4, seq=32, lr=3e-3, log_every=1000,
              log_fn=lambda *_: None)
    assert h.losses[-1] < h.losses[0] + 0.1  # finite + not diverging


@pytest.mark.parametrize("outside", [False, True], ids=["unset", "set"])
def test_compile_cache_placement(monkeypatch, tmp_path, outside):
    """The entry points' compile cache: the directory named in
    JAX_COMPILATION_CACHE_DIR when set (JAX reads it; the code sets no
    other), else the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache as cc
    calls = []
    monkeypatch.setattr(cc.jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    if outside:
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        assert calls == []
    else:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
        repo_cache = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert cc.use_compile_cache() == repo_cache
        assert calls == [("jax_compilation_cache_dir", repo_cache)]
