"""Training loop with the production-survival features:

  * checkpoint cadence with atomic writes + resume-from-LATEST (bitwise:
    the data pipeline is stateless-seeded by step, optimizer state is saved)
  * straggler monitor: per-step wall-time EMA; steps slower than
    ``straggler_factor`` x EMA are logged with their step id (on real
    multi-host deployments this feeds host-eviction; here it drives the
    log + test hooks)
  * optional FCS gradient compression (error-feedback state is part of the
    checkpoint, so restarts preserve convergence behaviour)
  * optional sketched optimizer state (cfg.sketch.opt_state_ratio > 0):
    AdamW moments live in count-sketch tables (repro.sketch), shrinking
    optimizer memory to O(numel/ratio); the state pytree checkpoints and
    resumes like the dense one.
  * optional loss-spike skip: steps whose loss is > spike_factor x EMA are
    applied with zero LR (gradient skipped), a common large-run guard.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.train import checkpoint as ckpt_lib
from repro.train import data as data_lib
from repro.train.grad_compress import (init_error_feedback,
                                       make_compressed_train_step)
from repro.train.optimizer import make_optimizer


@dataclass
class TrainHistory:
    losses: List[float] = field(default_factory=list)
    step_times: List[float] = field(default_factory=list)
    stragglers: List[int] = field(default_factory=list)
    skipped: List[int] = field(default_factory=list)


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          lr: float = 3e-4, seed: int = 0,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          resume: bool = False, grad_compression: Optional[bool] = None,
          straggler_factor: float = 3.0, spike_factor: float = 4.0,
          log_every: int = 10, crash_at_step: Optional[int] = None,
          log_fn: Callable[[str], None] = print) -> TrainHistory:
    """Single-process trainer (tests/examples scale; the distributed path
    shares the same step functions via launch/train.py)."""
    compress = (cfg.sketch.grad_compression if grad_compression is None
                else grad_compression)
    key = jax.random.PRNGKey(seed)
    params = M.init_params_jit(key, cfg)
    opt_init, opt_update = make_optimizer(cfg, lr=lr)
    opt = opt_init(params)
    if cfg.sketch.opt_state_ratio > 0:
        from repro.sketch.optimizer import moment_state_bytes
        b = moment_state_bytes(opt)
        shrink = (b["sketched_dense_equiv"] / b["sketched"]
                  if b["sketched"] else 1.0)
        log_fn(f"[opt] sketched moments: {b['sketched']} B sketched "
               f"({shrink:.1f}x vs dense) + {b['dense']} B dense leaves")
    ef = init_error_feedback(params, cfg.sketch.grad_hash_ratio,
                             cfg.sketch.seed) if compress else None
    start_step = 0

    if resume and ckpt_dir and ckpt_lib.latest_step(ckpt_dir) is not None:
        state_like = {"params": params, "opt": opt, "ef": ef}
        step_loaded, state = ckpt_lib.restore(ckpt_dir, state_like)
        params, opt, ef = state["params"], state["opt"], state["ef"]
        start_step = step_loaded
        log_fn(f"[resume] from step {start_step}")

    if compress:
        grad_step = make_compressed_train_step(cfg)
    base_step = M.make_train_step(cfg)

    @jax.jit
    def step_fn(params, opt, ef, batch_d, skip, step_idx):
        if compress:
            loss, grads, ef = grad_step(params, ef, batch_d, step_idx)
        else:
            loss, grads = base_step(params, batch_d)
        new_params, new_opt = opt_update(grads, opt, params)
        # loss-spike guard: keep old params/opt when skipping
        new_params = jax.tree.map(
            lambda np_, p: jnp.where(skip, p, np_), new_params, params)
        new_opt = jax.tree.map(
            lambda no, o: jnp.where(skip, o, no), new_opt, opt)
        return loss, new_params, new_opt, ef

    hist = TrainHistory()
    ema_time = None
    ema_loss = None
    for step in range(start_step, steps):
        if crash_at_step is not None and step == crash_at_step:
            raise RuntimeError(f"injected crash at step {step}")
        bd = data_lib.make_batch(cfg, step, batch, seq, seed)
        t0 = time.time()
        loss, params, opt, ef = step_fn(params, opt, ef, bd,
                                        jnp.bool_(False), jnp.int32(step))
        loss = float(loss)
        dt = time.time() - t0
        hist.losses.append(loss)
        hist.step_times.append(dt)
        if ema_time is not None and dt > straggler_factor * ema_time:
            hist.stragglers.append(step)
            log_fn(f"[straggler] step {step}: {dt:.3f}s vs EMA "
                   f"{ema_time:.3f}s")
        ema_time = dt if ema_time is None else 0.9 * ema_time + 0.1 * dt
        if ema_loss is not None and loss > spike_factor * max(ema_loss, 1e-6):
            hist.skipped.append(step)
        ema_loss = loss if ema_loss is None else 0.9 * ema_loss + 0.1 * loss
        if step % log_every == 0:
            log_fn(f"step {step:5d} loss {loss:.4f} ({dt*1e3:.0f} ms)")
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_lib.save(ckpt_dir, step + 1,
                          {"params": params, "opt": opt, "ef": ef},
                          extra={"cfg": cfg.name})
    return hist
