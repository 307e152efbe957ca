"""The serve path's own spans in JAX's profiler trace (``repro.obs.span``,
``serve.*``), the names of the programs the scheduler compiles, and the
named scopes inside the model step: spans present and nested where the
work happens, one read-back per collected round, tokens unchanged with
the profiler on, no program named ``_unknown``, scopes in the HLO."""
import asyncio
import dataclasses
import glob
import logging
import os
import re

import jax
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.models import model as M
from repro.serve.frontend import AsyncServeEngine
from repro.serve.scheduler import Request, SlotScheduler

SPANS = ("admit", "prefix_lookup", "prefix_count", "prefill_chunk",
         "dispatch", "collect", "collect.wait", "retire", "boundary",
         "deliver", "yield")


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced_config("gemma-2b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, **kw):
    base = dict(max_batch=2, max_seq=128, decode_chunk=4,
                prefill_bucket=16)
    base.update(kw)
    return dataclasses.replace(cfg.serve, **base)


def _prompts(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def _stream(cfg, params, serve, lens=(20, 37, 9, 50), max_new=6):
    """Four requests through the async front-end over two slots, then
    two through the pump's phases by hand, the second admitted while the
    first one's decode chunk is in flight; returns the scheduler and the
    served tokens by rid."""
    sched = SlotScheduler(cfg, params, serve=serve)
    front = AsyncServeEngine(scheduler=sched)
    prompts = _prompts(cfg, lens)

    async def go():
        hs = [await front.submit(p, max_new=max_new, rid=i)
              for i, p in enumerate(prompts)]
        return {h.rid: [t async for t in h.stream()] for h in hs}

    outs = asyncio.run(go())
    sched.submit(Request(rid=10, tokens=prompts[1], max_new=max_new))
    sched.admit_pending()
    sched.dispatch()
    sched.submit(Request(rid=11, tokens=prompts[3], max_new=max_new))
    assert sched.admit_pending() == 1
    sched.collect()
    sched.drain()
    outs.update({c.rid: [int(t) for t in c.tokens]
                 for c in sched.completed if c.rid >= 10})
    return sched, outs


def _profile_spans(log_dir):
    """``serve.*`` events of the newest profile under ``log_dir``:
    (name, thread, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name.split("#")[0][len("serve."):],
                                (plane.name, i), e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(gemma, tmp_path_factory):
    cfg, params = gemma
    serve = _serve(cfg)
    _, plain = _stream(cfg, params, serve)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        sched, outs = _stream(cfg, params, serve)
    return sched, outs, plain, _profile_spans(log_dir)


def test_every_span_of_the_vocabulary_is_traced(traced):
    _, _, _, spans = traced
    names = {s[0] for s in spans}
    assert set(SPANS) <= names, sorted(set(SPANS) - names)
    admits = [s for s in spans if s[0] == "admit"]
    assert {"rid", "slot", "ok"} <= set(admits[0][4])
    assert all(s[4]["ok"] for s in admits)
    pf = [s for s in spans if s[0] == "prefill_chunk"]
    assert {"slot", "off", "rows"} <= set(pf[0][4])


def _inside(inner, outer):
    return (inner[1] == outer[1] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def test_observer_gets_the_four_pump_phases(gemma):
    """Of the spans, only the pump's four phases reach an attached
    observer: its ``X`` events on the pump track and its ``pump.*``
    histograms hold dispatch, collect, prefill_chunk and admit, so the
    bounded trace keeps its room for requests."""
    from repro.obs import ServeObserver, Tracer
    cfg, params = gemma
    obs = ServeObserver(tracer=Tracer(sample_rate=1.0))
    front = AsyncServeEngine(scheduler=SlotScheduler(
        cfg, params, serve=_serve(cfg)), obs=obs)
    prompts = _prompts(cfg, (20, 37, 9), seed=1)

    async def go():
        hs = [await front.submit(p, max_new=4, rid=i)
              for i, p in enumerate(prompts)]
        return [[t async for t in h.stream()] for h in hs]

    assert all(len(o) == 4 for o in asyncio.run(go()))
    phases = {"dispatch", "collect", "prefill_chunk", "admit"}
    xs = {e["name"] for e in obs.tracer.events() if e["ph"] == "X"}
    assert xs == phases
    assert {k for k in obs.registry.hists if k.startswith("pump.")} == {
        f"pump.{p}_s" for p in phases}


def test_prefill_and_tracker_nest_in_admission(traced):
    _, _, _, spans = traced
    admits = [s for s in spans if s[0] == "admit"]
    for name in ("prefill_chunk", "prefix_count", "prefix_lookup"):
        inner = [s for s in spans if s[0] == name]
        assert inner, name
        for s in inner:
            assert any(_inside(s, a) for a in admits), (name, s)
    # some admission ran while a decode chunk was in flight: between a
    # dispatch's end and the next read-back's end
    disp = sorted(s[3] for s in spans if s[0] == "dispatch")
    waits = sorted(s[3] for s in spans if s[0] == "collect.wait")
    assert any(d < a[2] and a[3] <= w for d, w in zip(disp, waits)
               for a in admits)


def test_one_read_back_per_collected_round(traced):
    sched, _, _, spans = traced
    rounds = sched.decode_steps // sched.serve.decode_chunk
    collects = [s for s in spans if s[0] == "collect"]
    waits = [s for s in spans if s[0] == "collect.wait"]
    retires = [s for s in spans if s[0] == "retire"]
    assert len(collects) == len(waits) == len(retires) == rounds > 0
    assert len([s for s in spans if s[0] == "dispatch"]) == rounds
    for w in waits + retires:
        assert sum(_inside(w, c) for c in collects) == 1, w


def test_tokens_bitwise_equal_with_profiler_on(traced):
    _, outs, plain, _ = traced
    assert outs == plain
    assert sorted(outs) == [0, 1, 2, 3, 10, 11]
    assert all(len(t) == 6 for t in outs.values())


class _Compiled(logging.Handler):
    """Names of the programs JAX compiles, from its compile log."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = []

    def emit(self, record):
        m = re.match(r"Compiling jit\((\w+)\)", record.getMessage())
        if m:
            self.names.append(m.group(1))


@pytest.mark.parametrize("kind", ["plain", "sketch", "spec", "recurrent"])
def test_no_scheduler_program_is_unknown(gemma, kind):
    """Every program a scheduler compiles while serving carries a name:
    a trace shows it as ``jit_<name>``, never ``jit__unknown`` (a
    ``functools.partial``) or ``jit__lambda``."""
    cfg, params = gemma
    serve = _serve(cfg)
    if kind == "sketch":
        serve = _serve(cfg, max_seq=256, num_kv_blocks=24,
                       kv_sketch_window=2 * cfg.serve.kv_block_size)
    elif kind == "spec":
        serve = _serve(cfg, spec_k=2, draft_depth=1)
    elif kind == "recurrent":
        cfg = reduced_config("xlstm-1.3b")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        serve = _serve(cfg)
    log = logging.getLogger("jax._src.interpreters.pxla")
    h, level = _Compiled(), log.level
    log.addHandler(h)
    log.setLevel(logging.DEBUG)
    try:
        sched = SlotScheduler(cfg, params, serve=serve)
        lens = (150, 20) if kind == "sketch" else (20, 37, 9)
        sched.run([Request(rid=i, tokens=p, max_new=4)
                   for i, p in enumerate(_prompts(cfg, lens))])
    finally:
        log.removeHandler(h)
        log.setLevel(level)
    assert "_unknown" not in h.names and "_lambda" not in h.names, \
        sorted(set(h.names))
    chunk = {"plain": "chunk_fn", "sketch": "sketched_chunk_fn",
             "spec": "spec_chunk_fn", "recurrent": "chunk_fn"}[kind]
    assert chunk in h.names, sorted(set(h.names))
    if kind != "recurrent":
        pre = "sketched_prefill_chunk" if kind == "sketch" \
            else "prefill_chunk"
        assert pre in h.names, sorted(set(h.names))
    if kind == "spec":
        assert "draft_prefill_chunk" in h.names
    if kind == "recurrent":
        assert "recurrent_prefill" in h.names
    # no new name reads as the decode chunk to a trace reduction
    assert [n for n in h.names if "chunk_fn" in n] == \
        [n for n in h.names if n == chunk]


def test_decode_chunk_hlo_carries_named_scopes(gemma):
    """The lowered decode chunk of a sketch engine names its pieces in
    the ops' metadata: attention, mlp, the head with sampling, and the
    FCS fold and tail."""
    cfg, params = gemma
    serve = _serve(cfg, max_seq=256, num_kv_blocks=24,
                   kv_sketch_window=2 * cfg.serve.kv_block_size)
    sched = SlotScheduler(cfg, params, serve=serve)
    fold = np.zeros((serve.max_batch,), np.int32)
    text = sched._chunk_fn.lower(sched.params, sched.state,
                                 fold).as_text(debug_info=True)
    scopes = {seg for n in re.findall(r'loc\("([^"]*)"', text)
              for seg in n.split("/")}
    for scope in ("attention", "mlp", "lm_head", "fcs_fold", "fcs_tail"):
        assert scope in scopes, scope
