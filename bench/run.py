"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

A cell is a model configuration (``bench/configs/<config>.json``) under
a traffic mix (``bench/traffic/<traffic>.json``), served with the
geometry of ``bench/cells/<workload>.json``.  The run makes the weights
from the seed on the device, builds the program's ``SlotScheduler``
behind its ``AsyncServeEngine`` and drives that entry with the traffic,
closed (a queue that never drains) or open loop (requests sent when
due).  Set-up ends, and the measured window starts, at the first pump
round boundary once the warm-up the cell file states is over (every
compiled shape has run by then); ``--seconds`` later the run closes.
The measured window is the whole admission cycles in between: it starts
at the first boundary whose next round admits a request and ends at the
last such boundary before ``--seconds`` run out, so that it never ends
just before or just after a long admission round (a round that admits
and prefills takes several times a decode round: one more or less at an
edge moves a rate by percents).  Rates are taken over those whole rounds
on the host clock, after each round's blocking collect.

Then the program's state is freed and a plain float32 reference
(``bench/reference.py``) scores a sample, drawn from the seed, of the
requests the window finished: the widest gap by which a served token's
logit lies below the reference's best decides ``correct``.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read by ``bench/metrics/<name>.py`` from the host
spans, the program's counters and a profiler trace; a traced run's
window is its first ``TRACE_S`` seconds, all of them traced (its raw
trace stays in ``.bench_cache/trace``; ``bench/record_trace.py`` saves
it as test data).  The last
line of standard output is the JSON result.  Needs the chips the cell
asks for: anywhere else it exits non-zero before printing a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"
TRACE_S = 6.0        # seconds of the window a --trace 1 run profiles
WARM_LIMIT_S = 600.0  # a run whose window has not closed by then fails

if __name__ == "__main__":
    # run as a script, bench/ heads sys.path, where a module of ours
    # could shadow one of the standard library's: the checkout root and
    # the program's sources take its place
    if sys.path and Path(sys.path[0]).resolve() == BENCH:
        sys.path.pop(0)
    for _p in (ROOT / "src", ROOT):
        if str(_p) not in sys.path:
            sys.path.insert(0, str(_p))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")

import numpy as np  # noqa: E402

# model keys of a configuration file (public config.json names) -> the
# program's ModelConfig fields
MODEL_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "norm_eps": "norm_eps",
    "hidden_act": "act",
}


@dataclasses.dataclass
class Cell:
    """Everything one workload of BENCHMARK.json names, as data."""
    workload: str
    config: Dict            # the configuration file
    traffic: Dict           # the traffic file
    cell: Dict              # the cell file: serve geometry, rate, checks
    end_to_end: List[Dict]  # metric entries of BENCHMARK.json it reports
    per_layer: List[Dict]
    chips: int = 1

    @property
    def model(self) -> Dict:
        """The configuration in the names the reference and counts use."""
        return {v: self.config[k] for k, v in MODEL_KEYS.items()
                if k in self.config}


def load_cell(root: Path, workload: str) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = next(c for c in spec["configs"] if c["name"] == wl["config"])
    def mine(kind):
        return [m for m in spec[kind]
                if workload in m.get("workloads", [workload])]

    return Cell(
        workload=workload,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{wl['traffic']}.json")
            .read_text()),
        cell=json.loads(
            (root / "bench" / "cells" / f"{workload}.json").read_text()),
        end_to_end=mine("end_to_end"), per_layer=mine("per_layer"),
        chips=int(wl["chips"]))


def reader(name: str, root: Path = ROOT):
    """The metric's reader, ``bench/metrics/<name>.py`` or, for a name
    with a suffix, ``bench/metrics/<base>.py``: a ``read(ctx)`` that
    returns the value, or None where the run has nothing to read."""
    for stem in (name, name.split(".")[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Recorder:
    """The benchmark's view of a run, fed by the program's observer
    hooks (the scheduler and the front-end call them on the host) and by
    the consumers of the token streams.  Round boundaries, the window,
    and the traced sub-window are decided here, at the end of each
    round's collect."""

    def __init__(self, cell: Cell, seconds: float, trace: bool,
                 t0: float, clock=time.perf_counter, cpu=None):
        self.cell = cell
        self.clock = clock
        # CPU seconds of the thread that runs the pump, read at each
        # boundary (set by the driver; None: not read)
        self.cpu = cpu
        self.seconds = float(seconds)
        self.trace = trace
        self.t0 = t0                     # traffic time zero
        self.closed = cell.traffic["arrivals"] == "closed"
        self.sched: Any = None
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self.on_close = None
        self.rounds: List[Dict] = []     # one per collect
        self.admits = 0                  # admissions in the open round
        self.prompt_len: Dict[int, int] = {}
        self.delivered: Dict[int, int] = {}
        self.admitted: Dict[int, float] = {}
        self.finished = 0
        self.win_a: Optional[int] = None     # boundary at warm
        self.win_b: Optional[int] = None     # last boundary in time
        self.closing = False
        # traced sub-window: rounds (tr_a, tr_b], from host time tr_t0;
        # acc counts the work dispatched in it
        self.tr_a: Optional[int] = None
        self.tr_b: Optional[int] = None
        self.tr_t0: Optional[float] = None
        self.tr_dir = str(CACHE / "trace")
        self.acc = {"decode_tokens": 0, "decode_keys": 0.0,
                    "prefill_rows": 0, "prefill_keys": 0.0,
                    "prefill_bytes_keys": 0.0}
        self.fidelity_every = 0          # the observer API's probe knob
        self.error: Optional[BaseException] = None
        self.on_first_round = None

    # -- observer hooks (program -> benchmark) --------------------------

    def request_queued(self, rid, prompt_len, priority=0):
        self.prompt_len[rid] = int(prompt_len)

    def request_admitted(self, rid, slot, hit):
        self.admitted.setdefault(rid, self.clock())
        self.admits += 1

    def request_finished(self, rid, status, n_tokens):
        if status == "ok":
            self.finished += 1

    def tokens_delivered(self, rid, n_new):
        before = self.delivered.get(rid, 0)
        self.delivered[rid] = before + n_new
        if self._tracing():
            from bench import counts
            self.acc["decode_tokens"] += n_new
            self.acc["decode_keys"] += counts.decode_key_sum(
                self.prompt_len[rid] + before, n_new)

    def prefill_span(self, slot, off, rows, dur_s):
        if self._tracing():
            from bench import counts
            self.acc["prefill_rows"] += rows
            self.acc["prefill_keys"] += counts.chunk_key_sum(off, rows)
            self.acc["prefill_bytes_keys"] += off + rows

    def chunk_collected(self, tokens, queue_depth, active):
        # runs inside the program's collect: a fault here must end the
        # run, not the pump task alone
        try:
            self._boundary(tokens)
        except Exception as e:          # noqa: BLE001 - reported below
            self.error = e
            self._close()

    def _boundary(self, tokens):
        now = self.clock()
        i = len(self.rounds)
        self.rounds.append({"t": now, "tokens": int(tokens),
                            "admits": self.admits,
                            "cpu": self.cpu() if self.cpu else None})
        self.admits = 0
        if i == 0 and self.on_first_round is not None:
            self.loop.call_soon_threadsafe(self.on_first_round)
        if self.win_a is None:
            if self._warm(now):
                self.win_a = i
                if self.trace:
                    self._trace_start(i)
            return
        if self.trace:
            # a traced run's window is the traced span: stopping the
            # profiler stalls the pump for seconds, so nothing after it
            # describes the program
            if self.tr_b is not None:
                return
            if now - self.tr_t0 >= TRACE_S or \
                    now - self.rounds[self.win_a]["t"] > self.seconds:
                self._trace_stop(i)
                self.win_b = i
                self._close()
            return
        if now - self.rounds[self.win_a]["t"] <= self.seconds:
            self.win_b = i
        else:
            self._close()

    def _close(self):
        if not self.closing:
            self.closing = True
            self.loop.call_soon_threadsafe(self.on_close)

    def __getattr__(self, name):
        # any other hook of the observer API: nothing to record
        if name.startswith("_"):
            raise AttributeError(name)
        return lambda *a, **k: None

    # -- window ---------------------------------------------------------

    def _warm(self, now: float) -> bool:
        """Warm once a request has finished (retirement has run) and a
        whole block of the traffic has been admitted (every prompt
        length the mix draws has gone through admission, whose shapes
        the program compiles per length), and then after the cell's
        warm rounds (closed loop) or seconds of arrivals (open loop)."""
        from bench.traffic import BLOCK
        c = self.cell.cell
        if self.finished < 1 or len(self.admitted) < BLOCK:
            return False
        if self.closed:
            return len(self.rounds) >= c["warm_rounds"]
        return now - self.t0 >= c["warm_s"]

    def _tracing(self) -> bool:
        return self.tr_a is not None and self.tr_b is None

    def _quiesce(self):
        import jax
        jax.block_until_ready(self.sched.state)

    def _trace_start(self, i: int) -> None:
        import jax

        from bench import devtrace
        shutil.rmtree(self.tr_dir, ignore_errors=True)
        self._quiesce()
        jax.profiler.start_trace(self.tr_dir,
                                 profiler_options=devtrace.options())
        self.tr_a, self.tr_t0 = i, time.perf_counter()

    def _trace_stop(self, i: int) -> None:
        import jax
        self._quiesce()
        self.tr_b = i
        jax.profiler.stop_trace()

    def cycles(self, a: int, b: int) -> tuple:
        """Boundaries ``(a', b')`` of the whole admission cycles inside
        ``(a, b)``: ``a'`` the first boundary from ``a`` on whose next
        round admits, ``b'`` the last such up to ``b``; ``(a, b)``
        itself where fewer than two boundaries qualify."""
        rs = self.rounds
        edges = [k for k in range(a, b + 1)
                 if k + 1 < len(rs) and rs[k + 1]["admits"] > 0]
        if len(edges) < 2:
            return a, b
        return edges[0], edges[-1]

    def window(self) -> Dict:
        a, b = self.win_a, self.win_b
        if a is None or b is None or b <= a:
            return {}
        # set-up ends at warm; programs are counted to the close
        t_warm, t_end = self.rounds[a]["t"], self.rounds[-1]["t"]
        if not self.trace:
            a, b = self.cycles(a, b)
        rs = self.rounds[a + 1:b + 1]
        t_a, t_b = self.rounds[a]["t"], self.rounds[b]["t"]
        return {"a": a, "b": b, "t_a": t_a, "t_b": t_b, "rounds": rs,
                "t_warm": t_warm,
                "tokens": sum(r["tokens"] for r in rs),
                "built": sum(t_warm < t <= t_end for t in COMPILES),
                "built_names": sorted({n for t, n in BUILT.names
                                       if t_warm < t <= t_end}),
                "loaded": sum(t_warm < t <= t_end for t in CACHE_HITS)}


def span_phases(sched) -> None:
    """Host spans around the scheduler's pump phases, written into the
    profiler's trace so that idle gaps on the device can be named by
    what the host was doing."""
    import jax
    for name in ("admit_pending", "dispatch", "collect"):
        real = getattr(sched, name)

        def spanned(*a, _real=real, _name=name, **k):
            with jax.profiler.TraceAnnotation(f"bench.{_name}"):
                return _real(*a, **k)

        setattr(sched, name, spanned)


COMPILES: List[float] = []      # host times of programs built
CACHE_HITS: List[float] = []    # ... of those, loaded from the cache
COMPILES_HOOKED: List[bool] = []


def _on_compile(name, dur, **kw):
    if name == "/jax/core/compile/backend_compile_duration":
        COMPILES.append(time.perf_counter())


def _on_event(name, **kw):
    if name == "/jax/compilation_cache/cache_hits":
        CACHE_HITS.append(time.perf_counter())


class _BuiltNames(logging.Handler):
    """Names of the programs JAX builds, from its compile log records,
    with the host time of each (JAX logs them below the level it
    prints unless ``jax_log_compiles`` is on)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names: List[tuple] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            self.names.append((time.perf_counter(),
                               msg.split(" of ", 1)[1].split(" in ")[0]))


BUILT = _BuiltNames()


def warm_release_shapes(sched) -> None:
    """The scheduler frees slots with one scatter into its block tables
    per round, a program built for each count of slots freed at once;
    build each count the engine can free, on a scratch table of the same
    shape, so that none is built inside the window."""
    import jax
    import jax.numpy as jnp
    tables = sched.state.tables
    scratch = jnp.zeros(tables.shape, tables.dtype)
    for n in range(1, sched.serve.max_batch + 1):
        jax.block_until_ready(
            scratch.at[np.arange(n)].set(sched.num_blocks))


# the program's dense block; a configuration file states its block in
# these keys, and one that states another cannot be run by this program
BLOCK = {"norm": "rmsnorm", "partial_rotary_factor": 1.0, "mlp_gated": True}


def model_config(cell: Cell):
    from repro.configs.base import ModelConfig, ServeConfig
    for k, v in BLOCK.items():
        if cell.config.get(k, v) != v:
            raise SystemExit(f"{cell.config['name']}: {k} "
                             f"{cell.config[k]!r}, but the program's "
                             f"dense block has {v!r}")
    m = cell.model
    cfg = ModelConfig(name=cell.config["name"], family="dense",
                      num_layers=m["num_layers"], d_model=m["d_model"],
                      num_heads=m["num_heads"],
                      num_kv_heads=m["num_kv_heads"], d_ff=m["d_ff"],
                      vocab_size=m["vocab_size"], head_dim=m["head_dim"],
                      act=m.get("act", "silu"),
                      rope_theta=float(m["rope_theta"]),
                      norm_eps=float(m["norm_eps"]))
    serve = ServeConfig(**cell.cell["serve"])
    return cfg, serve


async def _drive(cell: Cell, sched, rec: Recorder, src, reqs: Dict
                 ) -> Dict:
    """Serve the request stream ``src`` through the async front-end
    until the recorder closes the window, keeping each submitted request
    in ``reqs``; returns per-request records and completions."""
    from bench.traffic import BLOCK
    from repro.serve.frontend import AsyncServeEngine
    engine = AsyncServeEngine(scheduler=sched)
    loop = asyncio.get_running_loop()
    closed = asyncio.Event()
    first = asyncio.Event()
    rec.loop, rec.on_close, rec.on_first_round = loop, closed.set, first.set
    clock_id = time.pthread_getcpuclockid(threading.get_ident())
    rec.cpu = lambda: time.clock_gettime(clock_id)
    recs: Dict[int, Dict] = {}
    consumers: List[asyncio.Task] = []

    async def consume(h, r):
        times = recs[r.rid]["times"]
        async for _ in h.stream():
            times.append(time.perf_counter())
        c = h.completion
        recs[r.rid]["status"] = c.status
        recs[r.rid]["out"] = np.asarray(c.tokens, np.int32)

    async def submit(r, due):
        reqs[r.rid] = r
        t_sub = time.perf_counter()
        h = await engine.submit(r.tokens, r.max_new, temperature=0.0,
                                rid=r.rid)
        recs[r.rid] = {"due": due, "submit": t_sub, "times": [],
                       "max_new": r.max_new, "status": None}
        consumers.append(loop.create_task(consume(h, r)))
        return h

    async def produce():
        if not rec.closed:
            # set-up of an open loop: its first block, submitted at once
            # and cancelled once every request of it is admitted, runs
            # each compiled shape the mix uses (prefill, decode, and the
            # admission path's per-prompt-length programs); the arrival
            # clock starts after it, so set-up leaves no backlog
            warm = [await submit(next(src), time.perf_counter())
                    for _ in range(BLOCK)]
            while not (first.is_set()
                       and all(h.rid in rec.admitted for h in warm)):
                await asyncio.sleep(0.01)
            for h in warm:
                h.cancel()
            rec.t0 = time.perf_counter()
        due0 = None
        for r in src:
            if due0 is None:
                due0 = r.due_s
            due = rec.t0 + (0.0 if rec.closed else r.due_s - due0)
            wait = due - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            if rec.closing:
                return
            await submit(r, due)

    producer = loop.create_task(produce())
    limit = WARM_LIMIT_S + rec.seconds
    try:
        await asyncio.wait_for(closed.wait(), limit)
    except asyncio.TimeoutError:
        rec.error = RuntimeError(f"window not closed after {limit} s: "
                                 f"{len(rec.rounds)} rounds, "
                                 f"{len(rec.admitted)} admitted, "
                                 f"{rec.finished} finished")
        rec.closing = True
    producer.cancel()
    try:
        await producer
    except asyncio.CancelledError:
        pass
    await engine.aclose()
    await asyncio.gather(*consumers)
    return recs


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, control: bool = False,
        inspect=None) -> Dict:
    """One run of ``cell``; returns the result object.  ``control``
    also reads the lower-precision control on the same sample (the
    reference in float8 put in the program's place: the f32 reference's
    gap at the token the float8 reference ranks first), under the
    result's ``control`` key; the benchmark's own runs never do.
    ``inspect``, if given, is called with the metric readers' context."""
    import jax

    from bench import counts
    from bench import reference as ref
    from bench import traffic
    from bench import weights as W

    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise SystemExit(f"needs {cell.chips} TPU chip(s); JAX found "
                         f"{len(devs)} {dev.platform} device(s)")
    if require_chip:
        counts.peak(dev.device_kind)        # an unknown chip fails here
    if not COMPILES_HOOKED:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        jax.monitoring.register_event_listener(_on_event)
        lg = logging.getLogger("jax._src.dispatch")
        lg.addHandler(BUILT)
        if lg.getEffectiveLevel() > logging.DEBUG:
            lg.setLevel(logging.DEBUG)
            lg.propagate = False
        COMPILES_HOOKED.append(True)

    from repro.serve.scheduler import SlotScheduler
    cfg, serve = model_config(cell)
    model = cell.model
    setup = {}
    t = time.perf_counter()
    params = jax.block_until_ready(W.make_params(model, seed))
    setup["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    sched = SlotScheduler(cfg, params, serve=serve)
    warm_release_shapes(sched)
    setup["engine_s"] = time.perf_counter() - t
    reqs: Dict[int, Any] = {}
    src = traffic.stream(cell.traffic, cell.cell, seed, model["vocab_size"])
    t0 = time.perf_counter()
    rec = Recorder(cell, seconds, trace, t0)
    rec.sched = sched
    sched.set_observer(rec)
    if trace:
        span_phases(sched)
    recs = asyncio.run(_drive(cell, sched, rec, src, reqs))
    if rec.error is not None:
        raise RuntimeError("run failed") from rec.error
    win = rec.window()
    if not win:
        raise RuntimeError(f"no measured window: {len(rec.rounds)} rounds, "
                           f"{rec.finished} finished")
    setup["first_round_s"] = rec.rounds[0]["t"] - t0
    setup["warm_s"] = win["t_warm"] - rec.rounds[0]["t"]
    setup_s = win["t_warm"] - T_START
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    stats = sched.stats()

    # free the program's state before the reference runs
    sched.set_observer(None)
    rec.sched = None
    del sched, params
    gc.collect()

    # correctness: a sample of the finished requests against the
    # reference, drawn from the seed, with the longest among them
    chk = cell.cell["check"]
    fin = [(rid, r) for rid, r in recs.items() if r["status"] == "ok"]
    rng = np.random.default_rng([seed, 1])
    pick: List[int] = []
    if fin:
        lens = {rid: len(reqs[rid].tokens) + len(r["out"])
                for rid, r in fin}
        order = [max(lens, key=lens.get)]
        rest = [rid for rid, _ in fin if rid != order[0]]
        order += [rest[i] for i in rng.permutation(len(rest))]
        served = 0
        for rid in order:
            if served >= chk["sample_tokens"] or \
                    len(pick) >= chk["max_sequences"]:
                break
            pick.append(rid)
            served += len(recs[rid]["out"])
    samples = [(reqs[rid].tokens, recs[rid]["out"]) for rid in pick]
    compared = {}
    if samples:
        t = time.perf_counter()
        seqs, rows, toks = ref.served_rows(samples)
        cols = [toks]
        if control:
            cols.append(ref.logit_rows(model, seed, seqs, rows, toks,
                                       mode="fp8")["argmax"])
        r32 = ref.logit_rows(model, seed, seqs, rows, np.stack(cols, 1))
        gap = float(ref.gaps(r32, 0).max())
        ref_s = time.perf_counter() - t
        compared["logit_gap"] = {"value": gap,
                                 "limit": chk["limits"]["logit_gap"]}
    else:
        ref_s = 0.0
        compared["finished"] = {"value": 0, "limit": 1}
    failed = sum(1 for r in recs.values()
                 if r["status"] not in ("ok", "cancelled"))
    correct = (bool(samples) and failed == 0
               and compared["logit_gap"]["value"]
               <= compared["logit_gap"]["limit"])

    ctx = dict(cell=cell, model=model, serve=dataclasses.asdict(serve),
               window=win, recs=recs, rec=rec, setup_s=setup_s,
               stats=stats, trace=None, traced=None,
               peak=counts.peak(dev.device_kind) if require_chip else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from bench import devtrace
        tr = devtrace.extract(rec.tr_dir)
        # from the first device op to the last: the drains that bracket
        # the trace are the benchmark's, not the program's
        red = devtrace.reduce(tr["events"])
        ctx["trace"] = red
        ctx["traced"] = dict(rec.acc, window_s=red.get("window_s"),
                             rounds=rec.rounds[rec.tr_a + 1:rec.tr_b + 1])
        device["busy_s"] = red.get("busy_s")
        device["window_s"] = red.get("window_s")
        breakdown = {"device_ops": red.get("device_ops", []),
                     "idle_gaps": red.get("idle_gaps", [])}
    metrics = {}
    ns = _Ctx(ctx)
    if inspect is not None:
        inspect(ns)
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(ns)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    log(f"setup: {setup_s!r} s from process start to the end of "
        f"warm-up: weights {setup['weights_s']!r} s, engine and its "
        f"slot-release programs {setup['engine_s']!r} s, first round "
        f"(compile or cache load, first admissions' prefill) "
        f"{setup['first_round_s']!r} s, warm rounds {setup['warm_s']!r} s")
    t_r = [r["t"] for r in rec.rounds[win["a"]:win["b"] + 1]]
    dur = np.diff(t_r)
    log("rounds (ms, admissions): " + " ".join(
        f"{1e3 * d:.0f},{r['admits']}" for d, r in zip(dur, win["rounds"])))
    log(f"window: {len(t_r) - 1} rounds from boundary {win['a']} to "
        f"{win['b']} (warm at {rec.win_a}, last in time {rec.win_b}), "
        f"{win['t_b'] - win['t_a']!r} s, {win['tokens']} tokens, "
        f"{sum(r['admits'] > 0 for r in win['rounds'])} rounds admitting, "
        f"{win['built']} programs built from warm to close "
        f"({win['loaded']} of them loaded from the compile cache: "
        f"{', '.join(win['built_names'])}), "
        f"longest round {float(dur.max())!r} s, "
        f"{len(fin)} requests finished, decode compilations "
        f"{stats.decode_compilations}, prefill compilations "
        f"{stats.prefill_compilations}")
    if not rec.closed:
        ttft = [1e3 * (r["times"][0] - r["due"]) for r in recs.values()
                if r["times"] and win["t_a"] < r["times"][0] <= win["t_b"]]
        if ttft:
            q = np.percentile(ttft, [50, 75, 90, 99]).tolist()
            log(f"latency: {len(ttft)} first tokens in the window, time to "
                f"first token p50/p75/p90/p99 {q!r} ms, mean "
                f"{float(np.mean(ttft))!r} ms")
    log(f"reference: {len(samples)} requests, "
        f"{sum(len(o) for _, o in samples)} served tokens, {ref_s!r} s")
    for k, v in compared.items():
        log(f"compared {k}: {v['value']!r} (limit {v['limit']!r})")
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control and samples:
        result["control"] = {"logit_gap": float(ref.gaps(r32, 1).max())}
    result["compared"] = compared
    return result


def use_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.bench_cache/jax``, holding every program, however small, so that
    only a checkout's first run of a cell compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _Ctx:
    """Attribute view of the run context handed to metric readers."""

    def __init__(self, d: Dict):
        self.__dict__.update(d)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    use_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
