"""Async serving front-end: an always-on admission/prefill/decode pump
over the slot scheduler's phase API.

``ServeEngine.generate`` is batch-in/batch-out; production traffic is an
open stream — requests arrive one at a time, consumers want tokens as
they commit, clients hang up, and some requests matter more than others.
``AsyncServeEngine`` exposes that shape:

    engine = AsyncServeEngine(cfg, params)
    handle = await engine.submit(prompt, max_new=64, priority=1,
                                 deadline_s=2.0)
    async for tok in handle.stream():
        ...                         # tokens as each decode chunk lands
    handle.cancel()                 # mid-flight: slot + blocks free now
    completion = await handle.result()

One asyncio task (the PUMP) owns the scheduler.  Each iteration:

    1. pump boundary: apply queued cancellations, expire deadlines
       (both ride slot-retire + block-free — CoW forks and folded
       tails already make mid-flight eviction safe);
    2. ``admit_pending()`` — queued requests into free slots, possibly
       preempting strictly lower-priority running slots;
    3. ``dispatch()`` — the compiled decode chunk launches and returns
       device FUTURES immediately;
    4. overlap: the pump yields to the event loop, so new submissions
       land and a second ``admit_pending()`` runs THEIR host-side
       bookkeeping and chunked prefill while the device crunches (the
       in-flight chunk read pre-admission state: an idle slot emits
       nothing and its sentinel table row drops the KV write, and the
       prefill ops enqueue after the chunk in device-stream order);
    5. ``collect()`` — run in a worker thread so the event loop stays
       live while the host blocks on the chunk — then per-token deltas
       fan out to handle queues and finished requests resolve.

The pump task exits when the scheduler drains and restarts on the next
submit, so ``asyncio.run`` driver loops never leak a pending task.

Backpressure: ``submit`` awaits while ``cfg.serve.queue_depth`` requests
are already queued — it defers, it NEVER raises — so an open-loop
arrival process can't grow host state without bound; the bound is the
admission queue, the pool pressure story is unchanged (admission defers
until blocks free).

Ordering / identity: admission order is the scheduler's priority-banded
FIFO, decode runs the same one-compilation-per-engine chunk, and a
greedy request's streamed tokens are BITWISE the tokens
``ServeEngine.generate`` returns for the same prompt set — the batch
facade is a thin wrapper over this class.
"""
from __future__ import annotations

import asyncio
import time
from typing import Any, AsyncIterator, Dict, List, Optional, Set, Union

import jax
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.models.draft import Draft
from repro.obs import span
from repro.serve.scheduler import (Completion, EngineStats, Request,
                                   SlotScheduler)


class StreamHandle:
    """Caller-side view of one submitted request: an async token stream,
    a result future, and a cancel switch.  ``tokens`` accumulates what
    ``stream()`` has yielded so far; ``completion`` is set once the
    request finishes (any status)."""

    def __init__(self, engine: "AsyncServeEngine", req: Request):
        self._engine = engine
        self.rid = req.rid
        self.prompt_len = len(req.tokens)
        self.tokens: List[int] = []
        self.completion: Optional[Completion] = None
        self._delivered = 0                    # pump-side watermark
        self._queue: "asyncio.Queue[Union[int, Completion]]" = \
            asyncio.Queue()
        self._done = asyncio.Event()

    @property
    def done(self) -> bool:
        return self.completion is not None

    async def stream(self) -> AsyncIterator[int]:
        """Yield tokens as the pump commits them (chunk granularity —
        ``cfg.serve.decode_chunk`` steps per delivery).  Ends when the
        request completes, cancels or expires; ``completion`` is set by
        then.  One streaming consumer per handle; ``result()`` may be
        awaited concurrently (it watches completion, it does not
        compete for the stream)."""
        while True:
            item = await self._queue.get()
            if isinstance(item, Completion):
                return
            self.tokens.append(item)
            yield item

    async def result(self) -> Completion:
        """Await the request's resolution and return the Completion
        (``completion.tokens`` is the full committed output regardless
        of what any stream consumer has pulled so far).  Safe alongside
        a concurrent ``stream()`` iterator."""
        await self._done.wait()
        return self.completion

    def cancel(self) -> None:
        """Request cancellation: applied at the next pump boundary —
        the slot retires, its pool blocks free, and the stream ends
        with a ``status == "cancelled"`` Completion holding whatever
        tokens were committed.  Idempotent; a no-op once done."""
        if self.completion is None:
            self._engine._cancel_rids.add(self.rid)


class AsyncServeEngine:
    """The async front door.  Either owns a fresh ``SlotScheduler``
    (``cfg`` + ``params``) or wraps an existing one (``scheduler=`` —
    how ``ServeEngine.generate`` reuses its cached, warmed-up
    scheduler).  All methods must be called from a single asyncio event
    loop at a time; the pump recreates its primitives when driven from
    a fresh ``asyncio.run``."""

    def __init__(self, cfg: Optional[ModelConfig] = None,
                 params: Any = None,
                 serve: Optional[ServeConfig] = None,
                 scheduler: Optional[SlotScheduler] = None,
                 temperature: float = 0.0,
                 draft: Optional[Draft] = None,
                 obs: Any = None):
        if scheduler is not None:
            self._sched = scheduler
        else:
            assert cfg is not None and params is not None, (
                "AsyncServeEngine needs (cfg, params) or scheduler=")
            self._sched = SlotScheduler(cfg, params, serve=serve,
                                        temperature=temperature,
                                        draft=draft)
        # observability (repro.obs.ServeObserver or None): an explicit
        # observer attaches to the wrapped scheduler too; otherwise the
        # front-end adopts whatever the scheduler already carries, so
        # front-end hooks (TTFT/ITL, pump spans, backpressure) and
        # scheduler hooks always land in the SAME observer
        if obs is not None:
            self._sched.set_observer(obs)
        self.obs = obs if obs is not None \
            else getattr(self._sched, "obs", None)
        sv = self._sched.serve
        self.queue_depth = max(1, int(sv.queue_depth))
        self.default_deadline_s = float(sv.default_deadline_s)
        self._handles: Dict[int, StreamHandle] = {}
        self._cancel_rids: Set[int] = set()
        self._rid = 0
        self._pump_task: Optional[asyncio.Task] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._space: Optional[asyncio.Event] = None

    # -- submission ----------------------------------------------------

    async def submit(self, tokens, max_new: int = 32, *,
                     temperature: Optional[float] = None, top_k: int = 0,
                     seed: Optional[int] = None,
                     key: Optional[jax.Array] = None,
                     spec_k: Optional[int] = None,
                     kv_sketch: Optional[bool] = None,
                     priority: int = 0,
                     deadline_s: Optional[float] = None,
                     rid: Optional[int] = None) -> StreamHandle:
        """Submit one request; returns its StreamHandle.  Blocks (never
        raises) while ``queue_depth`` requests are already waiting —
        open-loop backpressure.  ``deadline_s`` is a relative SLO from
        now (None -> ``cfg.serve.default_deadline_s``; 0 disables);
        ``priority`` orders admission and arms preemption.  ``rid``
        overrides the engine's counter (the batch facade threads its
        own ids through so key derivation matches)."""
        self._ensure_loop()
        stalled_at = None
        while self._sched.queue_len >= self.queue_depth:
            if stalled_at is None:
                stalled_at = time.perf_counter()
            self._space.clear()
            await self._space.wait()
        if stalled_at is not None and self.obs is not None:
            self.obs.backpressure_wait(time.perf_counter() - stalled_at)
        if rid is None:
            rid = self._rid
            self._rid += 1
        else:
            self._rid = max(self._rid, rid + 1)
        ds = (self.default_deadline_s if deadline_s is None
              else float(deadline_s))
        deadline = time.monotonic() + ds if ds and ds > 0 else None
        req = Request(rid=rid, tokens=np.asarray(tokens, np.int32),
                      max_new=int(max_new), temperature=temperature,
                      top_k=int(top_k), seed=seed, key=key,
                      spec_k=spec_k, kv_sketch=kv_sketch,
                      priority=int(priority), deadline=deadline)
        self._sched.submit(req)
        handle = StreamHandle(self, req)
        self._handles[rid] = handle
        # start (or restart) the pump only AFTER the request is queued:
        # a pump that wakes to an empty scheduler exits immediately
        self._ensure_pump()
        return handle

    async def drain(self) -> None:
        """Wait until every submitted request has resolved (the pump
        exits when the scheduler empties)."""
        while self._pump_task is not None and not self._pump_task.done():
            await self._pump_task

    async def aclose(self) -> None:
        """Cancel everything still queued or in flight and stop."""
        for h in list(self._handles.values()):
            h.cancel()
        await self.drain()

    def stats(self) -> EngineStats:
        return self._sched.stats()

    # -- the pump ------------------------------------------------------

    def _ensure_loop(self) -> None:
        """Bind (or rebind) to the running event loop: asyncio
        primitives don't survive across ``asyncio.run`` calls."""
        loop = asyncio.get_running_loop()
        if self._loop is not loop:
            self._loop = loop
            self._space = asyncio.Event()
            self._pump_task = None      # task belonged to the old loop

    def _ensure_pump(self) -> None:
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = self._loop.create_task(self._pump())

    async def _pump(self) -> None:
        sched = self._sched
        while True:
            with span("boundary"):
                # pump boundary: no chunk in flight — evictions are safe
                self._apply_cancels()
                for c in sched.expire_deadlines():
                    self._finish(c)
                self._notify_space()
            sched.admit_pending()
            if not sched.dispatch():
                if not sched.pending:
                    return              # drained; next submit restarts
                with span("yield"):
                    await asyncio.sleep(0)  # transient: let submitters run
                continue
            # overlap window: the chunk is crunching on-device; yield so
            # fresh submissions land, then run THEIR admission/prefill
            # host work now instead of serializing after collect
            with span("yield"):
                await asyncio.sleep(0)
            sched.admit_pending()
            done = await asyncio.to_thread(sched.collect)
            with span("deliver"):
                self._deliver_progress()
                for c in done:
                    self._finish(c)
                self._notify_space()
            # let consumers react to the tokens just delivered BEFORE
            # the next boundary, so a cancel() they issue now applies
            # ahead of the next dispatch instead of one chunk later
            with span("yield"):
                await asyncio.sleep(0)

    def _apply_cancels(self) -> None:
        while self._cancel_rids:
            rid = self._cancel_rids.pop()
            c = self._sched.cancel(rid)
            if c is not None:
                self._finish(c)

    def _deliver_progress(self) -> None:
        """Fan freshly committed tokens out to their handles."""
        for rid, toks in self._sched.progress().items():
            h = self._handles.get(rid)
            if h is None or len(toks) <= h._delivered:
                continue
            for t in toks[h._delivered:]:
                h._queue.put_nowait(int(t))
            if self.obs is not None:
                self.obs.tokens_delivered(rid, len(toks) - h._delivered)
            h._delivered = len(toks)

    def _finish(self, c: Completion) -> None:
        h = self._handles.pop(c.rid, None)
        if h is None:
            return
        total = [int(t) for t in c.tokens]
        if self.obs is not None and len(total) > h._delivered:
            self.obs.tokens_delivered(c.rid, len(total) - h._delivered)
        for t in total[h._delivered:]:
            h._queue.put_nowait(t)
        h._delivered = len(total)
        h.completion = c
        h._queue.put_nowait(c)      # terminates the stream() iterator
        h._done.set()               # resolves result() awaiters

    def _notify_space(self) -> None:
        if self._space is not None and \
                self._sched.queue_len < self.queue_depth:
            self._space.set()
