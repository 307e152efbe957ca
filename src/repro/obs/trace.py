"""Sampled low-overhead request/phase tracer for the serve path.

Records the full request lifecycle — queued -> admitted -> prefill
chunks -> decode chunks -> fold events -> speculative rounds ->
completion / cancel / expire / preempt — as Chrome trace-event JSON
(the ``traceEvents`` array format), loadable directly in Perfetto or
``chrome://tracing``.

Design constraints (the whole point of this module):

  * HOST-ONLY: every hook takes values the pump already holds on the
    host (mirrors, counters, wall-clock durations).  Nothing here ever
    touches a jax array, so tracing adds ZERO device syncs to the hot
    path — the one sync per round stays ``collect()``.
  * monotonic clock: timestamps are ``time.perf_counter()`` in
    microseconds (the trace-event unit), immune to wall-clock steps.
  * sampled: per-request lifecycle events are gated by a deterministic
    hash of the rid against ``sample_rate``, so heavy traffic can trace
    a stable subset; per-chunk pump spans are bounded (one per phase
    per round) and always recorded.
  * bounded: at most ``max_events`` events are retained; overflow is
    counted in ``dropped`` and surfaced as trace metadata, never an
    allocation blow-up.

Event vocabulary (Chrome trace-event phases):

  "b"/"e"  async nestable spans keyed by (cat, id) — one outer
           ``req<rid>`` span per request (queued -> resolved) with a
           nested ``active`` span per residency (admission -> retire /
           preempt; a preempted request opens a fresh ``active`` span
           when it is re-admitted);
  "X"      complete spans for pump phases (dispatch host time, the
           scheduler's collect on its worker thread, per-chunk prefill
           dispatch, one admission attempt) on tid 1;
  "i"      instants for point events (folds, preemptions, expiries);
  "C"      counter tracks (queue depth / active slots per round).

``span`` is the one way the serve path times a phase: it writes the
interval into JAX's profiler trace as ``serve.<name>`` (a TraceMe, on
the same clock as the device's ops when a profiler session is on) and,
where an observer is given, into this Chrome trace through the
observer's ``pump_span`` hook.  The serve path gives one to its four
pump phases (``dispatch``, ``collect``, ``prefill_chunk``, ``admit``)
and to no other span, so the bounded trace keeps its room for requests.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax

# pid/tid layout: one fake process; tid 0 carries request spans and
# counters, tid 1 carries pump-phase spans, so Perfetto renders the
# request timeline and the engine phases as two parallel tracks
_PID = 1
_TID_REQ = 0
_TID_PUMP = 1

# Knuth multiplicative hash: deterministic rid -> [0, 1) sampling that
# needs no RNG state and never re-decides for the same request
_HASH_MULT = 2654435761


_Annotation = jax.profiler.TraceAnnotation


class _Span:
    """Context manager behind ``span``; ``dur_s`` holds the interval's
    host seconds after exit where an observer is attached (else None)."""

    __slots__ = ("_tm", "_name", "_obs", "_args", "_t0", "dur_s")

    def __init__(self, name: str, obs: Any, args: Dict[str, Any]):
        self._name = name
        self._obs = obs
        self._args = args
        self.dur_s: Optional[float] = None

    def __enter__(self) -> "_Span":
        # the TraceMe is built only while a profiler session records
        if _Annotation.is_enabled():
            self._tm = _Annotation(f"serve.{self._name}", **self._args)
            self._tm.__enter__()
        else:
            self._tm = None
        if self._obs is not None:
            self._t0 = time.perf_counter()
        return self

    def set(self, **args: Any) -> None:
        """Arguments known only once the work is done (``ok`` of an
        admission attempt)."""
        if self._tm is not None:
            self._tm.set_metadata(**args)
        self._args.update(args)

    def __exit__(self, *exc) -> None:
        if self._obs is not None:
            self.dur_s = time.perf_counter() - self._t0
            self._obs.pump_span(self._name, self._t0, self.dur_s,
                                self._args or None)
        if self._tm is not None:
            self._tm.__exit__(*exc)


def span(name: str, obs: Any = None, **args: Any) -> _Span:
    """Time one phase of the serve path as ``serve.<name>`` in JAX's
    profiler trace (``args`` become the event's metadata); with no
    profiler session on, that costs one check.  Where ``obs`` (a
    ``ServeObserver`` or any object with its ``pump_span`` hook) is
    given, the same interval is also reported there, under ``name``."""
    return _Span(name, obs, args)


class Tracer:
    """Append-only trace-event recorder.  All methods are cheap dict
    appends; formatting costs are paid once at export."""

    def __init__(self, sample_rate: float = 1.0,
                 max_events: int = 200_000):
        self.sample_rate = float(sample_rate)
        self.max_events = int(max_events)
        self.dropped = 0
        self._events: List[Dict[str, Any]] = []

    # -- clock / sampling ----------------------------------------------

    @staticmethod
    def now() -> float:
        """Monotonic timestamp in trace-event microseconds."""
        return time.perf_counter() * 1e6

    def sampled(self, rid: int) -> bool:
        """Deterministic per-request sampling decision: the same rid
        always resolves the same way, so a request's span can never be
        half-recorded."""
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        h = (int(rid) * _HASH_MULT) & 0xFFFFFFFF
        return h / 4294967296.0 < self.sample_rate

    # -- event emission ------------------------------------------------

    def _push(self, ev: Dict[str, Any]) -> None:
        if len(self._events) >= self.max_events:
            self.dropped += 1
            return
        self._events.append(ev)

    def begin_async(self, cat: str, id_: int, name: str,
                    args: Optional[dict] = None,
                    ts: Optional[float] = None) -> None:
        self._push({"ph": "b", "cat": cat, "id": int(id_), "name": name,
                    "pid": _PID, "tid": _TID_REQ,
                    "ts": self.now() if ts is None else ts,
                    "args": args or {}})

    def end_async(self, cat: str, id_: int, name: str,
                  args: Optional[dict] = None,
                  ts: Optional[float] = None) -> None:
        self._push({"ph": "e", "cat": cat, "id": int(id_), "name": name,
                    "pid": _PID, "tid": _TID_REQ,
                    "ts": self.now() if ts is None else ts,
                    "args": args or {}})

    def instant(self, name: str, args: Optional[dict] = None,
                ts: Optional[float] = None, tid: int = _TID_REQ) -> None:
        self._push({"ph": "i", "name": name, "pid": _PID, "tid": tid,
                    "ts": self.now() if ts is None else ts, "s": "t",
                    "args": args or {}})

    def complete(self, name: str, ts_us: float, dur_us: float,
                 args: Optional[dict] = None,
                 tid: int = _TID_PUMP) -> None:
        """One "X" complete span: ``ts_us`` start, ``dur_us`` duration,
        both in trace microseconds (use ``Tracer.now()``)."""
        self._push({"ph": "X", "name": name, "pid": _PID, "tid": tid,
                    "ts": ts_us, "dur": max(dur_us, 0.0),
                    "args": args or {}})

    def counter(self, name: str, values: Dict[str, float],
                ts: Optional[float] = None) -> None:
        """One "C" counter sample; ``values`` become stacked series."""
        self._push({"ph": "C", "name": name, "pid": _PID, "tid": _TID_REQ,
                    "ts": self.now() if ts is None else ts,
                    "args": dict(values)})

    # -- export --------------------------------------------------------

    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> List[Dict[str, Any]]:
        """The recorded trace events (plus a metadata instant recording
        any overflow drops), ready for ``{"traceEvents": ...}``."""
        out = list(self._events)
        if self.dropped:
            out.append({"ph": "i", "name": "tracer_dropped_events",
                        "pid": _PID, "tid": _TID_REQ, "ts": self.now(),
                        "s": "g", "args": {"dropped": self.dropped}})
        return out
