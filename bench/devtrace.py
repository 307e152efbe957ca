"""Profiler trace of a run's window, reduced to the numbers the
per-layer metrics read.

The harness starts and stops JAX's profiler around the first seconds of
the measured window; ``extract`` keeps from the written ``.xplane.pb``
only what the reduction needs (the device's op and module lines and the
benchmark's own host spans, named ``bench.*``), as plain event dicts;
``reduce`` turns those into busy time, idle gaps labelled by what the
host was doing, self time per kind of op and time per compiled module.
The reduction works on the event list alone, so a small recorded trace
(``bench/testdata/``) checks it without a chip.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"[.:]\d+$")


def options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0       # no per-call Python events
    o.host_tracer_level = 2         # TraceMe spans, ours included
    return o


def extract(log_dir: str, device_prefix: str = "/device:TPU:") -> Dict:
    """Events of the newest ``.xplane.pb`` under ``log_dir``: the first
    device plane's op and module lines, and the host spans whose names
    start with ``bench.``, with times in ns from the trace's start; and
    the length of the traced span (``span_ns``, None where the trace
    does not state it)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(device_prefix)),
                     key=lambda p: p.name)
    events: List[Dict] = []
    if devices:
        for line in devices[0].lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                events.extend({"line": line.name, "name": e.name,
                               "start_ns": float(e.start_ns),
                               "dur_ns": float(e.duration_ns)}
                              for e in line.events)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events.extend({"line": "host", "name": e.name,
                           "start_ns": float(e.start_ns),
                           "dur_ns": float(e.duration_ns)}
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX))
    span = None
    for plane in pd.planes:
        st = dict(plane.stats) if hasattr(plane, "stats") else {}
        if "profile_start_time" in st and "profile_stop_time" in st:
            span = float(st["profile_stop_time"]) - float(
                st["profile_start_time"])
    return {"events": events, "span_ns": span}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def op_kind(name: str) -> str:
    """An op's kind: its instruction name (a TPU trace names an op by
    its whole HLO text, ``%fusion.12 = bf16[...] fusion(...)``) without
    XLA's numeric suffix (``fusion.12`` -> ``fusion``), so that one kind
    of op sums over its instances."""
    name = name.split(" = ", 1)[0].lstrip("%")
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name


def _self_times(ops: List[Dict]) -> List[float]:
    """Per op, its duration less what the ops nested inside it cover (a
    ``while`` op spans its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i]["start_ns"],
                                                   -ops[i]["dur_ns"]))
    out = [e["dur_ns"] for e in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i]["start_ns"]
        e = s + ops[i]["dur_ns"]
        while stack and ops[stack[-1]]["start_ns"] + \
                ops[stack[-1]]["dur_ns"] < e:
            stack.pop()
        if stack:
            out[stack[-1]] -= ops[i]["dur_ns"]
        stack.append(i)
    return out


def reduce(events: List[Dict], t0_ns: Optional[float] = None,
           t1_ns: Optional[float] = None, top: int = 10) -> Dict:
    """Busy and idle time of the device within [t0_ns, t1_ns] (default:
    from the first device op's start to the last one's end), the ``top``
    op kinds by self time, the modules by time and count, and the
    ``top`` longest idle gaps, each named by the benchmark's host span
    that covers the gap's midpoint (``host`` where none does)."""
    ops = [e for e in events if e["line"] == OPS_LINE]
    mods = [e for e in events if e["line"] == MODULES_LINE]
    spans = [e for e in events if e["line"] == "host"]
    if not ops:
        return {}
    lo = min(e["start_ns"] for e in ops) if t0_ns is None else t0_ns
    hi = (max(e["start_ns"] + e["dur_ns"] for e in ops)
          if t1_ns is None else t1_ns)

    def inside(e):
        return e["start_ns"] >= lo and e["start_ns"] + e["dur_ns"] <= hi

    ops = [e for e in ops if inside(e)]
    mods = [e for e in mods if inside(e)]
    busy = _union([(e["start_ns"], e["start_ns"] + e["dur_ns"])
                   for e in ops])
    busy_ns = sum(e - s for s, e in busy)
    gaps = []
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    named = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [h for h in spans
                 if h["start_ns"] <= mid <= h["start_ns"] + h["dur_ns"]]
        # the innermost span names the host's activity
        cover.sort(key=lambda h: h["dur_ns"])
        who = cover[0]["name"][len(SPAN_PREFIX):] if cover else "host"
        named.append((who, (e - s) * 1e-9))
    named.sort(key=lambda x: -x[1])
    by_op: Dict[str, float] = {}
    for e, own in zip(ops, _self_times(ops)):
        k = op_kind(e["name"])
        by_op[k] = by_op.get(k, 0.0) + own * 1e-9
    by_mod: Dict[str, Dict[str, float]] = {}
    for e in mods:
        m = by_mod.setdefault(op_kind(e["name"]), {"s": 0.0, "n": 0})
        m["s"] += e["dur_ns"] * 1e-9
        m["n"] += 1
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "ops": by_op,
        "modules": by_mod,
        "device_ops": sorted(([k, v] for k, v in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[w, s] for w, s in named[:top]],
    }


def time_of(reduced: Dict, op: str) -> float:
    """Seconds the device spent in ops of kind ``op`` (self time)."""
    return reduced.get("ops", {}).get(op, 0.0)


def module_time(reduced: Dict, pattern: str) -> Dict[str, float]:
    """Summed seconds and count of modules whose name matches
    ``pattern`` (a regular expression)."""
    s, n = 0.0, 0
    for name, m in reduced.get("modules", {}).items():
        if re.search(pattern, name):
            s += m["s"]
            n += m["n"]
    return {"s": s, "n": n}
