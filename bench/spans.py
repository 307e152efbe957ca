"""The program's own host spans in a traced run's window, and the
per-layer metrics read from them.

The serve path times its phases with ``repro.obs.span``, which writes
each one into JAX's profiler trace as ``serve.<name>`` on the same clock
as the device's ops: ``admit`` (one admission attempt) with
``prefix_lookup``, ``prefix_count`` (the count-min tracker's accumulate,
query and read-back) and ``prefill_chunk`` inside it; ``dispatch``;
``collect`` with ``collect.wait`` (the round's one read-back) and
``retire`` inside it; and the front-end's ``boundary``, ``deliver`` and
``yield``.  ``extract`` reads them from the raw profile a ``--trace 1``
run leaves (``ctx.rec.tr_dir``), with each span's thread and arguments
(TraceMe's ``#k=v#`` suffix stripped from its name), the device's busy
intervals (the union of its ops, and of its programs), and the window
``devtrace.reduce`` uses: from the first device op's start to the last
one's end.  ``view`` keeps what lies inside that window; ``of`` caches
it on the readers' context.  A program without these spans gives no
span, and every reader then returns None.

  python3 bench/spans.py bench/testdata/<name>.json.gz [rounds]

saves a slice of the newest traced run (``rounds`` collected rounds,
default 4, from the first one whose in-flight chunk overlaps an
admission where there is one) with the device events ``devtrace``
reads, for ``tests/bench/test_bench_spans.py``;

  python3 bench/spans.py --idle

prints, as JSON, how much of the newest traced run's device idle time
lies under some ``serve.*`` span, and under which (``idle_cover``).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    if sys.path and Path(sys.path[0]).resolve() == _root / "bench":
        sys.path.pop(0)
    sys.path.insert(0, str(_root))

from bench import devtrace  # noqa: E402

PREFIX = "serve."


def _value(v):
    return v if isinstance(v, (int, float, str)) else str(v)


def _union(iv) -> List[List[float]]:
    return [list(x) for x in devtrace._union(list(iv))]


def extract(log_dir: str, device_prefix: str = "/device:TPU:") -> Dict:
    """``serve.*`` spans of the newest ``.xplane.pb`` under ``log_dir``
    (name without the prefix, thread, start and length in ns,
    arguments); the first device's busy intervals, ``ops`` (union of its
    ops) and ``programs`` (union of its modules); and ``window_ns``, the
    first device op's start and the last one's end (None where the trace
    holds no device op)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    spans: List[Dict] = []
    dev = {devtrace.OPS_LINE: [], devtrace.MODULES_LINE: []}
    devices = sorted((p for p in pd.planes
                      if p.name.startswith(device_prefix)),
                     key=lambda p: p.name)
    for plane in pd.planes:
        if devices and plane.name == devices[0].name:
            for line in plane.lines:
                if line.name in dev:
                    dev[line.name].extend(
                        (float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events)
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                spans.append({
                    "name": e.name[len(PREFIX):].split("#", 1)[0],
                    "tid": f"{plane.name}/{i}",
                    "start_ns": float(e.start_ns),
                    "dur_ns": float(e.duration_ns),
                    "args": {k: _value(v) for k, v in e.stats}})
    ops = _union(dev[devtrace.OPS_LINE])
    return {"spans": spans, "ops": ops,
            "programs": _union(dev[devtrace.MODULES_LINE]),
            "window_ns": [ops[0][0], max(e for _, e in ops)] if ops
            else None}


def of_events(events: List[Dict], spans: List[Dict],
              window_ns: Optional[List[float]] = None) -> Dict:
    """``extract``'s form from ``devtrace`` events (a recorded slice's)
    and ``serve.*`` spans."""
    iv = {devtrace.OPS_LINE: [], devtrace.MODULES_LINE: []}
    for e in events:
        if e["line"] in iv:
            iv[e["line"]].append((e["start_ns"], e["start_ns"] + e["dur_ns"]))
    ops = _union(iv[devtrace.OPS_LINE])
    if window_ns is None and ops:
        window_ns = [ops[0][0], max(e for _, e in ops)]
    return {"spans": spans, "ops": ops,
            "programs": _union(iv[devtrace.MODULES_LINE]),
            "window_ns": window_ns}


def _clip(iv: List[List[float]], lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in iv if e > lo and s < hi]


def view(found: Dict) -> Dict:
    """What lies inside ``found``'s window: the spans wholly inside it,
    by start, and the device's busy intervals clipped to it; nothing
    where there is no window."""
    w = found.get("window_ns")
    if not w:
        return {"spans": [], "ops": [], "programs": [], "window_ns": None}
    lo, hi = w
    return {"spans": sorted((s for s in found["spans"]
                             if s["start_ns"] >= lo
                             and s["start_ns"] + s["dur_ns"] <= hi),
                            key=lambda s: s["start_ns"]),
            "ops": _clip(found.get("ops", []), lo, hi),
            "programs": _clip(found.get("programs", []), lo, hi),
            "window_ns": [lo, hi]}


def of(ctx) -> Dict:
    """The traced window's ``view``, read once per run and kept on the
    readers' context; with no span where the run was not traced."""
    v = getattr(ctx, "serve_spans", None)
    if v is None:
        v = view({})
        rec = getattr(ctx, "rec", None)
        tr_dir = getattr(rec, "tr_dir", None)
        if ctx.trace and tr_dir and os.path.isdir(tr_dir):
            v = view(extract(tr_dir))
        ctx.serve_spans = v
    return v


def _end(s: Dict) -> float:
    return s["start_ns"] + s["dur_ns"]


def _named(spans: List[Dict], name: str) -> List[Dict]:
    return [s for s in spans if s["name"] == name]


def _within(spans: List[Dict], outer: Dict) -> List[Dict]:
    """Spans on ``outer``'s thread that lie inside it."""
    return [s for s in spans if s["tid"] == outer["tid"]
            and s["start_ns"] >= outer["start_ns"] and _end(s) <= _end(outer)]


def idle_in(busy: List[List[float]], a: float, b: float) -> float:
    """ns of [a, b] that the sorted, disjoint ``busy`` intervals leave
    free."""
    t = b - a
    for s, e in busy[max(0, bisect.bisect_right(busy, [a]) - 1):]:
        if s >= b:
            break
        t -= max(0.0, min(b, e) - max(a, s))
    return t


def admissions(v: Dict) -> List[Dict]:
    """Per ``serve.admit`` span (one admission attempt): its length, the
    time its ``serve.prefix_count`` children took, and ``exposed_ns``,
    the time in it, outside those children, in which the device ran no
    program, in ns."""
    sp, prog = v["spans"], v["programs"]
    counts = _named(sp, "prefix_count")
    out = []
    for a in _named(sp, "admit"):
        kids = _within(counts, a)
        out.append({
            "dur_ns": a["dur_ns"],
            "count_ns": sum(c["dur_ns"] for c in kids),
            "exposed_ns": idle_in(prog, a["start_ns"], _end(a))
            - sum(idle_in(prog, c["start_ns"], _end(c)) for c in kids)})
    return out


def tracker_wait_ms(v: Dict) -> Optional[float]:
    """Mean ms per admission attempt in the prefix tracker's accumulate,
    query and read-back."""
    adm = admissions(v)
    if not adm:
        return None
    return 1e-6 * sum(a["count_ns"] for a in adm) / len(adm)


def admit_host_ms(v: Dict) -> Optional[float]:
    """Mean ms per admission attempt of host work the device waits for:
    time in ``serve.admit``, outside its ``serve.prefix_count``, in
    which the device runs no program.  The rest of the span is hidden
    behind the device: the admission's own prefill chunks, or a decode
    chunk in flight, run while the host dispatches, and once 32
    programs are in flight (a v5e's runtime) the host's next dispatch
    waits for the oldest to end, so the span's whole length would count
    the prefill's device time."""
    adm = admissions(v)
    if not adm:
        return None
    return 1e-6 * sum(a["exposed_ns"] for a in adm) / len(adm)


def stretches(v: Dict) -> List[Dict]:
    """Per round: from the end of ``serve.collect.wait`` (the device has
    finished the decode chunk) to the end of the next ``serve.dispatch``
    (the next one is launched), in ns, and the part of it that
    ``serve.admit`` spans cover."""
    sp = v["spans"]
    admits = _named(sp, "admit")
    ends = sorted(_end(d) for d in _named(sp, "dispatch"))
    out = []
    for w in _named(sp, "collect.wait"):
        a = _end(w)
        b = next((e for e in ends if e > a), None)
        if b is None:
            continue
        adm = sum(max(0.0, min(b, _end(s)) - max(a, s["start_ns"]))
                  for s in admits)
        out.append({"from_ns": a, "to_ns": b, "admit_ns": adm})
    return out


def boundary_host_ms(v: Dict) -> Optional[float]:
    """Mean ms per round between the device's end of one decode chunk
    and the launch of the next, less the admissions in between."""
    st = stretches(v)
    if not st:
        return None
    return 1e-6 * sum(s["to_ns"] - s["from_ns"] - s["admit_ns"]
                      for s in st) / len(st)


def idle_cover(v: Dict) -> Optional[Dict]:
    """The window's device idle time (no op running, as ``idle_share``
    counts it), the part of it that some ``serve.*`` span covers, and
    that time by the innermost (shortest) covering span's name, with
    ``none`` for the rest; in ns."""
    if not v["window_ns"] or not v["ops"]:
        return None
    lo, hi = v["window_ns"]
    gaps, prev = [], lo
    for s, e in v["ops"] + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    sp = v["spans"]
    edges = sorted({x for s in sp for x in (s["start_ns"], _end(s))})
    by: Dict[str, float] = {}
    for a, b in gaps:
        pts = ([a] + edges[bisect.bisect_right(edges, a):
                           bisect.bisect_left(edges, b)] + [b])
        for u, w in zip(pts, pts[1:]):
            m = 0.5 * (u + w)
            c = [s for s in sp if s["start_ns"] <= m <= _end(s)]
            k = min(c, key=lambda s: s["dur_ns"])["name"] if c else "none"
            by[k] = by.get(k, 0.0) + (w - u)
    idle = sum(b - a for a, b in gaps)
    return {"window_ns": hi - lo, "idle_ns": idle,
            "covered_ns": idle - by.get("none", 0.0),
            "by_ns": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def save(trace: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> Dict:
    """A saved slice in ``extract``'s form, its device events kept."""
    with gzip.open(path, "rt") as f:
        sl = json.load(f)
    return dict(of_events(sl["events"], sl["spans"], sl["window_ns"]),
                events=sl["events"], span_ns=sl.get("span_ns"))


def slice_of(events: Dict, found: Dict, rounds: int = 4) -> Dict:
    """``rounds`` collected rounds of a traced run: the device events of
    ``devtrace.extract`` and the ``serve.*`` spans of ``extract`` that
    lie in them, from the first round whose in-flight chunk (dispatch's
    end to read-back's end) overlaps an admission where there is one,
    else from the first round; times start at 0."""
    sp = view(found)["spans"]
    waits = _named(sp, "collect.wait")
    disp = sorted(_end(d) for d in _named(sp, "dispatch"))
    admits = _named(sp, "admit")
    starts = []
    for w in waits:
        d = max((e for e in disp if e < _end(w)), default=None)
        if d is not None:
            starts.append((d, any(d < a["start_ns"] < _end(w)
                                  for a in admits)))
    if not starts:
        raise RuntimeError("no collected round in the traced window")
    k = next((i for i, (_, hit) in enumerate(starts) if hit), 0)
    lo = starts[k][0] - 1e6
    hi = _end(waits[min(k + rounds, len(waits) - 1)]) + 1e6

    def cut(e):
        return e["start_ns"] >= lo and e["start_ns"] + e["dur_ns"] <= hi

    def shift(e):
        return dict(e, start_ns=e["start_ns"] - lo)

    ev = [shift(e) for e in events["events"] if cut(e)]
    ops = [e for e in ev if e["line"] == devtrace.OPS_LINE]
    return {"events": ev, "span_ns": hi - lo,
            "spans": [shift(s) for s in sp if cut(s)],
            "window_ns": [min(e["start_ns"] for e in ops),
                          max(e["start_ns"] + e["dur_ns"] for e in ops)]}


def main(argv=None) -> None:
    from bench.run import CACHE
    args = sys.argv[1:] if argv is None else argv
    tr = str(CACHE / "trace")
    if args == ["--idle"]:
        c = idle_cover(view(extract(tr)))
        if c is None:
            raise SystemExit("no device op in the newest trace")
        c["share_pct"] = 100.0 * c["covered_ns"] / max(c["idle_ns"], 1.0)
        print(json.dumps(c))
        return
    if len(args) not in (1, 2):
        raise SystemExit(__doc__)
    out = Path(args[0])
    out.parent.mkdir(parents=True, exist_ok=True)
    sl = slice_of(devtrace.extract(tr), extract(tr),
                  int(args[1]) if len(args) > 1 else 4)
    save(sl, str(out))


if __name__ == "__main__":
    main()
