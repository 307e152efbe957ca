"""Find an open-loop cell's knee: the highest arrival rate the system
sustains, by runs at fixed rates in one process.

  python3 bench/sweep.py --workload <name> --rates 1,2,3 --seconds 20

Each rate is one run of the cell with ``rate_per_s`` replaced.  Per rate
it prints one JSON line: the offered and completed request rates over
the window, the wait from due time to admission at the window's start
and end (a queue that grows through the window is above the knee), and
the cell's end-to-end metrics.  The cell file then fixes its rate at
about four fifths of the knee; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    if sys.path and Path(sys.path[0]).resolve() == _root / "bench":
        sys.path.pop(0)
    for _p in (_root / "src", _root):
        sys.path.insert(0, str(_p))


def main(argv=None) -> None:
    import numpy as np

    from bench import run as R
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)
    base = R.load_cell(R.ROOT, args.workload)
    R.use_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        cell = copy.deepcopy(base)
        cell.cell["rate_per_s"] = rate
        seen = {}

        def keep(ctx, seen=seen):
            seen["ctx"] = ctx

        res = R.run(cell, args.seed, args.seconds, False, inspect=keep)
        ctx = seen["ctx"]
        w, recs, adm = ctx.window, ctx.recs, ctx.rec.admitted
        span = w["t_b"] - w["t_a"]
        done = sum(1 for r in recs.values() if r["times"]
                   and w["t_a"] < r["times"][-1] <= w["t_b"]
                   and r["status"] == "ok")
        waits = sorted((adm[k] - r["due"], adm[k]) for k, r in recs.items()
                       if k in adm and w["t_a"] < adm[k] <= w["t_b"])
        third = max(1, len(waits) // 3)
        q = lambda xs: float(np.median([x[0] for x in xs])) if xs else None
        by_t = sorted(waits, key=lambda x: x[1])
        print(json.dumps({
            "rate_per_s": rate, "completed_per_s": done / span,
            "tokens_per_s": w["tokens"] / span,
            "wait_first_third_s": q(by_t[:third]),
            "wait_last_third_s": q(by_t[-third:]),
            "correct": res["correct"], "metrics": res["metrics"]}),
            flush=True)


if __name__ == "__main__":
    main()
