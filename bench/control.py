"""Readings that set a cell's limits: the program's, and the
lower-precision control's, on many seeds in one process.

  python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell at its own load and size (a short
window), then on the same sample of finished requests: the program's
reading (the widest gap of a served token below the float32
reference's best) and the control's (the same gap for the token that
the reference computed in float8 ranks first).  The benchmark's own
runs never run the control.  One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
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
    from bench import run as R
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = R.load_cell(R.ROOT, args.workload)
    R.use_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = R.run(cell, seed, args.seconds, False, control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "logit_gap": res["compared"].get(
                              "logit_gap", {}).get("value"),
                          "control_gap": res.get("control", {}).get(
                              "logit_gap"),
                          "metrics": res["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
