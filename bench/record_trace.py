"""Save the newest traced run's device trace as test data.

  python3 bench/run.py --workload <name> --seed <n> --seconds 10 --trace 1
  python3 bench/record_trace.py bench/testdata/<name>.json.gz

A ``--trace 1`` run leaves JAX's profiler output in
``.bench_cache/trace``; this keeps the events the reduction reads
(``devtrace.extract``) in a small gzipped JSON file, which
``tests/bench/test_bench_trace.py`` reduces without a chip.
"""
from __future__ import annotations

import sys
from pathlib import Path

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    if sys.path and Path(sys.path[0]).resolve() == _root / "bench":
        sys.path.pop(0)
    sys.path.insert(0, str(_root))


def main(argv=None) -> None:
    from bench import devtrace
    from bench.run import CACHE
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        raise SystemExit(__doc__)
    out = Path(args[0])
    out.parent.mkdir(parents=True, exist_ok=True)
    devtrace.save(devtrace.extract(str(CACHE / "trace")), str(out))


if __name__ == "__main__":
    main()
