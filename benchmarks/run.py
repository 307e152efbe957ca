"""Run every paper-table benchmark; print ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only a,b] [--json f]

``--json`` additionally writes the collected rows as a JSON list of
{name, us_per_call, derived, metrics, ts, sha} objects — the CI
bench-smoke job uploads it as a per-PR artifact so the perf trajectory
is recorded; ``ts`` (UTC wall clock) and ``sha`` (git commit) make
artifacts self-identifying when compared out of band.  ``--only``
restricts the pass to a comma-separated subset of benchmark modules
(e.g. ``--only serve,opt_state``).  ``--metrics-jsonl`` hands the serve
bench a path for its observability-overhead row to stream windowed
metrics snapshots to (uploaded as a CI artifact next to the bench JSON).
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

from repro.launch.compile_cache import use_compile_cache


def _parse_derived(derived: str) -> dict:
    """Split a ``k=v;k=v`` derived string into typed metric columns so the
    JSON artifact carries comparable numbers (tok_s, hit_rate, the paged
    kv_*_bytes columns, ...) instead of one opaque string."""
    metrics = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            metrics[k] = float(v.rstrip("x"))
        except ValueError:
            metrics[k] = v
    return metrics


def _git_sha() -> str:
    """Commit identity for the artifact: CI env first (works in shallow
    or detached checkouts), then git, then a placeholder."""
    sha = os.environ.get("GITHUB_SHA", "")
    if sha:
        return sha[:12]
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _write_json(path: str) -> None:
    from benchmarks.common import ROWS
    ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    sha = _git_sha()
    rows = []
    for r in ROWS:
        name, us, derived = r.split(",", 2)
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": derived,
                     "metrics": _parse_derived(derived),
                     "ts": ts, "sha": sha})
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"# wrote {len(rows)} rows to {path} (sha={sha} ts={ts})",
          file=sys.stderr)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="trim grids for a quick pass")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of benches "
                         "(rtpm,als,trl,kron,contract,grad_compress,"
                         "opt_state,serve)")
    ap.add_argument("--json", default="",
                    help="also write rows as JSON to this path")
    ap.add_argument("--metrics-jsonl", default="",
                    help="serve bench: stream windowed observability "
                         "metrics (JSONL) from the obs_overhead row to "
                         "this path")
    args, _ = ap.parse_known_args()

    known = {"rtpm", "als", "trl", "kron", "contract", "grad_compress",
             "opt_state", "serve"}
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    unknown = only - known
    if unknown:   # a typo must not silently produce an empty artifact
        raise SystemExit(f"--only: unknown benches {sorted(unknown)}; "
                         f"known: {sorted(known)}")
    want = lambda n: not only or n in only

    print("name,us_per_call,derived")
    t0 = time.time()

    from benchmarks import (bench_als, bench_contract, bench_grad_compress,
                            bench_kron, bench_opt_state, bench_rtpm,
                            bench_serve, bench_trl)

    if args.fast:
        if want("rtpm"):
            bench_rtpm.run(I=40, Js=(400,), table2=False)
        if want("als"):
            bench_als.run(I=40, Js=(800,), D=4, iters=8)
        if want("trl"):
            bench_trl.run(crs=(20, 100), n_train=512, n_test=256)
        if want("kron"):
            bench_kron.run(crs=(4, 16), D=8)
        if want("contract"):
            bench_contract.run(crs=(4, 16), D=8)
        if want("grad_compress"):
            bench_grad_compress.run(dims=1 << 18, ratios=(16,))
        if want("opt_state"):
            bench_opt_state.run(dims=(1 << 17, 1 << 13), ratios=(4,),
                                steps=10)
        if want("serve"):
            # hit_suffix must exceed prefill_bucket (32) so the
            # prefill_hit row really times the multi-bucket chunked path
            bench_serve.run(archs=("gemma-2b", "xlstm-1.3b"),
                            n_requests=8, max_new=4, max_batch=2,
                            hit_suffix=40, spec_max_new=32,
                            metrics_jsonl=args.metrics_jsonl or None)
    else:
        if want("rtpm"):
            bench_rtpm.run()
        if want("als"):
            bench_als.run()
        if want("trl"):
            bench_trl.run()
        if want("kron"):
            bench_kron.run()
        if want("contract"):
            bench_contract.run()
        if want("grad_compress"):
            bench_grad_compress.run()
        if want("opt_state"):
            bench_opt_state.run()
        if want("serve"):
            bench_serve.run(metrics_jsonl=args.metrics_jsonl or None)

    if args.json:
        _write_json(args.json)
    print(f"# total benchmark wall time: {time.time()-t0:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
