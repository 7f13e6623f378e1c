"""Profile driver: the sched_scale 100k workload alone (no seed leg).

    PYTHONPATH=src:. python -m repro.profile benchmarks/_profile_target.py

Profiles the simulator hot path (docs/observability.md, "Profiling the
simulator hot path"); takes --n and --trace like run_workload.
"""
import argparse

from benchmarks.sched_scale import run_workload

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--trace", action="store_true")
    args = ap.parse_args()
    _log, stats, elapsed = run_workload(args.n, trace=args.trace)
    print(f"n={args.n} elapsed={elapsed:.2f}s makespan={stats['makespan']:.1f}")
