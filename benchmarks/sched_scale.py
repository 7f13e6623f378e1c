"""Scheduler/simulator scale benchmark (ISSUE 1 tentpole evidence).

Two claims, one JSON:

* **Golden equivalence** — on a 1k-task mixed compute/I/O workload the
  rewritten hot path (indexed ready queues + heap event queue) produces a
  bit-identical ``launch_log`` and ``stats()`` to the frozen seed
  implementation (``benchmarks/_seed_impl.py``). Tuner ``choice_counts`` /
  ``last_choice`` / ``modal_choice`` are excluded from the comparison: the
  seed counted every *failed placement attempt* as a "choice", the rewrite
  counts granted placements (an intentional fix) — ``registry`` and
  ``history`` remain bitwise identical.
* **Speedup** — at 100k tasks the rewrite must be >= 10x faster wall-clock
  than the seed. The seed is O(ready^2), so it runs under a wall-clock
  deadline; if it blows through the deadline the recorded speedup is the
  proven lower bound.

Also:

* The seed comparison is skipped above ``SEED_MAX_N`` tasks (the
  O(ready^2) seed would need hours there; the 100k default already proves
  the bound). ``--check-regress`` exits non-zero when the scale leg misses
  ``--deadline`` seconds of wall clock (default 60).
* **Traced-overhead pin** — the memoized blocked-head diagnosis keeps a
  traced run within ``TRACED_RATIO_MAX`` x of the untraced wall clock on
  the same workload (before memoization a traced contended run re-walked
  every worker per round); asserted on every invocation.

Usage::

    PYTHONPATH=src python -m benchmarks.sched_scale \
        [--n-tasks 100000] [--golden-n 1000] \
        [--check-regress] [--deadline 60] \
        [--out BENCH_sched_scale.json]
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import itertools
import time

from repro.core import Cluster, IORuntime, SimBackend, constraint, io, task
from repro.core.scheduler import Scheduler
from repro.core.task import TaskInstance

from ._report import write_report
from ._seed_impl import SeedScheduler, SeedSimBackend

GOLDEN_N = 1_000
LARGE_N = 100_000
TRACED_N = 20_000          # workload for the traced-overhead pin
TRACED_RATIO_MAX = 5.0     # traced wall clock may cost at most this factor
SEED_MAX_N = 200_000       # beyond this the seed comparison is skipped


def _reset_ids() -> None:
    """Fresh tid space so launch logs from separate runs are comparable."""
    TaskInstance._ids = itertools.count()


@contextlib.contextmanager
def _gc_quiesced():
    """Suspend CPython's cyclic collector for a timed leg.

    The launch log and completed-task list keep every task object alive
    for the whole run, so each gen-2 collection rescans an ever-growing
    heap for garbage it can never find — at 1M tasks that is ~15 s of
    pure rescan overhead growing superlinearly with n. Plain refcounting
    frees everything those logs don't hold; the ``collect()`` on exit
    reclaims the task<->future cycles once the leg is over. Applied
    identically to seed and rewrite legs, so speedups stay comparable.
    """
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()
            gc.collect()


def _make_cluster() -> Cluster:
    # small cluster so a big submission wave keeps a deep ready backlog —
    # exactly the regime where the seed's O(ready) rescan per event blows up
    return Cluster.make(n_workers=4, cpus=8, io_executors=32)


def run_workload(n_tasks: int, scheduler_cls=Scheduler, backend=None,
                 trace=False):
    """Mixed compute/I/O workload: compute stages feeding static- and
    auto-constrained checkpoints (deterministic durations/sizes).
    ``trace=True`` wires an obs TraceRecorder (the determinism tests use
    this to pin that tracing never perturbs the launch log)."""
    _reset_ids()
    cluster = _make_cluster()
    backend = backend or SimBackend()

    @task(returns=1)
    def stage(i):
        pass

    @constraint(storageBW=8)
    @io
    @task()
    def ck_static(x, i):
        pass

    @constraint(storageBW="auto")
    @io
    @task()
    def ck_auto(x, i):
        pass

    t0 = time.perf_counter()
    with _gc_quiesced(), IORuntime(cluster, backend=backend,
                                   scheduler_cls=scheduler_cls,
                                   trace=trace) as rt:
        for i in range(n_tasks // 2):
            r = stage(i, duration=1.0 + (i % 7) * 0.25)
            if i % 3 == 2:
                ck_auto(r, i, io_mb=40.0)
            else:
                ck_static(r, i, io_mb=40.0)
        rt.barrier(final=True)
        elapsed = time.perf_counter() - t0
        return rt.scheduler.launch_log, rt.stats(), elapsed


def _normalize_stats(stats: dict) -> dict:
    """Drop the tuner bookkeeping whose counting semantics intentionally
    changed (see module docstring); everything else must match bitwise."""
    out = dict(stats)
    out["tuners"] = {
        sig: {k: v for k, v in summary.items()
              if k in ("signature", "phase", "registry", "history")}
        for sig, summary in stats.get("tuners", {}).items()
    }
    return out


def golden_compare(n_tasks: int = GOLDEN_N) -> dict:
    """Run seed and rewrite on the same workload; assert identical results."""
    seed_log, seed_stats, seed_s = run_workload(
        n_tasks, scheduler_cls=SeedScheduler, backend=SeedSimBackend())
    new_log, new_stats, new_s = run_workload(n_tasks)
    identical_log = seed_log == new_log
    identical_stats = _normalize_stats(seed_stats) == _normalize_stats(new_stats)
    if not identical_log:
        diff = next(((i, a, b) for i, (a, b)
                     in enumerate(zip(seed_log, new_log)) if a != b),
                    "one log is a prefix of the other")
        raise AssertionError(f"launch_log diverged at {diff} "
                             f"(lens {len(seed_log)}/{len(new_log)})")
    if not identical_stats:
        a, b = _normalize_stats(seed_stats), _normalize_stats(new_stats)
        keys = [k for k in a if a[k] != b.get(k)]
        raise AssertionError(f"stats diverged in fields {keys}: "
                             f"{[(a[k], b[k]) for k in keys]}")
    return {
        "n_tasks": n_tasks,
        "identical_launch_log": True,
        "identical_stats": True,
        "makespan": new_stats["makespan"],
        "seed_seconds": seed_s,
        "new_seconds": new_s,
    }


def scale_run(n_tasks: int = LARGE_N, seed_deadline_factor: float = 30.0,
              with_seed: bool = True) -> dict:
    new_log, new_stats, new_s = run_workload(n_tasks)
    out = {
        "n_tasks": n_tasks,
        "n_launched": len(new_log),
        "makespan": new_stats["makespan"],
        "new_seconds": new_s,
    }
    if not with_seed:
        out.update(seed_seconds=None, seed_timed_out=None, speedup=None,
                   speedup_is_lower_bound=None)
        return out
    deadline = max(60.0, seed_deadline_factor * new_s)
    seed_timed_out = False
    t0 = time.perf_counter()
    try:
        seed_log, seed_stats, seed_s = run_workload(
            n_tasks, scheduler_cls=SeedScheduler,
            backend=SeedSimBackend(deadline=deadline))
    except TimeoutError:
        seed_timed_out = True
        seed_s = time.perf_counter() - t0
    else:
        assert seed_log == new_log, "100k launch logs diverged"
        assert _normalize_stats(seed_stats) == _normalize_stats(new_stats)
    out.update(seed_seconds=seed_s, seed_timed_out=seed_timed_out,
               speedup=seed_s / new_s, speedup_is_lower_bound=seed_timed_out)
    return out


def traced_overhead(n_tasks: int = TRACED_N) -> dict:
    """Traced-vs-untraced pin for the memoized blocked-head diagnosis: a
    traced run of the contended workload must stay within
    ``TRACED_RATIO_MAX`` x of the untraced wall clock, and tracing must
    not perturb the launch log."""
    log_plain, _, plain_s = run_workload(n_tasks)
    log_traced, _, traced_s = run_workload(n_tasks, trace=True)
    assert log_traced == log_plain, "tracing perturbed the launch log"
    ratio = traced_s / plain_s if plain_s > 0 else float("inf")
    assert ratio <= TRACED_RATIO_MAX, (
        f"traced run cost {ratio:.1f}x the untraced wall clock at "
        f"{n_tasks} tasks (budget {TRACED_RATIO_MAX}x) — blocked-head "
        f"diagnosis memoization regressed (scheduler._diagnose_block)")
    return {"n_tasks": n_tasks, "untraced_seconds": plain_s,
            "traced_seconds": traced_s, "ratio": ratio,
            "budget": TRACED_RATIO_MAX}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", "--n-tasks", dest="n", type=int, default=LARGE_N)
    ap.add_argument("--golden-n", type=int, default=GOLDEN_N)
    ap.add_argument("--check-regress", action="store_true",
                    help="exit non-zero when the scale leg misses "
                         "--deadline seconds of wall clock")
    ap.add_argument("--deadline", type=float, default=60.0)
    ap.add_argument("--out", default="BENCH_sched_scale.json")
    args = ap.parse_args(argv)

    golden = golden_compare(args.golden_n)
    print(f"golden @ {args.golden_n}: launch_log + stats identical "
          f"(seed {golden['seed_seconds']:.2f}s, new {golden['new_seconds']:.2f}s)")
    traced = traced_overhead()
    print(f"traced overhead @ {traced['n_tasks']}: "
          f"{traced['ratio']:.2f}x (budget {TRACED_RATIO_MAX}x)")
    with_seed = args.n <= SEED_MAX_N
    scale = scale_run(args.n, with_seed=with_seed)
    if with_seed:
        tag = ">=" if scale["speedup_is_lower_bound"] else "="
        print(f"scale @ {args.n}: new {scale['new_seconds']:.2f}s, "
              f"seed {scale['seed_seconds']:.2f}s"
              f"{' (timed out)' if scale['seed_timed_out'] else ''} "
              f"-> speedup {tag} {scale['speedup']:.1f}x")
    else:
        print(f"scale @ {args.n}: new {scale['new_seconds']:.2f}s "
              f"(seed comparison skipped above {SEED_MAX_N})")
    results = {"golden": golden, "scale": scale, "traced": traced}
    report = write_report(
        args.out, results, bench="sched_scale",
        config={"n": args.n, "golden_n": args.golden_n},
        headline_metric=("scale_new_seconds", scale["new_seconds"], "min"))
    print(f"wrote {args.out}")
    if args.check_regress:
        if scale["new_seconds"] > args.deadline:
            raise SystemExit(
                f"REGRESSION: scale leg took {scale['new_seconds']:.2f}s "
                f"> deadline {args.deadline:.0f}s")
        print(f"check-regress: {scale['new_seconds']:.2f}s "
              f"<= {args.deadline:.0f}s deadline")
    return report


if __name__ == "__main__":
    main()
