"""How ``correct`` is decided: the numbers compared, each with its limit.

A training run's first steps are read on the program's side (each step's
loss and global gradient norm before clipping, the first gradient as the
optimizer holds it, each leaf's change over the steps) and followed by the
plain reference from the same seed. Norms are
compared leaf by leaf and the worst leaf counts: the gap between the
program's norm and the reference's, over the larger of the reference's norm
of that leaf and of the median leaf. A leaf whose reference gradient is
under a thousandth of the median leaf's moves by round-off alone and is left
out of the change.
"""
from __future__ import annotations

import math
import statistics

NEGLIGIBLE_GRAD = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    if set(prog) != set(ref) or not leaves:
        return math.inf
    floor = statistics.median(ref[k] for k in leaves)
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30) for k in leaves]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def step_gap(prog: list, ref: list) -> float:
    """The worst step's gap, relative to the reference."""
    if len(prog) != len(ref):
        return math.inf
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog, ref)]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def moving_leaves(ref_grad: dict) -> list:
    median = statistics.median(ref_grad.values())
    return sorted(k for k, g in ref_grad.items()
                  if not g < NEGLIGIBLE_GRAD * median)


def training_gaps(prog: dict, ref: dict) -> dict:
    """The four numbers a training cell compares."""
    return {
        "loss_gap": step_gap(prog["losses"], ref["losses"]),
        "gnorm_gap": step_gap(prog["gnorms"], ref["gnorms"]),
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": worst_leaf_gap(prog["change_norms"],
                                     ref["change_norms"],
                                     moving_leaves(ref["grad_norms"])),
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(every number within its limit, [[name, number, limit], ...]). A
    number without a limit, or a limit without a number, is not correct."""
    rows = [[k, numbers.get(k), limits.get(k)]
            for k in sorted(set(numbers) | set(limits))]
    ok = all(n is not None and lim is not None and n <= lim
             for _, n, lim in rows)
    return ok, rows
