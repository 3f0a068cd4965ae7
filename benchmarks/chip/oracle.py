"""The comparison that decides `correct` for a training cell.

Three numbers, each against the plain reference's reading of the same
seed, weights and rows:

  loss_gap         the largest |program loss - reference loss| over the
                   checked steps (nats);
  grad_norm_gap    the first step's gradient as the optimizer gets it
                   (clipped; the program's read back from AdamW's first
                   moment, mu_1 = (1 - beta1) g_1), per leaf: the gap
                   between the program's norm and the reference's, over
                   the larger of the reference's norm of that leaf and of
                   the median leaf; the worst leaf;
  update_norm_gap  the same for the norm of each leaf's change over the
                   checked steps (the float32 master weights that the
                   next step reads, against the reference's weights).
                   Leaves whose reference gradient is under a thousandth
                   of the median leaf's (a key bias under softmax) move
                   by round-off alone and are left out.

A leaf of a stacked layer parameter is one layer's slice of it, named
"layers.<i>.<group>.<leaf>".
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Tuple

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")
SMALL_GRAD = 1e-3


def _median(values):
    v = sorted(values)
    n = len(v)
    return v[n // 2] if n % 2 else 0.5 * (v[n // 2 - 1] + v[n // 2])


def loss_gap(program: Iterable[float], reference: Iterable[float]) -> float:
    program, reference = list(program), list(reference)
    if len(program) != len(reference) or not program:
        return math.inf
    gaps = [abs(a - b) for a, b in zip(program, reference)]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def norm_gap(program: Dict[str, float], reference: Dict[str, float],
             keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """-> (worst leaf's gap, that leaf's name)."""
    if set(program) != set(reference):
        missing = sorted(set(program) ^ set(reference))
        return math.inf, f"leaf sets differ: {missing[:4]}"
    names = sorted(reference if keep is None else keep)
    med = _median([reference[n] for n in names])
    worst, which = 0.0, ""
    for n in names:
        g = abs(program[n] - reference[n]) / max(reference[n], med, 1e-30)
        if not math.isfinite(g):
            return math.inf, n
        if g >= worst:
            worst, which = g, n
    return worst, which


def moving_leaves(ref_grad: Dict[str, float]):
    med = _median(list(ref_grad.values()))
    return [n for n, v in ref_grad.items() if v >= SMALL_GRAD * med]


def compare(program: dict, reference: dict) -> Dict[str, Tuple[float, str]]:
    """program / reference: {"loss": [...], "grad": {leaf: norm},
    "update": {leaf: norm}} -> {number: (value, note)}."""
    return {
        "loss_gap": (loss_gap(program["loss"], reference["loss"]), ""),
        "grad_norm_gap": norm_gap(program["grad"], reference["grad"]),
        "update_norm_gap": norm_gap(program["update"], reference["update"],
                                    keep=moving_leaves(reference["grad"])),
    }
