"""The comparison that decides `correct`: the program's readings against
the plain reference's, each number beside its limit (`limits/<cell>.json`).
"""
from __future__ import annotations

import statistics
from typing import Dict

import numpy as np

from .harness import Compared


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   skip=()):
    """(gap, leaf): the widest gap between the program's and the
    reference's norm of a leaf, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some leaves are
    all but zero)."""
    median = statistics.median(reference.values())
    return max((abs(program[k] - reference[k]) / max(reference[k], median),
                k) for k in reference if k not in skip)


def still_leaves(reference_grad: Dict[str, float]):
    """Leaves whose gradient is nought to rounding in the reference: under
    a thousandth of the median leaf's. Under Adam they move by round-off
    alone, so their change is not compared."""
    median = statistics.median(reference_grad.values())
    return {k for k, g in reference_grad.items() if g < 1e-3 * median}


def train_readings(program: dict, reference: dict, limits: dict):
    """({name: Compared}, {name: value}) for a training cell: the numbers
    that have a limit in the cell's file, and the readings that have none
    (PERF.md says why a number is not compared)."""
    values = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"])):
        values[f"loss{i + 1}_rel"] = (abs(a - b) / abs(b), "")
    values["grad_norm_gap"] = worst_leaf_gap(program["grad_norm"],
                                             reference["grad_norm"])
    values["change_norm_gap"] = worst_leaf_gap(
        program["change_norm"], reference["change_norm"],
        skip=still_leaves(reference["grad_norm"]))
    compared = {k: Compared(v, limits[k], where)
                for k, (v, where) in values.items() if k in limits}
    return compared, {k: v for k, (v, _) in values.items()
                      if k not in limits}


def served_gap(reference_logits, served_tokens) -> float:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the positions of one request."""
    lg = np.asarray(reference_logits, np.float32)
    tok = np.asarray(served_tokens)
    return float((lg.max(axis=-1) - lg[np.arange(len(tok)), tok]).max())
