"""The comparisons that decide ``correct`` (numbers only; limits live in the
cell's file)."""

from __future__ import annotations

import statistics


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """Largest gap between the program's and the reference's norm of a leaf,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some gradients are all but zero). Both are ``{path: norm}``;
    the paths have to be the same set."""
    if set(program) != set(reference):
        odd = sorted(set(program) ^ set(reference))[:4]
        raise ValueError(f"leaf paths differ between program and reference: {odd}")
    floor = statistics.median(reference.values())
    return max(abs(program[k] - reference[k]) / max(reference[k], floor)
               for k in reference)


def worst_relative(program, reference) -> float:
    """Largest ``|p - r| / |r|`` over paired numbers."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference))
