"""The numbers that hold a train step's first steps to the plain reference's,
for every configuration: gaps of losses and of leaf norms.

``program`` and ``reference`` are ``{'losses', 'grad1', 'change'}``: each
first step's loss, the first step's gradient as the optimizer got it, and
the parameters' change over the first steps, as nested dicts of arrays.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree, prefix=()):
    for key in sorted(tree):
        value = tree[key]
        if isinstance(value, dict):
            yield from _leaves(value, prefix + (key,))
        else:
            yield '/'.join(prefix + (key,)), value


def leaf_norms(tree):
    return {name: float(np.linalg.norm(np.asarray(v, np.float64))) for name, v in _leaves(tree)}


def leaf_gaps(program, reference, floor=1e-3):
    """The gap between the program's norm of each leaf and the reference's,
    over the reference's norm of that leaf or of the median leaf, whichever
    is larger. The median leaf is taken over the leaves the reference moves
    at all; leaves whose reference norm is under ``floor`` times the median
    leaf's are left out (nought to rounding: behind a zero-initialised
    residual scale the first gradient is exactly zero). Returns ``(median
    gap, worst gap, worst leaf)``."""
    ref = leaf_norms(reference)
    got = leaf_norms(program)
    median = float(np.median([r for r in ref.values() if r > 0]))
    gaps = {name: abs(got[name] - r) / max(r, median)
            for name, r in ref.items() if r >= floor * median}
    worst = max(gaps, key=gaps.get)
    return float(np.median(list(gaps.values()))), gaps[worst], worst


def leaf_difference(program, reference, floor=1e-3):
    """The median, over the leaves ``leaf_gaps`` keeps, of the norm of the
    difference between the program's leaf and the reference's, over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    ref = dict(_leaves(reference))
    got = dict(_leaves(program))
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref.items()}
    median = float(np.median([r for r in norms.values() if r > 0]))
    return float(np.median([
        float(np.linalg.norm(np.asarray(got[k], np.float64) - np.asarray(ref[k], np.float64)))
        / max(r, median) for k, r in norms.items() if r >= floor * median]))


def loss_gap(program_losses, reference_losses):
    return max(abs(p - r) / abs(r) for p, r in zip(program_losses, reference_losses))


def training(program, ref):
    """Every training number the configurations compare; each configuration
    holds those it gives a limit. ``loss_gap`` is the largest relative gap
    of the first steps' losses and ``loss1_gap`` that of the first step's;
    ``grad1_gap`` and ``change_gap`` are the median leaf's gap of norms of
    the first gradient and of the parameters' change over the first steps,
    ``grad1_worst_gap`` and ``change_worst_gap`` the worst leaf's, and
    ``grad1_diff`` and ``change_diff`` the median leaf's norm of the
    difference. ``_grad1_leaf`` and ``_change_leaf`` name the worst leaves."""
    grad1, grad1_worst, grad1_leaf = leaf_gaps(program['grad1'], ref['grad1'])
    change, change_worst, change_leaf = leaf_gaps(program['change'], ref['change'])
    return {
        'loss_gap': loss_gap(program['losses'], ref['losses']),
        'loss1_gap': loss_gap(program['losses'][:1], ref['losses'][:1]),
        'grad1_gap': grad1,
        'grad1_diff': leaf_difference(program['grad1'], ref['grad1']),
        'change_diff': leaf_difference(program['change'], ref['change']),
        'change_gap': change,
        'grad1_worst_gap': grad1_worst,
        'change_worst_gap': change_worst,
        '_grad1_leaf': grad1_leaf,
        '_change_leaf': change_leaf,
    }
