"""The numbers that decide ``correct``, each computed the same way for the
program and for a control put in its place."""

from __future__ import annotations

import numpy as np


def leaves(params) -> list:
    """Flat float64 arrays of an MLP param list, layer by layer, w then b."""
    return [np.asarray(layer[k], np.float64) for layer in params
            for k in ("w", "b")]


def norm_gaps(got, want, keep=None) -> np.ndarray:
    """Each kept leaf's gap between the two norms, ``| |got| - |want| |``,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  ``keep`` masks the leaves that count."""
    g = np.array([np.linalg.norm(a) for a in got])
    w = np.array([np.linalg.norm(a) for a in want])
    if keep is None:
        keep = np.ones(len(w), bool)
    denom = np.maximum(w, np.median(w))
    return (np.abs(g - w) / denom)[keep]


def moving_leaves(ref_grads, floor: float = 1e-3) -> np.ndarray:
    """Leaves whose reference gradient is at least ``floor`` of the median
    leaf's: the others move under Adam by round-off alone."""
    n = np.array([np.linalg.norm(a) for a in ref_grads])
    return n >= floor * np.median(n)


def grads(d: dict, optimizer: str, lr: float) -> list:
    """The gradient as the optimizer holds it after step 1: ``(p0 - p1) /
    lr`` for SGD and ``mu1 / (1 - b1)`` for Adam."""
    if optimizer == "sgd":
        return [(a - b) / lr for a, b in zip(leaves(d["p0"]),
                                              leaves(d["p1"]))]
    return [m / 0.1 for m in leaves(d["mu1"])]


def train_numbers(prog: dict, ref: dict, optimizer: str, lr: float) -> dict:
    """The numbers of a run against the reference; a cell's limits say
    which of them it compares.

    Both dicts hold ``losses`` (one per checked step), ``p0``, ``p1``,
    ``pn`` (param lists at the start and after the first and the last
    checked step) and, for Adam, ``mu1`` (the first moment after step 1).
    ``loss_gap`` is the worst step's relative loss gap and
    ``first_loss_gap`` step 1's; ``grad_gap`` (the first gradient) and
    ``change_gap`` (the change over the checked steps) take the worst leaf,
    the ``median_`` forms the median leaf.
    """
    def change(d):
        return [b - a for a, b in zip(leaves(d["p0"]), leaves(d["pn"]))]

    lp = np.asarray(prog["losses"], np.float64)
    lr_ = np.asarray(ref["losses"], np.float64)
    loss = np.abs(lp - lr_) / np.abs(lr_)
    if not np.all(np.isfinite(lp)):
        loss[:] = np.inf
    g_ref = grads(ref, optimizer, lr)
    grad = norm_gaps(grads(prog, optimizer, lr), g_ref)
    chg = norm_gaps(change(prog), change(ref), keep=moving_leaves(g_ref))
    return {"loss_gap": float(np.max(loss)),
            "first_loss_gap": float(loss[0]),
            "grad_gap": float(np.max(grad)),
            "median_grad_gap": float(np.median(grad)),
            "change_gap": float(np.max(chg)),
            "median_change_gap": float(np.median(chg))}


def end_numbers(start, end, keep) -> dict:
    """The window's end state against its start: ``end_nonfinite``, the
    parameters that are not finite, and ``end_unmoved``, the kept leaves
    whose every element is where the window found it."""
    a, b = leaves(start), leaves(end)
    return {"end_nonfinite": float(sum(np.sum(~np.isfinite(x)) for x in b)),
            "end_unmoved": float(sum(bool(np.array_equal(x, y))
                                     for x, y, k in zip(a, b, keep) if k))}
