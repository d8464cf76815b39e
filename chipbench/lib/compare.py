"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference, each number against its limit.

Training (the first three steps, taken through the window's own compiled
step and feed):

* ``loss_gap``: the worst step of ``|loss - loss_ref| / |loss_ref|``;
* ``grad_norm_gap``: the first gradient as the optimiser received it
  (Adam's first moment after one step over ``1 - b1``), each leaf's gap
  between the program's norm and the reference's, over the reference's
  norm of that leaf or of the median leaf, whichever is larger;
* ``grad_diff``: the same gradient, each leaf's norm of the difference
  over the same scale;
* ``update_norm_gap``: the parameters' change over the three steps, each
  leaf's gap of norms as in ``grad_norm_gap``.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's are left out: Adam moves them by round-off alone.

The three are taken at the median leaf (the lower middle one for an even
count, so of GCN's two leaves the smaller gap), since one leaf swings
from seed to seed by the nature of the model, not of the program: a ReLU
whose input lies within rounding of zero passes the gradient on one side
and not on the other, which moves that leaf's gradient (a layer's weight
below it) while the loss and the other leaves stay put; and Adam
normalises small gradient entries of a leaf's change.  A fault that
reaches every leaf, as lower precision does, reads at the median; a leaf
left unmoved or moved twice also moves the other leaves' later steps and
the loss, which ``loss_gap`` and ``update_norm_gap`` read.

Serving: ``logit_gap``, the worst answered request's
``max |served - ref|`` over the larger of its reference row's
``max |ref|`` and the median of those; ``unanswered``, the requests due in
the window that never got an answer.
"""
from __future__ import annotations

import statistics

import numpy as np

__all__ = ["train_numbers", "train_detail", "serve_numbers", "judge",
           "GRAD_FLOOR"]

GRAD_FLOOR = 1e-3     # of the median leaf's gradient norm


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def _gaps(got: dict, want: dict, keys) -> list:
    n_got, n_want = _norms(got), _norms(want)
    scale = float(np.median([n_want[k] for k in keys]))
    return [abs(n_got[k] - n_want[k]) / max(n_want[k], scale, 1e-30)
            for k in keys]


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses`` (three steps), ``first_grad``,
    ``params0`` and ``params3`` (dicts of arrays by parameter name)."""
    losses = max(abs(a - b) / max(abs(b), 1e-30)
                 for a, b in zip(prog["losses"], ref["losses"]))
    keys = sorted(ref["first_grad"])
    g_ref = _norms(ref["first_grad"])
    med = float(np.median(list(g_ref.values())))
    moved = [k for k in keys if g_ref[k] >= GRAD_FLOOR * med]

    def change(side):
        return {k: np.asarray(side["params3"][k], np.float64)
                - np.asarray(side["params0"][k], np.float64) for k in moved}
    median = statistics.median_low
    return {
        "loss_gap": losses,
        "grad_norm_gap": median(_gaps(prog["first_grad"], ref["first_grad"],
                                      keys)),
        "grad_diff": median(_grad_diffs(prog, ref, keys)),
        "update_norm_gap": median(_gaps(change(prog), change(ref), moved)),
    }


def _grad_diffs(prog: dict, ref: dict, keys) -> list:
    g_ref = _norms(ref["first_grad"])
    med = float(np.median(list(g_ref.values())))
    return [float(np.linalg.norm(
        np.asarray(prog["first_grad"][k], np.float64)
        - np.asarray(ref["first_grad"][k], np.float64)))
        / max(g_ref[k], med, 1e-30) for k in keys]


def train_detail(prog: dict, ref: dict) -> dict:
    """What the numbers are made of, for the log: each step's loss gap,
    and each leaf's first-gradient gap of norms and difference on the
    compared numbers' scale."""
    keys = sorted(ref["first_grad"])
    return {
        "loss_gaps": [abs(a - b) / max(abs(b), 1e-30)
                      for a, b in zip(prog["losses"], ref["losses"])],
        "grad_norm_gaps": dict(zip(keys, _gaps(
            prog["first_grad"], ref["first_grad"], keys))),
        "grad_diffs": dict(zip(keys, _grad_diffs(prog, ref, keys))),
        "update_norms": {
            k: [float(np.linalg.norm(np.asarray(side["params3"][k], np.float64)
                                     - np.asarray(side["params0"][k],
                                                  np.float64)))
                for side in (prog, ref)]
            for k in sorted(ref["first_grad"])}}


def serve_numbers(served: np.ndarray, ref: np.ndarray,
                  unanswered: int) -> dict:
    """``served`` and ``ref`` are ``(answered, classes)`` rows of the same
    requests."""
    if len(served) == 0:
        return {"logit_gap": float("inf"), "unanswered": int(unanswered)}
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    row_scale = np.abs(ref).max(axis=1)
    scale = np.maximum(row_scale, np.median(row_scale))
    gap = np.abs(served - ref).max(axis=1) / np.maximum(scale, 1e-30)
    return {"logit_gap": float(gap.max()), "unanswered": int(unanswered)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number at or under its limit (a non-finite number fails).
    Returns the verdict and ``{name: {"value", "limit"}}``."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        checks[name] = {"value": float(value) if np.isfinite(value)
                        else 1e308, "limit": limit}
    return ok, checks
