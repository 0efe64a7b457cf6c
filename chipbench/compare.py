"""The comparison that decides `correct` for a training cell.

What is compared (see PERF.md, "How `correct` is decided"): the timed
step's first three steps against the plain reference following the same
three from the same weights, rows and dropout keys.

* ``loss_gap.<k>``: ``|loss_program - loss_reference| / |loss_reference|``
  at step k = 1, 2, 3.
* ``grad_gap``: the first gradient as the optimizer gets it (after the
  global-norm clip; read back from Adam's first moment after one step,
  ``mu / (1 - b1)``).  By the worst leaf: the gap between the program's norm
  and the reference's norm of that leaf, measured against the reference's
  norm of that leaf or of the median leaf, whichever is larger.
  ``grad_gap_mean`` is the mean of that per-leaf gap over all leaves: the
  worst leaf swings from seed to seed by its nature, the mean does not.
* ``update_gap`` / ``update_gap_mean``: the same measures on the parameters'
  change after the three steps.  Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out: they move under Adam by
  round-off alone.

A number is compared where the cell's file gives it a limit (set between
the program's readings and the control's, PERF.md section 4); the others
are printed beside the result as ``not_compared``.  `correct` is true when
every compared number is at or under its limit and every loss is finite.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np


def leaf_norms(tree) -> Tuple[List[str], np.ndarray]:
    """(paths, float64 norms) of a pytree of arrays, in flatten order."""
    import jax

    flat = jax.tree_util.tree_leaves_with_path(tree)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    norms = np.array([float(np.linalg.norm(
        np.asarray(x, dtype=np.float64).ravel())) for _, x in flat])
    return names, norms


def norm_gap(prog: np.ndarray, ref: np.ndarray,
             keep: np.ndarray | None = None) -> Tuple[float, int, float]:
    """Per leaf ``|prog - ref| / max(ref, median(ref))`` -> (the worst
    leaf's, its index, the mean over leaves); ``keep`` masks the leaves
    that count."""
    floor = float(np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-300)
    if keep is not None:
        gap = gap[keep]
        index = np.flatnonzero(keep)
    else:
        index = np.arange(len(gap))
    if not len(gap):
        return 0.0, -1, 0.0
    i = int(np.argmax(gap))
    return float(gap[i]), int(index[i]), float(gap.mean())


def moving_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    return ref_grad_norms >= 1e-3 * float(np.median(ref_grad_norms))


def compare_training(prog: dict, ref: dict,
                     worst: dict | None = None) -> Dict[str, float]:
    """``prog`` / ``ref``: {"losses": [3 floats], "grad_norms": array,
    "update_norms": array} in the same leaf order -> the numbers compared.
    ``worst`` (a dict, filled in) receives the worst leaf's name per gap."""
    out: Dict[str, float] = {}
    for k, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_gap.{k}"] = (abs(lp - lr) / abs(lr)
                                if math.isfinite(lp) and lr != 0.0
                                else math.inf)
    out["grad_gap"], g_leaf, out["grad_gap_mean"] = norm_gap(
        prog["grad_norms"], ref["grad_norms"])
    out["update_gap"], u_leaf, out["update_gap_mean"] = norm_gap(
        prog["update_norms"], ref["update_norms"],
        keep=moving_leaves(ref["grad_norms"]))
    if worst is not None and "leaves" in ref:
        worst["grad_gap"] = ref["leaves"][g_leaf]
        worst["update_gap"] = ref["leaves"][u_leaf]
    return out


def limit_of(name: str, limits: dict):
    """The limit of a number: its own entry, else its family's
    (``loss_gap.2`` -> ``loss_gap``), else None (not compared)."""
    if name in limits:
        return float(limits[name])
    family = name.split(".")[0]
    return float(limits[family]) if family in limits else None


def verdict(numbers: Dict[str, float], limits: dict):
    """-> (correct, {name: [value, limit]} of the numbers compared,
    {name: value} of those that have no limit)."""
    table, rest = {}, {}
    for k, v in numbers.items():
        lim = limit_of(k, limits)
        if lim is None:
            rest[k] = v
        else:
            table[k] = [v, lim]
    if not table:
        raise ValueError("the cell's file gives no number a limit")
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table, rest
