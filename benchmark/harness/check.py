"""The comparison that decides ``correct`` for a training cell.

Both sides give: the loss of each of the first steps, the norm of each
tensor of the first gradient as the optimizer gets it, and the norm of
each tensor's change over those steps. A gap of norms is the distance
between the program's norm and the reference's (not the norm of a
difference), against the reference's norm of that tensor or of the median
tensor, whichever is larger; the number compared is the worst tensor's.
"""

from __future__ import annotations

import math

import numpy as np

# a tensor whose reference gradient is under this share of the median
# tensor's moves under Adam-like optimizers by round-off alone (a key's
# bias under softmax): it is left out of the change comparison
DEAD_GRADIENT_SHARE = 1e-3


def _flat(d: dict):
    names, vals = [], []
    for n in sorted(d):
        a = np.atleast_1d(np.asarray(d[n], np.float64))
        for i, x in enumerate(a):
            names.append(n if a.size == 1 else f"{n}[{i}]")
            vals.append(float(x))
    return names, np.asarray(vals)


def worst_gap(prog: dict, ref: dict, keep=None):
    """(gap of the worst tensor, its name, gap of the median tensor)."""
    names, r = _flat(ref)
    names_p, p = _flat(prog)
    if names != names_p:
        raise ValueError("the two sides name different tensors: "
                         f"{sorted(set(names) ^ set(names_p))[:6]}")
    floor = float(np.median(r))
    gaps = np.abs(p - r) / np.maximum(r, floor)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    counted = gaps if keep is None else gaps[keep]
    if keep is not None:
        gaps = np.where(keep, gaps, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i], float(np.median(counted))


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """``{"correct": bool, "numbers": {name: {"value", "limit", ...}}}``.

    ``limits`` names the numbers that are held: ``loss_1`` .. ``loss_n``
    (relative gap of a step's loss), ``grad_worst_leaf``,
    ``grad_median_leaf``, ``change_worst_leaf``, ``change_median_leaf``
    (the worst tensor swings with the noise of the smallest ones; the
    median tensor is steady from seed to seed). A number without a limit
    is reported and not held."""
    numbers = {}
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    if lp.shape != lr.shape:
        raise ValueError(f"losses of {lp.shape} steps against {lr.shape}")
    for i, (a, b) in enumerate(zip(lp, lr), 1):
        gap = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        numbers[f"loss_{i}"] = {"value": gap, "program": float(a),
                                "reference": float(b)}
    g, g_name, g_mid = worst_gap(prog["grad"], ref["grad"])
    numbers["grad_worst_leaf"] = {"value": g, "leaf": g_name}
    numbers["grad_median_leaf"] = {"value": g_mid}
    _, rg = _flat(ref["grad"])
    alive = rg >= DEAD_GRADIENT_SHARE * float(np.median(rg))
    c, c_name, c_mid = worst_gap(prog["change"], ref["change"], keep=alive)
    numbers["change_worst_leaf"] = {"value": c, "leaf": c_name,
                                    "leaves_left_out": int((~alive).sum())}
    numbers["change_median_leaf"] = {"value": c_mid}
    correct = True
    for name, rec in numbers.items():
        rec["limit"] = limits.get(name)
        if rec["limit"] is not None and not rec["value"] <= rec["limit"]:
            correct = False
    return {"correct": correct, "numbers": numbers}


def brief(numbers: dict) -> dict:
    """Short plain names, each with its number and its limit."""
    return {n: {"value": r["value"], "limit": r["limit"]}
            for n, r in numbers.items()}
