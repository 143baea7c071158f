"""The comparison that decides ``correct``.

Both sides give the same readings (see ``reference.run``): the loss of
each of the first steps, the norm of each tensor's share of the first
aggregated gradient, and the norm of each tensor's change, and of its
EMA's change, over those steps. A layer-stacked weight counts as one
tensor per layer. Each number compared is the worst gap:

    loss_gap          max over steps  |L_prog - L_ref| / |L_ref|
    grad_norm_gap     max over tensors |n_prog - n_ref| / max(n_ref, median n_ref)
    param_change_gap  the same, for the change of the weights
    ema_change_gap    the same, for the change of the EMA
    *_sketch_gap      the norm of the difference, estimated from each
                      side's sketches (``reference.tensor_sketches``),
                      over max(n_ref, median n_ref), worst tensor

A gap of norms, not the norm of a difference: each side's rounding
moves its own norm only a little. The median in the denominator keeps
a tensor whose gradient is all but zero from reading its round-off as a
fault. Tensors whose reference gradient is under ``NOUGHT`` of the
median tensor's are left out of the two change numbers: under RMSProp
such a tensor moves by its round-off alone.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

NUMBERS = ("loss_gap", "grad_norm_gap", "param_change_gap", "ema_change_gap",
           "grad_sketch_gap", "param_sketch_gap", "ema_sketch_gap")
NOUGHT = 1e-3


def _norm_gap(prog: Dict[str, float], ref: Dict[str, float],
              names: List[str]) -> float:
    if not names:
        return math.inf
    med = float(np.median([ref[n] for n in names]))
    worst = 0.0
    for n in names:
        p, r = prog.get(n, math.nan), ref[n]
        gap = abs(p - r) / max(r, med)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def _sketch_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                norms: Dict[str, float], names: List[str]) -> float:
    if not names:
        return math.inf
    med = float(np.median([norms[n] for n in names]))
    worst = 0.0
    for n in names:
        if n not in prog:
            return math.inf
        diff = float(np.sqrt(np.mean((prog[n] - ref[n]) ** 2)))
        gap = diff / max(norms[n], med)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers compared, from the two sides' readings."""
    loss = [abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"])]
    loss_gap = (max(loss) if len(loss) == len(ref["losses"])
                and all(map(math.isfinite, loss)) else math.inf)
    grads = sorted(ref["grad"])
    med = float(np.median([ref["grad"][n] for n in grads]))
    moved = [n for n in grads if ref["grad"][n] >= NOUGHT * med]
    return {"loss_gap": loss_gap,
            "grad_norm_gap": _norm_gap(prog["grad"], ref["grad"], grads),
            "param_change_gap": _norm_gap(prog["param_change"],
                                          ref["param_change"], moved),
            "ema_change_gap": _norm_gap(prog["ema_change"], ref["ema_change"],
                                        moved),
            "grad_sketch_gap": _sketch_gap(prog["grad_sketch"],
                                           ref["grad_sketch"], ref["grad"],
                                           grads),
            "param_sketch_gap": _sketch_gap(prog["param_change_sketch"],
                                            ref["param_change_sketch"],
                                            ref["param_change"], moved),
            "ema_sketch_gap": _sketch_gap(prog["ema_change_sketch"],
                                          ref["ema_change_sketch"],
                                          ref["ema_change"], moved)}


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for every number that has a limit."""
    return {name: {"value": numbers[name], "limit": limits[name]}
            for name in NUMBERS if limits.get(name) is not None}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
