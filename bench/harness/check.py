"""The numbers that decide ``correct``, and the judgement against the
cell's limits (``bench/limits/<cell>.json``).

Training: after the program's first calls in set-up and the plain
reference's same calls,

* ``loss_gap``: the worst epoch's |objective − reference| / |reference|;
* ``grad_norm_gap``: the first call's update direction as the optimiser
  applies it, (state₀ − state₁)/lr, per leaf; the gap between the
  program's norm and the reference's, over max(the reference leaf's norm,
  the median leaf's), worst leaf;
* ``change_norm_gap``: the same for state₃ − state₀;
* ``updates_rms_gap``: every call's update state_k − state_k+1, all
  leaves and calls together, ‖program − reference‖ / ‖reference‖.  The
  norm gaps hardly see rounding, which moves a vector's entries but
  barely its norm, and a largest entry swings from seed to seed; this
  number separates the control's precision from the configuration's.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of every number but the loss (they move by round-off
alone).
"""
from __future__ import annotations

import math

import numpy as np

KEEP_FRACTION = 1e-3


def _norms(leaves):
    return [float(np.linalg.norm(np.asarray(a, np.float64).ravel()))
            for a in leaves]


def _norm_gap(prog, ref, keep):
    pn, rn = _norms(prog), _norms(ref)
    med = float(np.median([r for r, k in zip(rn, keep) if k]))
    return max(abs(p - r) / max(r, med, 1e-30)
               for p, r, k in zip(pn, rn, keep) if k)


def _diff(a_leaves, b_leaves, scale=1.0):
    return [(np.asarray(a, np.float64) - np.asarray(b, np.float64)) * scale
            for a, b in zip(a_leaves, b_leaves)]


def _rms_gap(prog, ref):
    """‖prog − ref‖ / ‖ref‖ over all the leaves together."""
    num = sum(float(np.sum((p - r) ** 2)) for p, r in zip(prog, ref))
    den = sum(float(np.sum(r ** 2)) for r in ref)
    return float(np.sqrt(num / max(den, 1e-300)))


def _updates(lv, states, keep):
    """Every call's update state_k − state_k+1, the kept leaves."""
    return [d for k in range(len(states) - 1)
            for d, kp in zip(_diff(lv(states[k]), lv(states[k + 1])), keep)
            if kp]


def training_numbers(ref, states, objs, ref_states, ref_objs, lr):
    lv = ref.leaves
    g_prog = _diff(lv(states[0]), lv(states[1]), 1.0 / lr)
    g_ref = _diff(lv(ref_states[0]), lv(ref_states[1]), 1.0 / lr)
    rn = _norms(g_ref)
    med = float(np.median(rn))
    keep = [r >= KEEP_FRACTION * med for r in rn]
    last = len(ref_states) - 1
    c_prog = _diff(lv(states[last]), lv(states[0]))
    c_ref = _diff(lv(ref_states[last]), lv(ref_states[0]))
    loss = max(abs(p - r) / max(abs(r), 1e-30)
               for p, r in zip(objs, ref_objs))
    return {"loss_gap": loss,
            "grad_norm_gap": _norm_gap(g_prog, g_ref, keep),
            "change_norm_gap": _norm_gap(c_prog, c_ref, keep),
            "updates_rms_gap": _rms_gap(_updates(lv, states, keep),
                                        _updates(lv, ref_states, keep))}


def judge(numbers: dict, limits: dict):
    """(correct, checks): every number finite and within its limit."""
    checks, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits")
        limit = float(limits[name])
        value = float(value)
        good = math.isfinite(value) and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
