"""Operations and bytes, computed from shapes.

``model_flops_per_sample``: the forward and backward passes a training
algorithm requires for one inner-loop sample, recomputed work not
counted (multiply-adds count two).  ``kernel_cost``: the least work of one
``vfl_grad`` Mosaic call, from its operand and output shapes taken back
to the logical extents of the configuration: the program pads a party's
columns to 128 lanes and its rows to a row tile, and padding is not work.
"""
from __future__ import annotations

import numpy as np


def model_flops_per_sample(cfg: dict, algo: str, samples_per_epoch,
                           rows: int) -> float:
    """Model FLOPs per inner-loop sample of ``algo`` for the linear
    model: x·w forward and Xᵀϑ backward, 4·d.  SVRG takes two gradients
    per sample (iterate and snapshot) and one full-gradient pass over all
    ``rows`` per epoch."""
    grad = 4.0 * cfg["cols"]
    if algo == "sgd":
        return grad
    if algo == "svrg":
        return 2.0 * grad + grad * rows / samples_per_epoch
    raise ValueError(f"no FLOP count for algo {algo!r}")


def _logical_rows(padded: int, rows) -> int:
    """The largest logical row count (a minibatch, all rows) that pads to
    ``padded``."""
    fits = [r for r in rows if r <= padded]
    if not fits:
        raise ValueError(f"no logical row count in {rows} fits {padded}")
    return max(fits)


def kernel_cost(operands, outputs, rows, widths):
    """(flops, bytes) of one ``vfl_grad`` call at its logical extents.

    ``operands`` are the call's (shape, bytes per element) in order: the X
    block ``(q, Bp, Dp)``, then the weight columns ``(q, Dp, Mw)`` and/or
    the ϑ columns ``(Bp, Mθ)`` (and possibly a (1, 1) scalar).
    ``outputs`` are z ``(q, Bp, Mw)`` and/or g ``(q, Dp, Mθ)``.  ``rows``
    are the logical row counts the cell's calls take (the minibatch, all
    rows); ``widths`` the parties' column counts, one per entry of the
    leading party axis.  A padded dim ``Bp`` stands for the largest
    logical row count that fits it, ``Dp`` for each party's own width.
    Each side is one contraction over the X block, 2·B·Σd·M operations;
    the bytes are every operand read once and every output written once.
    """
    *lead, bp, dp = operands[0][0]
    if bp == dp:
        raise ValueError(f"X block {operands[0][0]}: rows and columns "
                         "cannot be told apart")
    if int(np.prod(lead)) != len(widths):
        raise ValueError(f"X block {operands[0][0]} holds {np.prod(lead)} "
                         f"parties; the configuration has {len(widths)}")
    b = _logical_rows(bp, rows)
    d = float(sum(widths))

    def mapped(n):
        return b if n == bp else n

    def elements(shape):
        """Logical elements of one shape: Bp → b; a Dp dim, under the
        party axis → the parties' widths, summed."""
        if len(shape) < 2:
            return float(np.prod(shape))
        *lo, r, c = shape
        if r == dp:
            return d * mapped(c)
        if c == dp:
            return d * mapped(r)
        return float(np.prod(lo)) * mapped(r) * mapped(c)

    flops = 0.0
    for shape, _ in outputs:
        if shape[-2] not in (bp, dp):
            raise ValueError(f"output {shape} matches neither B={bp} nor "
                             f"D={dp}")
        flops += 2.0 * b * d * shape[-1]
    nbytes = sum(elements(shape) * size
                 for shape, size in list(operands) + list(outputs))
    return flops, nbytes
