"""Plain reference of linear VFB²: ℓ2-regularised logistic regression
trained by SGD or SVRG epochs over vertically split features.

The masked aggregation of the paper's Algorithm 1 cancels exactly, so the
aggregate Σ_ℓ x_ℓ·w_ℓ is x·w, and every party's block update (Algorithm
3, step 3) is the block of the full gradient.  This module computes that
directly on the unpartitioned data, one minibatch at a time, in plain
``jax.numpy``: no kernel, no masks, no party axis.

``fault`` plants one of the faults a training cell can have, for reading
how far each number moves under it:

* ``"half_batch"``: each step uses the first half of its minibatch and
  takes the mean over that half;
* ``"no_exchange"``: the aggregation is left out, so each party's ϑ comes
  from its own partial product alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import common
from bench.reference.common import (batch_indices, logistic_loss,
                                    logistic_theta)

FAULTS = (None, "half_batch", "no_exchange")


def dot(a, b, precision: str):
    """Every product here has a 1-D operand: bf16x3 is written out."""
    return common.dot(a, b, precision, written_out=True)


def _grad(xb, yb, w, lam, bounds, precision, fault):
    """Minibatch BUM gradient Xᵀϑ/B + λw at ``w``."""
    if fault == "half_batch":
        h = xb.shape[0] // 2
        xb, yb = xb[:h], yb[:h]
    if fault == "no_exchange":
        parts = []
        for lo, hi in bounds:
            th = logistic_theta(dot(xb[:, lo:hi], w[lo:hi], precision), yb)
            parts.append(dot(th, xb[:, lo:hi], precision))
        data = jnp.concatenate(parts)
    else:
        th = logistic_theta(dot(xb, w, precision), yb)
        data = dot(th, xb, precision)
    return data / xb.shape[0] + lam * w


_STATIC = ("bounds", "precision", "fault")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _full_gradient(x, y, w, lam, bounds, precision, fault):
    return _grad(x, y, w, lam, bounds, precision, fault)


@functools.partial(jax.jit,
                   static_argnames=_STATIC + ("batch", "steps"))
def _sgd(x, y, w, lr, lam, key, bounds, precision, fault, batch, steps):
    idx = batch_indices(key, x.shape[0], batch, steps)

    def body(w, ib):
        g = _grad(x[ib], y[ib], w, lam, bounds, precision, fault)
        return w - lr * g, None

    return jax.lax.scan(body, w, idx)[0]


@functools.partial(jax.jit,
                   static_argnames=_STATIC + ("batch", "steps"))
def _svrg(x, y, w, mu, lr, lam, key, bounds, precision, fault, batch,
          steps):
    idx = batch_indices(key, x.shape[0], batch, steps)
    w_snap = w

    def body(w, ib):
        xb, yb = x[ib], y[ib]
        v = (_grad(xb, yb, w, lam, bounds, precision, fault)
             - _grad(xb, yb, w_snap, lam, bounds, precision, fault) + mu)
        return w - lr * v, None

    return jax.lax.scan(body, w, idx)[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _objective(x, y, w, lam, precision):
    agg = dot(x, w, precision)
    return jnp.mean(logistic_loss(agg, y)) + lam * jnp.sum(0.5 * w * w)


class Reference:
    """The reference over one data set, at one precision, with or
    without a planted fault."""

    def __init__(self, x, y, lam: float, bounds, precision: str = "highest",
                 fault=None):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.x = jnp.asarray(x, jnp.float32)
        self.y = jnp.asarray(y, jnp.float32)
        self.lam = jnp.float32(lam)
        self.static = dict(bounds=tuple(tuple(b) for b in bounds),
                           precision=precision, fault=fault)
        self.bounds = self.static["bounds"]
        self.precision = precision

    def objective(self, w) -> float:
        with jax.default_matmul_precision("highest"):
            return float(_objective(self.x, self.y,
                                    jnp.asarray(w, jnp.float32), self.lam,
                                    precision=self.precision))

    def epoch(self, algo: str, w, lr: float, key, batch: int, steps: int):
        """One epoch from ``w`` with the epoch's key; returns the new w."""
        w = jnp.asarray(w, jnp.float32)
        lr = jnp.float32(lr)
        with jax.default_matmul_precision("highest"):
            if algo == "sgd":
                return _sgd(self.x, self.y, w, lr, self.lam, key,
                            batch=batch, steps=steps, **self.static)
            if algo == "svrg":
                mu = _full_gradient(self.x, self.y, w, self.lam,
                                    **self.static)
                return _svrg(self.x, self.y, w, mu, lr, self.lam, key,
                             batch=batch, steps=steps, **self.static)
        raise ValueError(f"unknown algo {algo!r}")

    def leaves(self, w):
        """The per-party blocks: the leaves the comparison reads."""
        w = np.asarray(w)
        return [w[lo:hi] for lo, hi in self.bounds]
