"""VFB²-SGD / -SVRG / -SAGA (paper Algorithms 2–7) + comparison baselines.

This module is the *algorithmic* reference: a deterministic, vectorized
JAX implementation of the exact update rules.  Two properties tie it to the
protocol implementations:

* the aggregation ``agg = Σ_ℓ X_{G_ℓ} w_{G_ℓ}`` is block-separable — the
  secure two-tree masked aggregation (`core.secure_agg`) computes the same
  value to float tolerance (tested), so the sequential math here is the
  federated math ("lossless" by construction);
* every gradient is formed the BUM way: ϑ first, then per-block
  ``X_{G_ℓ}ᵀϑ + λ∇g(w_{G_ℓ})``, which is what passive parties compute from
  the received ϑ (paper Alg. 3/5/7 step 3).

Baselines:
* ``NONF``      — non-federated training (identical updates on pooled data;
                  equals VFB² exactly, which is the losslessness claim);
* ``AFSVRG_VP`` — ERCR without BUM (Gu et al. 2020b): coordinates owned by
                  passive parties are never updated (no labels → no ϑ).

The asynchronous execution of these same rules lives in
``core.async_engine`` (threads, wall-clock) and ``core.staleness``
(bounded-delay SPMD emulation).

This module is the *oracle*; the production hot path is the fused
federated step engine (``core.engine``), which runs the same epochs as one
party-mapped compiled program per epoch (secure aggregation included) and
is reachable here via ``train(..., engine="fused")``.  Tests pin the two
paths together to float tolerance.

``multi_*_epoch`` are the **multi-dominator** oracles: all m active
parties concurrently launch backward updates each round (independent
minibatches, ϑ_j all computed from the same read of the iterate, every
party applying the m BUM updates) — the paper's m-dominator regime,
reachable via ``train(..., multi_dominator=True)`` on both engines.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import Problem


@dataclasses.dataclass(frozen=True)
class PartyLayout:
    """Vertical partition of d features over q parties; m active parties.

    Parties 0..m-1 are active (hold labels); m..q-1 are passive.
    """

    q: int
    m: int
    bounds: Tuple[Tuple[int, int], ...]  # (lo, hi) per party

    @staticmethod
    def even(d: int, q: int, m: int) -> "PartyLayout":
        assert 1 <= m <= q
        cuts = np.linspace(0, d, q + 1).astype(int)
        return PartyLayout(q=q, m=m,
                           bounds=tuple((int(cuts[i]), int(cuts[i + 1]))
                                        for i in range(q)))

    def update_mask(self, d: int, active_only: bool) -> np.ndarray:
        """1.0 where the coordinate may be updated.

        ``active_only=True`` reproduces AFSVRG-VP: only active-party blocks
        (those whose owners hold labels) are trainable.
        """
        mask = np.zeros(d, np.float32)
        parties = range(self.m) if active_only else range(self.q)
        for p in parties:
            lo, hi = self.bounds[p]
            mask[lo:hi] = 1.0
        return mask

    def party_of_coord(self, d: int) -> np.ndarray:
        owner = np.zeros(d, np.int32)
        for p, (lo, hi) in enumerate(self.bounds):
            owner[lo:hi] = p
        return owner


def _batch_indices(key, n, batch, steps):
    return jax.random.randint(key, (steps, batch), 0, n)


def _grad_from_theta(problem: Problem, x, w, theta_vec):
    """BUM gradient: Xᵀϑ/b + λ∇g(w) (block-separable ⇒ full-vector form)."""
    return x.T @ theta_vec / theta_vec.shape[0] + problem.lam * problem.reg_grad(w)


# ---------------------------------------------------------------------------
# epoch drivers (jitted; scan over minibatches)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def sgd_epoch(problem: Problem, w, x, y, lr, mask, key, batch: int, steps: int):
    idx = _batch_indices(key, x.shape[0], batch, steps)

    def body(w, ib):
        xb, yb = x[ib], y[ib]
        agg = xb @ w                       # = Σ_ℓ secure-aggregated partials
        theta = problem.theta(agg, yb)     # dominator computes ϑ
        g = _grad_from_theta(problem, xb, w, theta)
        return w - lr * mask * g, None

    w, _ = jax.lax.scan(body, w, idx)
    return w


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def svrg_epoch(problem: Problem, w, w_snap, mu, x, y, lr, mask, key,
               batch: int, steps: int):
    """Inner loop of VFB²-SVRG (Alg. 4/5): v = g_i(w) − g_i(w̃) + ∇f(w̃)."""
    idx = _batch_indices(key, x.shape[0], batch, steps)

    def body(w, ib):
        xb, yb = x[ib], y[ib]
        th1 = problem.theta(xb @ w, yb)          # ϑ₁ at current iterate
        th0 = problem.theta(xb @ w_snap, yb)     # ϑ₀ at snapshot (distributed)
        g1 = _grad_from_theta(problem, xb, w, th1)
        g0 = _grad_from_theta(problem, xb, w_snap, th0)
        return w - lr * mask * (g1 - g0 + mu), None

    w, _ = jax.lax.scan(body, w, idx)
    return w


@functools.partial(jax.jit, static_argnames=("problem",))
def full_gradient(problem: Problem, w, x, y):
    theta = problem.theta(x @ w, y)
    return x.T @ theta / x.shape[0] + problem.lam * problem.reg_grad(w)


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def saga_epoch(problem: Problem, w, theta_tab, avg, x, y, lr, mask, key,
               batch: int, steps: int):
    """VFB²-SAGA (Alg. 6/7) with the linear-model memory trick.

    The history table stores per-sample ϑ̃_i (scalar) instead of the full
    α_i = ϑ̃_i·x_i vector; ``avg`` maintains (1/n)Σ_j ϑ̃_j x_j incrementally.
    The λ∇g term is applied at the current iterate (it is deterministic per
    block, so it needs no variance reduction).
    """
    n = x.shape[0]
    idx = _batch_indices(key, n, batch, steps)

    def body(carry, ib):
        w, tab, avg = carry
        xb, yb = x[ib], y[ib]
        th_new = problem.theta(xb @ w, yb)
        th_old = tab[ib]
        v = (xb.T @ (th_new - th_old)) / ib.shape[0] + avg \
            + problem.lam * problem.reg_grad(w)
        w = w - lr * mask * v
        # α-table update (last write wins on duplicate indices, as in async)
        avg = avg + xb.T @ (th_new - th_old) / n
        tab = tab.at[ib].set(th_new)
        return (w, tab, avg), None

    (w, theta_tab, avg), _ = jax.lax.scan(body, (w, theta_tab, avg), idx)
    return w, theta_tab, avg


# ---------------------------------------------------------------------------
# pipelined oracle epochs (τ = 1 stale forward read)
# ---------------------------------------------------------------------------
#
# The fused engine's *pipelined* epochs overlap the backward update of
# round t with the forward partial products of round t+1 in ONE kernel
# invocation.  Both halves execute from the same pre-update iterate, so
# round t+1's ϑ is computed from the iterate that is one update old:
#
#     ϑ_t  = ϑ(X_{b_t} w_{t−1}, y_{b_t})          (stale forward read)
#     w_{t+1} = w_t − η·mask·[X_{b_t}ᵀϑ_t/B + λ∇g(w_t)]
#
# with w_{−1} := w_0 (the epoch's prologue forward is fresh, so step 0 is
# exactly the sequential step).  This is precisely a τ = 1 bounded-delay
# (inconsistent-read) execution of the paper's model — Eqs. 4–5 with
# delay ≤ 1 — so Theorems 1–6 cover it.  The epochs below are the exact
# sequential references the engine's pipelined path is pinned against.

@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def pipelined_sgd_epoch(problem: Problem, w, x, y, lr, mask, key,
                        batch: int, steps: int):
    """Sequential oracle for the engine's pipelined VFB²-SGD schedule."""
    idx = _batch_indices(key, x.shape[0], batch, steps)

    def body(carry, inp):
        w, z = carry                    # z: forward of this batch at w_{t-1}
        ib, ib_next = inp
        theta = problem.theta(z, y[ib])
        z_next = x[ib_next] @ w         # forward(t+1) at the pre-update w_t
        g = x[ib].T @ theta / ib.shape[0] + problem.lam * problem.reg_grad(w)
        return (w - lr * mask * g, z_next), None

    z0 = x[idx[0]] @ w                  # prologue (fresh)
    (w, z), _ = jax.lax.scan(body, (w, z0), (idx[:-1], idx[1:]))
    theta = problem.theta(z, y[idx[-1]])            # epilogue (backward only)
    g = x[idx[-1]].T @ theta / batch + problem.lam * problem.reg_grad(w)
    return w - lr * mask * g


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def pipelined_svrg_epoch(problem: Problem, w, w_snap, mu, x, y, lr, mask,
                         key, batch: int, steps: int):
    """Pipelined VFB²-SVRG inner loop: ϑ₁ rides the stale forward read;
    the snapshot column is constant, so ϑ₀ is delay-free by construction."""
    idx = _batch_indices(key, x.shape[0], batch, steps)

    def step(w, z, ib):
        th1 = problem.theta(z, y[ib])
        th0 = problem.theta(x[ib] @ w_snap, y[ib])
        g1 = _grad_from_theta(problem, x[ib], w, th1)
        g0 = _grad_from_theta(problem, x[ib], w_snap, th0)
        return w - lr * mask * (g1 - g0 + mu)

    def body(carry, inp):
        w, z = carry
        ib, ib_next = inp
        z_next = x[ib_next] @ w
        return (step(w, z, ib), z_next), None

    z0 = x[idx[0]] @ w
    (w, z), _ = jax.lax.scan(body, (w, z0), (idx[:-1], idx[1:]))
    return step(w, z, idx[-1])


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps"))
def pipelined_saga_epoch(problem: Problem, w, theta_tab, avg, x, y, lr,
                         mask, key, batch: int, steps: int):
    """Pipelined VFB²-SAGA: ϑ̃ reads/writes stay at application time (only
    the forward read of the iterate is one step stale)."""
    n = x.shape[0]
    idx = _batch_indices(key, n, batch, steps)

    def step(w, tab, avg, z, ib):
        th_new = problem.theta(z, y[ib])
        raw = x[ib].T @ (th_new - tab[ib])
        v = raw / ib.shape[0] + avg + problem.lam * problem.reg_grad(w)
        w = w - lr * mask * v
        avg = avg + raw / n
        tab = tab.at[ib].set(th_new)
        return w, tab, avg

    def body(carry, inp):
        w, tab, avg, z = carry
        ib, ib_next = inp
        z_next = x[ib_next] @ w
        w, tab, avg = step(w, tab, avg, z, ib)
        return (w, tab, avg, z_next), None

    z0 = x[idx[0]] @ w
    (w, theta_tab, avg, z), _ = jax.lax.scan(
        body, (w, theta_tab, avg, z0), (idx[:-1], idx[1:]))
    w, theta_tab, avg = step(w, theta_tab, avg, z, idx[-1])
    return w, theta_tab, avg


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_pipelined_sgd_epoch(problem: Problem, w, x, y, lr, mask, key,
                              batch: int, steps: int, m: int):
    """Pipelined multi-dominator VFB²-SGD: all m dominators' ϑ vectors of
    round t are computed from the same stale read w_{t−1}."""
    d = x.shape[1]
    idx = _batch_indices(key, x.shape[0], m * batch, steps)

    def dom_sum(ibf, th):
        return jnp.einsum("jbd,jb->d", x[ibf].reshape(m, batch, d),
                          th.reshape(m, batch)) / batch

    def step(w, z, ibf):
        theta = problem.theta(z, y[ibf])
        g = dom_sum(ibf, theta) + m * problem.lam * problem.reg_grad(w)
        return w - lr * mask * g

    def body(carry, inp):
        w, z = carry
        ibf, ibf_next = inp
        z_next = x[ibf_next] @ w
        return (step(w, z, ibf), z_next), None

    z0 = x[idx[0]] @ w
    (w, z), _ = jax.lax.scan(body, (w, z0), (idx[:-1], idx[1:]))
    return step(w, z, idx[-1])


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_pipelined_svrg_epoch(problem: Problem, w, w_snap, mu, x, y, lr,
                               mask, key, batch: int, steps: int, m: int):
    """Pipelined multi-dominator VFB²-SVRG inner loop."""
    idx = _batch_indices(key, x.shape[0], m * batch, steps)

    def step(w, z, ibf):
        th1 = problem.theta(z, y[ibf])
        th0 = problem.theta(x[ibf] @ w_snap, y[ibf])
        v = x[ibf].T @ (th1 - th0) / batch + m * (
            problem.lam * (problem.reg_grad(w) - problem.reg_grad(w_snap))
            + mu)
        return w - lr * mask * v

    def body(carry, inp):
        w, z = carry
        ibf, ibf_next = inp
        z_next = x[ibf_next] @ w
        return (step(w, z, ibf), z_next), None

    z0 = x[idx[0]] @ w
    (w, z), _ = jax.lax.scan(body, (w, z0), (idx[:-1], idx[1:]))
    return step(w, z, idx[-1])


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_pipelined_saga_epoch(problem: Problem, w, theta_tab, avg, x, y,
                               lr, mask, key, batch: int, steps: int,
                               m: int):
    """Pipelined multi-dominator VFB²-SAGA (all m ϑ̃ writes at application
    time; last write wins on duplicates, as in the fresh-path oracle)."""
    n = x.shape[0]
    idx = _batch_indices(key, n, m * batch, steps)

    def step(w, tab, avg, z, ibf):
        th_new = problem.theta(z, y[ibf])
        rsum = x[ibf].T @ (th_new - tab[ibf])
        v = rsum / batch + m * avg + m * problem.lam * problem.reg_grad(w)
        w = w - lr * mask * v
        avg = avg + rsum / n
        tab = tab.at[ibf].set(th_new)
        return w, tab, avg

    def body(carry, inp):
        w, tab, avg, z = carry
        ibf, ibf_next = inp
        z_next = x[ibf_next] @ w
        w, tab, avg = step(w, tab, avg, z, ibf)
        return (w, tab, avg, z_next), None

    z0 = x[idx[0]] @ w
    (w, theta_tab, avg, z), _ = jax.lax.scan(
        body, (w, theta_tab, avg, z0), (idx[:-1], idx[1:]))
    w, theta_tab, avg = step(w, theta_tab, avg, z, idx[-1])
    return w, theta_tab, avg


# ---------------------------------------------------------------------------
# multi-dominator oracle epochs (m active parties concurrently launching
# backward updates)
# ---------------------------------------------------------------------------
#
# The paper's framework has every active party act as a dominator: at each
# round the m dominators *concurrently* draw independent minibatches,
# compute their ϑ_j from the same (inconsistently read) iterate, and every
# party applies all m BUM updates to its block.  The deterministic
# realization used as the oracle here: all m reads happen at w_t, so the
# round's update is the *sum* of the m BUM gradients,
#
#     w_{t+1} = w_t − η Σ_{j<m} [ X_{b_j}ᵀ ϑ_j / B + λ∇g(w_t) ],
#
# each dominator's data term normalized by its own minibatch size B (the
# regularizer is applied once per concurrent update, hence the m·λ∇g term
# in the collapsed form).  The fused engine (`core.engine`) reproduces the
# same sequence with one rank-k kernel pass per round (the m ϑ vectors ride
# the kernel's M axis) and is pinned against these epochs in tests.

@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_sgd_epoch(problem: Problem, w, x, y, lr, mask, key, batch: int,
                    steps: int, m: int):
    """VFB²-SGD with m concurrent dominators per round (Alg. 2/3, m > 1)."""
    idx = _batch_indices(key, x.shape[0], m * batch, steps)

    def body(w, ibf):
        ib = ibf.reshape(m, batch)

        def dom_grad(ibj):           # dominator j's BUM gradient at w_t
            xb, yb = x[ibj], y[ibj]
            theta = problem.theta(xb @ w, yb)
            return _grad_from_theta(problem, xb, w, theta)

        g = jax.vmap(dom_grad)(ib).sum(axis=0)   # m concurrent updates
        return w - lr * mask * g, None

    w, _ = jax.lax.scan(body, w, idx)
    return w


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_svrg_epoch(problem: Problem, w, w_snap, mu, x, y, lr, mask, key,
                     batch: int, steps: int, m: int):
    """Multi-dominator VFB²-SVRG inner loop: each dominator evaluates both
    the current iterate and the snapshot on its own minibatch."""
    idx = _batch_indices(key, x.shape[0], m * batch, steps)

    def body(w, ibf):
        ib = ibf.reshape(m, batch)

        def dom_v(ibj):
            xb, yb = x[ibj], y[ibj]
            th1 = problem.theta(xb @ w, yb)
            th0 = problem.theta(xb @ w_snap, yb)
            g1 = _grad_from_theta(problem, xb, w, th1)
            g0 = _grad_from_theta(problem, xb, w_snap, th0)
            return g1 - g0 + mu

        v = jax.vmap(dom_v)(ib).sum(axis=0)
        return w - lr * mask * v, None

    w, _ = jax.lax.scan(body, w, idx)
    return w


@functools.partial(jax.jit, static_argnames=("problem", "batch", "steps",
                                             "m"))
def multi_saga_epoch(problem: Problem, w, theta_tab, avg, x, y, lr, mask,
                     key, batch: int, steps: int, m: int):
    """Multi-dominator VFB²-SAGA: all m dominators read (w_t, tab_t, avg_t);
    the ϑ̃ table takes all m writes per round (last write wins on duplicate
    sample indices, matching the async execution and the fused engine)."""
    n = x.shape[0]
    idx = _batch_indices(key, n, m * batch, steps)

    def body(carry, ibf):
        w, tab, avg = carry
        ib = ibf.reshape(m, batch)

        def dom(ibj):
            xb, yb = x[ibj], y[ibj]
            th_new = problem.theta(xb @ w, yb)
            return xb.T @ (th_new - tab[ibj]), th_new

        raws, th_news = jax.vmap(dom)(ib)        # (m, d), (m, batch)
        v = raws.sum(axis=0) / batch + m * avg \
            + m * problem.lam * problem.reg_grad(w)
        w = w - lr * mask * v
        avg = avg + raws.sum(axis=0) / n
        tab = tab.at[ibf].set(th_news.reshape(-1))
        return (w, tab, avg), None

    (w, theta_tab, avg), _ = jax.lax.scan(body, (w, theta_tab, avg), idx)
    return w, theta_tab, avg


# ---------------------------------------------------------------------------
# top-level trainers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainResult:
    w: np.ndarray
    history: List[dict]  # per-epoch: objective, epoch, algo
    # deep (nonlinear-encoder) runs carry the full DeepVFLParams here;
    # ``w`` is then the shared head vector (the active parties' model)
    params: object = None
    # supervised runs (supervise=True) record every divergence rollback
    # here (core.supervisor.HealEvent dicts); empty list = no heals
    heals: Optional[List[dict]] = None
    # fused runs: the FusedEngine that ran the epochs (its compiled
    # epochs, kernel routing and placed data; a ServeEngine can wrap it).
    # It keeps the packed dataset resident on the device(s) for as long as
    # the result is held: drop the result, or set this to None, to free it.
    engine: object = None


def _eval(problem, w, x, y):
    agg = x @ w
    obj = float(jnp.mean(problem.loss(agg, y))
                + problem.lam * jnp.sum(problem.reg(w)))
    return obj


def _resume_hist(objs, ep0, algo, engine=None):
    """Rebuild the per-epoch history entries recorded before a preemption."""
    hist = []
    for i in range(ep0):
        entry = {"epoch": i + 1, "objective": float(objs[i]), "algo": algo}
        if engine is not None:
            entry["engine"] = engine
        hist.append(entry)
    return hist


def train(
    problem: Problem,
    x: np.ndarray,
    y: np.ndarray,
    layout: PartyLayout,
    algo: str = "svrg",
    epochs: int = 20,
    lr: float = 0.5,
    batch: int = 32,
    seed: int = 0,
    active_only: bool = False,  # True => AFSVRG-VP-style baseline
    w0: Optional[np.ndarray] = None,
    engine: str = "reference",  # "fused" => one compiled program per epoch
    engine_config=None,         # core.engine.EngineConfig when engine="fused"
    multi_dominator: bool = False,  # all m active parties update per round
    pipelined: bool = False,    # τ=1 backward(t) ∥ forward(t+1) schedule
    deep: bool = False,         # nonlinear party-local encoders (deep VFB²)
    hidden: int = 32,           # deep: encoder hidden width
    d_rep: int = 16,            # deep: aggregated representation width
    deep_params=None,           # deep: DeepVFLParams warm start (w0 analogue)
    checkpoint_dir: Optional[str] = None,  # atomic per-epoch checkpoints
    resume_from: Optional[str] = None,     # bit-exact preemption resume
    keep_last: Optional[int] = 1,          # checkpoint ring depth
    supervise: bool = False,               # divergence rollback supervisor
    supervisor_config=None,    # core.supervisor.SupervisorConfig
    horizon_epochs: Optional[int] = None,  # objs allocation horizon
) -> TrainResult:
    """``checkpoint_dir=`` atomically checkpoints the FULL trainer state
    after every epoch (iterate, RNG key, objective history — plus SAGA's
    ϑ̃ table/average); ``resume_from=`` restores it and continues.  A run
    killed at any instant resumes from the last epoch boundary and is
    **bit-exact** vs the uninterrupted run: each epoch is a deterministic
    function of the checkpointed state, and the checkpoint write itself is
    atomic (see ``checkpoint.ckpt``).  ``keep_last=`` sets the retention
    ring depth (older bundles are GC'd after each save; None keeps all).

    ``supervise=True`` hands the run to ``core.supervisor``: training
    proceeds in ring-depth segments, the objective trajectory is watched
    for divergence (non-finite or spike over a trailing window), and a
    diverged run is rolled back to the last healthy checkpoint with the
    learning rate backed off, under a bounded retry budget.  Requires
    ``checkpoint_dir=``; rollback events ride ``result.heals``."""
    if supervise:
        from repro.core.supervisor import supervised_train  # lazy: cycle
        return supervised_train(
            problem, x, y, layout, algo=algo, epochs=epochs, lr=lr,
            batch=batch, seed=seed, active_only=active_only, w0=w0,
            engine=engine, engine_config=engine_config,
            multi_dominator=multi_dominator, pipelined=pipelined,
            deep=deep, hidden=hidden, d_rep=d_rep,
            deep_params=deep_params, checkpoint_dir=checkpoint_dir,
            config=supervisor_config)
    n, d = x.shape
    m = layout.m
    if deep:
        if w0 is not None:
            raise ValueError("deep VFB² has no flat w0; pass deep_params="
                             "(a DeepVFLParams) to warm-start")
        return _train_deep(problem, x, y, layout, algo, epochs, lr, batch,
                           seed, active_only, engine, engine_config,
                           multi_dominator, pipelined, hidden, d_rep,
                           deep_params, checkpoint_dir, resume_from,
                           keep_last, horizon_epochs)
    if engine == "fused":
        return _train_fused(problem, x, y, layout, algo, epochs, lr, batch,
                            seed, active_only, w0, engine_config,
                            multi_dominator, pipelined, checkpoint_dir,
                            resume_from, keep_last, horizon_epochs)
    if engine != "reference":
        raise ValueError(f"unknown engine {engine}")
    from repro.checkpoint.ckpt import (checkpoint_step, load_checkpoint,
                                       save_checkpoint)
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    w = jnp.zeros(d, jnp.float32) if w0 is None else jnp.asarray(w0, jnp.float32)
    mask = jnp.asarray(layout.update_mask(d, active_only))
    steps = max(1, n // batch)
    key = jax.random.PRNGKey(seed)
    hist = []

    if algo == "saga":
        theta_tab = problem.theta(x @ w, y)          # Alg. 6 step 2 (init pass)
        avg = x.T @ theta_tab / n

    objs = np.full(max(horizon_epochs or 0, epochs), np.nan)

    def _state():
        st = {"w": np.asarray(w), "key": np.asarray(key),
              "objs": objs.copy()}
        if algo == "saga":
            st["tab"] = np.asarray(theta_tab)
            st["avg"] = np.asarray(avg)
        return st

    ep0 = 0
    if resume_from is not None:
        st = load_checkpoint(resume_from, _state())
        ep0 = checkpoint_step(resume_from)
        w = jnp.asarray(st["w"])
        key = jnp.asarray(st["key"])
        objs = st["objs"]
        if algo == "saga":
            theta_tab = jnp.asarray(st["tab"])
            avg = jnp.asarray(st["avg"])
        hist = _resume_hist(objs, ep0, algo)

    w_snap, mu = w, None
    for ep in range(ep0, epochs):
        key, sub = jax.random.split(key)
        if algo == "sgd":
            if multi_dominator:
                fn = multi_pipelined_sgd_epoch if pipelined \
                    else multi_sgd_epoch
                w = fn(problem, w, x, y, lr, mask, sub, batch, steps, m)
            else:
                fn = pipelined_sgd_epoch if pipelined else sgd_epoch
                w = fn(problem, w, x, y, lr, mask, sub, batch, steps)
        elif algo == "svrg":
            w_snap = w
            mu = full_gradient(problem, w_snap, x, y)
            if multi_dominator:
                fn = multi_pipelined_svrg_epoch if pipelined \
                    else multi_svrg_epoch
                w = fn(problem, w, w_snap, mu, x, y, lr, mask, sub, batch,
                       steps, m)
            else:
                fn = pipelined_svrg_epoch if pipelined else svrg_epoch
                w = fn(problem, w, w_snap, mu, x, y, lr, mask, sub, batch,
                       steps)
        elif algo == "saga":
            if multi_dominator:
                fn = multi_pipelined_saga_epoch if pipelined \
                    else multi_saga_epoch
                w, theta_tab, avg = fn(problem, w, theta_tab, avg, x, y,
                                       lr, mask, sub, batch, steps, m)
            else:
                fn = pipelined_saga_epoch if pipelined else saga_epoch
                w, theta_tab, avg = fn(problem, w, theta_tab, avg, x, y,
                                       lr, mask, sub, batch, steps)
        else:
            raise ValueError(f"unknown algo {algo}")
        hist.append({"epoch": ep + 1, "objective": _eval(problem, w, x, y),
                     "algo": algo})
        objs[ep] = hist[-1]["objective"]
        if checkpoint_dir is not None:
            save_checkpoint(checkpoint_dir, _state(), step=ep + 1,
                            keep_last=keep_last)
    return TrainResult(w=np.asarray(w), history=hist)


def _train_deep(problem, x, y, layout, algo, epochs, lr, batch, seed,
                active_only, engine, engine_config, multi_dominator,
                pipelined, hidden, d_rep, deep_params,
                checkpoint_dir=None, resume_from=None, keep_last=1,
                horizon_epochs=None) -> TrainResult:
    """Deep VFB² routing: nonlinear party-local encoders (``core.deep_vfl``
    is the sequential oracle; the fused engine's ``deep_*_epoch`` methods
    the hot path).  ``active_only=True`` freezes passive encoders (the
    AFSVRG-VP analogue); ``deep_params`` warm-starts either engine from
    external ``DeepVFLParams``.  ``w`` in the result is the shared head;
    the full ``DeepVFLParams`` ride ``result.params``."""
    from repro.core import deep_vfl  # lazy: deep_vfl imports this module

    if algo not in ("sgd", "svrg"):
        raise ValueError(f"deep VFB² supports algo in ('sgd', 'svrg'); "
                         f"got {algo!r}")
    if engine == "reference":
        params, objs = deep_vfl.train_deep_vfl(
            problem, x, y, layout, algo=algo, epochs=epochs, lr=lr,
            batch=batch, seed=seed, hidden=hidden, d_rep=d_rep,
            freeze_passive=active_only, params=deep_params,
            multi_dominator=multi_dominator, pipelined=pipelined,
            checkpoint_dir=checkpoint_dir, resume_from=resume_from,
            keep_last=keep_last, horizon_epochs=horizon_epochs)
        hist = [{"epoch": i + 1, "objective": o, "algo": f"deep_{algo}"}
                for i, o in enumerate(objs)]
        return TrainResult(w=np.asarray(params.head), history=hist,
                           params=params)
    if engine != "fused":
        raise ValueError(f"unknown engine {engine}")
    return _train_deep_fused(problem, x, y, layout, algo, epochs, lr,
                             batch, seed, active_only, engine_config,
                             hidden, d_rep, deep_params,
                             multi_dominator, pipelined, checkpoint_dir,
                             resume_from, keep_last, horizon_epochs)


def _train_deep_fused(problem, x, y, layout, algo, epochs, lr, batch, seed,
                      active_only, engine_config, hidden, d_rep,
                      deep_params=None, multi_dominator=False,
                      pipelined=False, checkpoint_dir=None,
                      resume_from=None, keep_last=1,
                      horizon_epochs=None) -> TrainResult:
    """Deep hot-path trainer: every nonlinear epoch is ONE device dispatch
    (encoder forward, masked secure aggregation of the (B, d_rep) vector
    partials, ϑ_z = ϑ_logit·head BUM broadcast, and Jacobian-transpose
    updates all inside the compiled program).  ``multi_dominator=True``
    routes through the engine's m-concurrent-dominator deep epochs and
    ``pipelined=True`` through the one-invocation-per-interior-step τ = 1
    schedule (the flags compose).  Key stream and math mirror
    ``deep_vfl.train_deep_vfl`` exactly (tests pin the histories and final
    params at 1e-5)."""
    from repro.checkpoint.ckpt import (checkpoint_step, load_checkpoint,
                                       save_checkpoint)
    from repro.core import deep_vfl  # lazy: deep_vfl imports this module
    from repro.core.engine import EngineConfig, FusedEngine  # lazy: cycle

    n, d = x.shape
    cfg = engine_config if engine_config is not None \
        else EngineConfig(donate=True)
    eng = FusedEngine(problem, x, y, layout, cfg, active_only=active_only)
    key = jax.random.PRNGKey(seed)
    if deep_params is None:
        deep_params = deep_vfl.init_deep_vfl(key, layout, d, hidden, d_rep)
    pq = eng.pack_deep(deep_params)
    steps = max(1, n // batch)
    if multi_dominator:
        sgd_epoch = eng.deep_multi_pipelined_sgd_epoch if pipelined \
            else eng.deep_multi_sgd_epoch
        svrg_epoch = eng.deep_multi_pipelined_svrg_epoch if pipelined \
            else eng.deep_multi_svrg_epoch
    else:
        sgd_epoch = eng.deep_pipelined_sgd_epoch if pipelined \
            else eng.deep_sgd_epoch
        svrg_epoch = eng.deep_pipelined_svrg_epoch if pipelined \
            else eng.deep_svrg_epoch
    hist = []
    objs = np.full(max(horizon_epochs or 0, epochs), np.nan)

    def _state():
        return {"pq": jax.tree_util.tree_map(np.asarray, pq),
                "key": np.asarray(key), "objs": objs.copy()}

    ep0 = 0
    if resume_from is not None:
        st = load_checkpoint(resume_from, _state())
        ep0 = checkpoint_step(resume_from)
        pq = jax.tree_util.tree_map(jnp.asarray, st["pq"])
        key = jnp.asarray(st["key"])
        objs = st["objs"]
        hist = _resume_hist(objs, ep0, f"deep_{algo}", engine="fused")
    for ep in range(ep0, epochs):
        key, sub = jax.random.split(key)
        if algo == "sgd":
            pq = sgd_epoch(pq, lr, sub, batch, steps)
        else:  # svrg: snapshot aliases the live iterate (no donation there)
            muq = eng.deep_full_gradient(pq, sub)
            pq = svrg_epoch(pq, pq, muq, lr, sub, batch, steps)
        hist.append({"epoch": ep + 1, "objective": eng.deep_objective(pq),
                     "algo": f"deep_{algo}", "engine": "fused"})
        objs[ep] = hist[-1]["objective"]
        if checkpoint_dir is not None:
            save_checkpoint(checkpoint_dir, _state(), step=ep + 1,
                            keep_last=keep_last)
    params = eng.unpack_deep(pq)
    return TrainResult(w=np.asarray(params.head), history=hist,
                       params=params, engine=eng)


def _train_fused(problem, x, y, layout, algo, epochs, lr, batch, seed,
                 active_only, w0, engine_config,
                 multi_dominator=False, pipelined=False,
                 checkpoint_dir=None, resume_from=None, keep_last=1,
                 horizon_epochs=None) -> TrainResult:
    """Hot-path trainer: every epoch is ONE device dispatch (secure
    aggregation, ϑ, and BUM updates all inside the compiled program).
    ``multi_dominator=True`` routes through the engine's m-active-party
    epochs (one rank-k kernel pass carries all m dominators' ϑ vectors);
    ``pipelined=True`` additionally overlaps backward(t) with forward(t+1)
    in a single kernel invocation per step (τ = 1 schedule).  The default
    engine config donates the parameter carries, so back-to-back epochs
    reuse buffers instead of allocating fresh ones."""
    from repro.checkpoint.ckpt import (checkpoint_step, load_checkpoint,
                                       save_checkpoint)
    from repro.core.engine import EngineConfig, FusedEngine  # lazy: cycle

    n, d = x.shape
    cfg = engine_config if engine_config is not None \
        else EngineConfig(donate=True)
    eng = FusedEngine(problem, x, y, layout, cfg, active_only=active_only)
    wq = eng.pack_w(np.zeros(d, np.float32) if w0 is None else w0)
    steps = max(1, n // batch)
    key = jax.random.PRNGKey(seed)
    hist = []

    if algo == "saga":
        tabq, avgq = eng.saga_init(wq, key)

    objs = np.full(max(horizon_epochs or 0, epochs), np.nan)

    def _state():
        st = {"wq": np.asarray(wq), "key": np.asarray(key),
              "objs": objs.copy()}
        if algo == "saga":
            st["tabq"] = np.asarray(tabq)
            st["avgq"] = np.asarray(avgq)
        return st

    ep0 = 0
    if resume_from is not None:
        st = load_checkpoint(resume_from, _state())
        ep0 = checkpoint_step(resume_from)
        wq = jnp.asarray(st["wq"])
        key = jnp.asarray(st["key"])
        objs = st["objs"]
        if algo == "saga":
            tabq = jnp.asarray(st["tabq"])
            avgq = jnp.asarray(st["avgq"])
        hist = _resume_hist(objs, ep0, algo, engine="fused")

    wq_snap, muq = wq, None
    for ep in range(ep0, epochs):
        key, sub = jax.random.split(key)
        if algo == "sgd":
            if multi_dominator:
                fn = eng.multi_pipelined_sgd_epoch if pipelined \
                    else eng.multi_sgd_epoch
            else:
                fn = eng.pipelined_sgd_epoch if pipelined else eng.sgd_epoch
            wq = fn(wq, lr, sub, batch, steps)
        elif algo == "svrg":
            wq_snap = wq
            muq = eng.full_gradient(wq_snap, sub)
            if multi_dominator:
                fn = eng.multi_pipelined_svrg_epoch if pipelined \
                    else eng.multi_svrg_epoch
            else:
                fn = eng.pipelined_svrg_epoch if pipelined \
                    else eng.svrg_epoch
            wq = fn(wq, wq_snap, muq, lr, sub, batch, steps)
        elif algo == "saga":
            if multi_dominator:
                fn = eng.multi_pipelined_saga_epoch if pipelined \
                    else eng.multi_saga_epoch
            else:
                fn = eng.pipelined_saga_epoch if pipelined \
                    else eng.saga_epoch
            wq, tabq, avgq = fn(wq, tabq, avgq, lr, sub, batch, steps)
        else:
            raise ValueError(f"unknown algo {algo}")
        hist.append({"epoch": ep + 1, "objective": eng.objective(wq),
                     "algo": algo, "engine": "fused"})
        objs[ep] = hist[-1]["objective"]
        if checkpoint_dir is not None:
            save_checkpoint(checkpoint_dir, _state(), step=ep + 1,
                            keep_last=keep_last)
    return TrainResult(w=eng.unpack_w(wq), history=hist, engine=eng)


def accuracy(w, x, y) -> float:
    pred = np.sign(np.asarray(x) @ np.asarray(w))
    pred[pred == 0] = 1
    return float((pred == np.asarray(y)).mean())


def rmse(w, x, y) -> float:
    err = np.asarray(x) @ np.asarray(w) - np.asarray(y)
    return float(np.sqrt(np.mean(err ** 2)))
