"""Pallas TPU fused VFL partial-product + BUM gradient kernel (rank-k).

The paper's per-iteration hot loop on a party is two passes over the same
minibatch feature block: the *forward* partial products
``z_i = w_{G_ℓ}ᵀ(x_i)_{G_ℓ}`` (Algorithm 1 step 2) and — after ϑ returns —
the *backward* rank-k update ``g = X_bᵀϑ/B + λ∇g(w)`` (Algorithm 3 step 3).
On the paper's CPUs this is cache-line bound; the TPU adaptation fuses both
passes so the X block is read from HBM once per invocation, tiled through
VMEM with both MXU contractions done per tile.

Batched rank-k form: one invocation processes **M concurrent iterates /
ϑ vectors** — the multi-dominator case of Algorithms 2/3 (m active parties
each issue a ϑ), and the variance-reduced algorithms (SVRG evaluates the
current iterate and the snapshot, M = 2) — in a *single* HBM pass over X:

    z = X @ W        (B, Mw)   forward partial products, one column per iterate
    g = XᵀΘ/B + λW   (D, Mθ)   BUM gradients, one column per ϑ

Both reductions complete **in-kernel**: z is accumulated across feature
tiles in a full-minibatch VMEM scratch (so callers never re-sum partials on
the host), g across batch tiles in a per-feature-tile scratch.  Inputs may
be bf16; products take f32 operands (``precision=HIGHEST``) and all
accumulation is f32 in VMEM.

Grid (nD, nB) — batch tiles minor-most (sequential) so the g accumulator
carries across batch tiles for a fixed feature tile; the z accumulator is a
full (B, M) scratch written through on every visit, so the last feature
pass (di == nD−1) leaves the completed sum in HBM (the grid is sequential:
last write wins).  Either accumulator is **elided** when its reduction
completes in a single visit — nD == 1 for z, a single backward row tile
for g — so narrow operands (the deep-VFL encoder layers, rank-1 single-
tile minibatches) write their outputs straight through with no dead VMEM
scratch and no per-grid-step accumulator traffic in interpret mode.

Shapes that do not divide the tile are zero-padded inside the wrapper and
the outputs sliced back, so odd party widths (``PartyLayout.even`` with
d % q != 0) work without caller-side ceremony.

``mode`` selects which contraction is materialized:
  * "fused"    — both (the async hot loop: ϑ from the previous round is
                 applied while the next round's partials are produced);
  * "forward"  — z only (pre-aggregation, ϑ not yet known);
  * "backward" — g only (post-aggregation BUM application).

Split-batch fused form (the pipelined-epoch hot path): the two sides of a
fused invocation may ride **distinct minibatch row-blocks** concatenated
into one X operand.  ``split=Bb`` declares rows [0, Bb) backward-only
(round t's BUM application) and rows [Bb, B) forward-only (round t+1's
partial products): ϑ is supplied for the backward rows alone (the wrapper
zero-masks the forward rows out of the XᵀΘ contraction, padding-aware) and
z is returned for the forward rows alone.  The column counts of the two
sides are then independent, and both sides may be **vector-valued**:
a single forward iterate next to M = m per-dominator ϑ columns
(block-diagonal Θ, the linear multi-dominator epochs), the deep pipelined
epochs' Mw = hidden encoder layer (W₁) beside Mθ = hidden Jacobian
cotangents (du), or Mθ = m·hidden block-diagonal du slabs in the
multi-dominator deep regime — one kernel grid streams the w/ϑ tiles once
and serves backward(t) ∥ forward(t+1) in a single launch instead of two
(``core.engine`` pipelined scan bodies are jaxpr-audited at exactly one
``pallas_call``).

λ is a **traced scalar operand** (SMEM), not a compile-time constant, so
sweeping the regularizer never recompiles the kernel.  It is required to
be a concrete 0 only where the λW term is undefined (``w=None`` backward,
or split-batch calls whose side column counts differ).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Every in-kernel dot states its precision.  Given none, Mosaic's f32 dot
# on a TPU rounds both operands to bf16 (unit roundoff 2^-9), which would
# put the chip path a bf16 rounding away from the f32 oracles it is pinned
# to; HIGHEST keeps f32 operands on the MXU.  Off the chip (interpret
# mode) f32 dots are f32 either way.
_F32 = jax.lax.Precision.HIGHEST


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _concrete_zero(lam) -> bool:
    """True iff ``lam`` is a host scalar equal to 0 (tracers are never)."""
    if isinstance(lam, (int, float, np.floating, np.integer)):
        return float(lam) == 0.0
    if isinstance(lam, (jnp.ndarray, np.ndarray)) \
            and not isinstance(lam, jax.core.Tracer):
        return float(lam) == 0.0
    return False


def _vfl_kernel(*refs, denom: int, block_b: int, fwd: bool, bwd: bool,
                has_w: bool, use_lamw: bool, nsplit: int | None,
                z_acc_used: bool, g_acc_used: bool):
    # Single-sided modes carry only their own operands/outputs (no HBM
    # traffic for a dead side); ref order follows the wrapper's specs.
    # ``has_w=False`` (backward with ``w=None``) additionally drops the
    # weight operand — the engine's multi-dominator BUM application only
    # needs XᵀΘ, so no dead (D, M) block is streamed into VMEM.
    # ``nsplit`` (split-batch form) is the number of backward-only row
    # tiles: tiles bi < nsplit skip the forward dot, tiles bi >= nsplit
    # skip the backward accumulate — each side's MXU work runs on its own
    # rows only, so the fused launch does the same flops as two
    # single-sided launches.
    # Scratch elision: a side whose reduction completes within one grid
    # visit (z with a single feature tile, g with a single backward row
    # tile) writes its output ref directly — no VMEM accumulator is
    # allocated and no per-grid-step accumulator traffic happens
    # (``z_acc_used``/``g_acc_used`` gate the scratch refs).
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    theta_ref = next(it) if bwd else None
    lam_ref = next(it) if use_lamw else None
    z_ref = next(it) if fwd else None
    g_ref = next(it) if bwd else None
    z_acc = next(it) if fwd and z_acc_used else None
    g_acc = next(it) if bwd and g_acc_used else None

    di = pl.program_id(0)
    bi = pl.program_id(1)
    nb = pl.num_programs(1)

    x = x_ref[...].astype(jnp.float32)                    # (Bb, Db)
    w = None if w_ref is None else w_ref[...].astype(jnp.float32)  # (Db, Mw)

    if fwd:
        def _z_work():
            # forward partials for this (feature, batch) tile: rank-k MXU
            zt = jnp.dot(x, w, precision=_F32,
                         preferred_element_type=jnp.float32)
            if z_acc is None:
                # nD == 1: one feature pass computes the full z — write
                # the output block directly, no accumulator round-trip
                z_ref[...] = zt
                return
            sl = pl.ds(bi * block_b, block_b)

            @pl.when(di == 0)
            def _z_init():
                z_acc[sl, :] = zt

            @pl.when(di > 0)
            def _z_accum():
                z_acc[sl, :] += zt

            # Written on every visit; the grid is sequential, so the final
            # feature pass (di == nD-1) is the last writer and the HBM
            # block holds the fully reduced z.  No out-of-kernel reduction
            # remains.  (Split-batch: backward-row tiles never write their
            # z block — the wrapper slices those rows away.)
            z_ref[...] = z_acc[sl, :]

        if nsplit is None:
            _z_work()
        else:
            pl.when(bi >= nsplit)(_z_work)

    if bwd and g_acc is None:
        # A single backward row tile: XᵀΘ is complete after one visit, so
        # finalize (scale + λW) inline and skip the accumulator.  The
        # output block for feature tile di persists across the remaining
        # (forward-only) batch-tile visits — same sequential-grid
        # revisiting contract the z path relies on.
        def _g_once():
            th = theta_ref[...].astype(jnp.float32)       # (Bb, Mθ)
            acc = jnp.dot(x.T, th, precision=_F32,
                          preferred_element_type=jnp.float32) / denom
            if use_lamw:
                acc = acc + lam_ref[0, 0] * w
            g_ref[...] = acc.astype(g_ref.dtype)

        if nsplit is None:
            _g_once()
        else:
            pl.when(bi < nsplit)(_g_once)
    elif bwd:
        @pl.when(bi == 0)
        def _g_init():
            g_acc[...] = jnp.zeros_like(g_acc)

        def _g_work():
            th = theta_ref[...].astype(jnp.float32)       # (Bb, Mθ)
            # backward accumulate: XᵀΘ, f32 in VMEM
            g_acc[...] += jnp.dot(x.T, th, precision=_F32,
                                  preferred_element_type=jnp.float32)

        if nsplit is None:
            _g_work()
        else:
            pl.when(bi < nsplit)(_g_work)

        @pl.when(bi == nb - 1)
        def _g_finalize():
            acc = g_acc[...] / denom
            if use_lamw:
                acc = acc + lam_ref[0, 0] * w
            g_ref[...] = acc.astype(g_ref.dtype)


def vfl_grad(xb, w, theta, lam=0.0, *, interpret: bool, block_b: int = 128,
             block_d: int = 128, mode: str = "fused",
             denom: int | None = None, split: int | None = None):
    """xb: (B, D); w: (D,), (D, Mw) or None; theta: (B,), (B, Mθ) or None.

    ``interpret`` has no default: True runs the kernel body in the Pallas
    interpreter (off-TPU validation), False compiles it through Mosaic.
    Every caller states which, so a chip run can never drift into the
    interpreter unnoticed.

    Returns ``(z, g)`` with z = xb @ w fully reduced in-kernel (shape (B,)
    or (B, Mw)) and g = xbᵀθ/denom + λw (shape (D,) or (D, Mθ)).  ``denom``
    defaults to the number of backward rows (the minibatch gradient 1/B
    scaling); SAGA's running average passes n.  Rank-1 inputs get rank-1
    outputs (per side).  ``lam`` may be a traced scalar — distinct
    regularizer values share one compilation.

    Single-sided modes return ``None`` for the inactive side and carry no
    HBM traffic for it; ``theta=None`` is allowed (and ϑ-free) in
    ``mode="forward"``, and ``w=None`` is allowed in ``mode="backward"``
    when ``lam == 0`` (pure XᵀΘ — the multi-dominator BUM application;
    the dead weight block is then never streamed into VMEM).

    ``split`` (fused mode only) activates the **split-batch** form: xb is
    the concatenation of a backward row-block (rows [0, split)) and a
    forward row-block (rows [split, B)).  ``theta`` then has ``split``
    rows (it is zero-masked over the forward rows before the XᵀΘ pass) and
    the returned z covers only the forward rows.  The two sides' column
    counts Mw/Mθ may differ; the λw term requires Mw == Mθ (pass a
    concrete ``lam=0`` otherwise — the engine adds its regularizer
    outside the kernel).
    """
    b, d = xb.shape
    assert mode in ("fused", "forward", "backward"), mode
    if split is not None:
        assert mode == "fused", "split-batch form is fused-mode only"
        assert 0 < split < b, (split, b)
    if w is None:
        assert mode == "backward", "w=None only valid in mode='backward'"
        if not _concrete_zero(lam):
            raise ValueError("the λw term needs w; pass a concrete lam=0 "
                             "with w=None")
        assert theta is not None
        w2, mw = None, None
        squeeze_z = False
    else:
        squeeze_z = (w.ndim == 1)
        w2 = w[:, None] if w.ndim == 1 else w
        mw = w2.shape[1]
    if theta is None:
        assert mode == "forward", "theta required outside mode='forward'"
        th2, mth = None, None
        squeeze_g = False
    else:
        squeeze_g = (theta.ndim == 1)
        th2 = theta[:, None] if theta.ndim == 1 else theta
        mth = th2.shape[1]
        nrows_bwd = b if split is None else split
        assert th2.shape[0] == nrows_bwd, (th2.shape, nrows_bwd)
        if split is None and mw is not None:
            assert mw == mth, (mw, mth)
    denom = (b if split is None else split) if denom is None else int(denom)

    fwd = mode in ("fused", "forward")
    bwd = mode in ("fused", "backward")
    has_w = w2 is not None
    # λw is only defined when both sides share a column count; the traced
    # operand is skipped entirely for a concrete zero (no dead SMEM read).
    use_lamw = bwd and has_w and mw == mth and not _concrete_zero(lam)
    if bwd and not use_lamw and not _concrete_zero(lam):
        raise ValueError(
            "nonzero lam requires w with matching column counts "
            f"(Mw={mw}, Mθ={mth}); pass a concrete lam=0 and apply the "
            "regularizer outside the kernel")

    # Pad to tile multiples instead of rejecting odd shapes; zero rows/cols
    # contribute zero to both products.  The 128-lane rounding is a Mosaic
    # tiling requirement; interpret mode (off-TPU validation) has no tiling
    # constraint, so it rounds to the 8-sublane granule only and the padded
    # copy volume stops dominating emulated runs.
    lane = 128 if not interpret else 8
    block_d = min(block_d, _round_up(d, lane))
    dp = _round_up(d, block_d)
    if split is None:
        block_b = min(block_b, _round_up(b, 8))
        bp = _round_up(b, block_b)
        nsplit = None
        if bp != b or dp != d:
            xb = jnp.pad(xb, ((0, bp - b), (0, dp - d)))
            if th2 is not None:
                th2 = jnp.pad(th2, ((0, bp - b), (0, 0)))
    else:
        # Per-side row padding so every row tile is purely backward or
        # purely forward — the kernel specializes on the tile index and
        # each side's MXU pass touches only its own rows.
        bf = b - split
        block_b = min(block_b, _round_up(split, 8), _round_up(bf, 8))
        split_p, bf_p = _round_up(split, block_b), _round_up(bf, block_b)
        bp = split_p + bf_p
        nsplit = split_p // block_b
        if split_p != split or bf_p != bf or dp != d:
            xb = jnp.concatenate([
                jnp.pad(xb[:split], ((0, split_p - split), (0, dp - d))),
                jnp.pad(xb[split:], ((0, bf_p - bf), (0, dp - d)))])
        # ϑ rows live on the backward tiles; the forward tiles' (never
        # read) Θ blocks stay zero.
        th2 = jnp.pad(th2, ((0, bp - split), (0, 0)))
    if w2 is not None and dp != d:
        w2 = jnp.pad(w2, ((0, dp - d), (0, 0)))
    nb, nd = bp // block_b, dp // block_d

    # Scratch elision (see kernel): the z accumulator exists only when the
    # forward reduction spans >1 feature tile; the g accumulator only when
    # the backward rows span >1 row tile (all rows without split, the
    # backward block's tiles with it).
    z_acc_used = fwd and nd > 1
    g_acc_used = bwd and (nb if nsplit is None else nsplit) > 1

    kernel = functools.partial(_vfl_kernel, denom=denom, block_b=block_b,
                               fwd=fwd, bwd=bwd, has_w=has_w,
                               use_lamw=use_lamw, nsplit=nsplit,
                               z_acc_used=z_acc_used, g_acc_used=g_acc_used)
    # Mode-specific specs: a single-sided call neither streams the unused
    # operand into VMEM nor DMAs a dead output back to HBM.  A dead side's
    # column count is None, so each side's specs are built only under its
    # own guard.
    in_specs = [pl.BlockSpec((block_b, block_d), lambda di, bi: (bi, di))]
    operands = (xb,)
    if has_w:
        in_specs.append(pl.BlockSpec((block_d, mw), lambda di, bi: (di, 0)))
        operands += (w2,)
    if bwd:
        in_specs.append(pl.BlockSpec((block_b, mth), lambda di, bi: (bi, 0)))
        operands += (th2,)
    if use_lamw:
        in_specs.append(pl.BlockSpec((1, 1), lambda di, bi: (0, 0),
                                     memory_space=pltpu.SMEM))
        operands += (jnp.asarray(lam, jnp.float32).reshape(1, 1),)
    sides = []
    if fwd:
        sides.append((pl.BlockSpec((block_b, mw), lambda di, bi: (bi, 0)),
                      jax.ShapeDtypeStruct((bp, mw), jnp.float32),
                      pltpu.VMEM((bp, mw), jnp.float32) if z_acc_used
                      else None))
    if bwd:
        sides.append((pl.BlockSpec((block_d, mth), lambda di, bi: (di, 0)),
                      jax.ShapeDtypeStruct((dp, mth), jnp.float32),
                      pltpu.VMEM((block_d, mth), jnp.float32) if g_acc_used
                      else None))
    outs = pl.pallas_call(
        kernel,
        grid=(nd, nb),
        in_specs=in_specs,
        out_specs=[s[0] for s in sides],
        out_shape=[s[1] for s in sides],
        scratch_shapes=[s[2] for s in sides if s[2] is not None],
        interpret=interpret,
        name=f"vfl_grad_{mode}",    # the kernel's name in a device trace
    )(*operands)
    if not fwd:
        z = None
    elif split is None:
        z = outs[0][:b]
    else:
        z = outs[0][split_p:split_p + (b - split)]   # the forward rows
    g = outs[-1][:d] if bwd else None
    if squeeze_z and z is not None:
        z = z[:, 0]
    if squeeze_g and g is not None:
        g = g[:, 0]
    return z, g
