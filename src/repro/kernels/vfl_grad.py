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

Parties in the block.  One party's call has 2-D blocks (the flat
``shard_map`` mesh makes it so, one party per chip).  Under ``jax.vmap``
over parties (the one-chip engine's emulated party axis, a packed
PartyMesh slot) a batching rule (``jax.custom_batching.custom_vmap``)
makes ONE call whose blocks carry Q of the G mapped parties — X
(Q, Bb, Db), w (Q, Db, Mw), ϑ (Q, Bb, Mθ), z (Q, Bb, Mw), g (Q, Db, Mθ) —
each contracted by its own dots in a static loop, with the grid still
(nD, nB), or (G/Q, nD, nB) where Q < G.  Pallas's own rule would put the
party axis in front of the grid instead: one sequential visit per party,
which costs on the chip about what a whole call does.  Q is chosen from
the shapes alone: all G where their double-buffered blocks and
accumulators fit ``VMEM_BUDGET``, else the largest divisor of G that
does.  A further vmap (the data axis, the slots around a packed slot)
folds into the same group axis; an operand it leaves unbatched is
broadcast.  Operands and outputs keep their shapes either way, (G, Bp, Dp)
for X.  Each grouped call is counted at trace time, under the program
being traced, as ``vfb2.kernel.grouped`` or, where the budget kept one
party a visit, ``vfb2.kernel.per_party`` (``repro.tracing``).

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

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import tracing


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


def _vfl_kernel(*refs, grid_axes: int, parties: int | None, denom: int,
                block_b: int, fwd: bool, bwd: bool, has_w: bool,
                use_lamw: bool, nsplit: int | None, z_acc_used: bool,
                g_acc_used: bool):
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
    # ``parties``: None for one party's 2-D blocks; Q for grouped blocks
    # whose leading axis holds Q parties, each contracted by its own dots
    # in a static loop; the grid's last two axes are (nD, nB) either way,
    # after a group axis where the parties span several blocks.
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it) if has_w else None
    theta_ref = next(it) if bwd else None
    lam_ref = next(it) if use_lamw else None
    z_ref = next(it) if fwd else None
    g_ref = next(it) if bwd else None
    z_acc = next(it) if fwd and z_acc_used else None
    g_acc = next(it) if bwd and g_acc_used else None

    di = pl.program_id(grid_axes - 2)
    bi = pl.program_id(grid_axes - 1)
    nb = pl.num_programs(grid_axes - 1)
    # one index prefix per party of the block: () or (p,)
    prefixes = [()] if parties is None else [(p,) for p in range(parties)]

    def whole(ix):
        return ix + (Ellipsis,) if ix else Ellipsis

    def each(fn):
        def run():
            for ix in prefixes:
                fn(ix)
        return run

    def x_of(ix):
        return x_ref[whole(ix)].astype(jnp.float32)       # (Bb, Db)

    def w_of(ix):
        return w_ref[whole(ix)].astype(jnp.float32)       # (Db, Mw)

    def lam_w(ix, acc):
        return acc + lam_ref[ix + (0, 0)] * w_of(ix) if use_lamw else acc

    if fwd:
        def _z_work(ix):
            # forward partials for this (feature, batch) tile: rank-k MXU
            zt = jnp.dot(x_of(ix), w_of(ix), precision=_F32,
                         preferred_element_type=jnp.float32)
            if z_acc is None:
                # nD == 1: one feature pass computes the full z — write
                # the output block directly, no accumulator round-trip
                z_ref[whole(ix)] = zt
                return
            acc = ix + (pl.ds(bi * block_b, block_b), slice(None))

            @pl.when(di == 0)
            def _z_init():
                z_acc[acc] = zt

            @pl.when(di > 0)
            def _z_accum():
                z_acc[acc] += zt

            # Written on every visit; the grid is sequential, so the final
            # feature pass (di == nD-1) is the last writer and the HBM
            # block holds the fully reduced z.  No out-of-kernel reduction
            # remains.  (Split-batch: backward-row tiles never write their
            # z block — the wrapper slices those rows away.)
            z_ref[whole(ix)] = z_acc[acc]

        if nsplit is None:
            each(_z_work)()
        else:
            pl.when(bi >= nsplit)(each(_z_work))

    def xt_theta(ix):
        x = x_of(ix)
        th = theta_ref[whole(ix)].astype(jnp.float32)     # (Bb, Mθ)
        return jnp.dot(x.T, th, precision=_F32,
                       preferred_element_type=jnp.float32)

    if bwd and g_acc is None:
        # A single backward row tile: XᵀΘ is complete after one visit, so
        # finalize (scale + λW) inline and skip the accumulator.  The
        # output block for feature tile di persists across the remaining
        # (forward-only) batch-tile visits — same sequential-grid
        # revisiting contract the z path relies on.
        def _g_once(ix):
            acc = lam_w(ix, xt_theta(ix) / denom)
            g_ref[whole(ix)] = acc.astype(g_ref.dtype)

        if nsplit is None:
            each(_g_once)()
        else:
            pl.when(bi < nsplit)(each(_g_once))
    elif bwd:
        @pl.when(bi == 0)
        def _g_init():
            g_acc[...] = jnp.zeros_like(g_acc)

        def _g_work(ix):
            # backward accumulate: XᵀΘ, f32 in VMEM
            g_acc[whole(ix)] += xt_theta(ix)

        if nsplit is None:
            each(_g_work)()
        else:
            pl.when(bi < nsplit)(each(_g_work))

        def _g_finalize(ix):
            acc = lam_w(ix, g_acc[whole(ix)] / denom)
            g_ref[whole(ix)] = acc.astype(g_ref.dtype)

        pl.when(bi == nb - 1)(each(_g_finalize))


#: VMEM a grouped call's blocks may take: half of the 16 MiB that Mosaic
#: scopes for a kernel by default on a v5e, so its own scratch keeps room
VMEM_BUDGET = 8 << 20


def _vmem_bytes(shape) -> int:
    """Bytes of an f32 VMEM buffer, its last two dims padded to the chip's
    (8, 128) tile (an upper bound for a narrower dtype)."""
    *lead, r, c = shape
    return int(np.prod(lead)) * _round_up(r, 8) * _round_up(c, 128) * 4


def parties_per_visit(q: int, party_bytes: int,
                      budget: int = VMEM_BUDGET) -> int:
    """Q, the parties one grid visit holds: the largest divisor of ``q``
    whose Q parties' VMEM (``party_bytes`` each) fits ``budget`` — all q
    where they fit, one where even two do not."""
    return max(n for n in range(1, q + 1)
               if q % n == 0 and (n == 1 or n * party_bytes <= budget))


@dataclasses.dataclass(frozen=True)
class _Call:
    """The static shape of one kernel call, operands already padded."""
    mode: str
    interpret: bool
    block_b: int
    block_d: int
    bp: int                 # padded rows
    dp: int                 # padded columns
    mw: int | None          # weight columns (None: no weight operand)
    mth: int | None         # ϑ columns (None: forward mode)
    nsplit: int | None      # backward-only row tiles of a split batch
    denom: int
    use_lamw: bool

    @property
    def fwd(self) -> bool:
        return self.mode in ("fused", "forward")

    @property
    def bwd(self) -> bool:
        return self.mode in ("fused", "backward")

    @property
    def grid(self) -> tuple:
        return self.dp // self.block_d, self.bp // self.block_b

    # Scratch elision (see kernel): the z accumulator exists only when the
    # forward reduction spans >1 feature tile; the g accumulator only when
    # the backward rows span >1 row tile (all rows without split, the
    # backward block's tiles with it).
    @property
    def z_acc_used(self) -> bool:
        return self.fwd and self.grid[0] > 1

    @property
    def g_acc_used(self) -> bool:
        nb = self.grid[1] if self.nsplit is None else self.nsplit
        return self.bwd and nb > 1

    @property
    def party_bytes(self) -> int:
        """VMEM one party takes in a grid visit: its operand and output
        blocks, double-buffered, and its accumulators."""
        bb, bd = self.block_b, self.block_d
        blocks = [(bb, bd)]
        if self.mw is not None:
            blocks.append((bd, self.mw))
        if self.fwd:
            blocks.append((bb, self.mw))
        if self.bwd:
            blocks += [(bb, self.mth), (bd, self.mth)]
        scratch = [s for s, used in (((self.bp, self.mw), self.z_acc_used),
                                     ((bd, self.mth), self.g_acc_used))
                   if used]
        return (2 * sum(map(_vmem_bytes, blocks))
                + sum(map(_vmem_bytes, scratch)))


def _pallas(c: _Call, operands, parties: int | None = None):
    """The Mosaic call.  ``parties=None``: one party's 2-D operands, grid
    (nD, nB).  ``parties=Q``: operands with a leading axis of G parties,
    Q of them in each block, grid (nD, nB) when Q == G and (G/Q, nD, nB)
    otherwise."""
    nd, nb = c.grid
    lead, grid = (), (nd, nb)
    if parties is not None:
        lead = (parties,)
        groups = operands[0].shape[0] // parties
        if groups > 1:
            grid = (groups, nd, nb)
    kernel = functools.partial(
        _vfl_kernel, grid_axes=len(grid), parties=parties, denom=c.denom,
        block_b=c.block_b, fwd=c.fwd, bwd=c.bwd, has_w=c.mw is not None,
        use_lamw=c.use_lamw, nsplit=c.nsplit, z_acc_used=c.z_acc_used,
        g_acc_used=c.g_acc_used)

    def spec(shape, f, **kw):
        """A block of ``shape`` per party at f(di, bi); grouped, behind the
        block of parties the group index (0 without a group axis) picks."""
        if parties is None:
            return pl.BlockSpec(shape, f, **kw)
        return pl.BlockSpec(
            lead + shape,
            lambda *ix: (ix[0] if len(ix) == 3 else 0,) + f(*ix[-2:]), **kw)

    def out(rows, cols):
        return jax.ShapeDtypeStruct(operands[0].shape[:-2] + (rows, cols),
                                    jnp.float32)

    bb, bd = c.block_b, c.block_d
    # Mode-specific specs: a single-sided call neither streams the unused
    # operand into VMEM nor DMAs a dead output back to HBM.  A dead side's
    # column count is None, so each side's specs are built only under its
    # own guard.
    in_specs = [spec((bb, bd), lambda di, bi: (bi, di))]
    if c.mw is not None:
        in_specs.append(spec((bd, c.mw), lambda di, bi: (di, 0)))
    if c.bwd:
        in_specs.append(spec((bb, c.mth), lambda di, bi: (bi, 0)))
    if c.use_lamw:
        in_specs.append(spec((1, 1), lambda di, bi: (0, 0),
                             memory_space=pltpu.SMEM))
    sides = []
    if c.fwd:
        sides.append((spec((bb, c.mw), lambda di, bi: (bi, 0)),
                      out(c.bp, c.mw),
                      pltpu.VMEM(lead + (c.bp, c.mw), jnp.float32)
                      if c.z_acc_used else None))
    if c.bwd:
        sides.append((spec((bd, c.mth), lambda di, bi: (di, 0)),
                      out(c.dp, c.mth),
                      pltpu.VMEM(lead + (bd, c.mth), jnp.float32)
                      if c.g_acc_used else None))
    return list(pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[s[0] for s in sides],
        out_shape=[s[1] for s in sides],
        scratch_shapes=[s[2] for s in sides if s[2] is not None],
        interpret=c.interpret,
        name=f"vfl_grad_{c.mode}",    # the kernel's name in a device trace
    )(*operands))


def _book(c: _Call, operands, n: int = 1):
    """Count a grouped call at trace time: ``vfb2.kernel.grouped`` when
    its blocks hold several parties (or its only one),
    ``vfb2.kernel.per_party`` when the VMEM budget kept one party a visit.
    Returns the call's Q."""
    g = operands[0].shape[0]
    qv = parties_per_visit(g, c.party_bytes)
    kind = "per_party" if qv == 1 and g > 1 else "grouped"
    tracing.count(f"vfb2.kernel.{kind}", n)
    return qv


def _invoke(c: _Call, operands, parties: int | None = None):
    """The kernel call, ``parties`` as in :func:`_pallas`.  Under
    ``jax.vmap`` (the engine's emulated party axis, a packed PartyMesh
    slot) one party's call becomes ONE call whose blocks carry the mapped
    parties, not the grid visit per party that Pallas's own batching rule
    would add; a further vmap (the data axis, the slots around a packed
    slot) folds its axis into the same group axis: A × G parties."""
    @custom_batching.custom_vmap
    def call(*ops):
        return _pallas(c, ops, parties)

    @call.def_vmap
    def _batched(axis_size, in_batched, *ops):
        # the mapped axis in front; an unbatched operand is broadcast to it
        ops = [o if b else jnp.broadcast_to(o, (axis_size,) + o.shape)
               for o, b in zip(ops, in_batched)]
        if parties is not None:
            g = operands[0].shape[0]
            ops = [o.reshape((axis_size * g,) + o.shape[2:]) for o in ops]
            _book(c, operands, -1)          # the call this one replaces
        outs = _invoke(c, ops, _book(c, ops))
        if parties is not None:
            outs = [o.reshape((axis_size, g) + o.shape[1:]) for o in outs]
        return outs, [True] * len(outs)

    return call(*operands)


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
    call = _Call(mode=mode, interpret=interpret, block_b=block_b,
                 block_d=block_d, bp=bp, dp=dp, mw=mw, mth=mth,
                 nsplit=nsplit, denom=denom, use_lamw=use_lamw)
    operands = (xb,)
    if has_w:
        operands += (w2,)
    if bwd:
        operands += (th2,)
    if use_lamw:
        operands += (jnp.asarray(lam, jnp.float32).reshape(1, 1),)
    outs = _invoke(call, operands)
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
