"""Fused on-device VFB² step engine — the canonical hot path.

One jitted program runs an **entire epoch** on device: minibatch sampling,
per-party partial products, masked secure aggregation (Algorithm 1), the
dominator's ϑ, and the BUM backward update (Algorithms 2/3) all live inside
a party-mapped ``lax.scan`` with **zero host↔device synchronization inside
the epoch**.  The three previously divergent paths share this one program:

* ``core.algorithms``   — the sequential reference math (oracle; the fused
                          epochs reproduce it to float tolerance, exactly
                          for a single party);
* ``core.async_engine`` — the wall-clock thread simulation (fidelity
                          reference for BAPA timing claims);
* ``kernels.vfl_grad``  — the batched rank-k Pallas kernel, which the
                          engine routes X-block contractions through when
                          ``use_kernel`` resolves True (default on TPU).

Party-axis realization
----------------------
The per-party program is written once against a named axis and bound two
ways:

* ``shard_map`` over a mesh whose party axis has q devices (true SPMD, one
  party per chip — production);
* ``jax.vmap(axis_name=...)`` when the mesh cannot host q parties (CPU
  tests/CI).  Collectives (``psum``/``ppermute``/``axis_index``) have
  identical semantics under a vmapped named axis, so the emulation is the
  same single compiled program — still one dispatch per epoch.

Secure aggregation inside the scan uses the same primitives as the rest of
the repo: ``secure_psum`` (two-tree masks, Algorithm 1), ``secure_psum_ring``
(pairwise-cancelling ring masks, §Perf), or a plain ``psum`` (``"off"``,
the losslessness oracle).  A fault-free flat epoch draws all of its
steps' masks before the scan, in one batched op from the same step keys
(``FusedEngine._masks``), and each step only adds its δ and reduces.
Labels are replicated across parties here — the SPMD stand-in for the
dominator broadcasting ϑ, numerically identical.

Multi-dominator epochs
----------------------
The paper's framework has all m active parties act as dominators
*concurrently*.  The ``multi_*_epoch`` methods realize that regime on the
fused path: each step, the m dominators draw independent minibatches, one
forward pass over the concatenated (m·B, dp) block produces every
dominator's partial products, the m partial-product sets are
masked-secure-aggregated together, and the m BUM gradients come back as
the columns of a single rank-k contraction — dominator j's ϑ occupies
column j of a block-diagonal Θ, so ``XᵀΘ`` (the kernel's M axis) is
exactly the per-dominator update set, applied summed (all m reads happen
at the same iterate; see ``core.algorithms.multi_sgd_epoch`` for the
update-sequence semantics and the oracle the fused path is pinned
against).  The bounded-delay variant keeps per-(party, dominator) ring
buffers so each dominator's column ages under its own delay schedule.

Pipelined epochs
----------------
``pipelined_*_epoch`` (and their ``multi_`` variants) software-pipeline
the scan: the BUM application of round t and the forward partial products
of round t+1 are data-independent (bilevel asynchrony), so each interior
step issues ONE split-batch fused kernel invocation — X rows =
[X_{b_t}; X_{b_{t+1}}], Θ over the backward rows, W over the forward rows
— instead of a forward launch plus a backward launch.  The w/ϑ tiles
stream into VMEM once per step and launches drop from 2·steps to
steps+1 (forward prologue, fused interior, backward epilogue).  Because
both halves read the same pre-update iterate, round t+1's ϑ is computed
one update late: the schedule is exactly a τ = 1 bounded-delay execution
(see ``core.staleness``), pinned against the ``core.algorithms``
``pipelined_*`` sequential oracles.

Deep epochs
-----------
``deep_{sgd,svrg,delayed_sgd}_epoch`` run the nonlinear generalization —
private party-local encoders producing (B, d_rep) vector partial
representations instead of scalar partial products (``core.deep_vfl`` is
the sequential oracle) — as the same one-dispatch compiled programs: the
encoder layers' X-block contractions ride the rank-k kernel with the
hidden/d_rep widths as the M axis, the vector partials take one masked
secure aggregation per step, and ϑ_z = ϑ_logit·head is the BUM payload.
The deep path carries the full schedule family of the linear path:
``deep_multi_*`` run all m dominators' concurrent backward updates per
step (m concatenated minibatches through ONE encoder forward, one masked
aggregation of all m vector partial sets, per-dominator ϑ_z as block
columns of the rank-k contraction), ``deep_pipelined_*`` overlap round
t's Jacobian-transpose BUM application with round t+1's encoder forward
in one split-batch invocation per interior step (τ = 1), and
``deep_multi_pipelined_*`` compose both.

Faulted epochs (elastic fault tolerance)
----------------------------------------
``faulted_{sgd,svrg,saga}_epoch`` and ``deep_faulted_{sgd,svrg}_epoch``
replay a deterministic :mod:`core.faults` trace *inside* the compiled
epoch: per-step membership masks ``fwd``/``bwd`` (q-vector liveness,
compiled from crash/rejoin/straggle/drop_msg events) gate the survivor
aggregation, the delay-ring writes, and the updates, so a crashed party's
block freezes mid-epoch, its stale contributions age through the existing
(τ+1)-slot ring buffers, and a rejoin replays them — a crash is formally
an **unbounded delay** in the bounded-staleness model.  Secure
aggregation under changing membership uses the survivor-re-keyed
collectives (``secure_psum_members`` / ``secure_psum_ring_members``): the
per-step pairwise masks are re-derived from the alive-set fingerprint so
they still cancel exactly over whoever survived.  The
``schedule_faithful`` ppermute replay of the two-tree schedule is **not**
membership-safe (a dead party is a hole in the fixed permutation
sequence), so faulted epochs always lower two-tree mode to the masked
psum form.  ``core.faults`` holds the sequential fault oracles the
faulted epochs are pinned against (1e-5, all secure modes).

Vertical partitioning packs party blocks to a uniform padded width
(``PartyLayout.even`` with d % q != 0 works); the pad coordinates are
masked out of every update.

Measured speedups (fused vs per-minibatch dispatch, pipelined vs
two-invocation fused) are **not** hardcoded here — see the committed
baseline ``benchmarks/BENCH_engine.json`` (``bench_engine.py`` warns when
a fresh run drifts >20% from it).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.algorithms import PartyLayout, _batch_indices
from repro.core.faults import HealthStats, apply_corruption
from repro.core.losses import Problem
from repro.core.secure_agg import (ring_apply, ring_mask,
                                   secure_psum, secure_psum_hier,
                                   secure_psum_hier_members,
                                   secure_psum_members,
                                   secure_psum_ring,
                                   secure_psum_ring_members,
                                   two_tree_apply, two_tree_mask)
from repro.kernels import vfl_grad as _vg
from repro.sharding.api import PartyMesh, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static knobs of the fused engine (hashable: used as a jit static)."""

    secure: str = "off"              # "off" | "two_tree" | "ring"
    mask_scale: float = 1.0
    schedule_faithful: bool = False  # replay exact T1/T2 rounds via ppermute
    use_kernel: Optional[bool] = None   # None = auto (True on TPU backends)
    interpret: Optional[bool] = None    # None = auto (True off-TPU)
    block_b: int = 128
    block_d: int = 128
    # Kernel routing is for minibatch-sized blocks; the rank-k kernel keeps
    # its z accumulator (B, M) f32 in VMEM, so full-dataset contractions
    # (full_gradient / saga_init) beyond this row count fall back to the
    # XLA matmul rather than risking a VMEM overflow on real TPUs.
    kernel_max_rows: int = 4096
    axis: str = "model"              # party axis name (mesh axis for SPMD)
    # Donate the parameter/state carries (wq, tabq, avgq, bufq) of the
    # jit'd epoch entry points: back-to-back epochs then update buffers in
    # place instead of allocating fresh ones every dispatch.  Off by
    # default because donation *invalidates the caller's input arrays* —
    # enable it (the trainers in core.algorithms/core.staleness do) only
    # when every epoch call rebinds its carries, `w = epoch(w, ...)`-style.
    # SVRG epochs never donate wq: the trainer aliases the epoch-boundary
    # snapshot to the live iterate, and donating one buffer bound to two
    # operands is invalid.
    donate: bool = False


class F32Program:
    """A jitted engine program traced with every contraction at f32.

    On a TPU, XLA's f32 dot at the default matmul precision rounds both
    operands to bf16 (unit roundoff 2^-9).  The engine is pinned to f32
    sequential oracles, so each program is traced (and lowered) under
    ``jax.default_matmul_precision("highest")``: the X-block routes, the
    deep head and the objective all keep f32 operands, on every backend.
    Each call is one ``vfb2.dispatch`` host span naming the program and
    its scanned steps (0 for a program with no ``steps``).
    Other attributes (``_cache_size`` …) are the jitted function's."""

    def __init__(self, jitted, name: str):
        self.jitted = jitted
        self.name = name
        params = list(inspect.signature(jitted).parameters)
        self._steps_at = params.index("steps") if "steps" in params else None

    def __call__(self, *args, **kwargs):
        steps = 0
        if self._steps_at is not None:
            steps = (args[self._steps_at] if len(args) > self._steps_at
                     else kwargs.get("steps", 0))
        with tracing.span(tracing.DISPATCH, program=self.name,
                          steps=int(steps)), \
                jax.default_matmul_precision("highest"):
            return self.jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return self.jitted.lower(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.jitted, name)


def _scoped(name: str):
    """Run the decorated helper under ``jax.named_scope(name)``: every op
    it stages carries the name in its metadata (``op_name``), which the
    profiler's device trace keeps.  A fresh scope per call, as JAX's own
    scope object is not re-entrant."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


#: bytes an epoch's pre-drawn step masks may take on one device (steps ×
#: the device's parties × |z| × 4); a larger stream is drawn step by step
MASK_STREAM_BUDGET = 64 << 20


@jax.tree_util.register_pytree_node_class
class _Masks:
    """An epoch's step masks δ, drawn before its scan (leading axis: the
    step).  The scan takes it where it would take the step keys, and an
    index selects steps as it does on the keys."""

    def __init__(self, delta):
        self.delta = delta

    def __getitem__(self, i):
        return _Masks(self.delta[i])

    def tree_flatten(self):
        return (self.delta,), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


@_scoped("vfb2.sample")
def _sample_indices(key, n, batch, steps):
    """The epoch's (steps, batch) minibatch row indices, drawn as the
    sequential oracles draw them."""
    return _batch_indices(key, n, batch, steps)


# ---------------------------------------------------------------------------
# vertical packing: (n, d) features -> (q, n, dp) padded party blocks
# ---------------------------------------------------------------------------

def party_widths(layout: PartyLayout) -> np.ndarray:
    return np.asarray([hi - lo for lo, hi in layout.bounds], np.int64)


# The pack_* helpers build host (numpy) arrays; ``FusedEngine.place`` puts
# them on the device(s), so a mesh-bound engine ships each party's block
# straight to its own device instead of staging everything on device 0.

def pack_features(x: np.ndarray, layout: PartyLayout) -> np.ndarray:
    """Stack per-party feature blocks, zero-padded to the widest block."""
    n = x.shape[0]
    dp = int(party_widths(layout).max())
    xs = np.zeros((layout.q, n, dp), np.float32)
    for p, (lo, hi) in enumerate(layout.bounds):
        xs[p, :, : hi - lo] = x[:, lo:hi]
    return xs


def pack_vec(v: np.ndarray, layout: PartyLayout) -> np.ndarray:
    """(d,) coordinate vector -> (q, dp) party-stacked, zero-padded."""
    dp = int(party_widths(layout).max())
    out = np.zeros((layout.q, dp), np.float32)
    for p, (lo, hi) in enumerate(layout.bounds):
        out[p, : hi - lo] = np.asarray(v)[lo:hi]
    return out


def unpack_vec(vq, layout: PartyLayout) -> np.ndarray:
    """(q, dp) party-stacked -> (d,) coordinate vector (drops padding)."""
    vq = np.asarray(vq)
    return np.concatenate([vq[p, : hi - lo]
                           for p, (lo, hi) in enumerate(layout.bounds)])


def dominator_onehot(m: int, batch: int) -> jax.Array:
    """(m·B, m) selector: row r of the concatenated minibatch block belongs
    to dominator r // B.  ``ϑ[:, None] * dominator_onehot(m, B)`` is the
    block-diagonal Θ whose columns are the m dominators' ϑ vectors — the
    rank-k kernel's M axis."""
    seg = jnp.repeat(jnp.arange(m), batch)
    return (seg[:, None] == jnp.arange(m)[None, :]).astype(jnp.float32)


def dom_block_cols(cots: jax.Array, m: int) -> jax.Array:
    """(m·B, K) per-row cotangents -> (m·B, m·K) block-diagonal layout:
    dominator j's rows occupy column block j, zeros elsewhere.  The deep
    generalization of the block-diagonal Θ above — each dominator's
    *vector-valued* cotangent block (du, ϑ_z) becomes K adjacent columns
    of one rank-k contraction, so XᵀΘ yields all m per-dominator
    Jacobian-transpose gradients in a single X pass."""
    rows, k = cots.shape
    sel = dominator_onehot(m, rows // m)              # (m·B, m)
    return (sel[:, :, None] * cots[:, None, :]).reshape(rows, m * k)


def _seg_contract(rows: jax.Array, cots: jax.Array, m: int) -> jax.Array:
    """(D, m, K) per-dominator segment contraction: slab j is
    rows_jᵀ · cots_j over dominator j's B rows of the concatenated
    (m·B, ·) blocks — the flop-optimal jnp form of the block-diagonal
    rank-k pass (used where a kernel launch must not be issued, e.g.
    inside the one-invocation pipelined scan bodies)."""
    b = rows.shape[0] // m
    return jnp.einsum("jbd,jbk->djk", rows.reshape(m, b, rows.shape[1]),
                      cots.reshape(m, b, cots.shape[1]))


def pack_deep_params(params, layout: PartyLayout):
    """``DeepVFLParams`` -> party-stacked ``(w1q, b1q, w2q, headq)``.

    ``w1q`` (q, dp, hidden) zero-pads each party's first encoder layer to
    the widest feature block (padded rows start zero and every shipped
    regularizer maps 0 → 0, so they stay zero under the masked updates);
    ``headq`` (q, d_rep) replicates the active parties' head — the SPMD
    stand-in for the dominator broadcasting ϑ_z, and every party's copy
    takes the identical (post-aggregation) head update, so replicas stay
    bitwise equal."""
    q = layout.q
    dp = int(party_widths(layout).max())
    hidden = int(np.asarray(params.enc_w1[0]).shape[1])
    w1q = np.zeros((q, dp, hidden), np.float32)
    for p, (lo, hi) in enumerate(layout.bounds):
        w1q[p, : hi - lo] = np.asarray(params.enc_w1[p])
    b1q = np.stack([np.asarray(b, np.float32) for b in params.enc_b1])
    w2q = np.stack([np.asarray(w, np.float32) for w in params.enc_w2])
    head = np.asarray(params.head, np.float32)
    headq = np.tile(head[None, :], (q, 1))
    return w1q, b1q, w2q, headq


def unpack_deep_params(pq, layout: PartyLayout):
    """Party-stacked deep params -> ``DeepVFLParams`` (drops padding)."""
    from repro.core.deep_vfl import DeepVFLParams

    w1q, b1q, w2q, headq = (np.asarray(a) for a in pq)
    enc_w1 = [jnp.asarray(w1q[p, : hi - lo])
              for p, (lo, hi) in enumerate(layout.bounds)]
    return DeepVFLParams(enc_w1,
                         [jnp.asarray(b) for b in b1q],
                         [jnp.asarray(w) for w in w2q],
                         jnp.asarray(headq[0]))


def pack_mask(layout: PartyLayout, active_only: bool = False) -> np.ndarray:
    """(q, dp) update mask: layout's trainable blocks minus the padding."""
    dp = int(party_widths(layout).max())
    mask = np.zeros((layout.q, dp), np.float32)
    parties = range(layout.m) if active_only else range(layout.q)
    for p in parties:
        lo, hi = layout.bounds[p]
        mask[p, : hi - lo] = 1.0
    return mask


# ---------------------------------------------------------------------------
# jaxpr audits (shared by tests and benchmarks)
# ---------------------------------------------------------------------------
# The walker implementations moved to ``repro.analysis.walkers`` (PR 7's
# static-analysis subsystem); these re-exports keep every existing import
# (tests, benchmarks, notebooks) working unchanged.

from repro.analysis.walkers import (count_primitive,  # noqa: F401,E402
                                    count_primitives,
                                    scan_body_primitive_counts,
                                    sub_jaxprs as _sub_jaxprs)


@dataclasses.dataclass(frozen=True)
class PartyProgram:
    """The per-party program of one fused epoch, recorded at trace time.

    ``fn(local, shared)`` is the function the engine maps over the party
    axis (shard_map or vmap-with-axis-name — identical collective
    semantics).  ``local_avals`` are the per-party slices of the
    party-stacked operands (leading q axis stripped), ``shared_avals``
    the replicated operands.  ``repro.analysis.taint`` retraces ``fn``
    with ``jax.make_jaxpr(..., axis_env=[(axis, q)])`` so cross-party
    collectives stay first-class primitives — the representation the
    leakage taint pass runs on.  By the ``_bind`` call convention the
    first leaf of ``local`` is always the party's private feature block:
    that is the taint source.
    """

    fn: object
    local_avals: object     # pytree of ShapeDtypeStruct (per-party slice)
    shared_avals: object    # pytree of ShapeDtypeStruct (replicated)
    axis: str
    q: int
    # Hierarchical (PartyMesh) binding: the full named-axis environment
    # of the per-party program, outermost first — e.g.
    # (("model", slots), ("party", pps), ("data", ddp)) — and the subset
    # of names that span the *logical* party axis.  Empty tuples mean
    # the flat layout: axis_env [(axis, q)], party axes (axis,).
    axes: Tuple = ()
    party_axes: Tuple = ()

    def trace(self):
        """Per-party closed jaxpr with every named axis abstractly bound."""
        env = list(self.axes) if self.axes else [(self.axis, self.q)]
        return jax.make_jaxpr(self.fn, axis_env=env)(
            self.local_avals, self.shared_avals)

    @property
    def boundary_axes(self) -> Tuple:
        """Names of the axes that cross party boundaries (taint target)."""
        return tuple(self.party_axes) if self.party_axes else (self.axis,)

    @property
    def n_local(self) -> int:
        """Number of flattened ``local`` leaves (they lead the trace's
        invars; leaf 0 is the party-private feature block)."""
        return len(jax.tree_util.tree_leaves(self.local_avals))


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class FusedEngine:
    """Holds the packed vertical data and the per-algorithm jitted epochs.

    All ``*_epoch`` methods take and return the **party-stacked** iterate
    ``wq`` of shape (q, dp); use :meth:`pack_w`/:meth:`unpack_w` at the
    boundary.  Each call is exactly one device dispatch.
    """

    def __init__(self, problem: Problem, x, y, layout: PartyLayout,
                 cfg: EngineConfig = EngineConfig(),
                 mesh=None, active_only: bool = False):
        if cfg.secure not in ("off", "two_tree", "ring"):
            raise ValueError(f"unknown secure mode {cfg.secure!r} "
                             "(expected 'off', 'two_tree' or 'ring')")
        self.problem = problem
        self.layout = layout
        self.cfg = cfg
        self.q = layout.q
        self.n = int(np.asarray(x).shape[0])
        # ``mesh`` is either a plain jax Mesh (flat layout: one party per
        # slot, the historical contract) or a PartyMesh decoupling the
        # logical party axis from the physical one (q = slots × pps, with
        # pps packed parties vmapped inside each slot and an optional
        # sample-parallel "data" dimension).
        if isinstance(mesh, PartyMesh):
            if mesh.q != layout.q:
                raise ValueError(
                    f"PartyMesh.q={mesh.q} != layout.q={layout.q}")
            if mesh.axis != cfg.axis:
                raise ValueError(
                    f"PartyMesh.axis={mesh.axis!r} != EngineConfig.axis="
                    f"{cfg.axis!r}")
            self.pmesh = mesh
            self.mesh = mesh.mesh
        else:
            if mesh is not None:
                # A supplied mesh states SPMD intent; a silent vmap
                # fallback would report "multi-chip" numbers that ran on
                # one device.
                if (cfg.axis not in mesh.axis_names
                        or mesh.shape[cfg.axis] != layout.q):
                    raise ValueError(
                        f"mesh must carry a {cfg.axis!r} axis of size q="
                        f"{layout.q} to host one party per device; got "
                        f"axes {dict(mesh.shape)}. Pass mesh=None for the "
                        "single-device vmap emulation, or a PartyMesh to "
                        "pack multiple parties per slot.")
            self.pmesh = None
            self.mesh = mesh
        self._use_shard_map = self.mesh is not None
        with tracing.span("vfb2.engine.build", q=layout.q, rows=self.n):
            with tracing.span("vfb2.engine.pack"):
                xs = pack_features(np.asarray(x), layout)      # (q,n,dp)
                maskq = pack_mask(layout, active_only)
                # (q,) per-party trainability flag for the deep epochs'
                # non-feature parameters (b1/w2 have no coordinate rows
                # for maskq to act on): active_only freezes passive
                # parties' encoders, the AFSVRG-VP analogue (deep_vfl's
                # freeze_passive).
                trainq = np.asarray(
                    [1.0 if (not active_only or p < layout.m) else 0.0
                     for p in range(layout.q)], np.float32)
            with tracing.span("vfb2.engine.place"):
                # ends when the copies have arrived, not when they are
                # queued, so the span times the transfer
                self.xs = self.place(xs)
                self.y = self.place(np.asarray(y, np.float32), party=False)
                self.maskq = self.place(maskq)
                self.trainq = self.place(trainq)
                jax.block_until_ready((self.xs, self.y, self.maskq,
                                       self.trainq))
        self.dp = int(self.xs.shape[2])
        pm = self.pmesh
        self._slots = pm.slots if pm is not None else layout.q
        self._pps = pm.parties_per_slot if pm is not None else 1
        self._ddp = pm.data_shards if pm is not None else 1
        self._party_axes = ((cfg.axis, pm.party_axis)
                            if pm is not None and pm.packed
                            else (cfg.axis,))
        self._data_axis = (pm.data_axis
                           if pm is not None and pm.data_shards > 1
                           else None)
        # full named-axis environment of one per-party program (taint
        # retrace + PartyProgram recording), outermost first
        env = [(cfg.axis, self._slots)]
        if self._pps > 1:
            env.append((pm.party_axis, self._pps))
        if self._data_axis is not None:
            env.append((self._data_axis, self._ddp))
        self._axis_env = tuple(env)
        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and cfg.interpret:
            # The Pallas interpreter on a chip runs the kernel body op by
            # op: correct numbers, none of the chip's kernel behaviour.
            raise ValueError("interpret=True is refused on a TPU backend; "
                             "leave EngineConfig.interpret unset there")
        kern = cfg.use_kernel
        self._kernel = on_tpu if kern is None else kern
        interp = cfg.interpret
        self._interpret = (not on_tpu) if interp is None else interp
        self._jitted = {}
        # epoch name -> PartyProgram, recorded by _bind at trace time for
        # the static-analysis subsystem (repro.analysis)
        self._party_programs = {}
        self._building = None

    # -- party-axis binding --------------------------------------------------

    def _bind(self, party_fn):
        """Map ``party_fn(local, shared)`` over the logical party axis.

        ``local`` is a pytree of party-stacked arrays (leading q axis),
        ``shared`` a replicated pytree.  Flat layout: shard_map on a
        q-wide mesh axis, vmap-with-axis-name otherwise — identical
        collective semantics.  PartyMesh layout: the q leading entries
        are viewed as (slots, parties_per_slot), the inner factor is
        vmapped (named ``party_axis``) *inside* each slot, the outer
        factor is the physical slot mapping, and an optional sample-
        parallel ``data`` axis is bound around it (a second mesh
        dimension under shard_map; a broadcast vmap in emulation, whose
        replicated outputs are collapsed by taking index 0 — sliced
        epochs re-synchronize shards via the data-axis psum, so outputs
        are shard-invariant).  ``party_fn`` itself is layout-blind: it
        sees one logical party either way.
        """
        tm = jax.tree_util.tree_map
        slots, pps, ddp = self._slots, self._pps, self._ddp
        # what the party function stages outside the helpers' own scopes
        # (ϑ, the regulariser, the masked update) is ``vfb2.party``
        fn = _scoped("vfb2.party")(party_fn)
        if pps > 1:
            fn = jax.vmap(fn, in_axes=(0, None), out_axes=0,
                          axis_name=self.pmesh.party_axis)
        if self._use_shard_map:
            def island(local, shared):
                sq = tm(lambda a: a[0], local)
                out = fn(sq, shared)
                return tm(lambda o: o[None], out)
            core = shard_map(island, mesh=self.mesh,
                             in_specs=(P(self.cfg.axis), P()),
                             out_specs=P(self.cfg.axis), check_vma=False)
        else:
            core = jax.vmap(fn, in_axes=(0, None), out_axes=0,
                            axis_name=self.cfg.axis)
            if ddp > 1:
                slot_core = core

                def core(local, shared):
                    dmapped = jax.vmap(slot_core, in_axes=(None, None),
                                       out_axes=0,
                                       axis_name=self._data_axis,
                                       axis_size=ddp)
                    return tm(lambda o: o[0], dmapped(local, shared))
        if pps > 1:
            packed_core = core

            def core(local, shared):
                l2 = tm(lambda a: a.reshape((slots, pps) + a.shape[1:]),
                        local)
                out = packed_core(l2, shared)
                return tm(lambda o: o.reshape((-1,) + o.shape[2:]), out)
        mapped = core
        name = self._building
        if name is None:
            return mapped

        def recording(local, shared):
            # Runs at trace time of the jitted epoch (operands may be
            # tracers): snapshot the per-party program + operand avals so
            # repro.analysis can retrace the party function with the axis
            # abstractly bound.  Convention: local leaf 0 is the party's
            # private feature block (the taint source).
            self._party_programs[name] = PartyProgram(
                fn=party_fn,
                local_avals=jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    local),
                shared_avals=jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    shared),
                axis=self.cfg.axis, q=self.q,
                axes=self._axis_env, party_axes=self._party_axes)
            return mapped(local, shared)

        return recording

    # -- X-block contractions (kernel-routed or jnp) -------------------------

    def _route_kernel(self, rows: int) -> bool:
        return self._kernel and rows <= self.cfg.kernel_max_rows

    @_scoped("vfb2.contract")
    def _fwd(self, xb, wcols):
        """(B, dp) @ (dp, M) -> (B, M) forward partial products."""
        if self._route_kernel(xb.shape[0]):
            z, _ = _vg.vfl_grad(
                xb, wcols, None, mode="forward", interpret=self._interpret,
                block_b=self.cfg.block_b, block_d=self.cfg.block_d)
            return z
        return xb @ wcols

    @_scoped("vfb2.contract")
    def _bwd(self, xb, thcols, denom: int):
        """(dp, M) BUM data gradients XᵀΘ/denom (reg term added by caller).

        The kernel path passes ``w=None``: backward-only invocations stream
        no dead weight block into VMEM (M>1 hot-path routing)."""
        if self._route_kernel(xb.shape[0]):
            _, g = _vg.vfl_grad(
                xb, None, thcols, mode="backward", denom=denom,
                interpret=self._interpret,
                block_b=self.cfg.block_b, block_d=self.cfg.block_d)
            return g
        return xb.T @ thcols / denom

    @_scoped("vfb2.contract")
    def _bwd_doms(self, xb, theta, m: int, denom: int):
        """(dp, m) per-dominator BUM data gradients from the concatenated
        (m·B, dp) minibatch block: column j = X_{b_j}ᵀϑ_j / denom.

        Kernel path: one M = m rank-k pass with the block-diagonal Θ (the
        X block is read from HBM once for all m dominators; zero columns
        cost nothing on the memory-bound MXU pass).  jnp path: the block
        structure is contracted directly (batched segment matmul), which
        is the flop-optimal form on CPU.  Identical columns either way.
        """
        if self._route_kernel(xb.shape[0]):
            thmat = theta[:, None] * dominator_onehot(m, xb.shape[0] // m)
            return self._bwd(xb, thmat, denom)
        b = xb.shape[0] // m
        return jnp.einsum("jbd,jb->dj", xb.reshape(m, b, xb.shape[1]),
                          theta.reshape(m, b)) / denom

    @_scoped("vfb2.contract")
    def _bwd_doms_wide(self, rows, cots, m: int, denom: int):
        """(D, m, K) per-dominator Jacobian-transpose blocks from the
        concatenated (m·B, D) row block and (m·B, K) vector cotangents:
        slab j = rows_jᵀ·cots_j / denom — the vector-valued (deep)
        generalization of :meth:`_bwd_doms`.

        Kernel path: ONE rank-k pass whose M axis is the m dominators'
        K-column blocks laid block-diagonally (`dom_block_cols`; the row
        block streams from HBM once for all m dominators).  jnp path: the
        flop-optimal batched segment einsum.  Identical slabs either way.
        """
        if self._route_kernel(rows.shape[0]):
            g = self._bwd(rows, dom_block_cols(cots, m), denom)
            return g.reshape(rows.shape[1], m, cots.shape[1])
        return _seg_contract(rows, cots, m) / denom

    @_scoped("vfb2.contract")
    def _pipe(self, xb_bwd, xb_fwd, wcols, thcols, denom: int):
        """The pipelined step's single contraction: the BUM application of
        round t (``xb_bwd`` against Θ = ``thcols``) and the forward partial
        products of round t+1 (``xb_fwd`` against W = ``wcols``) ride ONE
        split-batch fused kernel invocation — the w/ϑ tiles stream into
        VMEM once and kernel launches per step halve.  Returns
        ``(z_next (B_f, Mw), g (dp, Mθ))``; the jnp fallback contracts the
        two blocks directly (flop-optimal on CPU), identical numbers.
        """
        if self._route_kernel(xb_bwd.shape[0] + xb_fwd.shape[0]):
            xcat = jnp.concatenate([xb_bwd, xb_fwd], axis=0)
            return _vg.vfl_grad(
                xcat, wcols, thcols, mode="fused", denom=denom,
                split=xb_bwd.shape[0], interpret=self._interpret,
                block_b=self.cfg.block_b, block_d=self.cfg.block_d)
        return xb_fwd @ wcols, xb_bwd.T @ thcols / denom

    @_scoped("vfb2.contract")
    def _pipe_doms_wide(self, xb_bwd, xb_fwd, wcols, cots, m: int,
                        denom: int):
        """Pipelined per-dominator *vector* contraction: backward(t)'s m
        K-column Jacobian-cotangent slabs next to forward(t+1)'s Mw
        weight columns.  Kernel path: one split-batch invocation with the
        Mθ = m·K block-diagonal layout (`dom_block_cols`); jnp path: the
        forward matmul plus the flop-optimal segment einsum — the
        mostly-zero dense block matrix is never materialized (same
        policy as :meth:`_bwd_doms_wide` / :meth:`_pipe_doms`).  Returns
        ``(z_next (B_f, Mw), g (dp, m, K))``."""
        if self._route_kernel(xb_bwd.shape[0] + xb_fwd.shape[0]):
            z, g = self._pipe(xb_bwd, xb_fwd, wcols,
                              dom_block_cols(cots, m), denom)
            return z, g.reshape(xb_bwd.shape[1], m, cots.shape[1])
        return xb_fwd @ wcols, _seg_contract(xb_bwd, cots, m) / denom

    @_scoped("vfb2.contract")
    def _pipe_doms(self, xb_bwd, xb_fwd, wp, theta, m: int, denom: int):
        """Pipelined multi-dominator contraction: backward(t)'s m
        per-dominator columns (block-diagonal Θ, as in :meth:`_bwd_doms`)
        next to forward(t+1)'s single iterate column in one invocation —
        the split-batch form's side column counts differ (Mw=1, Mθ=m).
        Returns ``(z_next (m·B,), gg (dp, m))``."""
        if self._route_kernel(xb_bwd.shape[0] + xb_fwd.shape[0]):
            thmat = theta[:, None] * dominator_onehot(m, xb_bwd.shape[0] // m)
            z, gg = self._pipe(xb_bwd, xb_fwd, wp[:, None], thmat, denom)
            return z[:, 0], gg
        b = xb_bwd.shape[0] // m
        gg = jnp.einsum("jbd,jb->dj", xb_bwd.reshape(m, b, xb_bwd.shape[1]),
                        theta.reshape(m, b)) / denom
        return xb_fwd @ wp, gg

    @_scoped("vfb2.aggregate")
    def _masks(self, mkeys, shape, sliced: bool = False):
        """What an epoch's scan takes for its steps' masks: the whole
        stream of step masks δ for partials of ``shape``, drawn here in
        one batched op from the step keys ``mkeys`` (the values each step
        would draw itself), or the keys where the step must draw: secure
        off, the packed layout (two salted levels) or a stream over
        :data:`MASK_STREAM_BUDGET`.  ``sliced``: the epoch aggregates its
        data shard's slice, so each key first folds in the shard
        (:meth:`_dkey`)."""
        cfg = self.cfg
        if sliced:
            mkeys = jax.vmap(self._dkey)(mkeys)
        local = 1 if self._use_shard_map else self._slots * self._ddp
        nbytes = mkeys.shape[0] * local * int(np.prod(shape)) * 4
        if (cfg.secure == "off" or self._pps > 1
                or nbytes > MASK_STREAM_BUDGET):
            return mkeys
        # drawn flat, the values a draw of ``shape`` gives in row-major
        # order: a narrow trailing axis (SVRG's 2 columns) would pad to the
        # chip's 128 lanes in every step's slice of the stream
        size = (int(np.prod(shape)),)
        draw = ring_mask if cfg.secure == "ring" else two_tree_mask
        return _Masks(jax.vmap(
            lambda k: draw(k, cfg.axis, size, cfg.mask_scale))(mkeys))

    @_scoped("vfb2.aggregate")
    def _agg(self, z, kt):
        """Masked secure aggregation of partials over the party axis.

        ``kt`` is the step's mask key, or its pre-drawn mask from
        :meth:`_masks`.  Flat layout: one reduction over ``cfg.axis``.
        PartyMesh packed layout: the hierarchical two-level form —
        intra-slot reduce over the inner vmapped party axis, then the
        configured two_tree/ring lowering across slots, with every mask
        stream ``fold_in``-distinct per *logical* party (see
        ``secure_psum_hier``).  Each masked aggregation is counted at
        trace time as ``vfb2.masks.predrawn`` or ``.per_step``.
        """
        cfg = self.cfg
        if isinstance(kt, _Masks):
            tracing.count("vfb2.masks.predrawn")
            delta = kt.delta.reshape(z.shape)
            if cfg.secure == "ring":
                return ring_apply(z, delta, cfg.axis)
            return two_tree_apply(z, delta, cfg.axis,
                                  schedule_faithful=cfg.schedule_faithful,
                                  q=self.q)
        if cfg.secure != "off":
            tracing.count("vfb2.masks.per_step")
        if self._pps > 1:
            if cfg.secure == "off":
                return jax.lax.psum(z, self._party_axes)
            return secure_psum_hier(
                z, cfg.axis, self.pmesh.party_axis, kt, mode=cfg.secure,
                mask_scale=cfg.mask_scale,
                schedule_faithful=cfg.schedule_faithful,
                slots=self._slots, pps=self._pps)
        if cfg.secure == "off":
            return jax.lax.psum(z, cfg.axis)
        if cfg.secure == "ring":
            return secure_psum_ring(z, cfg.axis, kt,
                                    mask_scale=cfg.mask_scale)
        return secure_psum(z, cfg.axis, kt, mask_scale=cfg.mask_scale,
                           schedule_faithful=cfg.schedule_faithful,
                           q=self.q)

    @_scoped("vfb2.aggregate")
    def _agg_members(self, z, kt, alive):
        """Survivor-aware masked aggregation (the faulted epochs' Alg. 1).

        ``alive`` is this party's liveness flag for the step (0.0/1.0);
        the collective re-keys the per-step masks from the gathered
        alive-set so they cancel exactly over the survivors.  Two-tree
        mode always lowers to the masked-psum form here: the
        ``schedule_faithful`` ppermute replay of a fixed tree schedule is
        not membership-safe (a crashed party is a hole in the permutation
        sequence), while mask cancellation is schedule-independent.
        Packed layout: the hierarchical membership form, whose alive-set
        fingerprint is gathered over BOTH axes and folded into the key
        above both levels (``secure_psum_hier_members``).
        """
        cfg = self.cfg
        if cfg.secure != "off":
            tracing.count("vfb2.masks.per_step")
        if self._pps > 1:
            if cfg.secure == "off":
                return jax.lax.psum(alive * z, self._party_axes)
            return secure_psum_hier_members(
                z, cfg.axis, self.pmesh.party_axis, kt, alive,
                mode=cfg.secure, mask_scale=cfg.mask_scale)
        if cfg.secure == "off":
            return jax.lax.psum(alive * z, cfg.axis)
        if cfg.secure == "ring":
            return secure_psum_ring_members(z, cfg.axis, kt, alive,
                                            mask_scale=cfg.mask_scale)
        return secure_psum_members(z, cfg.axis, kt, alive,
                                   mask_scale=cfg.mask_scale)

    # -- data (sample-parallel) axis helpers ---------------------------------
    # Identity when no data axis is bound, so every epoch body can call
    # them unconditionally.  Data shards of one party share that party's
    # trust domain (see PartyMesh), so the gradient psum is plain.

    def _dslice(self, ib):
        """This data shard's disjoint slice of a (B,) minibatch index
        vector (identity without a data axis).  B must divide evenly."""
        if self._data_axis is None:
            return ib
        if ib.shape[0] % self._ddp != 0:
            raise ValueError(
                f"batch={ib.shape[0]} must divide data_shards={self._ddp}")
        bs = ib.shape[0] // self._ddp
        start = jax.lax.axis_index(self._data_axis) * bs
        return jax.lax.dynamic_slice_in_dim(ib, start, bs)

    def _dsum(self, g):
        """Sum a per-shard partial gradient over the data axis."""
        if self._data_axis is None:
            return g
        return jax.lax.psum(g, self._data_axis)

    def _dkey(self, kt):
        """Fold the data-shard index into a mask key: sliced epochs
        aggregate *different* sample slices per shard, so reusing one
        mask stream across shards would let a party-axis observer
        difference two shards' masked partials.  Replicated epochs skip
        this (identical plaintexts keep bitwise-replicated outputs)."""
        if self._data_axis is None:
            return kt
        return jax.random.fold_in(
            kt, 0xda7a + jax.lax.axis_index(self._data_axis))

    @_scoped("vfb2.sample")
    def _keys(self, key, steps: int):
        """Per-step mask keys, derived off the sampling key's stream."""
        return jax.random.split(jax.random.fold_in(key, 0x5ec), steps)

    @_scoped("vfb2.gather")
    def _rows(self, xp, ib):
        """The minibatch's rows of this party's feature block."""
        return xp[ib]

    def _epoch(self, name, builder):
        """Build-and-cache the jitted epoch function for this instance."""
        if name not in self._jitted:
            self._building = name
            try:
                self._jitted[name] = F32Program(builder(), name)
            finally:
                self._building = None
        return self._jitted[name]

    def party_program(self, name: str) -> "PartyProgram":
        """The recorded per-party program of a built epoch (see
        :class:`PartyProgram`; the epoch must have been called — or at
        least traced, e.g. under ``jax.make_jaxpr`` — once)."""
        if name not in self._party_programs:
            raise KeyError(
                f"no party program recorded for {name!r}; trace the epoch "
                f"first (built: {sorted(self._party_programs)})")
        return self._party_programs[name]

    def _donate(self, *argnames):
        """``donate_argnames`` for an epoch jit, honoring ``cfg.donate``."""
        return argnames if self.cfg.donate else ()

    # -- SGD (Algorithms 2/3) ------------------------------------------------

    def sgd_epoch(self, wq, lr, key, batch: int, steps: int):
        prob, cfg = self.problem, self.cfg

        def build():
            def party(local, shared):
                xp, wp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, (idx.shape[1] // self._ddp,),
                                    sliced=True)

                def body(wp, inp):
                    ib, kt = inp
                    # each data shard forwards/aggregates its own slice
                    # of the minibatch; the per-shard partial gradients
                    # (denominated by the FULL batch) are psum'd back
                    # over the data axis — identity without one
                    ibs = self._dslice(ib)
                    xb = self._rows(xp, ibs)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)
                    theta = prob.theta(agg, y[ibs])
                    g = self._dsum(
                        self._bwd(xb, theta[:, None], ib.shape[0]))[:, 0] \
                        + prob.lam * prob.reg_grad(wp)
                    return wp - lr * maskp * g, None

                wp, _ = jax.lax.scan(body, wp, (idx, masks))
                return wp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq"))
            def epoch(xs, wq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("sgd", build)(self.xs, wq, self.maskq, self.y,
                                         lr, key, batch, steps)

    # -- SVRG (Algorithms 4/5): rank-2 batched steps -------------------------

    def full_gradient(self, wq, key):
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp = local
                y, kt = shared
                z = self._fwd(xp, wp[:, None])[:, 0]
                agg = self._agg(z, kt)
                theta = prob.theta(agg, y)
                return self._bwd(xp, theta[:, None], y.shape[0])[:, 0] \
                    + prob.lam * prob.reg_grad(wp)

            mapped = self._bind(party)

            @jax.jit
            def full(xs, wq, y, key):
                return mapped((xs, wq), (y, jax.random.fold_in(key, 0xf)))

            return full

        return self._epoch("full_grad", build)(self.xs, wq, self.y, key)

    def svrg_epoch(self, wq, wq_snap, muq, lr, key, batch: int, steps: int):
        """Inner loop of VFB²-SVRG; the current iterate and the snapshot
        ride the same rank-2 kernel pass (M = 2)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, wsp, mup, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, (idx.shape[1] // self._ddp, 2),
                                    sliced=True)

                def body(wp, inp):
                    ib, kt = inp
                    ibs = self._dslice(ib)
                    xb = self._rows(xp, ibs)
                    z = self._fwd(xb, jnp.stack([wp, wsp], axis=1))  # (B, 2)
                    agg = self._agg(z, kt)
                    th1 = prob.theta(agg[:, 0], y[ibs])
                    th0 = prob.theta(agg[:, 1], y[ibs])
                    gg = self._dsum(
                        self._bwd(xb, jnp.stack([th1, th0], axis=1),
                                  ib.shape[0]))                      # (dp, 2)
                    g1 = gg[:, 0] + prob.lam * prob.reg_grad(wp)
                    g0 = gg[:, 1] + prob.lam * prob.reg_grad(wsp)
                    return wp - lr * maskp * (g1 - g0 + mup), None

                wp, _ = jax.lax.scan(body, wp, (idx, masks))
                return wp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, wq_snap, muq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("svrg", build)(self.xs, wq, wq_snap, muq,
                                          self.maskq, self.y, lr, key,
                                          batch, steps)

    # -- SAGA (Algorithms 6/7) -----------------------------------------------

    def saga_init(self, wq, key):
        """ϑ̃ table + per-party running average (Alg. 6 step 2 init pass)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp = local
                y, kt = shared
                z = self._fwd(xp, wp[:, None])[:, 0]
                agg = self._agg(z, kt)
                theta = prob.theta(agg, y)
                avgp = self._bwd(xp, theta[:, None], y.shape[0])[:, 0]
                return theta, avgp

            mapped = self._bind(party)

            @jax.jit
            def init(xs, wq, y, key):
                tab, avgq = mapped((xs, wq), (y, jax.random.fold_in(key, 0xa)))
                return tab, avgq

            return init

        return self._epoch("saga_init", build)(self.xs, wq, self.y, key)

    def saga_epoch(self, wq, tabq, avgq, lr, key, batch: int, steps: int):
        """``tabq`` is the replicated per-party copy of the ϑ̃ table
        ((q, n); every party maintains the same values)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, tab, avgp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                n = y.shape[0]

                def body(carry, inp):
                    wp, tab, avgp = carry
                    ib, kt = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)
                    th_new = prob.theta(agg, y[ib])
                    th_old = tab[ib]
                    dth = (th_new - th_old)[:, None]
                    # one X-block pass for XᵀΔϑ; the 1/B and 1/n scalings
                    # are scalar (the kernel-path HBM read is the cost)
                    raw = self._bwd(xb, dth, 1)[:, 0]
                    v = raw / ib.shape[0] + avgp \
                        + prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * v
                    avgp = avgp + raw / n
                    tab = tab.at[ib].set(th_new)
                    return (wp, tab, avgp), None

                (wp, tab, avgp), _ = jax.lax.scan(body, (wp, tab, avgp),
                                                  (idx, masks))
                return wp, tab, avgp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "tabq",
                                                            "avgq"))
            def epoch(xs, wq, tabq, avgq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, tabq, avgq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("saga", build)(self.xs, wq, tabq, avgq,
                                          self.maskq, self.y, lr, key,
                                          batch, steps)

    # -- multi-dominator epochs (m active parties per step) -------------------

    def multi_sgd_epoch(self, wq, lr, key, batch: int, steps: int):
        """VFB²-SGD with all m = layout.m dominators launching concurrent
        backward updates per step: one forward over the concatenated
        (m·B, dp) minibatch block, one secure aggregation of all m
        partial-product sets, one M = m rank-k backward whose columns are
        the m BUM gradients (see module docstring).  Pinned against
        ``algorithms.multi_sgd_epoch``."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])

                def body(wp, inp):
                    ibf, kt = inp                 # ibf: (m·B,) concatenated
                    b = ibf.shape[0] // m
                    xb = self._rows(xp, ibf)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)        # all m partials, one pass
                    theta = prob.theta(agg, y[ibf])
                    gg = self._bwd_doms(xb, theta, m, b)  # (dp, m) BUM set
                    g = gg.sum(axis=1) + m * prob.lam * prob.reg_grad(wp)
                    return wp - lr * maskp * g, None

                wp, _ = jax.lax.scan(body, wp, (idx, masks))
                return wp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq"))
            def epoch(xs, wq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_sgd", build)(self.xs, wq, self.maskq,
                                               self.y, lr, key, batch,
                                               steps)

    def multi_svrg_epoch(self, wq, wq_snap, muq, lr, key, batch: int,
                         steps: int):
        """Multi-dominator VFB²-SVRG inner loop: the m dominators'
        concatenated minibatches ride one M = 2 kernel pass (current
        iterate + snapshot), so each step is still a single forward and a
        single backward contraction."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, wsp, mup, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:] + (2,))

                def body(wp, inp):
                    ibf, kt = inp
                    b = ibf.shape[0] // m
                    xb = self._rows(xp, ibf)
                    z = self._fwd(xb, jnp.stack([wp, wsp], axis=1))
                    agg = self._agg(z, kt)
                    th1 = prob.theta(agg[:, 0], y[ibf])
                    th0 = prob.theta(agg[:, 1], y[ibf])
                    gg = self._bwd(xb, jnp.stack([th1, th0], axis=1), b)
                    v = gg[:, 0] - gg[:, 1] + m * (
                        prob.lam * (prob.reg_grad(wp) - prob.reg_grad(wsp))
                        + mup)
                    return wp - lr * maskp * v, None

                wp, _ = jax.lax.scan(body, wp, (idx, masks))
                return wp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, wq_snap, muq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_svrg", build)(self.xs, wq, wq_snap, muq,
                                                self.maskq, self.y, lr,
                                                key, batch, steps)

    def multi_saga_epoch(self, wq, tabq, avgq, lr, key, batch: int,
                         steps: int):
        """Multi-dominator VFB²-SAGA: the m dominators' Δϑ vectors occupy
        the M = m columns of one rank-k backward; the replicated ϑ̃ table
        takes all m writes per step (last write wins on duplicates, as in
        the sequential oracle and the async execution)."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, tab, avgp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                n = y.shape[0]

                def body(carry, inp):
                    wp, tab, avgp = carry
                    ibf, kt = inp
                    b = ibf.shape[0] // m
                    xb = self._rows(xp, ibf)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)
                    th_new = prob.theta(agg, y[ibf])
                    dth = th_new - tab[ibf]
                    raws = self._bwd_doms(xb, dth, m, 1)  # (dp, m)
                    rsum = raws.sum(axis=1)
                    v = rsum / b + m * avgp \
                        + m * prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * v
                    avgp = avgp + rsum / n
                    tab = tab.at[ibf].set(th_new)
                    return (wp, tab, avgp), None

                (wp, tab, avgp), _ = jax.lax.scan(body, (wp, tab, avgp),
                                                  (idx, masks))
                return wp, tab, avgp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "tabq",
                                                            "avgq"))
            def epoch(xs, wq, tabq, avgq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, tabq, avgq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_saga", build)(self.xs, wq, tabq, avgq,
                                                self.maskq, self.y, lr,
                                                key, batch, steps)

    # -- bounded-delay (τ) emulation (core.staleness, fused) ------------------

    def delayed_sgd_epoch(self, wq, bufq, t0, delays_q, lr, key,
                          batch: int, steps: int, tau: int):
        """Stale-gradient VFB²-SGD: party ℓ applies, at step t, the BUM
        gradient of step t − d_ℓ from a per-party ring buffer carried
        through the scan — ``core.staleness`` semantics on the fused path.

        ``bufq``: (q, τ+1, dp) gradient ring buffers; ``delays_q``: (q,)
        int32 per-party delays; ``t0``: scalar int32 global step counter.
        """
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, buf, delay, maskp = local
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:])

                def body(carry, inp):
                    wp, buf, t = carry
                    ib, kt = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)
                    theta = prob.theta(agg, y[ib])
                    g = self._bwd(xb, theta[:, None], ib.shape[0])[:, 0] \
                        + prob.lam * prob.reg_grad(wp)
                    slot = t % (tau + 1)
                    buf = jax.lax.dynamic_update_index_in_dim(buf, g, slot, 0)
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    # the same update mask as the fresh path: frozen
                    # (passive) blocks must stay frozen under staleness too
                    return (wp - lr * maskp * stale, buf, t + 1), None

                (wp, buf, _), _ = jax.lax.scan(body, (wp, buf, t0),
                                               (idx, masks))
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_q, maskq, y, lr, key, t0, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, bufq, delays_q, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"delayed{tau}", build)(
            self.xs, wq, bufq, delays_q, self.maskq, self.y, lr, key, t0,
            batch, steps)
        return wq, bufq, t0 + steps

    # -- faulted epochs (elastic membership; core.faults traces) --------------

    def faulted_sgd_epoch(self, wq, bufq, t0, delays_q, fwdq, bwdq, extraq,
                          lr, key, batch: int, steps: int, tau: int):
        """Fault-trace VFB²-SGD epoch: the compiled trace's per-step
        membership masks ride the scan.  ``fwdq``/``bwdq``: (q, steps)
        0/1 liveness (forward contribution / backward application);
        ``extraq``: (q, steps) int32 straggle delay added to the party's
        base delay.  A party with ``bwd = 0`` writes nothing into its
        ring and applies nothing — its block freezes; on rejoin the ring
        replays its last pre-crash gradients (crash = unbounded delay).
        Pinned against ``faults.faulted_sgd_epoch`` at 1e-5."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, buf, delay, fwd_p, bwd_p, extra_p, maskp = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    wp, buf, t = carry
                    ib, kt, fl, bl, ex = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg_members(z, kt, fl)
                    theta = prob.theta(agg, y[ib])
                    g = self._bwd(xb, theta[:, None], ib.shape[0])[:, 0] \
                        + prob.lam * prob.reg_grad(wp)
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, g, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    return (wp - lr * bl * maskp * stale, buf, t + 1), None

                (wp, buf, _), _ = jax.lax.scan(
                    body, (wp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p))
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_q, fwdq, bwdq, extraq, maskq,
                      y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, bufq, delays_q, fwdq, bwdq, extraq,
                               maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"faulted_sgd{tau}", build)(
            self.xs, wq, bufq, delays_q, fwdq, bwdq, extraq, self.maskq,
            self.y, lr, key, t0, batch, steps)
        return wq, bufq, t0 + steps

    def faulted_svrg_epoch(self, wq, wq_snap, muq, bufq, t0, delays_q,
                           fwdq, bwdq, extraq, lr, key, batch: int,
                           steps: int, tau: int):
        """Fault-trace VFB²-SVRG inner loop: both forward columns (iterate
        + snapshot) are survivor aggregates, and the variance-reduced
        direction v = g(w) − g(w̃) + μ̃ enters the fault-gated ring and
        ages like the SGD gradient.  μ̃/snapshot refreshes are
        epoch-boundary barrier rounds over full membership (the runners'
        responsibility).  Pinned against ``faults.faulted_svrg_epoch``."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, wp, wsp, mup, buf, delay, fwd_p, bwd_p, extra_p,
                 maskp) = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    wp, buf, t = carry
                    ib, kt, fl, bl, ex = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, jnp.stack([wp, wsp], axis=1))
                    agg = self._agg_members(z, kt, fl)
                    th1 = prob.theta(agg[:, 0], y[ib])
                    th0 = prob.theta(agg[:, 1], y[ib])
                    gg = self._bwd(xb, jnp.stack([th1, th0], axis=1),
                                   ib.shape[0])
                    g1 = gg[:, 0] + prob.lam * prob.reg_grad(wp)
                    g0 = gg[:, 1] + prob.lam * prob.reg_grad(wsp)
                    v = g1 - g0 + mup
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, v, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    return (wp - lr * bl * maskp * stale, buf, t + 1), None

                (wp, buf, _), _ = jax.lax.scan(
                    body, (wp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p))
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, bufq, delays_q, fwdq, bwdq,
                      extraq, maskq, y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, wq_snap, muq, bufq, delays_q, fwdq,
                               bwdq, extraq, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"faulted_svrg{tau}", build)(
            self.xs, wq, wq_snap, muq, bufq, delays_q, fwdq, bwdq, extraq,
            self.maskq, self.y, lr, key, t0, batch, steps)
        return wq, bufq, t0 + steps

    def faulted_saga_epoch(self, wq, tabq, avgq, bufq, t0, delays_q, fwdq,
                           bwdq, extraq, lr, key, batch: int, steps: int,
                           tau: int):
        """Fault-trace VFB²-SAGA.  State freshness split: the replicated
        ϑ̃ table is dominator-held protocol state and stays synchronized
        on every island at every step (a rejoiner re-syncs it from the
        dominator; SPMD replication realizes that as keeping it hot); the
        per-party running average is party-PRIVATE and freezes while the
        party is out — the documented non-recoverable bias of an outage.
        Pinned against ``faults.faulted_saga_epoch``."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, wp, tab, avgp, buf, delay, fwd_p, bwd_p, extra_p,
                 maskp) = local
                y, lr, idx, mkeys, t0 = shared
                n = y.shape[0]

                def body(carry, inp):
                    wp, tab, avgp, buf, t = carry
                    ib, kt, fl, bl, ex = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg_members(z, kt, fl)
                    th_new = prob.theta(agg, y[ib])
                    dth = (th_new - tab[ib])[:, None]
                    raw = self._bwd(xb, dth, 1)[:, 0]
                    v = raw / ib.shape[0] + avgp \
                        + prob.lam * prob.reg_grad(wp)
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, v, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    wp = wp - lr * bl * maskp * stale
                    avgp = avgp + bl * raw / n      # private: frozen out
                    tab = tab.at[ib].set(th_new)    # shared: always fresh
                    return (wp, tab, avgp, buf, t + 1), None

                (wp, tab, avgp, buf, _), _ = jax.lax.scan(
                    body, (wp, tab, avgp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p))
                return wp, tab, avgp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate(
                                   "wq", "tabq", "avgq", "bufq"))
            def epoch(xs, wq, tabq, avgq, bufq, delays_q, fwdq, bwdq,
                      extraq, maskq, y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, tabq, avgq, bufq, delays_q, fwdq,
                               bwdq, extraq, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, tabq, avgq, bufq = self._epoch(f"faulted_saga{tau}", build)(
            self.xs, wq, tabq, avgq, bufq, delays_q, fwdq, bwdq, extraq,
            self.maskq, self.y, lr, key, t0, batch, steps)
        return wq, tabq, avgq, bufq, t0 + steps

    # -- guarded epochs (corrupt-value faults + in-graph health telemetry) ----
    #
    # The faulted epochs with one more per-step channel (``corruptq``,
    # (q, steps) int32 codes — see ``faults.apply_corruption``) and a
    # static ``guard`` flag.  Each step corrupts the party's forward
    # partial BEFORE aggregation, computes a finiteness verdict, and —
    # when guarding — quarantines a non-finite party through the same
    # membership machinery as a crash: the sanitized partial (zeroed; a
    # masked NaN would re-poison via 0·NaN) enters ``_agg_members`` with
    # the shrunken alive-set, whose gathered fingerprint re-keys the
    # per-step masks (Definition 4 holds over the healthy survivors).
    # Quarantine is forward-only: the party still receives ϑ, writes its
    # ring, and applies.  Per-step HealthStats (finiteness, effective
    # liveness, partial/direction norms) accumulate as scan outputs —
    # entirely in-graph, zero mid-epoch host transfers, still ONE
    # dispatch per epoch (the guards bench audits the jaxpr).  The
    # finiteness verdict itself is protocol-public (additive masks can't
    # hide a NaN/Inf: the masked value is non-finite iff the raw one
    # is), which is exactly the declassification ``analysis.taint``
    # grants ``is_finite`` — see that module's docstring.

    @_scoped("vfb2.guard")
    def _guard_fwd(self, z, cc, fl, guard: bool):
        """Corrupt, verdict, sanitize: the guarded epochs' shared
        forward-side step.  Returns (shippable partial, healthy flag,
        effective forward liveness)."""
        zc = apply_corruption(z, cc)
        healthy = jnp.all(jnp.isfinite(zc)).astype(z.dtype)
        if guard:
            live = fl * healthy
            zs = jnp.where(healthy > 0, zc, jnp.zeros_like(zc))
        else:
            live, zs = fl, zc
        return zs, zc, healthy, live

    def guarded_sgd_epoch(self, wq, bufq, t0, delays_q, fwdq, bwdq,
                          extraq, corruptq, lr, key, batch: int,
                          steps: int, tau: int, guard: bool = True):
        """Guarded VFB²-SGD epoch: corrupt-value injection, finiteness
        quarantine (``guard=True``), and health telemetry on the faulted
        epoch's membership machinery.  Returns
        ``(wq, bufq, t0', HealthStats)``; pinned against
        ``faults.guarded_sgd_epoch`` at 1e-5."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, wp, buf, delay, fwd_p, bwd_p, extra_p, corr_p,
                 maskp) = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    wp, buf, t = carry
                    ib, kt, fl, bl, ex, cc = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    zs, zc, healthy, live = self._guard_fwd(z, cc, fl,
                                                            guard)
                    agg = self._agg_members(zs, kt, live)
                    theta = prob.theta(agg, y[ib])
                    g = self._bwd(xb, theta[:, None], ib.shape[0])[:, 0] \
                        + prob.lam * prob.reg_grad(wp)
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, g, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    hs = (healthy, live, jnp.max(jnp.abs(zc)),
                          jnp.max(jnp.abs(g)))
                    return (wp - lr * bl * maskp * stale, buf, t + 1), hs

                (wp, buf, _), hs = jax.lax.scan(
                    body, (wp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p, corr_p))
                return wp, buf, hs

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_q, fwdq, bwdq, extraq,
                      corruptq, maskq, y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, bufq, delays_q, fwdq, bwdq, extraq,
                               corruptq, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq, hs = self._epoch(
            f"guarded_sgd{tau}_{int(bool(guard))}", build)(
            self.xs, wq, bufq, delays_q, fwdq, bwdq, extraq, corruptq,
            self.maskq, self.y, lr, key, t0, batch, steps)
        return wq, bufq, t0 + steps, HealthStats(*hs)

    def guarded_svrg_epoch(self, wq, wq_snap, muq, bufq, t0, delays_q,
                           fwdq, bwdq, extraq, corruptq, lr, key,
                           batch: int, steps: int, tau: int,
                           guard: bool = True):
        """Guarded VFB²-SVRG inner loop: the party's forward message is
        both partial columns (iterate + snapshot) — one corrupt code
        rewrites both and the finiteness verdict covers both, so a
        party is healthy only if its whole message is."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, wp, wsp, mup, buf, delay, fwd_p, bwd_p, extra_p,
                 corr_p, maskp) = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    wp, buf, t = carry
                    ib, kt, fl, bl, ex, cc = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, jnp.stack([wp, wsp], axis=1))
                    zs, zc, healthy, live = self._guard_fwd(z, cc, fl,
                                                            guard)
                    agg = self._agg_members(zs, kt, live)
                    th1 = prob.theta(agg[:, 0], y[ib])
                    th0 = prob.theta(agg[:, 1], y[ib])
                    gg = self._bwd(xb, jnp.stack([th1, th0], axis=1),
                                   ib.shape[0])
                    g1 = gg[:, 0] + prob.lam * prob.reg_grad(wp)
                    g0 = gg[:, 1] + prob.lam * prob.reg_grad(wsp)
                    v = g1 - g0 + mup
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, v, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    hs = (healthy, live, jnp.max(jnp.abs(zc)),
                          jnp.max(jnp.abs(v)))
                    return (wp - lr * bl * maskp * stale, buf, t + 1), hs

                (wp, buf, _), hs = jax.lax.scan(
                    body, (wp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p, corr_p))
                return wp, buf, hs

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, bufq, delays_q, fwdq, bwdq,
                      extraq, corruptq, maskq, y, lr, key, t0, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, wq_snap, muq, bufq, delays_q, fwdq,
                               bwdq, extraq, corruptq, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq, hs = self._epoch(
            f"guarded_svrg{tau}_{int(bool(guard))}", build)(
            self.xs, wq, wq_snap, muq, bufq, delays_q, fwdq, bwdq, extraq,
            corruptq, self.maskq, self.y, lr, key, t0, batch, steps)
        return wq, bufq, t0 + steps, HealthStats(*hs)

    def guarded_saga_epoch(self, wq, tabq, avgq, bufq, t0, delays_q, fwdq,
                           bwdq, extraq, corruptq, lr, key, batch: int,
                           steps: int, tau: int, guard: bool = True):
        """Guarded VFB²-SAGA: the faulted epoch's state-freshness split
        (ϑ̃ table always fresh, per-party average gated by backward
        liveness) with the corrupt channel on the forward partial."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, wp, tab, avgp, buf, delay, fwd_p, bwd_p, extra_p,
                 corr_p, maskp) = local
                y, lr, idx, mkeys, t0 = shared
                n = y.shape[0]

                def body(carry, inp):
                    wp, tab, avgp, buf, t = carry
                    ib, kt, fl, bl, ex, cc = inp
                    xb = self._rows(xp, ib)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    zs, zc, healthy, live = self._guard_fwd(z, cc, fl,
                                                            guard)
                    agg = self._agg_members(zs, kt, live)
                    th_new = prob.theta(agg, y[ib])
                    dth = (th_new - tab[ib])[:, None]
                    raw = self._bwd(xb, dth, 1)[:, 0]
                    v = raw / ib.shape[0] + avgp \
                        + prob.lam * prob.reg_grad(wp)
                    slot = t % (tau + 1)
                    put = jax.lax.dynamic_update_index_in_dim(buf, v, slot,
                                                              0)
                    buf = jnp.where(bl > 0, put, buf)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    wp = wp - lr * bl * maskp * stale
                    avgp = avgp + bl * raw / n      # private: frozen out
                    tab = tab.at[ib].set(th_new)    # shared: always fresh
                    hs = (healthy, live, jnp.max(jnp.abs(zc)),
                          jnp.max(jnp.abs(v)))
                    return (wp, tab, avgp, buf, t + 1), hs

                (wp, tab, avgp, buf, _), hs = jax.lax.scan(
                    body, (wp, tab, avgp, buf, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p, corr_p))
                return wp, tab, avgp, buf, hs

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate(
                                   "wq", "tabq", "avgq", "bufq"))
            def epoch(xs, wq, tabq, avgq, bufq, delays_q, fwdq, bwdq,
                      extraq, corruptq, maskq, y, lr, key, t0, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, tabq, avgq, bufq, delays_q, fwdq,
                               bwdq, extraq, corruptq, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, tabq, avgq, bufq, hs = self._epoch(
            f"guarded_saga{tau}_{int(bool(guard))}", build)(
            self.xs, wq, tabq, avgq, bufq, delays_q, fwdq, bwdq, extraq,
            corruptq, self.maskq, self.y, lr, key, t0, batch, steps)
        return wq, tabq, avgq, bufq, t0 + steps, HealthStats(*hs)

    def multi_delayed_sgd_epoch(self, wq, bufq, t0, delays_qm, lr, key,
                                batch: int, steps: int, tau: int):
        """Bounded-delay multi-dominator VFB²-SGD: at step t every party
        holds m gradient ring buffers — one per dominator — and applies
        dominator j's BUM gradient of step t − d_{ℓ,j}, so each dominator's
        update stream ages under its own delay schedule (the per-dominator
        τ₁/τ₂ realization; `core.staleness.delayed_multi_sgd_epoch` is the
        sequential oracle).

        ``bufq``: (q, τ+1, dp, m) per-(party, dominator) ring buffers;
        ``delays_qm``: (q, m) int32 delays d_{ℓ,j}; ``t0``: scalar int32.
        """
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, buf, delay, maskp = local    # delay: (m,)
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:])

                def body(carry, inp):
                    wp, buf, t = carry
                    ibf, kt = inp
                    b = ibf.shape[0] // m
                    xb = self._rows(xp, ibf)
                    z = self._fwd(xb, wp[:, None])[:, 0]
                    agg = self._agg(z, kt)
                    theta = prob.theta(agg, y[ibf])
                    gg = self._bwd_doms(xb, theta, m, b) \
                        + prob.lam * prob.reg_grad(wp)[:, None]   # (dp, m)
                    slot = t % (tau + 1)
                    buf = jax.lax.dynamic_update_index_in_dim(buf, gg,
                                                              slot, 0)
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)   # (m,)
                    stale = jnp.take_along_axis(
                        buf, jnp.broadcast_to(eff[None, None, :],
                                              (1,) + gg.shape), axis=0)[0]
                    wp = wp - lr * maskp * stale.sum(axis=1)
                    return (wp, buf, t + 1), None

                (wp, buf, _), _ = jax.lax.scan(body, (wp, buf, t0),
                                               (idx, masks))
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_qm, maskq, y, lr, key, t0,
                      batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, bufq, delays_qm, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"multi_delayed{tau}", build)(
            self.xs, wq, bufq, delays_qm, self.maskq, self.y, lr, key, t0,
            batch, steps)
        return wq, bufq, t0 + steps

    # -- pipelined epochs: backward(t) ∥ forward(t+1), ONE kernel
    # -- invocation per interior step (τ = 1 stale forward read) --------------
    #
    # The bilevel asynchrony means round t's BUM application and round
    # t+1's partial products are data-independent, so each scan step issues
    # a single split-batch fused contraction (`_pipe`): rows = [X_{b_t};
    # X_{b_{t+1}}], Θ over the backward rows, W over the forward rows.
    # Both halves execute from the same pre-update iterate — round t+1's ϑ
    # is therefore computed from an iterate one update old, exactly a
    # τ = 1 bounded-delay trajectory of the paper's model (see
    # core.staleness docstring).  Each epoch is a forward-only prologue,
    # steps−1 fused invocations in the scan, and a backward-only epilogue:
    # steps+1 launches instead of 2·steps.  `core.algorithms.pipelined_*`
    # are the exact sequential oracles.

    def pipelined_sgd_epoch(self, wq, lr, key, batch: int, steps: int):
        """Pipelined VFB²-SGD epoch; pinned against
        ``algorithms.pipelined_sgd_epoch``."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]      # prologue
                agg0 = self._agg(z0, masks[0])

                def body(carry, inp):
                    wp, xb, ib, agg = carry
                    ib_next, kt = inp
                    theta = prob.theta(agg, y[ib])
                    xb_next = self._rows(xp, ib_next)
                    z_next, g = self._pipe(xb, xb_next, wp[:, None],
                                           theta[:, None], ib.shape[0])
                    agg_next = self._agg(z_next[:, 0], kt)
                    g = g[:, 0] + prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * g
                    return (wp, xb_next, ib_next, agg_next), None

                (wp, xb, ib, agg), _ = jax.lax.scan(
                    body, (wp, xb0, ib0, agg0), (idx[1:], masks[1:]))
                theta = prob.theta(agg, y[ib])              # epilogue
                g = self._bwd(xb, theta[:, None], ib.shape[0])[:, 0] \
                    + prob.lam * prob.reg_grad(wp)
                return wp - lr * maskp * g

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq"))
            def epoch(xs, wq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("pipelined_sgd", build)(
            self.xs, wq, self.maskq, self.y, lr, key, batch, steps)

    def pipelined_svrg_epoch(self, wq, wq_snap, muq, lr, key, batch: int,
                             steps: int):
        """Pipelined VFB²-SVRG inner loop: the iterate and the snapshot
        ride the same M = 2 split-batch invocation (ϑ₁ on the stale read;
        the snapshot column is constant, so ϑ₀ is delay-free)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, wsp, mup, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:] + (2,))
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, jnp.stack([wp, wsp], axis=1))  # (B, 2)
                agg0 = self._agg(z0, masks[0])

                def update(wp, gg):
                    th_reg = prob.lam * (prob.reg_grad(wp)
                                         - prob.reg_grad(wsp))
                    return wp - lr * maskp * (gg[:, 0] - gg[:, 1]
                                              + th_reg + mup)

                def body(carry, inp):
                    wp, xb, ib, agg = carry
                    ib_next, kt = inp
                    th1 = prob.theta(agg[:, 0], y[ib])
                    th0 = prob.theta(agg[:, 1], y[ib])
                    xb_next = self._rows(xp, ib_next)
                    z_next, gg = self._pipe(
                        xb, xb_next, jnp.stack([wp, wsp], axis=1),
                        jnp.stack([th1, th0], axis=1), ib.shape[0])
                    agg_next = self._agg(z_next, kt)
                    wp = update(wp, gg)
                    return (wp, xb_next, ib_next, agg_next), None

                (wp, xb, ib, agg), _ = jax.lax.scan(
                    body, (wp, xb0, ib0, agg0), (idx[1:], masks[1:]))
                th1 = prob.theta(agg[:, 0], y[ib])          # epilogue
                th0 = prob.theta(agg[:, 1], y[ib])
                gg = self._bwd(xb, jnp.stack([th1, th0], axis=1),
                               ib.shape[0])
                return update(wp, gg)

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, maskq, y, lr, key, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, wq_snap, muq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("pipelined_svrg", build)(
            self.xs, wq, wq_snap, muq, self.maskq, self.y, lr, key,
            batch, steps)

    def pipelined_saga_epoch(self, wq, tabq, avgq, lr, key, batch: int,
                             steps: int):
        """Pipelined VFB²-SAGA: Δϑ enters the split-batch invocation at
        application time; only the forward read of the iterate is one
        step stale (``algorithms.pipelined_saga_epoch`` is the oracle)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, tab, avgp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                n = y.shape[0]
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]
                agg0 = self._agg(z0, masks[0])

                def apply(wp, tab, avgp, raw, th_new, ib):
                    v = raw / ib.shape[0] + avgp \
                        + prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * v
                    avgp = avgp + raw / n
                    tab = tab.at[ib].set(th_new)
                    return wp, tab, avgp

                def body(carry, inp):
                    wp, tab, avgp, xb, ib, agg = carry
                    ib_next, kt = inp
                    th_new = prob.theta(agg, y[ib])
                    dth = (th_new - tab[ib])[:, None]
                    xb_next = self._rows(xp, ib_next)
                    z_next, raw = self._pipe(xb, xb_next, wp[:, None],
                                             dth, 1)
                    agg_next = self._agg(z_next[:, 0], kt)
                    wp, tab, avgp = apply(wp, tab, avgp, raw[:, 0],
                                          th_new, ib)
                    return (wp, tab, avgp, xb_next, ib_next, agg_next), None

                (wp, tab, avgp, xb, ib, agg), _ = jax.lax.scan(
                    body, (wp, tab, avgp, xb0, ib0, agg0),
                    (idx[1:], masks[1:]))
                th_new = prob.theta(agg, y[ib])             # epilogue
                dth = (th_new - tab[ib])[:, None]
                raw = self._bwd(xb, dth, 1)[:, 0]
                wp, tab, avgp = apply(wp, tab, avgp, raw, th_new, ib)
                return wp, tab, avgp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "tabq",
                                                            "avgq"))
            def epoch(xs, wq, tabq, avgq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, tabq, avgq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("pipelined_saga", build)(
            self.xs, wq, tabq, avgq, self.maskq, self.y, lr, key, batch,
            steps)

    def pipelined_delayed_sgd_epoch(self, wq, bufq, t0, delays_q, lr, key,
                                    batch: int, steps: int, tau: int):
        """Pipelined bounded-delay VFB²-SGD: the stale-read gradient of
        each step enters the per-party ring buffer and ages under the
        delay schedule (``staleness.pipelined_delayed_sgd_epoch`` is the
        oracle; same state layout as :meth:`delayed_sgd_epoch`)."""
        prob = self.problem

        def build():
            def party(local, shared):
                xp, wp, buf, delay, maskp = local
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:])
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]
                agg0 = self._agg(z0, masks[0])

                def apply(wp, buf, t, g):
                    slot = t % (tau + 1)
                    buf = jax.lax.dynamic_update_index_in_dim(buf, g,
                                                              slot, 0)
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)
                    stale = jax.lax.dynamic_index_in_dim(buf, eff, 0,
                                                         keepdims=False)
                    return wp - lr * maskp * stale, buf, t + 1

                def body(carry, inp):
                    wp, buf, t, xb, ib, agg = carry
                    ib_next, kt = inp
                    theta = prob.theta(agg, y[ib])
                    xb_next = self._rows(xp, ib_next)
                    z_next, g = self._pipe(xb, xb_next, wp[:, None],
                                           theta[:, None], ib.shape[0])
                    agg_next = self._agg(z_next[:, 0], kt)
                    g = g[:, 0] + prob.lam * prob.reg_grad(wp)
                    wp, buf, t = apply(wp, buf, t, g)
                    return (wp, buf, t, xb_next, ib_next, agg_next), None

                (wp, buf, t, xb, ib, agg), _ = jax.lax.scan(
                    body, (wp, buf, t0, xb0, ib0, agg0),
                    (idx[1:], masks[1:]))
                theta = prob.theta(agg, y[ib])              # epilogue
                g = self._bwd(xb, theta[:, None], ib.shape[0])[:, 0] \
                    + prob.lam * prob.reg_grad(wp)
                wp, buf, _ = apply(wp, buf, t, g)
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_q, maskq, y, lr, key, t0, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                return mapped((xs, wq, bufq, delays_q, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"pipelined_delayed{tau}", build)(
            self.xs, wq, bufq, delays_q, self.maskq, self.y, lr, key, t0,
            batch, steps)
        return wq, bufq, t0 + steps

    # -- multi-dominator pipelined epochs (m active parties per step) ---------

    def multi_pipelined_sgd_epoch(self, wq, lr, key, batch: int,
                                  steps: int):
        """Pipelined multi-dominator VFB²-SGD: the m dominators' ϑ columns
        (block-diagonal Θ) and the next round's concatenated forward ride
        one split-batch invocation with Mw = 1, Mθ = m."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]
                agg0 = self._agg(z0, masks[0])

                def body(carry, inp):
                    wp, xb, ibf, agg = carry
                    ibf_next, kt = inp
                    b = ibf.shape[0] // m
                    theta = prob.theta(agg, y[ibf])
                    xb_next = self._rows(xp, ibf_next)
                    z_next, gg = self._pipe_doms(xb, xb_next, wp, theta,
                                                 m, b)
                    agg_next = self._agg(z_next, kt)
                    g = gg.sum(axis=1) + m * prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * g
                    return (wp, xb_next, ibf_next, agg_next), None

                (wp, xb, ibf, agg), _ = jax.lax.scan(
                    body, (wp, xb0, ib0, agg0), (idx[1:], masks[1:]))
                b = ibf.shape[0] // m
                theta = prob.theta(agg, y[ibf])             # epilogue
                gg = self._bwd_doms(xb, theta, m, b)
                g = gg.sum(axis=1) + m * prob.lam * prob.reg_grad(wp)
                return wp - lr * maskp * g

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq"))
            def epoch(xs, wq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_pipelined_sgd", build)(
            self.xs, wq, self.maskq, self.y, lr, key, batch, steps)

    def multi_pipelined_svrg_epoch(self, wq, wq_snap, muq, lr, key,
                                   batch: int, steps: int):
        """Pipelined multi-dominator VFB²-SVRG: the m dominators'
        concatenated minibatches share the M = 2 columns (iterate +
        snapshot) of one split-batch invocation per step."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, wsp, mup, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:] + (2,))
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, jnp.stack([wp, wsp], axis=1))
                agg0 = self._agg(z0, masks[0])

                def update(wp, gg):
                    return wp - lr * maskp * (
                        gg[:, 0] - gg[:, 1] + m * (
                            prob.lam * (prob.reg_grad(wp)
                                        - prob.reg_grad(wsp)) + mup))

                def body(carry, inp):
                    wp, xb, ibf, agg = carry
                    ibf_next, kt = inp
                    b = ibf.shape[0] // m
                    th1 = prob.theta(agg[:, 0], y[ibf])
                    th0 = prob.theta(agg[:, 1], y[ibf])
                    xb_next = self._rows(xp, ibf_next)
                    z_next, gg = self._pipe(
                        xb, xb_next, jnp.stack([wp, wsp], axis=1),
                        jnp.stack([th1, th0], axis=1), b)
                    agg_next = self._agg(z_next, kt)
                    wp = update(wp, gg)
                    return (wp, xb_next, ibf_next, agg_next), None

                (wp, xb, ibf, agg), _ = jax.lax.scan(
                    body, (wp, xb0, ib0, agg0), (idx[1:], masks[1:]))
                b = ibf.shape[0] // m
                th1 = prob.theta(agg[:, 0], y[ibf])         # epilogue
                th0 = prob.theta(agg[:, 1], y[ibf])
                gg = self._bwd(xb, jnp.stack([th1, th0], axis=1), b)
                return update(wp, gg)

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, wq, wq_snap, muq, maskq, y, lr, key, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, wq_snap, muq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_pipelined_svrg", build)(
            self.xs, wq, wq_snap, muq, self.maskq, self.y, lr, key,
            batch, steps)

    def multi_pipelined_saga_epoch(self, wq, tabq, avgq, lr, key,
                                   batch: int, steps: int):
        """Pipelined multi-dominator VFB²-SAGA: per-dominator Δϑ columns
        (block-diagonal) next to the single forward column, one
        invocation per step."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, tab, avgp, maskp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:])
                n = y.shape[0]
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]
                agg0 = self._agg(z0, masks[0])

                def apply(wp, tab, avgp, raws, th_new, ibf):
                    b = ibf.shape[0] // m
                    rsum = raws.sum(axis=1)
                    v = rsum / b + m * avgp \
                        + m * prob.lam * prob.reg_grad(wp)
                    wp = wp - lr * maskp * v
                    avgp = avgp + rsum / n
                    tab = tab.at[ibf].set(th_new)
                    return wp, tab, avgp

                def body(carry, inp):
                    wp, tab, avgp, xb, ibf, agg = carry
                    ibf_next, kt = inp
                    th_new = prob.theta(agg, y[ibf])
                    dth = th_new - tab[ibf]
                    xb_next = self._rows(xp, ibf_next)
                    z_next, raws = self._pipe_doms(xb, xb_next, wp, dth,
                                                   m, 1)
                    agg_next = self._agg(z_next, kt)
                    wp, tab, avgp = apply(wp, tab, avgp, raws, th_new, ibf)
                    return (wp, tab, avgp, xb_next, ibf_next,
                            agg_next), None

                (wp, tab, avgp, xb, ibf, agg), _ = jax.lax.scan(
                    body, (wp, tab, avgp, xb0, ib0, agg0),
                    (idx[1:], masks[1:]))
                th_new = prob.theta(agg, y[ibf])            # epilogue
                dth = th_new - tab[ibf]
                raws = self._bwd_doms(xb, dth, m, 1)
                wp, tab, avgp = apply(wp, tab, avgp, raws, th_new, ibf)
                return wp, tab, avgp

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "tabq",
                                                            "avgq"))
            def epoch(xs, wq, tabq, avgq, maskq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, tabq, avgq, maskq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return self._epoch("multi_pipelined_saga", build)(
            self.xs, wq, tabq, avgq, self.maskq, self.y, lr, key, batch,
            steps)

    def multi_pipelined_delayed_sgd_epoch(self, wq, bufq, t0, delays_qm,
                                          lr, key, batch: int, steps: int,
                                          tau: int):
        """Pipelined bounded-delay multi-dominator VFB²-SGD: per-(party,
        dominator) ring buffers age the stale-read per-dominator gradient
        columns (``staleness.pipelined_delayed_multi_sgd_epoch`` is the
        oracle; same state layout as :meth:`multi_delayed_sgd_epoch`)."""
        prob, m = self.problem, self.layout.m

        def build():
            def party(local, shared):
                xp, wp, buf, delay, maskp = local    # delay: (m,)
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:])
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                z0 = self._fwd(xb0, wp[:, None])[:, 0]
                agg0 = self._agg(z0, masks[0])

                def apply(wp, buf, t, gg):
                    slot = t % (tau + 1)
                    buf = jax.lax.dynamic_update_index_in_dim(buf, gg,
                                                              slot, 0)
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)   # (m,)
                    stale = jnp.take_along_axis(
                        buf, jnp.broadcast_to(eff[None, None, :],
                                              (1,) + gg.shape), axis=0)[0]
                    return wp - lr * maskp * stale.sum(axis=1), buf, t + 1

                def body(carry, inp):
                    wp, buf, t, xb, ibf, agg = carry
                    ibf_next, kt = inp
                    b = ibf.shape[0] // m
                    theta = prob.theta(agg, y[ibf])
                    xb_next = self._rows(xp, ibf_next)
                    z_next, gg = self._pipe_doms(xb, xb_next, wp, theta,
                                                 m, b)
                    agg_next = self._agg(z_next, kt)
                    gg = gg + prob.lam * prob.reg_grad(wp)[:, None]
                    wp, buf, t = apply(wp, buf, t, gg)
                    return (wp, buf, t, xb_next, ibf_next, agg_next), None

                (wp, buf, t, xb, ibf, agg), _ = jax.lax.scan(
                    body, (wp, buf, t0, xb0, ib0, agg0),
                    (idx[1:], masks[1:]))
                b = ibf.shape[0] // m
                theta = prob.theta(agg, y[ibf])             # epilogue
                gg = self._bwd_doms(xb, theta, m, b) \
                    + prob.lam * prob.reg_grad(wp)[:, None]
                wp, buf, _ = apply(wp, buf, t, gg)
                return wp, buf

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("wq", "bufq"))
            def epoch(xs, wq, bufq, delays_qm, maskq, y, lr, key, t0,
                      batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                return mapped((xs, wq, bufq, delays_qm, maskq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        wq, bufq = self._epoch(f"multi_pipelined_delayed{tau}", build)(
            self.xs, wq, bufq, delays_qm, self.maskq, self.y, lr, key, t0,
            batch, steps)
        return wq, bufq, t0 + steps

    # -- deep VFB² epochs (nonlinear party-local encoders) --------------------
    #
    # The first nonlinear workload on the hot path: party ℓ holds a private
    # 1-hidden-layer encoder f_ℓ and the protocol aggregates the (B, d_rep)
    # partial representations h_ℓ instead of scalar partial products
    # (core.deep_vfl module docstring; that module is the sequential
    # oracle).  Per scan step: party-local encoder forward, ONE masked
    # secure aggregation of the vector partials, ϑ_z = ϑ_logit·head BUM
    # broadcast, and Jacobian-transpose updates — the encoder layers'
    # X-block contractions (x@W1, h@W2, xᵀ∂u, hᵀϑ_z) route through the
    # rank-k kernel with hidden/d_rep as the M axis.  The head is
    # replicated per party (the dominator's ϑ broadcast stand-in) and
    # takes the identical post-aggregation update everywhere.

    def _deep_grads(self, xb, yb, w1, b1, w2, head, kt, mdom: int = 1):
        """One deep BUM round at the given party-local params: returns the
        (g_w1, g_b1, g_w2, g_head) gradient pytree with the λ∇g(·)
        regularizer included on every leaf (matching the regularizer-fixed
        ``deep_vfl._bum_grads`` oracle).

        ``mdom > 1`` is the multi-dominator round: ``xb``/``yb`` carry the
        m dominators' concatenated minibatches, each dominator's ϑ is
        normalized by its own batch, the λ∇g term is applied once per
        concurrent update (mdom·λ∇g), and the full-row contractions sum
        the m per-dominator Jacobian-transpose gradients — exactly the
        summed block-column form of the rank-k pass."""
        prob = self.problem
        bsz = yb.shape[0] // mdom
        h = jnp.tanh(self._fwd(xb, w1) + b1)          # (m·B, hidden)
        hr = self._fwd(h, w2)                         # (m·B, d_rep) partials
        z = self._agg(hr, kt)                         # Algorithm-1 aggregate
        logit = z @ head
        th_l = prob.theta(logit, yb) / bsz            # dominators' ϑ
        th_z = th_l[:, None] * head                   # BUM payload ∂L/∂z
        g_head = z.T @ th_l + mdom * prob.lam * prob.reg_grad(head)
        g_w2 = self._bwd(h, th_z, 1) + mdom * prob.lam * prob.reg_grad(w2)
        du = (th_z @ w2.T) * (1.0 - h * h)            # tanh'
        g_w1 = self._bwd(xb, du, 1) + mdom * prob.lam * prob.reg_grad(w1)
        g_b1 = du.sum(axis=0) + mdom * prob.lam * prob.reg_grad(b1)
        return g_w1, g_b1, g_w2, g_head

    def _deep_dom_grads(self, xb, yb, w1, b1, w2, head, kt, m: int):
        """Per-dominator deep BUM round (the bounded-delay multi regime):
        one encoder forward over the m dominators' concatenated block, ONE
        masked secure aggregation of all m (B, d_rep) vector partial sets,
        then the m ϑ_z broadcasts come back as the K-column blocks of the
        rank-k contraction (:meth:`_bwd_doms_wide`), keeping every
        dominator's Jacobian-transpose gradient separate so each stream
        can age under its own delay.  Returns ``(g_w1 (dp, m, hid),
        g_b1 (m, hid), g_w2 (hid, m, dr), g_head (dr,))`` — encoder leaves
        carry per-stream λ∇g; the dominator-held head gradient is the
        fresh sum (m·λ∇g)."""
        prob = self.problem
        b = yb.shape[0] // m
        h = jnp.tanh(self._fwd(xb, w1) + b1)          # (m·B, hidden)
        hr = self._fwd(h, w2)                         # (m·B, d_rep)
        z = self._agg(hr, kt)
        th_l = prob.theta(z @ head, yb) / b
        th_z = th_l[:, None] * head
        g_head = z.T @ th_l + m * prob.lam * prob.reg_grad(head)
        du = (th_z @ w2.T) * (1.0 - h * h)
        g_w1 = self._bwd_doms_wide(xb, du, m, 1) \
            + prob.lam * prob.reg_grad(w1)[:, None, :]
        g_b1 = du.reshape(m, b, -1).sum(axis=1) \
            + prob.lam * prob.reg_grad(b1)[None, :]
        g_w2 = self._bwd_doms_wide(h, th_z, m, 1) \
            + prob.lam * prob.reg_grad(w2)[:, None, :]
        return g_w1, g_b1, g_w2, g_head

    def _deep_sgd_build(self, mdom: int):
        def build():
            def party(local, shared):
                xp, w1, b1, w2, head, maskp, trainp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)

                def body(carry, inp):
                    w1, b1, w2, head = carry
                    ib, kt = inp
                    g_w1, g_b1, g_w2, g_head = self._deep_grads(
                        self._rows(xp, ib), y[ib], w1, b1, w2, head, kt, mdom)
                    w1 = w1 - lr * maskp[:, None] * g_w1
                    b1 = b1 - lr * trainp * g_b1
                    w2 = w2 - lr * trainp * g_w2
                    head = head - lr * g_head
                    return (w1, b1, w2, head), None

                carry, _ = jax.lax.scan(body, (w1, b1, w2, head),
                                        (idx, masks))
                return carry

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq"))
            def epoch(xs, pq, maskq, trainq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], mdom * batch, steps)
                w1q, b1q, w2q, headq = pq
                return mapped((xs, w1q, b1q, w2q, headq, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return build

    def deep_sgd_epoch(self, pq, lr, key, batch: int, steps: int):
        """Deep VFB²-SGD epoch as ONE compiled program; pinned against
        ``deep_vfl.train_deep_vfl`` at 1e-5.  ``pq`` is the party-stacked
        ``(w1q, b1q, w2q, headq)`` from :meth:`pack_deep`."""
        return self._epoch("deep_sgd", self._deep_sgd_build(1))(
            self.xs, pq, self.maskq, self.trainq, self.y, lr, key, batch,
            steps)

    def deep_multi_sgd_epoch(self, pq, lr, key, batch: int, steps: int):
        """Deep VFB²-SGD with all m = layout.m dominators launching
        concurrent backward updates per step: the m independent minibatches
        are concatenated into ONE encoder forward, all m (B, d_rep) vector
        partial sets take one masked secure aggregation, and the m
        per-dominator ϑ_z broadcasts drive the summed Jacobian-transpose
        updates (see :meth:`_deep_grads`).  Pinned against
        ``deep_vfl.train_deep_vfl(..., multi_dominator=True)``."""
        return self._epoch("deep_multi_sgd",
                           self._deep_sgd_build(self.layout.m))(
            self.xs, pq, self.maskq, self.trainq, self.y, lr, key, batch,
            steps)

    def deep_full_gradient(self, pq, key):
        """Full-dataset deep BUM gradient pytree at ``pq`` (SVRG's μ)."""
        def build():
            def party(local, shared):
                xp, w1, b1, w2, head = local
                y, kt = shared
                return self._deep_grads(xp, y, w1, b1, w2, head, kt)

            mapped = self._bind(party)

            @jax.jit
            def full(xs, pq, y, key):
                w1q, b1q, w2q, headq = pq
                return mapped((xs, w1q, b1q, w2q, headq),
                              (y, jax.random.fold_in(key, 0xf)))

            return full

        return self._epoch("deep_full_grad", build)(self.xs, pq, self.y,
                                                    key)

    def _deep_svrg_build(self, mdom: int):
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, w1s, b1s, w2s, heads, mu, maskp,
                 trainp) = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys,
                                    idx.shape[1:] + (2 * head.shape[0],))
                mu_w1, mu_b1, mu_w2, mu_head = mu
                hid = w1.shape[1]
                dr = head.shape[0]

                def body(carry, inp):
                    w1, b1, w2, head = carry
                    ib, kt = inp
                    xb = self._rows(xp, ib)
                    yb = y[ib]
                    bsz = yb.shape[0] // mdom
                    uu = self._fwd(xb, jnp.concatenate([w1, w1s], axis=1))
                    h = jnp.tanh(uu[:, :hid] + b1)
                    hs = jnp.tanh(uu[:, hid:] + b1s)
                    zz = self._agg(jnp.concatenate(
                        [self._fwd(h, w2), self._fwd(hs, w2s)], axis=1), kt)
                    z, zs = zz[:, :dr], zz[:, dr:]
                    th1 = prob.theta(z @ head, yb) / bsz
                    th0 = prob.theta(zs @ heads, yb) / bsz
                    thz1 = th1[:, None] * head
                    thz0 = th0[:, None] * heads
                    v_head = (z.T @ th1 + mdom * prob.lam
                              * prob.reg_grad(head)
                              - zs.T @ th0 - mdom * prob.lam
                              * prob.reg_grad(heads)
                              + mdom * mu_head)
                    v_w2 = (self._bwd(h, thz1, 1) - self._bwd(hs, thz0, 1)
                            + mdom * prob.lam * (prob.reg_grad(w2)
                                                 - prob.reg_grad(w2s))
                            + mdom * mu_w2)
                    du1 = (thz1 @ w2.T) * (1.0 - h * h)
                    du0 = (thz0 @ w2s.T) * (1.0 - hs * hs)
                    duu = self._bwd(xb, jnp.concatenate([du1, du0], axis=1),
                                    1)
                    v_w1 = (duu[:, :hid] - duu[:, hid:]
                            + mdom * prob.lam * (prob.reg_grad(w1)
                                                 - prob.reg_grad(w1s))
                            + mdom * mu_w1)
                    v_b1 = (du1.sum(axis=0) - du0.sum(axis=0)
                            + mdom * prob.lam * (prob.reg_grad(b1)
                                                 - prob.reg_grad(b1s))
                            + mdom * mu_b1)
                    w1 = w1 - lr * maskp[:, None] * v_w1
                    b1 = b1 - lr * trainp * v_b1
                    w2 = w2 - lr * trainp * v_w2
                    head = head - lr * v_head
                    return (w1, b1, w2, head), None

                carry, _ = jax.lax.scan(body, (w1, b1, w2, head),
                                        (idx, masks))
                return carry

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, pq, pq_snap, muq, maskq, trainq, y, lr, key,
                      batch, steps):
                idx = _sample_indices(key, y.shape[0], mdom * batch, steps)
                w1q, b1q, w2q, headq = pq
                w1s, b1s, w2s, headsq = pq_snap
                return mapped((xs, w1q, b1q, w2q, headq, w1s, b1s, w2s,
                               headsq, muq, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return build

    def deep_svrg_epoch(self, pq, pq_snap, muq, lr, key, batch: int,
                        steps: int):
        """Deep VFB²-SVRG inner loop: v = g(w) − g(w̃) + μ per parameter
        leaf.  The iterate's and snapshot's encoder passes share the
        X-block kernel invocations where the left operand coincides (layer
        1 forward and its backward ride one M = 2·hidden pass), and both
        (B, d_rep) partial sets aggregate in ONE masked collective."""
        return self._epoch("deep_svrg", self._deep_svrg_build(1))(
            self.xs, pq, pq_snap, muq, self.maskq, self.trainq, self.y,
            lr, key, batch, steps)

    def deep_multi_svrg_epoch(self, pq, pq_snap, muq, lr, key, batch: int,
                              steps: int):
        """Multi-dominator deep VFB²-SVRG inner loop: the m dominators'
        concatenated minibatches ride the same shared M = 2·hidden layer-1
        pass and ONE masked aggregation of both (m·B, d_rep) partial sets;
        the applied step sums the m variance-reduced updates
        (v = Σ_j[g₁ⱼ − g₀ⱼ] + m·(λ∇g(w) − λ∇g(w̃)) + m·μ)."""
        return self._epoch("deep_multi_svrg",
                           self._deep_svrg_build(self.layout.m))(
            self.xs, pq, pq_snap, muq, self.maskq, self.trainq, self.y,
            lr, key, batch, steps)

    def deep_delay_buffers(self, pq, tau: int):
        """Zero-initialized per-party encoder gradient ring buffers for
        :meth:`deep_delayed_sgd_epoch`: ``(q, τ+1, ...)`` per leaf."""
        w1q, b1q, w2q, _ = pq

        def ring(a):
            return jnp.zeros((a.shape[0], tau + 1) + a.shape[1:],
                             jnp.float32)

        return (ring(w1q), ring(b1q), ring(w2q))

    def deep_delayed_sgd_epoch(self, pq, bufq, t0, delays_q, lr, key,
                               batch: int, steps: int, tau: int):
        """Bounded-delay deep VFB²-SGD: party ℓ applies, at step t, its
        *encoder* gradients of step t − d_ℓ from per-party ring buffers
        carried through the scan; the dominator-held head applies its
        gradient fresh (d = 0 — active parties are the dominators of the
        head, and delaying a replicated parameter would fork the
        replicas).  ``staleness.train_deep_delayed`` is the sequential
        oracle.  ``bufq``: pytree from :meth:`deep_delay_buffers`;
        ``delays_q``: (q,) int32."""
        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, maskp,
                 trainp) = local
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ib, kt = inp
                    g_w1, g_b1, g_w2, g_head = self._deep_grads(
                        self._rows(xp, ib), y[ib], w1, b1, w2, head, kt)
                    slot = t % (tau + 1)
                    bw1 = jax.lax.dynamic_update_index_in_dim(bw1, g_w1,
                                                              slot, 0)
                    bb1 = jax.lax.dynamic_update_index_in_dim(bb1, g_b1,
                                                              slot, 0)
                    bw2 = jax.lax.dynamic_update_index_in_dim(bw2, g_w2,
                                                              slot, 0)
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)
                    s_w1 = jax.lax.dynamic_index_in_dim(bw1, eff, 0,
                                                        keepdims=False)
                    s_b1 = jax.lax.dynamic_index_in_dim(bb1, eff, 0,
                                                        keepdims=False)
                    s_w2 = jax.lax.dynamic_index_in_dim(bw2, eff, 0,
                                                        keepdims=False)
                    w1 = w1 - lr * maskp[:, None] * s_w1
                    b1 = b1 - lr * trainp * s_b1
                    w2 = w2 - lr * trainp * s_w2
                    head = head - lr * g_head         # dominator-fresh
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), None

                (w1, b1, w2, head, bw1, bb1, bw2, _), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                    (idx, masks))
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_q, maskq, trainq, y, lr, key,
                      t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_q, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq = self._epoch(f"deep_delayed{tau}", build)(
            self.xs, pq, bufq, delays_q, self.maskq, self.trainq, self.y,
            lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    # -- deep faulted epochs (elastic membership) -----------------------------

    def _deep_fault_grads(self, xb, yb, w1, b1, w2, head, kt, fl):
        """:meth:`_deep_grads` with a survivor aggregate: a crashed
        party's (B, d_rep) vector partial is excluded from z, so the
        dominator's ϑ is computed over whoever is present."""
        prob = self.problem
        bsz = yb.shape[0]
        h = jnp.tanh(self._fwd(xb, w1) + b1)
        hr = self._fwd(h, w2)
        z = self._agg_members(hr, kt, fl)
        th_l = prob.theta(z @ head, yb) / bsz
        th_z = th_l[:, None] * head
        g_head = z.T @ th_l + prob.lam * prob.reg_grad(head)
        g_w2 = self._bwd(h, th_z, 1) + prob.lam * prob.reg_grad(w2)
        du = (th_z @ w2.T) * (1.0 - h * h)
        g_w1 = self._bwd(xb, du, 1) + prob.lam * prob.reg_grad(w1)
        g_b1 = du.sum(axis=0) + prob.lam * prob.reg_grad(b1)
        return g_w1, g_b1, g_w2, g_head

    def deep_faulted_sgd_epoch(self, pq, bufq, t0, delays_q, fwdq, bwdq,
                               extraq, lr, key, batch: int, steps: int,
                               tau: int):
        """Fault-trace deep VFB²-SGD: the per-step membership masks gate
        the survivor aggregation of the (B, d_rep) vector partials, the
        encoder-gradient ring writes, and the encoder applies; a crashed
        party's private encoder freezes whole.  The dominator-held
        replicated head applies fresh every step (shared protocol state —
        survivors keep it current, a rejoiner re-syncs).  Pinned against
        ``faults.run_deep_faulted_reference`` at 1e-5."""
        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, fwd_p, bwd_p,
                 extra_p, maskp, trainp) = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ib, kt, fl, bl, ex = inp
                    g_w1, g_b1, g_w2, g_head = self._deep_fault_grads(
                        self._rows(xp, ib), y[ib], w1, b1, w2, head, kt, fl)
                    slot = t % (tau + 1)
                    bw1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw1, g_w1,
                                                            slot, 0), bw1)
                    bb1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bb1, g_b1,
                                                            slot, 0), bb1)
                    bw2 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw2, g_w2,
                                                            slot, 0), bw2)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    s_w1 = jax.lax.dynamic_index_in_dim(bw1, eff, 0,
                                                        keepdims=False)
                    s_b1 = jax.lax.dynamic_index_in_dim(bb1, eff, 0,
                                                        keepdims=False)
                    s_w2 = jax.lax.dynamic_index_in_dim(bw2, eff, 0,
                                                        keepdims=False)
                    w1 = w1 - lr * bl * maskp[:, None] * s_w1
                    b1 = b1 - lr * bl * trainp * s_b1
                    w2 = w2 - lr * bl * trainp * s_w2
                    head = head - lr * g_head       # dominator-fresh
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), None

                (w1, b1, w2, head, bw1, bb1, bw2, _), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p))
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_q, fwdq, bwdq, extraq, maskq,
                      trainq, y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_q, fwdq, bwdq, extraq, maskq,
                               trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq = self._epoch(f"deep_faulted_sgd{tau}", build)(
            self.xs, pq, bufq, delays_q, fwdq, bwdq, extraq, self.maskq,
            self.trainq, self.y, lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    def deep_faulted_svrg_epoch(self, pq, pq_snap, muq, bufq, t0,
                                delays_q, fwdq, bwdq, extraq, lr, key,
                                batch: int, steps: int, tau: int):
        """Fault-trace deep VFB²-SVRG inner loop: both encoder passes
        (iterate + snapshot) contribute survivor-aggregated vector
        partials, the per-leaf variance-reduced directions enter the
        fault-gated rings, and the replicated head applies its
        v_head fresh.  μ̃/snapshot refreshes are epoch-boundary barrier
        rounds over full membership."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, w1s, b1s, w2s, heads, mu, bw1, bb1,
                 bw2, delay, fwd_p, bwd_p, extra_p, maskp, trainp) = local
                y, lr, idx, mkeys, t0 = shared
                mu_w1, mu_b1, mu_w2, mu_head = mu
                hid = w1.shape[1]
                dr = head.shape[0]

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ib, kt, fl, bl, ex = inp
                    xb = self._rows(xp, ib)
                    yb = y[ib]
                    bsz = yb.shape[0]
                    uu = self._fwd(xb, jnp.concatenate([w1, w1s], axis=1))
                    h = jnp.tanh(uu[:, :hid] + b1)
                    hs = jnp.tanh(uu[:, hid:] + b1s)
                    zz = self._agg_members(jnp.concatenate(
                        [self._fwd(h, w2), self._fwd(hs, w2s)], axis=1),
                        kt, fl)
                    z, zs = zz[:, :dr], zz[:, dr:]
                    th1 = prob.theta(z @ head, yb) / bsz
                    th0 = prob.theta(zs @ heads, yb) / bsz
                    thz1 = th1[:, None] * head
                    thz0 = th0[:, None] * heads
                    v_head = (z.T @ th1 + prob.lam * prob.reg_grad(head)
                              - zs.T @ th0 - prob.lam
                              * prob.reg_grad(heads)
                              + mu_head)
                    v_w2 = (self._bwd(h, thz1, 1) - self._bwd(hs, thz0, 1)
                            + prob.lam * (prob.reg_grad(w2)
                                          - prob.reg_grad(w2s))
                            + mu_w2)
                    du1 = (thz1 @ w2.T) * (1.0 - h * h)
                    du0 = (thz0 @ w2s.T) * (1.0 - hs * hs)
                    duu = self._bwd(xb, jnp.concatenate([du1, du0],
                                                        axis=1), 1)
                    v_w1 = (duu[:, :hid] - duu[:, hid:]
                            + prob.lam * (prob.reg_grad(w1)
                                          - prob.reg_grad(w1s))
                            + mu_w1)
                    v_b1 = (du1.sum(axis=0) - du0.sum(axis=0)
                            + prob.lam * (prob.reg_grad(b1)
                                          - prob.reg_grad(b1s))
                            + mu_b1)
                    slot = t % (tau + 1)
                    bw1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw1, v_w1,
                                                            slot, 0), bw1)
                    bb1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bb1, v_b1,
                                                            slot, 0), bb1)
                    bw2 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw2, v_w2,
                                                            slot, 0), bw2)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    s_w1 = jax.lax.dynamic_index_in_dim(bw1, eff, 0,
                                                        keepdims=False)
                    s_b1 = jax.lax.dynamic_index_in_dim(bb1, eff, 0,
                                                        keepdims=False)
                    s_w2 = jax.lax.dynamic_index_in_dim(bw2, eff, 0,
                                                        keepdims=False)
                    w1 = w1 - lr * bl * maskp[:, None] * s_w1
                    b1 = b1 - lr * bl * trainp * s_b1
                    w2 = w2 - lr * bl * trainp * s_w2
                    head = head - lr * v_head       # dominator-fresh
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), None

                (w1, b1, w2, head, bw1, bb1, bw2, _), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p))
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, pq, pq_snap, muq, bufq, delays_q, fwdq, bwdq,
                      extraq, maskq, trainq, y, lr, key, t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                w1s, b1s, w2s, headsq = pq_snap
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, w1s, b1s, w2s,
                               headsq, muq, bw1q, bb1q, bw2q, delays_q,
                               fwdq, bwdq, extraq, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq = self._epoch(f"deep_faulted_svrg{tau}", build)(
            self.xs, pq, pq_snap, muq, bufq, delays_q, fwdq, bwdq, extraq,
            self.maskq, self.trainq, self.y, lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    # -- deep guarded epochs (corrupt-value faults + health telemetry) --------

    def deep_guarded_sgd_epoch(self, pq, bufq, t0, delays_q, fwdq, bwdq,
                               extraq, corruptq, lr, key, batch: int,
                               steps: int, tau: int, guard: bool = True):
        """Guarded deep VFB²-SGD: the corrupt channel rewrites the
        party's (B, d_rep) vector partial before the survivor
        aggregation; ``guard=True`` quarantines a non-finite partial
        exactly like the linear guarded epochs (sanitize + drop from
        the step's alive-set, masks re-key on the healthy survivors).
        Returns ``(pq, bufq, t0', HealthStats)``; pinned against
        ``faults.run_deep_guarded_reference`` at 1e-5."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, fwd_p,
                 bwd_p, extra_p, corr_p, maskp, trainp) = local
                y, lr, idx, mkeys, t0 = shared

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ib, kt, fl, bl, ex, cc = inp
                    xb = self._rows(xp, ib)
                    yb = y[ib]
                    bsz = yb.shape[0]
                    h = jnp.tanh(self._fwd(xb, w1) + b1)
                    hr = self._fwd(h, w2)
                    zs, zc, healthy, live = self._guard_fwd(hr, cc, fl,
                                                            guard)
                    z = self._agg_members(zs, kt, live)
                    th_l = prob.theta(z @ head, yb) / bsz
                    th_z = th_l[:, None] * head
                    g_head = z.T @ th_l + prob.lam * prob.reg_grad(head)
                    g_w2 = self._bwd(h, th_z, 1) \
                        + prob.lam * prob.reg_grad(w2)
                    du = (th_z @ w2.T) * (1.0 - h * h)
                    g_w1 = self._bwd(xb, du, 1) \
                        + prob.lam * prob.reg_grad(w1)
                    g_b1 = du.sum(axis=0) + prob.lam * prob.reg_grad(b1)
                    slot = t % (tau + 1)
                    bw1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw1, g_w1,
                                                            slot, 0), bw1)
                    bb1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bb1, g_b1,
                                                            slot, 0), bb1)
                    bw2 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw2, g_w2,
                                                            slot, 0), bw2)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    s_w1 = jax.lax.dynamic_index_in_dim(bw1, eff, 0,
                                                        keepdims=False)
                    s_b1 = jax.lax.dynamic_index_in_dim(bb1, eff, 0,
                                                        keepdims=False)
                    s_w2 = jax.lax.dynamic_index_in_dim(bw2, eff, 0,
                                                        keepdims=False)
                    w1 = w1 - lr * bl * maskp[:, None] * s_w1
                    b1 = b1 - lr * bl * trainp * s_b1
                    w2 = w2 - lr * bl * trainp * s_w2
                    head = head - lr * g_head       # dominator-fresh
                    gnorm = jnp.maximum(
                        jnp.maximum(jnp.max(jnp.abs(g_w1)),
                                    jnp.max(jnp.abs(g_b1))),
                        jnp.max(jnp.abs(g_w2)))
                    hs = (healthy, live, jnp.max(jnp.abs(zc)), gnorm)
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), hs

                (w1, b1, w2, head, bw1, bb1, bw2, _), hs = jax.lax.scan(
                    body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                    (idx, mkeys, fwd_p, bwd_p, extra_p, corr_p))
                return (w1, b1, w2, head), (bw1, bb1, bw2), hs

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_q, fwdq, bwdq, extraq,
                      corruptq, maskq, trainq, y, lr, key, t0, batch,
                      steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_q, fwdq, bwdq, extraq, corruptq,
                               maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq, hs = self._epoch(
            f"deep_guarded_sgd{tau}_{int(bool(guard))}", build)(
            self.xs, pq, bufq, delays_q, fwdq, bwdq, extraq, corruptq,
            self.maskq, self.trainq, self.y, lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps, HealthStats(*hs)

    def deep_guarded_svrg_epoch(self, pq, pq_snap, muq, bufq, t0,
                                delays_q, fwdq, bwdq, extraq, corruptq,
                                lr, key, batch: int, steps: int, tau: int,
                                guard: bool = True):
        """Guarded deep VFB²-SVRG inner loop: the party's forward
        message is both vector partials (iterate + snapshot, one
        concatenated (B, 2·d_rep) block) — one corrupt code rewrites
        both and the finiteness verdict covers both."""
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, w1s, b1s, w2s, heads, mu, bw1, bb1,
                 bw2, delay, fwd_p, bwd_p, extra_p, corr_p, maskp,
                 trainp) = local
                y, lr, idx, mkeys, t0 = shared
                mu_w1, mu_b1, mu_w2, mu_head = mu
                hid = w1.shape[1]
                dr = head.shape[0]

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ib, kt, fl, bl, ex, cc = inp
                    xb = self._rows(xp, ib)
                    yb = y[ib]
                    bsz = yb.shape[0]
                    uu = self._fwd(xb, jnp.concatenate([w1, w1s], axis=1))
                    h = jnp.tanh(uu[:, :hid] + b1)
                    hs_ = jnp.tanh(uu[:, hid:] + b1s)
                    hr = jnp.concatenate(
                        [self._fwd(h, w2), self._fwd(hs_, w2s)], axis=1)
                    zsan, zc, healthy, live = self._guard_fwd(hr, cc, fl,
                                                              guard)
                    zz = self._agg_members(zsan, kt, live)
                    z, zsnap = zz[:, :dr], zz[:, dr:]
                    th1 = prob.theta(z @ head, yb) / bsz
                    th0 = prob.theta(zsnap @ heads, yb) / bsz
                    thz1 = th1[:, None] * head
                    thz0 = th0[:, None] * heads
                    v_head = (z.T @ th1 + prob.lam * prob.reg_grad(head)
                              - zsnap.T @ th0 - prob.lam
                              * prob.reg_grad(heads)
                              + mu_head)
                    v_w2 = (self._bwd(h, thz1, 1) - self._bwd(hs_, thz0, 1)
                            + prob.lam * (prob.reg_grad(w2)
                                          - prob.reg_grad(w2s))
                            + mu_w2)
                    du1 = (thz1 @ w2.T) * (1.0 - h * h)
                    du0 = (thz0 @ w2s.T) * (1.0 - hs_ * hs_)
                    duu = self._bwd(xb, jnp.concatenate([du1, du0],
                                                        axis=1), 1)
                    v_w1 = (duu[:, :hid] - duu[:, hid:]
                            + prob.lam * (prob.reg_grad(w1)
                                          - prob.reg_grad(w1s))
                            + mu_w1)
                    v_b1 = (du1.sum(axis=0) - du0.sum(axis=0)
                            + prob.lam * (prob.reg_grad(b1)
                                          - prob.reg_grad(b1s))
                            + mu_b1)
                    slot = t % (tau + 1)
                    bw1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw1, v_w1,
                                                            slot, 0), bw1)
                    bb1 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bb1, v_b1,
                                                            slot, 0), bb1)
                    bw2 = jnp.where(
                        bl > 0,
                        jax.lax.dynamic_update_index_in_dim(bw2, v_w2,
                                                            slot, 0), bw2)
                    eff = jnp.maximum(t - (delay + ex), 0) % (tau + 1)
                    s_w1 = jax.lax.dynamic_index_in_dim(bw1, eff, 0,
                                                        keepdims=False)
                    s_b1 = jax.lax.dynamic_index_in_dim(bb1, eff, 0,
                                                        keepdims=False)
                    s_w2 = jax.lax.dynamic_index_in_dim(bw2, eff, 0,
                                                        keepdims=False)
                    w1 = w1 - lr * bl * maskp[:, None] * s_w1
                    b1 = b1 - lr * bl * trainp * s_b1
                    w2 = w2 - lr * bl * trainp * s_w2
                    head = head - lr * v_head       # dominator-fresh
                    gnorm = jnp.maximum(
                        jnp.maximum(jnp.max(jnp.abs(v_w1)),
                                    jnp.max(jnp.abs(v_b1))),
                        jnp.max(jnp.abs(v_w2)))
                    hstat = (healthy, live, jnp.max(jnp.abs(zc)), gnorm)
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), hstat

                (w1, b1, w2, head, bw1, bb1, bw2, _), hstats = \
                    jax.lax.scan(
                        body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                        (idx, mkeys, fwd_p, bwd_p, extra_p, corr_p))
                return (w1, b1, w2, head), (bw1, bb1, bw2), hstats

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, pq, pq_snap, muq, bufq, delays_q, fwdq, bwdq,
                      extraq, corruptq, maskq, trainq, y, lr, key, t0,
                      batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                w1s, b1s, w2s, headsq = pq_snap
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, w1s, b1s, w2s,
                               headsq, muq, bw1q, bb1q, bw2q, delays_q,
                               fwdq, bwdq, extraq, corruptq, maskq,
                               trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq, hs = self._epoch(
            f"deep_guarded_svrg{tau}_{int(bool(guard))}", build)(
            self.xs, pq, pq_snap, muq, bufq, delays_q, fwdq, bwdq, extraq,
            corruptq, self.maskq, self.trainq, self.y, lr, key, t0, batch,
            steps)
        return pq, bufq, t0 + steps, HealthStats(*hs)

    def deep_multi_delay_buffers(self, pq, tau: int):
        """Zero-initialized per-(party, dominator) encoder gradient ring
        buffers for :meth:`deep_multi_delayed_sgd_epoch`: each dominator's
        update stream ages in its own slab of the ring."""
        w1q, b1q, w2q, _ = pq
        m = self.layout.m
        q, dp, hid = w1q.shape
        dr = w2q.shape[2]
        return (jnp.zeros((q, tau + 1, dp, m, hid), jnp.float32),
                jnp.zeros((q, tau + 1, m, hid), jnp.float32),
                jnp.zeros((q, tau + 1, hid, m, dr), jnp.float32))

    def _ring_put_take_multi(self, bufs, grads, t, delay, tau: int):
        """Write the per-dominator gradient slabs at slot t and read each
        dominator's slab at its own t − d_{ℓ,j}; returns the new buffers
        and the dominator-summed stale encoder gradients."""
        def take(buf, eff_b, shape):
            return jnp.take_along_axis(
                buf, jnp.broadcast_to(eff_b, (1,) + shape), axis=0)[0]

        slot = t % (tau + 1)
        bufs = tuple(jax.lax.dynamic_update_index_in_dim(b, g, slot, 0)
                     for b, g in zip(bufs, grads))
        eff = jnp.maximum(t - delay, 0) % (tau + 1)       # (m,)
        gw1, gb1, gw2 = grads
        s_w1 = take(bufs[0], eff[None, None, :, None], gw1.shape).sum(axis=1)
        s_b1 = take(bufs[1], eff[None, :, None], gb1.shape).sum(axis=0)
        s_w2 = take(bufs[2], eff[None, None, :, None], gw2.shape).sum(axis=1)
        return bufs, (s_w1, s_b1, s_w2)

    def deep_multi_delayed_sgd_epoch(self, pq, bufq, t0, delays_qm, lr,
                                     key, batch: int, steps: int,
                                     tau: int):
        """Bounded-delay multi-dominator deep VFB²-SGD: every party holds
        m encoder-gradient ring buffers — one per dominator's update
        stream — and applies dominator j's Jacobian-transpose gradients of
        step t − d_{ℓ,j}; the replicated dominator-held head applies the
        summed head gradient fresh (delaying it would fork the replicas).
        ``staleness.train_deep_multi_delayed`` is the sequential oracle.
        ``bufq``: pytree from :meth:`deep_multi_delay_buffers`;
        ``delays_qm``: (q, m) int32."""
        m = self.layout.m

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, maskp,
                 trainp) = local                      # delay: (m,)
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t = carry
                    ibf, kt = inp
                    gw1, gb1, gw2, gh = self._deep_dom_grads(
                        self._rows(xp, ibf), y[ibf], w1, b1, w2, head, kt, m)
                    (bw1, bb1, bw2), (s_w1, s_b1, s_w2) = \
                        self._ring_put_take_multi(
                            (bw1, bb1, bw2), (gw1, gb1, gw2), t, delay, tau)
                    w1 = w1 - lr * maskp[:, None] * s_w1
                    b1 = b1 - lr * trainp * s_b1
                    w2 = w2 - lr * trainp * s_w2
                    head = head - lr * gh             # dominator-fresh
                    return (w1, b1, w2, head, bw1, bb1, bw2, t + 1), None

                (w1, b1, w2, head, bw1, bb1, bw2, _), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, bw1, bb1, bw2, t0),
                    (idx, masks))
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_qm, maskq, trainq, y, lr, key,
                      t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_qm, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        pq, bufq = self._epoch(f"deep_multi_delayed{tau}", build)(
            self.xs, pq, bufq, delays_qm, self.maskq, self.trainq, self.y,
            lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    # -- pipelined deep epochs: backward(t) ∥ encoder-forward(t+1), ONE
    # -- kernel invocation per interior step ----------------------------------
    #
    # The deep generalization of the pipelined schedule: round t's
    # Jacobian-transpose BUM application (Xᵀdu — the wide X-block pass)
    # and round t+1's layer-1 encoder forward (X@W₁) are data-independent,
    # so each interior scan step issues ONE split-batch fused kernel
    # invocation — rows = [X_{b_t}; X_{b_{t+1}}], Θ = du over the backward
    # rows, W = W₁ over the forward rows — and the narrow layer-2
    # contractions (h@W₂, hᵀϑ_z: hidden×d_rep operands, not X-block-sized)
    # stay in jnp so the scan body contains exactly one launch.  Launches
    # per epoch drop 2·steps → steps+1 (forward-only prologue, fused
    # interior, backward-only epilogue; jaxpr-audited in
    # bench_engine.run_deep_pipelined).  Both halves execute from the same
    # pre-update iterate, so round t+1's activations (h, z) come from
    # encoder params one update old — a τ = 1 bounded-delay execution;
    # ``deep_vfl.train_deep_vfl(..., pipelined=True)`` is the exact
    # sequential oracle (the local Jacobians are evaluated at the stale
    # activations, ϑ and the regularizers at the application-time params,
    # and the dominator-held head is always fresh).

    def _deep_pipe_tail(self, h, agg, yb, b1, w2, head, mdom: int):
        """Application-time quantities of a pipelined deep round from the
        stale activations: returns (du, g_b1, g_w2, g_head) — everything
        except the X-block contraction that rides the fused launch."""
        prob = self.problem
        bsz = yb.shape[0] // mdom
        th_l = prob.theta(agg @ head, yb) / bsz
        th_z = th_l[:, None] * head
        g_head = agg.T @ th_l + mdom * prob.lam * prob.reg_grad(head)
        g_w2 = h.T @ th_z + mdom * prob.lam * prob.reg_grad(w2)
        du = (th_z @ w2.T) * (1.0 - h * h)
        g_b1 = du.sum(axis=0) + mdom * prob.lam * prob.reg_grad(b1)
        return du, g_b1, g_w2, g_head

    def _deep_pipe_sgd_build(self, mdom: int):
        prob = self.problem

        def build():
            def party(local, shared):
                xp, w1, b1, w2, head, maskp, trainp = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                u0 = self._fwd(xb0, w1)               # prologue launch
                h0 = jnp.tanh(u0 + b1)
                agg0 = self._agg(h0 @ w2, masks[0])

                def apply(w1, b1, w2, head, g_w1, g_b1, g_w2, g_head):
                    return (w1 - lr * maskp[:, None] * g_w1,
                            b1 - lr * trainp * g_b1,
                            w2 - lr * trainp * g_w2,
                            head - lr * g_head)

                def body(carry, inp):
                    w1, b1, w2, head, xb, ib, h, agg = carry
                    ib_next, kt = inp
                    du, g_b1, g_w2, g_head = self._deep_pipe_tail(
                        h, agg, y[ib], b1, w2, head, mdom)
                    xb_next = self._rows(xp, ib_next)
                    u_next, g1 = self._pipe(xb, xb_next, w1, du, 1)
                    g_w1 = g1 + mdom * prob.lam * prob.reg_grad(w1)
                    h_next = jnp.tanh(u_next + b1)    # pre-update params
                    agg_next = self._agg(h_next @ w2, kt)
                    w1, b1, w2, head = apply(w1, b1, w2, head, g_w1, g_b1,
                                             g_w2, g_head)
                    return (w1, b1, w2, head, xb_next, ib_next, h_next,
                            agg_next), None

                (w1, b1, w2, head, xb, ib, h, agg), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, xb0, ib0, h0, agg0),
                    (idx[1:], masks[1:]))
                du, g_b1, g_w2, g_head = self._deep_pipe_tail(
                    h, agg, y[ib], b1, w2, head, mdom)    # epilogue
                g_w1 = self._bwd(xb, du, 1) \
                    + mdom * prob.lam * prob.reg_grad(w1)
                return apply(w1, b1, w2, head, g_w1, g_b1, g_w2, g_head)

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq"))
            def epoch(xs, pq, maskq, trainq, y, lr, key, batch, steps):
                idx = _sample_indices(key, y.shape[0], mdom * batch, steps)
                w1q, b1q, w2q, headq = pq
                return mapped((xs, w1q, b1q, w2q, headq, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return build

    def deep_pipelined_sgd_epoch(self, pq, lr, key, batch: int,
                                 steps: int):
        """Pipelined deep VFB²-SGD epoch (see section comment); pinned
        against ``deep_vfl.train_deep_vfl(..., pipelined=True)``."""
        return self._epoch("deep_pipelined_sgd",
                           self._deep_pipe_sgd_build(1))(
            self.xs, pq, self.maskq, self.trainq, self.y, lr, key, batch,
            steps)

    def deep_multi_pipelined_sgd_epoch(self, pq, lr, key, batch: int,
                                       steps: int):
        """Pipelined multi-dominator deep VFB²-SGD: the m dominators'
        concatenated minibatches ride both halves of the one split-batch
        invocation (the summed du block next to the next round's
        concatenated layer-1 forward)."""
        return self._epoch("deep_multi_pipelined_sgd",
                           self._deep_pipe_sgd_build(self.layout.m))(
            self.xs, pq, self.maskq, self.trainq, self.y, lr, key, batch,
            steps)

    def _deep_pipe_svrg_build(self, mdom: int):
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, w1s, b1s, w2s, heads, mu, maskp,
                 trainp) = local
                y, lr, idx, mkeys = shared
                masks = self._masks(mkeys,
                                    idx.shape[1:] + (2 * head.shape[0],))
                mu_w1, mu_b1, mu_w2, mu_head = mu
                hid = w1.shape[1]
                dr = head.shape[0]

                def fwd_pair(uu, kt):
                    """Both sides' activations + ONE masked aggregation of
                    both (·, d_rep) partial sets, from the shared layer-1
                    pass ``uu = X[W₁|W₁ˢ]``."""
                    h = jnp.tanh(uu[:, :hid] + b1)
                    hs = jnp.tanh(uu[:, hid:] + b1s)
                    zz = self._agg(jnp.concatenate([h @ w2, hs @ w2s],
                                                   axis=1), kt)
                    return h, hs, zz

                def tail(h, hs, zz, yb, b1, w2, head):
                    """Application-time SVRG quantities from the stale
                    activation pair, at the *current* live params (the
                    snapshot side is constant, so its stale read equals
                    the fresh one)."""
                    bsz = yb.shape[0] // mdom
                    z, zs = zz[:, :dr], zz[:, dr:]
                    th1 = prob.theta(z @ head, yb) / bsz
                    th0 = prob.theta(zs @ heads, yb) / bsz
                    thz1 = th1[:, None] * head
                    thz0 = th0[:, None] * heads
                    v_head = (z.T @ th1 - zs.T @ th0
                              + mdom * prob.lam * (prob.reg_grad(head)
                                                   - prob.reg_grad(heads))
                              + mdom * mu_head)
                    v_w2 = (h.T @ thz1 - hs.T @ thz0
                            + mdom * prob.lam * (prob.reg_grad(w2)
                                                 - prob.reg_grad(w2s))
                            + mdom * mu_w2)
                    du1 = (thz1 @ w2.T) * (1.0 - h * h)
                    du0 = (thz0 @ w2s.T) * (1.0 - hs * hs)
                    v_b1 = (du1.sum(axis=0) - du0.sum(axis=0)
                            + mdom * prob.lam * (prob.reg_grad(b1)
                                                 - prob.reg_grad(b1s))
                            + mdom * mu_b1)
                    return du1, du0, v_b1, v_w2, v_head

                def v_w1_of(duu, w1):
                    return (duu[:, :hid] - duu[:, hid:]
                            + mdom * prob.lam * (prob.reg_grad(w1)
                                                 - prob.reg_grad(w1s))
                            + mdom * mu_w1)

                def apply(w1, b1, w2, head, v_w1, v_b1, v_w2, v_head):
                    return (w1 - lr * maskp[:, None] * v_w1,
                            b1 - lr * trainp * v_b1,
                            w2 - lr * trainp * v_w2,
                            head - lr * v_head)

                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                wpair = jnp.concatenate([w1, w1s], axis=1)
                h0, hs0, zz0 = fwd_pair(self._fwd(xb0, wpair), masks[0])

                def body(carry, inp):
                    w1, b1, w2, head, xb, ib, h, hs, zz = carry
                    ib_next, kt = inp
                    du1, du0, v_b1, v_w2, v_head = tail(h, hs, zz, y[ib],
                                                        b1, w2, head)
                    xb_next = self._rows(xp, ib_next)
                    uu_next, duu = self._pipe(
                        xb, xb_next, jnp.concatenate([w1, w1s], axis=1),
                        jnp.concatenate([du1, du0], axis=1), 1)
                    v_w1 = v_w1_of(duu, w1)
                    # pre-update forward for round t+1 (both sides)
                    h_next = jnp.tanh(uu_next[:, :hid] + b1)
                    hs_next = jnp.tanh(uu_next[:, hid:] + b1s)
                    zz_next = self._agg(jnp.concatenate(
                        [h_next @ w2, hs_next @ w2s], axis=1), kt)
                    w1, b1, w2, head = apply(w1, b1, w2, head, v_w1, v_b1,
                                             v_w2, v_head)
                    return (w1, b1, w2, head, xb_next, ib_next, h_next,
                            hs_next, zz_next), None

                (w1, b1, w2, head, xb, ib, h, hs, zz), _ = jax.lax.scan(
                    body, (w1, b1, w2, head, xb0, ib0, h0, hs0, zz0),
                    (idx[1:], masks[1:]))
                du1, du0, v_b1, v_w2, v_head = tail(h, hs, zz, y[ib], b1,
                                                    w2, head)
                duu = self._bwd(xb, jnp.concatenate([du1, du0], axis=1), 1)
                return apply(w1, b1, w2, head, v_w1_of(duu, w1), v_b1,
                             v_w2, v_head)

            mapped = self._bind(party)

            @functools.partial(jax.jit, static_argnames=("batch", "steps"))
            def epoch(xs, pq, pq_snap, muq, maskq, trainq, y, lr, key,
                      batch, steps):
                idx = _sample_indices(key, y.shape[0], mdom * batch, steps)
                w1q, b1q, w2q, headq = pq
                w1s, b1s, w2s, headsq = pq_snap
                return mapped((xs, w1q, b1q, w2q, headq, w1s, b1s, w2s,
                               headsq, muq, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps)))

            return epoch

        return build

    def deep_pipelined_svrg_epoch(self, pq, pq_snap, muq, lr, key,
                                  batch: int, steps: int):
        """Pipelined deep VFB²-SVRG inner loop: the iterate's and the
        snapshot's layer-1 passes share the single M = 2·hidden
        split-batch invocation per interior step (du₁ beside du₀ on the
        backward rows, [W₁|W₁ˢ] on the forward rows); the snapshot column
        is constant, so its τ = 1 stale read is delay-free."""
        return self._epoch("deep_pipelined_svrg",
                           self._deep_pipe_svrg_build(1))(
            self.xs, pq, pq_snap, muq, self.maskq, self.trainq, self.y,
            lr, key, batch, steps)

    def deep_multi_pipelined_svrg_epoch(self, pq, pq_snap, muq, lr, key,
                                        batch: int, steps: int):
        """Pipelined multi-dominator deep VFB²-SVRG (m concatenated
        minibatches through the shared M = 2·hidden invocation)."""
        return self._epoch("deep_multi_pipelined_svrg",
                           self._deep_pipe_svrg_build(self.layout.m))(
            self.xs, pq, pq_snap, muq, self.maskq, self.trainq, self.y,
            lr, key, batch, steps)

    def _deep_pipe_dom_tail(self, h, agg, yb, b1, w2, head, m: int):
        """Per-dominator application-time quantities of a pipelined
        multi-dominator deep round (jnp-only — the scan body must issue no
        launch besides the fused one): returns (du (m·B, hid),
        g_b1 (m, hid), g_w2 (hid, m, dr), g_head (dr,)) with per-stream
        λ∇g on the encoder slabs and the fresh summed head gradient."""
        prob = self.problem
        b = yb.shape[0] // m
        th_l = prob.theta(agg @ head, yb) / b
        th_z = th_l[:, None] * head
        g_head = agg.T @ th_l + m * prob.lam * prob.reg_grad(head)
        du = (th_z @ w2.T) * (1.0 - h * h)
        g_b1 = du.reshape(m, b, -1).sum(axis=1) \
            + prob.lam * prob.reg_grad(b1)[None, :]
        g_w2 = _seg_contract(h, th_z, m) \
            + prob.lam * prob.reg_grad(w2)[:, None, :]
        return du, g_b1, g_w2, g_head

    def _deep_pipe_delayed_build(self, tau: int):
        prob = self.problem

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, maskp,
                 trainp) = local
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                h0 = jnp.tanh(self._fwd(xb0, w1) + b1)
                agg0 = self._agg(h0 @ w2, masks[0])

                def ring_apply(w1, b1, w2, head, bufs, t, g_w1, g_b1,
                               g_w2, g_head):
                    slot = t % (tau + 1)
                    bufs = tuple(
                        jax.lax.dynamic_update_index_in_dim(bf, g, slot, 0)
                        for bf, g in zip(bufs, (g_w1, g_b1, g_w2)))
                    eff = jnp.maximum(t - delay, 0) % (tau + 1)
                    s_w1, s_b1, s_w2 = (
                        jax.lax.dynamic_index_in_dim(bf, eff, 0,
                                                     keepdims=False)
                        for bf in bufs)
                    return (w1 - lr * maskp[:, None] * s_w1,
                            b1 - lr * trainp * s_b1,
                            w2 - lr * trainp * s_w2,
                            head - lr * g_head,       # dominator-fresh
                            bufs, t + 1)

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t, xb, ib, h, agg \
                        = carry
                    ib_next, kt = inp
                    du, g_b1, g_w2, g_head = self._deep_pipe_tail(
                        h, agg, y[ib], b1, w2, head, 1)
                    xb_next = self._rows(xp, ib_next)
                    u_next, g1 = self._pipe(xb, xb_next, w1, du, 1)
                    g_w1 = g1 + prob.lam * prob.reg_grad(w1)
                    h_next = jnp.tanh(u_next + b1)
                    agg_next = self._agg(h_next @ w2, kt)
                    w1, b1, w2, head, (bw1, bb1, bw2), t = ring_apply(
                        w1, b1, w2, head, (bw1, bb1, bw2), t, g_w1, g_b1,
                        g_w2, g_head)
                    return (w1, b1, w2, head, bw1, bb1, bw2, t, xb_next,
                            ib_next, h_next, agg_next), None

                (w1, b1, w2, head, bw1, bb1, bw2, t, xb, ib, h, agg), _ \
                    = jax.lax.scan(
                        body, (w1, b1, w2, head, bw1, bb1, bw2, t0, xb0,
                               ib0, h0, agg0), (idx[1:], masks[1:]))
                du, g_b1, g_w2, g_head = self._deep_pipe_tail(
                    h, agg, y[ib], b1, w2, head, 1)       # epilogue
                g_w1 = self._bwd(xb, du, 1) + prob.lam * prob.reg_grad(w1)
                w1, b1, w2, head, (bw1, bb1, bw2), _ = ring_apply(
                    w1, b1, w2, head, (bw1, bb1, bw2), t, g_w1, g_b1,
                    g_w2, g_head)
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_q, maskq, trainq, y, lr, key,
                      t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_q, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        return build

    def deep_pipelined_delayed_sgd_epoch(self, pq, bufq, t0, delays_q, lr,
                                         key, batch: int, steps: int,
                                         tau: int):
        """Pipelined bounded-delay deep VFB²-SGD: the stale-read encoder
        gradients of each round enter the per-party ring buffers and age
        under the delay schedule (total delay τ + 1); the head stays
        dominator-fresh.  Same state layout as
        :meth:`deep_delayed_sgd_epoch`;
        ``staleness.train_deep_delayed(..., pipelined=True)`` is the
        oracle."""
        pq, bufq = self._epoch(f"deep_pipelined_delayed{tau}",
                               self._deep_pipe_delayed_build(tau))(
            self.xs, pq, bufq, delays_q, self.maskq, self.trainq, self.y,
            lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    def _deep_multi_pipe_delayed_build(self, tau: int):
        prob = self.problem
        m = self.layout.m

        def build():
            def party(local, shared):
                (xp, w1, b1, w2, head, bw1, bb1, bw2, delay, maskp,
                 trainp) = local                      # delay: (m,)
                y, lr, idx, mkeys, t0 = shared
                masks = self._masks(mkeys, idx.shape[1:] + head.shape)
                ib0 = idx[0]
                xb0 = self._rows(xp, ib0)
                h0 = jnp.tanh(self._fwd(xb0, w1) + b1)
                agg0 = self._agg(h0 @ w2, masks[0])

                def ring_apply(w1, b1, w2, head, bufs, t, gw1, gb1, gw2,
                               gh):
                    bufs, (s_w1, s_b1, s_w2) = self._ring_put_take_multi(
                        bufs, (gw1, gb1, gw2), t, delay, tau)
                    return (w1 - lr * maskp[:, None] * s_w1,
                            b1 - lr * trainp * s_b1,
                            w2 - lr * trainp * s_w2,
                            head - lr * gh, bufs, t + 1)

                def body(carry, inp):
                    w1, b1, w2, head, bw1, bb1, bw2, t, xb, ib, h, agg \
                        = carry
                    ib_next, kt = inp
                    du, gb1, gw2, gh = self._deep_pipe_dom_tail(
                        h, agg, y[ib], b1, w2, head, m)
                    xb_next = self._rows(xp, ib_next)
                    # Mθ = m·hidden block-diagonal du beside the Mw =
                    # hidden forward — the split-batch form's vector-valued
                    # per-side column counts
                    u_next, g1 = self._pipe_doms_wide(xb, xb_next, w1, du,
                                                      m, 1)
                    gw1 = g1 + prob.lam * prob.reg_grad(w1)[:, None, :]
                    h_next = jnp.tanh(u_next + b1)
                    agg_next = self._agg(h_next @ w2, kt)
                    w1, b1, w2, head, (bw1, bb1, bw2), t = ring_apply(
                        w1, b1, w2, head, (bw1, bb1, bw2), t, gw1, gb1,
                        gw2, gh)
                    return (w1, b1, w2, head, bw1, bb1, bw2, t, xb_next,
                            ib_next, h_next, agg_next), None

                (w1, b1, w2, head, bw1, bb1, bw2, t, xb, ib, h, agg), _ \
                    = jax.lax.scan(
                        body, (w1, b1, w2, head, bw1, bb1, bw2, t0, xb0,
                               ib0, h0, agg0), (idx[1:], masks[1:]))
                du, gb1, gw2, gh = self._deep_pipe_dom_tail(
                    h, agg, y[ib], b1, w2, head, m)       # epilogue
                gw1 = self._bwd_doms_wide(xb, du, m, 1) \
                    + prob.lam * prob.reg_grad(w1)[:, None, :]
                w1, b1, w2, head, (bw1, bb1, bw2), _ = ring_apply(
                    w1, b1, w2, head, (bw1, bb1, bw2), t, gw1, gb1, gw2,
                    gh)
                return (w1, b1, w2, head), (bw1, bb1, bw2)

            mapped = self._bind(party)

            @functools.partial(jax.jit,
                               static_argnames=("batch", "steps"),
                               donate_argnames=self._donate("pq", "bufq"))
            def epoch(xs, pq, bufq, delays_qm, maskq, trainq, y, lr, key,
                      t0, batch, steps):
                idx = _sample_indices(key, y.shape[0], m * batch, steps)
                w1q, b1q, w2q, headq = pq
                bw1q, bb1q, bw2q = bufq
                return mapped((xs, w1q, b1q, w2q, headq, bw1q, bb1q, bw2q,
                               delays_qm, maskq, trainq),
                              (y, lr, idx, self._keys(key, steps), t0))

            return epoch

        return build

    def deep_multi_pipelined_delayed_sgd_epoch(self, pq, bufq, t0,
                                               delays_qm, lr, key,
                                               batch: int, steps: int,
                                               tau: int):
        """Pipelined bounded-delay multi-dominator deep VFB²-SGD: the m
        dominators' stale-read Jacobian-transpose gradient slabs (Mθ =
        m·hidden block-diagonal columns of the one split-batch invocation)
        age in per-(party, dominator) ring buffers; heads stay fresh.
        ``staleness.train_deep_multi_delayed(..., pipelined=True)`` is the
        oracle; same state layout as
        :meth:`deep_multi_delayed_sgd_epoch`."""
        pq, bufq = self._epoch(f"deep_multi_pipelined_delayed{tau}",
                               self._deep_multi_pipe_delayed_build(tau))(
            self.xs, pq, bufq, delays_qm, self.maskq, self.trainq, self.y,
            lr, key, t0, batch, steps)
        return pq, bufq, t0 + steps

    # -- introspection -------------------------------------------------------

    def sgd_epoch_jaxpr(self, wq, lr, key, batch: int, steps: int):
        """The whole-epoch jaxpr (for auditing that no host round-trips —
        callbacks/infeed/transfers — exist inside the fused program)."""
        self.sgd_epoch(wq, lr, key, batch, steps)   # ensure built
        fn = self._jitted["sgd"]
        return jax.make_jaxpr(
            lambda xs, w: fn(xs, w, self.maskq, self.y, lr, key,
                             batch=batch, steps=steps))(self.xs, wq)

    def pipelined_sgd_epoch_jaxpr(self, wq, lr, key, batch: int,
                                  steps: int):
        """The pipelined epoch's jaxpr — the benchmark audits both that no
        host-transfer primitive exists and that the scan body contains
        exactly ONE kernel invocation (vs two on the sequential path)."""
        self.pipelined_sgd_epoch(wq, lr, key, batch, steps)   # ensure built
        fn = self._jitted["pipelined_sgd"]
        return jax.make_jaxpr(
            lambda xs, w: fn(xs, w, self.maskq, self.y, lr, key,
                             batch=batch, steps=steps))(self.xs, wq)

    def deep_sgd_epoch_jaxpr(self, pq, lr, key, batch: int, steps: int):
        """The deep epoch's jaxpr — audited for zero host-transfer
        primitives (the whole nonlinear epoch must stay on device)."""
        self.deep_sgd_epoch(pq, lr, key, batch, steps)   # ensure built
        fn = self._jitted["deep_sgd"]
        return jax.make_jaxpr(
            lambda xs, p: fn(xs, p, self.maskq, self.trainq, self.y, lr,
                             key, batch=batch, steps=steps))(self.xs, pq)

    def deep_pipelined_sgd_epoch_jaxpr(self, pq, lr, key, batch: int,
                                       steps: int):
        """The pipelined deep epoch's jaxpr — the benchmark audits that
        the scan body contains exactly ONE kernel invocation (the
        split-batch layer-1 fused pass; sequential deep bodies launch 4:
        two forward + two backward encoder-layer contractions) and zero
        host-transfer primitives."""
        self.deep_pipelined_sgd_epoch(pq, lr, key, batch, steps)
        fn = self._jitted["deep_pipelined_sgd"]
        return jax.make_jaxpr(
            lambda xs, p: fn(xs, p, self.maskq, self.trainq, self.y, lr,
                             key, batch=batch, steps=steps))(self.xs, pq)

    def faulted_sgd_epoch_jaxpr(self, wq, bufq, t0, delays_q, fwdq, bwdq,
                                extraq, lr, key, batch: int, steps: int,
                                tau: int):
        """The faulted epoch's jaxpr — the benchmark audits that the
        whole membership-masked, survivor-aggregated epoch stays on
        device (zero host-transfer primitives): fault handling must not
        smuggle host round-trips into the hot path."""
        self.faulted_sgd_epoch(wq, bufq, t0, delays_q, fwdq, bwdq, extraq,
                               lr, key, batch, steps, tau)   # ensure built
        fn = self._jitted[f"faulted_sgd{tau}"]
        return jax.make_jaxpr(
            lambda xs, w, b: fn(xs, w, b, delays_q, fwdq, bwdq, extraq,
                                self.maskq, self.y, lr, key, t0,
                                batch=batch, steps=steps))(
            self.xs, wq, bufq)

    def guarded_sgd_epoch_jaxpr(self, wq, bufq, t0, delays_q, fwdq, bwdq,
                                extraq, corruptq, lr, key, batch: int,
                                steps: int, tau: int, guard: bool = True):
        """The guarded epoch's jaxpr — the guards bench audits that
        corrupt-value injection, the finiteness quarantine, and the
        HealthStats telemetry all stay on device (zero host-transfer
        primitives) and that the epoch is still ONE dispatch: the
        telemetry accumulates as scan outputs, never as mid-epoch
        fetches."""
        self.guarded_sgd_epoch(wq, bufq, t0, delays_q, fwdq, bwdq, extraq,
                               corruptq, lr, key, batch, steps, tau,
                               guard=guard)                  # ensure built
        fn = self._jitted[f"guarded_sgd{tau}_{int(bool(guard))}"]
        return jax.make_jaxpr(
            lambda xs, w, b: fn(xs, w, b, delays_q, fwdq, bwdq, extraq,
                                corruptq, self.maskq, self.y, lr, key, t0,
                                batch=batch, steps=steps))(
            self.xs, wq, bufq)

    # -- boundary helpers ----------------------------------------------------

    def place(self, a, party: bool = True) -> jax.Array:
        """Put a host or device array where the epochs read it.

        Bound to a mesh, a party-stacked array (leading logical-party
        axis) is sharded on the party axis — each device holds only its
        own parties' rows, in the contiguous blocks the slot mapping of
        :meth:`_bind` reads — and anything else (``party=False``) is
        replicated.  Without a mesh it goes to the default device."""
        if self.mesh is None:
            return jnp.asarray(a)
        spec = P(self.cfg.axis) if party else P()
        return jax.device_put(a, NamedSharding(self.mesh, spec))

    def pack_w(self, w) -> jax.Array:
        return self.place(pack_vec(np.asarray(w), self.layout))

    def unpack_w(self, wq) -> np.ndarray:
        return unpack_vec(wq, self.layout)

    def pack_deep(self, params):
        return tuple(self.place(a)
                     for a in pack_deep_params(params, self.layout))

    def unpack_deep(self, pq):
        return unpack_deep_params(pq, self.layout)

    @staticmethod
    def _read_objective(enqueue) -> float:
        """``float(enqueue())`` as the ``vfb2.objective`` span: the eager
        ops' dispatch (``.enqueue``), then the wait for the device and the
        copy of the scalar to the host (``.fetch``)."""
        with tracing.span("vfb2.objective"):
            with tracing.span("vfb2.objective.enqueue"):
                value = enqueue()
            with tracing.span("vfb2.objective.fetch"):
                return float(value)

    def deep_objective(self, pq) -> float:
        """Full deep objective (one device sync; per-epoch telemetry).

        The padded w1 rows are zero and every shipped regularizer maps
        0 → 0, so summing ``reg`` over the padded stack is exact; the
        replicated head is counted once."""
        prob = self.problem
        w1q, b1q, w2q, headq = pq

        def enqueue():
            with jax.default_matmul_precision("highest"):  # as F32Program
                h = jnp.tanh(jnp.einsum("qnd,qdh->qnh", self.xs, w1q)
                             + b1q[:, None, :])
                z = jnp.einsum("qnh,qhr->nr", h, w2q)
                logit = z @ headq[0]
            regv = (jnp.sum(prob.reg(w1q)) + jnp.sum(prob.reg(b1q))
                    + jnp.sum(prob.reg(w2q)) + jnp.sum(prob.reg(headq[0])))
            return jnp.mean(prob.loss(logit, self.y)) + prob.lam * regv

        return self._read_objective(enqueue)

    def objective(self, wq) -> float:
        """Full objective (one device sync; for per-epoch telemetry).

        The padded coordinates are zero and every shipped regularizer maps
        0 → 0, so summing ``reg`` over the padded stack is exact."""
        prob = self.problem

        def enqueue():
            with jax.default_matmul_precision("highest"):  # as F32Program
                agg = jnp.einsum("qnd,qd->n", self.xs, wq)
            return (jnp.mean(prob.loss(agg, self.y))
                    + prob.lam * jnp.sum(prob.reg(wq)))

        return self._read_objective(enqueue)
