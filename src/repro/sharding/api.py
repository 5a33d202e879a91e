"""Runtime context: mesh, axis roles, sharding-constraint helpers.

Axis roles (fixed names across the framework):
  * "pod"   — inter-pod data parallelism = the paper's *upper-level*
              (distributed-memory, between active-party groups);
  * "data"  — intra-pod batch parallelism = the paper's *lower-level*
              (shared-memory collaborative threads within a party; the
              fused engine also binds it as the sample-parallel axis of
              its (party × batch) 2D mesh — see :class:`PartyMesh`);
  * "model" — the party axis: vertical feature/vocab partition, also
              used for TP/expert/sequence sharding.  Its size is
              **dynamic** (``PartyLayout.q`` / the mesh shape — nothing
              is hard-coded): one party per mesh slot in the flat
              engine layout, or ``slots`` physical islands each packing
              ``parties_per_slot`` *logical* parties when the engine is
              given a :class:`PartyMesh`.

Logical vs physical party axis
------------------------------
Historically the engine assumed q <= devices: the "model" axis WAS the
party axis.  :class:`PartyMesh` splits the two: the *logical* party
axis (size ``q``) factors as ``slots × parties_per_slot``, with the
outer factor mapped onto the physical "model" mesh axis (shard_map —
or an emulating vmap on one device) and the inner factor bound as a
vmapped named axis *inside* each slot.  Collectives address the pair
``(outer, inner)`` of named axes; masked secure aggregation becomes
hierarchical (intra-slot reduce, then cross-slot two_tree/ring — see
``core.secure_agg.secure_psum_hier``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` with ``check_vma`` off unless asked for — the
    one spelling every shard_map call site in the repo uses."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


@dataclasses.dataclass(frozen=True)
class PartyMesh:
    """Factorization of the logical party axis over a physical mesh.

    ``q = slots × parties_per_slot`` logical parties: the outer factor
    (``slots``) is the physical party dimension — one ``shard_map``
    island per slot when ``mesh`` is given, a vmapped named axis on one
    device otherwise — and the inner factor rides a vmapped named axis
    (``party_axis``) *inside* each slot, so q can exceed the device
    count arbitrarily.  ``data_shards`` adds the second (sample-
    parallel) mesh dimension: each data shard processes a disjoint
    slice of every minibatch and the per-party gradients are psum'd
    over ``data_axis`` — the (party × batch) 2D mesh.

    Security note: data shards of one party live in that party's trust
    domain (the paper's lower level — collaborative workers *within* a
    party), so party-local values may cross ``data_axis`` unmasked;
    every value crossing ``axis``/``party_axis`` remains mask-offset
    with streams ``fold_in``-distinct per *logical* party (the taint
    lint enforces this — see ``repro.analysis.taint``).

    ``mesh=None`` runs the single-device emulation (vmap with named
    axes — identical collective semantics, as everywhere else in the
    engine); a supplied mesh must carry ``axis`` of size ``slots`` and,
    when ``data_shards > 1``, ``data_axis`` of size ``data_shards``.
    """

    q: int                          # logical party count
    slots: int                      # physical party-axis width
    mesh: Optional[Mesh] = None     # device mesh; None = vmap emulation
    axis: str = "model"             # outer (slot) named axis
    party_axis: str = "party"       # inner (packed parties) named axis
    data_shards: int = 1            # sample-parallel width
    data_axis: str = "data"         # batch named axis

    def __post_init__(self):
        if self.q < 1 or self.slots < 1 or self.data_shards < 1:
            raise ValueError(
                f"PartyMesh sizes must be >= 1; got q={self.q}, "
                f"slots={self.slots}, data_shards={self.data_shards}")
        if self.q % self.slots != 0:
            raise ValueError(
                f"q={self.q} must divide evenly into slots={self.slots} "
                f"islands (got remainder {self.q % self.slots})")
        if self.axis == self.party_axis or self.data_axis in (
                self.axis, self.party_axis):
            raise ValueError(
                f"axis names must be distinct; got axis={self.axis!r}, "
                f"party_axis={self.party_axis!r}, "
                f"data_axis={self.data_axis!r}")
        if self.mesh is not None:
            shape = dict(self.mesh.shape)
            if shape.get(self.axis) != self.slots:
                raise ValueError(
                    f"mesh must carry a {self.axis!r} axis of size "
                    f"slots={self.slots}; got axes {shape}")
            if self.data_shards > 1 and \
                    shape.get(self.data_axis) != self.data_shards:
                raise ValueError(
                    f"mesh must carry a {self.data_axis!r} axis of size "
                    f"data_shards={self.data_shards}; got axes {shape}")

    @property
    def parties_per_slot(self) -> int:
        return self.q // self.slots

    @property
    def packed(self) -> bool:
        """More than one logical party per slot (hierarchical agg)."""
        return self.parties_per_slot > 1


@dataclasses.dataclass(frozen=True)
class Runtime:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    attn_chunk: int = 1024
    loss_chunk: int = 512
    remat: bool = True
    scan_layers: bool = True
    unroll_layers: Optional[int] = None   # roofline tool: lower L-layer unrolled
    secure_embed: bool = True
    mask_scale: float = 1.0
    schedule_faithful: bool = False
    secure_mode: str = "two_tree"   # or "ring_masks" (see §Perf)
    scan_impl: str = "reference"          # "pallas" on real TPU
    attn_impl: str = "reference"
    # axes that shard the decode KV-cache sequence dim (hillclimb lever)
    cache_seq_axes: Tuple[str, ...] = ("model",)
    # MoE dispatch: shard capacity dim over data axis as well
    moe_capacity_data_sharded: bool = True
    # MoE dispatch strategy: "replicated" (baseline) | "alltoall" (§Perf)
    moe_dispatch: str = "replicated"
    # Megatron-style sequence parallelism for norm/residual segments (§Perf)
    seq_parallel_norms: bool = False

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    def head_axis(self, n_heads: int) -> Optional[str]:
        return self.model_axis if n_heads % self.model_size == 0 else None

    def batch_size_divisible(self, b: int) -> bool:
        tot = 1
        for a in self.batch_axes:
            tot *= self.mesh.shape[a]
        return b % tot == 0 and tot > 1

    def bspec(self, b: int):
        """Batch partition entry (None if batch cannot be sharded)."""
        return self.batch_axes if (self.batch_axes and
                                   self.batch_size_divisible(b)) else None


def use_runtime(rt: Runtime):
    @contextlib.contextmanager
    def cm():
        prev = getattr(_STATE, "rt", None)
        _STATE.rt = rt
        try:
            yield rt
        finally:
            _STATE.rt = prev
    return cm()


def current_runtime() -> Runtime:
    rt = getattr(_STATE, "rt", None)
    if rt is None:
        raise RuntimeError("no Runtime active; wrap with use_runtime(...)")
    return rt


def current_mesh() -> Mesh:
    return current_runtime().mesh


def shard(x, *spec):
    """Place ``x`` as ``spec`` on the active runtime's mesh.

    Auto axes take a ``with_sharding_constraint`` hint; Explicit axes
    (``jax.make_mesh``'s default) are part of the value's type and are
    changed with ``jax.sharding.reshard``."""
    rt = getattr(_STATE, "rt", None)
    if rt is None:
        return x
    sharding = NamedSharding(rt.mesh, P(*spec))
    if any(t == jax.sharding.AxisType.Explicit
           for t in rt.mesh.axis_types):
        return jax.sharding.reshard(x, sharding)
    return jax.lax.with_sharding_constraint(x, sharding)


def single_device_runtime(**kw) -> Runtime:
    """1×1×1 mesh with the canonical axis names (CPU tests/smoke)."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = Mesh(dev, ("pod", "data", "model"))
    kw.setdefault("batch_axes", ("data",))
    return Runtime(mesh=mesh, **kw)
