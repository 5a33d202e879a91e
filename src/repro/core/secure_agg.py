"""Secure aggregation (paper Algorithm 1).

Two executable forms of the same protocol:

* ``secure_aggregate_host`` — the faithful reference: python/numpy values,
  explicit masks, explicit tree schedules, and a transcript of every message
  each party sees (used by the security property tests to verify that no
  transmitted value reveals a raw partial product).

* ``secure_psum`` — the TPU form: inside ``shard_map`` over the party
  ("model") mesh axis, each shard adds a per-party mask, the masked values
  are reduced with tree schedule T1 realized as ``lax.psum`` (XLA's
  reduction is schedule-free; we additionally provide
  ``tree_psum_collective_permute`` which replays the exact T1/T2 round
  structure with ``lax.ppermute`` for schedule-faithful lowering), the
  masks are reduced over the *significantly different* T2, and the mask sum
  is subtracted.  Output step (paper): ``wᵀx = ξ1 − ξ2``.  Each flat form
  is a draw (key, party index, shape → δ: ``two_tree_mask``,
  ``ring_mask``) then an apply (partial, δ → aggregate:
  ``two_tree_apply``, ``ring_apply``), so a scanned epoch can draw all of
  its steps' masks in one batched op before the scan.

The masking invariants the TPU form relies on — every value crossing the
party axis is mask-offset, masks are seeded per-party-distinct
(``fold_in(key, axis_index)``), and membership-dependent epochs re-key on
the alive-set fingerprint — are machine-checked statically:
``repro.analysis.taint`` runs a leakage taint pass over the per-party
jaxprs of every engine epoch (see ``analysis/INVARIANTS.json`` and the CI
lint job), so a refactor here that weakens a mask fails the lint gate
before it ever runs.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trees as trees_lib


@dataclasses.dataclass
class AggTranscript:
    """Every value each party observed during the protocol (for audits)."""

    # messages[p] = list of (tag, value) pairs party p received
    messages: List[List[Tuple[str, np.ndarray]]]

    def seen_by(self, party: int) -> List[np.ndarray]:
        return [v for _, v in self.messages[party]]


def secure_aggregate_host(
    partials: Sequence[np.ndarray],
    rng: np.random.Generator,
    t1: trees_lib.ReductionTree | None = None,
    t2: trees_lib.ReductionTree | None = None,
    mask_scale: float = 1.0,
) -> Tuple[np.ndarray, AggTranscript]:
    """Algorithm 1 on host values. Returns (sum, transcript).

    ``partials[ℓ]`` is party ℓ's local ``w_{G_ℓ}ᵀ(x_i)_{G_ℓ}`` (any shape).
    """
    q = len(partials)
    if t1 is None or t2 is None:
        t1, t2 = trees_lib.default_tree_pair(q)
        assert trees_lib.significantly_different(t1, t2) or q == 2
    # callers may pass explicit (possibly Definition-4-violating) trees to
    # study the collusion attack of supplementary B (tests do).
    partials = [np.asarray(p, dtype=np.float64) for p in partials]
    # step 2: mask locally
    deltas = [mask_scale * rng.standard_normal(partials[0].shape) for _ in range(q)]
    masked = [p + d for p, d in zip(partials, deltas)]

    transcript = AggTranscript(messages=[[] for _ in range(q)])

    def run(tree: trees_lib.ReductionTree, values: List[np.ndarray], tag: str):
        acc = list(values)
        for rnd in tree.rounds:
            for dst, src in rnd:
                transcript.messages[dst].append((f"{tag}:from{src}", acc[src].copy()))
                acc[dst] = acc[dst] + acc[src]
        return acc[tree.root]

    xi1 = run(t1, masked, "xi1")   # step 4: masked sum over T1
    xi2 = run(t2, deltas, "xi2")   # step 5: mask sum over totally different T2
    return xi1 - xi2, transcript   # output: wᵀx = ξ1 − ξ2


def secure_aggregate_survivors(
    partials: Sequence[np.ndarray],
    alive: Sequence[bool],
    rng: np.random.Generator,
    mask_scale: float = 1.0,
    strict: bool = False,
) -> Tuple[np.ndarray, AggTranscript]:
    """Algorithm 1 across a membership change (host reference).

    The protocol is re-run over the *survivor* set only: (T1, T2) are
    rebuilt over the survivors (``trees.survivor_tree_pair``, preserving
    Definition 4), fresh masks are drawn (re-keying — no mask from the
    pre-dropout configuration is reused), and crashed parties contribute
    neither value nor mask.  With fewer than 3 survivors the two-tree
    structure is degenerate, so the protocol **degrades to a
    pairwise-cancelling masked psum** (Σδ ≡ 0 over survivors, every
    transmitted value still masked) and emits a ``RuntimeWarning`` —
    easy to miss in a long run, so ``strict=True`` raises a
    ``RuntimeError`` at that boundary instead of degrading (the
    deployment-policy switch: refuse to continue without the
    mask-sum/value-sum schedule separation).

    Returns ``(survivor sum, transcript)`` with transcript rows indexed by
    *original* party ids (crashed parties see nothing).
    """
    q = len(partials)
    surv = [p for p in range(q) if alive[p]]
    if not surv:
        raise ValueError("secure aggregation needs >= 1 surviving party")
    sub = [np.asarray(partials[p], dtype=np.float64) for p in surv]
    transcript = AggTranscript(messages=[[] for _ in range(q)])
    if len(surv) >= 3:
        t1, t2, _ = trees_lib.survivor_tree_pair(q, surv)
        val, sub_tr = secure_aggregate_host(sub, rng, t1, t2, mask_scale)
        # route the compact-index transcript back to original party ids
        for ci, p in enumerate(surv):
            for tag, v in sub_tr.messages[ci]:
                tag = re.sub(r"from(\d+)",
                             lambda mo: f"from{surv[int(mo.group(1))]}", tag)
                transcript.messages[p].append((tag, v))
        return val, transcript
    if strict:
        raise RuntimeError(
            f"secure aggregation: only {len(surv)} survivor(s) < 3 and "
            "strict=True — refusing to degrade below the two-tree "
            "protocol (no Definition-4 tree pair exists)")
    warnings.warn(
        f"secure aggregation degraded: only {len(surv)} survivor(s) < 3, "
        "two-tree protocol has no Definition-4 pair — falling back to "
        "pairwise-cancelling masked psum (values stay masked; the "
        "mask-sum/value-sum schedule separation is lost)", RuntimeWarning)
    s = len(surv)
    deltas = [mask_scale * rng.standard_normal(sub[0].shape)
              for _ in range(s)]
    total = np.sum(deltas, axis=0)
    deltas = [d - total / s for d in deltas]          # Σδ ≡ 0 exactly
    masked = [p + d for p, d in zip(sub, deltas)]
    # psum = all-broadcast-reduce: every survivor sees every other
    # survivor's masked value (and nothing unmasked)
    for ci, p in enumerate(surv):
        for cj, pj in enumerate(surv):
            if ci != cj:
                transcript.messages[pj].append(
                    (f"psum:from{p}", masked[ci].copy()))
    return np.sum(masked, axis=0), transcript


# ---------------------------------------------------------------------------
# JAX / mesh-axis forms
# ---------------------------------------------------------------------------

def _complete_perm(perm, q: int):
    """Extend a partial (src, dst) permutation to a full one.

    The extra pairs route unscheduled sources to unscheduled destinations;
    callers mask non-scheduled receivers, so the filler values are never
    read.  Needed because ``lax.ppermute``'s vmap batching rule (the
    engine's single-device party emulation) only accepts full permutations,
    while real meshes also accept partial ones.
    """
    srcs = {s for s, _ in perm}
    dsts = {d for _, d in perm}
    fill = zip((i for i in range(q) if i not in srcs),
               (i for i in range(q) if i not in dsts))
    return list(perm) + list(fill)


def tree_psum_collective_permute(x: jax.Array, axis_name: str,
                                 tree: trees_lib.ReductionTree) -> jax.Array:
    """Reduce ``x`` over mesh axis ``axis_name`` replaying ``tree``'s rounds
    with ``lax.ppermute`` + local adds, then broadcast the root's value.

    Faithful to the round structure of Algorithm 1 (each round only the
    scheduled (dst, src) pairs move data).  Cost: log2(q) permutes, same
    asymptotics as a binary-tree all-reduce.
    """
    q = tree.q
    idx = jax.lax.axis_index(axis_name)
    acc = x
    for rnd in tree.rounds:
        perm = _complete_perm([(src, dst) for dst, src in rnd], q)
        moved = jax.lax.ppermute(acc, axis_name, perm)
        # parties that are a dst this round accumulate; others keep acc
        is_dst = jnp.zeros((), dtype=bool)
        for dst, _src in rnd:
            is_dst = jnp.logical_or(is_dst, idx == dst)
        acc = jnp.where(is_dst, acc + moved, acc)
    # distribute the root total back down the tree (reverse rounds; each
    # round is a disjoint pair set, hence a valid partial permutation)
    for rnd in reversed(tree.rounds):
        perm = _complete_perm([(dst, src) for dst, src in rnd], q)  # parent -> child
        moved = jax.lax.ppermute(acc, axis_name, perm)
        is_child = jnp.zeros((), dtype=bool)
        for _dst, src in rnd:
            is_child = jnp.logical_or(is_child, idx == src)
        acc = jnp.where(is_child, moved, acc)
    return acc


def ring_mask(
    key: jax.Array,
    axis_name: str,
    shape: Tuple[int, ...],
    mask_scale: float = 1.0,
) -> jax.Array:
    """This party's ring mask δ_ℓ = mask_scale · (PRG(s_ℓ) − PRG(s_{ℓ−1}))
    of ``shape`` (f32), with s_ℓ = ``fold_in(key, ℓ)``: the draw half of
    :func:`secure_psum_ring`, callable ahead of the partial it masks."""
    idx = jax.lax.axis_index(axis_name)
    q = jax.lax.psum(1, axis_name)
    r_self = jax.random.normal(jax.random.fold_in(key, idx), shape,
                               jnp.float32)
    r_prev = jax.random.normal(jax.random.fold_in(key, (idx - 1) % q),
                               shape, jnp.float32)
    return mask_scale * (r_self - r_prev)


def ring_apply(partial: jax.Array, delta: jax.Array,
               axis_name: str) -> jax.Array:
    """The reduction half of :func:`secure_psum_ring`: one psum of
    ``partial + δ`` (Σδ ≡ 0, so no mask sum is reduced)."""
    _check_mask(partial, delta)
    out_dtype = partial.dtype
    masked = partial.astype(jnp.float32) + delta
    return jax.lax.psum(masked, axis_name).astype(out_dtype)


def secure_psum_ring(
    partial: jax.Array,
    axis_name: str,
    key: jax.Array,
    mask_scale: float = 1.0,
) -> jax.Array:
    """Beyond-paper optimization (EXPERIMENTS §Perf): pairwise-cancelling
    ring masks δ_ℓ = PRG(s_ℓ) − PRG(s_{ℓ−1}) with Σ_ℓ δ_ℓ ≡ 0, so the mask
    sum never needs to be aggregated — ONE collective instead of the
    paper's two tree reductions (ξ₂ ≡ 0), halving VFL-frontend collective
    bytes.  :func:`ring_mask` then :func:`ring_apply`.

    Security: each seed s_ℓ is pairwise-shared between ring neighbours
    (DH-agreed in a real deployment; the SPMD simulation derives them from
    a common key, which is traffic-equivalent).  Under threat model 1
    every transmitted value is masked, as in Algorithm 1; under threat
    model 2 the two ring neighbours of ℓ can jointly strip δ_ℓ — the same
    collusion caveat as the paper's scheme, where Lemma 1 still protects
    the rank-1 factors.  See tests/test_security.py.
    """
    delta = ring_mask(key, axis_name, partial.shape, mask_scale)
    return ring_apply(partial, delta, axis_name)


def two_tree_mask(
    key: jax.Array,
    axis_name: str,
    shape: Tuple[int, ...],
    mask_scale: float = 1.0,
) -> jax.Array:
    """This party's Algorithm-1 mask δ = mask_scale · N(0, 1) of ``shape``
    (f32), drawn from ``fold_in(key, axis_index)`` — per-party distinct:
    the draw half of :func:`secure_psum`, callable ahead of the partial
    it masks (the engine draws a whole epoch's step masks at once)."""
    pkey = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    return mask_scale * jax.random.normal(pkey, shape, jnp.float32)


def two_tree_apply(
    partial: jax.Array,
    delta: jax.Array,
    axis_name: str,
    schedule_faithful: bool = False,
    q: int | None = None,
) -> jax.Array:
    """The reduction half of :func:`secure_psum`: ξ₁ = Σ(z + δ) over T1,
    ξ₂ = Σδ over T2, output ξ₁ − ξ₂ in ``partial``'s dtype.

    With ``schedule_faithful=True`` the exact T1/T2 round structures are
    replayed via ``ppermute``; otherwise both reductions lower to
    ``lax.psum`` (XLA all-reduce) which is the production fast path — the
    protocol security rests on masking + distinct schedules.
    """
    _check_mask(partial, delta)
    out_dtype = partial.dtype
    # Mask arithmetic in f32: masking/unmasking must cancel exactly enough
    # that the aggregate is lossless (bf16 partial + O(1) mask would lose
    # the partial's mantissa).
    masked = partial.astype(jnp.float32) + delta
    if schedule_faithful:
        nparties = q if q is not None else jax.lax.psum(1, axis_name)
        t1, t2 = trees_lib.default_tree_pair(int(nparties))
        xi1 = tree_psum_collective_permute(masked, axis_name, t1)
        xi2 = tree_psum_collective_permute(delta, axis_name, t2)
    else:
        xi1 = jax.lax.psum(masked, axis_name)
        xi2 = jax.lax.psum(delta, axis_name)
    return (xi1 - xi2).astype(out_dtype)


def secure_psum(
    partial: jax.Array,
    axis_name: str,
    key: jax.Array,
    mask_scale: float = 1.0,
    schedule_faithful: bool = False,
    q: int | None = None,
) -> jax.Array:
    """Masked two-tree reduction over a mesh axis (Algorithm 1 on TPU):
    :func:`two_tree_mask` then :func:`two_tree_apply`.

    Must be called inside ``shard_map`` (or any context where ``axis_name``
    is bound); the mask key is made per-party distinct by folding in
    ``axis_index``.
    """
    delta = two_tree_mask(key, axis_name, partial.shape, mask_scale)
    return two_tree_apply(partial, delta, axis_name, schedule_faithful, q)


def _check_mask(partial: jax.Array, delta: jax.Array):
    """A mask drawn ahead of its partial must match it exactly: a
    broadcast would mask some entries with another entry's δ."""
    if delta.shape != partial.shape or delta.dtype != jnp.float32:
        raise ValueError(
            f"mask {delta.dtype}{list(delta.shape)} does not fit partial "
            f"{partial.dtype}{list(partial.shape)} (f32 of its shape)")


# ---------------------------------------------------------------------------
# membership-aware forms (fault tolerance: party dropout / rejoin)
# ---------------------------------------------------------------------------

def _alive_fingerprint(av: jax.Array) -> jax.Array:
    """int32 fingerprint of the gathered alive vector (``(q,)`` int32).

    Folded into the mask key so every membership change re-keys the masks
    (no mask stream from one configuration is reused in another).  Exact
    bitmask for q <= 30; wider federations fold each flag sequentially
    (q static, so the loop unrolls at trace time).
    """
    q = av.shape[0]
    if q <= 30:
        return jnp.sum(av * (2 ** jnp.arange(q, dtype=jnp.int32)))
    fp = jnp.int32(0)
    for i in range(q):
        fp = fp * 2 + av[i]
    return fp


# ---------------------------------------------------------------------------
# hierarchical forms: logical party axis = (outer slots) × (inner packed)
# ---------------------------------------------------------------------------
# With q logical parties packed ``pps`` per physical slot (see
# ``sharding.api.PartyMesh``), one flat reduction over a single named axis
# no longer exists: aggregation becomes two-level.  Level 1 reduces the
# packed parties *within* a slot (over the inner vmapped axis — the
# intra-slot tree: masked psum on the fast path, the exact T1/T2 round
# replay from ``core.trees`` under ``schedule_faithful``); level 2 runs the
# existing two_tree/ring lowering across slots on the per-slot sums.
#
# Mask-stream discipline (what the taint lint proves):
#   * level-1 streams are keyed ``fold_in(fold_in(key, _L1_SALT),
#     slot_index)`` then per-inner-party inside the flat primitive — i.e.
#     distinct per *logical* party (slot AND inner index), so no stream is
#     reused across slots;
#   * level-2 streams fold the inner index into the key before the flat
#     primitive folds the slot index — also logical-party distinct.  Each
#     inner replica therefore runs an independently-masked copy of the
#     cross-slot protocol on identical per-slot sums (masks cancel within
#     each replica's plane; replicas agree to f32 mask-rounding).
# The two salts keep the level-1 and level-2 stream domains disjoint.

_L1_SALT = 0x51071   # level-1 (intra-slot) mask-stream domain
_L2_SALT = 0x1e2e1   # level-2 (cross-slot) mask-stream domain


def secure_psum_hier(
    partial: jax.Array,
    outer_axis: str,
    inner_axis: str,
    key: jax.Array,
    mode: str = "two_tree",
    mask_scale: float = 1.0,
    schedule_faithful: bool = False,
    slots: int | None = None,
    pps: int | None = None,
) -> jax.Array:
    """Two-level masked aggregation over ``(outer_axis, inner_axis)``.

    Numerically the masks cancel level by level, so the result equals the
    plain sum over all q = slots × pps logical parties (to f32 rounding —
    the same tolerance class as the flat lowerings).  ``mode`` selects the
    *cross-slot* lowering ("two_tree" or "ring"); the intra-slot level
    uses two-tree masking (ring masks within a slot under ``mode="ring"``)
    and honors ``schedule_faithful`` by replaying the
    ``trees.default_tree_pair`` rounds over the inner axis.
    """
    so = jax.lax.axis_index(outer_axis)
    si = jax.lax.axis_index(inner_axis)
    out_dtype = partial.dtype
    partial = partial.astype(jnp.float32)
    # level 1: intra-slot reduce; key slot-folded, then per-inner-party
    # inside the flat primitive => streams distinct per logical party
    k1 = jax.random.fold_in(jax.random.fold_in(key, _L1_SALT), so)
    if mode == "ring":
        z_slot = secure_psum_ring(partial, inner_axis, k1,
                                  mask_scale=mask_scale)
    else:
        z_slot = secure_psum(partial, inner_axis, k1,
                             mask_scale=mask_scale,
                             schedule_faithful=schedule_faithful, q=pps)
    # level 2: the existing cross-slot lowering on the per-slot sums; the
    # inner index is folded in so each replica's stream set is also
    # logical-party distinct (no stream reuse across the inner axis)
    k2 = jax.random.fold_in(jax.random.fold_in(key, _L2_SALT), si)
    if mode == "ring":
        tot = secure_psum_ring(z_slot, outer_axis, k2,
                               mask_scale=mask_scale)
    else:
        tot = secure_psum(z_slot, outer_axis, k2, mask_scale=mask_scale,
                          schedule_faithful=schedule_faithful, q=slots)
    return tot.astype(out_dtype)


def secure_psum_hier_members(
    partial: jax.Array,
    outer_axis: str,
    inner_axis: str,
    key: jax.Array,
    alive: jax.Array,
    mode: str = "two_tree",
    mask_scale: float = 1.0,
) -> jax.Array:
    """Membership-safe two-level aggregation (hierarchical fault path).

    The full *logical* alive vector is gathered over both axes and its
    fingerprint is folded into the key **once, above both levels** — the
    re-key is composed across the hierarchy, so any single party's
    dropout re-keys every level-1 and level-2 mask stream (no stream from
    one membership configuration survives into another, even in slots the
    crash didn't touch).  Level 1 then runs the flat membership lowering
    over the inner axis (which additionally folds the slot-local
    fingerprint — harmless double keying); level 2 aggregates the
    per-slot survivor sums across slots with the slot's any-alive flag as
    its liveness (an all-dead slot contributes neither value nor mask).
    """
    so = jax.lax.axis_index(outer_axis)
    si = jax.lax.axis_index(inner_axis)
    out_dtype = partial.dtype
    partial = partial.astype(jnp.float32)
    alive = alive.astype(jnp.float32)
    av_in = jax.lax.all_gather(alive, inner_axis)          # (pps,)
    av = jax.lax.all_gather(av_in, outer_axis)             # (slots, pps)
    kk = jax.random.fold_in(
        key, _alive_fingerprint(av.reshape(-1).astype(jnp.int32)))
    k1 = jax.random.fold_in(jax.random.fold_in(kk, _L1_SALT), so)
    if mode == "ring":
        z_slot = secure_psum_ring_members(partial, inner_axis, k1, alive,
                                          mask_scale=mask_scale)
    else:
        z_slot = secure_psum_members(partial, inner_axis, k1, alive,
                                     mask_scale=mask_scale)
    slot_alive = jnp.minimum(av_in.sum(), 1.0)
    k2 = jax.random.fold_in(jax.random.fold_in(kk, _L2_SALT), si)
    if mode == "ring":
        tot = secure_psum_ring_members(z_slot, outer_axis, k2, slot_alive,
                                       mask_scale=mask_scale)
    else:
        tot = secure_psum_members(z_slot, outer_axis, k2, slot_alive,
                                  mask_scale=mask_scale)
    return tot.astype(out_dtype)


def secure_psum_ring_members(
    partial: jax.Array,
    axis_name: str,
    key: jax.Array,
    alive: jax.Array,
    mask_scale: float = 1.0,
) -> jax.Array:
    """``secure_psum_ring`` on the *surviving sub-ring* (fault tolerance).

    ``alive`` is this party's own scalar liveness flag (1.0 / 0.0).  The
    pairwise-cancelling ring masks stop summing to zero when a member
    vanishes, so on every membership change the ring is rebuilt over the
    survivors: the alive vector is gathered, its fingerprint is folded
    into the step key (re-keying), and mask seeds are assigned by **rank
    in the surviving sub-ring** — survivor with rank r draws
    PRG(k, r) − PRG(k, (r−1) mod n_alive), so Σδ ≡ 0 over survivors for
    any survivor count (a lone survivor's two seeds coincide: δ = 0).
    Crashed parties contribute neither value nor mask.
    """
    idx = jax.lax.axis_index(axis_name)
    out_dtype = partial.dtype
    partial = partial.astype(jnp.float32)
    alive = alive.astype(jnp.float32)
    av = jax.lax.all_gather(alive, axis_name).astype(jnp.int32)   # (q,)
    q = av.shape[0]
    nal = jnp.maximum(av.sum(), 1)
    rank = jnp.sum(jnp.where(jnp.arange(q) < idx, av, 0))
    kk = jax.random.fold_in(key, _alive_fingerprint(av))
    r_self = jax.random.normal(jax.random.fold_in(kk, rank),
                               partial.shape, jnp.float32)
    r_prev = jax.random.normal(jax.random.fold_in(kk, (rank - 1) % nal),
                               partial.shape, jnp.float32)
    masked = partial + mask_scale * (r_self - r_prev)
    return jax.lax.psum(alive * masked, axis_name).astype(out_dtype)


def secure_psum_members(
    partial: jax.Array,
    axis_name: str,
    key: jax.Array,
    alive: jax.Array,
    mask_scale: float = 1.0,
) -> jax.Array:
    """Membership-safe two-tree lowering (fault tolerance).

    Both reductions are psums over the survivor set — ξ₁ = Σ alive·(z+δ),
    ξ₂ = Σ alive·δ — with the alive-set fingerprint folded into the mask
    key (re-keying on every membership change).  The schedule-faithful
    ``ppermute`` replay is **not** membership-safe (a crashed party sits
    on the reduction path and would forward stale accumulator values), so
    faulted epochs always use this lowering; the host reference
    (``secure_aggregate_survivors``) carries the explicit rebuilt-tree
    schedules and the < 3-survivor degrade warning.
    """
    idx = jax.lax.axis_index(axis_name)
    out_dtype = partial.dtype
    partial = partial.astype(jnp.float32)
    alive = alive.astype(jnp.float32)
    av = jax.lax.all_gather(alive, axis_name).astype(jnp.int32)
    kk = jax.random.fold_in(key, _alive_fingerprint(av))
    pkey = jax.random.fold_in(kk, idx)
    delta = mask_scale * jax.random.normal(pkey, partial.shape, jnp.float32)
    xi1 = jax.lax.psum(alive * (partial + delta), axis_name)
    xi2 = jax.lax.psum(alive * delta, axis_name)
    return (xi1 - xi2).astype(out_dtype)
