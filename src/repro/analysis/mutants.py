"""Known-bad aggregation mutants — the analyzer's self-test.

A static analyzer that never fires is worse than none, so the lint run
opens by analyzing *deliberately broken* aggregation kernels (each a
realistic way to get Algorithm 1 wrong) plus shipped-secure positive
controls.  The gate: every mutant must produce its named
finding and every control must be clean — otherwise the analyzer itself
is broken and the matrix results are meaningless.

The mutants:

* ``off_psum`` — partials cross the party boundary with no mask at all
  (``secure="off"`` in kernel form) → ``unmasked-boundary``;
* ``equal_seeded`` — a two-tree-shaped reduction whose mask key is NOT
  folded with ``axis_index``: every party draws the *same* δ, so any
  observer subtracts the public Σδ and recovers Σz from ξ₁ per party
  pair differences → ``mask-not-party-distinct``;
* ``no_rekey`` — ring masks correctly per-party but NOT re-keyed on the
  alive-set fingerprint: after a dropout the surviving masks no longer
  cancel pairwise, and mask streams are reused across membership
  configurations → ``mask-not-membership-keyed`` (caught only under
  ``membership=True``, which is how faulted entries are analyzed);
* ``hier_inner_only`` — hierarchical (slot × packed-party) aggregation
  whose mask key is folded with the *inner* party index only: parties in
  the same inner position of different slots share a mask stream, so the
  key is not distinct per logical party → ``mask-not-party-distinct``
  under the two-axis boundary rule (the matching positive control is the
  shipped ``secure_psum_hier``, which folds both levels);
* ``predrawn_equal_seeded`` — an epoch's step masks drawn before its
  scan in one batched op, as the engine draws them, but from the step
  keys alone (no ``fold_in(axis_index)``): the stream reaches the scan
  body as a scan input and every party adds the same δ[t] →
  ``mask-not-party-distinct`` (the matching positive control draws with
  the shipped ``two_tree_mask`` and reduces with ``two_tree_apply``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.analysis.taint import (EQUAL_SEEDED, NO_REKEY, UNMASKED,
                                  analyze_party_jaxpr, finding_codes)
from repro.core import secure_agg

AXIS = "model"
Q = 4
_SHAPE = (8,)
_STEPS = 3

# hierarchical packing self-test: q = SLOTS × PPS logical parties over
# the (outer slot axis, inner vmapped party axis) pair
INNER_AXIS = "party"
SLOTS, PPS = 2, 2
HIER_AXES = (AXIS, INNER_AXIS)


def _trace(fn, *args):
    return jax.make_jaxpr(fn, axis_env=[(AXIS, Q)])(*args)


def _trace_hier(fn, *args):
    return jax.make_jaxpr(
        fn, axis_env=[(AXIS, SLOTS), (INNER_AXIS, PPS)])(*args)


def off_psum(z):
    """Mutant: unmasked cross-party reduction."""
    return jax.lax.psum(z, AXIS)


def equal_seeded(z, key):
    """Mutant: two-tree masking with one shared seed for every party."""
    delta = jax.random.normal(key, z.shape, jnp.float32)   # no fold_in(idx)!
    xi1 = jax.lax.psum(z + delta, AXIS)
    xi2 = jax.lax.psum(delta, AXIS)
    return xi1 - xi2


def no_rekey(z, key, alive):
    """Mutant: per-party ring masks without the alive-set re-key."""
    idx = jax.lax.axis_index(AXIS)
    q = jax.lax.psum(1, AXIS)
    r_self = jax.random.normal(jax.random.fold_in(key, idx), z.shape)
    r_prev = jax.random.normal(jax.random.fold_in(key, (idx - 1) % q),
                               z.shape)
    masked = z + (r_self - r_prev)
    return jax.lax.psum(alive * masked, AXIS)


def control_two_tree(z, key):
    """Positive control: the shipped two-tree masked reduction."""
    return secure_agg.secure_psum(z, AXIS, key)


def control_ring_members(z, key, alive):
    """Positive control: the shipped membership-aware ring reduction."""
    return secure_agg.secure_psum_ring_members(z, AXIS, key, alive)


def hier_inner_only(z, key):
    """Mutant: hierarchical agg keyed by the inner party index only.

    Both levels mask, but every key folds just ``axis_index(INNER_AXIS)``
    — parties sitting at the same packed position in different slots draw
    identical δ streams, so the composed mask is not distinct per
    *logical* party.
    """
    si = jax.lax.axis_index(INNER_AXIS)
    k = jax.random.fold_in(key, si)                  # no slot-index fold!
    d1 = jax.random.normal(k, z.shape, jnp.float32)
    z_slot = jax.lax.psum(z + d1, INNER_AXIS) - jax.lax.psum(d1, INNER_AXIS)
    d2 = jax.random.normal(jax.random.fold_in(k, 1), z.shape, jnp.float32)
    return jax.lax.psum(z_slot + d2, AXIS) - jax.lax.psum(d2, AXIS)


def control_hier(z, key):
    """Positive control: the shipped hierarchical masked reduction."""
    return secure_agg.secure_psum_hier(z, AXIS, INNER_AXIS, key,
                                       slots=SLOTS, pps=PPS)


def _scan_predrawn(zs, deltas):
    """The engine's pre-drawn epoch shape: step t reduces z[t] masked by
    the scan input δ[t] (two-tree: ξ₁ − ξ₂)."""
    def step(_, inp):
        z, d = inp
        return None, secure_agg.two_tree_apply(z, d, AXIS)
    return jax.lax.scan(step, None, (zs, deltas))[1]


def predrawn_equal_seeded(zs, keys):
    """Mutant: the epoch's step masks drawn up front from the step keys,
    with no party index folded in."""
    deltas = jax.vmap(
        lambda k: jax.random.normal(k, zs.shape[1:], jnp.float32))(keys)
    return _scan_predrawn(zs, deltas)


def control_predrawn(zs, keys):
    """Positive control: the engine's pre-drawn two-tree step masks."""
    deltas = jax.vmap(
        lambda k: secure_agg.two_tree_mask(k, AXIS, zs.shape[1:]))(keys)
    return _scan_predrawn(zs, deltas)


@dataclasses.dataclass
class MutantResult:
    name: str
    expected: Dict[str, int]   # required finding codes (empty = clean)
    actual: Dict[str, int]

    @property
    def ok(self) -> bool:
        if not self.expected:
            return not self.actual
        return all(self.actual.get(code, 0) >= n
                   for code, n in self.expected.items())

    def to_dict(self) -> dict:
        return {"expected": dict(self.expected),
                "actual": dict(self.actual), "ok": self.ok}


def run_selftest() -> List[MutantResult]:
    """Analyze every mutant and control; see module docstring."""
    z = jnp.zeros(_SHAPE, jnp.float32)
    key = jax.random.key(0)
    alive = jnp.float32(1.0)
    zs = jnp.zeros((_STEPS,) + _SHAPE, jnp.float32)
    keys = jax.random.split(key, _STEPS)
    cases = [
        ("off_psum", _trace(off_psum, z), False, {UNMASKED: 1}),
        ("equal_seeded", _trace(equal_seeded, z, key), False,
         {EQUAL_SEEDED: 1}),
        ("no_rekey", _trace(no_rekey, z, key, alive), True, {NO_REKEY: 1}),
        ("control_two_tree", _trace(control_two_tree, z, key), False, {}),
        ("control_ring_members", _trace(control_ring_members, z, key, alive),
         True, {}),
        ("predrawn_equal_seeded", _trace(predrawn_equal_seeded, zs, keys),
         False, {EQUAL_SEEDED: 1}),
        ("control_predrawn", _trace(control_predrawn, zs, keys), False, {}),
    ]
    hier_cases = [
        ("hier_inner_only", _trace_hier(hier_inner_only, z, key), False,
         {EQUAL_SEEDED: 1}),
        ("control_hier", _trace_hier(control_hier, z, key), False, {}),
    ]
    results = []
    for name, jx, membership, expected in cases:
        findings = analyze_party_jaxpr(jx, [0], axis=AXIS,
                                       membership=membership)
        results.append(MutantResult(name, expected, finding_codes(findings)))
    for name, jx, membership, expected in hier_cases:
        findings = analyze_party_jaxpr(jx, [0], axis=HIER_AXES,
                                       membership=membership)
        results.append(MutantResult(name, expected, finding_codes(findings)))
    return results
