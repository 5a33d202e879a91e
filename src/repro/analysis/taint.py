"""Leakage taint analysis over per-party jaxprs (paper Definition 4).

The semi-honest security argument of the paper rests on one structural
property: **no raw party-private value ever crosses a party boundary
unmasked**.  Every transmitted quantity must be offset by PRNG mask noise
whose seeds are (a) per-party distinct (Algorithm 1 step 2 — equal-seeded
masks cancel in the adversary's view) and, across membership changes,
(b) re-keyed from the surviving-set fingerprint (PR 6's re-key rule — a
mask stream reused across configurations is a replay oracle).

The dynamic checks (``tests/test_faults_secure.py`` transcripts) sample a
few configurations; this pass proves the property for an *entire compiled
entry point* by static dataflow over the per-party jaxpr:

* the per-party program is traced with ``jax.make_jaxpr(...,
  axis_env=[(axis, q)])`` so cross-party collectives (``psum``,
  ``ppermute``, ``all_gather``...) and ``axis_index`` stay first-class
  primitives (the engine records each epoch's party function — see
  ``FusedEngine.party_program``);
* **taint** starts at the declared party-private sources (the feature
  block; every raw partial product / (B, d_rep) vector representation
  inherits it) and propagates through every equation, including
  ``scan``/``while`` fixpoints, ``cond`` branches, ``pjit`` bodies, and
  opaque combinators (``pallas_call``: any-in → all-out);
* **mask provenance** starts at ``random_bits`` outputs.  Each PRNG
  stream records the *set of party axes* its key depends on (via
  ``axis_index`` folds) plus a ``membership_keyed`` flag (key depends on
  an ``all_gather``'d liveness vector — the alive-set fingerprint
  re-key).  With hierarchical packing the logical party index factors
  over two named axes (outer slot × inner packed party), so a stream is
  party-distinct only if its axis set covers them all;
* at every cross-party primitive, each tainted operand must carry at
  least one mask stream distinct per *logical* party (and, for
  membership-varying entry points, one that is also membership-keyed) —
  otherwise a named finding is emitted.

Soundness stance: this is a linter, not a proof assistant.  Taint and
mask provenance both propagate by union through unknown primitives, so a
nonlinear op that *destroys* additive masking (while keeping the random
stream in its provenance) can in principle launder a value past the
check.  The shipped protocols only ever mask additively right at the
boundary, the seeded mutants in :mod:`repro.analysis.mutants` pin the
failure modes that matter, and the analyzer self-test runs in CI — a
regression that makes the pass vacuous fails the gate loudly.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from jax.extend import core as jax_core

from repro.analysis.walkers import CROSS_PARTY_PRIMS

Literal = jax_core.Literal


# A PRNG stream: (id of the random_bits eqn, frozenset of party-axis
# names its key depends on via axis_index, membership_keyed).  A stream
# is party-distinct for a boundary iff its axis set covers EVERY party
# axis — under the hierarchical (slots × parties_per_slot) factorization
# a key folded with only one of the two indices repeats across the
# other, so coverage of the full set is what "distinct per logical
# party" means.  Streams are compared structurally so a fixpoint over
# scan carries terminates (the stream set is bounded by the number of
# random_bits equations in the program).
Stream = Tuple[int, FrozenSet[str], bool]


@dataclasses.dataclass(frozen=True)
class Props:
    """Abstract state of one jaxpr variable."""

    taint: bool = False            # derives from a party-private source
    streams: FrozenSet[Stream] = frozenset()   # PRNG streams in provenance
    # party-axis names whose axis_index is in this value's provenance
    party_dep: FrozenSet[str] = frozenset()
    alive_dep: bool = False        # depends on an all_gather'd vector

    def join(self, other: "Props") -> "Props":
        return Props(self.taint or other.taint,
                     self.streams | other.streams,
                     self.party_dep | other.party_dep,
                     self.alive_dep or other.alive_dep)


EMPTY = Props()


@dataclasses.dataclass(frozen=True)
class TaintFinding:
    """One leakage violation at a cross-party boundary."""

    code: str          # "unmasked-boundary" | "mask-not-party-distinct"
    #                  # | "mask-not-membership-keyed"
    primitive: str     # the boundary primitive (psum, ppermute, ...)
    path: str          # enclosing-combinator path, e.g. "scan/pjit"
    detail: str

    def __str__(self) -> str:
        return f"[{self.code}] {self.primitive} @ {self.path}: {self.detail}"


# Findings, ordered by severity (used by the report formatter).
UNMASKED = "unmasked-boundary"
EQUAL_SEEDED = "mask-not-party-distinct"
NO_REKEY = "mask-not-membership-keyed"


class _Analyzer:
    def __init__(self, axis, membership: bool):
        # ``axis`` is one party-axis name or a tuple of them (hierarchical
        # packing: the outer slot axis plus the inner vmapped party axis).
        self.axes = frozenset((axis,) if isinstance(axis, str) else axis)
        self.membership = membership
        self.findings: List[TaintFinding] = []
        self.emit = True           # silenced during fixpoint pre-passes

    # -- environment helpers -------------------------------------------------

    def read(self, env: Dict, atom) -> Props:
        if isinstance(atom, Literal):
            return EMPTY
        return env.get(atom, EMPTY)

    def write(self, env: Dict, var, props: Props):
        # jax DropVar has no meaningful identity to key on
        if type(var).__name__ == "DropVar":
            return
        env[var] = props

    # -- boundary checking ---------------------------------------------------

    @staticmethod
    def _eqn_axes(params) -> FrozenSet[str]:
        axes = params.get("axes", params.get("axis_name", ()))
        if isinstance(axes, (str, int)):
            axes = (axes,)
        try:
            return frozenset(a for a in tuple(axes) if isinstance(a, str))
        except TypeError:
            return frozenset()

    def _axis_match(self, params) -> bool:
        """Does this collective operate over (any of) the party axes?"""
        return bool(self._eqn_axes(params) & self.axes)

    def _check_boundary(self, eqn, in_props: Sequence[Props], path: str):
        for props in in_props:
            if not props.taint:
                continue
            # A stream only protects the boundary if its key separates
            # EVERY logical party, i.e. its axis_index provenance covers
            # all party axes (outer slot axis AND inner packed axis).
            distinct = [s for s in props.streams if s[1] >= self.axes]
            if not props.streams:
                self._find(UNMASKED, eqn, path,
                           "party-private operand crosses the boundary "
                           "with no PRNG mask offset in its provenance")
            elif not distinct:
                self._find(EQUAL_SEEDED, eqn, path,
                           "no mask stream depends on the full set of "
                           "party axes %s (a key folded with only part "
                           "of the logical party index repeats across "
                           "the rest — equal-seeded masks are visible "
                           "to the aggregator after cancellation)"
                           % sorted(self.axes))
            elif self.membership and not any(s[2] for s in distinct):
                self._find(NO_REKEY, eqn, path,
                           "membership-varying entry point: mask key is "
                           "not derived from the gathered alive-set "
                           "(mask streams reused across membership "
                           "changes)")

    def _find(self, code: str, eqn, path: str, detail: str):
        if not self.emit:
            return
        f = TaintFinding(code, eqn.primitive.name, path, detail)
        if f not in self.findings:
            self.findings.append(f)

    # -- transfer functions --------------------------------------------------

    def walk(self, jaxpr, in_props: Sequence[Props],
             const_props: Optional[Sequence[Props]] = None,
             path: str = "") -> List[Props]:
        """Abstractly interpret ``jaxpr`` (a raw Jaxpr); returns outvar
        props.  ``in_props`` aligns with ``jaxpr.invars``."""
        env: Dict = {}
        consts = const_props or [EMPTY] * len(jaxpr.constvars)
        for v, p in zip(jaxpr.constvars, consts):
            self.write(env, v, p)
        for v, p in zip(jaxpr.invars, in_props):
            self.write(env, v, p)
        for eqn in jaxpr.eqns:
            self._eqn(env, eqn, path)
        return [self.read(env, v) for v in jaxpr.outvars]

    def _eqn(self, env: Dict, eqn, path: str):
        name = eqn.primitive.name
        ins = [self.read(env, a) for a in eqn.invars]
        union = EMPTY
        for p in ins:
            union = union.join(p)

        if name == "axis_index":
            hit = self._eqn_axes(eqn.params) & self.axes
            if hit:
                union = union.join(Props(party_dep=hit))
            self.write(env, eqn.outvars[0], union)
            return

        if name == "is_finite":
            # Declassification: the finiteness verdict of a party-private
            # value is protocol-public.  Boundary values are masked
            # *additively* (masked = z + δ with finite δ), so the masked
            # message is non-finite iff the raw partial is — every
            # aggregator already learns ``isfinite(z)`` from the message
            # it legitimately receives.  The guarded epochs' health flags
            # (``jnp.isfinite(zc)`` → liveness quarantine → alive-set
            # fingerprint) therefore drop taint here; stream and axis
            # provenance still propagate so a fingerprint derived from the
            # verdict keeps its membership pedigree.  Caveat (same stance
            # as the module docstring): a program that deliberately
            # *encodes* secret bits as inf/NaN patterns before calling
            # is_finite would launder them past this rule — the shipped
            # protocols only ever take finiteness of raw forward messages.
            out = Props(False, union.streams, union.party_dep,
                        union.alive_dep)
            for v in eqn.outvars:
                self.write(env, v, out)
            return

        if name == "random_bits":
            # a fresh PRNG stream; its quality flags come from the key's
            # provenance (fold_in(axis_index) per party axis => that axis
            # joins the stream's distinctness set;
            # fold_in(fingerprint(all_gather(alive))) => membership-keyed).
            # Stream identity is the eqn's object id — stable across the
            # repeated walks of a scan fixpoint, so carry sets converge.
            stream = (id(eqn), union.party_dep, union.alive_dep)
            out = Props(union.taint, union.streams | {stream},
                        union.party_dep, union.alive_dep)
            for v in eqn.outvars:
                self.write(env, v, out)
            return

        if name in CROSS_PARTY_PRIMS and self._axis_match(eqn.params):
            self._check_boundary(eqn, ins, path + name)
            if name == "all_gather":
                union = union.join(Props(alive_dep=True))
            for v in eqn.outvars:
                self.write(env, v, union)
            return

        if name == "scan":
            self._scan(env, eqn, ins, path)
            return
        if name == "while":
            self._while(env, eqn, ins, path)
            return
        if name == "cond":
            self._cond(env, eqn, ins, path)
            return

        sub = self._call_jaxpr(eqn)
        if sub is not None:
            outs = self.walk(sub.jaxpr, ins[: len(sub.jaxpr.invars)],
                             path=path + name + "/")
            # calls with extra invars (custom_vjp num_consts...) fall back
            # to the union rule for any outvar the sub-walk missed
            for v, p in zip(eqn.outvars,
                            outs + [union] * (len(eqn.outvars) - len(outs))):
                self.write(env, v, p)
            return

        # default / opaque rule (pallas_call, element-wise ops, ...):
        # any-in -> all-out, by union
        for v in eqn.outvars:
            self.write(env, v, union)

    @staticmethod
    def _call_jaxpr(eqn):
        """The ClosedJaxpr of a call-like primitive, if any."""
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "call"):
            v = eqn.params.get(key)
            if v is not None and hasattr(v, "jaxpr"):
                return v
            if v is not None and hasattr(v, "eqns"):     # raw jaxpr
                return jax_core.ClosedJaxpr(v, ())
        return None

    def _scan(self, env: Dict, eqn, ins: Sequence[Props], path: str):
        closed = eqn.params["jaxpr"]
        body = closed.jaxpr
        n_const = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        consts = list(ins[:n_const])
        carry = list(ins[n_const:n_const + n_carry])
        xs = list(ins[n_const + n_carry:])

        # fixpoint over the carry lattice, silenced; findings are emitted
        # in one final pass at the stable assignment
        prev_emit, self.emit = self.emit, False
        for _ in range(len(carry) * 4 + 8):
            outs = self.walk(body, consts + carry + xs, path=path + "scan/")
            new_carry = [c.join(o) for c, o in zip(carry, outs[:n_carry])]
            if new_carry == carry:
                break
            carry = new_carry
        self.emit = prev_emit
        outs = self.walk(body, consts + carry + xs, path=path + "scan/")
        outs = [c.join(o) for c, o in zip(carry, outs[:n_carry])] \
            + outs[n_carry:]
        for v, p in zip(eqn.outvars, outs):
            self.write(env, v, p)

    def _while(self, env: Dict, eqn, ins: Sequence[Props], path: str):
        body = eqn.params["body_jaxpr"].jaxpr
        n_c = eqn.params["body_nconsts"]
        cond_n = eqn.params["cond_nconsts"]
        consts = list(ins[cond_n:cond_n + n_c])
        carry = list(ins[cond_n + n_c:])
        prev_emit, self.emit = self.emit, False
        for _ in range(len(carry) * 4 + 8):
            outs = self.walk(body, consts + carry, path=path + "while/")
            new_carry = [c.join(o) for c, o in zip(carry, outs)]
            if new_carry == carry:
                break
            carry = new_carry
        self.emit = prev_emit
        outs = self.walk(body, consts + carry, path=path + "while/")
        for v, p in zip(eqn.outvars,
                        [c.join(o) for c, o in zip(carry, outs)]):
            self.write(env, v, p)

    def _cond(self, env: Dict, eqn, ins: Sequence[Props], path: str):
        branches = eqn.params["branches"]
        operands = ins[1:]
        outs: Optional[List[Props]] = None
        for br in branches:
            bouts = self.walk(br.jaxpr, operands, path=path + "cond/")
            outs = bouts if outs is None else [a.join(b)
                                               for a, b in zip(outs, bouts)]
        for v, p in zip(eqn.outvars, outs or []):
            self.write(env, v, p)


def analyze_party_jaxpr(closed_jaxpr, source_invars: Sequence[int],
                        axis="model",
                        membership: bool = False) -> List[TaintFinding]:
    """Run the leakage taint pass over a per-party (closed) jaxpr.

    ``source_invars``: indices (into ``jaxpr.invars``) of the
    party-private sources — for engine epochs, the party's feature block
    (always the first leaf of the ``local`` pytree by the ``_bind``
    convention).  ``axis`` is the party-axis name, or a tuple of names
    when the logical party index is factored over several named axes
    (hierarchical packing — ``FusedEngine`` exposes the right tuple as
    ``PartyProgram.boundary_axes``); mask streams must then be keyed per
    the *full* logical index, i.e. depend on axis_index over every axis
    in the tuple.  ``membership=True`` additionally requires boundary
    masks to be membership-keyed (faulted / survivor-aggregating entry
    points).

    Returns the (deduplicated) list of findings; empty means the program
    proves Definition 4's masking discipline at every boundary crossing.
    """
    jaxpr = closed_jaxpr.jaxpr
    an = _Analyzer(axis, membership)
    in_props = [Props(taint=(i in set(source_invars)))
                for i in range(len(jaxpr.invars))]
    an.walk(jaxpr, in_props, path="")
    return an.findings


def finding_codes(findings: Sequence[TaintFinding]) -> Dict[str, int]:
    """Histogram of finding codes (the manifest-stable summary)."""
    out: Dict[str, int] = {}
    for f in findings:
        out[f.code] = out.get(f.code, 0) + 1
    return dict(sorted(out.items()))
