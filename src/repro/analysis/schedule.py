"""Schedule audits: ring-buffer staleness proofs and donation/aliasing.

Staleness verifier
------------------
The delayed/faulted fused epochs carry per-party gradient **ring buffers**
with τ+1 slots: step t writes slot ``t mod (τ+1)`` and reads slot
``max(t − d, 0) mod (τ+1)``.  The bounded-staleness claim — *no read is
ever older than τ* — is structural: if (1) the ring has exactly τ+1
slots, (2) every scan iteration writes the current gradient into slot
``t mod (τ+1)`` before any read, and (3) every read index provably lies
in ``[0, τ]``, then any slot read holds a value written within the last
τ steps (the fault-gated variants relax (2) for dead parties — a crash
is *by design* an unbounded delay, so those rings are reported
``gated=True`` and the bound holds conditional on liveness).

:func:`ring_audit` proves (1)–(3) on the **per-party** jaxpr
(``FusedEngine.party_program(name).trace()``) with a small interval
abstract interpreter over the index arithmetic
(add/sub/mul/min/max/rem/select/broadcast/...).  Recorded precondition:
integer program inputs (step counters, delay schedules, straggle extras)
are nonnegative — which ``core.staleness`` / ``core.faults`` validate at
the API boundary.

Donation audit
--------------
``EngineConfig(donate=True)`` promises in-place buffer reuse for chained
epochs.  Donation silently degrades to a copy if XLA cannot alias the
buffer, so :func:`donation_audit` parses the *compiled* executable's
``input_output_alias`` table and checks every expected donated parameter
actually aliases an output.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.analysis.walkers import sub_jaxprs

_INF = math.inf

# primitives through which a ring-buffer value remains "the same buffer"
_RING_ALIAS_PRIMS = {"dynamic_update_slice", "select_n", "convert_element_type"}


def _call_body(eqn):
    """The body of a call-like eqn, or None.

    A call (``jit`` — named ``pjit`` before JAX 0.7 — ``closed_call``,
    custom-derivative calls) carries a closed jaxpr whose invars and
    outvars map one to one onto the eqn's, so the analyses inline it with
    the caller's facts.  Recognized by that shape, not by a primitive
    name, so a renamed call primitive cannot fail the proofs closed.  A
    scan also carries a closed body of matching arity, but its outputs
    are the fixpoint of many iterations (``num_carry``), so it is not a
    call."""
    if "num_carry" in eqn.params:
        return None
    for key in ("jaxpr", "call_jaxpr"):
        sub = eqn.params.get(key)
        inner = getattr(sub, "jaxpr", None)      # ClosedJaxpr only
        if (inner is not None and hasattr(inner, "eqns")
                and len(inner.invars) == len(eqn.invars)
                and len(inner.outvars) == len(eqn.outvars)):
            return inner
    return None


# ---------------------------------------------------------------------------
# interval abstract interpretation over index arithmetic
# ---------------------------------------------------------------------------

def _cmp(lo_a, hi_a, lo_b, hi_b, op) -> Tuple[float, float]:
    """Interval transfer for a comparison: [0,0]=always false,
    [1,1]=always true, [0,1]=unknown."""
    if op == "lt":
        if hi_a < lo_b:
            return (1.0, 1.0)
        if lo_a >= hi_b:
            return (0.0, 0.0)
    elif op == "le":
        if hi_a <= lo_b:
            return (1.0, 1.0)
        if lo_a > hi_b:
            return (0.0, 0.0)
    elif op == "gt":
        if lo_a > hi_b:
            return (1.0, 1.0)
        if hi_a <= lo_b:
            return (0.0, 0.0)
    elif op == "ge":
        if lo_a >= hi_b:
            return (1.0, 1.0)
        if hi_a < lo_b:
            return (0.0, 0.0)
    elif op == "eq":
        if lo_a == hi_a == lo_b == hi_b:
            return (1.0, 1.0)
        if hi_a < lo_b or hi_b < lo_a:
            return (0.0, 0.0)
    elif op == "ne":
        if lo_a == hi_a == lo_b == hi_b:
            return (0.0, 0.0)
        if hi_a < lo_b or hi_b < lo_a:
            return (1.0, 1.0)
    return (0.0, 1.0)


class _Intervals:
    """Forward interval analysis over one (raw) jaxpr body.

    At the top level, integer invars are assumed nonnegative (the
    engine's documented precondition for step counters / delay
    schedules); call bodies (:func:`_call_body`) are seeded from the caller's
    intervals instead — never re-assumed, since an inner invar may bind a
    possibly-negative intermediate like ``t - delay``.  Comparisons
    produce boolean intervals ([0,0] false / [1,1] true / [0,1] unknown)
    and ``select_n`` refines through a provably-constant selector — this
    is what resolves ``jnp.mod``'s sign-fix and negative-index
    normalization to tight bounds.  Unknown primitives return (-inf,
    inf), which fails the staleness proof rather than unsoundly passing
    it.
    """

    def __init__(self, jaxpr, seed: Optional[Dict] = None):
        self.env: Dict = {}
        if seed is None:
            for v in list(jaxpr.constvars) + list(jaxpr.invars):
                dt = getattr(v.aval, "dtype", None)
                try:
                    nonneg = dt is not None and np.issubdtype(
                        dt, np.signedinteger)
                except TypeError:              # extended dtypes (PRNG keys)
                    nonneg = False
                if nonneg:
                    self.env[v] = (0.0, _INF)
        else:
            self.env.update(seed)
        for eqn in jaxpr.eqns:
            self._eqn(eqn)

    def get(self, atom) -> Tuple[float, float]:
        if hasattr(atom, "val"):                       # Literal
            arr = np.asarray(atom.val)
            if arr.size == 0:
                return (0.0, 0.0)
            return (float(arr.min()), float(arr.max()))
        return self.env.get(atom, (-_INF, _INF))

    def _set(self, var, iv: Tuple[float, float]):
        if type(var).__name__ != "DropVar":
            self.env[var] = iv

    def _eqn(self, eqn):
        name = eqn.primitive.name
        ins = [self.get(a) for a in eqn.invars]
        out: Optional[Tuple[float, float]] = None
        if name == "add":
            out = (ins[0][0] + ins[1][0], ins[0][1] + ins[1][1])
        elif name == "sub":
            out = (ins[0][0] - ins[1][1], ins[0][1] - ins[1][0])
        elif name == "mul":
            cands = [a * b for a in ins[0] for b in ins[1]
                     if not math.isnan(a * b)]
            out = (min(cands), max(cands)) if cands else (-_INF, _INF)
        elif name == "max":
            out = (max(ins[0][0], ins[1][0]), max(ins[0][1], ins[1][1]))
        elif name == "min":
            out = (min(ins[0][0], ins[1][0]), min(ins[0][1], ins[1][1]))
        elif name == "clamp":
            lo, x, hi = ins
            out = (max(lo[0], min(x[0], hi[1])), max(lo[0], min(x[1], hi[1])))
        elif name == "rem":
            # XLA rem takes the dividend's sign (C semantics)
            dlo, dhi = ins[1]
            if dlo == dhi and dlo > 0 and dlo != _INF:
                L = dlo
                out = (0.0, L - 1) if ins[0][0] >= 0 else (-(L - 1), L - 1)
            else:
                out = (-_INF, _INF)
        elif name in ("lt", "le", "gt", "ge", "eq", "ne"):
            out = _cmp(*ins[0], *ins[1], name)
        elif name == "and":
            if ins[0] == (0.0, 0.0) or ins[1] == (0.0, 0.0):
                out = (0.0, 0.0)
            elif ins[0] == (1.0, 1.0) and ins[1] == (1.0, 1.0):
                out = (1.0, 1.0)
            else:
                out = (0.0, 1.0)
        elif name == "or":
            if ins[0] == (1.0, 1.0) or ins[1] == (1.0, 1.0):
                out = (1.0, 1.0)
            elif ins[0] == (0.0, 0.0) and ins[1] == (0.0, 0.0):
                out = (0.0, 0.0)
            else:
                out = (0.0, 1.0)
        elif name == "not":
            out = (1.0 - ins[0][1], 1.0 - ins[0][0])
        elif name in ("select_n", "select"):
            lo_w, hi_w = ins[0]
            if lo_w == hi_w and 1 + int(lo_w) < len(ins):
                out = ins[1 + int(lo_w)]       # provably-constant selector
            else:
                vals = ins[1:]
                out = (min(v[0] for v in vals), max(v[1] for v in vals))
        elif name in ("convert_element_type", "broadcast_in_dim", "reshape",
                      "squeeze", "expand_dims", "copy", "transpose",
                      "stop_gradient", "reduce_max", "reduce_min", "slice"):
            out = ins[0]
        elif name == "neg":
            out = (-ins[0][1], -ins[0][0])
        elif (body := _call_body(eqn)) is not None:
            inner = _Intervals(body, seed=dict(zip(body.invars, ins)))
            for ov, v in zip(eqn.outvars, body.outvars):
                self._set(ov, inner.get(v))
            return
        if out is None:
            out = (-_INF, _INF)
        for v in eqn.outvars:
            self._set(v, out)


# ---------------------------------------------------------------------------
# ring-buffer staleness audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RingAudit:
    """Verdict for one ring buffer inside one scan body."""

    scan_index: int          # which scan eqn (trace order)
    carry_index: int         # position in the scan carry
    length: int              # ring slots (must be tau + 1)
    writes: int              # dynamic_update_slice writes per iteration
    reads: int               # dynamic_slice / gather reads per iteration
    gated: bool              # write is liveness-gated (faulted epochs)
    write_in_range: bool     # every write index provably in [0, len-1]
    reads_in_range: bool     # every read index provably in [0, len-1]
    write_before_read: bool  # program order: write precedes every read
    notes: List[str]

    @property
    def bounded(self) -> bool:
        """τ-bounded staleness holds (conditional on liveness if gated)."""
        return (self.writes >= 1 and self.write_in_range
                and self.reads_in_range and self.write_before_read)

    def to_dict(self) -> dict:
        return {"scan": self.scan_index, "carry": self.carry_index,
                "length": self.length, "writes": self.writes,
                "reads": self.reads, "gated": self.gated,
                "bounded": self.bounded, "notes": self.notes}


def _scan_eqns(jaxpr, acc):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            acc.append(eqn)
        for v in eqn.params.values():
            for s in sub_jaxprs(v):
                _scan_eqns(s, acc)
    return acc


def ring_audit(closed_jaxpr, tau: int) -> List[RingAudit]:
    """Audit every (τ+1)-slot ring buffer carried through a scan.

    ``closed_jaxpr`` should be a **per-party** trace (see
    ``FusedEngine.party_program``) so buffer shapes carry no party axis.
    A carry is a ring iff its leading dimension is τ+1 and the body
    writes it with ``dynamic_update_slice``.  Returns one audit per
    ring; an entry with ``bounded=False`` is a staleness violation.
    """
    jx = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") else closed_jaxpr
    audits: List[RingAudit] = []
    for si, scan in enumerate(_scan_eqns(jx, [])):
        body = scan.params["jaxpr"].jaxpr
        n_const = scan.params["num_consts"]
        n_carry = scan.params["num_carry"]
        carries = body.invars[n_const:n_const + n_carry]
        iv = _Intervals(body)
        for ci, cv in enumerate(carries):
            shape = getattr(cv.aval, "shape", ())
            if len(shape) == 0 or shape[0] != tau + 1:
                continue
            audit = _audit_ring(body, cv, ci, si, iv, tau)
            if audit is not None:
                audits.append(audit)
    return audits


class _RingWalk:
    """Collect ring writes/reads/gates across a body and its call
    bodies (:func:`_call_body`), propagating the buffer-alias set and index intervals
    through call boundaries.  Positions are a global eqn counter so
    program order (write-before-read) survives the inlining."""

    def __init__(self):
        self.writes: List[Tuple[int, Tuple[float, float]]] = []
        self.reads: List[Tuple[int, Tuple[float, float], str]] = []
        self.gated = False
        self.pos = 0

    def walk(self, body, aliases: Set, iv: _Intervals) -> Set:
        for eqn in body.eqns:
            self.pos += 1
            name = eqn.primitive.name
            alias_ins = [a for a in eqn.invars
                         if not hasattr(a, "val") and a in aliases]
            if not alias_ins:
                continue
            if name == "dynamic_update_slice" and eqn.invars[0] in aliases:
                self.writes.append((self.pos, iv.get(eqn.invars[2])))
                aliases.add(eqn.outvars[0])
            elif name == "dynamic_slice" and eqn.invars[0] in aliases:
                self.reads.append((self.pos, iv.get(eqn.invars[1]),
                                   "dynamic_slice"))
            elif name == "gather" and eqn.invars[0] in aliases:
                self.reads.append((self.pos, iv.get(eqn.invars[1]),
                                   "gather"))
            elif name in ("select_n", "select"):
                # a data-dependent select over the buffer itself is the
                # fault gate (jnp.where(alive, put, buf)); selects whose
                # selector is provably constant are just index plumbing
                lo_w, hi_w = iv.get(eqn.invars[0])
                if any(a in aliases for a in eqn.invars[1:]
                       if not hasattr(a, "val")) and lo_w != hi_w:
                    self.gated = True
                aliases.add(eqn.outvars[0])
            elif name in ("convert_element_type", "copy", "reshape"):
                aliases.add(eqn.outvars[0])
            elif (inner := _call_body(eqn)) is not None:
                seed = {v: iv.get(a)
                        for v, a in zip(inner.invars, eqn.invars)}
                inner_iv = _Intervals(inner, seed=seed)
                inner_aliases = {v for v, a in zip(inner.invars, eqn.invars)
                                 if not hasattr(a, "val") and a in aliases}
                inner_aliases = self.walk(inner, inner_aliases, inner_iv)
                for ov, v in zip(eqn.outvars, inner.outvars):
                    if not hasattr(v, "val") and v in inner_aliases:
                        aliases.add(ov)
        return aliases


def _audit_ring(body, carry_var, ci: int, si: int, iv: _Intervals,
                tau: int) -> Optional[RingAudit]:
    L = carry_var.aval.shape[0]
    walk = _RingWalk()
    walk.walk(body, {carry_var}, iv)
    if not walk.writes and not walk.reads:
        return None                              # carried through untouched

    notes: List[str] = []
    write_ok = bool(walk.writes)
    for _, (lo, hi) in walk.writes:
        if not (lo >= 0 and hi <= L - 1):
            write_ok = False
            notes.append(f"write index interval [{lo}, {hi}] not within "
                         f"[0, {L - 1}]")
    reads_ok = True
    for _, (lo, hi), kind in walk.reads:
        if kind == "gather":
            notes.append("gather read (leading-axis indexing assumed)")
        if not (lo >= 0 and hi <= L - 1):
            reads_ok = False
            notes.append(f"read index interval [{lo}, {hi}] not within "
                         f"[0, {L - 1}]")
    first_write = (min(p for p, _ in walk.writes) if walk.writes
                   else walk.pos + 1)
    order_ok = all(p > first_write for p, _, _ in walk.reads)
    if walk.gated:
        notes.append("write liveness-gated: bound holds conditional on "
                     "liveness (crash = unbounded delay, by design)")
    return RingAudit(si, ci, L, len(walk.writes), len(walk.reads),
                     walk.gated, write_ok, reads_ok, order_ok, notes)


# ---------------------------------------------------------------------------
# donation / aliasing audit
# ---------------------------------------------------------------------------

_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\((\d+),")


@dataclasses.dataclass
class DonationAudit:
    """Which parameters of a compiled executable alias an output."""

    aliased_params: Set[int]
    expected_params: Set[int]

    @property
    def ok(self) -> bool:
        return self.expected_params <= self.aliased_params

    def to_dict(self) -> dict:
        return {"aliased_params": sorted(self.aliased_params),
                "expected_params": sorted(self.expected_params),
                "ok": self.ok}


def donation_audit(compiled_hlo_text: str,
                   expected_params: Sequence[int]) -> DonationAudit:
    """Parse ``input_output_alias`` from compiled HLO text and verify the
    expected donated parameter indices actually alias outputs.

    XLA records honored donations in the module header, e.g.
    ``input_output_alias={ {0}: (1, {}, may-alias), {1}: (2, {}, ...) }``
    — a donation that silently degraded to a copy simply won't appear.
    """
    aliased: Set[int] = set()
    marker = "input_output_alias="
    start = compiled_hlo_text.find(marker)
    if start >= 0:
        # the table nests braces ({0}: (1, {}, may-alias)) — scan for the
        # balanced closing brace rather than regex-matching across it
        i = start + len(marker)
        depth = 0
        for j in range(i, len(compiled_hlo_text)):
            ch = compiled_hlo_text[j]
            if ch == "{":
                depth += 1
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    section = compiled_hlo_text[i:j + 1]
                    aliased = {int(p)
                               for p in _ALIAS_ENTRY_RE.findall(section)}
                    break
    return DonationAudit(aliased, set(expected_params))
