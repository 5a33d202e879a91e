"""Shared jaxpr walkers — the one copy of the primitive-census machinery.

Before this module existed the repo carried three divergent ad-hoc
walkers: ``core/engine.py`` (primitive counts + scan-body census for the
launch audits), ``benchmarks/bench_engine.py`` (host-transfer census for
the zero-roundtrip gate), and inline variants in tests.  They are unified
here; ``core.engine`` and ``benchmarks.bench_engine`` re-export these
names so every existing import keeps working.

All walkers recurse through **every** jaxpr hiding in an equation's
params — ``pjit`` bodies, ``scan``/``while`` bodies, ``cond`` branch
tuples, custom-derivative call jaxprs, ``pallas_call`` kernel bodies — so
a primitive cannot hide from the census inside a nested combinator.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Set, Union

Names = Union[str, Set[str], frozenset, Iterable[str]]

#: Primitives that force a host↔device round-trip (or smuggle host data
#: into a compiled program).  The fused engine's whole-epoch programs must
#: contain **zero** of these — one of the structural headlines of PR 1.
HOST_TRANSFER_PRIMS = frozenset({
    "callback", "pure_callback", "io_callback", "debug_callback",
    "infeed", "outfeed", "device_put", "host_local_array_to_global_array",
})

#: Cross-party communication primitives: the trust-boundary crossings of
#: the VFB² protocol.  Any value flowing through one of these leaves the
#: party that computed it (under the vmap emulation and under shard_map
#: alike — the named-axis semantics are identical).
CROSS_PARTY_PRIMS = frozenset({
    "psum", "ppermute", "pbroadcast", "all_gather", "all_to_all",
    "psum_scatter", "pgather", "reduce_scatter",
})


def sub_jaxprs(v) -> Iterator:
    """Yield every jaxpr hiding in an eqn param value (ClosedJaxpr, raw
    Jaxpr, or tuples/lists of either — cond branches, pjit bodies...)."""
    inner = getattr(v, "jaxpr", None)
    if inner is not None:                      # ClosedJaxpr
        yield inner
    elif hasattr(v, "eqns"):                   # raw Jaxpr
        yield v
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from sub_jaxprs(item)


def _as_jaxpr(jaxpr):
    """Accept a ClosedJaxpr or a raw Jaxpr."""
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _as_name_set(names: Names) -> frozenset:
    if isinstance(names, str):
        return frozenset({names})
    return frozenset(names)


def count_primitives(jaxpr, names: Names) -> int:
    """Recursively count occurrences of any primitive in ``names`` (a
    name or a set of names) in a (closed) jaxpr."""
    names = _as_name_set(names)
    total = 0
    for eqn in _as_jaxpr(jaxpr).eqns:
        if eqn.primitive.name in names:
            total += 1
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                total += count_primitives(sub, names)
    return total


def count_primitive(jaxpr, name: str) -> int:
    """Recursively count occurrences of primitive ``name`` in a jaxpr."""
    return count_primitives(jaxpr, name)


def count_host_transfers(jaxpr) -> int:
    """Recursively count host-transfer primitives in a (closed) jaxpr.

    Recurses through every param value, including tuples/lists of jaxprs
    (``lax.cond`` branches, custom-call sub-jaxprs), so a callback hidden
    anywhere in an epoch program is counted.
    """
    return count_primitives(jaxpr, HOST_TRANSFER_PRIMS)


def count_cross_party(jaxpr) -> int:
    """Recursively count cross-party collective primitives."""
    return count_primitives(jaxpr, CROSS_PARTY_PRIMS)


def scan_body_primitive_counts(jaxpr, name: str) -> List[int]:
    """Per-``scan``-body occurrence counts of primitive ``name``.

    The scan body executes once per step of a fused epoch, so this is the
    audit for "N kernel invocations per step": the sequential SGD epoch
    shows [2] (forward + backward launch) and the pipelined epoch [1]
    (the single split-batch fused launch) for ``name='pallas_call'``.
    """
    counts: List[int] = []

    def walk(j):
        for eqn in j.eqns:
            subs = [s for v in eqn.params.values() for s in sub_jaxprs(v)]
            if eqn.primitive.name == "scan":
                counts.extend(count_primitive(s, name) for s in subs)
            else:
                for s in subs:
                    walk(s)

    walk(_as_jaxpr(jaxpr))
    return counts


def primitive_eqns(jaxpr, name: str) -> List:
    """Every equation of primitive ``name`` in a (closed) jaxpr, nested
    ones included (a kernel call inside its batching wrapper's ``call``
    jaxpr, a scan body...), in program order."""
    found: List = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == name:
                found.append(eqn)
            for v in eqn.params.values():
                for s in sub_jaxprs(v):
                    walk(s)

    walk(_as_jaxpr(jaxpr))
    return found


def primitive_histogram(jaxpr) -> Dict[str, int]:
    """Full recursive primitive census of a (closed) jaxpr."""
    hist: Dict[str, int] = {}

    def walk(j):
        for eqn in j.eqns:
            hist[eqn.primitive.name] = hist.get(eqn.primitive.name, 0) + 1
            for v in eqn.params.values():
                for s in sub_jaxprs(v):
                    walk(s)

    walk(_as_jaxpr(jaxpr))
    return hist
