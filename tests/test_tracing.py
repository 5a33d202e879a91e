"""Host spans, the in-memory recorder, and the engine's named scopes.

``repro.tracing`` puts the engine's set-up parts and program calls on
the profiler's clock; ``jax.named_scope`` names in ``core/engine.py``
carry the step's phases into the device ops' metadata.  Nothing here
needs a profiler or a chip: the recorder keeps what a trace would show
on the host, and the lowered text (with debug info) holds the scopes
the compiled program's ops will carry.
"""
import re

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import algorithms, losses
from repro.core.engine import EngineConfig, FusedEngine
from repro.data.synthetic import classification_dataset

N, D, Q, BATCH, STEPS = 256, 20, 4, 16, 8
SCOPES = ("vfb2.sample", "vfb2.gather", "vfb2.contract", "vfb2.aggregate",
          "vfb2.party")


def _engine(secure="two_tree", use_kernel=False):
    ds = classification_dataset("trace", N, D, seed=4, noise=0.3)
    layout = algorithms.PartyLayout.even(D, Q, 1)
    return FusedEngine(losses.logistic_l2(), ds.x_train, ds.y_train,
                       layout, EngineConfig(secure=secure,
                                            use_kernel=use_kernel))


@pytest.fixture(scope="module")
def eng():
    return _engine()


def test_recorder_nesting_parents_and_attributes():
    with tracing.Recorder() as rec:
        with tracing.span("outer", kind="a", n=3):
            with tracing.span("inner.first"):
                pass
            with tracing.span("inner.second", step=1):
                with tracing.span("leaf"):
                    pass
        with tracing.span("after"):
            pass
    names = [s.name for s in rec.spans]
    assert names == ["outer", "inner.first", "inner.second", "leaf", "after"]
    outer, first, second, leaf, after = rec.spans
    assert outer.parent is None and after.parent is None
    assert first.parent == 0 and second.parent == 0 and leaf.parent == 2
    assert outer.attrs == {"kind": "a", "n": 3}
    assert second.attrs == {"step": 1} and leaf.attrs == {}
    for child, parent in ((first, outer), (second, outer), (leaf, second)):
        assert parent.start_ns <= child.start_ns <= child.end_ns \
            <= parent.end_ns
    assert outer.end_ns <= after.start_ns
    assert rec.seconds("outer") == pytest.approx(outer.seconds)
    assert rec.seconds("missing") == 0


def test_span_and_count_without_recorder_or_profiler():
    with tracing.span("vfb2.idle", program="p", steps=2):
        tracing.count("things", 5)
    with pytest.raises(ZeroDivisionError):
        with tracing.span("raises"):
            1 / 0
    with tracing.Recorder() as rec:     # nothing from before it started
        pass
    assert rec.spans == [] and rec.counters == {}


def test_span_closes_on_error_and_one_recorder_at_a_time():
    with tracing.Recorder() as rec:
        with pytest.raises(RuntimeError, match="already active"):
            with tracing.Recorder():
                pass
        with pytest.raises(KeyError):
            with tracing.span("fails"):
                raise KeyError("x")
        with tracing.span("next"):
            pass
    assert [s.name for s in rec.spans] == ["fails", "next"]
    assert rec.spans[1].parent is None


def test_counts_are_booked_to_the_enclosing_program():
    with tracing.Recorder() as rec:
        tracing.count("calls")
        with tracing.span(tracing.DISPATCH, program="sgd", steps=4):
            with tracing.span("inside"):
                tracing.count("calls", 2)
        with tracing.span(tracing.DISPATCH, program="svrg", steps=4):
            tracing.count("calls")
    assert rec.counters == {("calls", None): 1, ("calls", "sgd"): 2,
                            ("calls", "svrg"): 1}
    assert rec.total("calls") == 4
    assert rec.total("calls", "sgd") == 2


def _lowered(eng, name):
    """The program's lowering, after one call has built it."""
    wq = eng.pack_w(np.zeros(D, np.float32))
    key = jax.random.PRNGKey(2)
    if name == "sgd":
        eng.sgd_epoch(wq, 0.1, key, BATCH, STEPS)
        low = eng._jitted["sgd"].lower(eng.xs, wq, eng.maskq, eng.y, 0.1,
                                       key, BATCH, STEPS)
    elif name == "svrg":
        mu = eng.full_gradient(wq, key)
        eng.svrg_epoch(wq, wq, mu, 0.1, key, BATCH, STEPS)
        low = eng._jitted["svrg"].lower(eng.xs, wq, wq, mu, eng.maskq,
                                        eng.y, 0.1, key, BATCH, STEPS)
    else:
        eng.full_gradient(wq, key)
        low = eng._jitted["full_grad"].lower(eng.xs, wq, eng.y, key)
    return low


@pytest.mark.parametrize("name,scopes", [
    ("sgd", SCOPES),
    ("svrg", SCOPES),
    ("full_grad", ("vfb2.contract", "vfb2.aggregate", "vfb2.party")),
])
def test_scopes_reach_the_lowered_program(eng, name, scopes):
    low = _lowered(eng, name)
    found = set(re.findall(r"vfb2\.[a-z]+", low.as_text(debug_info=True)))
    assert set(scopes) <= found
    # the compiled ops' metadata, which a device trace reports per op,
    # holds the whole name stack: each phase inside the party's scope
    op_names = set(re.findall(r'op_name="([^"]*)"', low.compile().as_text()))
    for scope in set(scopes) - {"vfb2.sample", "vfb2.party"}:
        assert any("vfb2.party" in n and n.rfind(scope) > n.find("vfb2.party")
                   for n in op_names), scope


def _name_stacks(jaxpr):
    from repro.analysis.walkers import sub_jaxprs
    for eqn in jaxpr.eqns:
        yield str(eqn.source_info.name_stack)
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from _name_stacks(sub)


def test_guard_scope_in_guarded_epoch():
    import jax.numpy as jnp
    eng = _engine(secure="ring")
    tau = 1
    wq = eng.pack_w(np.zeros(D, np.float32))
    bufq = jnp.zeros((Q, tau + 1, eng.dp), jnp.float32)
    ones = jnp.ones((Q, STEPS), jnp.float32)
    zeros_i = jnp.zeros((Q, STEPS), jnp.int32)
    jx = eng.guarded_sgd_epoch_jaxpr(
        wq, bufq, jnp.int32(0), jnp.zeros((Q,), jnp.int32), ones, ones,
        zeros_i, zeros_i, 0.3, jax.random.PRNGKey(0), BATCH, STEPS, tau)
    stacks = set(_name_stacks(jx.jaxpr))
    found = {m for s in stacks for m in re.findall(r"vfb2\.[a-z]+", s)}
    assert found == set(SCOPES) | {"vfb2.guard"}


def test_engine_build_dispatch_and_loads_are_recorded():
    wq0 = np.zeros(D, np.float32)
    key = jax.random.PRNGKey(7)
    with tracing.Recorder() as rec:
        eng = _engine()
        wq = eng.pack_w(wq0)
        wq = eng.sgd_epoch(wq, 0.1, key, BATCH, STEPS)
        jax.block_until_ready(wq)
        first = rec.load_s("sgd")
        n_first = rec.total("jax.compile", "sgd")
        wq = eng.sgd_epoch(wq, 0.1, key, BATCH, STEPS)
        obj = eng.objective(wq)
    assert np.isfinite(obj)
    by_name = {}
    for i, s in enumerate(rec.spans):
        by_name.setdefault(s.name, []).append((i, s))
    [(b, build)] = by_name["vfb2.engine.build"]
    [(_, pack)] = by_name["vfb2.engine.pack"]
    [(_, place)] = by_name["vfb2.engine.place"]
    assert pack.parent == b and place.parent == b
    assert build.attrs == {"q": Q, "rows": eng.n}
    assert pack.end_ns <= place.start_ns
    assert build.start_ns <= pack.start_ns and place.end_ns <= build.end_ns
    dispatch = [s for _, s in by_name[tracing.DISPATCH]]
    assert [s.attrs for s in dispatch] == [{"program": "sgd",
                                            "steps": STEPS}] * 2
    # the first call traced, lowered and compiled the program; the second
    # found it built, so no load second is booked to it
    assert first > 0 and n_first == 1
    assert rec.load_s("sgd") == first
    assert rec.total("jax.compile", "sgd") == 1
    assert rec.total("jax.trace", "sgd") == 1
    assert rec.load_s() >= first
    [(o, _)] = by_name["vfb2.objective"]
    assert [s.parent for _, s in by_name["vfb2.objective.enqueue"]] == [o]
    assert [s.parent for _, s in by_name["vfb2.objective.fetch"]] == [o]


def test_one_chip_svrg_books_grouped_kernel_calls():
    """The one-chip engine calls the kernel under its vmap over parties:
    each call is built with the parties inside its blocks, and counted as
    such under the program that traced it."""
    eng = _engine(use_kernel=True)
    wq = eng.pack_w(np.zeros(D, np.float32))
    key = jax.random.PRNGKey(3)
    with tracing.Recorder() as rec:
        mu = eng.full_gradient(wq, key)
        eng.svrg_epoch(wq, wq, mu, 0.1, key, BATCH, STEPS)
    # forward at both iterates and the backward: one call each (M = 2)
    assert rec.total("vfb2.kernel.grouped", "svrg") == 2
    assert rec.total("vfb2.kernel.per_party") == 0


@pytest.mark.parametrize("secure", ["two_tree", "ring"])
def test_one_chip_epochs_predraw_their_step_masks(secure):
    """The one-chip SGD and SVRG epochs draw every step's mask before the
    scan: each books its aggregation as ``vfb2.masks.predrawn``, and no
    random-bit generation is left in the scan body."""
    from repro.analysis.walkers import count_primitives, primitive_eqns
    eng = _engine(secure)
    wq = eng.pack_w(np.zeros(D, np.float32))
    key = jax.random.PRNGKey(3)
    with tracing.Recorder() as rec:
        mu = eng.full_gradient(wq, key)
        epochs = {
            "sgd": lambda w: eng.sgd_epoch(w, 0.1, key, BATCH, STEPS),
            "svrg": lambda w: eng.svrg_epoch(w, w, mu, 0.1, key, BATCH,
                                             STEPS)}
        jaxprs = {name: jax.make_jaxpr(f)(wq) for name, f in epochs.items()}
    rng = {"random_bits", "threefry2x32"}
    for name, jx in jaxprs.items():
        assert rec.total("vfb2.masks.predrawn", name) == 1
        assert rec.total("vfb2.masks.per_step", name) == 0
        [scan] = primitive_eqns(jx, "scan")
        assert count_primitives(scan.params["jaxpr"], rng) == 0
        assert count_primitives(jx, rng) > 0       # the draw, before it


def test_member_keyed_and_packed_aggregations_draw_per_step():
    """Faulted and guarded epochs key each step's masks on that step's
    alive set, and a packed PartyMesh epoch salts two stream levels:
    all three still draw inside the step."""
    from repro.analysis import entrypoints as ep
    entries = {e.name: e for e in ep._entries()}
    runs = [(ep._Fixture("two_tree"), f"faulted_sgd{ep.TAU}"),
            (ep._Fixture("two_tree"), f"guarded_sgd{ep.TAU}_1"),
            (ep._Fixture("two_tree", pmesh=ep.HIER), "hier_sgd")]
    for fx, name in runs:
        ent = entries[name]
        prog = ent.prog or name
        with tracing.Recorder() as rec:
            ent.trace(fx.eng, fx)
        assert rec.total("vfb2.masks.per_step", prog) >= 1, name
        assert rec.total("vfb2.masks.predrawn", prog) == 0, name


@pytest.mark.parametrize("q,party_mib,expect", [
    (4, 1, 4),      # all four fit half the default scope
    (4, 3, 2),      # four do not, two do
    (4, 5, 1),      # not even two: one party a visit
    (3, 3, 1),      # three do not fit, and 3's only smaller divisor is 1
    (6, 3, 2),      # the largest divisor of 6 that fits
])
def test_parties_per_visit_from_shapes(q, party_mib, expect):
    from repro.kernels import vfl_grad as vg
    assert vg.VMEM_BUDGET == 8 << 20
    assert vg.parties_per_visit(q, party_mib << 20) == expect


@pytest.mark.parametrize("rows,parties,kind", [
    (64, 4, "grouped"),         # D4's step: 4 × 0.25 MiB
    (4096, 2, "grouped"),       # 4 × 2.4 MiB (the z accumulator) > 8 MiB
    (16384, 1, "per_party"),    # one party's accumulator alone is 8 MiB
])
def test_vmem_budget_sets_parties_per_visit(rows, parties, kind):
    """Q comes from the block shapes alone: traced abstractly (nothing of
    this size is allocated), a forward call over 1,024 columns per party
    whose z accumulator outgrows the budget holds fewer parties a visit."""
    from repro.analysis.walkers import primitive_eqns
    from repro.kernels import vfl_grad as vg
    x = jax.ShapeDtypeStruct((4, rows, 1024), np.float32)
    w = jax.ShapeDtypeStruct((4, 1024, 32), np.float32)

    def party(xb, wb):
        return vg.vfl_grad(xb, wb, None, mode="forward", interpret=False)[0]

    with tracing.Recorder() as rec:
        jx = jax.make_jaxpr(jax.vmap(party))(x, w)
    assert {k: rec.total(f"vfb2.kernel.{k}")
            for k in ("grouped", "per_party")} == {
                k: int(k == kind) for k in ("grouped", "per_party")}
    [eqn] = primitive_eqns(jx, "pallas_call")
    gm = eqn.params["grid_mapping"]
    assert gm.block_mappings[0].block_shape[0].block_size == parties
    assert gm.grid[:-2] == ((4 // parties,) if parties < 4 else ())
