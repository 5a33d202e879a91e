"""The XPlane reader and the phase reduction, against traces recorded on a
TPU v5e.

``fixtures/trace_1chip.xplane.pb`` is the program before it named its
phases (``test_trace.py`` counts it); ``fixtures/trace_1chip_scoped.xplane.pb``
is the same recording, by the same ``bench/record_fixture.py`` (two SVRG
calls of 8 steps on 4,096 rows × 4,096 columns, q = 4), of the program
with its ``vfb2.*`` spans and scopes.  The scoped numbers below were
counted from that file with TensorFlow's own ``xplane_pb2`` reader, not
with the code under test.
"""
from pathlib import Path

import pytest

from bench.harness import phases, trace, xspace

FIXTURES = Path(__file__).parent / "fixtures"
OLD = str(FIXTURES / "trace_1chip.xplane.pb")
SCOPED = str(FIXTURES / "trace_1chip_scoped.xplane.pb")


@pytest.mark.parametrize("path", [OLD, SCOPED])
def test_reader_agrees_with_profile_data(path):
    """Every event of every line: name, start and duration as JAX's own
    reader gives them."""
    from jax.profiler import ProfileData
    ours = xspace.read(path)
    theirs = list(ProfileData.from_file(path).planes)
    assert [p.name for p in ours] == [p.name for p in theirs]
    n = 0
    for p, q in zip(ours, theirs):
        lines = list(q.lines)
        assert [ln.name for ln in p.lines] == [ln.name for ln in lines]
        for ln, lq in zip(p.lines, lines):
            evs = list(lq.events)
            assert len(ln.events) == len(evs)
            for e, f in zip(ln.events, evs):
                assert (e.name, e.start_ns, e.duration_ns) == \
                    (f.name, f.start_ns, f.duration_ns)
                assert e.stats == dict(f.stats)
                n += 1
    assert n > 1000


def test_scope_is_the_innermost():
    assert phases.scope("jit(epoch)/vmap(vfb2.party)/while/body/closed_call/"
                        "vfb2.contract/vfl_grad_forward/pallas_call") \
        == "vfb2.contract"
    assert phases.scope("jit(epoch)/vmap(vfb2.party)/while/body/add") \
        == "vfb2.party"
    assert phases.scope("jit(_threefry_split)/slice:") is None
    assert phases.scope(None) is None


def test_unscoped_program_reads_nothing():
    """The program before this reduction existed: no scope, no dispatch
    span, so no phase number, and the same idle gaps as the benchmark's
    own reduction."""
    ph = phases.load(OLD)
    red = trace.reduce_file(OLD)
    assert ph.steps == 0 and not ph.scoped()
    assert all(ph.step_us(k) is None for k in phases.PHASES)
    assert ph.unattributed_share() is None
    assert ph.idle_gaps() == red.breakdown()["idle_gaps"]
    [ops] = ph.chips.values()
    [ref] = red.chips.values()
    assert [(o.start, o.end) for o in ops] == [(o.start, o.end) for o in ref]


# Counted from the scoped fixture (TensorFlow's xplane_pb2, an event
# enclosing another taken as its parent, a boolean grid for the union):
# ``bench_window`` runs 39,775,237..53,226,286 ns; the ``vfb2.dispatch``
# spans inside it are full_grad (steps 0), svrg (8), full_grad (0), svrg
# (8).  The ``XLA Ops`` line of ``/device:TPU:0`` holds 526 events, 520
# ops in the window once the two ``while`` events are set aside, 400 of
# them inside a ``while``.  Device ns by innermost scope:
SCOPED_WINDOW = (39_775_237, 53_226_286)
SCOPED_ALL = {None: 547_328, "vfb2.party": 91_868,
              "vfb2.contract": 2_034_958, "vfb2.aggregate": 36_480,
              "vfb2.sample": 8_077, "vfb2.gather": 38_185}
SCOPED_LOOP = {"vfb2.party": 86_039, "vfb2.aggregate": 33_865,
               "vfb2.gather": 38_185, "vfb2.contract": 317_083}
SCOPED_LOOP_BUSY = 475_172       # union of the in-loop ops' intervals
SCOPED_BUSY = 2_756_896


@pytest.fixture(scope="module")
def scoped():
    return phases.load(SCOPED)


def test_scoped_window_ops_and_steps(scoped):
    assert scoped.window == SCOPED_WINDOW
    [ops] = scoped.chips.values()
    assert len(ops) == 520 and sum(o.in_loop for o in ops) == 400
    assert scoped.steps == 16


def test_scoped_time_by_scope(scoped):
    assert scoped.by_scope() == SCOPED_ALL
    assert scoped.by_scope(True) == SCOPED_LOOP
    # the phases and the unscoped rest make up the loop's busy time
    assert sum(scoped.by_scope(True).values()) == SCOPED_LOOP_BUSY
    assert sum(scoped.by_scope().values()) == SCOPED_BUSY
    assert trace.reduce_file(SCOPED).busy_s == pytest.approx(
        SCOPED_BUSY * 1e-9, rel=1e-12)


def test_scoped_step_phases(scoped):
    step = {k: scoped.step_us(k) for k in phases.PHASES}
    assert step == pytest.approx({
        "gather": 38_185 / 16e3, "contract": 317_083 / 16e3,
        "aggregate": 33_865 / 16e3, "update": 86_039 / 16e3}, rel=1e-12)
    assert sum(step.values()) == pytest.approx(SCOPED_LOOP_BUSY / 16e3,
                                               rel=1e-12)
    assert scoped.unattributed_share() == pytest.approx(
        100 * 547_328 / SCOPED_BUSY, rel=1e-12)


def test_scoped_idle_gaps_carry_program_labels(scoped):
    gaps = scoped.idle_gaps()
    assert [g[0] for g in gaps] == [
        "vfb2.objective.enqueue", "vfb2.objective.enqueue",
        "vfb2.objective.fetch", "vfb2.objective.enqueue",
        "vfb2.objective.enqueue", "vfb2.objective.enqueue", "vfb2.dispatch",
        "vfb2.objective.enqueue", "vfb2.objective.enqueue", "vfb2.dispatch"]
    assert [round(g[1] * 1e9) for g in gaps[:3]] == [2_706_149, 2_243_762,
                                                     744_984]


def test_named_kernels_are_still_mosaic_calls():
    """The ``pallas_call`` names reach the trace as the HLO instructions'
    names, and each call still carries ``custom_call_target=
    "tpu_custom_call"``, so the benchmark's kernel reduction finds all 36
    as on the unnamed program."""
    red = trace.reduce_file(SCOPED)
    calls = red.mosaic_calls()
    assert len(calls) == 36
    names = {c.text.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
             for c in calls}
    assert names == {"vfl_grad_forward", "vfl_grad_backward"}
    assert all(trace.MOSAIC in c.text for c in calls)
