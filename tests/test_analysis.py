"""The static-analysis subsystem (``repro.analysis``): taint, schedule
audits, walker unification, mutant self-test, and the lint runner.

Everything here traces jaxprs only — no epoch is compiled or run — so
the module stays fast despite covering the whole analysis stack.
"""
import jax
import jax.numpy as jnp
import pytest

from repro import analysis
from repro.analysis import entrypoints as ep
from repro.analysis import mutants as mu
from repro.analysis import runner
from repro.analysis.schedule import _Intervals, donation_audit, ring_audit
from repro.analysis.taint import (EQUAL_SEEDED, NO_REKEY, UNMASKED,
                                  analyze_party_jaxpr, finding_codes)


# -- walker unification (satellite a) ---------------------------------------

def test_engine_reexports_shared_walkers():
    from repro.core import engine
    assert engine.count_primitives is analysis.count_primitives
    assert engine.count_primitive is analysis.count_primitive
    assert engine.scan_body_primitive_counts is \
        analysis.scan_body_primitive_counts


def test_bench_reexports_shared_walkers():
    from benchmarks import bench_engine
    assert bench_engine.count_host_transfers is analysis.count_host_transfers
    assert set(bench_engine.HOST_TRANSFER_PRIMS) == \
        set(analysis.HOST_TRANSFER_PRIMS)


def test_walker_counts_through_nested_combinators():
    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "i"), None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    jx = jax.make_jaxpr(f, axis_env=[("i", 2)])(jnp.zeros(4))
    assert analysis.count_primitive(jx, "psum") == 1
    assert analysis.count_cross_party(jx) == 1
    assert analysis.count_host_transfers(jx) == 0


# -- interval abstract interpretation ---------------------------------------

def test_intervals_prove_mod_bounds():
    # jnp.mod lowers to a pjit with a sign-fix select; the analysis must
    # still prove the [0, L-1] bound for a nonnegative dividend
    jx = jax.make_jaxpr(lambda t: jnp.maximum(t - 5, 0) % 3)(
        jnp.int32(0))
    iv = _Intervals(jx.jaxpr)
    lo, hi = iv.get(jx.jaxpr.outvars[0])
    assert (lo, hi) == (0.0, 2.0)


def test_intervals_unknown_primitive_fails_closed():
    jx = jax.make_jaxpr(lambda t: jnp.sin(t.astype(jnp.float32)))(
        jnp.int32(0))
    iv = _Intervals(jx.jaxpr)
    lo, hi = iv.get(jx.jaxpr.outvars[0])
    assert lo == float("-inf") and hi == float("inf")


# -- leakage taint analysis --------------------------------------------------

@pytest.fixture(scope="module")
def quick_reports():
    return ep.analyze_matrix(secure_modes=("off", "ring"), names=ep.QUICK)


def test_insecure_mode_flags_unmasked_boundary(quick_reports):
    for r in quick_reports:
        if r.secure == "off":
            assert r.taint.get(UNMASKED, 0) >= 1, r.key


def test_secure_modes_are_clean(quick_reports):
    for r in quick_reports:
        if r.secure != "off":
            assert r.taint == {}, (r.key, r.taint)


def test_two_tree_and_schedule_faithful_clean():
    reports = ep.analyze_matrix(secure_modes=("two_tree", "two_tree_sf"),
                                names=("sgd",))
    for r in reports:
        assert r.taint == {}, (r.key, r.taint)
        assert r.cross_party >= 2  # masked value + mask aggregate


def test_epochs_have_no_host_transfers(quick_reports):
    for r in quick_reports:
        assert r.host_transfers == 0, r.key


# -- mutants (satellite c): the analyzer must actually fire ------------------

def test_mutant_selftest_catches_all_three():
    results = {r.name: r for r in mu.run_selftest()}
    assert results["off_psum"].actual.get(UNMASKED, 0) >= 1
    assert results["equal_seeded"].actual.get(EQUAL_SEEDED, 0) >= 1
    assert results["no_rekey"].actual.get(NO_REKEY, 0) >= 1
    assert results["control_two_tree"].actual == {}
    assert results["control_ring_members"].actual == {}
    assert all(r.ok for r in results.values())


def test_predrawn_mutant_flags_equal_seeded_stream():
    """A stream drawn before the scan, from the step keys without the
    party index, reaches the boundary through the scan input: the pass
    follows it there and calls it equal-seeded; the shipped draw is
    clean."""
    results = {r.name: r for r in mu.run_selftest()}
    assert results["predrawn_equal_seeded"].actual == {EQUAL_SEEDED: 1}
    assert results["control_predrawn"].actual == {}


#: the matrix's flat fault-free epochs: each draws its step masks before
#: its scan
PREDRAWN = ("sgd", "svrg", "saga", "multi_sgd", "pipelined_sgd",
            f"delayed{ep.TAU}", f"multi_delayed{ep.TAU}", "deep_sgd",
            "deep_multi_sgd", "deep_svrg", "deep_pipelined_sgd",
            f"deep_delayed{ep.TAU}")


@pytest.mark.parametrize("secure", ["two_tree", "ring", "two_tree_sf"])
def test_predrawn_epochs_prove_masked_boundaries(secure):
    """Every flat fault-free epoch of the matrix feeds its aggregations
    from a pre-drawn stream, and the taint pass still proves each
    boundary masked by a party-distinct stream."""
    from repro import tracing
    with tracing.Recorder() as rec:
        reports = ep.analyze_matrix(secure_modes=(secure,), names=PREDRAWN)
    assert sorted(r.name for r in reports) == sorted(PREDRAWN)
    for r in reports:
        assert r.taint == {}, (r.key, r.taint)
        assert rec.total("vfb2.masks.predrawn", r.name) >= 1, r.key
        assert rec.total("vfb2.masks.per_step", r.name) == 0, r.key


def test_no_rekey_only_flagged_under_membership():
    # without membership semantics the per-party ring masks are fine;
    # the finding is specifically about the missing alive-set re-key
    z = jnp.zeros((8,), jnp.float32)
    key = jax.random.key(0)
    jx = mu._trace(mu.no_rekey, z, key, jnp.float32(1.0))
    assert finding_codes(analyze_party_jaxpr(jx, [0], axis=mu.AXIS)) == {}
    flagged = finding_codes(
        analyze_party_jaxpr(jx, [0], axis=mu.AXIS, membership=True))
    assert flagged.get(NO_REKEY, 0) >= 1


def test_is_finite_declassification():
    """The health channel: a program that ships ONLY the finiteness
    verdict of its private partial is clean (additive masks cannot hide
    a NaN/Inf, so the verdict is protocol-public), while shipping the
    raw partial still flags."""
    def health_only(x):
        healthy = jnp.all(jnp.isfinite(x)).astype(jnp.float32)
        return jax.lax.psum(healthy, "model")

    def raw_leak(x):
        return jax.lax.psum(x, "model")

    axis_env = [("model", 4)]
    jx = jax.make_jaxpr(health_only, axis_env=axis_env)(jnp.ones(8))
    assert finding_codes(analyze_party_jaxpr(jx, [0], axis="model")) == {}
    jx2 = jax.make_jaxpr(raw_leak, axis_env=axis_env)(jnp.ones(8))
    flagged = finding_codes(analyze_party_jaxpr(jx2, [0], axis="model"))
    assert flagged.get(UNMASKED, 0) >= 1


def test_taint_sees_through_the_kernel_wrapper():
    """The kernel call sits inside its batching wrapper's ``call`` jaxpr:
    the taint pass walks into it, so a party's raw partial from the
    kernel still flags at the boundary, and its finiteness verdict alone
    stays clean."""
    from repro.kernels import vfl_grad as vg

    def partial(x, w):
        return vg.vfl_grad(x, w, None, mode="forward", interpret=True)[0]

    def raw_leak(x, w):
        return jax.lax.psum(partial(x, w), "model")

    def health_only(x, w):
        ok = jnp.all(jnp.isfinite(partial(x, w))).astype(jnp.float32)
        return jax.lax.psum(ok, "model")

    args = (jnp.ones((8, 5)), jnp.ones((5,)))
    axis_env = [("model", 4)]
    jx = jax.make_jaxpr(raw_leak, axis_env=axis_env)(*args)
    assert analysis.count_primitive(jx, "custom_vmap_call") == 1
    found = analyze_party_jaxpr(jx, [0], axis="model")
    assert finding_codes(found).get(UNMASKED, 0) >= 1
    jx2 = jax.make_jaxpr(health_only, axis_env=axis_env)(*args)
    assert finding_codes(analyze_party_jaxpr(jx2, [0], axis="model")) == {}


def test_guarded_entries_lint_like_faulted(quick_reports):
    """Guarded epochs are membership-varying (the quarantine drops
    parties), so they must be analyzed with mask re-keying required."""
    guarded = [r for r in quick_reports
               if r.name == f"guarded_sgd{ep.TAU}_1"]
    assert guarded
    for r in guarded:
        assert r.membership and r.gated, r.key
        if r.secure != "off":
            assert r.taint == {}, (r.key, r.taint)


def test_membership_invariant_gates_guarded_entries():
    """The lint invariant: a guarded/faulted entry analyzed WITHOUT
    membership semantics is a hard check_reports error."""
    reports = ep.analyze_matrix(secure_modes=("ring",),
                                names=(f"guarded_sgd{ep.TAU}_1",))
    assert ep.check_reports(reports) == []
    for r in reports:
        r.membership = False
    errs = ep.check_reports(reports)
    assert any("membership" in e for e in errs)


# -- ring-buffer staleness audits -------------------------------------------

def test_delayed_rings_bounded_ungated(quick_reports):
    delayed = [r for r in quick_reports if r.name == f"delayed{ep.TAU}"]
    assert delayed
    for r in delayed:
        assert r.rings, r.key
        for ring in r.rings:
            assert ring["bounded"], (r.key, ring)
            assert not ring["gated"], (r.key, ring)
            assert ring["length"] == ep.TAU + 1


def test_faulted_rings_bounded_gated(quick_reports):
    faulted = [r for r in quick_reports if r.name == f"faulted_sgd{ep.TAU}"]
    assert faulted
    for r in faulted:
        assert r.rings, r.key
        for ring in r.rings:
            assert ring["bounded"], (r.key, ring)
            assert ring["gated"], (r.key, ring)


def test_oversized_ring_read_fails_the_proof():
    # a read indexed mod (tau+2) over a (tau+1)-slot buffer must not
    # verify: the interval [0, tau+1] exceeds the ring
    tau = 2

    def epoch(buf, t0):
        def body(carry, _):
            buf, t = carry
            g = jnp.ones(4) * t
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, g, t % (tau + 1), 0)
            bad = jax.lax.dynamic_index_in_dim(
                buf, jnp.maximum(t - 1, 0) % (tau + 2), 0,
                keepdims=False)
            return (buf, t + 1), bad
        (buf, _), out = jax.lax.scan(body, (buf, t0), None, length=3)
        return buf, out

    jx = jax.make_jaxpr(epoch)(jnp.zeros((tau + 1, 4)), jnp.int32(0))
    audits = ring_audit(jx, tau)
    assert audits and not audits[0].bounded


# -- donation audit ----------------------------------------------------------

def test_donation_audit_parses_alias_table():
    hlo = ("HloModule jit_epoch, input_output_alias={ {0}: (0, {}, "
           "may-alias), {1}: (2, {}, must-alias) }, "
           "entry_computation_layout={...}")
    audit = donation_audit(hlo, [0, 2])
    assert audit.aliased_params == {0, 2}
    assert audit.ok
    assert not donation_audit(hlo, [0, 1]).ok
    assert not donation_audit("HloModule bare", [0]).ok


def test_compiled_epoch_honors_donation():
    report = runner._donation_report()
    assert report["ok"], report


# -- lint runner gates -------------------------------------------------------

def test_check_reports_gates_on_leak():
    reports = ep.analyze_matrix(secure_modes=("off",), names=("sgd",))
    # untouched: off must flag, so no "secure mode leaks" error
    assert runner.check_report(
        {"mutants": {}, "matrix": {}, "donation": {"ok": True,
                                                   "expected_params": [],
                                                   "aliased_params": []},
         "kernels": {}, "_matrix_errors": ep.check_reports(reports)},
        None)[0] == []
    # simulate the analyzer going blind on the off entry
    blind = [r for r in reports]
    blind[0].taint = {}
    errs = ep.check_reports(blind)
    assert any("vacuity" in e for e in errs)


def test_check_report_flags_manifest_drift():
    report = {
        "mutants": {}, "_matrix_errors": [],
        "donation": {"ok": True, "expected_params": [], "aliased_params": []},
        "matrix": {"ring/sgd": {"taint": {}, "host_transfers": 0,
                                "cross_party": 1, "rings": []}},
        "kernels": {"sgd": [2]},
    }
    manifest = {
        "matrix": {"ring/sgd": {"taint": {"unmasked-boundary": 1},
                                "host_transfers": 0, "cross_party": 1,
                                "rings": []}},
        "kernels": {"sgd": [2]},
    }
    errors, _ = runner.check_report(report, manifest)
    assert any("drifted" in e for e in errors)
    manifest["matrix"]["ring/sgd"]["taint"] = {}
    errors, _ = runner.check_report(report, manifest)
    assert errors == []


def test_committed_manifest_matches_quick_run(quick_reports):
    """The committed INVARIANTS.json agrees with a fresh quick matrix."""
    import json
    if not runner.DEFAULT_MANIFEST.exists():
        pytest.skip("no committed manifest")
    manifest = json.loads(runner.DEFAULT_MANIFEST.read_text())
    for r in quick_reports:
        want = manifest["matrix"].get(r.key)
        assert want is not None, r.key
        assert want["taint"] == dict(r.taint), r.key
        assert want["host_transfers"] == r.host_transfers, r.key
        assert want["rings"] == runner._normalize_rings(r.rings), r.key
