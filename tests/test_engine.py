"""Fused federated step engine vs the sequential reference (losslessness).

The acceptance bar: each fused epoch must reproduce ``core.algorithms``'s
epoch bodies to ≤ 1e-5 (they match to float ulp in practice), with the
secure-aggregation modes costing nothing, and both the jnp and the Pallas
rank-k kernel routings agreeing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms, losses, staleness
from repro.core.engine import (EngineConfig, FusedEngine, pack_vec,
                               scan_body_primitive_counts, unpack_vec)
from repro.data.synthetic import classification_dataset

NTOTAL, D, BATCH = 1000, 50, 32


@pytest.fixture(scope="module")
def ds():
    # d = 50 over q = 8 parties => uneven block widths (pad path exercised)
    return classification_dataset("eng", NTOTAL, D, seed=3, noise=0.4)


@pytest.fixture(scope="module")
def layout():
    return algorithms.PartyLayout.even(D, 8, 3)


@pytest.fixture(scope="module")
def prob():
    return losses.logistic_l2()


def _ref_inputs(ds, layout):
    x = jnp.asarray(ds.x_train)
    y = jnp.asarray(ds.y_train)
    mask = jnp.asarray(layout.update_mask(D, False))
    return x, y, mask


def test_pack_unpack_roundtrip(layout):
    v = np.arange(D, dtype=np.float32)
    assert np.array_equal(unpack_vec(pack_vec(v, layout), layout), v)


def test_fused_sgd_matches_reference(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(0)
    steps = ds.x_train.shape[0] // BATCH
    w_ref = algorithms.sgd_epoch(prob, jnp.zeros(D), x, y, 0.5, mask, key,
                                 BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq = eng.sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-6, rtol=0)


def test_fused_sgd_single_party_equals_pooled(ds, prob):
    """q = 1: the fused program degenerates to the pooled-data math —
    the losslessness claim with no partition error at all."""
    layout1 = algorithms.PartyLayout.even(D, 1, 1)
    x, y, _ = _ref_inputs(ds, layout1)
    mask = jnp.asarray(layout1.update_mask(D, False))
    key = jax.random.PRNGKey(1)
    steps = ds.x_train.shape[0] // BATCH
    w_ref = algorithms.sgd_epoch(prob, jnp.zeros(D), x, y, 0.5, mask, key,
                                 BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout1,
                      EngineConfig(secure="off"))
    wq = eng.sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-6, rtol=0)


def test_fused_svrg_matches_reference(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(2)
    steps = ds.x_train.shape[0] // BATCH
    w0 = jnp.zeros(D)
    mu = algorithms.full_gradient(prob, w0, x, y)
    w_ref = algorithms.svrg_epoch(prob, w0, w0, mu, x, y, 0.5, mask, key,
                                  BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    muq = eng.full_gradient(wq0, key)
    np.testing.assert_allclose(eng.unpack_w(muq), np.asarray(mu), atol=1e-6,
                               rtol=0)
    wq = eng.svrg_epoch(wq0, wq0, muq, 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_fused_saga_matches_reference(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(3)
    steps = ds.x_train.shape[0] // BATCH
    tab = prob.theta(x @ jnp.zeros(D), y)
    avg = x.T @ tab / x.shape[0]
    w_ref, tab_ref, _ = algorithms.saga_epoch(prob, jnp.zeros(D), tab, avg,
                                              x, y, 0.5, mask, key, BATCH,
                                              steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    tabq, avgq = eng.saga_init(wq0, key)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tab),
                               atol=1e-6, rtol=0)
    wq, tabq, avgq = eng.saga_epoch(wq0, tabq, avgq, 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)
    # every party maintains the same ϑ̃ table (replicated by construction)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tabq[-1]),
                               atol=0, rtol=0)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tab_ref),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("secure", ["two_tree", "ring"])
def test_secure_modes_are_lossless(ds, layout, prob, secure):
    """Algorithm 1's masks cancel exactly enough that the secure epochs
    track the unmasked ones (the paper's losslessness under security)."""
    key = jax.random.PRNGKey(4)
    steps = ds.x_train.shape[0] // BATCH
    base = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                       EngineConfig(secure="off"))
    w_base = base.unpack_w(base.sgd_epoch(base.pack_w(np.zeros(D)), 0.5,
                                          key, BATCH, steps))
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure=secure))
    w_sec = eng.unpack_w(eng.sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key,
                                       BATCH, steps))
    np.testing.assert_allclose(w_sec, w_base, atol=1e-5, rtol=0)


def _mask_epoch(eng, epoch, key, steps):
    """One SGD or SVRG epoch from a nonzero iterate; host copy of wq."""
    wq = eng.pack_w(np.linspace(-0.2, 0.2, D))
    if epoch == "sgd":
        return np.asarray(eng.sgd_epoch(wq, 0.5, key, BATCH, steps))
    mu = eng.full_gradient(wq, key)
    return np.asarray(eng.svrg_epoch(wq, wq, mu, 0.5, key, BATCH, steps))


# no ring case on one party: its mask PRG(s₀) − PRG(s₀) is zero when drawn
# once, while the per-step draw subtracts two separately fused copies of
# one draw, which XLA's CPU backend may round apart
MASK_CASES = [(e, s, lay, "fits") for e in ("sgd", "svrg")
              for s in ("two_tree", "ring")
              for lay in ("flat", "data_axis", "mesh1")
              if (s, lay) != ("ring", "mesh1")] \
    + [("svrg", "two_tree", "data_axis", "over")]


@pytest.mark.parametrize("epoch,secure,lay,budget", MASK_CASES)
def test_predrawn_masks_equal_per_step_draws(ds, layout, prob, monkeypatch,
                                             epoch, secure, lay, budget):
    """An epoch's step masks drawn before its scan, in one batched op, are
    bit for bit the masks each step draws from its own key (data shard,
    then party folded in), and the epoch's updates match the per-step
    draw's (budget 0).  ``over``: a budget one byte short of the stream
    takes the per-step path; the stream's own size still pre-draws.

    The updates agree to f32 rounding, not to the bit: XLA's CPU backend
    computes a per-step δ twice, in the fusion that adds it to z and in
    the one that sums it, and the two copies may differ in the last bit;
    a pre-drawn δ is computed once."""
    from jax.sharding import Mesh

    from repro import tracing
    from repro.core import engine as engine_mod
    from repro.core.secure_agg import ring_mask, two_tree_mask
    from repro.sharding.api import PartyMesh
    lay_, mesh, local = layout, None, 8
    if lay == "data_axis":        # flat parties, two data shards each
        mesh, local = PartyMesh(q=8, slots=8, data_shards=2), 16
    elif lay == "mesh1":          # shard_map, one party on one device
        lay_ = algorithms.PartyLayout.even(D, 1, 1)
        mesh, local = Mesh(np.array(jax.devices()[:1]), ("model",)), 1
    steps, key = 6, jax.random.PRNGKey(31)
    shape = (BATCH // (2 if lay == "data_axis" else 1),) \
        + ((2,) if epoch == "svrg" else ())
    stream = steps * local * int(np.prod(shape)) * 4

    def engine(limit):
        monkeypatch.setattr(engine_mod, "MASK_STREAM_BUDGET", limit)
        return FusedEngine(prob, ds.x_train, ds.y_train, lay_,
                           EngineConfig(secure=secure), mesh=mesh)

    # the stream against each step's own draw, under the epochs' binding
    eng = engine(stream)
    draw = ring_mask if secure == "ring" else two_tree_mask

    def predrawn(local_, mkeys):
        return eng._masks(mkeys, shape, sliced=True).delta

    def per_step(local_, mkeys):
        def step(c, kt):
            return c, draw(eng._dkey(kt), "model", shape)
        return jax.lax.scan(step, 0, mkeys)[1]

    mkeys = eng._keys(key, steps)
    got, want = (np.asarray(jax.jit(eng._bind(f))(eng.maskq, mkeys))
                 for f in (predrawn, per_step))
    assert got.shape == (lay_.q, steps, int(np.prod(shape)))
    np.testing.assert_array_equal(got, want.reshape(got.shape))

    # the epochs: which path each budget takes, and the updates
    def run(limit):
        with tracing.Recorder() as rec:
            w = _mask_epoch(engine(limit), epoch, key, steps)
        return w, (rec.total("vfb2.masks.predrawn", epoch),
                   rec.total("vfb2.masks.per_step", epoch))

    limits = (stream, 0) if budget == "fits" else (stream, stream - 1)
    (w_pre, n_pre), (w_step, n_step) = map(run, limits)
    assert n_pre == (1, 0) and n_step == (0, 1)
    np.testing.assert_allclose(w_pre, w_step, rtol=0, atol=2e-7)


def test_schedule_faithful_two_tree(ds, layout, prob):
    """T1/T2 replayed round-by-round with ppermute == all-reduce lowering."""
    key = jax.random.PRNGKey(5)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="two_tree",
                                   schedule_faithful=True))
    w = eng.unpack_w(eng.sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key,
                                   BATCH, 8))
    base = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                       EngineConfig(secure="off"))
    w_base = base.unpack_w(base.sgd_epoch(base.pack_w(np.zeros(D)), 0.5,
                                          key, BATCH, 8))
    np.testing.assert_allclose(w, w_base, atol=1e-5, rtol=0)


def test_kernel_routing_matches_jnp(ds, layout, prob):
    """The batched rank-k Pallas kernel and the jnp contraction produce the
    same epoch (interpret mode; small step count to keep CI fast)."""
    key = jax.random.PRNGKey(6)
    jnp_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                          EngineConfig(secure="off", use_kernel=False))
    krn_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                          EngineConfig(secure="off", use_kernel=True))
    w_j = jnp_eng.unpack_w(jnp_eng.sgd_epoch(jnp_eng.pack_w(np.zeros(D)),
                                             0.5, key, BATCH, 4))
    w_k = krn_eng.unpack_w(krn_eng.sgd_epoch(krn_eng.pack_w(np.zeros(D)),
                                             0.5, key, BATCH, 4))
    np.testing.assert_allclose(w_k, w_j, atol=1e-5, rtol=0)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_interpret_routing_by_backend(ds, layout, prob, monkeypatch,
                                      backend):
    """Unset, the kernel and interpreter follow the backend; on a TPU an
    explicit ``interpret=True`` is refused rather than run."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout, EngineConfig())
    assert (eng._kernel, eng._interpret) == (backend == "tpu",
                                             backend != "tpu")
    cfg = EngineConfig(use_kernel=True, interpret=True)
    if backend == "tpu":
        with pytest.raises(ValueError, match="refused on a TPU"):
            FusedEngine(prob, ds.x_train, ds.y_train, layout, cfg)
    else:
        eng = FusedEngine(prob, ds.x_train, ds.y_train, layout, cfg)
        assert eng._kernel and eng._interpret


def _dot_precisions(jaxpr):
    from repro.analysis.walkers import sub_jaxprs
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                out.extend(_dot_precisions(sub))
    return out


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("program", ["sgd", "pipelined_sgd",
                                     "full_gradient", "deep_sgd"])
def test_every_contraction_keeps_f32_operands(ds, layout, prob, use_kernel,
                                              program):
    """Every dot of an engine program — inside the Pallas kernel and on
    XLA's route — is traced at HIGHEST precision: given none, a TPU f32
    dot rounds its operands to bf16 and the chip path drifts from the
    f32 oracles by about 2^-9."""
    from repro.core import deep_vfl
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(use_kernel=use_kernel))
    key = jax.random.PRNGKey(0)
    wq = eng.pack_w(np.zeros(D))
    pq = eng.pack_deep(deep_vfl.init_deep_vfl(key, layout, D, 8, 4))
    run = {
        "sgd": lambda: eng.sgd_epoch(wq, 0.5, key, BATCH, 2),
        "pipelined_sgd": lambda: eng.pipelined_sgd_epoch(wq, 0.5, key,
                                                         BATCH, 2),
        "full_gradient": lambda: eng.full_gradient(wq, key),
        "deep_sgd": lambda: eng.deep_sgd_epoch(pq, 0.05, key, BATCH, 2),
    }[program]
    precisions = _dot_precisions(jax.make_jaxpr(run)().jaxpr)
    assert precisions
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert all(p == highest for p in precisions), precisions


def test_delayed_fused_matches_staleness_reference(ds, layout, prob):
    tau, lr, epochs, seed = 4, 0.3, 3, 0
    delays = staleness.party_delays(layout, D, tau, seed=seed)
    st = staleness.init_state(D, tau)
    x, y, _ = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(seed)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = staleness.delayed_sgd_epoch(prob, st, x, y, lr,
                                         jnp.asarray(delays), sub, BATCH,
                                         steps, tau)
    w_fused = staleness.run_delayed_fused(prob, ds.x_train, ds.y_train,
                                          layout, tau, epochs, lr, BATCH,
                                          seed=seed)
    np.testing.assert_allclose(w_fused, np.asarray(st.w), atol=1e-5, rtol=0)


@pytest.mark.parametrize("algo", ["sgd", "svrg", "saga"])
def test_train_fused_engine_matches_reference_trainer(ds, layout, prob,
                                                      algo):
    kw = dict(algo=algo, epochs=3, lr=0.3, batch=BATCH, seed=7)
    ref = algorithms.train(prob, ds.x_train, ds.y_train, layout, **kw)
    fused = algorithms.train(prob, ds.x_train, ds.y_train, layout,
                             engine="fused", **kw)
    np.testing.assert_allclose(fused.w, ref.w, atol=1e-5, rtol=0)
    for hf, hr in zip(fused.history, ref.history):
        assert abs(hf["objective"] - hr["objective"]) < 1e-5


def test_train_fused_secure_converges(ds, layout, prob):
    res = algorithms.train(prob, ds.x_train, ds.y_train, layout,
                           algo="svrg", epochs=5, lr=0.5, batch=BATCH,
                           engine="fused",
                           engine_config=EngineConfig(secure="two_tree"))
    assert res.history[-1]["objective"] < 0.62


# ---------------------------------------------------------------------------
# multi-dominator fused epochs vs the sequential multi-dominator oracle
# (m active parties concurrently launching backward updates per step)
# ---------------------------------------------------------------------------

MLAYOUTS = [algorithms.PartyLayout.even(D, 8, 1),
            algorithms.PartyLayout.even(D, 8, 2)]


@pytest.fixture(params=MLAYOUTS, ids=["m1", "m2"])
def mlayout(request):
    return request.param


def test_multi_sgd_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(10)
    steps = ds.x_train.shape[0] // BATCH
    w_ref = algorithms.multi_sgd_epoch(prob, jnp.zeros(D), x, y, 0.5, mask,
                                       key, BATCH, steps, mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq = eng.multi_sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key, BATCH,
                             steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_multi_sgd_m1_degenerates_to_single_dominator(ds, prob):
    """m = 1: the multi-dominator epoch IS the single-dominator epoch
    (same sampling stream, same update sequence)."""
    layout1 = MLAYOUTS[0]
    key = jax.random.PRNGKey(11)
    steps = ds.x_train.shape[0] // BATCH
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout1,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    w_multi = eng.unpack_w(eng.multi_sgd_epoch(wq0, 0.5, key, BATCH, steps))
    w_single = eng.unpack_w(eng.sgd_epoch(wq0, 0.5, key, BATCH, steps))
    np.testing.assert_allclose(w_multi, w_single, atol=1e-6, rtol=0)


def test_multi_svrg_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(12)
    steps = ds.x_train.shape[0] // BATCH
    w0 = jnp.zeros(D)
    mu = algorithms.full_gradient(prob, w0, x, y)
    w_ref = algorithms.multi_svrg_epoch(prob, w0, w0, mu, x, y, 0.5, mask,
                                        key, BATCH, steps, mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    muq = eng.full_gradient(wq0, key)
    wq = eng.multi_svrg_epoch(wq0, wq0, muq, 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_multi_saga_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(13)
    steps = ds.x_train.shape[0] // BATCH
    tab = prob.theta(x @ jnp.zeros(D), y)
    avg = x.T @ tab / x.shape[0]
    w_ref, tab_ref, _ = algorithms.multi_saga_epoch(
        prob, jnp.zeros(D), tab, avg, x, y, 0.5, mask, key, BATCH, steps,
        mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    tabq, avgq = eng.saga_init(wq0, key)
    wq, tabq, avgq = eng.multi_saga_epoch(wq0, tabq, avgq, 0.5, key, BATCH,
                                          steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)
    # the replicated ϑ̃ table took all m dominators' writes identically
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tabq[-1]),
                               atol=0, rtol=0)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tab_ref),
                               atol=1e-5, rtol=0)


def test_multi_delayed_matches_oracle(ds, mlayout, prob):
    """Per-(party, dominator) ring buffers on the fused path reproduce the
    sequential multi-dominator bounded-delay trajectory."""
    tau, lr, epochs, seed = 4, 0.3, 3, 0
    m = mlayout.m
    delays = staleness.dominator_delays_by_coord(mlayout, D, tau, seed=seed)
    st = staleness.init_multi_state(D, tau, m)
    x, y, _ = _ref_inputs(ds, mlayout)
    key = jax.random.PRNGKey(seed)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = staleness.delayed_multi_sgd_epoch(prob, st, x, y, lr,
                                               jnp.asarray(delays), sub,
                                               BATCH, steps, tau, m)
    w_fused = staleness.run_delayed_multi_fused(prob, ds.x_train,
                                                ds.y_train, mlayout, tau,
                                                epochs, lr, BATCH,
                                                seed=seed)
    np.testing.assert_allclose(w_fused, np.asarray(st.w), atol=1e-5,
                               rtol=0)


@pytest.mark.parametrize("secure", ["two_tree", "ring"])
def test_multi_secure_modes_are_lossless(ds, prob, secure):
    """All m partial-product sets of a step are masked-aggregated in one
    collective; Algorithm 1's cancellation must stay exact."""
    layout2 = MLAYOUTS[1]
    key = jax.random.PRNGKey(14)
    steps = ds.x_train.shape[0] // BATCH
    base = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                       EngineConfig(secure="off"))
    w_base = base.unpack_w(base.multi_sgd_epoch(base.pack_w(np.zeros(D)),
                                                0.5, key, BATCH, steps))
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                      EngineConfig(secure=secure))
    w_sec = eng.unpack_w(eng.multi_sgd_epoch(eng.pack_w(np.zeros(D)), 0.5,
                                             key, BATCH, steps))
    np.testing.assert_allclose(w_sec, w_base, atol=1e-5, rtol=0)


def test_multi_kernel_routing_matches_jnp(ds, prob):
    """The M = m rank-k kernel path (block-diagonal Θ, w=None backward)
    and the jnp contraction produce the same multi-dominator epoch."""
    layout2 = MLAYOUTS[1]
    key = jax.random.PRNGKey(15)
    jnp_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                          EngineConfig(secure="off", use_kernel=False))
    krn_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                          EngineConfig(secure="off", use_kernel=True))
    w_j = jnp_eng.unpack_w(jnp_eng.multi_sgd_epoch(
        jnp_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    w_k = krn_eng.unpack_w(krn_eng.multi_sgd_epoch(
        krn_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    np.testing.assert_allclose(w_k, w_j, atol=1e-5, rtol=0)


@pytest.mark.parametrize("algo", ["sgd", "svrg", "saga"])
def test_train_multi_dominator_fused_matches_reference(ds, prob, algo):
    layout2 = MLAYOUTS[1]
    kw = dict(algo=algo, epochs=3, lr=0.3, batch=BATCH, seed=7,
              multi_dominator=True)
    ref = algorithms.train(prob, ds.x_train, ds.y_train, layout2, **kw)
    fused = algorithms.train(prob, ds.x_train, ds.y_train, layout2,
                             engine="fused", **kw)
    np.testing.assert_allclose(fused.w, ref.w, atol=1e-5, rtol=0)
    for hf, hr in zip(fused.history, ref.history):
        assert abs(hf["objective"] - hr["objective"]) < 1e-5


# ---------------------------------------------------------------------------
# pipelined epochs (backward(t) ∥ forward(t+1), ONE kernel invocation per
# step) vs their τ = 1 sequential oracles
# ---------------------------------------------------------------------------


def test_pipelined_sgd_matches_oracle(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(20)
    steps = ds.x_train.shape[0] // BATCH
    w_ref = algorithms.pipelined_sgd_epoch(prob, jnp.zeros(D), x, y, 0.5,
                                           mask, key, BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq = eng.pipelined_sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key, BATCH,
                                 steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_pipelined_schedule_is_genuinely_stale(ds, layout, prob):
    """The pipelined trajectory must differ from the fresh sequential one
    (ϑ reads are one update old) while step 0 stays exactly sequential —
    a regression against silently collapsing to the unpipelined path."""
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(21)
    steps = ds.x_train.shape[0] // BATCH
    w_seq = algorithms.sgd_epoch(prob, jnp.zeros(D), x, y, 0.5, mask, key,
                                 BATCH, steps)
    w_pipe = algorithms.pipelined_sgd_epoch(prob, jnp.zeros(D), x, y, 0.5,
                                            mask, key, BATCH, steps)
    assert float(jnp.abs(w_pipe - w_seq).max()) > 1e-4
    # a single-step epoch has no interior step: prologue is fresh, so the
    # two schedules coincide exactly
    w1_seq = algorithms.sgd_epoch(prob, jnp.zeros(D), x, y, 0.5, mask, key,
                                  BATCH, 1)
    w1_pipe = algorithms.pipelined_sgd_epoch(prob, jnp.zeros(D), x, y, 0.5,
                                             mask, key, BATCH, 1)
    np.testing.assert_allclose(np.asarray(w1_pipe), np.asarray(w1_seq),
                               atol=1e-7, rtol=0)


def test_pipelined_svrg_matches_oracle(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(22)
    steps = ds.x_train.shape[0] // BATCH
    w0 = jnp.zeros(D)
    mu = algorithms.full_gradient(prob, w0, x, y)
    w_ref = algorithms.pipelined_svrg_epoch(prob, w0, w0, mu, x, y, 0.5,
                                            mask, key, BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    muq = eng.full_gradient(wq0, key)
    wq = eng.pipelined_svrg_epoch(wq0, wq0, muq, 0.5, key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_pipelined_saga_matches_oracle(ds, layout, prob):
    x, y, mask = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(23)
    steps = ds.x_train.shape[0] // BATCH
    tab = prob.theta(x @ jnp.zeros(D), y)
    avg = x.T @ tab / x.shape[0]
    w_ref, tab_ref, _ = algorithms.pipelined_saga_epoch(
        prob, jnp.zeros(D), tab, avg, x, y, 0.5, mask, key, BATCH, steps)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    tabq, avgq = eng.saga_init(wq0, key)
    wq, tabq, avgq = eng.pipelined_saga_epoch(wq0, tabq, avgq, 0.5, key,
                                              BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tab_ref),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("secure", ["two_tree", "ring"])
def test_pipelined_secure_modes_are_lossless(ds, layout, prob, secure):
    key = jax.random.PRNGKey(24)
    steps = ds.x_train.shape[0] // BATCH
    base = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                       EngineConfig(secure="off"))
    w_base = base.unpack_w(base.pipelined_sgd_epoch(
        base.pack_w(np.zeros(D)), 0.5, key, BATCH, steps))
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure=secure))
    w_sec = eng.unpack_w(eng.pipelined_sgd_epoch(
        eng.pack_w(np.zeros(D)), 0.5, key, BATCH, steps))
    np.testing.assert_allclose(w_sec, w_base, atol=1e-5, rtol=0)


def test_pipelined_kernel_routing_matches_jnp(ds, layout, prob):
    """The split-batch fused kernel invocation and the jnp two-block
    contraction produce the same pipelined epoch."""
    key = jax.random.PRNGKey(25)
    jnp_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                          EngineConfig(secure="off", use_kernel=False))
    krn_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                          EngineConfig(secure="off", use_kernel=True))
    w_j = jnp_eng.unpack_w(jnp_eng.pipelined_sgd_epoch(
        jnp_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    w_k = krn_eng.unpack_w(krn_eng.pipelined_sgd_epoch(
        krn_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    np.testing.assert_allclose(w_k, w_j, atol=1e-5, rtol=0)


def test_pipelined_one_kernel_invocation_per_step(ds, layout, prob):
    """The acceptance audit: on the kernel path the pipelined scan body
    contains exactly ONE pallas_call (the sequential epoch's two)."""
    key = jax.random.PRNGKey(26)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off", use_kernel=True))
    wq = eng.pack_w(np.zeros(D))
    jx_pipe = eng.pipelined_sgd_epoch_jaxpr(wq, 0.3, key, BATCH, 8)
    assert scan_body_primitive_counts(jx_pipe, "pallas_call") == [1]
    jx_seq = eng.sgd_epoch_jaxpr(wq, 0.3, key, BATCH, 8)
    assert scan_body_primitive_counts(jx_seq, "pallas_call") == [2]


def _kernel_blocks(jx):
    """(grid, leading block dim of X) of every kernel call in a jaxpr."""
    from repro.analysis.walkers import primitive_eqns
    out = []
    for eqn in primitive_eqns(jx, "pallas_call"):
        gm = eqn.params["grid_mapping"]
        lead = gm.block_mappings[0].block_shape
        out.append((gm.grid, getattr(lead[0], "block_size", None)
                    if len(lead) == 3 else None))
    return out


@pytest.mark.parametrize("pmesh", [None, "packed"])
def test_one_chip_kernel_calls_hold_all_parties(ds, layout, prob, pmesh):
    """The one-chip engine calls the kernel under its vmap over parties
    (and, packed, a vmap over slots around one over each slot's parties):
    each scanned call of the SGD and SVRG steps is one call whose blocks
    hold all q = 8 parties, with no grid axis over them."""
    from repro.sharding.api import PartyMesh
    mesh = PartyMesh(q=8, slots=2) if pmesh else None
    key = jax.random.PRNGKey(27)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off", use_kernel=True), mesh=mesh)
    wq = eng.pack_w(np.zeros(D))
    jx_sgd = eng.sgd_epoch_jaxpr(wq, 0.3, key, BATCH, 8)
    mu = eng.full_gradient(wq, key)
    eng.svrg_epoch(wq, wq, mu, 0.3, key, BATCH, 8)
    jx_svrg = jax.make_jaxpr(
        lambda xs, w: eng._jitted["svrg"](xs, w, w, mu, eng.maskq, eng.y,
                                          0.3, key, BATCH, 8))(eng.xs, wq)
    for jx in (jx_sgd, jx_svrg):
        assert scan_body_primitive_counts(jx, "pallas_call") == [2]
        assert _kernel_blocks(jx) == [((1, 1), 8)] * 2


def test_flat_mesh_kernel_calls_are_per_party(ds, prob):
    """A flat mesh binds the parties with ``shard_map``, not vmap: each
    device's call keeps one party's 2-D blocks (a one-device mesh here)."""
    from jax.sharding import Mesh
    lay = algorithms.PartyLayout.even(D, 1, 1)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, lay,
                      EngineConfig(secure="off", use_kernel=True),
                      mesh=Mesh(np.array(jax.devices()[:1]), ("model",)))
    jx = eng.sgd_epoch_jaxpr(eng.pack_w(np.zeros(D)), 0.3,
                             jax.random.PRNGKey(28), BATCH, 8)
    assert scan_body_primitive_counts(jx, "pallas_call") == [2]
    assert _kernel_blocks(jx) == [((1, 1), None)] * 2


def test_pipelined_delayed_matches_oracle(ds, layout, prob):
    tau, lr, epochs, seed = 4, 0.3, 3, 0
    delays = staleness.party_delays(layout, D, tau, seed=seed)
    st = staleness.init_state(D, tau)
    x, y, _ = _ref_inputs(ds, layout)
    key = jax.random.PRNGKey(seed)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = staleness.pipelined_delayed_sgd_epoch(
            prob, st, x, y, lr, jnp.asarray(delays), sub, BATCH, steps, tau)
    w_fused = staleness.run_delayed_fused(prob, ds.x_train, ds.y_train,
                                          layout, tau, epochs, lr, BATCH,
                                          seed=seed, pipelined=True)
    np.testing.assert_allclose(w_fused, np.asarray(st.w), atol=1e-5, rtol=0)


def test_pipelined_delayed_active_only_freezes_passive_blocks(ds, layout,
                                                              prob):
    tau = 4
    w = staleness.run_delayed_fused(prob, ds.x_train, ds.y_train, layout,
                                    tau, 2, 0.3, BATCH, seed=0,
                                    active_only=True, pipelined=True)
    active = layout.update_mask(D, True)
    assert np.abs(w[active == 0]).max() == 0.0
    assert np.abs(w[active == 1]).max() > 0.0
    st = staleness.init_state(D, tau)
    x, y, _ = _ref_inputs(ds, layout)
    delays = staleness.party_delays(layout, D, tau, seed=0)
    key = jax.random.PRNGKey(0)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(2):
        key, sub = jax.random.split(key)
        st = staleness.pipelined_delayed_sgd_epoch(
            prob, st, x, y, 0.3, jnp.asarray(delays), sub, BATCH, steps,
            tau, mask=jnp.asarray(active))
    np.testing.assert_allclose(w, np.asarray(st.w), atol=1e-5, rtol=0)


def test_multi_pipelined_sgd_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(27)
    steps = ds.x_train.shape[0] // BATCH
    w_ref = algorithms.multi_pipelined_sgd_epoch(
        prob, jnp.zeros(D), x, y, 0.5, mask, key, BATCH, steps, mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq = eng.multi_pipelined_sgd_epoch(eng.pack_w(np.zeros(D)), 0.5, key,
                                       BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_multi_pipelined_svrg_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(28)
    steps = ds.x_train.shape[0] // BATCH
    w0 = jnp.zeros(D)
    mu = algorithms.full_gradient(prob, w0, x, y)
    w_ref = algorithms.multi_pipelined_svrg_epoch(
        prob, w0, w0, mu, x, y, 0.5, mask, key, BATCH, steps, mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    muq = eng.full_gradient(wq0, key)
    wq = eng.multi_pipelined_svrg_epoch(wq0, wq0, muq, 0.5, key, BATCH,
                                        steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)


def test_multi_pipelined_saga_matches_oracle(ds, mlayout, prob):
    x, y, _ = _ref_inputs(ds, mlayout)
    mask = jnp.asarray(mlayout.update_mask(D, False))
    key = jax.random.PRNGKey(29)
    steps = ds.x_train.shape[0] // BATCH
    tab = prob.theta(x @ jnp.zeros(D), y)
    avg = x.T @ tab / x.shape[0]
    w_ref, tab_ref, _ = algorithms.multi_pipelined_saga_epoch(
        prob, jnp.zeros(D), tab, avg, x, y, 0.5, mask, key, BATCH, steps,
        mlayout.m)
    eng = FusedEngine(prob, ds.x_train, ds.y_train, mlayout,
                      EngineConfig(secure="off"))
    wq0 = eng.pack_w(np.zeros(D))
    tabq, avgq = eng.saga_init(wq0, key)
    wq, tabq, avgq = eng.multi_pipelined_saga_epoch(wq0, tabq, avgq, 0.5,
                                                    key, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), np.asarray(w_ref),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(tabq[0]), np.asarray(tab_ref),
                               atol=1e-5, rtol=0)


def test_multi_pipelined_delayed_matches_oracle(ds, mlayout, prob):
    tau, lr, epochs, seed = 4, 0.3, 3, 0
    m = mlayout.m
    delays = staleness.dominator_delays_by_coord(mlayout, D, tau, seed=seed)
    st = staleness.init_multi_state(D, tau, m)
    x, y, _ = _ref_inputs(ds, mlayout)
    key = jax.random.PRNGKey(seed)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        st = staleness.pipelined_delayed_multi_sgd_epoch(
            prob, st, x, y, lr, jnp.asarray(delays), sub, BATCH, steps,
            tau, m)
    w_fused = staleness.run_delayed_multi_fused(
        prob, ds.x_train, ds.y_train, mlayout, tau, epochs, lr, BATCH,
        seed=seed, pipelined=True)
    np.testing.assert_allclose(w_fused, np.asarray(st.w), atol=1e-5,
                               rtol=0)


@pytest.mark.parametrize("secure", ["two_tree", "ring"])
def test_multi_pipelined_secure_modes_are_lossless(ds, prob, secure):
    layout2 = MLAYOUTS[1]
    key = jax.random.PRNGKey(30)
    steps = ds.x_train.shape[0] // BATCH
    base = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                       EngineConfig(secure="off"))
    w_base = base.unpack_w(base.multi_pipelined_sgd_epoch(
        base.pack_w(np.zeros(D)), 0.5, key, BATCH, steps))
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                      EngineConfig(secure=secure))
    w_sec = eng.unpack_w(eng.multi_pipelined_sgd_epoch(
        eng.pack_w(np.zeros(D)), 0.5, key, BATCH, steps))
    np.testing.assert_allclose(w_sec, w_base, atol=1e-5, rtol=0)


def test_multi_pipelined_kernel_routing_matches_jnp(ds, prob):
    """The Mw=1/Mθ=m split-batch kernel invocation and the jnp segment
    einsum produce the same multi-dominator pipelined epoch."""
    layout2 = MLAYOUTS[1]
    key = jax.random.PRNGKey(31)
    jnp_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                          EngineConfig(secure="off", use_kernel=False))
    krn_eng = FusedEngine(prob, ds.x_train, ds.y_train, layout2,
                          EngineConfig(secure="off", use_kernel=True))
    w_j = jnp_eng.unpack_w(jnp_eng.multi_pipelined_sgd_epoch(
        jnp_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    w_k = krn_eng.unpack_w(krn_eng.multi_pipelined_sgd_epoch(
        krn_eng.pack_w(np.zeros(D)), 0.5, key, BATCH, 4))
    np.testing.assert_allclose(w_k, w_j, atol=1e-5, rtol=0)


@pytest.mark.parametrize("algo", ["sgd", "svrg", "saga"])
@pytest.mark.parametrize("multi", [False, True])
def test_train_pipelined_fused_matches_reference(ds, prob, algo, multi):
    layout2 = MLAYOUTS[1]
    kw = dict(algo=algo, epochs=3, lr=0.3, batch=BATCH, seed=7,
              pipelined=True, multi_dominator=multi)
    ref = algorithms.train(prob, ds.x_train, ds.y_train, layout2, **kw)
    fused = algorithms.train(prob, ds.x_train, ds.y_train, layout2,
                             engine="fused", **kw)
    np.testing.assert_allclose(fused.w, ref.w, atol=1e-5, rtol=0)
    for hf, hr in zip(fused.history, ref.history):
        assert abs(hf["objective"] - hr["objective"]) < 1e-5


def test_donated_epochs_chain_without_recompilation(ds, layout, prob):
    """cfg.donate: back-to-back epochs rebind the parameter carry in place
    (the donated input is invalidated) and reuse one compilation."""
    key = jax.random.PRNGKey(32)
    steps = ds.x_train.shape[0] // BATCH
    eng = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off", donate=True))
    ref = FusedEngine(prob, ds.x_train, ds.y_train, layout,
                      EngineConfig(secure="off"))
    wq = eng.pack_w(np.zeros(D))
    wq_ref = ref.pack_w(np.zeros(D))
    for ep in range(3):
        sub = jax.random.fold_in(key, ep)
        wq = eng.pipelined_sgd_epoch(wq, 0.3, sub, BATCH, steps)
        wq_ref = ref.pipelined_sgd_epoch(wq_ref, 0.3, sub, BATCH, steps)
    np.testing.assert_allclose(eng.unpack_w(wq), ref.unpack_w(wq_ref),
                               atol=0, rtol=0)
    assert eng._jitted["pipelined_sgd"]._cache_size() == 1
    # the donated input buffer really was consumed
    stale_in = eng.pack_w(np.zeros(D))
    eng.sgd_epoch(stale_in, 0.3, key, BATCH, steps)
    with pytest.raises(Exception):
        eng.sgd_epoch(stale_in, 0.3, key, BATCH, steps)


# ---------------------------------------------------------------------------
# delayed-path mask regression (active_only must freeze passive blocks on
# the stale-gradient path exactly as on the fresh path)
# ---------------------------------------------------------------------------

def test_delayed_active_only_freezes_passive_blocks(ds, layout, prob):
    tau = 4
    w = staleness.run_delayed_fused(prob, ds.x_train, ds.y_train, layout,
                                    tau, 2, 0.3, BATCH, seed=0,
                                    active_only=True)
    active = layout.update_mask(D, True)
    assert np.abs(w[active == 0]).max() == 0.0     # passive: never updated
    assert np.abs(w[active == 1]).max() > 0.0      # active: trained
    # and the masked fused path still matches the masked oracle
    st = staleness.init_state(D, tau)
    x, y, _ = _ref_inputs(ds, layout)
    delays = staleness.party_delays(layout, D, tau, seed=0)
    key = jax.random.PRNGKey(0)
    steps = ds.x_train.shape[0] // BATCH
    for _ in range(2):
        key, sub = jax.random.split(key)
        st = staleness.delayed_sgd_epoch(prob, st, x, y, 0.3,
                                         jnp.asarray(delays), sub, BATCH,
                                         steps, tau,
                                         mask=jnp.asarray(active))
    np.testing.assert_allclose(w, np.asarray(st.w), atol=1e-5, rtol=0)
