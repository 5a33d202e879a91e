"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.analysis.walkers import primitive_eqns
from repro.kernels import ops, ref
from repro.kernels import vfl_grad as vg


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,sq,skv,dh", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 256, 256, 64),
    (1, 8, 1, 128, 256, 128),    # strong GQA, rectangular
    (2, 2, 2, 64, 64, 256),      # gemma3-style head dim
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 96),
                                           (False, None)])
def test_flash_attention_sweep(dtype, b, h, hkv, sq, skv, dh, causal,
                               window):
    if not causal and sq != skv:
        pytest.skip("cross shape covered elsewhere")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, h, sq, dh), dtype)
    k = _rand(ks[1], (b, hkv, skv, dh), dtype)
    v = _rand(ks[2], (b, hkv, skv, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@given(bq=st.sampled_from([32, 64, 128]), bk=st.sampled_from([32, 64]),
       seed=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_flash_attention_block_shape_invariance(bq, bk, seed):
    """Output must not depend on the tiling (pure performance knob)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (1, 2, 128, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 64), jnp.float32)
    a = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    b = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,c,n,chunk,bc", [
    (1, 64, 128, 8, 16, 64),
    (2, 128, 256, 16, 32, 128),
    (1, 32, 512, 4, 32, 256),
])
def test_selective_scan_sweep(dtype, b, s, c, n, chunk, bc):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    xa = _rand(ks[0], (b, s, c), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, c), jnp.float32))
    b_ssm = _rand(ks[2], (b, s, n), jnp.float32)
    c_ssm = _rand(ks[3], (b, s, n), jnp.float32)
    a_log = jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None],
                             (c, 1)))
    d_skip = jnp.ones((c,))
    y = ops.selective_scan(xa, dt, b_ssm, c_ssm, a_log, d_skip,
                           chunk=chunk, block_c=bc)
    y_ref, _ = ref.selective_scan_ref(xa, dt, b_ssm, c_ssm, a_log, d_skip)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)


def test_selective_scan_chunk_invariance():
    """State carried across seq chunks must make chunking invisible."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    b, s, c, n = 1, 128, 128, 8
    xa = _rand(ks[0], (b, s, c), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, c), jnp.float32))
    b_ssm = _rand(ks[2], (b, s, n), jnp.float32)
    c_ssm = _rand(ks[3], (b, s, n), jnp.float32)
    a_log = jnp.zeros((c, n))
    d_skip = jnp.zeros((c,))
    outs = [ops.selective_scan(xa, dt, b_ssm, c_ssm, a_log, d_skip,
                               chunk=ch, block_c=64) for ch in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-4)


@given(b=st.sampled_from([64, 128, 256]), d=st.sampled_from([128, 256, 384]),
       lam=st.floats(0, 0.1), seed=st.integers(0, 50))
@settings(max_examples=12, deadline=None)
def test_vfl_grad_property(b, d, lam, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xb = _rand(ks[0], (b, d), jnp.float32)
    w = _rand(ks[1], (d,), jnp.float32)
    th = _rand(ks[2], (b,), jnp.float32)
    z, g = ops.vfl_grad(xb, w, th, lam=float(lam))
    zr, gr = ref.vfl_grad_ref(xb, w, th, float(lam))
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,d,m", [
    (128, 256, 1),
    (256, 512, 2),      # SVRG: iterate + snapshot in one pass
    (128, 384, 3),      # multi-dominator (m active parties)
    (100, 200, 2),      # non-tile-divisible: pad path
    (32, 7, 1),         # tiny odd party block (PartyLayout.even remainder)
    (96, 130, 4),
])
def test_vfl_grad_rank_k_sweep(dtype, b, d, m):
    """Batched rank-k kernel vs oracle across dtypes/shapes; z must arrive
    fully reduced from the kernel (no host-side partial sum exists)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    xb = _rand(ks[0], (b, d), dtype)
    w = _rand(ks[1], (d, m), dtype)
    th = _rand(ks[2], (b, m), dtype)
    z, g = ops.vfl_grad(xb, w, th, lam=0.01)
    zr, gr = ref.vfl_grad_ref(xb, w, th, 0.01)
    assert z.shape == (b, m) and g.shape == (d, m)
    assert z.dtype == jnp.float32 and g.dtype == jnp.float32  # f32 accum
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("mode", ["forward", "backward"])
def test_vfl_grad_modes(mode):
    """Single-sided modes produce the same active output as fused, and
    the inactive side is absent (no dead HBM traffic), not zero-filled."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    xb = _rand(ks[0], (64, 96), jnp.float32)
    w = _rand(ks[1], (96, 2), jnp.float32)
    th = _rand(ks[2], (64, 2), jnp.float32)
    zf, gf = ops.vfl_grad(xb, w, th, lam=0.02)
    z, g = ops.vfl_grad(xb, w, th, lam=0.02, mode=mode)
    if mode == "forward":
        np.testing.assert_allclose(np.asarray(z), np.asarray(zf), atol=1e-6)
        assert g is None
        # theta is not an operand of the forward pass
        z2, _ = ops.vfl_grad(xb, w, None, lam=0.02, mode="forward")
        np.testing.assert_allclose(np.asarray(z2), np.asarray(zf),
                                   atol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(g), np.asarray(gf), atol=1e-6)
        assert z is None


def test_vfl_grad_backward_without_w():
    """mode='backward' with w=None (the engine's multi-dominator BUM
    application): pure XᵀΘ/denom, no weight operand streamed at all."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    xb = _rand(ks[0], (100, 96), jnp.float32)      # non-tile B: pad path
    th = _rand(ks[2], (100, 3), jnp.float32)       # M = 3 dominators
    _, g = ops.vfl_grad(xb, None, th, lam=0.0, mode="backward")
    np.testing.assert_allclose(np.asarray(g), np.asarray(xb.T @ th / 100),
                               atol=1e-5, rtol=1e-5)
    _, g1 = ops.vfl_grad(xb, None, th[:, 0], lam=0.0, mode="backward",
                         denom=7)
    assert g1.shape == (96,)                       # rank-1 in, rank-1 out
    np.testing.assert_allclose(np.asarray(g1),
                               np.asarray(xb.T @ th[:, 0] / 7),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("b,d,m", [
    (128, 256, 1),      # tile-divisible
    (100, 130, 1),      # non-tile: pad path on both axes
    (96, 384, 3),       # multi-dominator rank
    (100, 70, 3),       # non-tile + M = 3
])
def test_vfl_grad_fused_equals_separate_calls(b, d, m):
    """mode='fused' must produce exactly the forward-only z and the
    backward-only g of two separate invocations (the pipelined engine
    replaces those two launches with one)."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    xb = _rand(ks[0], (b, d), jnp.float32)
    w = _rand(ks[1], (d, m), jnp.float32)
    th = _rand(ks[2], (b, m), jnp.float32)
    zf, gf = ops.vfl_grad(xb, w, th, lam=0.03)
    z1, _ = ops.vfl_grad(xb, w, None, lam=0.0, mode="forward")
    _, g1 = ops.vfl_grad(xb, w, th, lam=0.03, mode="backward")
    np.testing.assert_allclose(np.asarray(zf), np.asarray(z1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(g1), atol=1e-6)
    zr, gr = ref.vfl_grad_ref(xb, w, th, 0.03)
    np.testing.assert_allclose(np.asarray(zf), np.asarray(zr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("bb,bf,d,mw,mth", [
    (64, 64, 128, 1, 1),     # tile-divisible, symmetric sides
    (60, 40, 70, 1, 3),      # non-tile rows + distinct side column counts
    (32, 96, 130, 2, 2),     # asymmetric row blocks, SVRG rank
    (100, 100, 96, 1, 4),
])
def test_vfl_grad_split_batch(bb, bf, d, mw, mth):
    """Split-batch fused form (the pipelined step): rows [0, bb) are the
    backward block (ϑ rows), rows [bb, bb+bf) the forward block; z covers
    the forward rows only and g contracts the backward rows only."""
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    xcat = _rand(ks[0], (bb + bf, d), jnp.float32)
    w = _rand(ks[1], (d, mw), jnp.float32)
    th = _rand(ks[2], (bb, mth), jnp.float32)
    z, g = ops.vfl_grad(xcat, w, th, lam=0.0, mode="fused", split=bb,
                        denom=bb)
    assert z.shape == (bf, mw) and g.shape == (d, mth)
    np.testing.assert_allclose(np.asarray(z), np.asarray(xcat[bb:] @ w),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(xcat[:bb].T @ th / bb),
                               atol=1e-5, rtol=1e-4)


def test_vfl_grad_split_batch_rank1():
    """Rank-1 sides squeeze independently in the split-batch form."""
    ks = jax.random.split(jax.random.PRNGKey(14), 3)
    xcat = _rand(ks[0], (96, 50), jnp.float32)
    w = _rand(ks[1], (50,), jnp.float32)
    th = _rand(ks[2], (64,), jnp.float32)
    z, g = ops.vfl_grad(xcat, w, th, mode="fused", split=64)
    assert z.shape == (32,) and g.shape == (50,)
    np.testing.assert_allclose(np.asarray(z), np.asarray(xcat[64:] @ w),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(xcat[:64].T @ th / 64),
                               atol=1e-5, rtol=1e-4)


def test_vfl_grad_lam_is_traced_not_static():
    """Sweeping λ must reuse ONE compilation (λ is a traced operand of the
    jit'd wrapper, not a static) — and still produce correct values."""
    ks = jax.random.split(jax.random.PRNGKey(15), 3)
    xb = _rand(ks[0], (64, 96), jnp.float32)
    w = _rand(ks[1], (96, 2), jnp.float32)
    th = _rand(ks[2], (64, 2), jnp.float32)
    ops.vfl_grad(xb, w, th, lam=0.011)        # warm the traced-λ cache
    before = ops._vfl_grad_jit._cache_size()
    for lam in (0.02, 0.5, 3.0):
        _, g = ops.vfl_grad(xb, w, th, lam=lam)
        _, gr = ref.vfl_grad_ref(xb, w, th, lam)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=1e-5, rtol=1e-4)
    assert ops._vfl_grad_jit._cache_size() == before


def test_vfl_grad_denom_override():
    """SAGA's running average divides by n, not the minibatch size."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    xb = _rand(ks[0], (64, 96), jnp.float32)
    w = jnp.zeros((96,), jnp.float32)
    th = _rand(ks[2], (64,), jnp.float32)
    _, g = ops.vfl_grad(xb, w, th, lam=0.0, mode="backward", denom=1000)
    _, gr = ref.vfl_grad_ref(xb, w, th, 0.0, denom=1000)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-6)


def test_vfl_grad_block_shape_invariance():
    """Tiling is a pure performance knob: output independent of blocks."""
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    xb = _rand(ks[0], (192, 320), jnp.float32)
    w = _rand(ks[1], (320, 2), jnp.float32)
    th = _rand(ks[2], (192, 2), jnp.float32)
    outs = [ops.vfl_grad(xb, w, th, lam=0.01, block_b=bb, block_d=bd)
            for bb, bd in [(64, 64), (128, 128), (192, 320)]]
    for z, g in outs[1:]:
        np.testing.assert_allclose(np.asarray(z), np.asarray(outs[0][0]),
                                   atol=1e-4, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(outs[0][1]),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("b,d,bb,bd", [
    (32, 16, 128, 128),     # single tile both ways: z AND g elided
    (300, 16, 64, 128),     # nd==1, nb>1: z elided, g accumulates
    (32, 300, 128, 64),     # nb==1, nd>1: g elided, z accumulates
    (300, 300, 64, 64),     # neither elided (regression anchor)
])
def test_vfl_grad_scratch_elision_equivalence(b, d, bb, bd):
    """Whether a side's VMEM accumulator exists is decided by the tile
    counts (nd==1 elides z, a single backward row tile elides g) — a pure
    perf property that must not change any output.  Each shape is checked
    against the jnp oracle AND against a small-block run of the same
    problem that forces both accumulators on."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    xb = _rand(ks[0], (b, d), jnp.float32)
    w = _rand(ks[1], (d, 2), jnp.float32)
    th = _rand(ks[2], (b, 2), jnp.float32)
    z, g = ops.vfl_grad(xb, w, th, lam=0.02, block_b=bb, block_d=bd)
    zr, gr = ref.vfl_grad_ref(xb, w, th, 0.02)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-5,
                               rtol=1e-4)
    # both-accumulators-on rerun of the identical problem (8-row/8-lane
    # tiles guarantee nb > 1 and nd > 1 at these shapes)
    z2, g2 = ops.vfl_grad(xb, w, th, lam=0.02, block_b=8, block_d=8)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z2), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g2), atol=1e-5,
                               rtol=1e-5)


def test_vfl_grad_scratch_elision_split_batch():
    """Split-batch fused form with a single backward row tile (nsplit==1):
    the elided-g direct write must persist across the later forward-only
    tile visits (the sequential-grid revisiting contract)."""
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    bb, bf, d = 32, 64, 48
    xb = _rand(ks[0], (bb + bf, d), jnp.float32)
    w = _rand(ks[1], (d, 1), jnp.float32)
    th = _rand(ks[2], (bb, 3), jnp.float32)
    z, g = ops.vfl_grad(xb, w, th, lam=0.0, split=bb, block_b=64,
                        block_d=128)
    np.testing.assert_allclose(np.asarray(z),
                               np.asarray(xb[bb:] @ w), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(g),
                               np.asarray(xb[:bb].T @ th / bb), atol=1e-5,
                               rtol=1e-4)


def test_vfl_grad_partials_are_party_blocks():
    """Per-party kernel invocations on column blocks produce exactly the
    partial products Algorithm 1 masks and aggregates: their sum equals the
    pooled-data kernel's (fully in-kernel-reduced) z."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    xb = _rand(ks[0], (128, 256), jnp.float32)
    w = _rand(ks[1], (256,), jnp.float32)
    th = _rand(ks[2], (128,), jnp.float32)
    z_full, _ = ops.vfl_grad(xb, w, th, lam=0.0)
    z0, _ = ops.vfl_grad(xb[:, :100], w[:100], th, lam=0.0)   # odd widths
    z1, _ = ops.vfl_grad(xb[:, 100:], w[100:], th, lam=0.0)
    np.testing.assert_allclose(np.asarray(z0), np.asarray(xb[:, :100] @ w[:100]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(z0 + z1), np.asarray(z_full),
                               atol=1e-4, rtol=1e-4)


def _party_call(mode, m, tiles):
    """One party's kernel call in ``mode``, rank ``m``; ``tiles`` (8-row,
    8-lane blocks) forces several grid visits and both accumulators."""
    blocks = dict(block_b=8, block_d=8) if tiles else {}

    def call(x, w, th):
        if mode == "forward":
            return vg.vfl_grad(x, w, None, mode="forward", interpret=True,
                               **blocks)[0]
        if mode == "backward":
            return vg.vfl_grad(x, None, th, mode="backward", denom=7,
                               interpret=True, **blocks)[1]
        if mode == "fused":
            return vg.vfl_grad(x, w, th, 0.03, interpret=True, **blocks)
        return vg.vfl_grad(x, w, th[:11], split=11, interpret=True,
                           **blocks)
    return call


@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("q", [1, 2, 3, 4])
@pytest.mark.parametrize("mode", ["forward", "backward", "fused", "split"])
def test_vfl_grad_grouped_equals_per_party(mode, q, m):
    """Under ``jax.vmap`` over parties the kernel is ONE call whose blocks
    hold the parties; it returns what one call per party returns, to f32
    rounding: the same dots run, but XLA compiles the interpreted 2-D and
    3-D bodies apart, and may order a dot's sums or fuse λw's multiply-add
    differently in each.
    Odd party widths (13 columns) and rows (23) take the pad path; M = 2
    also runs on 8 × 8 tiles, so the accumulators of both sides are on."""
    ks = jax.random.split(jax.random.PRNGKey(40 + q), 3)
    xs = _rand(ks[0], (q, 23, 13), jnp.float32)
    ws = _rand(ks[1], (q, 13, m), jnp.float32)
    ths = _rand(ks[2], (q, 23, m), jnp.float32)
    call = jax.jit(_party_call(mode, m, tiles=m == 2))
    grouped = jax.jit(jax.vmap(call))(xs, ws, ths)
    [eqn] = primitive_eqns(jax.make_jaxpr(jax.vmap(call))(xs, ws, ths),
                           "pallas_call")
    assert eqn.params["grid_mapping"].block_mappings[0] \
        .block_shape[0].block_size == q
    for p in range(q):
        one = call(xs[p], ws[p], ths[p])
        for a, b in zip(jax.tree.leaves(grouped), jax.tree.leaves(one)):
            np.testing.assert_allclose(np.asarray(a[p]), np.asarray(b),
                                       rtol=1e-6, atol=2e-6)


@pytest.mark.parametrize("mode", ["forward", "backward", "fused", "split"])
def test_vfl_grad_grouped_nested_vmaps(mode):
    """A packed PartyMesh emulation nests vmaps: slots around packed
    parties, inside a data axis whose operands are partly unbatched.  Every
    level folds into the one call's party axis (3 × 2 × 2 = 12 parties),
    and each party's outputs equal its own call's."""
    ks = jax.random.split(jax.random.PRNGKey(50), 4)
    xs = _rand(ks[0], (2, 2, 23, 13), jnp.float32)      # (slots, pps, ...)
    ws = _rand(ks[1], (2, 2, 13, 2), jnp.float32)
    ths = _rand(ks[2], (2, 2, 23, 2), jnp.float32)
    scale = 1.0 + jnp.arange(3, dtype=jnp.float32)      # the data axis
    call = jax.jit(_party_call(mode, 2, tiles=False))

    def sharded(s):        # the data axis batches X alone
        return jax.vmap(jax.vmap(call))(xs * s, ws, ths)

    out = jax.jit(jax.vmap(sharded))(scale)
    [eqn] = primitive_eqns(jax.make_jaxpr(jax.vmap(sharded))(scale),
                           "pallas_call")
    gm = eqn.params["grid_mapping"]
    assert len(gm.grid) == 2
    assert gm.block_mappings[0].block_shape[0].block_size == 12
    for i in range(3):
        for s in range(2):
            for p in range(2):
                one = call(xs[s, p] * scale[i], ws[s, p], ths[s, p])
                for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(one)):
                    np.testing.assert_allclose(np.asarray(a[i, s, p]),
                                               np.asarray(b), rtol=1e-6,
                                               atol=2e-6)


@pytest.mark.parametrize("pos,off,win", [(300, 0, None), (300, 0, 128),
                                         (700, 512, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(pos, off, win, dtype):
    """Flash-decoding kernel vs local_decode_attention oracle (normalized
    outputs + sum-exp agree, so cross-shard LSE merges are identical)."""
    from repro.models.attention import local_decode_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, Hkv, S, dh = 2, 4, 2, 512, 64
    q = _rand(ks[0], (B, H, dh), dtype)
    kc = _rand(ks[1], (B, S, Hkv, dh), dtype)
    vc = _rand(ks[2], (B, S, Hkv, dh), dtype)
    o1, m1, l1 = ops.decode_attention(q, kc, vc, pos, off, win, block_k=128)
    o2, m2, l2 = local_decode_attention(
        q, kc, vc, jnp.asarray(pos), jnp.asarray(off),
        window=jnp.asarray(win, jnp.int32) if win else None)
    n1 = np.asarray(o1) / np.maximum(np.asarray(l1)[..., None], 1e-30)
    n2 = np.asarray(o2) / np.maximum(np.asarray(l2)[..., None], 1e-30)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(n1, n2, atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_fully_masked_shard():
    """A shard owning only future positions contributes zero mass."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 2, 32), jnp.float32)
    kc = _rand(ks[1], (1, 128, 2, 32), jnp.float32)
    vc = _rand(ks[2], (1, 128, 2, 32), jnp.float32)
    o, m, l = ops.decode_attention(q, kc, vc, pos=10, shard_offset=512,
                                   block_k=64)
    assert float(np.abs(np.asarray(l)).max()) == 0.0
