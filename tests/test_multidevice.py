"""Multi-party (multi-device) semantics, run in a subprocess with 8 forced
host devices (the main pytest process keeps the real 1-device topology).

Covers: BUM gradient broadcast, secure-psum exactness + both schedules,
sharded-MoE == reference, vocab-parallel loss == plain CE, sequence-sharded
decode attention == single-shard decode; and, separately, the fused
engine's one-party-per-device binding and data placement.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.sharding.api import shard_map
    from repro.core.bum import secure_vfl_reduce
    from repro.models import moe as moe_lib
    from repro.models import model as model_lib
    from repro.sharding.api import Runtime, use_runtime
    from repro.vfl.heads import vocab_parallel_loss
    from repro.vfl.embed import secure_vocab_embed

    mesh = jax.make_mesh((1, 2, 4), ("pod", "data", "model"))
    rt = Runtime(mesh=mesh, batch_axes=("data",), attn_chunk=16,
                 loss_chunk=8)
    key = jax.random.PRNGKey(0)

    # --- BUM: forward exact, backward broadcasts theta ---
    parts = jnp.arange(4.0).reshape(4, 1) * jnp.ones((4, 8))
    for faithful in (False, True):
        f = shard_map(lambda p, k: secure_vfl_reduce(p, "model", k, 1.0,
                                                     faithful),
                      mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
                      check_vma=False)
        out = jax.jit(f)(parts, key)
        assert np.allclose(out, 6.0, atol=1e-4), out
        g = jax.jit(jax.grad(lambda p: jnp.sum(f(p, key))))(parts)
        assert np.allclose(g, 1.0, atol=1e-5), g
    print("BUM ok")

    # --- sharded MoE == reference at high capacity ---
    params = moe_lib.init_moe(jax.random.PRNGKey(1), 32, 64, 8)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 32), jnp.float32)
    with use_runtime(rt):
        ref, _ = jax.jit(lambda p, x: moe_lib.apply_moe(
            p, x, top_k=2, capacity_factor=8.0))(params, x)
        shd, _ = jax.jit(lambda p, x: moe_lib.apply_moe_sharded(
            rt, p, x, top_k=2, capacity_factor=8.0))(params, x)
    assert np.allclose(ref, shd, atol=1e-5), float(jnp.abs(ref-shd).max())
    print("MoE ok")

    # --- vocab-parallel loss == plain CE ---
    V, D, B, S = 64, 16, 4, 8
    table = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (V, D))
    h = jax.random.normal(jax.random.PRNGKey(4), (B, S, D), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, V)
    with use_runtime(rt):
        loss = jax.jit(lambda t, h, y: vocab_parallel_loss(rt, t, h, y, V))(
            table, h, y)
    logits = h @ table.T
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), y[..., None],
                              -1).mean()
    assert np.allclose(float(loss), float(ce), atol=2e-3), (loss, ce)
    # grads agree
    with use_runtime(rt):
        g1 = jax.jit(jax.grad(lambda t: vocab_parallel_loss(rt, t, h, y, V)))(table)
    g2 = jax.grad(lambda t: -jnp.take_along_axis(
        jax.nn.log_softmax(h @ t.T), y[..., None], -1).mean())(table)
    assert np.allclose(g1, g2, atol=2e-3), float(jnp.abs(g1-g2).max())
    print("loss head ok")

    # --- secure embed == table lookup ---
    tok = jax.random.randint(jax.random.PRNGKey(6), (4, 8), 0, V)
    with use_runtime(rt):
        emb = jax.jit(lambda t, x: secure_vocab_embed(rt, t, x, key))(table, tok)
    expect = jnp.take(table, tok, axis=0)
    assert np.allclose(np.asarray(emb, np.float32), expect, atol=2e-2), \
        float(jnp.abs(emb.astype(jnp.float32)-expect).max())
    print("secure embed ok")

    # --- sequence-sharded decode == full forward next-token ---
    # The chunked attention's head-splitting reshapes are written for Auto
    # axes; under Explicit (typed) sharding they would need an
    # out_sharding each.  This section keeps an Auto mesh.
    from repro.configs.base import get_arch
    cfg = get_arch("stablelm_1_6b").reduced()
    rt = Runtime(mesh=jax.make_mesh(
        (1, 2, 4), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3),
        batch_axes=("data",), attn_chunk=16, loss_chunk=8)
    with use_runtime(rt):
        params = model_lib.init_params(cfg, key)
        b, s = 4, 16
        tokens = jax.random.randint(jax.random.PRNGKey(7), (b, s), 0,
                                    cfg.vocab)
        cache = model_lib.init_cache(rt, cfg, b, s)
        dec = jax.jit(lambda p, bt, k: model_lib.decode_step(rt, cfg, p, bt, k))
        preds = []
        for t in range(s):
            batch = {"token": tokens[:, t], "pos": jnp.asarray(t, jnp.int32),
                     "cache": cache}
            tk, cache = dec(params, batch, key)
            preds.append(np.asarray(tk))
        dec_preds = np.stack(preds, 1)

        def fwd(params, tokens):
            x = model_lib._embed_tokens(rt, cfg, params, tokens, key)
            h, _, _ = model_lib._backbone(rt, cfg, params, x, s)
            from repro.vfl.heads import vocab_parallel_greedy
            return jax.vmap(lambda hh: vocab_parallel_greedy(
                rt, params["embed"], hh), in_axes=1, out_axes=1)(h)
        full_preds = np.asarray(jax.jit(fwd)(params, tokens))
    agree = (full_preds == dec_preds).mean()
    assert agree >= 0.95, agree
    print("sharded decode ok")

    print("ALL-MULTIDEVICE-OK")
""")


# The fused engine's shard_map party binding, on its own so that a failure
# in the LM section above cannot hide it: one party per device, the data
# placed on the party axis at construction, results == sequential oracle.
ENGINE_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import algorithms as alg
    from repro.core.engine import EngineConfig, FusedEngine
    from repro.core.losses import logistic_l2

    rngd = np.random.default_rng(0)
    xd = rngd.standard_normal((256, 26)).astype(np.float32)
    yd = np.sign(rngd.standard_normal(256)).astype(np.float32)
    layout = alg.PartyLayout.even(26, 4, 2)   # q=4 == model axis, odd widths
    prob = logistic_l2()
    kk = jax.random.PRNGKey(0)
    maskd = jnp.asarray(layout.update_mask(26, False))
    w_ref = np.asarray(alg.sgd_epoch(prob, jnp.zeros(26), jnp.asarray(xd),
                                     jnp.asarray(yd), 0.3, maskd, kk, 32, 8))
    meshes = {"make_mesh": jax.make_mesh((1, 2, 4), ("pod", "data", "model")),
              "Mesh": Mesh(np.asarray(jax.devices()[:4]), ("model",))}
    for mname, mesh in meshes.items():
        for secure in ("two_tree", "ring"):
            eng = FusedEngine(prob, xd, yd, layout,
                              EngineConfig(secure=secure), mesh=mesh)
            assert eng._use_shard_map
            for arr in (eng.xs, eng.maskq):
                rows = {s.index[0] for s in arr.addressable_shards}
                assert len(rows) == 4, (mname, rows)   # one party per shard
                assert all(s.data.shape[0] == 1
                           for s in arr.addressable_shards)
            assert eng.y.sharding.is_fully_replicated
            wq = eng.pack_w(np.zeros(26))
            assert wq.sharding == eng.maskq.sharding, wq.sharding
            w_eng = eng.unpack_w(eng.sgd_epoch(wq, 0.3, kk, 32, 8))
            assert np.allclose(w_eng, w_ref, atol=1e-5), \\
                (mname, secure, np.abs(w_eng - w_ref).max())
    print("ALL-ENGINE-MESH-OK")
""")


def _run_8_devices(script):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=1200)


@pytest.mark.slow
def test_multidevice_semantics():
    r = _run_8_devices(SCRIPT)
    assert "ALL-MULTIDEVICE-OK" in r.stdout, r.stdout + "\n" + r.stderr


@pytest.mark.slow
def test_fused_engine_shard_map_places_parties():
    r = _run_8_devices(ENGINE_SCRIPT)
    assert "ALL-ENGINE-MESH-OK" in r.stdout, r.stdout + "\n" + r.stderr
