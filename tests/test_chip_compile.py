"""Compile the main path's Pallas kernel and a whole fused epoch for a TPU
v5e chip that is described, not attached.

Interpret mode (every other kernel test) pads lanes to 8 and has no VMEM
limit, so only the chip's own compiler checks the Mosaic tiling and fast-
memory use of ``kernels/vfl_grad.py``.  The widths are those
``chip_smoke.py`` runs: the D4 dense width split over q = 4 parties
(dp = 1,024), batch 64, SVRG's rank 2, the deep encoder's hidden width 32
on the D2 split (dp = 46), serving's 64 requests as the M axis, and the
engine's kernel row limit (``kernel_max_rows``).

The topology is described only inside the ``topo`` fixture: describing it
loads the TPU library, which one process at a time may hold, so no
module import may do it.  Nothing here runs; a compile that passes is not
a chip run.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.walkers import primitive_eqns
from repro.core import algorithms, losses
from repro.core.engine import EngineConfig, FusedEngine
from repro.kernels import vfl_grad as vg


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (mode, B, D, Mw, Mθ, split, traced λ) — the contractions chip_smoke routes
# through the kernel, plus the widest block the engine may route there
KERNEL_CASES = {
    "sgd_forward": ("forward", 64, 1024, 1, None, None, False),
    "svrg_forward": ("forward", 64, 1024, 2, None, None, False),
    "svrg_backward_no_w": ("backward", 64, 1024, None, 2, None, False),
    "fused_lambda": ("fused", 64, 1024, 1, 1, None, True),
    "pipelined_split": ("fused", 128, 1024, 1, 1, 64, False),
    "deep_encoder_forward": ("forward", 64, 46, 32, None, None, False),
    "deep_encoder_backward": ("backward", 64, 46, None, 32, None, False),
    "serve_requests": ("forward", 1, 1024, 64, None, None, False),
    "max_rows_forward": ("forward", 4096, 1024, 32, None, None, False),
    "max_rows_backward": ("backward", 4096, 1024, None, 32, None, False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_vfl_grad_compiles_for_v5e(one_chip, case):
    mode, b, d, mw, mth, split, traced_lam = KERNEL_CASES[case]
    rows_th = split if split is not None else b
    args = [_spec(one_chip, (b, d))]
    if mw is not None:
        args.append(_spec(one_chip, (d, mw)))
    if mth is not None:
        args.append(_spec(one_chip, (rows_th, mth)))
    if traced_lam:
        args.append(_spec(one_chip, ()))

    def call(*ops):
        it = iter(ops)
        xb = next(it)
        w = next(it) if mw is not None else None
        th = next(it) if mth is not None else None
        lam = next(it) if traced_lam else 0.0
        return vg.vfl_grad(xb, w, th, lam, mode=mode, split=split,
                           interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sgd_epoch_compiles_for_v5e(one_chip):
    """One whole ``FusedEngine.sgd_epoch`` (q = 4 parties, D4 width, the
    full 131,072-row data set) with the kernel compiled, not interpreted.
    The engine is built on host data of the same width; its jitted epoch
    is then lowered against the described chip."""
    q, n, d, batch = 4, 131_072, 4096, 64
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, d)).astype(np.float32)
    y = np.sign(rng.standard_normal(64)).astype(np.float32)
    layout = algorithms.PartyLayout.even(d, q, 1)
    eng = FusedEngine(losses.logistic_l2(), x, y, layout,
                      EngineConfig(secure="two_tree", donate=True,
                                   use_kernel=True, interpret=False))
    key = jax.random.PRNGKey(0)
    wq = eng.pack_w(np.zeros(d, np.float32))
    # tracing builds (and caches) the jitted epoch without compiling it
    jax.make_jaxpr(lambda w: eng.sgd_epoch(w, 0.1, key, batch, 2))(wq)
    dp = eng.dp
    compiled = eng._jitted["sgd"].lower(
        _spec(one_chip, (q, n, dp)), _spec(one_chip, (q, dp)),
        _spec(one_chip, (q, dp)), _spec(one_chip, (n,)),
        _spec(one_chip, ()), _spec(one_chip, (2,), jnp.uint32),
        batch=batch, steps=n // batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (q, B, D, mode, Mw, Mθ, split, traced λ) — the grouped call that the
# one-chip engine makes under its vmap over parties: D4's 4 parties of 64
# columns (padded to 128 lanes) at SVRG's rank 2, D2's 2 parties of 46
GROUPED_CASES = {
    "d4_forward": (4, 64, 64, "forward", 2, None, None, False),
    "d4_backward_no_w": (4, 64, 64, "backward", None, 2, None, False),
    "d4_fused_lambda": (4, 64, 64, "fused", 2, 2, None, True),
    "d4_split": (4, 128, 64, "fused", 1, 1, 64, False),
    "d2_forward": (2, 64, 46, "forward", 1, None, None, False),
    "d2_backward_no_w": (2, 64, 46, "backward", None, 1, None, False),
    "d2_fused": (2, 64, 46, "fused", 1, 1, None, False),
    "d2_split": (2, 128, 46, "fused", 1, 1, 64, False),
}


@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_vfl_grad_compiles_for_v5e(one_chip, case):
    """``jax.vmap`` over parties compiles to one Mosaic call whose X
    operand keeps the party axis in front, with no grid visit per party."""
    q, b, d, mode, mw, mth, split, traced_lam = GROUPED_CASES[case]
    rows_th = split if split is not None else b
    args = [_spec(one_chip, (q, b, d))]
    if mw is not None:
        args.append(_spec(one_chip, (q, d, mw)))
    if mth is not None:
        args.append(_spec(one_chip, (q, rows_th, mth)))
    if traced_lam:
        args.append(_spec(one_chip, ()))

    def call(*ops):
        it = iter(ops)
        xb = next(it)
        w = next(it) if mw is not None else None
        th = next(it) if mth is not None else None
        lam = next(it) if traced_lam else None

        def party(x, wp, tp):
            return vg.vfl_grad(x, wp, tp, 0.0 if lam is None else lam,
                               mode=mode, split=split, interpret=False)
        return jax.vmap(party)(xb, w, th)

    [eqn] = primitive_eqns(jax.make_jaxpr(call)(*args), "pallas_call")
    gm = eqn.params["grid_mapping"]
    assert len(gm.grid) == 2            # (nD, nB): no axis of parties
    assert all(getattr(bm.block_shape[0], "block_size", None) == q
               for bm in gm.block_mappings)
    text = jax.jit(call).lower(*args).compile().as_text()
    [kernel] = [ln for ln in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln]
    assert f"f32[{q},{b},128]" in kernel
