"""``chip_smoke.py`` off the chip.

The script refuses to run without a TPU.  Its phases and the four-chip
mesh path are rehearsed here at a tiny size on the CPU (one, and four
virtual, devices): the size, the device check and the chip-kernel check
are relaxed by patching the module inside the rehearsal only — the script
has no flag for any of them.  Each rehearsal runs in its own process so
that the device count can be forced.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

REHEARSAL = textwrap.dedent("""
    import functools, sys
    sys.path.insert(0, {root!r})
    import jax
    import chip_smoke as cs
    import repro.compile_cache as cc
    import repro.core.engine as E

    for k, v in dict(D4_ROWS=512, D4_COLS=64, D2_ROWS=640, D2_COLS=12,
                     Q_PACKED=8).items():
        setattr(cs, k, v)
    cs.require_tpu = lambda chips: jax.devices()[:chips]
    # the kernel path, interpreted: what the chip compiles, the CPU emulates
    E.EngineConfig = functools.partial(E.EngineConfig, use_kernel=True)
    cs.require_chip_program = lambda phase, eng, text: cs.require(
        phase, eng._kernel and eng._interpret, "expected the kernel path")
    cc.enable_compile_cache = lambda: "off"      # tests keep no cache
    cs.main(sys.argv[1:])
""")


def _rehearse(chips: int) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    return subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(root=str(ROOT)),
         "--chips", str(chips)],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)


def _run_script(*args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                           *args], env=env, capture_output=True, text=True,
                          timeout=300, cwd=ROOT)


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_refuses_to_run_without_a_tpu(args):
    r = _run_script(*args)
    assert r.returncode != 0
    assert r.stdout == ""                       # no result line at all
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_at_tiny_size(chips):
    r = _rehearse(chips)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    checks = [ln for ln in lines if " check=" in ln]
    assert checks and all("verdict=ok" in ln for ln in checks)
    if chips == 1:
        phases = {ln.split("]")[0][1:] for ln in checks}
        assert phases == {"svrg_two_tree", "pipelined_sgd_ring",
                          "deep_sgd_two_tree", "serve"}
    else:
        resident = [ln for ln in lines if "resident_xs_bytes=" in ln]
        assert len(resident) == 3                # two flat legs, PartyMesh
