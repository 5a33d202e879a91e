"""Run a cell of the benchmark at a tiny size on the CPU.

Only the look for a chip and the sizes are changed, and only inside the
process that calls :func:`run_cell`: the device check returns the CPU
devices, the peaks are the v5e's, the compile cache stays off, and every
configuration and traffic file is shrunk as :data:`TINY_CONFIG` and
:data:`TINY_TRAFFIC` say.
"""
from __future__ import annotations

import contextlib
import io
import json
import time

import jax

from bench.harness import device, runner, spec

TINY_CONFIG = {"rows": 512, "cols": 64}
TINY_TRAFFIC = {"trace_seconds": 0.3}
V5E = "TPU v5 lite"


def _tiny_config(orig):
    def load(bench, name):
        return dict(orig(bench, name), **TINY_CONFIG)
    return load


def _tiny_traffic(orig):
    def load(name):
        t = dict(orig(name))
        t.update({k: v for k, v in TINY_TRAFFIC.items() if k in t})
        return t
    return load


def run_cell(monkeypatch, workload: str, seed: int = 7, seconds=0.4,
             trace: bool = False):
    """(result line as a dict, stderr text) of one tiny run."""
    monkeypatch.setattr(device, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(device, "enable_compile_cache", lambda: "off")
    peaks = device.peaks
    monkeypatch.setattr(device, "peaks", lambda kind: peaks(V5E))
    monkeypatch.setattr(spec, "config", _tiny_config(spec.config))
    monkeypatch.setattr(spec, "traffic", _tiny_traffic(spec.traffic))
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = runner.main(workload, seed, seconds, trace, time.perf_counter())
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
