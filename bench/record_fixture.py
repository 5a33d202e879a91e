"""Record the small chip trace that the trace reduction's test reads.

    python3 bench/record_fixture.py --out <dir>

A few steps of the SVRG cell's programs at a small shape (4,096 rows ×
4,096 columns, q = 4, so every party's 1,024 columns fill whole 128-lane
tiles and no padding enters the counts): two SVRG calls of 8 steps on
one chip, inside the benchmark's own host spans.  The trace is copied to
``<dir>/trace_1chip.xplane.pb``.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import device, trace as tr  # noqa: E402
from bench.harness.train_epochs import Session  # noqa: E402


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    devs = device.require_tpu(1)
    cfg = {"model": "linear", "lam": 1e-4, "secure": "two_tree",
           "parties": 4, "dominators": 1, "batch": 64, "lr": 0.05,
           "rows": 4096, "cols": 4096, "onehot_frac": 0.0}
    traffic = {"algo": "svrg", "check_epochs": 2}
    sess = Session(cfg, traffic, 5, devs)
    sess.steps = 8
    sess.call()
    jax.block_until_ready(sess.state)
    out_dir = tempfile.mkdtemp(prefix="bench-fixture-")
    try:
        jax.profiler.start_trace(out_dir)
        try:
            with tr.span(tr.WINDOW):
                for _ in range(2):
                    sess.call(tr.span)
                jax.block_until_ready(sess.state)
        finally:
            jax.profiler.stop_trace()
        src = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)[0]
        os.makedirs(args.out, exist_ok=True)
        dst = os.path.join(args.out, "trace_1chip.xplane.pb")
        shutil.copy(src, dst)
        print(dst, os.path.getsize(dst))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
