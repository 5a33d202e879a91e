"""Run one cell of ``BENCHMARK.json`` on the chip and print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``; its configuration file (``configs/``), traffic mix
(``traffic/<name>.json``, whose ``kind`` names the driver in
``harness/``), correctness limits (``limits/<cell>.json``) and, with
``--trace 1``, one reader per per-layer metric (``metrics/<name>.py``).

The run refuses to start without a TPU, or with fewer chips than the
cell asks for.  Set-up (data from the seed, the engine, warm-up through
the compile cache) is timed as ``setup_s``; the window then measures for
``--seconds``; the check against the plain reference runs after the
window.  The last line of stdout is the result; the numbers compared,
each beside its limit, are the last lines of stderr too.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return runner.main(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
