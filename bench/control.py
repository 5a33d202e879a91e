"""Readings that set a cell's correctness limits: the program's, the
control's and the faults', over many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--out F]

For each seed the program is driven from the seed through its first
calls (no measured window), then the plain reference follows them at
"highest", and each variant of the reference is put in the program's
place: the control (the next precision below, ``bf16x3``), and the
faults ``half_batch`` and ``no_exchange``.  Each prints its numbers
against the reference.

The benchmark's own runs never run this.  It needs the chips the cell
asks for.  The last line is a summary: per number, the largest program
reading (the lower), the smallest control and fault readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import device, spec  # noqa: E402

TRAIN_VARIANTS = (("bf16x3", None), ("highest", "half_batch"),
                  ("highest", "no_exchange"))


def train_readings(cfg, traffic, seed, devs) -> dict:
    from bench.harness.train_epochs import Session
    sess = Session(cfg, traffic, seed, devs)
    sess.release()
    gc.collect()
    return sess.readings(TRAIN_VARIANTS)


def summary(rows: list) -> dict:
    out = {}
    for variant in rows[0]:
        if not isinstance(rows[0][variant], dict):
            continue
        for name in rows[0][variant]:
            vals = [r[variant][name] for r in rows]
            key = "lower" if variant == "program" else f"min_{variant}"
            out.setdefault(name, {})[key] = (max(vals)
                                              if variant == "program"
                                              else min(vals))
            out[name].setdefault("by_seed", {})[variant] = vals
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    devs = device.require_tpu(int(cell["chips"]))
    device.enable_compile_cache()
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = train_readings(cfg, traffic, seed, devs)
        r["seed"], r["seconds"] = seed, time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    result = {"workload": args.workload, "device": device.describe(devs),
              "summary": summary(rows)}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            dict(result, rows=rows), indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
