"""One run of one cell: set-up, window, check, and the result line."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import sys
import time

from bench.harness import check, device, spec


@dataclasses.dataclass
class RunContext:
    """What a traffic driver is given, and the helpers it calls back."""

    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devs: list
    peaks: dict
    t_start: float
    clock: device.CompileClock

    def begin_window(self) -> float:
        """End of set-up: the objects set-up made are frozen out of the
        garbage collector's scans, and the set-up seconds are returned."""
        gc.collect()
        gc.freeze()
        self._compiles_at_window = self.clock.compiles
        return time.perf_counter() - self.t_start

    def compiles_in_window(self) -> int:
        return self.clock.compiles - self._compiles_at_window

    def memory_peak(self) -> int:
        return device.memory_peak_bytes(self.devs)

    def free(self) -> None:
        """Drop what the program left on the device before the check."""
        gc.unfreeze()
        gc.collect()

    def outcome(self, *, setup_s, e2e, attempted, failed, numbers,
                memory_peak_bytes, layer, notes):
        correct, checks = check.judge(numbers, self.limits)
        return {"setup_s": setup_s, "e2e": e2e, "attempted": attempted,
                "failed": failed, "correct": correct and failed == 0,
                "checks": checks, "memory_peak_bytes": memory_peak_bytes,
                "layer": layer, "notes": notes}


def _metric(value, unit):
    return {"value": value, "unit": unit}


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    bench = spec.benchmark()
    cell = spec.cell(bench, workload)
    devs = device.require_tpu(int(cell["chips"]))
    cache = device.enable_compile_cache()
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    driver = importlib.import_module(f"bench.harness.{traffic['kind']}")
    with device.CompileClock() as clock:
        rc = RunContext(cfg=cfg, traffic=traffic,
                        limits=spec.limits(workload), seed=seed,
                        seconds=seconds, trace=trace, devs=devs,
                        peaks=device.peaks(devs[0].device_kind),
                        t_start=t_start, clock=clock)
        out = driver.run(rc)
        compiles = clock.snapshot()

    metrics = {}
    if trace:
        ctx = dict(out["layer"], cell=cell, cfg=cfg, traffic=traffic)
        for m in spec.per_layer(bench, workload):
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
    else:
        e2e = dict(out["e2e"], setup_s=out["setup_s"])
        for m in spec.end_to_end(bench, workload):
            metrics[m["name"]] = _metric(e2e[spec.family(m["name"], e2e)],
                                         m["unit"])
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": dev}
    if trace:
        red = out["layer"]["trace"]
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        line["breakdown"] = red.breakdown()
    line["checks"] = out["checks"]
    info = dict(out["notes"], compile_cache=cache, **compiles)
    print(json.dumps({"run": info}), file=sys.stderr, flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
