"""Trace one cell with the program's own spans and scopes, and print where
its set-up and its steps go.

    python3 bench/trace_phases.py --workload <cell> --seed <n> [--out <dir>]

A profiling script beside ``bench/run.py``, for the readings in ``PERF.md``; it
decides nothing about a run.  It builds the cell's session as the
benchmark does, with set-up inside a ``repro.tracing.Recorder`` (the
engine build and its parts, each program's trace, lowering and compile
or cache load), then runs three windows of the traffic's
``trace_seconds``: untraced, under ``jax.profiler``, untraced again.
The trace is reduced by :mod:`bench.harness.phases` (per-step device
time by phase, the unscoped share, idle gaps put down to the innermost
host span) and by :mod:`bench.harness.trace` (the benchmark's own
per-layer numbers).  With ``--out`` the trace is kept there, gzipped.
The last line of stdout is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.harness import data, device, phases, spec  # noqa: E402
from bench.harness import trace as tr  # noqa: E402
from bench.harness.train_epochs import Session  # noqa: E402


def setup_parts(rec, t_session_ns: int, t_end_ns: int, setup_s: float):
    """``setup_s`` part by part, from the recorder's spans and counters."""
    from repro import tracing
    [build] = [s for s in rec.spans if s.name == "vfb2.engine.build"]
    load = rec.load_s()
    programs = sorted({p for (_, p) in rec.counters}, key=str)
    return {
        "setup_s": setup_s,
        "jax_start_s": setup_s - (t_end_ns - t_session_ns) * 1e-9,
        "data_s": (build.start_ns - t_session_ns) * 1e-9,
        "engine_build_s": build.seconds,
        "engine_pack_s": rec.seconds("vfb2.engine.pack"),
        "engine_place_s": rec.seconds("vfb2.engine.place"),
        "check_calls_s": (t_end_ns - build.end_ns) * 1e-9,
        "program_load_s": load,
        "remainder_s": setup_s - build.seconds - load,
        "objective_s": rec.seconds("vfb2.objective"),
        "dispatch_s": rec.seconds(tracing.DISPATCH),
        "loads": {str(p): {part: [rec.total(part, p), rec.total(part + "_s", p)]
                           for part in tracing.LOAD_EVENTS.values()
                           if rec.total(part, p)}
                  for p in programs},
    }


def place_timing(sess) -> dict:
    """Whether ``FusedEngine.place`` returns before the copy of ``xs``
    has arrived: seconds to return, and to arrival."""
    import jax
    from repro.core.engine import pack_features
    host = pack_features(sess.x, sess.eng.layout)
    t0 = time.perf_counter()
    placed = sess.eng.place(host)
    t1 = time.perf_counter()
    jax.block_until_ready(placed)
    t2 = time.perf_counter()
    del placed
    return {"bytes": host.nbytes, "return_s": t1 - t0, "arrive_s": t2 - t0}


def main(argv=None) -> int:
    import jax
    from repro import tracing

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    devs = device.require_tpu(int(cell["chips"]))
    device.enable_compile_cache()
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    t_session = time.perf_counter_ns()
    with tracing.Recorder() as rec:
        sess = Session(cfg, traffic, args.seed, devs)
    t_end = time.perf_counter_ns()
    setup_s = time.perf_counter() - T_START
    parts = setup_parts(rec, t_session, t_end, setup_s)
    print(json.dumps({"setup": parts}), file=sys.stderr, flush=True)

    seconds = traffic["trace_seconds"]
    samples = sess.steps * sess.batch

    def rate(window):
        calls, _, elapsed = window
        return calls * samples / elapsed

    untraced = [rate(sess.window(seconds, False))]
    out_dir = tempfile.mkdtemp(prefix="bench-phases-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            traced = rate(sess.window(seconds, True))
        finally:
            jax.profiler.stop_trace()
        untraced.append(rate(sess.window(seconds, False)))
        path = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        ids = {d.id for d in devs}
        ph = phases.load(path, ids)
        red = tr.reduce_file(path, ids)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            dst = os.path.join(args.out,
                               f"{args.workload}-{args.seed}.xplane.pb.gz")
            with open(path, "rb") as src, gzip.open(dst, "wb") as out:
                shutil.copyfileobj(src, out)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    bounds = data.party_bounds(cfg["cols"], cfg["parties"])
    calls = red.mosaic_calls()
    # as in the benchmark, no roofline where each chip's call holds one
    # party (the reduction takes the parties from the call's leading axis)
    roofline = (red.roofline_share(calls, device.peaks(devs[0].device_kind),
                                   (sess.batch, cfg["rows"]),
                                   [hi - lo for lo, hi in bounds])
                if calls and cell["chips"] == 1 else None)
    ns = {str(k): v for k, v in ph.by_scope().items()}
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": device.describe(devs),
        "setup": parts,
        "place": place_timing(sess) if cell["chips"] == 1 else None,
        "tracing_cost": {"traced_samples_per_s": traced,
                         "untraced_samples_per_s": untraced},
        "steps": ph.steps,
        "step_us": {k: ph.step_us(k) for k in phases.PHASES},
        "step_us_by_scope": {str(k): v / ph.steps * 1e-3
                             for k, v in ph.by_scope(True).items()}
        if ph.steps else None,
        "window_ns_by_scope": ns,
        "scope_unattributed_share": ph.unattributed_share(),
        "unscoped_ops": ph.unscoped_ops(),
        "idle_gaps": ph.idle_gaps(),
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_share": red.idle_share,
        "collective_exposed_share": red.collective_exposed_share,
        "vfl_grad_roofline": roofline,
        "kernel_calls": len(calls),
        "kernel_names": sorted({c.text.split(" = ", 1)[0].lstrip("%")
                                .rsplit(".", 1)[0] for c in calls}),
        "breakdown": red.breakdown(),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
