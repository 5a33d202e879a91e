"""Bring-up smoke run of VFB² training and serving on a TPU.

Drives the system's main path once, through the entry points a user
calls, at the widths of the paper's data sets, and checks every result
against the repository's sequential oracles:

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: parties sharded, only

One chip, in order (each phase raises on failure):

1. device check — JAX must report a TPU; there is no CPU fallback;
2. linear VFB²-SVRG, two_tree masking, through ``algorithms.train`` on
   the D4 dense width (131,072 × 4,096, q = 4, m = 1, batch 64);
3. pipelined VFB²-SGD, ring masking, on the same data (the split-batch
   fused kernel);
4. deep VFB²-SGD, two_tree, on the D2 shape (150,000 × 92, q = 2);
5. serving: a ``ServeEngine`` over phase 2's engine answers cold, repeat
   (cache hit) and post-update (delta) batches.

``--chips 4`` runs only the multi-chip path: the flat one-party-per-chip
mesh (two_tree and ring) and ``PartyMesh(q=64, slots=4)``, each against
the one-chip vmap emulation and the oracle.

Earlier lines print, per phase, compile seconds, steady epoch seconds,
the epoch's ``memory_analysis()`` and the device's peak bytes: they are
informational.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
starts no other process and keeps the compile cache where
``repro.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import collections
import functools
import importlib.metadata
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# -- sizes (the paper's data-set widths; a CPU rehearsal shrinks them) -------
D4_ROWS, D4_COLS = 131_072, 4_096     # webspam stand-in, dense width
D2_ROWS, D2_COLS = 150_000, 92        # GiveMeSomeCredit rows, one-hot width
Q_LINEAR, Q_DEEP = 4, 2
Q_PACKED, SLOTS = 64, 4               # PartyMesh leg of --chips 4
BATCH, EPOCHS = 64, 2
HIDDEN, D_REP = 32, 16
LR_LINEAR, LR_DEEP = 0.05, 0.05
SERVE_BATCH, SERVE_BATCHES = 64, 3


# -- tolerances (each with its reason) ---------------------------------------
# Every contraction of the engine's programs takes f32 operands on the chip:
# the Mosaic kernel's dots say precision=HIGHEST, and the engine traces its
# programs under jax.default_matmul_precision("highest") (F32Program).  The
# oracles run under the same setting.  Both sides are f32 throughout and
# differ only in summation order; U32 = 2^-24 is f32's unit roundoff.
# Relative differences are taken against the largest entry of the
# reference.
U32 = 2.0 ** -24
TOL_LINEAR = 2 ** 10 * U32
TOL_LINEAR_WHY = ("f32 summation order (kernel tiles, masked aggregation) "
                  "carried through two epochs of 2,048 steps")
TOL_DEEP = 2 ** 10 * U32
TOL_DEEP_WHY = ("f32 summation order through the encoder, the masked "
                "aggregation and two epochs of 2,343 steps")
TOL_OBJECTIVE = 2 ** 6 * U32
TOL_OBJECTIVE_WHY = "one f32 pass over every row: summation order only"
TOL_SERVE = 2 ** 8 * U32
TOL_SERVE_WHY = ("one f32 contraction of 4,096 features and a masked sum "
                 "of four partials against a float64 product; the delta "
                 "answer adds a cancelling update to the cached sum")
# Mesh vs emulation on a TPU v5e read 3.9e-7 to 5.5e-7 (about 9 U32) for
# every leg; with bf16 kernel operands the same check read up to 1.8e-3,
# so the limit sits well below that fault and above the f32 reading.
TOL_EMULATION = 2 ** 6 * U32
TOL_EMULATION_WHY = ("the same kernel and masks on one chip or four, f32 "
                     "operands throughout: measured 3.9e-7 to 5.5e-7 on "
                     "the chip, f32 summation order only")


class SmokeFailure(AssertionError):
    """A phase's result is wrong: the run must not report ok."""


def log(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def check(phase: str, name: str, value: float, tol: float,
          reason: str) -> None:
    """Fail the phase unless ``value`` is finite and at most ``tol``."""
    ok = value == value and value <= tol          # NaN fails
    log(phase, check=name, value=f"{value:.3e}", tol=f"{tol:.3e}",
        verdict="ok" if ok else "FAIL", why=f'"{reason}"')
    if not ok:
        raise SmokeFailure(f"{phase}: {name} = {value:.3e} > {tol:.3e}")


def require(phase: str, cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(f"{phase}: {what}")


# -- device ------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices to run on; exits non-zero unless JAX found TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform "
            f"{devs[0].platform!r}; this script never falls back to it")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices; JAX found {len(devs)}")
    return devs


def require_chip_program(phase: str, eng, compiled_text: str) -> None:
    """The engine took the Mosaic kernel, and the program contains it."""
    require(phase, eng._kernel and not eng._interpret,
            f"engine resolved _kernel={eng._kernel}, "
            f"_interpret={eng._interpret}")
    require(phase, "tpu_custom_call" in compiled_text,
            "compiled epoch holds no tpu_custom_call (no Mosaic kernel)")


def peak_bytes(dev) -> str:
    stats = dev.memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), from JAX's own monitoring events."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        self.secs = collections.Counter()
        self.cache_hits = 0

    def on_duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.secs[self.EVENTS[event]] += duration

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {f"{k}_s": f"{self.secs[k]:.2f}"
               for k in ("trace", "lower", "compile")}
        out["cache_hits"] = self.cache_hits
        self.secs.clear()
        self.cache_hits = 0
        return out


def contraction_census(fn, *args, **static) -> str:
    """Which X-block contractions of a program took the Pallas kernel
    (``pallas_call``) and which XLA's ``dot_general``, with operand
    shapes — the engine routes by row count (``kernel_max_rows``)."""
    import jax
    from repro.analysis.walkers import sub_jaxprs

    seen = collections.Counter()

    def walk(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name in ("pallas_call", "dot_general"):
                shapes = "x".join(str(tuple(v.aval.shape))
                                  for v in eqn.invars[:2])
                tag = "kernel" if name == "pallas_call" else "xla"
                seen[f"{tag}:{shapes}"] += 1
                continue                  # never count inside a kernel
            for v in eqn.params.values():
                for sub in sub_jaxprs(v):
                    walk(sub)

    walk(jax.make_jaxpr(functools.partial(fn, **static))(*args).jaxpr)
    return ",".join(f"{k}*{c}" for k, c in sorted(seen.items())) or "none"


def program_report(phase: str, eng, name: str, args, static) -> None:
    """Compile the engine's epoch ``name`` as it ran, require the chip
    kernel in it, and print its memory analysis and contraction census."""
    fn = eng._jitted[name]
    compiled = fn.lower(*args, **static).compile()
    require_chip_program(phase, eng, compiled.as_text())
    mem = compiled.memory_analysis()
    log(phase, program=name, kernel=eng._kernel, interpret=eng._interpret,
        tpu_custom_call="tpu_custom_call" in compiled.as_text(),
        argument_bytes=mem.argument_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        alias_bytes=mem.alias_size_in_bytes,
        code_bytes=mem.generated_code_size_in_bytes)
    log(phase, program=name,
        contractions=contraction_census(fn, *args, **static))


def steady_seconds(run) -> str:
    """Two timed calls of an already compiled epoch, each ended by
    ``block_until_ready``."""
    import jax
    out = []
    for _ in range(2):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        out.append(f"{time.perf_counter() - t0:.4f}")
    return "/".join(out)


def rel_diff(got, want) -> float:
    """max |got − want| over max |want|, across matching leaves."""
    import numpy as np
    num = max(float(np.max(np.abs(np.asarray(a, np.float64)
                                  - np.asarray(b, np.float64))))
              for a, b in zip(got, want))
    den = max(float(np.max(np.abs(np.asarray(b, np.float64))))
              for b in want)
    return num / max(den, 1e-30)


def oracle(train_ref, precision: str = "highest"):
    """The sequential oracle, traced at the given matmul precision.  At
    "highest" it keeps f32 operands on a TPU, as the engine does."""
    import jax
    with jax.default_matmul_precision(precision):
        return train_ref()


def dataset(name: str, rows: int, cols: int, seed: int, onehot: float):
    """A synthetic stand-in with exactly ``rows`` training rows (the
    generator keeps 80% of what it draws for training)."""
    from repro.data import synthetic
    ds = synthetic.classification_dataset(name, n=rows * 5 // 4, d=cols,
                                          seed=seed, onehot_frac=onehot)
    return ds.x_train[:rows], ds.y_train[:rows]


# -- one chip ------------------------------------------------------------------

def linear_phase(phase, clock, dev, x, y, *, algo, secure, pipelined,
                 seed):
    import jax
    from repro.core import algorithms, losses
    from repro.core.engine import EngineConfig

    prob = losses.logistic_l2()
    layout = algorithms.PartyLayout.even(x.shape[1], Q_LINEAR, 1)
    kw = dict(algo=algo, epochs=EPOCHS, lr=LR_LINEAR, batch=BATCH,
              seed=seed, pipelined=pipelined)
    clock.take()
    t0 = time.perf_counter()
    res = algorithms.train(
        prob, x, y, layout, engine="fused",
        engine_config=EngineConfig(secure=secure, donate=True), **kw)
    train_s = time.perf_counter() - t0
    log(phase, train_s=f"{train_s:.2f}", **clock.take(),
        objective=[round(h["objective"], 6) for h in res.history])
    eng = res.engine
    steps = x.shape[0] // BATCH
    key = jax.random.PRNGKey(seed)
    static = dict(batch=BATCH, steps=steps)
    if algo == "svrg":
        wq = eng.pack_w(res.w)
        muq = eng.full_gradient(wq, key)
        program_report(phase, eng, "svrg",
                       (eng.xs, wq, wq, muq, eng.maskq, eng.y, LR_LINEAR,
                        key), static)
        log(phase, program="full_grad", contractions=contraction_census(
            eng._jitted["full_grad"], eng.xs, wq, eng.y, key))
        log(phase, steady_epoch_s=steady_seconds(
            lambda: eng.svrg_epoch(wq, wq, muq, LR_LINEAR, key, BATCH,
                                   steps)))
    else:
        name = "pipelined_sgd" if pipelined else "sgd"
        wq = eng.pack_w(res.w)
        program_report(phase, eng, name,
                       (eng.xs, wq, eng.maskq, eng.y, LR_LINEAR, key),
                       static)
        epoch = getattr(eng, f"{name}_epoch")
        # the epoch donates wq: every timed call gets its own copy
        copies = iter([eng.pack_w(res.w) for _ in range(2)])
        log(phase, steady_epoch_s=steady_seconds(
            lambda: epoch(next(copies), LR_LINEAR, key, BATCH, steps)))
    log(phase, peak_bytes_in_use=peak_bytes(dev))

    ref = oracle(lambda: algorithms.train(
        prob, x, y, layout, engine="reference", **kw))
    d_w = rel_diff([res.w], [ref.w])
    obj = res.history[-1]["objective"]
    d_obj = abs(obj - ref.history[-1]["objective"]) / abs(obj)
    check(phase, "max|w-w_oracle|/max|w_oracle|", d_w, TOL_LINEAR,
          TOL_LINEAR_WHY)
    check(phase, "objective vs oracle, relative", d_obj, TOL_OBJECTIVE,
          TOL_OBJECTIVE_WHY)
    return res


def deep_phase(phase, clock, dev, x, y, seed):
    import jax
    from repro.core import algorithms, losses
    from repro.core.engine import EngineConfig

    prob = losses.logistic_l2()
    layout = algorithms.PartyLayout.even(x.shape[1], Q_DEEP, 1)
    kw = dict(algo="sgd", epochs=EPOCHS, lr=LR_DEEP, batch=BATCH,
              seed=seed, deep=True, hidden=HIDDEN, d_rep=D_REP)
    clock.take()
    t0 = time.perf_counter()
    res = algorithms.train(
        prob, x, y, layout, engine="fused",
        engine_config=EngineConfig(secure="two_tree", donate=True), **kw)
    train_s = time.perf_counter() - t0
    log(phase, train_s=f"{train_s:.2f}", **clock.take(),
        objective=[round(h["objective"], 6) for h in res.history])
    eng = res.engine
    steps = x.shape[0] // BATCH
    key = jax.random.PRNGKey(seed)
    pq = eng.pack_deep(res.params)
    program_report(phase, eng, "deep_sgd",
                   (eng.xs, pq, eng.maskq, eng.trainq, eng.y, LR_DEEP, key),
                   dict(batch=BATCH, steps=steps))
    copies = iter([eng.pack_deep(res.params) for _ in range(2)])
    log(phase, steady_epoch_s=steady_seconds(
        lambda: eng.deep_sgd_epoch(next(copies), LR_DEEP, key, BATCH,
                                   steps)))
    log(phase, peak_bytes_in_use=peak_bytes(dev))

    def leaves(p):
        return (list(p.enc_w1) + list(p.enc_b1) + list(p.enc_w2)
                + [p.head])

    def train_ref():
        return algorithms.train(prob, x, y, layout, engine="reference", **kw)

    ref = oracle(train_ref)
    # informational: how far XLA's default precision (bf16 operands on a
    # TPU) moves the same oracle — what the f32 contractions guard against
    ref_def = oracle(train_ref, "default")
    moved = rel_diff(leaves(ref_def.params), leaves(ref.params))
    log(phase, oracle_default_vs_highest=f"{moved:.3e}")
    d_p = rel_diff(leaves(res.params), leaves(ref.params))
    obj = res.history[-1]["objective"]
    d_obj = abs(obj - ref.history[-1]["objective"]) / abs(obj)
    check(phase, "max|p-p_oracle|/max|p_oracle|", d_p, TOL_DEEP,
          TOL_DEEP_WHY)
    check(phase, "objective vs oracle, relative", d_obj, TOL_OBJECTIVE,
          TOL_OBJECTIVE_WHY)


def serve_phase(phase, clock, dev, res, x, seed):
    import jax
    import numpy as np
    from repro.serve import ServeEngine

    eng = res.engine
    sv = ServeEngine(eng, max_batch=SERVE_BATCH)
    # distinct ids: a repeated id would be recomputed by a later cold
    # batch under a fresh mask, and the cache keeps the latest value
    ids = np.random.default_rng(seed).choice(
        x.shape[0], SERVE_BATCH * SERVE_BATCHES, replace=False)
    w = np.asarray(res.w, np.float64)

    def forward(wv):
        """The training forward Σ_ℓ x_ℓ·w_ℓ = x·w, in float64 on the host."""
        return np.asarray(x[ids], np.float64) @ wv

    def timed(label):
        t0 = time.perf_counter()
        out = sv.serve(ids)
        return out, f"{label}_s={time.perf_counter() - t0:.4f}"

    clock.take()
    sv.set_weights(res.w)
    cold, t_cold = timed("cold")
    hit, t_hit = timed("hit")
    w2 = (0.5 * w).astype(np.float32)
    sv.set_weights(w2)
    upd, t_upd = timed("delta")
    log(phase, batches=SERVE_BATCHES, max_batch=SERVE_BATCH,
        timings=",".join((t_cold, t_hit, t_upd)), **clock.take(),
        stats=sv.stats)
    wq = eng.pack_w(w2)
    pid = jax.numpy.zeros((SERVE_BATCH,), jax.numpy.int32)
    program_report(phase, eng, "serve_full",
                   (sv.xs, wq, sv._pfq, pid, sv._csum, sv._cver,
                    jax.random.PRNGKey(seed), jax.numpy.int32(0)), {})
    log(phase, peak_bytes_in_use=peak_bytes(dev))

    require(phase, np.array_equal(cold, hit),
            "cache hit is not bit-exact vs the cold dispatch")
    st = sv.stats
    require(phase, (st.full_dispatches, st.hit_dispatches,
                    st.delta_dispatches) == (SERVE_BATCHES,) * 3,
            f"routing: {st}")
    check(phase, "cold vs training forward, relative", rel_diff(
        [cold], [forward(w)]), TOL_SERVE, TOL_SERVE_WHY)
    check(phase, "delta vs training forward, relative", rel_diff(
        [upd], [forward(w2.astype(np.float64))]), TOL_SERVE, TOL_SERVE_WHY)


def one_chip(clock, devs, seed) -> None:
    dev = devs[0]
    t0 = time.perf_counter()
    x, y = dataset("D4", D4_ROWS, D4_COLS, seed, 0.0)
    log("data", name="D4", shape=x.shape, gen_s=f"{time.perf_counter()-t0:.2f}")
    res = linear_phase("svrg_two_tree", clock, dev, x, y, algo="svrg",
                       secure="two_tree", pipelined=False, seed=seed)
    pipe = linear_phase("pipelined_sgd_ring", clock, dev, x, y, algo="sgd",
                        secure="ring", pipelined=True, seed=seed)
    del pipe                                  # its engine holds a copy of x
    x2, y2 = dataset("D2", D2_ROWS, D2_COLS, seed + 1, 0.4)
    log("data", name="D2", shape=x2.shape)
    deep_phase("deep_sgd_two_tree", clock, dev, x2, y2, seed)
    serve_phase("serve", clock, dev, res, x, seed)


# -- four chips ----------------------------------------------------------------

def four_chip(clock, devs, seed) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import algorithms, losses
    from repro.core.engine import EngineConfig, FusedEngine
    from repro.sharding.api import PartyMesh

    x, y = dataset("D4", D4_ROWS, D4_COLS, seed, 0.0)
    mesh = Mesh(np.asarray(devs[:4]), ("model",))
    prob = losses.logistic_l2()
    steps = x.shape[0] // BATCH
    key = jax.random.PRNGKey(seed)
    w0 = np.zeros(x.shape[1], np.float32)

    ref = oracle(lambda: np.asarray(algorithms.sgd_epoch(
        prob, jax.numpy.asarray(w0), jax.numpy.asarray(x),
        jax.numpy.asarray(y), LR_LINEAR,
        jax.numpy.ones(x.shape[1], jax.numpy.float32), key, BATCH, steps)))

    def run(eng):
        t0 = time.perf_counter()
        w = eng.unpack_w(eng.sgd_epoch(eng.pack_w(w0), LR_LINEAR, key,
                                       BATCH, steps))
        return w, time.perf_counter() - t0

    legs = [(f"mesh_q{Q_LINEAR}_{s}", Q_LINEAR, s, mesh, None)
            for s in ("two_tree", "ring")]
    legs.append((f"partymesh_q{Q_PACKED}_slots{SLOTS}", Q_PACKED,
                 "two_tree", PartyMesh(q=Q_PACKED, slots=SLOTS, mesh=mesh),
                 PartyMesh(q=Q_PACKED, slots=SLOTS)))
    for phase, q, secure, sharded, emulated in legs:
        layout = algorithms.PartyLayout.even(x.shape[1], q, 1)
        cfg = EngineConfig(secure=secure, donate=True)
        clock.take()
        eng = FusedEngine(prob, x, y, layout, cfg, mesh=sharded)
        require(phase, eng._use_shard_map, "engine did not bind shard_map")
        resident = {str(s.device.id): s.data.nbytes
                    for s in eng.xs.addressable_shards}
        log(phase, xs_bytes=eng.xs.nbytes, resident_xs_bytes=resident)
        require(phase, sorted(resident) == sorted(str(d.id)
                                                  for d in devs[:4])
                and set(resident.values()) == {eng.xs.nbytes // 4},
                "each chip must hold exactly a quarter of xs")
        w_mesh, t_first = run(eng)
        log(phase, first_epoch_s=f"{t_first:.2f}", **clock.take())
        wq = eng.pack_w(w0)
        program_report(phase, eng, "sgd",
                       (eng.xs, wq, eng.maskq, eng.y, LR_LINEAR, key),
                       dict(batch=BATCH, steps=steps))
        log(phase, steady_epoch_s=steady_seconds(
            lambda: eng.sgd_epoch(eng.pack_w(w0), LR_LINEAR, key, BATCH,
                                  steps)))
        del eng
        emu = FusedEngine(prob, x, y, layout, cfg, mesh=emulated)
        require(phase, not emu._use_shard_map, "emulation bound shard_map")
        w_emu, _ = run(emu)
        del emu
        log(phase, peak_bytes_in_use=[peak_bytes(d) for d in devs[:4]])
        check(phase, "mesh vs one-chip emulation, relative",
              rel_diff([w_mesh], [w_emu]), TOL_EMULATION, TOL_EMULATION_WHY)
        check(phase, "mesh vs oracle, relative", rel_diff([w_mesh], [ref]),
              TOL_LINEAR, TOL_LINEAR_WHY)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the parties-sharded-over-chips path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache

    devs = require_tpu(args.chips)
    cache_dir = enable_compile_cache()       # before the first compile
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    log("device", platform=devs[0].platform, kind=f'"{devs[0].device_kind}"',
        count=len(devs), jax=jax.__version__, libtpu=libtpu,
        compile_cache=cache_dir)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    try:
        (four_chip if args.chips == 4 else one_chip)(clock, devs, args.seed)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock.on_duration)
        jax.monitoring.unregister_event_listener(clock.on_event)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
