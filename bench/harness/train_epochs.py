"""Training traffic: the trainer's epoch sequence, repeated for the
window, on one engine built in set-up.

The traffic file names the algorithm (``sgd`` or ``svrg``).  One window
call is one epoch exactly as ``algorithms._train_fused`` runs it: split
the key, SVRG's full-gradient pass, the epoch program, and the per-epoch
objective read on the host.
Set-up builds the engine and drives it from the seed through its first
``check_epochs`` calls; the window carries on with the same engine and
state.  After the window the plain reference follows those first calls
and the comparison decides ``correct``.
"""
from __future__ import annotations

import contextlib
import importlib
import time

import numpy as np

from bench.harness import check, cost, data, trace as tr


def build_engine(cfg: dict, x, y, devs):
    """The engine the trainer builds for this configuration: on one chip
    the parties are the engine's emulated party axis; with ``"mesh":
    "flat"`` each party has a chip of its own and the masked aggregation
    is a collective across them."""
    import jax
    from jax.sharding import Mesh
    from repro.core import algorithms, losses
    from repro.core.engine import EngineConfig, FusedEngine

    layout = algorithms.PartyLayout.even(cfg["cols"], cfg["parties"],
                                         cfg["dominators"])
    mesh = None
    if cfg.get("mesh") == "flat":
        mesh = Mesh(np.asarray(devs[:cfg["parties"]]), ("model",))
    eng = FusedEngine(losses.logistic_l2(cfg["lam"]), x, y, layout,
                      EngineConfig(secure=cfg["secure"], donate=True),
                      mesh=mesh)
    jax.block_until_ready(eng.xs)
    return eng


def reference(cfg: dict, x, y, precision: str = "highest", fault=None):
    """The plain reference the configuration names (its ``reference``
    file's ``Reference`` class), over the same data."""
    module = importlib.import_module(
        cfg["reference"].removesuffix(".py").replace("/", "."))
    bounds = data.party_bounds(cfg["cols"], cfg["parties"])
    return module.Reference(x, y, cfg["lam"], bounds, precision, fault)


def follow(ref, algo, start, subs, lr, batch, steps):
    """The reference through the calls the program made in set-up:
    states after each, and each one's objective."""
    states, objs = [start], []
    for sub in subs:
        states.append(ref.epoch(algo, states[-1], lr, sub, batch, steps))
        objs.append(ref.objective(states[-1]))
    return states, objs


class Session:
    """One engine, driven from the seed through the trainer's first
    calls in set-up and then on through the window."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devs):
        import jax

        if traffic["algo"] not in ("sgd", "svrg"):
            raise ValueError(f"unknown algo {traffic['algo']!r}")
        self.cfg, self.traffic = cfg, traffic
        self.algo, self.lr = traffic["algo"], cfg["lr"]
        self.batch = cfg["batch"]
        self.steps = max(1, cfg["rows"] // self.batch)
        self.bounds = data.party_bounds(cfg["cols"], cfg["parties"])
        self.x, self.y = data.dataset(cfg, seed)
        self.eng = build_engine(cfg, self.x, self.y, devs)
        self.key = jax.random.PRNGKey(data.jax_seed(seed))
        self.state = self.eng.pack_w(np.zeros(cfg["cols"], np.float32))
        self.states = [self.host(self.state)]
        self.objs, self.subs = [], []
        for _ in range(traffic["check_epochs"]):
            obj = self.call()
            self.states.append(self.host(self.state))
            self.objs.append(obj)

    def host(self, wq):
        """The party-stacked iterate as the reference holds it: the (d,)
        vector."""
        wq = np.asarray(wq)
        return np.concatenate([wq[p, :hi - lo]
                               for p, (lo, hi) in enumerate(self.bounds)])

    def epoch(self, wq, sub):
        """One epoch program of the traffic's algorithm (SVRG with its
        full-gradient pass at the epoch's start)."""
        eng = self.eng
        if self.algo == "svrg":
            muq = eng.full_gradient(wq, sub)
            return eng.svrg_epoch(wq, wq, muq, self.lr, sub, self.batch,
                                  self.steps)
        return eng.sgd_epoch(wq, self.lr, sub, self.batch, self.steps)

    def call(self, span=lambda _: contextlib.nullcontext()) -> float:
        """One call of the window: the trainer's epoch and its objective
        read."""
        import jax
        with span("epoch_dispatch"):
            self.key, sub = jax.random.split(self.key)
            self.subs.append(sub)
            self.state = self.epoch(self.state, sub)
        with span("objective_read"):
            return self.eng.objective(self.state)

    def window(self, seconds: float, annotate: bool):
        """(calls, non-finite objectives, seconds) of one window."""
        import jax
        span = tr.span if annotate else (lambda _: contextlib.nullcontext())
        calls = bad = 0
        t0 = time.perf_counter()
        with span(tr.WINDOW):
            while True:
                bad += not np.isfinite(self.call(span))
                calls += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(self.state)
        return calls, bad, time.perf_counter() - t0

    def release(self):
        """Free the program's state; keep the first calls' records."""
        self.eng = self.state = None
        del self.subs[self.traffic["check_epochs"]:]

    def readings(self, variants=(("highest", None),)):
        """Numbers of the program and of each (precision, fault) variant
        of the reference put in its place, all against the reference at
        "highest": {"program": {...}, "bf16x3": {...}, ...}."""
        args = (self.algo, self.states[0], self.subs, self.lr, self.batch,
                self.steps)
        ref = reference(self.cfg, self.x, self.y)
        r_states, r_objs = follow(ref, *args)
        out = {"program": check.training_numbers(
            ref, self.states, self.objs, r_states, r_objs, self.lr)}
        for precision, fault in variants:
            if (precision, fault) == ("highest", None):
                continue
            alt = reference(self.cfg, self.x, self.y, precision, fault)
            a_states, a_objs = follow(alt, *args)
            out[fault or precision] = check.training_numbers(
                ref, a_states, a_objs, r_states, r_objs, self.lr)
        return out


def run(rc):
    sess = Session(rc.cfg, rc.traffic, rc.seed, rc.devs)
    setup_s = rc.begin_window()
    calls, bad, elapsed = sess.window(rc.seconds, False)
    compiles_in_window = rc.compiles_in_window()
    samples_per_call = sess.steps * sess.batch
    rate = calls * samples_per_call / elapsed
    layer = {"samples_per_s": rate, "chips": len(rc.devs),
             "peaks": rc.peaks,
             "model_flops_per_sample": cost.model_flops_per_sample(
                 rc.cfg, sess.algo, samples_per_call, rc.cfg["rows"]),
             "kernel_rows": (sess.batch, rc.cfg["rows"]),
             "party_widths": [hi - lo for lo, hi in sess.bounds]}
    if rc.trace:
        tr.record(lambda: sess.window(rc.traffic["trace_seconds"], True),
                  rc, layer)
    memory = rc.memory_peak()
    sess.release()
    rc.free()
    numbers = sess.readings()["program"]
    return rc.outcome(
        setup_s=setup_s,
        e2e={"train_samples_per_s": rate},
        attempted=calls, failed=bad, numbers=numbers,
        memory_peak_bytes=memory, layer=layer,
        notes={"window_calls": calls, "window_s": elapsed,
               "compiles_in_window": compiles_in_window})
