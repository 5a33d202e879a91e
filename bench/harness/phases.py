"""Step phases and idle causes from a trace of the scoped program.

The engine names its phases with ``jax.named_scope`` (``vfb2.sample``,
``vfb2.gather``, ``vfb2.contract``, ``vfb2.aggregate``, ``vfb2.guard``,
``vfb2.party``) and its host work with ``repro.tracing`` spans
(``vfb2.dispatch``, ``vfb2.objective.enqueue``, ...).  From one
``.xplane.pb`` (read by :mod:`bench.harness.xspace`):

* a device op's phase is the innermost ``vfb2.*`` scope in its JAX name
  stack (the ``tf_op`` stat of its event metadata); an op under none is
  unscoped.  A fusion carries its root instruction's name stack, so it
  counts under one scope even where it fuses ops of two;
* the ops are the events of each chip's ``XLA Ops`` line that enclose no
  other (as in :mod:`bench.harness.trace`), clipped to the benchmark's
  ``bench_window`` span;
* an op is *in the loop* when a ``while`` event encloses it on the line:
  the epoch's scan over steps, the one loop the compiled epochs keep on
  the chip (the ``while`` carries no name stack of its own);
* per step = the in-loop device time under a phase, averaged over the
  chips, over the steps that ran in the window: the ``steps`` attributes
  of the ``vfb2.dispatch`` host spans that start inside it;
* an idle gap on the first chip is put down to the innermost host span,
  the benchmark's or the program's, that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import re

from bench.harness import xspace
from bench.harness.trace import HOST_SPANS, WINDOW, _subtract, _union

_SCOPE = re.compile(r"vfb2\.[a-z]+")
DISPATCH = "vfb2.dispatch"
#: the per-step phases the benchmark reports, by the scopes each takes
PHASES = {"gather": ("vfb2.sample", "vfb2.gather"),
          "contract": ("vfb2.contract",),
          "aggregate": ("vfb2.aggregate",),
          "update": ("vfb2.party",)}


def scope(tf_op) -> str | None:
    """The innermost ``vfb2.*`` scope of a JAX name stack, or None."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else None


@dataclasses.dataclass
class Op:
    name: str             # the op's HLO text
    start: int            # ns
    end: int
    scope: str | None
    in_loop: bool

    @property
    def dur(self) -> int:
        return self.end - self.start


def device_ops(events) -> list:
    """The ops of one ``XLA Ops`` line that enclose no other, each with
    its scope and whether a ``while`` encloses it."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    scopes = [scope(e.meta.get("tf_op")) for e in evs]
    parent, stack, in_loop = set(), [], []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        outer = stack[-1] if stack else None
        if outer is not None:
            parent.add(outer)
        in_loop.append(outer is not None and (
            in_loop[outer] or evs[outer].name.startswith("%while")))
        stack.append(i)
    return [Op(e.name, e.start_ns, e.end_ns, scopes[i], in_loop[i])
            for i, e in enumerate(evs) if i not in parent]


@dataclasses.dataclass
class Phases:
    window: tuple               # (start, end) ns
    chips: dict                 # plane name -> [Op] clipped to the window
    spans: list                 # [(name, start, end, attrs)] host spans

    @property
    def steps(self) -> int:
        lo, hi = self.window
        return sum(int(a.get("steps", 0)) for n, s, _, a in self.spans
                   if n == DISPATCH and lo <= s <= hi)

    def step_us(self, phase: str) -> float | None:
        """Device µs per step in ``phase`` (a key of :data:`PHASES`),
        averaged over the chips; None where no op carries a scope or no
        scanned step ran."""
        if self.steps == 0 or not self.scoped():
            return None
        loop = self.by_scope(in_loop=True)
        return sum(loop.get(k, 0) for k in PHASES[phase]) / self.steps * 1e-3

    def scoped(self) -> bool:
        return any(o.scope for ops in self.chips.values() for o in ops)

    def by_scope(self, in_loop=None) -> dict:
        """ns per scope (None: unscoped), averaged over the chips; all
        ops, or only those in (``True``) or outside (``False``) the
        loop."""
        out = {}
        for ops in self.chips.values():
            for o in ops:
                if in_loop is None or o.in_loop == in_loop:
                    out[o.scope] = out.get(o.scope, 0) + o.dur
        return {k: v / len(self.chips) for k, v in out.items()}

    def unattributed_share(self) -> float | None:
        """% of the window's device op time under no ``vfb2.*`` scope;
        None where no op carries one (a program without scopes)."""
        if not self.scoped():
            return None
        tot = self.by_scope()
        return 100.0 * tot.get(None, 0) / sum(tot.values())

    def unscoped_ops(self, top: int = 10) -> list:
        """[HLO op kind, ns a chip] of the longest unscoped ops."""
        tot = {}
        for ops in self.chips.values():
            for o in ops:
                if o.scope is None:
                    kind = re.sub(r"\.\d+$", "",
                                  o.name.split(" = ", 1)[0].lstrip("%"))
                    tot[kind] = tot.get(kind, 0) + o.dur
        return sorted(([k, v / len(self.chips)] for k, v in tot.items()),
                      key=lambda kv: -kv[1])[:top]

    def label(self, t: float) -> str:
        best = None
        for name, s, e, _ in self.spans:
            if s <= t <= e and name != WINDOW:
                if best is None or e - s < best[2] - best[1]:
                    best = (name, s, e)
        return best[0] if best else "outside benchmark spans"

    def idle_gaps(self, top: int = 10) -> list:
        """[label, seconds] of the first chip's longest idle gaps."""
        ops = next(iter(self.chips.values()))
        busy = _union([(o.start, o.end) for o in ops])
        gaps = [[self.label((s + e) / 2), (e - s) * 1e-9]
                for s, e in _subtract([list(self.window)], busy)]
        return sorted(gaps, key=lambda g: -g[1])[:top]


def load(path: str, devices=None) -> Phases:
    """Read one ``.xplane.pb``.  ``devices``: the device ids whose planes
    count (all TPU planes if None)."""
    planes = xspace.read(path, event_stats=lambda p: p == "/host:CPU")
    chips, spans = {}, []
    for plane in planes:
        dev = plane.name[len("/device:TPU:"):]
        if plane.name.startswith("/device:TPU:") and dev.isdigit():
            if devices is not None and int(dev) not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[plane.name] = device_ops(line.events)
        elif plane.name == "/host:CPU":
            spans += [(e.name, e.start_ns, e.end_ns, e.stats)
                      for line in plane.lines for e in line.events
                      if e.name in HOST_SPANS or e.name.startswith("vfb2.")]
    windows = [(s, e) for n, s, e, _ in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    if not chips:
        raise ValueError(f"{path}: no TPU plane with an 'XLA Ops' line")
    lo, hi = windows[0]
    clipped = {k: [dataclasses.replace(o, start=max(o.start, lo),
                                       end=min(o.end, hi))
                   for o in v if o.end > lo and o.start < hi]
               for k, v in chips.items()}
    return Phases((lo, hi), clipped, spans)
