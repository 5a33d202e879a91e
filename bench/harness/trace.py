"""From a profiler trace to per-layer numbers.

``record`` runs a function under ``jax.profiler`` and reduces the trace
it writes; ``reduce_file`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``).  The reduction:

* the window is the benchmark's own ``bench_window`` host span;
* a chip's device ops are the events of the ``XLA Ops`` line of its
  ``/device:TPU:<n>`` plane, each named by its HLO text; an op that
  encloses others there (a ``while`` loop around its body) is control
  flow, not work, and is dropped so that the gaps between the ops of a
  loop body count as idle;
* busy time is the union of the ops' intervals, clipped to the window;
  the idle share is 1 − busy / window, averaged over the chips;
* a Mosaic kernel's calls are the ops whose HLO text is a
  ``tpu_custom_call``; their time is the sum of their device durations,
  their least time comes from the operand and output shapes in the same
  text, taken back to the configuration's logical extents (padding to
  the chip's tiles is not work);
* a collective is exposed where it runs on a chip and no other op does;
* idle gaps are labelled by the innermost benchmark host span that
  covers the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench_window"
#: host spans the benchmark writes around its calls into the program
HOST_SPANS = ("bench_window", "epoch_dispatch", "objective_read",
              "generator_submit", "result_wait")
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")
_SHAPE = re.compile(r"\b(f32|bf16|f16|s32|u32|s8|u8|pred)\[([0-9,]*)\]")
_ONE_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\](\{[^}]*\})?")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1}


@contextlib.contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@dataclasses.dataclass
class Op:
    text: str             # the op's HLO text (the trace's event name)
    start: float          # ns
    end: float            # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a, b):
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(ops, lo, hi):
    return [Op(o.text, max(o.start, lo), min(o.end, hi))
            for o in ops if o.end > lo and o.start < hi]


MOSAIC = 'custom_call_target="tpu_custom_call"'


def _base(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``: ops grouped by
    kind for the breakdown; Mosaic kernels as ``tpu_custom_call``."""
    if MOSAIC in text:
        return "tpu_custom_call"
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def leaf_ops(events) -> list:
    """The ops of one ``XLA Ops`` line without the ones that enclose
    others (control flow such as a ``while`` around its body)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    parent, stack = set(), []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent.add(stack[-1])
        stack.append(i)
    return [Op(e.name, e.start_ns, e.end_ns)
            for i, e in enumerate(evs) if i not in parent]


@dataclasses.dataclass
class Reduction:
    window: tuple                   # (start, end) ns
    chips: dict                     # plane name -> [Op] in the window
    spans: list                     # [(name, start, end)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, ops):
        return _union([(o.start, o.end) for o in ops])

    @property
    def busy_s(self) -> float:
        vals = [_length(self._busy(ops)) for ops in self.chips.values()]
        return sum(vals) / len(vals) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def collective_exposed_share(self):
        shares, seen = [], False
        for ops in self.chips.values():
            coll = [o for o in ops if _base(o.text).startswith(COLLECTIVES)]
            seen |= bool(coll)
            other = [o for o in ops
                     if not _base(o.text).startswith(COLLECTIVES)]
            exposed = _subtract(self._busy(coll), self._busy(other))
            shares.append(_length(exposed)
                          / (self.window[1] - self.window[0]))
        return sum(shares) / len(shares) if seen else None

    def mosaic_calls(self):
        """The Mosaic kernel calls on every chip."""
        return [o for ops in self.chips.values() for o in ops
                if MOSAIC in o.text]

    def roofline_share(self, calls, peaks, rows, widths) -> float:
        """Least time of ``calls`` over their device time, in %: each
        call's ops and bytes at the logical extents (``rows``: the row
        counts the calls take; ``widths``: the parties' column counts)."""
        from bench.harness.cost import kernel_cost
        least = spent = 0.0
        for o in calls:
            outs, operands = hlo_shapes(o.text)
            flops, nbytes = kernel_cost(operands, outs, rows, widths)
            least += max(flops / peaks["flops_bf16_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
            spent += o.dur * 1e-9
        return 100.0 * least / spent

    def breakdown(self, top: int = 10) -> dict:
        tot = {}
        for ops in self.chips.values():
            for o in ops:
                tot[_base(o.text)] = tot.get(_base(o.text), 0.0) + o.dur
        n = len(self.chips)
        device_ops = sorted(([k, v * 1e-9 / n] for k, v in tot.items()),
                            key=lambda kv: -kv[1])[:top]
        gaps = []
        for ops in list(self.chips.values())[:1]:
            busy = self._busy(ops)
            idle = _subtract([list(self.window)], busy)
            for s, e in idle:
                gaps.append([self.label((s + e) / 2), (e - s) * 1e-9])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": device_ops, "idle_gaps": gaps[:top]}

    def label(self, t: float) -> str:
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and name != WINDOW:
                if best is None or e - s < best[2] - best[1]:
                    best = (name, s, e)
        return best[0] if best else "outside benchmark spans"


def hlo_shapes(text: str):
    """(outputs, operands) of one HLO instruction's text, each a list of
    (shape, bytes per element):
    ``%n = <outputs> op(<operands>), attributes...``, where the outputs
    are one shape with its layout or a parenthesised tuple."""
    _, _, rhs = text.partition(" = ")
    if rhs.startswith("("):
        pos = _close(rhs, 0) + 1
    else:
        m = _ONE_SHAPE.match(rhs)
        if m is None:
            raise ValueError(f"no output shape in {text[:80]!r}")
        pos = m.end()
    start = rhs.index("(", pos)
    end = _close(rhs, start)

    def shapes(part):
        return [(tuple(int(d) for d in dims.split(",") if d),
                 _ITEMSIZE[dtype]) for dtype, dims in _SHAPE.findall(part)]

    return shapes(rhs[:start]), shapes(rhs[start:end + 1])


def _close(s: str, i: int) -> int:
    """Index of the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for k in range(i, len(s)):
        if s[k] == "(":
            depth += 1
        elif s[k] == ")":
            depth -= 1
            if depth == 0:
                return k
    raise ValueError(f"unbalanced parentheses in {s[:80]!r}")


def reduce_file(path: str, devices=None) -> Reduction:
    """Reduce one ``.xplane.pb``.  ``devices``: the device ids whose
    planes count (all TPU planes if None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, chips = [], {}
    host_ops = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = int(plane.name[len("/device:TPU:"):])
            if devices is not None and dev not in devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chips[plane.name] = leaf_ops(line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        host_ops.append(e)
    if not chips and host_ops:
        # a CPU backend runs its ops on host threads (rehearsal only)
        chips["/host:CPU"] = leaf_ops(host_ops)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    lo, hi = windows[0]
    return Reduction((lo, hi),
                     {k: _clip(v, lo, hi) for k, v in chips.items()},
                     spans)


def record(fn, rc, layer: dict):
    """Run ``fn`` under the profiler; put the reduction in
    ``layer["trace"]`` and return what ``fn`` returned."""
    import jax
    out_dir = tempfile.mkdtemp(prefix="bench-trace-")
    # Python function tracing off: it slows the host path being traced,
    # and the benchmark's own spans are trace annotations, kept without it
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        layer["trace"] = reduce_file(path, {d.id for d in rc.devs})
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return result
