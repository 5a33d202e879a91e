"""Every cell's code path at a tiny size on the CPU, traced and not.

The limits are the chip's, set at the cells' own sizes; a tiny CPU run
is checked for a well-formed line with every number compared, not for
``correct`` (``test_faults`` and ``test_control`` check that side)."""
import math

import pytest

from bench.harness import spec
from bench.tests.tiny import run_cell

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(monkeypatch, workload, trace):
    line, err = run_cell(monkeypatch, workload, trace=trace)
    bench = spec.benchmark()
    assert isinstance(line["correct"], bool), err
    assert all(math.isfinite(c["value"]) for c in line["checks"].values())
    assert set(line["checks"]) == set(spec.limits(workload))
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    want = (spec.per_layer(bench, workload) if trace
            else spec.end_to_end(bench, workload))
    names = {m["name"] for m in want}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:
        assert line["device"]["busy_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for name, c in line["checks"].items():
        assert f"check {name} " in err
