"""The control, at a size a test run can hold (4,096 rows, 1,024
columns): the reference put in the program's place at the
next precision below (bf16x3, written out on the CPU) must come out not
correct under each cell's limits.  The same readings are taken on the
chip at the cells' own sizes by ``bench/control.py``."""
import jax
import pytest

from bench import control
from bench.harness import check, spec
from bench.tests import tiny

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def _readings(workload):
    bench = spec.benchmark()
    cell = spec.cell(bench, workload)
    cfg = dict(spec.config(bench, cell["config"]), rows=4096, cols=1024)
    traffic = tiny._tiny_traffic(spec.traffic)(cell["traffic"])
    return control.train_readings(cfg, traffic, 11,
                                  jax.devices()[:cell["chips"]])


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    r = _readings(workload)
    ok, checks = check.judge(r["bf16x3"], spec.limits(workload))
    assert not ok, checks
