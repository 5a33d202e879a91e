"""A run with the timed path broken underneath must come out not
correct: the harness runs as on the chip (only the look for a chip and
the sizes changed, see ``tiny``), with one fault planted in the program.
"""
import pytest

from bench.harness import spec
from bench.tests.tiny import run_cell
from repro.core.engine import FusedEngine

EPOCHS = ("sgd_epoch", "svrg_epoch")


def unchanged(mp):
    """Every epoch returns its state as it came."""
    for name in EPOCHS:
        mp.setattr(FusedEngine, name, lambda self, state, *a, **k: state)


def half_batch(mp):
    """Every epoch steps on half of each minibatch, the mean over it."""
    for name in EPOCHS:
        orig = getattr(FusedEngine, name)

        def epoch(self, *args, _orig=orig):
            *head, batch, steps = args
            return _orig(self, *head, batch // 2, steps)
        mp.setattr(FusedEngine, name, epoch)


def no_exchange(mp):
    """The masked aggregation across parties is left out."""
    mp.setattr(FusedEngine, "_agg", lambda self, z, kt: z)


CASES = [(w["name"], f) for w in spec.benchmark()["workloads"]
         for f in (unchanged, half_batch, no_exchange)]


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f.__name__}" for w, f in CASES])
def test_fault_is_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    line, err = run_cell(monkeypatch, workload)
    assert line["correct"] is False, err
