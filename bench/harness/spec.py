"""``BENCHMARK.json`` and the files it names, found by name."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _json(CHECKOUT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(CHECKOUT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _json(BENCH_DIR / "traffic" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return _json(BENCH_DIR / "limits" / f"{cell_name}.json")["limits"]


def end_to_end(bench: dict, cell_name: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer(bench: dict, cell_name: str) -> list:
    """The per-layer metrics whose ``workloads`` list the cell."""
    return [m for m in bench["per_layer"] if cell_name in m["workloads"]]


def family(name: str, known) -> str:
    """The longest of ``name``, ``name`` less its last ``.<part>``, ... in
    ``known``: ``train_mfu.mesh4`` is the quantity ``train_mfu`` held to
    a bound of its own for one family of cells."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        if ".".join(parts[:k]) in known:
            return ".".join(parts[:k])
    raise KeyError(f"no quantity for metric {name!r}")


def reader(metric_name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``, or of the
    file of the quantity ``metric_name`` is a family of."""
    files = {p.stem for p in (BENCH_DIR / "metrics").glob("*.py")}
    path = BENCH_DIR / "metrics" / f"{family(metric_name, files)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
