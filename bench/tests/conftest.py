"""Tests of the benchmark itself, on the CPU at tiny sizes.

Run from the checkout root:  ``python -m pytest -q bench/tests``.
Four virtual CPU devices stand in for the four-chip cell's chips.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags
                               + " --xla_force_host_platform_device_count=4")

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
