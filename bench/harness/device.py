"""The chip: device check, peaks table, compile cache, compile clock,
peak memory."""
from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent
#: JAX's persistent compilation cache, at a fixed path inside the
#: checkout, so that only the first run of a cell there compiles.
CACHE_DIR = CHECKOUT / ".bench_cache" / "jax"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; exits non-zero otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX found platform "
                     f"{devs[0].platform!r}; the benchmark never falls "
                     "back to it")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips; JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``; a
    device missing from the table is an error, never a default."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"bench/peaks.json has no entry for device kind "
                       f"{device_kind!r}")
    return table["devices"][device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at :data:`CACHE_DIR`, every program kept
    however short its compile (the objective and reference programs
    compile in well under a second)."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileClock:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events; a compile inside the measured window shows here."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


def memory_peak_bytes(devs) -> int:
    """The peak on the fullest chip, as the backend reports it."""
    peaks_ = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks_.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks_)


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
