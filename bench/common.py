"""Shared pieces of the benchmark: seeds, the peaks table, compile
accounting and the device record of a result line."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def seed_words(seed: int, stream: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words for one named stream of a run's seed: any whole
    number, however large, gives its own words (JAX keeps only the low 32
    bits of an integer seed)."""
    return np.random.SeedSequence([int(seed) & (2**64 - 1), stream]).generate_state(n)


def np_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed, stream, 4))


def jax_key(seed: int, stream: int):
    import jax
    w = seed_words(seed, stream)
    return jax.random.fold_in(jax.random.key(int(w[0] >> 1)), int(w[1] >> 1))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


class CompileLog:
    """JAX's own compile events (``jax.monitoring``): seconds tracing,
    lowering and compiling or loading from the persistent cache, and how
    many programs were traced or compiled.  ``window()`` counts what
    happened since ``mark()``: inside a measured window it should be 0."""

    _DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration": "compile"}
    _COUNTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        from jax import monitoring
        self.totals = {"trace": 0.0, "lower": 0.0, "compile": 0.0,
                       "hits": 0, "misses": 0, "events": 0}
        self._mark = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.totals[self._DURATIONS[event]] += secs
            self.totals["events"] += 1

    def _on_event(self, event, **_):
        if event in self._COUNTS:
            self.totals[self._COUNTS[event]] += 1

    def mark(self) -> None:
        self._mark = self.totals["events"]

    def window(self) -> int:
        return self.totals["events"] - self._mark

    def summary(self) -> str:
        t = self.totals
        return (f"trace {t['trace']:.1f}s, lower {t['lower']:.1f}s, compile "
                f"or cache load {t['compile']:.1f}s; persistent cache "
                f"{t['hits']} hits / {t['misses']} misses")


def device_record(devices) -> dict:
    """Platform, kind, count and the peak memory of the fullest chip: the
    larger of the buffers' peak and the runtime's reservation, which holds
    the executables' temporaries."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                   int(st.get("peak_bytes_reserved", 0)))
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
