"""The chip side of a run: device checks, compile cache, compile counter.

The device checks and the compile clock follow ``chip_smoke.py``, the
compile cache ``benchmarks/common.enable_compile_cache``; copied here so
that an edit of those programs cannot move the benchmark's yardstick.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax import monitoring

ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".bench" / "jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; :class:`NoChip` on any other
    platform, with fewer chips, or with Pallas interpret mode forced."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") not in ("", "0"):
        raise NoChip("REPRO_PALLAS_INTERPRET forces interpret mode")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else at the fixed ``.bench/jax_cache``
    inside the checkout; every program is cached, however quick."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), and how many such events it recorded: a
    window that adds an event traced or compiled something.  JAX's
    listeners cannot be removed: make one per process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1


def peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
