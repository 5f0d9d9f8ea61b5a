"""Peaks of the chip and the work of one engine tick, from shapes alone.

The least time of one lane-tick is the bytes of the engine's carried
state (every ``EngineState`` leaf of the lane), read once and written
once per tick, at the chip's peak HBM bandwidth.  It counts the model's
state, not what an implementation streams, so moving work between the
staged XLA tick and the fused kernel never changes it.  A kernel that
kept the state on-chip across several ticks would beat this count; such
a change needs the count revisited by a change of the benchmark.
"""
from __future__ import annotations

from .cells import BENCH, load_json

WORD = 4          # every EngineState leaf is int32, float32 or uint32


def peak(device_kind: str, name: str = "hbm_bytes_per_s") -> float:
    """A peak of ``device_kind`` from ``peaks.json``; unknown devices fail."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r}; have "
                       f"{sorted(table)}")
    return float(table[device_kind][name])


def state_bytes(F: int, W: int, L: int, J: int, D: int) -> int:
    """Bytes of one lane's carried engine state: per flow slot
    (next step, done count, finish), per instance (step, bytes sent,
    rate, target, DCQCN alpha, stage, mark accumulator), per link plus
    the null link (queue), per (Symphony domain + none, job) block
    (step-min, psn window, alpha, two counters), per job (segment,
    ready tick, finish) and the two-word PRNG key."""
    words = 3 * F + 7 * F * W + (L + 1) + 5 * (D + 1) * J + 3 * J + 2
    return WORD * words


def tick_bytes(F: int, W: int, L: int, J: int, D: int) -> int:
    """Bytes one lane-tick must move at least: its state read and written."""
    return 2 * state_bytes(F, W, L, J, D)
