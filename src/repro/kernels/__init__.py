"""Pallas kernels.  :func:`use_interpret` is the one rule every kernel
entry point uses to choose between Pallas interpret mode and a compiled
(Mosaic) kernel."""
from __future__ import annotations

import os

import jax


def use_interpret() -> bool:
    """Interpret exactly when the backend is the CPU, unless
    ``REPRO_PALLAS_INTERPRET=0|1`` forces compiled/interpret mode."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() == "cpu"
