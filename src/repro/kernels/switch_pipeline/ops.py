"""jit'd wrapper for the switch-pipeline kernel."""
from __future__ import annotations

from .kernel import switch_pipeline  # noqa: F401
