"""The one place that decides whether a Pallas kernel runs interpreted.

Every call site that launches a kernel asks here, so the rule cannot
drift between them: interpret mode exists for the CPU backend (the test
suite, dry runs) and nowhere else. On a TPU every kernel compiles through
Mosaic; a kernel that cannot compile there is a failure to repair, never
something to interpret around.
"""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True only when the default backend is the CPU."""
    return jax.default_backend() == "cpu"
