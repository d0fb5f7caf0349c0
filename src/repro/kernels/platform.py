"""Where the Pallas kernels run: compiled on a TPU, in the Pallas
interpreter on any other backend (the CPU test runs)."""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    return not on_tpu()
