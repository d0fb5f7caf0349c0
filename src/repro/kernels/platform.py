"""Where the Pallas kernels run: compiled on a TPU, in the Pallas
interpreter on any other backend (the CPU test runs)."""
from __future__ import annotations

import contextlib

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    return not on_tpu()


class PathLog:
    """Trace-time record of which path each call of one dispatch took
    (True: the Pallas kernel), for whoever traces inside ``collect()``."""

    def __init__(self):
        self._open: list = []

    @contextlib.contextmanager
    def collect(self):
        """Collects, while open, one bool per call traced."""
        taken: list = []
        self._open.append(taken)
        try:
            yield taken
        finally:
            self._open.remove(taken)

    def record(self, kernel: bool) -> None:
        for taken in self._open:
            taken.append(kernel)
