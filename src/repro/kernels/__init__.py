"""Pallas kernels for the TPU: sorts, monoid scans and attention.

Every entry point takes ``interpret=None`` and resolves it here, once: the
kernels compile for the TPU and run in the Pallas interpreter on any other
backend (the CPU test suite).  Model, serving and training code never pass
the flag themselves.
"""

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


__all__ = ["resolve_interpret"]
