"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

Decided while the kernel is traced, never at import: importing a kernel
module must not initialise a JAX backend, because the process that
imports it may not have chosen its device yet.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` when the caller fixed it; otherwise compile for a
    TPU backend and interpret on any other (the CPU test path). Call it
    inside the traced function, so the backend it reads is the one the
    computation is being built for."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
