"""Pallas TPU kernels for the model hot spots, each with a jnp ``ref.py``.

Kernels compile for the device JAX runs on. Only where that is the CPU do
the ``ops.py`` wrappers run them in interpret mode, so a kernel on the
served path can never fall back to the interpreter on a TPU.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The wrappers' ``interpret=None`` default: interpret only when the
    default backend is the CPU. An explicit bool wins, so a test that
    compiles for a described TPU (while the backend is still the CPU)
    passes ``interpret=False`` itself."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
