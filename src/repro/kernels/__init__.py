"""Pallas kernels of the serving path, each with a jitted front door
(``ops.py``) and a pure-jnp oracle (``ref.py``).

Every front door takes ``interpret=None`` and resolves it through
:func:`interpret_default`: on a TPU or GPU the kernel compiles natively,
and only where no accelerator backs JAX does it run in the Pallas
interpreter.
"""

from __future__ import annotations

import jax

__all__ = ["interpret_default"]


def interpret_default() -> bool:
    """True when no TPU/GPU is present (Pallas must run interpreted)."""
    return jax.default_backend() not in ("tpu", "gpu")
