"""Pallas TPU kernels.

Every wrapper takes ``interpret=None`` by default and derives the mode from
the platform (:func:`interpret_mode`): compiled on a TPU, run by the Pallas
interpreter anywhere else, which is how the CPU tests drive them.
"""

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True everywhere but on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
