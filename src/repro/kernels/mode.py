"""Where the Pallas kernels run: natively on a TPU, interpreted elsewhere."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` when given, else chosen from the backend alone: the
    native kernel on TPU, Pallas interpret mode on any other backend
    (Mosaic compiles for TPU only)."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
