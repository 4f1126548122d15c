"""Where the Pallas kernels run: compiled by Mosaic on a TPU, through the
Pallas interpreter on any other backend (the CPU the tests use)."""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """A kernel's `interpret` flag: an explicit bool wins (the compile tests
    pass False to compile for a described TPU from a CPU process); None
    follows the backend alone -- False on a TPU, True elsewhere."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
