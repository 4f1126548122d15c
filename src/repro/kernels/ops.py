"""Public entry points of the Pallas kernels.

Every kernel takes ``interpret=None`` by default and resolves it through
``backend.resolve_interpret``: compiled Mosaic on a TPU, the Pallas
interpreter elsewhere. An explicit ``interpret=`` overrides it per call
(the compile tests pass ``interpret=False`` to compile for a described
TPU from a CPU process).
"""

from __future__ import annotations

from .backend import resolve_interpret
from .flash_decode import flash_decode
from .lamp_attention import lamp_flash_attention
from .paged_attention import (paged_decode_attention, paged_mixed_attention,
                              paged_prefill_attention)
from .ps_matmul import ps_matmul
from .rmsnorm import rmsnorm

__all__ = ["resolve_interpret", "flash_decode", "lamp_flash_attention",
           "paged_decode_attention", "paged_mixed_attention",
           "paged_prefill_attention", "ps_matmul", "rmsnorm"]
