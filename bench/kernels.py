"""Which device operations of a trace are the LAMP attention kernels.

The fused serving step holds two Pallas calls per layer, the look-ahead
pass and the recompute pass of `paged_prefill_attention`. In a TPU trace
each is a `tpu_custom_call` op named after the jitted kernel entry
(`%paged_prefill_attention.6`, `%paged_prefill_attention.7`); the decode
variant, where a path uses it, is `paged_decode_attention`.
"""

from __future__ import annotations

import re

LAMP_KERNEL = re.compile(r"%?paged_(prefill|decode)_attention(\.\d+)?$")


def kernel_seconds(red: dict) -> float:
    return sum(s for text, s in red.get("ops", ())
               if LAMP_KERNEL.match(text.split(" ", 1)[0]))
