"""Pallas TPU kernels for the perf-critical compute layers.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and
are validated on CPU in interpret mode against their ref.py oracles:

  * bitplane_transpose - the SIMDRAM transposition unit
  * simdram_vm         - the control unit executing uPrograms on VMEM tiles
  * bitserial_matmul   - weight bit-plane quantized matmul (MXU adaptation)
  * paged_attention    - VBI-paged decode attention (translation in-kernel)

Every entry point takes ``interpret=None`` by default, which
:func:`resolve_interpret` turns into the backend rule: lower for real on a
TPU, interpret everywhere else.  An explicit ``True``/``False`` wins.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else True unless the default backend is a
    TPU — the one place the kernels' run mode is decided."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# the subpackages import resolve_interpret from here, so it is defined first
from .bitplane_transpose import from_bitplanes, to_bitplanes  # noqa: E402
from .bitserial_matmul import (QuantizedLinear, bitserial_matmul,  # noqa: E402
                               quantize_activations, quantize_weights)
from .paged_attention import paged_decode_attention  # noqa: E402
from .simdram_vm import simdram_op  # noqa: E402

__all__ = [
    "to_bitplanes", "from_bitplanes", "simdram_op", "bitserial_matmul",
    "quantize_weights", "quantize_activations", "QuantizedLinear",
    "paged_decode_attention", "resolve_interpret",
]
