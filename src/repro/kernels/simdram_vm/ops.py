"""Public API: run any registered SIMDRAM operation through the Pallas VM."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.bitplane import BitPlaneArray
from ...core.operations import OPS, get_uprogram
from .kernel import run_uprogram


def simdram_op(name: str, *inputs: BitPlaneArray, style: str = "simdram",
               block_words: int = 128, interpret: Optional[bool] = None
               ) -> BitPlaneArray:
    spec = OPS[name]
    n = inputs[0].n_bits
    prog = get_uprogram(name, n, style)
    out_bits = spec.out_bits(n)
    nw = inputs[0].n_words
    pad = (-nw) % block_words
    planes = tuple(jnp.pad(x.planes, ((0, 0), (0, pad))) for x in inputs)
    out = run_uprogram(prog, planes, spec.input_names, out_bits,
                       block_words=block_words, interpret=interpret)
    return BitPlaneArray(out[:, :nw], inputs[0].n_elems, inputs[0].signed)
