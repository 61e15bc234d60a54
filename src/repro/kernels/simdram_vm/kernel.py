"""Pallas TPU kernel: the SIMDRAM control unit executing a μProgram.

μPrograms are *static artifacts* (generated offline by Steps 1–2), so the
control-unit FSM becomes trace-time unrolling: every AAP/AP of the flattened
μProgram turns into VPU bitwise ops on packed bit-plane rows held in
VMEM/registers.  The Pallas grid plays the role of the Loop Counter: each
grid step processes one ``block_words``-lane subarray segment.

The kernel body literally reuses ``core.engine.execute`` — the same
destructive-TRA semantics validated against the oracles — applied to VMEM
tiles instead of whole arrays.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.engine import execute
from ...core.uprogram import UProgram
from .. import resolve_interpret


def make_vm_kernel(uprog: UProgram, input_names: Sequence[str],
                   out_bits: int):
    def kernel(*refs):
        in_refs = refs[:-1]
        o_ref = refs[-1]
        bw = o_ref.shape[1]
        inputs = {nm: r[...] for nm, r in zip(input_names, in_refs)}
        o_ref[...] = execute(uprog, inputs, bw, out_bits=out_bits)
    return kernel


def run_uprogram(uprog: UProgram, planes: Tuple[jax.Array, ...],
                 input_names: Sequence[str], out_bits: int,
                 block_words: int = 128,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Execute a μProgram over packed planes [n_bits_i, n_words] each."""
    n_words = planes[0].shape[1]
    assert n_words % block_words == 0, "pad words to block multiple"
    grid = (n_words // block_words,)
    in_specs = [
        pl.BlockSpec((p.shape[0], block_words), lambda i: (0, i))
        for p in planes
    ]
    return pl.pallas_call(
        make_vm_kernel(uprog, input_names, out_bits),
        out_shape=jax.ShapeDtypeStruct((out_bits, n_words), jnp.uint32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((out_bits, block_words), lambda i: (0, i)),
        interpret=resolve_interpret(interpret),
    )(*planes)
