"""RG-LRU linear-recurrence kernel (Pallas, TPU).

Computes h_t = a_t * h_{t-1} + b_t over the sequence, the Griffin/
RecurrentGemma recurrence.  TPU adaptation: instead of a CUDA per-thread
selective scan, the sequence is processed in chunks; within a chunk a
sequential fori_loop updates a (block_b, block_d) carry held in VMEM —
pure VPU element-wise work with lane-aligned d_rnn tiles.  Grid is
(batch_blocks, d_blocks, seq_chunks) with the sequence innermost so the
carry persists across chunk iterations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, chunk):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    def step(t, carry):
        # Ref slices (not slices of a loaded value): Mosaic lowers a
        # dynamic pl.ds row of a VMEM ref, not a dynamic_slice of a vector.
        row = pl.ds(t, 1)
        h = a_ref[:, row, :] * carry + b_ref[:, row, :]
        h_ref[:, row, :] = h
        return h

    carry_ref[...] = jax.lax.fori_loop(0, chunk, step, carry_ref[...])


def rglru_pallas(a, b, *, chunk=128, block_b=8, block_d=128, interpret=False):
    """a, b: (B, S, D) fp32 -> h (B, S, D)."""
    bsz, s, d = a.shape
    block_b = min(block_b, bsz)
    while bsz % block_b:
        block_b -= 1
    block_d = min(block_d, d)
    while d % block_d:
        block_d -= 1
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    grid = (bsz // block_b, d // block_d, s // chunk)
    spec = pl.BlockSpec((block_b, chunk, block_d), lambda i, j, k: (i, k, j))
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, 1, block_d), jnp.float32)],
        interpret=interpret,
    )(a, b)
