"""Mamba-2 SSD chunk-scan kernel (Pallas, TPU).

One grid cell processes one (batch, head) pair and walks the sequence in
chunks (innermost grid dim), carrying the (head_dim, d_state) SSM state in
VMEM.  Within a chunk everything is dense matmul work sized for the MXU:

    L        = exp(segsum(dA))           (chunk, chunk) decay matrix
    y_diag   = (C B^T * L) @ (x*dt)      intra-chunk
    y_off    = C @ h_in^T * decay_in     contribution of the carried state
    h_out    = h_in * decay_chunk + (B * decay_out)^T @ (x*dt)

This mirrors the chunked reference in repro/nn/ssm.py (the oracle).
B/C are per-group; the caller broadcasts groups to heads beforehand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, carry_ref,
            *, chunk, nchunks):
    h_idx = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    x = x_ref[0].astype(jnp.float32)        # (q, p)
    dt = dt_ref[0].astype(jnp.float32)      # (q, 1)... stored (q, 1)
    A = a_ref[h_idx]                        # scalar decay rate (SMEM)
    B = b_ref[0].astype(jnp.float32)        # (q, n)
    C = c_ref[0].astype(jnp.float32)        # (q, n)

    q = x.shape[0]
    xb = x * dt                             # (q, p)

    # In-chunk cumulative decay as a lower-triangular matmul (Mosaic has no
    # cumsum): cs[i, j] = sum_{k <= i} dA_k for every column j, so cs holds
    # csum_i down its rows and cs.T holds csum_j along its columns.
    tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    dA = jnp.broadcast_to(dt * A, (q, q))                               # dA_i
    cs = jnp.dot(tri.astype(jnp.float32), dA,
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    csum = cs[:, 0:1]                                                   # (q, 1)

    # Intra-chunk decay matrix L[i, j] = exp(csum_i - csum_j) for j <= i.
    L = jnp.where(tri, jnp.exp(cs - cs.T), 0.0)

    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32) * L   # (q, q)
    y = jnp.dot(scores, xb, preferred_element_type=jnp.float32)        # (q, p)

    # Carried-state contribution.
    h_in = carry_ref[...]                                              # (p, n)
    decay_from_start = jnp.exp(csum)                                   # (q, 1)
    y = y + decay_from_start * jnp.dot(C, h_in.T, preferred_element_type=jnp.float32)

    # State update.
    total = cs[q - 1:q, 0:1]                                           # (1, 1)
    decay_to_end = jnp.exp(total - csum)                               # (q, 1)
    # The chunk's total decay as a (1, n) row (a sublane sum), broadcast
    # down the state's rows: Mosaic cannot broadcast a (1, 1) both ways.
    n = h_in.shape[1]
    total_row = jnp.sum(jnp.broadcast_to(dt * A, (q, n)), axis=0,
                        keepdims=True)
    chunk_decay = jnp.broadcast_to(jnp.exp(total_row), h_in.shape)
    h_new = h_in * chunk_decay + jnp.dot(
        (xb * decay_to_end).T, B, preferred_element_type=jnp.float32)  # (p, n)
    carry_ref[...] = h_new

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nchunks - 1)
    def _final():
        state_ref[0] = h_new.astype(state_ref.dtype)


def ssd_pallas(x, dt, A, B, C, chunk, *, interpret=False):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, h, n) (heads
    already broadcast).  Returns (y (b, s, h, p), final_state (b, h, p, n))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    nchunks = s // chunk
    grid = (b, h, nchunks)

    # Layout: move head next to batch so blocks are (1, chunk, p|n).
    xt = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    Bt = B.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    Ct = C.transpose(0, 2, 1, 3).reshape(b * h, s, n)
    dtt = dt.transpose(0, 2, 1).reshape(b * h, s, 1)

    def idx(i, j, k):
        return (i * h + j, k, 0)

    y, state = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, nchunks=nchunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), idx),
            pl.BlockSpec((1, chunk, 1), idx),
            pl.BlockSpec(memory_space=pltpu.SMEM),  # whole (h,) A vector
            pl.BlockSpec((1, chunk, n), idx),
            pl.BlockSpec((1, chunk, n), idx),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), idx),
            pl.BlockSpec((1, p, n), lambda i, j, k: (i * h + j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bt, Ct)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    state = state.reshape(b, h, p, n)
    return y, state
