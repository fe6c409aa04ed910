"""Sliding-window decode attention kernel (Pallas, TPU).

The long_500k serving hot spot: one query token attending to a (ring) KV
cache.  The kernel streams cache tiles through VMEM with an online softmax
(flash economics: one pass over K/V, no (W,) score materialization in HBM),
computing the ring-buffer position mask in-register:

    slot j holds absolute position a_j = pos - ((pos - j) mod W)
    valid = (a_j >= 0) & (a_j <= pos) & (a_j > pos - window)

Grid: (batch, kv_heads, cache_tiles), cache innermost; scratch carries the
(groups, head_dim) output accumulator and per-group max/denominator.
For a contiguous (non-ring) cache, pass ring=False and the same kernel
masks by j <= pos directly.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref,
            *, tile, window, ring, scale):
    t_idx = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        d_ref[...] = jnp.zeros(d_ref.shape, d_ref.dtype)

    pos = pos_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
    k = k_ref[0, :, 0].astype(jnp.float32)       # (tile, D)
    v = v_ref[0, :, 0].astype(jnp.float32)       # (tile, D)

    j = t_idx * tile + jax.lax.iota(jnp.int32, tile)
    total = nt * tile
    if ring:
        a = pos - jax.lax.rem(pos - j + total * 64, total)  # absolute positions
    else:
        a = j
    valid = (a >= 0) & (a <= pos)
    if window is not None:
        valid = valid & (a > pos - window)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, tile)
    s = jnp.where(valid[None, :], s, NEG)

    m_prev = m_ref[...]                          # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                       # (G, tile)
    d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(t_idx == nt - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, d_ref, *, page_size, window, scale):
    del layer_ref, pt_ref  # consumed by the BlockSpec index maps (page gather)
    i = pl.program_id(0)
    t_idx = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        d_ref[...] = jnp.zeros(d_ref.shape, d_ref.dtype)

    pos = pos_ref[i]
    # Logical page t holds absolute positions [t*ps, (t+1)*ps); rows past
    # pos are masked, so page-table entries beyond the slot's allocation
    # (the trash page) contribute nothing.
    a = t_idx * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = a <= pos
    if window is not None:
        valid = valid & (a > pos - window)

    # One block holds every kv head of the page, heads side by side in the
    # lanes; each head is a static lane slice.
    d = q_ref.shape[3]
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)                  # (G, D)
        k = k_ref[0, 0, :, pl.ds(h * d, d)].astype(jnp.float32)  # (ps, D)
        v = v_ref[0, 0, :, pl.ds(h * d, d)].astype(jnp.float32)  # (ps, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG)                         # (G, page_size)

        m_prev = m_ref[h]                                    # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[h] = d_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(t_idx == nt - 1)
    def _final():
        o_ref[0] = (acc_ref[...] / jnp.maximum(d_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode(q, k_pool, v_pool, pt, pos, *, layer=0, window=None,
                 interpret=False):
    """Paged-cache decode attention: one query token per slot attending a
    block-paged KV pool through its page table.

    q: (B, N, G, D) grouped GQA heads; k/v_pool: (L, P, page_size, N*D) —
    the engine's physical page pools of L layers, each page's kv heads side
    by side in its last dim; ``layer`` (int or traced int32) picks the
    layer's pool; pt: (B, PP) int32 page table (logical page t of slot b
    lives at physical page ``pt[b, t]``); pos: per-slot (B,) int32.  The
    gather happens in the BlockSpec index maps via scalar prefetch — each
    grid step DMAs exactly the physical page it attends, straight from the
    stacked pool, so HBM traffic is the slot's *allocated* pages, not a
    dense (B, max_len) view or a per-layer copy of the pool.  A block is
    one whole page, (page_size, N*D): the TPU tiling rule holds for any
    page size that is a multiple of 8 and N*D that is a multiple of 128,
    and the pool's layout is the row-major one XLA keeps for such shapes,
    so the pool is read in place.  Returns (B, N, G, D)."""
    b, n, g, d = q.shape
    page_size = k_pool.shape[2]
    pp = pt.shape[1]
    grid = (b, pp)
    scale = 1.0 / math.sqrt(d)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kernel = functools.partial(_paged_kernel, page_size=page_size,
                               window=window, scale=scale)
    page = lambda i, t, layer_ref, pt_ref, pos_ref: (
        layer_ref[0], pt_ref[i, t], 0, 0)
    slot = lambda i, t, layer_ref, pt_ref, pos_ref: (i, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, g, d), slot),
            pl.BlockSpec((1, 1, page_size, n * d), page),
            pl.BlockSpec((1, 1, page_size, n * d), page),
        ],
        out_specs=pl.BlockSpec((1, n, g, d), slot),
        scratch_shapes=[
            pltpu.VMEM((n, g, d), jnp.float32),
            pltpu.VMEM((n, g, 1), jnp.float32),
            pltpu.VMEM((n, g, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, g, d), q.dtype),
        interpret=interpret,
    )(layer_arr, pt, pos_arr, q, k_pool, v_pool)


def swa_decode(q, k_cache, v_cache, pos, *, window=None, ring=False,
               tile=256, interpret=False):
    """q: (B, N, G, D) one token per sequence, grouped GQA heads;
    k/v_cache: (B, W, N, D); pos: scalar int32 or per-sequence (B,) int32
    (continuous-batching serving: every slot decodes at its own position,
    the per-slot ring mask computed in-kernel from its pos block).
    Returns (B, N, G, D)."""
    b, n, g, d = q.shape
    w = k_cache.shape[1]
    tile = min(tile, w)
    while w % tile:
        tile -= 1
    grid = (b, n, w // tile)
    scale = 1.0 / math.sqrt(d)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kernel = functools.partial(_kernel, tile=tile, window=window, ring=ring,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, h, t: (i,)),
            pl.BlockSpec((1, 1, g, d), lambda i, h, t: (i, h, 0, 0)),
            pl.BlockSpec((1, tile, 1, d), lambda i, h, t: (i, t, h, 0)),
            pl.BlockSpec((1, tile, 1, d), lambda i, h, t: (i, t, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, h, t: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, q, k_cache, v_cache)
