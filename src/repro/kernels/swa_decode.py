"""Decode attention kernels (Pallas, TPU): a sliding-window one over a
dense (ring) cache, and the paged one the serving engine's tick runs
(:func:`paged_decode`, below).

:func:`swa_decode`, the long_500k serving hot spot: one query token
attending to a (ring) KV cache.  The kernel streams cache tiles through
VMEM with an online softmax (flash economics: one pass over K/V, no (W,)
score materialization in HBM), computing the ring-buffer position mask
in-register:

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


# Bytes of K a block aims at: a few hundred KB moves at HBM speed, and a
# double-buffered K and V block stays far inside the scoped VMEM.
_BLOCK_BYTES = 512 * 1024


def pages_per_block(page_size, width, itemsize, pages_per_slot):
    """Pages one block copies: the power of two nearest ``_BLOCK_BYTES``
    of K over a page's bytes, at most the slot's table."""
    fit = _BLOCK_BYTES / (page_size * width * itemsize)
    return min(1 << max(0, round(math.log2(fit))), pages_per_slot)


class _QueryRows:
    """G > 1 query rows per kv head.  At a row's start q becomes
    block-diagonal, (N*G, N*D) with head h's G rows at head h's lanes, so
    one dot meets every head's rows with the block's keys on the MXU,
    s = q . k^T (N*G, keys), and one more gives p . v for every pair of
    rows and heads, (N*G, N*D), of which the diagonal blocks are kept.  q
    and the output are (B, N, G, D)."""

    def __init__(self, n, g, d, dtype):
        self.n, self.g, self.d = n, g, d
        self.block = (1, n, g, d)
        self.scratch = [pltpu.VMEM((n * g, n * d), dtype),
                        pltpu.VMEM((n * g, n * d), jnp.float32),
                        pltpu.VMEM((n * g, 1), jnp.float32),
                        pltpu.VMEM((n * g, 1), jnp.float32)]
        self.keys_axis = 1                  # valid is (1, keys)

    def begin(self, q_ref, qd_ref, acc_ref):
        """Lays q out block-diagonally, through the f32 tiles of
        ``acc_ref``."""
        g, d = self.g, self.d
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        for h in range(self.n):
            acc_ref[h * g:(h + 1) * g, h * d:(h + 1) * d] = (
                q_ref[0, h].astype(jnp.float32))
        qd_ref[...] = acc_ref[...].astype(qd_ref.dtype)

    def attend(self, k, v, valid, scale, qd_ref, acc_ref, m_ref, d_ref):
        s = jax.lax.dot_general(qd_ref[...], k.astype(qd_ref.dtype),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, NEG)                 # (N*G, keys)
        m_prev = m_ref[...]                                  # (N*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def finish(self, o_ref, acc_ref, d_ref):
        g, d = self.g, self.d
        den = jnp.maximum(d_ref[...], 1e-30)
        for h in range(self.n):
            rows = slice(h * g, (h + 1) * g)
            o_ref[0, h] = (acc_ref[rows, h * d:(h + 1) * d]
                           / den[rows]).astype(o_ref.dtype)


class _KeyRows:
    """G = 1, one query row per kv head.  q and the output are flat,
    (B, 1, N*D).  At a row's start q becomes block-diagonal, (N, N*D) with
    head h's q in row h at head h's lanes; a block's keys then stream
    through the MXU against it, s = k . q^T (keys, N) with the keys down
    the sublanes, and p . v is a multiply and a sum down the keys on the
    VPU: neither K nor V is loaded as MXU weights for one row of output."""

    def __init__(self, n, g, d, dtype):
        del g
        self.n, self.d = n, d
        self.block = (1, 1, n * d)
        self.scratch = [pltpu.VMEM((n, n * d), dtype),
                        pltpu.VMEM((1, n * d), jnp.float32),
                        pltpu.VMEM((1, n), jnp.float32),
                        pltpu.VMEM((1, n), jnp.float32)]
        self.keys_axis = 0                  # valid is (keys, 1)

    def begin(self, q_ref, qd_ref, acc_ref):
        del acc_ref
        shape = qd_ref.shape
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * self.d
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        q = jnp.broadcast_to(q_ref[0].astype(jnp.float32), shape)
        qd_ref[...] = jnp.where((lane >= head) & (lane < head + self.d), q,
                                0.0).astype(qd_ref.dtype)

    def attend(self, k, v, valid, scale, qd_ref, acc_ref, m_ref, d_ref):
        s = jax.lax.dot_general(k.astype(qd_ref.dtype), qd_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, NEG)                     # (keys, N)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                          # (1, N)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
        m_ref[...] = m_new
        d = self.d
        for h in range(self.n):
            lanes = slice(h * d, (h + 1) * d)
            vh = v[:, lanes].astype(jnp.float32)
            acc_ref[:, lanes] = (acc_ref[:, lanes] * alpha[:, h:h + 1]
                                 + jnp.sum(p[:, h:h + 1] * vh, axis=0,
                                           keepdims=True))

    def finish(self, o_ref, acc_ref, d_ref):
        den = jnp.maximum(d_ref[...], 1e-30)
        d = self.d
        for h in range(self.n):
            lanes = slice(h * d, (h + 1) * d)
            o_ref[0, :, lanes] = (acc_ref[:, lanes]
                                  / den[:, h:h + 1]).astype(o_ref.dtype)


def _paged_kernel(layer_ref, pt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, state, qd_ref, acc_ref, m_ref, d_ref, *,
                  path, page_size, ppb, window, scale, rows):
    """Grid step i attends row i.  Its keys, logical pages [first page,
    pos // page_size], are copied by hand, ``ppb`` pages a block, into one
    half of a double buffer while the other half is attended; a row's last
    block starts the copies of the next active row's first, so the copies
    run on across grid steps.  ``state`` (SMEM) keeps the buffer half the
    next block lands in, and whether its copies are in flight."""
    i = pl.program_id(0)
    layer = layer_ref[0]
    keys = ppb * page_size
    width = k_buf.shape[3]

    @pl.when(i == 0)
    def _init():
        state[0] = 0
        state[1] = 0
        # A row's last block attends, at p == 0, the buffer pages it did
        # not copy: keep them finite.
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    def first_page(r):
        if window is None:
            return 0
        return jnp.maximum(pos_ref[r] - window + 1, 0) // page_size

    def last_page(r):                   # never past the table's end
        return jnp.minimum(pos_ref[r] // page_size, pt_ref.shape[1] - 1)

    def n_blocks(r):
        return jnp.where(pos_ref[r] < 0, 0,
                         (last_page(r) - first_page(r)) // ppb + 1)

    def block(r, j):
        """Block j of row r: its first logical page and its page count."""
        start = first_page(r) + j * ppb
        return start, jnp.minimum(last_page(r) - start + 1, ppb)

    def copy(buf, hbm, page, slot, t, kv):
        return pltpu.make_async_copy(hbm.at[layer, page], buf.at[slot, t],
                                     sem.at[kv, slot])

    def fetch(r, j, slot):
        start, n = block(r, j)

        def one(t, c):
            page = pt_ref[r, start + t]
            copy(k_buf, k_hbm, page, slot, t, 0).start()
            copy(v_buf, v_hbm, page, slot, t, 1).start()
            return c

        jax.lax.fori_loop(0, n, one, 0)

    def wait(slot, n):
        # A wait takes only the size of one page's copy from its descriptor.
        def one(t, c):
            copy(k_buf, k_hbm, 0, slot, 0, 0).wait()
            copy(v_buf, v_hbm, 0, slot, 0, 1).wait()
            return c

        jax.lax.fori_loop(0, n, one, 0)

    def next_active(r):
        return jax.lax.while_loop(
            lambda r: (r < rows) & (pos_ref[jnp.minimum(r, rows - 1)] < 0),
            lambda r: r + 1, r)

    nb = n_blocks(i)

    @pl.when(nb == 0)
    def _inactive():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(nb > 0)
    def _row():
        @pl.when(state[1] == 0)
        def _first():
            fetch(i, 0, state[0])

        path.begin(q_ref, qd_ref, acc_ref)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG, m_ref.dtype)
        d_ref[...] = jnp.zeros(d_ref.shape, d_ref.dtype)
        pos = pos_ref[i]
        end = (last_page(i) + 1) * page_size    # past it the buffer is stale
        shape = [1, 1]
        shape[path.keys_axis] = keys

        def body(j, c):
            slot = state[0]

            @pl.when(j + 1 < nb)
            def _next_block():
                fetch(i, j + 1, 1 - slot)

            @pl.when(j + 1 == nb)
            def _next_row():
                r = next_active(i + 1)
                more = r < rows

                @pl.when(more)
                def _():
                    fetch(r, 0, 1 - slot)

                state[1] = more.astype(jnp.int32)

            start, n = block(i, j)
            wait(slot, n)
            a = start * page_size + jax.lax.broadcasted_iota(
                jnp.int32, tuple(shape), path.keys_axis)
            valid = (a <= pos) & (a < end)
            if window is not None:
                valid = valid & (a > pos - window)
            path.attend(k_buf[slot].reshape(keys, width),
                        v_buf[slot].reshape(keys, width), valid, scale,
                        qd_ref, acc_ref, m_ref, d_ref)
            state[0] = 1 - slot
            return c

        jax.lax.fori_loop(0, nb, body, 0)
        path.finish(o_ref, acc_ref, d_ref)


def paged_decode(q, k_pool, v_pool, pt, pos, *, layer=0, window=None,
                 interpret=False):
    """Paged-cache decode attention: one query token per slot attending a
    block-paged KV pool through its page table.

    q: (B, N, G, D) grouped GQA heads; k/v_pool: (L, P, page_size, N*D) —
    the engine's physical page pools of L layers, each page's kv heads side
    by side in its last dim; ``layer`` (int or traced int32) picks the
    layer's pool; pt: (B, PP) int32 page table (logical page t of slot b
    lives at physical page ``pt[b, t]``); pos: per-slot (B,) int32, and a
    row with ``pos < 0`` is inactive: it reads no page and its output is
    zeros.

    The pools stay in HBM and are read in place.  The grid is one step per
    slot; a step copies the pages its row attends, logical pages
    ``[first, pos // page_size]`` (``first`` from the window, else 0), in
    blocks of :func:`pages_per_block` pages, double-buffered: the next
    block's copies (after a row's last block, the next active row's first)
    run under the current block's compute.  Table entries past ``pos`` are
    never read.  q . k takes the stored dtype with f32 accumulation;
    softmax, p and p . v are f32.  How a block meets the MXU follows G
    (:class:`_QueryRows`, :class:`_KeyRows`).  Returns (B, N, G, D)."""
    b, n, g, d = q.shape
    page_size, width = k_pool.shape[2], k_pool.shape[3]
    ppb = pages_per_block(page_size, width, k_pool.dtype.itemsize,
                          pt.shape[1])
    path = (_KeyRows if g == 1 else _QueryRows)(n, g, d, q.dtype)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    kernel = functools.partial(_paged_kernel, path=path, page_size=page_size,
                               ppb=ppb, window=window,
                               scale=1.0 / math.sqrt(d), rows=b)
    row = lambda i, *_: (i,) + (0,) * (len(path.block) - 1)
    buf = pltpu.VMEM((2, ppb, page_size, width), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec(path.block, row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(path.block, row),
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)] + path.scratch,
    )
    shape = (b,) + path.block[1:]
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer_arr, pt, pos_arr, q.reshape(shape), k_pool, v_pool)
    return out.reshape(b, n, g, d)


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
