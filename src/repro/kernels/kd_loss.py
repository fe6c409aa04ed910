"""Fused buffered-KD loss kernel (Pallas, TPU).

The Phase-2 hot spot: for LLM vocabularies (152k–256k) the loss reads three
(rows, V) fp32 logit tensors — HBM-bandwidth-bound.  This kernel streams
vocab tiles through VMEM with flash-style *online* logsumexp accumulation
and produces, in ONE pass and without materializing any softmax:

    per-row statistics
      lse_s      logsumexp(s)            (cross-entropy denominator)
      s_y        s[label]                (cross-entropy numerator)
      lse_st     logsumexp(s/tau)
      lse_tt     logsumexp(t/tau)
      n_tt, n_ts sum exp(t/tau - m) * (t/tau), ... * (s/tau)
      (optionally the same for the buffer b)

from which ops.py assembles  CE + tau^2 KL(t||s) [+ tau^2 KL(b||s)] in
closed form, and the backward kernel computes

    ds = g * [ softmax(s) - onehot(y) + tau*(softmax(s/tau) - softmax(t/tau))
               (+ tau*(softmax(s/tau) - softmax(b/tau))) ]

re-reading the logits once more (two total passes, matching flash-attention
economics; the jnp reference needs >= 6 full-tensor passes and a live
softmax).  Teachers/buffer are frozen in Phase 2 so they get no gradient.

Block shapes: rows_block x vocab_tile, vocab_tile a multiple of 128 lanes
and rows_block a multiple of 32 sublanes (int8/bf16/f32 all tile legally)
sized so one f32 tile stays within ``_TILE_ELEMS``.  Per-row operands
(labels, upstream grad, dequant scale/zero) enter as ``(rows, 1)`` columns:
a rank-1 block of rows_block would need rows_block % 128 == 0 on TPU.
Grid is (row_blocks, vocab_blocks) with vocab innermost; VMEM scratch
carries the online stats across vocab tiles of one row block.

The *quant* variants (``kd_quant_stats_fwd`` / ``kd_quant_grad_bwd``) take
the teacher as transport-codec payload — int8 codes + a per-row float32
(scale, zero) affine — and dequantize each tile in VMEM right before the
online update.  The f32 teacher tensor never exists in HBM: the uplink's
1-byte-per-entry representation is also what the kernel reads (4x less
teacher bandwidth).  Tile math is shared with the exact kernels via
``_fwd_body`` / ``_bwd_body``; the only quant-specific twist is padding —
codes can't encode the -1e30 sentinel, so padded vocab columns are masked
by column index against the true (static) vocab size instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
N_STATS = 11  # [lse_s, s_y, lse_st, n_ts_t, lse_tt, n_tt_t, lse_bt, n_bb_t, n_bs_t, n_bst, pad]


def _online_update(m, d, n_pairs, x, extras):
    """Online logsumexp over tile `x` (rows, tile) with weighted numerators.

    m, d: (rows, 1) running max / denom.  n_pairs: list of (rows, 1) running
    numerators paired with `extras` (rows, tile) weights:  n_i accumulates
    sum exp(x - m_final) * extras_i."""
    tile_max = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, tile_max)
    scale = jnp.exp(m - m_new)
    e = jnp.exp(x - m_new)
    d_new = d * scale + jnp.sum(e, axis=-1, keepdims=True)
    n_new = [n * scale + jnp.sum(e * w, axis=-1, keepdims=True)
             for n, w in zip(n_pairs, extras)]
    return m_new, d_new, n_new


def _fwd_body(labels_ref, s, t, b, stats_ref, acc_ref, *, tau, vocab_tile,
              with_buffer):
    """Shared forward tile math over materialized f32 tiles ``s``/``t`` (and
    ``b`` when ``with_buffer``) — the exact and dequant kernels differ only
    in how they produce ``t``."""
    v_idx = pl.program_id(1)
    nv = pl.num_programs(1)
    st = s / tau
    tt = t / tau

    @pl.when(v_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        # maxes start at NEG (plane 0 holds the running maxes)
        acc_ref[0, :, :] = jnp.full(acc_ref.shape[1:], NEG, acc_ref.dtype)

    # acc layout: (4, rows, 8) planes: [0]=maxes, [1]=denoms, [2]=numerators a, [3]=numerators b
    maxes = acc_ref[0]     # (rows, 8): cols 0..3 = m_s, m_st, m_tt, m_bt
    denoms = acc_ref[1]    # cols 0..3 = d_s, d_st, d_tt, d_bt
    nums_a = acc_ref[2]    # cols: 0 = s_y, 1 = n_tt (E_t[t/tau]), 2 = n_ts (E_t[s/tau])
    nums_b = acc_ref[3]    # cols: 0 = n_bb, 1 = n_bs

    rows = s.shape[0]
    cols = v_idx * vocab_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    y = labels_ref[...]                                   # (rows, 1)
    hit = (cols == y).astype(jnp.float32)
    s_y = nums_a[:, 0:1] + jnp.sum(s * hit, axis=-1, keepdims=True)

    m_s, d_s, _ = _online_update(maxes[:, 0:1], denoms[:, 0:1], [], s, [])
    m_st, d_st, _ = _online_update(maxes[:, 1:2], denoms[:, 1:2], [], st, [])
    m_tt, d_tt, (n_tt, n_ts) = _online_update(
        maxes[:, 2:3], denoms[:, 2:3],
        [nums_a[:, 1:2], nums_a[:, 2:3]], tt, [tt, st])

    if with_buffer:
        bt = b / tau
        m_bt, d_bt, (n_bb, n_bs) = _online_update(
            maxes[:, 3:4], denoms[:, 3:4],
            [nums_b[:, 0:1], nums_b[:, 1:2]], bt, [bt, st])
    else:
        m_bt = maxes[:, 3:4]
        d_bt = denoms[:, 3:4]
        n_bb, n_bs = nums_b[:, 0:1], nums_b[:, 1:2]

    acc_ref[0] = jnp.concatenate(
        [m_s, m_st, m_tt, m_bt, jnp.zeros((rows, 4), jnp.float32)], axis=-1)
    acc_ref[1] = jnp.concatenate(
        [d_s, d_st, d_tt, d_bt, jnp.zeros((rows, 4), jnp.float32)], axis=-1)
    acc_ref[2] = jnp.concatenate(
        [s_y, n_tt, n_ts, jnp.zeros((rows, 5), jnp.float32)], axis=-1)
    acc_ref[3] = jnp.concatenate(
        [n_bb, n_bs, jnp.zeros((rows, 6), jnp.float32)], axis=-1)

    @pl.when(v_idx == nv - 1)
    def _final():
        lse_s = jnp.log(acc_ref[1][:, 0:1]) + acc_ref[0][:, 0:1]
        lse_st = jnp.log(acc_ref[1][:, 1:2]) + acc_ref[0][:, 1:2]
        lse_tt = jnp.log(acc_ref[1][:, 2:3]) + acc_ref[0][:, 2:3]
        et_tt = acc_ref[2][:, 1:2] / acc_ref[1][:, 2:3]   # E_t[t/tau]
        et_ts = acc_ref[2][:, 2:3] / acc_ref[1][:, 2:3]   # E_t[s/tau]
        if with_buffer:
            lse_bt = jnp.log(acc_ref[1][:, 3:4]) + acc_ref[0][:, 3:4]
            eb_bb = acc_ref[3][:, 0:1] / acc_ref[1][:, 3:4]
            eb_bs = acc_ref[3][:, 1:2] / acc_ref[1][:, 3:4]
        else:
            lse_bt = jnp.zeros_like(lse_s)
            eb_bb = jnp.zeros_like(lse_s)
            eb_bs = jnp.zeros_like(lse_s)
        sy = acc_ref[2][:, 0:1]
        pad = jnp.zeros((s.shape[0], N_STATS - 10), jnp.float32)
        stats_ref[...] = jnp.concatenate(
            [lse_s, sy, lse_st, lse_tt, et_tt, et_ts, lse_bt, eb_bb, eb_bs,
             jnp.zeros_like(lse_s), pad], axis=-1)


def _fwd_kernel(labels_ref, s_ref, t_ref, b_ref, stats_ref,
                acc_ref, *, tau, vocab_tile, with_buffer):
    s = s_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32) if with_buffer else None
    _fwd_body(labels_ref, s, t, b, stats_ref, acc_ref, tau=tau,
              vocab_tile=vocab_tile, with_buffer=with_buffer)


def _dequant_tile(codes_ref, scale_ref, zero_ref, v_idx, vocab_tile, vocab):
    """Reconstruct a teacher tile from int8 codes + per-row (scale, zero),
    masking padded vocab columns to NEG (codes can't encode the sentinel)."""
    t = codes_ref[...].astype(jnp.float32) * scale_ref[...] + zero_ref[...]
    cols = v_idx * vocab_tile + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.where(cols < vocab, t, NEG)


def _quant_fwd_kernel(labels_ref, s_ref, codes_ref, scale_ref, zero_ref,
                      b_ref, stats_ref, acc_ref, *, tau, vocab_tile,
                      with_buffer, vocab):
    v_idx = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32)
    t = _dequant_tile(codes_ref, scale_ref, zero_ref, v_idx, vocab_tile,
                      vocab)
    b = b_ref[...].astype(jnp.float32) if with_buffer else None
    _fwd_body(labels_ref, s, t, b, stats_ref, acc_ref, tau=tau,
              vocab_tile=vocab_tile, with_buffer=with_buffer)


def _bwd_body(labels_ref, g_ref, stats_ref, s, t, b, ds_ref, *, tau,
              vocab_tile, with_buffer, mean_scale):
    """Shared backward tile math (see module docstring for the ds formula)."""
    v_idx = pl.program_id(1)
    stats = stats_ref[...]
    lse_s = stats[:, 0:1]
    lse_st = stats[:, 2:3]
    lse_tt = stats[:, 3:4]
    g = g_ref[...] * mean_scale                             # (rows, 1)

    p_s = jnp.exp(s - lse_s)
    p_st = jnp.exp(s / tau - lse_st)
    p_tt = jnp.exp(t / tau - lse_tt)
    cols = v_idx * vocab_tile + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (cols == labels_ref[...]).astype(jnp.float32)

    ds = p_s - onehot + tau * (p_st - p_tt)
    if with_buffer:
        lse_bt = stats[:, 6:7]
        p_bt = jnp.exp(b / tau - lse_bt)
        ds = ds + tau * (p_st - p_bt)
    ds_ref[...] = (g * ds).astype(ds_ref.dtype)


def _bwd_kernel(labels_ref, g_ref, stats_ref, s_ref, t_ref, b_ref, ds_ref,
                *, tau, vocab_tile, with_buffer, mean_scale):
    s = s_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32) if with_buffer else None
    _bwd_body(labels_ref, g_ref, stats_ref, s, t, b, ds_ref, tau=tau,
              vocab_tile=vocab_tile, with_buffer=with_buffer,
              mean_scale=mean_scale)


def _quant_bwd_kernel(labels_ref, g_ref, stats_ref, s_ref, codes_ref,
                      scale_ref, zero_ref, b_ref, ds_ref, *, tau, vocab_tile,
                      with_buffer, mean_scale, vocab):
    v_idx = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32)
    t = _dequant_tile(codes_ref, scale_ref, zero_ref, v_idx, vocab_tile,
                      vocab)
    b = b_ref[...].astype(jnp.float32) if with_buffer else None
    _bwd_body(labels_ref, g_ref, stats_ref, s, t, b, ds_ref, tau=tau,
              vocab_tile=vocab_tile, with_buffer=with_buffer,
              mean_scale=mean_scale)


# Largest f32 tile (rows_block * vocab_tile elements) a grid step holds:
# 256 KiB, so the double-buffered inputs plus the kernel's tile-sized f32
# temporaries stay well inside the default scoped VMEM.
_TILE_ELEMS = 1 << 16


def _row_block(rows, vt):
    """Rows per block: a multiple of 32 (int8 codes tile at (32, 128)) or
    of 8 that divides ``rows`` and keeps the tile within ``_TILE_ELEMS``;
    the whole row extent (always a legal block) when none divides."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if cand * vt <= _TILE_ELEMS and rows % cand == 0:
            return cand
    return rows


def _col(a, dtype):
    """A per-row vector as the (rows, 1) column the kernels block over."""
    return a.astype(dtype).reshape(-1, 1)


def _vocab_tile(v):
    for cand in (2048, 1024, 512, 256, 128):
        if v % cand == 0:
            return cand
    raise ValueError(f"vocab {v} must be a multiple of 128")


def kd_stats_fwd(labels, s, t, b, tau, *, interpret=False):
    """Returns stats (rows, N_STATS).  b may be None (plain KD)."""
    rows, v = s.shape
    vt = _vocab_tile(v)
    rb = _row_block(rows, vt)
    with_buffer = b is not None
    if b is None:
        b = s  # dummy operand (ignored by the kernel)
    grid = (rows // rb, v // vt)
    kernel = functools.partial(_fwd_kernel, tau=float(tau), vocab_tile=vt,
                               with_buffer=with_buffer)
    return pl.pallas_call(
        kernel,
        name="kd_loss_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rb, N_STATS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, N_STATS), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4, rb, 8), jnp.float32)],
        interpret=interpret,
    )(_col(labels, jnp.int32), s, t, b)


def kd_grad_bwd(labels, g, stats, s, t, b, tau, mean_scale, *, interpret=False):
    rows, v = s.shape
    vt = _vocab_tile(v)
    rb = _row_block(rows, vt)
    with_buffer = b is not None
    if b is None:
        b = s
    grid = (rows // rb, v // vt)
    kernel = functools.partial(_bwd_kernel, tau=float(tau), vocab_tile=vt,
                               with_buffer=with_buffer, mean_scale=float(mean_scale))
    return pl.pallas_call(
        kernel,
        name="kd_loss_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, N_STATS), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, v), s.dtype),
        interpret=interpret,
    )(_col(labels, jnp.int32), _col(g, jnp.float32), stats, s, t, b)


def kd_quant_stats_fwd(labels, s, codes, scale, zero, b, tau, vocab, *,
                       interpret=False):
    """Forward stats with the teacher dequantized in-tile from int8 codes +
    per-row (scale, zero).  ``vocab`` is the true (pre-padding) vocab size;
    padded code columns are masked to NEG by column index.  b may be None."""
    rows, v = s.shape
    vt = _vocab_tile(v)
    rb = _row_block(rows, vt)
    with_buffer = b is not None
    if b is None:
        b = s  # dummy operand (ignored by the kernel)
    grid = (rows // rb, v // vt)
    kernel = functools.partial(_quant_fwd_kernel, tau=float(tau),
                               vocab_tile=vt, with_buffer=with_buffer,
                               vocab=int(vocab))
    return pl.pallas_call(
        kernel,
        name="kd_loss_quant_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rb, N_STATS), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, N_STATS), jnp.float32),
        scratch_shapes=[pltpu.VMEM((4, rb, 8), jnp.float32)],
        interpret=interpret,
    )(_col(labels, jnp.int32), s, codes, _col(scale, jnp.float32),
      _col(zero, jnp.float32), b)


def kd_quant_grad_bwd(labels, g, stats, s, codes, scale, zero, b, tau, vocab,
                      mean_scale, *, interpret=False):
    rows, v = s.shape
    vt = _vocab_tile(v)
    rb = _row_block(rows, vt)
    with_buffer = b is not None
    if b is None:
        b = s
    grid = (rows // rb, v // vt)
    kernel = functools.partial(_quant_bwd_kernel, tau=float(tau),
                               vocab_tile=vt, with_buffer=with_buffer,
                               mean_scale=float(mean_scale),
                               vocab=int(vocab))
    return pl.pallas_call(
        kernel,
        name="kd_loss_quant_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, N_STATS), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rb, vt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, v), s.dtype),
        interpret=interpret,
    )(_col(labels, jnp.int32), _col(g, jnp.float32), stats, s, codes,
      _col(scale, jnp.float32), _col(zero, jnp.float32), b)


def assemble_loss(stats, tau, with_buffer):
    """Per-row loss from kernel stats.

    CE = lse_s - s_y
    tau^2 KL(t||s) = tau^2 * (E_t[t/tau] - lse_tt - E_t[s/tau] + lse_st)
    """
    lse_s, sy = stats[:, 0], stats[:, 1]
    lse_st, lse_tt = stats[:, 2], stats[:, 3]
    et_tt, et_ts = stats[:, 4], stats[:, 5]
    ce = lse_s - sy
    kl_t = (tau ** 2) * (et_tt - lse_tt - et_ts + lse_st)
    loss = ce + kl_t
    if with_buffer:
        lse_bt, eb_bb, eb_bs = stats[:, 6], stats[:, 7], stats[:, 8]
        loss = loss + (tau ** 2) * (eb_bb - lse_bt - eb_bs + lse_st)
    return loss
