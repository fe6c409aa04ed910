"""Jit'd public wrappers around the Pallas kernels.

`use_pallas` selects the kernel path; the default is chosen by backend
(kernels on TPU, jnp reference on CPU so the multi-pod dry-run lowers with
stock XLA ops).  `interpret=True` runs the kernel bodies in Python on CPU —
that is how the test-suite validates them against ref.py.  Left at None,
`interpret` follows the backend too: compiled kernels on TPU, never the
interpreter there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.kernels import kd_loss as _kd
from repro.kernels import ref as _ref
from repro.kernels.rglru import rglru_pallas
from repro.kernels.ssd import ssd_pallas
from repro.sharding.rules import get_abstract_mesh_or_none, logical_to_spec


def default_use_pallas():
    return jax.default_backend() == "tpu"


def _interpret(interpret):
    """Interpret mode (kernel bodies run in Python) everywhere but the TPU,
    unless the caller chose."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _over_rows(fn, *args):
    """``fn(*args)``, a mean over rows, for row-major ``args``.

    Mosaic kernels cannot be partitioned automatically, so under a mesh
    the call runs in a ``shard_map``: the rows split over every mesh axis
    that divides them, each device runs the kernel on whole rows (the full
    vocab) of its shard, and the equal-sized shard means are averaged."""
    mesh = get_abstract_mesh_or_none()
    axes, n = (), 1
    if mesh is not None:
        rows = args[0].shape[0]
        for name, size, kind in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types):
            if kind == AxisType.Auto and size > 1 and rows % (n * size) == 0:
                axes, n = axes + (name,), n * size
    if not axes:
        return fn(*args)
    spec = P(axes)
    means = jax.shard_map(lambda *a: fn(*a)[None], mesh=mesh,
                          in_specs=(spec,) * len(args), out_specs=spec,
                          check_vma=False)(*args)
    return jnp.mean(means)


# ---------------------------------------------------------------------------
# Fused buffered-KD loss with custom VJP.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kd_loss_pallas(labels, s, t, b, tau, with_buffer, interpret):
    stats = _kd.kd_stats_fwd(labels, s, t, b if with_buffer else None, tau,
                             interpret=interpret)
    return jnp.mean(_kd.assemble_loss(stats, tau, with_buffer))


def _kd_fwd(labels, s, t, b, tau, with_buffer, interpret):
    stats = _kd.kd_stats_fwd(labels, s, t, b if with_buffer else None, tau,
                             interpret=interpret)
    loss = jnp.mean(_kd.assemble_loss(stats, tau, with_buffer))
    return loss, (labels, stats, s, t, b)


def _kd_bwd(tau, with_buffer, interpret, res, g):
    labels, stats, s, t, b = res
    rows = s.shape[0]
    gv = jnp.broadcast_to(g, (rows,)).astype(jnp.float32)
    ds = _kd.kd_grad_bwd(labels, gv, stats, s, t,
                         b if with_buffer else None, tau, 1.0 / rows,
                         interpret=interpret)
    # Teachers and buffer are frozen in Phase 2: zero cotangents.
    return (None, ds, jnp.zeros_like(t), jnp.zeros_like(b))


_kd_loss_pallas.defvjp(_kd_fwd, _kd_bwd)


def kd_loss(labels, student_logits, teacher_logits, buffer_logits=None, tau=2.0,
            *, use_pallas=None, interpret=None):
    """Mean buffered-KD loss over rows.  Differentiable w.r.t. student logits.
    Shapes: labels (R,), logits (R, V).  Vocabularies that are not a
    multiple of the kernel's 128-lane tile are padded with NEG_INF columns
    (exp underflows to zero, so loss and student gradient are unchanged)."""
    if use_pallas is None:
        use_pallas = default_use_pallas()
    if use_pallas:
        v = student_logits.shape[-1]
        pad = (-v) % 128
        if pad:
            def _pad(a):
                return jnp.pad(a, ((0, 0), (0, pad)), constant_values=-1e30)
            student_logits = _pad(student_logits)
            teacher_logits = _pad(teacher_logits)
            if buffer_logits is not None:
                buffer_logits = _pad(buffer_logits)
        b = buffer_logits if buffer_logits is not None else student_logits
        kernel = functools.partial(
            _kd_loss_pallas, tau=float(tau),
            with_buffer=buffer_logits is not None,
            interpret=_interpret(interpret))
        return _over_rows(kernel, labels, student_logits, teacher_logits, b)
    t = jax.lax.stop_gradient(teacher_logits)
    b = jax.lax.stop_gradient(buffer_logits) if buffer_logits is not None else None
    return _ref.kd_loss_mean_ref(labels, student_logits, t, b, tau)


# ---------------------------------------------------------------------------
# Dequant-fused buffered-KD loss: the teacher arrives as transport-codec
# payload (int8 codes + per-row affine) and is dequantized inside the kernel.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _kd_loss_quant_pallas(labels, s, codes, scale, zero, b, tau, with_buffer,
                          vocab, interpret):
    stats = _kd.kd_quant_stats_fwd(labels, s, codes, scale, zero,
                                   b if with_buffer else None, tau, vocab,
                                   interpret=interpret)
    return jnp.mean(_kd.assemble_loss(stats, tau, with_buffer))


def _kd_quant_fwd(labels, s, codes, scale, zero, b, tau, with_buffer, vocab,
                  interpret):
    stats = _kd.kd_quant_stats_fwd(labels, s, codes, scale, zero,
                                   b if with_buffer else None, tau, vocab,
                                   interpret=interpret)
    loss = jnp.mean(_kd.assemble_loss(stats, tau, with_buffer))
    return loss, (labels, stats, s, codes, scale, zero, b)


def _kd_quant_bwd(tau, with_buffer, vocab, interpret, res, g):
    labels, stats, s, codes, scale, zero, b = res
    rows = s.shape[0]
    gv = jnp.broadcast_to(g, (rows,)).astype(jnp.float32)
    ds = _kd.kd_quant_grad_bwd(labels, gv, stats, s, codes, scale, zero,
                               b if with_buffer else None, tau, vocab,
                               1.0 / rows, interpret=interpret)
    # Teacher payload and buffer are frozen: zero cotangents (None for the
    # integer operands, matching the labels convention above).
    return (None, ds, None, jnp.zeros_like(scale), jnp.zeros_like(zero),
            jnp.zeros_like(b))


_kd_loss_quant_pallas.defvjp(_kd_quant_fwd, _kd_quant_bwd)


def kd_loss_quant(labels, student_logits, codes, scale, zero,
                  buffer_logits=None, tau=2.0, *, use_pallas=None,
                  interpret=None):
    """Mean buffered-KD loss with the teacher given as per-row affine
    quantization payload: ``teacher = codes * scale[:, None] + zero[:, None]``
    (int8 codes — the int4 codec ships nibble-packed bytes and unpacks its
    [-8, 7] grid into this int8 container per batch before the call).
    Differentiable w.r.t. student logits only.

    On the pallas path the dequant runs inside the fused kernel, tile by
    tile in VMEM — no f32 (rows, V) teacher tensor is ever materialized.
    Student/buffer are padded to the 128-lane tile with NEG columns as in
    :func:`kd_loss`; codes are padded with 0 and the kernel masks padded
    columns by index against the true vocab instead (a pad code would
    otherwise dequantize to the row's mid-range, not to -inf)."""
    if use_pallas is None:
        use_pallas = default_use_pallas()
    v = student_logits.shape[-1]
    if use_pallas:
        pad = (-v) % 128
        if pad:
            def _pad(a, value):
                return jnp.pad(a, ((0, 0), (0, pad)), constant_values=value)
            student_logits = _pad(student_logits, -1e30)
            codes = _pad(codes, 0)
            if buffer_logits is not None:
                buffer_logits = _pad(buffer_logits, -1e30)
        b = buffer_logits if buffer_logits is not None else student_logits
        kernel = functools.partial(
            _kd_loss_quant_pallas, tau=float(tau),
            with_buffer=buffer_logits is not None, vocab=v,
            interpret=_interpret(interpret))
        return _over_rows(kernel, labels, student_logits, codes, scale, zero,
                          b)
    t = jax.lax.stop_gradient(codes.astype(jnp.float32) * scale[:, None]
                              + zero[:, None])
    b = (jax.lax.stop_gradient(buffer_logits)
         if buffer_logits is not None else None)
    return _ref.kd_loss_mean_ref(labels, student_logits, t, b, tau)


# ---------------------------------------------------------------------------
# RG-LRU scan.
# ---------------------------------------------------------------------------

def rglru(a, b, *, use_pallas=None, interpret=None):
    if use_pallas is None:
        use_pallas = default_use_pallas()
    if use_pallas:
        return rglru_pallas(a, b, interpret=_interpret(interpret))
    return _ref.rglru_ref(a, b)


# ---------------------------------------------------------------------------
# SSD chunk scan (B/C broadcast to heads before the kernel).
# ---------------------------------------------------------------------------

def ssd(x, dt, A, B, C, chunk, *, use_pallas=None, interpret=None):
    if use_pallas is None:
        use_pallas = default_use_pallas()
    h = x.shape[2]
    g = B.shape[2]
    if use_pallas:
        Bh = jnp.repeat(B, h // g, axis=2)
        Ch = jnp.repeat(C, h // g, axis=2)
        return ssd_pallas(x.astype(jnp.float32), dt, A, Bh.astype(jnp.float32),
                          Ch.astype(jnp.float32), chunk,
                          interpret=_interpret(interpret))
    return _ref.ssd_ref(x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
                        C.astype(jnp.float32), chunk)


# ---------------------------------------------------------------------------
# Sliding-window decode attention (long-context serving hot spot).
# ---------------------------------------------------------------------------

def swa_decode_attn(q, k_cache, v_cache, pos, *, window=None, ring=False,
                    use_pallas=None, interpret=None):
    if use_pallas is None:
        use_pallas = default_use_pallas()
    if use_pallas:
        from repro.kernels.swa_decode import swa_decode
        return swa_decode(q, k_cache, v_cache, pos, window=window, ring=ring,
                          interpret=_interpret(interpret))
    return _ref.swa_decode_ref(q, k_cache, v_cache, pos, window=window, ring=ring)


def paged_decode_attn(q, k_pool, v_pool, pt, pos, *, layer=None, window=None,
                      use_pallas=None, interpret=None):
    """Block-paged decode attention (the paged ServeEngine's tick hot spot).
    q: (B, N, G, D); k/v_pool: (P, page_size, N*D), or (L, P, page_size,
    N*D) stacked over layers with ``layer`` picking one; pt: (B, PP) int32
    page table; pos: (B,) int32.  The pallas path gathers pages inside the
    kernel's index maps (scalar-prefetched page table and layer), reading
    the pool in place; the reference path materializes the dense per-slot
    view.

    Under a mesh the kernel runs in a ``shard_map``: Mosaic kernels are not
    auto-partitioned, and XLA would otherwise gather the whole pool onto
    every device.  Slots split over the axes that carry the batch, kv heads
    over those that carry them, as the model's sharding rules lay out q;
    every device keeps all pages of its heads."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    if use_pallas is None:
        use_pallas = default_use_pallas()
    if not use_pallas:
        return _ref.paged_decode_ref(q, k_pool[layer], v_pool[layer], pt, pos,
                                     window=window)
    from repro.kernels.swa_decode import paged_decode

    def kernel(q_, k_, v_, pt_, pos_, layer_):
        return paged_decode(q_, k_, v_, pt_, pos_, layer=layer_, window=window,
                            interpret=_interpret(interpret))

    args = (q, k_pool, v_pool, pt, pos, jnp.asarray(layer, jnp.int32))
    mesh = get_abstract_mesh_or_none()
    if mesh is None or AxisType.Auto not in mesh.axis_types:
        return kernel(*args)
    qs = logical_to_spec(("batch", "kv_heads", None, None), q.shape, mesh)
    pool = P(None, None, None, qs[1])
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(qs, pool, pool, P(qs[0], None), P(qs[0]),
                                   P()),
                         out_specs=qs, check_vma=False)(*args)
