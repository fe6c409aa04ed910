"""Pure-jnp oracles for every Pallas kernel (the `assert_allclose` targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kd_loss_ref(labels, s, t, b, tau):
    """Per-row buffered-KD loss: CE + tau^2 KL(t||s) [+ tau^2 KL(b||s)].
    s, t, b: (rows, V) logits (b may be None)."""
    s = s.astype(jnp.float32)
    t = t.astype(jnp.float32)
    lse_s = jax.scipy.special.logsumexp(s, axis=-1)
    ce = lse_s - jnp.take_along_axis(s, labels[:, None], axis=-1)[:, 0]

    def kl(teacher):
        lt = jax.nn.log_softmax(teacher.astype(jnp.float32) / tau, axis=-1)
        ls = jax.nn.log_softmax(s / tau, axis=-1)
        return (tau ** 2) * jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1)

    loss = ce + kl(t)
    if b is not None:
        loss = loss + kl(b)
    return loss


def kd_loss_mean_ref(labels, s, t, b, tau):
    return jnp.mean(kd_loss_ref(labels, s, t, b, tau))


def rglru_ref(a, b):
    """h_t = a_t h_{t-1} + b_t (associative-scan reference)."""
    from repro.nn.rglru import rglru_scan_reference
    return rglru_scan_reference(a, b)


def ssd_ref(x, dt, A, B, C, chunk):
    """Chunked SSD reference (B, C per group)."""
    from repro.nn.ssm import ssd_reference
    return ssd_reference(x, dt, A, B, C, chunk)


def ssd_ref_heads(x, dt, A, Bh, Ch, chunk):
    """Variant taking B/C already broadcast to heads (kernel's calling
    convention): treat each head as its own group."""
    return ssd_reference(x, dt, A, Bh, Ch, chunk)


def paged_decode_ref(q, k_pool, v_pool, pt, pos, window=None):
    """Paged-cache decode attention oracle: gather every slot's pages into
    a dense (B, PP*ps, N, D) view and run the masked softmax.  q:
    (B, N, G, D); pools (P, ps, N*D); pt (B, PP) int32; pos (B,) int32.
    Positions past ``pos`` (including trash-page placeholders) are masked."""
    b, n, g, d = q.shape
    ps = k_pool.shape[1]
    pp = pt.shape[1]
    kc = k_pool[pt].reshape(b, pp * ps, n, d)
    vc = v_pool[pt].reshape(b, pp * ps, n, d)
    p_col = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
    j = jnp.arange(pp * ps)[None, :]
    valid = j <= p_col
    if window is not None:
        valid = valid & (j > p_col - window)
    s = jnp.einsum("bngd,bwnd->bngw", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / jnp.sqrt(float(d))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngw,bwnd->bngd", p,
                      vc.astype(jnp.float32)).astype(q.dtype)


def swa_decode_ref(q, k_cache, v_cache, pos, window=None, ring=False):
    """Decode attention over a (ring) cache.  q: (B, N, G, D); cache
    (B, W, N, D); pos: scalar int32 or per-sequence (B,) int32."""
    b, n, g, d = q.shape
    w = k_cache.shape[1]
    p_col = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]  # (B,1)
    j = jnp.arange(w)[None, :]                                            # (1,W)
    a = p_col - jnp.mod(p_col - j, w) if ring else jnp.broadcast_to(j, (b, w))
    valid = (a >= 0) & (a <= p_col)
    if window is not None:
        valid = valid & (a > p_col - window)
    s = jnp.einsum("bngd,bwnd->bngw", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / jnp.sqrt(float(d))
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngw,bwnd->bngd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)
