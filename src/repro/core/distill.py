"""Distillation losses — Eqs. (1)–(4) of the paper, plus variants.

Notation (paper §3.1):
    F(x)     core/student softmax;  f_k(x) k-th edge/teacher softmax
    A_f(x)   ensemble average of the R returned teachers' probabilities
    L_core   = sum CE(F(x), y)                                   (Eq. 1)
    L_KD     = L_core + tau^2 * sum KL(F || A_f / tau)           (Eq. 3)
    L_BKD    = L_KD   + tau^2 * sum KL(F || F0 / tau)            (Eq. 4)
where F0 is the student cloned & frozen at the start of Phase 2 — the
"buffer".  KL terms follow Hinton et al.: softened distributions at
temperature tau, scaled by tau^2 so gradients match the CE scale.

All losses take *logits* and are mean-reduced over examples.  `vocab`
masks out padded vocabulary entries.  For LLM-scale vocabularies the
sequence is processed in chunks (bounded live memory); on TPU the fused
Pallas kernel (repro/kernels/kd_loss.py) implements the same math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask_pad(logits, vocab):
    if vocab is not None and vocab != logits.shape[-1]:
        valid = jnp.arange(logits.shape[-1]) < vocab
        logits = jnp.where(valid, logits, NEG_INF)
    return logits


def tail_logsumexp(logits, top_idx):
    """logsumexp of ``logits`` over the entries ``top_idx`` does not name.

    Taken over those entries directly: ``log(1 - exp(top - full))`` cancels
    to zero in float32 once the top entries hold nearly all the mass."""
    in_top = jnp.put_along_axis(jnp.zeros(logits.shape, jnp.bool_), top_idx,
                                True, axis=-1, inplace=False)
    return jax.scipy.special.logsumexp(jnp.where(in_top, NEG_INF, logits),
                                       axis=-1)


def ce_loss(logits, labels, *, vocab=None, mask=None):
    """Cross entropy, mean over (optionally masked) examples.
    logits: (..., V); labels: (...) int."""
    logits = _mask_pad(logits.astype(jnp.float32), vocab)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def kl_soft(student_logits, teacher_logits, tau, *, vocab=None, mask=None):
    """tau^2 * KL( softmax(t/tau) || softmax(s/tau) ), mean over examples."""
    s = _mask_pad(student_logits.astype(jnp.float32), vocab) / tau
    t = _mask_pad(teacher_logits.astype(jnp.float32), vocab) / tau
    ls = jax.nn.log_softmax(s, axis=-1)
    lt = jax.nn.log_softmax(t, axis=-1)
    pt = jnp.exp(lt)
    kl = jnp.sum(pt * (lt - ls), axis=-1) * (tau ** 2)
    if mask is not None:
        return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(kl)


def kl_soft_vs_probs(student_logits, teacher_probs, tau, *, vocab=None, mask=None):
    """KL against an ensemble probability vector A_f (already temperature-soft).
    teacher_probs must be a valid distribution over the (unpadded) vocab."""
    s = _mask_pad(student_logits.astype(jnp.float32), vocab) / tau
    ls = jax.nn.log_softmax(s, axis=-1)
    pt = teacher_probs.astype(jnp.float32)
    lt = jnp.log(jnp.maximum(pt, 1e-30))
    kl = jnp.sum(pt * (lt - ls), axis=-1) * (tau ** 2)
    if mask is not None:
        return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(kl)


def ensemble_probs(teacher_logits, tau, *, vocab=None):
    """A_f: mean of temperature-softened teacher probabilities.

    Accepts either a list of R ``(..., V)`` logit tensors or one stacked
    ``(R, ..., V)`` tensor (the vectorized engine's layout: one vmapped
    teacher forward instead of R Python-level forwards)."""
    if isinstance(teacher_logits, (list, tuple)):
        ps = [jax.nn.softmax(_mask_pad(t.astype(jnp.float32), vocab) / tau,
                             axis=-1)
              for t in teacher_logits]
        return sum(ps) / len(ps)
    p = jax.nn.softmax(
        _mask_pad(teacher_logits.astype(jnp.float32), vocab) / tau, axis=-1)
    return jnp.mean(p, axis=0)


def _num_teachers(teacher_logits):
    return (len(teacher_logits) if isinstance(teacher_logits, (list, tuple))
            else teacher_logits.shape[0])


def l_kd(student_logits, teacher_logits_list, labels, tau, *, vocab=None, mask=None):
    """Eq. 3.  teacher_logits_list: R teachers (R=1: single-edge
    distillation), as a list or a stacked ``(R, ..., V)`` tensor."""
    ce = ce_loss(student_logits, labels, vocab=vocab, mask=mask)
    if _num_teachers(teacher_logits_list) == 1:
        kd = kl_soft(student_logits, teacher_logits_list[0], tau, vocab=vocab, mask=mask)
    else:
        af = ensemble_probs(teacher_logits_list, tau, vocab=vocab)
        kd = kl_soft_vs_probs(student_logits, af, tau, vocab=vocab, mask=mask)
    return ce + kd


def l_bkd(student_logits, teacher_logits_list, buffer_logits, labels, tau,
          *, vocab=None, mask=None):
    """Eq. 4 — buffered KD: Eq. 3 plus the frozen-clone KL term."""
    kd = l_kd(student_logits, teacher_logits_list, labels, tau, vocab=vocab, mask=mask)
    buf = kl_soft(student_logits, buffer_logits, tau, vocab=vocab, mask=mask)
    return kd + buf


# ---------------------------------------------------------------------------
# Chunked LLM-scale variants (token-level, big vocab).
# ---------------------------------------------------------------------------

def chunked_token_bkd(student_logits_fn, teacher_logits_fn, buffer_logits_fn,
                      hidden_chunks, labels_chunks, tau, vocab, kd_weight=1.0,
                      buffer_weight=1.0):
    """Streaming form: callers pass per-chunk logit functions so the three
    (tokens, V) logit tensors never coexist for the full sequence."""
    total, count = 0.0, 0
    for h, y in zip(hidden_chunks, labels_chunks):
        s = student_logits_fn(h)
        t = teacher_logits_fn(h)
        loss = ce_loss(s, y, vocab=vocab)
        loss = loss + kd_weight * kl_soft(s, t, tau, vocab=vocab)
        if buffer_logits_fn is not None:
            b = buffer_logits_fn(h)
            loss = loss + buffer_weight * kl_soft(s, b, tau, vocab=vocab)
        total = total + loss
        count += 1
    return total / count


def topk_kl(student_logits, teacher_logits, tau, k, *, vocab=None, mask=None):
    """Beyond-paper: KL restricted to the teacher's top-k entries plus a
    renormalised tail bucket.  Exact in the limit k -> V; cuts loss-side
    memory traffic by ~V/k for big-vocab distillation."""
    s = _mask_pad(student_logits.astype(jnp.float32), vocab) / tau
    t = _mask_pad(teacher_logits.astype(jnp.float32), vocab) / tau
    lt = jax.nn.log_softmax(t, axis=-1)
    ls = jax.nn.log_softmax(s, axis=-1)
    top_lt, idx = jax.lax.top_k(lt, k)
    top_ls = jnp.take_along_axis(ls, idx, axis=-1)
    pt_top = jnp.exp(top_lt)
    head = jnp.sum(pt_top * (top_lt - top_ls), axis=-1)
    # Tail bucket: remaining teacher mass vs remaining student mass.
    mt = jnp.maximum(1.0 - pt_top.sum(-1), 1e-9)
    ms = jnp.maximum(1.0 - jnp.exp(top_ls).sum(-1), 1e-9)
    tail = mt * (jnp.log(mt) - jnp.log(ms))
    kl = (head + tail) * (tau ** 2)
    if mask is not None:
        return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(kl)


def topk_kl_cached(student_logits, top_vals, top_idx, tail_lse, tau,
                   *, vocab=None, mask=None):
    """KL(buffer || student) from a *compressed* cached buffer: the buffer's
    top-k logits + logsumexp of its tail (see repro/core/buffer.py).  The
    teacher-side distribution is exact on the top-k and lumps the tail into
    one bucket — identical in the limit k -> V.

    top_vals/top_idx: (..., k) raw buffer logits (temperature applied here);
    tail_lse: (...,) logsumexp of the buffer's non-top logits.
    """
    s = _mask_pad(student_logits.astype(jnp.float32), vocab) / tau
    ls = jax.nn.log_softmax(s, axis=-1)
    tv = top_vals.astype(jnp.float32) / tau
    tl = tail_lse.astype(jnp.float32) / tau  # lse scales ~1/tau approximately
    # Buffer log-normalizer over {top-k, tail bucket} at temperature tau.
    z = jnp.logaddexp(jax.scipy.special.logsumexp(tv, axis=-1), tl)
    lp_top = tv - z[..., None]
    lp_tail = tl - z
    ls_top = jnp.take_along_axis(ls, top_idx, axis=-1)
    ms_tail = tail_logsumexp(ls, top_idx)      # log of the student tail mass
    kl = (jnp.sum(jnp.exp(lp_top) * (lp_top - ls_top), axis=-1)
          + jnp.exp(lp_tail) * (lp_tail - ms_tail)) * (tau ** 2)
    if mask is not None:
        return jnp.sum(kl * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(kl)


# ---------------------------------------------------------------------------
# EMA baseline (paper Fig. 4a) and Factor Transfer (FT+KD baseline).
# ---------------------------------------------------------------------------

def ema_update(ema_params, new_params, decay):
    return jax.tree.map(lambda e, p: decay * e + (1.0 - decay) * p,
                        ema_params, new_params)


def factor_loss(student_feat, teacher_feat, translator_w):
    """Simplified Factor Transfer (Kim et al. 2018): a linear translator maps
    student features into the teacher's factor space; loss is the L2 between
    L2-normalised factors.  (The full paraphraser autoencoder is replaced by
    an identity paraphraser — noted in DESIGN.md.)"""
    fs = student_feat.reshape(student_feat.shape[0], -1) @ translator_w
    ft = teacher_feat.reshape(teacher_feat.shape[0], -1)

    def norm(v):
        # generous eps: ReLU features can be exactly zero for some inputs,
        # and 1/||v|| gradients explode through near-zero norms
        return v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-3)

    return jnp.mean(jnp.sum((norm(fs) - norm(ft)) ** 2, axis=-1))
