"""The plain reference: the dense decoder, its buffered-KD loss and AdamW,
written out in float32 ``jax.numpy`` from the equations, with no kernel,
cache, padding trick or batching of the program under test.

It imports nothing of the program and reads only what ``bench/weights.py``
makes from the seed.  Every matrix product runs at ``highest`` precision,
so on a TPU it is a true float32 product.  ``fp8=True`` is the control: each
operand of every product is rounded to float8 (e4m3, one scale per tensor)
first, the nearest precision below the bfloat16 the configurations state.

Equations (per layer, x the residual stream):
    h = rmsnorm(x) * ln1;  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
    q, k = rope(q), rope(k)            (rotate-half, theta from the config)
    a = softmax(q k^T / sqrt(head_dim) + causal mask) v, kv head = head // G
    x = x + a Wo
    h = rmsnorm(x) * ln2;  x = x + (silu(h Wgate) * (h Wup)) Wdown
logits = (rmsnorm(x) * ln_f) Wunembed, over the true vocabulary.
Buffered-KD loss per token: CE(s, y) + tau^2 KL(p_t || p_s) + tau^2
KL(p_b || p_s), with p = softmax(logits / tau), averaged over all tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import Dims

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor.  The
    gradient passes straight through the rounding (as fp8 training does),
    so the backward products run on the rounded operands too."""
    amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    scale = F8_MAX / amax
    q = (x * scale).astype(F8).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, fp8):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (T, heads, hd); rotate-half RoPE at integer positions ``pos``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dm: Dims, fp8, x, lw):
    """One decoder layer over one sequence x (T, D), float32."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rmsnorm(x, lw["ln1"], dm.rms_eps)
    q = _mm("td,dhk->thk", h, lw["wq"], fp8)
    k = _mm("td,dnk->tnk", h, lw["wk"], fp8)
    v = _mm("td,dnk->tnk", h, lw["wv"], fp8)
    if dm.qkv_bias:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q = _rope(q, pos, dm.rope_theta)
    k = _rope(k, pos, dm.rope_theta)
    kh = jnp.repeat(k, dm.groups, axis=1)           # head h reads kv h // G
    vh = jnp.repeat(v, dm.groups, axis=1)
    s = _mm("qhk,thk->hqt", q, kh, fp8) / jnp.sqrt(jnp.float32(dm.head_dim))
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = _mm("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), vh, fp8)
    x = x + _mm("thk,hkd->td", a, lw["wo"], fp8)
    h = _rmsnorm(x, lw["ln2"], dm.rms_eps)
    g = _mm("td,df->tf", h, lw["w_gate"], fp8)
    u = _mm("td,df->tf", h, lw["w_up"], fp8)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"], fp8)


LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
              "w_down", "bq", "bk", "bv")


def hidden(dm: Dims, w, tokens, fp8=False, remat=False):
    """Final hidden states (T, D) of one sequence, before ln_f."""
    x = w["embed"][tokens].astype(jnp.float32)
    stacked = {k: w[k] for k in LAYER_KEYS if k in w}
    body = functools.partial(_layer, dm, fp8)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, lw: (body(c, lw), None), x, stacked)
    return x


def logits_from_hidden(dm: Dims, w, h, fp8=False):
    """(T, vocab) float32 logits over the true vocabulary."""
    h = _rmsnorm(h, w["ln_f"], dm.rms_eps)
    return _mm("td,dv->tv", h, w["unembed"][:, :dm.vocab], fp8)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(dm, w, tokens, fp8):
    return logits_from_hidden(dm, w, hidden(dm, w, tokens, fp8), fp8)


def logits(dm: Dims, w, tokens, fp8=False):
    """(T, vocab) logits of one sequence ``tokens`` (T,) int32."""
    return _logits(dm, w, tokens, fp8)


# ---------------------------------------------------------------------------
# Buffered-KD loss and AdamW (the Phase-2 step)
# ---------------------------------------------------------------------------

def kd_loss_tokens(s, t, b, y, tau):
    """Per-token CE(s, y) + tau^2 KL(t||s) + tau^2 KL(b||s); logits (T, V)."""
    ce = jax.nn.logsumexp(s, -1) - jnp.take_along_axis(s, y[:, None], -1)[:, 0]
    ls = jax.nn.log_softmax(s / tau, -1)

    def kl(u):
        lu = jax.nn.log_softmax(u / tau, -1)
        return tau ** 2 * jnp.sum(jnp.exp(lu) * (lu - ls), -1)

    return ce + kl(t) + kl(b)


def _row_loss(dm, hp, fp8, student, teacher, buffer, tokens, labels):
    """Mean KD loss of one sequence, the logits taken in chunks of rows."""
    hs = hidden(dm, student, tokens, fp8, remat=True)
    ht = jax.lax.stop_gradient(hidden(dm, teacher, tokens, fp8))
    hb = jax.lax.stop_gradient(hidden(dm, buffer, tokens, fp8))
    n = tokens.shape[0] // hp["loss_rows"]
    split = lambda a: a.reshape(n, hp["loss_rows"], *a.shape[1:])

    @jax.checkpoint
    def chunk(args):
        a, b_, c, y = args
        return jnp.sum(kd_loss_tokens(
            logits_from_hidden(dm, student, a, fp8),
            jax.lax.stop_gradient(logits_from_hidden(dm, teacher, b_, fp8)),
            jax.lax.stop_gradient(logits_from_hidden(dm, buffer, c, fp8)),
            y, hp["tau"]))

    sums = jax.lax.map(chunk, (split(hs), split(ht), split(hb), split(labels)))
    return jnp.sum(sums) / tokens.shape[0]


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(3, 4, 5))
def _step(dm, hp_items, fp8, student, m, v, teacher, buffer, tokens, labels,
          step):
    hp = dict(hp_items)
    grad_fn = jax.value_and_grad(functools.partial(_row_loss, dm, hp, fp8))
    zeros = jax.tree.map(jnp.zeros_like, student)

    def row(carry, xs):
        loss, g = carry
        l_, g_ = grad_fn(student, teacher, buffer, *xs)
        return (loss + l_, jax.tree.map(jnp.add, g, g_)), None

    rows = tokens.shape[0]
    (loss, g), _ = jax.lax.scan(row, (jnp.float32(0), zeros), (tokens, labels))
    loss = loss / rows
    g = jax.tree.map(lambda a: a / rows, g)
    b1, b2, eps, wd, lr = hp["b1"], hp["b2"], hp["eps"], hp["wd"], hp["lr"]
    t = step.astype(jnp.float32) + 1.0
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    # Round to the stored type with reduce_precision: XLA may drop a
    # float32 -> bfloat16 -> float32 convert pair as excess precision.
    fi = jnp.finfo(hp["param_dtype"])
    student = jax.tree.map(
        lambda p, m_, v_: jax.lax.reduce_precision(
            p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p),
            exponent_bits=fi.nexp, mantissa_bits=fi.nmant), student, m, v)
    gnorm = {k: jnp.sqrt(jnp.sum(jnp.square(a))) for k, a in g.items()}
    return student, m, v, loss, gnorm


def train(dm: Dims, hp, student, teacher, buffer, batches, fp8=False,
          rows=None):
    """``len(batches)`` reference steps from ``student`` (computed in
    float32; after each update the parameters are stored in the type the
    configuration states, ``hp["param_dtype"]``, as a bf16 model keeps
    them).  ``batches``: list of (tokens, labels), each (B, S) int32;
    ``rows`` keeps only that many leading rows of each batch (a planted
    fault: half the batch left out).  Returns (losses, first gradient,
    final float32 student)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), student)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    hp_items = tuple(sorted(hp.items()))
    losses, first = [], None
    for i, (tok, lab) in enumerate(batches):
        if rows is not None:
            tok, lab = tok[:rows], lab[:rows]
        p, m, v, loss, gnorm = _step(dm, hp_items, fp8, p, m, v, teacher,
                                     buffer, tok, lab, jnp.int32(i))
        losses.append(float(loss))
        if first is None:
            first = {k: float(a) for k, a in gnorm.items()}
    return losses, first, p
