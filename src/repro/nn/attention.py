"""GQA attention with RoPE / M-RoPE, qk-norm, sliding windows, KV caches.

Memory notes (TPU target): full-sequence attention is computed in query
chunks (``lax.map`` over blocks) so peak live memory is
(B, H, q_chunk, S) rather than (B, H, S, S) — the jnp analogue of flash
attention's outer loop; exact, not approximate.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn.rope import apply_mrope, apply_rope
from repro.sharding.rules import constrain

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    causal: bool = True
    window: Optional[int] = None          # sliding window (None = full)
    qk_norm: bool = False                 # qwen3-style per-head RMS norm
    qkv_bias: bool = False                # qwen1.5-style bias
    rope: str = "rope"                    # "rope" | "mrope" | "none"
    rope_theta: float = 10000.0
    mrope_sections: tuple = (16, 24, 24)
    q_chunk: int = 512
    ring_cache: bool = False              # windowed decode: cache only the
                                          # last `window` K/V in a ring buffer

    @property
    def q_groups(self):
        assert self.num_heads % self.kv_heads == 0
        return self.num_heads // self.kv_heads


def init(key, cfg: AttnConfig, *, stack=None, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    sh = (lambda *s: s) if stack is None else (lambda *s: (stack, *s))
    ax = (lambda *a: a) if stack is None else (lambda *a: ("layers", *a))
    std = 1.0 / math.sqrt(cfg.d_model)
    p = {
        "wq": L._trunc_normal(ks[0], sh(cfg.d_model, cfg.num_heads, cfg.head_dim), std, dtype),
        "wk": L._trunc_normal(ks[1], sh(cfg.d_model, cfg.kv_heads, cfg.head_dim), std, dtype),
        "wv": L._trunc_normal(ks[2], sh(cfg.d_model, cfg.kv_heads, cfg.head_dim), std, dtype),
        "wo": L._trunc_normal(ks[3], sh(cfg.num_heads, cfg.head_dim, cfg.d_model), std, dtype),
    }
    s = {
        "wq": ax("embed", "heads", "head_dim"),
        "wk": ax("embed", "kv_heads", "head_dim"),
        "wv": ax("embed", "kv_heads", "head_dim"),
        "wo": ax("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros(sh(cfg.num_heads, cfg.head_dim), dtype)
        p["bk"] = jnp.zeros(sh(cfg.kv_heads, cfg.head_dim), dtype)
        p["bv"] = jnp.zeros(sh(cfg.kv_heads, cfg.head_dim), dtype)
        s["bq"] = ax("heads", "head_dim")
        s["bk"] = ax("kv_heads", "head_dim")
        s["bv"] = ax("kv_heads", "head_dim")
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(sh(cfg.head_dim), dtype)
        p["k_norm"] = jnp.ones(sh(cfg.head_dim), dtype)
        s["q_norm"] = ax("head_dim")
        s["k_norm"] = ax("head_dim")
    return p, s


def _headwise_rms(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def _project_qkv(params, cfg: AttnConfig, x, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dnk->bsnk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnk->bsnk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _headwise_rms(q, params["q_norm"])
        k = _headwise_rms(k, params["k_norm"])
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _attend_chunk(q, k, v, q_pos, k_pos, cfg: AttnConfig):
    """q: (B, Q, N, G, D); k/v: (B, T, N, D); positions 1-D per side."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqngd,btnd->bngqt", q, k) * scale
    scores = scores.astype(jnp.float32)
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if cfg.causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if cfg.window is not None:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - cfg.window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqt,btnd->bqngd", probs, v)
    return out


def attend_full(q, k, v, cfg: AttnConfig, q_offset=0):
    """Exact attention, chunked over queries.  q: (B, S, H, D), k/v (B, T, N, D)."""
    b, s, h, d = q.shape
    t = k.shape[1]
    n, g = cfg.kv_heads, cfg.q_groups
    qg = q.reshape(b, s, n, g, d)
    chunk = min(cfg.q_chunk, s)
    if s % chunk != 0:
        chunk = s  # fallback: no chunking for ragged sizes
    nblk = s // chunk
    k_pos = jnp.arange(t)

    def one_block(i):
        qs = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
        q_pos = q_offset + i * chunk + jnp.arange(chunk)
        return _attend_chunk(qs, k, v, q_pos, k_pos, cfg)

    if nblk == 1:
        out = one_block(0)
    else:
        out = jax.lax.map(one_block, jnp.arange(nblk))     # (nblk, B, chunk, N, G, D)
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, n, g, d)
    return out.reshape(b, s, h, d)


def forward(params, cfg: AttnConfig, x, positions):
    """Training / encoding forward.  x: (B, S, D); positions (B, S) or (B, 3, S)."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = attend_full(q, k, v, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y  # residual-stream layout is constrained by the block owner


def cache_len(cfg: AttnConfig, max_len):
    if cfg.ring_cache and cfg.window is not None:
        return min(max_len, cfg.window)
    return max_len


def init_cache(cfg: AttnConfig, batch, max_len, dtype=jnp.bfloat16):
    shape = (batch, cache_len(cfg, max_len), cfg.kv_heads, cfg.head_dim)
    k = jnp.zeros(shape, dtype)
    v = jnp.zeros(shape, dtype)
    return {"k": k, "v": v}


def cache_specs():
    return {"k": ("batch", "kv_seq", "kv_heads", None),
            "v": ("batch", "kv_seq", "kv_heads", None)}


def prefill(params, cfg: AttnConfig, x, positions, max_len, lengths=None):
    """Forward over a prompt; returns (output, cache).  Full caches are
    length max_len; ring caches keep only the last `window` positions,
    stored at slot (absolute_position % window).

    ``lengths`` (B,) marks right-padded prompts: sequence b's real tokens
    are x[b, :lengths[b]].  Full caches need no special handling (pad K/V
    beyond ``lengths`` sit at positions the causal decode mask never admits
    before they are overwritten); ring caches DO — the roll-based packing
    below keys slots off the padded length, so pad junk would land on live
    ring slots.  With ``lengths`` the ring cache is instead gathered
    per-sequence: slot j holds the K/V of the unique absolute position
    a_j = (len-1) - ((len-1 - j) mod W) when a_j >= 0, else zeros —
    identical to the roll packing for unpadded input."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = attend_full(q, k, v, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    s_len = k.shape[1]
    clen = cache_len(cfg, max_len)
    if clen < max_len and lengths is not None:
        w = clen
        j = jnp.arange(w)
        last = lengths.astype(jnp.int32)[:, None] - 1            # (B, 1)
        a = last - jnp.mod(last - j[None, :], w)                 # (B, w)
        valid = (a >= 0)[..., None, None]
        idx = jnp.clip(a, 0)[..., None, None]
        gather = lambda t: jnp.where(
            valid, jnp.take_along_axis(t, jnp.broadcast_to(
                idx, (t.shape[0], w, t.shape[2], t.shape[3])), axis=1), 0)
        cache = {"k": gather(k), "v": gather(v)}
    elif clen < max_len:  # ring: keep the last `window` tokens, ring-ordered
        w = clen
        if s_len >= w:
            k_last, v_last = k[:, s_len - w:], v[:, s_len - w:]
            shift = (s_len - w) % w
        else:
            padw = w - s_len
            k_last = jnp.pad(k, ((0, 0), (0, padw), (0, 0), (0, 0)))
            v_last = jnp.pad(v, ((0, 0), (0, padw), (0, 0), (0, 0)))
            shift = 0
        cache = {"k": jnp.roll(k_last, shift, axis=1),
                 "v": jnp.roll(v_last, shift, axis=1)}
    else:
        pad = max_len - s_len
        cache = {
            "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
        }
    cache = {kk: constrain(vv, ("batch", "kv_seq", "kv_heads", None)) for kk, vv in cache.items()}
    return constrain(y, ("batch", None, "embed_act")), cache


def init_paged_cache(cfg: AttnConfig, num_pages, page_size, dtype=jnp.bfloat16):
    """One block-paged KV pool: physical page p's K/V for positions
    ``[t*page_size, (t+1)*page_size)`` of whichever slot's page table maps
    logical page t to p.  Page 0 is the trash page (never attended).

    A page is (page_size, N*D): the kv heads sit side by side in the last
    dim.  With head_dim under the TPU's 128 lanes a separate (N, D) pair
    would be padded, so XLA would pick another layout for the pool and
    transpose it around the decode kernel on every call."""
    shape = (num_pages, page_size, cfg.kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_cache_specs():
    return {"k": (None, None, "kv_heads"), "v": (None, None, "kv_heads")}


def _write_pages(pool, layer, page, off, k, v):
    """Scatter K/V (page.shape + (N, D)) into the pools at (page, off) —
    of ``layer`` when the pools are stacked over layers."""
    at, axes = (page, off), (None, None, "kv_heads")
    if layer is not None:
        at, axes = (layer, *at), ("layers", *axes)
    put = lambda p, t: constrain(
        p.at[at].set(t.reshape(*page.shape, -1).astype(p.dtype)), axes)
    return put(pool["k"], k), put(pool["v"], v)


def paged_prefill(params, cfg: AttnConfig, x, positions, pool, pt, lengths,
                  fill, n_prefix_pages, page_size, layer=None):
    """Prompt-suffix forward against a block-paged pool.

    ``x`` (B, L, D) embeds the right-padded prompt *suffixes* of one
    admission group — every row shares the same static ``n_prefix_pages``
    of prefix-cache hits, so its suffix starts at absolute position
    ``start = n_prefix_pages * page_size`` (``positions`` carries those
    absolute offsets for RoPE).  The suffix K/V is scattered into the pool
    first (rows past ``lengths`` or with ``fill`` False are redirected to
    the trash page), then the shared prefix pages are gathered back and
    attention runs over [gathered prefix, computed suffix] with the causal
    / window mask at absolute positions.  Scatter-before-gather means an
    admission *later in the same tick* (a higher ``n_prefix_pages`` group)
    sees pages this group just wrote.  ``pool`` and ``layer`` are as in
    :func:`paged_decode_step`.

    With ``n_prefix_pages == 0`` the attention is literally
    ``attend_full(q, k, v)`` — bit-identical math to the dense
    :func:`prefill`, which is what the paged-vs-dense parity suite pins."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    b, s_len = x.shape[0], x.shape[1]
    start = n_prefix_pages * page_size
    apos = start + jnp.arange(s_len)                          # (L,) absolute
    valid = fill[:, None] & (jnp.arange(s_len)[None, :] < lengths[:, None])
    page = jnp.where(valid, jnp.take(pt, apos // page_size, axis=1), 0)
    off = jnp.broadcast_to((apos % page_size)[None, :], (b, s_len))
    pk, pv = _write_pages(pool, layer, page, off, k, v)
    if n_prefix_pages:
        def gather(p):
            pages = pt[:, :n_prefix_pages]
            g = p[pages] if layer is None else p[layer, pages]  # (B, npp, ps, N*D)
            return g.reshape(b, start, cfg.kv_heads, cfg.head_dim).astype(q.dtype)
        kc = jnp.concatenate([gather(pk), k], axis=1)
        vc = jnp.concatenate([gather(pv), v], axis=1)
        # key j of the concat sits at absolute position j (prefix pages
        # cover [0, start); suffix key j' at start + j'), so attend_full's
        # arange(T) k_pos IS the absolute position — q_offset aligns q.
        out = attend_full(q, kc, vc, cfg, q_offset=start)
    else:
        out = attend_full(q, k, v, cfg)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return constrain(y, ("batch", None, "embed_act")), {"k": pk, "v": pv}


def paged_decode_step(params, cfg: AttnConfig, pool, pt, x, pos, positions=None,
                      active=None, *, page_size, layer=None, use_pallas=None,
                      interpret=None):
    """One token against the block-paged pool.  x: (B, 1, D); pos: (B,)
    int32 per-slot positions; pt: (B, PP) int32 page table.  ``pool`` is
    one layer's (P, ps, N*D) pools, or with ``layer`` the (L, P, ps, N*D)
    pools of every layer: the token's K/V is written at ``layer`` and the
    kernel reads that layer in place, so a layer scan that carries the
    stacked pools copies no pool.  Rows with ``active`` False write their
    K/V to the trash page — a freed slot's stale table may point at pages
    since reallocated to another slot, so unlike the dense cache its junk
    writes must be *redirected*, not merely overwritten later, and they
    attend nothing: the kernel gets ``pos = -1`` for them, reads none of
    their pages and returns zeros.  The attention gather runs through
    :func:`repro.kernels.ops.paged_decode_attn` (Pallas on TPU, dense-view
    reference elsewhere)."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if positions is None:
        positions = pos[:, None]
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, 1))
    q, k, v = _project_qkv(params, cfg, x, positions)
    rows = jnp.arange(b)
    page = pt[rows, pos // page_size]
    attend_pos = pos
    if active is not None:
        page = jnp.where(active, page, 0)
        attend_pos = jnp.where(active, pos, -1)
    off = pos % page_size
    pk, pv = _write_pages(pool, layer, page, off, k[:, 0], v[:, 0])
    from repro.kernels import ops  # local import: kernels must not be a hard dep of nn
    qg = q[:, 0].reshape(b, cfg.kv_heads, cfg.q_groups, cfg.head_dim)
    out = ops.paged_decode_attn(qg, pk, pv, pt, attend_pos, layer=layer,
                                window=cfg.window, use_pallas=use_pallas,
                                interpret=interpret)
    out = out.reshape(b, 1, cfg.num_heads, cfg.head_dim).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return constrain(y, ("batch", None, "embed_act")), {"k": pk, "v": pv}


def decode_step(params, cfg: AttnConfig, cache, x, pos, positions=None):
    """One token.  x: (B, 1, D); pos: scalar int32 (current index) or a
    per-sequence (B,) int32 vector — the serving engine's per-slot path,
    where each batch row attends (and writes its cache) at its OWN
    position; positions: rope positions (B, 1) or (B, 3, 1) — defaults
    to pos."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    if positions is None:
        positions = pos[:, None] if per_slot else jnp.full((b, 1), pos, jnp.int32)
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions[:, None, :], (b, 3, 1))
    q, k, v = _project_qkv(params, cfg, x, positions)
    t = cache["k"].shape[1]
    ring = cfg.ring_cache and cfg.window is not None and t == min(t, cfg.window)
    slot = (pos % t) if ring else pos
    if per_slot:
        rows = jnp.arange(b)
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    ck = constrain(ck, ("batch", "kv_seq", "kv_heads", None))
    cv = constrain(cv, ("batch", "kv_seq", "kv_heads", None))
    n, g = cfg.kv_heads, cfg.q_groups
    qg = q.reshape(b, 1, n, g, cfg.head_dim)
    p_col = pos[:, None] if per_slot else pos                    # (B,1) | scalar
    k_pos = jnp.arange(t)
    if per_slot:
        k_pos = jnp.broadcast_to(k_pos[None, :], (b, t))
    if ring:
        # slot j holds absolute position a_j = pos - ((pos - j) mod t)
        k_pos = p_col - jnp.mod(p_col - k_pos, t)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqngd,btnd->bngqt", qg, ck.astype(q.dtype)) * scale
    scores = scores.astype(jnp.float32)
    mask = (k_pos <= p_col) & (k_pos >= 0)
    if cfg.window is not None:
        mask = mask & (k_pos > p_col - cfg.window)
    if per_slot:
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    else:
        scores = jnp.where(mask[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqt,btnd->bqngd", probs, cv.astype(q.dtype))
    out = out.reshape(b, 1, cfg.num_heads, cfg.head_dim)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return constrain(y, ("batch", None, "embed_act")), {"k": ck, "v": cv}
