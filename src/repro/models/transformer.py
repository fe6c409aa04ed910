"""Composable decoder/encoder LM covering every assigned architecture family.

A model is a stack of *sub-blocks* described by `block_pattern`, e.g.
    ("attn",)                    dense transformer (qwen3, nemotron, ...)
    ("rglru", "rglru", "attn")   Griffin/RecurrentGemma 2:1 hybrid
    ("ssd",)                     Mamba-2 (attention-free; mlp="none")
    ("attn",) + mlp="moe"        MoE transformer (kimi-k2, phi-3.5-moe)

Layers are grouped into super-blocks of len(block_pattern) and run under
`jax.lax.scan` over stacked parameters (bounded compile time for 61–96-layer
configs); a remainder (num_layers % len(pattern)) is unrolled as a tail.

All parameters carry logical sharding axes (see repro/sharding/rules.py).
`Transformer.init` runs under `jax.eval_shape` for the allocation-free
dry-run path.

The attention mixers, the MLPs and the unembedding run under the named
scopes `attention`, `mlp` and `unembed` (HLO metadata only), so a device
trace can put an op down to its layer.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import attention as attn_lib
from repro.nn import layers as L
from repro.nn import moe as moe_lib
from repro.nn import rglru as rglru_lib
from repro.nn import ssm as ssm_lib
from repro.sharding.rules import constrain

NEG_INF = -1e30


def pad_vocab(v: int, multiple: int = 256) -> int:
    return ((v + multiple - 1) // multiple) * multiple


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    block_pattern: tuple = ("attn",)
    mlp: str = "swiglu"              # "swiglu" | "gelu" | "squared_relu" | "none" | "moe"
    norm: str = "rmsnorm"            # "rmsnorm" | "layernorm"
    causal: bool = True
    qk_norm: bool = False
    qkv_bias: bool = False
    rope: str = "rope"               # "rope" | "mrope" | "none"
    rope_theta: float = 10000.0
    window: Optional[int] = None     # window for "local" attention sub-blocks
    sliding_window: Optional[int] = None  # if set, ALL attention is windowed (long-ctx variant)
    # MoE
    num_experts: int = 0
    top_k: int = 0
    expert_dim: int = 0
    shared_experts: int = 0
    moe_tokens_per_group: int = 128
    moe_capacity_factor: float = 1.25
    # SSM / RG-LRU
    d_state: int = 128
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    d_rnn: Optional[int] = None
    conv_width: int = 4
    # Modality front-end stubs
    is_encoder: bool = False         # hubert: bidirectional, frame inputs
    feat_dim: int = 512              # audio frontend embedding dim
    is_vlm: bool = False             # vision patch embeds scattered into the sequence
    mrope_sections: tuple = (16, 24, 24)
    # Numerics / scan
    dtype: str = "bfloat16"          # activation dtype
    param_dtype: str = "bfloat16"
    remat: str = "block"             # "none" | "block"
    unroll: bool = False             # python-unroll the layer stack (used by
                                     # the dry-run's per-layer cost probe)
    seq_parallel: bool = False       # Megatron-style sequence parallelism:
                                     # residual stream sharded over "model"
                                     # between blocks (RS/AG instead of AR)
    ring_cache: bool = False         # windowed decode caches hold only the
                                     # last `window` tokens (ring buffer)
    q_chunk: int = 512
    vocab_pad: int = 256

    @property
    def hd(self):
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def padded_vocab(self):
        return pad_vocab(self.vocab_size, self.vocab_pad)

    @property
    def n_super(self):
        return self.num_layers // len(self.block_pattern)

    @property
    def n_tail(self):
        return self.num_layers % len(self.block_pattern)

    @property
    def adt(self):
        return jnp.dtype(self.dtype)

    @property
    def pdt(self):
        return jnp.dtype(self.param_dtype)

    def attn_cfg(self, local: bool) -> attn_lib.AttnConfig:
        window = self.sliding_window or (self.window if local else None)
        return attn_lib.AttnConfig(
            d_model=self.d_model, num_heads=self.num_heads, kv_heads=self.kv_heads,
            head_dim=self.hd, causal=self.causal, window=window,
            qk_norm=self.qk_norm, qkv_bias=self.qkv_bias,
            rope="none" if (self.is_encoder and self.rope == "rope") else self.rope,
            rope_theta=self.rope_theta, mrope_sections=self.mrope_sections,
            q_chunk=self.q_chunk, ring_cache=self.ring_cache)

    def ssd_cfg(self) -> ssm_lib.SSDConfig:
        return ssm_lib.SSDConfig(
            d_model=self.d_model, d_state=self.d_state, head_dim=self.ssm_head_dim,
            conv_width=self.conv_width, chunk=self.ssm_chunk)

    def rglru_cfg(self) -> rglru_lib.RGLRUConfig:
        return rglru_lib.RGLRUConfig(
            d_model=self.d_model, d_rnn=self.d_rnn or self.d_model,
            conv_width=self.conv_width)

    def moe_cfg(self) -> moe_lib.MoEConfig:
        return moe_lib.MoEConfig(
            d_model=self.d_model, num_experts=self.num_experts, top_k=self.top_k,
            expert_dim=self.expert_dim, tokens_per_group=self.moe_tokens_per_group,
            capacity_factor=self.moe_capacity_factor)


# ---------------------------------------------------------------------------
# Sub-block init / apply
# ---------------------------------------------------------------------------

def _norm_init(cfg, dim, stack=None):
    if cfg.norm == "layernorm":
        return L.layernorm_init(dim, stack=stack, dtype=cfg.pdt)
    return L.rmsnorm_init(dim, stack=stack, dtype=cfg.pdt)


def _norm_apply(cfg, p, x):
    if cfg.norm == "layernorm":
        return L.layernorm(p, x)
    return L.rmsnorm(p, x)


def _mlp_init(cfg: LMConfig, key, stack=None):
    if cfg.mlp == "none":
        return {}, {}
    if cfg.mlp == "moe":
        p, s = moe_lib.init(key, cfg.moe_cfg(), stack=stack, dtype=cfg.pdt)
        if cfg.shared_experts:
            k2 = jax.random.fold_in(key, 7)
            sp, ss = _dense_mlp_init(cfg, k2, cfg.expert_dim * cfg.shared_experts, stack=stack)
            p["shared"], s["shared"] = sp, ss
        return p, s
    return _dense_mlp_init(cfg, key, cfg.d_ff, stack=stack)


def _dense_mlp_init(cfg: LMConfig, key, d_ff, stack=None):
    ks = jax.random.split(key, 3)
    gated = cfg.mlp in ("swiglu", "gelu_glu") or cfg.mlp == "moe"
    p, s = {}, {}
    wu, su = L.stacked_dense_init(ks[0], stack, cfg.d_model, d_ff, dtype=cfg.pdt) \
        if stack is not None else L.dense_init(ks[0], cfg.d_model, d_ff, dtype=cfg.pdt)
    p["up"], s["up"] = wu, su
    if gated:
        wg, sg = L.stacked_dense_init(ks[1], stack, cfg.d_model, d_ff, dtype=cfg.pdt) \
            if stack is not None else L.dense_init(ks[1], cfg.d_model, d_ff, dtype=cfg.pdt)
        p["gate"], s["gate"] = wg, sg
    wd, sd = L.stacked_dense_init(ks[2], stack, d_ff, cfg.d_model, in_axis="mlp",
                                  out_axis="embed", dtype=cfg.pdt) \
        if stack is not None else L.dense_init(ks[2], d_ff, cfg.d_model, in_axis="mlp",
                                               out_axis="embed", dtype=cfg.pdt)
    p["down"], s["down"] = wd, sd
    return p, s


def _dense_mlp_apply(cfg: LMConfig, p, x):
    up = L.dense(p["up"], x)
    up = constrain(up, ("batch", None, "mlp"))
    if "gate" in p:
        gate = L.dense(p["gate"], x)
        gate = constrain(gate, ("batch", None, "mlp"))
        h = L.swiglu(gate, up)
    elif cfg.mlp == "squared_relu":
        h = L.squared_relu(up)
    else:
        h = L.gelu(up)
    y = L.dense(p["down"], h)
    return y  # residual-stream layout is constrained by the block owner


def _mlp_apply(cfg: LMConfig, p, x):
    """Returns (y, aux_loss)."""
    if cfg.mlp == "none":
        return jnp.zeros_like(x), 0.0
    with jax.named_scope("mlp"):
        if cfg.mlp == "moe":
            y, aux = moe_lib.forward(
                {k: v for k, v in p.items() if k != "shared"}, cfg.moe_cfg(),
                x)
            if "shared" in p:
                y = y + _dense_mlp_apply(cfg, p["shared"], x)
            return y, aux
        return _dense_mlp_apply(cfg, p, x), 0.0


def _subblock_init(cfg: LMConfig, kind: str, key, stack=None):
    ks = jax.random.split(key, 2)
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_init(cfg, cfg.d_model, stack=stack)
    if kind in ("attn", "local"):
        p["mixer"], s["mixer"] = attn_lib.init(ks[0], cfg.attn_cfg(kind == "local"),
                                               stack=stack, dtype=cfg.pdt)
    elif kind == "ssd":
        p["mixer"], s["mixer"] = ssm_lib.init(ks[0], cfg.ssd_cfg(), stack=stack, dtype=cfg.pdt)
    elif kind == "rglru":
        p["mixer"], s["mixer"] = rglru_lib.init(ks[0], cfg.rglru_cfg(), stack=stack, dtype=cfg.pdt)
    else:
        raise ValueError(kind)
    if cfg.mlp != "none":
        p["ln2"], s["ln2"] = _norm_init(cfg, cfg.d_model, stack=stack)
        p["mlp"], s["mlp"] = _mlp_init(cfg, ks[1], stack=stack)
    return p, s


def _mixer_apply(cfg: LMConfig, kind: str, p, x, positions):
    if kind in ("attn", "local"):
        with jax.named_scope("attention"):
            return attn_lib.forward(p, cfg.attn_cfg(kind == "local"), x,
                                    positions)
    if kind == "ssd":
        return ssm_lib.forward(p, cfg.ssd_cfg(), x)
    if kind == "rglru":
        return rglru_lib.forward(p, cfg.rglru_cfg(), x)
    raise ValueError(kind)


def _subblock_fwd(cfg: LMConfig, kind: str, p, x, positions):
    # Sequence parallelism (Megatron-SP): the residual stream lives
    # seq-sharded over "model"; each mixer/MLP *output* (a partial sum over
    # the model axis) is constrained to the seq-sharded layout BEFORE the
    # residual add, so GSPMD lowers partial->sharded as a reduce-scatter
    # (1x payload) rather than an all-reduce (2x) plus a re-shard.
    def _res(t):
        if cfg.seq_parallel:
            return constrain(t, ("batch", "seq_sp", "embed_act"))
        return constrain(t, ("batch", None, "embed_act"))

    y = _mixer_apply(cfg, kind, p["mixer"], _norm_apply(cfg, p["ln1"], x), positions)
    x = _res(x) + _res(y)
    aux = 0.0
    if cfg.mlp != "none":
        m, aux = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
        x = x + _res(m)
    return x, aux


# ---------------------------------------------------------------------------
# Decode-path sub-block (cache-carrying)
# ---------------------------------------------------------------------------

def _subblock_cache_init(cfg: LMConfig, kind: str, batch, max_len):
    if kind in ("attn", "local"):
        return attn_lib.init_cache(cfg.attn_cfg(kind == "local"), batch, max_len,
                                   dtype=cfg.adt)
    if kind == "ssd":
        return ssm_lib.init_cache(cfg.ssd_cfg(), batch)
    if kind == "rglru":
        return rglru_lib.init_cache(cfg.rglru_cfg(), batch)
    raise ValueError(kind)


def _subblock_cache_specs(kind: str):
    if kind in ("attn", "local"):
        return attn_lib.cache_specs()
    if kind == "ssd":
        return ssm_lib.cache_specs()
    if kind == "rglru":
        return rglru_lib.cache_specs()
    raise ValueError(kind)


def _subblock_decode(cfg: LMConfig, kind: str, p, cache, x, pos, positions):
    h = _norm_apply(cfg, p["ln1"], x)
    if kind in ("attn", "local"):
        y, cache = attn_lib.decode_step(p["mixer"], cfg.attn_cfg(kind == "local"),
                                        cache, h, pos, positions)
    elif kind == "ssd":
        y, cache = ssm_lib.decode_step(p["mixer"], cfg.ssd_cfg(), cache, h)
    elif kind == "rglru":
        y, cache = rglru_lib.decode_step(p["mixer"], cfg.rglru_cfg(), cache, h)
    else:
        raise ValueError(kind)
    x = x + y
    if cfg.mlp != "none":
        m, _ = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
        x = x + m
    return x, cache


def _assert_pageable(cfg: LMConfig):
    """Paged KV caching covers attention state only: recurrent sub-blocks
    carry dense per-slot state (no pages to share), and ring caches already
    bound their own memory — both keep the dense engine."""
    if any(k not in ("attn", "local") for k in cfg.block_pattern):
        raise ValueError(
            f"paged KV cache needs an attention-only block_pattern; "
            f"{cfg.block_pattern} has recurrent sub-blocks")
    if cfg.ring_cache:
        raise ValueError("paged KV cache and ring_cache are exclusive — "
                         "the page pool replaces the ring buffer")


def _paged_subblock_decode(cfg: LMConfig, kind: str, p, pool, pt, x, pos,
                           positions, active, page_size, layer=None):
    h = _norm_apply(cfg, p["ln1"], x)
    y, pool = attn_lib.paged_decode_step(
        p["mixer"], cfg.attn_cfg(kind == "local"), pool, pt, h, pos,
        positions, active, page_size=page_size, layer=layer)
    x = x + y
    if cfg.mlp != "none":
        m, _ = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
        x = x + m
    return x, pool


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

def _scan_layers(cfg: LMConfig, body, x, stacked):
    """lax.scan over stacked layer params, or a Python unroll (dry-run probe)."""
    if not cfg.unroll:
        return jax.lax.scan(body, x, stacked)
    ys = []
    for i in range(cfg.n_super):
        lp = jax.tree.map(lambda l: l[i], stacked)
        x, y = body(x, lp)
        ys.append(y)
    stacked_ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    return x, stacked_ys


class Transformer:
    """Namespace of pure functions over (cfg, params)."""

    @staticmethod
    def init(cfg: LMConfig, key):
        keys = jax.random.split(key, 8)
        p, s = {}, {}
        if cfg.is_encoder:
            p["in_proj"], s["in_proj"] = L.dense_init(
                keys[0], cfg.feat_dim, cfg.d_model, in_axis=None, out_axis="embed",
                dtype=cfg.pdt, use_bias=True)
            p["mask_embed"] = jnp.zeros((cfg.feat_dim,), cfg.pdt)
            s["mask_embed"] = (None,)
        else:
            p["embed"], s["embed"] = L.embedding_init(keys[0], cfg.padded_vocab,
                                                      cfg.d_model, dtype=cfg.pdt)
        pat = cfg.block_pattern

        if cfg.n_super > 0:
            def one_super(k):
                pp, ss = {}, {}
                for i, kind in enumerate(pat):
                    pp[f"b{i}"], ss[f"b{i}"] = _subblock_init(
                        cfg, kind, jax.random.fold_in(k, i), stack=None)
                return pp, ss

            sk = jax.random.split(keys[1], cfg.n_super)
            stacked_p = jax.vmap(lambda k: one_super(k)[0])(sk)
            proto_s = one_super(sk[0])[1]  # specs are static; params discarded
            # prepend "layers" logical axis to every spec tuple
            stacked_s = jax.tree.map(
                lambda ax: ("layers", *ax), proto_s,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x))
            p["blocks"], s["blocks"] = stacked_p, stacked_s

        for j in range(cfg.n_tail):
            kind = pat[j % len(pat)]
            p[f"tail{j}"], s[f"tail{j}"] = _subblock_init(
                cfg, kind, jax.random.fold_in(keys[2], j), stack=None)

        p["ln_f"], s["ln_f"] = _norm_init(cfg, cfg.d_model)
        p["unembed"], s["unembed"] = L.dense_init(
            keys[3], cfg.d_model, cfg.padded_vocab, in_axis="embed", out_axis="vocab",
            dtype=cfg.pdt, std=1.0 / math.sqrt(cfg.d_model))
        return p, s

    # -- shared plumbing ----------------------------------------------------

    @staticmethod
    def _embed_inputs(cfg: LMConfig, params, batch):
        if cfg.is_encoder:
            feats = batch["features"].astype(cfg.adt)              # (B,S,feat)
            if "mask" in batch:
                m = batch["mask"][..., None]
                feats = jnp.where(m, params["mask_embed"].astype(cfg.adt), feats)
            x = L.dense(params["in_proj"], feats)
        else:
            x = L.embedding(params["embed"], batch["tokens"]).astype(cfg.adt)
            if cfg.is_vlm and "vision_embeds" in batch:
                ve = batch["vision_embeds"].astype(cfg.adt)         # (B,P,D)
                vp = batch["vision_positions"]                      # (B,P)
                x = jax.vmap(lambda e, p_, v: e.at[p_].set(v))(x, vp, ve)
        x = constrain(x, ("batch", None, "embed_act"))
        positions = batch.get("positions")
        if positions is None:
            bsz, slen = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(slen, dtype=jnp.int32), (bsz, slen))
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[:, None, :], (bsz, 3, slen))
        return x, positions

    @staticmethod
    def _unembed(cfg: LMConfig, params, x):
        with jax.named_scope("unembed"):
            x = _norm_apply(cfg, params["ln_f"], x)
            logits = L.dense(params["unembed"], x).astype(jnp.float32)
            logits = constrain(logits, ("batch", None, "vocab"))
            if cfg.padded_vocab != cfg.vocab_size:
                pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
                logits = jnp.where(pad_mask, logits, NEG_INF)
            return logits

    # -- full-sequence forward (train / prefill trunk) ----------------------

    @staticmethod
    def apply_hidden(cfg: LMConfig, params, batch):
        """-> (final hidden states (B,S,D) pre-ln_f, aux_loss scalar)."""
        x, positions = Transformer._embed_inputs(cfg, params, batch)

        def super_fwd(x, layer_p):
            aux = 0.0
            for i, kind in enumerate(cfg.block_pattern):
                x, a = _subblock_fwd(cfg, kind, layer_p[f"b{i}"], x, positions)
                aux = aux + a
            return x, aux

        aux = 0.0
        if cfg.n_super > 0:
            body = super_fwd
            if cfg.remat == "block":
                body = jax.checkpoint(body)
            x, auxes = _scan_layers(cfg, body, x, params["blocks"])
            aux = jnp.sum(auxes)
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            x, a = _subblock_fwd(cfg, kind, params[f"tail{j}"], x, positions)
            aux = aux + a
        return x, aux

    @staticmethod
    def logits_from_hidden(cfg: LMConfig, params, hidden):
        return Transformer._unembed(cfg, params, hidden)

    @staticmethod
    def apply(cfg: LMConfig, params, batch):
        """-> (logits (B,S,V_pad) fp32, aux_loss scalar)."""
        x, aux = Transformer.apply_hidden(cfg, params, batch)
        return Transformer._unembed(cfg, params, x), aux

    # -- decode path ---------------------------------------------------------

    @staticmethod
    def init_cache(cfg: LMConfig, batch, max_len):
        caches = {}
        if cfg.n_super > 0:
            def one(_):
                return {f"b{i}": _subblock_cache_init(cfg, kind, batch, max_len)
                        for i, kind in enumerate(cfg.block_pattern)}
            caches["blocks"] = jax.vmap(one)(jnp.arange(cfg.n_super))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            caches[f"tail{j}"] = _subblock_cache_init(cfg, kind, batch, max_len)
        return caches

    @staticmethod
    def cache_specs(cfg: LMConfig):
        specs = {}
        if cfg.n_super > 0:
            one = {f"b{i}": _subblock_cache_specs(kind)
                   for i, kind in enumerate(cfg.block_pattern)}
            specs["blocks"] = jax.tree.map(
                lambda ax: ("layers", *ax), one,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            specs[f"tail{j}"] = _subblock_cache_specs(kind)
        return specs

    @staticmethod
    def decode_step(cfg: LMConfig, params, caches, token, pos, positions=None):
        """token: (B, 1) int32 (or features (B,1,feat)); pos: scalar int32,
        or a per-sequence (B,) int32 vector — the serving engine's per-slot
        decode, where every batch row sits at its own position."""
        batch = {"tokens": token} if not cfg.is_encoder else {"features": token}
        x, _ = Transformer._embed_inputs(cfg, params, batch)
        pos = jnp.asarray(pos, jnp.int32)
        if positions is None:
            bsz = x.shape[0]
            positions = (pos[:, None] if pos.ndim == 1
                         else jnp.full((bsz, 1), pos, jnp.int32))
            if cfg.rope == "mrope":
                positions = jnp.broadcast_to(positions[:, None, :], (bsz, 3, 1))

        def super_step(x, scanned):
            layer_p, cache = scanned
            new_cache = {}
            for i, kind in enumerate(cfg.block_pattern):
                x, new_cache[f"b{i}"] = _subblock_decode(
                    cfg, kind, layer_p[f"b{i}"], cache[f"b{i}"], x, pos, positions)
            return x, new_cache

        new_caches = {}
        if cfg.n_super > 0:
            x, new_caches["blocks"] = _scan_layers(
                cfg, super_step, x, (params["blocks"], caches["blocks"]))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            x, new_caches[f"tail{j}"] = _subblock_decode(
                cfg, kind, params[f"tail{j}"], caches[f"tail{j}"], x, pos, positions)
        logits = Transformer._unembed(cfg, params, x)
        return logits, new_caches

    # -- block-paged decode path (the paged ServeEngine) ---------------------

    @staticmethod
    def init_paged_cache(cfg: LMConfig, num_pages, page_size):
        """Per-layer physical page pools, mirroring :meth:`init_cache`'s
        tree shape ((layers, P, ps, N*D) under "blocks", (P, ps, N*D) for
        tails).  One page table indexes every layer's pool — the logical ->
        physical mapping is per slot, not per layer."""
        _assert_pageable(cfg)
        caches = {}
        if cfg.n_super > 0:
            def one(_):
                return {f"b{i}": attn_lib.init_paged_cache(
                            cfg.attn_cfg(kind == "local"), num_pages,
                            page_size, dtype=cfg.adt)
                        for i, kind in enumerate(cfg.block_pattern)}
            caches["blocks"] = jax.vmap(one)(jnp.arange(cfg.n_super))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            caches[f"tail{j}"] = attn_lib.init_paged_cache(
                cfg.attn_cfg(kind == "local"), num_pages, page_size,
                dtype=cfg.adt)
        return caches

    @staticmethod
    def paged_cache_specs(cfg: LMConfig):
        specs = {}
        if cfg.n_super > 0:
            one = {f"b{i}": attn_lib.paged_cache_specs()
                   for i in range(len(cfg.block_pattern))}
            specs["blocks"] = jax.tree.map(
                lambda ax: ("layers", *ax), one,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(e, (str, type(None))) for e in x))
        for j in range(cfg.n_tail):
            specs[f"tail{j}"] = attn_lib.paged_cache_specs()
        return specs

    @staticmethod
    def paged_decode_step(cfg: LMConfig, params, caches, pt, token, pos,
                          active=None, *, page_size):
        """One token per slot against the paged pools.  token: (B, 1) int32;
        pos: (B,) int32; pt: (B, PP) int32; ``active`` (B,) bool redirects
        inactive rows' cache writes to the trash page.  The layer scan
        carries the stacked pools and each layer writes and reads its own
        in place, so a tick touches the pages it attends, not whole pools."""
        batch = {"tokens": token}
        x, _ = Transformer._embed_inputs(cfg, params, batch)
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None]
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(positions[:, None, :], (x.shape[0], 3, 1))

        def super_step(carry, scanned):
            x, pools = carry
            layer_p, layer = scanned
            pools = dict(pools)
            for i, kind in enumerate(cfg.block_pattern):
                x, pools[f"b{i}"] = _paged_subblock_decode(
                    cfg, kind, layer_p[f"b{i}"], pools[f"b{i}"], pt, x, pos,
                    positions, active, page_size, layer)
            return (x, pools), None

        new_caches = {}
        if cfg.n_super > 0:
            (x, new_caches["blocks"]), _ = _scan_layers(
                cfg, super_step, (x, caches["blocks"]),
                (params["blocks"], jnp.arange(cfg.n_super, dtype=jnp.int32)))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            x, new_caches[f"tail{j}"] = _paged_subblock_decode(
                cfg, kind, params[f"tail{j}"], caches[f"tail{j}"], pt, x,
                pos, positions, active, page_size)
        logits = Transformer._unembed(cfg, params, x)
        return logits, new_caches

    @staticmethod
    def paged_prefill(cfg: LMConfig, params, batch, caches, pt, lengths,
                      fill, n_prefix_pages, page_size):
        """Prompt-suffix prefill into the paged pools (one admission group
        sharing a static ``n_prefix_pages``).  ``batch["tokens"]`` holds
        the right-padded suffixes and ``batch["positions"]`` their absolute
        positions (``n_prefix_pages * page_size`` onward); returns suffix
        logits plus the updated pools.  Like :meth:`paged_decode_step` the
        layer scan carries the stacked pools and updates them in place."""
        x, positions = Transformer._embed_inputs(cfg, params, batch)

        def block_prefill(p, kind, x, pool, layer=None):
            h = _norm_apply(cfg, p["ln1"], x)
            y, pool = attn_lib.paged_prefill(
                p["mixer"], cfg.attn_cfg(kind == "local"), h, positions,
                pool, pt, lengths, fill, n_prefix_pages, page_size, layer)
            x = x + y
            if cfg.mlp != "none":
                m, _ = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
                x = x + m
            return x, pool

        def super_fwd(carry, scanned):
            x, pools = carry
            layer_p, layer = scanned
            pools = dict(pools)
            for i, kind in enumerate(cfg.block_pattern):
                x, pools[f"b{i}"] = block_prefill(
                    layer_p[f"b{i}"], kind, x, pools[f"b{i}"], layer)
            return (x, pools), None

        new_caches = {}
        if cfg.n_super > 0:
            body = super_fwd
            if cfg.remat == "block":
                body = jax.checkpoint(body)
            (x, new_caches["blocks"]), _ = _scan_layers(
                cfg, body, (x, caches["blocks"]),
                (params["blocks"], jnp.arange(cfg.n_super, dtype=jnp.int32)))
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            x, new_caches[f"tail{j}"] = block_prefill(
                params[f"tail{j}"], kind, x, caches[f"tail{j}"])
        logits = Transformer._unembed(cfg, params, x)
        return logits, new_caches

    @staticmethod
    def prefill(cfg: LMConfig, params, batch, max_len, lengths=None):
        """Run the prompt, build caches by re-projecting K/V per layer.

        For simplicity and bounded memory the prefill computes the full
        forward for logits; caches are produced by the same scan (attention
        sub-blocks store K/V; recurrent sub-blocks store final states).

        ``lengths`` (B,) marks right-padded prompts (the serving engine's
        bucketed batched prefill): row b's real prompt is tokens[b, :len_b].
        Attention caches are padding-safe (ring caches are packed
        length-aware; full-cache pad junk is never attended); recurrent
        caches are NOT — their final state would include pad tokens — so
        padded prefill is rejected for ssd/rglru blocks."""
        if lengths is not None and any(k != "attn" and k != "local"
                                       for k in cfg.block_pattern):
            raise ValueError(
                "padded (bucketed) prefill needs length-aware recurrent "
                f"state handling; block_pattern {cfg.block_pattern} has "
                "recurrent sub-blocks — prefill each prompt at its exact "
                "length instead (lengths=None)")
        x, positions = Transformer._embed_inputs(cfg, params, batch)

        def block_prefill(p, kind, x):
            h = _norm_apply(cfg, p["ln1"], x)
            if kind in ("attn", "local"):
                y, c = attn_lib.prefill(p["mixer"], cfg.attn_cfg(kind == "local"),
                                        h, positions, max_len, lengths=lengths)
            elif kind == "ssd":
                y, c = ssm_lib.forward(p["mixer"], cfg.ssd_cfg(), h, return_cache=True)
            else:
                y, c = rglru_lib.forward(p["mixer"], cfg.rglru_cfg(), h, return_cache=True)
            x = x + y
            if cfg.mlp != "none":
                m, _ = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
                x = x + m
            return x, c

        def super_fwd(x, layer_p):
            cache = {}
            for i, kind in enumerate(cfg.block_pattern):
                x, cache[f"b{i}"] = block_prefill(layer_p[f"b{i}"], kind, x)
            return x, cache

        caches = {}
        if cfg.n_super > 0:
            body = super_fwd
            if cfg.remat == "block":
                body = jax.checkpoint(body)
            x, caches["blocks"] = _scan_layers(cfg, body, x, params["blocks"])
        for j in range(cfg.n_tail):
            kind = cfg.block_pattern[j % len(cfg.block_pattern)]
            x, caches[f"tail{j}"] = block_prefill(params[f"tail{j}"], kind, x)
        logits = Transformer._unembed(cfg, params, x)
        return logits, caches
