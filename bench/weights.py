"""Seeded random weights and token data, in the benchmark's own layout.

A dense decoder (RMSNorm, GQA attention with RoPE, SwiGLU MLP, untied
output head) is described by :class:`Dims`, read from a configuration file
under ``bench/configs``.  Its weights are a flat dict of arrays stacked over
layers, made on the device in one jitted call from ``(seed, tag)``, in the
type they are served in.  The same call made again gives the same bits, so
the reference regenerates the weights instead of taking them from the
program.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

VOCAB_PAD = 256          # the program pads the vocabulary to this multiple


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    qkv_bias: bool
    dtype: str = "bfloat16"

    @property
    def padded_vocab(self):
        return -(-self.vocab // VOCAB_PAD) * VOCAB_PAD

    @property
    def groups(self):
        return self.heads // self.kv_heads

    @classmethod
    def from_config(cls, c):
        """From a ``bench/configs/*.json`` object (Hugging Face key names).
        Where the program departs from the published configuration, the
        file's ``assumed.as_run`` holds the values run, and they are read
        in place of the published keys."""
        c = {**c, **c.get("assumed", {}).get("as_run", {})}
        heads = c["num_attention_heads"]
        plain = {"tie_word_embeddings": False, "embedding_multiplier": 1.0,
                 "residual_multiplier": 1.0, "logits_scaling": 1.0,
                 "attention_multiplier": (c.get("head_dim") or
                                          c["hidden_size"] // heads) ** -0.5}
        off = {k: c[k] for k, v in plain.items() if c.get(k, v) != v}
        if off:
            raise ValueError(f"{c.get('name')}: the decoder run here has no "
                             f"{sorted(off)}; state the values run under "
                             f"assumed.as_run")
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]),
                   qkv_bias=bool(c["program"]["qkv_bias"]),
                   dtype=c["program"]["dtype"])


def layout(dm: Dims):
    """name -> (shape, kind, scale).  ``normal`` draws N(0, scale^2);
    ``scale`` draws 1 + N(0, scale^2) (a norm's gain)."""
    L, D, H, N, K, F, V = (dm.layers, dm.d_model, dm.heads, dm.kv_heads,
                           dm.head_dim, dm.d_ff, dm.padded_vocab)
    s = 1.0 / math.sqrt(D)
    spec = {
        "embed": ((V, D), "normal", 1.0),
        "ln1": ((L, D), "scale", 0.1),
        "wq": ((L, D, H, K), "normal", s),
        "wk": ((L, D, N, K), "normal", s),
        "wv": ((L, D, N, K), "normal", s),
        "wo": ((L, H, K, D), "normal", 1.0 / math.sqrt(H * K)),
        "ln2": ((L, D), "scale", 0.1),
        "w_gate": ((L, D, F), "normal", s),
        "w_up": ((L, D, F), "normal", s),
        "w_down": ((L, F, D), "normal", 1.0 / math.sqrt(F)),
        "ln_f": ((D,), "scale", 0.1),
        "unembed": ((D, V), "normal", s),
    }
    if dm.qkv_bias:
        spec["bq"] = ((L, H, K), "normal", 0.1)
        spec["bk"] = ((L, N, K), "normal", 0.1)
        spec["bv"] = ((L, N, K), "normal", 0.1)
    return spec


def seed_key(seed: int, tag: int):
    """A threefry key from any non-negative integer seed (wider than 32
    bits too) and a tag that separates the streams of one run."""
    import jax
    words = np.random.SeedSequence([int(seed), int(tag)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


@functools.lru_cache(maxsize=None)
def _maker(dm: Dims):
    import jax
    import jax.numpy as jnp
    spec = layout(dm)
    dt = jnp.dtype(dm.dtype)

    def make(key):
        out = {}
        for i, (name, (shape, kind, scale)) in enumerate(sorted(spec.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, dt)
            out[name] = (x * jnp.asarray(scale, dt) + (1 if kind == "scale"
                                                        else 0)).astype(dt)
        return out

    return jax.jit(make)


def make_weights(dm: Dims, seed: int, tag: int):
    """The weights of stream ``tag`` of ``seed``, on the default device."""
    return _maker(dm)(seed_key(seed, tag))


@functools.lru_cache(maxsize=None)
def _token_maker(shape, vocab):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab,
                                                jnp.int32))


def make_tokens(seed: int, tag: int, shape, vocab: int):
    """Uniform token ids in [0, vocab), on the default device."""
    return _token_maker(tuple(shape), int(vocab))(seed_key(seed, tag))
