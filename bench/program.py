"""The seam between the benchmark and the program under test.

Everything the benchmark knows of the program's interface is here: how a
configuration file becomes the program's ``LMConfig``, and how the
benchmark's weights (``bench/weights.py`` layout) become the program's
parameter tree.  The tree is checked leaf by leaf against the program's
own ``Transformer.init`` shapes, so a change to the program's layout fails
loudly instead of serving wrong weights.
"""

from __future__ import annotations

import jax

from bench.weights import Dims

_MIXER = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")


def lm_config(dm: Dims, name: str):
    from repro.models.transformer import LMConfig
    return LMConfig(name=name, num_layers=dm.layers, d_model=dm.d_model,
                    num_heads=dm.heads, kv_heads=dm.kv_heads,
                    head_dim=dm.head_dim, d_ff=dm.d_ff, vocab_size=dm.vocab,
                    block_pattern=("attn",), mlp="swiglu", norm="rmsnorm",
                    rope="rope", rope_theta=dm.rope_theta,
                    qkv_bias=dm.qkv_bias, dtype=dm.dtype,
                    param_dtype=dm.dtype)


def to_program(w):
    """The program's parameter tree over the benchmark's arrays (no copy)."""
    mixer = {k: w[k] for k in _MIXER if k in w}
    return {
        "embed": {"table": w["embed"]},
        "blocks": {"b0": {
            "ln1": {"scale": w["ln1"]},
            "mixer": mixer,
            "ln2": {"scale": w["ln2"]},
            "mlp": {"gate": {"w": w["w_gate"]}, "up": {"w": w["w_up"]},
                    "down": {"w": w["w_down"]}},
        }},
        "ln_f": {"scale": w["ln_f"]},
        "unembed": {"w": w["unembed"]},
    }


def from_program(tree):
    """Inverse of :func:`to_program`: the benchmark's flat names."""
    b = tree["blocks"]["b0"]
    w = {"embed": tree["embed"]["table"], "ln1": b["ln1"]["scale"],
         "ln2": b["ln2"]["scale"], "w_gate": b["mlp"]["gate"]["w"],
         "w_up": b["mlp"]["up"]["w"], "w_down": b["mlp"]["down"]["w"],
         "ln_f": tree["ln_f"]["scale"], "unembed": tree["unembed"]["w"]}
    w.update(b["mixer"])
    return w


def check_layout(cfg, params):
    """Raise if ``params`` is not shaped as the program's own init."""
    from repro.models.transformer import Transformer
    want = jax.eval_shape(lambda k: Transformer.init(cfg, k)[0],
                          jax.random.key(0))
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), want)
    if got != exp:
        raise RuntimeError(f"weights do not match the program's layout:\n"
                           f"  program {exp}\n  benchmark {got}")
