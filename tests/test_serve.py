"""Serving driver: admission must cost exactly one batched prompt forward.

Regression lineage: the seed's `prefill_into` ran `Transformer.prefill` AND
a second full-prompt `Transformer.apply` just to pick the first token (2x
prompt FLOPs per admission); the engine keeps the single-forward admission
AND batches it — same-tick arrivals sharing a length bucket are admitted
through ONE prefill trace.  The counting adapter wraps both entry points:
`apply` must never run on the serve path, and the prefill trace count must
equal the bucket count, not the request count.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import serve
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import Transformer


def _smoke_setup():
    cfg = registry.get_smoke_config("granite-3-2b")
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
    return cfg, params, mesh


def test_admission_is_single_batched_prefill_forward(monkeypatch):
    """Two same-tick requests in one length bucket: ONE prefill trace, zero
    `Transformer.apply` calls (the deleted duplicate full-prompt forward),
    and one jit executable for the whole admission path."""
    cfg, params, mesh = _smoke_setup()
    counts = {"prefill": 0, "apply": 0}
    real_prefill, real_apply = Transformer.prefill, Transformer.apply

    def counting_prefill(cfg, params, batch, max_len, lengths=None):
        counts["prefill"] += 1
        return real_prefill(cfg, params, batch, max_len, lengths=lengths)

    def counting_apply(cfg, params, batch):
        counts["apply"] += 1
        return real_apply(cfg, params, batch)

    monkeypatch.setattr(Transformer, "prefill", staticmethod(counting_prefill))
    monkeypatch.setattr(Transformer, "apply", staticmethod(counting_apply))

    rng = np.random.default_rng(0)
    reqs = [serve.Request(rid=i, arrival=0,
                          prompt=rng.integers(0, cfg.vocab_size - 1, size=6),
                          max_new=3)
            for i in range(2)]
    with jax.set_mesh(mesh):
        engine = serve.ServeEngine(cfg, params, slots=2, max_len=24)
        finished = engine.run(reqs, log=None)
    assert len(finished) == 2
    assert all(len(r.out) == 3 for r in finished)
    assert counts["prefill"] == 1      # one batched admission trace
    assert counts["apply"] == 0        # the duplicate full-prompt forward
    assert engine.prefill_compile_count() == 1


def test_first_token_from_prefill_matches_full_forward():
    """The token picked from prefill's last-position logits is the one the
    deleted duplicate `Transformer.apply` forward would have picked."""
    cfg, params, mesh = _smoke_setup()
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0,
                              cfg.vocab_size - 1)
    with jax.set_mesh(mesh):
        lg_pre, _ = Transformer.prefill(cfg, params, {"tokens": toks}, 16)
        lg_full, _ = Transformer.apply(cfg, params, {"tokens": toks})
    np.testing.assert_allclose(lg_pre[0, -1], lg_full[0, -1],
                               rtol=5e-4, atol=5e-4)
    assert int(jnp.argmax(lg_pre[0, -1])) == int(jnp.argmax(lg_full[0, -1]))


def test_padded_batched_prefill_rows_match_exact_length():
    """Bucket padding is numerically invisible: row b of a right-padded
    (S, L) prefill produces the same last-real-position logits as an
    exact-length single-prompt prefill (pad scores are -inf -> exact 0
    probability mass)."""
    cfg, params, mesh = _smoke_setup()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size - 1, size=n) for n in (5, 9)]
    L, max_len = 16, 32
    padded = np.zeros((2, L), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    with jax.set_mesh(mesh):
        lg_b, _ = Transformer.prefill(cfg, params,
                                      {"tokens": jnp.asarray(padded)},
                                      max_len, lengths=lengths)
        for i, p in enumerate(prompts):
            lg_1, _ = Transformer.prefill(cfg, params,
                                          {"tokens": jnp.asarray(p)[None]},
                                          max_len)
            # pad mass is exactly zero, but batch-2 vs batch-1 XLA fusion
            # may differ in the last ulp on some backends
            np.testing.assert_allclose(np.asarray(lg_b[i, len(p) - 1]),
                                       np.asarray(lg_1[0, -1]),
                                       rtol=2e-5, atol=2e-5)
