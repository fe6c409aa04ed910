"""Cached-logit buffer: exact and top-k compressed caches.

The latent bug these pin down: ``precompute_logits(..., topk=k)`` produces a
``(top_vals, top_idx, tail_lse)`` triple that the Phase-2 KD step must
consume via ``distill.topk_kl_cached`` (the exact-cache array path cannot).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distill
from repro.core.buffer import LogitCache, precompute_logits, reconstruct_logits
from repro.core.fl import mlp_adapter
from repro.data import Dataset, make_synthetic_classification

V = 10


@pytest.fixture(scope="module")
def cache_setup():
    x, y = make_synthetic_classification(num_classes=V, dim=16, per_class=40,
                                         seed=3)
    ds = Dataset(x, y)
    adapter = mlp_adapter(16, 32, V)
    state = adapter.init(jax.random.key(0))
    exact = precompute_logits(adapter, state, ds)
    return adapter, state, ds, exact


def test_exact_cache_matches_forward(cache_setup):
    adapter, state, ds, exact = cache_setup
    lg, _ = adapter.logits(state, jnp.asarray(ds.x[:7]), False)
    np.testing.assert_allclose(exact.lookup(np.arange(7)), lg, rtol=1e-5,
                               atol=1e-5)


def test_topk_lookup_returns_consumable_triple(cache_setup):
    adapter, state, ds, _ = cache_setup
    cache = precompute_logits(adapter, state, ds, topk=4)
    assert not cache.exact
    tv, ti, tail = cache.lookup(np.arange(5))
    assert tv.shape == (5, 4) and ti.shape == (5, 4) and tail.shape == (5,)
    s = jax.random.normal(jax.random.key(1), (5, V))
    loss = distill.topk_kl_cached(s, tv, ti, tail, tau=2.0)
    assert np.isfinite(float(loss))


def test_topk_kl_cached_exact_as_k_to_v(cache_setup):
    """k = V-1 leaves exactly one tail entry, so the tail bucket IS that
    entry and the compressed KL equals the exact kl_soft."""
    adapter, state, ds, exact = cache_setup
    cache = precompute_logits(adapter, state, ds, topk=V - 1)
    idx = np.arange(16)
    s = jax.random.normal(jax.random.key(2), (16, V)) * 2
    tv, ti, tail = cache.lookup(idx)
    for tau in (1.0, 2.0):
        got = float(distill.topk_kl_cached(s, tv, ti, tail, tau))
        want = float(distill.kl_soft(s, exact.lookup(idx), tau))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_topk_clamped_to_leave_tail(cache_setup):
    """topk >= V must clamp to V-1 (a k=V cache has no tail mass and the
    tail logsumexp would be log(0)); topk < 1 would silently drop the
    buffer KL term and must be rejected."""
    adapter, state, ds, _ = cache_setup
    cache = precompute_logits(adapter, state, ds, topk=V + 5)
    assert cache.top_vals.shape[-1] == V - 1
    assert np.all(np.isfinite(cache.tail_lse))
    with pytest.raises(ValueError):
        precompute_logits(adapter, state, ds, topk=0)


def test_reconstruct_logits_softmax_matches_on_topk_support(cache_setup):
    adapter, state, ds, exact = cache_setup
    k = 4
    cache = precompute_logits(adapter, state, ds, topk=k)
    idx = np.arange(12)
    entry = cache.lookup(idx)
    recon = reconstruct_logits(entry, V)
    assert recon.shape == (12, V)
    p_recon = jax.nn.softmax(recon, axis=-1)
    p_exact = jax.nn.softmax(exact.lookup(idx).astype(jnp.float32), axis=-1)
    ti = np.asarray(entry[1])
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(p_recon), ti, axis=-1),
        np.take_along_axis(np.asarray(p_exact), ti, axis=-1),
        rtol=1e-4, atol=1e-6)
    # total mass still normalises and the tail keeps the exact tail mass
    np.testing.assert_allclose(np.asarray(p_recon).sum(-1), 1.0, rtol=1e-5)
    top_mass_r = np.take_along_axis(np.asarray(p_recon), ti, -1).sum(-1)
    top_mass_e = np.take_along_axis(np.asarray(p_exact), ti, -1).sum(-1)
    np.testing.assert_allclose(1 - top_mass_r, 1 - top_mass_e, rtol=1e-3,
                               atol=1e-6)


def test_reconstruct_logits_full_k():
    """k = V-1 reconstruction recovers the original softmax everywhere."""
    logits = np.random.default_rng(0).normal(size=(6, V)).astype(np.float32)
    tv, ti = jax.lax.top_k(jnp.asarray(logits), V - 1)
    full = jax.scipy.special.logsumexp(jnp.asarray(logits), -1)
    top = jax.scipy.special.logsumexp(tv, -1)
    tail = full + jnp.log(jnp.maximum(1 - jnp.exp(top - full), 1e-9))
    recon = reconstruct_logits((tv, ti, tail), V)
    np.testing.assert_allclose(jax.nn.softmax(recon, -1),
                               jax.nn.softmax(jnp.asarray(logits), -1),
                               rtol=1e-3, atol=1e-5)


def test_core_logits_one_executable_pads_tail(cache_setup, trace_guard):
    """core_logits jits ONE batch-shaped executable: a dataset length that
    is not a multiple of the batch pads the tail batch up to shape instead
    of tracing a second (tail-shaped) executable, and a warm second sweep
    compiles nothing at all."""
    from repro.core import buffer
    adapter, state, ds, exact = cache_setup
    fwd = buffer._forward_fn(adapter)
    assert len(ds) % 48 != 0              # the sweep genuinely has a tail
    with trace_guard(fwd, max_compiles=1):
        out = buffer.core_logits(adapter, state, ds, batch=48)
    with trace_guard(fwd, max_compiles=0):
        again = buffer.core_logits(adapter, state, ds, batch=48)
    assert out.shape == (len(ds), V)
    # Padding rows are sliced off: the padded-tail sweep equals a reference
    # forward at the same 48-row batch shape over zero-padded data (a
    # different batch shape may reduce in another order on this backend).
    x = np.asarray(ds.x)
    xp = np.concatenate([x, np.zeros((-len(x) % 48,) + x.shape[1:],
                                      x.dtype)])
    ref_fwd = jax.jit(lambda st, xb: adapter.logits(st, xb, False)[0])
    want = np.concatenate([ref_fwd(state, jnp.asarray(xp[i:i + 48]))
                           for i in range(0, len(xp), 48)])[:len(x)]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out, again, rtol=0, atol=0)


def test_lookup_is_device_resident_gather(cache_setup):
    """The cache gathers with jnp.take on device — lookup results are jax
    arrays (never host numpy), and a traced integer index works (the
    scan-carried path)."""
    adapter, state, ds, exact = cache_setup
    out = exact.lookup(np.array([3, 1, 2]))
    assert isinstance(out, jax.Array)
    lookup_fn = jax.jit(exact.lookup)
    np.testing.assert_allclose(lookup_fn(jnp.array([3, 1, 2])), out,
                               rtol=0, atol=0)


def test_whole_cache_lookup_for_scan_path(cache_setup):
    """The scanned engine gathers from the full cache on device:
    lookup(slice(None)) must return the whole arrays."""
    adapter, state, ds, exact = cache_setup
    assert exact.lookup(slice(None)).shape == (len(ds), V)
    cache = precompute_logits(adapter, state, ds, topk=3)
    tv, ti, tail = cache.lookup(slice(None))
    assert tv.shape == (len(ds), 3) and tail.shape == (len(ds),)
