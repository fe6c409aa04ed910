"""Property tests for the swa_decode ring-mask (hypothesis, interpret mode).

The kernel's correctness contract: for any cache width W, decode position
``pos`` (including positions many wraparounds past W), window, and tile
split, attending over the ring cache equals dense attention over the
*true trailing sequence* — the reconstruction is independent of the
kernel's own in-register mask algebra, so a mask bug cannot cancel out.

Guarded by ``pytest.importorskip`` (PR 2 convention: hypothesis is
installed in CI, optional locally)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(installed in CI; optional locally)")
from hypothesis import given, settings, strategies as st

from repro.kernels.swa_decode import paged_decode, swa_decode


def _ring_setup(seed, w, pos, n=2, g=2, d=16, junk=37.0):
    """Build a ring cache for the true sequence k/v[0..pos]: absolute
    position p occupies slot p % w for the last min(w, pos+1) positions;
    every other slot is filled with huge junk a correct mask never reads."""
    rng = np.random.default_rng(seed)
    b = 2
    q = rng.normal(size=(b, n, g, d)).astype(np.float32)
    seq_k = rng.normal(size=(b, pos + 1, n, d)).astype(np.float32)
    seq_v = rng.normal(size=(b, pos + 1, n, d)).astype(np.float32)
    kc = np.full((b, w, n, d), junk, np.float32)
    vc = np.full((b, w, n, d), junk, np.float32)
    for p in range(max(0, pos + 1 - w), pos + 1):
        kc[:, p % w] = seq_k[:, p]
        vc[:, p % w] = seq_v[:, p]
    return q, seq_k, seq_v, kc, vc


def _dense_ref(q, seq_k, seq_v, pos, window):
    """Dense attention over the attendable tail of the true sequence."""
    w_eff = pos + 1 if window is None else min(window, pos + 1)
    lo = pos + 1 - w_eff
    k = seq_k[:, lo:pos + 1]
    v = seq_v[:, lo:pos + 1]
    d = q.shape[-1]
    s = np.einsum("bngd,btnd->bngt", q, k) / math.sqrt(d)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.einsum("bngt,btnd->bngd", p, v)


@given(seed=st.integers(0, 2**16),
       w_exp=st.integers(3, 6),                      # cache width 8..64
       wrap=st.integers(0, 3),                       # ring wraparounds
       off=st.integers(0, 63),
       win_frac=st.sampled_from([None, 0.25, 0.5, 1.0]),
       tile=st.sampled_from([4, 8, 16, 256]))
@settings(max_examples=40, deadline=None)
def test_ring_mask_matches_dense_reference(seed, w_exp, wrap, off, win_frac,
                                           tile):
    """Random (pos, window, cache width, tile) — including pos several
    wraparounds past W — against the independent dense reconstruction."""
    w = 2 ** w_exp
    pos = wrap * w + (off % w)
    window = None if win_frac is None else max(1, int(w * win_frac))
    if window is not None and pos + 1 > w and window > w:
        window = w  # cache can only ever hold the last w positions
    q, seq_k, seq_v, kc, vc = _ring_setup(seed, w, pos)
    got = swa_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                     jnp.int32(pos), window=window, ring=True, tile=tile,
                     interpret=True)
    # The ring only retains w positions: the dense window is capped at w.
    eff_window = min(window or (pos + 1), w)
    want = _dense_ref(q, seq_k, seq_v, pos, eff_window)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5, atol=3e-5)


@given(seed=st.integers(0, 2**16),
       w=st.sampled_from([16, 32]),
       window=st.sampled_from([None, 8, 16]))
@settings(max_examples=20, deadline=None)
def test_vectorized_pos_matches_per_row_scalar(seed, w, window):
    """The (B,) per-slot pos path must equal B independent scalar-pos
    calls — the property the serving engine's batched decode relies on."""
    rng = np.random.default_rng(seed)
    b, n, g, d = 3, 2, 2, 16
    pos = rng.integers(0, 4 * w, size=b).astype(np.int32)
    q = rng.normal(size=(b, n, g, d)).astype(np.float32)
    kc = rng.normal(size=(b, w, n, d)).astype(np.float32)
    vc = rng.normal(size=(b, w, n, d)).astype(np.float32)
    got = swa_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                     jnp.asarray(pos), window=window, ring=True,
                     interpret=True)
    for i in range(b):
        one = swa_decode(jnp.asarray(q[i:i + 1]), jnp.asarray(kc[i:i + 1]),
                         jnp.asarray(vc[i:i + 1]), jnp.int32(int(pos[i])),
                         window=window, ring=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(one[0]),
                                   rtol=1e-6, atol=1e-6)


@given(seed=st.integers(0, 2**16), w=st.sampled_from([8, 32]),
       pos_frac=st.floats(0.0, 1.0))
@settings(max_examples=20, deadline=None)
def test_contiguous_cache_masks_future(seed, w, pos_frac):
    """ring=False: slots beyond pos (zero/junk-filled future) contribute
    nothing; equals dense attention over the prefix."""
    pos = int(pos_frac * (w - 1))
    rng = np.random.default_rng(seed)
    b, n, g, d = 2, 2, 2, 16
    q = rng.normal(size=(b, n, g, d)).astype(np.float32)
    seq_k = rng.normal(size=(b, pos + 1, n, d)).astype(np.float32)
    seq_v = rng.normal(size=(b, pos + 1, n, d)).astype(np.float32)
    kc = np.full((b, w, n, d), 41.0, np.float32)
    vc = np.full((b, w, n, d), 41.0, np.float32)
    kc[:, :pos + 1] = seq_k
    vc[:, :pos + 1] = seq_v
    got = swa_decode(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                     jnp.int32(pos), window=None, ring=False, interpret=True)
    want = _dense_ref(q, seq_k, seq_v, pos, None)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5, atol=3e-5)


@given(seed=st.integers(0, 2**16),
       ps=st.sampled_from([4, 8, 16]),
       pp=st.integers(2, 6),
       window=st.sampled_from([None, 5, 16]))
@settings(max_examples=30, deadline=None)
def test_paged_gather_matches_dense_reference(seed, ps, pp, window):
    """Random page tables over a shared pool: the paged kernel must equal
    dense attention over each slot's *gathered* sequence, reconstructed
    independently with numpy — so a page-indexing bug cannot cancel out.
    Pool slots no table row points at are junk a correct gather never
    reads; positions past ``pos`` inside the last page are junk a correct
    mask never reads."""
    rng = np.random.default_rng(seed)
    b, n, g, d = 2, 2, 2, 16
    num_pages = b * pp + 3
    q = rng.normal(size=(b, n, g, d)).astype(np.float32)
    kp = np.full((num_pages, ps, n, d), 53.0, np.float32)
    vp = np.full((num_pages, ps, n, d), 53.0, np.float32)
    pt = np.zeros((b, pp), np.int32)
    pos = rng.integers(0, ps * pp, size=b).astype(np.int32)
    free = list(rng.permutation(np.arange(1, num_pages)))
    seqs = []
    for i in range(b):
        used = 1 + pos[i] // ps          # logical pages actually attended
        pages = np.asarray([free.pop() for _ in range(used)])
        pt[i, :used] = pages
        seq_k = rng.normal(size=(pos[i] + 1, n, d)).astype(np.float32)
        seq_v = rng.normal(size=(pos[i] + 1, n, d)).astype(np.float32)
        for p in range(pos[i] + 1):
            kp[pages[p // ps], p % ps] = seq_k[p]
            vp[pages[p // ps], p % ps] = seq_v[p]
        seqs.append((seq_k, seq_v))
    # The kernel's pools: one layer, each page's heads side by side.
    stack = lambda a: jnp.asarray(a.reshape(1, num_pages, ps, n * d))
    got = paged_decode(jnp.asarray(q), stack(kp), stack(vp),
                       jnp.asarray(pt), jnp.asarray(pos), window=window,
                       interpret=True)
    for i in range(b):
        seq_k, seq_v = seqs[i]
        want = _dense_ref(q[i:i + 1], seq_k[None], seq_v[None], int(pos[i]),
                          window)
        np.testing.assert_allclose(np.asarray(got[i:i + 1]), want,
                                   rtol=3e-5, atol=3e-5)
