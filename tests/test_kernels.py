"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("rows,vocab", [(8, 256), (16, 512), (32, 1024), (6, 384)])
@pytest.mark.parametrize("tau", [1.0, 2.0])
@pytest.mark.parametrize("with_buffer", [False, True])
def test_kd_loss_forward(rows, vocab, tau, with_buffer):
    ks = jax.random.split(jax.random.key(rows + vocab), 4)
    s = jax.random.normal(ks[0], (rows, vocab)) * 3
    t = jax.random.normal(ks[1], (rows, vocab)) * 3
    b = jax.random.normal(ks[2], (rows, vocab)) * 3 if with_buffer else None
    y = jax.random.randint(ks[3], (rows,), 0, vocab)
    got = ops.kd_loss(y, s, t, b, tau, use_pallas=True, interpret=True)
    want = ref.kd_loss_mean_ref(y, s, t, b, tau)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kd_loss_dtypes(dtype):
    ks = jax.random.split(jax.random.key(7), 4)
    s = (jax.random.normal(ks[0], (8, 256)) * 3).astype(dtype)
    t = (jax.random.normal(ks[1], (8, 256)) * 3).astype(dtype)
    y = jax.random.randint(ks[3], (8,), 0, 256)
    got = ops.kd_loss(y, s, t, None, 2.0, use_pallas=True, interpret=True)
    want = ref.kd_loss_mean_ref(y, s.astype(jnp.float32), t.astype(jnp.float32),
                                None, 2.0)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("with_buffer", [False, True])
def test_kd_loss_grad_matches_autodiff(with_buffer):
    ks = jax.random.split(jax.random.key(3), 4)
    s = jax.random.normal(ks[0], (16, 512)) * 2
    t = jax.random.normal(ks[1], (16, 512)) * 2
    b = jax.random.normal(ks[2], (16, 512)) * 2 if with_buffer else None
    y = jax.random.randint(ks[3], (16,), 0, 512)
    gk = jax.grad(lambda s_: ops.kd_loss(y, s_, t, b, 2.0, use_pallas=True,
                                         interpret=True))(s)
    gr = jax.grad(lambda s_: ref.kd_loss_mean_ref(
        y, s_, jax.lax.stop_gradient(t),
        None if b is None else jax.lax.stop_gradient(b), 2.0))(s)
    np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-6)


def test_kd_loss_extreme_logits_stable():
    """Online logsumexp must survive +/- large logits (padding = -1e30)."""
    s = jnp.array([[100.0, -1e30, 0.0, -50.0] + [0.0] * 252])
    t = jnp.array([[-1e30, 80.0, 1.0, 0.0] + [0.0] * 252])
    y = jnp.array([0])
    got = ops.kd_loss(y, s, t, None, 2.0, use_pallas=True, interpret=True)
    want = ref.kd_loss_mean_ref(y, s, t, None, 2.0)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("b,s,d", [(1, 64, 128), (4, 96, 256), (2, 128, 130)])
def test_rglru_kernel(b, s, d):
    ks = jax.random.split(jax.random.key(b * s), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, d)))
    x = jax.random.normal(ks[1], (b, s, d))
    got = ops.rglru(a, x, use_pallas=True, interpret=True)
    want = ref.rglru_ref(a, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_rglru_kernel_long_seq_carry():
    """Carry must persist across seq chunks (s > chunk)."""
    ks = jax.random.split(jax.random.key(0), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 384, 128)))
    x = jax.random.normal(ks[1], (2, 384, 128))
    got = ops.rglru(a, x, use_pallas=True, interpret=True)
    want = ref.rglru_ref(a, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 4, 32, 16, 64),
    (1, 128, 2, 64, 32, 128),
    (2, 192, 8, 16, 8, 64),
])
def test_ssd_kernel(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.key(s + h), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    yk, sk = ops.ssd(x, dt, A, B, C, chunk, use_pallas=True, interpret=True)
    yr, sr = ref.ssd_ref(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(yk, yr, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(sk, sr, rtol=3e-4, atol=3e-4)


def test_ssd_kernel_state_equals_sequential():
    """Chunked scan == naive sequential recurrence."""
    ks = jax.random.split(jax.random.key(11), 5)
    b, s, h, p, n = 1, 64, 2, 8, 4
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    yk, _ = ops.ssd(x, dt, A, B, C, 16, use_pallas=True, interpret=True)
    # Naive recurrence
    hstate = np.zeros((b, h, p, n))
    ys = np.zeros((b, s, h, p))
    for t_ in range(s):
        dA = np.exp(np.asarray(dt[:, t_]) * np.asarray(A))          # (b,h)
        Bx = np.einsum("bh,bhp,bn->bhpn", np.asarray(dt[:, t_]),
                       np.asarray(x[:, t_]), np.asarray(B[:, t_, 0]))
        hstate = hstate * dA[:, :, None, None] + Bx
        ys[:, t_] = np.einsum("bn,bhpn->bhp", np.asarray(C[:, t_, 0]), hstate)
    np.testing.assert_allclose(yk, ys, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("w,pos", [(256, 3), (256, 255), (128, 300)])
def test_swa_decode_kernel(ring, window, w, pos):
    if not ring and pos >= w:
        pos = w - 1  # contiguous cache: pos must be in range
    ks = jax.random.split(jax.random.key(pos + w), 3)
    b, n, g, d = 2, 2, 4, 32
    q = jax.random.normal(ks[0], (b, n, g, d))
    kc = jax.random.normal(ks[1], (b, w, n, d))
    vc = jax.random.normal(ks[2], (b, w, n, d))
    got = ops.swa_decode_attn(q, kc, vc, jnp.int32(pos), window=window,
                              ring=ring, use_pallas=True, interpret=True)
    want = ref.swa_decode_ref(q, kc, vc, jnp.int32(pos), window=window, ring=ring)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("ring,window", [(False, None), (True, 64),
                                         (True, None)])
def test_swa_decode_vectorized_pos(ring, window):
    """Per-sequence pos (B,) — the serving engine's per-slot decode path —
    must equal per-row scalar-pos calls AND the vectorized jnp oracle."""
    ks = jax.random.split(jax.random.key(42), 3)
    b, w, n, g, d = 3, 128, 2, 4, 32
    pos = jnp.asarray([5, 127, 300] if ring else [5, 60, 127], jnp.int32)
    q = jax.random.normal(ks[0], (b, n, g, d))
    kc = jax.random.normal(ks[1], (b, w, n, d))
    vc = jax.random.normal(ks[2], (b, w, n, d))
    got = ops.swa_decode_attn(q, kc, vc, pos, window=window, ring=ring,
                              use_pallas=True, interpret=True)
    want = ref.swa_decode_ref(q, kc, vc, pos, window=window, ring=ring)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    for i in range(b):
        one = ops.swa_decode_attn(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                                  jnp.int32(int(pos[i])), window=window,
                                  ring=ring, use_pallas=True, interpret=True)
        np.testing.assert_allclose(got[i], one[0], rtol=1e-6, atol=1e-6)


def test_swa_decode_bf16():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 2, 64)).astype(jnp.bfloat16)
    kc = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(jnp.bfloat16)
    got = ops.swa_decode_attn(q, kc, vc, jnp.int32(100), ring=True, window=64,
                              use_pallas=True, interpret=True)
    want = ref.swa_decode_ref(q, kc, vc, jnp.int32(100), window=64, ring=True)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=3e-2, atol=3e-2)


# -- block-paged decode attention (paged serving engine) ----------------------


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("ps,pp", [(8, 4), (16, 8), (64, 2)])
def test_paged_decode_kernel(window, ps, pp):
    ks = jax.random.split(jax.random.key(ps + pp), 4)
    b, n, g, d = 3, 2, 4, 32
    num_pages = b * pp + 1
    q = jax.random.normal(ks[0], (b, n, g, d))
    kp = jax.random.normal(ks[1], (num_pages, ps, n * d))
    vp = jax.random.normal(ks[2], (num_pages, ps, n * d))
    pt = jax.random.randint(ks[3], (b, pp), 0, num_pages).astype(jnp.int32)
    pos = jnp.asarray([0, ps * pp // 2, ps * pp - 1], jnp.int32)
    got = ops.paged_decode_attn(q, kp, vp, pt, pos, window=window,
                                use_pallas=True, interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, pt, pos, window=window)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_paged_decode_identity_table_matches_dense():
    """With an identity page table the paged kernel IS the contiguous-cache
    decode: reshaping a dense (B, W, N, D) cache into pages must reproduce
    swa_decode_ref bit-for-bit math."""
    ks = jax.random.split(jax.random.key(5), 3)
    b, w, n, g, d, ps = 2, 128, 2, 2, 32, 16
    pp = w // ps
    q = jax.random.normal(ks[0], (b, n, g, d))
    kc = jax.random.normal(ks[1], (b, w, n, d))
    vc = jax.random.normal(ks[2], (b, w, n, d))
    # slot b's logical page t -> physical page b*pp + t (+1 for trash at 0)
    kp = jnp.concatenate([jnp.zeros((1, ps, n * d)),
                          kc.reshape(b * pp, ps, n * d)])
    vp = jnp.concatenate([jnp.zeros((1, ps, n * d)),
                          vc.reshape(b * pp, ps, n * d)])
    pt = (1 + jnp.arange(b * pp, dtype=jnp.int32)).reshape(b, pp)
    pos = jnp.asarray([37, 127], jnp.int32)
    got = ops.paged_decode_attn(q, kp, vp, pt, pos, use_pallas=True,
                                interpret=True)
    want = ref.swa_decode_ref(q, kc, vc, pos)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_paged_decode_shared_prefix_pages():
    """Two slots whose tables alias the same physical prefix pages must
    each attend exactly what a private copy of those pages would give —
    prefix sharing is a pure aliasing optimization."""
    ks = jax.random.split(jax.random.key(9), 3)
    b, n, g, d, ps, pp = 2, 2, 2, 32, 8, 4
    num_pages = 16
    q = jax.random.normal(ks[0], (b, n, g, d))
    kp = jax.random.normal(ks[1], (num_pages, ps, n * d))
    vp = jax.random.normal(ks[2], (num_pages, ps, n * d))
    # both slots share physical pages 1, 2 for logical pages 0, 1
    pt_shared = jnp.asarray([[1, 2, 3, 4], [1, 2, 5, 6]], jnp.int32)
    pos = jnp.asarray([ps * 3 - 1, ps * 4 - 1], jnp.int32)
    got = ops.paged_decode_attn(q, kp, vp, pt_shared, pos, use_pallas=True,
                                interpret=True)
    # oracle: materialize each slot's private dense view
    for i in range(b):
        kc = kp[pt_shared[i]].reshape(1, pp * ps, n, d)
        vc = vp[pt_shared[i]].reshape(1, pp * ps, n, d)
        want = ref.swa_decode_ref(q[i:i + 1], kc, vc, pos[i:i + 1])
        np.testing.assert_allclose(got[i:i + 1], want, rtol=3e-5, atol=3e-5)


def test_paged_decode_bf16():
    ks = jax.random.split(jax.random.key(1), 4)
    b, n, g, d, ps, pp = 2, 2, 2, 64, 16, 4
    q = jax.random.normal(ks[0], (b, n, g, d)).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (9, ps, n * d)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (9, ps, n * d)).astype(jnp.bfloat16)
    pt = jax.random.randint(ks[3], (b, pp), 0, 9).astype(jnp.int32)
    pos = jnp.asarray([20, 63], jnp.int32)
    got = ops.paged_decode_attn(q, kp, vp, pt, pos, use_pallas=True,
                                interpret=True)
    want = ref.paged_decode_ref(q, kp, vp, pt, pos)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), rtol=3e-2, atol=3e-2)


def test_paged_decode_reads_the_given_layer_of_stacked_pools():
    """The engine's layer scan hands the kernel every layer's pools and a
    traced layer index: the kernel must attend that layer's pages only."""
    ks = jax.random.split(jax.random.key(3), 4)
    layers, b, n, g, d, ps, pp = 3, 2, 2, 2, 32, 8, 4
    num_pages = b * pp + 1
    q = jax.random.normal(ks[0], (b, n, g, d))
    kp = jax.random.normal(ks[1], (layers, num_pages, ps, n * d))
    vp = jax.random.normal(ks[2], (layers, num_pages, ps, n * d))
    pt = (1 + jax.random.permutation(ks[3], b * pp)).reshape(b, pp)
    pt = pt.astype(jnp.int32)
    pos = jnp.asarray([ps * 2 + 3, ps * pp - 1], jnp.int32)
    attn = jax.jit(lambda layer: ops.paged_decode_attn(
        q, kp, vp, pt, pos, layer=layer, use_pallas=True, interpret=True))
    for layer in range(layers):
        want = ref.paged_decode_ref(q, kp[layer], vp[layer], pt, pos)
        np.testing.assert_allclose(attn(jnp.int32(layer)), want,
                                   rtol=3e-5, atol=3e-5)


# -- dequant-fused KD loss (transport subsystem) ------------------------------


def _quantized_teacher(key, rows, vocab, bits):
    # The kernel consumes the (rows, V) int8 container; int4 ships
    # nibble-packed bytes, unpacked per batch at the call site.
    from repro.transport.codecs import Int4, Int8
    t = jax.random.normal(key, (rows, vocab)) * 3
    codec = Int8() if bits == 8 else Int4()
    p = codec.encode(t)
    return t, codec.unpack_codes(p["codes"], vocab), p["scale"], p["zero"]


@pytest.mark.parametrize("rows,vocab", [(8, 256), (6, 200), (32, 1024)])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("with_buffer", [False, True])
def test_kd_loss_quant_forward(rows, vocab, bits, with_buffer):
    """The fused kernel dequantizes in-tile; it must match the jnp path
    (dequantize, then the reference loss) including odd vocabs that pad to
    the 128-lane tile — the padded columns are masked by the static vocab,
    not by a sentinel code."""
    ks = jax.random.split(jax.random.key(rows + vocab + bits), 4)
    s = jax.random.normal(ks[0], (rows, vocab)) * 3
    t, codes, scale, zero = _quantized_teacher(ks[1], rows, vocab, bits)
    b = jax.random.normal(ks[2], (rows, vocab)) * 3 if with_buffer else None
    y = jax.random.randint(ks[3], (rows,), 0, vocab)
    got = ops.kd_loss_quant(y, s, codes, scale, zero, b, 2.0,
                            use_pallas=True, interpret=True)
    want = ops.kd_loss_quant(y, s, codes, scale, zero, b, 2.0,
                             use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-5)
    assert np.isfinite(float(got))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("with_buffer", [False, True])
def test_kd_loss_quant_grad_matches_autodiff(bits, with_buffer):
    ks = jax.random.split(jax.random.key(11 + bits), 4)
    rows, vocab = 16, 384
    s = jax.random.normal(ks[0], (rows, vocab)) * 2
    _, codes, scale, zero = _quantized_teacher(ks[1], rows, vocab, bits)
    b = jax.random.normal(ks[2], (rows, vocab)) * 2 if with_buffer else None
    y = jax.random.randint(ks[3], (rows,), 0, vocab)
    gk = jax.grad(lambda s_: ops.kd_loss_quant(
        y, s_, codes, scale, zero, b, 2.0, use_pallas=True,
        interpret=True))(s)
    gr = jax.grad(lambda s_: ops.kd_loss_quant(
        y, s_, codes, scale, zero, b, 2.0, use_pallas=False))(s)
    np.testing.assert_allclose(gk, gr, rtol=2e-4, atol=1e-6)
    # Frozen operands: no gradient flows into the wire payload.
    gz = jax.grad(lambda z_: ops.kd_loss_quant(
        y, s, codes, scale, z_, b, 2.0, use_pallas=True, interpret=True))(zero)
    np.testing.assert_allclose(gz, np.zeros_like(gz), atol=0)


def test_kd_loss_quant_equals_dequantized_kd_loss():
    """Dequantizing on the host and calling the plain fused kernel must give
    the same loss as the dequant-fused kernel — the fusion changes memory
    traffic, not math."""
    ks = jax.random.split(jax.random.key(5), 3)
    rows, vocab = 8, 256
    s = jax.random.normal(ks[0], (rows, vocab)) * 3
    t, codes, scale, zero = _quantized_teacher(ks[1], rows, vocab, 8)
    y = jax.random.randint(ks[2], (rows,), 0, vocab)
    deq = codes.astype(jnp.float32) * scale[:, None] + zero[:, None]
    got = ops.kd_loss_quant(y, s, codes, scale, zero, None, 2.0,
                            use_pallas=True, interpret=True)
    want = ops.kd_loss(y, s, deq, None, 2.0, use_pallas=True, interpret=True)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-5)
