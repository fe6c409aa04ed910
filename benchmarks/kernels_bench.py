"""Kernel hot-spot benchmark — the fused buffered-KD loss.

Off the TPU the Pallas kernels run in interpret mode (Python), so wall-clock
favors the jnp reference; the meaningful numbers here are (a) correctness
parity at benchmark scale and (b) the analytic HBM-traffic model that
motivates the fusion (reported as derived columns):

    jnp path  >= 6 full passes over the (rows, V) logits + softmax temps
    kernel    2 passes (fwd stats + bwd), no materialized softmax
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def bench(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main(rows=256, vocab=8192, verbose=True):
    ks = jax.random.split(jax.random.key(0), 4)
    s = jax.random.normal(ks[0], (rows, vocab)) * 2
    t = jax.random.normal(ks[1], (rows, vocab)) * 2
    b = jax.random.normal(ks[2], (rows, vocab)) * 2
    y = jax.random.randint(ks[3], (rows,), 0, vocab)
    tau = 2.0

    grad_ref = jax.jit(jax.grad(lambda s_: ref.kd_loss_mean_ref(
        y, s_, jax.lax.stop_gradient(t), jax.lax.stop_gradient(b), tau)))
    us_ref = bench(grad_ref, s)
    parity = float(jnp.max(jnp.abs(
        ops.kd_loss(y, s, t, b, tau, use_pallas=True)
        - ref.kd_loss_mean_ref(y, s, t, b, tau))))

    # Derived HBM traffic (bytes) per backward step at fp32.
    tensor = rows * vocab * 4
    jnp_traffic = 6 * 3 * tensor      # log_softmax temps + grads, 3 tensors
    kernel_traffic = 2 * 3 * tensor   # one fwd read + one bwd read/write
    print(f"kd_loss_jnp_grad,{us_ref:.0f},rows={rows};vocab={vocab};"
          f"traffic_model_bytes={jnp_traffic}")
    print(f"kd_loss_kernel,{0:.0f},parity_maxerr={parity:.2e};"
          f"traffic_model_bytes={kernel_traffic};"
          f"traffic_ratio={jnp_traffic/kernel_traffic:.1f}x")

    # RG-LRU + SSD kernel parity at bench scale.
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (8, 512, 256)))
    bb = jax.random.normal(ks[1], (8, 512, 256))
    us_rg = bench(jax.jit(ref.rglru_ref), a, bb)
    err = float(jnp.max(jnp.abs(
        ops.rglru(a, bb, use_pallas=True) - ref.rglru_ref(a, bb))))
    print(f"rglru_ref_scan,{us_rg:.0f},shape=8x512x256")
    print(f"rglru_kernel,0,parity_maxerr={err:.2e}")

    x = jax.random.normal(ks[0], (2, 512, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 512, 8)))
    A = -jnp.exp(jax.random.normal(ks[2], (8,)) * 0.3)
    B = jax.random.normal(ks[3], (2, 512, 1, 64)) * 0.5
    C = jax.random.normal(ks[0], (2, 512, 1, 64)) * 0.5
    us_ssd = bench(jax.jit(lambda *a_: ref.ssd_ref(*a_, 128)[0]), x, dt, A, B, C)
    yk, _ = ops.ssd(x, dt, A, B, C, 128, use_pallas=True)
    yr, _ = ref.ssd_ref(x, dt, A, B, C, 128)
    err = float(jnp.max(jnp.abs(yk - yr)))
    print(f"ssd_ref_chunked,{us_ssd:.0f},shape=2x512x8x64")
    print(f"ssd_kernel,0,parity_maxerr={err:.2e}")
    return {"kd_parity": parity}


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
