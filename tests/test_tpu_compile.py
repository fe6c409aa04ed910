"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed on the CPU host and compiles for a chip that
is described, not attached.  It refuses what interpret mode accepts: block
shapes off the (8, 128) tiling, rank-1 blocks that are not lane multiples,
primitives Mosaic cannot lower.  Each test compiles one kernel at the
published widths of the configuration that runs it and asserts that the
kernel survived as a ``tpu_custom_call`` (no silent jnp fallback).  The
paged serving steps are compiled whole, to check that they keep the page
pools in place on one chip and on a 2x2 mesh.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, so describing it while the
module is collected would break the other test workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (ImportError, RuntimeError) as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_compiles_at_granite_decode_shapes(one_chip, dtype):
    # granite-3-2b: 32 query / 8 kv heads of 64; 16-token pages, 4096 pages
    # plus the trash page, 256 pages per slot (a 4096-token slot); the
    # pools of 2 layers, one picked by a traced index as in the layer scan.
    fn = lambda q, k, v, pt, pos, layer: ops.paged_decode_attn(
        q, k, v, pt, pos, layer=layer, use_pallas=True, interpret=False)
    _compile(fn, one_chip, ((4, 8, 4, 64), dtype),
             ((2, 4097, 16, 512), dtype), ((2, 4097, 16, 512), dtype),
             ((4, 256), jnp.int32), ((4,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("step", ["tick", "admit"])
def test_paged_serving_steps_keep_the_pools_in_place(topo, monkeypatch, step,
                                                     shape):
    """The paged engine's decode tick and its admission (with a prefix hit)
    at all 40 granite-3-2b layers, on one chip and sharded over a 2x2
    (data, model) mesh.  They must write and read the donated pools in
    place, the decode kernel per kv-head shard under the mesh: no op
    copies, transposes or gathers a whole pool."""
    import re
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs import registry
    from repro.launch import specs as S
    from repro.serve import engine as E
    # Pick the kernels as a TPU host would.
    monkeypatch.setattr(ops, "default_use_pallas", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda i: bool(i))
    cfg = registry.get_config("granite-3-2b")
    slots, max_len, ps = 4, 2048, 16
    pp = max_len // ps
    pages = slots * pp + 1
    n = int(np.prod(shape))
    mesh = Mesh(np.array(topo.devices[:n]).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shapes, shardings = S.params_shardings(cfg, mesh)
    params = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sh), shapes, shardings)
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    with jax.set_mesh(mesh):
        tokens, caches, pt, pos, budget, active = jax.jit(
            E.make_paged_init_state(cfg, slots, pages, ps, pp)).eval_shape()
        admit, tick = E.make_paged_steps(cfg, max_len,
                                         E.make_sampler("greedy"), ps)
        if step == "tick":
            lowered = tick.lower(params, caches, pt, tokens, pos, budget,
                                 active, key)
        else:
            lowered = admit.lower(
                params, caches, pt, tokens, pos, budget, active,
                i32(slots, 128), i32(slots), i32(slots),
                jax.ShapeDtypeStruct((slots,), jnp.bool_), key, npp=8)
        text = lowered.compile().as_text()
    if step == "tick":
        assert "tpu_custom_call" in text
    width = cfg.kv_heads * cfg.head_dim // shape[1]
    pool = rf"\(?bf16\[{cfg.num_layers},{pages},{ps},{width}\]"
    moved = re.findall(rf"= {pool}\S* (copy|copy-start|transpose|"
                       rf"all-gather|all-gather-start|dynamic-slice|"
                       rf"dynamic-update-slice)\(", text)
    assert not moved, moved


@pytest.mark.parametrize("with_buffer", [False, True])
def test_kd_loss_fwd_grad_compiles_at_granite_vocab(one_chip, with_buffer):
    # 4096 token rows against the granite vocab (49155, padded to 49280).
    rows, vocab = 4096, 49155

    def fn(y, s, t, b):
        return jax.value_and_grad(lambda s_: ops.kd_loss(
            y, s_, t, b if with_buffer else None, 2.0, use_pallas=True,
            interpret=False))(s)

    _compile(fn, one_chip, ((rows,), jnp.int32), ((rows, vocab), jnp.float32),
             ((rows, vocab), jnp.float32), ((rows, vocab), jnp.float32))


def test_kd_loss_quant_fwd_grad_compiles_at_granite_vocab(one_chip):
    rows, vocab = 4096, 49155

    def fn(y, s, codes, scale, zero):
        return jax.value_and_grad(lambda s_: ops.kd_loss_quant(
            y, s_, codes, scale, zero, None, 2.0, use_pallas=True,
            interpret=False))(s)

    _compile(fn, one_chip, ((rows,), jnp.int32), ((rows, vocab), jnp.float32),
             ((rows, vocab), jnp.int8), ((rows,), jnp.float32),
             ((rows,), jnp.float32))


def test_ssd_compiles_at_mamba2_370m_widths(one_chip):
    # mamba2-370m: d_inner 2048 = 32 heads of 64, d_state 128, one B/C
    # group, chunk 128.
    b, s, h, p, n, chunk = 2, 2048, 32, 64, 128, 128
    fn = lambda x, dt, A, B, C: ops.ssd(x, dt, A, B, C, chunk,
                                        use_pallas=True, interpret=False)
    _compile(fn, one_chip, ((b, s, h, p), jnp.bfloat16), ((b, s, h), jnp.float32),
             ((h,), jnp.float32), ((b, s, 1, n), jnp.bfloat16),
             ((b, s, 1, n), jnp.bfloat16))


def test_rglru_compiles_at_recurrentgemma_width(one_chip):
    # recurrentgemma-9b: d_rnn 4096.
    fn = lambda a, x: ops.rglru(a, x, use_pallas=True, interpret=False)
    _compile(fn, one_chip, ((2, 2048, 4096), jnp.float32),
             ((2, 2048, 4096), jnp.float32))


def test_kd_loss_fwd_grad_compiles_sharded_over_a_2x2_mesh(topo):
    """Mosaic kernels are not auto-partitioned: under a 2x2 mesh with the
    logits' vocab on the model axis (as the Phase-2 step lays them out) the
    fused loss must still compile, as one kernel per row shard."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rows, vocab = 2048, 49408
    row = NamedSharding(mesh, P("data"))
    logit = NamedSharding(mesh, P("data", "model"))

    def fn(y, s, t, b):
        return jax.value_and_grad(lambda s_: ops.kd_loss(
            y, s_, t, b, 2.0, use_pallas=True, interpret=False))(s)

    args = [jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=row)] + [
        jax.ShapeDtypeStruct((rows, vocab), jnp.float32, sharding=logit)] * 3
    with jax.set_mesh(mesh):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
