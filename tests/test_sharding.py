"""Logical sharding rules: divisibility fallback, uniqueness, multi-axis batch."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import DEFAULT_RULES, logical_to_spec


@pytest.fixture(scope="module")
def meshes():
    # abstract meshes over the real (1-device) CPU
    from jax.sharding import AbstractMesh
    single = AbstractMesh((16, 16), ("data", "model"))
    multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return single, multi


def test_vocab_shards_over_model(meshes):
    single, _ = meshes
    spec = logical_to_spec(("vocab", "embed"), (152064, 8192), single)
    assert spec == P("model", "data")


def test_heads_fallback_when_not_divisible(meshes):
    single, _ = meshes
    # qwen3: 40 heads not divisible by 16 -> replicate heads; embed still FSDP
    spec = logical_to_spec(("embed", "heads", "head_dim"), (5120, 40, 128), single)
    assert spec == P("data", None, None)
    # 64 heads shard fine
    spec = logical_to_spec(("embed", "heads", "head_dim"), (8192, 64, 128), single)
    assert spec == P("data", "model", None)


def test_kv_heads_replicate_under_gqa(meshes):
    single, _ = meshes
    spec = logical_to_spec(("batch", "kv_seq", "kv_heads", None),
                           (128, 32768, 8, 128), single)
    assert spec == P("data", "model", None, None)


def test_batch_uses_pod_axis_when_present(meshes):
    single, multi = meshes
    assert logical_to_spec(("batch", None), (256, 4096), single) == P("data", None)
    assert logical_to_spec(("batch", None), (256, 4096), multi) == \
        P(("pod", "data"), None)


def test_batch_of_one_replicates_seq_shards(meshes):
    single, _ = meshes
    # long_500k: B=1 -> batch replicated, kv_seq picks up the model axis
    spec = logical_to_spec(("batch", "kv_seq", "kv_heads", None),
                           (1, 524288, 8, 128), single)
    assert spec == P(None, "model", None, None)


def test_no_axis_reuse_within_tensor(meshes):
    single, _ = meshes
    # experts take "model"; a later mlp dim must not reuse it
    spec = logical_to_spec(("experts", "mlp", None), (384, 2048, 4), single)
    assert spec == P("model", None, None)


def test_spec_matches_rank_check(meshes):
    single, _ = meshes
    with pytest.raises(ValueError):
        logical_to_spec(("batch",), (8, 8), single)


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (2, (2, 1)), (4, (2, 2)),
                                     (8, (4, 2)), (256, (16, 16))])
def test_device_mesh_shape(n, shape):
    from repro.launch.mesh import device_mesh_shape
    assert device_mesh_shape(n) == shape


def test_constrain_is_a_no_op_on_manual_axes():
    """Inside a shard_map over every axis the per-shard code has nothing to
    constrain; the logical constraint must not raise there."""
    from repro.launch.mesh import make_test_mesh
    from repro.sharding.rules import constrain
    mesh = make_test_mesh(1)
    x = jax.numpy.ones((4, 8))
    f = jax.shard_map(lambda a: constrain(a, ("batch", None)) * 2, mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
    g = jax.jit(f)
    with jax.set_mesh(mesh):
        np.testing.assert_array_equal(g(x), 2 * np.ones((4, 8)))


def test_fused_kd_loss_runs_over_row_shards_under_a_mesh():
    """Mosaic kernels are not auto-partitioned: under a 2x2 mesh the fused
    KD loss traces through one shard_map that splits the rows over both
    axes (each shard keeps whole vocab rows)."""
    from jax.sharding import AbstractMesh, AxisType
    from repro.kernels import ops
    mesh = AbstractMesh((2, 2), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    s = jax.ShapeDtypeStruct((64, 256), jax.numpy.float32)
    y = jax.ShapeDtypeStruct((64,), jax.numpy.int32)
    with jax.sharding.use_abstract_mesh(mesh):
        jaxpr = jax.make_jaxpr(jax.grad(lambda s_, y_: ops.kd_loss(
            y_, s_, s_, None, 2.0, use_pallas=True, interpret=True)))(s, y)
    text = str(jaxpr)
    assert "shard_map" in text and "('data', 'model')" in text


_SHARDED_KD_LOSS = """
import jax, numpy as np
from repro.kernels import ops
from repro.launch.mesh import make_device_mesh
ks = jax.random.split(jax.random.key(0), 4)
s, t, b = (3.0 * jax.random.normal(k, (64, 256)) for k in ks[:3])
y = jax.random.randint(ks[3], (64,), 0, 256)
loss = lambda s_, y_, t_, b_: ops.kd_loss(y_, s_, t_, b_, 2.0, use_pallas=True)
v1, g1 = jax.jit(jax.value_and_grad(loss))(s, y, t, b)
with jax.set_mesh(make_device_mesh(jax.devices())):
    v4, g4 = jax.jit(jax.value_and_grad(loss))(s, y, t, b)
np.testing.assert_allclose(float(v4), float(v1), rtol=1e-6)
np.testing.assert_allclose(np.asarray(g4), np.asarray(g1), rtol=1e-5,
                           atol=1e-6 * float(np.abs(g1).max()))
print("sharded == unsharded")
"""


def test_fused_kd_loss_sharded_over_four_devices_matches_one():
    """The row-sharded fused loss on a 2x2 mesh of host devices gives the
    unsharded value and gradient: a shard whose rows were dropped, doubled
    or sent to the wrong device would move both."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    out = subprocess.run([sys.executable, "-c", _SHARDED_KD_LOSS], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "sharded == unsharded" in out.stdout
