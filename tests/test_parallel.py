"""Parallel-layer tests: mesh building, collectives, ring attention,
sharded data-parallel executor (runs on the 8-virtual-CPU-device mesh)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu.parallel import (build_mesh, data_sharding, replicated,
                                all_reduce, all_gather, reduce_scatter,
                                shard_map)
from mxnet_tpu.parallel.ring_attention import (attention, ring_attention,
                                               ring_attention_sharded)

pytestmark = pytest.mark.skipif(
    len(jax.devices("cpu")) < 8, reason="needs 8 virtual cpu devices")


def _cpu_devices():
    return jax.devices("cpu")


def test_build_mesh_axes():
    mesh = build_mesh(data=4, model=2, devices=_cpu_devices())
    assert mesh.shape["data"] == 4
    assert mesh.shape["model"] == 2
    mesh1 = build_mesh(devices=_cpu_devices())
    assert mesh1.shape["data"] == 8


def test_sharded_psum():
    mesh = build_mesh(data=8, devices=_cpu_devices())

    @shard_map(mesh=mesh, in_specs=P("data"), out_specs=P())
    def total(x):
        return all_reduce(jnp.sum(x), "data")

    x = jnp.arange(64, dtype=jnp.float32)
    out = total(jax.device_put(x, NamedSharding(mesh, P("data"))))
    assert float(out) == x.sum()


def test_all_gather_reduce_scatter():
    mesh = build_mesh(data=4, devices=_cpu_devices())

    @shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def ag_rs(x):
        full = all_gather(x, "data")            # (16,)
        return reduce_scatter(full, "data")     # each gets sum-of-shards
    x = jnp.arange(16, dtype=jnp.float32)
    out = ag_rs(jax.device_put(x, NamedSharding(mesh, P("data"))))
    # all_gather tiles to full vector, psum_scatter sums the 4 copies of
    # each position group -> 4x the original shard values reassembled
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 4)


def test_ring_attention_matches_full():
    mesh = build_mesh(seq=8, devices=_cpu_devices())
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 3, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    expect = attention(q, k, v)
    with mesh:
        got = jax.jit(lambda *a: ring_attention_sharded(*a, mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal():
    mesh = build_mesh(seq=4, devices=_cpu_devices())
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 16, 4
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    expect = attention(q, k, v, causal=True)
    with mesh:
        got = jax.jit(lambda *a: ring_attention_sharded(
            *a, mesh, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grads():
    """Ring attention must be differentiable (it sits in training graphs)."""
    mesh = build_mesh(seq=4, devices=_cpu_devices())
    rng = np.random.RandomState(2)
    B, H, T, D = 1, 1, 8, 4
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def loss_full(q, k, v):
        return jnp.sum(attention(q, k, v) ** 2)

    spec = P(None, None, "seq", None)

    @jax.jit
    def loss_ring(q, k, v):
        @shard_map(mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        def att(qs, ks, vs):
            return ring_attention(qs, ks, vs, axis_name="seq")
        return jnp.sum(att(q, k, v) ** 2)

    g_full = jax.grad(loss_full)(q, k, v)
    with mesh:
        g_ring = jax.grad(loss_ring)(
            jax.device_put(q, NamedSharding(mesh, spec)),
            jax.device_put(k, NamedSharding(mesh, spec)),
            jax.device_put(v, NamedSharding(mesh, spec)))
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_grads(causal):
    """Flash-ring backward (custom_vjp recomputing through the XLA ring)
    must match full-attention gradients — locks in what was previously
    only hand-verified. Traced as one program, as a training graph runs
    it: op by op a ``shard_map`` ring compiles every primitive of its
    gradient by itself (85 s of the gate a case, ROADMAP D22)."""
    mesh = build_mesh(seq=4, devices=_cpu_devices()[:4])
    rng = np.random.RandomState(5)
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def loss_full(q, k, v):
        return jnp.sum(attention(q, k, v, causal=causal) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(
            ring_attention_sharded(q, k, v, mesh, causal=causal,
                                   use_flash=True) ** 2)

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    with mesh:
        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=1e-3, atol=1e-4)


def test_mesh_scope():
    from mxnet_tpu.parallel import current_mesh, mesh_scope
    mesh = build_mesh(data=2, devices=_cpu_devices())
    assert current_mesh() is None
    with mesh_scope(mesh):
        assert current_mesh() is mesh
    assert current_mesh() is None


def test_ring_attention_flash_block_matches_full():
    """Flash-kernel ring (Pallas local block, interpret mode on this CPU
    mesh via check_vma=False) must match full attention exactly like the
    XLA-block ring does."""
    mesh = build_mesh(seq=4, devices=_cpu_devices()[:4])
    rng = np.random.RandomState(3)
    B, H, T, D = 2, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    expect = attention(q, k, v)
    with mesh:
        got = jax.jit(lambda *a: ring_attention_sharded(
            *a, mesh, use_flash=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_flash_block_causal():
    """Causal flash ring: static per-step offsets + wrapped-shard gating
    must reproduce the absolute-position mask exactly."""
    mesh = build_mesh(seq=4, devices=_cpu_devices()[:4])
    rng = np.random.RandomState(4)
    B, H, T, D = 1, 2, 16, 4
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    expect = attention(q, k, v, causal=True)
    with mesh:
        got = jax.jit(lambda *a: ring_attention_sharded(
            *a, mesh, causal=True, use_flash=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)
