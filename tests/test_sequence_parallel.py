"""Sequence/context parallelism tests on the 8-device CPU mesh:
Ulysses all-to-all attention and ring attention match single-device
full attention, forward and gradient."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dlrover_tpu.ops.attention import xla_causal_attention
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.parallel.sequence import ring_attention, ulysses_attention


@pytest.fixture(scope="module")
def sp_mesh():
    return build_mesh(MeshConfig(data=-1, sequence=4))


def _qkv(b=2, s=64, h=4, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), dtype=jnp.float32) * 0.5
        for k in ks
    )


def _shard(x, mesh):
    return jax.device_put(
        x, NamedSharding(mesh, P(None, "sequence", None, None))
    )


def test_ulysses_matches_full_attention(sp_mesh):
    q, k, v = _qkv()
    ref = xla_causal_attention(q, k, v, dtype=jnp.float32)
    qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
    out = ulysses_attention(
        xla_causal_attention, qs, ks, vs, sp_mesh, dtype=jnp.float32
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_ring_matches_full_attention(sp_mesh):
    q, k, v = _qkv(seed=1)
    ref = xla_causal_attention(q, k, v, dtype=jnp.float32)
    qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, sp_mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_ring_noncausal(sp_mesh):
    q, k, v = _qkv(seed=2)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    probs = jax.nn.softmax(logits, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, sp_mesh, causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_ring_gradients_match(sp_mesh):
    q, k, v = _qkv(b=2, s=32, h=2, d=8, seed=3)

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v, dtype=jnp.float32) ** 2).sum()

    def loss_ring(q, k, v):
        qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
        return (ring_attention(qs, ks, vs, sp_mesh) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for gr, gg, name in zip(g_ref, g_ring, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(gr), atol=1e-4, rtol=1e-4,
            err_msg=f"ring grad mismatch for {name}",
        )


def test_ulysses_gradients_match(sp_mesh):
    q, k, v = _qkv(b=2, s=32, h=4, d=8, seed=4)

    def loss_ref(q, k, v):
        return (xla_causal_attention(q, k, v, dtype=jnp.float32) ** 2).sum()

    def loss_sp(q, k, v):
        qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
        out = ulysses_attention(
            xla_causal_attention, qs, ks, vs, sp_mesh,
            dtype=jnp.float32,
        )
        return (out ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_sp = jax.grad(loss_sp, argnums=(0, 1, 2))(q, k, v)
    for gr, gg, name in zip(g_ref, g_sp, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(gr), atol=1e-4, rtol=1e-4,
            err_msg=f"ulysses grad mismatch for {name}",
        )


def test_long_context_ring_runs(sp_mesh):
    """Ring attention on a sequence 4x the per-device block."""
    q, k, v = _qkv(b=2, s=512, h=2, d=16, seed=5)
    qs, ks, vs = (_shard(x, sp_mesh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, sp_mesh)
    assert out.shape == q.shape
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("kv_heads,batch", [(4, 4), (2, 4), (4, 3)])
def test_flash_runs_per_shard_of_the_steps_mesh(kv_heads, batch):
    """The flash kernel under a step built for a mesh: batch over
    data x fsdp, heads over tensor (GQA keeps each q head with its kv
    head; a batch the axes do not divide stays replicated), forward
    and gradient equal to the kernel on the whole arrays."""
    from dlrover_tpu.models.layers import attention
    from dlrover_tpu.ops.flash_attention import flash_attention
    from dlrover_tpu.parallel.mesh import scoped_to_mesh

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    q, _, _ = _qkv(b=batch, s=128, h=4, d=16, seed=5)
    _, k, v = _qkv(b=batch, s=128, h=kv_heads, d=16, seed=6)

    def loss(attn):
        def f(q, k, v):
            return (attn(q, k, v, dtype=jnp.float32) ** 2).sum()

        return jax.value_and_grad(f, argnums=(0, 1, 2))

    ref, g_ref = loss(flash_attention)(q, k, v)
    step = scoped_to_mesh(
        jax.jit(loss(functools.partial(attention, "flash"))), mesh
    )
    assert "sdy.manual_computation" in step.lower(q, k, v).as_text()
    out, g = step(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
        )
