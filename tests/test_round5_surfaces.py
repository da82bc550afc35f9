"""Direct tests for round-5 surfaces that are otherwise covered only
end-to-end: scoped activation constraints, mesh permutedness, the
bf16-moment adam recipe, and KvVariable spill re-enable semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dlrover_tpu.parallel.mesh import (
    activation_constraint_mesh,
    get_activation_constraint_mesh,
    mesh_is_permuted,
)
from dlrover_tpu.parallel.sharding import constrain_activation


def _mesh(order):
    devs = np.array(jax.devices()[:8])[order].reshape(2, 4)
    return Mesh(devs, ("data", "fsdp"))


def test_mesh_is_permuted_detects_order():
    assert not mesh_is_permuted(_mesh(np.arange(8)))
    assert mesh_is_permuted(_mesh(np.arange(8)[::-1]))


def test_activation_constraint_scope_nesting():
    m1, m2 = _mesh(np.arange(8)), _mesh(np.arange(8)[::-1])
    assert get_activation_constraint_mesh() is None
    with activation_constraint_mesh(m1):
        assert get_activation_constraint_mesh() is m1
        with activation_constraint_mesh(m2):
            assert get_activation_constraint_mesh() is m2
        assert get_activation_constraint_mesh() is m1
    assert get_activation_constraint_mesh() is None


def test_constrain_activation_noop_outside_scope_and_on_iota():
    x = jnp.ones((8, 4))
    # no scope: identity (a computation traced under another mesh
    # must not inherit training constraints)
    assert constrain_activation(x) is x
    # iota mesh in scope: propagation handles it; still identity
    with activation_constraint_mesh(_mesh(np.arange(8))):
        assert constrain_activation(x) is x


def test_constrain_activation_applies_on_permuted_mesh():
    mesh = _mesh(np.arange(8)[::-1])
    x = jnp.ones((8, 4))
    with activation_constraint_mesh(mesh):
        with mesh:
            y = jax.jit(constrain_activation)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    # the constraint actually landed: output sharded over the batch
    # axes of the permuted mesh
    assert "data" in str(y.sharding.spec)


def test_adamw_bf16_moment_dtype_and_convergence():
    from dlrover_tpu.optim import adamw_bf16

    params = {"w": jnp.zeros((4,), jnp.float32)}
    target = jnp.asarray([1.0, -2.0, 3.0, 0.5])

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    opt = adamw_bf16(0.1)
    state = opt.init(params)
    mus = [
        l for l in jax.tree_util.tree_leaves(state)
        if hasattr(l, "dtype") and l.dtype == jnp.bfloat16
    ]
    assert mus, "no bf16 moment found in the optimizer state"
    for _ in range(200):
        g = jax.grad(loss)(params)
        updates, state = opt.update(g, state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
    np.testing.assert_allclose(
        np.asarray(params["w"]), np.asarray(target), atol=0.05
    )


def test_spill_reenable_same_path_adjusts_budget(tmp_path):
    from dlrover_tpu.ops.kv_variable import KvVariable

    t = KvVariable(dim=4, initial_capacity=32)
    keys = np.arange(300, dtype=np.int64)
    t.gather(keys)
    path = str(tmp_path / "kv.spill")
    t.enable_spill(path, max_dram_rows=200)
    assert t.spill_stats()["dram_rows"] <= 200
    # same path: budget adjustment, disk rows preserved
    t.enable_spill(path, max_dram_rows=100)
    st = t.spill_stats()
    assert st["dram_rows"] <= 100
    assert len(t) == 300
    # different path: refused — replacing the tier would orphan the
    # disk-resident rows
    with pytest.raises(ValueError):
        t.enable_spill(str(tmp_path / "other.spill"), 100)
