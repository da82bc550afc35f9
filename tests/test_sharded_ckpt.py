"""GSPMD-sharded flash checkpoint: shm-save a globally sharded
TrainState, persist via the agent saver, restore at a DIFFERENT mesh
shape (re-shard on load) — the reference capability of
``fsdp_engine.py:568`` (SharedMemoryWriter/Reader) done the JAX way."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    SaverConfig,
)
from dlrover_tpu.checkpoint.sharded import (
    assemble_shard,
    index_ranges,
    local_shards,
)
from dlrover_tpu.common.constants import CheckpointConstant


@pytest.fixture()
def saver(tmp_path):
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(
        SaverConfig(
            checkpoint_dir=str(tmp_path), local_shard_num=1,
            global_shard_num=1, node_rank=0,
        )
    )
    AsyncCheckpointSaver._instance = s
    yield s
    AsyncCheckpointSaver.reset()


def _mesh(shape, axes):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, axes)


def _sharded_state(mesh, spec_w=P("fsdp"), spec_b=P()):
    w = jnp.arange(64 * 4, dtype=jnp.float32).reshape(64, 4)
    b = jnp.arange(8, dtype=jnp.float32)
    return {
        "params": {
            "w": jax.device_put(w, NamedSharding(mesh, spec_w)),
            "b": jax.device_put(b, NamedSharding(mesh, spec_b)),
        },
        "step": 5,
    }


def test_local_shards_dedup_replicated():
    mesh = _mesh((8,), ("fsdp",))
    x = jnp.ones((16, 4))
    replicated = jax.device_put(x, NamedSharding(mesh, P()))
    shards = local_shards(replicated)
    assert len(shards) == 1
    assert shards[0][0] == ((0, 16), (0, 4))
    sharded = jax.device_put(x, NamedSharding(mesh, P("fsdp")))
    shards = local_shards(sharded)
    assert len(shards) == 8
    assert sorted(r[0] for r, _ in shards) == [
        (i * 2, i * 2 + 2) for i in range(8)
    ]


def test_assemble_shard_overlaps():
    entries = [
        (((0, 2), (0, 4)), np.full((2, 4), 1.0)),
        (((2, 4), (0, 4)), np.full((2, 4), 2.0)),
    ]
    out = assemble_shard(((1, 3), (0, 4)), np.float32, entries)
    np.testing.assert_array_equal(out[0], np.full(4, 1.0))
    np.testing.assert_array_equal(out[1], np.full(4, 2.0))
    # incomplete coverage -> None
    assert assemble_shard(((0, 5), (0, 4)), np.float32, entries) is None


def test_shm_sharded_roundtrip_same_mesh(saver, tmp_path):
    mesh = _mesh((8,), ("fsdp",))
    state = _sharded_state(mesh)
    engine = CheckpointEngine(
        str(tmp_path), replicated=False, local_rank=0, global_rank=0,
        world_size=1,
    )
    assert engine.save(5, state)
    target = jax.tree.map(
        lambda x: jnp.zeros_like(x) if isinstance(x, jax.Array) else x,
        state,
    )
    step, restored = engine.load_sharded(target)
    assert step == 5
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(state["params"]["w"]),
    )
    assert restored["params"]["w"].sharding.is_equivalent_to(
        target["params"]["w"].sharding, 2
    )
    engine.close()


def test_storage_sharded_restore_at_different_mesh(saver, tmp_path):
    """Save on {fsdp:8}, kill the trainer's shm, restore on
    {data:2, fsdp:4} with different PartitionSpecs."""
    mesh1 = _mesh((8,), ("fsdp",))
    state = _sharded_state(mesh1)
    engine = CheckpointEngine(
        str(tmp_path), replicated=False, local_rank=0, global_rank=0,
        world_size=1,
    )
    assert engine.save(5, state, persist=True)
    assert engine.wait_async(timeout=60.0)
    tracker = os.path.join(str(tmp_path), CheckpointConstant.TRACKER_FILE)
    deadline = time.time() + 30
    while time.time() < deadline and not os.path.exists(tracker):
        time.sleep(0.1)
    assert os.path.exists(tracker)
    # trainer dies: shm snapshot gone
    engine._shm_handler.unlink()
    engine.close()

    mesh2 = _mesh((2, 4), ("data", "fsdp"))
    target = {
        "params": {
            "w": jax.device_put(
                jnp.zeros((64, 4)),
                NamedSharding(mesh2, P(("data", "fsdp"))),
            ),
            "b": jax.device_put(
                jnp.zeros(8), NamedSharding(mesh2, P("fsdp"))
            ),
        },
        "step": 0,
    }
    engine2 = CheckpointEngine(
        str(tmp_path), replicated=False, local_rank=0, global_rank=0,
        world_size=1,
    )
    step, restored = engine2.load_sharded(target)
    assert step == 5
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.arange(64 * 4, dtype=np.float32).reshape(64, 4),
    )
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["b"]),
        np.arange(8, dtype=np.float32),
    )
    assert restored["params"]["w"].sharding.is_equivalent_to(
        target["params"]["w"].sharding, 2
    )
    assert restored["step"] == 5
    engine2.close()


def test_orbax_fallback_when_storage_empty(saver, tmp_path):
    """No shm, no flash storage: load_sharded falls through to the
    orbax tier."""
    from dlrover_tpu.checkpoint.orbax_compat import GlobalCheckpointer

    mesh = _mesh((8,), ("fsdp",))
    state = _sharded_state(mesh)
    orbax_dir = str(tmp_path / "orbax")
    ckptr = GlobalCheckpointer(orbax_dir)
    ckptr.save(7, state, wait=True)
    ckptr.close()

    engine = CheckpointEngine(
        str(tmp_path / "flash"), replicated=False, local_rank=0,
        global_rank=0, world_size=1,
    )
    target = jax.tree.map(
        lambda x: jnp.zeros_like(x) if isinstance(x, jax.Array) else x,
        state,
    )
    step, restored = engine.load_sharded(target, orbax_dir=orbax_dir)
    assert step == 7
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(state["params"]["w"]),
    )
    engine.close()


def test_checkpointer_orbax_tier_roundtrip(saver, tmp_path):
    """Checkpointer writes every Nth save through the orbax tier and
    load_checkpoint(target) falls back to it when the flash tier is
    gone (the two-tier deployment shape)."""
    from dlrover_tpu.checkpoint.checkpointer import Checkpointer

    mesh = _mesh((8,), ("fsdp",))
    state = _sharded_state(mesh)
    ckpt = Checkpointer(
        str(tmp_path / "flash"), replicated=False,
        local_rank=0, global_rank=0, world_size=1,
        orbax_dir=str(tmp_path / "orbax"), orbax_every=2,
    )
    assert ckpt.save_checkpoint(2, state)  # orbax tier fires (2 % 2)
    ckpt._engine.wait_async(timeout=60)
    ckpt._orbax_tier().wait()
    ckpt.close()

    # everything flash-tier is wiped (disk AND the persistent shm
    # snapshot, which survives close() by design); restore must come
    # from orbax
    import shutil

    shutil.rmtree(str(tmp_path / "flash"), ignore_errors=True)
    from dlrover_tpu.checkpoint.shm_handler import SharedMemoryHandler

    h = SharedMemoryHandler(0, host=False)
    h.unlink()
    h.close()
    ckpt2 = Checkpointer(
        str(tmp_path / "flash2"), replicated=False,
        local_rank=0, global_rank=0, world_size=1,
        orbax_dir=str(tmp_path / "orbax"),
    )
    target = jax.tree.map(
        lambda x: jnp.zeros_like(x) if isinstance(x, jax.Array) else x,
        state,
    )
    step, restored = ckpt2.load_checkpoint(target_state=target)
    assert step == 2
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(state["params"]["w"]),
    )
    ckpt2.close()
