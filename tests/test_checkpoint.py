"""Flash-checkpoint tests: shm handler pytree round-trip, async saver
commit protocol, engine save/load paths, breakpoint save — trainer and
agent sides run in one process over the real unix-socket IPC, the
reference's test pattern (test_ckpt_saver.py)."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    SaverConfig,
    read_last_checkpoint,
)
from dlrover_tpu.checkpoint.shm_handler import (
    CheckpointConfig,
    SharedMemoryHandler,
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


def _state_dict():
    return {
        "params": {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": np.ones(4, dtype=np.float32),
        },
        "opt": {"mu": jnp.zeros((3, 4), dtype=jnp.bfloat16)},
        "step": 7,
        "note": "hello",
    }


def _assert_state_equal(a, b):
    np.testing.assert_allclose(
        np.asarray(a["params"]["w"]), np.asarray(b["params"]["w"])
    )
    np.testing.assert_allclose(
        np.asarray(a["params"]["b"]), np.asarray(b["params"]["b"])
    )
    assert np.asarray(b["opt"]["mu"]).dtype == np.asarray(a["opt"]["mu"]).dtype
    assert b["step"] == a["step"]
    assert b["note"] == a["note"]


def test_shm_handler_roundtrip(saver):
    # trainer-side client handler against the saver's host SharedDict
    handler = SharedMemoryHandler(0, host=False)
    sd = _state_dict()
    handler.save_state_dict(sd, CheckpointConfig(step=7, rank=0))
    cfg, restored = handler.load_state_dict()
    assert cfg.step == 7
    _assert_state_equal(sd, restored)
    handler.close()


def test_engine_save_to_memory_and_restore(saver, tmp_path):
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    sd = _state_dict()
    assert engine.save_to_memory(3, sd)
    step, restored = engine.load()
    assert step == 3
    _assert_state_equal(sd, restored)
    engine.close()


def test_engine_save_to_storage_commit(saver, tmp_path):
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    sd = _state_dict()
    assert engine.save_to_storage(5, sd)
    tracker = os.path.join(str(tmp_path), CheckpointConstant.TRACKER_FILE)
    deadline = time.time() + 30
    while time.time() < deadline and not os.path.exists(tracker):
        time.sleep(0.1)
    assert os.path.exists(tracker)
    with open(tracker) as f:
        assert int(f.read().strip()) == 5
    step, shards = read_last_checkpoint(str(tmp_path))
    assert step == 5 and 0 in shards
    engine.close()


def test_storage_load_after_shm_gone(saver, tmp_path):
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    sd = _state_dict()
    engine.save_to_storage(9, sd)
    deadline = time.time() + 30
    tracker = os.path.join(str(tmp_path), CheckpointConstant.TRACKER_FILE)
    while time.time() < deadline and not os.path.exists(tracker):
        time.sleep(0.1)
    step, restored = engine.load_from_storage()
    assert step == 9
    _assert_state_equal(sd, restored)
    engine.close()


def test_breakpoint_save(saver, tmp_path):
    """Simulates a trainer that wrote shm but died before persisting:
    the agent's breakpoint hook must persist the snapshot."""
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    engine.save_to_memory(11, _state_dict())
    AsyncCheckpointSaver.save_shm_to_storage()
    step, shards = read_last_checkpoint(str(tmp_path))
    assert step == 11 and 0 in shards
    engine.close()


def test_checkpointer_api(saver, tmp_path):
    ckpt = Checkpointer(
        str(tmp_path), local_rank=0, global_rank=0, world_size=1
    )
    sd = _state_dict()
    assert ckpt.save_checkpoint(2, sd, storage_type=StorageType.MEMORY)
    step, restored = ckpt.load_checkpoint()
    assert step == 2
    _assert_state_equal(sd, restored)
    ckpt.close()


def test_deletion_keeps_latest(tmp_path):
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(
        SaverConfig(
            checkpoint_dir=str(tmp_path), local_shard_num=1,
            global_shard_num=1, node_rank=0, deletion_keep_latest=2,
        )
    )
    AsyncCheckpointSaver._instance = s
    try:
        engine = CheckpointEngine(
            str(tmp_path), replicated=True, local_rank=0, global_rank=0,
            world_size=1,
        )
        for step in (1, 2, 3):
            engine.save_to_memory(step, _state_dict())
            s.save_step_checkpoint(step)
        dirs = [
            d for d in os.listdir(str(tmp_path))
            if d.startswith(CheckpointConstant.CKPT_NAME_PREFIX)
        ]
        assert sorted(dirs) == ["checkpoint-2", "checkpoint-3"]
        engine.close()
    finally:
        AsyncCheckpointSaver.reset()


def test_snapshot_save_stall_and_integrity(saver, tmp_path):
    """The snapshot route of a flash save must (a) return without doing the
    host copy inline and (b) write a snapshot immune to later updates
    of the training state (on-device copy guards against donation)."""
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    sd = _state_dict()
    assert engine.save_to_storage(4, sd)
    # mutate what the caller holds immediately after the call returns;
    # the snapshot already copied on-device so it must keep step-4 data
    sd["params"]["b"][:] = -123.0
    assert engine.wait_async(timeout=30.0)
    assert engine._last_async_error is None
    step, restored = engine.load()
    assert step == 4
    np.testing.assert_allclose(
        np.asarray(restored["params"]["w"]),
        np.arange(12, dtype=np.float32).reshape(3, 4),
    )
    np.testing.assert_allclose(
        np.asarray(restored["params"]["b"]), np.ones(4, dtype=np.float32)
    )
    engine.close()


def test_snapshot_save_skips_when_busy(saver, tmp_path):
    import threading

    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    sd = _state_dict()
    # block the writer deterministically: monkeypatch save_to_memory to
    # wait on a gate, then prove a save issued meanwhile is skipped
    gate = threading.Event()
    orig = engine.save_to_memory

    def gated(step, state, path="", **kw):
        gate.wait(timeout=30.0)
        return orig(step, state, path, **kw)

    engine.save_to_memory = gated
    assert engine.save_to_storage(2, sd)  # writer now blocked on gate
    assert engine.save_to_storage(3, sd) is False  # busy -> skipped
    gate.set()
    assert engine.wait_async(timeout=30.0)
    engine.save_to_memory = orig
    # writer idle again: next save is accepted
    assert engine.save_to_storage(4, sd)
    assert engine.wait_async(timeout=30.0)
    step, _ = engine.load()
    assert step == 4
    engine.close()


def test_fastcopy_gil_release_and_correctness():
    """The native copy matches numpy and keeps other threads running
    during a large transfer (the GIL-starvation fix)."""
    import threading
    import time as _time

    from dlrover_tpu.ops.fastcopy import _load, copy_into

    src = np.random.default_rng(0).normal(size=(400, 1024, 64)).astype(
        np.float32
    )  # ~100 MB
    dst = np.empty_like(src)
    copy_into(dst, src)
    np.testing.assert_array_equal(dst, src)

    if _load() is None:
        pytest.skip("no native toolchain")
    # tick thread must keep running while the copy is in flight
    ticks = []
    stop = threading.Event()

    def ticker():
        while not stop.is_set():
            ticks.append(_time.perf_counter())
            _time.sleep(0.001)

    t = threading.Thread(target=ticker, daemon=True)
    t.start()
    _time.sleep(0.02)
    t0 = _time.perf_counter()
    for _ in range(5):
        copy_into(dst, src)
    elapsed = _time.perf_counter() - t0
    stop.set()
    t.join(timeout=2)
    during = [x for x in ticks if t0 <= x <= t0 + elapsed]
    # with the GIL released the ticker runs throughout the copies
    assert len(during) >= max(3, int(elapsed / 0.01)), (
        len(during), elapsed
    )


def test_restore_to_template_rebuilds_optax_state(saver, tmp_path):
    """Flash restores come back as plain dicts; restore_to_template
    rebuilds optax tuples/NamedTuples and re-places shardings."""
    import optax

    from dlrover_tpu.checkpoint.checkpointer import (
        restore_to_template,
    )

    params = {"w": jnp.arange(6.0).reshape(2, 3)}
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    assert engine.save_to_memory(
        1, {"params": params, "opt_state": opt_state}
    )
    step, restored = engine.load()
    assert step == 1
    rebuilt = restore_to_template(opt_state, restored["opt_state"])
    # same tree structure as the live optax state
    assert jax.tree_util.tree_structure(
        rebuilt
    ) == jax.tree_util.tree_structure(opt_state)
    # usable in an update without errors
    g = {"w": jnp.ones((2, 3))}
    updates, _ = opt.update(g, rebuilt, params)
    assert jax.tree_util.tree_leaves(updates)
    # missing leaves fail loudly
    with pytest.raises(KeyError):
        restore_to_template(opt_state, {"nope": {}})
    engine.close()


# -- what a restart reads back from the segment -------------------------


def _mk_adapter(seed=7):
    """One KvVariable table under Adam (its ``m``/``v`` slot tables
    ride along), trained a few steps."""
    from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
    from dlrover_tpu.ops.kv_variable import GroupAdamOptimizer, KvVariable

    t = KvVariable(dim=4, initial_capacity=64, seed=seed, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)
    adapter = SparseStateAdapter(digest=True)
    adapter.register_optimizer(opt)
    return t, opt, adapter


def _train_kv(t, opt, step, n_keys=500, batch=64):
    keys = np.random.default_rng(1000 + step).integers(
        0, n_keys, batch
    ).astype(np.int64)
    opt.apply_gradients(keys, np.tanh(t.gather(keys)) * 0.1)


def _sorted_rows(table):
    k, v, f = table.export()
    order = np.argsort(k, kind="stable")
    return k[order], v[order], f[order]


@pytest.mark.parametrize("workers", ["1", "4"])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_flat_restore_bit_identical(
    saver, tmp_path, monkeypatch, sparse, workers
):
    """What ``save_to_memory`` wrote is what ``load()`` hands back,
    bit for bit: a leaf the device keeps column-major (the strided
    native pass, split over ``DLROVER_SAVE_WORKERS`` threads above a
    MiB), a bf16 leaf, host leaves, scalars, and with ``sparse`` the
    KvVariable table and its Adam slots."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from dlrover_tpu.checkpoint.sparse import KV_STATE_KEY, rows_digest

    monkeypatch.setenv("DLROVER_SAVE_WORKERS", workers)
    one = SingleDeviceSharding(jax.devices()[0])
    rng = np.random.default_rng(11)
    w = rng.normal(size=(1030, 300)).astype(np.float32)  # > 1 MiB
    state = {
        "params": {
            "w_col_major": jax.device_put(
                w, Format(Layout(major_to_minor=(1, 0)), one)
            ),
            "mu_bf16": jnp.asarray(
                rng.normal(size=(37, 129)), dtype=jnp.bfloat16
            ),
            "b": jnp.asarray(rng.normal(size=(129,)), jnp.float32),
        },
        "host": rng.integers(0, 255, size=(5, 6, 7)).astype(np.uint8),
        "step": 3,
        "note": "hello",
    }
    assert not jax.device_get(
        state["params"]["w_col_major"]
    ).flags["C_CONTIGUOUS"]
    engine = CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    try:
        if sparse:
            t, opt, adapter = _mk_adapter()
            for step in (1, 2, 3):
                _train_kv(t, opt, step)
            engine.register_sparse(adapter)
            tables = {"emb": t, "emb/m": opt.m, "emb/v": opt.v}
            saved_rows = {n: _sorted_rows(tb) for n, tb in tables.items()}
        assert engine.save_to_memory(3, state)
        if sparse:
            _train_kv(t, opt, 4)  # the live tables move on
            assert rows_digest(*_sorted_rows(t)) != rows_digest(
                *saved_rows["emb"]
            )
        step, back = engine.load()
        assert step == 3 and back["step"] == 3
        assert back["note"] == "hello"
        assert KV_STATE_KEY not in back
        want = jax.device_get(
            {"params": state["params"], "host": state["host"]}
        )
        got = {"params": back["params"], "host": back["host"]}

        def same_bits(path, leaf, other):
            assert other.shape == leaf.shape, path
            assert other.dtype == leaf.dtype, path
            assert other.tobytes() == np.ascontiguousarray(
                leaf
            ).tobytes(), path

        jax.tree_util.tree_map_with_path(same_bits, want, got)
        if sparse:
            for name, table in tables.items():
                k, v, f = _sorted_rows(table)  # rolled back by load()
                ks, vs, fs = saved_rows[name]
                assert rows_digest(k, v, f) == rows_digest(ks, vs, fs), name
                np.testing.assert_array_equal(k, ks, err_msg=name)
                assert v.tobytes() == vs.tobytes(), name
                np.testing.assert_array_equal(f, fs, err_msg=name)
    finally:
        engine.close()


_KILLED_WRITER = r"""
import os, signal, sys, time
import numpy as np

ckpt_dir, kill_at = sys.argv[1], int(sys.argv[2])

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import read_last_checkpoint
from dlrover_tpu.ops import fastcopy

def state(step):
    return {"a": np.full((300,), float(step), np.float32),
            "b": np.full((32, 8), float(step), np.float32),
            "c": np.full((64,), step, np.int32),
            "step": step}

engine = CheckpointEngine(
    ckpt_dir, replicated=True, local_rank=0, global_rank=0, world_size=1,
)
assert engine.save_to_storage(1, state(1))
deadline = time.time() + 60
while read_last_checkpoint(ckpt_dir)[0] != 1:
    assert time.time() < deadline, "step 1 never committed"
    time.sleep(0.05)

real, calls = fastcopy.copy_into, 0

def dying(dst, src):
    global calls
    calls += 1
    if calls == kill_at:
        os.kill(os.getpid(), signal.SIGKILL)
    return real(dst, src)

fastcopy.copy_into = dying
engine.save_to_memory(2, state(2))
sys.exit(7)  # unreachable: the kill lands inside the save
"""


@pytest.mark.parametrize("reader", ["load_state_dict", "read_raw"])
def test_sigkill_mid_flat_write_is_refused(saver, tmp_path, reader):
    """A trainer SIGKILLed inside the second leaf copy of a save dies
    between the two meta publishes: the segment holds half of step 2
    over step 1.  Neither the restore path nor the agent's persist
    path may take it, and a restart falls back to committed step 1."""
    import dlrover_tpu

    AsyncCheckpointSaver.start_async_saving_ckpt()  # the agent's factory
    script = tmp_path / "killed_writer.py"
    script.write_text(_KILLED_WRITER)
    ckpt_dir = str(tmp_path)
    pkg_root = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=pkg_root + os.pathsep + os.environ.get(
            "PYTHONPATH", ""
        ),
    )
    child = subprocess.run(  # noqa: S603
        [sys.executable, str(script), ckpt_dir, "2"],
        env=env, timeout=120,
    )
    assert child.returncode == -9, child.returncode  # SIGKILLed

    handler = SharedMemoryHandler(0, host=False)
    try:
        torn = handler.get_checkpoint_config()
        assert torn.step == 2 and torn.writing  # died mid-save
        if reader == "load_state_dict":
            assert handler.load_state_dict() == (None, {})
        else:
            assert handler.read_raw() == (None, b"", {})
            # the agent's breakpoint save finds nothing to persist
            AsyncCheckpointSaver.save_shm_to_storage()
            assert read_last_checkpoint(ckpt_dir)[0] == 1
            assert not os.path.exists(
                os.path.join(ckpt_dir, "checkpoint-2")
            )
    finally:
        handler.close()
    engine = CheckpointEngine(
        ckpt_dir, replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    try:
        step, state = engine.load()
        assert step == 1 and state["step"] == 1
        assert engine.last_restore_phases["tier"] == "storage"
        np.testing.assert_array_equal(
            state["a"], np.full((300,), 1.0, np.float32)
        )
        np.testing.assert_array_equal(
            state["c"], np.full((64,), 1, np.int32)
        )
    finally:
        engine._shm_handler.unlink()  # the dead writer's segment
        engine.close()


@pytest.mark.parametrize("size", ["same", "larger"])
def test_respawned_writer_reuses_or_regrows_segment(saver, size):
    """The respawned trainer's handler starts with no mapping: a state
    of the size already there goes into the existing segment, a larger
    one unlinks and recreates it, and the agent-side reader (whose
    cached mapping is of the old segment) sees the new step each
    time."""
    def state(step, n):
        return {"w": np.full((n,), float(step), np.float32), "step": step}

    reader = saver._shm_handlers[0]
    first = SharedMemoryHandler(0, host=False)
    first.save_state_dict(state(1, 1000), CheckpointConfig(step=1))
    cfg, back = reader.load_state_dict()
    assert cfg.step == 1 and back["w"][0] == 1.0
    inode = os.fstat(first._shm._fd).st_ino
    first.close()  # the trainer died; the segment outlives it

    n = 1000 if size == "same" else 5000
    respawned = SharedMemoryHandler(0, host=False)
    try:
        assert respawned._shm is None
        respawned.save_state_dict(state(2, n), CheckpointConfig(step=2))
        same_segment = os.fstat(respawned._shm._fd).st_ino == inode
        assert same_segment == (size == "same")
        cfg, back = reader.load_state_dict()
        assert cfg.step == 2 and back["step"] == 2
        assert back["w"].tobytes() == state(2, n)["w"].tobytes()
        config, raw, meta = reader.read_raw()
        assert config.step == 2
        assert len(raw) == meta["scalar_offset"] + meta["scalar_nbytes"]
    finally:
        respawned.unlink()
        respawned.close()
