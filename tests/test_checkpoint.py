"""Flash-checkpoint tests: shm handler pytree round-trip, async saver
commit protocol, engine save/load paths, breakpoint save — trainer and
agent sides run in one process over the real unix-socket IPC, the
reference's test pattern (test_ckpt_saver.py)."""

import os
import subprocess
import sys
import threading
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
    assert engine.save(3, sd)
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
    assert engine.save(5, sd, persist=True)
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
    engine.save(9, sd, persist=True)
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
    engine.save(11, _state_dict())
    assert engine.wait_async(timeout=30.0)  # the agent reads the commit
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
            engine.save(step, _state_dict())
            assert engine.wait_async(timeout=30.0)
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
    assert engine.save(4, sd, persist=True)
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


@pytest.fixture()
def event_log(tmp_path, monkeypatch):
    path = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", path)
    return path


def _events(path, type_, **match):
    from dlrover_tpu.telemetry.events import read_events

    if not os.path.exists(path):
        return []
    return [
        e for e in read_events(path)
        if e["type"] == type_
        and all(e.get(k) == v for k, v in match.items())
    ]


def _skipped(reason):
    from dlrover_tpu.checkpoint.engine import _SAVE_SKIPPED_TOTAL

    return _SAVE_SKIPPED_TOTAL.value(reason=reason)


def _engine(tmp_path):
    return CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )


def _wait_persisted(tmp_path, step):
    deadline = time.time() + 30
    while (time.time() < deadline
           and read_last_checkpoint(str(tmp_path))[0] != step):
        time.sleep(0.05)
    assert read_last_checkpoint(str(tmp_path))[0] == step


def _release_later(lock, seconds):
    t = threading.Timer(seconds, lock.release)
    t.daemon = True
    t.start()
    return t


BOTH = pytest.mark.parametrize(
    "persist", [False, True], ids=["memory", "disk"]
)


@BOTH
def test_save_returns_before_the_commit_and_load_drains(
    saver, tmp_path, event_log, persist
):
    """A save of a ``jax.Array`` state, MEMORY or DISK, is accepted
    before ``checkpoint_shm_save``; ``load()`` right after it waits
    for this engine's own writer and returns that step bit for bit."""
    engine = _engine(tmp_path)
    lock = saver._shm_locks[0]
    sd = _state_dict()
    try:
        assert lock.acquire(note="test")  # the writer cannot commit
        assert engine.save(7, sd, persist=persist)
        assert engine.last_save_route == "snapshot"
        time.sleep(0.2)
        assert _events(event_log, "checkpoint_shm_save") == []
        sd["params"]["b"][:] = -123.0  # the snapshot holds step 7's
        _release_later(lock, 0.3)
        step, restored = engine.load()
        assert step == 7
        assert len(_events(event_log, "checkpoint_shm_save", step=7)) == 1
        want = jax.device_get(_state_dict())
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            {k: want[k] for k in ("params", "opt")}
        ):
            got = restored
            for p in path:
                got = got[p.key]
            assert got.dtype == leaf.dtype, path
            assert got.tobytes() == leaf.tobytes(), path
        if persist:
            _wait_persisted(tmp_path, 7)
    finally:
        engine.close()


@BOTH
def test_second_save_waits_for_the_writer_and_skips_none(
    saver, tmp_path, event_log, persist
):
    """While the previous snapshot is still being written the next
    save WAITS (``ckpt.save.writer_wait``, stall), then takes its
    snapshot: both commit, none is skipped."""
    from dlrover_tpu.telemetry.schema import SPAN_SCHEMAS, validate_event

    ckpt = Checkpointer(
        str(tmp_path), local_rank=0, global_rank=0, world_size=1
    )
    kind = StorageType.DISK if persist else StorageType.MEMORY
    lock = saver._shm_locks[0]
    before = _skipped("writer_busy")
    try:
        assert lock.acquire(note="test")
        assert ckpt.save_checkpoint(1, _state_dict(), storage_type=kind)
        _release_later(lock, 0.4)
        t0 = time.perf_counter()
        assert ckpt.save_checkpoint(2, _state_dict(), storage_type=kind)
        stalled = time.perf_counter() - t0
        assert ckpt.wait(timeout=30.0)
    finally:
        ckpt.close()
    assert _skipped("writer_busy") == before
    assert [
        e["step"] for e in _events(event_log, "checkpoint_shm_save")
    ] == [1, 2]
    spans = _events(event_log, "span")
    assert all(validate_event(e) == [] for e in spans)
    roots = {
        e["attributes"]["step"]: e for e in spans
        if e["name"] == "ckpt.save"
    }
    assert [roots[s]["attributes"]["route"] for s in (1, 2)] == [
        "snapshot", "snapshot"
    ]
    assert "route" in SPAN_SCHEMAS["ckpt.save"].reads
    (wait,) = [e for e in spans if e["name"] == "ckpt.save.writer_wait"]
    assert wait["parent_id"] == roots[2]["span_id"]
    assert 0.2 < wait["attributes"]["waited_s"] <= stalled
    # the wait came BEFORE the snapshot: at most one alive
    (snap,) = [
        e for e in spans if e["name"] == "ckpt.save.snapshot"
        and e["trace_id"] == roots[2]["trace_id"]
    ]
    assert snap["start_ts"] >= wait["start_ts"] + wait["duration_s"]


def test_save_skips_and_counts_only_past_the_bound(
    saver, tmp_path, monkeypatch
):
    from dlrover_tpu.checkpoint import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_WRITER_WAIT_BOUND_S", 0.2)
    engine = _engine(tmp_path)
    lock = saver._shm_locks[0]
    before = _skipped("writer_busy")
    try:
        assert lock.acquire(note="test")
        assert engine.save(2, _state_dict())
        t0 = time.perf_counter()
        assert engine.save(3, _state_dict()) is False
        assert time.perf_counter() - t0 >= 0.2
        assert _skipped("writer_busy") == before + 1
        assert lock.release()
        assert engine.wait_async(timeout=30.0)
        # writer idle again: the next save is accepted
        assert engine.save(4, _state_dict(), persist=True)
        step, _ = engine.load()
        assert step == 4
        assert engine._last_async_error is None
    finally:
        engine.close()


def test_at_most_one_snapshot_is_alive(saver, tmp_path):
    """The writer thread lets go of a snapshot before the next save
    can take its own: the device never holds the state three times."""
    import gc
    import weakref

    engine = _engine(tmp_path)
    taken, alive_at_next = [], []
    real = engine._device_snapshot

    def spy(state_dict):
        gc.collect()
        alive_at_next.append(
            sum(any(r() is not None for r in refs) for refs in taken)
        )
        snap = real(state_dict)
        taken.append([
            weakref.ref(leaf) for leaf in jax.tree_util.tree_leaves(snap)
            if isinstance(leaf, jax.Array)
        ])
        return snap

    engine._device_snapshot = spy
    try:
        for step in (1, 2, 3, 4):
            assert engine.save(step, _state_dict(), persist=step == 2)
        assert engine.wait_async(timeout=30.0)
        assert len(taken) == 4 and all(taken)
        assert alive_at_next == [0, 0, 0, 0]
        gc.collect()
        assert not any(r() is not None for refs in taken for r in refs)
    finally:
        engine.close()


@BOTH
def test_snapshot_out_of_memory_takes_the_caller_route(
    saver, tmp_path, event_log, persist
):
    """A snapshot that raises RESOURCE_EXHAUSTED all the same: the
    save is written on the caller's thread (committed when the call
    returns; persisted if DISK).  Nothing is remembered: the next
    save asks the device again and takes its snapshot."""
    ckpt = Checkpointer(
        str(tmp_path), local_rank=0, global_rank=0, world_size=1
    )
    engine = ckpt._engine
    kind = StorageType.DISK if persist else StorageType.MEMORY
    real = engine._device_snapshot

    def exhausted(state_dict):
        engine._device_snapshot = real  # a transient shortage
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Error allocating device buffer: "
            "Attempting to allocate 9.40G. That was not possible."
        )

    engine._device_snapshot = exhausted
    try:
        assert ckpt.save_checkpoint(5, _state_dict(), storage_type=kind)
        # committed on return: nothing is queued
        assert engine._writer_queue.unfinished_tasks == 0
        assert len(
            _events(event_log, "checkpoint_shm_save", step=5)
        ) == 1
        assert ckpt.save_checkpoint(6, _state_dict(), storage_type=kind)
        step, restored = ckpt.load_checkpoint()
        assert step == 6
        _assert_state_equal(_state_dict(), restored)
        if persist:
            _wait_persisted(tmp_path, 6)
        else:
            assert read_last_checkpoint(str(tmp_path))[0] is None
    finally:
        ckpt.close()
    routes = [
        e["attributes"]["route"] for e in _events(event_log, "span")
        if e["name"] == "ckpt.save"
    ]
    assert routes == ["caller", "snapshot"]


GB = 2**30


@pytest.mark.parametrize("stats,route", [
    # 2S < HBM < 2S + T (the state here is 72 bytes on the device)
    ({"bytes_in_use": 6.7 * GB, "peak_bytes_reserved": 7.25 * GB,
      "bytes_limit": 6.7 * GB + 7.25 * GB + 50}, "caller"),
    ({"bytes_in_use": 6.7 * GB, "peak_bytes_reserved": 7.25 * GB,
      "bytes_limit": 15.75 * GB}, "snapshot"),
    # a backend without the figures: the allocation decides
    ({}, "snapshot"),
    ({"bytes_in_use": 15 * GB, "bytes_limit": 15.75 * GB}, "snapshot"),
], ids=["in-the-band", "fits", "no-stats", "no-reserved"])
def test_route_follows_what_the_device_reports(
    saver, tmp_path, monkeypatch, stats, route
):
    """The snapshot lives through the next steps, so it is taken only
    where the device reports room for it beside the largest scratch a
    program has reserved; decided at every save, whatever the last
    one did."""
    from dlrover_tpu.checkpoint import engine as engine_mod

    engine = _engine(tmp_path)
    asked = []

    def fake(dev):
        asked.append(dev)
        return dict(stats)

    try:
        assert engine.save(1, _state_dict())
        assert engine.last_save_route == "snapshot"
        monkeypatch.setattr(engine_mod, "_memory_stats", fake)
        assert engine.save(2, _state_dict())
        assert engine.last_save_route == route
        assert asked == [jax.devices()[0]]
        monkeypatch.setattr(engine_mod, "_memory_stats", lambda d: {})
        assert engine.save(3, _state_dict())
        assert engine.last_save_route == "snapshot"
        step, restored = engine.load()
        assert step == 3
        _assert_state_equal(_state_dict(), restored)
    finally:
        engine.close()


def test_first_snapshot_on_a_reporting_device_commits_in_the_call(
    saver, tmp_path, event_log, monkeypatch
):
    """What a device has reserved before the first save says nothing
    of the steps to come: that one snapshot is committed before the
    call returns (a ``writer_wait`` after the hand-over); the second
    save leaves the loop at once."""
    from dlrover_tpu.checkpoint import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_memory_stats", lambda dev: {
        "bytes_in_use": GB, "peak_bytes_reserved": GB // 8,
        "bytes_limit": 16 * GB,
    })
    ckpt = Checkpointer(
        str(tmp_path), local_rank=0, global_rank=0, world_size=1
    )
    engine = ckpt._engine
    lock = saver._shm_locks[0]
    try:
        assert lock.acquire(note="test")
        # (held well past what comes before the wait: the first
        # save's set-up is 0.05-0.2 s of the host's own)
        _release_later(lock, 0.6)
        t0 = time.perf_counter()
        assert ckpt.save_checkpoint(
            1, _state_dict(), storage_type=StorageType.MEMORY
        )
        assert time.perf_counter() - t0 >= 0.25
        assert engine._writer_queue.unfinished_tasks == 0
        assert len(_events(event_log, "checkpoint_shm_save", step=1)) == 1
        assert lock.acquire(note="test")
        assert ckpt.save_checkpoint(
            2, _state_dict(), storage_type=StorageType.MEMORY
        )
        assert engine._writer_queue.unfinished_tasks == 1
        assert _events(event_log, "checkpoint_shm_save", step=2) == []
        lock.release()
        assert ckpt.wait()
    finally:
        ckpt.close()
    spans = _events(event_log, "span")
    roots = {
        e["attributes"]["step"]: e for e in spans
        if e["name"] == "ckpt.save"
    }
    assert {r["attributes"]["route"] for r in roots.values()} == {
        "snapshot"
    }
    waits = [
        e for e in spans if e["name"] == "ckpt.save.writer_wait"
    ]
    assert [w["parent_id"] for w in waits] == [roots[1]["span_id"]]
    assert waits[0]["attributes"]["waited_s"] >= 0.25


def test_snapshot_is_counted_per_device_by_its_shards(saver, tmp_path):
    """A sharded leaf weighs a shard on each of its devices, a
    replicated one its whole size on every device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from dlrover_tpu.checkpoint import engine as engine_mod

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices), ("x",))
    state = {
        "sharded": jax.device_put(
            np.zeros((8, 256), np.float32), NamedSharding(mesh, P("x"))
        ),
        "replicated": jax.device_put(
            np.zeros((16,), np.float32), NamedSharding(mesh, P())
        ),
        "host": np.zeros(3, np.float32),
    }
    per_device = 8 * 256 * 4 // 4 + 16 * 4
    engine = _engine(tmp_path)
    seen = {}

    def fake(dev):
        # room for the snapshot to the byte on three devices
        seen[dev] = True
        limit = 10 + per_device + 5
        return {"bytes_in_use": 10, "peak_bytes_reserved": 5,
                "bytes_limit": limit - (dev == devices[3])}

    real = engine_mod._memory_stats
    engine_mod._memory_stats = fake
    try:
        why = engine._why_no_snapshot(state)
        assert str(devices[3]) in why and "pass the device" in why
        seen.clear()
        devices[3] = None  # room on every device, to the byte
        assert engine._why_no_snapshot(state) == ""
        assert set(seen) == set(jax.devices()[:4])
    finally:
        engine_mod._memory_stats = real
        engine.close()


def test_snapshot_error_other_than_memory_is_raised(saver, tmp_path):
    engine = _engine(tmp_path)

    def broken(state_dict):
        raise jax.errors.JaxRuntimeError("INTERNAL: not an allocation")

    engine._device_snapshot = broken
    try:
        with pytest.raises(jax.errors.JaxRuntimeError):
            engine.save(1, _state_dict())
        assert engine._writer_queue.unfinished_tasks == 0
    finally:
        engine.close()


@BOTH
def test_sparse_export_is_durable_only_for_a_persisted_save(
    saver, tmp_path, persist
):
    """A registered adapter's export joins the snapshot on the
    caller's thread; a MEMORY save asks for a full, non-durable
    export, a DISK save for a durable one."""
    from dlrover_tpu.checkpoint.sparse import KV_STATE_KEY

    t, opt, adapter = _mk_adapter()
    _train_kv(t, opt, 1)
    asked = []
    real = adapter.export_for_checkpoint

    def spy(step, rank, durable):
        asked.append((durable, threading.current_thread().name))
        return real(step=step, rank=rank, durable=durable)

    adapter.export_for_checkpoint = spy
    engine = _engine(tmp_path)
    engine.register_sparse(adapter)
    try:
        assert engine.save(1, _state_dict(), persist=persist)
        assert engine.last_save_route == "snapshot"
        assert asked == [(persist, threading.current_thread().name)]
        saved_rows = _sorted_rows(t)
        _train_kv(t, opt, 2)  # the live table moves on
        step, back = engine.load()
        assert step == 1 and KV_STATE_KEY not in back
        k, v, f = _sorted_rows(t)  # rolled back by load()
        np.testing.assert_array_equal(k, saved_rows[0])
        assert v.tobytes() == saved_rows[1].tobytes()
    finally:
        engine.close()


def test_kick_off_lets_the_loop_thread_in_every_few_leaves(monkeypatch):
    """The writer thread's kick-off sleeps after every
    ``_KICKOFF_TURN`` transfers it starts (host leaves start none):
    unbroken, it kept the interpreter's lock, and the loop's thread
    inside the save call, until its last leaf."""
    from dlrover_tpu.checkpoint import engine as engine_mod

    pauses = []
    monkeypatch.setattr(engine_mod.time, "sleep", pauses.append)
    leaves = 2 * engine_mod._KICKOFF_TURN + 3
    snap = {
        "device": [jnp.full((4,), i) for i in range(leaves)],
        "host": [np.zeros(4)] * engine_mod._KICKOFF_TURN,
    }
    CheckpointEngine._kick_off_fetch(7, snap)
    assert pauses == [engine_mod._KICKOFF_PAUSE_S] * 2
    assert engine_mod._KICKOFF_PAUSE_S > 0  # a real sleep, not a yield


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
    assert engine.save(
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
    """What ``save`` wrote is what ``load()`` hands back,
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
        assert engine.save(3, state)
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
assert engine.save(1, state(1), persist=True)
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
engine.save(2, state(2))
sys.exit(7)  # unreachable: the kill lands inside the save
"""


_EXITS_AFTER_ACCEPT = r"""
import sys, time
import jax.numpy as jnp

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.ops import fastcopy

real = fastcopy.copy_into

def slow(dst, src):
    time.sleep(0.5)
    return real(dst, src)

fastcopy.copy_into = slow
ckpt = Checkpointer(sys.argv[1], local_rank=0, global_rank=0, world_size=1)
state = {"w": jnp.arange(64, dtype=jnp.float32), "step": 3}
assert ckpt.save_checkpoint(3, state, storage_type=StorageType.MEMORY)
print("queued", ckpt._engine._writer_queue.unfinished_tasks, flush=True)
sys.exit(0)  # the old contract: no wait(), no close()
"""


def test_exit_right_after_a_save_commits_it(saver, tmp_path):
    """A script written to the old contract (MEMORY save, then exit
    with no ``wait()``): the accepted save is committed before the
    interpreter goes, not left ``writing`` over the previous one."""
    import dlrover_tpu

    AsyncCheckpointSaver.start_async_saving_ckpt()  # the agent's factory
    script = tmp_path / "exits_after_accept.py"
    script.write_text(_EXITS_AFTER_ACCEPT)
    pkg_root = os.path.dirname(os.path.dirname(dlrover_tpu.__file__))
    child = subprocess.run(  # noqa: S603
        [sys.executable, str(script), str(tmp_path)],
        env=dict(
            os.environ, JAX_PLATFORMS="cpu",
            PYTHONPATH=pkg_root + os.pathsep + os.environ.get(
                "PYTHONPATH", ""
            ),
        ),
        timeout=120, capture_output=True, text=True,
    )
    assert child.returncode == 0, child.stderr[-2000:]
    assert "queued 1" in child.stdout  # it did return before the commit
    handler = SharedMemoryHandler(0, host=False)
    try:
        config = handler.get_checkpoint_config()
        assert config.step == 3 and not config.writing
        config, state = handler.load_state_dict()
        assert config.step == 3 and state["step"] == 3
        np.testing.assert_array_equal(
            state["w"], np.arange(64, dtype=np.float32)
        )
    finally:
        handler.unlink()
        handler.close()


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
