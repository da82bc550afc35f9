"""Restore-pipeline tests (ISSUE 3): the pipelined shm/storage
restore is BIT-identical to the serial path, ``DLROVER_RESTORE_WORKERS
=1`` reproduces the serial path exactly, re-shard-on-load still covers
topology changes through the staged executor, and the restore
telemetry (span/event/engine phases) carries the new stage breakdown.
Stdlib+numpy-heavy and fast — conftest runs this file in the early
wall-clock-protected group."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint import restore as restore_mod
from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.restore import (
    RestoreStats,
    StagedRestore,
    detach_flat,
    restore_workers,
    zero_copy_device_put,
)
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    SaverConfig,
    read_last_checkpoint,
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
    """Mixed dtypes (incl. bf16), odd shapes, non-array leaves — the
    shapes a real TrainState ships."""
    rng = np.random.default_rng(7)
    return {
        "params": {
            "w": jnp.asarray(
                rng.normal(size=(37, 129)).astype(np.float32)
            ),
            "b": rng.normal(size=(513,)).astype(np.float32),
            "bf": jnp.asarray(
                rng.normal(size=(64, 65)), dtype=jnp.bfloat16
            ),
        },
        "opt": {"mu": np.zeros((37, 129), np.float16), "nu": 3},
        "step": 41,
        "note": "pipeline",
    }


def _leaf_bytes(tree):
    out = {}
    for k, v in jax.tree_util.tree_leaves_with_path(tree):
        out[str(k)] = (
            np.asarray(v).tobytes() if hasattr(v, "dtype") else v
        )
    return out


def _engine(tmp_path):
    return CheckpointEngine(
        str(tmp_path), replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )


def _wait_tracker(tmp_path, timeout=30):
    tracker = os.path.join(
        str(tmp_path), CheckpointConstant.TRACKER_FILE
    )
    deadline = time.time() + timeout
    while time.time() < deadline and not os.path.exists(tracker):
        time.sleep(0.1)
    assert os.path.exists(tracker)


def test_workers_env_knob_and_serial_inline(monkeypatch):
    """DLROVER_RESTORE_WORKERS=1 must bypass the pool entirely (the
    serial-path guarantee is structural, not just numerical)."""
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "1")
    assert restore_workers() == 1
    with StagedRestore() as staged:
        assert staged._pool is None
        fut = staged.submit(lambda a, b: a + b, 1, 2)
        assert fut.result() == 3
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "4")
    assert restore_workers() == 4
    with StagedRestore() as staged:
        assert staged._pool is not None
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "garbage")
    assert restore_workers() >= 1  # sane default, no crash


def test_detach_flat_bit_identical_serial_vs_parallel(monkeypatch):
    rng = np.random.default_rng(0)
    views = {
        "a": rng.normal(size=(1 << 20,)).astype(np.float32),
        "b": rng.integers(0, 255, size=(3, 5, 7)).astype(np.uint8),
        "c": np.asarray(1.5, dtype=np.float64),  # 0-d leaf
        "d": np.empty((0, 4), np.float32),       # empty leaf
    }
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "1")
    serial = detach_flat(dict(views))
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "4")
    # tiny chunks force many parallel pieces over each leaf
    monkeypatch.setattr(restore_mod, "CHUNK_BYTES", 2**20)
    parallel = detach_flat(dict(views))
    assert set(serial) == set(parallel)
    for key in views:
        assert serial[key].dtype == parallel[key].dtype
        assert serial[key].shape == parallel[key].shape
        assert serial[key].tobytes() == parallel[key].tobytes()
        assert parallel[key].tobytes() == views[key].tobytes()
        assert parallel[key].base is None  # truly detached


def test_shm_restore_equivalence_and_phases(saver, tmp_path,
                                            monkeypatch):
    """Pipelined shm restore returns bit-identical state to the saved
    snapshot AND to the workers=1 serial path; the engine surfaces
    the stage breakdown."""
    engine = _engine(tmp_path)
    sd = _state_dict()
    assert engine.save(3, sd)

    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "1")
    step1, serial = engine.load()
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "4")
    monkeypatch.setattr(restore_mod, "CHUNK_BYTES", 2**20)
    step2, pipelined = engine.load()
    assert step1 == step2 == 3
    assert _leaf_bytes(serial) == _leaf_bytes(pipelined)
    assert _leaf_bytes(pipelined) == _leaf_bytes(
        {"params": sd["params"], "opt": sd["opt"],
         "step": sd["step"], "note": sd["note"]}
    )
    phases = engine.last_restore_phases
    assert phases["tier"] == "shm" and phases["workers"] == 4
    for key in ("read_s", "assemble_s", "h2d_s", "total_s", "bytes"):
        assert key in phases, phases
    engine.close()


def test_storage_restore_equivalence_and_disk_phases(
    saver, tmp_path, monkeypatch
):
    engine = _engine(tmp_path)
    sd = _state_dict()
    assert engine.save(9, sd, persist=True)
    assert engine.wait_async(timeout=30.0)
    _wait_tracker(tmp_path)

    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "1")
    step1, serial = engine.load_from_storage()
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "4")
    monkeypatch.setattr(restore_mod, "CHUNK_BYTES", 2**20)
    step2, pipelined = engine.load_from_storage()
    assert step1 == step2 == 9
    assert _leaf_bytes(serial) == _leaf_bytes(pipelined)
    np.testing.assert_array_equal(
        np.asarray(pipelined["params"]["w"]),
        np.asarray(sd["params"]["w"]),
    )
    assert engine.last_restore_phases["tier"] == "storage"
    engine.close()


def test_read_last_checkpoint_mmap_views_match_eager_read(
    saver, tmp_path
):
    """The lazy read_view path must hand back the same bytes the old
    eager read did (and tolerate workers=1)."""
    engine = _engine(tmp_path)
    engine.save(5, _state_dict(), persist=True)
    engine.wait_async(timeout=30.0)
    _wait_tracker(tmp_path)
    step_a, shards_a = read_last_checkpoint(str(tmp_path), workers=1)
    step_b, shards_b = read_last_checkpoint(str(tmp_path), workers=4)
    assert step_a == step_b == 5
    for rank in shards_a:
        meta_a, raw_a = shards_a[rank]
        meta_b, raw_b = shards_b[rank]
        assert bytes(raw_a[:]) == bytes(raw_b[:])
        assert meta_a["scalar_offset"] == meta_b["scalar_offset"]
    engine.close()


def test_posix_read_view_matches_read(tmp_path):
    from dlrover_tpu.common.storage import PosixDiskStorage

    stg = PosixDiskStorage()
    p = os.path.join(str(tmp_path), "blob.bin")
    payload = os.urandom(1 << 16)
    stg.write(payload, p)
    view = stg.read_view(p)
    assert bytes(view[:]) == payload == stg.read(p)
    assert np.frombuffer(view, np.uint8).nbytes == len(payload)
    # empty + missing files
    stg.write(b"", os.path.join(str(tmp_path), "empty.bin"))
    assert stg.read_view(
        os.path.join(str(tmp_path), "empty.bin")
    ) == b""
    assert stg.read_view(
        os.path.join(str(tmp_path), "nope.bin")
    ) is None


def _mesh(shape, axes):
    devs = np.array(jax.devices()[: int(np.prod(shape))]).reshape(
        shape
    )
    return Mesh(devs, axes)


def test_load_sharded_pipeline_reshard_bit_identical(
    saver, tmp_path, monkeypatch
):
    """Re-shard-on-load through the staged executor: save on
    {fsdp:8}, restore on {data:2, fsdp:4}, serial vs pipelined bit-
    identical, and the data never aliases the shm segment on the CPU
    backend (zero-copy guard)."""
    assert not zero_copy_device_put()  # CPU backend: views detached
    mesh1 = _mesh((8,), ("fsdp",))
    w = jnp.asarray(
        np.random.default_rng(3).normal(size=(64, 4)).astype(
            np.float32
        )
    )
    state = {
        "params": {
            "w": jax.device_put(w, NamedSharding(mesh1, P("fsdp"))),
        },
        "step": 5,
    }
    engine = _engine(tmp_path)
    engine.replicated = False
    assert engine.save(5, state)

    mesh2 = _mesh((2, 4), ("data", "fsdp"))
    target = {
        "params": {
            "w": jax.device_put(
                jnp.zeros((64, 4)),
                NamedSharding(mesh2, P(("data", "fsdp"))),
            ),
        },
        "step": 0,
    }
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "1")
    step1, serial = engine.load_sharded(target)
    monkeypatch.setenv(restore_mod.RESTORE_WORKERS_ENV, "4")
    step2, pipelined = engine.load_sharded(target)
    assert step1 == step2 == 5
    assert np.asarray(serial["params"]["w"]).tobytes() == np.asarray(
        pipelined["params"]["w"]
    ).tobytes() == np.asarray(w).tobytes()
    assert pipelined["params"]["w"].sharding.is_equivalent_to(
        target["params"]["w"].sharding, 2
    )
    # corrupting the shm segment afterwards must NOT change the
    # restored arrays (no aliasing of the snapshot buffer)
    before = np.asarray(pipelined["params"]["w"]).copy()
    shm = engine._shm_handler._attach()
    for i in range(0, min(shm.size, 4096)):
        shm.buf[i] = 0xAA
    np.testing.assert_array_equal(
        np.asarray(pipelined["params"]["w"]), before
    )
    assert engine.last_restore_phases["tier"] == "shm"
    engine.close()


@pytest.mark.parametrize(
    "save_mesh,restore_mesh",
    [
        # (device_count, axis shape) grids: N-shard save -> M-shard
        # restore must be bit-identical to the unsharded state for
        # every combination, including identity and the elastic
        # 2 -> 1 shapes
        ((8,), (4,)),
        ((4,), (8,)),
        ((2,), (1,)),
        ((1,), (2,)),
        ((8,), (2, 4)),
        ((2, 4), (8,)),
        ((4,), (4,)),
    ],
)
def test_reshard_save_restore_grid_bit_identical(
    saver, tmp_path, save_mesh, restore_mesh
):
    """Elastic-resize property (ISSUE 8 satellite): save under mesh
    (N, shards) -> restore under mesh (M, shards') is bit-identical
    to the unsharded source state, for a grid of N/M combinations.
    Exercises assemble_target_pieces/commit_target_pieces with
    genuinely different save-time and restore-time device index
    maps."""
    rng = np.random.default_rng(11)
    src = rng.normal(size=(64, 8)).astype(np.float32)

    axes_of = {1: ("a",), 2: ("a", "b")}
    m1 = _mesh(save_mesh, axes_of[len(save_mesh)])
    spec1 = P(*axes_of[len(save_mesh)]) if len(save_mesh) > 1 else P("a")
    state = {
        "w": jax.device_put(
            jnp.asarray(src), NamedSharding(m1, spec1)
        ),
        "step": 3,
    }
    engine = _engine(tmp_path)
    engine.replicated = False
    assert engine.save(3, state)

    m2 = _mesh(restore_mesh, axes_of[len(restore_mesh)])
    spec2 = (
        P(*axes_of[len(restore_mesh)])
        if len(restore_mesh) > 1 else P("a")
    )
    target = {
        "w": jax.device_put(
            jnp.zeros((64, 8)), NamedSharding(m2, spec2)
        ),
        "step": 0,
    }
    step, restored = engine.load_sharded(target)
    assert step == 3
    assert np.asarray(restored["w"]).tobytes() == src.tobytes()
    assert restored["w"].sharding.is_equivalent_to(
        target["w"].sharding, 2
    )
    assert restored["step"] == 3
    engine.close()


def test_reshard_round_trip_2_1_2(saver, tmp_path):
    """The elastic churn arc in miniature: save sharded over 2
    devices -> restore+resave over 1 -> restore over 2 again, every
    hop from the STORAGE tier (the cross-world path: shm snapshots
    from another world size are refused), final bytes identical to
    the source."""
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    rng = np.random.default_rng(13)
    src = rng.normal(size=(32, 4)).astype(np.float32)

    def sharded(ndev, arr):
        m = _mesh((ndev,), ("a",))
        return jax.device_put(
            jnp.asarray(arr), NamedSharding(m, P("a"))
        )

    def engine_for(world):
        e = CheckpointEngine(
            str(tmp_path), replicated=False, local_rank=0,
            global_rank=0, world_size=world,
        )
        return e

    def wait_commit(step):
        tracker = os.path.join(
            str(tmp_path), CheckpointConstant.TRACKER_FILE
        )
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                with open(tracker) as f:
                    if int(f.read().strip() or -1) >= step:
                        return
            except (OSError, ValueError):
                pass
            time.sleep(0.1)
        raise AssertionError(f"step {step} never committed")

    e2 = engine_for(2)
    assert e2.save(1, {"w": sharded(2, src)}, persist=True)
    assert e2.wait_async(timeout=30)
    wait_commit(1)

    e1 = engine_for(1)
    step, got = e1.load_sharded({"w": sharded(1, np.zeros_like(src))})
    assert step == 1
    assert e1.last_restore_phases["tier"] == "storage"
    assert np.asarray(got["w"]).tobytes() == src.tobytes()
    assert e1.save(2, {"w": got["w"]}, persist=True)
    assert e1.wait_async(timeout=30)
    wait_commit(2)

    e2b = engine_for(2)
    step, back = e2b.load_sharded(
        {"w": sharded(2, np.zeros_like(src))}
    )
    assert step == 2
    assert e2b.last_restore_phases["tier"] == "storage"
    assert np.asarray(back["w"]).tobytes() == src.tobytes()
    for e in (e2, e1, e2b):
        e.close()


def test_restore_span_and_event_carry_stage_breakdown(
    saver, tmp_path, monkeypatch
):
    """The ckpt.restore span and the checkpoint_restore event both
    carry tier + read_s/assemble_s/h2d_s — what the chaos tier
    invariant consumes."""
    from dlrover_tpu.telemetry.events import EVENT_LOG_ENV, read_events
    from dlrover_tpu.telemetry.tracing import get_tracer

    evlog = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(EVENT_LOG_ENV, evlog)
    tracer = get_tracer()
    tracer.clear()
    engine = _engine(tmp_path)
    assert engine.save(4, _state_dict())
    step, _state = engine.load()
    assert step == 4
    spans = tracer.finished_spans("ckpt.restore")
    assert spans, "no ckpt.restore span finished"
    attrs = spans[-1].attributes
    assert attrs["tier"] == "shm"
    for key in ("read_s", "assemble_s", "h2d_s", "total_s", "workers"):
        assert key in attrs, attrs
    events = [
        e for e in read_events(evlog)
        if e.get("type") == "checkpoint_restore"
    ]
    assert events, "no checkpoint_restore event emitted"
    last = events[-1]
    assert last["tier"] == "shm"
    for key in ("read_s", "assemble_s", "h2d_s", "total_s", "workers"):
        assert key in last, last
    engine.close()


def test_restore_stage_histogram_observed(saver, tmp_path):
    from dlrover_tpu.telemetry.metrics import get_registry

    engine = _engine(tmp_path)
    assert engine.save(6, _state_dict())
    hist = get_registry().get(
        "dlrover_checkpoint_restore_stage_seconds"
    )
    before_h2d = hist.snapshot(stage="h2d", tier="shm")["count"]
    step, _ = engine.load()
    assert step == 6
    # read/assemble stages observed for the shm tier...
    assert hist.snapshot(stage="read", tier="shm")["count"] >= 1
    assert hist.snapshot(stage="assemble", tier="shm")["count"] >= 1
    # ...but a host-array load has NO h2d stage — observing 0.0
    # samples would fabricate the percentile this histogram exists
    # to surface (the phases dict still reports h2d_s=0 for humans)
    assert hist.snapshot(
        stage="h2d", tier="shm"
    )["count"] == before_h2d
    assert engine.last_restore_phases["h2d_s"] == 0.0
    engine.close()


# -- restore overlap (ISSUE 10) ----------------------------------------


def test_overlapped_restore_bit_identical_to_serial(saver, tmp_path):
    """load_checkpoint_async (restore stages overlapped with caller
    setup) produces BIT-identical state vs the serial load — asserted
    via per-leaf byte digests, for both the shm and storage tiers."""
    from dlrover_tpu.checkpoint.checkpointer import (
        Checkpointer, StorageType,
    )

    state = _state_dict()
    ck = Checkpointer(str(tmp_path))
    try:
        ck.save_checkpoint(5, state, storage_type=StorageType.DISK)
        assert ck.wait(timeout=60)
        _wait_tracker(tmp_path)

        # shm tier
        step_a, async_state = ck.load_checkpoint_async().result(
            timeout=60
        )
        step_s, serial_state = ck.load_checkpoint()
        assert step_a == step_s == 5
        assert _leaf_bytes(async_state) == _leaf_bytes(serial_state)

        # storage tier (fresh engine in this process would still see
        # shm; drop the shm snapshot to force the disk path)
        ck._engine._shm_handler.unlink()
        step_d, disk_async = ck.load_checkpoint_async().result(
            timeout=60
        )
        assert step_d == 5
        assert _leaf_bytes(disk_async) == _leaf_bytes(serial_state)
    finally:
        ck.close()


def test_engine_prefault_thread_on_respawn(saver, tmp_path,
                                           monkeypatch):
    """A respawned trainer (restart_count > 0) pre-faults the shm
    snapshot on a daemon thread at engine construction — and the
    subsequent restore still round-trips exactly."""
    state = _state_dict()
    eng = _engine(tmp_path)
    try:
        assert eng.save(3, state)
    finally:
        eng.close()
    monkeypatch.setenv("DLROVER_RESTART_COUNT", "1")
    eng2 = _engine(tmp_path)
    try:
        assert eng2._prefault_thread is not None
        eng2._prefault_thread.join(timeout=30)
        assert not eng2._prefault_thread.is_alive()
        cfg, restored = eng2.get_state_dict_from_memory()
        assert cfg is not None and cfg.step == 3
        assert _leaf_bytes(restored) == _leaf_bytes(state)
        step, serial = eng2.load()
        assert step == 3
        assert _leaf_bytes(serial) == _leaf_bytes(state)
    finally:
        eng2.close()
    monkeypatch.setenv("DLROVER_RESTORE_PREFETCH", "0")
    eng3 = _engine(tmp_path)
    try:
        assert eng3._prefault_thread is None  # knob respected
    finally:
        eng3.close()


def test_prefault_touches_whole_snapshot(saver, tmp_path):
    """handler.prefault returns the snapshot's full byte size (every
    page visited) and tolerates an absent snapshot."""
    from dlrover_tpu.checkpoint.shm_handler import (
        PREFAULT_WORKERS, SharedMemoryHandler,
    )

    assert PREFAULT_WORKERS >= 1
    eng = _engine(tmp_path)
    try:
        h = SharedMemoryHandler(0, host=False)
        assert h.prefault() == 0  # nothing saved yet
        assert eng.save(9, _state_dict())
        assert eng.wait_async(timeout=30)  # h reads the commit
        meta = h.metadata()
        expect = meta["scalar_offset"] + meta["scalar_nbytes"]
        assert h.prefault(workers=2) == expect
        assert h.prefault(workers=1) == expect  # serial path too
        h.close()
    finally:
        eng.close()
