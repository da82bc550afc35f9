"""Object-store checkpoint backend (fsspec; gs:// in production,
memory:// here) — full flash save -> commit -> restore cycle through
the saver/engine against the non-POSIX storage surface (reference:
get_checkpoint_storage factory, common/storage.py:320)."""

import time

import fsspec
import numpy as np
import pytest

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    SaverConfig,
    read_last_checkpoint,
)
from dlrover_tpu.common.constants import CheckpointConstant
from dlrover_tpu.common.storage import (
    FsspecStorage,
    KeepLatestStepStrategy,
    PosixDiskStorage,
    get_checkpoint_storage,
)


@pytest.fixture()
def memfs():
    fs = fsspec.filesystem("memory")
    # memory filesystem is process-global; start clean
    for entry in list(fs.ls("/", detail=False)):
        fs.rm(entry, recursive=True)
    yield fs


def test_factory_dispatches_on_url():
    assert isinstance(get_checkpoint_storage(path="/tmp/x"), PosixDiskStorage)
    assert isinstance(
        get_checkpoint_storage(path="memory://ckpt"), FsspecStorage
    )


def test_fsspec_storage_surface(memfs):
    st = FsspecStorage(fs=memfs)
    st.write(b"abc", "memory://bucket/ckpt/rank_0.ckpt")
    assert st.exists("memory://bucket/ckpt/rank_0.ckpt")
    assert st.read("memory://bucket/ckpt/rank_0.ckpt") == b"abc"
    st.write("5", "memory://bucket/ckpt/tracker")
    assert st.read("memory://bucket/ckpt/tracker", mode="r") == "5"
    assert "rank_0.ckpt" in st.listdir("memory://bucket/ckpt")
    st.safe_rmtree("memory://bucket/ckpt")
    assert not st.exists("memory://bucket/ckpt/rank_0.ckpt")
    # missing files read as None, missing dirs list as empty
    assert st.read("memory://bucket/nope") is None
    assert st.listdir("memory://bucket/nope") == []


def test_flash_ckpt_cycle_through_object_store(memfs):
    ckpt_dir = "memory://jobs/myjob/ckpt"
    AsyncCheckpointSaver.reset()
    saver = AsyncCheckpointSaver(
        SaverConfig(
            checkpoint_dir=ckpt_dir, local_shard_num=1,
            global_shard_num=1, node_rank=0,
        )
    )
    assert isinstance(saver.storage, FsspecStorage)
    AsyncCheckpointSaver._instance = saver
    try:
        engine = CheckpointEngine(
            ckpt_dir, replicated=True, local_rank=0, global_rank=0,
            world_size=1,
        )
        sd = {"w": np.arange(8, dtype=np.float32), "step": 3}
        assert engine.save(3, sd, persist=True)
        assert engine.wait_async(timeout=30.0)
        tracker = f"{ckpt_dir}/{CheckpointConstant.TRACKER_FILE}"
        deadline = time.time() + 30
        while time.time() < deadline and not memfs.exists(tracker):
            time.sleep(0.1)
        assert memfs.exists(tracker)
        step, restored = engine.load_from_storage()
        assert step == 3
        np.testing.assert_array_equal(
            restored["w"], np.arange(8, dtype=np.float32)
        )
        engine.close()
    finally:
        AsyncCheckpointSaver.reset()


def test_deletion_strategy_on_object_store(memfs):
    st = FsspecStorage(
        deletion_strategy=KeepLatestStepStrategy(2, "memory://b/ck"),
        fs=memfs,
    )
    for step in (1, 2, 3):
        st.write(b"x", f"memory://b/ck/{step}/rank_0.ckpt")
        st.commit(step, True)
    assert not st.exists("memory://b/ck/1/rank_0.ckpt")
    assert st.exists("memory://b/ck/2/rank_0.ckpt")
    assert st.exists("memory://b/ck/3/rank_0.ckpt")
