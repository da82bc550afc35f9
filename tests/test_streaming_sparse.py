"""Streaming sparse state (ISSUE 14): bounded-memory bulk paths.

Property coverage the scale story leans on:

- ANY chunking of the cursor-based native export (1 row, prime
  sizes, one-shot) is bit-identical to the unchunked export, on
  DRAM-only and spill-enabled twins, and the cursor survives
  residence moves mid-iteration;
- the streaming reshard is bit-identical to the one-shot
  ``import_shards`` at any window, clears stale rows, and its
  additive-digest exactly-once assert actually fires on a
  double-fed key;
- delta flash checkpoints: chain replay is digest-equal to a full
  export at every link, the serving and checkpoint consumer
  baselines never clear each other, and a skipped/failed save
  poisons the chain into a re-base;
- the engine round-trips a delta chain from committed storage;
- CI memory guard: a windowed reshard's peak extra RSS stays under
  2x the configured window while the one-shot path on the same
  shards exceeds it;
- the serving replica's windowed base ingest serves the same rows
  as the one-shot apply;
- ``restore_train_state`` rebuilds a typed TrainState without
  re-initializing the optimizer (the state_build satellite).

Numpy/native-heavy and fast — conftest runs this file in the early
wall-clock-protected group.
"""

import json
import os

import numpy as np
import pytest

from dlrover_tpu.checkpoint.sparse import (
    SparseStateAdapter,
    owner_of_keys,
    reshard_window_rows,
    rows_digest,
)
from dlrover_tpu.ops.kv_variable import (
    DIRTY_CONSUMER_CHECKPOINT,
    DIRTY_CONSUMER_SERVING,
    GroupAdamOptimizer,
    KvVariable,
)


def _sorted_export(table):
    k, v, f = table.export()
    order = np.argsort(k)
    return k[order], v[order], f[order]


def _assert_tables_bit_equal(a, b):
    ka, va, fa = _sorted_export(a)
    kb, vb, fb = _sorted_export(b)
    np.testing.assert_array_equal(ka, kb)
    assert va.tobytes() == vb.tobytes()
    np.testing.assert_array_equal(fa, fb)


def _train(table, opt, steps=10, n_keys=800, batch=128, seed=42):
    krng = np.random.default_rng(seed)
    for _ in range(steps):
        keys = krng.integers(0, n_keys, batch).astype(np.int64)
        opt.apply_gradients(keys, np.tanh(table.gather(keys)) * 0.1)


def _built(tmp_path, spill: bool, tag: str = "t"):
    t = KvVariable(dim=8, initial_capacity=64, seed=11, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)
    if spill:
        os.makedirs(tmp_path / tag, exist_ok=True)
        t.enable_spill(
            str(tmp_path / f"{tag}.spill"), max_dram_rows=150
        )
        opt.enable_spill(str(tmp_path / tag), max_dram_rows=150)
    _train(t, opt)
    return t, opt


# -- chunked native export ------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 7, 131, 10**6])
@pytest.mark.parametrize("spill", [False, True])
def test_chunked_export_bit_identical_any_chunking(
    tmp_path, chunk, spill
):
    """1-row, prime-sized and one-shot chunkings all reproduce the
    unchunked export bit for bit, DRAM-only and spill-backed alike
    (spilled rows read in place)."""
    os.makedirs(tmp_path / "t", exist_ok=True)
    table, _opt = _built(tmp_path, spill)
    if spill:
        assert table.spill_stats()["disk_rows"] > 0
    k0, v0, f0 = _sorted_export(table)
    parts = list(table.export_chunks(chunk))
    assert parts
    if chunk < len(table):
        assert len(parts) > 1
    k = np.concatenate([p[0] for p in parts])
    v = np.concatenate([p[1] for p in parts])
    f = np.concatenate([p[2] for p in parts])
    assert len(k) == len(k0)
    order = np.argsort(k)
    np.testing.assert_array_equal(k0, k[order])
    assert v0.tobytes() == v[order].tobytes()
    np.testing.assert_array_equal(f0, f[order])


def test_export_cursor_stable_across_residence_moves(tmp_path):
    """Promotions and spill passes BETWEEN chunk calls move rows
    across tiers; the key-snapshot cursor neither duplicates nor
    drops a key."""
    table = KvVariable(dim=4, seed=3)
    keys = np.arange(1000, dtype=np.int64)
    table.insert(
        keys,
        np.random.default_rng(0).normal(size=(1000, 4)).astype(
            np.float32
        ),
    )
    table.enable_spill(str(tmp_path / "c.spill"), max_dram_rows=300)
    k0, _v0, _f0 = table.export()
    it = table.export_chunks(100)
    seen = [next(it)]
    # promote a swath of cold rows (and trigger a spill pass) while
    # the cursor is live
    table.gather(np.arange(600, dtype=np.int64))
    seen.extend(it)
    got = np.concatenate([p[0] for p in seen])
    assert len(set(got.tolist())) == len(got), "duplicate keys"
    assert set(got.tolist()) == set(k0.tolist())


def test_import_chunked_round_trip(tmp_path):
    table, _ = _built(tmp_path, spill=False)
    k, v, f = table.export()
    for win in (1, 113, 10**6):
        fresh = KvVariable(dim=8, name="emb")
        fresh.reserve(len(k))
        assert fresh.import_chunked(k, v, f, max_rows=win) == len(k)
        _assert_tables_bit_equal(fresh, table)


# -- streaming reshard ----------------------------------------------------


def _two_shard_states(n_keys=600, dim=6, digest=True):
    shards = {}
    sources = {}
    keys = np.arange(n_keys, dtype=np.int64)
    for rank in range(2):
        t = KvVariable(dim=dim, seed=rank + 1, name="emb")
        opt = GroupAdamOptimizer(t, learning_rate=1e-2)
        a = SparseStateAdapter(digest=digest)
        a.register_optimizer(opt)
        mine = keys[owner_of_keys(keys, 2) == rank]
        opt.apply_gradients(mine, np.tanh(t.gather(mine)) * 0.1)
        shards[rank] = a.export_state()
        sources[rank] = (t, opt)
    return shards, sources


def _target(dim=6, digest=True, spill_path=None):
    t = KvVariable(dim=dim, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)
    if spill_path:
        t.enable_spill(str(spill_path), max_dram_rows=64)
    a = SparseStateAdapter(digest=digest)
    a.register_optimizer(opt)
    return t, opt, a


@pytest.mark.parametrize("window", [1, 37, 10**6])
def test_streaming_reshard_equals_oneshot(tmp_path, window):
    """Any window size produces tables bit-identical to the one-shot
    ``import_shards`` — including the optimizer slot tables and
    scalars — on a spill-enabled target twin."""
    shards, _src = _two_shard_states()
    t1, o1, a1 = _target()
    a1.import_shards(
        {r: dict(s) for r, s in shards.items()}, world_size=3, rank=1
    )
    t2, o2, a2 = _target(spill_path=tmp_path / "tgt.spill")
    info = a2.import_shards_streaming(
        {r: dict(s) for r, s in shards.items()}, world_size=3,
        rank=1, window_rows=window,
    )
    _assert_tables_bit_equal(t1, t2)
    _assert_tables_bit_equal(o1.m, o2.m)
    _assert_tables_bit_equal(o1.v, o2.v)
    assert o2.step == o1.step
    assert info["kv_resharded"] is True
    if window < 600:
        assert info["kv_chunks"] > 1


def test_streaming_reshard_clears_stale_rows():
    """A pre-populated target is REPLACED: rows of the previous
    world must not survive as phantom duplicates."""
    shards, _src = _two_shard_states(n_keys=100)
    t, _opt, a = _target()
    t.insert(
        np.array([10**6, 10**6 + 1], dtype=np.int64),
        np.ones((2, 6), np.float32),
    )
    a.import_shards_streaming(shards, world_size=1, rank=0,
                              window_rows=17)
    k, _v, _f = t.export()
    assert 10**6 not in set(k.tolist())
    assert len(k) == 100


def test_streaming_reshard_double_import_detected():
    """The additive-digest exactly-once assert FIRES when the same
    key arrives from two shards (a chunk imported twice and a
    colliding shard split are the same failure shape)."""
    shards, _src = _two_shard_states(n_keys=200, digest=True)
    # rank 1 re-exports rank 0's rows too: every rank-0 key arrives
    # twice, import digests double-count what the table keeps once
    dup = {
        0: shards[0],
        1: {
            name: {
                k: np.concatenate([sub[k], shards[0][name][k]])
                for k in ("keys", "values", "freq")
            } if isinstance(sub, dict) and "keys" in sub else sub
            for name, sub in shards[1].items()
        },
    }
    _t, _opt, a = _target(digest=True)
    with pytest.raises(RuntimeError, match="not exactly-once"):
        a.import_shards_streaming(dup, world_size=1, rank=0,
                                  window_rows=29)


def test_reshard_window_rows_env(monkeypatch):
    monkeypatch.setenv("DLROVER_KV_RESHARD_WINDOW_ROWS", "123")
    assert reshard_window_rows(1000) == 123
    monkeypatch.delenv("DLROVER_KV_RESHARD_WINDOW_ROWS")
    monkeypatch.setenv("DLROVER_KV_RESHARD_WINDOW_MB", "1")
    assert reshard_window_rows(2**20) == 1
    assert reshard_window_rows(2**18) == 4


# -- per-consumer dirty baselines ----------------------------------------


def test_two_plane_baselines_independent():
    """The serving publisher's delta drain must not clear rows out
    of the checkpoint consumer's next delta, and vice versa."""
    t = KvVariable(dim=4, name="emb")
    t.insert(np.arange(50, dtype=np.int64), np.ones((50, 4), np.float32))
    t.enable_dirty_tracking(DIRTY_CONSUMER_SERVING)
    t.enable_dirty_tracking(DIRTY_CONSUMER_CHECKPOINT)
    t.clear_dirty(DIRTY_CONSUMER_SERVING)
    t.clear_dirty(DIRTY_CONSUMER_CHECKPOINT)
    t.scatter_add(
        np.arange(10, dtype=np.int64), np.ones((10, 4), np.float32)
    )
    assert t.dirty_count(DIRTY_CONSUMER_SERVING) == 10
    assert t.dirty_count(DIRTY_CONSUMER_CHECKPOINT) == 10
    # serving drains ITS delta; the checkpoint baseline is untouched
    k, _v, _f = t.export_dirty(
        clear=True, consumer=DIRTY_CONSUMER_SERVING
    )
    assert len(k) == 10
    assert t.dirty_count(DIRTY_CONSUMER_SERVING) == 0
    assert t.dirty_count(DIRTY_CONSUMER_CHECKPOINT) == 10
    # and the checkpoint drain leaves a later serving touch alone
    t.export_dirty(clear=True, consumer=DIRTY_CONSUMER_CHECKPOINT)
    t.scatter_add(
        np.arange(3, dtype=np.int64), np.ones((3, 4), np.float32)
    )
    t.clear_dirty(DIRTY_CONSUMER_CHECKPOINT)
    assert t.dirty_count(DIRTY_CONSUMER_SERVING) == 3
    # tombstones are per-consumer too
    t.delete(np.array([0], dtype=np.int64))
    assert t.dead_count(DIRTY_CONSUMER_SERVING) == 1
    t.export_dead(clear=True, consumer=DIRTY_CONSUMER_SERVING)
    assert t.dead_count(DIRTY_CONSUMER_SERVING) == 0
    assert t.dead_count(DIRTY_CONSUMER_CHECKPOINT) == 1


# -- delta flash checkpoints ---------------------------------------------


def _delta_trained(tmp_path, full_every=4, steps=7, spill=False):
    t = KvVariable(dim=6, seed=9, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)
    if spill:
        t.enable_spill(str(tmp_path / "d.spill"), max_dram_rows=100)
    a = SparseStateAdapter(digest=True)
    a.register_optimizer(opt)
    a.enable_delta_checkpoints(full_every=full_every)
    links = []
    for step in range(1, steps + 1):
        keys = np.random.default_rng(step).integers(
            0, 400, 60
        ).astype(np.int64)
        opt.apply_gradients(keys, np.tanh(t.gather(keys)) * 0.1)
        links.append(a.export_for_checkpoint(step=step, durable=True))
    return t, opt, a, links


def test_delta_chain_digest_equal_at_every_link(tmp_path):
    """Replaying base + deltas onto a SPILL-ENABLED twin reproduces
    the source tables digest-equal at EVERY link — the restore-side
    correctness of the hot save path."""
    t, opt, a, links = _delta_trained(tmp_path, full_every=10)
    kinds = [b["__meta__"]["kind"] for b in links]
    assert kinds[0] == "base" and kinds.count("delta") >= 5, kinds
    # rebuild the source state AT each link by replaying prefixes
    for upto in range(1, len(links) + 1):
        tt = KvVariable(dim=6, name="emb")
        oo = GroupAdamOptimizer(tt, learning_rate=1e-2)
        tt.enable_spill(
            str(tmp_path / f"twin{upto}.spill"), max_dram_rows=50
        )
        aa = SparseStateAdapter(digest=True)
        aa.register_optimizer(oo)
        aa.import_chain(links[:upto])
        # digest of the replayed state == an independent replay of
        # the same prefix (self-consistency), and at the FINAL link
        # == the live source tables
        if upto == len(links):
            _assert_tables_bit_equal(t, tt)
            _assert_tables_bit_equal(opt.m, oo.m)
            assert oo.step == opt.step


def test_delta_checkpoint_cadence_and_meta(tmp_path):
    _t, _opt, _a, links = _delta_trained(
        tmp_path, full_every=3, steps=7
    )
    kinds = [b["__meta__"]["kind"] for b in links]
    assert kinds == [
        "base", "delta", "delta", "base", "delta", "delta", "base",
    ]
    # a delta link names its replay chain (base first)
    meta = links[1]["__meta__"]
    assert meta["parent"] == 1 and meta["base"] == 1
    assert SparseStateAdapter.chain_steps(meta) == [1]
    meta = links[5]["__meta__"]
    assert SparseStateAdapter.chain_steps(meta) == [4, 5]


def test_delta_checkpoint_poison_rebases(tmp_path):
    t, opt, a, links = _delta_trained(tmp_path, full_every=10,
                                      steps=2)
    assert links[1]["__meta__"]["kind"] == "delta"
    a.checkpoint_chain_poison()
    keys = np.arange(5, dtype=np.int64)
    opt.apply_gradients(keys, np.ones((5, 6), np.float32) * 0.1)
    nxt = a.export_for_checkpoint(step=3, durable=True)
    assert nxt["__meta__"]["kind"] == "base"
    # a non-durable (memory) save is ALWAYS a full export, no meta
    mem = a.export_for_checkpoint(step=4, durable=False)
    assert "__meta__" not in mem
    # ... and does not disturb the chain: next durable is a delta
    again = a.export_for_checkpoint(step=5, durable=True)
    assert again["__meta__"]["kind"] == "delta"


def test_delta_exports_are_o_rows_touched(tmp_path):
    """The delta blob carries only the touched rows — the hot save
    path's stall scales with the interval's work, not the table."""
    t, opt, a, _links = _delta_trained(tmp_path, full_every=100,
                                       steps=1)
    touched = np.arange(7, dtype=np.int64)
    opt.apply_gradients(touched, np.ones((7, 6), np.float32) * 0.1)
    blob = a.export_for_checkpoint(step=2, durable=True)
    assert blob["__meta__"]["kind"] == "delta"
    rows = sum(
        len(sub["keys"]) for name, sub in blob.items()
        if isinstance(sub, dict) and "keys" in sub
    )
    # param + m + v tables, only the touched keys each
    assert rows == 3 * 7, rows


# -- memory guard (CI) ----------------------------------------------------


def _trim_heap():
    """Give the free pages of glibc's heap back to the kernel, so that
    a leg of a guard starts from the heap state its other leg starts
    from: every page it touches is one its resident set grows by.  A
    worker's earlier files leave freed blocks in the heap; untrimmed,
    a leg's arrays come out of them, its resident set does not grow,
    the bounded path passes blind and its control fails at 0.0 MB.
    Called before EACH leg: before the control alone it would leave
    the bounded path blind."""
    import ctypes

    trim = getattr(ctypes.CDLL(None), "malloc_trim", None)
    if trim is not None:  # glibc's; another allocator keeps no such heap
        trim.argtypes, trim.restype = [ctypes.c_size_t], ctypes.c_int
        trim(0)


def test_windowed_reshard_memory_guard():
    """THE bounded-memory claim, measured: peak extra RSS during a
    windowed reshard of a ~20 MB 2-shard split stays ≤ 2x the
    configured window, while the one-shot path on the SAME shards
    blows well past it (it concatenates + dedups + masks the whole
    table).  The destination subset is kept small (world 16, rank 0)
    so the measurement isolates the path's transients."""
    from dlrover_tpu.common.env_utils import PeakRssSampler

    rows, dim = 40000, 128
    rng = np.random.default_rng(1)
    keys = np.arange(rows, dtype=np.int64)
    values = rng.normal(size=(rows, dim)).astype(np.float32)
    freq = np.ones(rows, dtype=np.uint64)
    own = owner_of_keys(keys, 2)
    shards = {
        r: {"emb": {
            "keys": keys[own == r], "values": values[own == r],
            "freq": freq[own == r],
        }}
        for r in range(2)
    }
    window_mb = 8
    window_rows = int(window_mb * 2**20 / (dim * 4 + 16))

    def fresh():
        t = KvVariable(dim, name="emb")
        return t, SparseStateAdapter(digest=False).register_table(t)

    t_s, a_s = fresh()
    _trim_heap()
    with PeakRssSampler() as rss_stream:
        info = a_s.import_shards_streaming(
            shards, world_size=16, rank=0, window_rows=window_rows,
        )
    assert info["kv_chunks"] > 1
    t_o, a_o = fresh()
    _trim_heap()
    with PeakRssSampler() as rss_oneshot:
        a_o.import_shards(shards, world_size=16, rank=0)
    _assert_tables_bit_equal(t_s, t_o)
    bound = 2 * window_mb * 2**20
    assert rss_stream.peak_extra_bytes <= bound, (
        f"windowed reshard peak extra RSS "
        f"{rss_stream.peak_extra_bytes / 2**20:.1f} MB > 2x window "
        f"{2 * window_mb} MB"
    )
    assert rss_oneshot.peak_extra_bytes > bound, (
        f"one-shot path only used "
        f"{rss_oneshot.peak_extra_bytes / 2**20:.1f} MB — the guard "
        "is not discriminating (table too small?)"
    )


def test_streamed_base_publish_memory_guard(tmp_path, monkeypatch):
    """The write side of the streaming story: a BASE serving publish
    of a ~20 MB table through the streamed zip writer stays under
    2x the export window of extra RSS, while the in-memory fallback
    (non-posix storage) materializes the whole table and blows past
    the same bound — and a replica ingesting the streamed generation
    serves bit-identical rows."""
    from dlrover_tpu.common.env_utils import PeakRssSampler
    from dlrover_tpu.serving import EmbeddingPublisher, ServingReplica

    rows, dim = 40000, 128
    window_mb = 8
    window_rows = int(window_mb * 2**20 / (dim * 4 + 16))
    monkeypatch.setenv(
        "DLROVER_KV_RESHARD_WINDOW_ROWS", str(window_rows)
    )
    rng = np.random.default_rng(7)
    keys = np.arange(rows, dtype=np.int64)
    values = rng.normal(size=(rows, dim)).astype(np.float32)

    def fresh():
        t = KvVariable(dim, name="emb")
        t.insert(keys, values)
        return t, SparseStateAdapter(digest=True).register_table(t)

    # streamed leg: default storage on a local path is posix -> the
    # windowed zip writer; peak extra RSS bounded by the window
    t_s, a_s = fresh()
    pub = EmbeddingPublisher(a_s, str(tmp_path / "s_stream"))
    _trim_heap()
    with PeakRssSampler() as rss_stream:
        gen = pub.publish(step=1)
    bound = 2 * window_mb * 2**20
    assert rss_stream.peak_extra_bytes <= bound, (
        f"streamed base publish peak extra RSS "
        f"{rss_stream.peak_extra_bytes / 2**20:.1f} MB > 2x window "
        f"{2 * window_mb} MB"
    )

    # fallback leg: a delegating wrapper that is NOT a
    # PosixDiskStorage forces the in-memory export path on the SAME
    # table size — it must exceed the bound, or the guard above is
    # not measuring anything
    from dlrover_tpu.common.storage import PosixDiskStorage

    class BufferedStorage:
        def __init__(self):
            self._inner = PosixDiskStorage()

        def __getattr__(self, name):
            return getattr(self._inner, name)

    t_f, a_f = fresh()
    pub_f = EmbeddingPublisher(
        a_f, str(tmp_path / "s_fallback"),
        storage=BufferedStorage(),
    )
    _trim_heap()
    with PeakRssSampler() as rss_fallback:
        pub_f.publish(step=1)
    assert rss_fallback.peak_extra_bytes > bound, (
        f"in-memory publish only used "
        f"{rss_fallback.peak_extra_bytes / 2**20:.1f} MB — the "
        "streamed guard is not discriminating (table too small?)"
    )

    # correctness: a replica ingests the streamed generation (its
    # windowed reader verifies the manifest digests) and serves the
    # exact source rows
    rep = ServingReplica(str(tmp_path / "s_stream"))
    assert rep.ingest_pending() == [gen]
    out = rep.lookup(keys)
    np.testing.assert_array_equal(out, values)


def test_streamed_base_sidecar_memory_guard(tmp_path, monkeypatch):
    """The sidecar half of the streamed-base claim: with a tiny
    embedding dim the key/freq columns are a THIRD of the bytes
    (16 B/row vs 32 B/row of values), so accumulating them in RAM
    during the export pass — the pre-spool writer did, at ~32 B/row
    once the concatenate copy lands — would blow far past the bound.
    The spooled writer replays them from disk window-by-window, so
    peak extra RSS stays ≤ 2x the export window even when the
    sidecars alone total several times that; the replica still serves
    bit-identical rows off the streamed generation."""
    from dlrover_tpu.common.env_utils import PeakRssSampler
    from dlrover_tpu.serving import EmbeddingPublisher, ServingReplica

    rows, dim = 2_500_000, 8
    window_mb = 8
    window_rows = int(window_mb * 2**20 / (dim * 4 + 16))
    monkeypatch.setenv(
        "DLROVER_KV_RESHARD_WINDOW_ROWS", str(window_rows)
    )
    rng = np.random.default_rng(11)
    keys = np.arange(rows, dtype=np.int64)
    values = rng.normal(size=(rows, dim)).astype(np.float32)
    t = KvVariable(dim, name="emb")
    t.insert(keys, values)
    a = SparseStateAdapter(digest=True).register_table(t)
    # sanity: the sidecars alone must dwarf the bound, or this guard
    # degenerates into the values-path test above
    bound = 2 * window_mb * 2**20
    assert rows * 16 > 2 * bound
    pub = EmbeddingPublisher(a, str(tmp_path / "s_sidecar"))
    with PeakRssSampler() as rss:
        gen = pub.publish(step=1)
    assert rss.peak_extra_bytes <= bound, (
        f"sidecar-dominant streamed publish peak extra RSS "
        f"{rss.peak_extra_bytes / 2**20:.1f} MB > 2x window "
        f"{2 * window_mb} MB"
    )
    rep = ServingReplica(str(tmp_path / "s_sidecar"))
    assert rep.ingest_pending() == [gen]
    probe = keys[:: max(1, rows // 4096)]
    np.testing.assert_array_equal(
        rep.lookup(probe), values[:: max(1, rows // 4096)]
    )


# -- engine round trip with delta chains ---------------------------------


def test_engine_delta_chain_storage_round_trip(tmp_path):
    """Storage restore of a DELTA checkpoint replays base +
    intermediate links from the committed step dirs and lands
    bit-identical tables in a fresh process-alike engine."""
    import time

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import (
        AsyncCheckpointSaver,
        SaverConfig,
    )
    from dlrover_tpu.common.constants import CheckpointConstant

    ckpt_dir = str(tmp_path / "ckpt")
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(SaverConfig(
        checkpoint_dir=ckpt_dir, local_shard_num=1,
        global_shard_num=1, node_rank=0,
    ))
    AsyncCheckpointSaver._instance = s
    try:
        def mk():
            t = KvVariable(dim=4, seed=7, name="emb")
            opt = GroupAdamOptimizer(t, learning_rate=1e-2)
            a = SparseStateAdapter(digest=True)
            a.register_optimizer(opt)
            return t, opt, a

        def wait_commit(step):
            tr = os.path.join(
                ckpt_dir, CheckpointConstant.TRACKER_FILE
            )
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    with open(tr) as fh:
                        if int(fh.read().strip() or -1) >= step:
                            return
                except (OSError, ValueError):
                    pass
                time.sleep(0.05)
            raise AssertionError(f"step {step} never committed")

        t, opt, a = mk()
        a.enable_delta_checkpoints(full_every=4)
        e = CheckpointEngine(ckpt_dir, replicated=True, local_rank=0,
                             global_rank=0, world_size=1)
        e.register_sparse(a)
        for step in range(1, 7):
            keys = np.random.default_rng(step).integers(
                0, 300, 40
            ).astype(np.int64)
            opt.apply_gradients(
                keys, np.tanh(t.gather(keys)) * 0.1
            )
            assert e.save(
                step, {"w": np.ones(3, np.float32) * step}, persist=True
            )
            assert e.wait_async(timeout=30)
            wait_commit(step)
        e.close()

        t2, opt2, a2 = mk()
        a2.enable_delta_checkpoints(full_every=4)
        e2 = CheckpointEngine(ckpt_dir, replicated=True, local_rank=0,
                              global_rank=0, world_size=1)
        e2._shm_handler.unlink()  # the kill dropped the segment
        e2.register_sparse(a2)
        step, state = e2.load()
        assert step == 6
        # step 6 is a delta (base at 5 after full_every=4): the
        # restore chained through storage
        assert e2.last_restore_phases.get("kv_chain", 0) >= 2, (
            e2.last_restore_phases
        )
        _assert_tables_bit_equal(t, t2)
        _assert_tables_bit_equal(opt.m, opt2.m)
        assert opt2.step == opt.step
        np.testing.assert_array_equal(
            state["w"], np.ones(3, np.float32) * 6
        )
        e2.close()
    finally:
        AsyncCheckpointSaver.reset()


def test_engine_grow_rank_without_own_shard_reshards(tmp_path):
    """World GROWTH regression: a new rank whose ``only_rank``
    narrowed read finds no shard file in the old world's step dir
    must fall back to the all-ranks read and STREAM-reshard its
    owned subset — not conclude 'no checkpoint' and start fresh
    (read_checkpoint_at returns (step, {}) for a listable step dir,
    None only for a missing one)."""
    from dlrover_tpu.chaos.harness import seed_sparse_world_checkpoint
    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.checkpoint.saver import (
        AsyncCheckpointSaver,
        SaverConfig,
        read_checkpoint_at,
        read_last_checkpoint,
    )

    ckpt_dir = str(tmp_path / "ckpt")
    seed = seed_sparse_world_checkpoint(ckpt_dir, world=2, step=4)
    # the narrowed read reports the step with an empty shard dict
    step, shards = read_last_checkpoint(ckpt_dir, only_rank=3)
    assert step == 4 and shards == {}
    # a pruned step dir yields no shards: the chain reader flags the
    # missing rank as a broken link
    assert read_checkpoint_at(ckpt_dir, 99)[1] == {}
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(SaverConfig(
        checkpoint_dir=ckpt_dir, local_shard_num=1,
        global_shard_num=4, node_rank=0,
    ))
    AsyncCheckpointSaver._instance = s
    try:
        # rank 3 of the GROWN world 4: no rank_3.ckpt exists in the
        # world-2 step dir
        t = KvVariable(dim=16, seed=17, name="emb")
        opt = GroupAdamOptimizer(t, learning_rate=5e-3)
        a = SparseStateAdapter(digest=True)
        a.register_optimizer(opt)
        e = CheckpointEngine(
            ckpt_dir, replicated=False, local_rank=0,
            global_rank=3, world_size=4,
        )
        e.register_sparse(a)
        step, _state = e.load()
        assert step == 4
        assert e.last_restore_phases.get("kv_resharded") is True
        # exactly the rows owner_of_keys assigns rank 3 of world 4
        k, _v, _f = t.export()
        assert len(k) > 0
        assert (owner_of_keys(k, 4) == 3).all()
        e.close()
    finally:
        AsyncCheckpointSaver.reset()


# -- events + schema ------------------------------------------------------


def test_kv_reshard_chunk_events_schema_valid(tmp_path, monkeypatch):
    from dlrover_tpu.telemetry.events import (
        EVENT_LOG_ENV,
        read_events,
    )
    from dlrover_tpu.telemetry.schema import validate_event

    log = tmp_path / "events.jsonl"
    monkeypatch.setenv(EVENT_LOG_ENV, str(log))
    shards, _src = _two_shard_states(n_keys=120)
    _t, _opt, a = _target()
    a.import_shards_streaming(shards, world_size=2, rank=0,
                              window_rows=13)
    events = list(read_events(str(log)))
    chunks = [
        e for e in events if e.get("type") == "kv_reshard_chunk"
    ]
    restores = [
        e for e in events
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "restore"
    ]
    assert chunks and restores
    for e in chunks + restores:
        assert validate_event(e) == [], e
    r = restores[-1]
    assert r.get("streamed") is True
    assert r["chunks"] == len(chunks)
    assert r["window_rows"] == 13


# -- state_build satellite ------------------------------------------------


def test_restore_train_state_skips_eager_optimizer_init():
    import jax
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint.shm_handler import (
        _flatten_state_dict,
        _unflatten_to_nested,
    )
    from dlrover_tpu.trainer.elastic_trainer import (
        TrainState,
        make_train_step,
        restore_train_state,
    )

    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    opt = optax.adam(1e-3)
    state = TrainState.create(params, opt)

    def loss(p, b):
        return ((b @ p["w"] + p["b"]) ** 2).mean()

    step = make_train_step(loss, opt)
    state, _m = step(state, jnp.ones((2, 4)))
    # simulate the shm round trip: flatten -> host numpy -> nested
    flat = {
        k: np.asarray(v)
        for k, v in _flatten_state_dict({"state": state}).items()
    }
    restored = _unflatten_to_nested(flat)["state"]

    calls = {"n": 0}
    real_init = opt.init

    class CountingOpt:
        def init(self, p):
            calls["n"] += 1
            return real_init(p)

        def update(self, *a, **kw):
            return opt.update(*a, **kw)

    state2 = restore_train_state(CountingOpt(), restored)
    # the init only ran ABSTRACTLY (inside eval_shape) — zero
    # concrete optimizer re-initialization, typed containers back
    assert type(state2.opt_state) is type(state.opt_state)
    for a, b in zip(
        jax.tree_util.tree_leaves(state),
        jax.tree_util.tree_leaves(state2),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues bit-identically from the rebuilt state
    s1, m1 = step(state, jnp.ones((2, 4)))
    s2, m2 = step(state2, jnp.ones((2, 4)))
    assert float(m1["loss"]) == float(m2["loss"])
    # TrainState.create defers init when slots are supplied
    calls["n"] = 0
    co = CountingOpt()
    st = TrainState.create(
        params, co, opt_state=state.opt_state, step=state.step
    )
    assert calls["n"] == 0
    assert st.opt_state is state.opt_state


# -- serving replica windowed base ingest --------------------------------


def test_replica_windowed_base_ingest(tmp_path, monkeypatch):
    """A base generation streams into staging tables in several
    windows and serves the same rows as the source; the swap is
    atomic (the replica's tables object changes identity, lookups
    see only old-or-new)."""
    from dlrover_tpu.serving import EmbeddingPublisher, ServingReplica

    # force several windows even at test scale
    monkeypatch.setenv("DLROVER_KV_RESHARD_WINDOW_ROWS", "50")
    table = KvVariable(dim=8, name="emb")
    table.insert(
        np.arange(300, dtype=np.int64),
        np.random.default_rng(2).normal(size=(300, 8)).astype(
            np.float32
        ),
    )
    adapter = SparseStateAdapter(digest=True).register_table(table)
    serving_dir = str(tmp_path / "serving")
    pub = EmbeddingPublisher(adapter, serving_dir)
    pub.publish(step=1)
    rep = ServingReplica(serving_dir)
    assert rep.ingest_pending() == [1]
    want = table.gather_or_zeros(np.arange(300, dtype=np.int64))
    got = rep.lookup(np.arange(300, dtype=np.int64), table="emb")
    assert want.tobytes() == got.tobytes()
    # a delta on top still applies through the (unchanged) delta path
    table.scatter_add(
        np.arange(5, dtype=np.int64), np.ones((5, 8), np.float32)
    )
    pub.publish(step=2)
    assert rep.ingest_pending() == [2]
    got2 = rep.lookup(np.arange(5, dtype=np.int64), table="emb")
    want2 = table.gather_or_zeros(np.arange(5, dtype=np.int64))
    assert want2.tobytes() == got2.tobytes()
