"""Sparse elastic recovery tests (ISSUE 9): KvVariable state riding
the flash-checkpoint engine.

Covers the load-bearing properties the chaos scenarios lean on:

- ``KvVariable.export()/import_()`` round trips BIT-EXACT with an
  ACTIVE spill tier (spilled rows included, equal to an identical
  DRAM-only table) and across ``evict_to_capacity`` — the export
  path is what checkpointing persists;
- the sparse optimizer family tail (sparse SGD, plain sparse Adam,
  rectified Adam) against numpy references, spill-parity included;
- ``SparseStateAdapter`` export/import/reshard semantics: content
  digests (order-independent, additive across disjoint shards),
  exactly-once key-hash repartitioning, optimizer scalars;
- the engine integration: shm + storage round trips, the cross-world
  shm refusal, and the 2->1 storage-tier reshard;
- telemetry: ``kv_checkpoint`` events, the
  ``dlrover_kv_checkpoint_seconds`` histogram, the timeline's ``+kv``
  restore slices, and the chaos invariants' verdict logic.

Numpy-heavy and fast — conftest runs this file in the early
wall-clock-protected group.
"""

import os
import time

import numpy as np
import pytest

from dlrover_tpu import chaos as chaos_mod
from dlrover_tpu.checkpoint.saver import (
    AsyncCheckpointSaver,
    SaverConfig,
)
from dlrover_tpu.checkpoint.sparse import (
    KV_STATE_KEY,
    SparseStateAdapter,
    owner_of_keys,
    rows_digest,
)
from dlrover_tpu.common.constants import CheckpointConstant
from dlrover_tpu.ops.kv_variable import (
    GroupAdagradOptimizer,
    GroupAdamOptimizer,
    GroupFtrlOptimizer,
    KvVariable,
    RectifiedAdamOptimizer,
    SparseAdamOptimizer,
    SparseSGDOptimizer,
)


def _sorted_export(table):
    """Export sorted by key — export order is an implementation
    detail; content equality is not."""
    k, v, f = table.export()
    order = np.argsort(k)
    return k[order], v[order], f[order]


def _assert_tables_bit_equal(a, b):
    ka, va, fa = _sorted_export(a)
    kb, vb, fb = _sorted_export(b)
    np.testing.assert_array_equal(ka, kb)
    assert va.tobytes() == vb.tobytes()
    np.testing.assert_array_equal(fa, fb)


def _train(table, opt, steps=20, n_keys=800, batch=128, seed=42):
    krng = np.random.default_rng(seed)
    for _ in range(steps):
        keys = krng.integers(0, n_keys, batch).astype(np.int64)
        emb = table.gather(keys)
        opt.apply_gradients(keys, np.tanh(emb) * 0.1)


@pytest.fixture()
def saver(tmp_path):
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(
        SaverConfig(
            checkpoint_dir=str(tmp_path / "ckpt"), local_shard_num=1,
            global_shard_num=1, node_rank=0,
        )
    )
    AsyncCheckpointSaver._instance = s
    yield s
    AsyncCheckpointSaver.reset()


@pytest.fixture()
def no_chaos():
    yield
    chaos_mod.uninstall()


# -- satellite 1: export/import round trip with an ACTIVE spill tier --


def test_export_import_bit_exact_with_active_spill(tmp_path):
    """The property checkpointing is built on: an export taken while
    real rows live on the cold tier equals the export of an identical
    DRAM-only table, bit for bit, and importing it reproduces the
    table exactly."""
    def build(spill):
        t = KvVariable(dim=8, initial_capacity=64, seed=11)
        opt = GroupAdamOptimizer(t, learning_rate=1e-2)
        if spill:
            t.enable_spill(
                str(tmp_path / "p.spill"), max_dram_rows=150
            )
            opt.enable_spill(str(tmp_path), max_dram_rows=150)
        _train(t, opt)
        return t, opt

    dram_t, _ = build(False)
    spill_t, spill_opt = build(True)
    st = spill_t.spill_stats()
    assert st["disk_rows"] > 0, st  # the tier is genuinely ACTIVE
    _assert_tables_bit_equal(dram_t, spill_t)
    for slot in spill_opt.slot_tables().values():
        assert slot.spill_stats()["disk_rows"] > 0

    # import into a fresh table (DRAM-only) -> bit-exact again
    k, v, f = spill_t.export()
    fresh = KvVariable(dim=8)
    fresh.import_(k, v, f)
    _assert_tables_bit_equal(fresh, spill_t)

    # and importing ONTO a table with an active spill tier round
    # trips too (the restore path of a spill-configured trainer)
    target = KvVariable(dim=8, initial_capacity=64)
    target.gather(np.arange(500, dtype=np.int64))  # stale junk
    target.enable_spill(
        str(tmp_path / "t.spill"), max_dram_rows=150
    )
    target.clear()
    target.import_(k, v, f)
    _assert_tables_bit_equal(target, spill_t)


def test_export_import_bit_exact_across_evict_to_capacity(tmp_path):
    """evict_to_capacity over a spilled table and over its DRAM-only
    twin must leave the same logical content, and the survivors'
    export still round trips."""
    def build(spill):
        t = KvVariable(dim=4, initial_capacity=64, seed=5)
        t.gather(np.arange(1200, dtype=np.int64))     # freq 1
        for _ in range(3):
            t.gather(np.arange(80, dtype=np.int64))   # hot class
        if spill:
            t.enable_spill(
                str(tmp_path / "e.spill"), max_dram_rows=100
            )
        return t

    dram, spill = build(False), build(True)
    assert spill.spill_stats()["disk_rows"] > 0
    ev_d = dram.evict_to_capacity(200)
    ev_s = spill.evict_to_capacity(200)
    assert ev_d == ev_s == 1200 - 80
    _assert_tables_bit_equal(dram, spill)

    k, v, f = spill.export()
    fresh = KvVariable(dim=4)
    fresh.import_(k, v, f)
    _assert_tables_bit_equal(fresh, spill)
    assert len(fresh) == 80


# -- satellite 2: the sparse optimizer family tail --------------------


def test_sparse_sgd_matches_numpy_reference():
    t = KvVariable(dim=4, seed=3)
    keys = np.array([2, 9, 2], dtype=np.int64)  # dup key in one batch
    w0 = t.gather(np.unique(keys)).copy()
    opt = SparseSGDOptimizer(t, learning_rate=0.5)
    rng = np.random.default_rng(0)
    grads = rng.normal(size=(3, 4)).astype(np.float32)
    opt.apply_gradients(keys, grads)

    ref = {k: w0[i].copy() for i, k in enumerate(np.unique(keys))}
    for i, k in enumerate(keys):
        ref[k] -= np.float32(0.5) * grads[i]
    got = t.gather(np.unique(keys), insert_missing=False,
                   count_freq=False)
    for i, k in enumerate(np.unique(keys)):
        np.testing.assert_array_equal(got[i], ref[k])
    assert opt.slot_tables() == {}


def test_sparse_adam_matches_numpy_reference():
    dim, steps = 4, 7
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8
    t = KvVariable(dim=dim, seed=1)
    keys = np.array([5], dtype=np.int64)
    w = t.gather(keys).astype(np.float64).copy()
    opt = SparseAdamOptimizer(t, learning_rate=lr, beta1=b1,
                              beta2=b2, eps=eps)
    m = np.zeros((1, dim)); v = np.zeros((1, dim))
    rng = np.random.default_rng(7)
    for step in range(1, steps + 1):
        g = rng.normal(size=(1, dim)).astype(np.float32)
        opt.apply_gradients(keys, g)
        g64 = np.float32(g).astype(np.float64)
        m = b1 * m + (1 - b1) * g64
        v = b2 * v + (1 - b2) * g64 * g64
        lr_t = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
        w -= lr_t * m / (np.sqrt(v) + eps)
    got = t.gather(keys, insert_missing=False, count_freq=False)
    np.testing.assert_allclose(got, w, rtol=1e-4, atol=1e-6)
    assert opt.state_scalars() == {"step": steps}


def test_rectified_adam_warmup_then_adaptive():
    """Early steps (rho_t <= 4) must be the bias-corrected momentum
    fallback — no adaptive division — and the rectified regime must
    engage later; the whole trajectory still learns."""
    dim = 2
    lr, b1, b2 = 0.05, 0.9, 0.999
    t = KvVariable(dim=dim, seed=2)
    keys = np.array([1], dtype=np.int64)
    w = t.gather(keys).astype(np.float64).copy()
    opt = RectifiedAdamOptimizer(t, learning_rate=lr, beta1=b1,
                                 beta2=b2)
    # rho_inf ~ 1999; rho_t(1) = rho_inf - 2*b2/(1-b2) ~ -0.0013 <= 4
    g = np.full((1, dim), 0.25, np.float32)
    opt.apply_gradients(keys, g)
    m = (1 - b1) * np.float64(0.25)
    expect = w - lr * (m / (1 - b1))  # momentum fallback, no v term
    got = t.gather(keys, insert_missing=False, count_freq=False)
    np.testing.assert_allclose(got, expect, rtol=1e-5)

    # drive past the rectification threshold and verify learning
    target = np.array([[1.0, -1.0]], np.float32)
    losses = []
    for _ in range(300):
        emb = t.gather(keys, count_freq=False)
        losses.append(float(((emb - target) ** 2).sum()))
        opt.apply_gradients(keys, 2 * (emb - target))
    assert opt.step > 5  # rho_t > 4 territory for b2=0.999
    assert losses[-1] < 0.1 * max(losses[0], 1e-3)


@pytest.mark.parametrize("opt_cls", [
    SparseSGDOptimizer, SparseAdamOptimizer, RectifiedAdamOptimizer,
])
def test_new_optimizers_spill_parity(tmp_path, opt_cls):
    """Like the GroupAdam parity test: bounding per-key state to a
    fraction of the key space must not change what is learned."""
    def run(spill):
        t = KvVariable(dim=4, initial_capacity=64, seed=9)
        opt = opt_cls(t, learning_rate=1e-2)
        if spill:
            t.enable_spill(
                str(tmp_path / f"{opt_cls.__name__}.spill"),
                max_dram_rows=120,
            )
            if hasattr(opt, "enable_spill"):
                opt.enable_spill(str(tmp_path), max_dram_rows=120)
        _train(t, opt, steps=15, n_keys=600)
        return t

    dense, spilled = run(False), run(True)
    assert spilled.spill_stats()["spills"] > 0
    _assert_tables_bit_equal(dense, spilled)


def test_optimizer_slot_and_scalar_contracts():
    """Every sparse optimizer exposes the adapter's registration
    surface; the stateful ones round-trip their step counter."""
    t = KvVariable(dim=4)
    cases = [
        (GroupAdamOptimizer(t), {"m", "v"}, True),
        (GroupAdagradOptimizer(t), {"acc"}, False),
        (GroupFtrlOptimizer(t), {"z", "n"}, False),
        (SparseSGDOptimizer(t), set(), False),
        (SparseAdamOptimizer(t), {"m", "v"}, True),
        (RectifiedAdamOptimizer(t), {"m", "v"}, True),
    ]
    for opt, slots, has_step in cases:
        assert set(opt.slot_tables()) == slots, type(opt).__name__
        if has_step:
            opt.step = 7
            assert opt.state_scalars() == {"step": 7}
            opt.load_state_scalars({"step": 3})
            assert opt.step == 3


# -- digests + ownership ----------------------------------------------


def _random_rows(n, dim, seed):
    rng = np.random.default_rng(seed)
    keys = rng.choice(10_000, size=n, replace=False).astype(np.int64)
    vals = rng.normal(size=(n, dim)).astype(np.float32)
    freq = rng.integers(1, 50, n).astype(np.uint64)
    return keys, vals, freq


def test_rows_digest_order_independent_and_additive():
    k, v, f = _random_rows(64, 4, 0)
    whole = rows_digest(k, v, f)
    perm = np.random.default_rng(1).permutation(64)
    assert rows_digest(k[perm], v[perm], f[perm]) == whole
    # disjoint shards ADD (mod 2**64) — the exactly-once invariant's
    # raw material
    a = rows_digest(k[:20], v[:20], f[:20])
    b = rows_digest(k[20:], v[20:], f[20:])
    assert (a + b) % (1 << 64) == whole
    assert rows_digest(
        np.empty(0, np.int64), np.empty((0, 4), np.float32),
        np.empty(0, np.uint64),
    ) == 0


def test_rows_digest_detects_any_mutation():
    k, v, f = _random_rows(32, 4, 2)
    base = rows_digest(k, v, f)
    v2 = v.copy()
    v2[5, 2] = np.nextafter(v2[5, 2], np.float32(np.inf))  # 1 ulp
    assert rows_digest(k, v2, f) != base
    f2 = f.copy(); f2[9] += 1
    assert rows_digest(k, v, f2) != base                   # freq counts
    assert rows_digest(k[:-1], v[:-1], f[:-1]) != base     # lost row
    kd = np.concatenate([k, k[:1]])
    vd = np.concatenate([v, v[:1]])
    fd = np.concatenate([f, f[:1]])
    assert rows_digest(kd, vd, fd) != base                 # dup row


def test_owner_of_keys_partitions_disjointly():
    keys = np.arange(5000, dtype=np.int64)
    for world in (1, 2, 3, 7):
        owners = owner_of_keys(keys, world)
        assert owners.min() >= 0 and owners.max() < max(world, 1)
        if world > 1:
            # every rank owns a non-trivial share (hash spreads)
            counts = np.bincount(owners, minlength=world)
            assert (counts > 5000 / world / 2).all(), counts
    assert (owner_of_keys(keys, 1) == 0).all()
    # deterministic: the train loops and the reshard must agree
    np.testing.assert_array_equal(
        owner_of_keys(keys, 3), owner_of_keys(keys, 3)
    )


# -- adapter ----------------------------------------------------------


def _adapter_with_state(seed=0, n=300, spill_dir=None):
    t = KvVariable(dim=4, initial_capacity=64, seed=seed, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)
    if spill_dir:
        t.enable_spill(
            os.path.join(spill_dir, "emb.spill"), max_dram_rows=80
        )
        opt.enable_spill(spill_dir, max_dram_rows=80)
    _train(t, opt, steps=10, n_keys=n)
    adapter = SparseStateAdapter(digest=True)
    adapter.register_optimizer(opt)
    return t, opt, adapter


def test_adapter_export_import_round_trip_events(
    tmp_path, monkeypatch,
):
    from dlrover_tpu.telemetry.events import EVENT_LOG_ENV, read_events
    from dlrover_tpu.telemetry.metrics import get_registry

    evlog = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(EVENT_LOG_ENV, evlog)
    t, opt, adapter = _adapter_with_state(
        spill_dir=str(tmp_path)
    )
    hist = get_registry().get("dlrover_kv_checkpoint_seconds")
    before = hist.snapshot(stage="export")["count"]
    state = adapter.export_state(step=4, rank=0)
    assert set(state) >= {"emb", "emb.m", "emb.v", "__scalars__"}
    assert hist.snapshot(stage="export")["count"] == before + 1

    # a different process restores: fresh tables, same registration
    t2, opt2, adapter2 = _adapter_with_state(seed=99, n=10)
    adapter2.import_state(state, tier="shm", step=4, rank=0)
    _assert_tables_bit_equal(t, t2)
    _assert_tables_bit_equal(opt.m, opt2.m)
    _assert_tables_bit_equal(opt.v, opt2.v)
    assert opt2.step == opt.step  # bias-correction counter restored

    events = [
        e for e in read_events(evlog)
        if e.get("type") == "kv_checkpoint"
    ]
    exports = [e for e in events if e["stage"] == "export"]
    restores = [e for e in events if e["stage"] == "restore"]
    assert exports and restores
    assert exports[-1]["spilled_rows"] > 0
    assert exports[-1]["digests"] == restores[-1]["digests"]
    assert restores[-1]["tier"] == "shm"
    assert restores[-1]["resharded"] is False


def test_adapter_reshard_exactly_once_any_world():
    """Shards from a 2-rank world resharded onto worlds of 1 and 3:
    row counts sum to the union, every owned row lands on exactly the
    rank the key hash names, content digests add up."""
    shards = {}
    source = {}
    for rank in range(2):
        t = KvVariable(dim=4, seed=rank + 1, name="emb")
        keys = np.arange(400, dtype=np.int64)
        mine = keys[owner_of_keys(keys, 2) == rank]
        t.gather(mine)
        k, v, f = t.export()
        source[rank] = (k, v, f)
        shards[rank] = {"emb": {"keys": k, "values": v, "freq": f}}
    total = sum(len(source[r][0]) for r in source)
    want_sum = sum(
        rows_digest(*source[r]) for r in source
    ) % (1 << 64)

    for new_world in (1, 3):
        imported = 0
        got_sum = 0
        seen = set()
        for rank in range(new_world):
            t = KvVariable(dim=4, name="emb")
            a = SparseStateAdapter(digest=True)
            a.register_table(t)
            info = a.import_shards(
                shards, world_size=new_world, rank=rank,
                from_world=2, step=7,
            )
            assert info.get("kv_resharded") is True
            imported += info["kv_rows"]
            k, v, f = t.export()
            assert (owner_of_keys(k, new_world) == rank).all()
            assert not (set(k.tolist()) & seen)  # disjoint
            seen |= set(k.tolist())
            got_sum = (got_sum + rows_digest(k, v, f)) % (1 << 64)
        assert imported == total == len(seen)
        assert got_sum == want_sum


def test_adapter_spill_io_error_breaks_tier_gracefully(
    tmp_path, monkeypatch, no_chaos,
):
    """The chaos leg in miniature: io_error on the ``kv.spill`` hook
    during export -> the cold tier dies, stranded rows drop out of
    the export (lost_rows stamped), DRAM rows persist, and the NEXT
    export reports the production breaker tripped."""
    from dlrover_tpu.telemetry.events import EVENT_LOG_ENV, read_events

    evlog = str(tmp_path / "events.jsonl")
    monkeypatch.setenv(EVENT_LOG_ENV, evlog)
    t, opt, adapter = _adapter_with_state(spill_dir=str(tmp_path))
    logical = len(t)
    disk_rows = t.spill_stats()["disk_rows"]
    assert disk_rows > 0
    chaos_mod.install(chaos_mod.Scenario(
        name="t", seed=0,
        rules=[chaos_mod.Rule(point="kv.spill", action="io_error")],
    ))
    state = adapter.export_state(step=2, rank=0)
    # DRAM rows exported; the stranded cold rows are skipped
    assert 0 < len(state["emb"]["keys"]) < logical
    # training continues; the next spill pass trips the breaker
    _train(t, opt, steps=3, n_keys=300)
    adapter.export_state(step=3, rank=0)
    events = [
        e for e in read_events(evlog)
        if e.get("type") == "kv_checkpoint"
        and e.get("stage") == "export"
    ]
    assert events[0].get("lost_rows", 0) > 0
    assert any(e.get("spill_disabled") for e in events)
    # the faulted export is still a VALID checkpoint of what it holds
    t2 = KvVariable(dim=4, name="emb")
    a2 = SparseStateAdapter(digest=True)
    a2.register_table(t2)
    a2.import_state({"emb": state["emb"]}, tier="storage", step=2)
    k, v, f = t2.export()
    got = t.gather(k, insert_missing=False, count_freq=False)
    # values of the surviving rows match the live table... modulo
    # the 3 extra training steps on touched keys; compare the export
    # against itself round-tripped instead
    k2, v2, f2 = _sorted_export(t2)
    order = np.argsort(state["emb"]["keys"])
    np.testing.assert_array_equal(
        k2, state["emb"]["keys"][order]
    )
    assert v2.tobytes() == np.ascontiguousarray(
        state["emb"]["values"]
    )[order].tobytes()


def test_adapter_rejects_duplicate_table_names():
    a = SparseStateAdapter()
    a.register_table(KvVariable(dim=2, name="emb"))
    with pytest.raises(ValueError, match="unique"):
        a.register_table(KvVariable(dim=2, name="emb"))


# -- engine integration -----------------------------------------------


def _engine(tmp_path, **kw):
    from dlrover_tpu.checkpoint.engine import CheckpointEngine

    kw.setdefault("replicated", True)
    kw.setdefault("local_rank", 0)
    kw.setdefault("global_rank", 0)
    kw.setdefault("world_size", 1)
    return CheckpointEngine(str(tmp_path / "ckpt"), **kw)


def _wait_commit(tmp_path, step, timeout=30):
    tracker = os.path.join(
        str(tmp_path / "ckpt"), CheckpointConstant.TRACKER_FILE
    )
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with open(tracker) as fh:
                if int(fh.read().strip() or -1) >= step:
                    return
        except (OSError, ValueError):
            pass
        time.sleep(0.1)
    raise AssertionError(f"step {step} never committed")


def test_engine_shm_round_trip_strips_kv(saver, tmp_path):
    t, opt, adapter = _adapter_with_state()
    engine = _engine(tmp_path)
    engine.register_sparse(adapter)
    dense = {"w": np.arange(6, dtype=np.float32), "step": 5}
    assert engine.save(5, dense)
    snapshot = {"emb": _sorted_export(t)}
    _train(t, opt, steps=5, n_keys=300, seed=77)  # diverge the table
    step, state = engine.load()
    assert step == 5
    assert KV_STATE_KEY not in state          # stripped before return
    np.testing.assert_array_equal(state["w"], dense["w"])
    k, v, f = _sorted_export(t)               # table rolled back
    np.testing.assert_array_equal(k, snapshot["emb"][0])
    assert v.tobytes() == snapshot["emb"][1].tobytes()
    np.testing.assert_array_equal(f, snapshot["emb"][2])
    assert engine.last_restore_phases["kv_rows"] > 0
    engine.close()


def test_engine_storage_round_trip_fresh_process(saver, tmp_path):
    t, opt, adapter = _adapter_with_state()
    engine = _engine(tmp_path)
    engine.register_sparse(adapter)
    assert engine.save(3, {"w": np.ones(4, np.float32)}, persist=True)
    assert engine.wait_async(timeout=30)
    _wait_commit(tmp_path, 3)
    engine.close()

    # a replacement process: fresh tables, fresh engine, no shm
    t2, opt2, adapter2 = _adapter_with_state(seed=50, n=10)
    e2 = _engine(tmp_path)
    e2._shm_handler.unlink()  # the kill dropped the shm segment
    e2.register_sparse(adapter2)
    step, state = e2.load()
    assert step == 3
    assert KV_STATE_KEY not in state
    _assert_tables_bit_equal(t, t2)
    _assert_tables_bit_equal(opt.m, opt2.m)
    assert opt2.step == opt.step
    assert e2.last_restore_phases["tier"] == "storage"
    e2.close()


def test_engine_cross_world_reshards_and_refuses_shm(tmp_path):
    """The elastic contract end to end: two world-2 ranks commit
    their hash-partitioned kv shards; a world-1 restore REFUSES the
    (world-2) shm snapshot and reshards the union from storage."""
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver(SaverConfig(
        checkpoint_dir=str(tmp_path / "ckpt"), local_shard_num=2,
        global_shard_num=2, node_rank=0,
    ))
    AsyncCheckpointSaver._instance = s
    try:
        ranks = {}
        for rank in range(2):
            t = KvVariable(dim=4, seed=rank + 1, name="emb")
            opt = GroupAdamOptimizer(t, learning_rate=1e-2)
            a = SparseStateAdapter(digest=True)
            a.register_optimizer(opt)
            keys = np.arange(300, dtype=np.int64)
            mine = keys[owner_of_keys(keys, 2) == rank]
            opt.apply_gradients(mine, np.tanh(t.gather(mine)) * 0.1)
            e = _engine(
                tmp_path, replicated=False, local_rank=rank,
                global_rank=rank, world_size=2,
            )
            e.register_sparse(a)
            ranks[rank] = (t, opt, e, mine)
        # local rank 0 notifies the agent; its persist reads ALL
        # local shards, so rank 1's shm snapshot must exist first
        assert ranks[1][2].save(
            1, {"w": np.full(2, 1.0, np.float32)}, persist=True
        )
        assert ranks[0][2].save(
            1, {"w": np.full(2, 0.0, np.float32)}, persist=True
        )
        assert ranks[0][2].wait_async(timeout=30)
        _wait_commit(tmp_path, 1)

        tn = KvVariable(dim=4, name="emb")
        on = GroupAdamOptimizer(tn, learning_rate=1e-2)
        an = SparseStateAdapter(digest=True)
        an.register_optimizer(on)
        en = _engine(
            tmp_path, replicated=False, local_rank=0,
            global_rank=0, world_size=1,
        )
        en.register_sparse(an)
        step, _state = en.load()
        assert step == 1
        # the shm tier (a world-2 snapshot) was refused
        assert en.last_restore_phases["tier"] == "storage"
        assert en.last_restore_phases.get("kv_resharded") is True
        # exactly the union, content bit-exact per source rank
        assert len(tn) == sum(len(r[3]) for r in ranks.values())
        for t_src, _o, _e, mine in ranks.values():
            got = tn.gather(mine, insert_missing=False,
                            count_freq=False)
            want = t_src.gather(mine, insert_missing=False,
                                count_freq=False)
            assert got.tobytes() == want.tobytes()
        for _t, _o, e, _m in ranks.values():
            e.close()
        en.close()
    finally:
        AsyncCheckpointSaver.reset()


# -- telemetry surfaces -----------------------------------------------


def test_timeline_restore_slice_shows_kv_stage():
    from dlrover_tpu.telemetry.timeline import assemble

    base = 1000.0
    tl = assemble([
        {"type": "train_step", "ts": base, "step": 1,
         "restart_count": 0},
        {"type": "checkpoint_restore", "ts": base + 10.0, "step": 4,
         "tier": "storage", "total_s": 2.0, "read_s": 0.5,
         "assemble_s": 0.5, "h2d_s": 0.2, "kv_s": 0.6,
         "kv_rows": 1200, "kv_resharded": True},
        {"type": "train_step", "ts": base + 11.0, "step": 5,
         "restart_count": 1},
    ])
    restores = [s for s in tl.slices if s.name.startswith("restore")]
    assert restores, [s.name for s in tl.slices]
    sl = restores[0]
    assert sl.name.endswith("+kv")
    assert sl.meta["kv_rows"] == 1200
    assert sl.meta["kv_s"] == 0.6
    assert sl.meta["kv_resharded"] is True


def test_kv_checkpoint_schema_registered():
    from dlrover_tpu.telemetry.schema import validate_event

    assert validate_event({
        "type": "kv_checkpoint", "ts": 1.0, "stage": "export",
        "rows": 10, "bytes": 1024, "spilled_rows": 2, "step": 3,
        "rank": 0, "digests": {"emb": {"rows": 10, "sum": "ff"}},
    }) == []
    assert validate_event(
        {"type": "kv_checkpoint", "ts": 1.0, "stage": "export"}
    )  # missing required rows/bytes flagged


# -- chaos invariant verdict logic ------------------------------------


def _ev(ts, **kw):
    kw["ts"] = ts
    return kw


def test_kv_state_round_trip_invariant_verdicts():
    from dlrover_tpu.chaos.harness import KvStateRoundTrip

    digests = {"emb": {"rows": 5, "sum": "00ab"}}
    good = [
        _ev(1.0, type="kv_checkpoint", stage="export", step=4,
            rows=5, bytes=1, digests=digests),
        _ev(2.0, type="chaos_inject", point="trainer.step",
            action="kill"),
        _ev(3.0, type="kv_checkpoint", stage="restore", step=4,
            rows=5, bytes=1, digests=digests),
    ]
    assert KvStateRoundTrip().check(good, None).ok
    bad = [dict(e) for e in good]
    bad[2]["digests"] = {"emb": {"rows": 5, "sum": "00ac"}}
    res = KvStateRoundTrip().check(bad, None)
    assert not res.ok and "emb" in res.detail
    # no digested export at the restored step -> fail, not pass
    res = KvStateRoundTrip().check(good[1:], None)
    assert not res.ok


def test_spill_breaker_tripped_invariant_verdicts():
    from dlrover_tpu.chaos.harness import SpillBreakerTripped

    events = [
        _ev(1.0, type="chaos_inject", point="kv.spill",
            action="io_error"),
        _ev(2.0, type="kv_checkpoint", stage="export", step=5,
            rows=3, bytes=1, spill_disabled=True, lost_rows=7),
    ]
    assert SpillBreakerTripped().check(events, None).ok
    no_trip = [events[0], dict(events[1])]
    no_trip[1].pop("spill_disabled")
    assert not SpillBreakerTripped().check(no_trip, None).ok


def test_kv_reshard_exactly_once_invariant_verdicts():
    from dlrover_tpu.chaos.harness import KvReshardExactlyOnce

    def exports(step):
        return [
            _ev(step, type="kv_checkpoint", stage="export",
                step=step, rank=r, rows=10, bytes=1,
                digests={"emb": {"rows": 10, "sum": f"{h:x}"}})
            for r, h in ((0, 0x10), (1, 0x20))
        ]

    def reshard(step, world, rows_by_rank, sums):
        return [
            _ev(step + 1, type="kv_checkpoint", stage="restore",
                step=step, resharded=True, world_size=world,
                rank=r, rows=rows, bytes=1, total_rows=20,
                digests={"emb": {"rows": rows, "sum": s}})
            for (r, rows), s in zip(rows_by_rank.items(), sums)
        ]

    ok = (
        exports(3)
        + reshard(3, 1, {0: 20}, ["30"])
        + reshard(3, 2, {0: 12, 1: 8}, ["12", "1e"])  # 0x12+0x1e=0x30
    )
    assert KvReshardExactlyOnce(min_reshards=2).check(ok, None).ok
    lost = exports(3) + reshard(3, 1, {0: 19}, ["30"])
    res = KvReshardExactlyOnce(min_reshards=1).check(lost, None)
    assert not res.ok and "19" in res.detail
    forged = exports(3) + reshard(3, 1, {0: 20}, ["31"])
    res = KvReshardExactlyOnce(min_reshards=1).check(forged, None)
    assert not res.ok and "diverge" in res.detail


# -- pipeline wiring --------------------------------------------------


def test_pipeline_attach_checkpoint_and_on_step(saver, tmp_path):
    """SparseTrainPipeline.attach_checkpoint registers table + slots
    with the engine, and on_step fires update-retired so a strict
    loop can checkpoint step-consistent state."""
    import jax.numpy as jnp

    from dlrover_tpu.checkpoint.checkpointer import Checkpointer
    from dlrover_tpu.trainer.sparse_pipeline import SparseTrainPipeline

    t = KvVariable(dim=4, seed=21, name="emb")
    opt = GroupAdamOptimizer(t, learning_rate=1e-2)

    def device_step(state, emb, ids):
        return state + 1, emb * 0.1, {"loss": jnp.sum(emb)}

    pipe = SparseTrainPipeline(t, opt, device_step, pipeline=False)
    ckpt = Checkpointer(str(tmp_path / "ckpt"))
    adapter = pipe.attach_checkpoint(ckpt)
    assert set(adapter.tables) == {"emb", "emb.m", "emb.v"}

    rng = np.random.default_rng(0)
    seen = []

    def on_step(state, steps_done):
        seen.append(steps_done)

    batches = [
        (rng.integers(0, 50, (4, 3)).astype(np.int64),
         np.zeros(1, np.float32))
        for _ in range(3)
    ]
    pipe.run(jnp.zeros(()), iter(batches), on_step=on_step)
    assert seen == [1, 2, 3]
    ckpt.close()
