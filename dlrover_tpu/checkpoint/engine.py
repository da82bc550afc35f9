"""Trainer-process checkpoint engine: shm write + async persist enqueue.

Reference: ``CheckpointEngine`` / ``FullCheckpointEngine``
(``dlrover/trainer/torch/flash_checkpoint/engine.py:135,291``,
``full_ckpt_engine.py``).  ``save`` copies the state dict into
agent-owned shared memory under the shm lock — for a state of device
arrays from an on-device snapshot, on a writer thread beside the
steps — and with ``persist`` enqueues a SAVE event the agent persists
asynchronously (the reference's ``save_to_memory`` /
``save_to_storage``); ``load`` prefers the shm snapshot (process
restart with agent alive) and falls back to storage.
"""

import atexit
import functools
import os
import queue
import threading
import time
import weakref
from typing import Any, Dict, Optional, Tuple

from dlrover_tpu.checkpoint.saver import (
    EVENT_QUEUE,
    FACTORY_QUEUE,
    LOCK_PREFIX,
    CheckpointEvent,
    CheckpointEventType,
    SaverConfig,
    read_last_checkpoint,
)
import numpy as np

from dlrover_tpu.checkpoint.sharded import SHARD_SEP
from dlrover_tpu.checkpoint.sparse import KV_META_KEY, KV_STATE_KEY
from dlrover_tpu.checkpoint.shm_handler import (
    CheckpointConfig,
    SharedMemoryHandler,
    flat_from_raw,
    state_dict_from_raw,
)
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import SharedLock, SharedQueue
from dlrover_tpu.common.storage import get_checkpoint_storage
from dlrover_tpu.telemetry.events import emit_event
from dlrover_tpu.telemetry.metrics import get_registry
from dlrover_tpu.telemetry.tracing import (
    attach_context,
    inject_context,
    span as _span,
)

# how long a save waits for the writer thread to finish the previous
# snapshot before it is skipped: many times a write (seconds) behind
# the agent's in-RAM copy (seconds), short of a hung agent's 600 s
_WRITER_WAIT_BOUND_S = 60.0

# the writer thread's kick-off pauses after this many leaves, for this
# long: each ``copy_to_host_async`` keeps the interpreter's lock, and a
# thread that waits for that lock is handed it only every 5 ms, so an
# unbroken loop over 446 leaves (14-50 ms) held the loop's thread in
# the save call until it ended (PERF.md, PR 35)
_KICKOFF_TURN = 8
_KICKOFF_PAUSE_S = 1e-4

_REG = get_registry()
_SHM_SAVE_SECONDS = _REG.histogram(
    "dlrover_checkpoint_shm_save_seconds",
    "Device->host + shm memcpy time of one flash save (incl. lock)",
)
_ASYNC_WRITE_SECONDS = _REG.histogram(
    "dlrover_checkpoint_async_write_seconds",
    "Background writer latency from dequeue to shm write done",
)
_SAVE_SKIPPED_TOTAL = _REG.counter(
    "dlrover_checkpoint_save_skipped_total",
    "Flash saves skipped: the saver held the shard lock "
    "(saver_busy), or the writer thread was still busy after the "
    "bounded wait (writer_busy)",
)
_SAVE_ERRORS_TOTAL = _REG.counter(
    "dlrover_checkpoint_save_errors_total",
    "Failed async snapshot writes",
)
_RESTORE_SECONDS = _REG.histogram(
    "dlrover_checkpoint_restore_seconds",
    "Restore latency by tier (shm fast path vs storage)",
)
_RESTORE_STAGE_SECONDS = _REG.histogram(
    "dlrover_checkpoint_restore_stage_seconds",
    "Per-stage restore pipeline time (labels: tier, stage = "
    "read / assemble / h2d)",
)


def _memory_stats(dev) -> Dict[str, int]:
    """What the runtime says of ``dev``'s memory ({} on the CPU)."""
    return dev.memory_stats() or {}


def _drain_at_exit(engine_ref):
    """``atexit``: a normal exit commits what was accepted."""
    engine = engine_ref()
    if engine is not None and not engine.wait_async(_WRITER_WAIT_BOUND_S):
        logger.warning(
            "exit with a snapshot still being written after %.0f s; "
            "the shm segment is left torn", _WRITER_WAIT_BOUND_S,
        )


class CheckpointEngine:
    """Base engine: one per training process.

    ``replicated=True`` (DDP-style full checkpoint): every rank writes
    shm for fast restart-restore, only global rank 0's shard is
    persisted (global_shard_num=1).  ``replicated=False``
    (FSDP/GSPMD-style): every process persists its addressable shard
    (global_shard_num=world_size).
    """

    def __init__(
        self,
        checkpoint_dir: str,
        replicated: bool = True,
        local_rank: Optional[int] = None,
        global_rank: Optional[int] = None,
        world_size: Optional[int] = None,
        deletion_keep_latest: int = 0,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.replicated = replicated
        self._writer_queue: "queue.Queue" = queue.Queue(maxsize=1)
        self._writer_thread: Optional[threading.Thread] = None
        self._writer_lock = threading.Lock()
        self._jit_copy = None
        self._exit_drain = None
        # the route of a save (:meth:`_why_no_snapshot`): whether a
        # device has reported its memory, and whether a snapshot of
        # this engine has yet lived beside the caller's loop
        self._device_reports = False
        self._loop_unseen = True
        # "snapshot" | "caller": the route the last save took
        self.last_save_route = ""
        self._last_async_error: Optional[Exception] = None
        # phase breakdown of the last completed shm save (lock wait,
        # device->host fetch, memcpy) — surfaced so benches report the
        # dominant term instead of burying it in logs (VERDICT r2)
        self.last_save_phases: Dict[str, float] = {}
        # bytes of the last save this engine accepted (the shm
        # layout's total, or the snapshot's leaves for an async save)
        self.last_save_bytes = 0
        # stage breakdown of the last restore (tier + read/assemble/
        # h2d seconds) — same surfacing contract as the save phases
        self.last_restore_phases: Dict[str, Any] = {}
        self._local_rank = (
            local_rank if local_rank is not None
            else env_utils.get_local_rank()
        )
        self._rank = (
            global_rank if global_rank is not None else env_utils.get_rank()
        )
        self._world_size = (
            world_size if world_size is not None
            else env_utils.get_world_size()
        )
        self._shm_handler = SharedMemoryHandler(self._local_rank, host=False)
        self._shm_lock = SharedLock(
            f"{LOCK_PREFIX}_{self._local_rank}", create=False
        )
        # the LOCAL lead process drives its node's saver: each agent
        # hosts one saver and persists its node's shards, so every
        # node's local rank 0 must enqueue SAVE events.  (Gating on
        # GLOBAL rank 0 — the old condition — meant a multi-NODE
        # GSPMD job never persisted rank>0 shards: node 1's saver got
        # no events, and the world-2 commit waited forever for a done
        # file nobody would write.  Found by the elastic-resize chaos
        # run.)
        self._event_queue = (
            SharedQueue(EVENT_QUEUE, create=False)
            if self._local_rank == 0 else None
        )
        self._storage = get_checkpoint_storage(path=checkpoint_dir)
        # sparse (KvVariable) state adapter: when registered, every
        # save asks it for an export snapshot that rides the shm
        # segment under the reserved "__kv__" key, and every restore
        # imports (or cross-world reshards) the blobs back before the
        # dense state is returned
        self._sparse = None
        self._warned_keep_latest = False
        self._notified_agent = False
        self._deletion_keep_latest = deletion_keep_latest
        self._cached_step = -1
        # ship the saver config now so the agent-side saver (and its
        # shm/meta/lock servers) exists before the first load()
        # (reference creates the saver at engine construction too,
        # engine.py:253)
        self._notify_agent_to_create_saver()
        # trainer-side restore pre-fault: page-table population is
        # per process, so the agent's prefetch warms the AGENT — a
        # respawned trainer still cold-faults every page of the shm
        # snapshot inside the restore's assemble stage (measured ~5x
        # the warm copy).  Kick the strided touches on a daemon
        # thread NOW, overlapped with the caller's model build / jit
        # trace; by the time load() runs, the mapping is (mostly)
        # warm.  Only for respawns — a first incarnation has no
        # snapshot to warm.
        self._prefault_thread = None
        if env_utils.get_restart_count() > 0 and os.getenv(
            "DLROVER_RESTORE_PREFETCH", "1"
        ).strip().lower() not in ("0", "false", "no", "off"):
            self._prefault_thread = threading.Thread(
                target=self._prefault_shm,
                daemon=True,
                name="restore-prefault",
            )
            self._prefault_thread.start()

    def _prefault_shm(self):
        try:
            nbytes = self._shm_handler.prefault()
            if nbytes:
                logger.info(
                    "pre-faulted %.1f MB of shm snapshot during "
                    "trainer setup", nbytes / 2**20,
                )
        except Exception:  # noqa: BLE001 - warmup must never break
            logger.exception("shm pre-fault failed")

    @property
    def global_shard_num(self) -> int:
        return 1 if self.replicated else self._world_size

    def register_sparse(self, adapter) -> None:
        """Attach a
        :class:`~dlrover_tpu.checkpoint.sparse.SparseStateAdapter`:
        its KvVariable tables become checkpoint state alongside the
        dense pytree.  Requires dict-shaped state dicts (the blobs
        nest under the reserved ``__kv__`` key)."""
        if self.replicated and self._world_size > 1:
            # replicated persists only rank 0's shard
            # (global_shard_num=1): every other rank's kv rows would
            # silently vanish on a storage-tier restore.
            raise ValueError(
                "sparse state requires per-rank shards: construct the "
                "engine with replicated=False for world_size "
                f"{self._world_size} (replicated=True persists only "
                "rank 0, losing every other rank's kv rows)"
            )
        self._sparse = adapter

    def _merge_sparse(self, state_dict, step: int,
                      durable: bool = False):
        """Fold the adapter's export snapshot into a COPY of the
        state dict.  Runs synchronously with respect to table
        mutation (before the async writer takes over), so the sparse
        snapshot is consistent with the dense one: the save stall
        grows only by the export memcpy — the tables are host RAM
        already, there is no device fetch to wait on.

        ``durable`` marks a save headed for a committed storage step
        dir: with delta checkpoints enabled the adapter then exports
        only the rows touched since the previous durable export
        (periodic full bases, chain metadata under the kv subtree);
        memory-only saves always export full state — the shm segment
        holds exactly one snapshot and must stand alone."""
        if self._sparse is None:
            return state_dict
        if not isinstance(state_dict, dict):
            raise TypeError(
                "a sparse adapter requires a dict state_dict (the kv "
                f"blobs ride under {KV_STATE_KEY!r}); got "
                f"{type(state_dict).__name__}"
            )
        if KV_STATE_KEY in state_dict:
            return state_dict
        if durable and self._sparse.delta_checkpoints_enabled() and (
            # the newest delta's chain spans at most full_every
            # committed steps (base included), so keep_latest >=
            # full_every retains every link — the documented contract
            0 < self._deletion_keep_latest
            < self._sparse.delta_full_every()
        ) and not self._warned_keep_latest:
            self._warned_keep_latest = True
            logger.warning(
                "delta flash checkpoints need every chain link on "
                "storage, but deletion_keep_latest=%d < full_every="
                "%d — a pruned link breaks restore; raise "
                "keep_latest or lower full_every",
                self._deletion_keep_latest,
                self._sparse.delta_full_every(),
            )
        merged = dict(state_dict)
        merged[KV_STATE_KEY] = self._sparse.export_for_checkpoint(
            step=step, rank=self._rank, durable=durable
        )
        return merged

    def _notify_agent_to_create_saver(self):
        """Ship the saver config to the agent's factory queue once
        (reference: engine.py:253)."""
        if self._notified_agent or self._local_rank != 0:
            self._notified_agent = True
            return
        from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
        from dlrover_tpu.common.multi_process import _socket_path

        if AsyncCheckpointSaver.get_ckpt_saver() is not None:
            # saver already exists in this process (tests / local mode)
            self._notified_agent = True
            return
        if not os.path.exists(_socket_path(FACTORY_QUEUE)):
            # standalone mode (no tpurun agent): host the saver in this
            # process so the shm/meta/lock servers exist and persists
            # still happen asynchronously — they just no longer survive
            # a crash of *this* process (the agent-process deployment
            # does; reference behaviour is a warning + no persistence)
            logger.warning(
                "no agent checkpoint-saver factory found; hosting an "
                "in-process saver (snapshots will not survive a crash "
                "of this process)"
            )
            AsyncCheckpointSaver._instance = AsyncCheckpointSaver(
                SaverConfig(
                    checkpoint_dir=self.checkpoint_dir,
                    local_shard_num=1,
                    global_shard_num=self.global_shard_num,
                    node_rank=env_utils.get_node_rank(),
                    deletion_keep_latest=self._deletion_keep_latest,
                )
            )
            self._notified_agent = True
            return
        factory = SharedQueue(FACTORY_QUEUE, create=False)
        factory.put(
            SaverConfig(
                checkpoint_dir=self.checkpoint_dir,
                local_shard_num=env_utils.get_local_world_size(),
                global_shard_num=self.global_shard_num,
                node_rank=env_utils.get_node_rank(),
                deletion_keep_latest=self._deletion_keep_latest,
            )
        )
        self._notified_agent = True

    # -- save ---------------------------------------------------------------

    def save(
        self, step: int, state_dict, path: str = "",
        persist: bool = False,
    ) -> bool:
        """One flash save.  ``True`` means ACCEPTED: the state is in
        shared memory already, or in an on-device snapshot the writer
        thread is copying there.  The ``checkpoint_shm_save`` event is
        the commit (:meth:`wait_async` waits for it, every read
        through this engine does so first); with ``persist`` the agent
        is then asked to write the step to storage.

        ``route="snapshot"``, a state that holds a ``jax.Array``: the
        loop is blocked for an on-device copy alone.  A ``jax.Array``
        is immutable, so only buffer donation by the caller's next
        step has to be guarded against, where the reference copies
        synchronously because torch tensors mutate in place
        (ckpt_saver.py:174 _traverse_copy_to_shm); the device->host
        fetch and the copy into shm run on the writer thread beside
        the steps.  At most one snapshot is alive: while the previous
        one is still being written the call waits for the writer, and
        skips the save only past ``_WRITER_WAIT_BOUND_S``; and an
        engine's first snapshot on a device that reports its memory
        is committed before the call returns.  A crash between the
        call and the commit leaves a segment whose meta says
        ``writing``, and the restore falls to the next tier, as after
        a crash inside a synchronous copy.

        ``route="caller"``, written on the caller's thread and skipped
        if the agent holds the shard lock: a state of host leaves
        alone, and a state whose snapshot would not fit beside the
        next step.  That is decided anew at every save from what the
        device reports (:meth:`_why_no_snapshot`); a snapshot that
        raises RESOURCE_EXHAUSTED all the same sends this one save
        the same way."""
        import jax

        if not self._notified_agent:
            with _span("ckpt.save.notify_agent"):
                self._notify_agent_to_create_saver()
        # before the route is chosen: a save written on this thread
        # must not be overtaken by an older snapshot either
        if self._writer_queue.unfinished_tasks and not (
            self._wait_for_writer(step)
        ):
            logger.warning(
                "step %s: previous snapshot still writing after "
                "%.0f s; skipping save", step, _WRITER_WAIT_BOUND_S,
            )
            _SAVE_SKIPPED_TOTAL.inc(reason="writer_busy")
            return False
        snap = None
        # a walk over every leaf and one memory report a device: 1.4
        # ms of a 2.7 GB state's 25 ms call (PERF.md, PR 54)
        with _span("ckpt.save.route", step=step):
            on_device = any(
                isinstance(leaf, jax.Array)
                for leaf in jax.tree_util.tree_leaves(state_dict)
            )
            why = (
                self._why_no_snapshot(state_dict) if on_device else ""
            )
        if on_device and not why:
            try:
                with _span("ckpt.save.snapshot", step=step):
                    snap = self._device_snapshot(state_dict)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                why = str(e).splitlines()[0][:200]
        if why and self.last_save_route != "caller":
            logger.warning(
                "step %s: no snapshot, the state is written on the "
                "caller's thread: %s", step, why,
            )
        self.last_save_route = "caller" if snap is None else "snapshot"
        state = state_dict if snap is None else snap
        # the sparse export joins the state NOW, on the caller's
        # thread: synchronous with respect to table mutation, like
        # the on-device copy is for the dense leaves; the writer
        # thread must not read a table the next step scatters into
        exported = False
        if self._sparse is not None:
            with _span("ckpt.save.sparse_merge", step=step):
                merged = self._merge_sparse(state, step, durable=persist)
            exported = merged is not state
            state = merged
        if snap is None:
            ok = self._write_shm(step, state, path, block_lock=False)
            if ok and persist:
                self._enqueue_persist(step)
            if not ok and exported and persist:
                # a delta export already DRAINED its baseline; the
                # skipped save means those rows never became durable
                # — the next export must re-base
                self._sparse.checkpoint_chain_poison()
            return ok
        # the writer thread continues THIS call's span
        trace_ctx = inject_context()
        with _span("ckpt.save.enqueue", step=step):
            # (the byte count was the kick-off's until PR 32 moved
            # that to the writer thread: a walk over every leaf)
            self.last_save_bytes = sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)
                if isinstance(leaf, jax.Array)
            )
            self._ensure_writer()
            self._writer_queue.put(
                (step, state, path, persist, trace_ctx)
            )
        if self._loop_unseen and self._device_reports:
            # the first snapshot of this engine's life is not left
            # alive beside the loop: what the device has reserved so
            # far says nothing yet of the steps between two saves
            self._wait_for_writer(step)
        self._loop_unseen = False
        return True

    def _wait_for_writer(self, step: int) -> bool:
        """Stall until the writer thread is idle, up to the bound."""
        with _span("ckpt.save.writer_wait", step=step) as sp:
            t0 = time.perf_counter()
            idle = self.wait_async(_WRITER_WAIT_BOUND_S)
            sp.set_attribute(
                "waited_s", round(time.perf_counter() - t0, 6)
            )
        return idle

    def _write_shm(
        self, step: int, state_dict, path: str, block_lock: bool,
    ) -> bool:
        """Device->host fetch and copy into shm under the shard's shm
        lock; the ``checkpoint_shm_save`` event is the commit.  On the
        caller's thread the lock is tried once (``block_lock=False``):
        if the agent is still persisting the previous snapshot the
        save is skipped rather than stall training.  The writer
        thread waits for it — it is off the training path, and an
        accepted save must not be dropped."""
        # every rank locks its shard: the agent's breakpoint save reads
        # all local shards, so an unlocked write can be torn even for
        # ranks that never persist to storage; without an agent there
        # is no concurrent reader and no lock server to talk to
        locked = False
        lock_wait = 0.0
        if self._agent_lock_available():
            t0 = time.perf_counter()
            # held_by = what the holder said it was doing when this
            # acquire found the lock taken ("persist:<step>")
            with _span("ckpt.save.lock_wait", step=step) as sp:
                got = self._shm_lock.acquire(
                    blocking=block_lock, timeout=600.0
                )
                sp.set_attribute("acquired", got)
                sp.set_attribute(
                    "held_by", self._shm_lock.contended_with
                )
            if not got:
                logger.info(
                    "step %s: saver busy persisting; skipping shm save",
                    step,
                )
                _SAVE_SKIPPED_TOTAL.inc(reason="saver_busy")
                return False
            lock_wait = time.perf_counter() - t0
            locked = True
        try:
            config = CheckpointConfig(
                step=step,
                path=path or self.checkpoint_dir,
                rank=self._rank,
                world_size=self._world_size,
                global_shard_num=self.global_shard_num,
            )
            start = time.time()
            self._shm_handler.save_state_dict(state_dict, config)
            self._cached_step = step
            phases = dict(self._shm_handler.last_save_phases)
            self.last_save_bytes = phases.get("bytes", 0)
            phases["lock_wait_s"] = round(lock_wait, 3)
            phases["total_s"] = round(time.time() - start + lock_wait, 3)
            self.last_save_phases = phases
            _SHM_SAVE_SECONDS.observe(phases["total_s"])
            emit_event(
                "checkpoint_shm_save",
                step=step,
                rank=self._rank,
                **{k: v for k, v in phases.items()},
            )
            logger.info(
                "rank %s shm save of step %s took %.3fs "
                "(lock %.2fs, d2h fetch %.2fs, memcpy %.2fs)",
                self._rank, step, time.time() - start,
                lock_wait, phases.get("fetch_s", 0.0),
                phases.get("memcpy_s", 0.0),
            )
            return True
        finally:
            if locked:
                self._shm_lock.release()

    def _agent_lock_available(self) -> bool:
        """Whether an agent-side lock server exists for this shard
        (absent in standalone/no-agent mode, where a shm write has
        no concurrent reader to guard against)."""
        from dlrover_tpu.common.multi_process import _socket_path

        return os.path.exists(
            _socket_path(f"{LOCK_PREFIX}_{self._local_rank}")
        )

    # -- the snapshot and its writer thread ------------------------------------

    def _why_no_snapshot(self, state_dict) -> str:
        """Empty if every device that holds the state reports room for
        its shards a second time BESIDE the largest scratch a program
        has reserved there, else the reason.  A snapshot stays alive
        through the next steps: where ``bytes_in_use`` + the snapshot
        + ``peak_bytes_reserved`` pass ``bytes_limit`` the snapshot
        itself would succeed and the step after it would fail to load
        (TPU v5e, 34-layer GPT-2-XL: "Attempting to reserve 7.25G at
        the bottom of memory").  A backend that reports no such
        figures (the CPU's) is left to the allocation itself."""
        import math

        import jax

        snapshot: Dict[Any, int] = {}
        for leaf in jax.tree_util.tree_leaves(state_dict):
            if not isinstance(leaf, jax.Array):
                continue
            nbytes = leaf.dtype.itemsize * math.prod(
                leaf.sharding.shard_shape(leaf.shape)
            )
            for dev in leaf.sharding.addressable_devices:
                snapshot[dev] = snapshot.get(dev, 0) + nbytes
        for dev, nbytes in snapshot.items():
            stats = _memory_stats(dev)
            limit = stats.get("bytes_limit")
            reserved = stats.get("peak_bytes_reserved")
            if limit is None or reserved is None:
                continue
            self._device_reports = True
            in_use = stats.get("bytes_in_use", 0)
            if in_use + nbytes + reserved > limit:
                return (
                    f"{dev}: {in_use / 2**30:.2f} GB in use + "
                    f"{nbytes / 2**30:.2f} GB of snapshot + "
                    f"{reserved / 2**30:.2f} GB of program scratch "
                    f"pass the device's {limit / 2**30:.2f} GB"
                )
        return ""

    def _device_snapshot(self, state_dict):
        """Copy every device-array leaf to a fresh on-device buffer.

        The copy runs at HBM bandwidth (milliseconds) and protects the
        snapshot from buffer donation in the caller's jitted train
        step; mutable host arrays are copied too (typically tiny —
        step counters and the like), immutable scalars pass through.
        """
        import jax
        import numpy as np

        leaves, treedef = jax.tree_util.tree_flatten(state_dict)
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, np.ndarray):
                leaves[i] = leaf.copy()
        idx = [
            i for i, leaf in enumerate(leaves)
            if isinstance(leaf, jax.Array)
        ]
        if idx:
            if self._jit_copy is None:
                import jax.numpy as jnp

                self._jit_copy = jax.jit(
                    lambda xs: [jnp.copy(x) for x in xs]
                )
            copied = self._jit_copy([leaves[i] for i in idx])
            for i, c in zip(idx, copied):
                leaves[i] = c
        return jax.tree_util.tree_unflatten(treedef, leaves)

    @staticmethod
    def _kick_off_fetch(step: int, snap) -> None:
        """Start every leaf's device->host transfer, so that they run
        beside the wait for the shard's lock and the copies of the
        leaves before them.  On the writer thread: issued from the
        loop's thread they cost a 2.7 GB state 0.2 s of every call
        (0.02 s here), a time that varied from save to save, and
        slowed the steps after them (PERF.md, PR 32).  Every
        ``_KICKOFF_TURN`` leaves it sleeps ``_KICKOFF_PAUSE_S``: a
        real sleep, because the loop's thread, on its way out of the
        save call (the span's events are a file append), must wake
        and take the interpreter's lock before this thread asks for
        it again."""
        import jax

        with _span("ckpt.save.d2h_kickoff", step=step):
            started = 0
            for leaf in jax.tree_util.tree_leaves(snap):
                if isinstance(leaf, jax.Array):
                    try:
                        leaf.copy_to_host_async()
                    except Exception:  # noqa: BLE001
                        break
                    started += 1
                    if started % _KICKOFF_TURN == 0:
                        time.sleep(_KICKOFF_PAUSE_S)

    def _ensure_writer(self):
        with self._writer_lock:
            if self._exit_drain is None:
                # the writer is a daemon thread: without this a script
                # that ends right after an accepted save would leave
                # the segment ``writing`` and lose the previous
                # snapshot with it
                self._exit_drain = functools.partial(
                    _drain_at_exit, weakref.ref(self)
                )
                atexit.register(self._exit_drain)
            if self._writer_thread is None or (
                not self._writer_thread.is_alive()
            ):
                self._writer_thread = threading.Thread(
                    target=self._writer_loop, daemon=True,
                    name="ckpt-snapshot-writer",
                )
                self._writer_thread.start()

    def _writer_loop(self):
        while True:
            item = self._writer_queue.get()
            if item is None:
                return
            step, snap, path, persist, trace_ctx = item
            try:
                # the save call's trace continues on this thread
                with attach_context(trace_ctx), _span(
                    "ckpt.save.write", step=step
                ):
                    self._kick_off_fetch(step, snap)
                    with _ASYNC_WRITE_SECONDS.time():
                        ok = self._write_shm(
                            step, snap, path, block_lock=True
                        )
                    if ok and persist:
                        self._enqueue_persist(step)
                if not ok:
                    raise TimeoutError(
                        "the shard's shm lock was not free in 600 s"
                    )
            except Exception as e:  # noqa: BLE001
                # without the traceback, whose frames hold the snapshot
                self._last_async_error = e.with_traceback(None)
                _SAVE_ERRORS_TOTAL.inc()
                if self._sparse is not None:
                    # the queued snapshot may hold a drained delta
                    # that never reached shm — re-base next export
                    self._sparse.checkpoint_chain_poison()
                logger.exception(
                    "async snapshot of step %s failed", step
                )
            finally:
                # the snapshot dies BEFORE the waiter wakes: the next
                # save takes its own right after, and the device holds
                # the state twice, never three times
                del item, snap
                self._writer_queue.task_done()

    def wait_async(self, timeout: float = 600.0) -> bool:
        """Block until every accepted save is committed to shm;
        returns False on timeout.  ``unfinished_tasks`` counts queued
        and in-progress items."""
        q = self._writer_queue
        with q.all_tasks_done:
            return q.all_tasks_done.wait_for(
                lambda: not q.unfinished_tasks, timeout
            )

    def _enqueue_persist(self, step: int):
        """Ask the agent to persist ``step`` (this node's lead process
        only); the event carries the save's trace context, so the
        agent's ``ckpt.persist`` span joins the same trace."""
        if self._event_queue is not None:
            self._event_queue.put(
                CheckpointEvent(
                    event_type=CheckpointEventType.SAVE, step=step,
                    trace=inject_context(),
                )
            )

    # -- load ---------------------------------------------------------------

    def _record_restore(
        self, tier: str, step: Optional[int], total_s: float,
        phases: Dict[str, Any], sp=None,
    ):
        """One restore's telemetry: phase dict on the engine (bench
        reads it), stage histograms, restore span attributes and the
        ``checkpoint_restore`` event (its ``tier`` field is what the
        chaos tier-fallback invariant keys on)."""
        phases = dict(phases)
        phases["total_s"] = round(total_s, 4)
        self.last_restore_phases = {"tier": tier, **phases}
        _RESTORE_SECONDS.observe(total_s, tier=tier)
        for stage in ("read", "assemble", "h2d"):
            # absent stages record nothing: orbax is opaque (no
            # stages at all), and the host-array load paths have no
            # h2d stage — their phases report h2d_s=0 for humans,
            # but 0.0 samples would fabricate the percentiles this
            # histogram exists to surface
            val = phases.get(f"{stage}_s")
            if val is not None and (stage != "h2d" or val > 0):
                _RESTORE_STAGE_SECONDS.observe(
                    val, tier=tier, stage=stage
                )
        if sp is not None:
            sp.set_attribute("tier", tier)
            for key, val in phases.items():
                sp.set_attribute(key, val)
        emit_event(
            "checkpoint_restore", step=step, tier=tier,
            rank=self._rank, **phases,
        )

    def load(self) -> Tuple[Optional[int], Any]:
        """Restore: shm snapshot if present (fast path after process
        restart), else storage via the tracker file.  Both tiers run
        the staged read/assemble pipeline; the per-stage breakdown
        lands in ``last_restore_phases``, the ``ckpt.restore`` span
        and the ``checkpoint_restore`` event."""
        from dlrover_tpu.checkpoint.restore import RestoreStats
        with _span("ckpt.restore") as sp:
            stats = RestoreStats()
            t0 = time.perf_counter()
            config, state = self.get_state_dict_from_memory(stats)
            if (
                config is not None
                and self._sparse is not None
                and int(getattr(config, "world_size", 0) or 0)
                != self._world_size
            ):
                # the dense cross-world rule applies to kv state too:
                # an shm snapshot of another world is per-node state —
                # sparse cross-world restores reshard the hash table
                # from the globally COMMITTED storage tier
                logger.warning(
                    "shm snapshot is from world size %s but this "
                    "world is %s; skipping the shm tier (sparse "
                    "cross-world restores reshard from storage)",
                    config.world_size, self._world_size,
                )
                config, state = None, {}
            if config is not None:
                state = self._consume_sparse(
                    state, stats, tier="shm", step=config.step
                )
                self._record_restore(
                    "shm", config.step, time.perf_counter() - t0,
                    stats.to_phases(), sp,
                )
                logger.info(
                    "restored step %s from shared memory "
                    "(read %.3fs, assemble %.3fs, %d workers)",
                    config.step, stats.read_s, stats.assemble_s,
                    stats.workers,
                )
                return config.step, state
            stats = RestoreStats()
            t0 = time.perf_counter()
            step, state = self.load_from_storage(stats)
            if step is not None:
                self._record_restore(
                    "storage", step, time.perf_counter() - t0,
                    stats.to_phases(), sp,
                )
            else:
                sp.set_attribute("tier", "none")
            return step, state

    def get_state_dict_from_memory(self, stats=None):
        """shm-tier restore.  With ``stats=None`` (direct callers,
        e.g. the bench's shm-only measurement) the engine records the
        restore itself; inside :meth:`load` the caller passes its
        accumulator and records with the tier decision."""
        from dlrover_tpu.checkpoint.restore import RestoreStats

        own = stats is None
        if own:
            stats = RestoreStats()
        # a process reads its own writes: accepted saves commit first
        self.wait_async()
        t0 = time.perf_counter()
        try:
            config, state = self._shm_handler.load_state_dict(
                stats=stats
            )
        except Exception as e:  # noqa: BLE001
            logger.warning("shm restore failed: %s", e)
            return None, {}
        if own and config is not None:
            self._record_restore(
                "shm", config.step, time.perf_counter() - t0,
                stats.to_phases(),
            )
        return config, state

    def _consume_sparse(self, state, stats, tier: str, step):
        """Pop the ``__kv__`` subtree out of a restored (same-world)
        state dict and import it into the registered tables; the kv
        stage timings land in ``stats.extra`` so the restore event
        and the timeline's restore slices show them."""
        if self._sparse is None or not isinstance(state, dict):
            return state
        kv_state = state.pop(KV_STATE_KEY, None)
        if kv_state is None:
            logger.warning(
                "sparse adapter registered but checkpoint step %s "
                "carries no kv state; tables left untouched", step,
            )
            return state
        self._import_kv_same_world(kv_state, tier, step, stats)
        return state

    def _import_kv_same_world(self, kv_state, tier, step, stats):
        """Same-world kv import, delta-chain aware: a full/base blob
        imports verbatim; a delta blob replays its chain — base +
        intermediate deltas read from the committed storage step dirs
        named in the link metadata, then the blob in hand.  A broken
        chain (pruned or never-persisted link) raises: silently
        restoring partial sparse state would be worse than failing
        the tier loudly."""
        meta = kv_state.get(KV_META_KEY)
        if isinstance(meta, dict) and meta.get("kind") == "delta":
            want_rank = 0 if self.replicated else self._rank
            links = self._kv_chain_links(meta, want_rank)
            if links is None:
                raise RuntimeError(
                    f"kv delta checkpoint of step {step} is "
                    "unusable: a chain link is missing from storage "
                    "(pruned by deletion_keep_latest, or its persist "
                    "never committed)"
                )
            info = self._sparse.import_chain(
                links + [kv_state], tier=tier, step=step,
                rank=self._rank,
            )
        else:
            info = self._sparse.import_state(
                kv_state, tier=tier, step=step, rank=self._rank
            )
        stats.extra.update(info)

    def _read_kv_state_at(self, step: int, rank: int):
        """One rank's nested kv subtree of a SPECIFIC committed step,
        as lazy views into the shard's mmap (the streaming import
        pages in only the window it copies).  None when the step dir
        or the kv subtree is absent."""
        from dlrover_tpu.checkpoint.saver import read_checkpoint_at
        from dlrover_tpu.checkpoint.sparse import SparseStateAdapter

        got_step, shards = read_checkpoint_at(
            self.checkpoint_dir, step, self._storage, only_rank=rank,
        )
        if got_step is None or rank not in shards:
            return None
        meta, raw = shards[rank]
        flat, _metas = flat_from_raw(meta, raw, detach=False)
        kv_flat, _rest = SparseStateAdapter.split_flat(flat)
        if not kv_flat:
            return None
        # the views reference `raw` via .base, so the mapping stays
        # alive for as long as the caller holds the nested dict
        return SparseStateAdapter.nest_flat(kv_flat)

    def _kv_chain_links(self, kv_meta, rank: int):
        """Resolve a delta link's replay prefix (base + intermediate
        deltas, oldest first) for one rank; None when any link is
        missing."""
        from dlrover_tpu.checkpoint.sparse import SparseStateAdapter

        links = []
        for s in SparseStateAdapter.chain_steps(kv_meta):
            st = self._read_kv_state_at(int(s), rank)
            if st is None:
                logger.error(
                    "kv delta chain broken: step %s has no kv shard "
                    "for rank %s on storage", s, rank,
                )
                return None
            links.append(st)
        return links

    def _kv_chains_for(self, nested_per_rank):
        """{rank: [links..., blob]} for a cross-world streaming
        reshard, resolving each rank's delta chain; None when any
        chain is broken."""
        chains = {}
        for rank, kv_state in sorted(nested_per_rank.items()):
            meta = kv_state.get(KV_META_KEY)
            if isinstance(meta, dict) and meta.get("kind") == "delta":
                links = self._kv_chain_links(meta, rank)
                if links is None:
                    return None
                chains[rank] = links + [kv_state]
            else:
                chains[rank] = [kv_state]
        return chains

    def _checkpoint_world(self, meta) -> Optional[int]:
        """World size stamped on a persisted shard's meta (the
        CheckpointConfig every save publishes)."""
        cfg = meta.get("config") if isinstance(meta, dict) else None
        if cfg is None:
            return None
        return int(getattr(cfg, "world_size", 0) or 0) or None

    def load_from_storage(self, stats=None) -> Tuple[Optional[int], Any]:
        """Storage-tier restore: tracker -> this rank's shard, read
        as a lazy mmap view and detached through the chunked parallel
        pipeline (page-in overlaps the copies).

        With a sparse adapter registered, every rank file is read:
        same-world restores import this rank's own kv shard verbatim;
        a WORLD CHANGE reshards — all old ranks' kv rows are
        re-partitioned by key hash and this rank imports its owned
        subset (the dense part then comes from the lowest surviving
        rank, which is only meaningful for replicated dense state —
        GSPMD jobs restore through :meth:`load_sharded`)."""
        from dlrover_tpu.checkpoint.restore import RestoreStats

        own = stats is None
        if own:
            stats = RestoreStats()
        t0 = time.perf_counter()
        want_rank = 0 if self.replicated else self._rank
        step, shards = read_last_checkpoint(
            self.checkpoint_dir, self._storage, stats=stats,
            only_rank=want_rank,
        )
        if step is None:
            return None, {}
        if self._sparse is not None:
            own_shard = shards.get(want_rank)
            ckpt_world = (
                self._checkpoint_world(own_shard[0])
                if own_shard else None
            )
            if own_shard is None or ckpt_world != self._world_size:
                # missing own shard or a world-stamp mismatch: only
                # now pay the all-ranks read (a cross-world reshard
                # needs every old rank's kv shard; the routine
                # same-world restore above reads exactly one file)
                step, shards = read_last_checkpoint(
                    self.checkpoint_dir, self._storage, stats=stats,
                )
                if step is None:
                    return None, {}
            if shards:
                return self._load_sparse_from_storage(
                    step, shards, want_rank, stats, t0, own
                )
        if want_rank not in shards:
            logger.error(
                "checkpoint step %s has no shard for rank %s "
                "(topology changed? shards=%s)",
                step, want_rank, sorted(shards),
            )
            return None, {}
        meta, raw = shards[want_rank]
        state = state_dict_from_raw(meta, raw, stats=stats)
        if own:
            self._record_restore(
                "storage", step, time.perf_counter() - t0,
                stats.to_phases(),
            )
        logger.info(
            "restored step %s from storage (read %.3fs, assemble "
            "%.3fs, %d workers)",
            step, stats.read_s, stats.assemble_s, stats.workers,
        )
        return step, state

    def _load_sparse_from_storage(
        self, step, shards, want_rank, stats, t0, own,
    ):
        """Storage restore with kv state: same-world = own shard
        verbatim; cross-world = dense from the lowest surviving rank
        + the hash-resharded kv subset."""
        any_meta = shards[min(shards)][0]
        ckpt_world = self._checkpoint_world(any_meta) or len(shards)
        if ckpt_world == self._world_size and want_rank not in shards:
            # the world did NOT change — a missing own shard is a
            # broken checkpoint (partial commit, lost file), not a
            # reshard: falling through would silently hand this rank
            # another rank's DENSE state
            logger.error(
                "checkpoint step %s has no shard for rank %s though "
                "the world size (%s) is unchanged; treating the "
                "checkpoint as unusable", step, want_rank, ckpt_world,
            )
            return None, {}
        same_world = (
            ckpt_world == self._world_size and want_rank in shards
        )
        if same_world:
            meta, raw = shards[want_rank]
            state = state_dict_from_raw(meta, raw, stats=stats)
            state = self._consume_sparse(
                state, stats, tier="storage", step=step
            )
        else:
            logger.warning(
                "checkpoint step %s is from world %s, this world is "
                "%s: streaming-resharding kv state from %d rank "
                "file(s)", step, ckpt_world, self._world_size,
                len(shards),
            )
            from dlrover_tpu.checkpoint.sparse import (
                SparseStateAdapter,
            )

            dense_rank = (
                want_rank if want_rank in shards else min(shards)
            )
            kv_per_rank = {}
            state = {}
            # kv subtrees stay LAZY VIEWS into each shard's mmap —
            # the streaming reshard copies one window at a time, so
            # peak extra RAM is O(window), not O(sum of shards).
            # Only the dense rank's remainder is materialized.
            for rank, (meta, raw) in sorted(shards.items()):
                flat, metas = flat_from_raw(
                    meta, raw, detach=False, stats=stats
                )
                kv_flat, _rest = SparseStateAdapter.split_flat(flat)
                if kv_flat:
                    kv_per_rank[rank] = SparseStateAdapter.nest_flat(
                        kv_flat
                    )
                if rank == dense_rank:
                    state = self._detach_dense_flat(
                        flat, metas, stats
                    )
            if kv_per_rank:
                chains = self._kv_chains_for(kv_per_rank)
                if chains is None:
                    # same contract as the load_sharded path: a
                    # broken chain fails the restore LOUDLY —
                    # returning "no checkpoint" would silently
                    # restart the job from scratch
                    raise RuntimeError(
                        f"kv delta chain of step {step} is unusable "
                        "for the cross-world reshard (a link is "
                        "missing from storage)"
                    )
                info = self._sparse.import_shards_streaming(
                    chains,
                    world_size=self._world_size,
                    rank=self._rank,
                    from_world=ckpt_world,
                    tier="storage",
                    step=step,
                )
                stats.extra.update(info)
        if own:
            self._record_restore(
                "storage", step, time.perf_counter() - t0,
                stats.to_phases(),
            )
        logger.info(
            "restored step %s from storage (read %.3fs, assemble "
            "%.3fs, %d workers)",
            step, stats.read_s, stats.assemble_s, stats.workers,
        )
        return step, state

    def _detach_dense_flat(self, flat, metas, stats):
        """Materialize the dense remainder of a flat VIEW dict (kv
        entries already split out): array views detach through the
        staged pipeline, scalars pass through, shard entries
        assemble — the pieces of ``state_dict_from_raw`` without
        re-reading (or detaching) the kv blobs."""
        import time as _time

        from dlrover_tpu.checkpoint.restore import detach_flat
        from dlrover_tpu.checkpoint.shm_handler import (
            _assemble_flat,
            _unflatten_to_nested,
        )

        views = {
            k: v for k, v in flat.items()
            if isinstance(v, np.ndarray) and v.base is not None
        }
        out = dict(flat)
        out.update(detach_flat(views, stats=stats))
        t0 = _time.perf_counter()
        out = _assemble_flat(out, metas)
        if stats is not None:
            stats.assemble_s += _time.perf_counter() - t0
        return _unflatten_to_nested(out)

    def load_sharded(
        self, target_state, orbax_dir: str = "",
    ) -> Tuple[Optional[int], Any]:
        """Restore a GSPMD-sharded pytree onto ``target_state``'s
        shardings, re-sharding as needed (reference capability:
        fsdp_engine.py re-shard on load).

        Tier order: (1) this rank's shm snapshot, (2) all visible
        rank files of the last committed storage step (covers any
        topology change on a shared filesystem), (3) the orbax tier at
        ``orbax_dir``.  Every target shard is assembled from the
        overlapping saved shard boxes; a tier is skipped when its
        shards do not cover the target arrays.

        Both flash tiers run the staged pipeline: the shm/mmap
        snapshot is consumed as zero-copy views (shard assembly copies
        straight out of them on the restore pool; plain leaves feed
        batched ``device_put``), so shard k+1 is paging in while shard
        k is in flight to the device.
        """
        from dlrover_tpu.checkpoint.restore import RestoreStats
        with _span("ckpt.restore") as sp:
            sp.set_attribute("sharded", True)
            self.wait_async()  # as get_state_dict_from_memory does
            stats = RestoreStats()
            t0 = time.perf_counter()
            config, flat, metas = self._shm_handler.load_flat(
                detach=False, stats=stats
            )
            if config is not None and int(
                getattr(config, "world_size", 0) or 0
            ) != self._world_size:
                # elastic world-resize: an shm snapshot from a
                # DIFFERENT world size is per-node state — each
                # survivor's segment may hold a different step, so
                # assembling from them would desync the re-formed
                # world.  Cross-world restores use the globally
                # COMMITTED storage tier; that is where the N-hosts ->
                # M-hosts shard redistribution happens.
                logger.warning(
                    "shm snapshot is from world size %s but this "
                    "world is %s; skipping the shm tier (cross-world "
                    "restores reshard from committed storage)",
                    config.world_size, self._world_size,
                )
                config, flat = None, {}
            if config is not None and flat:
                kv_flat = (
                    self._split_kv_flat(flat)
                    if self._sparse is not None else {}
                )
                state = self._assemble_to_target(
                    target_state, flat, metas, stats
                )
                if state is not None:
                    if kv_flat:
                        from dlrover_tpu.checkpoint.sparse import (
                            SparseStateAdapter,
                        )

                        self._import_kv_same_world(
                            SparseStateAdapter.nest_flat(kv_flat),
                            tier="shm", step=config.step,
                            stats=stats,
                        )
                    self._record_restore(
                        "shm", config.step,
                        time.perf_counter() - t0, stats.to_phases(), sp,
                    )
                    logger.info(
                        "restored sharded step %s from shared memory "
                        "(read %.3fs, assemble %.3fs, h2d %.3fs)",
                        config.step, stats.read_s, stats.assemble_s,
                        stats.h2d_s,
                    )
                    return config.step, state
            stats = RestoreStats()
            t0 = time.perf_counter()
            step, shards = read_last_checkpoint(
                self.checkpoint_dir, self._storage, stats=stats
            )
            if step is not None and shards:
                flat_all: Dict[str, Any] = {}
                metas_all: Dict[str, Any] = {}
                kv_per_rank: Dict[int, Dict[str, Any]] = {}
                for rank, (meta, raw) in sorted(shards.items()):
                    f, m = flat_from_raw(
                        meta, raw, detach=False, stats=stats
                    )
                    if self._sparse is not None:
                        # kv keys carry no shard suffix, so across
                        # ranks they would collide in flat_all (last
                        # rank silently winning) — each rank's rows
                        # are DISTINCT table shards, not replicas
                        kv_f = self._split_kv_flat(f)
                        if kv_f:
                            kv_per_rank[rank] = kv_f
                    for key, val in f.items():
                        # shard keys collide across ranks; namespace them
                        nk = (
                            f"{key}~r{rank}" if SHARD_SEP in key else key
                        )
                        flat_all[nk] = val
                        if key in m:
                            metas_all[nk] = m[key]
                state = self._assemble_to_target(
                    target_state, flat_all, metas_all, stats
                )
                if state is not None:
                    if kv_per_rank:
                        self._import_sharded_kv(
                            kv_per_rank, shards, step, stats
                        )
                    self._record_restore(
                        "storage", step,
                        time.perf_counter() - t0, stats.to_phases(), sp,
                    )
                    logger.info(
                        "restored sharded step %s from storage "
                        "(%d rank files; read %.3fs, assemble %.3fs, "
                        "h2d %.3fs)", step, len(shards), stats.read_s,
                        stats.assemble_s, stats.h2d_s,
                    )
                    return step, state
            if orbax_dir:
                from dlrover_tpu.checkpoint.orbax_compat import (
                    GlobalCheckpointer,
                )

                t0 = time.perf_counter()
                ckptr = GlobalCheckpointer(orbax_dir)
                try:
                    step, state = ckptr.restore(target_state)
                finally:
                    ckptr.close()
                if step is not None:
                    # the orbax tier is opaque — total only
                    self._record_restore(
                        "orbax", step, time.perf_counter() - t0,
                        {}, sp,
                    )
                return step, state
            sp.set_attribute("tier", "none")
            return None, {}

    @staticmethod
    def _split_kv_flat(flat: Dict[str, Any]) -> Dict[str, Any]:
        """Pop the ``__kv__/``-prefixed entries out of a flat dict,
        returned keyed relative to the prefix."""
        from dlrover_tpu.checkpoint.sparse import SparseStateAdapter

        kv, rest = SparseStateAdapter.split_flat(flat)
        if kv:
            flat.clear()
            flat.update(rest)
        return kv

    def _import_sharded_kv(self, kv_per_rank, shards, step, stats):
        """kv import for the load_sharded storage tier: own shard
        verbatim (chain-replayed when it is a delta link) when the
        world is unchanged and this rank's file exists, the STREAMING
        hash-reshard otherwise — the nested values are live views
        into the shard mmaps, so only one window is ever private."""
        from dlrover_tpu.checkpoint.sparse import SparseStateAdapter

        nested = {
            rank: SparseStateAdapter.nest_flat(kv)
            for rank, kv in kv_per_rank.items()
        }
        ckpt_world = (
            self._checkpoint_world(shards[min(shards)][0])
            or len(shards)
        )
        if ckpt_world == self._world_size and self._rank in nested:
            self._import_kv_same_world(
                nested[self._rank], tier="storage", step=step,
                stats=stats,
            )
            return
        chains = self._kv_chains_for(nested)
        if chains is None:
            raise RuntimeError(
                f"kv delta chain of step {step} is unusable for the "
                "cross-world reshard (a link is missing from storage)"
            )
        info = self._sparse.import_shards_streaming(
            chains, world_size=self._world_size, rank=self._rank,
            from_world=ckpt_world, tier="storage", step=step,
        )
        stats.extra.update(info)

    def _assemble_to_target(self, target_state, flat, metas, stats=None):
        """Assemble every leaf of ``target_state`` from saved entries;
        None when coverage is incomplete (caller tries next tier).

        Staged: host-side shard assembly for leaf k+1 runs on the
        restore pool while this thread commits leaf k's pieces to the
        devices, and plain host leaves ride batched ``device_put``
        calls (zero-copy views where the backend provably copies, a
        private detach otherwise) — so H2D, memcpy and page-in
        overlap instead of chaining.  The final block_until_ready
        keeps the shm/mmap views alive until every transfer landed.
        """
        import jax

        from dlrover_tpu.checkpoint.restore import (
            CHUNK_BYTES,
            RestoreStats,
            StagedRestore,
            detach_for_device_put,
        )
        from dlrover_tpu.checkpoint.sharded import (
            assemble_shard,
            assemble_target_pieces,
            commit_target_pieces,
            group_shard_entries,
            is_sharded_leaf,
        )
        from dlrover_tpu.checkpoint.shm_handler import (
            _flatten_state_dict,
            _path_str,
        )

        if stats is None:
            stats = RestoreStats()
        grouped, plain = group_shard_entries(flat, metas)
        target_flat = _flatten_state_dict(target_state)

        def host_job(key, target_leaf):
            """Host-side assembly of one leaf (pool thread; numpy
            only).  Returns (kind, payload): 'pieces' per-device host
            arrays for a sharded target, 'plain' a saved host leaf
            (possibly a view), 'plain_private' a freshly assembled
            private array, 'missing' a coverage failure message."""
            if is_sharded_leaf(target_leaf):
                entries = grouped.get(key)
                if entries is None and key in plain:
                    # saved unsharded (replicated whole array)
                    entries = [(
                        tuple((0, d) for d in plain[key].shape),
                        plain[key],
                    )]
                if entries is None:
                    return "missing", f"no saved shards for '{key}'"
                pieces = assemble_target_pieces(
                    tuple(target_leaf.shape),
                    np.dtype(target_leaf.dtype),
                    target_leaf.sharding,
                    entries,
                )
                if pieces is None:
                    return (
                        "missing", f"saved shards do not cover '{key}'"
                    )
                return "pieces", pieces
            if key in plain:
                return "plain", plain[key]
            if key in grouped:
                # saved sharded, target unsharded: assemble fully
                m = None
                for mk, mv in metas.items():
                    if mk.split(SHARD_SEP, 1)[0] == key:
                        m = mv
                        break
                if m is None:
                    return "missing", f"no shard metadata for '{key}'"
                full = assemble_shard(
                    tuple((0, d) for d in m.global_shape),
                    np.dtype(m.dtype),
                    grouped[key],
                )
                if full is None:
                    return (
                        "missing", f"saved shards do not cover '{key}'"
                    )
                return "plain_private", full
            return "missing", f"missing leaf '{key}' in checkpoint"

        out: Dict[str, Any] = {}
        failed: Optional[str] = None
        with StagedRestore() as staged:
            # BOUNDED in-flight window: submitting every leaf upfront
            # would let the pool assemble a full private copy of the
            # state ahead of consumption (serial mode would too — its
            # futures are lazy, but eager submission was the bug) —
            # peak host RAM must stay ~window leaves, not 2x the state
            window = max(2, staged.workers + 2)
            leaf_iter = iter(target_flat.items())
            jobs: list = []
            depth = 0

            def refill():
                nonlocal depth
                while depth < window:
                    nxt = next(leaf_iter, None)
                    if nxt is None:
                        return
                    key, leaf = nxt
                    jobs.append(
                        (key, leaf, staged.submit(host_job, key, leaf))
                    )
                    depth += 1

            refill()
            # batched H2D: plain host leaves accumulate and ship in one
            # device_put call per ~budget bytes — the per-call
            # dispatch overhead dominates small leaves, and a batch
            # issues all transfers at once
            budget = CHUNK_BYTES
            pending: list = []
            pending_bytes = 0

            def flush():
                nonlocal pending_bytes
                if not pending:
                    return
                t0 = time.perf_counter()
                arrs = jax.device_put(
                    [a for _, a, _ in pending],
                    [s for _, _, s in pending],
                )
                stats.h2d_s += time.perf_counter() - t0
                for (k, _, _), arr in zip(pending, arrs):
                    out[k] = arr
                pending.clear()
                pending_bytes = 0

            # index walk so refill() can append mid-loop AND each
            # consumed slot can be nulled — a completed future pins
            # its assembled host arrays via ._value, and keeping them
            # all would grow peak RAM to a full extra state copy
            i = 0
            while i < len(jobs):
                key, target_leaf, fut = jobs[i]
                jobs[i] = None
                i += 1
                t0 = time.perf_counter()
                try:
                    kind, payload = fut.result()
                except Exception as e:  # noqa: BLE001
                    kind, payload = "missing", f"'{key}': {e}"
                del fut
                stats.assemble_s += time.perf_counter() - t0
                depth -= 1
                if failed is None:
                    refill()
                if failed is not None:
                    continue  # drain remaining futures
                if kind == "missing":
                    failed = payload
                    continue
                if kind == "pieces":
                    t0 = time.perf_counter()
                    out[key] = commit_target_pieces(
                        tuple(target_leaf.shape),
                        target_leaf.sharding, payload,
                    )
                    stats.h2d_s += time.perf_counter() - t0
                    continue
                val = payload
                if isinstance(target_leaf, jax.Array) and isinstance(
                    val, np.ndarray
                ):
                    host = (
                        val if kind == "plain_private"
                        else detach_for_device_put(val)
                    )
                    pending.append((key, host, target_leaf.sharding))
                    pending_bytes += host.nbytes
                    if pending_bytes >= budget:
                        flush()
                elif isinstance(val, np.ndarray) and val.base is not None:
                    # view into shm/mmap headed back to the caller as a
                    # host array: detach — its buffer will be reused
                    out[key] = np.array(val, copy=True)
                else:
                    out[key] = val
            if failed is not None:
                logger.warning(failed)
                return None
            flush()
        # block so the views feeding any zero-copy transfer stay alive
        # until the bytes are on the device, and so h2d_s reports the
        # real transfer time rather than the async dispatch
        t0 = time.perf_counter()
        device_vals = [
            v for v in out.values() if isinstance(v, jax.Array)
        ]
        if device_vals:
            jax.block_until_ready(device_vals)
        stats.h2d_s += time.perf_counter() - t0
        # rebuild with the target's tree structure
        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(
            target_state
        )
        ordered = []
        for path, _ in leaves_with_path:
            key = "/".join(_path_str(p) for p in path)
            ordered.append(out[key])
        return jax.tree_util.tree_unflatten(treedef, ordered)

    def close(self):
        self.wait_async(timeout=_WRITER_WAIT_BOUND_S)
        if self._exit_drain is not None:
            atexit.unregister(self._exit_drain)
            self._exit_drain = None
        if self._writer_thread is not None and self._writer_thread.is_alive():
            self._writer_queue.put(None)
            self._writer_thread.join(timeout=5.0)
        # the prefault thread holds a numpy view over shm.buf while it
        # touches pages; closing the segment under it raises
        # BufferError — wait it out (page touches are memory-speed)
        if (
            self._prefault_thread is not None
            and self._prefault_thread.is_alive()
        ):
            self._prefault_thread.join(timeout=30.0)
        self._prefault_thread = None
        self._shm_handler.close()
