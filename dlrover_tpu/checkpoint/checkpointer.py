"""User-facing flash-checkpoint API.

Reference: ``Checkpointer`` ABC + ``DdpCheckpointer``
(``dlrover/trainer/torch/flash_checkpoint/checkpointer.py:23``,
``ddp.py:25``).  One class covers the JAX cases: replicated pytrees
(DDP parity) and per-process-sharded pytrees (FSDP/GSPMD parity) —
the sharding story is a constructor flag, not a separate engine
hierarchy, because on TPU both are just pytrees of ``jax.Array``.
"""

import threading
from enum import Enum
from typing import Any, Optional, Tuple

from dlrover_tpu.checkpoint.engine import CheckpointEngine
from dlrover_tpu.telemetry.tracing import span as _span


class RestoreHandle:
    """A restore running on a background thread, so its read/assemble
    stages overlap the caller's own setup (model build, optimizer
    init, jit trace) — the respawn-overlap half of invisible recovery.
    ``result()`` joins and returns ``(step, state)`` exactly as the
    synchronous call would (bit-identical: it IS the same code on
    another thread; the overlap regression test pins this).

    Not a ``concurrent.futures`` future on purpose: executor threads
    are non-daemon, and a restore wedged on a dead storage tier must
    never block process exit in this crash-heavy path."""

    def __init__(self, fn, args=(), kwargs=None):
        self._value: Optional[tuple] = None
        self._exc: Optional[Exception] = None

        def run():
            try:
                self._value = fn(*args, **(kwargs or {}))
            except Exception as e:  # noqa: BLE001 - re-raised
                self._exc = e

        self._thread = threading.Thread(
            target=run, daemon=True, name="restore-async"
        )
        self._thread.start()

    def done(self) -> bool:
        return not self._thread.is_alive()

    def result(self, timeout: Optional[float] = None):
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            raise TimeoutError("restore still running")
        if self._exc is not None:
            raise self._exc
        return self._value


class StorageType(Enum):
    """What a save asks for beyond shared memory: ``MEMORY`` nothing,
    ``DISK`` the agent's persist to storage after the commit.  Both
    take the same route into shared memory."""

    MEMORY = 0
    DISK = 1


class Checkpointer:
    """Save/load JAX pytree checkpoints with sub-second step stall.

    Usage::

        ckpt = Checkpointer("/ckpt/dir")
        ckpt.save_checkpoint(step, {"params": params, "opt": opt_state},
                             storage_type=StorageType.DISK)
        step, state = ckpt.load_checkpoint()
        ...
        ckpt.wait()   # before exit: the last save is committed
    """

    def __init__(
        self,
        checkpoint_dir: str,
        replicated: bool = True,
        deletion_keep_latest: int = 0,
        orbax_dir: str = "",
        orbax_every: int = 0,
        **engine_kwargs,
    ):
        """``orbax_dir`` + ``orbax_every``: additionally write every
        Nth storage save through the orbax tier — the re-shardable
        durable copy a topology change restores from (reference: the
        DCP/dist-ckpt tier next to flash saves)."""
        self.checkpoint_dir = checkpoint_dir
        self._engine = CheckpointEngine(
            checkpoint_dir,
            replicated=replicated,
            deletion_keep_latest=deletion_keep_latest,
            **engine_kwargs,
        )
        self._orbax_dir = orbax_dir
        self._orbax_every = orbax_every
        self._orbax = None
        self._orbax_waiter = None
        self._orbax_hung = False
        self._orbax_dirty = False
        self._storage_saves = 0

    def _orbax_tier(self):
        if self._orbax is None and self._orbax_dir:
            from dlrover_tpu.checkpoint.orbax_compat import (
                GlobalCheckpointer,
            )

            self._orbax = GlobalCheckpointer(self._orbax_dir)
        return self._orbax

    def register_sparse(self, adapter) -> None:
        """Attach a
        :class:`~dlrover_tpu.checkpoint.sparse.SparseStateAdapter`:
        the registered KvVariable tables (embedding + optimizer
        slots, spill tier included) ride every save under the
        reserved ``__kv__`` key and are imported — or, across a world
        change, hash-resharded from all old ranks' storage shards —
        on every restore."""
        self._engine.register_sparse(adapter)

    def save_checkpoint(
        self,
        step: int,
        state_dict: Any,
        path: str = "",
        storage_type: StorageType = StorageType.DISK,
    ) -> bool:
        """Three stages, for either storage type.  ACCEPTED: this
        call returns True; a state of device arrays is then held in an
        on-device snapshot (the call blocks the loop for that copy,
        and first for a still-running previous write).  COMMITTED: the
        writer thread has copied it into shared memory
        (``checkpoint_shm_save``); :meth:`wait` waits for that, and
        :meth:`load_checkpoint` and :meth:`close` do so themselves.
        PERSISTED (DISK only): the agent has written the step to
        storage (``checkpoint_persist``, the tracker file); nothing in
        this process waits for it.  False: the save was skipped.

        One ``ckpt.save`` span per call (``route``: ``snapshot``, or
        ``caller`` where the state was written on this thread): what
        the call blocks on is its children (``ckpt.save.<part>``); a
        snapshot's shm write runs later on the writer thread, as
        ``ckpt.save.write`` under the same trace id."""
        with _span(
            "ckpt.save", step=step, storage=storage_type.name.lower()
        ) as sp:
            ok = self._save(step, state_dict, path, storage_type)
            sp.set_attribute("ok", bool(ok))
            sp.set_attribute("bytes", self._engine.last_save_bytes)
            sp.set_attribute("route", self._engine.last_save_route)
        return ok

    def _save(self, step, state_dict, path, storage_type) -> bool:
        persist = storage_type == StorageType.DISK
        ok = self._engine.save(step, state_dict, path, persist=persist)
        if not persist:
            return ok
        # the durable tier is independent of the flash tier: a flash
        # save skipped as busy must not starve the orbax cadence, and
        # the cadence counts SAVES (not raw step numbers, which may
        # never hit the modulo)
        self._storage_saves += 1
        if (
            self._orbax_every
            and (self._storage_saves - 1) % self._orbax_every == 0
            and self._orbax_tier() is not None
        ):
            # async inside orbax; jax.Array immutability makes the
            # concurrent snapshot safe
            self._orbax_tier().save(step, state_dict)
            self._orbax_dirty = True
        return ok

    @property
    def last_restore_phases(self):
        """Stage breakdown of the last restore (``tier``, ``read_s``,
        ``assemble_s``, ``h2d_s``, ``total_s``, ``workers``) — the
        same numbers the ``checkpoint_restore`` event carries."""
        return dict(self._engine.last_restore_phases)

    def load_checkpoint(
        self, target_state: Any = None, orbax_dir: str = "",
    ) -> Tuple[Optional[int], Any]:
        """Without ``target_state``: host-array restore (replicated /
        same-topology).  With ``target_state`` (a pytree of sharded
        jax.Arrays): every leaf is re-assembled onto the target's
        shardings — shm, then storage, then the orbax tier at
        ``orbax_dir`` (reference: fsdp_engine re-shard on load).

        Both paths run the staged restore pipeline (read → assemble →
        h2d overlapped; ``DLROVER_RESTORE_WORKERS`` sizes the pool,
        ``1`` = exact serial path)."""
        if target_state is not None:
            return self._engine.load_sharded(
                target_state, orbax_dir=orbax_dir or self._orbax_dir
            )
        step, state = self._engine.load()
        if step is None and (orbax_dir or self._orbax_dir):
            # shm + flash storage gone (node replacement): the
            # durable tier is the last resort even without a target
            # template; a per-call orbax_dir overrides the configured
            # one (mirrors the target_state branch)
            if orbax_dir and orbax_dir != self._orbax_dir:
                from dlrover_tpu.checkpoint.orbax_compat import (
                    GlobalCheckpointer,
                )

                tier = GlobalCheckpointer(orbax_dir)
                try:
                    return tier.restore()
                finally:
                    tier.close()
            tier = self._orbax_tier()
            if tier is not None:
                return tier.restore()
        return step, state

    def load_checkpoint_async(
        self, target_state: Any = None, orbax_dir: str = "",
    ) -> RestoreHandle:
        """:meth:`load_checkpoint` on a background thread: start it
        FIRST, build the model/optimizer/jitted step — and resolve
        the step through the AOT executable cache
        (``RecoveryProfiler.resolve_step`` with ``restore_busy=not
        handle.done()``) — while the read+assemble stages run, then
        ``handle.result()``; only the (device-bound) tail of the
        restore stays serial with the caller.  One restore at a time:
        do not save or load through this checkpointer until
        ``result()`` returned.

        Note the host-array path (no ``target_state``) performs no
        device transfers at all, so with enough setup work to hide
        behind, the whole restore disappears from the critical path."""
        return RestoreHandle(
            self.load_checkpoint,
            kwargs={
                "target_state": target_state, "orbax_dir": orbax_dir,
            },
        )

    def wait(self, timeout: float = 600.0) -> bool:
        """Block until every accepted save, MEMORY or DISK, is
        committed to shared memory AND in-flight orbax tier writes
        complete (call before process exit, or before anything outside
        this process is to find the last save there).  It does not
        wait for the agent's persist.  The timeout bounds the whole
        call — a hung remote store cannot block a preemption grace
        period."""
        import threading
        import time as _time

        deadline = _time.monotonic() + timeout
        # split the budget only when the durable tier actually has
        # pending work — orbax then needs a real share, not a 50 ms
        # floor probe that would falsely mark a healthy store hung;
        # with nothing pending the shm drain keeps the whole budget
        orbax_pending = self._orbax is not None and (
            self._orbax_dirty or self._orbax_waiter is not None
        )
        engine_budget = (
            max(0.1, timeout * 0.7) if orbax_pending else timeout
        )
        ok = self._engine.wait_async(timeout=engine_budget)
        if orbax_pending:
            # drain any stale waiter first: it entered orbax's wait
            # BEFORE saves issued since, so only a FRESH wait that
            # completes counts as success (a stale thread finishing
            # in a race gap must not)
            stale = self._orbax_waiter
            if stale is not None and stale.is_alive():
                stale.join(
                    timeout=max(0.05, deadline - _time.monotonic())
                )
                if stale.is_alive():
                    self._orbax_hung = True
                    return False
            fresh = threading.Thread(
                target=self._orbax.wait, daemon=True
            )
            fresh.start()
            fresh.join(
                timeout=max(0.05, deadline - _time.monotonic())
            )
            timed_out = fresh.is_alive()
            self._orbax_waiter = fresh if timed_out else None
            self._orbax_hung = timed_out
            self._orbax_dirty = timed_out
            ok = ok and not timed_out
        return ok

    def close(self):
        if self._orbax is not None and not self._orbax_hung:
            # a wait() that already timed out means the store is hung;
            # re-entering the unbounded wait here would blow through
            # the preemption grace period the caller bounded
            self._orbax.wait()
            self._orbax.close()
        self._engine.close()


def restore_to_template(template, restored, device_put: bool = True):
    """Rebuild a restored checkpoint (plain nested dicts — the shm
    format flattens pytrees to string paths) onto ``template``'s tree
    structure: optax tuples/NamedTuples, flax containers, dataclasses
    all come back typed, each leaf ``device_put`` to the template
    leaf's sharding when it has one.

    The reference never needed this (torch state dicts are already
    plain dicts); JAX optimizer states are structured pytrees, so the
    restructure lives here next to the engine.

    Prefer ``load_checkpoint(target_state=...)`` when you hold a
    template with shardings — it additionally re-assembles shards
    after a topology change; this helper covers the replicated
    plain-``load_checkpoint()`` flow.
    """
    import jax

    from dlrover_tpu.checkpoint.shm_handler import _path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    # BATCHED placement: one device_put over all sharded leaves and
    # one over the default-placed ones — a per-leaf asarray+put chain
    # pays one dispatch per leaf, which is the measured
    # ``state_build`` residual of the recovery budget
    put_default: list = []   # (leaf_index, host_value)
    put_sharded: list = []   # (leaf_index, host_value, sharding)
    for path, tleaf in flat:
        node = restored
        for p in path:
            key = _path_str(p)
            if isinstance(node, dict) and key in node:
                node = node[key]
            else:
                raise KeyError(
                    f"checkpoint is missing leaf "
                    f"'{'/'.join(_path_str(q) for q in path)}'"
                )
        val = node
        if device_put and hasattr(tleaf, "sharding"):
            sh = tleaf.sharding
            if sh is None:
                put_default.append((len(leaves), val))
            else:
                put_sharded.append((len(leaves), val, sh))
        leaves.append(val)
    if put_sharded:
        arrs = jax.device_put(
            [v for _, v, _ in put_sharded],
            [s for _, _, s in put_sharded],
        )
        for (i, _, _), arr in zip(put_sharded, arrs):
            leaves[i] = arr
    if put_default:
        arrs = jax.device_put([v for _, v in put_default])
        for (i, _), arr in zip(put_default, arrs):
            leaves[i] = arr
    return jax.tree_util.tree_unflatten(treedef, leaves)
