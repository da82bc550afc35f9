"""Staged, pipelined checkpoint restore executor.

The save side of Flash Checkpoint is nearly free (the training stall
is one on-device copy); the restore side is the paper's actual
recovery promise — "seconds-order restore from host shared memory"
(reference: ckpt_saver.py) — and it was serial end to end: per-leaf
``arr.copy()`` detaches from shm (each copy page-faulting the mapping
single-threaded), then shard blobs read one after another, then
``device_put`` leaf by leaf.  Like Orbax's async restore and the
Pathways/GSPMD checkpointing pipelines, the fix is overlap, not a
faster single stream:

- **read**: storage shard blobs attach as mmap views (posix) or are
  fetched concurrently, so byte k+1 is paged in while byte k is being
  assembled;
- **assemble**: detach copies run as ~64 MB chunks on a small thread
  pool through :func:`dlrover_tpu.ops.fastcopy.copy_into` — the GIL is
  released for the memcpy AND the page faults it triggers, which is
  the dominant restore term on a cold mapping (~seconds/GB
  single-threaded);
- **h2d**: host arrays go to the device in batched ``device_put``
  calls issued while later leaves are still assembling, so the
  host→device transfer of leaf k overlaps the memcpy of leaf k+1.

``DLROVER_RESTORE_WORKERS`` sizes the pool; ``1`` bypasses the pool
entirely and reproduces the serial path exactly (the equivalence
tests pin this).  Stage wall times land in :class:`RestoreStats`
(``read_s``/``assemble_s``/``h2d_s``), which the engine exports to
the restore span/event/histograms.
"""

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import numpy as np

from dlrover_tpu.ops.fastcopy import copy_into_chunked

RESTORE_WORKERS_ENV = "DLROVER_RESTORE_WORKERS"

# Piece size of the chunked detach copies and byte budget of one batched
# ``device_put``: large enough that the pool's per-piece dispatch is
# noise beside the memcpy, small enough that a multi-GB leaf still
# splits across every worker (its page faults are what parallelise).
CHUNK_BYTES = 64 * 2**20


def restore_workers() -> int:
    """Pool size for the restore pipeline.  Default: half the host's
    cores capped at 8 — restore shares the host with the agent, the
    respawning trainer and jit re-trace, and memcpy saturates memory
    bandwidth long before it saturates cores."""
    val = os.getenv(RESTORE_WORKERS_ENV, "").strip()
    if val:
        try:
            return max(1, int(val))
        except ValueError:
            pass
    return min(8, max(2, (os.cpu_count() or 4) // 2))


def zero_copy_device_put() -> bool:
    """Whether ``np.frombuffer`` views of shm/mmap may be fed straight
    to ``device_put``.  On a real accelerator H2D always copies, so
    views are safe and save one host memcpy per leaf.  On the CPU
    backend jax may alias a suitably-aligned host buffer instead of
    copying — a restored param aliased to shm would be silently
    corrupted by the next snapshot — so views are detached first."""
    try:
        import jax

        return jax.default_backend() != "cpu"
    except Exception:  # noqa: BLE001 - no jax yet: be safe
        return False


@dataclass
class RestoreStats:
    """Per-restore stage accounting (seconds of main-thread wall per
    stage; with mmap-lazy reads the page-fault cost lands in
    ``assemble_s``, where the faulting copies actually run)."""

    read_s: float = 0.0
    assemble_s: float = 0.0
    h2d_s: float = 0.0
    bytes: int = 0
    workers: int = field(default_factory=restore_workers)
    # tier-specific extras surfaced on the restore event/phase dict —
    # the sparse (KvVariable) import records kv_s/kv_rows here so the
    # timeline's restore slices show the kv stage
    extra: Dict[str, Any] = field(default_factory=dict)

    def to_phases(self) -> Dict[str, Any]:
        phases = {
            "read_s": round(self.read_s, 4),
            "assemble_s": round(self.assemble_s, 4),
            "h2d_s": round(self.h2d_s, 4),
            "bytes": int(self.bytes),
            "workers": int(self.workers),
        }
        phases.update(self.extra)
        return phases


class _InlineFuture:
    """Future-shaped LAZY call so the workers==1 path runs the EXACT
    serial sequence behind the same driving code: nothing executes at
    submit time — the work runs when (and in the order) the driving
    loop consumes ``result()``, which also keeps the serial path's
    one-leaf-at-a-time memory profile."""

    __slots__ = ("_fn", "_args", "_done", "_value", "_exc")

    def __init__(self, fn, args):
        self._fn = fn
        self._args = args
        self._done = False
        self._value = None
        self._exc = None

    def result(self):
        if not self._done:
            self._done = True
            try:
                self._value = self._fn(*self._args)
            except BaseException as e:  # noqa: BLE001
                self._exc = e
            self._fn = self._args = None
        if self._exc is not None:
            raise self._exc
        return self._value


class StagedRestore:
    """Owns the restore thread pool (or nothing, when workers==1).

    Use as a context manager; ``submit`` returns something with
    ``.result()``.  With one worker every submit executes inline at
    the call site, which makes the pipeline degrade to the exact
    serial path — the `DLROVER_RESTORE_WORKERS=1` guard tests rely on
    this, and it doubles as the zero-risk escape hatch.
    """

    def __init__(self, workers: Optional[int] = None):
        self.workers = workers if workers is not None else restore_workers()
        self._pool: Optional[ThreadPoolExecutor] = None

    def __enter__(self) -> "StagedRestore":
        if self.workers > 1:
            self._pool = ThreadPoolExecutor(
                max_workers=self.workers,
                thread_name_prefix="ckpt-restore",
            )
        return self

    def __exit__(self, *exc):
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        return False

    def submit(self, fn: Callable, *args):
        if self._pool is None:
            return _InlineFuture(fn, args)
        return self._pool.submit(fn, *args)

    def map_ordered(self, fn: Callable, items: Iterable) -> List:
        """Run ``fn`` over ``items`` concurrently, results in input
        order (inline when serial)."""
        futs = [self.submit(fn, item) for item in items]
        return [f.result() for f in futs]

    def map_pipelined(
        self, fn: Callable, items: Iterable, depth: int = 2,
    ):
        """Generator of ``fn(item)`` results in input order with at
        most ``depth`` calls in flight — the bounded-lookahead shape
        of the streaming reshard: window k+1's partition runs on the
        pool while the caller imports window k, and peak memory stays
        ~``depth`` windows instead of the whole item list.  Serial
        mode (workers==1) degrades to the exact inline sequence via
        the lazy inline futures."""
        from collections import deque

        pending: deque = deque()
        for item in items:
            pending.append(self.submit(fn, item))
            if len(pending) >= max(1, depth):
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()

    # -- chunked detach ----------------------------------------------------

    def copy_chunked(self, dst: np.ndarray, src: np.ndarray) -> List:
        """``dst[...] = src`` split into ~CHUNK_BYTES pieces, each a
        GIL-released :func:`fastcopy.copy_into`; returns the futures
        (already done when serial).  Splitting a single large leaf is
        what parallelizes the page faults of a cold shm mapping."""
        return copy_into_chunked(
            dst, src, submit=self.submit, chunk_bytes=CHUNK_BYTES
        )

    def detach_flat(
        self,
        views: Dict[str, np.ndarray],
        stats: Optional[RestoreStats] = None,
    ) -> Dict[str, np.ndarray]:
        """Copy every view into a private array (chunked, parallel).
        Replaces the serial per-leaf ``arr.copy()`` detach; bit-
        identical output, wall time into ``stats.assemble_s``."""
        import time as _time

        t0 = _time.perf_counter()
        out: Dict[str, np.ndarray] = {}
        pending: List = []
        for key, view in views.items():
            dst = np.empty(view.shape, dtype=view.dtype)
            out[key] = dst
            pending.extend(self.copy_chunked(dst, view))
        for f in pending:
            f.result()
        if stats is not None:
            stats.assemble_s += _time.perf_counter() - t0
            stats.bytes += sum(v.nbytes for v in views.values())
        return out


def detach_flat(
    views: Dict[str, np.ndarray],
    stats: Optional[RestoreStats] = None,
    workers: Optional[int] = None,
) -> Dict[str, np.ndarray]:
    """One-shot convenience around :meth:`StagedRestore.detach_flat`."""
    with StagedRestore(workers) as staged:
        return staged.detach_flat(views, stats)


def detach_for_device_put(arr: np.ndarray) -> np.ndarray:
    """Return ``arr`` ready to hand to ``device_put``: the view itself
    when zero-copy is safe (H2D copies anyway), else a private copy so
    a CPU-backend jax array can never alias the shm/mmap buffer."""
    if not isinstance(arr, np.ndarray) or arr.base is None:
        return arr
    if zero_copy_device_put():
        return arr
    return np.array(arr, copy=True)
