"""Recovery-phase profiler: the death→first-step budget, measured.

The invisible-recovery target (``elastic_recovery_s ≤ 2.0``) is only
reachable — and only *provable* — with the serial chain broken into
named phases, each measured where it actually runs:

- **spawn**: the agent witnesses the death → this process exists
  (kernel start time from ``/proc/self/stat``, so the measurement
  covers the fork/exec itself, not just userland);
- **import**: process start → the program's first call
  (interpreter + jax/flax imports — near zero under a warm fork):
  ``init_jax_distributed()`` where the entrypoint made it before
  constructing this profiler, else the construction itself;
- **backend**: that call → this profiler's construction: the
  distributed initialize and the backend's opening (on a TPU host,
  taking the chip), the spans ``trainer.distributed_init`` and
  ``trainer.backend_open``;
- **restore**: the checkpoint restore (the engine's measured
  ``total_s``);
- **aot**: resolving the step through the AOT executable cache
  (:mod:`dlrover_tpu.common.aot_cache`) — on a HIT this is the
  deserialize+link time and the retrace phase collapses to zero; on
  a MISS it is the entry write (so incarnation N+1 hits);
- **retrace**: the first post-restore step's trace+compile, with the
  persistent compilation cache's hit/miss witnessed from the cache
  directory (:mod:`dlrover_tpu.common.compile_cache`);
- **first_step**: the remainder, from the last phase recorded
  before it, until the first step completes.

Each phase lands as a ``recovery_phase`` event + a
``dlrover_recovery_phase_seconds{phase}`` histogram sample, so the
chaos invariants and the timeline's recovery breakdown read the same
numbers.  The agent exports ``DLROVER_RECOVERY_T0``
(the wall clock at which it observed the death) into every respawned
worker's env; without it the profiler still measures import/restore/
retrace relative to process start (a first incarnation, or a cold
launch).
"""

import os
import time
from typing import Dict, Optional

from dlrover_tpu.common import env_utils
from dlrover_tpu.common.compile_cache import (
    cache_entries,
    enable_persistent_cache,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry.events import emit_event
from dlrover_tpu.telemetry.metrics import get_registry
from dlrover_tpu.telemetry.tracing import get_tracer

RECOVERY_T0_ENV = "DLROVER_RECOVERY_T0"

_REG = get_registry()
_PHASE_SECONDS = _REG.histogram(
    "dlrover_recovery_phase_seconds",
    "Measured death->first-step recovery budget by phase "
    "(spawn / import / backend / restore / aot / retrace / "
    "first_step)",
)


class _Phase:
    __slots__ = ("_profiler", "_name", "_t0")

    def __init__(self, profiler: "RecoveryProfiler", name: str):
        self._profiler = profiler
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._profiler.record(
            self._name, time.perf_counter() - self._t0
        )
        return False


class RecoveryProfiler:
    """Construct RIGHT AFTER the heavy imports; the constructor books
    the spawn and import phases and activates the job's persistent
    compile cache in-process (covering entrypoints whose jax imported
    before the agent's env reached them)."""

    def __init__(
        self,
        restart_count: Optional[int] = None,
        node_rank: Optional[int] = None,
    ):
        self.restart_count = (
            restart_count if restart_count is not None
            else env_utils.get_restart_count()
        )
        self.node_rank = (
            node_rank if node_rank is not None
            else env_utils.get_node_rank()
        )
        self.phases: Dict[str, float] = {}
        self.cache_hit: Optional[bool] = None
        self.aot_hit: Optional[bool] = None
        self.cache_dir = enable_persistent_cache()
        try:
            self.t0 = float(os.getenv(RECOVERY_T0_ENV, "") or 0.0)
        except ValueError:
            self.t0 = 0.0
        now = time.time()
        self._proc_start = env_utils.proc_start_before(now)
        if self.t0 > 0 and self._proc_start >= self.t0:
            self.record(
                "spawn", self._proc_start - self.t0,
                end_ts=self._proc_start,
            )
        # where ``init_jax_distributed()`` ran before this
        # construction, the imports ended as it began
        opened = get_tracer().finished_spans("trainer.distributed_init")
        imported = opened[-1].start_time if opened else now
        self.record(
            "import", imported - self._proc_start, end_ts=imported
        )
        if opened:
            # ended at ``now``, not when the two events before it
            # have been written
            self.record("backend", now - imported, end_ts=now)

    # -- recording ---------------------------------------------------------

    def record(
        self, phase: str, seconds: float,
        end_ts: Optional[float] = None,
    ):
        """Book ``seconds`` to ``phase``, as having ended now, or at
        the wall clock ``end_ts`` (a phase that ended before there
        was a profiler to see it): readers lay a phase on the
        timeline as ``[ts - seconds, ts]``."""
        seconds = max(0.0, float(seconds))
        self.phases[phase] = round(seconds, 4)
        # where the phase recorded last ended: ``first_step`` is the
        # remainder since
        self._boundary = time.perf_counter()
        _PHASE_SECONDS.observe(seconds, phase=phase)
        event = {
            "phase": phase,
            "seconds": round(seconds, 4),
            "restart_count": self.restart_count,
            "node_rank": self.node_rank,
        }
        if end_ts is not None:
            event["ts"] = end_ts
        emit_event("recovery_phase", **event)

    def phase(self, name: str) -> _Phase:
        """``with profiler.phase("restore"): step, state = load()``"""
        return _Phase(self, name)

    def record_restore(self, restore_phases: Dict) -> None:
        """Book the restore phase from the engine's measured
        breakdown (``Checkpointer.last_restore_phases``)."""
        total = restore_phases.get("total_s")
        if isinstance(total, (int, float)) and total > 0:
            self.record("restore", float(total))

    def resolve_step(
        self,
        fn,
        example_args,
        label: str = "train_step",
        cache_dir: Optional[str] = None,
        restore_busy: Optional[bool] = None,
    ):
        """Resolve the jitted step through the AOT executable cache,
        booking the budget phases and emitting the witnesses::

            step = prof.resolve_step(step_fn, (abstract_state, batch))
            ...
            state, metrics = step(state, batch)   # no trace on a HIT

        HIT: the ``aot`` phase is the deserialize+link time and
        ``retrace`` is recorded as 0 — tracing left the critical path.
        MISS: the lower+compile inside the resolve IS the measured
        retrace (recorded exactly as :meth:`measured_retrace` would),
        and the entry is written so incarnation N+1 hits.  Off or
        failed: returns a wrapper whose first call runs under
        :meth:`measured_retrace` — byte-for-byte today's behavior.

        ``restore_busy`` (pass ``lambda: not load_handle.done()``; a
        plain bool works too) stamps whether the async restore was
        still reading when this resolve finished — the overlap
        witness on the ``aot_cache`` event.  Call this BEFORE joining
        the restore to actually overlap."""
        from dlrover_tpu.common import aot_cache as _aot

        entries_before = cache_entries(self.cache_dir)
        t0 = time.perf_counter()
        res = _aot.resolve_step(
            fn, example_args, label=label, cache_dir=cache_dir
        )
        wall = time.perf_counter() - t0
        return self._book_resolution(
            res, wall, entries_before, restore_busy
        )

    def _book_resolution(
        self,
        res,
        wall: float,
        entries_before: int,
        restore_busy=None,
    ):
        """Book an :class:`aot_cache.Resolution` into the budget
        phases and emit the ``aot_cache`` + ``compile_cache``
        witnesses; returns the callable the training loop should use."""
        from dlrover_tpu.common import aot_cache as _aot

        aot_n = _aot.aot_entries(res.dir) if res.dir else 0
        event = {
            "hit": res.hit,
            # "resolution", not "source": the event envelope's
            # source field is the emitting process's identity
            "resolution": res.source,
            "key": res.key,
            "dir": res.dir,
            "wrote": res.wrote,
            "preloaded": res.preloaded,
            "seconds": round(wall, 4),
            "load_s": round(res.load_s, 4),
            "trace_s": round(res.trace_s, 4),
            "save_s": round(res.save_s, 4),
            "entries": aot_n,
            "restart_count": self.restart_count,
            "node_rank": self.node_rank,
        }
        for k, v in res.extra.items():
            event[k] = round(v, 4) if isinstance(v, float) else v
        if res.reason:
            event["reason"] = res.reason
        if restore_busy is not None:
            busy = restore_busy() if callable(restore_busy) else (
                restore_busy
            )
            event["overlapped_restore"] = bool(busy)
        if res.source == "aot":
            self.aot_hit = True
            self.cache_hit = True
            self.record("aot", res.load_s)
            # no tracing happened anywhere: the retrace phase the
            # invariants/budget sum over is genuinely zero
            self.record("retrace", 0.0)
            emit_event("aot_cache", **event)
            self._emit_compile_cache(
                hit=True, status="aot-hit", retrace_s=0.0,
                entries_before=entries_before,
                entries_after=cache_entries(self.cache_dir),
                aot_entries=aot_n,
            )
            return res.fn
        self.aot_hit = False
        if res.source == "trace" and not res.deferred:
            # the eager lower+compile inside the resolve IS the
            # measured retrace; the entry write rides the aot phase
            self.record("retrace", res.trace_s)
            self.record("aot", res.load_s + res.save_s)
            entries_after = cache_entries(self.cache_dir)
            hit = entries_before > 0 and entries_after <= entries_before
            self.cache_hit = hit
            emit_event("aot_cache", **event)
            self._emit_compile_cache(
                hit=hit,
                status="xla-cache-hit" if hit else "cold",
                retrace_s=res.trace_s,
                entries_before=entries_before,
                entries_after=entries_after,
                aot_entries=aot_n,
            )
            return res.fn
        # off / failed resolve: keep today's semantics — the first
        # call traces under the measured_retrace bracket (still books
        # the failed load attempt so the budget stays complete)
        self.record("aot", res.load_s)
        emit_event("aot_cache", **event)
        inner = res.fn
        done = [False]
        profiler = self

        def first_call_measured(*args, **kwargs):
            if done[0]:
                return inner(*args, **kwargs)
            done[0] = True
            with profiler.measured_retrace() as r:
                out = inner(*args, **kwargs)
                r.block(out)
            return out

        return first_call_measured

    def _emit_compile_cache(
        self, hit, status, retrace_s, entries_before, entries_after,
        aot_entries,
    ):
        emit_event(
            "compile_cache",
            hit=hit,
            status=status,
            entries_before=entries_before,
            entries_after=entries_after,
            aot_entries=aot_entries,
            retrace_s=round(retrace_s, 4),
            dir=self.cache_dir,
            restart_count=self.restart_count,
            node_rank=self.node_rank,
        )

    def measured_retrace(self) -> "_Retrace":
        """Bracket the FIRST post-restore step::

            with profiler.measured_retrace() as r:
                state, metrics = step_fn(state, batch)
                r.block(metrics)

        The block's wall time is the retrace phase; the cache
        directory's entry count before/after witnesses the compile-
        cache hit (no new ``*-cache`` entries over a warm dir = HIT),
        emitted as a ``compile_cache`` event.  ``block`` brackets
        ``block_until_ready`` so async dispatch cannot shrink the
        measurement."""
        return _Retrace(self)

    def record_first_step(self):
        """Close the budget: the remainder since the last recorded
        phase boundary (the end of ``aot`` / ``retrace`` /
        ``restore`` or of a phase of the entrypoint's own, whichever
        came last).  What lies before that boundary has its own
        names: the phases, and the ``trainer.*`` spans."""
        self.record(
            "first_step", time.perf_counter() - self._boundary
        )
        if self.t0 > 0:
            total = time.time() - self.t0
            logger.info(
                "recovery budget (restart %s): %.2fs total — %s",
                self.restart_count, total, self.phases,
            )


class _Retrace:
    def __init__(self, profiler: RecoveryProfiler):
        self._p = profiler
        self._blocked = None

    def block(self, x):
        """Remember the step's output so ``__exit__`` can wait on it
        (retrace_s must include the compile's execution barrier)."""
        self._blocked = x
        return x

    def __enter__(self):
        self._before = cache_entries(self._p.cache_dir)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is not None:
            return False
        if self._blocked is not None:
            try:
                import jax

                jax.block_until_ready(self._blocked)
            except Exception:  # noqa: BLE001 - non-jax outputs
                pass
        retrace_s = time.perf_counter() - self._t0
        after = cache_entries(self._p.cache_dir)
        hit = self._before > 0 and after <= self._before
        self._p.cache_hit = hit
        self._p.record("retrace", retrace_s)
        from dlrover_tpu.common.aot_cache import aot_entries

        self._p._emit_compile_cache(
            hit=hit,
            status="xla-cache-hit" if hit else "cold",
            retrace_s=retrace_s,
            entries_before=self._before,
            entries_after=after,
            aot_entries=aot_entries(),
        )
        return False
