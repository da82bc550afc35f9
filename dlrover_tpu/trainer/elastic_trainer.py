"""Elastic training loop utilities.

Reference: ``ElasticTrainer``
(``dlrover/trainer/torch/elastic/trainer.py``): keeps the *global*
batch size fixed as the world resizes by adjusting gradient
accumulation, counts steps, and writes a runtime-metrics file the
agent's TrainingMonitor reports to the master's SpeedMonitor.

TPU-native shape: instead of wrapping a torch optimizer, the trainer
builds one jitted train step that scans over the gradient-accumulation
microbatches inside the compiled program (``lax.scan`` — no Python
loop, one XLA program per world size) and applies the optax update.
Sharding: params/opt-state placed by partition rules, batch split over
the data axes; XLA inserts the gradient psum.
"""

import gc
import json
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu import chaos as _chaos
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel.mesh import dp_world_size, scoped_to_mesh
from dlrover_tpu.parallel.sharding import (
    PartitionRules,
    batch_spec,
    sharding_tree,
)
from dlrover_tpu.telemetry import tracing as trace
from dlrover_tpu.telemetry.events import emit_event
from dlrover_tpu.telemetry.metrics import get_registry
from dlrover_tpu.telemetry.tracing import annotation, device_scope

_REG = get_registry()
_REPORTED_STEP = _REG.gauge(
    "dlrover_trainer_reported_step",
    "Latest step the trainer wrote to the agent-tailed metrics file",
)
_GRAD_ACCUM_GAUGE = _REG.gauge(
    "dlrover_trainer_grad_accum",
    "Gradient-accumulation factor keeping the global batch fixed",
)
_STEP_PHASE_SECONDS = _REG.histogram(
    "dlrover_step_phase_seconds",
    "Per-step wall time by phase (data_wait / h2d / compute / "
    "checkpoint / report / other)",
)


class _GcClock:
    """Seconds this process has spent in garbage collections, from
    ``gc.callbacks``: two clock reads a collection.  One callback for
    the process, whatever number of profilers read it; each
    collection is also a ``dlrover.step.gc`` profiler annotation."""

    def __init__(self):
        self.total = 0.0
        self._started = 0.0
        self._ann = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._ann = annotation(
                "step.gc", time.time_ns(),
                generation=info.get("generation"),
            )
            self._started = time.perf_counter()
        elif self._started:
            self.total += time.perf_counter() - self._started
            self._started = 0.0
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None


_GC_CLOCK = _GcClock()


class StepPhaseProfiler:
    """Always-on phase breakdown of one training step.

    The diagnosis layer needs to tell a *data-starved* trainer (input
    pipeline dominates) from a *slow* one (compute dominates) from a
    *hung* one (nothing progresses), which requires real per-phase
    durations — a bare step time cannot distinguish them.  Cost per
    phase is two ``perf_counter`` reads, a dict add and one inactive
    profiler annotation (~2 µs), so this stays on in production; the
    event emission is a no-op unless an event log is configured.

    The canonical phases are ``data_wait`` (blocking on the input
    pipeline), ``h2d`` (host-to-device transfer), ``compute`` (the
    jitted step — bracket with :meth:`PhaseHandle.block` so async
    dispatch doesn't leak compute time into the next data wait),
    ``checkpoint`` and ``report``; arbitrary names are accepted.
    Un-profiled remainder of the step lands in ``other``.  The first
    step begins at its first phase, not at this object's
    construction: what a process does before its first step (the
    state's init, a restore, the step's compile) has names of its own
    (``recovery_phase``, ``trainer.*`` spans) and is no part of a
    step; every later step begins where the one before it ended.

    Two kinds of entry stand BESIDE the phases and are not summed
    into them: sub-phases (``report.events``, a name with a dot: a
    part of the phase before the dot) and ``gc`` (seconds of garbage
    collection during the step, inside whichever phase it
    interrupted).  Every phase and sub-phase is also a
    ``dlrover.step.<name>`` profiler annotation carrying the step.
    """

    KNOWN_PHASES = (
        "data_wait", "h2d", "compute", "checkpoint", "report",
    )

    def __init__(self):
        self._acc: Dict[str, float] = {}
        self._sub: Dict[str, float] = {}
        self._open: Dict[str, float] = {}
        self._step_started: Optional[float] = None
        self._gc_seen = _GC_CLOCK.total
        # the step in progress (the trainer sets it): a stat of every
        # phase's profiler annotation
        self.step = 0

    @contextmanager
    def phase(self, name: str):
        """One phase of the step; a name with a dot (``report.
        metrics_file``) is a sub-phase, recorded beside the phases."""
        into = self._sub if "." in name else self._acc
        ann = annotation("step." + name, time.time_ns(), step=self.step)
        start = time.perf_counter()
        self._begin(start)
        self._open[name] = start
        handle = PhaseHandle()
        try:
            yield handle
        finally:
            if handle.pending is not None:
                try:
                    jax.block_until_ready(handle.pending)
                except Exception:  # noqa: BLE001 - profiling must
                    pass  # never break the step it measures
            dt = time.perf_counter() - start
            self._open.pop(name, None)
            into[name] = into.get(name, 0.0) + dt
            if ann is not None:
                ann.__exit__(None, None, None)

    def add(self, name: str, seconds: float):
        """Record an externally-timed phase (e.g. the checkpoint
        engine's own stall measurement) or sub-phase."""
        self._begin(time.perf_counter() - float(seconds))
        into = self._sub if "." in name else self._acc
        into[name] = into.get(name, 0.0) + float(seconds)

    def _begin(self, now: float):
        """The first step starts with its first phase."""
        if self._step_started is None:
            self._step_started = now
            self._gc_seen = _GC_CLOCK.total

    def _phases(self, now: float) -> Dict[str, float]:
        acc = dict(self._acc)
        sub = dict(self._sub)
        for name, start in self._open.items():
            into = sub if "." in name else acc
            into[name] = into.get(name, 0.0) + now - start
        started = (
            now if self._step_started is None else self._step_started
        )
        total = max(0.0, now - started)
        phases = {k: round(v, 6) for k, v in acc.items()}
        phases.update((k, round(v, 6)) for k, v in sub.items())
        phases["gc"] = round(_GC_CLOCK.total - self._gc_seen, 6)
        phases["total_s"] = round(total, 6)
        phases["other_s"] = round(
            max(0.0, total - sum(acc.values())), 6
        )
        return phases

    def peek(self) -> Dict[str, float]:
        """The step's breakdown so far, phases still open included;
        nothing is reset."""
        return self._phases(time.perf_counter())

    def finish_step(self) -> Dict[str, float]:
        """Close the step: returns ``{phase: seconds, ...,
        "total_s", "other_s"}`` and resets for the next step."""
        now = time.perf_counter()
        phases = self._phases(now)
        self._acc.clear()
        self._sub.clear()
        self._step_started = now
        self._gc_seen = _GC_CLOCK.total
        return phases


class PhaseHandle:
    """Yielded by :meth:`StepPhaseProfiler.phase`; ``block(x)`` marks
    ``x`` to be ``jax.block_until_ready``-ed before the phase closes,
    so the recorded duration covers the device work, not just the
    async dispatch."""

    __slots__ = ("pending",)

    def __init__(self):
        self.pending = None

    def block(self, x):
        self.pending = x
        return x


@jax.tree_util.register_dataclass
@dataclass
class TrainState:
    """Minimal train state pytree (params + optax state + step)."""

    params: Any
    opt_state: Any
    step: jax.Array

    @classmethod
    def create(cls, params, optimizer, opt_state=None, step=None):
        """``opt_state``/``step`` default to a fresh optimizer init —
        pass restored slots to DEFER the eager init entirely (a
        restore that already supplies the moments must not pay
        ``optimizer.init`` just to overwrite it)."""
        return cls(
            params=params,
            opt_state=(
                optimizer.init(params) if opt_state is None
                else opt_state
            ),
            step=(
                jnp.zeros((), dtype=jnp.int32) if step is None
                else step
            ),
        )


def restore_train_state(optimizer, restored) -> TrainState:
    """Typed :class:`TrainState` from a restored nested dict with the
    recovery ``state_build`` residual shaved off: the optimizer is
    never re-initialized (the restore supplies params AND slots) and
    every leaf conversion rides ONE batched ``device_put`` instead of
    a per-leaf ``jnp.asarray`` chain (each of which dispatches its
    own transfer — ~0.3 s of the measured recovery budget at toy
    scale, worse at real scale).

    The typed optax containers are rebuilt by tracing
    ``TrainState.create`` over the restored params' avals — no model
    code runs and nothing touches a device during the trace."""
    from dlrover_tpu.checkpoint.checkpointer import (
        restore_to_template,
    )

    abs_params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        restored["params"],
    )
    template = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    return restore_to_template(template, restored)


# the key of a loss's ``aux`` under which it hands the step deltas
# for leaves of the parameters that it moves itself (a loss writes the
# literal: the models' side imports nothing from the trainer's)
STATE_UPDATES = "state_updates"


def _add_at(new, old, deltas):
    """``new`` with ``old + delta`` at every leaf that ``deltas`` (a
    nested dict over part of the parameters' tree) names."""
    if not isinstance(deltas, dict):
        return (old + deltas).astype(old.dtype)
    return {
        **new,
        **{
            key: _add_at(new[key], old[key], sub)
            for key, sub in deltas.items()
        },
    }


def make_train_step(
    loss_fn: Callable,
    optimizer,
    grad_accum: int = 1,
    mesh=None,
    rules: Optional[PartitionRules] = None,
    has_aux: Optional[bool] = None,
):
    """Build the jitted (state, batch) -> (state, metrics) step.

    ``loss_fn(params, batch) -> scalar``.  With ``grad_accum > 1`` the
    batch's leading dim must be ``grad_accum * micro``; the scan keeps
    the accumulation inside the compiled program.  When a mesh is
    given, in/out shardings pin state to the rule-derived placement and
    the batch to the data axes — GSPMD inserts all collectives — and
    the step is traced with that mesh in scope (``scoped_to_mesh``).

    With ``has_aux``, ``loss_fn(params, batch) -> (loss, aux)`` and
    ``aux``'s scalars (a model's own counters: ``moe.load_max_over_mean``,
    ``gdn.state_rms_max``)
    join ``metrics`` (with ``grad_accum``, their mean over the micro
    batches).  Left at None it is read from ``loss_fn.has_aux``, so a
    loss that carries counters says so itself and a training script
    written for scalar losses runs it unchanged.

    ``aux`` may also carry ``"state_updates"`` (``STATE_UPDATES``): a
    pytree of deltas, laid out as the part of ``params`` it names
    (``{"block_1": {"moe": {"select_bias": delta}}}``), for leaves
    that no gradient reaches and that the loss moves by a rule of its
    own (a router's load bias).  The step sets each such leaf to ``old + delta`` after the
    optimizer, whatever the optimizer made of it: no Adam, no weight
    decay on it.  It is not a metric.  (With ``grad_accum`` the
    deltas, like the counters, are the micro batches' mean.)
    """
    if has_aux is None:
        has_aux = bool(getattr(loss_fn, "has_aux", False))

    def grads_of(params, batch):
        # (with has_aux, ``loss`` is the pair (loss, aux) down to
        # where step_fn splits it)
        with device_scope("forward_backward"):
            loss, grads = jax.value_and_grad(
                loss_fn, has_aux=has_aux
            )(params, batch)
        return loss, grads

    def step_fn(state: TrainState, batch):
        if grad_accum > 1:
            micro = jax.tree.map(
                lambda x: x.reshape(
                    (grad_accum, x.shape[0] // grad_accum) + x.shape[1:]
                ),
                batch,
            )

            def accum(carry, mb):
                loss_sum, grads_sum = carry
                loss, grads = grads_of(state.params, mb)
                return (
                    jax.tree.map(jnp.add, loss_sum, loss),
                    jax.tree.map(jnp.add, grads_sum, grads),
                ), None

            zeros = jax.tree.map(jnp.zeros_like, state.params)
            loss_zero = jnp.zeros((), jnp.float32)
            if has_aux:
                loss_zero = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, x.dtype),
                    jax.eval_shape(
                        loss_fn, state.params,
                        jax.tree.map(lambda x: x[0], micro),
                    ),
                )
            (loss_sum, grads), _ = jax.lax.scan(
                accum, (loss_zero, zeros), micro
            )
            loss = jax.tree.map(lambda x: x / grad_accum, loss_sum)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
        else:
            loss, grads = grads_of(state.params, batch)
        aux = {}
        if has_aux:
            loss, aux = loss
        import optax

        # device scope: every op of the optimizer pass carries
        # "optimizer" in its name stack, so a trace can sum them
        with device_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            if STATE_UPDATES in aux:
                aux = dict(aux)
                new_params = _add_at(
                    new_params, state.params, aux.pop(STATE_UPDATES)
                )
        new_state = TrainState(
            params=new_params, opt_state=new_opt, step=state.step + 1
        )
        metrics = {**aux, "loss": loss, "grad_norm": grad_norm}
        return new_state, metrics

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=0)

    rules = rules or PartitionRules()
    from jax.sharding import NamedSharding

    def jit_with_shardings(state_example):
        state_sh = sharding_tree(state_example, mesh, rules)
        batch_sh = NamedSharding(mesh, batch_spec())
        return scoped_to_mesh(
            jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=0,
            ),
            mesh,
        )

    return step_fn, jit_with_shardings


def abstract_like(tree):
    """``ShapeDtypeStruct`` twin of a pytree — the zero-cost abstract
    example :func:`resolve_train_step` lowers against, buildable from
    restored params or an ``eval_shape`` of the init, so the AOT
    resolve can run BEFORE the restore joins."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x)
        ),
        tree,
    )


def resolve_train_step(
    step_fn,
    example_state,
    example_batch,
    profiler=None,
    label: str = "train_step",
    restore_busy=None,
):
    """Resolve the jitted train step through the AOT executable cache
    before the first step: a warm incarnation DESERIALIZES the
    compiled executable instead of re-tracing (the PR 10 budget's
    dominant term), a cold one traces once and writes the entry so
    the next incarnation hits.  With a
    :class:`~dlrover_tpu.trainer.recovery.RecoveryProfiler` the
    resolve books the ``aot``/``retrace`` budget phases and emits the
    ``aot_cache``/``compile_cache`` witnesses; without one it still
    returns a ready step (plain :func:`aot_cache.resolve_step`).
    Examples may be concrete arrays or :func:`abstract_like` trees.
    Always safe: any cache problem falls back to tracing."""
    args = (example_state, example_batch)
    if profiler is not None:
        return profiler.resolve_step(
            step_fn, args, label=label, restore_busy=restore_busy
        )
    from dlrover_tpu.common import aot_cache

    return aot_cache.resolve_step(step_fn, args, label=label).fn


def _chip_metrics() -> str:
    """Memory stats of the devices THIS process owns, one line each —
    written into the metrics file so the agent's diagnosis collector
    can report them without ever opening the chip itself.  Empty on
    backends that report no memory stats (the CPU backend)."""
    lines = []
    for dev in jax.local_devices():
        stats = dev.memory_stats()
        if stats:
            lines.append(
                f"{dev}: in_use={stats.get('bytes_in_use', 0)} "
                f"peak={stats.get('peak_bytes_in_use', 0)} "
                f"limit={stats.get('bytes_limit', 0)}"
            )
    return "\n".join(lines)


@dataclass
class _StepReport:
    """One completed step as ``report_step`` hands it over, written
    at once or inside the next step's ``compute`` phase.  ``metrics``
    are the device arrays they were: nothing is fetched before the
    write.  ``phases`` and ``ts`` are set where the write comes AFTER
    the step closed: its breakdown, and when it completed (the ``ts``
    of its two events and the metrics file's ``timestamp``)."""

    step: int
    epoch: int
    metrics: Dict[str, Any]
    phases: Optional[Dict[str, float]] = None
    ts: Optional[float] = None

    def stamp(self) -> Dict[str, float]:
        """The events' own ``ts`` (``emit_many``: an event written
        after it happened); none where they are written as it does."""
        return {} if self.ts is None else {"ts": self.ts}

    def scalars(self) -> Dict[str, float]:
        """The metrics' scalars on the host after ONE device-to-host
        fetch (a ``float()`` each is a round trip each: 10-30 of
        them a step in the newer families)."""
        fetched = jax.device_get({
            k: v for k, v in self.metrics.items() if np.ndim(v) == 0
        })
        scalars = {}
        for name, value in fetched.items():
            try:
                scalars[name] = float(value)
            except (TypeError, ValueError):
                pass
        return scalars


def _flush_at_exit(trainer_ref):
    trainer = trainer_ref()
    if trainer is not None:
        trainer.flush_reports()


class ElasticTrainer:
    """Step/epoch accounting with a fixed global batch across resizes
    (reference: trainer.py GradientState + _ElasticOptimizer)."""

    def __init__(
        self,
        global_batch_size: int,
        micro_batch_size: int,
        dp_size: Optional[int] = None,
        metrics_path: Optional[str] = None,
    ):
        entered = time.time()
        self.global_batch_size = global_batch_size
        self.micro_batch_size = micro_batch_size
        self.dp_size = dp_size or env_utils.get_world_size()
        if global_batch_size % (micro_batch_size * self.dp_size):
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"micro {micro_batch_size} x dp {self.dp_size}"
            )
        self.grad_accum = global_batch_size // (
            micro_batch_size * self.dp_size
        )
        self.global_step = 0
        self._metrics_path = metrics_path or os.getenv(
            "DLROVER_METRICS_FILE",
            os.path.join("/tmp", f"dlrover_metrics_{os.getuid()}.json"),
        )
        self._epoch = 0
        self._restart_count = env_utils.get_restart_count()
        # always-on step-phase profiler: report_step() closes the
        # current step's breakdown and ships it (event + histogram +
        # metrics-file record for the agent's collectors)
        self.profiler = StepPhaseProfiler()
        self.last_step_phases: Dict[str, float] = {}
        # the report of the last step, where it waits for the next
        # step's ``compute`` phase, and whether the step in progress
        # bracketed its compute with ``block``
        self._pending: Optional[_StepReport] = None
        self._compute_blocked = False
        # (imported here: no line above ``make_train_step`` may move,
        # tests/test_step_report.py)
        import atexit
        import weakref

        atexit.register(_flush_at_exit, weakref.ref(self))
        _GRAD_ACCUM_GAUGE.set(self.grad_accum)
        devices = jax.local_devices()
        emit_event(
            "worker_backend",
            platform=devices[0].platform,
            kind=devices[0].device_kind,
            count=len(devices),
            restart_count=self._restart_count,
            node_rank=env_utils.get_node_rank(),
        )
        # the launch chain's last link before the step's own phases:
        # this constructor up to ``worker_backend``.  It holds the
        # backend's opening where no ``init_jax_distributed()`` ran
        # before it
        with trace.attach_context(trace.inherited_context()):
            trace.record_span(
                "trainer.init", entered, time.time(),
                restart_count=self._restart_count,
                node_rank=env_utils.get_node_rank(),
            )
        logger.info(
            "elastic trainer: global_batch=%s micro=%s dp=%s accum=%s "
            "on %d x %s",
            global_batch_size, micro_batch_size, self.dp_size,
            self.grad_accum, len(devices), devices[0].device_kind,
        )

    @property
    def local_batch_size(self) -> int:
        """Samples this data-parallel rank consumes per step."""
        return self.micro_batch_size * self.grad_accum

    @contextmanager
    def profile(self, name: str):
        """``with trainer.profile("data_wait"): batch = next(it)`` —
        see :class:`StepPhaseProfiler`.  For the compute phase,
        ``with trainer.profile("compute") as p: state, m = step(...);
        p.block(m)`` brackets the device work with
        ``block_until_ready``; the report of the step BEFORE is
        written there, after the dispatch and before the block, while
        the device computes (see :meth:`report_step`)."""
        with self.profiler.phase(name) as handle:
            yield handle
            if name == "compute" and handle.pending is not None:
                self._compute_blocked = True
                if self._pending is not None:
                    with self.profiler.phase("compute.report"):
                        self._write_pending("compute.report")

    def report_step(self, metrics: Optional[Dict[str, float]] = None):
        """Advance the step counter and close the step's phases; the
        step's report (the ``train_step`` event, the chip's memory
        line, the metrics file the agent monitor tails, the
        ``step_phases`` event; reference: trainer.py report to file +
        monitor/training.py) is written beside the NEXT step and not
        between two steps, where the device would wait for it.

        A step whose ``compute`` phase was bracketed with ``block``
        hands its report over: it is written inside the next such
        phase, booked there as ``compute.report`` (sub-phases
        ``.events``, ``.chip_metrics``, ``.metrics_file``), under its
        own step number and with the time it completed as ``ts``, and
        this call's ``report`` phase is the hand-over alone.  The
        events and the file so lag the chip by one step; whatever is
        still pending is written by the next ``report_step``, by
        :meth:`flush_reports` and at interpreter exit, never on entry
        to ``checkpoint`` (a save's stall is the save's).  A loop
        without such a phase, and every process with a fault injector
        armed (its rules are placed against the log as it is written
        step by step: a kill at step N leaves step N's event and
        fires before the save that follows), write at once, all of it
        in ``report`` (``report.events``, ``report.chip_metrics``,
        ``report.metrics_file``)."""
        prof = self.profiler
        deferred = self._compute_blocked and not _chaos.chaos_enabled()
        self._compute_blocked = False
        with prof.phase("report"):
            self._write_pending("report")
            # (a copy: the loop may reuse its dict before the write;
            # made before the counter moves, so metrics that are no
            # dict raise with nothing advanced)
            report = _StepReport(
                self.global_step + 1, self._epoch, dict(metrics or {})
            )
            self.global_step = report.step
            _REPORTED_STEP.set(self.global_step)
            if not deferred:
                self._write_step(report, "report")
        # close the step's phase breakdown: everything since the last
        # report (minus profiled phases) is "other"
        report.phases = self.last_step_phases = prof.finish_step()
        prof.step = self.global_step + 1
        if deferred:
            report.ts = time.time()
            self._pending = report
            return
        # the breakdown's own event is the first thing the next step
        # pays for: booked to its ``report``, not left in ``other``
        with prof.phase("report"), prof.phase("report.events"):
            self._write_phases(report)

    def flush_reports(self):
        """Write the report that waits for a next step which may not
        come: a loop's own last line, and a test's before it reads
        the log or the metrics file."""
        if self._pending is not None:
            with self.profiler.phase("report"):
                self._write_pending("report")

    def _write_pending(self, phase: str):
        """The pending report, if any, in sub-phases of the open
        ``phase``."""
        report, self._pending = self._pending, None
        if report is not None:
            self._write_step(report, phase)
            with self.profiler.phase(phase + ".events"):
                self._write_phases(report)

    def _write_step(self, report: _StepReport, phase: str):
        """The step's event and the metrics file, in three sub-phases
        of ``phase``."""
        prof = self.profiler
        with prof.phase(phase + ".events"):
            scalars = report.scalars()
            self._emit_train_step(report, scalars)
        with prof.phase(phase + ".chip_metrics"):
            chip_metrics = _chip_metrics()
        with prof.phase(phase + ".metrics_file"):
            self._write_metrics_file(report, scalars, chip_metrics)

    def _emit_train_step(self, report, scalars):
        # per-step training event: this is what lets the chaos
        # invariant checkers compute "steps lost across a fault" from
        # the event log alone (no-op unless an event log is configured)
        step_event = {
            "step": report.step,
            "restart_count": self._restart_count,
            # which node stepped: multi-agent chaos invariants decide
            # per-node progress from the event log alone
            "node_rank": env_utils.get_node_rank(),
        }
        if "loss" in scalars:
            # the elastic-resize loss-trajectory invariant compares
            # same-step losses across incarnations and world sizes —
            # a resharded restore that mangled the params shows up
            # as a divergence here, decided from the log alone
            step_event["loss"] = scalars["loss"]
        for name, value in scalars.items():
            # a model's own counters (``has_aux`` of make_train_step:
            # a sparse model's routing, a linear-attention model's
            # state, a windowed model's walk, a looped model's exits,
            # a state-space model's states and decays, a
            # hyper-connected model's streams, differential attention
            # and prediction layer, a channel-gated model's decays)
            # ride on this
            # event: one more event would cost the loop
            # 0.65 ms a step (PERF.md, PR 25)
            if name.startswith((
                "moe.", "gdn.", "attn.", "loop.", "ssm.", "mhc.", "gdla.",
                "mtp.", "kda.", "sconv.", "s6.",
            )):
                step_event[name] = value
        emit_event("train_step", **step_event, **report.stamp())
        # chaos hook AFTER the event: a kill rule at step N must leave
        # step N's completion in the log before the process dies; a
        # slow rule stretches the observable step time (straggler)
        _chaos.fire("trainer.step", step=report.step)

    def _write_metrics_file(self, report, scalars, chip_metrics):
        record = {
            "global_step": report.step,
            "timestamp": report.ts or time.time(),
            "epoch": report.epoch,
            # the agent's StepPhaseCollector ships these to the
            # master's diagnosis chain (data-starved detection): the
            # closed step's or, written at once, the step's so far
            # (its own write is still open, so counted up to here)
            "phases": report.phases or self.profiler.peek(),
        }
        if chip_metrics:
            record["chip_metrics"] = chip_metrics
        record.update(scalars)
        tmp = self._metrics_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(record, f)
            os.replace(tmp, self._metrics_path)
        except OSError as e:
            logger.debug("metrics file write failed: %s", e)

    def _write_phases(self, report):
        """The closed step's breakdown: histograms and its event."""
        for name, seconds in report.phases.items():
            if name == "total_s":
                continue
            _STEP_PHASE_SECONDS.observe(
                seconds,
                phase="other" if name == "other_s" else name,
            )
        # dict-build instead of kwargs so a user phase named
        # "step" can never collide with the envelope fields
        emit_event("step_phases", **{
            **report.phases,
            "step": report.step,
            "node_rank": env_utils.get_node_rank(),
            **report.stamp(),
        })

    def set_epoch(self, epoch: int):
        self._epoch = epoch

    def state_dict(self) -> Dict[str, int]:
        return {"global_step": self.global_step, "epoch": self._epoch}

    def load_state_dict(self, state: Dict[str, int]):
        self.global_step = int(state.get("global_step", 0))
        self._epoch = int(state.get("epoch", 0))


def init_jax_distributed():
    """Initialize multi-host JAX from the agent's env contract
    (reference analog: dist.init_process_group with MASTER_ADDR/PORT
    set by the agent, training.py:430-447), then open the backend:
    the first program call of every entrypoint, and two spans of the
    launch's trace.  ``trainer.distributed_init`` is the connect to
    the coordinator (``initialized`` false on one process: nothing
    to join).  ``trainer.backend_open`` is ``jax.local_devices()``,
    the call that creates the backend and, on a TPU host, takes the
    chip: every worker makes it next anyway, and it can only come
    AFTER the distributed initialize.  Before these spans a worker's
    time is interpreter + imports (``recovery_phase`` ``import``);
    :class:`~dlrover_tpu.trainer.recovery.RecoveryProfiler` books
    them as ``backend``."""
    coordinator = env_utils.get_coordinator_addr()
    num_processes = int(
        os.getenv("DLROVER_NUM_PROCESSES", "1")
    )
    distributed = bool(coordinator) and num_processes > 1
    labels = {
        "restart_count": env_utils.get_restart_count(),
        "node_rank": env_utils.get_node_rank(),
    }
    with trace.attach_context(trace.inherited_context()):
        with trace.span(
            "trainer.distributed_init", initialized=distributed,
            num_processes=num_processes, **labels,
        ):
            if distributed:
                process_id = int(os.getenv("DLROVER_PROCESS_ID", "0"))
                jax.distributed.initialize(
                    coordinator_address=coordinator,
                    num_processes=num_processes,
                    process_id=process_id,
                )
                logger.info(
                    "jax.distributed initialized: process %s/%s via %s",
                    process_id, num_processes, coordinator,
                )
        with trace.span("trainer.backend_open", **labels) as opened:
            devices = jax.local_devices()
            opened.set_attribute("platform", devices[0].platform)
            opened.set_attribute("kind", devices[0].device_kind)
            opened.set_attribute("count", len(devices))
    return distributed
