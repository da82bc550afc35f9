"""Registry of every known training-event type and its fields.

Four PRs of instrumentation made the JSONL event log the substrate
that chaos invariants, the timeline assembler and the goodput
diagnosis all decide from — which means a silently forked schema
(a renamed field, an unregistered type) breaks *verification*, not
just dashboards.  This module is the single source of truth:

- :data:`EVENT_SCHEMAS` lists every event type with its required and
  optional fields;
- :func:`validate_event` checks one recorded event dict;
- :func:`validate_call` checks one ``emit_event`` call site (the AST
  scanner in :mod:`dlrover_tpu.telemetry.check_events` feeds it).

New instrumentation MUST register its event type here; the tier-1
schema checker fails otherwise.
"""

from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Sequence

# envelope stamped by TrainingEventExporter.emit on every record
COMMON_FIELDS: FrozenSet[str] = frozenset(
    {"schema", "ts", "pid", "source", "type"}
)


@dataclass(frozen=True)
class EventSchema:
    type: str
    required: FrozenSet[str]
    optional: FrozenSet[str] = frozenset()
    # events whose payload is an open phase/stat dict (e.g. the
    # checkpoint engine's per-stage timings) accept extra fields
    allow_extra: bool = False

    @property
    def known(self) -> FrozenSet[str]:
        return self.required | self.optional | COMMON_FIELDS


def _s(
    type_: str,
    required: Sequence[str],
    optional: Sequence[str] = (),
    allow_extra: bool = False,
) -> EventSchema:
    return EventSchema(
        type_, frozenset(required), frozenset(optional), allow_extra
    )


EVENT_SCHEMAS: Dict[str, EventSchema] = {
    s.type: s
    for s in (
        # -- telemetry core ------------------------------------------
        # start_ts = the wall clock at the span's entry, the same
        # reading its profiler annotation carries as ``wall_ns``
        # (optional: logs recorded before it existed stay valid)
        _s("span", [
            "name", "trace_id", "span_id", "parent_id",
            "duration_s", "status", "attributes",
        ], ["start_ts"]),
        # -- master lifecycle ----------------------------------------
        _s("master_start", ["job", "port", "node_num", "metrics_port"]),
        _s("master_exit", [
            "job", "rc", "exit_reason", "global_step", "goodput",
            "recoveries",
        ]),
        _s("master_recovered",
           ["job", "incarnation", "recoveries", "rdzv_round"],
           ["entries", "applied", "requeued", "snapshot", "truncated",
            "from_mirror"]),
        _s("master_respawn", ["port", "respawn", "rc"]),
        _s("journal_replay", [
            "dir", "entries", "snapshot_seq", "last_seq", "truncated",
        ]),
        # -- rendezvous / sharding -----------------------------------
        _s("rendezvous_complete", ["rdzv", "round", "nodes", "wait_s"]),
        _s("shard_dispatch",
           ["dataset", "task_id", "worker", "start", "end"]),
        _s("shard_ack",
           ["dataset", "task_id", "success", "start", "end", "worker"]),
        # -- session resync (master crash recovery) ------------------
        _s("agent_resync", [
            "node_id", "node_rank", "restart_count", "last_step",
            "last_acked_dataset", "last_acked_task",
        ]),
        _s("master_resync", [
            "node_id", "incarnation", "recoveries", "rdzv_round",
            "master_changed", "last_step",
        ]),
        # -- trainer -------------------------------------------------
        # loss rides along when the step loop reported it: the
        # elastic-resize loss-trajectory invariant compares same-step
        # losses across incarnations/world sizes from the log alone
        # moe.*: a sparse model's per-step routing counters (the
        # ``aux`` of a ``has_aux`` loss; models/olmoe.py); for a layer
        # that holds a range of its experts (models/sarvam_mla.py):
        # the share of the step's assignments that reached a held
        # expert, the share of the layout's row tiles they fill (what
        # the layer's row movement and kernels walk) and the router
        # bias's size
        # gdn.state_rms_max: the largest rms of a linear-attention
        # layer's final state (models/olmo_hybrid.py)
        # attn.window_tiles_share: the sub-blocks the sliding layers'
        # flash kernels walk over those a causal walk would
        # (models/laguna.py)
        # attn.sink_*: a softmax with a learned sink a head
        # (models/mimo_v2.py): the share of a row's softmax the sink
        # takes, mean over the sinked layers, heads and rows, and the
        # largest |sink|
        # mhc.* / gdla.* / mtp.*: a model of several residual streams
        # and differential attention (models/motif.py): the worst
        # |row or column sum - 1| of the streams' mixing matrices
        # after Sinkhorn's iterations; the mean lambda and the mean
        # |lambda A_noise| over the mean |A_signal|; the prediction
        # layer's own cross entropy
        # loop.*: a looped model's exits (models/ouro.py): the mean
        # exit a token is expected to leave at, the exit
        # distribution's mean entropy, and the first and the last
        # exit's mean cross entropy (what a further pass buys)
        # ssm.*: a state-space model's layers (models/nemotron_h.py):
        # the largest rms of a layer's final state, and the mean of
        # the decay exp(dt A) over tokens, heads and layers (1 / (1 -
        # a) tokens is how far back a layer remembers)
        # kda.* / moe.groups_per_token_mean: channel-gated linear
        # attention over grouped experts (models/bailing_hybrid.py):
        # the least log-decay a step of any channel (the safe gate
        # holds it at or above kda_lower_bound), the largest rms of a
        # layer's final state, and the mean number of distinct groups
        # among a token's k choices (at most topk_group)
        # sconv.out_rms_max: the largest rms of a gated short
        # convolution's output y = C * conv(B * u) over the conv
        # layers (models/lfm2_moe.py): a product of three projections
        # of one input, what grows first if the mixer's scale drifts
        # s6.*: the selective scans (models/jamba.py): the largest rms
        # of a layer's final state, the mean of exp(dt A) over every
        # 64th row, the channels, the lanes and the layers, and the
        # mean step dt
        _s("train_step", ["step", "restart_count", "node_rank"],
           ["loss", "moe.load_max_over_mean", "moe.lb_loss",
            "moe.z_loss", "gdn.state_rms_max", "moe.held_rows_share",
            "moe.held_tiles_share", "moe.bias_abs_max",
            "attn.window_tiles_share", "attn.sink_mass_mean",
            "attn.sink_abs_max", "loop.expected_exit",
            "loop.exit_entropy", "loop.nll_first", "loop.nll_last",
            "ssm.state_rms_max", "ssm.decay_mean",
            "mhc.res_sum_err_max", "gdla.lambda_mean",
            "gdla.noise_share", "mtp.loss", "kda.log_decay_min",
            "kda.state_rms_max", "moe.groups_per_token_mean",
            "sconv.out_rms_max", "s6.state_rms_max", "s6.decay_mean",
            "s6.dt_mean"]),
        _s("loss_spike", ["step", "loss", "ema", "factor"]),
        # which devices the trainer process owns (its own
        # jax.local_devices()): the agent never opens the chip, so
        # this is the job's only first-hand device report
        _s("worker_backend",
           ["platform", "kind", "count", "restart_count",
            "node_rank"]),
        # per-step phase breakdown from the always-on profiler
        # (open dict: data_wait / h2d / compute / checkpoint /
        # report / other_s / total_s, arbitrary user phases allowed)
        _s("step_phases", ["step", "node_rank"], allow_extra=True),
        # one completed PPO iteration of the elastic RL loop: the
        # measured phase seconds (rollout / score / gae / train) give
        # the timeline its RL phase slices, so recovery losses book
        # against real iteration anatomy instead of a flat gap
        _s("rl_iteration", ["iteration", "restart_count", "node_rank"],
           ["leases", "rollout_s", "score_s", "gae_s", "train_s",
            "actor_loss", "critic_loss"]),
        # -- checkpoint (open phase dicts: stage timings vary) -------
        _s("checkpoint_shm_save", ["step", "rank"],
           ["bytes", "fetch_s", "memcpy_s", "lock_wait_s", "total_s"],
           allow_extra=True),
        _s("checkpoint_restore", ["step", "tier", "rank"],
           allow_extra=True),
        _s("checkpoint_persist", ["step", "ok", "seconds"]),
        # start_ts / seconds: the commit poll (done files -> tracker)
        _s("checkpoint_commit", ["step"], ["start_ts", "seconds"]),
        # sparse (KvVariable) state riding the flash checkpoint:
        # stage=export on every save, stage=restore on every import;
        # resharded restores carry exactly-once accounting
        # (rows = imported subset, total_rows = distinct union across
        # the old world) and per-table content digests when
        # DLROVER_KV_DIGEST is armed (order-independent, additive
        # across disjoint shards — the chaos invariants' raw material)
        _s("kv_checkpoint",
           ["stage", "rows", "bytes"],
           ["step", "rank", "tier", "seconds", "tables",
            "spilled_rows", "spill_disabled", "lost_rows",
            "resharded", "from_world", "world_size", "total_rows",
            "digests",
            # dirty-row delta exports (serving plane): delta=True
            # marks an export of only the rows touched since the
            # last cleared delta (dead_rows = eviction tombstones,
            # table_rows = logical table size for the delta ratio)
            "delta", "dead_rows", "table_rows",
            # streaming reshard (bounded-memory cross-world
            # restore): streamed=True, chunks = windows applied,
            # window_rows = the configured window
            "streamed", "chunks", "window_rows",
            # delta flash checkpoints (hot save path): kind =
            # base/delta for the CHECKPOINT consumer, with the
            # chain link steps a restore replays
            "kind", "consumer", "base_step", "parent_step",
            "chain_len"]),
        # one window of a streaming reshard applied: rows = input
        # rows partitioned in this window, owned = the subset this
        # rank imported; the mid-reshard kill scenario counts these
        # to prove the replayed reshard re-ran from the top
        _s("kv_reshard_chunk",
           ["table", "chunk", "rows", "owned", "rank"],
           ["step"]),
        # -- serving plane (train-to-serve publication) --------------
        # one committed generation published by the trainer: kind =
        # base (full snapshot) or delta (dirty rows + tombstones);
        # emitted AFTER the tracker advance, so per-generation
        # exactly-once publication is countable from the log; tables
        # carries the per-table content digests the ingest must match
        _s("serving_publish",
           ["generation", "kind", "rows", "bytes", "seconds"],
           ["step", "dead_rows", "delta_ratio", "tables"]),
        # one generation applied on a replica, emitted only after the
        # FULL apply under the swap lock — its digests (restated from
        # the verified manifest) tie it to the matching publish: a
        # torn or uncommitted generation can never produce this event
        _s("serving_ingest",
           ["generation", "kind", "rows", "seconds"],
           ["step", "dead_rows", "bytes", "freshness_s", "respawned",
            "tables"]),
        # train-commit -> servable latency of the generation now
        # being served, after each catch-up
        _s("serving_freshness",
           ["generation", "freshness_s"],
           ["step", "lag_generations", "respawned"]),
        # periodic lookup-traffic sample from the replica process:
        # latency percentiles + throughput under (possibly) live
        # ingest, tagged with the served generation
        # ``replica`` is the reporting side: a pool member's id, or
        # "load" for the fleet harness's client-side aggregate (which
        # adds ``failed``/``streams`` — the zero-client-visible-
        # failure half of the fleet chaos verdict)
        _s("serving_lookup_stats",
           ["count", "p50_ms", "p99_ms", "qps", "window_s"],
           ["rows", "generation", "replica", "failed", "streams"]),
        # -- serving fleet (replica pool + lookup router) ------------
        # one routed-traffic window from the lookup router: outcome
        # counts (ok / rerouted / stale / failed — the zero-failure
        # and zero-stale invariants count these), shared-estimator
        # p50/p99 over the window's bucket deltas, pool composition
        # and the newest admitted generation (the freshness floor)
        _s("serving_route",
           ["count", "qps", "window_s", "generation_floor", "ok",
            "rerouted", "stale", "failed", "members_up"],
           ["p50_ms", "p99_ms", "members_draining",
            "members_suspect", "hedged"]),
        # routing-table state transition for one pool member: state =
        # joined / admitted / draining / suspect / lost / recovered /
        # removed; emitted on CHANGE only (heartbeats are silent), so
        # shed/admit latency and membership history read from the log
        _s("replica_status",
           ["replica_id", "generation", "state"],
           ["addr", "draining", "respawned", "target_generation"]),
        # -- agent ---------------------------------------------------
        # reason: failure / membership / hang / resize — what drove
        # this restart (resize restarts are planned drains)
        _s("worker_restart", ["node_rank", "restart_count"],
           ["reason"]),
        # restore prefetch hint: agent paged the shm snapshot in
        # while the replacement trainer was importing
        _s("shm_prefetch", ["bytes", "seconds"],
           ["segments", "restart_count"]),
        # measured death->first-step budget, one event per phase
        # (spawn / import / backend / restore / aot / retrace /
        # first_step) — the trainer-side RecoveryProfiler emits them
        # and the timeline derives the recovery breakdown slices; a
        # phase lies at [ts - seconds, ts] (spawn and import carry
        # the ts at which they ENDED, before there was a profiler).
        # `backend` is the distributed initialize + the backend's
        # opening (init_jax_distributed), no part of `import`.  `aot` is
        # the AOT executable cache resolve: deserialize+link on a
        # HIT (retrace collapses to 0), entry write on a MISS
        _s("recovery_phase", ["phase", "seconds", "restart_count"],
           ["node_rank"]),
        # persistent-compile-cache witness around the first
        # post-restore step: hit = no new cache entries over a warm
        # dir (the retrace-elimination invariant's raw material);
        # status distinguishes aot-hit / xla-cache-hit / cold and
        # aot_entries counts the serialized-executable half
        _s("compile_cache", ["hit", "restart_count"],
           ["entries_before", "entries_after", "retrace_s", "dir",
            "node_rank", "status", "aot_entries"]),
        # AOT executable cache resolve: hit = the compiled step was
        # DESERIALIZED (no trace); a miss carries the measured
        # trace_s and whether the entry was written so incarnation
        # N+1 hits; wait_s = what the critical path stalled when the
        # resolve ran on the overlap thread; overlapped_restore =
        # the async restore was still reading when it finished
        _s("aot_cache", ["hit", "restart_count"],
           ["resolution", "key", "dir", "wrote", "preloaded",
            "seconds",
            "load_s", "trace_s", "save_s", "wait_s", "entries",
            "reason", "overlapped_restore", "node_rank", "fast",
            "read_s", "unpickle_s", "deserialize_s",
            "deserialize_cpu_s"]),
        # master journal mirrored to the checkpoint storage tier
        # (async group commit): how far the mirror lagged when a
        # batch flushed — the host-portable control plane's witness
        _s("journal_mirror_flush", ["records", "lag_s"],
           ["dir"]),
        _s("warm_fork_fallback", [
            "node_rank", "local_rank", "restart_count", "reason",
        ]),
        _s("node_check", ["round", "elapsed_s", "world_size"]),
        # -- diagnosis / chaos ---------------------------------------
        _s("diagnosis_verdict",
           ["hung", "action", "culprit_node", "reason"],
           # actionable-verdict fields (PR 6): classification,
           # measured stall/excess durations (the timeline's real
           # claim windows) and the evidence excerpt
           ["verdict", "stall_s", "duration_s", "evidence"]),
        # agent watchdog hang flight data: measured stall + captured
        # stacks + /proc state of the worker tree
        _s("hang_evidence",
           ["node_rank", "stall_s", "last_step"],
           ["stacks", "workers"]),
        # control-plane SLO breach onset (per-verb RPC latency
        # quantile over its declared bound)
        _s("rpc_slo_breach",
           ["verb", "quantile", "threshold_s", "observed_s"],
           ["count"]),
        _s("chaos_inject", [
            "scenario", "seed", "seq", "point", "rule", "action",
            "step", "node_rank",
        ]),
        # -- elastic world-resize ------------------------------------
        # the coordinator's journaled decision (target world size,
        # why, what it decided from); detected_ts = the lost node's
        # last sign of life, so the timeline's decide phase covers
        # the real outage
        _s("resize_decision",
           ["target", "from_world", "reason", "round"],
           ["detected_ts"]),
        # master-observed resize phase completions (rendezvous /
        # first_step); drain and reshard-restore are derived on the
        # assembled timeline from worker_restart/checkpoint_restore
        _s("resize_phase", ["phase", "seconds", "target"]),
        # -- flight recorder -----------------------------------------
        _s("goodput_attribution", [
            "window_start", "window_end", "window_s", "training_s",
            "loss_s", "goodput", "buckets",
        ]),
        # periodic goodput-ledger summary published by the master's
        # ledger service (per-category seconds live in the open dict)
        _s("goodput_ledger",
           ["goodput", "attributed_pct", "incarnations", "window_s"],
           ["top_loss_cause", "wall_s", "totals"]),
        # ledger-derived goodput vs the SpeedMonitor's step-gap ratio
        # drifted past the cross-check tolerance (1%)
        _s("goodput_divergence", ["ledger", "monitor", "divergence"]),
        # event-log rotation could not take the advisory lock and fell
        # back to best-effort rotation (possible concurrent rotator)
        _s("telemetry_rotate_contended", ["path"]),
        # -- fleet observatory ---------------------------------------
        # periodic control-plane scoreboard sample under synthetic
        # fleet load: windowed per-verb latency view + fan-in gauges
        # (open dict: the verbs payload varies with the traffic mix)
        _s("fleet_report", ["agents", "rps", "window_s"],
           allow_extra=True),
        # SLO-green capacity search result: the max agent count one
        # master sustained with every windowed rule green
        _s("fleet_capacity",
           ["max_sustained_agents"],
           ["rps_at_capacity", "levels", "search_s",
            "first_breach_agents"]),
    )
}


@dataclass(frozen=True)
class SpanSchema:
    """One span name: the process that records it and what an
    operator reads from it (README "Telemetry" prints this table)."""

    name: str
    process: str
    reads: str


# every literal span name of the package (``lint_events.lint_spans``
# holds the two in step).  In a process that has imported jax (the
# trainer) a span is also the profiler annotation ``dlrover.<name>``.
SPAN_SCHEMAS: Dict[str, SpanSchema] = {
    s.name: s
    for s in (
        # -- a launch (restart 0) or a respawn: one trace, opened by
        # tpurun.boot; every one carries restart_count + node_rank --
        SpanSchema(
            "tpurun.boot", "agent (tpurun)",
            "tpurun's process start (the kernel's clock) -> run() "
            "entered: interpreter + imports; the root of the "
            "launch's trace"),
        SpanSchema(
            "tpurun.master_boot", "agent (tpurun)",
            "spawning the local master and polling its port until it "
            "answers (polls; slept_s = the poll's own sleep); "
            "master.boot lies inside it"),
        SpanSchema(
            "master.boot", "master",
            "the master's process start -> it serves (master_start): "
            "imports, construction, journal.replay"),
        SpanSchema(
            "agent.init", "agent",
            "the agent's constructor: monitors and, with "
            "warm_restart, the forkserver template's start"),
        SpanSchema(
            "rdzv.join", "agent + master",
            "one rendezvous join, agent side linked to the master's "
            "handler by the RPC's trace context (agent side: polls; "
            "slept_s = whole JOIN_INTERVALs slept waiting for the "
            "world)"),
        SpanSchema(
            "agent.spawn_workers", "agent",
            "starting this node's workers (workers; warm_fork = "
            "forked from the template); the workers' trainer.* "
            "spans are its children through the environment"),
        SpanSchema(
            "trainer.distributed_init", "trainer",
            "jax.distributed.initialize from the agent's env "
            "(initialized false on one process: nothing to join); "
            "recovery_phase import ends where it starts"),
        SpanSchema(
            "trainer.backend_open", "trainer",
            "the first jax.local_devices(): creating the backend, on "
            "a TPU host taking the chip (platform, kind, count)"),
        SpanSchema(
            "trainer.init", "trainer",
            "ElasticTrainer's constructor up to worker_backend"),
        SpanSchema(
            "node_check", "agent",
            "one node-check round before workers start"),
        SpanSchema(
            "journal.replay", "master",
            "replay of the state journal at master start"),
        SpanSchema(
            "ckpt.restore", "trainer",
            "one restore; tier and stage seconds in its attributes"),
        # -- a flash save, trainer side (one trace id a save) --------
        SpanSchema(
            "ckpt.save", "trainer",
            "one save_checkpoint call: how long it blocked the loop "
            "(step, storage, bytes, ok; route = snapshot, or caller "
            "where the state was written on the calling thread); its "
            "children say where"),
        SpanSchema(
            "ckpt.save.notify_agent", "trainer",
            "shipping the saver config to the agent (first save)"),
        SpanSchema(
            "ckpt.save.sparse_merge", "trainer",
            "exporting KvVariable tables into the state"),
        SpanSchema(
            "ckpt.save.lock_wait", "trainer",
            "waiting for the shard's shm lock; held_by names the "
            "holder (persist:<step> = the agent's in-RAM copy)"),
        SpanSchema(
            "ckpt.save.layout", "trainer",
            "flattening the pytree and laying out the segment"),
        SpanSchema(
            "ckpt.save.segment", "trainer",
            "creating (or growing) the shm segment"),
        SpanSchema(
            "ckpt.save.publish_meta", "trainer",
            "publishing the layout to the agent's meta dict (twice "
            "a save: writing=True, then False)"),
        SpanSchema(
            "ckpt.save.fetch", "trainer",
            "device->host transfer of one ~256 MB chunk (bytes)"),
        SpanSchema(
            "ckpt.save.memcpy", "trainer",
            "native copy of one chunk into the segment (bytes)"),
        SpanSchema(
            "ckpt.save.scalars", "trainer",
            "writing the pickled non-array leaves"),
        SpanSchema(
            "ckpt.save.writer_wait", "trainer",
            "waiting for the writer thread: to commit the previous "
            "snapshot before taking the next (past the bound the "
            "save is skipped), or this engine's first snapshot before "
            "the call returns (waited_s)"),
        SpanSchema(
            "ckpt.save.route", "trainer",
            "choosing the save's route: does the state hold device "
            "arrays, and does every device report room for a "
            "snapshot beside the step's scratch"),
        SpanSchema(
            "ckpt.save.snapshot", "trainer",
            "on-device copy of the state"),
        SpanSchema(
            "ckpt.save.d2h_kickoff", "trainer (writer thread)",
            "starting the async device->host copies, first thing "
            "beneath ckpt.save.write"),
        SpanSchema(
            "ckpt.save.enqueue", "trainer",
            "counting the snapshot's bytes and handing it to the "
            "writer thread"),
        SpanSchema(
            "ckpt.save.write", "trainer (writer thread)",
            "the snapshot's shm write (lock_wait, fetch, memcpy ... "
            "beneath it), off the loop, same trace id as its "
            "ckpt.save"),
        # -- a persist, agent side -----------------------------------
        SpanSchema(
            "ckpt.persist", "agent",
            "one persist of a step: shm -> storage -> commit"),
        SpanSchema(
            "ckpt.persist.prefault", "agent",
            "touching the segment's pages before taking the lock"),
        SpanSchema(
            "ckpt.persist.lock_hold", "agent",
            "how long a shard's shm lock was held: the in-RAM copy "
            "of the segment (bytes; wait_s = how long the lock took "
            "to get)"),
        SpanSchema(
            "ckpt.persist.write_storage", "agent",
            "writing the copy, its meta and the done file"),
        SpanSchema(
            "ckpt.persist.commit", "agent",
            "polling the done files and moving the tracker"),
        # -- periodic services beside the worker (one span a tick) ---
        SpanSchema(
            "master.goodput_ledger_tick", "master",
            "re-reading every event log into the goodput ledger"),
        SpanSchema(
            "master.slo_check", "master",
            "one pass over the RPC latency SLOs"),
        SpanSchema(
            "master.journal_snapshot", "master",
            "folding the journal into a snapshot"),
        SpanSchema(
            "agent.training_monitor", "agent",
            "reading the metrics file, reporting the global step"),
        SpanSchema(
            "agent.resource_monitor", "agent",
            "host CPU and memory, reported to the master"),
        SpanSchema(
            "agent.diagnosis_collect", "agent",
            "one round of the diagnosis collectors"),
        SpanSchema(
            "agent.heartbeat", "agent",
            "one heartbeat RPC"),
    )
}


def validate_event(record: Dict) -> List[str]:
    """Problems with one recorded event dict (empty = valid)."""
    problems: List[str] = []
    etype = record.get("type")
    if not isinstance(etype, str) or not etype:
        return ["event record has no 'type'"]
    schema = EVENT_SCHEMAS.get(etype)
    if schema is None:
        return [f"unregistered event type {etype!r}"]
    missing = schema.required - set(record)
    if missing:
        problems.append(
            f"{etype}: missing required field(s) {sorted(missing)}"
        )
    if not schema.allow_extra:
        extra = set(record) - schema.known
        if extra:
            problems.append(
                f"{etype}: unregistered field(s) {sorted(extra)}"
            )
    return problems


def validate_call(
    event_type: str,
    kwarg_names: Sequence[str],
    has_dynamic: bool = False,
    where: str = "",
) -> List[str]:
    """Problems with one ``emit_event(type, ...)`` call site.

    ``has_dynamic`` marks a ``**kwargs`` expansion at the site: the
    literal keywords are still checked against the registry, but
    required-field completeness cannot be decided statically and is
    left to the recorded-log check."""
    loc = f" at {where}" if where else ""
    schema = EVENT_SCHEMAS.get(event_type)
    if schema is None:
        return [f"unregistered event type {event_type!r}{loc}"]
    problems: List[str] = []
    names = set(kwarg_names)
    if not schema.allow_extra:
        drift = names - schema.required - schema.optional
        if drift:
            problems.append(
                f"{event_type}: unregistered field(s) "
                f"{sorted(drift)}{loc}"
            )
    if not has_dynamic:
        missing = schema.required - names
        if missing:
            problems.append(
                f"{event_type}: call omits required field(s) "
                f"{sorted(missing)}{loc}"
            )
    return problems
