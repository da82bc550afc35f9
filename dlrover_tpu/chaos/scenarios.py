"""Built-in chaos scenarios + the toy elastic train loop they drive.

Each scenario is a factory ``(seed) -> Scenario`` registered in
:data:`SCENARIOS`; the CLI (``python -m dlrover_tpu.chaos``) and the
e2e tests run them through :mod:`dlrover_tpu.chaos.harness`.  They are
deliberately small compositions of the schedule vocabulary — the point
of the subsystem is that new failure modes are a dict away, not a new
test file away.
"""

from typing import Callable, Dict, Optional

from dlrover_tpu.agent.forkserver import TRAINER_PRELOAD
from dlrover_tpu.chaos.schedule import Scenario

# knobs the harness exports to the training subprocess
TOTAL_STEPS_ENV = "DLROVER_CHAOS_TOTAL_STEPS"
CKPT_EVERY_ENV = "DLROVER_CHAOS_CKPT_EVERY"
# durable mid-run saves every N steps (0 = only the final step goes
# to disk) — the tier-fallback scenarios restore from these when the
# shm snapshot is refused
DISK_EVERY_ENV = "DLROVER_CHAOS_DISK_EVERY"
# per-step sleep stretching the toy loop's wall clock so wall-time
# triggered rules (preemption notices, brownout windows) land
# mid-run instead of after the job already finished
STEP_SLEEP_ENV = "DLROVER_CHAOS_STEP_SLEEP"
# drive the master's dynamic data sharding: the dataset size (one
# sample per shard, one step per shard; 0 = plain fixed step loop).
# The master-recovery scenarios need shard traffic so "no shard lost,
# none acked twice" is decidable from shard_dispatch/shard_ack events
SHARD_DATASET_ENV = "DLROVER_CHAOS_SHARD_DATASET"

# Toy GPT elastic train loop (it never crashes itself: faults come
# exclusively from the chaos schedule).  Flash-checkpoints to shm every CKPT_EVERY
# steps; a killed incarnation restores from the snapshot the agent
# kept alive and finishes the fixed step budget; the final step is
# persisted to disk and committed.  argv: ckpt_dir
CHAOS_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, TrainState, abstract_like, make_train_step,
    restore_train_state,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "10"))
CKPT_EVERY = int(os.environ.get("DLROVER_CHAOS_CKPT_EVERY", "2"))
DISK_EVERY = int(os.environ.get("DLROVER_CHAOS_DISK_EVERY", "0"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
SHARD_DATASET = int(os.environ.get("DLROVER_CHAOS_SHARD_DATASET", "0"))

# measured death->first-step budget: books the spawn/import phases
# now, restore/retrace/first_step below — every incarnation emits
# recovery_phase events the invariants and timeline read
prof = RecoveryProfiler()

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

# restore overlap: the read/assemble stages run on a background
# thread WHILE the model/optimizer/step build below proceeds — only
# the result() join is serial with training
with prof.phase("ckpt_init"):
    ckpt = Checkpointer(ckpt_dir)
    load_handle = ckpt.load_checkpoint_async()

with prof.phase("model_build"):
    cfg = GPTConfig.tiny()
    model = GPT(cfg)
    optimizer = optax.adam(1e-3)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    step_fn = make_train_step(loss_fn, optimizer)

rng = np.random.default_rng(0)
data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)

def place_batch():
    # per-step host->device placement so the always-on profiler's
    # h2d phase measures a real transfer, not zero
    return {"x": jnp.asarray(data[:, :-1]),
            "y": jnp.asarray(data[:, 1:])}

# AOT resolve, OVERLAPPED with the async restore read (which runs
# on its own thread — the PR 10 composition): a warm incarnation
# resolves straight through the label index and DESERIALIZES the
# compiled step — no eval_shape, no Python trace, no XLA compile —
# while the restore reads; a cold one traces+compiles here and
# WRITES the entry + index the next incarnation hits.  Deliberately
# on the MAIN thread: a second XLA-heavy thread fighting the
# restore/state build measurably inflates the deserialize on small
# hosts.
def _abstract_examples():
    abs_params = jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    return abs_state, abstract_like(place_batch())

step = prof.resolve_step(
    step_fn, _abstract_examples,
    restore_busy=lambda: not load_handle.done(),
)

start_step, restored = load_handle.result()
prof.record_restore(ckpt.last_restore_phases)
with prof.phase("state_build"):
    if start_step is None:
        params = model.init_params(jax.random.PRNGKey(0))
        start_step = 0
        state = TrainState.create(params, optimizer)
    else:
        # shaved state_build: the checkpoint carries the WHOLE train
        # state (params + optax slots + step), so nothing re-inits
        # eagerly and all leaf conversions ride one batched
        # device_put instead of a per-leaf jnp.asarray chain
        state = restore_train_state(optimizer, restored["state"])

_first_step = [True]
def run_step(state, batch):
    # no trace on an AOT hit — the step dispatches straight into the
    # deserialized executable; the MISS path already measured its
    # retrace (or measures it here on the deferred fallback)
    state, metrics = step(state, batch)
    if _first_step[0]:
        _first_step[0] = False
        jax.block_until_ready(metrics)
        prof.record_first_step()
    return state, metrics

with prof.phase("loop_setup"):
    trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                             dp_size=1)
    trainer.global_step = start_step

    batch = place_batch()

def after_step():
    # identical checkpoint cadence for both loop flavours; the FULL
    # train state rides the snapshot so a restore supplies the optax
    # slots and state_build defers the optimizer init
    sd = {"state": state, "trainer": trainer.state_dict()}
    if DISK_EVERY and trainer.global_step % DISK_EVERY == 0:
        # durable mid-run save; wait for the commit so a kill rule
        # scheduled a couple of steps later deterministically finds
        # a committed storage step to fall back to
        ckpt.save_checkpoint(
            trainer.global_step, sd, storage_type=StorageType.DISK,
        )
        ckpt.wait()
        deadline = time.time() + 30
        while (time.time() < deadline
               and committed_step() < trainer.global_step):
            time.sleep(0.1)
    elif trainer.global_step % CKPT_EVERY == 0:
        ckpt.save_checkpoint(
            trainer.global_step, sd, storage_type=StorageType.MEMORY,
        )
        # accepted is not committed: a kill rule a step later
        # must find THIS step in shm
        ckpt.wait()

if SHARD_DATASET:
    # master-driven dynamic sharding: one step per shard task.  The
    # master journals every dispatch/ack, so a master crash mid-run
    # (the master-recovery scenarios SIGKILL it between dispatches)
    # must lose no shard and complete none twice — decided later
    # from the shard_ack events
    from dlrover_tpu.agent.sharding_client import ShardingClient

    sc = ShardingClient(
        dataset_name="chaos-ds", batch_size=1, num_epochs=1,
        dataset_size=SHARD_DATASET, shuffle=False,
        num_minibatches_per_shard=1, storage_type="table",
    )
    while True:
        with trainer.profile("data_wait"):
            task = sc.fetch_task()
        if task is None:
            break
        with trainer.profile("h2d"):
            batch = place_batch()
        with trainer.profile("compute") as p:
            state, metrics = run_step(state, batch)
            p.block(metrics)
        trainer.report_step(metrics)
        if STEP_SLEEP:
            time.sleep(STEP_SLEEP)
        sc.report_task_done(task.task_id)
        # books into the NEXT step's breakdown (the step is closed by
        # report_step), which is where a save's stall is felt anyway
        with trainer.profile("checkpoint"):
            after_step()
    FINAL_STEP = trainer.global_step
else:
    for i in range(start_step, TOTAL_STEPS):
        # the always-on profiler: h2d is a real per-step placement
        # and compute is bracketed by block_until_ready, so every
        # train_step ships a real step_phases breakdown
        with trainer.profile("h2d"):
            batch = place_batch()
        with trainer.profile("compute") as p:
            state, metrics = run_step(state, batch)
            p.block(metrics)
        # report_step emits the train_step event and fires the
        # trainer.step chaos hook — a kill rule ends the process HERE
        trainer.report_step(metrics)
        if STEP_SLEEP:
            time.sleep(STEP_SLEEP)
        with trainer.profile("checkpoint"):
            after_step()
    FINAL_STEP = TOTAL_STEPS

# final durable save, retried until the commit lands: a transient
# brownout may eat one persist round (reported through telemetry,
# never retried by the saver itself — the next SAVE event is the
# retry), and the job's contract is that the final step ends up
# committed anyway.  Only node rank 0 waits on the commit tracker —
# the saver writes it on rank 0 alone, so in multi-agent runs the
# other ranks persist their shard and exit
final_sd = {"state": state, "trainer": trainer.state_dict()}
NODE_RANK = int(os.environ.get("DLROVER_NODE_RANK", "0") or 0)
if NODE_RANK == 0:
    deadline = time.time() + 60
    while time.time() < deadline and committed_step() < FINAL_STEP:
        ckpt.save_checkpoint(
            FINAL_STEP, final_sd, storage_type=StorageType.DISK,
        )
        ckpt.wait()
        poll_end = time.time() + 10
        while time.time() < poll_end and committed_step() < FINAL_STEP:
            time.sleep(0.2)
    assert committed_step() >= FINAL_STEP, (
        "checkpoint commit did not land"
    )
else:
    ckpt.save_checkpoint(
        FINAL_STEP, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
ckpt.close()
'''

# The same loop WITHOUT the commit-wait after a MEMORY save: what a
# production loop does.  The loop steps on while the writer thread
# copies the snapshot, so a kill can land between a save's accept and
# its commit (kill_between_accept_and_commit).
_COMMIT_WAIT = """\
        # accepted is not committed: a kill rule a step later
        # must find THIS step in shm
        ckpt.wait()
"""
assert CHAOS_TRAIN_SCRIPT.count(_COMMIT_WAIT) == 1
NO_COMMIT_WAIT_TRAIN_SCRIPT = CHAOS_TRAIN_SCRIPT.replace(_COMMIT_WAIT, "")



# Elastic world-resize train loop (ISSUE 8): a GLOBAL param sharded
# over ALL devices of the current world (2 hosts x 2 CPU devices at
# world=2, 1 host x 2 at world=1 — the harness exports
# xla_force_host_platform_device_count=2), trained in lockstep with a
# real cross-process collective per step via jax.distributed.  Every
# incarnation re-forms the mesh from the agent's env contract and
# restores the checkpoint RESHARDED onto it: the storage tier holds
# per-host shard files, so a 2-host -> 1-host restore genuinely
# redistributes node 1's shards onto node 0's devices.  The per-step
# batch is a pure function of the step index (counter-based PRNG), so
# the loss at step k is identical for ANY world size / restart
# history — :func:`resize_reference_losses` recomputes the
# uninterrupted-control trajectory in-process and the harness compares
# every reported loss against it.  argv: ckpt_dir (SHARED across all
# nodes — that is what makes cross-host redistribution possible).
RESIZE_TRAIN_SCRIPT = r'''
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, init_jax_distributed,
)

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "24"))
DISK_EVERY = int(os.environ.get("DLROVER_CHAOS_DISK_EVERY", "3"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
SHARD_DATASET = int(os.environ.get("DLROVER_CHAOS_SHARD_DATASET", "0"))
DIM = int(os.environ.get("DLROVER_CHAOS_RESIZE_DIM", "64"))
# tail-stretch: while running below full strength (the shrunken
# world between the kill and the grow-back), slow the step cadence so
# the job cannot finish before the coordinator's grow-back decision
# lands — the decision race, not the training math, is what the
# churn scenario exercises
NNODES = int(os.environ.get("DLROVER_CHAOS_NNODES", "0") or 0)
SHRUNK_SLEEP = float(
    os.environ.get("DLROVER_CHAOS_SHRUNK_STEP_SLEEP", "0") or 0
)

WORLD = int(os.environ.get("DLROVER_WORLD_SIZE", "1") or 1)
RANK = int(os.environ.get("DLROVER_RANK", "0") or 0)

# multi-host runtime from the agent's rendezvous env contract
# (no-op at world 1); the mesh spans EVERY device of this world
init_jax_distributed()
devs = jax.devices()
mesh = Mesh(np.array(devs), ("fsdp",))
shard = NamedSharding(mesh, P("fsdp"))

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

def make_sharded(global_np):
    # per-device placement of this process's addressable shards —
    # works at any world size (device_put of a full host array onto
    # a cross-process sharding would not)
    arrs = [
        jax.device_put(np.ascontiguousarray(global_np[index]), d)
        for d, index in shard.addressable_devices_indices_map(
            global_np.shape
        ).items()
    ]
    return jax.make_array_from_single_device_arrays(
        global_np.shape, shard, arrs
    )

template = make_sharded(np.zeros((DIM, 8), np.float32))
ckpt = Checkpointer(ckpt_dir, replicated=False)
# cross-world restores skip the shm tier (per-node, possibly
# different steps) and RESHARD from the committed storage tier
step0, restored = ckpt.load_checkpoint(target_state={"w": template})
if step0 is None:
    start_step, w = 0, template
else:
    start_step, w = int(step0), restored["w"]

# MUST mirror scenarios.resize_reference_losses exactly: the batch is
# derived from the step index inside the jitted program (counter-based
# PRNG -> same bits at any world size), so the loss trajectory of any
# incarnation matches the uninterrupted single-device control
@jax.jit
def step_fn(w, k):
    x = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1000), k),
        (8,), jnp.float32,
    )
    def loss_fn(w):
        # row-sharded w: the mean over DIM is a real cross-device
        # (and at world 2, cross-process) reduction
        return ((w @ x - 1.0) ** 2).mean()
    loss, g = jax.value_and_grad(loss_fn)(w)
    return w - 0.1 * g, loss

trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                         dp_size=1)
trainer.global_step = start_step

# dynamic data sharding rides along on the lead rank only: the
# lockstep collective loop cannot let members consume different task
# counts, so global rank 0 is the data feeder — exactly-once shard
# accounting across all three world incarnations is still decided
# from shard_ack events alone
sc = None
if SHARD_DATASET and RANK == 0:
    from dlrover_tpu.agent.sharding_client import ShardingClient

    sc = ShardingClient(
        dataset_name="chaos-ds", batch_size=1, num_epochs=1,
        dataset_size=SHARD_DATASET, shuffle=False,
        num_minibatches_per_shard=1, storage_type="table",
    )

for k in range(start_step, TOTAL_STEPS):
    task = None
    if sc is not None:
        with trainer.profile("data_wait"):
            task = sc.fetch_task()
    with trainer.profile("compute") as p:
        w, loss = step_fn(w, k + 1)
        p.block(loss)
    trainer.report_step({"loss": float(loss)})
    if task is not None:
        sc.report_task_done(task.task_id)
    if NNODES and WORLD < NNODES and SHRUNK_SLEEP:
        time.sleep(SHRUNK_SLEEP)
    elif STEP_SLEEP:
        time.sleep(STEP_SLEEP)
    with trainer.profile("checkpoint"):
        if DISK_EVERY and trainer.global_step % DISK_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step, {"w": w},
                storage_type=StorageType.DISK,
            )
            ckpt.wait()
            deadline = time.time() + 30
            while (time.time() < deadline
                   and committed_step() < trainer.global_step):
                time.sleep(0.1)
        else:
            ckpt.save_checkpoint(
                trainer.global_step, {"w": w},
                storage_type=StorageType.MEMORY,
            )
            # accepted is not committed: a kill rule a step later
            # must find THIS step in shm
            ckpt.wait()

# final durable save: every rank persists its shard; the lead rank
# waits for the commit (needs every surviving rank's done file)
final_sd = {"w": w}
if RANK == 0:
    deadline = time.time() + 60
    while time.time() < deadline and committed_step() < TOTAL_STEPS:
        ckpt.save_checkpoint(
            TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
        )
        ckpt.wait()
        poll_end = time.time() + 10
        while time.time() < poll_end and committed_step() < TOTAL_STEPS:
            time.sleep(0.2)
    assert committed_step() >= TOTAL_STEPS, (
        "checkpoint commit did not land"
    )
else:
    ckpt.save_checkpoint(
        TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
ckpt.close()
'''


# Sparse elastic train loop (ISSUE 9): a DeepFM job whose embedding
# lives in a host KvVariable table (GroupAdam slot tables riding
# along, spill tier armed when DLROVER_CHAOS_KV_SPILL sets a DRAM
# budget).  The SparseStateAdapter registers the tables with the
# flash-checkpoint engine, so every save snapshots keys/values/freq +
# optimizer slots into the shm segment next to the dense state, and a
# restore imports them back bit-exact.  The batch at step k is a pure
# function of k, so :func:`sparse_reference_losses` recomputes the
# uninterrupted control in-process and the harness compares every
# reported loss against it — a restore that lost an embedding row,
# a frequency count or an Adam moment forks the trajectory at the
# first replayed step.  argv: ckpt_dir
SPARSE_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import (
    Checkpointer, StorageType, restore_to_template,
)
from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
from dlrover_tpu.trainer.sparse_pipeline import make_deepfm_device_step
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "12"))
CKPT_EVERY = int(os.environ.get("DLROVER_CHAOS_CKPT_EVERY", "2"))
DISK_EVERY = int(os.environ.get("DLROVER_CHAOS_DISK_EVERY", "0"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
KV_SPILL = int(os.environ.get("DLROVER_CHAOS_KV_SPILL", "0"))

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

# MUST mirror scenarios.sparse_reference_losses exactly
cfg = DeepFMConfig(num_sparse_fields=6, num_dense_features=4,
                   embedding_dim=8, hidden_dims=(16,), seed=5)
model = DeepFM(cfg)
if KV_SPILL:
    # node-local spill files next to (not inside) the shared ckpt dir;
    # O_TRUNC on re-open wipes a dead predecessor's file
    spill_dir = os.path.join(os.path.dirname(ckpt_dir), "kvspill")
    os.makedirs(spill_dir, exist_ok=True)
    model.table.enable_spill(
        os.path.join(spill_dir, "emb.spill"), KV_SPILL
    )
    model.sparse_optimizer.enable_spill(spill_dir, KV_SPILL)

dense_opt = optax.adam(1e-2)
adapter = SparseStateAdapter()
adapter.register_optimizer(model.sparse_optimizer)
ckpt = Checkpointer(ckpt_dir)
ckpt.register_sparse(adapter)

params = model.init_dense_params()
opt_state = dense_opt.init(params)
start_step, restored = ckpt.load_checkpoint()
if start_step is None:
    start_step = 0
else:
    # dense params AND optax state restored typed; the kv tables were
    # already imported by the engine through the adapter
    params, opt_state = restore_to_template(
        (params, opt_state), restored["dense"]
    )
state = (params, opt_state)
device_step = make_deepfm_device_step(model, dense_opt)

trainer = ElasticTrainer(global_batch_size=16, micro_batch_size=16,
                         dp_size=1)
trainer.global_step = start_step

def batch_for(k):
    rng = np.random.default_rng(10_000 + k)
    sparse = rng.integers(
        0, 4000, (16, cfg.num_sparse_fields)
    ).astype(np.int64)
    dense = rng.normal(
        size=(16, cfg.num_dense_features)
    ).astype(np.float32)
    labels = (sparse[:, 0] % 2).astype(np.float32)
    return sparse, dense, labels

for k in range(start_step, TOTAL_STEPS):
    sparse_ids, dense_x, labels = batch_for(k)
    with trainer.profile("h2d"):
        emb = jnp.asarray(model.gather_embeddings(sparse_ids))
        dx, lb = jnp.asarray(dense_x), jnp.asarray(labels)
    with trainer.profile("compute") as p:
        state, egrads, aux = device_step(state, emb, dx, lb)
        p.block(aux["loss"])
    # strict split step: the sparse update retires before the step is
    # reported, so a checkpoint taken after the report is exactly
    # step-consistent across dense AND host-table state
    model.apply_sparse_gradients(sparse_ids, np.asarray(egrads))
    trainer.report_step({"loss": float(aux["loss"])})
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)
    with trainer.profile("checkpoint"):
        sd = {"dense": state, "trainer": trainer.state_dict()}
        if DISK_EVERY and trainer.global_step % DISK_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step, sd,
                storage_type=StorageType.DISK,
            )
            ckpt.wait()
            deadline = time.time() + 30
            while (time.time() < deadline
                   and committed_step() < trainer.global_step):
                time.sleep(0.1)
        elif trainer.global_step % CKPT_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step, sd,
                storage_type=StorageType.MEMORY,
            )
            # accepted is not committed: a kill rule a step later
            # must find THIS step in shm
            ckpt.wait()

final_sd = {"dense": state, "trainer": trainer.state_dict()}
deadline = time.time() + 60
while time.time() < deadline and committed_step() < TOTAL_STEPS:
    ckpt.save_checkpoint(
        TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
    poll_end = time.time() + 10
    while time.time() < poll_end and committed_step() < TOTAL_STEPS:
        time.sleep(0.2)
assert committed_step() >= TOTAL_STEPS, (
    "checkpoint commit did not land"
)
ckpt.close()
'''


# Streaming-reshard kill loop (ISSUE 14): a WORLD-1 job whose
# checkpoint dir was PRE-SEEDED by the harness with a committed
# world-2 sparse checkpoint.  The very first restore is therefore a
# cross-world STREAMING reshard — `kv.reshard_chunk` fires once per
# window, and the scenario SIGKILLs the worker mid-stream.  Committed
# storage is untouched by the partial reshard (it only mutates
# in-process tables), so the replacement replays the identical
# reshard from the same shards and trains to completion; the
# exactly-once digests are checked against the seeder's JSON.
# argv: ckpt_dir
SPARSE_RESHARD_TRAIN_SCRIPT = r'''
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
from dlrover_tpu.ops.kv_variable import GroupAdamOptimizer, KvVariable
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "10"))
CKPT_EVERY = int(os.environ.get("DLROVER_CHAOS_CKPT_EVERY", "2"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
DIM = int(os.environ.get("DLROVER_CHAOS_RESHARD_KV_DIM", "16"))

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

table = KvVariable(dim=DIM, seed=17, name="emb")
kv_opt = GroupAdamOptimizer(table, learning_rate=5e-3)
adapter = SparseStateAdapter()
adapter.register_optimizer(kv_opt)
ckpt = Checkpointer(ckpt_dir)
ckpt.register_sparse(adapter)

# the seeded checkpoint is stamped world 2, this job is world 1: the
# load below IS the streaming reshard (kv.reshard_chunk per window —
# the kill rule lands here in incarnation 0, before any train step)
step0, restored = ckpt.load_checkpoint()
assert step0 is not None, "pre-seeded world-2 checkpoint missing"
start_step = int(step0)
w = jnp.asarray(np.asarray(restored["w"], dtype=np.float32))

trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                         dp_size=1)
trainer.global_step = start_step

for k in range(start_step, TOTAL_STEPS):
    krng = np.random.default_rng(5_000 + k)
    keys = krng.integers(0, 1_200, 64).astype(np.int64)
    with trainer.profile("h2d"):
        emb = table.gather(keys)
    with trainer.profile("compute") as p:
        kv_opt.apply_gradients(keys, np.tanh(emb) * 0.1)
        w = w * 0.9
        p.block(w)
    trainer.report_step({"loss": float(jnp.sum(w))})
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)
    with trainer.profile("checkpoint"):
        if trainer.global_step % CKPT_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step, {"w": np.asarray(w)},
                storage_type=StorageType.MEMORY,
            )

final_sd = {"w": np.asarray(w)}
deadline = time.time() + 60
while time.time() < deadline and committed_step() < TOTAL_STEPS:
    ckpt.save_checkpoint(
        TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
    poll_end = time.time() + 10
    while time.time() < poll_end and committed_step() < TOTAL_STEPS:
        time.sleep(0.2)
assert committed_step() >= TOTAL_STEPS, (
    "checkpoint commit did not land"
)
ckpt.close()
'''


def sparse_reference_losses(total_steps: int):
    """Uninterrupted-control loss trajectory of
    :data:`SPARSE_TRAIN_SCRIPT`, computed in-process: same DeepFM
    config/seeds, same step-indexed batches, same strict split-step
    order.  ``result[k-1]`` is the loss step ``k`` must report
    regardless of kills and flash restores — a restore that dropped
    an embedding row, a frequency count, an optimizer slot or the
    Adam step counter forks the trajectory at the first replayed
    step."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
    from dlrover_tpu.trainer.sparse_pipeline import (
        make_deepfm_device_step,
    )

    cfg = DeepFMConfig(num_sparse_fields=6, num_dense_features=4,
                       embedding_dim=8, hidden_dims=(16,), seed=5)
    model = DeepFM(cfg)
    dense_opt = optax.adam(1e-2)
    params = model.init_dense_params()
    state = (params, dense_opt.init(params))
    device_step = make_deepfm_device_step(model, dense_opt)
    out = []
    for k in range(total_steps):
        rng = np.random.default_rng(10_000 + k)
        sparse = rng.integers(
            0, 4000, (16, cfg.num_sparse_fields)
        ).astype(np.int64)
        dense = rng.normal(
            size=(16, cfg.num_dense_features)
        ).astype(np.float32)
        labels = (sparse[:, 0] % 2).astype(np.float32)
        emb = jnp.asarray(model.gather_embeddings(sparse))
        state, egrads, aux = device_step(
            state, emb, jnp.asarray(dense), jnp.asarray(labels)
        )
        model.apply_sparse_gradients(sparse, np.asarray(egrads))
        out.append(float(aux["loss"]))
    return out


# Elastic PPO loop (ISSUE 16): the four-role RL engine driven by
# master-dispatched ROLLOUT LEASES.  Each shard task is one rollout:
# prompts and the generation RNG derive purely from the lease id, so
# a lease requeued off a SIGKILLed worker regenerates bit-identically
# on the replacement — exactly-once rollout accounting from
# shard_dispatch/shard_ack events.  The full four-role state (actor +
# critic train states, RNG key, iteration cursor, the PARTIAL rollout
# buffer) rides every flash snapshot through PPOStateAdapter; the
# snapshot is taken after every completed lease and NEVER after a
# train phase, so a mid-iteration kill restores to the last completed
# lease and REPLAYS that iteration's train steps — the replayed
# train_step losses are the loss-trajectory invariant's
# multi-incarnation cross-check.  One PPO train step per lease
# (LEASES_PER_ITER leases buffered, then that many in-order PPO
# updates), so total train steps == total leases == TOTAL_STEPS.
# argv: ckpt_dir
RL_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu import chaos as _chaos
from dlrover_tpu.accel import Strategy
from dlrover_tpu.agent.sharding_client import ShardingClient
from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.rl.elastic import (
    PPOCursor, PPOStateAdapter, lease_prompts, lease_rng,
    resolve_role_steps,
)
from dlrover_tpu.rl.model_engine import ModelRole, RLModelEngine, RoleSpec
from dlrover_tpu.rl.rollout import (
    make_actor_loss, make_critic_loss, make_experience,
    sample_rollout_batch, train_on_batch,
)
from dlrover_tpu.rl.trainer import ReplayBuffer
from dlrover_tpu.telemetry.events import emit_event
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "8"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
LEASES_PER_ITER = int(
    os.environ.get("DLROVER_CHAOS_RL_LEASES_PER_ITER", "2")
)
RESTART_COUNT = int(os.environ.get("DLROVER_RESTART_COUNT", "0") or 0)
NODE_RANK = int(os.environ.get("DLROVER_NODE_RANK", "0") or 0)

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

# MUST mirror scenarios.rl_reference_losses exactly.  B=8 divides
# the data-axis of any test mesh (1 or 8 host devices)
B, PROMPT_LEN, MAX_NEW, VOCAB, SEED = 8, 4, 8, 32, 2
actor_cfg = GPTConfig.tiny(max_seq_len=16, vocab_size=VOCAB)
actor_model = GPT(actor_cfg)
critic_model = GPT(
    GPTConfig.tiny(max_seq_len=16, vocab_size=VOCAB, head="value")
)
ref_model = GPT(actor_cfg)
ref_params = actor_model.init_params(jax.random.PRNGKey(1))
sample = sample_rollout_batch(
    jnp.zeros((B, PROMPT_LEN), jnp.int32), MAX_NEW
)
dp = Strategy(opts=[("parallel_mode", {})])
engine = RLModelEngine(sample, {
    ModelRole.ACTOR: RoleSpec(
        model=actor_model,
        loss_fn=make_actor_loss(actor_model, PROMPT_LEN),
        optim_factory=lambda: optax.adam(5e-3),
        strategy=dp,
    ),
    ModelRole.CRITIC: RoleSpec(
        model=critic_model,
        loss_fn=make_critic_loss(critic_model, PROMPT_LEN),
        optim_factory=lambda: optax.adam(1e-3),
        strategy=dp,
    ),
    ModelRole.REF: RoleSpec(model=ref_model, params=ref_params),
}).build()

def reward_fn(sequences):
    resp = sequences[:, PROMPT_LEN:]
    return (resp < 16).mean(axis=1).astype(jnp.float32)

# register the PPO adapter BEFORE the load: the import needs the
# engine's fresh states as restore templates
buffer = ReplayBuffer()
cursor = PPOCursor(rng_key=np.asarray(jax.random.PRNGKey(SEED)))
adapter = PPOStateAdapter(engine, buffer, cursor)
ckpt = Checkpointer(ckpt_dir)
ckpt.register_sparse(adapter)
start_step, restored = ckpt.load_checkpoint()
# roles/buffer/cursor were rebuilt by the adapter during the load;
# the dense subtree only carried the trainer bookkeeping

trainer = ElasticTrainer(global_batch_size=B, micro_batch_size=B,
                         dp_size=1)
trainer.global_step = cursor.ppo_updates

# AOT-cached actor/critic steps: a respawn deserializes the compiled
# steps the first incarnation wrote — retrace-free RL recovery
steps = {
    role: res.fn
    for role, res in resolve_role_steps(engine, sample).items()
}

sc = ShardingClient(
    dataset_name="rl-rollouts", batch_size=1, num_epochs=1,
    dataset_size=TOTAL_STEPS, shuffle=False,
    num_minibatches_per_shard=1, storage_type="table",
)

phase_s = {"rollout": 0.0, "score": 0.0, "gae": 0.0}

def train_phase():
    # in INSERTION order, never shuffled: a restored incarnation
    # replays byte-identical PPO steps off the restored buffer
    t0 = time.perf_counter()
    batches = buffer.batches()
    actor_loss = critic_loss = 0.0
    for bt in batches:
        with trainer.profile("compute"):
            losses = train_on_batch(engine, bt, steps=steps)
        actor_loss = losses["actor_loss"]
        critic_loss = losses["critic_loss"]
        trainer.report_step(
            {"loss": losses["actor_loss"] + losses["critic_loss"]}
        )
    cursor.ppo_updates = trainer.global_step
    emit_event(
        "rl_iteration",
        iteration=trainer.global_step // max(1, LEASES_PER_ITER),
        restart_count=RESTART_COUNT, node_rank=NODE_RANK,
        leases=len(batches),
        rollout_s=round(phase_s["rollout"], 4),
        score_s=round(phase_s["score"], 4),
        gae_s=round(phase_s["gae"], 4),
        train_s=round(time.perf_counter() - t0, 4),
        actor_loss=actor_loss, critic_loss=critic_loss,
    )
    phase_s.update(rollout=0.0, score=0.0, gae=0.0)
    buffer.reset()

while True:
    if len(buffer.batches()) >= LEASES_PER_ITER:
        train_phase()
    with trainer.profile("data_wait"):
        task = sc.fetch_task()
    if task is None:
        break
    lease_id = int(task.start)
    if lease_id < cursor.leases_done:
        # the checkpointed predecessor already buffered (or trained
        # on) this lease before dying un-acked: ack WITHOUT
        # regenerating, or the batch would enter the buffer twice
        sc.report_task_done(task.task_id)
        continue
    with trainer.profile("rollout"):
        batch, metrics = make_experience(
            engine, jnp.asarray(
                lease_prompts(lease_id, B, PROMPT_LEN, VOCAB)
            ),
            lease_rng(SEED, lease_id), max_new_tokens=MAX_NEW,
            kl_coef=0.01, reward_fn=reward_fn,
        )
    for k in ("rollout", "score", "gae"):
        phase_s[k] += metrics[k + "_s"]
    # the kill rule lands HERE: batch generated but neither buffered,
    # checkpointed nor acked — the master requeues the lease and the
    # replacement regenerates it bit-identically
    _chaos.fire("rl.rollout", step=lease_id)
    buffer.add(batch)
    cursor.leases_done = lease_id + 1
    # flash snapshot after EVERY completed lease and never after a
    # train phase: a mid-iteration kill restores to the last lease
    # and REPLAYS the iteration's train steps (the loss-trajectory
    # invariant's multi-incarnation cross-check needs those replays)
    with trainer.profile("checkpoint"):
        ckpt.save_checkpoint(
            trainer.global_step, {"trainer": trainer.state_dict()},
            storage_type=StorageType.MEMORY,
        )
    sc.report_task_done(task.task_id)
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)

if buffer.batches():
    train_phase()

FINAL_STEP = trainer.global_step
final_sd = {"trainer": trainer.state_dict()}
deadline = time.time() + 60
while time.time() < deadline and committed_step() < FINAL_STEP:
    ckpt.save_checkpoint(
        FINAL_STEP, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
    poll_end = time.time() + 10
    while time.time() < poll_end and committed_step() < FINAL_STEP:
        time.sleep(0.2)
assert committed_step() >= FINAL_STEP, (
    "checkpoint commit did not land"
)
ckpt.close()
'''


def rl_reference_losses(total_steps: int):
    """Uninterrupted-control loss trajectory of
    :data:`RL_TRAIN_SCRIPT`, computed in-process: same four-role
    engine recipe, same lease-derived prompts/RNG, same
    buffer-then-train iteration structure.  ``result[k-1]`` is the
    combined actor+critic loss PPO train step ``k`` must report
    regardless of kills and flash restores — a restore that dropped
    an optimizer slot, a buffered rollout batch or the cursor forks
    the trajectory at the first replayed step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.accel import Strategy
    from dlrover_tpu.models.gpt import GPT, GPTConfig
    from dlrover_tpu.rl.elastic import lease_prompts, lease_rng
    from dlrover_tpu.rl.model_engine import (
        ModelRole,
        RLModelEngine,
        RoleSpec,
    )
    from dlrover_tpu.rl.rollout import (
        make_actor_loss,
        make_critic_loss,
        make_experience,
        sample_rollout_batch,
        train_on_batch,
    )
    from dlrover_tpu.rl.trainer import ReplayBuffer

    b, prompt_len, max_new, vocab, seed = 8, 4, 8, 32, 2
    leases_per_iter = 2
    actor_cfg = GPTConfig.tiny(max_seq_len=16, vocab_size=vocab)
    actor_model = GPT(actor_cfg)
    critic_model = GPT(
        GPTConfig.tiny(max_seq_len=16, vocab_size=vocab,
                       head="value")
    )
    ref_model = GPT(actor_cfg)
    ref_params = actor_model.init_params(jax.random.PRNGKey(1))
    sample = sample_rollout_batch(
        jnp.zeros((b, prompt_len), jnp.int32), max_new
    )
    dp = Strategy(opts=[("parallel_mode", {})])
    engine = RLModelEngine(sample, {
        ModelRole.ACTOR: RoleSpec(
            model=actor_model,
            loss_fn=make_actor_loss(actor_model, prompt_len),
            optim_factory=lambda: optax.adam(5e-3),
            strategy=dp,
        ),
        ModelRole.CRITIC: RoleSpec(
            model=critic_model,
            loss_fn=make_critic_loss(critic_model, prompt_len),
            optim_factory=lambda: optax.adam(1e-3),
            strategy=dp,
        ),
        ModelRole.REF: RoleSpec(model=ref_model, params=ref_params),
    }).build()

    def reward_fn(sequences):
        resp = sequences[:, prompt_len:]
        return (resp < 16).mean(axis=1).astype(jnp.float32)

    buffer = ReplayBuffer()
    out = []
    for lease_id in range(total_steps):
        batch, _metrics = make_experience(
            engine, jnp.asarray(
                lease_prompts(lease_id, b, prompt_len, vocab)
            ),
            lease_rng(seed, lease_id), max_new_tokens=max_new,
            kl_coef=0.01, reward_fn=reward_fn,
        )
        buffer.add(batch)
        if len(buffer.batches()) >= leases_per_iter:
            for bt in buffer.batches():
                losses = train_on_batch(engine, bt)
                out.append(
                    losses["actor_loss"] + losses["critic_loss"]
                )
            buffer.reset()
    for bt in buffer.batches():
        losses = train_on_batch(engine, bt)
        out.append(losses["actor_loss"] + losses["critic_loss"])
    return out


# Train-to-serve loop: the sparse DeepFM loop PLUS an
# EmbeddingPublisher shipping the embedding table to a serving
# replica as committed base/delta generations every
# DLROVER_CHAOS_PUB_EVERY steps.  A fresh incarnation's publisher
# always opens with a base at a NEW generation (it cannot know what a
# dead predecessor half-published), which is what makes the
# trainer-kill-mid-publish scenario's recovery exactly-once by
# construction.  argv: ckpt_dir; serving dir from
# DLROVER_SERVING_DIR (harness) or <workdir>/serving.
SPARSE_SERVING_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import (
    Checkpointer, StorageType, restore_to_template,
)
from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
from dlrover_tpu.serving import EmbeddingPublisher
from dlrover_tpu.trainer.sparse_pipeline import make_deepfm_device_step
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "12"))
CKPT_EVERY = int(os.environ.get("DLROVER_CHAOS_CKPT_EVERY", "2"))
PUB_EVERY = int(os.environ.get("DLROVER_CHAOS_PUB_EVERY", "2"))
COMPACT_EVERY = int(os.environ.get("DLROVER_CHAOS_COMPACT_EVERY", "4"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
serving_dir = os.environ.get("DLROVER_SERVING_DIR") or os.path.join(
    os.path.dirname(ckpt_dir), "serving"
)

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

# MUST mirror scenarios.sparse_reference_losses exactly
cfg = DeepFMConfig(num_sparse_fields=6, num_dense_features=4,
                   embedding_dim=8, hidden_dims=(16,), seed=5)
model = DeepFM(cfg)

dense_opt = optax.adam(1e-2)
adapter = SparseStateAdapter()
adapter.register_optimizer(model.sparse_optimizer)
ckpt = Checkpointer(ckpt_dir)
ckpt.register_sparse(adapter)

# serving publishes ONLY the embedding table (replicas have no use
# for optimizer moments); its own adapter shares the table object, so
# dirty tracking is one truth for both planes
serving_adapter = SparseStateAdapter().register_table(model.table)
publisher = EmbeddingPublisher(
    serving_adapter, serving_dir, compact_every=COMPACT_EVERY,
)

params = model.init_dense_params()
opt_state = dense_opt.init(params)
start_step, restored = ckpt.load_checkpoint()
if start_step is None:
    start_step = 0
else:
    params, opt_state = restore_to_template(
        (params, opt_state), restored["dense"]
    )
state = (params, opt_state)
device_step = make_deepfm_device_step(model, dense_opt)

trainer = ElasticTrainer(global_batch_size=16, micro_batch_size=16,
                         dp_size=1)
trainer.global_step = start_step

def batch_for(k):
    rng = np.random.default_rng(10_000 + k)
    sparse = rng.integers(
        0, 4000, (16, cfg.num_sparse_fields)
    ).astype(np.int64)
    dense = rng.normal(
        size=(16, cfg.num_dense_features)
    ).astype(np.float32)
    labels = (sparse[:, 0] % 2).astype(np.float32)
    return sparse, dense, labels

for k in range(start_step, TOTAL_STEPS):
    sparse_ids, dense_x, labels = batch_for(k)
    with trainer.profile("h2d"):
        emb = jnp.asarray(model.gather_embeddings(sparse_ids))
        dx, lb = jnp.asarray(dense_x), jnp.asarray(labels)
    with trainer.profile("compute") as p:
        state, egrads, aux = device_step(state, emb, dx, lb)
        p.block(aux["loss"])
    model.apply_sparse_gradients(sparse_ids, np.asarray(egrads))
    trainer.report_step({"loss": float(aux["loss"])})
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)
    with trainer.profile("checkpoint"):
        if trainer.global_step % CKPT_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step,
                {"dense": state, "trainer": trainer.state_dict()},
                storage_type=StorageType.MEMORY,
            )
            # accepted is not committed: a kill rule a step later
            # must find THIS step in shm
            ckpt.wait()
    if trainer.global_step % PUB_EVERY == 0:
        publisher.publish(step=trainer.global_step)

# final publish so the replica can converge on the last trained state
if publisher.generation == 0 or TOTAL_STEPS % PUB_EVERY != 0:
    publisher.publish(step=TOTAL_STEPS)

final_sd = {"dense": state, "trainer": trainer.state_dict()}
deadline = time.time() + 60
while time.time() < deadline and committed_step() < TOTAL_STEPS:
    ckpt.save_checkpoint(
        TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
    poll_end = time.time() + 10
    while time.time() < poll_end and committed_step() < TOTAL_STEPS:
        time.sleep(0.2)
assert committed_step() >= TOTAL_STEPS, (
    "checkpoint commit did not land"
)
ckpt.close()
'''


# Sparse elastic world-resize loop: RESIZE_TRAIN_SCRIPT's GSPMD dense
# leg (lockstep collectives, loss == the uninterrupted control at any
# world size) PLUS a KvVariable embedding partitioned across the
# world by the SAME key hash the cross-world reshard uses
# (checkpoint.sparse.owner_of_keys) — so a 2->1->2 churn genuinely
# redistributes hash-table rows from committed storage, exactly once,
# provable from the kv_checkpoint digests.  argv: ckpt_dir (SHARED).
SPARSE_RESIZE_TRAIN_SCRIPT = r'''
import os, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.checkpoint.sparse import (
    SparseStateAdapter, owner_of_keys,
)
from dlrover_tpu.ops.kv_variable import GroupAdamOptimizer, KvVariable
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, init_jax_distributed,
)

ckpt_dir = sys.argv[1]
TOTAL_STEPS = int(os.environ.get("DLROVER_CHAOS_TOTAL_STEPS", "24"))
DISK_EVERY = int(os.environ.get("DLROVER_CHAOS_DISK_EVERY", "3"))
STEP_SLEEP = float(os.environ.get("DLROVER_CHAOS_STEP_SLEEP", "0"))
DIM = int(os.environ.get("DLROVER_CHAOS_RESIZE_DIM", "64"))

WORLD = int(os.environ.get("DLROVER_WORLD_SIZE", "1") or 1)
RANK = int(os.environ.get("DLROVER_RANK", "0") or 0)

init_jax_distributed()
devs = jax.devices()
mesh = Mesh(np.array(devs), ("fsdp",))
shard = NamedSharding(mesh, P("fsdp"))

tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")

def committed_step():
    try:
        with open(tracker) as f:
            return int(f.read().strip() or -1)
    except (OSError, ValueError):
        return -1

def make_sharded(global_np):
    arrs = [
        jax.device_put(np.ascontiguousarray(global_np[index]), d)
        for d, index in shard.addressable_devices_indices_map(
            global_np.shape
        ).items()
    ]
    return jax.make_array_from_single_device_arrays(
        global_np.shape, shard, arrs
    )

# host-table sparse state, hash-partitioned across the world: this
# rank's table holds ONLY the keys owner_of_keys assigns it, so each
# rank's checkpoint shard is a distinct slice of the logical table
# and a world change must genuinely redistribute rows
table = KvVariable(dim=8, seed=17, name="emb")
kv_opt = GroupAdamOptimizer(table, learning_rate=5e-3)
adapter = SparseStateAdapter()
adapter.register_optimizer(kv_opt)

template = make_sharded(np.zeros((DIM, 8), np.float32))
ckpt = Checkpointer(ckpt_dir, replicated=False)
ckpt.register_sparse(adapter)
# cross-world restores refuse the shm tier and reshard BOTH the dense
# GSPMD shards and the kv rows from committed storage
step0, restored = ckpt.load_checkpoint(target_state={"w": template})
if step0 is None:
    start_step, w = 0, template
else:
    start_step, w = int(step0), restored["w"]

# dense leg MUST mirror scenarios.resize_reference_losses exactly
@jax.jit
def step_fn(w, k):
    x = jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(1000), k),
        (8,), jnp.float32,
    )
    def loss_fn(w):
        return ((w @ x - 1.0) ** 2).mean()
    loss, g = jax.value_and_grad(loss_fn)(w)
    return w - 0.1 * g, loss

trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                         dp_size=1)
trainer.global_step = start_step

for k in range(start_step, TOTAL_STEPS):
    # sparse leg: a step-indexed global key stream, routed to this
    # rank by the same owner hash the reshard partitions with; the
    # per-row update depends only on the row's own state, so row
    # trajectories are world-size-independent
    krng = np.random.default_rng(5_000 + k)
    gkeys = krng.integers(0, 3_000, 48).astype(np.int64)
    mine = gkeys[owner_of_keys(gkeys, WORLD) == RANK]
    if mine.size:
        emb = table.gather(mine)
        kv_opt.apply_gradients(mine, np.tanh(emb) * 0.1)
    with trainer.profile("compute") as p:
        w, loss = step_fn(w, k + 1)
        p.block(loss)
    trainer.report_step({"loss": float(loss)})
    if STEP_SLEEP:
        time.sleep(STEP_SLEEP)
    with trainer.profile("checkpoint"):
        if DISK_EVERY and trainer.global_step % DISK_EVERY == 0:
            ckpt.save_checkpoint(
                trainer.global_step, {"w": w},
                storage_type=StorageType.DISK,
            )
            ckpt.wait()
            deadline = time.time() + 30
            while (time.time() < deadline
                   and committed_step() < trainer.global_step):
                time.sleep(0.1)
        else:
            ckpt.save_checkpoint(
                trainer.global_step, {"w": w},
                storage_type=StorageType.MEMORY,
            )
            # accepted is not committed: a kill rule a step later
            # must find THIS step in shm
            ckpt.wait()

final_sd = {"w": w}
if RANK == 0:
    deadline = time.time() + 60
    while time.time() < deadline and committed_step() < TOTAL_STEPS:
        ckpt.save_checkpoint(
            TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
        )
        ckpt.wait()
        poll_end = time.time() + 10
        while time.time() < poll_end and committed_step() < TOTAL_STEPS:
            time.sleep(0.2)
    assert committed_step() >= TOTAL_STEPS, (
        "checkpoint commit did not land"
    )
else:
    ckpt.save_checkpoint(
        TOTAL_STEPS, final_sd, storage_type=StorageType.DISK,
    )
    ckpt.wait()
ckpt.close()
'''


def resize_reference_losses(total_steps: int, dim: int = 64):
    """Uninterrupted-control loss trajectory of
    :data:`RESIZE_TRAIN_SCRIPT`'s update rule, computed single-device
    in-process.  ``result[k-1]`` is the loss the job must report at
    step ``k`` regardless of world size, restarts, or resharded
    restores — the batch derivation and update MUST stay in lockstep
    with the script's ``step_fn``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step_fn(w, k):
        x = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(1000), k),
            (8,), jnp.float32,
        )

        def loss_fn(w):
            return ((w @ x - 1.0) ** 2).mean()

        loss, g = jax.value_and_grad(loss_fn)(w)
        return w - 0.1 * g, loss

    w = jnp.zeros((dim, 8), jnp.float32)
    out = []
    for k in range(1, total_steps + 1):
        w, loss = step_fn(w, k)
        out.append(float(loss))
    return out


def kill_worker_midstep(seed: int = 42) -> Scenario:
    """THE acceptance scenario: SIGKILL the worker at a seed-chosen
    step mid-run.  The agent's monitor loop observes the death,
    persists the shm snapshot, re-rendezvouses and respawns; the
    recovered incarnation must lose at most one checkpoint interval."""
    return Scenario.from_dict({
        "name": "kill-worker-midstep",
        "seed": seed,
        "rules": [{
            "name": "kill-midstep",
            "point": "trainer.step",
            "action": "kill",
            "step_window": [4, 7],
            "only_first_incarnation": True,
        }],
    })


def sigterm_worker_midstep(seed: int = 42) -> Scenario:
    """Graceful-eviction flavour of the kill scenario (SIGTERM)."""
    return Scenario.from_dict({
        "name": "sigterm-worker-midstep",
        "seed": seed,
        "rules": [{
            "name": "term-midstep",
            "point": "trainer.step",
            "action": "kill",
            "step_window": [4, 7],
            "only_first_incarnation": True,
            "args": {"signal": "TERM"},
        }],
    })


def rpc_partition(seed: int = 7) -> Scenario:
    """Drop every master RPC for a 2 s window early in the run: the
    client's jittered-backoff reconnect path must ride it out with no
    job impact beyond latency."""
    return Scenario.from_dict({
        "name": "rpc-partition",
        "seed": seed,
        "rules": [{
            "name": "partition",
            "point": "rpc.client.roundtrip",
            "action": "drop",
            "after_time": 1.0,
            "duration": 2.0,
        }],
    })


def storage_brownout(seed: int = 11) -> Scenario:
    """Every storage write fails for the first few persist attempts,
    then the backend 'recovers': persistence must degrade to a
    reported failure (telemetry event, error counter) and the next
    interval's save must still commit."""
    return Scenario.from_dict({
        "name": "storage-brownout",
        "seed": seed,
        "rules": [{
            "name": "flaky-writes",
            "point": "storage.write",
            "action": "io_error",
            "max_count": 3,
        }],
    })


def storage_stall(seed: int = 13) -> Scenario:
    """One slow (hung-NFS-style) storage write mid-run."""
    return Scenario.from_dict({
        "name": "storage-stall",
        "seed": seed,
        "rules": [{
            "name": "stalled-write",
            "point": "storage.write",
            "action": "stall",
            "after_calls": 2,
            "max_count": 1,
            "args": {"seconds": 1.0},
        }],
    })


def straggler(seed: int = 5) -> Scenario:
    """Seeded-probabilistic slow steps: the per-node step-time
    distribution degrades and the diagnosis chain's straggler rule has
    something real to catch in multi-node runs."""
    return Scenario.from_dict({
        "name": "straggler",
        "seed": seed,
        "rules": [{
            "name": "slow-steps",
            "point": "trainer.step",
            "action": "slow",
            "prob": 0.5,
            "max_count": 5,
            "args": {"seconds": 0.3},
        }],
    })


def preemption_notice(seed: int = 3) -> Scenario:
    """Simulated ~30s-warning spot preemption: the monitor's probe
    reads TRUE, the agent reports to the master and breakpoint-saves
    the shm snapshot while the 'VM' is still alive."""
    return Scenario.from_dict({
        "name": "preemption-notice",
        "seed": seed,
        "rules": [{
            "name": "notice",
            "point": "preemption.probe",
            "action": "preempt",
            "after_time": 2.0,
        }],
    })


def shm_corrupt_storage_fallback(seed: int = 23) -> Scenario:
    """Tier-fallback acceptance: tear the shm snapshot at a MEMORY
    save, then kill the worker one step later.  The respawned trainer
    must refuse the torn shm tier and restore from the last committed
    storage step (the harness runs this with ``disk_every=4`` so one
    exists) — asserted by the ``RestoredFromTier`` invariant reading
    the ``checkpoint_restore`` event's ``tier`` field."""
    return Scenario.from_dict({
        "name": "shm-corrupt-storage-fallback",
        "seed": seed,
        "rules": [
            {
                "name": "torn-snapshot",
                "point": "ckpt.shm_save",
                "action": "corrupt_shm",
                "at_step": 6,
                "only_first_incarnation": True,
                "args": {"mode": "torn"},
            },
            {
                "name": "kill-after-tear",
                "point": "trainer.step",
                "action": "kill",
                "at_step": 7,
                "only_first_incarnation": True,
            },
        ],
    })


def kill_between_accept_and_commit(seed: int = 29) -> Scenario:
    """A save's ``True`` means accepted, not committed: SIGKILL the
    worker on its writer thread inside the copy of the step-6 MEMORY
    save — after the call returned and the loop went on, for this
    scenario's train script has no ``ckpt.wait()`` behind a MEMORY
    save.  The segment's meta says ``writing`` over half of step 6,
    and step 4's snapshot is gone with it: the respawned trainer must
    refuse the shm tier and restore the committed DISK step 4 from
    storage (``disk_every=4``), as after a kill inside a synchronous
    copy."""
    return Scenario.from_dict({
        "name": "kill-between-accept-and-commit",
        "seed": seed,
        "rules": [{
            "name": "kill-mid-write",
            "point": "ckpt.shm_write",
            "action": "kill",
            "at_step": 6,
            "only_first_incarnation": True,
        }],
    })


def ckpt_brownout_during_preemption(seed: int = 19) -> Scenario:
    """ROADMAP scenario: a storage brownout lands exactly while a
    preemption notice's grace-period breakpoint save is trying to
    persist — the two grace paths compete for the persist executor.
    The job must ride it out: the failed persist is REPORTED
    (``checkpoint_persist`` ok=false event + error counter), later
    saves commit, training completes, nothing deadlocks.  Wall-clock
    triggered (the notice is a timer by nature), so the timeline is
    bounded, not byte-stable; the harness stretches the toy loop with
    ``step_sleep`` so the window lands mid-run."""
    return Scenario.from_dict({
        "name": "ckpt-brownout-during-preemption",
        "seed": seed,
        "rules": [
            {
                "name": "notice",
                "point": "preemption.probe",
                "action": "preempt",
                "after_time": 5.0,
            },
            {
                # exactly one injected failure, on the FIRST storage
                # write of the job — MEMORY saves never touch storage,
                # so that write is a grace-path persist (the notice's
                # breakpoint save when the snapshot beat the notice,
                # else the final commit's first round, which the toy
                # loop re-issues) — then the fault is spent so the
                # retried commit goes through
                "name": "brownout",
                "point": "storage.write",
                "action": "io_error",
                "max_count": 1,
            },
        ],
    })


def master_kill_restart_midround(seed: int = 31) -> Scenario:
    """Master crash recovery acceptance (ISSUE 4): SIGKILL the MASTER
    on its 3rd shard dispatch — mid-rendezvous-round, with one shard
    journaled-but-undelivered and acks in flight.  tpurun's watchdog
    respawns it on the same port; the new incarnation replays the
    state journal (re-entering rendezvous round 1, re-queueing only
    the un-acked shard), parked agents/trainers session-resync, and
    training completes with no shard lost, none acked twice, and NO
    healthy-worker restart — all decided from telemetry events."""
    return Scenario.from_dict({
        "name": "master-kill-restart-midround",
        "seed": seed,
        "rules": [{
            "name": "kill-master-middispatch",
            "point": "master.task_dispatch",
            "action": "kill",
            "after_calls": 3,
            # the respawned master (DLROVER_RESTART_COUNT=1) must
            # survive replaying the very dispatch that killed its
            # predecessor
            "only_first_incarnation": True,
        }],
    })


def multinode_rpc_partition(seed: int = 29) -> Scenario:
    """Partition a SUBSET of the job: drop every master RPC of node
    rank 1 (its agent AND its trainer) for a 3 s window while rank 0
    is untouched.  The un-partitioned node must keep training and the
    partitioned one must ride out the window on the reconnect path
    and rejoin WITHOUT a full-job restart (run via the multi-agent
    harness, ``run_scenario_multinode``)."""
    return Scenario.from_dict({
        "name": "multinode-rpc-partition",
        "seed": seed,
        "rules": [{
            "name": "partition-rank1",
            "point": "rpc.client.roundtrip",
            "action": "drop",
            "after_time": 2.0,
            "duration": 3.0,
            "env_equals": {"DLROVER_NODE_RANK": "1"},
        }],
    })


def warm_template_import_kill(seed: int = 37) -> Scenario:
    """Warm-restart chaos: SIGKILL the forkserver template DURING its
    heavy preload imports — generation 1 and its rebuild both die, so
    the agent's spawn must detect the dead template immediately and
    fall back to cold spawns with no orphan processes."""
    return Scenario.from_dict({
        "name": "warm-template-import-kill",
        "seed": seed,
        "rules": [
            {
                "name": "kill-template-import-gen1",
                "point": "forkserver.template_import",
                "action": "kill",
                "after_calls": 2,
                "env_equals": {"DLROVER_FORKSERVER_GENERATION": "1"},
            },
            {
                # the rebuilt template dies the same way: the agent
                # must give up on warm forks for the round instead of
                # rebuilding forever
                "name": "kill-template-import-gen2",
                "point": "forkserver.template_import",
                "action": "kill",
                "after_calls": 2,
                "env_equals": {"DLROVER_FORKSERVER_GENERATION": "2"},
            },
        ],
    })


def warm_template_midspawn_kill(seed: int = 41) -> Scenario:
    """Warm-restart chaos: SIGKILL the template mid-spawn — the spawn
    request is consumed but no child is forked and no reply is coming,
    the hardest template loss to detect.  The agent must fall back to
    a cold spawn in milliseconds (dead-template check in the wait
    loop), leaving no orphans."""
    return Scenario.from_dict({
        "name": "warm-template-midspawn-kill",
        "seed": seed,
        "rules": [{
            "name": "kill-template-midspawn",
            "point": "forkserver.spawn",
            "action": "kill",
            "env_equals": {"DLROVER_FORKSERVER_GENERATION": "1"},
        }],
    })


def goodput_under_scheduled_churn(seed: int = 43) -> Scenario:
    """Goodput under churn as a seeded scenario: the worker is
    SIGKILLed at fixed absolute steps, one kill per incarnation (the
    ``incarnation`` trigger keeps a respawn replaying step N from
    being re-killed at N).  The invariant is on the master's own
    goodput accounting: ``dlrover_goodput_ratio`` ≥ 0.90, read from
    the ``master_exit`` event."""
    return Scenario.from_dict({
        "name": "goodput-under-scheduled-churn",
        "seed": seed,
        "rules": [
            {
                "name": "churn-kill-1",
                "point": "trainer.step",
                "action": "kill",
                "at_step": 7,
                "incarnation": 0,
            },
            {
                "name": "churn-kill-2",
                "point": "trainer.step",
                "action": "kill",
                "at_step": 14,
                "incarnation": 1,
            },
        ],
    })


def trainer_hang_detected(seed: int = 47) -> Scenario:
    """Deep-diagnosis acceptance (ISSUE 7): freeze one trainer
    mid-step with the stall primitive (a sleep in the report path —
    the process is alive, heartbeats flow, steps stop: exactly the
    silent-hang class that is indistinguishable from slowness without
    flight data).  The agent watchdog must capture stacks + /proc
    state and ship ``hang_evidence``; the master's inference chain
    must reach a *hung* verdict carrying that evidence and a measured
    stall, and restart ONLY the culprit node through the
    heartbeat-action relaunch path; the restored incarnation finishes
    the budget.  Thresholds are shrunk via RUN_OPTIONS env so the
    whole diagnosis plays out in seconds (tier-1)."""
    return Scenario.from_dict({
        "name": "trainer-hang-detected",
        "seed": seed,
        "rules": [{
            "name": "freeze-midstep",
            "point": "trainer.step",
            "action": "stall",
            "at_step": 5,
            "max_count": 1,
            "only_first_incarnation": True,
            # far beyond every diagnosis threshold: the sleep is
            # ended by the culprit restart's SIGTERM, never by the
            # timer — a diagnosis that fails leaves the job hung
            # until the harness timeout, not a silent pass
            "args": {"seconds": 90.0},
        }],
    })


def elastic_resize_churn(seed: int = 53) -> Scenario:
    """Elastic world-resize acceptance (ISSUE 8): a NODE LOSS — one of
    two agents dies with its whole worker tree (``kill_node``, no
    failure report, exactly like a vanished VM) — and the job survives
    by training SMALLER: the master's resize coordinator detects the
    silence, decides world 2 -> 1, drains the survivor over the
    heartbeat-action channel, and the re-formed world restores the
    checkpoint RESHARDED (node 1's storage shards redistributed onto
    node 0's devices).  The harness then respawns the lost agent (a
    replacement host: fresh shm namespace, ``DLROVER_AGENT_RESPAWNED``
    marks it so the kill rule never re-fires) and the job grows back
    to world 2 the same way.  Wall-clock triggered (the loss IS a
    timer event), so the timeline is bounded, not byte-stable."""
    return Scenario.from_dict({
        "name": "elastic-resize-churn",
        "seed": seed,
        "rules": [{
            "name": "node1-loss",
            "point": "agent.monitor",
            "action": "kill_node",
            "after_time": 8.0,
            "env_equals": {
                "DLROVER_NODE_RANK": "1",
                "DLROVER_AGENT_RESPAWNED": "",
            },
        }],
    })


def multinode_hang_culprit(seed: int = 59) -> Scenario:
    """Multinode hang diagnosis (ROADMAP carried-forward): freeze ONE
    node's trainer of a two-agent job mid-step while the other keeps
    stepping — the silence rule alone cannot convict (global progress
    continues), so the verdict must come from the culprit-selection
    evidence scoring over the agents' shipped flight data, and ONLY
    node 1 may be restarted."""
    return Scenario.from_dict({
        "name": "multinode-hang-culprit",
        "seed": seed,
        "rules": [{
            "name": "freeze-node1-midstep",
            "point": "trainer.step",
            "action": "stall",
            # early: node 1's whole recovery must finish while node 0
            # is STILL TRAINING — a peer that succeeds mid-recovery
            # leaves the liveness set and the in-place rejoin
            # (correctly) refuses a world with a departed member
            "at_step": 3,
            "max_count": 1,
            "only_first_incarnation": True,
            "env_equals": {"DLROVER_NODE_RANK": "1"},
            # ended by the culprit restart's SIGTERM, never the timer
            "args": {"seconds": 90.0},
        }],
    })


def sparse_kill_restore(seed: int = 61) -> Scenario:
    """Sparse elastic recovery acceptance (ISSUE 9): SIGKILL a DeepFM
    job mid-run — embedding table, frequency counters and GroupAdam
    slot tables (spill tier ACTIVE: the harness arms a DRAM budget so
    real rows live on the cold tier) must ride the flash checkpoint
    and come back bit-identical: the restored incarnation's loss
    trajectory equals the uninterrupted control, and the
    ``kv_checkpoint`` digests prove every row/freq/slot survived —
    all decided from telemetry events alone."""
    return Scenario.from_dict({
        "name": "sparse-kill-restore",
        "seed": seed,
        "rules": [{
            "name": "kill-sparse-midstep",
            "point": "trainer.step",
            "action": "kill",
            "step_window": [5, 7],
            "only_first_incarnation": True,
        }],
    })


def rl_rollout_worker_kill(seed: int = 97) -> Scenario:
    """Elastic RL acceptance (ISSUE 16): SIGKILL the rollout worker
    mid-PPO-iteration — on the ``rl.rollout`` hook of lease 2, after
    the batch is generated but BEFORE it is buffered, checkpointed or
    acked.  The master requeues the lease (journaled dispatch/ack);
    the replacement restores the four-role state + partial buffer +
    cursor from the flash checkpoint, REPLAYS the interrupted
    iteration's train steps, regenerates the lost lease
    bit-identically and finishes the budget.  Exactly-once rollout
    accounting, the loss trajectory equal to the uninterrupted
    control, and recovery-loss attribution are all decided from the
    event log alone."""
    return Scenario.from_dict({
        "name": "rl-rollout-worker-kill",
        "seed": seed,
        "rules": [{
            "name": "kill-rollout-midlease",
            "point": "rl.rollout",
            "action": "kill",
            # lease 2 = the first lease AFTER a train phase: the
            # restore must land on the post-lease-1 snapshot and
            # replay PPO steps 1-2 (multi-incarnation loss agreement)
            "at_step": 2,
            "only_first_incarnation": True,
        }],
    })


def sparse_spill_io_error(seed: int = 67) -> Scenario:
    """Graceful degradation (ISSUE 9): the spill tier's disk dies
    DURING a checkpoint export (io_error on the ``kv.spill`` hook).
    Stranded cold rows drop out of that export; training continues
    and the production write-failure breaker trips on the next spill
    pass (``spill_disabled`` on the following export event); the
    checkpoint of the DRAM-resident rows still commits, and after a
    kill two steps later the restore is valid — round-trip digests
    still match the (post-fault) export."""
    return Scenario.from_dict({
        "name": "sparse-spill-io-error",
        "seed": seed,
        "rules": [
            {
                "name": "spill-disk-dies",
                "point": "kv.spill",
                "action": "io_error",
                "at_step": 4,
                "max_count": 1,
                "only_first_incarnation": True,
            },
            {
                "name": "kill-after-breaker",
                "point": "trainer.step",
                "action": "kill",
                "at_step": 7,
                "only_first_incarnation": True,
            },
        ],
    })


def sparse_resize_churn(seed: int = 71) -> Scenario:
    """Sparse elastic world-resize (ISSUE 9 — the genuinely novel
    combination with PR 8's ResizeCoordinator): a node loss shrinks a
    two-node sparse job to one, and the hash-table embedding (plus
    its optimizer slot tables) is RESHARDED from committed storage —
    all old ranks' kv shards read, rows re-partitioned by key hash,
    the owned subset imported — then the world grows back and
    reshards again.  Exactly-once row accounting and the shm-tier
    refusal across world sizes are decided from the ``kv_checkpoint``
    events alone."""
    return Scenario.from_dict({
        "name": "sparse-resize-churn",
        "seed": seed,
        "rules": [{
            "name": "node1-loss",
            "point": "agent.monitor",
            "action": "kill_node",
            # progress-based, not wall-clock: the node dies only once
            # its trainer has REPORTED past step 6 (two world-2 disk
            # commits exist) — a slow jax/distributed startup cannot
            # turn the scenario into train-from-scratch at world 1
            "after_step": 6,
            "env_equals": {
                "DLROVER_NODE_RANK": "1",
                "DLROVER_AGENT_RESPAWNED": "",
            },
        }],
    })


def sparse_streaming_reshard_kill(seed: int = 79) -> Scenario:
    """Streaming-reshard crash consistency (ISSUE 14): the harness
    pre-seeds a committed world-2 sparse checkpoint, the world-1
    job's first restore streams the cross-world reshard in bounded
    windows, and the worker is SIGKILLed on the 3rd
    ``kv.reshard_chunk`` — mid-stream, tables half-imported.
    Committed storage is untouched (the reshard mutates only
    in-process tables), so the replacement replays the identical
    reshard from the same shards; the additive per-table digests on
    its resharded restore event must equal the seeder's per-shard
    export sums with imported rows == the distinct union — no row
    lost, no chunk double-imported."""
    return Scenario.from_dict({
        "name": "sparse-streaming-reshard-kill",
        "seed": seed,
        "rules": [{
            "name": "kill-mid-reshard",
            "point": "kv.reshard_chunk",
            "action": "kill",
            "after_calls": 3,
            "max_count": 1,
            "only_first_incarnation": True,
        }],
    })


def serving_replica_kill_midingest(seed: int = 83) -> Scenario:
    """Serving-plane replica recovery (ISSUE 13): SIGKILL the serving
    replica INSIDE a generation apply (the ``serving.ingest`` hook
    fires under the swap lock, tables half-applied).  The harness
    respawns it; the fresh replica re-ingests from the newest
    committed base and converges on the trainer's final generation.
    The digest chain on ``serving_ingest`` vs ``serving_publish``
    events proves no torn generation was ever served — the
    half-applied state died with the process and no event claimed
    it."""
    return Scenario.from_dict({
        "name": "serving-replica-kill-midingest",
        "seed": seed,
        "rules": [{
            "name": "kill-replica-midingest",
            "point": "serving.ingest",
            "action": "kill",
            "after_calls": 3,
            "max_count": 1,
            "env_equals": {
                "DLROVER_SERVING_ROLE": "replica",
                "DLROVER_SERVING_RESPAWNED": "",
            },
        }],
    })


def serving_fleet_replica_kill(seed: int = 97) -> Scenario:
    """Serving-fleet routing under fire (ISSUE 17): against a live
    replica POOL fronted by the lookup router, SIGKILL (a) replica 0
    INSIDE a generation apply (``serving.ingest``, env-pinned to
    ``DLROVER_SERVING_REPLICA_ID=0`` — role alone would kill every
    member) and (b) the ROUTER itself mid-stream (``serving.route``
    fires once per routed lookup).  The router must shed the dead
    replica within the heartbeat window and keep answering from the
    survivors — zero failed and zero stale lookups counted on the
    ``serving_route`` windows — and the respawned router must replay
    its journaled membership to the identical routing table and
    resume routing without restarting any healthy replica.  The
    ``DLROVER_SERVING_RESPAWNED`` guards keep both kills
    single-shot."""
    return Scenario.from_dict({
        "name": "serving-fleet-replica-kill",
        "seed": seed,
        "rules": [{
            "name": "kill-pool-replica-midingest",
            "point": "serving.ingest",
            "action": "kill",
            "after_calls": 3,
            "max_count": 1,
            "env_equals": {
                "DLROVER_SERVING_ROLE": "replica",
                "DLROVER_SERVING_REPLICA_ID": "0",
                "DLROVER_SERVING_RESPAWNED": "",
            },
        }, {
            # time-based, NOT call-count: the router kill must land
            # AFTER the killed replica has been shed and its respawn
            # re-admitted (simultaneous kills would leave no router
            # alive to witness the shed), and the route hook fires
            # continuously under load so the window is hit exactly
            "name": "kill-router-midroute",
            "point": "serving.route",
            "action": "kill",
            "after_time": 5.0,
            "max_count": 1,
            "env_equals": {
                "DLROVER_SERVING_ROLE": "router",
                "DLROVER_SERVING_RESPAWNED": "",
            },
        }],
    })


def serving_trainer_kill_midpublish(seed: int = 89) -> Scenario:
    """Serving-plane publisher exactly-once (ISSUE 13): SIGKILL the
    trainer between writing a generation's blobs/manifest and its
    ``DONE`` marker (the ``serving.publish`` hook sits exactly
    there).  The half-published generation is never committed — the
    replica keeps serving the previous one — and the respawned
    trainer's publisher opens with a fresh BASE at the next
    generation number: every committed generation is published
    exactly once, provable by counting ``serving_publish`` events."""
    return Scenario.from_dict({
        "name": "serving-trainer-kill-midpublish",
        "seed": seed,
        "rules": [{
            "name": "kill-trainer-midpublish",
            "point": "serving.publish",
            "action": "kill",
            "after_calls": 3,
            "max_count": 1,
            "only_first_incarnation": True,
        }],
    })


def warm_recovery_cache_hit(seed: int = 73) -> Scenario:
    """Invisible-recovery acceptance (ISSUE 10): SIGKILL the worker
    mid-run under warm restarts + the job-keyed persistent compile
    cache.  The replacement incarnation must prove — from the event
    log alone — that its re-trace HIT the cache the first incarnation
    populated (``compile_cache`` event, no new entries over a warm
    dir), that the measured ``retrace_s`` stayed under the ceiling,
    and that the whole death->first-step budget landed as
    ``recovery_phase`` slices on the assembled timeline."""
    return Scenario.from_dict({
        "name": "warm-recovery-cache-hit",
        "seed": seed,
        "rules": [{
            "name": "kill-midstep",
            "point": "trainer.step",
            "action": "kill",
            "step_window": [5, 6],
            "only_first_incarnation": True,
        }],
    })


def master_respawn_other_host(seed: int = 79) -> Scenario:
    """Host-portable control plane (ISSUE 10): SIGKILL the master
    mid-dispatch like ``master_kill_restart_midround`` — but the
    respawn gets a FRESH, EMPTY journal dir (what a replacement host
    has), so recovery must come entirely from the async-group-commit
    journal mirror on the checkpoint storage tier.  Exactly-once
    sharding and the final commit are still asserted from events;
    ``master_recovered.from_mirror`` is the witness that the mirror,
    not the local disk, carried the state."""
    return Scenario.from_dict({
        "name": "master-respawn-other-host",
        "seed": seed,
        "rules": [{
            "name": "kill-master-middispatch",
            "point": "master.task_dispatch",
            "action": "kill",
            "after_calls": 3,
            "only_first_incarnation": True,
        }],
    })


def shm_corruption(seed: int = 17) -> Scenario:
    """Tear one shm snapshot right after it is written (writing=True
    republish): the persist and restore paths must refuse the torn
    snapshot instead of committing garbage."""
    return Scenario.from_dict({
        "name": "shm-corruption",
        "seed": seed,
        "rules": [{
            "name": "torn-snapshot",
            "point": "ckpt.shm_save",
            "action": "corrupt_shm",
            "at_step": 4,
            "args": {"mode": "torn"},
        }],
    })


SCENARIOS: Dict[str, Callable[[int], Scenario]] = {
    "kill_worker_midstep": kill_worker_midstep,
    "sigterm_worker_midstep": sigterm_worker_midstep,
    "rpc_partition": rpc_partition,
    "storage_brownout": storage_brownout,
    "storage_stall": storage_stall,
    "straggler": straggler,
    "preemption_notice": preemption_notice,
    "shm_corruption": shm_corruption,
    "shm_corrupt_storage_fallback": shm_corrupt_storage_fallback,
    "kill_between_accept_and_commit": kill_between_accept_and_commit,
    "ckpt_brownout_during_preemption": ckpt_brownout_during_preemption,
    "master_kill_restart_midround": master_kill_restart_midround,
    "multinode_rpc_partition": multinode_rpc_partition,
    "warm_template_import_kill": warm_template_import_kill,
    "warm_template_midspawn_kill": warm_template_midspawn_kill,
    "goodput_under_scheduled_churn": goodput_under_scheduled_churn,
    "trainer_hang_detected": trainer_hang_detected,
    "elastic_resize_churn": elastic_resize_churn,
    "multinode_hang_culprit": multinode_hang_culprit,
    "sparse_kill_restore": sparse_kill_restore,
    "sparse_spill_io_error": sparse_spill_io_error,
    "sparse_resize_churn": sparse_resize_churn,
    "sparse_streaming_reshard_kill": sparse_streaming_reshard_kill,
    "serving_replica_kill_midingest": serving_replica_kill_midingest,
    "serving_fleet_replica_kill": serving_fleet_replica_kill,
    "serving_trainer_kill_midpublish": (
        serving_trainer_kill_midpublish
    ),
    "warm_recovery_cache_hit": warm_recovery_cache_hit,
    "master_respawn_other_host": master_respawn_other_host,
    "rl_rollout_worker_kill": rl_rollout_worker_kill,
}


# per-scenario harness knobs, keyed by the SCENARIO's name field, so
# the CLI and the tests drive each scenario the way it needs without
# repeating the recipe: the tier-fallback scenario needs a committed
# disk step to fall back to; the preemption scenarios need the
# monitor armed (a fast-failing metadata URL keeps the pre-notice
# probes cheap) and a stretched loop so the wall-clock window lands
# mid-run
RUN_OPTIONS: Dict[str, Dict] = {
    "shm-corrupt-storage-fallback": {"disk_every": 4},
    "kill-between-accept-and-commit": {
        "disk_every": 4, "train_script": "no_commit_wait",
    },
    "ckpt-brownout-during-preemption": {
        "step_sleep": 1.0,
        "extra_env": {
            "DLROVER_PREEMPTION_MONITOR": "1",
            "DLROVER_METADATA_SERVER": "http://127.0.0.1:9/preempted",
        },
    },
    "preemption-notice": {
        "extra_env": {
            "DLROVER_PREEMPTION_MONITOR": "1",
            "DLROVER_METADATA_SERVER": "http://127.0.0.1:9/preempted",
        },
    },
    # the master-recovery acceptance drives the sharding path (one
    # shard per step) so shard-loss/duplication is decidable from
    # telemetry; shard_dataset=True sizes the dataset to total_steps
    "master-kill-restart-midround": {"shard_dataset": True},
    # churn goodput: warm restarts keep recovery ~1 s (cold jax
    # imports would eat the goodput the scenario measures), a
    # stretched step makes productive time dominate, and a fast
    # monitor-report cadence gives the master's SpeedMonitor a real
    # gap distribution to book recovery losses against
    "goodput-under-scheduled-churn": {
        "warm_restart": True,
        "total_steps": 20,
        # per-step flash snapshot (the reference's headline feature):
        # a respawn resumes at the killed step with zero replay —
        # at ~10 ms per shm save it costs nothing and is exactly the
        # churn posture a production job would run
        "ckpt_every": 1,
        # ~1 s steps: the toy loop's step:recovery ratio should
        # resemble real training (seconds-long steps vs ~1-2 s warm
        # recovery), not a microbenchmark where restart cost dwarfs
        # the step time it protects
        "step_sleep": 1.0,
        "extra_env": {
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            # preload the framework modules the train script needs —
            # a respawn then pays fork+restore+retrace only, which is
            # exactly the warm-restart goodput story under test
            "DLROVER_PRELOAD": TRAINER_PRELOAD,
        },
    },
    "warm-template-import-kill": {"warm_restart": True},
    "warm-template-midspawn-kill": {"warm_restart": True},
    # run_scenario_multinode applies these to every agent process
    "multinode-rpc-partition": {"step_sleep": 0.5},
    # elastic resize in seconds: a 2.5 s heartbeat-silence window
    # detects the SIGKILLed node (no failure report exists), a 1 s
    # decision grace debounces it, and sub-second master polls /
    # monitor reports keep every control-plane reaction prompt; the
    # loop is stretched so the kill lands mid-run and disk commits
    # every 3 steps bound the cross-world restore's step loss
    "elastic-resize-churn": {
        "total_steps": 24,
        "disk_every": 3,
        "step_sleep": 0.3,
        # while the world is shrunken the loop crawls: on a loaded
        # box the replacement can take several seconds to boot, and
        # at 0.3 s/step the survivor would otherwise finish all 24
        # steps before the grow-back decision fires (flaky "never
        # grew back" verdicts) — stretching only the shrunken tail
        # bounds that race without slowing the healthy phases
        "shrunk_step_sleep": 1.0,
        "shard_dataset": True,
        "extra_env": {
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            "DLROVER_HANG_DETECTION_S": "2.5",
            "DLROVER_RESIZE_GRACE_S": "1.0",
            "DLROVER_RESIZE_REDELIVER_S": "15.0",
            "DLROVER_RESIZE_STOP_TIMEOUT_S": "1.5",
            "DLROVER_SECONDS_TO_CHECK_HANG": "0.5",
            "DLROVER_BREAKPOINT_COMMIT_TIMEOUT_S": "3",
            # the coordinator owns BOTH resize directions: the
            # agent-side membership fallback would race it on the
            # grow-back and leave the decision un-journaled
            "DLROVER_MEMBERSHIP_SELF_RESTART": "0",
            # the world-2 mesh is 2 hosts x 2 devices; world-1 is
            # 1 x 2 — the restore genuinely redistributes shards
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    },
    # multinode hang: same shrunk diagnosis thresholds as the
    # single-node scenario, but the conviction must come from the
    # per-node evidence scoring — node 0 keeps stepping throughout,
    # and the budget/step pacing keeps it training PAST node 1's
    # whole recovery (a peer succeeding mid-recovery would leave the
    # world node 1 needs to rejoin)
    "multinode-hang-culprit": {
        "total_steps": 16,
        "step_sleep": 0.8,
        "extra_env": {
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            "DLROVER_HANG_THRESHOLD_S": "2",
            "DLROVER_HANG_TIMEOUT": "3",
            "DLROVER_SECONDS_TO_CHECK_HANG": "0.5",
            "DLROVER_HANG_RESTART_GRACE_S": "20",
        },
    },
    # sparse recovery: the toy DeepFM loop (train_script selects it in
    # the harness), per-table content digests armed so the round-trip
    # invariant can decide bit-identity from events alone, and a DRAM
    # budget small enough that real rows live on the spill tier (the
    # control runs DRAM-only — residence is transparent, values equal)
    "sparse-kill-restore": {
        "total_steps": 12,
        "ckpt_every": 2,
        "train_script": "sparse",
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_CHAOS_KV_SPILL": "48",
        },
    },
    # serving plane: the sparse loop + publisher shipping the
    # embedding table every 2 steps (digests armed — manifests and
    # the torn-serve invariants need them); the serving runner reads
    # train_script="sparse_serving" and supervises the replica
    # subprocess itself
    "serving-replica-kill-midingest": {
        "total_steps": 12,
        "ckpt_every": 2,
        "train_script": "sparse_serving",
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_CHAOS_PUB_EVERY": "2",
            # slow the loop slightly so several generations commit
            # while the replica is alive on a loaded CI box
            "DLROVER_CHAOS_STEP_SLEEP": "0.2",
        },
    },
    # serving fleet: no trainer subprocess at all — the fleet runner
    # (run_serving_fleet_scenario) publishes in-process and drives
    # real routed load; these knobs shape the run.  compact_every=3
    # forces base generations (= drained re-bases) to land mid-load;
    # the 2 ms lookup floor models the TPU device-gather a CPU-only
    # CI box cannot reproduce, so in-flight requests genuinely
    # overlap across the pool
    "serving-fleet-replica-kill": {
        "pool_size": 3,
        "generations": 10,
        "publish_every_s": 0.35,
        "compact_every": 3,
        "load_streams": 4,
        "lookup_floor_ms": 2.0,
    },
    # ckpt_every=4 vs publish-every-2: the kill (3rd publish = step
    # 6) restores the step-4 snapshot and REPLAYS steps 5-6, so the
    # loss-trajectory invariant's multi-incarnation cross-check has
    # real replayed steps to agree on
    "serving-trainer-kill-midpublish": {
        "total_steps": 12,
        "ckpt_every": 4,
        "train_script": "sparse_serving",
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_CHAOS_PUB_EVERY": "2",
            "DLROVER_CHAOS_STEP_SLEEP": "0.2",
        },
    },
    # streaming reshard: the harness pre-seeds a committed world-2
    # sparse checkpoint at step 4 (seed_kv_world), the window is
    # pinned to 200 rows so the ~600-row-per-rank tables stream in
    # several chunks (the kill rule needs a 3rd chunk to land on),
    # and digests are armed for the exactly-once verdict
    "sparse-streaming-reshard-kill": {
        "total_steps": 10,
        "ckpt_every": 2,
        "train_script": "sparse_reshard",
        "seed_kv_world": 2,
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_KV_RESHARD_WINDOW_ROWS": "200",
        },
    },
    # elastic RL: 8 rollout leases = 8 PPO train steps (2 leases per
    # iteration), so total_steps doubles as the lease-dataset size and
    # the trainer's step budget; ckpt_every=2 is nominal — the RL loop
    # flash-saves after EVERY lease, and the kill on lease 2 restores
    # the post-lease-1 snapshot and replays PPO steps 1-2 before
    # regenerating the lost lease.  compile_cache gives the respawn
    # the AOT executable path for its actor/critic steps.
    "rl-rollout-worker-kill": {
        "total_steps": 8,
        "ckpt_every": 2,
        "train_script": "rl",
        "compile_cache": True,
    },
    # spill-disk death mid-export: same loop + budget; the kill lands
    # at step 7 so the step-6 export (post-breaker, spill_disabled
    # stamped) is the one the restore round-trips
    "sparse-spill-io-error": {
        "total_steps": 12,
        "ckpt_every": 2,
        "train_script": "sparse",
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_CHAOS_KV_SPILL": "48",
        },
    },
    # sparse resize: the elastic-resize recipe (same control-plane
    # knobs as elastic-resize-churn) with the kv-partitioned loop and
    # digests armed; disk commits every 3 steps bound the cross-world
    # restore's step loss AND guarantee a world-1 commit exists
    # before the harness respawns the replacement agent
    "sparse-resize-churn": {
        "total_steps": 24,
        "disk_every": 3,
        "step_sleep": 0.3,
        "train_script": "sparse_resize",
        "extra_env": {
            "DLROVER_KV_DIGEST": "1",
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            "DLROVER_HANG_DETECTION_S": "2.5",
            "DLROVER_RESIZE_GRACE_S": "1.0",
            "DLROVER_RESIZE_REDELIVER_S": "15.0",
            "DLROVER_RESIZE_STOP_TIMEOUT_S": "1.5",
            "DLROVER_SECONDS_TO_CHECK_HANG": "0.5",
            "DLROVER_BREAKPOINT_COMMIT_TIMEOUT_S": "3",
            "DLROVER_MEMBERSHIP_SELF_RESTART": "0",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    },
    # invisible recovery: warm restarts + the framework preload so a
    # respawn pays fork+restore+aot only, a workdir-scoped
    # compile-cache dir (the harness materializes it; the AOT cache
    # rides under it) so the FIRST incarnation deterministically
    # pre-populates the replacement — it WRITES the serialized step
    # executable its replacement DESERIALIZES — and the forkserver
    # template pre-loads the entry bytes before each fork so the
    # replacement inherits them in memory.  The hit/miss, the
    # retrace+aot ceiling and the sub-second cycle are all decided
    # from the event log alone.
    "warm-recovery-cache-hit": {
        "warm_restart": True,
        "total_steps": 12,
        "ckpt_every": 2,
        "compile_cache": True,
        "extra_env": {
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            "DLROVER_PRELOAD": TRAINER_PRELOAD,
            "DLROVER_AOT_PRETRACE": "1",
        },
    },
    # host-portable master: the respawn is forced onto a FRESH
    # journal dir (a replacement host's view) and must seed from the
    # storage-tier mirror (the harness materializes the mirror dir
    # via the journal_mirror knob); shard traffic armed so
    # exactly-once sharding is decidable from events
    "master-respawn-other-host": {
        "shard_dataset": True,
        "journal_mirror": True,
        "extra_env": {
            "DLROVER_MASTER_RESPAWN_FRESH_JOURNAL": "1",
            # tight group-commit window: the kill must not outrun the
            # mirror by more than one shard dispatch
            "DLROVER_JOURNAL_MIRROR_INTERVAL_S": "0.05",
        },
    },
    # hang diagnosis in seconds instead of half an hour: fast step
    # reporting, a 2 s agent watchdog window, a 3 s master hang
    # timeout and a sub-second master poll — the 90 s stall is
    # diagnosed, evidenced and culprit-restarted long before the
    # sleep could expire
    "trainer-hang-detected": {
        "extra_env": {
            "DLROVER_MONITOR_REPORT_INTERVAL": "0.5",
            "DLROVER_HANG_THRESHOLD_S": "2",
            "DLROVER_HANG_TIMEOUT": "3",
            "DLROVER_SECONDS_TO_CHECK_HANG": "0.5",
            # the 3 s hang timeout is smaller than a cold restart;
            # the post-restart grace keeps the recovery window from
            # re-convicting the fresh incarnation
            "DLROVER_HANG_RESTART_GRACE_S": "20",
        },
    },
}


def build(name: str, seed: Optional[int] = None) -> Scenario:
    if name not in SCENARIOS:
        raise KeyError(
            f"unknown scenario {name!r}; known: {sorted(SCENARIOS)}"
        )
    factory = SCENARIOS[name]
    return factory(seed) if seed is not None else factory()
