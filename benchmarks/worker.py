"""The benchmark's training script: the one worker that ``tpurun``
spawns and that owns the chip.

A copy of the ``--phase elastic`` loop of
``examples/train_xl_elastic.py`` (``ElasticTrainer``,
``make_train_step``, ``adamw_bf16``, ``RecoveryProfiler.resolve_step``,
``Checkpointer``: the path a user runs), with the measurement around
it.  It names no cell and no model: the configuration file gives the
sizes and the recipe, ``models/<model_type>.py`` builds the system's
model from them and holds the plain reference, and the traffic file
gives the batch, the save schedule and the trace plan.

What it does, in order:

set-up   weights and optimizer state on the device in ONE jitted call
         from ``--seed``; the fixed batch; the plain reference's loss
         on the initial parameters (``models/``); the step
         executable through the AOT / XLA caches; warm-up steps, the
         first of which gives the system's loss on the same
         parameters; with saves in the traffic, the first DISK save
         (new shm segment, the snapshot program, the agent's first
         persist) and the first MEMORY save, both committed.
window   steady traffic: steps until ``--seconds`` have passed; it
         closes as the first step completes at or after the deadline.
         Traffic with saves: the whole save cycles (steps and their
         save) that fit ``--seconds`` at the traffic's nominal cycle
         length, a fixed amount of work.  All work and all time of
         the window count.
after    with saves: drain, one more save, read the shared-memory copy
         back on the host and compare it with the device state bit
         for bit.  With ``--trace 1``: a profiler trace of the
         traffic's ``trace.steps`` steady steps (with saves: centred
         on one memory save, one full save cycle).

Everything it measures goes to ``<out>/report.json``; ``run.py`` reads
that and the job's event log.
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import loader  # noqa: E402  (the benchmark's own, beside this file)

from dlrover_tpu.checkpoint.checkpointer import (  # noqa: E402
    Checkpointer,
    StorageType,
    restore_to_template,
)
from dlrover_tpu.models.gpt import count_params  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    ElasticTrainer,
    TrainState,
    abstract_like,
    init_jax_distributed,
    make_train_step,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler  # noqa: E402

TRACKER = "latest_checkpointed_iteration.txt"


def write_report(path, report):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.replace(tmp, path)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass
    2**31, which a 32-bit key seed does not hold)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def fixed_batch(cfg, traffic, seed):
    tokens = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (traffic["batch"], traffic["seq"] + 1),
        dtype=np.int32,
    )
    return {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def bits_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if a.size == 0:
        return True
    word = f"u{a.dtype.itemsize}"
    return bool(np.array_equal(
        np.ascontiguousarray(a).view(word),
        np.ascontiguousarray(b).view(word),
    ))


class CompileCounter:
    """Counts XLA backend compilations of this process (jax's own
    monitoring event): the window must see none."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Loop:
    """The step loop a user writes, with each call into a layer under
    a host span (``jax.profiler.TraceAnnotation``) so a trace can
    name what the host was doing while the device idled."""

    def __init__(self, trainer, step_fn, state, batch, ckpt, saves):
        self.trainer = trainer
        self.step_fn = step_fn
        self.state = state
        self.batch = batch
        self.ckpt = ckpt
        self.saves = saves or {}
        self.records = {"steps": [], "saves": []}
        self.last_disk_step = None

    def take_records(self):
        """What was recorded since the last call."""
        records, self.records = self.records, {"steps": [], "saves": []}
        return records

    def save_kind(self, step):
        """The kind of save the schedule puts after ``step``."""
        every, disk = (
            self.saves.get("memory_every"), self.saves.get("disk_every")
        )
        if disk and step % disk == 0:
            return "disk"
        if every and step % every == 0:
            return "memory"
        return None

    def step(self):
        """One step, ending in ``block_until_ready``; returns the
        time it completed."""
        trainer = self.trainer
        with jax.profiler.TraceAnnotation("bench.compute"):
            with trainer.profile("compute") as p:
                self.state, metrics = self.step_fn(
                    self.state, self.batch
                )
                p.block(metrics)
        done = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.report"):
            trainer.report_step(metrics)
        self.records["steps"].append({
            "step": trainer.global_step,
            "done": done,
            "loss": float(metrics["loss"]),
        })
        return done

    def save(self, kind):
        """One flash save as the user calls it; how long the call
        blocked the loop, on this clock, is the stall."""
        trainer = self.trainer
        payload = {
            "state": self.state, "trainer": trainer.state_dict(),
        }
        called = time.time()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.checkpoint"):
            with trainer.profile("checkpoint"):
                ok = self.ckpt.save_checkpoint(
                    trainer.global_step, payload,
                    storage_type=(
                        StorageType.DISK if kind == "disk"
                        else StorageType.MEMORY
                    ),
                )
        stall = time.perf_counter() - t0
        self.records["saves"].append({
            "step": trainer.global_step, "kind": kind,
            "called": called, "stall_s": stall, "ok": bool(ok),
        })
        if ok and kind == "disk":
            self.last_disk_step = trainer.global_step
        return bool(ok)

    def scheduled_save(self):
        """The save the schedule puts after the step just taken."""
        kind = self.save_kind(self.trainer.global_step)
        if kind:
            self.save(kind)


def wait_persisted(ckpt_dir, step, timeout):
    """Until the agent's tracker file names ``step`` (or a later
    one): the DISK save of ``step`` is committed to storage."""
    path = os.path.join(ckpt_dir, TRACKER)
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with open(path) as f:
                if int(f.read().strip() or -1) >= step:
                    return True
        except (OSError, ValueError):
            pass
        time.sleep(0.05)
    return False


def drain(loop, ckpt_dir):
    """Wait until every accepted save has reached shared memory and
    the agent has persisted the last accepted DISK save; False where
    that does not happen in two minutes."""
    loop.ckpt.wait()
    return loop.last_disk_step is None or wait_persisted(
        ckpt_dir, loop.last_disk_step, 120
    )


def run_window(loop, seconds):
    """The measured window; returns ``(t0, t1)`` on the perf counter.

    It opens as one more step completes.  Steady traffic: it closes as
    the first step completes at or after ``seconds``.  Traffic with
    saves does a FIXED amount of work: the whole save cycles that fit
    ``seconds`` at the traffic file's nominal cycle length.  A save
    blocks the loop for seconds, so a window cut by the clock would
    hold one cycle more or fewer from run to run and the rate would
    swing by several per cent; with whole cycles every run, and both
    sides of a comparison, hold the same steps and the same saves."""
    t0 = loop.step()
    loop.take_records()
    trainer, saves = loop.trainer, loop.saves
    if saves:
        cycles = max(1, round(seconds / saves["nominal_cycle_s"]))
        last_step = trainer.global_step + cycles * saves["memory_every"]
        deadline = float("inf")
    else:
        last_step = 0
        deadline = t0 + seconds
    while True:
        loop.scheduled_save()
        done = loop.step()
        if done >= deadline or trainer.global_step == last_step:
            return t0, done


def run_trace(loop, plan, trace_dir):
    """A profiler trace of ``plan["steps"]`` steady steps; with saves,
    centred on one MEMORY save: one save cycle."""
    trainer = loop.trainer
    every = loop.saves.get("memory_every")
    if every and plan["steps"] > every:
        sys.exit("trace.steps may not pass saves.memory_every")
    if every:
        half = plan["steps"] // 2
        while not (
            trainer.global_step % every == every - half
            and loop.save_kind(trainer.global_step + half) == "memory"
        ):
            loop.step()
            loop.scheduled_save()
        loop.ckpt.wait()
    loop.take_records()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    # level 1 records the loop's own spans; at level 2 the runtime's
    # transfer events doubled the length of a save (PERF.md, PR 24)
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(plan["steps"]):
        loop.step()
        loop.scheduled_save()
    jax.profiler.stop_trace()
    return loop.take_records()


def read_back(loop, ckpt_dir):
    """One more save, committed; the shared-memory copy read back on
    the host and compared with the device state bit for bit."""
    trainer, ckpt = loop.trainer, loop.ckpt
    drain(loop, ckpt_dir)
    save_ok = loop.save("memory")
    ckpt.wait()
    host_state = jax.device_get(loop.state)
    step_back, restored = ckpt.load_checkpoint()
    same = False
    if save_ok and step_back == trainer.global_step and restored:
        back = restore_to_template(
            host_state, restored["state"], device_put=False
        )
        same = all(jax.tree_util.tree_leaves(
            jax.tree.map(bits_equal, host_state, back)
        ))
    return {
        "save_ok": save_ok, "step": step_back,
        "expected_step": trainer.global_step,
        "tier": ckpt.last_restore_phases.get("tier"),
        "bit_identical": bool(same),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cfg = loader.load_json(args.config)
    traffic = loader.load_json(args.traffic)
    report_path = os.path.join(args.out, "report.json")
    report = {"phase": "start"}

    init_jax_distributed()
    prof = RecoveryProfiler()
    compiles = CompileCounter()
    devices = jax.devices()
    report["device"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    write_report(report_path, report)
    if devices[0].platform != cfg["platform"]:
        # never a number from another backend under this cell's name
        sys.exit(
            f"configuration {cfg['name']} runs on {cfg['platform']}, "
            f"jax.devices() is {devices}"
        )

    # -- set-up ----------------------------------------------------------------
    # the trainer first: it reports the backend this worker owns
    batch_size, seq = traffic["batch"], traffic["seq"]
    trainer = ElasticTrainer(
        global_batch_size=batch_size, micro_batch_size=batch_size,
        dp_size=1,
    )
    saves = traffic.get("saves")
    ckpt = ckpt_dir = None
    if saves:
        ckpt_dir = os.path.join(args.out, "ckpt")
        ckpt = Checkpointer(
            ckpt_dir, deletion_keep_latest=saves.get("keep_latest", 0)
        )

    family = loader.load_module("models", cfg["model_type"])
    model, optimizer, loss_fn = family.build(cfg)
    host_batch = fixed_batch(cfg, traffic, args.seed)
    step_fn = make_train_step(loss_fn, optimizer)

    def init(key):
        return TrainState.create(
            model.init_params(key, seq_len=seq), optimizer
        )

    t0 = time.perf_counter()
    abs_state = jax.eval_shape(init, seed_key(args.seed))
    step = prof.resolve_step(
        step_fn, lambda: (abs_state, abstract_like(host_batch))
    )
    report["resolve_step_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    state = jax.jit(init)(seed_key(args.seed))
    batch = jax.device_put(host_batch)
    jax.block_until_ready((state, batch))
    report["init_s"] = time.perf_counter() - t0
    report["params"] = count_params(state.params)

    # the plain reference on the SAME initial parameters, before the
    # first step donates them
    t0 = time.perf_counter()
    report["reference_loss"] = family.reference_loss(
        state.params, batch["x"], batch["y"], cfg
    )
    report["reference_s"] = time.perf_counter() - t0
    report["phase"] = "warmup"
    write_report(report_path, report)

    loop = Loop(trainer, step, state, batch, ckpt, saves)
    del state
    for _ in range(traffic["warmup_steps"]):
        loop.step()
    # the step program's own loss on the initial parameters
    report["system_loss"] = loop.records["steps"][0]["loss"]
    prof.record_first_step()
    report["setup_saves_ok"] = True
    if saves:
        # a user's first save: new shm segment (page-fault bound),
        # the snapshot program compiles, the agent persists
        ok = loop.save("disk")
        ok = ckpt.wait() and ok
        ok = wait_persisted(ckpt_dir, trainer.global_step, 300) and ok
        loop.step()
        report["setup_saves_ok"] = loop.save("memory") and ok
    loop.step()
    report["setup_records"] = loop.take_records()

    # -- the window ------------------------------------------------------------
    compiles_before = compiles.count
    report["window_t0"], report["window_t1"] = run_window(
        loop, args.seconds
    )
    report["window_t0_epoch"] = time.time() - (
        time.perf_counter() - report["window_t0"]
    )
    report["compiles_in_window"] = compiles.count - compiles_before
    report["window"] = loop.take_records()
    report["tokens_per_step"] = batch_size * seq
    report["phase"] = "after"
    write_report(report_path, report)

    # -- after the window ------------------------------------------------------
    if saves:
        report["window_persisted"] = drain(loop, ckpt_dir)
    if args.trace:
        report["trace_records"] = run_trace(
            loop, traffic["trace"], os.path.join(args.out, "trace")
        )
    if saves:
        report["readback"] = read_back(loop, ckpt_dir)

    report["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices
    )
    report["compiles_total"] = compiles.count
    report["phase"] = "done"
    write_report(report_path, report)
    if ckpt is not None:
        ckpt.close()


if __name__ == "__main__":
    main()
