"""GPT-2-XL-width training on a TPU v5e, in the three shapes
``chip_smoke.py`` drives (and a user can run by hand):

``--phase step`` — one process, no agent: the full 48-layer 1.56B
model on ONE 16 GB chip (bf16 params + bf16 Adam moments, Pallas
flash attention, per-block remat, buffer donation)::

    python examples/train_xl_elastic.py --phase step --steps 20

``--phase elastic`` — under ``tpurun``: the same widths at a depth
whose train state fits the chip TWICE (the flash save snapshots it on
the device), with a flash checkpoint to shared memory, a restore after
the worker is killed, and a clean stop when ``--until-file`` appears::

    python -m dlrover_tpu.run --nproc_per_node=1 --max_restarts=2 \
        examples/train_xl_elastic.py --phase elastic --layers 12 \
        --ckpt-dir /path/ckpt --save-at 50

``--phase sharded`` — under ``tpurun``, one worker driving every
local chip: ``auto_accelerate`` with fsdp + amp_native + checkpoint,
a flash save and a restore into the sharded placement, compared in
the same process with the unsharded step on one device.

The process that runs this script owns the chip(s).  Without
``--toy`` it refuses to run on anything but a TPU backend — a
replacement worker that cannot get the chip must fail, never train on
the CPU.  ``--toy`` is the CPU rehearsal: tiny widths, Pallas kernels
interpreted.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.accel import Strategy, auto_accelerate
from dlrover_tpu.checkpoint.checkpointer import (
    Checkpointer,
    StorageType,
)
from dlrover_tpu.common.compile_cache import (
    cache_entries,
    enable_persistent_cache,
)
from dlrover_tpu.models.gpt import (
    GPT,
    GPTConfig,
    count_params,
    cross_entropy_loss,
)
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer,
    TrainState,
    abstract_like,
    init_jax_distributed,
    make_train_step,
    restore_train_state,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler

XL_LAYERS = 48
BATCH, SEQ = 4, 1024


def xl_config(layers: int, toy: bool, **overrides) -> GPTConfig:
    """GPT-2-XL at its published widths (hidden 1600, 25 heads of 64,
    the config's vocabulary, seq 1024); only the depth is a
    parameter.  ``toy`` keeps the recipe and shrinks every width."""
    if toy:
        return GPTConfig(
            vocab_size=512, max_seq_len=128, num_layers=layers,
            num_heads=2, hidden_dim=128, attention_impl="flash",
            remat=True, **overrides,
        )
    return GPTConfig(
        num_layers=layers, num_heads=25, hidden_dim=1600,
        max_seq_len=SEQ, attention_impl="flash", remat=True,
        **overrides,
    )


def fixed_batch(cfg: GPTConfig, seed: int, toy: bool):
    batch, seq = (BATCH, 128) if toy else (BATCH, SEQ)
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
    )
    return {"x": tokens[:, :-1], "y": tokens[:, 1:]}


def require_backend(toy: bool):
    """The devices this process owns.  Without ``--toy`` anything but
    a TPU backend is a hard failure."""
    devices = jax.devices()
    if not toy and devices[0].platform != "tpu":
        sys.exit(
            f"no TPU backend: jax.devices() is {devices} "
            "(pass --toy for the CPU rehearsal)"
        )
    return devices


def device_report(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` per device (None where the backend does
    not report memory stats, as the CPU backend does not)."""
    out = []
    for dev in devices:
        stats = dev.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def write_report(path: str, report: dict):
    if not path:
        return
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.replace(tmp, path)


def build_model(args):
    cfg = xl_config(args.layers, args.toy, param_dtype=jnp.bfloat16)
    if args.xla_attention:
        cfg = dataclasses.replace(cfg, attention_impl="xla")
    model = GPT(cfg)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    return cfg, model, optimizer, loss_fn


def abstract_state(model, optimizer, seq_len: int):
    abs_params = jax.eval_shape(
        lambda: model.init_params(
            jax.random.PRNGKey(0), seq_len=seq_len
        )
    )
    return jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )


# -- phase step: the whole model, one process, no agent --------------------


def run_step(args):
    devices = require_backend(args.toy)
    cache_dir = enable_persistent_cache()
    cfg, model, optimizer, loss_fn = build_model(args)
    batch = fixed_batch(cfg, args.seed, args.toy)
    seq = batch["x"].shape[1]
    step_fn = make_train_step(loss_fn, optimizer)

    entries_before = cache_entries(cache_dir)
    t0 = time.perf_counter()
    compiled = step_fn.lower(
        abstract_state(model, optimizer, seq), abstract_like(batch)
    ).compile()
    compile_s = time.perf_counter() - t0
    entries_after = cache_entries(cache_dir)
    kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()

    params = model.init_params(
        jax.random.PRNGKey(args.seed), seq_len=seq
    )
    state = TrainState.create(params, optimizer)
    n_params = count_params(params)
    del params
    batch = jax.device_put(batch)
    losses, seconds = [], []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics)
        seconds.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        print(
            f"step {len(losses)}: loss {losses[-1]:.4f} "
            f"({seconds[-1]:.3f}s)", flush=True,
        )
    write_report(args.report, {
        "phase": "step",
        "device": device_report(devices),
        "layers": cfg.num_layers,
        "params": n_params,
        "losses": losses,
        "step_seconds": seconds,
        "compile_s": compile_s,
        "kernels": kernels,
        "program_bytes": {
            "arguments": mem.argument_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "code": mem.generated_code_size_in_bytes,
        } if mem is not None else None,
        "peak_bytes": peak_bytes(devices),
        "cache_dir": cache_dir,
        "cache_entries_before": entries_before,
        "cache_entries_after": entries_after,
    })


# -- phase elastic: under tpurun, flash save, killed, resumed -------------


def run_elastic(args):
    init_jax_distributed()
    prof = RecoveryProfiler()
    require_backend(args.toy)
    # the trainer first: it reports the backend this worker owns
    trainer = ElasticTrainer(
        global_batch_size=BATCH, micro_batch_size=BATCH, dp_size=1
    )
    # the restore reads shared memory on its own thread while the
    # model and the step executable are built below
    ckpt = Checkpointer(args.ckpt_dir)
    load_handle = ckpt.load_checkpoint_async()

    cfg, model, optimizer, loss_fn = build_model(args)
    batch = fixed_batch(cfg, args.seed, args.toy)
    seq = batch["x"].shape[1]
    step_fn = make_train_step(loss_fn, optimizer)
    step = prof.resolve_step(
        step_fn,
        lambda: (
            abstract_state(model, optimizer, seq), abstract_like(batch)
        ),
        restore_busy=lambda: not load_handle.done(),
    )

    start_step, restored = load_handle.result()
    prof.record_restore(ckpt.last_restore_phases)
    if start_step is None:
        start_step = 0
        state = TrainState.create(
            model.init_params(
                jax.random.PRNGKey(args.seed), seq_len=seq
            ),
            optimizer,
        )
    else:
        state = restore_train_state(optimizer, restored["state"])
        del restored

    trainer.global_step = start_step
    batch = jax.device_put(batch)
    save_at = {int(s) for s in args.save_at.split(",") if s}

    def flash_save():
        # stalls the loop for the on-device snapshot only; the host
        # fetch, the shm write and the agent's persist are async
        ckpt.save_checkpoint(
            trainer.global_step,
            {"state": state, "trainer": trainer.state_dict()},
            storage_type=StorageType.DISK,
        )

    first = True
    while trainer.global_step < args.steps and not (
        args.until_file and os.path.exists(args.until_file)
    ):
        with trainer.profile("compute") as p:
            state, metrics = step(state, batch)
            p.block(metrics)
        if first:
            first = False
            prof.record_first_step()
        trainer.report_step(metrics)
        if trainer.global_step in save_at:
            with trainer.profile("checkpoint"):
                flash_save()

    # the last step's report has no next step to be written in
    trainer.flush_reports()
    # the last state goes to disk through the agent before exit
    ckpt.wait()
    flash_save()
    ckpt.wait()
    tracker = os.path.join(
        args.ckpt_dir, "latest_checkpointed_iteration.txt"
    )
    deadline = time.time() + 300
    while time.time() < deadline:
        try:
            with open(tracker) as f:
                if int(f.read().strip() or -1) == trainer.global_step:
                    break
        except (OSError, ValueError):
            pass
        time.sleep(0.2)
    else:
        sys.exit("the final checkpoint never committed to disk")
    ckpt.close()


# -- phase sharded: one worker, every local chip ---------------------------


def _device_param_bytes(params) -> dict:
    per_device: dict = {}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
    return per_device


def run_sharded(args):
    init_jax_distributed()
    devices = require_backend(args.toy)
    cache_dir = enable_persistent_cache()
    # fp32 master params here: amp_native is the bf16 COMPUTE policy
    base = xl_config(args.layers, args.toy)
    model = GPT(base)
    # the STRATEGY owns the attention implementation (the plan
    # rebuilds the model): module_replace is what the strategy search
    # adds on a real TPU
    opts = [
        ("fsdp", {}), ("amp_native", {}), ("checkpoint", {}),
        ("module_replace", {"attention": "flash"}),
    ]

    def loss_fn(params, batch, model=model):
        logits = model.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    host_batch = fixed_batch(base, args.seed, args.toy)

    def optim():
        return optax.adamw(3e-4, weight_decay=0.1)

    result = auto_accelerate(
        model, optim, loss_fn, host_batch,
        strategy=Strategy(opts=opts),
        devices=devices,
    )
    state = result.state
    # the one-device comparison starts from the SAME values
    host_params = jax.device_get(state.params)
    param_bytes = _device_param_bytes(state.params)
    total_param_bytes = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(host_params)
    )

    lowered = result.train_step.lower(
        state, result.place_batch(host_batch)
    )
    compiled_text = lowered.compile().as_text()
    kernels = compiled_text.count("tpu_custom_call")
    gathers = compiled_text.count("all-gather")

    placed = result.place_batch(host_batch)
    losses = []
    for _ in range(args.steps):
        state, metrics = result.train_step(state, placed)
        losses.append(float(metrics["loss"]))
        print(f"sharded step {len(losses)}: loss {losses[-1]:.4f}",
              flush=True)

    # flash save, then a restore INTO the sharded placement
    ckpt = Checkpointer(args.ckpt_dir)
    ckpt.save_checkpoint(
        args.steps,
        {"params": state.params, "opt_state": state.opt_state},
        storage_type=StorageType.DISK,
    )
    ckpt.wait()
    saved_params = jax.device_get(state.params)
    restored_step, restored = ckpt.load_checkpoint(target_state={
        "params": state.params, "opt_state": state.opt_state,
    })
    tier = ckpt.last_restore_phases.get("tier")
    restored_bytes = _device_param_bytes(restored["params"])
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(a, np.asarray(b))),
        saved_params, jax.device_get(restored["params"]),
    )
    restore_identical = all(jax.tree_util.tree_leaves(same))
    same_sharding = all(jax.tree_util.tree_leaves(jax.tree.map(
        lambda a, b: a.sharding.is_equivalent_to(b.sharding, a.ndim),
        state.params, restored["params"],
    )))
    ckpt.close()
    del state, restored, saved_params

    # the same model and step, unsharded, on ONE of the devices
    one = devices[0]
    ref_step = make_train_step(
        lambda p, b: loss_fn(p, b, model=result.model), optim()
    )
    ref_state = TrainState.create(
        jax.device_put(host_params, one), optim()
    )
    ref_batch = jax.device_put(host_batch, one)
    ref_losses = []
    for _ in range(args.steps):
        ref_state, metrics = ref_step(ref_state, ref_batch)
        ref_losses.append(float(metrics["loss"]))
        print(f"one-device step {len(ref_losses)}: loss "
              f"{ref_losses[-1]:.4f}", flush=True)

    write_report(args.report, {
        "phase": "sharded",
        "device": device_report(devices),
        "layers": base.num_layers,
        "mesh": {k: int(v) for k, v in result.mesh.shape.items()},
        "strategy": [name for name, _ in opts],
        "attention": result.model.config.attention_impl,
        "kernels": kernels,
        "all_gathers": gathers,
        "losses": losses,
        "ref_losses": ref_losses,
        "param_bytes": total_param_bytes,
        "param_bytes_per_device": param_bytes,
        "restored_step": restored_step,
        "restore_tier": tier,
        "restore_identical": restore_identical,
        "restore_same_sharding": same_sharding,
        "restored_bytes_per_device": restored_bytes,
        "peak_bytes": peak_bytes(devices),
        "cache_dir": cache_dir,
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", required=True,
                    choices=("step", "elastic", "sharded"))
    ap.add_argument("--layers", type=int, default=XL_LAYERS)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--toy", action="store_true",
                    help="CPU rehearsal: tiny widths")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--save-at", default="",
                    help="comma-separated steps to flash-save after")
    ap.add_argument("--until-file", default="",
                    help="stop cleanly once this file exists")
    ap.add_argument("--report", default="",
                    help="write a JSON report of the run here")
    ap.add_argument("--xla-attention", action="store_true",
                    help="phases step and elastic: XLA attention in "
                         "place of the Pallas kernel")
    args = ap.parse_args()
    {"step": run_step, "elastic": run_elastic,
     "sharded": run_sharded}[args.phase](args)


if __name__ == "__main__":
    main()
