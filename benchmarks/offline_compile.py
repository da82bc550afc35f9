"""The third rehearsal: compile a cell's programs at their real size
for a DESCRIBED TPU v5e, with no chip attached::

    JAX_PLATFORMS=cpu python benchmarks/offline_compile.py \
        --workload xl48_steady

What the chip's compiler would refuse (a kernel's tiling, a program
that does not fit 16 GB) it refuses here, at no chip time.  Nothing
runs: this says nothing about results or times, and no number it
prints is a measurement.
"""

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import loader  # noqa: E402
import run as harness  # noqa: E402
import worker  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    TrainState,
    make_train_step,
)


def report(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    print(
        f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
        f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB "
        f"(aliased {mem.alias_size_in_bytes / 1e9:.2f}), temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB, code "
        f"{mem.generated_code_size_in_bytes / 1e9:.2f} GB; "
        f"{compiled.as_text().count('tpu_custom_call')} tpu_custom_call",
        flush=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--cells", default=os.path.join(
        harness.ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    cell = harness.Cell(args.cells, args.workload)
    cfg, traffic = cell.config, cell.traffic
    jax.config.update("jax_enable_compilation_cache", False)
    # the kernels decide by jax.default_backend() whether to run
    # interpreted; here that is the CPU, the target is not
    from dlrover_tpu.ops import flash_attention

    flash_attention._interpret = lambda: False
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=chip
            ),
            tree,
        )

    family = loader.load_module("models", cfg["model_type"])
    reference = family.reference
    model, optimizer, loss_fn = family.build(cfg)
    seq = traffic["seq"]

    def init(key):
        return TrainState.create(
            model.init_params(key, seq_len=seq), optimizer
        )

    key = on_chip(jax.eval_shape(lambda: worker.seed_key(0)))
    state = on_chip(jax.eval_shape(init, worker.seed_key(0)))
    batch = on_chip(jax.eval_shape(
        lambda: worker.fixed_batch(cfg, traffic, 0)
    ))
    report("init", jax.jit(init).lower(key))
    report("train step", make_train_step(loss_fn, optimizer).lower(
        state, batch))
    # the plain reference's three pieces, one sequence at a time
    params = state.params
    tokens = jax.ShapeDtypeStruct((seq,), batch["x"].dtype, sharding=chip)
    wte, wpe = params["wte"]["embedding"], params["wpe"]["embedding"]
    x = jax.ShapeDtypeStruct(
        (seq, cfg["n_embd"]), jax.numpy.float32, sharding=chip
    )
    report("reference embed", reference._embed.lower(wte, wpe, tokens))
    report("reference block", reference._block.lower(
        x, params["block_0"], n_head=cfg["n_head"],
        eps=cfg["layer_norm_epsilon"],
    ))
    report("reference head", reference._head_loss.lower(
        x, params["ln_f"], wte, tokens, eps=cfg["layer_norm_epsilon"],
    ))


if __name__ == "__main__":
    main()
