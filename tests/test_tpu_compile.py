"""The main path's kernels and step programs, COMPILED for a described
TPU v5e (no chip attached, nothing runs): what interpret mode cannot
show — a slice the tiling refuses, a kernel over its fast-memory
budget, a Mosaic kernel the partitioner cannot split — fails here, at
no chip time.  A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU's library, and every xdist worker
imports every test file), the tests steer ``_interpret()`` by
monkeypatching, and the compile cache is off around them (an entry
compiled for a described chip cannot be read back without one)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import quantization as qz
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)

# GPT-2-XL widths; the largest XL leaf is the fused MLP kernel
XL = dict(num_heads=25, hidden_dim=1600, max_seq_len=1024)
XL_LEAF = (1600, 6400)
# GPT-2-XL's and OLMoE's own (K and V resident for the whole sequence)
# and one past the residency budget (8 MB of K and V at 8192 x 128:
# two kv-major blocks on the grid, their index map clamped)
ATTN_SHAPES = [
    (4, 1024, 25, 64), (4, 2048, 32, 128), (2, 4096, 16, 128),
    (1, 8192, 8, 128),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any reason is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """The kernels lower for the TPU, as they do on the chip."""
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(qz, "_interpret", lambda: False)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding
        ),
        tree,
    )


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_flash_attention_forward_compiles(one_chip, on_tpu, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(fa.flash_attention).lower(x, x, x).compile()
    assert _kernels(compiled) >= 1


@pytest.mark.parametrize("shape", ATTN_SHAPES, ids=str)
def test_flash_attention_backward_compiles(one_chip, on_tpu, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(x, x, x).compile()
    # forward (recomputed for the residuals), dq and dkv
    assert _kernels(compiled) >= 3


def test_blockwise_quantiser_compiles_at_xl_leaf(one_chip, on_tpu):
    x = jax.ShapeDtypeStruct(XL_LEAF, jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a: qz.quantize_blockwise(a)[:2]
    ).lower(x).compile()
    assert _kernels(compiled) >= 1


def test_fused_qadam_compiles_at_xl_leaf(one_chip, on_tpu):
    rows = XL_LEAF[0] * XL_LEAF[1] // qz.DEFAULT_BLOCK

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tiles = s((rows, qz.DEFAULT_BLOCK), jnp.float32)
    q = s((rows, qz.DEFAULT_BLOCK), jnp.int8)
    scales = s((rows, 1), jnp.float32)
    compiled = jax.jit(
        lambda *a: qz.fused_qadam_step(
            *a, b1=0.9, b2=0.999, eps=1e-8, lr=3e-4, wd=0.1
        )
    ).lower(
        tiles, tiles, q, scales, q, scales, s((1, 2), jnp.float32)
    ).compile()
    assert _kernels(compiled) >= 1


def _two_layers(widths=XL, **kw):
    cfg = GPTConfig(
        num_layers=2, attention_impl="flash", remat=True, **widths,
        **kw,
    )
    model = GPT(cfg)

    def loss_fn(params, batch, model=model):
        logits = model.apply({"params": params}, batch["x"])
        return cross_entropy_loss(logits, batch["y"])

    batch = {
        "x": np.zeros((4, 1024), np.int32),
        "y": np.zeros((4, 1024), np.int32),
    }
    return model, loss_fn, batch


def test_xl_width_train_step_compiles(one_chip, on_tpu):
    """The smoke's one-chip step recipe (bf16 params and moments,
    flash attention, remat, donation) at two of its 48 layers."""
    model, loss_fn, batch = _two_layers(param_dtype=jnp.bfloat16)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    abs_params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    compiled = make_train_step(loss_fn, optimizer).lower(
        _shapes(abs_state, one_chip), _shapes(batch, one_chip)
    ).compile()
    # per layer: forward, its remat, dq, dkv
    assert _kernels(compiled) >= 6
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2**30


def test_step_for_the_chip_carries_the_programs_names(one_chip, on_tpu):
    """What a reader of a device trace joins on, in the step as the
    chip's compiler leaves it: the three kernels' names in the
    lowering, the program's scopes in the instructions' ``op_name``,
    and the custom calls still named after the flax module
    (``%attn.<n>``), which is how ``benchmarks/kernels.py`` finds
    them.  A ``name=`` on the ``pl.pallas_call``s would rename them
    to ``%flash_fwd.<n>`` and the benchmark's pattern would find
    none (PERF.md, open questions): it stays off until the benchmark
    matches on something else."""
    import re

    from dlrover_tpu.common.aot_cache import op_names

    model, loss_fn, batch = _two_layers(param_dtype=jnp.bfloat16)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    abs_params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    lowered = make_train_step(loss_fn, optimizer).lower(
        _shapes(abs_state, one_chip), _shapes(batch, one_chip)
    )
    text = lowered.as_text(debug_info=True)
    for kernel in ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    for scope in ("optimizer", "loss_head", "forward_backward"):
        assert f"/{scope}/" in text, scope
    compiled = lowered.compile().as_text()
    calls = re.findall(
        r"^\s*(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', compiled, re.M,
    )
    assert len(calls) >= 6
    assert all(re.match(r"^%?attn(\.|$)", name) for name in calls)
    op_map = op_names(compiled)
    assert op_map["module"] == "jit_step_fn"
    stacks = op_map["op_names"]
    assert all(name in stacks for name in calls)
    assert any("/optimizer/" in s for s in stacks.values())
    assert any("/loss_head/" in s for s in stacks.values())


# strategy -> mesh axes -> the operand every kernel must see per device
# (batch x heads folded, seq, head dim); batch is 4 and seq 1024
MESH_CASES = {
    # the smoke's four-chip path: XL widths, batch over fsdp=4
    "xl-fsdp4": (
        XL, ("fsdp", {}), {"fsdp": 4}, "bf16[25,1024,64]",
    ),
    # heads over tensor as well (25 heads have no divisor a 2x2 mesh
    # offers): 2 of the batch x 16 of 32 heads per device
    "heads32-fsdp2-tensor2": (
        dict(num_heads=32, hidden_dim=2048, max_seq_len=1024),
        ("mixed_parallel", {"fsdp": 2, "tensor": 2, "data": 1}),
        {"fsdp": 2, "tensor": 2}, "bf16[32,1024,64]",
    ),
}


@pytest.mark.parametrize("case", list(MESH_CASES))
def test_sharded_step_compiles_on_the_mesh(topo, on_tpu, case):
    """GSPMD cannot partition a Mosaic kernel, so under a train step
    built for a mesh the flash kernel must sit in a ``shard_map`` —
    per device its share of batch and heads, no all-gather of q/k/v
    in front of it."""
    from dlrover_tpu.accel.accelerate import _apply_plan_to_model
    from dlrover_tpu.accel.model_context import ModelContext
    from dlrover_tpu.accel.opt_lib import OptimizationLibrary
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.parallel.mesh import build_mesh

    widths, parallel_opt, axes, operand = MESH_CASES[case]
    model, loss_fn, batch = _two_layers(widths)
    context = ModelContext(
        model=model,
        optim_factory=lambda: optax.adamw(3e-4, weight_decay=0.1),
        loss_fn=loss_fn, sample_batch=batch,
    )
    plan = OptimizationLibrary().apply_strategy(
        Strategy(opts=[
            parallel_opt, ("amp_native", {}), ("checkpoint", {}),
            ("module_replace", {"attention": "flash"}),
        ]),
        context,
    )
    mesh = build_mesh(plan.mesh_config, devices=topo.devices)
    assert {a: mesh.shape[a] for a in axes} == axes
    model = _apply_plan_to_model(plan, context)
    assert model.config.attention_impl == "flash"
    optimizer = context.optimizer()
    abs_params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0))
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    _, jit_for = make_train_step(
        lambda p, b: loss_fn(p, b, model=model), optimizer,
        mesh=mesh, rules=plan.param_rules,
    )
    text = jit_for(abs_state).lower(
        abs_state, _shapes(batch, None)
    ).compile().as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) >= 6
    assert all(operand in line for line in calls), calls[0]


def test_olmoe_block_at_published_widths_compiles(one_chip, on_tpu):
    """One OLMoE block (hidden 2048, 16 heads of 128 with QK-norm, 64
    experts of 1024, top-8) forward and backward on 2 x 4096 tokens:
    the flash kernel at seq 4096 / head 128 and the three grouped
    matmul kernels at their tuned tiles, named ``gmm_*`` and lowered
    under the layer's ``moe_experts`` scope (what the benchmark's
    readers join on); the block's temporaries stay under 3 GB."""
    import re

    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.olmoe import Olmoe, OlmoeConfig

    model = Olmoe(OlmoeConfig(
        num_layers=1, attention_impl="flash", remat=True,
        param_dtype=jnp.bfloat16,
    ))
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    abs_params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), seq_len=4096)
    )

    def loss(params, tokens):
        hidden, stats = model.apply(
            {"params": params}, tokens, return_hidden=True,
            return_router_stats=True,
        )
        return hidden.astype(jnp.float32).sum() + stats["z_loss"].sum()

    compiled = jax.jit(jax.grad(loss)).lower(
        _shapes(abs_params, one_chip), tokens
    ).compile()
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    kinds = [re.sub(r"^%|\.\d+$", "", c) for c in calls]
    # three matrices, each forward and both gradients
    for kernel in ("gmm_fwd", "gmm_dlhs", "gmm_drhs"):
        assert kinds.count(kernel) >= 3, calls
    assert kinds.count("attn") >= 3, calls
    stacks = op_names(text)["op_names"]
    assert all(
        "moe_experts" in stacks[c] for c in calls if "gmm_" in c
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30
