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
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import SingleDeviceSharding

from dlrover_tpu.common.aot_cache import COMPILER_OPTIONS, compile_lowered
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


def _compile_and_reserved_hbm(lowered, dump_dir):
    """``(compiled, bytes)``: the program compiled as the engine does,
    and the buffer assignment's preallocated temporary in HBM, the one
    block the chip reserves for the step's temporaries (the TPU
    compiler's "HLO temp").  ``memory_analysis().temp_size_in_bytes``
    is NOT that: libtpu reports the block PLUS its fragmentation, the
    block less the most that is live in it at once (its log says
    "HLO temp 3.72G (.. Padded (3.31G), 10.9% fragmentation
    (415.19M))" where the figure reads 3.72 + 0.41 = 4.12 GiB), so a
    looser packing of a SMALLER block reads as more (PERF.md, PR 49).
    The block's size comes from the compile's own dump."""
    compiled = lowered.compile(compiler_options={
        **COMPILER_OPTIONS, "xla_dump_to": str(dump_dir),
    })
    (report,) = dump_dir.glob("*after_optimizations-buffer-assignment.txt")
    (reserved,) = re.findall(
        r"^allocation \d+: size (\d+), preallocated-temp:$",
        report.read_text(), re.M,
    )
    shutil.rmtree(dump_dir)
    return compiled, int(reserved)


def _passes_at_the_static_size(text, stacks, rows: int, scope="moe_experts"):
    """The compiled program's instructions under ``scope`` (fused
    ones too) whose result holds an array of ``rows`` padded rows by
    a width and that are no Pallas call, nor one's result taken out
    of its tuple: XLA's own passes over the experts' rows at their
    static size, most of which belong to no expert.  Until PR 52 the
    activation between the grouped matmuls and ``add_any``'s sum of
    the two gradients to the rows were such passes; since then the
    kernels do that work on the used tiles."""
    found = []
    for line in text.splitlines():
        m = re.match(
            r"^\s*(?:ROOT )?(%[\w\-.]+) = (.+?[\}\)\]]) ([a-z][\w\-]*)\(",
            line,
        )
        if (
            m and re.search(rf"\[{rows},\d+\]", m[2])
            and f"/{scope}/" in stacks.get(m[1], "")
            and m[3] not in ("custom-call", "get-tuple-element", "bitcast")
        ):
            found.append(f"{m[3]} {m[2][:40]} {m[1]}")
    found += [
        s for s in stacks.values() if f"/{scope}/" in s and "add_any" in s
    ]
    return found


# an expert layer's kernels in a step whose blocks are rematted, by
# name (PR 52): gate, up and the activation are ONE call forward and
# one in the remat copy (``gmm_up_fwd``), down is ``gmm_fwd`` twice;
# backward the down projection's gradient to its rows with the
# activation's derivative as its epilogue (``gmm_down_dlhs``) and ONE
# gradient to the rows, over two pairs of operands (``gmm_up_dlhs``)
# or, without a gate matrix, the plain ``gmm_dlhs``; a ``gmm_drhs`` a
# matrix
def _expert_kernels(layers: int, gated: bool = True):
    return {
        "gmm_up_fwd": 2 * layers, "gmm_fwd": 2 * layers,
        "gmm_down_dlhs": layers,
        ("gmm_up_dlhs" if gated else "gmm_dlhs"): layers,
        "gmm_drhs": (3 if gated else 2) * layers,
    }


def _head_matmul_shapes(text):
    """The result shapes, sorted, of the matmuls (``convolution`` on
    the TPU) that the compiled program holds under the scope
    ``loss_head``.  The chunked head's all sit in its scan's body, so
    each runs once a chunk, and none is jax's recomputation."""
    found = re.findall(
        r"^\s*%[\w.\-]+ = (\w+\[[\d,]*\])[^\n]*? convolution\("
        r'[^\n]*op_name="([^"]*loss_head[^"]*)"', text, re.M,
    )
    assert all("/while/body/" in stack for _, stack in found)
    assert not any("rematted_computation" in stack for _, stack in found)
    return sorted(shape for shape, _ in found)


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
    compiled = compile_lowered(make_train_step(loss_fn, optimizer).lower(
        _shapes(abs_state, one_chip), _shapes(batch, one_chip)
    ))
    # per layer: forward, dq, dkv (the block's remat copy of the
    # forward merges with it: prevent_cse=False)
    assert _kernels(compiled) == 6
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2**30


def test_where_the_compiler_may_merge_the_kept_names_cost_nothing(
    one_chip, on_tpu, monkeypatch
):
    """A rematted flash caller with no cell (``llama.py``, grouped kv
    heads): its blocks sit behind ``prevent_cse=False`` like GPT's
    and OLMoE's, the compiler merges the remat copy with the forward,
    and the step asks the bytes it asks under ``policy=None``.  The
    five kept arrays cost memory only behind ``prevent_cse=True``,
    and each of those four families has a cell and a limit here."""
    from dlrover_tpu.models import layers
    from dlrover_tpu.models.llama import Llama, LlamaConfig

    model = Llama(LlamaConfig(
        vocab_size=32000, num_layers=2, num_heads=16, num_kv_heads=4,
        hidden_dim=2048, intermediate_dim=5632, remat=True,
        attention_impl="flash", param_dtype=jnp.bfloat16,
    ))
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    tokens = np.zeros((1, 4096), np.int32)
    abs_state = jax.eval_shape(
        lambda: TrainState.create(
            model.init_params(jax.random.PRNGKey(0), 1, seq_len=4096),
            optimizer,
        )
    )

    def step_memory():
        # a function of its own each time: a trace is cached by it
        def loss_fn(params, batch):
            logits = model.apply({"params": params}, batch["x"])
            return cross_entropy_loss(logits, batch["y"])

        compiled = compile_lowered(
            make_train_step(loss_fn, optimizer).lower(
                _shapes(abs_state, one_chip),
                _shapes({"x": tokens, "y": tokens}, one_chip),
            )
        )
        return _kernels(compiled), compiled.memory_analysis()

    kernels, kept = step_memory()
    monkeypatch.setattr(layers, "remat_policy", lambda name: None)
    parents_kernels, parents = step_memory()
    assert parents_kernels == kernels == 6
    assert parents.temp_size_in_bytes == kept.temp_size_in_bytes


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
    # three matrices under the names the benchmark's readers join on
    # (their index maps hold a tile of no group on the last used one:
    # that lowers for the chip).  ONE up and ONE down projection
    # forward: this family's blocks are rematted with
    # ``prevent_cse=False`` and XLA merges a block's first pass with
    # its remat copy, which are the same calls: the forward rule's,
    # in both (a first pass that wrote the hidden rows alone, tried
    # through ``custom_dce`` at PR 52, no longer merged, and
    # ``olmoe_steady_4k`` lost 5.5%: PERF.md section 6)
    assert {k: kinds.count(k) for k in kinds if k.startswith("gmm_")} == {
        kind: count // 2 if kind in ("gmm_up_fwd", "gmm_fwd") else count
        for kind, count in _expert_kernels(1).items()
    }, calls
    assert kinds.count("attn") >= 3, calls
    stacks = op_names(text)["op_names"]
    assert all(
        "moe_experts" in stacks[c] for c in calls if "gmm_" in c
    )
    # 65536 assignments + a tile an expert: nothing of XLA's passes
    # over them between the kernels
    assert not _passes_at_the_static_size(text, stacks, 81920)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


# -- Olmo-Hybrid: the rule, flash attention at its shapes, the one-period step ----

HYBRID_ATTN = (1, 8192, 30, 128)
HYBRID_RULE = dict(batch=1, seq=8192, heads=30, dk=96, dv=192)


def _rule_operands(one_chip, dtype=jnp.bfloat16):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, t, h = (HYBRID_RULE[k] for k in ("batch", "seq", "heads"))
    keys = s((b, t, h, HYBRID_RULE["dk"]), dtype)
    values = s((b, t, h, HYBRID_RULE["dv"]), dtype)
    gate = s((b, t, h), jnp.float32)
    return keys, keys, values, gate, gate


def test_flash_attention_compiles_at_olmo_hybrids_shape(one_chip, on_tpu):
    """30 heads of 128 over 8192 tokens, forward and backward: the
    full-attention layer of ``olmo_hybrid_steady_8k``."""
    x = jax.ShapeDtypeStruct(HYBRID_ATTN, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    forward = jax.jit(fa.flash_attention).lower(x, x, x).compile()
    assert _kernels(forward) >= 1
    backward = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(x, x, x).compile()
    assert _kernels(backward) >= 3


def _calls(compiled, name) -> int:
    """The compiled program's Pallas calls whose name holds ``name``
    (``%jvp_gdn_fwd_.1``, ``%transpose_jvp_gdn_bwd__.1``)."""
    return len(re.findall(
        rf"^\s*(?:ROOT )?%[\w.\-]*{name}[\w.\-]* = [^\n]*custom_call_target="
        r'"tpu_custom_call"', compiled.as_text(), re.M,
    ))


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_gated_delta_rule_compiles_at_published_sizes(
    one_chip, on_tpu, dtype
):
    """The rule at (1, 8192, 30, 96 / 192), forward and backward, for
    the described chip: the forward is the ``gdn_fwd`` kernel and no
    ``while`` (the hand-over is the kernel's chunk axis), the gradient
    adds ``gdn_bwd``, and both stay inside Mosaic's scoped-VMEM limit
    (no ``vmem_limit_bytes`` is asked for: a kernel over the default
    16 MB fails to compile).  bf16 is the cell's; float32 operands
    (every matmul at ``HIGHEST``) are the tests' exact path."""
    from dlrover_tpu.ops import gated_delta_rule as gdr

    operands = _rule_operands(one_chip, dtype)
    forward = jax.jit(gdr.gated_delta_rule).lower(*operands).compile()
    out, state = forward.out_info
    assert out.shape == (1, 8192, 30, 192) and out.dtype == dtype
    assert state.shape == (1, 30, 96, 192) and state.dtype == jnp.float32
    assert _calls(forward, "gdn_fwd") == _kernels(forward) == 1
    text = forward.as_text()
    assert " while(" not in text
    # the states each chunk starts from and its inverse, for the
    # backward, in the operands' type
    hlo = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    chunks = f"{hlo}[30,{8192 // gdr.CHUNK}"
    assert f"{chunks},96,192]" in text
    assert f"{chunks},{gdr.CHUNK},{gdr.CHUNK}]" in text

    def loss(*a):
        return gdr.gated_delta_rule(*a)[0].astype(jnp.float32).sum()

    backward = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    ).lower(*operands).compile()
    assert _calls(backward, "gdn_fwd") == 1
    assert _calls(backward, "gdn_bwd") == 1
    assert _kernels(backward) == 2
    assert " while(" not in backward.as_text()
    # one layer's rule and its gradient: the XLA form of PR 32 took
    # 3.8 GB of temporaries under a bound of 4.5 GiB
    temp = backward.memory_analysis().temp_size_in_bytes
    print(f"gdn backward temporaries {hlo}: {temp / 2**30:.3f} GiB")
    # 0.73 GiB in bf16, 1.47 in float32
    assert temp < 2.0 * 2**30


def test_flash_attention_compiles_at_two_head_sizes(one_chip, on_tpu):
    """Latent attention's shape in the cell: 16 heads x 8192 tokens, q
    and k of 192 (one and a half lane tiles), v of 128, bf16, a scale
    that is no power of two: forward, dq and dkv compile for the v5e,
    a quarter of the sequence resident a grid step."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 16, 192), jnp.bfloat16, sharding=one_chip
    )
    v = jax.ShapeDtypeStruct(
        (1, 8192, 16, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, scale=0.1352
        ).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, q, v).compile()
    assert _kernels(compiled) == 3
    assert fa.resident_rows(8192, 1024, 192, 2, 128) == 2048


@pytest.mark.parametrize("heads, window", [(72, 512), (48, None)])
def test_flash_attention_compiles_at_lagunas_two_kinds_of_layer(
    one_chip, on_tpu, heads, window
):
    """Laguna's attention in the cell: 8192 tokens, 8 kv heads of 128,
    72 query heads under a window of 512 (a group of 9) and 48 without
    (a group of 6): forward, dq and dkv compile within the v5e's
    scoped VMEM, the windowed ones one tile of K and V a grid step
    over a last grid axis of two (the own tile and the one before)."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, heads, 128), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, 8192, 8, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, window=window
        ).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3
    assert fa.resident_rows(8192, 1024, 128, 2, window=window) == (
        4096 if window is None else 1024
    )
    if window is not None:
        assert fa._tiles_back(1024, window) == 1


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_state_space_scan_compiles_at_published_sizes(
    one_chip, on_tpu, dtype
):
    """The scan at (1, 8192, 64 heads of 64, 8 groups, state 128),
    forward and backward, for the described chip: the forward is the
    ``ssd_fwd`` kernel and no ``while`` (the hand-over is the kernel's
    chunk axis), the gradient adds ``ssd_bwd``, both inside Mosaic's
    scoped-VMEM limit with eight heads a grid step, and what lives
    between them is the float32 chunk-start states and nothing
    ``chunk x chunk``.  bf16 is the cell's; float32 operands (every
    matmul at ``HIGHEST``) are the tests' exact path."""
    from dlrover_tpu.ops.ssd import ssd_scan

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (
        s((1, 8192, 64, 64), dtype), s((1, 8192, 64), jnp.float32),
        s((64,), jnp.float32), s((1, 8192, 8, 128), dtype),
        s((1, 8192, 8, 128), dtype),
    )
    forward = jax.jit(ssd_scan).lower(*operands).compile()
    out, state = forward.out_info
    assert out.shape == (1, 8192, 64, 64) and out.dtype == dtype
    assert state.shape == (1, 64, 64, 128) and state.dtype == jnp.float32
    assert _calls(forward, "ssd_fwd") == _kernels(forward) == 1
    assert " while(" not in forward.as_text()

    def loss(*a):
        y, state = ssd_scan(*a)
        return y.astype(jnp.float32).sum() + state.sum()

    backward = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    ).lower(*operands).compile()
    assert _calls(backward, "ssd_fwd") == 1
    assert _calls(backward, "ssd_bwd") == 1
    assert _kernels(backward) == 2
    text = backward.as_text()
    assert " while(" not in text
    # the states 64 chunks start from, a group's heads side by side
    assert "f32[1,64,8,128,512]" in text
    assert not re.search(r"\[[\d,]*,128,128\]", text)
    # 0.19 GiB in bf16, 0.38 in float32 (the XLA form's 268 MB
    # chunk-square arrays, several at a time, are gone)
    temp = backward.memory_analysis().temp_size_in_bytes
    print(f"ssd backward temporaries {dtype.__name__}: {temp / 2**30:.3f} GiB")
    assert temp < 0.5 * 2**30


# (operand's lanes, first lane, width, bias, output): the state-space
# mixer's x and C out of its projection, the hybrid's q / k and v
CONV_SHAPES = {
    "ssm-x": (10304, 4096, 4096, True, jnp.bfloat16),
    "ssm-C": (10304, 9216, 1024, True, jnp.bfloat16),
    "gdn-qk": (2880, 0, 2880, False, jnp.float32),
    "gdn-v": (5760, 0, 5760, False, jnp.bfloat16),
}


@pytest.mark.parametrize(
    "shape", CONV_SHAPES.values(), ids=CONV_SHAPES.keys()
)
def test_causal_conv_compiles_at_both_cells_shapes(one_chip, on_tpu, shape):
    """``conv_fwd`` and ``conv_bwd`` at 1 x 8192 tokens for the
    described chip: a window of 1024-lane blocks read in place out of
    ``[.., 10304]`` (80.5 lane tiles), a whole width of 22.5 lane
    tiles in one block with float32 out, 45 lane tiles in blocks of
    five; the gradient is ONE kernel (the residuals are the operands)
    and the only large temporary is the window's gradient laid into
    the operand's lanes."""
    from dlrover_tpu.ops.causal_conv import causal_conv

    total, first, c, bias, out = shape

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = [s((1, 8192, total), jnp.bfloat16), s((4, c), jnp.float32)]
    if bias:
        operands.append(s((c,), jnp.float32))

    def conv(x, taps, bias=None):
        return causal_conv(x, taps, bias, first=first, dtype=out)

    forward = jax.jit(conv).lower(*operands).compile()
    (y,) = jax.tree.leaves(forward.out_info)
    assert y.shape == (1, 8192, c) and y.dtype == out
    assert _calls(forward, "conv_fwd") == _kernels(forward) == 1
    assert not re.search(r" (pad|slice)\(", forward.as_text())

    backward = jax.jit(jax.grad(
        lambda *a: conv(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(operands))),
    )).lower(*operands).compile()
    assert _calls(backward, "conv_bwd") == _kernels(backward) == 1
    grads = jax.tree.leaves(backward.out_info)
    assert [g.dtype for g in grads] == [o.dtype for o in operands]
    assert f"f32[8,{c}]" in backward.as_text()
    # nothing float32 of the sequence's size, nothing padded by rows
    assert not re.search(r"f32\[1,81\d\d,", backward.as_text()) or (
        out == jnp.float32
    )
    assert not re.search(r"\[1,8195,", backward.as_text())


def test_chunked_head_compiles_at_olmoes_shapes(one_chip):
    """The head alone at ``olmoe_steady_4k``'s shapes (2 x 4096 rows of
    2048 against a 50304-word vocabulary, bf16, 8 chunks): value and
    both gradients hold three vocabulary-sized matmuls a chunk, all in
    the scan's body, none recomputed, and one chunk's float32 logits
    (206 MB) and their gradient are the vocabulary-sized
    temporaries."""
    from dlrover_tpu.models.losses import chunked_cross_entropy

    hidden = jax.ShapeDtypeStruct(
        (2, 4096, 2048), jnp.bfloat16, sharding=one_chip
    )
    kernel = jax.ShapeDtypeStruct(
        (2048, 50304), jnp.bfloat16, sharding=one_chip
    )
    targets = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda h, k, t: chunked_cross_entropy(h, k, t, num_chunks=8),
        argnums=(0, 1),
    )).lower(hidden, kernel, targets).compile()
    assert _head_matmul_shapes(compiled.as_text()) == [
        "bf16[1024,2048]", "f32[1024,50304]", "f32[2048,50304]",
    ]
    # one chunk's float32 logits, their bf16 gradient and the
    # chunk-major copies of hidden and d_hidden: under two chunks of
    # logits
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 206_045_184
