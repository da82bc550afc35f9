"""AI21-Jamba2-3B's kernels and the cell's step, COMPILED for a
described TPU v5e (no chip attached, nothing runs): the fixtures and
helpers are ``test_tpu_compile.py``'s.  In a file of its own (PR 50's
departure (1): under ``--dist loadfile`` a file is one worker's, and a
long file ends the run).  ``tests/conftest.py`` holds every test to
the CPU backend, so the kernels' agreement with the plain form ON the
device is the builder's chip run (PERF.md, PR 68), not a test."""

import re

import jax
import jax.numpy as jnp
import numpy as np

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    _calls,
    _compile_and_reserved_hbm,
    _kernels,
    _shapes,
    on_tpu,
    one_chip,
    topo,
)

from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops.selective_scan import selective_scan
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)

SCAN = dict(batch=1, seq=8192, lanes=5120, n=16)


def _scan_operands(one_chip):
    b, s, e, n = (SCAN[k] for k in ("batch", "seq", "lanes", "n"))

    def of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (
        of((b, s, e), jnp.bfloat16), of((b, s, e)), of((e, n)),
        of((b, s, n)), of((b, s, n)), of((e,)),
    )


def _as_large_as_the_states(compiled):
    """The compiled program's arrays of ``s x E x N`` elements or
    more: the discretised operands no kernel may leave in HBM."""
    whole = SCAN["seq"] * SCAN["lanes"] * SCAN["n"]
    found = []
    for line in compiled.as_text().splitlines():
        hit = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]", line)
        if hit and np.prod([int(n) for n in hit[1].split(",")]) >= whole:
            found.append(line.strip()[:120])
    return found


def test_the_selective_scan_compiles_at_published_sizes(one_chip, on_tpu):
    """The recurrence at (1, 8192, 5120 x 16), forward and gradient,
    for the described chip: ONE kernel each way (``s6_fwd``;
    ``s6_bwd``, which needs no forward of its own), legal Mosaic, no
    ``[s, E, N]`` array outside the kernels."""
    operands = _scan_operands(one_chip)
    forward = jax.jit(selective_scan).lower(*operands).compile()
    y, final = forward.out_info
    assert y.shape == (1, 8192, 5120) and y.dtype == jnp.bfloat16
    assert final.shape == (1, 5120, 16) and final.dtype == jnp.float32
    assert _calls(forward, "s6_fwd") == _kernels(forward) == 1
    assert _as_large_as_the_states(forward) == []

    def loss(*operands):
        y, final = selective_scan(*operands)
        return y.astype(jnp.float32).sum() + final.sum()

    backward = jax.jit(
        jax.grad(loss, argnums=tuple(range(6)))
    ).lower(*operands).compile()
    assert _calls(backward, "s6_fwd") == _calls(backward, "s6_bwd") == 1
    assert _kernels(backward) == 2
    assert _as_large_as_the_states(backward) == []
    for got, operand in zip(backward.out_info, operands):
        assert got.shape == operand.shape and got.dtype == operand.dtype


def test_flash_attention_compiles_at_20_heads_over_one(one_chip, on_tpu):
    """The attention layer's shape: 8192 tokens, 20 query heads of 128
    in ONE group over one kv head (the cells' largest group was 16):
    forward, dq and dkv (the group-fold of ``dk``, ``dv`` at 20)
    inside the v5e's scoped VMEM."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 20, 128), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, 8192, 1, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3


def test_jamba_step_fits_the_chip(one_chip, on_tpu, tmp_path):
    """The cell's step (``jamba2_3b_cut``: published layers 0-13, one
    period: thirteen Mamba-1 layers and the attention layer at index
    7, every width and the tied vocabulary of 65536 whole, bf16 state,
    flash attention, per-block remat, 1 x 8192 tokens): state +
    temporaries under the chip's 15.75 GiB, the scan's kernels ONCE a
    layer each way (the remat policy keeps what ``s6_fwd`` wrote), the
    convolution's forward, its remat copy and backward, the flash
    kernels under the module ``attn``, no ``[s, E, N]`` array in HBM,
    and every scope the benchmark's readers join on in the op-name
    map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.jamba import (
        ATTENTION,
        MAMBA,
        Jamba,
        JambaConfig,
        make_jamba_loss,
    )

    config = JambaConfig(
        layer_types=JambaConfig.layers_block_type(14, 14, 7),
        attention_impl="flash", remat=True, param_dtype=jnp.bfloat16,
    )
    assert config.layer_types.count(MAMBA) == 13
    assert config.layer_types[7] == ATTENTION
    model = Jamba(config)
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    abs_state = jax.eval_shape(
        lambda: TrainState.create(
            model.init_params(jax.random.PRNGKey(0), seq_len=8192),
            optimizer,
        )
    )
    tokens = np.zeros((1, 8192), np.int32)
    compiled, reserved = _compile_and_reserved_hbm(make_train_step(
        make_jamba_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1,598,556,096 parameters x 6 bytes = 9.591 GB, and a little more
    # for the 1,341,376 float32 ones (A_log, D, the two biases and the
    # norms' scales)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 9.60
    print(
        f"jamba step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{(2 * reserved - mem.temp_size_in_bytes) / 1e9:.3f} live at "
        f"once, {mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    text = compiled.as_text()
    # once a layer each way: no second forward
    assert _calls(compiled, "s6_fwd") == 13
    assert _calls(compiled, "s6_bwd") == 13
    assert _calls(compiled, "conv_fwd") == 26
    assert _calls(compiled, "conv_bwd") == 13
    assert _as_large_as_the_states(compiled) == []
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    found = op_names(text)
    stacks = found["op_names"]
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in the one attention block, no forward again
    assert len(flash) == 3
    assert all(
        re.search(r"/block_7/full_attn/attn/", stacks[c]) for c in flash
    )
    for scope in (
        "s6_in_proj", "s6_conv", "s6_x_proj", "s6_params", "s6_scan",
        "s6_gate", "s6_out_proj", "full_attn", "loss_head",
    ):
        # (bare or inside jax's wrappers: ``jvp(loss_head)``)
        assert any(
            re.search(rf"[/(]{scope}[/)]|/{scope}$", s)
            for s in stacks.values()
        ), scope
    # nothing of the step is left without a name of the program
    assert not found["unnamed"]
