"""LFM2-24B-A2B's kernels and the cell's step, COMPILED for a described
TPU v5e (no chip attached, nothing runs): the fixtures and helpers are
``test_tpu_compile.py``'s.  In a file of its own (PR 50's departure
(1): under ``--dist loadfile`` a file is one worker's, and a long file
ends the run).  ``tests/conftest.py`` holds every test to the CPU
backend, so the kernels' agreement with the plain form ON the device
is the builder's chip run (PERF.md, PR 63), not a test."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
from dlrover_tpu.ops.short_conv import short_conv
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)

MIXER = dict(batch=1, seq=8192, c=2048, taps=3)


def _mixer_operands(one_chip, dtype):
    """As ``ShortConv`` holds them: ``W_in``'s ``[b, s, 3 c]`` output
    and the taps."""
    b, s, c, k = (MIXER[n] for n in ("batch", "seq", "c", "taps"))
    return (
        jax.ShapeDtypeStruct((b, s, 3 * c), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((k, c), dtype, sharding=one_chip),
    )


def _moved_outside_the_kernels(compiled):
    """The compiled program's instructions that move a whole ``[b, s,
    c]`` array or more in XLA: a copy, a slice, a pad, a concatenate,
    a transpose or a fusion of that many elements."""
    whole = MIXER["batch"] * MIXER["seq"] * MIXER["c"]
    found = []
    for line in compiled.as_text().splitlines():
        hit = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
            r"(copy|copy-start|slice|pad|concatenate|transpose|fusion)\(",
            line,
        )
        if hit and np.prod([int(n) for n in hit[1].split(",")]) >= whole:
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_the_short_convolution_compiles_at_published_sizes(
    one_chip, on_tpu, dtype
):
    """``y = C * conv3(B * u)`` at (1, 8192, 3 x 2048), forward and
    gradient, for the described chip: ONE kernel each way
    (``bcx_fwd``; ``bcx_bwd``, which needs no forward), the three
    windows read in place and the three gradients written into the
    one ``[b, s, 3 c]`` array (nothing of a ``[b, s, c]`` array's
    size or more is sliced, padded, concatenated or copied outside
    the kernels), legal Mosaic inside the scoped VMEM (no
    ``vmem_limit_bytes`` is asked for)."""
    operands = _mixer_operands(one_chip, dtype)
    forward = jax.jit(short_conv).lower(*operands).compile()
    out = forward.out_info
    assert out.shape == (1, 8192, 2048) and out.dtype == dtype
    assert _calls(forward, "bcx_fwd") == _kernels(forward) == 1
    assert _moved_outside_the_kernels(forward) == []

    def loss(bcu, taps, dy):
        return jnp.vdot(short_conv(bcu, taps).astype(jnp.float32), dy)

    dy = jax.ShapeDtypeStruct(
        (1, 8192, 2048), jnp.float32, sharding=one_chip
    )
    backward = jax.jit(
        jax.grad(loss, argnums=(0, 1))
    ).lower(*operands, dy).compile()
    assert _calls(backward, "bcx_bwd") == 1
    # the cotangent's cast to the output's type is the test's own
    assert _calls(backward, "bcx_fwd") == 0 and _kernels(backward) == 1
    dbcu, dtaps = backward.out_info
    assert dbcu.shape == (1, 8192, 3 * 2048) and dbcu.dtype == dtype
    assert dtaps.shape == (3, 2048) and dtaps.dtype == dtype
    moved = [
        line for line in _moved_outside_the_kernels(backward)
        if "convert" not in line
    ]
    assert moved == [], moved


def test_flash_attention_compiles_at_32_heads_of_64_over_8(
    one_chip, on_tpu
):
    """The attention layers' shape: 8192 tokens, 32 query heads of 64
    over 8 kv heads (head 64 ran at 1024 tokens, groups at head 128
    only): forward, dq and dkv inside the v5e's scoped VMEM."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 32, 64), jnp.bfloat16, sharding=one_chip
    )
    kv = jax.ShapeDtypeStruct(
        (1, 8192, 8, 64), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3


def test_lfm2_step_fits_the_chip(one_chip, on_tpu, tmp_path):
    """The cell's step (``lfm2_24b_a2b_cut``: a dense conv block, then
    ``full, conv, conv, conv`` twice over sparse blocks at the
    published widths, 16 of 64 experts held, the whole tied vocabulary
    of 65536, bf16 state, flash attention, per-block remat, 1 x 8192
    tokens): state + temporaries under the chip's 15.75 GiB, the
    mixer's kernels a conv layer (forward, its remat copy, backward),
    the flash kernels under the module ``attn``, and every scope the
    benchmark's readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.lfm2_moe import (
        ATTENTION,
        CONV,
        Lfm2Moe,
        Lfm2MoeConfig,
        make_lfm2_moe_loss,
    )

    period = (ATTENTION, CONV, CONV, CONV)
    model = Lfm2Moe(Lfm2MoeConfig(
        layer_types=(CONV,) + 2 * period, num_dense_layers=1,
        experts_held=(0, 16), attention_impl="flash", remat=True,
        param_dtype=jnp.bfloat16,
    ))
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    abs_state = jax.eval_shape(
        lambda: TrainState.create(
            model.init_params(jax.random.PRNGKey(0), seq_len=8192),
            optimizer,
        )
    )
    tokens = np.zeros((1, 8192), np.int32)
    compiled, reserved = _compile_and_reserved_hbm(make_train_step(
        make_lfm2_moe_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.554 B parameters x 6 bytes (the norms' scales and the select
    # bias are float32)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 9.32
    print(
        f"lfm2 step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{(2 * reserved - mem.temp_size_in_bytes) / 1e9:.3f} live at "
        f"once, {mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    text = compiled.as_text()
    assert _calls(compiled, "bcx_fwd") == 14
    assert _calls(compiled, "bcx_bwd") == 7
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    found = op_names(text)
    stacks = found["op_names"]
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in each of the two attention blocks; neither
    # runs its forward again
    assert len(flash) == 6
    assert all(
        re.search(r"/block_[15]/full_attn/attn/", stacks[c]) for c in flash
    )
    for scope in (
        "sconv_proj", "sconv_mix", "full_attn", "attn_qkv", "attn_rope",
        "attn_out", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "loss_head",
    ):
        # (bare or inside jax's wrappers: ``jvp(loss_head)``)
        assert any(
            re.search(rf"[/(]{scope}[/)]|/{scope}$", s)
            for s in stacks.values()
        ), scope
    # nothing of the step is left without a name of the program
    assert not found["unnamed"]
