"""Motif-3-Beta's kernels and the cell's step, COMPILED for a described
TPU v5e (no chip attached, nothing runs): the fixtures and helpers are
``test_tpu_compile.py``'s.  In a file of its own (PR 50's departure
(1): under ``--dist loadfile`` a file is one worker's, and a long file
ends the run)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    _compile_and_reserved_hbm,
    _kernels,
    _shapes,
    on_tpu,
    one_chip,
    topo,
)

from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import grouped_matmul as gmm
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)


@pytest.mark.parametrize("window", [128, None])
def test_flash_attention_compiles_at_a_group_of_five(one_chip, on_tpu, window):
    """The cell's attention: 8192 tokens, 20 query heads of 192 | 128
    over 4 latent kv heads, a GQA group of FIVE (4 signal heads and 1
    noise head; the other cells run 6, 8, 9 and 16), a window of 128
    WITHOUT a sink or none: forward, dq and dkv compile within the
    v5e's scoped VMEM."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 20, 192), jnp.bfloat16, sharding=one_chip
    )
    k = jax.ShapeDtypeStruct(
        (1, 8192, 4, 192), jnp.bfloat16, sharding=one_chip
    )
    v = jax.ShapeDtypeStruct(
        (1, 8192, 4, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, window=window
        ).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, k, v).compile()
    assert _kernels(compiled) == 3


def test_polynorm_experts_compile_at_one_block_of_1280(one_chip, on_tpu):
    """The held layer's experts at the cell's size (8 experts of 4096
    x 1280, 67584 padded rows): ``gmm_up_fwd`` takes the gate's and
    the up matrix's WHOLE width in one column block each (two
    double-buffered 10.5 MB blocks beside the row tile: ``_fit_tile``
    would split 1280 into two blocks of 640 for an element-wise
    activation), ``gmm_down_dlhs`` hands the coefficients' sums back,
    and both fit the v5e's VMEM."""
    groups, d, m = 8, 4096, 1280
    rows = (8192 * 8 // gmm.ROW_TILE + groups) * gmm.ROW_TILE
    tiles = rows // gmm.ROW_TILE

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w_gate, w_up, w_down, coeffs, tile_group, tiles_used):
        return gmm.grouped_expert(
            x, w_gate, w_up, w_down, tile_group, tiles_used, coeffs=coeffs
        ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        abstract((rows, d)), abstract((groups, d, m)),
        abstract((groups, d, m)), abstract((groups, m, d)),
        abstract((4,), jnp.float32), abstract((tiles,), jnp.int32),
        abstract((1,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    # up_fwd, down_dlhs, up_dlhs and three drhs (the down projection's
    # forward feeds nothing of a gradient and is dropped)
    assert _kernels(compiled) == 6
    # the sums leave their kernel a tile at a time
    assert f"f32[{tiles},8,128]" in text


def test_motif_step_fits_the_chip(one_chip, on_tpu, tmp_path):
    """The cell's step (``motif_3_beta_cut``: a window dense block,
    three window sparse blocks, a full sparse one and the prediction
    layer's at the published widths, 4 streams, 20 query heads over 4
    latent kv heads, 8 of 384 experts held + the shared one, an
    eighth of the vocabulary through TWO passes of the head, bf16
    state, flash attention, per-block remat, 1 x 8192 tokens): state +
    temporaries under the chip's 15.75 GiB, the flash kernels under
    the module ``attn`` inside ``swa`` or ``full_attn``, and every
    scope the benchmark's readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.motif import Motif, MotifConfig, make_motif_loss

    model = Motif(MotifConfig(
        vocab_size=27520, num_heads=20, num_kv_heads=4, num_noise_heads=4,
        experts_held=(0, 8), attention_impl="flash", remat=True,
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
        make_motif_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.298 B parameters x 6 bytes (norms, alpha, biases and
    # PolyNorm's scalars are float32)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 7.79
    print(
        f"motif step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{(2 * reserved - mem.temp_size_in_bytes) / 1e9:.3f} live at "
        f"once, {mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    # offline compile, PR 57: 6.332 GB reserved, 5.591 live at once
    # (7.072 reported: the block plus its fragmentation,
    # ``_compile_and_reserved_hbm``); ``mimo_v2_5_cut`` reserves
    # 3.616: the four streams' kept block inputs (268 MB for 67) and
    # the stream-wide arrays of a block's backward are the difference
    assert reserved < 6.5e9, f"{reserved / 1e9:.3f} GB where 6.332 was read"
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    found = op_names(text)
    stacks = found["op_names"]
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in each of six blocks; no block runs its
    # forward again
    assert len(flash) == 3 * 6
    assert sum("/swa/attn/" in stacks[c] for c in flash) == 3 * 4
    assert sum("/mtp/" in stacks[c] for c in flash) == 3
    for scope in (
        "mhc_coeff", "mhc_sinkhorn", "mhc_mix", "gdla_q_latent", "gdla_kv",
        "gdla_rope", "gdla_diff", "gdla_gate", "gdla_out", "polynorm",
        "mtp", "moe_experts", "moe_shared", "loss_head",
    ):
        assert any(f"/{scope}/" in s or s.endswith(f"/{scope}")
                   for s in stacks.values()), scope
