"""MiMo-V2.5's kernels and the cell's step, COMPILED for a described
TPU v5e (no chip attached, nothing runs): the fixtures and helpers are
``test_tpu_compile.py``'s.  In a file of its own so that the suite's
longest file does not grow: under ``--dist loadfile`` a file is one
worker's, and ``test_tpu_compile.py`` ends the run."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    _compile_and_reserved_hbm,
    _expert_kernels,
    _kernels,
    _passes_at_the_static_size,
    _shapes,
    on_tpu,
    one_chip,
    topo,
)

from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)


@pytest.mark.parametrize("kv, window", [(2, 128), (1, None)])
def test_flash_attention_compiles_at_mimos_two_kinds_of_layer(
    one_chip, on_tpu, kv, window
):
    """MiMo-V2.5's attention in the cell: 8192 tokens, 16 query heads
    of 192 | 128; a window layer's over 2 kv heads (groups of 8) under
    a window of 128 with a learned sink, a full layer's over 1 (a
    group of 16: the dkv kernel's group-x temporary) with none:
    forward, dq and dkv compile within the v5e's scoped VMEM, the
    sink one ``[1, 1, 128]`` block more in the forward alone; the
    window is walked one tile back, in chunks of 256 columns (3.97 x
    the scores the band requires)."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 16, 192), jnp.bfloat16, sharding=one_chip
    )
    k = jax.ShapeDtypeStruct(
        (1, 8192, kv, 192), jnp.bfloat16, sharding=one_chip
    )
    v = jax.ShapeDtypeStruct(
        (1, 8192, kv, 128), jnp.bfloat16, sharding=one_chip
    )
    sink = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)

    def loss(q, k, v, sink):
        return fa.flash_attention(
            q, k, v, window=window,
            sink=sink if window is not None else None,
        ).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2, 3))
    ).lower(q, k, v, sink).compile()
    assert _kernels(compiled) == 3
    if window is not None:
        assert fa._tiles_back(1024, window) == 1
        walk = fa.block_schedule(8192, 1024, 1024, True, window)
        assert walk["visited"] == 15 and walk["computed"] == 0.0615234375


def test_mimo_seven_layer_step_fits_the_chip(one_chip, on_tpu, tmp_path):
    """The cell's step (``mimo_v2_5_cut``: a full dense block, five
    window sparse blocks with a sink and a full sparse one at the
    published widths, 16 query heads over 1 | 2 kv heads, 8 of 256
    experts held and none shared, an eighth of the vocabulary, bf16
    state, flash attention, per-block remat, 1 x 8192 tokens): state +
    temporaries under the chip's 15.75 GB, the flash kernels under the
    module ``attn`` inside ``swa`` or ``full_attn``, and every scope
    the benchmark's readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.mimo_v2 import (
        MiMoV2,
        MiMoV2Config,
        make_mimo_v2_loss,
    )

    model = MiMoV2(MiMoV2Config(
        vocab_size=19072, num_heads=16, num_kv_heads=1, swa_num_heads=16,
        swa_num_kv_heads=2, experts_held=(0, 8), sink_init_std=1.0,
        attention_impl="flash", remat=True, param_dtype=jnp.bfloat16,
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
        make_mimo_v2_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.734 B parameters x 6 bytes (the 80 sinks are float32)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 10.41
    # Beside it the chip reserves 3.616 GB for the step's temporaries
    # (offline compile, PR 52; 3,767,550,464 B at PR 50) of which
    # 2.923 GB are live at once (3.464 at PR 50): the combine's
    # gradient to the experts' rows is made after the backward ran
    # the experts again, not beside their hidden rows
    # (``tests/test_tpu_compile.py``, the sarvam step).
    # ``temp_size_in_bytes`` reads the block PLUS its fragmentation
    # (``_compile_and_reserved_hbm``), 4.31 GB where PR 50 read 4.07:
    # a block that holds less at its fullest reads as more
    # fragmentation, so the limit that stood on that figure (4.3 GB)
    # is held on the two it is made of, each under what PR 50 read
    live = 2 * reserved - mem.temp_size_in_bytes
    print(
        f"mimo step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{live / 1e9:.3f} live at once, "
        f"{mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    assert reserved < 3.768e9, f"{reserved / 1e9:.3f} GB where 3.616 was read"
    assert live < 3.464e9, f"{live / 1e9:.3f} GB live where 2.923 was read"
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    stacks = op_names(text)["op_names"]
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in each of seven blocks; no block runs its
    # forward again
    assert len(flash) == 3 * 7
    assert sum("/swa/attn/" in stacks[c] for c in flash) == 3 * 5
    assert sum("/full_attn/attn/" in stacks[c] for c in flash) == 3 * 2
    kinds = [
        re.sub(r"^%|\.\d+$", "", c) for c in calls if c not in flash
    ]
    # six layers' experts (``test_tpu_compile._expert_kernels``: gate,
    # up and the activation one call, ONE gradient to the rows)
    assert {kind: kinds.count(kind) for kind in kinds} == {
        **_expert_kernels(6),
        "gmm_tokens_from_rows": 2 * 6, "gmm_unwritten": 3 * 6,
    }
    assert all(
        "/moe_experts/" in stacks[c] for c in calls
        if re.sub(r"^%|\.\d+$", "", c) in _expert_kernels(6)
    )
    # 65536 assignments + a tile a held expert: no ``add_any`` and no
    # elementwise pass over them between the kernels
    assert not _passes_at_the_static_size(text, stacks, 67584)
    for scope in (
        "attn_qkv", "attn_rope", "attn_sink", "attn_out", "moe_router",
        "moe_dispatch", "moe_experts", "moe_combine",
    ):
        assert any(f"/{scope}/" in s for s in stacks.values()), scope
    assert not any("/moe_shared/" in s for s in stacks.values())
    # the split of the fused projection: v leaves through a fused
    # slice-and-scale, k and q through slices of their own (q's is
    # [8192, 3072]: PERF.md, PR 50), and nothing COPIES the whole
    # [8192, 3712] result
    assert not re.search(r"= bf16\[1,8192,3712\]\S* copy\(", text)
