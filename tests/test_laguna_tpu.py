"""The ``laguna`` cell's step, COMPILED for a described TPU v5e (no chip
attached, nothing runs): the fixtures and helpers are
``test_tpu_compile.py``'s.  In a file of its own: under ``--dist
loadfile`` a file is one worker's, and a family's offline compile is
the longest test it has."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    _compile_and_reserved_hbm,
    _expert_kernels,
    _passes_at_the_static_size,
    _shapes,
    on_tpu,
    one_chip,
    topo,
)

from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)


def test_laguna_one_dense_four_sparse_step_fits_the_chip(
    one_chip, on_tpu, tmp_path
):
    """The cell's step (``laguna_s_2_1_cut``: a full dense block, three
    sliding sparse blocks and a full sparse one at the published
    widths, 16 of 256 experts held, an eighth of the vocabulary, bf16
    state, flash attention, per-block remat, 1 x 8192 tokens): state +
    temporaries under the chip's 15.75 GB, the flash kernels under the
    module ``attn`` inside ``swa`` or ``full_attn``, the grouped
    matmuls (hidden 3072 in tiles of 1536) under ``moe_experts``, and
    every scope the benchmark's readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.laguna import (
        FULL,
        SLIDING,
        Laguna,
        LagunaConfig,
        make_laguna_loss,
    )

    model = Laguna(LagunaConfig(
        vocab_size=12544,
        layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
        heads_per_layer=(48, 72, 72, 72, 48),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
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
        make_laguna_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.113 B parameters x 6 bytes
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 6.68
    # 4.11 GB (4,108,032,000 B).  2.96 before a block kept its
    # kernel's ``out`` and ``lse`` (3 x 151 + 2 x 101 + 10 MB = 0.67
    # GB: 3.63, PR 44); since PR 45 it keeps q, k and v too: q as
    # ``out`` (0.65 GB), k and v at the 8 kv heads (5 x 2 x 16.8 MB =
    # 0.17 GB), 0.82 GB kept for 0.48 GB more, because the backward
    # of the block at the peak held its remat copy's q, k and v there
    # before.  That figure is the reserved block PLUS its
    # fragmentation (``_compile_and_reserved_hbm``): 3.901 GB reserved
    # (3,901,096,448 B) with 3.694 live at once.  Since PR 52:
    # 3.881 reserved, 3.141 live at once (the combine's gradient to
    # the experts' rows is made after the backward ran the experts
    # again, not beside their hidden rows: the sarvam step's test
    # above), and the FIGURE reads 4.62 GB, because a block that
    # holds less at its fullest reads as more fragmentation.  So the
    # limit that stood on the figure (4 GiB) is held on the two it is
    # made of, each under what the parent read (offline compile, PR
    # 52; PERF.md section 7)
    live = 2 * reserved - mem.temp_size_in_bytes
    print(
        f"laguna step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{live / 1e9:.3f} live at once, "
        f"{mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    assert reserved < 3.901e9, f"{reserved / 1e9:.3f} GB where 3.881 was read"
    assert live < 3.694e9, f"{live / 1e9:.3f} GB live where 3.141 was read"
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
    # forward, dq, dkv in each of five blocks: 9 in the sliding layers,
    # 6 in the full ones; no block runs its forward again
    assert len(flash) == 3 * 5
    assert sum("/swa/attn/" in stacks[c] for c in flash) == 3 * 3
    assert sum("/full_attn/attn/" in stacks[c] for c in flash) == 3 * 2
    for block, scope in enumerate(
        ("full_attn", "swa", "swa", "swa", "full_attn")
    ):
        assert sum(
            f"/block_{block}/{scope}/attn/" in stacks[c] for c in flash
        ) == 3
    kinds = [
        re.sub(r"^%|\.\d+$", "", c) for c in calls if c not in flash
    ]
    assert {kind: kinds.count(kind) for kind in kinds} == {
        **_expert_kernels(4),
        "gmm_tokens_from_rows": 2 * 4, "gmm_unwritten": 3 * 4,
    }
    assert all(
        "/moe_experts/" in stacks[c] for c in calls
        if re.sub(r"^%|\.\d+$", "", c) in _expert_kernels(4)
    )
    # no array of every assignment's row, forward or backward
    assert not re.search(r"\[8192,10,3072\]|\[81920,3072\]", text)
    # ... and, of the 81920 + 16 tiles of padded rows, no ``add_any``
    # and no elementwise pass between the experts' kernels
    assert not _passes_at_the_static_size(text, stacks, 86016)
    for scope in (
        "attn_rope", "attn_gate", "moe_router", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared",
    ):
        assert any(f"/{scope}/" in s for s in stacks.values()), scope
