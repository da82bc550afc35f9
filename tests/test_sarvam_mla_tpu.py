"""The ``sarvam_mla`` cell's step, COMPILED for a described TPU v5e (no chip
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


def test_sarvam_one_dense_four_expert_step_fits_the_chip(
    one_chip, on_tpu, tmp_path
):
    """The cell's step (``sarvam_105b_cut``: the leading dense block
    and four expert blocks at the published widths, 16 heads and 8 of
    128 experts held, an eighth of the vocabulary, bf16 state, flash
    attention at 192 | 128, per-block remat, 1 x 8192 tokens): state +
    temporaries under the chip's 15.75 GB, the flash kernels under the
    module ``attn``, the grouped matmuls under ``moe_experts``, and
    every scope the benchmark's readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.sarvam_mla import (
        SarvamMla,
        SarvamMlaConfig,
        make_sarvam_mla_loss,
    )

    model = SarvamMla(SarvamMlaConfig(
        vocab_size=32768, num_layers=5, num_heads_held=16,
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
        make_sarvam_mla_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.505 B parameters x 6 bytes
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 9.03
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    # the block's remat holds (left to the compiler's CSE the step
    # asked for 7.8 GB and did not fit: offline compile, PR 35).
    # 4.24 GB (4,236,185,088 B) until PR 52: 3.57 with ``out`` and
    # ``lse`` kept (PR 44) + q and k at 16 x 8192 x 192 and v at 128,
    # bf16: 134 MB a layer x 5 = 0.67 GB, all of it (3,573,515,776 B
    # before).  That figure is the reserved block PLUS its
    # fragmentation (``_compile_and_reserved_hbm``): the block was
    # 3.927 GB (3,927,294,464 B) with 3.618 live in it at once.
    # Since PR 52: 3.779 reserved, 3.074 live (4.485 reported: a
    # block that holds less at its fullest reads as more
    # fragmentation).  The fullest moment was the backward's second
    # run of the last block's experts with the combine's gradient to
    # their rows ALREADY made beside them; it is made after them now
    # (``parallel/moe.py::_held_combine_bwd``), in the place of the
    # rows it is the gradient of.  Both held under what the parent
    # read (offline compile, PR 52; PERF.md section 7)
    live = 2 * reserved - mem.temp_size_in_bytes
    print(
        f"sarvam step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{live / 1e9:.3f} live at once, "
        f"{mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    assert mem.temp_size_in_bytes < 5 * 2**30
    assert reserved < 3.927e9, f"{reserved / 1e9:.3f} GB where 3.779 was read"
    assert live < 3.618e9, f"{live / 1e9:.3f} GB live where 3.074 was read"
    text = compiled.as_text()
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in each of five blocks: a block keeps what the
    # backward kernels read of the forward (5 x 33.8 MB: the
    # temporaries are 3.57 GB where they were 3.44, PR 44)
    assert len(flash) == 3 * 5
    stacks = op_names(text)["op_names"]
    grouped = [c for c in calls if c not in flash]
    # four layers' experts (``_expert_kernels``) under the names the
    # benchmark's readers join on; the rows back to their tokens in
    # the combine's forward and the dispatch's backward; a buffer for
    # the walk of the used tiles to fill in the dispatch's forward,
    # its remat copy and the combine's backward
    kinds = [re.sub(r"^%|\.\d+$", "", c) for c in grouped]
    assert {kind: kinds.count(kind) for kind in kinds} == {
        **_expert_kernels(4),
        "gmm_tokens_from_rows": 2 * 4, "gmm_unwritten": 3 * 4,
    }
    for call, kind in zip(grouped, kinds):
        if kind in _expert_kernels(4):
            assert "/moe_experts/" in stacks[call]
        else:
            assert re.search("/moe_(dispatch|combine)/", stacks[call]), call
    # both passes of a rematted block run the forward RULE: all eight
    # up calls write the gate's two products beside the hidden rows,
    # and the first pass's are dropped unread (0.09 ms a call here)
    assert set(re.findall(
        r"^\s*%gmm_up_fwd[.\d]* = (\(?)bf16\[67584,2048\]", text, re.M
    )) == {"("}
    # 65536 assignments + a tile a held expert: no ``add_any`` and no
    # elementwise pass over them between the kernels
    assert not _passes_at_the_static_size(text, stacks, 67584)
    # no array of every assignment's row, forward or backward
    assert not re.search(r"\[8192,8,4096\]|\[65536,4096\]", text)
    assert not any("/block_0/moe" in s for s in stacks.values())
    for scope in (
        "mla_q", "mla_kv_down", "mla_kv_up", "mla_rope", "mla_out",
        "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
        "moe_shared",
    ):
        assert any(f"/{scope}/" in s for s in stacks.values()), scope
