"""The ``nemotron_h`` cell's step, COMPILED for a described TPU v5e (no chip
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


def test_nemotron_eighteen_layer_step_fits_the_chip(
    one_chip, on_tpu, tmp_path
):
    """The cell's step (``nemotron_3_nano_30b_cut``: ``MEMEM*EMEMEM*EMEME``
    at the published widths, 8 of 128 experts held, an eighth of the
    vocabulary, bf16 state, flash attention at 32 heads over 2, per-layer
    remat, 1 x 8192 tokens): state + temporaries under the chip's 15.75
    GB, the expert width of 1856 (14.5 lane tiles) WHOLE through the
    grouped-matmul kernels and the hidden size of 2688 in thirds of 896,
    two grouped matmuls an expert layer forward, the flash kernels
    under ``full_attn``, and every scope the benchmark's readers join
    on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.nemotron_h import (
        NemotronH,
        NemotronHConfig,
        make_nemotron_h_loss,
    )

    model = NemotronH(NemotronHConfig(
        vocab_size=16384, pattern="MEMEM*EMEMEM*EMEME",
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
        make_nemotron_h_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.2458 B parameters x 6 bytes (the three per-head vectors of a
    # state-space layer are float32)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 7.48
    # What the chip reserves for the step's temporaries: 3.718 GiB
    # (3,991,798,272 B, offline compile, PR 49; 3.866 GiB at PR 48,
    # 4,150,641,152 B: the padded float32 xBC and its transposes are
    # gone), and the most that is live in it at once 3.312 GiB (3.759
    # at PR 48): both DOWN.  ``temp_size_in_bytes`` is the block plus
    # its fragmentation (``_compile_and_reserved_hbm``): 3.718 + 0.405
    # = 4.123 GiB where PR 48 read 3.866 + 0.106 = 3.972, a smaller
    # block packed looser, so the limit that stood on that figure
    # (4.0 GiB) is held on the two it is made of, each under PR 48's.
    # Nothing chunk-square is among them (4.45 GB with the scan as
    # XLA einsums, PR 47).  Since PR 52 (``relu(.) ** 2`` inside the
    # up projection's kernel, which writes the hidden rows and keeps
    # nothing else: the derivative takes ``relu(u)`` as their root):
    # 3.644 GiB reserved, 3.292 live at once, 3.996 reported, each
    # under PR 49's.  Since PR 65 the eight state-space blocks keep
    # what ``ssd_fwd`` wrote (``y`` 67 MB and the float32 start states
    # 134 MB a layer, 1.5 GiB in all) so that it runs once: 5.060 GiB
    # reserved, 4.420 live at once, 5.699 reported; the limits are
    # those that stood and what is kept
    kept = 8 * (1 * 8192 * 4096 * 2 + 64 * 8 * 128 * 512 * 4)
    temp = mem.temp_size_in_bytes
    live = 2 * reserved - temp
    print(
        f"nemotron step temporaries: {reserved / 2**30:.3f} GiB reserved, "
        f"{live / 2**30:.3f} live at once, {temp / 2**30:.3f} reported"
    )
    assert reserved < 3.75 * 2**30 + kept, (
        f"{reserved / 2**30:.3f} GiB reserved where 5.060 was read"
    )
    assert live < 3.4 * 2**30 + kept, (
        f"{live / 2**30:.3f} GiB live at once where 4.420 was read"
    )
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
    # forward, dq, dkv in each of the two attention layers
    assert len(flash) == 3 * 2
    assert all("/full_attn/attn/" in stacks[c] for c in flash)
    # the state-space scan: eight layers' forward and one backward
    # each, all under the scan's scope; the block keeps what the
    # forward wrote, so its remat copy holds none (16 before PR 65)
    scan = [c for c in calls if "ssd_" in c]
    assert all("/ssm_scan/" in stacks[c] for c in scan)
    kinds = [
        re.sub(r"^%|\.\d+$", "", c) for c in calls if c not in flash
    ]
    # an expert layer: up (with ``relu(.) ** 2`` inside) and down
    # forward and in the remat copy, each with its two gradients: no
    # third matrix, so the rows' gradient is the plain ``gmm_dlhs``
    assert {kind: kinds.count(kind) for kind in kinds} == {
        **_expert_kernels(8, gated=False),
        "gmm_tokens_from_rows": 2 * 8, "gmm_unwritten": 3 * 8,
        "ssd_fwd": 8, "ssd_bwd": 8,
        # x, B and C, each a window of the projection's lanes
        "conv_fwd": 3 * 2 * 8, "conv_bwd": 3 * 8,
    }
    conv = [c for c in calls if "conv_" in c]
    assert all(
        re.search(r"(?:^|[/(])ssm_conv(?:[/)]|$)", stacks[c]) for c in conv
    )
    # read where the projection wrote them: no copy of its lanes
    assert not re.search(r"bf16\[1,8192,6144\]", text)
    # no array of every assignment's row, forward or backward
    assert not re.search(r"\[8192,6,2688\]|\[49152,2688\]", text)
    assert all(
        "/moe_experts/" in stacks[c] for c in calls
        if re.sub(r"^%|\.\d+$", "", c) in _expert_kernels(8, gated=False)
    )
    # ... and, of the 49152 + 8 tiles of padded rows, no elementwise
    # pass between the experts' kernels
    assert not _passes_at_the_static_size(text, stacks, 51200)
    for scope in (
        "ssm_in_proj", "ssm_conv", "ssm_gates", "ssm_scan", "ssm_norm",
        "ssm_out_proj", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "moe_shared",
    ):
        named = [s for s in stacks.values() if f"/{scope}/" in s]
        assert any("transpose(" in s for s in named), scope
