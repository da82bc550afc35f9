"""The ``ouro`` cell's step, COMPILED for a described TPU v5e (no chip
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
    _head_matmul_shapes,
    _shapes,
    on_tpu,
    one_chip,
    topo,
)

from dlrover_tpu.common.aot_cache import compile_lowered
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)


def test_ouro_twelve_layers_four_passes_step_fits_the_chip(one_chip, on_tpu):
    """The cell's step (``ouro_2_6b_cut``: twelve blocks at the
    published widths run four times over the same weights, the whole
    vocabulary, bf16 state, flash attention, per-block remat, 1 x 4096
    tokens, the four exits through one weighted head of 16 chunks):
    state + temporaries under the chip's 15.75 GB, compiled as the
    engine compiles it (``aot_cache.compile_lowered``); the tree holds
    12 blocks and the program ONE pass's instructions in the bodies of
    two scans (twelve applications' flash kernels under ``ut``, run
    four times); the head's three vocabulary-sized matmuls a chunk,
    none recomputed."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.ouro import Ouro, OuroConfig, make_ouro_loss

    model = Ouro(OuroConfig(
        num_layers=12, attention_impl="flash", remat=True,
        param_dtype=jnp.bfloat16,
    ))
    optimizer = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    abs_state = jax.eval_shape(
        lambda: TrainState.create(
            model.init_params(jax.random.PRNGKey(0), 1, seq_len=4096),
            optimizer,
        )
    )
    assert sum(k.startswith("block_") for k in abs_state.params) == 12
    tokens = np.zeros((1, 4096), np.int32)
    compiled = compile_lowered(make_train_step(
        make_ouro_loss(model, num_chunks=16), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ))
    mem = compiled.memory_analysis()
    # 818.0 M parameters x 6 bytes
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 4.91
    assert mem.alias_size_in_bytes == mem.argument_size_in_bytes - (
        2 * 4096 * 4
    )
    # 7.80 GB (7,803,492,864 B): 4.88 at PR 44 (3.69 + the ``out`` and
    # ``lse`` of 48 applications) + 36 stacks of ``bf16[4,16,4096,
    # 128]``, the q, k and v of twelve applications over the four
    # passes (2.42 GB: 7.30) + 0.5 round them; the dumped buffer
    # assignment's one preallocated temporary, which is what the chip
    # reserves, is 6.99 GB (4.32 at PR 44).  Under the compiler's OWN
    # choice of order this step asked 14.59 GB and reserved 9.63
    # (``aot_cache.COMPILER_OPTIONS``; PERF.md section 6, PR 45), and
    # the sum below did not hold
    assert mem.temp_size_in_bytes < 7.9e9
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
    # forward, dq, dkv of a pass's 12 applications
    assert len(calls) == 3 * 12
    assert all(re.match(r"^%?attn(\.|$)", c) for c in calls)
    assert all("/while/body/" in stacks[c] for c in calls)
    assert all("/ut/" in stacks[c] for c in calls)
    for block in range(12):
        assert sum(
            f"block_{block}/attn/" in stacks[c] for c in calls
        ) == 3, block
    for scope in ("exit_gate", "loss_head", "optimizer"):
        assert any(scope in s for s in stacks.values()), scope
    assert _head_matmul_shapes(text) == [
        "bf16[1024,2048]", "f32[1024,49152]", "f32[2048,49152]",
    ]
