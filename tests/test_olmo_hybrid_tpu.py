"""The ``olmo_hybrid`` cell's step, COMPILED for a described TPU v5e (no chip
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


def test_olmo_hybrid_one_period_step_fits_the_chip(one_chip, on_tpu):
    """The cell's step (``olmo_hybrid_7b_cut``: one period at the
    published widths, the whole vocabulary, bf16 state, flash
    attention, per-block remat, 1 x 8192 tokens): state + temporaries
    under the chip's 15.75 GB, the loss head's three matmuls a chunk,
    the three flash kernels under the module ``attn`` (the block keeps
    the five arrays the kernel's backward reads: no second forward,
    PR 44 and PR 45), and
    under each linear layer's ``gdn_rule`` scope one ``gdn_fwd`` and
    one ``gdn_bwd`` (the block keeps ``o``, the chunk-start states and
    ``T`` as the kernel wrote them: no second forward, PR 65)."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.olmo_hybrid import (
        PERIOD,
        OlmoHybrid,
        OlmoHybridConfig,
        make_olmo_hybrid_loss,
    )

    model = OlmoHybrid(OlmoHybridConfig(
        layer_types=PERIOD, attention_impl="flash", remat=True,
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
    compiled = compile_lowered(make_train_step(
        make_olmo_hybrid_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ))
    mem = compiled.memory_analysis()
    # 1.603 B parameters x 6 bytes
    assert round(mem.argument_size_in_bytes / 1e9, 1) == 9.6
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    # no more scratch than the checkpointed head of PR 32 asked for
    # (offline compile of 2da395f, this very program).  4.44 GB now
    # (4,441,295,360 B; 4,442,198,528 before the one full-attention
    # layer kept its q, k and v, 3 x 62.9 MB: the peak is not in that
    # layer's backward, so 0.19 GB kept shows as nothing); 4.07 GB
    # with the convolutions as kernels (4,069,591,040 B, PR 49: the
    # padded float32 copies of q, k and v are gone); 4.52 GB since
    # PR 65 (4,522,255,872 B: the three linear blocks keep ``o``, the
    # chunk-start states and ``T`` as ``gdn_fwd`` wrote them, 94.4 +
    # 70.8 + 62.9 MB a layer, for the kernel's second run): the limit
    # is PR 49's reading and what is kept
    kept = 3 * 8192 * 30 * (192 + 96 * 192 // 128 + 128) * 2
    assert mem.temp_size_in_bytes <= 4_069_591_040 + kept
    text = compiled.as_text()
    # the head: 3 vocabulary-sized matmuls a chunk of 8192 / 8 tokens
    # (logits, d_hidden, d_kernel: 3 x 8 a step, where the
    # checkpointed head made 4 x 8), none of them a recomputation
    assert _head_matmul_shapes(text) == [
        "bf16[1024,3840]", "f32[1024,100352]", "f32[3840,100352]",
    ]
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    stacks = op_names(text)["op_names"]
    rule = [c for c in calls if "gdn_" in c]
    conv = [c for c in calls if "conv_" in c]
    calls = [c for c in calls if c not in rule + conv]
    # the convolutions of q, k and v in each linear layer: forward,
    # the block's remat copy and one backward each, under the scope
    # the mix's reader sums
    assert sorted(
        (re.search(r"block_(\d)/gdn/", stacks[c]).group(1),
         re.search(r"conv_(fwd|bwd)", c).group(1))
        for c in conv
    ) == sorted(
        (str(i), kind) for i in range(3)
        for kind in ("fwd", "fwd", "bwd") * 3
    )
    assert all(
        re.search(r"(?:^|[/(])gdn_conv(?:[/)]|$)", stacks[c]) for c in conv
    )
    # forward, dq, dkv: one layer of four
    assert len(calls) == 3
    assert all(re.match(r"^%?attn(\.|$)", name) for name in calls)
    assert all("/block_3/attn/" in stacks[c] for c in calls)
    # the other three: the rule's kernels, the backward's too under
    # the scope the benchmark's readers look for
    assert sorted(
        (re.search(r"block_(\d)/gdn/", stacks[c]).group(1),
         re.search(r"gdn_(fwd|bwd)", c).group(1))
        for c in rule
    ) == sorted(
        (str(i), kind) for i in range(3) for kind in ("fwd", "bwd")
    )
    # (bare forward, ``transpose(jvp(gdn_rule))`` backward)
    assert all(
        re.search(r"(?:^|[/(])gdn_rule(?:[/)]|$)", stacks[c]) for c in rule
    )
    for scope in ("gdn_conv", "gdn_gates", "gdn_rule", "gdn_norm"):
        assert any(f"/gdn/{scope}/" in s for s in stacks.values()), scope
