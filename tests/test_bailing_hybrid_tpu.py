"""Ling-3.0-flash's kernels and the cell's step, COMPILED for a
described TPU v5e (no chip attached, nothing runs): the fixtures and
helpers are ``test_tpu_compile.py``'s.  In a file of its own (PR 50's
departure (1): under ``--dist loadfile`` a file is one worker's, and a
long file ends the run)."""

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
from dlrover_tpu.ops import kda, kda_rows
from dlrover_tpu.optim import adamw_bf16
from dlrover_tpu.trainer.elastic_trainer import (
    TrainState,
    make_train_step,
)

RULE = dict(batch=1, seq=8192, heads=32, d=128)


def _rule_operands(one_chip, dtype):
    """As ``KdaAttention`` holds them: its projections' ``[b, s, h d]``
    rows.  (A ``[b, s, h, d]`` array AT a jit boundary lies tiled over
    ``(h, d)``, and the view of it a row a token is a relayout that
    is the boundary's, not the rule's.)"""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, t, h, d = (RULE[k] for k in ("batch", "seq", "heads", "d"))
    tokens = s((b, t, h * d), dtype)
    return (
        tokens, tokens, tokens, s((b, t, h * d), jnp.float32),
        s((b, t, h), jnp.float32),
    )


def _rule(q, k, v, g, beta):
    """The rule as ``KdaAttention`` calls it: a head a 128-lane column
    of the rows, ``o`` back into rows for the norm and ``o_proj``."""
    def heads(x):
        return x.reshape(x.shape[:2] + (RULE["heads"], RULE["d"]))

    o, state = kda.kda_rule(heads(q), heads(k), heads(v), heads(g), beta)
    return o.reshape(q.shape), state


def _moved_outside_the_kernels(compiled):
    """The compiled program's instructions that move or sum a whole
    token array in XLA, whatever its view: a copy, a transpose, a
    reshape that is no bitcast, a fusion or a ``reduce-window`` of
    ``s x h x d`` elements or more."""
    whole = RULE["batch"] * RULE["seq"] * RULE["heads"] * RULE["d"]
    found = []
    for line in compiled.as_text().splitlines():
        if " reduce-window(" in line:
            found.append(line.strip()[:120])
        hit = re.match(
            r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* "
            r"(copy|copy-start|transpose|reshape|fusion)\(", line,
        )
        if hit and np.prod([int(n) for n in hit[1].split(",")]) >= whole:
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"]
)
def test_the_channel_wise_rule_compiles_at_published_sizes(
    one_chip, on_tpu, dtype
):
    """The rule at (1, 8192, 32, 128 | 128) with a log-decay a
    channel, forward and backward, for the described chip: the forward
    is the ``kda_fwd`` kernel and no ``while``, the gradient adds
    ``kda_bwd``, the column blocks of ``[b, s, h d]``, the running
    sum's sublane rolls and the row-of-a-block gathers of the levels'
    reference rows are legal Mosaic, and both stay inside the scoped
    VMEM (no ``vmem_limit_bytes`` is asked for).  Since PR 60 the
    kernels make their own operands: NOTHING of a token array's size
    is copied, transposed or summed outside them, forward or backward
    (PR 59's program: 11 copies and a ``reduce-window`` fusion in the
    forward, 24 and three in the gradient).  bf16 is the cell's;
    float32 operands (every matmul at ``HIGHEST``) are the tests'
    exact path."""
    operands = _rule_operands(one_chip, dtype)
    forward = jax.jit(_rule).lower(*operands).compile()
    out, state = forward.out_info
    assert out.shape == (1, 8192, 32 * 128) and out.dtype == dtype
    assert state.shape == (1, 32, 128, 128) and state.dtype == jnp.float32
    assert _calls(forward, "kda_fwd") == _kernels(forward) == 1
    text = forward.as_text()
    assert " while(" not in text
    assert _moved_outside_the_kernels(forward) == []
    hlo = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    chunks = f"{hlo}[32,{8192 // kda.CHUNK}"
    # the states each chunk starts from and its inverse, for the
    # backward, in the operands' type
    assert f"{chunks},128,128]" in text

    def loss(*a):
        # (a cotangent that is one scalar: no array of the test's own)
        return _rule(*a)[0][0, -1, -1].astype(jnp.float32)

    backward = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    ).lower(*operands).compile()
    assert _calls(backward, "kda_fwd") == 1
    assert _calls(backward, "kda_bwd") == 1
    assert _kernels(backward) == 2
    assert " while(" not in backward.as_text()
    assert _moved_outside_the_kernels(backward) == []
    # the decay's gradient leaves the program a float32 a channel
    assert backward.out_info[3].shape == (1, 8192, 32 * 128)
    assert backward.out_info[3].dtype == jnp.float32
    temp = backward.memory_analysis().temp_size_in_bytes
    print(f"kda backward temporaries {hlo}: {temp / 2**30:.3f} GiB")
    # 0.63 GiB in bf16, 0.88 in float32 (offline compile, PR 59)
    assert temp < 1.5 * 2**30


def _mixer_operands(one_chip):
    """What ``KdaAttention`` holds between its convolutions and its
    output projection: ``q``, ``k`` (float32) and ``v`` out of
    ``causal_conv``, ``f`` (float32) out of ``f_proj``, ``z`` and the
    write strength's logits out of theirs, and the four parameters."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, t, h, d = (RULE[k] for k in ("batch", "seq", "heads", "d"))
    wide, narrow = s((b, t, h * d), jnp.float32), s(
        (b, t, h * d), jnp.bfloat16
    )
    return dict(
        q=wide, k=wide, v=narrow, f=wide, z=narrow,
        bb=s((b, t, h), jnp.float32), a_log=s((h,), jnp.float32),
        dt_bias=s((h * d,), jnp.float32), scale=s((d,), jnp.float32),
    )


def _gates(x):
    return kda_rows.kda_gates(
        x["q"], x["k"], x["f"], x["a_log"], x["dt_bias"], lower=kda.LOWER,
        dtype=jnp.bfloat16,
    )


def _norm(o, x):
    return kda_rows.kda_norm(
        o, x["z"], x["scale"], eps=1e-6, dtype=jnp.bfloat16
    )


def _mixer(x):
    """Convolutions' outputs -> gates -> rule -> norm, as
    ``KdaAttention`` chains them."""
    q, k, g, least = _gates(x)
    o, state = _rule(q, k, x["v"], g, jax.nn.sigmoid(x["bb"]))
    return _norm(o, x), state, least


# the three programs: their outputs' types, the kernels each holds
# forward and those its gradient holds (the row kernels' residuals are
# their inputs: a gradient runs a forward kernel only for what the
# NEXT kernel reads)
ROW_KERNELS = {
    "gates": (
        lambda x: _gates(x)[:3], [jnp.bfloat16, jnp.bfloat16, jnp.float32],
        ["kda_gates_fwd"], ["kda_gates_bwd"],
    ),
    "norm": (
        lambda x: (_norm(x["v"], x),), [jnp.bfloat16],
        ["kda_norm_fwd"], ["kda_norm_bwd"],
    ),
    "mixer": (
        lambda x: _mixer(x)[:1], [jnp.bfloat16],
        ["kda_gates_fwd", "kda_fwd", "kda_norm_fwd"],
        [
            "kda_gates_fwd", "kda_fwd", "kda_norm_bwd", "kda_bwd",
            "kda_gates_bwd",
        ],
    ),
}


@pytest.mark.parametrize("program", list(ROW_KERNELS))
def test_the_mixers_row_kernels_compile_at_published_sizes(
    one_chip, on_tpu, program
):
    """The gates and the head norm at (1, 8192, 32 x 128), alone and
    chained round the rule as the mixer chains them, forward and
    gradient, for the described chip: every kernel is legal Mosaic
    inside the scoped VMEM (no ``vmem_limit_bytes`` is asked for), and
    NOTHING of a token array's size is copied, transposed, reshaped
    or fused outside the kernels: the float32 ``q`` and ``k``, ``g``
    and the bf16 ``o`` pass from kernel to kernel as the rows they
    are (PR 60's program: 228 operations under ``kda_gates`` a
    step)."""
    outputs, types, forward_kernels, backward_kernels = ROW_KERNELS[
        program
    ]
    operands = _mixer_operands(one_chip)
    forward = jax.jit(outputs).lower(operands).compile()
    assert [(o.shape, o.dtype) for o in forward.out_info] == [
        ((1, 8192, 32 * 128), dtype) for dtype in types
    ]
    for name in forward_kernels:
        assert _calls(forward, name) == 1, name
    assert _kernels(forward) == len(forward_kernels)
    assert _moved_outside_the_kernels(forward) == []

    def loss(x):
        # (cotangents that are one scalar each: no array of the test's)
        return sum(o[0, -1, -1].astype(jnp.float32) for o in outputs(x))

    backward = jax.jit(jax.grad(loss)).lower(operands).compile()
    for name in backward_kernels:
        assert _calls(backward, name) == 1, name
    assert _kernels(backward) == len(backward_kernels)
    assert _moved_outside_the_kernels(backward) == []
    grads = backward.out_info
    assert {k: (g.shape, g.dtype) for k, g in grads.items()} == {
        k: (x.shape, x.dtype) for k, x in operands.items()
    }


def test_flash_attention_compiles_at_32_heads_of_192_and_128(
    one_chip, on_tpu
):
    """The latent layer's attention: 8192 tokens, 32 query and 32 key
    heads of 192 | 128 (the other latent cells run 16 and 20 query
    heads): forward, dq and dkv inside the v5e's scoped VMEM."""
    q = jax.ShapeDtypeStruct(
        (1, 8192, 32, 192), jnp.bfloat16, sharding=one_chip
    )
    v = jax.ShapeDtypeStruct(
        (1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()

    compiled = jax.jit(
        jax.grad(loss, argnums=(0, 1, 2))
    ).lower(q, q, v).compile()
    assert _kernels(compiled) == 3


def test_ling_step_fits_the_chip(one_chip, on_tpu, tmp_path):
    """The cell's step (``ling_3_flash_cut``: a dense KDA block, five
    sparse KDA blocks and a sparse latent-attention block at the
    published widths, 16 of 512 experts held under the group mask, a
    quarter of the vocabulary, bf16 state, flash attention, per-block
    remat, 1 x 8192 tokens): state + temporaries under the chip's
    15.75 GiB, the rule's two kernels a KDA layer (the block keeps
    what ``kda_fwd`` wrote: no second forward, PR 65) between the row
    kernels' three (forward, their remat copy, backward), the flash
    kernels under the module ``attn``, and every scope the benchmark's
    readers join on in the op-name map."""
    from dlrover_tpu.common.aot_cache import op_names
    from dlrover_tpu.models.bailing_hybrid import (
        BailingHybrid,
        BailingHybridConfig,
        make_bailing_hybrid_loss,
    )

    model = BailingHybrid(BailingHybridConfig(
        vocab_size=39296, num_layers=7, layer_ids=(1, 6, 7, 8, 9, 10, 11),
        first_dense=1, experts_held=(0, 16), attention_impl="flash",
        remat=True, param_dtype=jnp.bfloat16,
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
        make_bailing_hybrid_loss(model, num_chunks=8), optimizer
    ).lower(
        _shapes(abs_state, one_chip),
        _shapes({"x": tokens, "y": tokens}, one_chip),
    ), tmp_path)
    mem = compiled.memory_analysis()
    # 1.268 B parameters x 6 bytes (the norms' scales, A_log, dt_bias
    # and the select bias are float32)
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 7.61
    print(
        f"ling step temporaries: {reserved / 1e9:.3f} GB reserved, "
        f"{(2 * reserved - mem.temp_size_in_bytes) / 1e9:.3f} live at "
        f"once, {mem.temp_size_in_bytes / 1e9:.3f} reported"
    )
    # offline compile: 4.85 GB reported, 4.17 reserved since PR 65
    # (six KDA blocks keep ``o``, the chunk-start states and ``T``,
    # 3 x 67 MB each and 1.2 GB in all, for the forward kernel's
    # second run); 3.90 | 3.17 since PR 62 (the mixer's float32 passes
    # hold no copies); 4.44 | 3.72 before.  The limit is PR 62's 4.8
    # GB and what is kept
    assert mem.temp_size_in_bytes < 4.8e9 + 6 * 3 * 67.2e6
    assert (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        < 15.75 * 2**30
    )
    text = compiled.as_text()
    # the rule once forward and once backward a layer: its forward is
    # in no block's remat copy (12 before PR 65)
    assert _calls(compiled, "kda_fwd") == 6
    assert _calls(compiled, "kda_bwd") == 6
    # the gates and the head norm round the rule three times: gradients
    # of their own read what they wrote, so they stay in the copy
    for rows in ("kda_gates", "kda_norm"):
        assert _calls(compiled, f"{rows}_fwd") == 12
        assert _calls(compiled, f"{rows}_bwd") == 6
    calls = re.findall(
        r"^\s*(?:ROOT )?(%[\w\-.]+) = [^\n]*custom_call_target="
        r'"tpu_custom_call"', text, re.M,
    )
    found = op_names(text)
    stacks = found["op_names"]
    flash = [c for c in calls if re.match(r"^%?attn(\.|$)", c)]
    # forward, dq, dkv in the one latent block; it does not run its
    # forward again
    assert len(flash) == 3
    assert all("/block_6/attn/" in stacks[c] for c in flash)
    for scope in (
        "kda_proj", "kda_conv", "kda_gates", "kda_rule", "kda_norm",
        "kda_out", "mla_q", "mla_kv_down", "mla_kv_up", "mla_rope",
        "attn_gate", "mla_out", "moe_router", "moe_group_select",
        "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
        "loss_head",
    ):
        # (bare or inside jax's wrappers: ``jvp(loss_head)``)
        assert any(
            re.search(rf"[/(]{scope}[/)]|/{scope}$", s)
            for s in stacks.values()
        ), scope
    # nothing of the step is left without a name of the program
    assert not found["unnamed"]
