"""The held-experts layer at every cell's widths, COMPILED for a
described TPU v5e (no chip attached, nothing runs): a whole expert
through the grouped-matmul kernels, and the index work of a held range
without a sort or a scatter.  The fixtures and helpers are
``test_tpu_compile.py``'s; in a file of its own because under ``--dist
loadfile`` a file is one worker's."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures by name)
    _expert_kernels,
    on_tpu,
    one_chip,
    topo,
)


# a cell's expert layer: assignments, experts held, hidden size, expert
# width, whether an expert has a gate matrix
EXPERT_LAYERS = {
    "olmoe_steady_4k": (65536, 64, 2048, 1024, True),
    "sarvam_steady_8k": (65536, 8, 4096, 2048, True),
    "laguna_steady_8k": (81920, 16, 3072, 1024, True),
    "nemotron_steady_8k": (49152, 8, 2688, 1856, False),
    "mimo_v2_5_steady": (65536, 8, 4096, 2048, True),
}


@pytest.mark.parametrize("cell", sorted(EXPERT_LAYERS))
def test_a_whole_expert_compiles_at_the_cells_widths(one_chip, on_tpu, cell):
    """``grouped_expert`` at each cell's widths, bf16, the result
    alone (a program that asks for no gradient: the up kernel writes
    ONE result) and value with all gradients: the up projection(s)
    with the activation in ONE kernel whose weight blocks (two of
    ``[4096, 1024]`` where a gate and an up matrix of ``[4096,
    2048]`` go through one grid step) fit the chip's fast memory, the
    derivative in the epilogue of the down projection's gradient and
    ONE gradient to the rows; a width of 1856 = 14.5 lane tiles
    whole, a hidden size of 3072 in halves and of 2688 in thirds."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    assignments, groups, d, m, gated = EXPERT_LAYERS[cell]
    tiles = assignments // gmm.ROW_TILE + groups

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (
        s((tiles * gmm.ROW_TILE, d)), s((groups, d, m)), s((groups, d, m)),
        s((groups, m, d)), s((tiles,), jnp.int32), s((1,), jnp.int32),
    )

    def expert(rows, w_gate, *rest):
        return gmm.grouped_expert(rows, w_gate if gated else None, *rest)

    def loss(*operands):
        return expert(*operands).astype(jnp.float32).sum()

    def kinds(compiled):
        calls = re.findall(
            r"^\s*(?:ROOT )?%([\w\-.]+) = [^\n]*custom_call_target="
            r'"tpu_custom_call"', compiled.as_text(), re.M,
        )
        return sorted(re.search(r"gmm_[a-z_]*[a-z]", c)[0] for c in calls)

    primal = jax.jit(expert).lower(*operands).compile()
    assert kinds(primal) == ["gmm_fwd", "gmm_up_fwd"]
    # one result: a gate's two products are the forward rule's to keep
    assert re.search(
        rf"%gmm_up_fwd[\w.]* = bf16\[{tiles * gmm.ROW_TILE},{m}\]",
        primal.as_text(),
    )
    both = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
    ).lower(*operands).compile()
    wanted = _expert_kernels(1, gated)
    wanted.update(gmm_up_fwd=1, gmm_fwd=1)  # no remat here
    assert kinds(both) == sorted(
        kind for kind, count in wanted.items() for _ in range(count)
    )
    assert "add_any" not in both.as_text()


# a cell's router: its outputs, the choices a token makes, the score,
# whether a bias picks them
ROUTERS = {
    "olmoe_steady_4k": (64, 8, "softmax", False),
    "sarvam_steady_8k": (128, 8, "sigmoid", True),
    "laguna_steady_8k": (256, 10, "softmax", False),
    "nemotron_steady_8k": (128, 6, "sigmoid", True),
    "mimo_v2_5_steady": (256, 8, "sigmoid", True),
}


def _index_passes(text, sizes):
    """``(kind, shape)`` of every ``sort``, ``scatter`` and ``gather``
    of a compiled program (fused ones too) that makes an array of one
    of ``sizes`` elements: the operations that take a TPU 7-10 ns an
    element where a vector pass takes bytes."""
    found = []
    for kind, made in re.findall(
        r"^\s*(?:ROOT )?%[\w\-.]+ = (\(?[^=\n]*?\)?) "
        r"(sort|scatter|gather)\(", text, re.M,
    ):
        found += [
            (made, shape) for shape in re.findall(r"\w+\[([\d,]+)\]", kind)
            if int(np.prod([int(n) for n in shape.split(",")])) in sizes
        ]
    return found


def _arrays_of(text, elements: int):
    """The results of ``elements`` elements that a compiled program's
    own instructions make (what stands in memory), a fusion's inner
    values left out."""
    found, fused = [], False
    for line in text.splitlines():
        if line.endswith("{") and " -> " in line:
            fused = "fused_computation" in line.split("(", 1)[0]
        elif not fused:
            found += [
                shape for shape in re.findall(
                    r"^\s*(?:ROOT )?%[\w\-.]+ = \w+\[([\d,]+)\]", line
                )
                if int(np.prod([int(n) for n in shape.split(",")]))
                == elements
            ]
    return found


@pytest.mark.parametrize("cell", sorted(EXPERT_LAYERS))
def test_the_held_layers_index_work_is_no_sort_and_no_scatter(
    one_chip, on_tpu, cell
):
    """The layer at each cell's tokens, choices, router outputs and
    held experts (the widths small: the index work does not see
    them), value and all gradients through a rematted layer, compiled:
    where a chip holds a range, NO ``sort``, ``scatter`` or ``gather``
    makes an array of ``tokens x k`` or of the padded rows' size (the
    router's top-k sorts ``[tokens, e]``; the row side gathers a
    tile's 256), and no array of ``tokens x k x e`` stands in memory
    (100 MB of int32 at 384 outputs): masks and prefix sums are
    fused vector passes.  Where every expert is held
    (``olmoe_steady_4k``) the sort and its scatters stay, and the
    search finds them."""
    from dlrover_tpu.ops import grouped_matmul as gmm
    from dlrover_tpu.parallel.moe import dropless_moe

    assignments, groups, _, _, gated = EXPERT_LAYERS[cell]
    e, k, score, bias = ROUTERS[cell]
    t, d, m = assignments // k, 384, 128
    held = None if groups == e else (0, groups)
    padded_rows = assignments + groups * gmm.ROW_TILE

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, router, w_gate, w_up, w_down, select_bias):
        out, stats = dropless_moe(
            x, router, w_gate if gated else None, w_up, w_down, k,
            held=held, score=score, renormalise=True, scale=2.5,
            select_bias=select_bias if bias else None,
        )
        return out.astype(jnp.float32).sum() + jnp.vdot(
            stats["prob_sum"], stats["counts"]
        )

    text = jax.jit(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1, 3, 4)
    )).lower(
        s((t, d)), s((d, e), jnp.float32), s((groups, d, m)),
        s((groups, d, m)), s((groups, m, d)), s((e,), jnp.float32),
    ).compile().as_text()
    found = _index_passes(text, {assignments, padded_rows})
    if held is None:
        assert {kind for kind, _ in found} == {"sort", "scatter", "gather"}
    else:
        assert not found
    assert not _arrays_of(text, assignments * e)
