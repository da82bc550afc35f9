"""The held layer's index arrays (``parallel/moe.py``, PR 58): masks
over the held experts and prefix sums over the tokens, against the
stable sort and the two scatters that made them until then.  The index
work is integer work and the two float gathers beside it select single
elements, so the contract is EQUALITY, at every routing: the same
``counts`` and ``tiles_used``, the same ``slot`` wherever an assignment
has a row, the same ``token_of_row`` and ``gate_of_row`` on every tile,
and with them the layer's output and every gradient to the bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import fill_inside_an_expert, fill_past

from dlrover_tpu.ops import grouped_matmul as gmm
from dlrover_tpu.parallel import moe
from dlrover_tpu.parallel.moe import dropless_moe

TILE = gmm.ROW_TILE


# The held side's index work as it stood until PR 58, word for word
# (``dropless_moe`` under ``moe_router`` / ``moe_dispatch``, and
# ``_token_of_row`` / ``_gate_of_row`` below the layer): the plain
# reference of the index arrays.


def layout_by_sorting(expert_ids, e: int, lo: int, count: int):
    t, top_k = expert_ids.shape
    assignments = t * top_k
    flat_ids = expert_ids.reshape(-1)
    counts = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
    group_sizes = counts[lo:lo + count]
    tile_group, tiles_used, padded_starts = gmm.group_layout(
        group_sizes, assignments
    )
    padded_rows = tile_group.shape[0] * gmm.ROW_TILE
    # an expert held elsewhere sorts as group ``count``
    local = flat_ids - lo
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    here = local[order] < count
    sorted_ids = jnp.minimum(local[order], count - 1)
    starts = jnp.cumsum(group_sizes) - group_sizes
    # the padded row of the assignment at sorted position i
    row = (
        padded_starts[sorted_ids] - starts[sorted_ids]
        + jnp.arange(assignments, dtype=jnp.int32)
    )
    # no row: a slot past the last one, each its own
    row = jnp.where(
        here, row, padded_rows + jnp.arange(assignments, dtype=jnp.int32),
    )
    slot = jnp.zeros_like(order).at[order].set(
        row, unique_indices=True
    ).reshape(t, top_k)
    source = jnp.full(
        (padded_rows,), assignments, jnp.int32
    ).at[row].set(order, unique_indices=True, mode="drop")
    return counts, tile_group, tiles_used, padded_starts, source, slot


def token_of_row_by_source(source, slot):
    t, k = slot.shape
    padding = t + jnp.arange(source.shape[0], dtype=jnp.int32)
    return jnp.where(source < t * k, source // k, padding)


def gate_of_row_by_source(gate, source):
    return gate.reshape(-1).at[source].get(mode="fill", fill_value=0)


def held_layout_by_sorting(
    expert_ids, gate, lo, count, tile_group, tiles_used, padded_starts
):
    """``moe._held_layout``'s two results from the sort: what a test
    puts in its place."""
    # any ``e`` past the held range gives the same held arrays
    *_, source, slot = layout_by_sorting(
        expert_ids, lo + count + 1, lo, count
    )
    return (
        token_of_row_by_source(source, slot),
        gate_of_row_by_source(gate, source),
    )


# -- the routings ------------------------------------------------------------


def routed(t, e, k, seed, bias=None):
    """The top-k's own choice over random scores."""
    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(seed), (t, e))
    )
    _, ids = jax.lax.top_k(scores if bias is None else scores + bias, k)
    return ids


def towards(t, e, k, seed, first, among):
    """Every token's first choice is ``first``; the others by score
    among the experts of ``among`` (``first`` not one of them)."""
    bias = jnp.full((e,), -9.0).at[jnp.asarray(among)].set(0.0)
    ids = routed(t, e, k - 1, seed, bias)
    return jnp.concatenate([jnp.full((t, 1), first, ids.dtype), ids], axis=1)


def a_group_of(rows: int):
    """Held expert 5 takes exactly ``rows`` tokens, strewn over the
    batch, as their LAST choice; every other choice goes elsewhere."""
    t, e, k = 2 * TILE + 100, 16, 3
    ids = np.array(routed(t, e, k, 11, jnp.zeros((e,)).at[4:8].set(-9.0)))
    chosen = np.sort(
        np.random.default_rng(rows).choice(t, rows, replace=False)
    )
    ids[chosen, k - 1] = 5
    return jnp.asarray(ids)


# e, k, held: the families' toys (``tiny()`` of the five model files)
# and their cells' routers over fewer tokens
TOYS = {
    "sarvam_toy": (16, 4, (4, 4)), "laguna_toy": (16, 4, (4, 4)),
    "nemotron_toy": (16, 3, (4, 4)), "mimo_toy": (16, 4, (4, 4)),
    "motif_toy": (16, 4, (4, 4)),
    "sarvam_cell": (128, 8, (0, 8)), "laguna_cell": (256, 10, (0, 16)),
    "nemotron_cell": (128, 6, (0, 8)), "mimo_cell": (256, 8, (0, 8)),
    "motif_cell": (384, 8, (0, 8)),
}

ROUTINGS = {
    # (four toys have one shape: each draws its own three routings)
    **{
        f"{name}_seed{seed}": (
            lambda e=e, k=k, seed=10 * at + seed, toy=name.endswith("toy"):
                routed(128 if toy else 1024, e, k, seed),
            e, held,
        )
        for at, (name, (e, k, held)) in enumerate(TOYS.items())
        for seed in (1, 2, 3)
    },
    # 2048 assignments to 4 of 16 experts: groups of two or three tiles
    "several_tiles_a_group": (lambda: routed(2048, 16, 4, 5), 16, (8, 4)),
    "lo_past_zero_to_the_last_expert": (
        lambda: routed(700, 32, 6, 6), 32, (24, 8)
    ),
    # the worst case the static sizes are for
    "every_assignment_held_here": (
        lambda: routed(
            512, 16, 4, 7, jnp.zeros((16,)).at[4:8].set(9.0)
        ), 16, (4, 4),
    ),
    "none_held_here": (
        lambda: routed(
            512, 16, 4, 7, jnp.zeros((16,)).at[4:8].set(-9.0)
        ), 16, (4, 4),
    ),
    "one_expert_takes_every_token": (
        lambda: towards(
            3 * TILE + 17, 16, 4, 8, 6, [0, 1, 2, 3, 8, 9, 10, 11]
        ), 16, (4, 4),
    ),
    "one_expert_takes_every_token_beside_its_neighbours": (
        lambda: towards(3 * TILE + 17, 16, 4, 9, 6, [0, 1, 4, 5, 7, 12]),
        16, (4, 4),
    ),
    "a_group_of_one_tile_exactly": (lambda: a_group_of(TILE), 16, (4, 4)),
    "a_group_of_one_tile_and_a_row": (
        lambda: a_group_of(TILE + 1), 16, (4, 4)
    ),
    # the top-k's own tie rule: every token takes experts 0 .. k - 1
    "equal_scores": (
        lambda: jax.lax.top_k(jnp.full((300, 16), 0.5), 4)[1], 16, (2, 4)
    ),
    "equal_scores_in_pairs": (
        lambda: jax.lax.top_k(
            jnp.repeat(
                jax.random.uniform(jax.random.PRNGKey(3), (300, 8)), 2,
                axis=1,
            ), 5,
        )[1], 16, (3, 6),
    ),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_index_arrays_are_the_sorts(routing):
    """``counts`` as ``bincount``'s, the layout that follows from them,
    ``slot`` (``padded_starts[j] + reached[j, t] - 1``, the rule the
    row side inverts) wherever an assignment has a row, and
    ``token_of_row`` and ``gate_of_row`` on EVERY tile, used or not:
    equal to the sort's, element for element.  A tile's tokens are
    ascending and distinct, its padding past the last token."""
    make, e, (lo, count) = ROUTINGS[routing]
    expert_ids = make()
    t, k = expert_ids.shape
    assert expert_ids.dtype == jnp.int32
    # a token's k choices are distinct experts: what the rule rests on
    assert all(len(set(row)) == k for row in np.asarray(expert_ids))
    gate = jax.random.uniform(
        jax.random.PRNGKey(4), (t, k), minval=0.1, maxval=1.0
    )
    counts, tile_group, tiles_used, padded_starts, source, slot = (
        layout_by_sorting(expert_ids, e, lo, count)
    )
    got_counts = moe._assignments_of(expert_ids, e)
    assert got_counts.dtype == jnp.int32
    np.testing.assert_array_equal(got_counts, counts)
    layout = gmm.group_layout(got_counts[lo:lo + count], t * k)
    for got, want in zip(layout, (tile_group, tiles_used, padded_starts)):
        np.testing.assert_array_equal(got, want)

    padded_rows = tile_group.shape[0] * TILE
    reached, gate_of_choice = jax.jit(
        moe._held_choices, static_argnums=(2, 3)
    )(expert_ids, gate, lo, count)
    assert reached.dtype == jnp.int32
    local = np.asarray(expert_ids) - lo
    here = (local >= 0) & (local < count)
    at = np.clip(local, 0, count - 1)
    token = np.arange(t)[:, None]
    np.testing.assert_array_equal(
        (np.asarray(padded_starts)[at] + np.asarray(reached)[at, token] - 1)[
            here
        ],
        np.asarray(slot)[here],
    )
    assert (np.asarray(slot)[~here] >= padded_rows).all()
    np.testing.assert_array_equal(
        np.asarray(gate_of_choice)[at, token][here], np.asarray(gate)[here]
    )
    assert int(here.sum()) == int(counts[lo:lo + count].sum())

    token_of_row, gate_of_row = jax.jit(
        moe._held_layout, static_argnums=(2, 3)
    )(expert_ids, gate, lo, count, tile_group, tiles_used, padded_starts)
    np.testing.assert_array_equal(
        token_of_row, token_of_row_by_source(source, slot)
    )
    np.testing.assert_array_equal(
        gate_of_row, gate_of_row_by_source(gate, source)
    )
    tiles = np.asarray(token_of_row).reshape(-1, TILE)
    assert (np.diff(tiles, axis=1) > 0).all()
    assert int((tiles < t).sum()) == int(here.sum())


@pytest.mark.parametrize("routing", [
    "sarvam_toy_seed1", "several_tiles_a_group", "none_held_here",
    "a_group_of_one_tile_and_a_row",
])
def test_a_rows_number_goes_back_to_its_tokens_choice(routing):
    """The gradient of ``gate_of_row``: a row's number lands at its
    token's choice of its expert and nowhere else, the same ``[t, k]``
    array as the transpose of the sort's gather gives (one term, so
    to the bit), with NaN on every row past ``tiles_used``."""
    make, e, (lo, count) = ROUTINGS[routing]
    expert_ids = make()
    t, k = expert_ids.shape
    gate = jax.random.uniform(jax.random.PRNGKey(4), (t, k))
    _, tile_group, tiles_used, padded_starts, source, _ = layout_by_sorting(
        expert_ids, e, lo, count
    )
    cot = fill_past(
        jax.random.normal(jax.random.PRNGKey(5), (*source.shape, 1)),
        tiles_used, jnp.nan,
    )[:, 0]
    got = jax.grad(lambda g: jnp.vdot(cot, moe._held_layout(
        expert_ids, g, lo, count, tile_group, tiles_used, padded_starts
    )[1]))(gate)
    want = jax.grad(lambda g: jnp.vdot(
        jnp.nan_to_num(cot), gate_of_row_by_source(g, source)
    ))(gate)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


LAYERS = {
    # sarvam / nemotron / mimo: sigmoid scores, chosen with a bias,
    # renormalised and scaled
    "sigmoid_bias_renormalise": dict(
        score="sigmoid", bias=True, renormalise=True, scale=2.5
    ),
    # laguna
    "softmax_renormalise": dict(
        score="softmax", bias=False, renormalise=True, scale=2.5
    ),
    "softmax": dict(score="softmax", bias=False, renormalise=False),
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_the_layer_is_the_same_to_the_bit(layer, gated, jit, monkeypatch):
    """``dropless_moe`` with the masks and prefix sums against the same
    function fed the sort's arrays: ``out``, the counters and the
    gradients of the tokens, the router and the experts are EQUAL
    (``==``), with every row past ``tiles_used`` of the dispatch's
    result, of the experts' and of every array inside them NaN.
    Compiled too: the weights are an array of their own before their
    sum (``moe._scores_of``), or the compiler adds them in another
    order."""
    kw = dict(LAYERS[layer])
    t, d, m, e, k, held = 256, 32, 16, 32, 6, (8, 8)
    lo, count = held
    ks = jax.random.split(jax.random.PRNGKey(2), 7)
    operands = (
        jax.random.normal(ks[0], (t, d)),
        jax.random.normal(ks[1], (d, e)),
        jax.random.normal(ks[2], (count, d, m)) * 0.2 if gated else None,
        jax.random.normal(ks[3], (count, d, m)) * 0.2,
        jax.random.normal(ks[4], (count, m, d)) * 0.2,
    )
    bias = (
        0.3 * jax.random.normal(ks[5], (e,)) if kw.pop("bias") else None
    )
    cot = jax.random.normal(ks[6], (t, d))
    real = gmm.grouped_expert
    held_dispatch, held_combine = moe._held_dispatch, moe._held_combine

    def experts(rows, w_gate, w_up, w_down, tile_group, tiles_used):
        return fill_past(
            real(
                fill_past(rows, tiles_used, jnp.nan), w_gate, w_up, w_down,
                tile_group, tiles_used,
            ),
            tiles_used, jnp.nan,
        )

    def dispatch(tokens, token_of_row, tiles_used, t):
        return fill_past(
            held_dispatch(tokens, token_of_row, tiles_used, t), tiles_used,
            jnp.nan,
        )

    def combine(rows, gate_of_row, token_of_row, tiles_used, t):
        return held_combine(
            fill_past(rows, tiles_used, jnp.nan), gate_of_row, token_of_row,
            tiles_used, t,
        )

    monkeypatch.setattr(moe.gmm, "grouped_expert", experts)
    fill_inside_an_expert(monkeypatch, jnp.nan, [])
    monkeypatch.setattr(moe, "_held_dispatch", dispatch)
    monkeypatch.setattr(moe, "_held_combine", combine)

    def scored(*ops):
        out, stats = dropless_moe(
            *ops, k, jnp.float32, held=held, select_bias=bias, **kw
        )
        return jnp.sum(out * cot), (out, stats)

    def results():
        argnums = (0, 1, 2, 3, 4) if gated else (0, 1, 3, 4)
        run = jax.value_and_grad(scored, argnums=argnums, has_aux=True)
        (_, (out, stats)), grads = (jax.jit(run) if jit else run)(*operands)
        return jax.tree_util.tree_leaves_with_path((out, stats, grads))

    got = results()
    monkeypatch.setattr(moe, "_held_layout", held_layout_by_sorting)
    want = results()
    assert len(got) == len(want) == 1 + 6 + (5 if gated else 4)
    for (path, a), (_, b) in zip(got, want):
        name = jax.tree_util.keystr(path)
        assert np.isfinite(a).all() and np.asarray(a).any(), name
        np.testing.assert_array_equal(a, b, err_msg=name)
