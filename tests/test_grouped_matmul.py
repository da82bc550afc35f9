"""The grouped-matmul kernels (``ops/grouped_matmul.py``, interpreter
mode here) against a per-group loop and against ``jax.lax.ragged_dot``:
ragged, empty and single-group sizes, forward and both gradients, the
tile-aligned layout itself, and what a tile of no group costs: no
block moves for it, and nothing it holds reaches a result.  Three
forms go through every such check: the plain ``product``
(``grouped_matmul``) and a whole expert with and without a gate
matrix (``grouped_expert``: the activation and its derivative inside
the kernels, one gradient to the rows)."""

import functools
import itertools

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dlrover_tpu.ops import grouped_matmul as gmm  # noqa: E402

# small tiles, the k dimension split in two: the accumulator path too
TILES = (32, 128, 128)
K, N = 256, 128

SIZES = {
    "ragged": [5, 1, 70, 3, 0, 33],
    "empty_groups": [0, 0, 40, 0, 24, 0],
    "single_group": [64],
    "one_group_takes_all": [0, 0, 64, 0],
    "whole_tiles": [32, 64, 32, 32],
    "most_tiles_empty": [40, 0, 24],
    # groups of no row, one row, a row tile and a row tile and one
    "tile_edges": [0, 1, 32, 33],
}
# the arrays' static row count where it is not the sum of the sizes: a
# layer that holds a range of the experts sizes its rows for every
# assignment and gets a share of them
STATIC_ROWS = {"most_tiles_empty": 256}


def by_loop(rows, weights, sizes):
    """Each group's rows times its own matrix, one group at a time."""
    out, start = [], 0
    for group, size in enumerate(sizes):
        out.append(rows[start:start + size] @ weights[group])
        start += size
    return jnp.concatenate(out, axis=0)


# what goes through the kernels: ``product`` is ``grouped_matmul`` of
# one matrix; ``gated`` and ``ungated`` are ``grouped_expert`` of
# (gate, up, down) and of (up, down) with the activation inside
FORMS = ("product", "gated", "ungated")
EXPERTS = FORMS[1:]
# the product goes through every case it went through; an expert,
# whose every kernel shares the product's walk, through those that
# differ for it
PRODUCT_CASES = [
    ("product", case) for case in sorted(SIZES) if case != "tile_edges"
]


def cases(*experts_cases):
    return pytest.mark.parametrize("form, case", PRODUCT_CASES + [
        (form, case) for form in EXPERTS for case in experts_cases
    ])


KERNELS = {
    "product": ["gmm_fwd", "gmm_dlhs", "gmm_drhs"],
    "gated": [
        "gmm_up_fwd", "gmm_fwd", "gmm_down_dlhs", "gmm_up_dlhs",
        "gmm_drhs", "gmm_drhs", "gmm_drhs",
    ],
    "ungated": [
        "gmm_up_fwd", "gmm_fwd", "gmm_down_dlhs", "gmm_dlhs", "gmm_drhs",
        "gmm_drhs",
    ],
}


def form_by_loop(form, rows, weights, sizes):
    """The form's plain reference: the per-group loop and jax's own
    activation between two of them."""
    if form == "product":
        return by_loop(rows, weights[0], sizes)
    up = by_loop(rows, weights[-2], sizes)
    if form == "ungated":
        hidden = jnp.square(jax.nn.relu(up))
    else:
        hidden = jax.nn.silu(by_loop(rows, weights[0], sizes)) * up
    return by_loop(hidden, weights[-1], sizes)


def form_kernels(form, padded_rows, weights, layout, tiles=TILES):
    if form == "product":
        return gmm.grouped_matmul(
            padded_rows, weights[0], layout[0], layout[1], tiles
        )
    return gmm.grouped_expert(
        padded_rows, weights[0] if form == "gated" else None, *weights[-2:],
        layout[0], layout[1], tiles,
    )


class Aligned:
    """Sorted rows laid out as the kernels take them, and back."""

    def __init__(self, case, seed=0, dtype=jnp.float32):
        self.sizes = sizes = SIZES[case]
        self.rows = sum(sizes)
        self.layout = gmm.group_layout(
            jnp.asarray(sizes, jnp.int32),
            STATIC_ROWS.get(case, self.rows), TILES[0],
        )
        self.padded = self.layout[0].shape[0] * TILES[0]
        starts = np.asarray(self.layout[2])
        self.index = np.concatenate([
            starts[g] + np.arange(size) for g, size in enumerate(sizes)
        ]).astype(np.int32)
        keys = jax.random.split(jax.random.PRNGKey(seed), 3)
        self.x = jax.random.normal(keys[0], (self.rows, K), dtype)
        self.w = jax.random.normal(keys[1], (len(sizes), K, N), dtype)
        self.cot = jax.random.normal(keys[2], (self.rows, N), dtype)
        self.w_gate = jax.random.normal(
            jax.random.fold_in(keys[1], 1), self.w.shape, dtype
        )
        self.w_down = jax.random.normal(
            jax.random.fold_in(keys[1], 2), (len(sizes), N, K), dtype
        )

    def weights(self, form):
        """The form's matrices; an expert's are scaled so that an
        activation's argument and the result are of order one."""
        if form == "product":
            return (self.w,)
        scale = jnp.asarray(K ** -0.5, self.w.dtype)
        all_three = (
            self.w_gate * scale, self.w * scale,
            self.w_down * jnp.asarray(N ** -0.5, self.w.dtype),
        )
        return all_three if form == "gated" else all_three[1:]

    def cotangent(self, form):
        """A cotangent of the form's result: an expert's is as wide
        as its rows."""
        return self.cot if form == "product" else self.x[::-1]

    def through(self, form, x, weights):
        """The form's kernels on sorted rows, through the layout:
        ``(padded result, sorted result)``."""
        out = form_kernels(form, self.pad(x), weights, self.layout)
        return out, out[self.index]

    def pad(self, sorted_rows):
        return jnp.zeros(
            (self.padded, sorted_rows.shape[1]), sorted_rows.dtype
        ).at[self.index].set(sorted_rows)


@functools.lru_cache(maxsize=None)
def there_and_back(form, case, dtype=jnp.float32):
    """``(aligned, padded result, sorted result, gradients)`` of the
    form's kernels on a case, the gradients to the sorted rows and to
    every matrix under the form's cotangent.  ONE compile of the
    interpreted kernels a (form, case, type), for every test that
    looks at a part of it."""
    a = Aligned(case, seed=1, dtype=dtype)
    weights = a.weights(form)

    def loss(x, *w):
        padded, got = a.through(form, x, w)
        cot = a.cotangent(form)
        return jnp.sum((got * cot).astype(jnp.float32)), (padded, got)

    (_, (padded, got)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(1 + len(weights))), has_aux=True
    ))(a.x, *weights)
    return a, padded, got, grads


def by_loop_and_back(form, a, dtype=jnp.float32):
    """The same of the form's plain reference, computed in ``dtype``
    from the aligned case's own (already rounded) operands."""
    weights = [w.astype(dtype) for w in a.weights(form)]

    def loss(x, *w):
        want = form_by_loop(form, x, w, a.sizes)
        return jnp.sum(want * a.cotangent(form).astype(dtype)), want

    (_, want), grads = jax.value_and_grad(
        loss, argnums=tuple(range(1 + len(weights))), has_aux=True
    )(a.x.astype(dtype), *weights)
    return want, grads


@functools.lru_cache(maxsize=None)
def past_tiles_used(form, case):
    """``(aligned, rows used, results with NaN, results with zeros)``
    in the rows past ``tiles_used`` of the operand and of the
    cotangent: the result on the used tiles, the gradient to their
    rows and the gradient to every matrix."""
    a = Aligned(case, seed=2)
    used = int(a.layout[1][0]) * TILES[0]
    assert used < a.padded

    @jax.jit
    def results(fill):
        def past(sorted_rows):
            return a.pad(sorted_rows).at[used:].set(fill)

        out, vjp = jax.vjp(
            lambda x, *w: form_kernels(form, x, w, a.layout),
            past(a.x), *a.weights(form),
        )
        d_rows, *d_weights = vjp(past(a.cotangent(form)))
        return out[:used], d_rows[:used], *d_weights

    return a, used, *(
        [np.asarray(r) for r in results(fill)] for fill in (jnp.nan, 0.0)
    )


@pytest.mark.parametrize("case", sorted(SIZES))
def test_layout_gives_every_group_whole_tiles_of_its_own(case):
    sizes = SIZES[case]
    tile = TILES[0]
    rows = STATIC_ROWS.get(case, sum(sizes))
    tile_group, used, starts = (
        np.asarray(a) for a in gmm.group_layout(
            jnp.asarray(sizes, jnp.int32), rows, tile
        )
    )
    per_group = [max(1, -(-size // tile)) for size in sizes]
    assert used[0] == sum(per_group)
    assert len(tile_group) == -(-rows // tile) + len(sizes)
    assert list(tile_group[:used[0]]) == [
        g for g, n in enumerate(per_group) for _ in range(n)
    ]
    # the tiles of no group are named after the last group
    assert set(tile_group[used[0]:]) <= {len(sizes) - 1}
    assert list(starts) == [
        tile * sum(per_group[:g]) for g in range(len(sizes))
    ]


@cases("tile_edges", "most_tiles_empty")
def test_forward_equals_the_per_group_loop(form, case):
    if case == "most_tiles_empty":
        # (what the rows past ``tiles_used`` hold is another test's)
        a, _, _, (padded, *_) = past_tiles_used(form, case)
        got = padded[a.index]
    else:
        a, _, got, _ = there_and_back(form, case)
    weights = a.weights(form)
    want = form_by_loop(form, a.x, weights, a.sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    if form == "product":
        np.testing.assert_allclose(
            got,
            jax.lax.ragged_dot(a.x, a.w, jnp.asarray(a.sizes, jnp.int32)),
            rtol=1e-5, atol=1e-4,
        )


@cases("tile_edges")
def test_padding_rows_inside_a_used_tile_come_out_zero(form, case):
    """Zero in, zero out for a group's own padding (``parallel/moe.py
    ::_collect_bwd`` rests on it), through an activation too (``silu(0)
    * 0 = relu(0) ** 2 = 0``).  The tiles past ``tiles_used`` are
    another matter: their rows are not written."""
    a, padded, _, _ = there_and_back(form, case)
    padding = np.ones(a.padded, bool)
    padding[a.index] = False
    padding[int(a.layout[1][0]) * TILES[0]:] = False
    assert padding.any() == any(
        size == 0 or size % TILES[0] for size in a.sizes
    )
    assert not np.asarray(padded)[padding].any()


@cases("most_tiles_empty")
def test_nothing_past_tiles_used_reaches_a_result(form, case):
    """The contract from the reading side: with the rows of the
    operand and of the cotangent past ``tiles_used`` set to NaN, the
    result on the used tiles, the gradient to their rows and the
    gradient to every matrix (an empty group's zeros among them) are
    finite and bit-equal to what zeros there give.  (What the forward
    rule keeps for the backward, a gate's two products or the hidden
    rows, is not written there either, and the derivative's kernel
    reads none of it.)"""
    a, _, got, want = past_tiles_used(form, case)
    assert len(got) == 2 + len(a.weights(form))
    for g, w in zip(got, want):
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g, w)
    for group, size in enumerate(a.sizes):
        if size == 0:
            assert not any(d[group].any() for d in got[2:])


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


# the two cells' real tile counts (``sarvam_steady_8k``: 65536
# assignments + 8 tiles, about 20 with a row; ``olmoe_steady_4k``:
# 65536 + 64 tiles, about 288), and a contraction split in two
REAL_TILES = (gmm.ROW_TILE, gmm.K_TILE, gmm.N_TILE)
INDEX_CASES = {
    "sarvam_264_tiles_20_used": dict(
        sizes=[600, 520, 0, 700, 512, 300, 900, 500], rows=65536,
        k=4096, n=2048, tiles=REAL_TILES, want=(264, 20),
    ),
    "olmoe_320_tiles_288_used": dict(
        sizes=[1040, 1008] * 32, rows=65536, k=2048, n=1024,
        tiles=REAL_TILES, want=(320, 288),
    ),
    "split_contraction": dict(
        sizes=[40, 0, 24], rows=256, k=K, n=N, tiles=TILES,
        want=(11, 4),
    ),
}


@pytest.mark.parametrize("form, case", [
    *(("product", case) for case in sorted(INDEX_CASES)),
    ("gated", "sarvam_264_tiles_20_used"), ("gated", "split_contraction"),
    ("ungated", "olmoe_320_tiles_288_used"),
])
def test_a_tile_of_no_group_moves_no_block(form, case):
    """The index rule itself, read from the traced kernels: walking
    each grid in its order, a used tile names its own rows (operand,
    cotangent, output, an activation's kept arguments and their
    gradients) and its group's weights; on a tile past ``tiles_used``
    NO block's index differs from the grid step before, so the
    pipeline neither fetches nor writes back for it."""
    c = INDEX_CASES[case]
    tile_sizes = c["tiles"]
    layout = gmm.group_layout(
        jnp.asarray(c["sizes"], jnp.int32), c["rows"], tile_sizes[0]
    )
    tile_group, used = np.asarray(layout[0]), int(layout[1][0])
    tiles = len(tile_group)
    assert (tiles, used) == c["want"]
    dtype = jnp.bfloat16

    shapes = {
        "product": ["kn"], "gated": ["kn", "kn", "nk"],
        "ungated": ["kn", "nk"],
    }[form]

    def loss(r, *w):
        return form_kernels(form, r, w, layout, tile_sizes).astype(
            jnp.float32
        ).sum()

    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=tuple(range(1 + len(shapes))))
    )(
        jax.ShapeDtypeStruct((tiles * tile_sizes[0], c["k"]), dtype),
        *[
            jax.ShapeDtypeStruct(
                (len(c["sizes"]), c[shape[0]], c[shape[1]]), dtype
            )
            for shape in shapes
        ],
    )
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert [e.params["name"] for e in calls] == KERNELS[form]
    # a call's blocks: its operands and results (with a gate the
    # forward rule's up call also writes the two products, the down
    # projection's gradient reads them and writes their gradients,
    # and the rows' ONE gradient takes a pair a matrix; without a
    # gate the hidden rows are what the derivative reads)
    gated = form == "gated"
    blocks = {
        "gmm_fwd": 3, "gmm_dlhs": 3, "gmm_drhs": 3,
        "gmm_up_fwd": 6 if gated else 3,
        "gmm_down_dlhs": 6 if gated else 4, "gmm_up_dlhs": 5,
    }
    for call in calls:
        name = call.params["name"]
        mapping = call.params["grid_mapping"]
        row_axis = 2 if name == "gmm_drhs" else 1
        assert mapping.grid[row_axis] == tiles
        steps = np.array(
            list(itertools.product(*map(range, mapping.grid))), np.int32
        )
        row = steps[:, row_axis]
        assert len(mapping.block_mappings) == blocks[name]
        index_maps = [b.index_map_jaxpr for b in mapping.block_mappings]

        def at(step, index_maps=index_maps):
            # every block's index at one grid step
            return [
                jnp.stack(jax.core.eval_jaxpr(
                    index_map.jaxpr, index_map.consts, *step,
                    jax.new_ref(layout[0]), jax.new_ref(layout[1]),
                ))
                for index_map in index_maps
            ]

        for index in jax.jit(lambda s, at=at: jax.lax.map(at, s))(steps):
            index = np.asarray(index)
            own = row if index.shape[1] == 2 else tile_group[row]
            np.testing.assert_array_equal(
                index[row < used, 0], own[row < used]
            )
            empty = np.flatnonzero(row >= used)
            assert len(empty) and empty[0] > 0
            np.testing.assert_array_equal(index[empty], index[empty - 1])


@cases("tile_edges")
def test_both_gradients_equal_the_per_group_loop(form, case):
    """The gradient to the rows (ONE, where they fed two products)
    and to every matrix against ``jax.grad`` of the loop."""
    a, _, _, got = there_and_back(form, case)
    _, want = by_loop_and_back(form, a)
    assert len(got) == 1 + len(a.weights(form))
    for g, w in zip(got, want, strict=True):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)
    # a group without rows gets a zero gradient, not garbage
    for group, size in enumerate(a.sizes):
        if size == 0:
            assert not any(np.asarray(d[group]).any() for d in got[1:])


@pytest.mark.parametrize("form", FORMS)
def test_bf16_operands_accumulate_in_float32(form, case="ragged"):
    """bf16 in, bf16 out, but the sum over k (two tiles of it here) is
    kept in float32, and an activation is taken from that sum, not
    from its rounding: against the float32 form of the same (already
    rounded) operands the result is within one bf16 rounding."""
    a, _, got, _ = there_and_back(form, case, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    want, _ = by_loop_and_back(form, a)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() <= 2.0 ** -8 * np.abs(np.asarray(want)).max()


@pytest.mark.parametrize("form", EXPERTS)
def test_an_experts_bf16_gradients_are_the_float32_forms_within_rounding(
    form, case="tile_edges"
):
    """The derivative is taken in float32 from what the forward kept
    in bf16 (a gate's two products; without a gate the hidden rows,
    whose root is ``relu(up)``) and every product accumulates in
    float32: each gradient is within a few bf16 roundings of
    ``jax.grad`` of the float32 form of the same operands."""
    a, _, _, got = there_and_back(form, case, jnp.bfloat16)
    _, want = by_loop_and_back(form, a)
    for g, w in zip(got, want, strict=True):
        assert g.dtype == jnp.bfloat16
        err = np.abs(np.asarray(g, np.float32) - np.asarray(w))
        assert err.max() <= 2.0 ** -6 * np.abs(np.asarray(w)).max()


def test_sizes_that_do_not_divide_into_tiles_are_refused():
    a = Aligned("single_group")
    with pytest.raises(ValueError, match="do not divide"):
        gmm.grouped_matmul(
            a.pad(a.x)[:-1], a.w, a.layout[0], a.layout[1], TILES
        )


@pytest.mark.parametrize("k, n", [(2048, 1024), (1024, 2048)])
def test_olmoes_widths_trace_to_the_kernels_they_had(k, n):
    """``K_TILE`` went from 2048 to 4096 for a contraction of 4096 (PR
    35).  At ``olmoe_steady_4k``'s real shapes (65536 rows + a tile an
    expert, 64 experts, hidden 2048 x width 1024 and back) no
    contraction passes 2048, so the three kernels of each matmul
    (forward, the gradient to the rows, the gradient to the weights)
    trace to the same grids, blocks and bodies under the old tiles and
    the new; a tile that does cut a contraction traces to others."""
    groups, assignments = 64, 2 * 4096 * 8
    tiles = assignments // gmm.ROW_TILE + groups
    operands = (
        jax.ShapeDtypeStruct((tiles * gmm.ROW_TILE, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((tiles,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
    )

    def traced(tile_sizes):
        def loss(r, w, tg, nu):
            return gmm.grouped_matmul(
                r, w, tg, nu, tiles=tile_sizes
            ).astype(jnp.float32).sum()

        return str(jax.make_jaxpr(
            jax.value_and_grad(loss, argnums=(0, 1))
        )(*operands))

    assert (gmm.ROW_TILE, gmm.K_TILE, gmm.N_TILE) == (256, 4096, 2048)
    before = traced((256, 2048, 2048))
    assert before.count("pallas_call") == 3
    assert traced((gmm.ROW_TILE, gmm.K_TILE, gmm.N_TILE)) == before
    assert traced((256, 512, 2048)) != before


@pytest.mark.parametrize("dim, tile, fitted", [
    # a hidden size of 3072 (Laguna) goes in halves under 2048
    (3072, 2048, 1536), (3072, 4096, 3072), (6144, 2048, 2048),
    # every accepted cell's dims take the tile they took
    (4096, 2048, 2048), (4096, 4096, 4096), (2048, 2048, 2048),
    (1024, 2048, 1024), (384, 256, 128), (96, 64, 64),
])
def test_a_dim_takes_its_largest_whole_lane_divisor_under_the_tile(
    dim, tile, fitted
):
    assert gmm._fit_tile(dim, tile) == fitted


@pytest.mark.parametrize("k, n", [(384, 128), (128, 384), (384, 384)])
def test_a_dim_the_tile_does_not_divide_goes_in_fitted_tiles(k, n):
    """384 under tiles of 256: three blocks of 128, as an output dim
    and as the contraction, forward and both gradients against the
    per-group loop."""
    sizes = SIZES["ragged"]
    tiles = (32, 256, 256)
    layout = gmm.group_layout(
        jnp.asarray(sizes, jnp.int32), sum(sizes), tiles[0]
    )
    starts = np.asarray(layout[2])
    index = np.concatenate([
        starts[g] + np.arange(size) for g, size in enumerate(sizes)
    ]).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (sum(sizes), k))
    w = jax.random.normal(keys[1], (len(sizes), k, n))
    cot = jax.random.normal(keys[2], (sum(sizes), n))

    def product(x, w):
        padded = jnp.zeros(
            (layout[0].shape[0] * tiles[0], k), x.dtype
        ).at[index].set(x)
        return gmm.grouped_matmul(
            padded, w, layout[0], layout[1], tiles
        )[index]

    np.testing.assert_allclose(
        product(x, w), by_loop(x, w, sizes), rtol=1e-5, atol=1e-4
    )
    got = jax.grad(
        lambda x, w: jnp.sum(product(x, w) * cot), argnums=(0, 1)
    )(x, w)
    want = jax.grad(
        lambda x, w: jnp.sum(by_loop(x, w, sizes) * cot), argnums=(0, 1)
    )(x, w)
    for g, wanted in zip(got, want):
        np.testing.assert_allclose(g, wanted, rtol=1e-5, atol=1e-4)
