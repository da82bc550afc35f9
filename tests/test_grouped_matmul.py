"""The grouped-matmul kernels (``ops/grouped_matmul.py``, interpreter
mode here) against a per-group loop and against ``jax.lax.ragged_dot``:
ragged, empty and single-group sizes, forward and both gradients, the
tile-aligned layout itself, and what a tile of no group costs: no
block moves for it, and nothing it holds reaches a result."""

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

    def pad(self, sorted_rows):
        return jnp.zeros(
            (self.padded, sorted_rows.shape[1]), sorted_rows.dtype
        ).at[self.index].set(sorted_rows)

    def product(self, x, w):
        """The kernels' product on sorted rows, through the layout."""
        out = gmm.grouped_matmul(
            self.pad(x), w, self.layout[0], self.layout[1], TILES
        )
        return out, out[self.index]


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


@pytest.mark.parametrize("case", sorted(SIZES))
def test_forward_equals_the_per_group_loop(case):
    a = Aligned(case)
    padded, got = a.product(a.x, a.w)
    want = by_loop(a.x, a.w, a.sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        got, jax.lax.ragged_dot(a.x, a.w, jnp.asarray(a.sizes, jnp.int32)),
        rtol=1e-5, atol=1e-4,
    )


@pytest.mark.parametrize("case", sorted(SIZES))
def test_padding_rows_inside_a_used_tile_come_out_zero(case):
    """Zero in, zero out for a group's own padding (``parallel/moe.py
    ::_collect_bwd`` rests on it).  The tiles past ``tiles_used`` are
    another matter: their rows are not written."""
    a = Aligned(case)
    padded, _ = a.product(a.x, a.w)
    padding = np.ones(a.padded, bool)
    padding[a.index] = False
    padding[int(a.layout[1][0]) * TILES[0]:] = False
    assert padding.any() == any(
        size == 0 or size % TILES[0] for size in a.sizes
    )
    assert not np.asarray(padded)[padding].any()


@pytest.mark.parametrize("case", sorted(SIZES))
def test_nothing_past_tiles_used_reaches_a_result(case):
    """The contract from the reading side: with the rows of the
    operand and of the cotangent past ``tiles_used`` set to NaN, the
    product on the used tiles, the gradient to their rows and the
    gradient to the weights (an empty group's zeros among them) are
    finite and bit-equal to what zeros there give."""
    a = Aligned(case, seed=2)
    used = int(a.layout[1][0]) * TILES[0]
    assert used < a.padded

    def results(fill):
        def past(sorted_rows):
            return a.pad(sorted_rows).at[used:].set(fill)

        out, vjp = jax.vjp(
            lambda x, w: gmm.grouped_matmul(
                x, w, a.layout[0], a.layout[1], TILES
            ),
            past(a.x), a.w,
        )
        d_rows, d_weights = vjp(past(a.cot))
        return [np.asarray(r) for r in (out[:used], d_rows[:used], d_weights)]

    got, want = results(jnp.nan), results(0.0)
    for g, w in zip(got, want):
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g, w)
    for group, size in enumerate(a.sizes):
        if size == 0:
            assert not got[2][group].any()


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


@pytest.mark.parametrize("case", sorted(INDEX_CASES))
def test_a_tile_of_no_group_moves_no_block(case):
    """The index rule itself, read from the traced kernels: walking
    each grid in its order, a used tile names its own rows (operand,
    cotangent, output) and its group's weights; on a tile past
    ``tiles_used`` NO block's index differs from the grid step before,
    so the pipeline neither fetches nor writes back for it."""
    c = INDEX_CASES[case]
    tile_sizes = c["tiles"]
    layout = gmm.group_layout(
        jnp.asarray(c["sizes"], jnp.int32), c["rows"], tile_sizes[0]
    )
    tile_group, used = np.asarray(layout[0]), int(layout[1][0])
    tiles = len(tile_group)
    assert (tiles, used) == c["want"]
    dtype = jnp.bfloat16

    def loss(r, w):
        return gmm.grouped_matmul(
            r, w, layout[0], layout[1], tile_sizes
        ).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        jax.ShapeDtypeStruct((tiles * tile_sizes[0], c["k"]), dtype),
        jax.ShapeDtypeStruct((len(c["sizes"]), c["k"], c["n"]), dtype),
    )
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert [e.params["name"] for e in calls] == [
        "gmm_fwd", "gmm_dlhs", "gmm_drhs"
    ]
    for call in calls:
        mapping = call.params["grid_mapping"]
        row_axis = 2 if call.params["name"] == "gmm_drhs" else 1
        assert mapping.grid[row_axis] == tiles
        steps = np.array(
            list(itertools.product(*map(range, mapping.grid))), np.int32
        )
        row = steps[:, row_axis]
        assert len(mapping.block_mappings) == 3
        for block in mapping.block_mappings:
            index_map = block.index_map_jaxpr

            def at(step, index_map=index_map):
                return jnp.stack(jax.core.eval_jaxpr(
                    index_map.jaxpr, index_map.consts, *step,
                    jax.new_ref(layout[0]), jax.new_ref(layout[1]),
                ))

            index = np.asarray(
                jax.jit(lambda s, at=at: jax.lax.map(at, s))(steps)
            )
            own = row if index.shape[1] == 2 else tile_group[row]
            np.testing.assert_array_equal(
                index[row < used, 0], own[row < used]
            )
            empty = np.flatnonzero(row >= used)
            assert len(empty) and empty[0] > 0
            np.testing.assert_array_equal(index[empty], index[empty - 1])


@pytest.mark.parametrize("case", sorted(SIZES))
def test_both_gradients_equal_the_per_group_loop(case):
    a = Aligned(case, seed=1)

    def through(fn):
        return jax.grad(
            lambda x, w: jnp.sum(fn(x, w) * a.cot), argnums=(0, 1)
        )(a.x, a.w)

    got = through(lambda x, w: a.product(x, w)[1])
    want = through(lambda x, w: by_loop(x, w, a.sizes))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-4)
    # a group without rows gets a zero gradient, not garbage
    for group, size in enumerate(a.sizes):
        if size == 0:
            assert not np.asarray(got[1][group]).any()


def test_bf16_operands_accumulate_in_float32():
    """bf16 in, bf16 out, but the sum over k (two tiles of it here) is
    kept in float32: against the float32 product of the same (already
    rounded) operands the result is within one bf16 rounding."""
    a = Aligned("ragged", dtype=jnp.bfloat16)
    _, got = a.product(a.x, a.w)
    assert got.dtype == jnp.bfloat16
    want = by_loop(
        a.x.astype(jnp.float32), a.w.astype(jnp.float32), a.sizes
    )
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() <= 2.0 ** -8 * np.abs(np.asarray(want)).max()


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
