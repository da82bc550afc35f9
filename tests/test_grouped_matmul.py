"""The grouped-matmul kernels (``ops/grouped_matmul.py``, interpreter
mode here) against a per-group loop and against ``jax.lax.ragged_dot``:
ragged, empty and single-group sizes, forward and both gradients, the
tile-aligned layout itself."""

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
}


def by_loop(rows, weights, sizes):
    """Each group's rows times its own matrix, one group at a time."""
    out, start = [], 0
    for group, size in enumerate(sizes):
        out.append(rows[start:start + size] @ weights[group])
        start += size
    return jnp.concatenate(out, axis=0)


class Aligned:
    """Sorted rows laid out as the kernels take them, and back."""

    def __init__(self, sizes, seed=0, dtype=jnp.float32):
        self.sizes = sizes
        self.rows = sum(sizes)
        self.layout = gmm.group_layout(
            jnp.asarray(sizes, jnp.int32), self.rows, TILES[0]
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
    tile_group, used, starts = (
        np.asarray(a) for a in gmm.group_layout(
            jnp.asarray(sizes, jnp.int32), sum(sizes), tile
        )
    )
    per_group = [max(1, -(-size // tile)) for size in sizes]
    assert used[0] == sum(per_group)
    assert len(tile_group) == -(-sum(sizes) // tile) + len(sizes)
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
    a = Aligned(SIZES[case])
    padded, got = a.product(a.x, a.w)
    want = by_loop(a.x, a.w, a.sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        got, jax.lax.ragged_dot(a.x, a.w, jnp.asarray(a.sizes, jnp.int32)),
        rtol=1e-5, atol=1e-4,
    )
    # padding rows and the tiles of no group come out zero
    rest = np.ones(a.padded, bool)
    rest[a.index] = False
    assert not np.asarray(padded)[rest].any()


@pytest.mark.parametrize("case", sorted(SIZES))
def test_both_gradients_equal_the_per_group_loop(case):
    a = Aligned(SIZES[case], seed=1)

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
    a = Aligned(SIZES["ragged"], dtype=jnp.bfloat16)
    _, got = a.product(a.x, a.w)
    assert got.dtype == jnp.bfloat16
    want = by_loop(
        a.x.astype(jnp.float32), a.w.astype(jnp.float32), a.sizes
    )
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    assert err.max() <= 2.0 ** -8 * np.abs(np.asarray(want)).max()


def test_sizes_that_do_not_divide_into_tiles_are_refused():
    a = Aligned(SIZES["single_group"])
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
