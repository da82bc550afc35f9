"""The doubly gated short convolution ``y = C * conv_K(B * u)``
(``ops/short_conv.py``: the ``bcx_fwd`` / ``bcx_bwd`` kernels, in
interpreter mode here) against the plain form beside it: outputs and
both gradients through the ``custom_vjp`` (``dB``, ``dC``, ``du`` as
the lane windows of the one ``[b, s, 3 c]`` gradient, ``dtaps``),
sequences that fill no tile, widths that are no multiple of 128, the
zeros before row 0 and after the last row, nothing from after ``t``,
and the block's remat."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops import causal_conv as cc  # noqa: E402
from dlrover_tpu.ops import short_conv as sc  # noqa: E402
from dlrover_tpu.ops.short_conv import (  # noqa: E402
    short_conv,
    short_conv_plain,
)

F32, BF16 = jnp.float32, jnp.bfloat16


def operands(b, s, c, dtype=F32, k=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    bound = k ** -0.5
    return (
        jax.random.normal(keys[0], (b, s, 3 * c)).astype(dtype),
        jax.random.uniform(keys[1], (k, c), F32, -bound, bound),
    ), jax.random.normal(keys[2], (b, s, c))


def gradients(f, args, weights):
    """Of ``sum(f * weights)`` in ``bcu`` and ``taps``, as float32."""
    def loss(*a):
        return jnp.sum(f(*a).astype(F32) * weights)

    return [
        np.asarray(g, np.float32) for g in jax.grad(loss, (0, 1))(*args)
    ]


# (b, s, c): a toy's width that is no multiple of 128 with rows that
# fill no tile, rows fewer than a halo, whole lane tiles with several
# row tiles of several strips (VMEM_BYTES shrunk: see the fixture),
# a width of one tile and a half
SHAPES = {
    "toy-96": (2, 40, 96),
    "rows-12": (1, 12, 64),
    "row-tiles": (2, 600, 256),
    "tile-and-a-half": (1, 130, 192),
}


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 256 | 128 rows (forward | backward) at a width of
    256 in float32, the published width's in bf16: several tiles a
    sequence, two strips a forward tile, both halo views read.  (The
    calls are jitted and the constant is no part of their key: the
    shapes under this fixture are used under it alone.)"""
    monkeypatch.setattr(sc, "VMEM_BYTES", 3 << 20)
    assert sc._tile_rows(600, 4 * 256, 4) == 256
    assert sc._tile_rows(600, 7 * 256, 4) == 128


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_float32_matches_the_plain_form(shape, small_tiles):
    b, s, c = shape
    args, weights = operands(b, s, c)
    got, want = short_conv(*args), short_conv_plain(*args)
    assert got.shape == (b, s, c) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    mine = gradients(short_conv, args, weights)
    ref = gradients(short_conv_plain, args, weights)
    # dB | dC | du, the lane windows of one array, each on its own
    for w, name in enumerate(("dB", "dC", "du")):
        np.testing.assert_allclose(
            mine[0][..., w * c:(w + 1) * c], ref[0][..., w * c:(w + 1) * c],
            rtol=1e-5, atol=1e-5 * np.abs(ref[0]).max(), err_msg=name,
        )
    assert mine[1].shape == (3, c)
    np.testing.assert_allclose(
        mine[1], ref[1], rtol=1e-5, atol=1e-5 * np.abs(ref[1]).max()
    )


@pytest.mark.parametrize(
    "out", [BF16, F32], ids=["bf16-out", "float32-out"]
)
def test_bf16_operands_round_once(out):
    """bf16 in, float32 inside, ONE rounding to the type asked for:
    the plain form's values in float32, and in bf16 but for a value
    in a thousand that lands on the neighbour; the
    three gradients are rounded to bf16 once on each side and the
    taps' gradient is a float32 sum."""
    args, weights = operands(1, 300, 256, dtype=BF16)

    def ours(*a):
        return short_conv(*a, dtype=out)

    def theirs(*a):
        return short_conv_plain(*a, dtype=out)

    got, want = ours(*args), theirs(*args)
    assert got.dtype == out
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if out == BF16:
        # a neighbour in bf16 at most, and rarely that
        assert np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + 1e-6)
        assert np.mean(got != want) < 1e-3
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    mine = gradients(ours, args, weights)
    ref = gradients(theirs, args, weights)
    np.testing.assert_allclose(mine[0], ref[0], rtol=2.0 ** -7, atol=1e-6)
    np.testing.assert_allclose(
        mine[1], ref[1], rtol=1e-5, atol=1e-5 * np.abs(ref[1]).max()
    )


def test_nothing_comes_from_after_t_nor_from_another_channel():
    (bcu, taps), _ = operands(1, 40, 8)
    y = short_conv(bcu, taps)
    later = bcu.at[0, 20:].set(7.0)
    np.testing.assert_array_equal(
        np.asarray(y)[0, :20], np.asarray(short_conv(later, taps))[0, :20]
    )
    # channel 3 of u reaches channel 3 of y alone, three rows of it
    moved = np.asarray(
        short_conv(bcu.at[0, 10, 2 * 8 + 3].add(1.0), taps) - y
    )[0]
    assert set(zip(*np.nonzero(moved))) == {(10, 3), (11, 3), (12, 3)}


@pytest.mark.parametrize("t", [0, 1, 127, 128, 129, 255, 256, 257, 599])
def test_a_row_reaches_two_rows_across_strip_and_tile_edges(t, small_tiles):
    """One row of ``u`` (``B = C = 1``) comes out at rows ``t, t + 1,
    t + 2`` under taps ``K - 1 .. 0`` (``taps[j]`` meets ``v_{t - (K -
    1 - j)}``), across the edges of a strip (128 rows) and of a row
    tile (256), and its gradient comes back from them."""
    s, c = 600, 256
    taps = jnp.asarray([[1.0], [10.0], [100.0]]) * jnp.ones((1, c))
    bcu = jnp.ones((1, s, 3 * c)).at[:, :, 2 * c:].set(0.0)
    y = np.asarray(short_conv(bcu.at[0, t, 2 * c:].set(1.0), taps))[0, :, 0]
    want = np.zeros(s)
    want[t:t + 3] = [100.0, 10.0, 1.0][:s - t]
    np.testing.assert_array_equal(y, want)
    du = np.asarray(jax.grad(
        lambda x: short_conv(x, taps)[0, min(t + 2, s - 1), 0]
    )(bcu))[0, :, 2 * c]
    assert du[t] == (1.0 if t + 2 < s else 100.0) and du.sum() == 111.0


def test_the_first_tiles_halo_is_zeros():
    """``v`` before row 0 is 0: the first rows see fewer taps, in
    every sequence of the batch (the halo view of a sequence's first
    tile reads the rows of ANOTHER sequence's end)."""
    s, c = 40, 128
    bcu = jnp.ones((3, s, 3 * c))
    taps = jnp.ones((3, c))
    y = np.asarray(short_conv(bcu, taps))
    np.testing.assert_array_equal(y[:, 0], 1.0)
    np.testing.assert_array_equal(y[:, 1], 2.0)
    np.testing.assert_array_equal(y[:, 2:], 3.0)
    # and past the last row: the last two rows' u reach fewer outputs
    du = np.asarray(jax.grad(lambda x: short_conv(x, taps).sum())(bcu))
    np.testing.assert_array_equal(du[:, -1, 2 * c:], 1.0)
    np.testing.assert_array_equal(du[:, -2, 2 * c:], 2.0)
    np.testing.assert_array_equal(du[:, :-2, 2 * c:], 3.0)


def test_gradients_through_a_rematted_block():
    """``jax.checkpoint`` round the call (the models' per-layer
    remat) runs ``bcx_fwd`` again in the backward and gives the same
    gradients."""
    args, weights = operands(2, 72, 96, dtype=BF16, seed=7)
    plainly = gradients(short_conv, args, weights)
    rematted = gradients(jax.checkpoint(short_conv), args, weights)
    for a, b in zip(plainly, rematted):
        np.testing.assert_array_equal(a, b)


def test_what_the_tiling_reads_off_the_shapes():
    # the published width in bf16: 256 rows forward, 128 backward
    assert sc._tile_rows(8192, 4 * 2048, 2) == 256
    assert sc._tile_rows(8192, 7 * 2048, 2) == 128
    # float32 operands (the tests' exact path) halve them
    assert sc._tile_rows(8192, 4 * 2048, 4) == 128
    # no more rows than the sequence needs, a halo's at least
    assert sc._tile_rows(40, 4 * 128, 4) == 64
    assert sc._tile_rows(5, 4 * 128, 4) == cc.HALO
    # both calls' blocks, held twice, inside the VMEM a kernel is
    # granted unasked
    for lanes, rows in ((4 * 2048, 256), (7 * 2048, 128)):
        assert 2 * rows * lanes * 2 <= sc.VMEM_BYTES < 16 << 20
    # the helpers and constants are the ungated convolution's own
    for name in ("_shifted", "_rows_before", "_rows_after", "_columns"):
        assert getattr(sc, name) is getattr(cc, name), name


def test_other_lanes_or_too_many_taps_are_refused():
    (bcu, taps), _ = operands(1, 16, 8)
    with pytest.raises(ValueError, match="three windows"):
        short_conv(bcu[..., :-1], taps)
    with pytest.raises(ValueError, match="8 taps"):
        short_conv(bcu, jnp.ones((8, 8)))
