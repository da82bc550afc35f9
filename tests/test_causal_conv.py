"""The depthwise causal convolution with its bias and SiLU
(``ops/causal_conv.py``: the ``conv_fwd`` / ``conv_bwd`` kernels, in
interpreter mode here) against the plain float32 form written out
below: outputs and all three gradients through the ``custom_vjp``, a
window of the operand's lanes, every kind of tiling the shapes can
ask for, the zeros before row 0 and after the last row, and the
block's remat."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from dlrover_tpu.ops import causal_conv as cc  # noqa: E402
from dlrover_tpu.ops.causal_conv import causal_conv  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16


def plain(x, taps, bias=None, first=0, dtype=None):
    """Pad the sequence, cast to float32, add ``K`` slices that start
    at rows ``0 .. K-1``; bias, SiLU, one cast: what the two mixers
    did before the kernels."""
    k, c = taps.shape
    out = dtype or x.dtype
    x = x[..., first:first + c]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(F32)
    pre = sum(padded[:, j:j + s] * taps[j].astype(F32) for j in range(k))
    if bias is not None:
        pre = pre + bias.astype(F32)
    return jax.nn.silu(pre).astype(out)


def operands(b, s, total, c, bias, dtype=F32, k=4, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bound = k ** -0.5
    return (
        jax.random.normal(keys[0], (b, s, total)).astype(dtype),
        jax.random.uniform(keys[1], (k, c), F32, -bound, bound),
        0.3 * jax.random.normal(keys[2], (c,)) if bias else None,
    ), jax.random.normal(keys[3], (b, s, c))


def gradients(f, args, weights):
    """Of ``sum(f * weights)`` in ``x``, ``taps`` and the bias if there
    is one, as float32."""
    def loss(*a):
        return jnp.sum(f(*a).astype(F32) * weights)

    wrt = tuple(i for i, a in enumerate(args) if a is not None)
    return [
        np.asarray(g, np.float32)
        for g in jax.grad(loss, argnums=wrt)(*args)
    ]


# (b, s, the operand's lanes, first lane, width): one block of a whole
# width that is no multiple of 128 (the hybrid's 2880 stands so; 96 is
# the toys'), rows that fill no tile, lane tiles read in place at a
# block offset with several row tiles (the state-space mixer's x, B,
# C), a window that has to be sliced out (a toy's 32 of 132), a whole
# width of several lane tiles
SHAPES = {
    "whole-96": (2, 40, 96, 0, 96),
    "rows-12": (1, 12, 96, 0, 96),
    "in-place": (2, 1100, 640, 256, 256),
    "sliced-out": (1, 200, 132, 32, 32),
    "lane-tiles": (1, 130, 384, 0, 384),
}


@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_float32_matches_the_plain_form(shape, bias):
    b, s, total, first, c = shape
    args, weights = operands(b, s, total, c, bias)

    def ours(*a):
        return causal_conv(*a, first=first)

    def theirs(*a):
        return plain(*a, first=first)

    got, want = ours(*args), theirs(*args)
    assert got.shape == (b, s, c) and got.dtype == F32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for mine, ref in zip(
        gradients(ours, args, weights), gradients(theirs, args, weights)
    ):
        assert mine.shape == ref.shape
        np.testing.assert_allclose(
            mine, ref, rtol=1e-5, atol=1e-5 * np.abs(ref).max()
        )


@pytest.mark.parametrize(
    "out", [BF16, F32], ids=["bf16-out", "float32-out"]
)
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
def test_bf16_operands_round_once(bias, out):
    """bf16 in, float32 inside, ONE rounding to the type asked for:
    the plain form's values in float32, and in bf16 but for a value
    in a thousand that lands on the neighbour (the two SiLUs differ
    in float32's last bits, which now and then lie across a rounding
    boundary); the taps' and the bias's gradients are float32 sums."""
    b, s, total, first, c = 1, 300, 384, 128, 256
    args, weights = operands(b, s, total, c, bias, dtype=BF16)

    def ours(*a):
        return causal_conv(*a, first=first, dtype=out)

    def theirs(*a):
        return plain(*a, first=first, dtype=out)

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
    # dx is rounded to bf16 once on each side
    np.testing.assert_allclose(mine[0], ref[0], rtol=2.0 ** -7, atol=1e-6)
    for a, r in zip(mine[1:], ref[1:]):
        np.testing.assert_allclose(
            a, r, rtol=1e-5, atol=1e-5 * np.abs(r).max()
        )


def test_the_convolution_is_causal_and_depthwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 3))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    y = causal_conv(x, taps)
    # by hand: y_t = silu(sum_j taps[j] x_{t - 3 + j})
    want = np.zeros((12, 3))
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += np.asarray(taps[j]) * np.asarray(x[0, t - 3 + j])
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)
    moved = causal_conv(x.at[0, 7, 1].add(1.0), taps) - y
    assert not np.asarray(moved[0, :7]).any()       # nothing before t
    assert not np.asarray(moved[0, :, [0, 2]]).any()  # its channel only
    assert np.asarray(moved[0, 7:11, 1]).all()      # exactly K rows
    assert not np.asarray(moved[0, 11:]).any()


@pytest.mark.parametrize("t", [0, 127, 128, 509, 511, 512, 1023, 1098])
def test_a_row_reaches_k_rows_across_strip_and_tile_edges(t):
    """Row ``t`` moves rows ``t .. t + K - 1`` and no other, where
    they lie in the next strip of a tile (128 rows) or in the next
    tile (512 rows of 1100: the halo views), and ``dx`` at ``t``
    reads exactly those rows' cotangents."""
    s = 1100
    (x, taps, bias), _ = operands(1, s, 96, 96, True, seed=3)
    assert cc._tiling(s, 0, 96, 96)[0] == 512
    y = causal_conv(x, taps, bias)
    moved = np.asarray(causal_conv(x.at[0, t].add(1.0), taps, bias) - y)
    rows = np.flatnonzero(np.abs(moved[0]).max(axis=1))
    assert rows.tolist() == list(range(t, min(t + 4, s)))

    def read(at):
        return jax.grad(
            lambda x: causal_conv(x, taps, bias)[0, at].sum()
        )(x)

    for at in (t, min(t + 3, s - 1)):
        rows = np.flatnonzero(np.abs(np.asarray(read(at)[0])).max(axis=1))
        assert rows.tolist() == list(range(max(at - 3, 0), at + 1))


def test_the_first_tiles_halo_is_zeros():
    """Rows ``0 .. K-2`` see zeros before them whatever lies in the
    view the first tile is given (the operand's own first rows): the
    first rows of a sequence alone and of the same rows after a
    prefix differ by the prefix's reach and nothing else."""
    (x, taps, bias), _ = operands(2, 48, 96, 96, True, seed=5)
    y = np.asarray(causal_conv(x, taps, bias))
    alone = np.asarray(causal_conv(x[:, :1], taps, bias))
    want = np.asarray(
        jax.nn.silu(x[:, :1] * taps[3] + bias)
    )
    np.testing.assert_allclose(alone, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[:, :1], want, rtol=1e-5, atol=1e-6)
    # the second sequence of a batch starts from zeros too, not from
    # the first one's last rows
    np.testing.assert_allclose(
        y[1], np.asarray(causal_conv(x[1:], taps, bias))[0],
        rtol=1e-6, atol=1e-6,
    )


def test_gradients_through_a_rematted_block():
    """``jax.checkpoint`` round the call (the models' per-layer
    remat) runs ``conv_fwd`` again in the backward and gives the same
    gradients."""
    args, weights = operands(2, 72, 160, 96, True, dtype=BF16, seed=7)

    def block(*a):
        return causal_conv(*a, first=32)

    plainly = gradients(block, args, weights)
    rematted = gradients(jax.checkpoint(block), args, weights)
    for a, b in zip(plainly, rematted):
        np.testing.assert_array_equal(a, b)


def test_the_sums_block_is_resident_over_a_sequential_row_axis():
    """``dtaps`` and ``dbias`` add up across the row tiles in ONE
    output block a lane tile: right only while the grid's row axis
    runs in order on one core.  The module says so itself
    (``cc._params``, no other kernel's tuning), the call carries it,
    and several row tiles over several lane tiles sum to the plain
    form's."""
    assert cc._params().dimension_semantics == ("parallel", "arbitrary")
    args, weights = operands(2, 1100, 1280, 1280, True, seed=11)
    (call,) = [
        e for e in jax.make_jaxpr(
            lambda x, taps, bias, dy: cc._backward.__wrapped__(
                x, taps, bias, dy, first=0
            )
        )(*args, weights).eqns if e.primitive.name == "pallas_call"
    ]
    assert call.params["name"] == "conv_bwd"
    (params,) = call.params["compiler_params"].values()
    assert params.dimension_semantics == ("parallel", "arbitrary")
    lane_tiles, row_tiles = call.params["grid_mapping"].grid
    assert (lane_tiles, row_tiles) == (2, 2 * 3)
    # the sums' block: where a lane tile says, whatever the row tile
    sums = call.params["grid_mapping"].block_mappings[-1]
    at = sums.index_map_jaxpr
    assert [
        [int(n) for n in jax.core.eval_jaxpr(at.jaxpr, at.consts, j, i)]
        for j in range(2) for i in (0, 5)
    ] == [[0, 0], [0, 0], [0, 1], [0, 1]]
    _, dtaps, dbias = gradients(causal_conv, args, weights)
    _, want_taps, want_bias = gradients(plain, args, weights)
    np.testing.assert_allclose(
        dtaps, want_taps, rtol=1e-5, atol=1e-5 * np.abs(want_taps).max()
    )
    np.testing.assert_allclose(
        dbias, want_bias, rtol=1e-5, atol=1e-5 * np.abs(want_bias).max()
    )


def test_what_the_tiling_reads_off_the_shapes():
    """``(rows, lanes, read in place)`` for the two cells' operands
    and the toys'."""
    # the state-space mixer's x, B, C out of [.., 10304]
    assert cc._tiling(8192, 4096, 4096, 10304) == (512, 1024, True)
    assert cc._tiling(8192, 8192, 1024, 10304) == (512, 1024, True)
    assert cc._tiling(8192, 9216, 1024, 10304) == (512, 1024, True)
    # the hybrid's q / k (22.5 lane tiles: the whole width) and v (45)
    assert cc._tiling(8192, 0, 2880, 2880) == (256, 2880, True)
    assert cc._tiling(8192, 0, 5760, 5760) == (512, 640, True)
    # toys: a whole width, a window to slice out, a short sequence
    assert cc._tiling(40, 0, 96, 96) == (64, 96, True)
    assert cc._tiling(200, 32, 32, 132) == (256, 32, False)
    assert cc._tiling(12, 0, 96, 96) == (16, 96, True)


def test_too_many_taps_or_lanes_are_refused():
    x = jnp.zeros((1, 16, 96))
    with pytest.raises(ValueError, match="taps"):
        causal_conv(x, jnp.zeros((8, 96)))
    with pytest.raises(ValueError, match="lanes 64..160"):
        causal_conv(x, jnp.zeros((4, 96)), first=64)
