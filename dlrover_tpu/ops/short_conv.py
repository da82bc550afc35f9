"""The doubly gated short convolution of a convolution-and-attention
hybrid (LFM2's ``Lfm2ShortConv``), as one Pallas pass forward and one
backward over the input projection's own array::

    [B | C | u] = bcu            three lane windows of c: 0, c, 2 c
    v_t = B_t * u_t
    c_t = sum_j taps[j] * v_{t-K+1+j}        j = 0 .. K-1 (v before row 0 is 0)
    y_t = C_t * c_t

``bcu [b, s, 3 c]`` in the caller's type, ``taps [K, c]`` a channel a
lane, no bias and NO activation anywhere: a gate before a depthwise
causal convolution of ``K`` taps and a gate after it.  The plain form
(three slices, two products, a pad, float32 casts, ``K`` shifted adds,
each through HBM, and as much again backward) moves some nine times the
bytes the arithmetic needs (PERF.md, PR 49, of the ungated form).  Here
a grid step is a tile of rows by the ``c`` lanes of a window, each
window read IN PLACE at its lane offset out of ``bcu`` (no slice copy),
and the tiling's constants, the halo rule and the in-register helpers
are ``ops/causal_conv.py``'s, whose kernels stay as they are.

``bcx_fwd`` casts the three tiles to float32 in VMEM, multiplies ``B``
by ``u``, takes the ``K - 1`` shifted copies as sublane rotations of
what it holds (the rows before a tile from a view of the 16 rows of
``B`` and ``u`` that end where the tile starts; zeros before row 0),
sums the terms in the plain form's order, multiplies by ``C`` and
rounds ONCE, to the type the caller asks for.

``bcx_bwd`` reads the three windows and ``dy``, makes ``v`` and ``c``
again in VMEM and::

    g_t  = dy_t * C_t                    dC_t = dy_t * c_t
    dv_t = sum_j taps[j] * g_{t+K-1-j}   (rows after the tile from the
                                          16 rows of dy and C that
                                          follow it; zeros past the end)
    dB_t = dv_t * u_t                    du_t = dv_t * B_t
    dtaps[j] = sum_t g_t v_{t-K+1+j}

and writes ``dB | dC | du`` as the lane windows of ONE ``[b, s, 3 c]``
output, which the input projection's backward matmuls read as it
stands (no concatenate); ``dtaps`` are float32 sums in an output block
that stays resident over the row tiles (rows ``0 .. K-1`` of an ``[8,
c]`` array).  A ``jax.custom_vjp`` joins the two: the residuals are
the caller's operands, and a model calls it inside a rematted block as
it stands (``models/layers.py::rematted``).

A width ``c`` that is no multiple of 128 (toys) is widened to whole
lane tiles with zero lanes first; a sequence that fills no row tile is
padded with zero rows.  On the CPU the kernels run in interpreter
mode.  Mosaic kernels are not auto-partitioned: under a mesh the call
needs a ``shard_map``, as ``causal_conv`` does (M6(b4)).

Precision: operands in the caller's type (bf16 on the training path),
every product and sum in float32, one rounding to the output's type
(the three gradients to ``bcu``'s); ``dtaps`` float32 throughout.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.causal_conv import (
    EDGE,
    HALO,
    LANES,
    MAX_ROWS,
    STRIP,
    SUMS,
    _columns,
    _fold,
    _params,
    _pre,
    _rows,
    _rows_after,
    _rows_before,
    _shifted,
    _whole_tiles,
)
from dlrover_tpu.ops.gated_delta_rule import F32, _interpret

# of a call's blocks in VMEM, each held twice (the pipeline's two
# buffers): under the 16 MB a v5e's compiler grants a kernel unasked
VMEM_BYTES = 12 << 20
B, C, U = 0, 1, 2   # the windows' order in the projection's lanes


def _tile_rows(s, lanes, itemsize):
    """Rows of a tile: a power of two up to ``MAX_ROWS`` (and no more
    than the sequence needs) that keeps a grid step's ``lanes`` lanes
    of blocks, held twice, within ``VMEM_BYTES``."""
    rows = HALO
    while (
        rows < min(s, MAX_ROWS)
        and 2 * (2 * rows) * lanes * itemsize <= VMEM_BYTES
    ):
        rows *= 2
    return rows


def _specs(c, rows, tiles):
    """Block specs over the grid ``(1, batch x row tiles)``: a tile or
    a 16-row halo view of one window (``c`` lanes at lane block
    ``window``) of an array, the taps and the sums."""
    halos = rows // HALO

    def tile(window, width=c):
        return pl.BlockSpec(
            (1, rows, width),
            lambda j, i: (i // tiles, i % tiles, window),
        )

    def before(window):
        # the HALO rows that end where the tile starts (any rows at
        # the sequence's start: zeroed inside)
        return pl.BlockSpec((1, HALO, c), lambda j, i: (
            i // tiles, jnp.maximum(i % tiles * halos - 1, 0), window,
        ))

    def after(window):
        # the HALO rows that start where the tile ends
        return pl.BlockSpec((1, HALO, c), lambda j, i: (
            i // tiles,
            jnp.minimum((i % tiles + 1) * halos, tiles * halos - 1),
            window,
        ))

    return tile, before, after


def _fwd_kernel(
    b_ref, c_ref, u_ref, bprev_ref, uprev_ref, taps_ref, y_ref, *, tiles
):
    first = pl.program_id(1) % tiles == 0
    r = b_ref.shape[1]
    k = taps_ref.shape[0]
    strip = min(STRIP, r)

    def column(at):
        taps = taps_ref[:, at].astype(F32)

        def rows(n, before):
            lo = n * strip
            v = _rows(b_ref, lo, strip, at) * _rows(u_ref, lo, strip, at)
            conv = _pre(_shifted(
                jnp.concatenate([before, v], axis=0), k, strip
            ), taps, None)
            y_ref[0, pl.ds(pl.multiple_of(lo, EDGE), strip), at] = (
                _rows(c_ref, lo, strip, at) * conv
            ).astype(y_ref.dtype)
            return v[strip - EDGE:]

        jax.lax.fori_loop(0, r // strip, rows, jnp.where(
            first, 0.0,
            bprev_ref[0, :, at].astype(F32)[EDGE:]
            * uprev_ref[0, :, at].astype(F32)[EDGE:],
        ))

    _columns(b_ref.shape[2], column)


def _bwd_kernel(
    b_ref, c_ref, u_ref, bprev_ref, uprev_ref, cnext_ref, dy_ref,
    dnext_ref, taps_ref, d_ref, sums_ref, *, tiles,
):
    i = pl.program_id(1)
    first, last = i % tiles == 0, i % tiles == tiles - 1
    r, c = b_ref.shape[1:]
    k = taps_ref.shape[0]
    strip = min(STRIP, r)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def column(at):
        taps = taps_ref[:, at].astype(F32)

        def window(w):
            # the same column of window ``w`` of the one output
            return pl.ds(pl.multiple_of(at.start + w * c, LANES), at.size)

        def rows(n, sums):
            lo = n * strip
            hi = lo + strip
            here = pl.ds(pl.multiple_of(lo, EDGE), strip)
            gate_b = _rows(b_ref, lo, strip, at)
            u = _rows(u_ref, lo, strip, at)
            # v from EDGE rows before the strip on: c reads them
            vs = _shifted(jnp.concatenate([
                _rows_before(b_ref, bprev_ref, lo, at, first)
                * _rows_before(u_ref, uprev_ref, lo, at, first),
                gate_b * u,
            ], axis=0), k, strip)
            dy = _rows(dy_ref, lo, strip, at)
            gate_c = _rows(c_ref, lo, strip, at)
            d_ref[0, here, window(C)] = (
                dy * _pre(vs, taps, None)
            ).astype(d_ref.dtype)
            # g over the strip AND the EDGE rows after it: dv reads them
            g = jnp.concatenate([
                dy * gate_c,
                _rows_after(dy_ref, dnext_ref, hi, at, last)
                * _rows_after(c_ref, cnext_ref, hi, at, last),
            ], axis=0)
            dv = g[:strip] * taps[k - 1:k]
            for j in range(k - 1):
                # g_{t+K-1-j}: a rotation the other way
                dv = dv + pltpu.roll(
                    g, strip + EDGE - (k - 1 - j), 0
                )[:strip] * taps[j:j + 1]
            d_ref[0, here, window(B)] = (dv * u).astype(d_ref.dtype)
            d_ref[0, here, window(U)] = (dv * gate_b).astype(d_ref.dtype)
            mine = g[:strip]
            return tuple(
                total + _fold(mine * v) for total, v in zip(sums, vs)
            )

        zero = jnp.zeros((EDGE, taps.shape[1]), F32)
        sums = jax.lax.fori_loop(0, r // strip, rows, (zero,) * k)
        block = jnp.zeros((SUMS, taps.shape[1]), F32)
        row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
        for j, part in enumerate(sums):
            block = jnp.where(
                row == j, jnp.sum(part, axis=0, keepdims=True), block
            )
        sums_ref[:, at] += block

    _columns(c, column)


def _lane_tiles(bcu, taps):
    """``(bcu, taps)`` with each window widened to whole lane tiles by
    zero lanes (a toy's width; the published one is 16 tiles)."""
    c = taps.shape[1]
    extra = -c % LANES
    if not extra:
        return bcu, taps
    b, s, _ = bcu.shape
    return jnp.pad(
        bcu.reshape(b, s, 3, c), ((0, 0),) * 3 + ((0, extra),)
    ).reshape(b, s, 3 * (c + extra)), jnp.pad(taps, ((0, 0), (0, extra)))


# (jitted: traced once for all of a model's layers and call sites)
@functools.partial(jax.jit, static_argnames=("dtype",))
def _forward(bcu, taps, *, dtype):
    """``y [b, s, c]`` in ``dtype``."""
    b, s, _ = bcu.shape
    c = taps.shape[1]
    bcu, taps = _lane_tiles(bcu, taps)
    k, wide = taps.shape
    rows = _tile_rows(s, 4 * wide, max(bcu.dtype.itemsize, dtype.itemsize))
    bcu = _whole_tiles(bcu, rows)
    tiles = bcu.shape[1] // rows
    tile, before, _ = _specs(wide, rows, tiles)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles),
        grid=(1, b * tiles),
        in_specs=[
            tile(B), tile(C), tile(U), before(B), before(U),
            pl.BlockSpec((k, wide), lambda j, i: (0, 0)),
        ],
        out_specs=tile(0),
        out_shape=jax.ShapeDtypeStruct((b, bcu.shape[1], wide), dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="bcx_fwd",
    )(bcu, bcu, bcu, bcu, bcu, taps)[:, :s, :c]


@jax.jit
def _backward(bcu, taps, dy):
    """-> ``(dbcu [b, s, 3 c] in bcu's type: dB | dC | du, dtaps [K,
    c] float32)``."""
    b, s, _ = bcu.shape
    c = taps.shape[1]
    bcu, taps = _lane_tiles(bcu, taps)
    k, wide = taps.shape
    dy = jnp.pad(dy, ((0, 0), (0, 0), (0, wide - c)))
    rows = _tile_rows(s, 7 * wide, bcu.dtype.itemsize)
    bcu, dy = _whole_tiles(bcu, rows), _whole_tiles(dy, rows)
    tiles = bcu.shape[1] // rows
    tile, before, after = _specs(wide, rows, tiles)
    dbcu, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles),
        grid=(1, b * tiles),
        in_specs=[
            tile(B), tile(C), tile(U), before(B), before(U), after(C),
            tile(0), after(0),
            pl.BlockSpec((k, wide), lambda j, i: (0, 0)),
        ],
        out_specs=[
            tile(0, 3 * wide),
            pl.BlockSpec((SUMS, wide), lambda j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
            jax.ShapeDtypeStruct((SUMS, wide), F32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
        name="bcx_bwd",
    )(bcu, bcu, bcu, bcu, bcu, bcu, dy, dy, taps)
    if wide != c:
        dbcu = dbcu.reshape(b, -1, 3, wide)[..., :c].reshape(b, -1, 3 * c)
    return dbcu[:, :s], sums[:k, :c]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv(bcu, taps, dtype):
    return _forward(bcu, taps, dtype=dtype)


def _short_conv_fwd(bcu, taps, dtype):
    return _short_conv(bcu, taps, dtype), (bcu, taps)


def _short_conv_bwd(dtype, kept, dy):
    bcu, taps = kept
    dbcu, dtaps = _backward(bcu, taps, dy)
    return dbcu, dtaps.astype(taps.dtype)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(
    bcu: jax.Array,    # [b, s, 3 c]: B | C | u
    taps: jax.Array,   # [K, c]
    *,
    dtype=None,
):
    """``C * conv_K(B * u)`` of the three lane windows of ``bcu``,
    ``[b, s, c]`` in ``dtype`` (``bcu``'s unless given);
    differentiable in ``bcu`` and ``taps``."""
    k, c = taps.shape
    if k > SUMS - 1 or bcu.shape[2] != 3 * c:
        raise ValueError(
            f"{k} taps over three windows of {c} lanes of {bcu.shape}"
        )
    return _short_conv(bcu, taps, jnp.dtype(dtype or bcu.dtype))


def short_conv_plain(bcu, taps, *, dtype=None):
    """The same in plain ``jax.numpy``: three slices, float32 casts,
    the product, a pad and ``K`` shifted adds, the second gate, one
    rounding.  What the kernels are compared with."""
    k, c = taps.shape
    s = bcu.shape[1]
    gate_b, gate_c, u = (
        bcu[..., w * c:(w + 1) * c].astype(F32) for w in (B, C, U)
    )
    v = jnp.pad(gate_b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(v[:, j:j + s] * taps[j].astype(F32) for j in range(k))
    return (gate_c * conv).astype(dtype or bcu.dtype)
