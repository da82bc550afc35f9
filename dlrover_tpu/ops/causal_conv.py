"""The depthwise causal convolution of a state-space or linear-attention
mixer, with its bias and SiLU, as one Pallas pass forward and one
backward::

    pre_t = sum_j taps[j] * x_{t-K+1+j} (+ bias)      j = 0 .. K-1
    y_t   = pre_t * sigmoid(pre_t)                    (x before row 0 is 0)

``x [b, s, C]`` in the caller's type, ``taps [K, c]`` over the ``c``
lanes of ``x`` that start at lane ``first``, a channel a lane, nothing
from after ``t``.  ``K = 4`` taps over ``8192 x 6144`` is no work to
speak of and 100 MB to read: the plain form (pad the sequence, cast to
float32, add four slices that start at rows 0 .. 3) writes the padded
float32 copy and reads it at four row offsets that are no multiple of
a sublane tile, and its transpose does the same again, nine times the
bytes' time in all (PERF.md, PR 49).  Here a grid step is a tile of
rows by a tile of lanes in the caller's own layout.  ``conv_fwd`` casts
the tile to float32 in VMEM, takes the ``K - 1`` shifted copies as
sublane rotations of what it holds, sums the terms in the order the
plain form does, adds the bias, applies SiLU and rounds ONCE, to the
type the caller asks for: nothing float32 and nothing padded reaches
HBM.  The rows before a tile come from a second view of the same
operand (the 16 rows that end where the tile starts; zeros before row
0), so no tile waits for another.

``conv_bwd`` reads ``x`` and ``dy``, makes ``pre`` again in VMEM (the
same terms: cheaper than keeping 100 MB a layer) and::

    g_t   = dy_t * sigmoid(pre_t) (1 + pre_t (1 - sigmoid(pre_t)))
    dx_t  = sum_j taps[j] * g_{t+K-1-j}
    dtaps[j] = sum_t g_t x_{t-K+1+j}         dbias = sum_t g_t

with ``g`` of the ``K - 1`` rows AFTER the tile made from a view of
the 16 rows of ``x`` and ``dy`` that follow it (zeros after the last
row).  ``dtaps`` and ``dbias`` are float32 sums in an output block that
stays resident over the row tiles (rows ``0 .. K-1`` and ``K`` of an
``[8, c]`` array).  A ``jax.custom_vjp`` joins the two: the residuals
are the caller's operands, and a model calls it inside a rematted
block as it stands (``models/layers.py::rematted``).

What differs between callers is read off the operands.  A window of
``x`` whose first lane and width are whole 128-lane tiles is read in
place at a block offset (the state-space mixer's ``x``, ``B`` and
``C`` out of its input projection's ``[.., 10304]``: no slice copy on
either side); a width that is no multiple of 128 (2880 lanes of the
hybrid's ``q`` and ``k``, a toy's 96) is one block of the whole width,
the one such block Pallas allows, with fewer rows to a tile; a window
that is neither is sliced out first (toys only).  A sequence that
fills no row tile is padded with zero rows.  No bias is a static
absence, not a zero array.  On the CPU the kernels run in interpreter
mode.  Mosaic kernels are not auto-partitioned: rows of different
sequences and lanes are independent, so under a mesh the call needs a
``shard_map``: none yet (M6(b4)).

Precision: operands in the caller's type (bf16 on the training path),
every product, sum, the bias, SiLU and its derivative in float32, one
rounding to the output's type (``dx`` to ``x``'s); ``dtaps`` and
``dbias`` are float32 throughout.  That is the plain form's, the
sigmoid included: ``jax.nn.sigmoid``, as ``nn.silu`` has it (on the
chip Mosaic's ``tanh`` is an approximation: ``(1 + tanh(pre / 2)) / 2``
in its place was a tenth faster and read 5.5e-5 from the float64 value
of a float32 output where this reads 1.2e-6, the plain form's own:
PERF.md, PR 49).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.gated_delta_rule import F32, _interpret

LANES = 128   # a lane tile: the columns a kernel walks inside its block
HALO = 16     # rows of a halo view: one sublane tile of bf16
EDGE = 8      # of which the nearest 8 are read (float32's tile): K <= 7
STRIP = 128   # rows taken through the arithmetic at a time (in vregs)
SUMS = 8      # rows of the sums' block: K of the taps, one of the bias
MAX_ROWS = 512          # of a block
MAX_LANE_TILES = 8      # of a block that is not the whole width
BLOCK_BYTES = 3 << 20   # of a block's float32 form: 3 MB


def _params():
    """The grid is ``(lane tiles, batch x row tiles)``.  The row axis
    is ``arbitrary`` and the backward RELIES on it: ``dtaps`` and
    ``dbias`` add up in an output block that stays resident while a
    lane tile's row tiles pass in order, so a chip with two cores may
    split the lane tiles between them and never the rows (this
    module's own, not another kernel's tuning: retuning that one must
    not turn these sums into a race)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary")
    )


def _shifted(ext, taps_n, rows):
    """``[x_{t-K+1+j} for j]`` over ``rows`` rows from ``ext``, whose
    row ``EDGE`` is the first of them: sublane rotations."""
    return [
        (
            pltpu.roll(ext, taps_n - 1 - j, 0) if j < taps_n - 1 else ext
        )[EDGE:EDGE + rows]
        for j in range(taps_n)
    ]


def _pre(xs, taps, bias):
    """The pre-activation from the shifted copies, summed as the plain
    form sums them (``j = 0`` first, the bias last)."""
    pre = xs[0] * taps[0:1]
    for j in range(1, len(xs)):
        pre = pre + xs[j] * taps[j:j + 1]
    return pre if bias is None else pre + bias


def _columns(width, column):
    """``column(at)`` for every 128-lane column ``at`` of a block: a
    loop over the whole ones (ONE trace of the body: unrolled, a
    block of 8 columns by 8 strips took a second to trace and the
    step's programs 12 s more at every launch, PERF.md, PR 49), then
    what is left of a width that is no multiple of 128."""
    whole = width // LANES

    def body(c, _):
        column(pl.ds(pl.multiple_of(c * LANES, LANES), LANES))
        return _

    if whole:
        jax.lax.fori_loop(0, whole, body, 0)
    if width % LANES:
        column(pl.ds(whole * LANES, width % LANES))


def _rows(ref, lo, n, at):
    return ref[0, pl.ds(pl.multiple_of(lo, EDGE), n), at].astype(F32)


def _rows_before(ref, halo_ref, lo, at, first):
    """The ``EDGE`` rows that end where row ``lo`` of the tile starts,
    float32: the tile's own, or above its top the halo view's (zeros
    where the sequence starts)."""
    inside = _rows(ref, jnp.maximum(lo - HALO, 0), HALO, at)[EDGE:]
    above = jnp.where(first, 0.0, halo_ref[0, :, at].astype(F32)[EDGE:])
    return jnp.where(lo == 0, above, inside)


def _rows_after(ref, halo_ref, hi, at, last):
    """The ``EDGE`` rows from row ``hi`` of the tile on: below its end
    the halo view's (zeros where the sequence ends)."""
    r = ref.shape[1]
    inside = _rows(ref, jnp.minimum(hi, r - HALO), HALO, at)[:EDGE]
    below = jnp.where(last, 0.0, halo_ref[0, :, at].astype(F32)[:EDGE])
    return jnp.where(hi == r, below, inside)


def _fwd_kernel(*refs, tiles, has_bias):
    x_ref, prev_ref, taps_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    y_ref = refs[-1]
    first = pl.program_id(1) % tiles == 0
    r = x_ref.shape[1]
    k = taps_ref.shape[0]
    strip = min(STRIP, r)

    def column(at):
        taps = taps_ref[:, at].astype(F32)
        bias = bias_ref[:, at].astype(F32) if has_bias else None

        def rows(n, before):
            lo = n * strip
            cur = _rows(x_ref, lo, strip, at)
            pre = _pre(_shifted(
                jnp.concatenate([before, cur], axis=0), k, strip
            ), taps, bias)
            y_ref[0, pl.ds(pl.multiple_of(lo, EDGE), strip), at] = (
                pre * jax.nn.sigmoid(pre)
            ).astype(y_ref.dtype)
            return cur[strip - EDGE:]

        jax.lax.fori_loop(0, r // strip, rows, jnp.where(
            first, 0.0, prev_ref[0, :, at].astype(F32)[EDGE:]
        ))

    _columns(x_ref.shape[2], column)


def _fold(x):
    """``[rows, w]`` summed down to one sublane tile ``[EDGE, w]``:
    whole-register adds."""
    out = x[:EDGE]
    for i in range(EDGE, x.shape[0], EDGE):
        out = out + x[i:i + EDGE]
    return out


def _bwd_kernel(*refs, tiles, has_bias):
    x_ref, prev_ref, next_ref, dy_ref, dnext_ref, taps_ref = refs[:6]
    bias_ref = refs[6] if has_bias else None
    dx_ref, sums_ref = refs[-2:]
    i = pl.program_id(1)
    first, last = i % tiles == 0, i % tiles == tiles - 1
    r = x_ref.shape[1]
    k = taps_ref.shape[0]
    strip = min(STRIP, r)

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def column(at):
        taps = taps_ref[:, at].astype(F32)
        bias = bias_ref[:, at].astype(F32) if has_bias else None

        def rows(n, sums):
            lo = n * strip
            hi = lo + strip
            # g over the strip AND the EDGE rows after it (dx reads
            # them), so x from EDGE rows before to EDGE rows after
            xs = _shifted(jnp.concatenate([
                _rows_before(x_ref, prev_ref, lo, at, first),
                _rows(x_ref, lo, strip, at),
                _rows_after(x_ref, next_ref, hi, at, last),
            ], axis=0), k, strip + EDGE)
            pre = _pre(xs, taps, bias)
            sig = jax.nn.sigmoid(pre)
            g = jnp.concatenate([
                _rows(dy_ref, lo, strip, at),
                _rows_after(dy_ref, dnext_ref, hi, at, last),
            ], axis=0) * (sig * (1.0 + pre * (1.0 - sig)))
            dx = g[:strip] * taps[k - 1:k]
            for j in range(k - 1):
                # g_{t+K-1-j}: a rotation the other way
                dx = dx + pltpu.roll(
                    g, strip + EDGE - (k - 1 - j), 0
                )[:strip] * taps[j:j + 1]
            dx_ref[0, pl.ds(pl.multiple_of(lo, EDGE), strip), at] = (
                dx.astype(dx_ref.dtype)
            )
            mine = g[:strip]
            return tuple(
                total + _fold(part) for total, part in zip(
                    sums, [mine * x[:strip] for x in xs] + [mine]
                )
            )

        zero = jnp.zeros((EDGE, taps.shape[1]), F32)
        sums = jax.lax.fori_loop(0, r // strip, rows, (zero,) * (k + 1))
        block = jnp.zeros((SUMS, taps.shape[1]), F32)
        row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
        for j, part in enumerate(sums):
            block = jnp.where(
                row == j, jnp.sum(part, axis=0, keepdims=True), block
            )
        sums_ref[:, at] += block

    _columns(x_ref.shape[2], column)


def _tiling(s, first, c, total):
    """``(rows, lanes, whole)`` of a block: ``whole`` says whether the
    window can be read out of the array as it stands.  Lanes: the
    most lane tiles up to ``MAX_LANE_TILES`` that divide both the
    window's first lane and its width, else the whole width.  Rows: a
    power of two up to ``MAX_ROWS`` that keeps a block's float32 form
    within ``BLOCK_BYTES`` (256 x 2880 lanes is 2.9 MB)."""
    if c % LANES == 0 and first % LANES == 0:
        tiles = math.gcd(first, c) // LANES
        lanes = LANES * max(
            n for n in range(1, MAX_LANE_TILES + 1) if tiles % n == 0
        )
        whole = True
    else:
        lanes, whole = c, first == 0 and c == total
    rows = HALO
    while rows < min(s, MAX_ROWS) and 2 * rows * lanes * 4 <= BLOCK_BYTES:
        rows *= 2
    return rows, lanes, whole


def _whole_tiles(x, rows):
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % rows), (0, 0)))


def _window(x, first, taps):
    """What both calls share: ``(x or its window's copy, padded to
    whole row tiles; rows of a tile; tiles of a sequence; the grid
    ``(lane tiles, batch x row tiles)``; the block specs over it)``."""
    b, s, total = x.shape
    k, c = taps.shape
    rows, lanes, whole = _tiling(s, first, c, total)
    if not whole:
        x, first = x[..., first:first + c], 0
    x = _whole_tiles(x, rows)
    tiles = x.shape[1] // rows
    halos, lane0 = rows // HALO, first // lanes

    def tile(lane_of):
        return pl.BlockSpec(
            (1, rows, lanes),
            lambda j, i: (i // tiles, i % tiles, lane_of + j),
        )

    def halo(row_of, lane_of):
        return pl.BlockSpec(
            (1, HALO, lanes),
            lambda j, i: (i // tiles, row_of(i % tiles), lane_of + j),
        )

    def before(t):
        return jnp.maximum(t * halos - 1, 0)

    def after(t):
        return jnp.minimum((t + 1) * halos, tiles * halos - 1)

    return x, rows, tiles, (c // lanes, b * tiles), dict(
        x=tile(lane0), out=tile(0),
        # the HALO rows that end where the tile starts / start where
        # it ends (any rows at the sequence's own ends: zeroed inside)
        prev=halo(before, lane0), next=halo(after, lane0),
        out_next=halo(after, 0),
        taps=pl.BlockSpec((k, lanes), lambda j, i: (0, j)),
        bias=pl.BlockSpec((1, lanes), lambda j, i: (0, j)),
        sums=pl.BlockSpec((SUMS, lanes), lambda j, i: (0, j)),
    )


def _row(bias):
    return [] if bias is None else [bias.reshape(1, -1)]


# (jitted: traced once for all of a model's layers and call sites)
@functools.partial(jax.jit, static_argnames=("first", "dtype"))
def _forward(x, taps, bias, *, first, dtype):
    """``y [b, s, c]`` in ``dtype`` from lanes ``[first, first + c)``
    of ``x``."""
    b, s, _ = x.shape
    x, _, tiles, grid, spec = _window(x, first, taps)
    has_bias = bias is not None
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, has_bias=has_bias),
        grid=grid,
        in_specs=[spec["x"], spec["prev"], spec["taps"]]
        + [spec["bias"]] * has_bias,
        out_specs=spec["out"],
        out_shape=jax.ShapeDtypeStruct(
            (b, x.shape[1], taps.shape[1]), dtype
        ),
        compiler_params=_params(),
        interpret=_interpret(),
        name="conv_fwd",
    )(x, x, taps, *_row(bias))[:, :s]


@functools.partial(jax.jit, static_argnames=("first",))
def _backward(x, taps, bias, dy, *, first):
    """-> ``(dx [b, s, c] in x's type, sums [SUMS, c] float32: rows
    ``0 .. K-1`` the taps' gradient, row ``K`` the bias's)``."""
    s = x.shape[1]
    x, rows, tiles, grid, spec = _window(x, first, taps)
    dy = _whole_tiles(dy, rows)
    has_bias = bias is not None
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles, has_bias=has_bias),
        grid=grid,
        in_specs=[
            spec["x"], spec["prev"], spec["next"], spec["out"],
            spec["out_next"], spec["taps"],
        ] + [spec["bias"]] * has_bias,
        out_specs=[spec["out"], spec["sums"]],
        out_shape=[
            jax.ShapeDtypeStruct(dy.shape, x.dtype),
            jax.ShapeDtypeStruct((SUMS, taps.shape[1]), F32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
        name="conv_bwd",
    )(x, x, x, dy, dy, taps, *_row(bias))
    return dx[:, :s], sums


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, taps, bias, first, dtype):
    return _forward(x, taps, bias, first=first, dtype=dtype)


def _conv_fwd(x, taps, bias, first, dtype):
    return _conv(x, taps, bias, first, dtype), (x, taps, bias)


def _conv_bwd(first, dtype, kept, dy):
    x, taps, bias = kept
    k, c = taps.shape
    dx, sums = _backward(x, taps, bias, dy, first=first)
    # (the window's gradient in the whole operand's lanes: XLA folds
    # the pad into whatever adds the other lanes' gradients)
    dx = jnp.pad(
        dx, ((0, 0), (0, 0), (first, x.shape[2] - first - c))
    )
    return dx, sums[:k].astype(taps.dtype), (
        None if bias is None else sums[k].astype(bias.dtype)
    )


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv(
    x: jax.Array,      # [b, s, C]
    taps: jax.Array,   # [K, c]
    bias=None,         # [c]
    *,
    first: int = 0,
    dtype=None,
):
    """``SiLU(depthwise causal conv + bias)`` of lanes ``[first, first
    + c)`` of ``x``, ``[b, s, c]`` in ``dtype`` (``x``'s unless
    given); differentiable in ``x``, ``taps`` and ``bias``."""
    k, c = taps.shape
    if k > SUMS - 1 or first + c > x.shape[2]:
        raise ValueError(
            f"{k} taps over lanes {first}..{first + c} of {x.shape}"
        )
    return _conv(x, taps, bias, first, jnp.dtype(dtype or x.dtype))
