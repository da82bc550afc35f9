"""Grouped matmul over tile-aligned groups, as Pallas TPU kernels.

``rows[i] @ weights[group of row i]`` for rows sorted by group: the
expert matmuls of a dropless mixture-of-experts layer
(``parallel/moe.py``).  The caller lays the rows out so that every
group starts on a multiple of the row tile (:func:`group_layout`:
each group is padded to whole tiles with zero rows, an empty group
gets one tile of them), so a tile of rows belongs to exactly one
group and the kernels need no mask: the tile's group comes from a
scalar-prefetched ``tile_group`` and picks the weight block in the
``BlockSpec``'s index map.  Consecutive tiles of one group keep the
same weight block, which Pallas then does not fetch again.

The arrays have a static size (the worst routing), so they end in
tiles of no group, from ``tiles_used`` on.  Such a tile costs an empty
grid step and nothing else: every index map names the last used
tile's blocks for it, so the pipeline fetches nothing and writes
nothing, and the bodies do not run.  Its rows of the result are NOT
WRITTEN (:func:`grouped_matmul`).

Why not ``jax.lax.ragged_dot``: the v5e compiler lowers it to a
Mosaic kernel of its own whose 512-row tiles straddle group
boundaries, and a straddling tile is computed once per group it
touches (191 tile visits for 128 tiles of rows at 64 groups of about
1024: PERF.md, PR 28).  Aligned tiles waste only each group's own
padding.

Three kernels, named for a trace: ``gmm_fwd`` (rows x weights),
``gmm_dlhs`` (the gradient to the rows: the same product against the
transposed weight block), ``gmm_drhs`` (the gradient to the weights:
per group, rows^T x cotangent summed over the group's tiles in a
float32 accumulator).  Interpreter mode off the TPU, as
``flash_attention.py``.

A whole expert, :func:`grouped_expert`, is three more (PR 52), so
that nothing between an expert's matmuls is a pass of XLA's over the
rows at their static size: ``gmm_up_fwd`` (a row tile, read once,
against its group's gate AND up blocks, and the activation from their
float32 accumulators; one block and ``relu ** 2`` for an expert
without a gate), ``gmm_down_dlhs`` (``gmm_dlhs`` of the down
projection with the activation's derivative as its epilogue: it
writes the pre-activations' gradients, not the hidden rows', and a
gate's over the two products the forward kept) and
``gmm_up_dlhs`` (``gmm_dlhs`` over two pairs of operands summed in
one accumulator: ONE gradient to the rows that fed two products).

Beside them, for a layer most of whose tiles hold no row (a chip that
holds a range of the experts, PR 38): ``gmm_tokens_from_rows``
(:func:`tokens_from_rows`: the rows of the used tiles added back to
their tokens, what XLA would run as a scatter-add) and
``gmm_unwritten`` (:func:`unwritten`: a buffer for a walk over the
used tiles to fill, which nothing has zeroed).
"""

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops import flash_attention as _flash

# Tiles (v5e sweep at rows 65536, groups 64, 2048 x 1024 and the
# transposed shape: PERF.md, PR 28): the contraction whole (no
# accumulator pass) and the output as wide as it comes, so a group's
# weight block is fetched once and its rows read once.  Row tiles of
# 512 are 1% faster in the kernels and cost 20% more padded rows in
# everything around them; 128 is 6% slower.  The row tile is part of
# the rows' layout, so all three kernels share it.  The contraction
# stays whole up to 4096 (hidden 4096 x expert 2048, PR 35): split in
# two, the weight block's index changes with every grid step and each
# ROW TILE fetches the group's 8 MB halves again (5.4 ms a call where
# the whole 16 MB block, fetched once a group, takes 0.6).
ROW_TILE = 256
K_TILE = 4096
N_TILE = 2048
# a row tile's sums leave their kernel as one float32 tile
_SUMS_BLOCK = (8, 128)


def _interpret() -> bool:
    # one answer for both kernels of a step: what steers the flash
    # kernel (a compile for a described chip) steers this one
    return _flash._interpret()


def group_layout(group_sizes: jax.Array, rows: int, row_tile: int = ROW_TILE):
    """The tile-aligned layout of ``rows`` sorted rows in groups of
    ``group_sizes``: ``(tile_group [tiles], tiles_used [1],
    padded_starts [groups])``.  Group g's rows live at
    ``padded_starts[g] + (0 .. group_sizes[g])`` of a ``tiles *
    row_tile``-row array, ``tiles = ceil(rows / row_tile) + groups``
    whatever the sizes (every group takes ``max(1, ceil(size /
    row_tile))`` tiles); tiles from ``tiles_used`` on belong to no
    group and are named after the last one."""
    groups = group_sizes.shape[0]
    tiles = pl.cdiv(rows, row_tile) + groups
    per_group = jnp.maximum(pl.cdiv(group_sizes, row_tile), 1)
    ends = jnp.cumsum(per_group)
    tile = jnp.minimum(jnp.arange(tiles), ends[-1] - 1)
    # a tile's group: how many groups end at or before it
    tile_group = jnp.sum(tile[:, None] >= ends[None, :], axis=1)
    return (
        tile_group.astype(jnp.int32),
        ends[-1:].astype(jnp.int32),
        ((ends - per_group) * row_tile).astype(jnp.int32),
    )


def _params(semantics, *block_bytes):
    # double-buffered blocks + scratch + the compiler's own room
    need = 2 * sum(block_bytes) + (8 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=int(min(max(need, 32 << 20), 100 << 20)),
    )


def _nbytes(shape, dtype):
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _held_tile(i, tiles_used):
    # the row tile whose blocks grid step ``i`` names: its own, or for
    # a tile of no group the last used one's (every group takes a
    # tile, so there is one).  A block index that does not change
    # between grid steps is neither fetched nor written back
    return jnp.minimum(i, tiles_used[0] - 1)


def unwritten(shape, dtype, after: jax.Array) -> jax.Array:
    """An array that nothing has written: it holds whatever its
    memory held (NaN off the TPU).  What a walk over the used tiles
    fills, where a ``jnp.zeros`` would be a pass over every row of
    the static size for the sake of rows that nobody reads.  It
    exists no sooner than ``after`` (an operand nobody reads): with
    no operand the compiler allocates every such array of a step as
    the step begins."""
    return pl.pallas_call(
        lambda after_ref, out_ref: None,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=_interpret(),
        name="gmm_unwritten",
    )(after)


# -- rows x weights (forward, and the gradient to the rows) -------------------


def _gmm_kernel(
    tile_group, tiles_used,     # scalar prefetch
    *refs,                      # lhs x [row_tile, tk],
                                # rhs x [1, tk, tn] ([1, tn, tk] transposed),
                                # beside x [row_tile, tn],
                                # outs x [row_tile, tn],
                                # acc [products, row_tile, tn] f32 where k
                                # is split
    lhs: int, rhs: int, beside: int, coeffs: int, tile_sums: int,
    k_steps: int, transpose_rhs: bool, finish,
):
    row, step = pl.program_id(1), pl.program_id(2)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    lhs_refs, refs = refs[:lhs], refs[lhs:]
    rhs_refs, refs = refs[:rhs], refs[rhs:]
    beside_refs, refs = refs[:beside], refs[beside:]
    # an epilogue's learned scalars, whole in SMEM
    coeff_refs, out_refs = refs[:bool(coeffs)], refs[bool(coeffs):]
    if k_steps > 1:
        out_refs, acc_ref = out_refs[:-1], out_refs[-1]
    if tile_sums:
        out_refs, sums_ref = out_refs[:-1], out_refs[-1]

    # a tile of no group holds the last used tile's blocks (_gmm):
    # an ``out_ref`` is that tile's result, not yet written back, and
    # must not be touched
    @pl.when(row < tiles_used[0])
    def _compute():
        # one lhs: its product with each rhs.  Several: the SUM of the
        # pairs' products, one accumulator
        parts = [
            jax.lax.dot_general(
                lhs_ref[...], rhs_ref[0], contract,
                preferred_element_type=jnp.float32,
            )
            for lhs_ref, rhs_ref in zip(
                lhs_refs * rhs if lhs == 1 else lhs_refs, rhs_refs,
                strict=True,
            )
        ]
        if lhs > 1:
            parts = [functools.reduce(operator.add, parts)]

        def store(sums):
            values = finish(
                sums, [ref[...].astype(jnp.float32) for ref in beside_refs],
                *([ref[at] for at in range(coeffs)] for ref in coeff_refs),
            )
            values, totals = values[:len(out_refs)], values[len(out_refs):]
            for ref, value in zip(out_refs, values, strict=True):
                ref[...] = value.astype(ref.dtype)
            if tile_sums:
                # this tile's ``[1, 1]`` sums, one a lane of its block
                lane = jax.lax.broadcasted_iota(
                    jnp.int32, sums_ref.shape[1:], 1
                )
                block = jnp.zeros(sums_ref.shape[1:], jnp.float32)
                for at, total in zip(range(tile_sums), totals, strict=True):
                    block = jnp.where(lane == at, total, block)
                sums_ref[0] = block

        if k_steps == 1:
            store(parts)
            return

        @pl.when(step == 0)
        def _first():
            for at, part in enumerate(parts):
                acc_ref[at] = part

        @pl.when(step > 0)
        def _rest():
            for at, part in enumerate(parts):
                acc_ref[at] += part

        @pl.when(step == k_steps - 1)
        def _store():
            store([acc_ref[at] for at in range(len(parts))])


def _fit_tile(dim: int, tile: int, whole: bool = False) -> int:
    """A dim's tile: the dim whole up to ``tile``, else its largest
    divisor that is a whole number of lanes and at most ``tile`` (a
    hidden size of 3072 under a tile of 2048 goes in halves of 1536;
    2048 and 4096 take the tile itself).  Where there is none the
    tile, which the caller's check then refuses.  ``whole``: the
    epilogue reduces over a row's whole width, so the dim is ONE block
    or the call is refused (a norm over a part is another function)."""
    if dim <= tile:
        return dim
    if whole:
        raise ValueError(
            f"a width of {dim} does not fit one block of {tile}: the "
            "epilogue needs a row's whole width"
        )
    for parts in range(-(-dim // tile), dim // 128 + 1):
        if dim % parts == 0 and (dim // parts) % 128 == 0:
            return dim // parts
    return tile


def _gmm(
    lhs, rhs, tile_group, tiles_used, *, name, tiles, transpose_rhs=False,
    beside=(), outs=1, over=0, finish=lambda sums, beside: sums,
    whole_n=False, coeffs=None, tile_sums=0,
):
    """One walk over the row tiles: ``lhs`` (each ``[m, k]``) against
    their groups' blocks of ``rhs`` (each ``[groups, k, n]``,
    ``[groups, n, k]`` transposed) -> a list of ``outs`` arrays ``[m,
    n]``.
    One lhs is multiplied with every rhs, a product each; several are
    paired with as many rhs and the products SUMMED in one float32
    accumulator.  ``finish(products, beside)`` (float32 in, the
    ``beside [m, n]`` arrays' own tiles among them) makes what is
    written; the first ``over`` outs are written over the ``beside``
    of their index (a tile's block of each is read before it is
    written, and the caller reads that ``beside`` nowhere later).

    An epilogue that is not element-wise says so: ``whole_n`` (it
    reduces over a row's whole width: ``n`` is one block, or the call
    is refused), ``coeffs`` (a float32 vector of learned scalars,
    handed to ``finish`` as a third argument) and ``tile_sums`` (that
    many ``[1, 1]`` sums over a tile's rows follow the ``outs`` values
    in what ``finish`` returns; they come back as one more result,
    ``[tiles, 8, 128]`` float32, a sum a lane of sublane 0, NOT
    WRITTEN for the tiles of no group as every result)."""
    row_tile, k_tile, n_tile = tiles
    m, k = lhs[0].shape
    n = rhs[0].shape[1] if transpose_rhs else rhs[0].shape[2]
    tk, tn = _fit_tile(k, k_tile), _fit_tile(n, n_tile, whole=whole_n)
    if m % row_tile or k % tk or n % tn:
        raise ValueError(
            f"rows {lhs[0].shape} x weights {rhs[0].shape} do not "
            f"divide into tiles {(row_tile, tk, tn)}"
        )
    k_steps = k // tk
    products = len(rhs) if len(lhs) == 1 else 1
    dtype = lhs[0].dtype

    def held_step(i, s, nu):
        # where the contraction is split, a tile of no group also
        # stays on the last used tile's last step, rows and weights
        return jnp.where(i < nu[0], s, k_steps - 1)

    lhs_spec = pl.BlockSpec(
        (row_tile, tk),
        lambda j, i, s, tg, nu: (_held_tile(i, nu), held_step(i, s, nu)),
    )
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (1, tn, tk),
            lambda j, i, s, tg, nu: (tg[i], j, held_step(i, s, nu)),
        )
    else:
        rhs_spec = pl.BlockSpec(
            (1, tk, tn),
            lambda j, i, s, tg, nu: (tg[i], held_step(i, s, nu), j),
        )
    # held over the tiles of no group, the last used tile's result
    # stays in VMEM and is written back once, when the sweep over the
    # rows ends
    out_spec = pl.BlockSpec(
        (row_tile, tn), lambda j, i, s, tg, nu: (_held_tile(i, nu), j)
    )
    # (only with ``whole_n``: one column block, so one writer a tile)
    sums_spec = pl.BlockSpec(
        (1,) + _SUMS_BLOCK,
        lambda j, i, s, tg, nu: (_held_tile(i, nu), 0, 0),
    )
    if tile_sums and not whole_n:
        raise ValueError("a tile's sums need the row's whole width")
    return pl.pallas_call(
        functools.partial(
            _gmm_kernel, lhs=len(lhs), rhs=len(rhs), beside=len(beside),
            coeffs=0 if coeffs is None else coeffs.shape[0],
            tile_sums=tile_sums, k_steps=k_steps,
            transpose_rhs=transpose_rhs, finish=finish,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # rows innermost of the two: a group's tiles follow each
            # other and keep its weight block
            grid=(n // tn, m // row_tile, k_steps),
            in_specs=(
                [lhs_spec] * len(lhs) + [rhs_spec] * len(rhs)
                + [out_spec] * len(beside)
                + [pl.BlockSpec(memory_space=pltpu.SMEM)]
                * (coeffs is not None)
            ),
            out_specs=[out_spec] * outs + [sums_spec] * bool(tile_sums),
            scratch_shapes=[
                pltpu.VMEM((products, row_tile, tn), jnp.float32)
            ] if k_steps > 1 else [],
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), dtype)] * outs + [
            jax.ShapeDtypeStruct((m // row_tile,) + _SUMS_BLOCK, jnp.float32)
        ] * bool(tile_sums),
        # (the scalars are operands 0 and 1)
        input_output_aliases={
            2 + len(lhs) + len(rhs) + at: at for at in range(over)
        },
        # the rows are NOT "parallel": several grid steps share one
        # output block, which is right only on one walk in order.  A
        # chip that splits a parallel dimension over two cores would
        # hand one of them only tiles of no group, and it would write
        # a buffer it never filled over the last used tile (v5e has
        # one core a chip: the order costs nothing there)
        compiler_params=_params(
            ("parallel", "arbitrary", "arbitrary"),
            len(lhs) * _nbytes((row_tile, tk), dtype),
            len(rhs) * _nbytes((tk, tn), rhs[0].dtype),
            (len(beside) + outs) * _nbytes((row_tile, tn), dtype),
            # the accumulators and an epilogue's own values
            (products + len(beside)) * _nbytes((row_tile, tn), jnp.float32),
        ),
        interpret=_interpret(),
        name=name,
    )(
        tile_group, tiles_used, *lhs, *rhs, *beside,
        *(() if coeffs is None else (coeffs,)),
    )


# -- the gradient to the weights ----------------------------------------------------


def _tgmm_kernel(
    tile_group, tiles_used,     # scalar prefetch
    lhs_ref,                    # [row_tile, tk]
    cot_ref,                    # [row_tile, tn]
    out_ref,                    # [1, tk, tn]
    acc_ref,                    # [tk, tn] f32
    *, tiles: int,
):
    row = pl.program_id(2)
    used = tiles_used[0]
    group = tile_group[row]
    first = (row == 0) | (tile_group[jnp.maximum(row - 1, 0)] != group)
    last = (row == used - 1) | (
        tile_group[jnp.minimum(row + 1, tiles - 1)] != group
    )

    @pl.when(row < used)
    def _compute():
        part = jax.lax.dot_general(
            lhs_ref[...], cot_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(first)
        def _first():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _rest():
            acc_ref[...] += part

        @pl.when(last)
        def _store():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(rows, cotangent, tile_group, tiles_used, *, groups, tiles):
    row_tile, _, n_tile = tiles
    m, k = rows.shape
    n = cotangent.shape[1]
    # both sides of the weight block are OUTPUT dims here (its float32
    # accumulator lives in VMEM), so both take the output's tile
    tk, tn = _fit_tile(k, n_tile), _fit_tile(n, n_tile)
    if m % row_tile or k % tk or n % tn:
        raise ValueError(
            f"rows {rows.shape} and cotangent {cotangent.shape} do "
            f"not divide into tiles {(row_tile, tk, tn)}"
        )
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tiles=m // row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // row_tile),
            in_specs=[
                pl.BlockSpec(
                    (row_tile, tk),
                    lambda a, j, i, tg, nu: (_held_tile(i, nu), a),
                ),
                pl.BlockSpec(
                    (row_tile, tn),
                    lambda a, j, i, tg, nu: (_held_tile(i, nu), j),
                ),
            ],
            # every group has a tile, so every block is written; the
            # tiles of no group are named after the last group and
            # leave its block as its last tile stored it
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, j, i, tg, nu: (tg[i], a, j)
            ),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), rows.dtype),
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary"),
            _nbytes((row_tile, tk), rows.dtype),
            _nbytes((row_tile, tn), rows.dtype),
            _nbytes((tk, tn), rows.dtype),
            _nbytes((tk, tn), jnp.float32),
        ),
        interpret=_interpret(),
        name="gmm_drhs",
    )(tile_group, tiles_used, rows, cotangent)


# -- the differentiable product ---------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_matmul(rows, weights, tile_group, tiles_used, tiles):
    (out,) = _gmm(
        [rows], [weights], tile_group, tiles_used, name="gmm_fwd",
        tiles=tiles,
    )
    return out


def _fwd(rows, weights, tile_group, tiles_used, tiles):
    out = _grouped_matmul(rows, weights, tile_group, tiles_used, tiles)
    return out, (rows, weights, tile_group, tiles_used)


def _bwd(tiles, residuals, cotangent):
    rows, weights, tile_group, tiles_used = residuals
    cotangent = cotangent.astype(rows.dtype)
    (d_rows,) = _gmm(
        [cotangent], [weights], tile_group, tiles_used, name="gmm_dlhs",
        transpose_rhs=True, tiles=tiles,
    )
    d_weights = _tgmm(
        rows, cotangent, tile_group, tiles_used,
        groups=weights.shape[0], tiles=tiles,
    )
    return d_rows, d_weights.astype(weights.dtype), None, None


_grouped_matmul.defvjp(_fwd, _bwd)


def grouped_matmul(
    rows: jax.Array,         # [tiles * row_tile, k], tile-aligned groups
    weights: jax.Array,      # [groups, k, n]
    tile_group: jax.Array,   # [tiles] int32   } of group_layout
    tiles_used: jax.Array,   # [1] int32       }
    tiles=(ROW_TILE, K_TILE, N_TILE),
) -> jax.Array:
    """``rows[i] @ weights[tile_group[i // row_tile]]`` -> ``[rows,
    n]``, differentiable in ``rows`` and ``weights``.  Rows of a
    group's padding are zero in and zero out.  The rows of the tiles
    from ``tiles_used`` on are NOT READ and NOT WRITTEN, here and in
    both gradients: the result holds whatever the memory held there,
    and the caller reads no such row (``parallel/moe.py`` gathers
    through indices that name only rows of a group;
    ``tests/test_sarvam_mla.py`` overwrites the others with NaN)."""
    return _grouped_matmul(rows, weights, tile_group, tiles_used, tiles)


# -- a whole expert: up, activation, down ------------------------------------


def _hidden(pre):
    """``silu(gate) * up`` of ``pre = [gate, up]``, ``relu(up) ** 2``
    of ``[up]``: float32 in, float32 out."""
    if len(pre) == 1:
        return jnp.square(jnp.maximum(pre[0], 0.0))
    gate, up = pre
    return gate * jax.nn.sigmoid(gate) * up


def _d_activation(d_hidden, kept):
    """The pre-activations' gradients from the hidden rows' and what
    the forward kept: ``[gate, up]``, or without a gate the hidden
    rows, ``relu(up) ** 2``, whose root is ``relu(up)`` (half the
    rounding of a kept ``up``)."""
    (d_hidden,) = d_hidden
    if len(kept) == 1:
        return [2.0 * jnp.sqrt(kept[0]) * d_hidden]
    gate, up = kept
    sig = jax.nn.sigmoid(gate)
    return [
        d_hidden * up * (sig * (1.0 + gate * (1.0 - sig))),
        d_hidden * (gate * sig),
    ]


POLYNORM_EPS = 1e-6


def _poly_parts(z, eps=POLYNORM_EPS):
    """``[(N(z^3), r_3), (N(z^2), r_2), (N(z), r_1)]`` of ``z [rows,
    width]`` float32: ``N(a) = a r``, ``r = (mean over the WIDTH of
    a^2 + eps)^-1/2``."""
    z2 = z * z
    parts = []
    for power in (z2 * z, z2, z):
        r = jax.lax.rsqrt(
            jnp.mean(power * power, axis=-1, keepdims=True) + eps
        )
        parts.append((power * r, r))
    return parts


def poly_norm(z, coeffs, eps=POLYNORM_EPS):
    """PolyNorm (arXiv:2411.03884) of ``z [rows, width]`` float32:
    ``c_3 N(z^3) + c_2 N(z^2) + c_1 N(z) + bias`` for ``coeffs = (c_3,
    c_2, c_1, bias)``, four scalars (the caller's: a learned weight
    times its output scale, a clamped bias).  Each norm is over a
    row's WHOLE width.  A row of zeros gives ``bias``."""
    return _poly_value(_poly_parts(z, eps), coeffs)


def _poly_value(parts, coeffs):
    (n3, _), (n2, _), (n1, _) = parts
    c3, c2, c1, bias = coeffs
    return c3 * n3 + c2 * n2 + c1 * n1 + bias


def _tile_sum(x):
    return jnp.sum(
        jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True
    )


def _d_poly(d_hidden, kept, coeffs):
    """``[d gate, d up]`` from the hidden rows' gradient and the kept
    ``[gate, up]``, then the four sums over this tile's rows that are
    the coefficients' gradients: ``sum(g N(z^3)), sum(g N(z^2)),
    sum(g N(z)), sum(g)``, ``g = d hidden x up`` the gradient to
    ``poly_norm``'s value.  Through a norm: ``d/dz sum(g N_i) = i
    z^(i-1) r_i (g - N_i mean(g N_i))``."""
    (d_hidden,) = d_hidden
    gate, up = kept
    c3, c2, c1, _ = coeffs
    parts = _poly_parts(gate)
    (n3, r3), (n2, r2), (n1, r1) = parts
    g = d_hidden * up

    def through(n, r):
        return r * (g - n * jnp.mean(g * n, axis=-1, keepdims=True))

    d_gate = (
        c3 * 3.0 * gate * gate * through(n3, r3)
        + c2 * 2.0 * gate * through(n2, r2) + c1 * through(n1, r1)
    )
    d_up = d_hidden * _poly_value(parts, coeffs)
    return [d_gate, d_up] + [_tile_sum(g * n) for n in (n3, n2, n1)] + [
        _tile_sum(g)
    ]


def _up(tiles, tile_group, tiles_used, rows, *weights, keep, coeffs=None):
    """``[hidden, *a gate's two products with keep]`` of ``rows``
    through ``weights = (gate, up) | (up,)``.  The blocks of one grid
    step are together as wide as one product's: at hidden 4096 x
    width 2048 two double-buffered blocks of 16 MB would leave VMEM no
    room beside them.  With ``coeffs`` the activation is
    :func:`poly_norm`, which needs a row's whole width: the two blocks
    are as wide as the products, and a width past one tile is
    refused."""
    row_tile, k_tile, n_tile = tiles
    poly = coeffs is not None

    def finish(pre, _, *c):
        hidden = poly_norm(pre[0], *c) * pre[1] if poly else _hidden(pre)
        return [hidden] + (pre if keep else [])

    return _gmm(
        [rows], weights, tile_group, tiles_used, name="gmm_up_fwd",
        tiles=tiles if poly else (row_tile, k_tile, n_tile // len(weights)),
        outs=3 if keep else 1, finish=finish, whole_n=poly, coeffs=coeffs,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _grouped_expert(
    rows, w_gate, w_up, w_down, coeffs, tile_group, tiles_used, tiles
):
    # (a program that asks for no gradient: nothing is kept)
    weights = [w_up] if w_gate is None else [w_gate, w_up]
    (hidden,) = _up(
        tiles, tile_group, tiles_used, rows, *weights, keep=False,
        coeffs=coeffs,
    )
    return _grouped_matmul(hidden, w_down, tile_group, tiles_used, tiles)


def _expert_fwd(
    rows, w_gate, w_up, w_down, coeffs, tile_group, tiles_used, tiles
):
    weights = [w_up] if w_gate is None else [w_gate, w_up]
    # kept: what the derivative reads.  A gate's two products, or
    # without a gate the hidden rows themselves
    hidden, *kept = _up(
        tiles, tile_group, tiles_used, rows, *weights,
        keep=w_gate is not None, coeffs=coeffs,
    )
    out = _grouped_matmul(hidden, w_down, tile_group, tiles_used, tiles)
    return out, (
        rows, weights, w_down, hidden, kept or [hidden], coeffs,
        tile_group, tiles_used,
    )


def _expert_bwd(tiles, residuals, d_out):
    (
        rows, weights, w_down, hidden, kept, coeffs, tile_group, tiles_used,
    ) = residuals
    d_out = d_out.astype(rows.dtype)
    gated = len(weights) > 1

    def d_weights(lhs, cotangent, like):
        return _tgmm(
            lhs, cotangent, tile_group, tiles_used, groups=like.shape[0],
            tiles=tiles,
        ).astype(like.dtype)

    # the hidden rows' gradient never leaves its kernel: the
    # derivative is its epilogue.  A gate's two gradients are written
    # over its two products, which nothing else reads (a step of the
    # backward holds two arrays of the padded rows fewer); the hidden
    # rows are still the down matrix's gradient's to read
    poly = coeffs is not None
    d_pre = _gmm(
        [d_out], [w_down], tile_group, tiles_used, name="gmm_down_dlhs",
        transpose_rhs=True, tiles=tiles, beside=kept, outs=len(weights),
        over=2 if gated else 0, finish=_d_poly if poly else _d_activation,
        whole_n=poly, coeffs=coeffs,
        tile_sums=coeffs.shape[0] if poly else 0,
    )
    d_coeffs = None
    if poly:
        # poly_norm's derivative needs the row means again, and the
        # coefficients' gradients are sums over EVERY row of the
        # layer: a tile hands back its own, and the tiles of a group
        # (the tiles of no group are not written) add up here
        *d_pre, sums = d_pre
        used = jnp.arange(sums.shape[0]) < tiles_used[0]
        d_coeffs = jnp.sum(jnp.where(
            used[:, None], sums[:, 0, :coeffs.shape[0]], 0.0
        ), axis=0)
    # ONE gradient to the rows: both products in one accumulator
    (d_rows,) = _gmm(
        d_pre, weights, tile_group, tiles_used,
        name="gmm_up_dlhs" if gated else "gmm_dlhs",
        transpose_rhs=True, tiles=tiles,
    )
    return (
        d_rows,
        # (no gate matrix, no gradient to one)
        *([None] * (2 - len(weights))),
        *(d_weights(rows, d, w) for d, w in zip(d_pre, weights)),
        d_weights(hidden, d_out, w_down),
        d_coeffs, None, None,
    )


_grouped_expert.defvjp(_expert_fwd, _expert_bwd)


def grouped_expert(
    rows: jax.Array,         # [tiles * row_tile, k], tile-aligned groups
    w_gate,                  # [groups, k, n], or None: no gate
    w_up: jax.Array,         # [groups, k, n]
    w_down: jax.Array,       # [groups, n, k]
    tile_group: jax.Array,   # [tiles] int32   } of group_layout
    tiles_used: jax.Array,   # [1] int32       }
    tiles=(ROW_TILE, K_TILE, N_TILE),
    coeffs=None,             # [4] float32: poly_norm's, for silu
) -> jax.Array:
    """Each row through its group's expert -> ``[rows, k]``: ``(silu(x
    @ w_gate[g]) * (x @ w_up[g])) @ w_down[g]`` or, with
    ``w_gate=None``, ``relu(x @ w_up[g]) ** 2 @ w_down[g]``, ``g`` a
    row tile's group; differentiable in the rows and every matrix.
    The activation is taken from the products' float32 accumulators
    inside the kernel that makes them (``gmm_up_fwd``), its
    derivative inside the kernel that makes the hidden rows' gradient
    (``gmm_down_dlhs``: from a gate's two products as the forward
    rule kept them, in the rows' type; without a gate from the
    hidden rows, whose root is ``relu(up)``), and the rows get ONE
    gradient (``gmm_up_dlhs``);
    the down projection and the matrices' gradients are
    :func:`grouped_matmul`'s kernels.  As there: a
    group's padding rows are zero in and zero out (``silu(0) * 0 =
    relu(0) ** 2 = 0``), and the rows of the tiles from
    ``tiles_used`` on are NOT READ and NOT WRITTEN, in the result, in
    what the forward keeps and in every gradient.

    With ``coeffs`` (differentiable) the gate's activation is
    :func:`poly_norm` in silu's place, the third form and the first
    that is not element-wise: each norm is over the expert's whole
    width, so ``n`` is ONE column block of ``gmm_up_fwd`` and
    ``gmm_down_dlhs`` (a wider expert is refused), the derivative
    takes the row means again, and the coefficients' gradients, sums
    over every row of the layer, leave ``gmm_down_dlhs`` a tile at a
    time.  A padding row is still zero out: ``poly_norm(0) * 0``."""
    if coeffs is not None and w_gate is None:
        raise ValueError("poly_norm is a gate's activation: no gate")
    return _grouped_expert(
        rows, w_gate, w_up, w_down, coeffs, tile_group, tiles_used, tiles
    )


# -- the rows back to their tokens --------------------------------------------


# rows of one tile are accumulated this many at a time: within a tile
# the tokens are distinct, so the reads of a batch may all come before
# its writes
_ROW_BATCH = 4


def _tokens_from_rows_kernel(
    tiles_used, token_of_row, *refs,    # scalar prefetch, then blocks
    tokens: int, tiles: int, weighted: bool,
):
    if weighted:
        weight_ref, rows_ref, out_ref, acc_ref, part_ref = refs
    else:
        rows_ref, out_ref, acc_ref, part_ref = refs
    tile = pl.program_id(1)
    first_row = tile * rows_ref.shape[0]

    @pl.when(tile == 0)
    def _clear():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile < tiles_used[0])
    def _accumulate():
        part_ref[...] = rows_ref[...].astype(jnp.float32)

        def batch(b, carry):
            at = [b * _ROW_BATCH + u for u in range(_ROW_BATCH)]
            # a row of padding names a token past the last one: it
            # lands in the spare row behind them, which nobody reads
            to = [
                jnp.minimum(token_of_row[first_row + r], tokens)
                for r in at
            ]
            parts = [part_ref[pl.ds(r, 1), :] for r in at]
            if weighted:
                parts = [
                    part * weight_ref[first_row + r]
                    for part, r in zip(parts, at)
                ]
            sums = [
                acc_ref[pl.ds(row, 1), :] + part
                for row, part in zip(to, parts)
            ]
            for row, total in zip(to, sums):
                acc_ref[pl.ds(row, 1), :] = total
            return carry

        jax.lax.fori_loop(0, rows_ref.shape[0] // _ROW_BATCH, batch, 0)

    @pl.when(tile == tiles - 1)
    def _store():
        out_ref[...] = acc_ref[pl.ds(0, tokens), :].astype(out_ref.dtype)


def tokens_from_rows(
    rows: jax.Array,          # [tiles * row_tile, d], tile-aligned groups
    token_of_row: jax.Array,  # [tiles * row_tile] int32
    tiles_used: jax.Array,    # [1] int32, of group_layout
    tokens: int,
    weight: jax.Array = None,  # [tiles * row_tile] float32
) -> jax.Array:
    """``out[token_of_row[p]] += weight[p] * rows[p]`` over the rows of
    the tiles before ``tiles_used``, accumulated in float32 and cast
    once to the rows' type: ``[tokens, d]``.  A row whose token is
    ``tokens`` or more (a group's padding) is dropped; the rows of the
    tiles from ``tiles_used`` on are NOT READ.  Within one tile the
    tokens must be distinct.  A ``[tokens, column block]`` float32
    accumulator stays in VMEM while the used tiles go by; each row is
    one read-add-write of it (XLA's scatter-add takes 2.8 us a row
    of 4096 on a v5e, this 0.07: PERF.md, PR 38)."""
    m, d = rows.shape
    if m % ROW_TILE:
        raise ValueError(f"{m} rows do not divide into tiles of {ROW_TILE}")
    tiles = m // ROW_TILE
    # the widest column block whose accumulator and double-buffered
    # result fit beside the row tiles: the width divided by the least
    # factor of its lane tiles while it does not (4096 and 3072 go in
    # halves, 2688 = 21 lane tiles in thirds of 896 and, where
    # ``init_params`` traces two rows of 8192, on in sevenths)
    tn = d
    while (tokens + 8) * tn * (4 + 2 * rows.dtype.itemsize) > (72 << 20):
        lanes, rest = divmod(tn, 128)
        part = next(
            (f for f in (2, 3, 5, 7) if not rest and lanes % f == 0), None
        )
        if part is None:
            raise ValueError(
                f"no column block of {d} keeps {tokens} tokens in VMEM"
            )
        tn //= part
    weighted = weight is not None
    return pl.pallas_call(
        functools.partial(
            _tokens_from_rows_kernel, tokens=tokens, tiles=tiles,
            weighted=weighted,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 if weighted else 2,
            grid=(d // tn, tiles),
            in_specs=[pl.BlockSpec(
                (ROW_TILE, tn), lambda j, i, nu, *_: (_held_tile(i, nu), j)
            )],
            out_specs=pl.BlockSpec(
                (tokens, tn), lambda j, i, *_: (0, j)
            ),
            scratch_shapes=[
                pltpu.VMEM((tokens + 8, tn), jnp.float32),
                pltpu.VMEM((ROW_TILE, tn), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        compiler_params=_params(
            ("parallel", "arbitrary"),
            _nbytes((ROW_TILE, tn), rows.dtype),
            _nbytes((tokens, tn), rows.dtype),
            _nbytes((tokens + 8, tn), jnp.float32) // 2,
            _nbytes((ROW_TILE, tn), jnp.float32) // 2,
        ),
        interpret=_interpret(),
        name="gmm_tokens_from_rows",
    )(
        tiles_used, token_of_row,
        *((weight,) if weighted else ()), rows,
    )
