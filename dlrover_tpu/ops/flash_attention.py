"""Pallas flash attention (forward + backward) for TPU.

The reference binds a prebuilt CUDA FMHA library
(``tfplus/tfplus/flash_attn/kernels/flash_attention_fwd_kernel.cc:29``,
ATorch's module swaps in ``atorch/modules/transformer/layers.py``);
the TPU rebuild implements the kernel itself in Pallas: online-softmax
tiling so the [seq, seq] score matrix never materializes in HBM, MXU
matmuls in bf16 with fp32 accumulators.

Layout: q, k, v are [batch, seq, heads, head_dim] (the model's bqhd).
Internally folded to [batch*heads, seq, head_dim].  q and k share one
head size, ``d_qk``; v (and so the output, dO and dv) may have another,
``d_v`` (latent attention: 192 | 128).  Scores, dq and dk are ``d_qk``
wide, the accumulator ``d_v``; nothing is padded to the larger.

The walk.  Forward and dq: the grid is (batch*heads, q tiles, kv-major
blocks).  A grid step holds one q tile of ``block_q`` rows and the
head's K and V, whole while they fit ``_RESIDENT_BYTES`` (then the
last grid axis is 1 and K / V are fetched once a head); inside, a
``fori_loop`` walks kv sub-blocks of ``block_k`` rows.  With ``causal``
the loop stops at the last sub-block that touches the q tile's rows
(``_kv_walk``); sub-blocks wholly below the diagonal take the body
without the mask, only the ones the diagonal crosses are masked.  dkv
is the mirror image: the grid is (batch*heads, kv tiles of ``block_k``
rows, q-major blocks), Q / dO / lse / delta are resident, the loop
walks q sub-blocks of ``block_q`` rows and STARTS at the first one that
reaches the kv tile (``_q_walk``); it works on the transposed
sub-block, where lse and delta are rows.  Past the residency budget
the resident extent becomes a major block on the grid with the same
loop inside it; its index map is clamped at the diagonal, so a major
block the walk skips is not fetched.

Inside a sub-block.  Where ``block_q == block_k`` (the default) the
one sub-block the diagonal crosses is the tile's own, so its shape is
known when the kernel is traced: it is taken as a triangle of
``_CHUNK``-column passes, each over only the rows that see those
columns, the mask on one square chunk a pass and nothing above the
diagonal computed (10 of 16 chunk pairs at 1024 x 1024).  Other
sub-blocks are one pass (or a few, ``_PASS_SCORES``).

A window.  With ``window`` a query sees the ``window`` keys up to its
own, so a tile's walk has a second end and a second masked edge.  Such
a call takes square tiles, and then everything about a sub-block is
known when the kernel is traced from how many tiles it lies away from
the grid step's own: the last grid axis runs over those distances (the
own tile and the ``ceil((window - 1) / block)`` before it; after it
in dkv), the walked operands come one tile a grid step (the index map
clamped at the sequence's ends, so a tile the walk skips is not
fetched), and a sub-block is taken as passes of ``_CHUNK`` columns,
each over only the rows the BAND gives those columns, the masks on
the chunk-square pieces an edge crosses (``_window_passes``; at a
window of 512 and tiles of 1024, 1.5 x the scores the window
requires).  ``m``, ``l`` and
the accumulator live in VMEM scratch, the statistics lane-dense
(``[block_q, 128]``); lse and delta cross between that form and the
``[1, seq]`` rows they travel as by 128 x 128 transposes, once a grid
step.  ``block_schedule`` counts the walk.

A sink.  With ``sink`` (a learned float32 scalar a head) every row's
softmax has one more column whose value is dropped: the forward adds
``exp(sink - m)`` to ``l`` where it closes the statistics (and takes
``max(m, sink)``), so ``lse`` holds the sink, and nothing in the walk
changes.  The backward kernels do not change at all: ``p = exp(s -
lse)`` is already the sinked probability and ``delta = rowsum(dO out)``
already ``sum_j p_j dP_j``; the sink's gradient is ``- sum_i exp(sink -
lse_i) delta_i``, formed in XLA under the device scope ``attn_sink``.
``sink=None`` is the program this file always traced to.

What a rematted caller keeps.  The backward kernels read the five
arrays the forward rule hands them: ``q``, ``k``, ``v`` as folded,
``out`` and ``lse``.  The rule names all five (``RESIDUAL_NAMES``),
and a ``jax.checkpoint`` whose policy saves those names
(``models/layers.py::remat_policy``) runs in its backward neither the
forward kernel again nor anything that stands before it only to feed
it.

On CPU (tests / virtual mesh) the kernel runs in interpreter mode.
"""

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.telemetry.tracing import device_scope

NEG_INF = -1e30
_LANES = 128
# the five arrays the backward kernels read, under the names a remat
# policy keeps them by, in the order of the forward rule's residuals:
# the operands as the kernels take them (``q`` [b h, s, d_qk], ``k``
# [b kv_heads, s, d_qk], ``v`` [b kv_heads, s, d_v]: a grouped call
# keeps no repeated array) and the forward kernel's two results
# (``out`` [b h, s, d_v], ``lse`` [b h, 1, s] float32)
RESIDUAL_NAMES = (
    "flash_q", "flash_k", "flash_v", "flash_out", "flash_lse"
)
# the device scope of what a sink costs outside the kernels (its
# gradient's reduction here, a caller's counter)
SINK_SCOPE = "attn_sink"
# What a grid step may keep of the operands its loop walks (K and V in
# forward and dq, Q and dO in dkv: one of each pair is d_qk wide, the
# other d_v), as the pipeline holds them: two buffers x rows x (d_qk +
# d_v) x itemsize.  4 MB is the whole sequence at 4096 x 128 in bf16
# (8 x seq x d bytes), 0.5 MB at 1024 x 64; at 8192 x (192 | 128) it
# is a quarter of the sequence.  Beside it a step holds its own tile
# and its output (double-buffered, 1 MB at 1024 x 128), dkv's lse and
# delta rows (an
# eighth more) and a pass's float32 score temporaries (``_PASS_SCORES``
# below): what the v5e's compiler allows a kernel, as
# ``tests/test_tpu_compile.py`` checks for both cells' shapes and one
# past the budget.
_RESIDENT_BYTES = 4 * 2**20


def resident_rows(
    seq: int, sub_block: int, head_dim: int, itemsize: int,
    v_head_dim: int | None = None, window: int | None = None,
) -> int:
    """Rows of the walked operands one grid step holds: the largest
    divisor of ``seq`` that is a whole number of loop sub-blocks and
    fits ``_RESIDENT_BYTES``; one sub-block if none does.  The walked
    pair is one operand of ``head_dim`` (= ``d_qk``: K, or Q in dkv)
    and one of ``v_head_dim`` (``d_v``: V, or dO; left out, the
    same), so the count takes their SUM.  A windowed walk needs
    ``sub_block + window`` rows at most and takes them one sub-block
    a grid step."""
    if window is not None:
        return sub_block
    if v_head_dim is None:
        v_head_dim = head_dim
    for parts in range(1, seq // sub_block + 1):
        rows, rest = divmod(seq, parts)
        if rest or rows % sub_block:
            continue
        if 2 * rows * (head_dim + v_head_dim) * itemsize <= (
            _RESIDENT_BYTES
        ):
            return rows
    return sub_block


# Columns of the score sub-block that one pass of the loop body takes
# where the sub-block the diagonal crosses is the tile's own (block_q
# == block_k): a static loop over chunks of columns, each with only the
# rows that see it, so the triangle above the diagonal is not computed
# and the mask falls on one square chunk a pass.  256: at 128 the
# forward ran 18% slower (more passes than the scores they save), at
# 512 the backward 8% slower (PERF.md, PR 29).
_CHUNK = 256
# Scores one pass may hold as float32 temporaries ([rows, columns];
# dkv holds four such, forward and dq three): a sub-block larger than
# this is taken in passes of fewer columns.
_PASS_SCORES = 1024 * 1024
_PASS_SCORES_DKV = 512 * 1024


def _chunk(block: int) -> int:
    return _CHUNK if block % _CHUNK == 0 else block


def _passes(rows: int, cols: int, triangle: bool, scores: int):
    """``(first column, columns, first row)`` of each pass over a
    ``[rows, cols]`` score sub-block whose rows belong to the grid
    step's own tile.  A triangle (rows == cols, the diagonal running
    through it) goes by chunks, each from its own first row down."""
    if triangle:
        width = _chunk(cols)
        return [(c, width, c) for c in range(0, cols, width)]
    width = cols
    while rows * width > scores and width % 2 == 0 and width > _LANES:
        width //= 2
    return [(c, width, 0) for c in range(0, cols, width)]


def _kv_walk(q_start, block_q: int, block_k: int):
    """Causal walk of the q tile that starts at row ``q_start``: kv
    sub-blocks ``[0, full)`` lie wholly below the diagonal, ``[full,
    end)`` are crossed by it, the rest is never visited."""
    full = (q_start + 1) // block_k
    end = (q_start + block_q - 1) // block_k + 1
    return full, end


def _q_walk(k_start, block_q: int, block_k: int):
    """The mirror image for the kv tile that starts at ``k_start``: q
    sub-blocks ``[start, full)`` are crossed by the diagonal, ``[full,
    seq // block_q)`` lie wholly below it, ``[0, start)`` are never
    visited."""
    start = k_start // block_q
    full = (k_start + block_k + block_q - 2) // block_q
    return start, full


def _tiles_back(block: int, window: int) -> int:
    """Tiles before its own that a q tile's window reaches (after its
    own, the q tiles that reach a kv tile)."""
    return -(-(window - 1) // block)


def _band_edges(nq: int, nk: int, off: int, window: int):
    """``(causal, trailing)``: which edges of the band cross a piece
    of ``nq`` queries by ``nk`` keys whose first key lies ``off``
    positions after its first query (query ``a`` sees key ``b`` where
    ``0 <= (a - b) - off < window``)."""
    return off > -(nk - 1), nq - 1 >= off + window


def _window_passes(
    block: int, window: int, away: int, kv_side: bool, scores: int
):
    """``(first column, columns, first row, end row, shift)`` of each
    pass over the ``[block, block]`` sub-block that lies ``away``
    tiles from the grid step's own (before it for a q tile, after it
    for a kv tile, whose rows are kv positions and whose columns are q
    positions: ``kv_side``).  A chunk of columns meets only the rows
    the band gives it; ``shift`` is the chunk's first position less
    the tile's, None where no edge crosses the pass (a sub-block
    wholly inside the band goes as :func:`_passes` takes it)."""
    c = _chunk(block)
    out, whole = [], True
    for col in range(0, block, c):
        shift = away * block + col
        if kv_side:
            first = max(0, (shift - window + 1) // c * c)
            end = min(block, shift + c)
        else:
            first = max(0, shift)
            end = min(block, -(-(shift + c + window - 1) // c) * c)
        if end <= first:
            whole = False
            continue
        crossed = any(
            any(_band_edges(
                c, c, row - shift if kv_side else shift - row, window
            )) for row in range(first, end, c)
        )
        whole = whole and not crossed and (first, end) == (0, block)
        out.append((col, c, first, end, shift if crossed else None))
    if whole:
        return [
            (col, width, 0, block, None)
            for col, width, _ in _passes(block, block, False, scores)
        ]
    return out


def _window_walk(tile, major, block, window, kv_side, scores, body):
    """``body(*pass)`` over the passes of the sub-block this grid step
    holds: step ``major`` of the last grid axis is the sub-block
    ``major - back`` tiles from q tile ``tile`` (``+ major`` from kv
    tile ``tile``), skipped where that lies outside the sequence."""
    back = _tiles_back(block, window)
    by_passes = {}
    for step in range(back + 1):
        passes = tuple(_window_passes(
            block, window, step if kv_side else step - back, kv_side,
            scores,
        ))
        by_passes.setdefault(passes, []).append(step)
    for passes, steps in by_passes.items():
        here = functools.reduce(
            jnp.logical_or, [major == step for step in steps]
        )
        inside = (
            tile + major < pl.num_programs(1) if kv_side
            else tile + major >= back
        )

        def run(passes=passes):
            for one in passes:
                body(*one)

        pl.when(jnp.logical_and(here, inside))(run)


def block_schedule(
    seq: int, block_q: int, block_k: int, causal: bool = True,
    window: int | None = None,
) -> dict:
    """What one head's walk costs: how many ``[block_q, block_k]``
    sub-blocks the kernels visit (a count of score tiles: neither
    ``d_qk`` nor ``d_v`` enters it), how many of those take the masked
    body, the square's total, and ``computed``, the share of the
    square's scores that are computed at all (a masked sub-block that
    is the tile's own is walked as a triangle of chunks).  The three
    kernels walk the same set (dkv by kv tile, the other two by q
    tile); their loop bounds are ``_kv_walk`` / ``_q_walk``, summed
    here.  With a ``window`` (square tiles) the walk is
    ``_window_walk``'s: a q tile visits its own tile and those its
    window reaches, a sub-block is masked where an edge of the band
    crosses it, and ``computed`` counts the passes' rows by
    columns."""
    total = (seq // block_q) * (seq // block_k)
    if window is not None and window < seq:
        if not causal or block_q != block_k:
            raise ValueError("a window takes causal, square tiles")
        visited = masked = scores = 0
        for tile in range(seq // block_q):
            for away in range(-min(tile, _tiles_back(block_q, window)), 1):
                passes = _window_passes(
                    block_q, window, away, False, _PASS_SCORES
                )
                visited += 1
                masked += any(p[4] is not None for p in passes)
                scores += sum(
                    width * (end - first)
                    for _, width, first, end, _ in passes
                )
        return {
            "visited": visited, "masked": masked, "total": total,
            "computed": scores / seq**2,
        }
    if not causal:
        return {
            "visited": total, "masked": 0, "total": total,
            "computed": 1.0,
        }
    visited = masked = 0
    for q_start in range(0, seq, block_q):
        full, end = _kv_walk(q_start, block_q, block_k)
        visited += end
        masked += end - full
    chunks = block_q // _chunk(block_q) if block_q == block_k else 1
    triangle = (chunks + 1) / (2 * chunks)
    return {
        "visited": visited, "masked": masked, "total": total,
        "computed": (visited - masked + masked * triangle) / total,
    }


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


def _scale_is_exact(scale: float) -> bool:
    """A power of two (head 64: 0.125) multiplies a bf16 tile without
    rounding, so it goes onto the resident tile once a grid step; any
    other scale stays a float32 multiply of the scores."""
    return math.frexp(scale)[0] == 0.5


def _lanes(x, n: int):
    """Lane-dense ``[rows, 128]`` statistics (every lane the same) as
    ``[rows, n]``."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _rows_to_lanes(x):
    """Lane-dense ``[rows, 128]`` statistics as the ``[1, rows]`` row
    that lse and delta travel as.  By 128 x 128 transposes: a lane
    reduction a row group (what ``jnp.max(x, axis=1)`` lowers to) took
    0.13 of the forward's 0.44 ms a call at 100 x 1024 x 64 (my chip
    run, PR 29)."""
    rows = x.shape[0]
    if rows % _LANES:
        return jnp.max(x, axis=1)[None, :]
    blocks = [x[i:i + _LANES].T[:1] for i in range(0, rows, _LANES)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(
        blocks, axis=1
    )


def _lanes_to_rows(ref):
    """The way back: a ``[1, 1, rows]`` block of lse or delta as a
    ``[rows, 1]`` column."""
    rows = ref.shape[2]
    if rows % _LANES:
        return ref[0, 0][:, None]
    blocks = [
        jnp.broadcast_to(ref[0, :, i:i + _LANES], (_LANES, _LANES)).T
        for i in range(0, rows, _LANES)
    ]
    column = blocks[0] if len(blocks) == 1 else jnp.concatenate(
        blocks, axis=0
    )
    return column[:, :1]


def _nt(a, b):
    """``a @ b.T`` with a float32 result."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _nn(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _mask(s, q_axis: int, offset):
    """Scores of a sub-block the diagonal crosses; ``offset`` is its
    first kv position less its first q position."""
    q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos - k_pos >= offset, s, NEG_INF)


def _mask_corner(s, q_axis: int, first: int):
    """One pass over the triangle: the square chunk the diagonal
    crosses (the sub-block's columns by as many of its rows, from row
    ``first``) is masked, what lies below or above it is whole."""
    size = s.shape[1]
    parts = [
        s[:first], _mask(s[first:first + size], q_axis, 0),
        s[first + size:],
    ]
    parts = [part for part in parts if part.shape[0]]
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts, axis=0)


def _band_rows(s, q_axis: int, first: int, shift: int, window: int):
    """One pass of a windowed walk: scores of the tile's rows from
    ``first`` against a chunk of columns that starts ``shift``
    positions from the tile's start.  The chunk-square pieces an edge
    of the band crosses are masked, what lies between them is whole
    (all of it where ``shift`` is None)."""
    if shift is None:
        return s
    size = s.shape[1]
    parts, whole_from = [], None
    for top in range(0, s.shape[0], size):
        off = shift - first - top if q_axis == 0 else first + top - shift
        causal, trailing = _band_edges(size, size, off, window)
        if not (causal or trailing):
            whole_from = top if whole_from is None else whole_from
            continue
        if whole_from is not None:
            parts.append(s[whole_from:top])
            whole_from = None
        part = s[top:top + size]
        ahead = jax.lax.broadcasted_iota(
            jnp.int32, part.shape, q_axis
        ) - jax.lax.broadcasted_iota(jnp.int32, part.shape, 1 - q_axis)
        if causal and trailing:
            keep = (ahead >= off) & (ahead < off + window)
        elif causal:
            keep = ahead >= off
        else:
            keep = ahead < off + window
        parts.append(jnp.where(keep, part, NEG_INF))
    if whole_from is not None:
        parts.append(s[whole_from:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _clip(lo, x, hi):
    if all(isinstance(n, int) for n in (lo, x, hi)):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _walk(step, lo, hi, plain, masked=None):
    """``step(index, masked)`` over the sub-blocks of ``[lo, hi)`` (what
    this grid step holds) that the walk visits: without the mask over
    ``plain``, with it over ``masked``, both ``(first, end)``."""
    def loop(bounds, with_mask):
        jax.lax.fori_loop(
            _clip(lo, bounds[0], hi), _clip(lo, bounds[1], hi),
            lambda i, _: step(i, with_mask), None,
        )

    loop(plain, False)
    if masked is not None:
        loop(masked, True)


def _bracket(major, num_major: int, init, walk, final):
    """One grid step of a walk whose last grid axis has ``num_major``
    steps: the accumulators start on its first and leave on its last
    (both at once, with no condition, when the operands are resident)."""
    if num_major == 1:
        init()
        walk()
        final()
        return
    pl.when(major == 0)(init)
    walk()
    pl.when(major == num_major - 1)(final)


def _major(num_major: int, window):
    """This grid step's place on the last grid axis: static where the
    axis has one step and its place picks no branch."""
    if num_major == 1 and window is None:
        return 0
    return pl.program_id(2)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref,      # [1, block_q, d_qk], [1, major rows, d_qk | d_v]
    o_ref,                    # [1, block_q, d_v]
    lse_ref,                  # [1, 1, block_q]
    m_scr, l_scr, acc_scr,    # [block_q, 128] x2, [block_q, d_v]
    *, scale: float, block_q: int, block_k: int, causal: bool,
    num_major: int, window: int | None = None, sink_ref=None,
):
    q_start = pl.program_id(1) * block_q
    major = _major(num_major, window)
    subs = k_ref.shape[1] // block_k
    first = major * subs
    d = v_ref.shape[2]
    fold = _scale_is_exact(scale)

    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def walk():
        q = q_ref[0]
        if fold:
            q = q * scale

        def one_pass(mine, col, width, mask):
            # ``col()`` once an operand: the causal walk's start is
            # an addition on the device, and its trace keeps the two
            # it always had
            k = k_ref[0, pl.ds(col(), width), :]
            v = v_ref[0, pl.ds(col(), width), :]
            s = _nt(q[mine], k)
            if not fold:
                s = s * scale
            s = mask(s)
            m_prev = m_scr[mine, :]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=1, keepdims=True)
            )
            p = jnp.exp(s - _lanes(m_new, width))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[mine, :] = alpha * l_scr[mine, :] + jnp.sum(
                p, axis=1, keepdims=True
            )
            acc_scr[mine, :] = acc_scr[mine, :] * _lanes(
                alpha, d
            ) + _nn(p.astype(v.dtype), v)
            m_scr[mine, :] = m_new

        def step(j, masked):
            base = pl.multiple_of((j - first) * block_k, block_k)
            triangle = masked and block_q == block_k
            for col, width, top in _passes(
                block_q, block_k, triangle, _PASS_SCORES
            ):
                def mask(s, col=col):
                    if triangle:
                        return _mask_corner(s, 0, 0)
                    if masked:
                        return _mask(s, 0, j * block_k + col - q_start)
                    return s

                one_pass(
                    slice(top, block_q), lambda col=col: base + col,
                    width, mask,
                )

        def band(col, width, top, end, shift):
            one_pass(
                slice(top, end), lambda: col, width,
                lambda s: _band_rows(s, 0, top, shift, window),
            )

        if window is not None:
            _window_walk(
                pl.program_id(1), major, block_q, window, False,
                _PASS_SCORES, band,
            )
        elif causal:
            full, end = _kv_walk(q_start, block_q, block_k)
            _walk(step, first, first + subs, (0, full), (full, end))
        else:
            _walk(step, first, first + subs, (0, num_major * subs))

    def final():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / _lanes(safe_l, d)).astype(o_ref.dtype)
        lse_ref[0] = _rows_to_lanes(m_scr[...] + jnp.log(safe_l))

    def final_with_sink():
        # the sink is one more column of the scores whose value is
        # dropped: it joins the maximum and the denominator where the
        # statistics are closed, and nothing before
        m, sink = m_scr[...], sink_ref[0]
        m_new = jnp.maximum(m, sink)
        alpha = jnp.exp(m - m_new)
        l = alpha * l_scr[...] + jnp.exp(sink - m_new)
        o_ref[0] = (
            acc_scr[...] * _lanes(alpha / l, d)
        ).astype(o_ref.dtype)
        lse_ref[0] = _rows_to_lanes(m_new + jnp.log(l))

    _bracket(
        major, num_major, init, walk,
        final if sink_ref is None else final_with_sink,
    )


def _fwd_kernel_with_sink(
    q_ref, k_ref, v_ref, sink_ref, o_ref, lse_ref, m_scr, l_scr,
    acc_scr, **kw,
):
    """:func:`_fwd_kernel` with a fourth operand, the head's sink
    along the lanes ``[1, 1, 128]``."""
    _fwd_kernel(
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
        sink_ref=sink_ref, **kw,
    )


def _kv_walked(seq, block_q, block_k, d, d_v, itemsize, causal, group,
               window):
    """``(rows, num_major, index map)`` of the K and V blocks a grid
    step of the forward or of dq holds, on the grid (batch * heads, q
    tiles, major blocks)."""
    rows = resident_rows(seq, block_k, d, itemsize, d_v, window)
    if window is not None:
        back = _tiles_back(block_q, window)

        # step j is the tile j - back from the q tile's own; before
        # the sequence's start it maps to the first tile, which the
        # next step holds too, so nothing is fetched for it
        def kv_block(b, i, j):
            return (b // group, jnp.maximum(i + j - back, 0), 0)

        return rows, back + 1, kv_block
    num_major = seq // rows

    # GQA: k/v carry bh//group rows; `group` consecutive q heads read
    # the same kv row through the index map - the repeated kv tensor
    # never materializes in HBM.  Causal: a major block above the q
    # tile's diagonal maps to the last one below it, which is already
    # there, so nothing is fetched for it.
    def kv_block(b, i, j):
        if causal and num_major > 1:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // rows)
        return (b // group, j, 0)

    return rows, num_major, kv_block


def _fwd(
    q, k, v, scale: float, causal: bool, block_q: int, block_k: int,
    group: int = 1, window: int | None = None, sink=None,
):
    """``sink`` [bh] float32 (None: no sink) goes in lane-dense, one
    ``[1, 1, 128]`` block a head."""
    bh, seq, d = q.shape
    d_v = v.shape[2]
    rows, num_major, kv_block = _kv_walked(
        seq, block_q, block_k, d, d_v, k.dtype.itemsize, causal, group,
        window,
    )

    def q_block(b, i, j):
        return (b, i, 0)

    kernel, operands, sink_specs = _fwd_kernel, (q, k, v), []
    if sink is not None:
        kernel = _fwd_kernel_with_sink
        operands += (jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (bh, 1, _LANES)
        ),)
        sink_specs = [
            pl.BlockSpec((1, 1, _LANES), lambda b, i, j: (b, 0, 0))
        ]
    out, lse = pl.pallas_call(
        functools.partial(
            kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal, num_major=num_major,
            window=window,
        ),
        grid=(bh, seq // block_q, num_major),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_block),
            pl.BlockSpec((1, rows, d), kv_block),
            pl.BlockSpec((1, rows, d_v), kv_block),
            *sink_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), q_block),
            # lse carried as [bh, 1, seq]: (1, 1, block_q) blocks satisfy
            # the TPU (8, 128) tiling rule on the last two dims
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, _LANES), jnp.float32),
            _scratch((block_q, _LANES), jnp.float32),
            _scratch((block_q, d_v), jnp.float32),
        ],
        interpret=_interpret(),
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *, scale: float, block_q: int, block_k: int, causal: bool,
    num_major: int, window: int | None = None,
):
    q_start = pl.program_id(1) * block_q
    major = _major(num_major, window)
    subs = k_ref.shape[1] // block_k
    first = major * subs
    fold = _scale_is_exact(scale)

    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def walk():
        q = q_ref[0]
        if fold:
            q = q * scale
        do = do_ref[0].astype(jnp.float32)
        # the statistics arrive along the lanes; turned into columns
        # once a grid step, not once a sub-block
        lse = _lanes_to_rows(lse_ref)
        delta = _lanes_to_rows(delta_ref)

        def one_pass(mine, col, width, mask):
            k = k_ref[0, pl.ds(col(), width), :]
            v = v_ref[0, pl.ds(col(), width), :]
            s = _nt(q[mine], k)
            if not fold:
                s = s * scale
            s = mask(s)
            p = jnp.exp(s - lse[mine])
            dp = _nt(do[mine], v.astype(jnp.float32))
            ds = p * (dp - delta[mine])
            if not fold:
                ds = ds * scale
            dq_scr[mine, :] += _nn(ds.astype(k.dtype), k)

        def step(j, masked):
            base = pl.multiple_of((j - first) * block_k, block_k)
            triangle = masked and block_q == block_k
            for col, width, top in _passes(
                block_q, block_k, triangle, _PASS_SCORES
            ):
                def mask(s, col=col):
                    if triangle:
                        return _mask_corner(s, 0, 0)
                    if masked:
                        return _mask(s, 0, j * block_k + col - q_start)
                    return s

                one_pass(
                    slice(top, block_q), lambda col=col: base + col,
                    width, mask,
                )

        def band(col, width, top, end, shift):
            one_pass(
                slice(top, end), lambda: col, width,
                lambda s: _band_rows(s, 0, top, shift, window),
            )

        if window is not None:
            _window_walk(
                pl.program_id(1), major, block_q, window, False,
                _PASS_SCORES, band,
            )
        elif causal:
            full, end = _kv_walk(q_start, block_q, block_k)
            _walk(step, first, first + subs, (0, full), (full, end))
        else:
            _walk(step, first, first + subs, (0, num_major * subs))

    def final():
        dq = dq_scr[...]
        if fold:
            dq = dq * scale
        dq_ref[0] = dq.astype(dq_ref.dtype)

    _bracket(major, num_major, init, walk, final)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, block_q: int, block_k: int, causal: bool,
    num_major: int, window: int | None = None,
):
    """Works on the TRANSPOSED sub-block, ``[block_k, block_q]``: the
    statistics are rows there and broadcast down the sublanes, and
    both accumulating matmuls contract over its last axis."""
    k_start = pl.program_id(1) * block_k
    major = _major(num_major, window)
    subs = q_ref.shape[1] // block_q
    first = major * subs
    fold = _scale_is_exact(scale)

    def init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def walk():
        k = k_ref[0]
        if fold:
            k = k * scale
        v = v_ref[0].astype(jnp.float32)

        def one_pass(mine, cols, mask):
            q = q_ref[0, cols, :]
            do = do_ref[0, cols, :].astype(jnp.float32)
            s = _nt(k[mine], q)
            if not fold:
                s = s * scale
            s = mask(s)
            p = jnp.exp(s - lse_ref[0, :, cols])
            dv_scr[mine, :] += _nn(p, do)
            dp = _nt(v[mine], do)
            ds = p * (dp - delta_ref[0, :, cols])
            if not fold:
                ds = ds * scale
            dk_scr[mine, :] += _nn(ds, q.astype(jnp.float32))

        def step(i, masked):
            base = pl.multiple_of((i - first) * block_q, block_q)
            triangle = masked and block_q == block_k
            for col, width, _ in _passes(
                block_k, block_q, triangle, _PASS_SCORES_DKV
            ):
                def mask(s, col=col):
                    if triangle:
                        return _mask_corner(s, 1, col)
                    if masked:
                        return _mask(s, 1, k_start - i * block_q - col)
                    return s

                # here the tile's rows are kv positions: a chunk of q
                # columns is seen by the rows down to its own last one
                one_pass(
                    slice(0, col + width if triangle else block_k),
                    pl.ds(base + col, width), mask,
                )

        def band(col, width, top, end, shift):
            one_pass(
                slice(top, end), pl.ds(col, width),
                lambda s: _band_rows(s, 1, top, shift, window),
            )

        total = num_major * subs
        if window is not None:
            _window_walk(
                pl.program_id(1), major, block_k, window, True,
                _PASS_SCORES_DKV, band,
            )
        elif causal:
            start, full = _q_walk(k_start, block_q, block_k)
            _walk(step, first, first + subs, (full, total), (start, full))
        else:
            _walk(step, first, first + subs, (0, total))

    def final():
        dk = dk_scr[...]
        if fold:
            dk = dk * scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    _bracket(major, num_major, init, walk, final)


def _delta(out, dout):
    """sum(out * dout) over the head dim, [bh, 1, seq] like lse."""
    return jnp.sum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1
    )[:, None, :]


def _bwd_dq(
    q, k, v, dout, lse, delta, scale, causal, block_q, block_k, group,
    window=None,
):
    bh, seq, d = q.shape
    d_v = v.shape[2]
    rows, num_major, kv_block = _kv_walked(
        seq, block_q, block_k, d, d_v, k.dtype.itemsize, causal, group,
        window,
    )

    def q_block(b, i, j):
        return (b, i, 0)

    def stat_block(b, i, j):
        return (b, 0, i)

    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal, num_major=num_major,
            window=window,
        ),
        grid=(bh, seq // block_q, num_major),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_block),
            pl.BlockSpec((1, rows, d), kv_block),
            pl.BlockSpec((1, rows, d_v), kv_block),
            pl.BlockSpec((1, block_q, d_v), q_block),
            pl.BlockSpec((1, 1, block_q), stat_block),
            pl.BlockSpec((1, 1, block_q), stat_block),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_block),
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)


def _bwd_dkv(
    q, k, v, dout, lse, delta, scale, causal, block_q, block_k, group,
    window=None,
):
    bh, seq, d = q.shape
    d_v = v.shape[2]
    rows = resident_rows(seq, block_q, d, q.dtype.itemsize, d_v, window)
    num_major = seq // rows
    if window is not None:
        num_major = _tiles_back(block_k, window) + 1

    # causal: a q-major block above the kv tile maps to the first one
    # that reaches it, so the pipeline fetches that one early and
    # nothing for the ones the walk skips.  A window: step m is the q
    # tile m after the kv tile's own, past the sequence's end the last
    def major(j, m):
        if window is not None:
            return jnp.minimum(j + m, seq // block_q - 1)
        if causal and num_major > 1:
            m = jnp.maximum(m, (j * block_k) // rows)
        return m

    def q_block(b, j, m):
        return (b, major(j, m), 0)

    def stat_block(b, j, m):
        return (b, 0, major(j, m))

    def kv_block(b, j, m):
        return (b // group, j, 0)

    def out_block(b, j, m):
        return (b, j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal, num_major=num_major,
            window=window,
        ),
        grid=(bh, seq // block_k, num_major),
        in_specs=[
            pl.BlockSpec((1, rows, d), q_block),
            pl.BlockSpec((1, block_k, d), kv_block),
            pl.BlockSpec((1, block_k, d_v), kv_block),
            pl.BlockSpec((1, rows, d_v), q_block),
            pl.BlockSpec((1, 1, rows), stat_block),
            pl.BlockSpec((1, 1, rows), stat_block),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), out_block),
            pl.BlockSpec((1, block_k, d_v), out_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d_v), v.dtype),
        ],
        scratch_shapes=[
            _scratch((block_k, d), jnp.float32),
            _scratch((block_k, d_v), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)
    if group > 1:
        # per-q-head kv grads -> per-kv-head (rows sharing a kv head
        # are the `group` consecutive q heads)
        dk = dk.reshape(bh // group, group, seq, d).astype(
            jnp.float32
        ).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh // group, group, seq, d_v).astype(
            jnp.float32
        ).sum(axis=1).astype(v.dtype)
    return dk, dv


def _bwd(
    scale, causal, block_q, block_k, group, window, residuals, dout
):
    q, k, v, out, lse = residuals
    args = (
        q, k, v, dout, lse, _delta(out, dout), scale, causal,
        block_q, block_k, group, window,
    )
    return (_bwd_dq(*args), *_bwd_dkv(*args))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def _flash_mha(q, k, v, scale, causal, block_q, block_k, group=1,
               window=None):
    out, _ = _fwd(
        q, k, v, scale, causal, block_q, block_k, group, window
    )
    return out


def _named(x, name):
    """``x`` under ``name`` for a remat policy, named as its BITS: for
    an array that the forward goes on to read.  ``jax.checkpoint``
    puts a ``reduce_precision`` behind the producer of every
    floating-point residual it saves that the forward also USES,
    against excess precision in a fused producer; the TPU compiler
    keeps it as a pass of its own over the array, every layer, also in
    a program whose remat copy it merges with the forward
    (``prevent_cse=False``).  A kernel's result in HBM has no excess
    precision, and an integer residual gets no such pass.  The two
    bitcasts cancel where the compiler may merge, and the step is then
    the program it was without a name; behind a rematted block's
    barrier (``prevent_cse=True``) the forward's is a copy of ``out``
    (1.9 of 409 ms a step at Laguna's five layers, PERF.md, PR 44) and
    the backward's fuses into its consumers."""
    bits = jnp.dtype(f"uint{8 * x.dtype.itemsize}")
    return jax.lax.bitcast_convert_type(
        checkpoint_name(jax.lax.bitcast_convert_type(x, bits), name),
        x.dtype,
    )


def _flash_mha_fwd(q, k, v, scale, causal, block_q, block_k,
                   group=1, window=None, sink=None):
    # q, k and v are named on arrays only the residuals hold: no
    # ``reduce_precision``, so as the numbers they are (``_named``;
    # ``test_a_saved_residual_costs_no_pass_over_it`` holds jax to it)
    out, lse = _fwd(
        q, k, v, scale, causal, block_q, block_k, group, window, sink
    )
    q_name, k_name, v_name, out_name, lse_name = RESIDUAL_NAMES
    q, k, v = (
        checkpoint_name(x, name)
        for x, name in ((q, q_name), (k, k_name), (v, v_name))
    )
    # ``out`` goes on into the block, so it is named as bits (``lse``
    # as PR 44 left it), and the NAMED arrays are both the primal
    # output and the residuals
    out = _named(out, out_name)
    lse = _named(lse, lse_name)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(scale, causal, block_q, block_k, group, window,
                   residuals, dout):
    return _bwd(
        scale, causal, block_q, block_k, group, window, residuals, dout
    )


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9)
)
def _flash_mha_sink(q, k, v, sink, scale, causal, block_q, block_k,
                    group=1, window=None):
    """:func:`_flash_mha` with a learned sink a head (``sink`` [b h]
    float32): ``(out, lse)``, both with the sink in the softmax's
    denominator.  ``lse`` is handed out for counters only: its
    cotangent is not read."""
    return _fwd(
        q, k, v, scale, causal, block_q, block_k, group, window, sink
    )


def _flash_mha_sink_fwd(q, k, v, sink, scale, causal, block_q, block_k,
                        group=1, window=None):
    out, residuals = _flash_mha_fwd(
        q, k, v, scale, causal, block_q, block_k, group, window, sink
    )
    return (out, residuals[-1]), (*residuals, sink)


def _flash_mha_sink_bwd(scale, causal, block_q, block_k, group, window,
                        residuals, cotangents):
    """The three kernels as they are: ``lse`` holds the sink, so ``p =
    exp(s - lse)`` is the sinked probability and ``delta = rowsum(dO
    out) = sum_j p_j dP_j`` already (the sink's ``dP`` is 0).  The
    sink's own gradient is its column's ``ds = p (dP - delta)`` summed
    over the rows: ``- sum_i exp(sink - lse_i) delta_i``, in XLA from
    two ``[b h, s]`` rows."""
    *residuals, sink = residuals
    dout, _ = cotangents
    dq, dk, dv = _bwd(
        scale, causal, block_q, block_k, group, window, residuals, dout
    )
    with device_scope(SINK_SCOPE):
        out, lse = residuals[3:]
        dsink = -jnp.sum(
            jnp.exp(sink[:, None, None] - lse) * _delta(out, dout),
            axis=(1, 2),
        )
    return dq, dk, dv, dsink


_flash_mha_sink.defvjp(_flash_mha_sink_fwd, _flash_mha_sink_bwd)


def default_blocks(seq: int, itemsize: int):
    """(block_q, block_k) for a call that names none, from the call's
    own shape: one square tile of up to 1024 rows (512 for 4-byte
    operands, whose tiles are twice the bytes).  Square, so that the
    sub-block the diagonal crosses is the tile's own and is walked as
    a triangle; large, because what a tile costs beyond its scores
    (statistics, accumulators, the pipeline's fill) is paid once a
    tile: at 1024 x 64 and at 4096 x 128 alike the three kernels
    together ran 10% slower at 512 and 50-85% slower at 256 (PERF.md,
    PR 29), so neither head size (``d_qk``, ``d_v``) nor ``causal``
    moves the choice."""
    block = min(seq, 2048 // itemsize)
    return block, block


def _fit_block(s: int, requested: int) -> int:
    """Largest divisor of ``s`` that is <= requested — so a seq that
    is a multiple of 128 but not of the (large) default block still
    works, just with a smaller tile."""
    block = min(requested, s)
    while block > 1 and s % block:
        block //= 2
    if s % block:  # odd seq lens: fall back to the full sequence
        return s
    return block


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    dtype: Any = None,  # accepted for model-pluggability; output dtype
    window: int | None = None,
    sink: jax.Array | None = None,
    return_lse: bool = False,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    Drop-in for :func:`dlrover_tpu.ops.attention.xla_causal_attention`.
    Sequence length must be divisible by the block sizes (the caller
    pads; GPT training shapes are powers of two).

    ``q`` and ``k`` share their head size ``d_qk``; ``v`` may have
    another, ``d_v`` (latent attention: 192 | 128).  The output and
    ``dv`` are ``d_v`` wide, ``dq`` and ``dk`` ``d_qk``; the default
    ``scale`` is ``d_qk ** -0.5``.  With ``d_v == d_qk`` every shape
    and block choice is what a call with one head size always had.

    ``block_q`` is the q rows a grid step of the forward and of dq
    holds, ``block_k`` the kv rows one iteration of their loop takes
    (dkv holds ``block_k`` kv rows and walks ``block_q`` q rows at a
    time).  Left out, both come from the call's own shape
    (``default_blocks``); with ``causal`` the loop runs only to the
    diagonal, and equal blocks let the sub-block on it be walked as a
    triangle (the module docstring; ``block_schedule`` counts it).

    ``window`` (with ``causal``): query ``i`` sees keys ``(i - window,
    i]``.  The three kernels walk only the tiles the band touches and
    mask only the pieces an edge crosses (the module docstring).  A
    windowed call takes square tiles (``block_k`` is ``block_q``); a
    window that covers the sequence is the plain causal call.

    GQA: ``k``/``v`` may carry fewer heads than ``q`` (``kv_heads``
    dividing ``heads``, kv-head-major q layout as in the Llama
    family); the forward and dq kernels read each kv head once per
    group through their index maps, so the repeated kv tensor never
    materializes there.  The dkv backward still emits per-q-head
    gradients (a transient group-x temporary) before the group
    reduction.

    ``sink`` (``[heads]``, float32; with ``causal``): a learned score
    a head that joins every row's softmax as one more column whose
    value is dropped: ``p_ij = exp(s_ij - m_i) / (exp(sink_h - m_i) +
    sum_j' exp(s_ij' - m_i))`` with ``m_i = max(max_j s_ij, sink_h)``.
    The forward differs only where the statistics are closed, the
    backward kernels not at all; ``d sink`` comes back in float32.
    ``None`` is the program this always was.  ``return_lse`` (with a
    sink) also hands out the rows' log-sum-exp ``[b, heads, s]``
    float32, the sink in it, for a counter: no gradient flows
    through it.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    if k.shape[3] != d:
        raise ValueError(
            f"q has head size {d} but k has {k.shape[3]}"
        )
    if v.shape[2] != kvh:
        raise ValueError(
            f"k has {kvh} heads but v has {v.shape[2]}"
        )
    if h % kvh:
        raise ValueError(
            f"q heads {h} not a multiple of kv heads {kvh}"
        )
    group = h // kvh
    scale = scale if scale is not None else d**-0.5
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                f"a window ({window}) is at least 1 and needs causal"
            )
        if block_k is not None and block_k != (block_q or block_k):
            raise ValueError(
                "a windowed call takes square tiles, not blocks "
                f"({block_q},{block_k})"
            )
        block_q = block_k = block_q or block_k
        if window >= s:
            window = None
    if block_q is None or block_k is None:
        tq, tk = default_blocks(s, q.dtype.itemsize)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = _fit_block(s, block_q)
    block_k = _fit_block(s, block_k)
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq len {s} must be divisible by blocks "
            f"({block_q},{block_k})"
        )

    def fold(x):
        hh, width = x.shape[2:]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, width)

    if sink is None:
        if return_lse:
            raise ValueError("lse is handed out with a sink only")
        out = _flash_mha(
            fold(q), fold(k), fold(v), scale, causal, block_q, block_k,
            group, window,
        )
    else:
        if not causal or sink.shape != (h,):
            raise ValueError(
                f"a sink is [heads] = [{h}] and needs causal, not "
                f"{sink.shape}"
            )
        out, lse = _flash_mha_sink(
            fold(q), fold(k), fold(v),
            jnp.tile(sink.astype(jnp.float32), b), scale, causal,
            block_q, block_k, group, window,
        )
    out = out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)
    if dtype is not None:
        out = out.astype(dtype)
    if return_lse:
        return out, jax.lax.stop_gradient(lse).reshape(b, h, s)
    return out
