"""The selective state-space scan of a Mamba-1 layer (Gu and Dao 2023,
arXiv:2312.00752) as Pallas kernels, forward and backward.

Per channel ``e`` of ``E`` and state lane ``n`` of ``N``, with a state
``h`` in ``R^{E x N}`` that starts at 0, a step ``dt_t[e] > 0`` a token
AND channel, and ``A[e, n] < 0``::

    h_t[e, n] = exp(dt_t[e] A[e, n]) h_{t-1}[e, n] + dt_t[e] B_t[n] x_t[e]
    y_t[e]    = sum_n C_t[n] h_t[e, n] (+ D[e] x_t[e])

``B_t`` and ``C_t`` in ``R^N`` are shared by ALL channels.  The decay
differs by channel and lane, so there is no head to batch a matmul
over and no ``chunk x chunk`` scores form (``ops/ssd.py``'s, whose
decay is one scalar a head): the work is ``s E N`` exponentials and as
many multiply-adds, the VPU's and the EUP's.  The plain forms cannot
stand in at a training size: the discretised operands ``exp(dt A)``
and ``dt B x`` are ``[s, E, N]`` float32 (2.7 GB each at 8192 x 5120 x
16) where these kernels read ``x``, ``dt``, ``B``, ``C`` and write
``y``.

Layout: the operands as the projections write them (``x``, ``dt``,
``y`` ``[b, s, E]``, a channel a lane).  The state is ``[N, E]``: the
state lanes on SUBLANES (``N = 16`` is two float32 tiles), a channel a
lane, float32 in a VMEM scratch from one grid step (a chunk of rows)
to the next.  A token's ``dt`` and ``dt x`` are rows, sublane
broadcasts; its ``B_t[n]`` and ``C_t[n]`` must stand on sublane ``n``
of every lane, so ``B`` and ``C`` arrive transposed (``[b, s / chunk,
N, chunk]``, 0.5 MB, XLA's) and each grid step first lays a token's
column over 128 lanes in a scratch (``[chunk, N, 128]``: two lane
broadcasts a token, shared by all ``E / 128`` lane tiles).  Inside a
grid step a loop walks lane tiles of ``width`` lanes, and for each the
chunk's rows 16 at a time (a bf16 tile of ``x`` and ``y``) with the
tile's state in registers; ``y``'s row is a sublane reduction of ``C_t
* h``, put into its row of the 16 by a select and rounded ONCE, with
the skip ``D x`` already added (the kernel has ``x`` in hand: outside,
the skip reads ``x`` again, 84 MB a layer at 8192 x 5120).  The gate
``y * silu(z)`` stays the caller's: inside, the backward would need
``y`` before the gate again, a second read-out of every state.

``s6_fwd`` writes ``y``, the final state and, for the backward, the
state every chunk STARTS from (float32: ``s / chunk x N x E``, 21 MB
at 8192 / 128 x 16 x 5120).  ``s6_bwd`` walks the chunks in reverse
with ``a_{t+1} dh_{t+1}`` in a VMEM scratch: for a lane tile it makes
the chunk's ``h_t`` again from the start state into a scratch
(``[chunk, N, width]``), then goes back over the tokens with, for
``g_t = dL/dy_t`` and ``a_t = exp(dt_t A)``::

    dh_t = C_t g_t + a_{t+1} dh_{t+1}
    dC_t[n] = sum_e g_t[e] h_t[e, n]
    dB_t[n] = sum_e dh_t[e, n] dt_t[e] x_t[e]
    dx_t[e] = dt_t[e] sum_n dh_t[e, n] B_t[n] + D[e] g_t[e]
    d dt_t[e] = sum_n dh_t[e, n] (A[e, n] a_t h_{t-1}[e, n] + B_t[n] x_t[e])
    dA[e, n] = sum_t dh_t[e, n] dt_t[e] a_t h_{t-1}[e, n]
    dD[e] = sum_t g_t[e] x_t[e]

(``a_t h_{t-1}`` is ``h_t - dt_t B_t x_t``: no division and no second
scratch).  ``dB`` and ``dC`` sum over ALL channels: a ``[chunk, N,
128]`` scratch each adds up the lane tiles and is reduced over its
lanes once a grid step; ``dA`` and ``dD`` sum over all rows in output
blocks that stay resident over a sequence's chunks (the chunk axis is
``arbitrary`` and the kernel relies on it).  A ``jax.custom_vjp``
joins the two; the residuals are the caller's operands and the start
states.

What a rematted caller keeps: the forward rule names what ``s6_fwd``
wrote (``RESIDUAL_NAMES``: ``y``, the final state, the chunk-start
states) and ``models/layers.py::remat_policy`` saves those names, so
the forward kernel runs once a layer; the operands are not named.

A sequence that fills no chunk is padded with ``dt = 0`` (such a token
neither decays nor writes), a channel count that fills no lane tile
with zero lanes.  On the CPU the kernels run in interpreter mode.
Mosaic kernels are not auto-partitioned: sequences and channels are
independent, so under a mesh the call needs a ``shard_map``: none yet
(M6(b4)).

Precision: ``x`` and ``y`` in the caller's type (bf16 on the training
path), ``dt``, ``A``, ``B``, ``C``, ``D``, the state, ``exp``, every
product and sum and the read-out float32 (the family's CUDA path; no
operand of these kernels meets the MXU).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import _named
from dlrover_tpu.ops.gated_delta_rule import F32, _interpret

# what the forward kernel writes, under the names a remat policy keeps
# it by: ``y``, the final state, and the array only the backward
# kernel reads, the chunk-start states
RESIDUAL_NAMES = ("s6_y", "s6_final", "s6_starts")

LANES = 128        # a lane tile
GROUP = 16         # rows taken together: a bf16 tile of x and y
MAX_WIDTH = 512    # lanes of the state held in registers at a time
VMEM_BYTES = 64 << 20


def _params():
    """The grid is ``(batch, chunks)``.  The chunk axis is
    ``arbitrary`` and both kernels RELY on it: the state (forward) and
    ``a dh`` (backward) pass from one chunk to the next in a scratch,
    and ``dA`` and ``dD`` add up in output blocks that stay resident
    over a sequence's chunks."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_BYTES,
    )


def _over_lanes(t_ref, wide_ref):
    """``wide_ref[t]`` (``[N, 128]``) = column ``t`` of ``t_ref``'s
    ``[N, chunk]`` block on every lane: token ``t``'s ``B`` or ``C``
    with lane ``n`` of the state on sublane ``n``."""
    cols = t_ref[0, 0]
    n = cols.shape[0]
    for t in range(cols.shape[1]):
        wide_ref[t] = jnp.broadcast_to(cols[:, t:t + 1], (n, LANES))


def _wide(tile, width):
    """A ``[N, 128]`` tile whose lanes are all the same as ``[N,
    width]``."""
    reps = width // LANES
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _row(rows, j, n):
    """Row ``j`` of ``rows [GROUP, width]`` on ``n`` sublanes."""
    return jnp.broadcast_to(rows[j:j + 1], (n, rows.shape[1]))


def _lane_tiles(total, width, tile):
    """``tile(at)`` for every ``width``-lane window ``at`` of
    ``total`` lanes (ONE trace of the body)."""

    def body(q, _):
        tile(pl.ds(pl.multiple_of(q * width, width), width))
        return _

    jax.lax.fori_loop(0, total // width, body, 0)


def _fwd_kernel(
    x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref,
    y_ref, final_ref, start_ref, state, bb, cb, *, width,
):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[0, 0] = state[...]
    _over_lanes(bt_ref, bb)
    _over_lanes(ct_ref, cb)
    rows, n = x_ref.shape[1], state.shape[0]
    at_row = jax.lax.broadcasted_iota(jnp.int32, (GROUP, width), 0)

    def tile(at):
        a = a_ref[:, at]

        def group(g, h):
            lo = pl.multiple_of(g * GROUP, GROUP)
            x = x_ref[0, pl.ds(lo, GROUP), at].astype(F32)
            dt = dt_ref[0, pl.ds(lo, GROUP), at]
            dtx = dt * x
            y = d_ref[:, at] * x
            for j in range(GROUP):
                h = jnp.exp(_row(dt, j, n) * a) * h + (
                    _row(dtx, j, n) * _wide(bb[lo + j], width)
                )
                out = jnp.sum(
                    h * _wide(cb[lo + j], width), axis=0, keepdims=True
                )
                y = jnp.where(at_row == j, y + out, y)
            y_ref[0, pl.ds(lo, GROUP), at] = y.astype(y_ref.dtype)
            return h

        state[:, at] = jax.lax.fori_loop(
            0, rows // GROUP, group, state[:, at]
        )

    _lane_tiles(x_ref.shape[2], width, tile)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        final_ref[0] = state[...]


def _lane_sums(acc_ref, out_ref):
    """``out_ref``'s ``[N, chunk]`` block: column ``t`` the sum of
    ``acc_ref[t]`` (``[N, 128]``) over its lanes."""
    chunk, n, _ = acc_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1)
    out = jnp.zeros((n, chunk), F32)
    for t in range(chunk):
        out = jnp.where(
            lane == t, jnp.sum(acc_ref[t], axis=1, keepdims=True), out
        )
    out_ref[0, 0] = out


def _fold(x):
    """``[N, width]`` summed down to one lane tile ``[N, 128]``."""
    out = x[:, :LANES]
    for i in range(LANES, x.shape[1], LANES):
        out = out + x[:, i:i + LANES]
    return out


def _bwd_kernel(
    x_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, start_ref, dy_ref,
    dfinal_ref, dx_ref, ddt_ref, dbt_ref, dct_ref, da_ref, dd_ref,
    dstate, bb, cb, db_acc, dc_acc, hs, *, width,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = dfinal_ref[0]
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    _over_lanes(bt_ref, bb)
    _over_lanes(ct_ref, cb)
    db_acc[...] = jnp.zeros_like(db_acc)
    dc_acc[...] = jnp.zeros_like(dc_acc)
    rows, n = x_ref.shape[1], dstate.shape[0]
    groups = rows // GROUP
    at_row = jax.lax.broadcasted_iota(jnp.int32, (GROUP, width), 0)

    def tile(at):
        a = a_ref[:, at]

        def again(g, h):
            # the chunk's states from the one it starts from
            lo = pl.multiple_of(g * GROUP, GROUP)
            dt = dt_ref[0, pl.ds(lo, GROUP), at]
            dtx = dt * x_ref[0, pl.ds(lo, GROUP), at].astype(F32)
            for j in range(GROUP):
                h = jnp.exp(_row(dt, j, n) * a) * h + (
                    _row(dtx, j, n) * _wide(bb[lo + j], width)
                )
                hs[lo + j] = h
            return h

        jax.lax.fori_loop(0, groups, again, start_ref[0, 0, :, at])

        def group(k, carry):
            # carried: a_{t+1} dh_{t+1}, and dA's and dD's sums
            ahead, da, dd = carry
            lo = pl.multiple_of((groups - 1 - k) * GROUP, GROUP)
            x = x_ref[0, pl.ds(lo, GROUP), at].astype(F32)
            dt = dt_ref[0, pl.ds(lo, GROUP), at]
            dy = dy_ref[0, pl.ds(lo, GROUP), at].astype(F32)
            dtx = dt * x
            dx = d_ref[:, at] * dy
            ddt = jnp.zeros_like(x)
            for j in reversed(range(GROUP)):
                t = lo + j
                h = hs[t]
                b_t, c_t = _wide(bb[t], width), _wide(cb[t], width)
                dy_t, dt_t = _row(dy, j, n), _row(dt, j, n)
                dtx_t = _row(dtx, j, n)
                dh = c_t * dy_t + ahead
                dc_acc[t] += _fold(dy_t * h)
                db_acc[t] += _fold(dh * dtx_t)
                # sum_n dh B: dt's share through x, and x's through dt
                read = jnp.sum(dh * b_t, axis=0, keepdims=True)
                # dh a_t h_{t-1}, with a_t h_{t-1} = h_t - dt B x
                kept = dh * (h - dtx_t * b_t)
                da = da + dt_t * kept
                ddt = jnp.where(
                    at_row == j,
                    jnp.sum(kept * a, axis=0, keepdims=True)
                    + x[j:j + 1] * read,
                    ddt,
                )
                dx = jnp.where(at_row == j, dx + dt[j:j + 1] * read, dx)
                ahead = jnp.exp(dt_t * a) * dh
            dx_ref[0, pl.ds(lo, GROUP), at] = dx.astype(dx_ref.dtype)
            ddt_ref[0, pl.ds(lo, GROUP), at] = ddt
            return ahead, da, dd + dy * x

        ahead, da, dd = jax.lax.fori_loop(0, groups, group, (
            dstate[:, at], jnp.zeros((n, width), F32),
            jnp.zeros((GROUP, width), F32),
        ))
        dstate[:, at] = ahead
        da_ref[0, :, at] += da
        dd_ref[0, :, at] += jnp.sum(dd, axis=0, keepdims=True)

    _lane_tiles(x_ref.shape[2], width, tile)
    _lane_sums(db_acc, dbt_ref)
    _lane_sums(dc_acc, dct_ref)


def _width(lanes: int) -> int:
    return max(
        w for w in range(LANES, MAX_WIDTH + 1, LANES) if lanes % w == 0
    )


def _specs(chunk, lanes, n, chunk_of):
    """The block specs of both kernels over a grid of ``(batch,
    chunks)``; ``chunk_of`` maps the grid's second index to the chunk
    it works on."""
    return dict(
        rows=pl.BlockSpec(
            (1, chunk, lanes), lambda b, i: (b, chunk_of(i), 0)
        ),
        a=pl.BlockSpec((n, lanes), lambda b, i: (0, 0)),
        skip=pl.BlockSpec((1, lanes), lambda b, i: (0, 0)),
        bc=pl.BlockSpec(
            (1, 1, n, chunk), lambda b, i: (b, chunk_of(i), 0, 0)
        ),
        state=pl.BlockSpec((1, n, lanes), lambda b, i: (b, 0, 0)),
        starts=pl.BlockSpec(
            (1, 1, n, lanes), lambda b, i: (b, chunk_of(i), 0, 0)
        ),
        sums=pl.BlockSpec((1, 1, lanes), lambda b, i: (b, 0, 0)),
    )


# (jitted: traced once for all of a model's layers and call sites)
@functools.partial(jax.jit, static_argnames=("chunk",))
def _forward(x, dt, a, bt, ct, d, *, chunk):
    """``x [b, s, E]``, ``dt [b, s, E]`` float32, ``a [N, E]``, ``bt,
    ct [b, s / chunk, N, chunk]`` float32, ``d [1, E]`` ->
    ``(y, final state [b, N, E], chunk-start states [b, s / chunk, N,
    E])``, ``s`` whole chunks and ``E`` whole lane tiles."""
    batch, s, lanes = x.shape
    n, z = a.shape[0], s // chunk
    spec = _specs(chunk, lanes, n, lambda i: i)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=_width(lanes)),
        grid=(batch, z),
        in_specs=[
            spec["rows"], spec["rows"], spec["a"], spec["bc"], spec["bc"],
            spec["skip"],
        ],
        out_specs=[spec["rows"], spec["state"], spec["starts"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, n, lanes), F32),
            jax.ShapeDtypeStruct((batch, z, n, lanes), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, lanes), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
        name="s6_fwd",
    )(x, dt, a, bt, ct, d)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _backward(x, dt, a, bt, ct, d, starts, dy, dfinal, *, chunk):
    """-> ``(dx, d dt, dBt, dCt, dA [b, N, E], dD [b, 1, E])`` in the
    layouts of :func:`_forward`."""
    batch, s, lanes = x.shape
    n, z = a.shape[0], s // chunk
    # the chunks in reverse
    spec = _specs(chunk, lanes, n, lambda i: z - 1 - i)
    width = _width(lanes)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width),
        grid=(batch, z),
        in_specs=[
            spec["rows"], spec["rows"], spec["a"], spec["bc"], spec["bc"],
            spec["skip"], spec["starts"], spec["rows"], spec["state"],
        ],
        out_specs=[
            spec["rows"], spec["rows"], spec["bc"], spec["bc"],
            spec["state"], spec["sums"],
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(bt.shape, F32),
            jax.ShapeDtypeStruct(ct.shape, F32),
            jax.ShapeDtypeStruct((batch, n, lanes), F32),
            jax.ShapeDtypeStruct((batch, 1, lanes), F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, lanes), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
            pltpu.VMEM((chunk, n, LANES), F32),
            pltpu.VMEM((chunk, n, width), F32),
        ],
        compiler_params=_params(),
        interpret=_interpret(),
        name="s6_bwd",
    )(x, dt, a, bt, ct, d, starts, dy, dfinal)


def _operands(x, dt, A, B, C, D, chunk):
    """The caller's arrays as the kernels take them: a tail that fills
    no chunk padded with ``dt = 0`` (it neither decays nor writes, so
    the final state is the last real token's), channels that fill no
    lane tile with zero lanes, ``A`` with the state's lanes leading,
    ``B`` and ``C`` a chunk's tokens a lane."""
    b, s, lanes = x.shape
    n = A.shape[1]
    rows, more = -s % chunk, -lanes % LANES

    def tokens(a):
        return jnp.pad(a, ((0, 0), (0, rows), (0, more)))

    def columns(a):
        a = jnp.pad(a.astype(F32), ((0, 0), (0, rows), (0, 0)))
        return a.reshape(b, -1, chunk, n).transpose(0, 1, 3, 2)

    def channels(a):
        return jnp.pad(a.astype(F32), ((0, 0), (0, more)))

    return (
        tokens(x), tokens(dt.astype(F32)), channels(A.T), columns(B),
        columns(C), channels(D[None]),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, A, B, C, D, chunk):
    return _scan_fwd(x, dt, A, B, C, D, chunk)[0]


def _scan_fwd(x, dt, A, B, C, D, chunk):
    s, lanes = x.shape[1:]
    y, final, starts = _forward(
        *_operands(x, dt, A, B, C, D, chunk), chunk=chunk
    )
    y_name, final_name, starts_name = RESIDUAL_NAMES
    # ``y`` and the final state go on into the block, so they are
    # named as bits; the start states are the residuals' alone
    y, final = _named(y, y_name), _named(final, final_name)
    return (
        y[:, :s, :lanes], final[:, :, :lanes].transpose(0, 2, 1)
    ), (x, dt, A, B, C, D, checkpoint_name(starts, starts_name))


def _scan_bwd(chunk, kept, cotangents):
    *given, starts = kept
    dy, dfinal = cotangents
    operands, back = jax.vjp(
        functools.partial(_operands, chunk=chunk), *given
    )
    x = operands[0]
    rows, more = x.shape[1] - dy.shape[1], x.shape[2] - dy.shape[2]
    dx, ddt, dbt, dct, da, dd = _backward(
        *operands, starts,
        jnp.pad(dy, ((0, 0), (0, rows), (0, more))),
        jnp.pad(dfinal.transpose(0, 2, 1), ((0, 0), (0, 0), (0, more))),
        chunk=chunk,
    )
    return back((dx, ddt, da.sum(axis=0), dbt, dct, dd.sum(axis=0)))


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    x: jax.Array,    # [b, s, E]
    dt: jax.Array,   # [b, s, E]  float32, > 0 (after the softplus)
    A: jax.Array,    # [E, N]     float32, < 0
    B: jax.Array,    # [b, s, N]
    C: jax.Array,    # [b, s, N]
    D=None,          # [E]        float32: the skip, inside the kernel
    *,
    chunk: int = 128,
):
    """``(y [b, s, E] in x's type, final state [b, E, N] float32)`` of
    the recurrence above from a zero state.  Differentiable in all six
    operands, and the final state's cotangent is read.  ``chunk`` (a
    multiple of 16) is the rows of a grid step and the spacing of the
    kept start states.  Without ``D`` the kernels' skip is zero.  The
    gate ``y * silu(z)`` is the caller's."""
    if chunk % GROUP or B.shape != C.shape or A.shape != (
        x.shape[2], B.shape[2]
    ) or dt.shape != x.shape:
        raise ValueError(
            f"x {x.shape}, dt {dt.shape}, A {A.shape}, B {B.shape}, "
            f"C {C.shape}, chunk {chunk}"
        )
    if D is None:
        D = jnp.zeros(x.shape[2], F32)
    return _scan(x, dt, A, B, C, D, chunk)
