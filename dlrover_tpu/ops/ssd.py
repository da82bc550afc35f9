"""The state-space scan of a Mamba-2 layer, computed over chunks in
Pallas kernels (the "state space duality" form: Dao and Gu 2024,
arXiv:2405.21060).

Per head ``h`` of ``H``, with a state ``S`` in ``R^{P x N}`` that starts
at 0, a scalar step ``dt_t > 0`` and a scalar ``A_h < 0``::

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t

``x_t`` in ``R^P`` is the head's own; ``B_t`` and ``C_t`` in ``R^N``
belong to the head's GROUP (``G`` groups of ``H / G`` heads share
them).  There is no delta correction and no inverse: the decay is a
scalar a head and token.  Token by token this is a scan of ``seq``
rank-one updates: no training path.  Over a chunk of ``chunk``
tokens, with ``cum`` the running sum of ``dt A`` inside the chunk and
``S`` the state the chunk starts from::

    L_ij = exp(cum_i - cum_j), i >= j
    Y    = ((C B^T) * L) (dt x) + (C * exp(cum)) S^T
    S   <- exp(cum_last) S + ((dt x) * exp(cum_last - cum))^T B

``ssd_fwd`` walks a group's chunks in order, one grid step a chunk of
ONE GROUP's heads: the state of those heads lives in a VMEM scratch
across the chunk axis (the scratch IS the hand-over between chunks),
and the running sums, the decays ``L`` (as a difference under the
causal mask: ``exp(cum_i) exp(-cum_j)`` overflows), ``C B^T`` and the
masked scores never leave VMEM.  It writes ``y``, the final state and,
for the backward, the state each chunk STARTS from, in float32 (what
the state is; 134 MB a layer at 1 x 8192 x 64 heads of 64 x 128).
``ssd_bwd`` walks the chunks in reverse with ``dS`` in VMEM, makes a
chunk's decays, scores and outputs again from the operands and the
saved start state and emits ``dx``, ``dB``, ``dC`` (summed over a
group's heads), ``d dt`` and the gradient of ``dt A`` a token, whose
sum over tokens against ``dt`` is ``dA``.  The running sum's gradient
needs no ``chunk x chunk`` matrix of its own: ``cum_i`` scales token
``i``'s output, so its share is ``<dy_i, y_i>``; ``cum_j`` shrinks
what token ``j`` gives later tokens and leaves in the state, whose
gradients are column sums the kernel has anyway (of ``dm * m`` with
``m`` AS ROUNDED for the matmuls: the two sides have to be made of the
same numbers, or the pairs that cancel in ``dt A``'s gradient leave
their rounding behind); and the chunk's last token takes ``<dS', S'>``
besides.  A ``jax.custom_vjp`` joins the
two, so what is kept for the backward is said here (the caller's five
operands and the chunk-start states) and nothing ``chunk x chunk`` is
a residual.

What a rematted caller keeps.  The forward rule names what
``ssd_fwd`` wrote and anything reads after it (``RESIDUAL_NAMES``:
``y``, the final state, the chunk-start states), and a
``jax.checkpoint`` whose policy saves those names
(``models/layers.py::remat_policy``) does not run the forward kernel
again in its backward: EVERY layer's ``y`` and start states then live
from its forward to its backward (67 + 134 MB a layer at the sizes
above, where one layer's lived at a time before PR 65), which is the
price of the kernel's second run.  The operands are not named: their
producers run again, read by gradients of their own.

Layout: the operands as the model holds them.  A group's ``H / G``
heads are ``H / G x P`` contiguous lanes of ``x [b, s, H P]`` and the
group's ``B`` and ``C`` ``N`` lanes of ``[b, s, G N]``: legal blocks
as they stand, so there is no heads-leading copy of anything and ``C
B^T`` is computed once for a group.  A head is three matrices in
VMEM (:func:`_head`): ``m = (C B^T) * L * dt`` (``[chunk, chunk]``),
``read = C * exp(cum)`` (``[chunk, N]``) and ``write = B^T * dt
exp(cum_last - cum)`` (``[N, chunk]``), so that ``Y = [m | read] [x;
S^T]`` and ``S'^T = exp(cum_last) S^T + write x`` (the kernels hold
the state transposed, ``[N, H/G P]``, beside ``x``'s lanes) with ``x``
as it arrives; the products are taken ``128 / P`` heads at a time
over whole 128-lane columns of ``x``, each head keeping its own lanes.
Per-token factors are rows of lanes wherever they can be: ``dt``
alone is transposed outside (``[b, G, H/G, s]`` float32, 2 MB), so a
head's steps are a row, the running sum is a few lane rotations of
ONE vector register, and ``dt`` and ``exp(cum_last - cum)`` scale
COLUMNS of ``m`` and ``write``.  The one factor that scales rows is
``cum_i`` (in ``L`` and ``read``): one ``chunk x chunk`` transpose a
grid step and a lane broadcast a head (laying ``dt``, ``exp(cum)``
and ``exp(cum_last - cum)`` over ``x``'s lanes instead took three
more broadcasts a head and a third more time in the forward: PERF.md,
PR 48).  On the CPU the kernels run in interpreter mode.  Mosaic
kernels are not auto-partitioned: batch and groups are independent,
so under such a mesh the call needs a ``shard_map``: none yet (M6(b4)).

Precision: ``dt``, ``A``, the running sums, every decay, the state and
``dS`` are float32.  The matmuls take their operands in the type ``x``
arrives in (bf16 on the training path: ``m``, ``read``, ``write``
and, for the read-out alone, the chunk-start state are rounded to it,
``x`` is not touched; in the backward the cotangents likewise) and
accumulate in float32; float32 operands run at ``HIGHEST``
throughout.  The per-head sum behind ``<dy, y>`` is a float32 sum (on
the MXU, of values split into two bf16).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlrover_tpu.ops.flash_attention import _named
from dlrover_tpu.ops.gated_delta_rule import (
    F32, NN, NT, TN, _dot, _interpret, _iotas, _lanes, _params, _split,
)

# what the forward kernel writes, under the names a remat policy keeps
# it by: ``y`` (``[b, s, H P]``), the final state, and the array only
# the backward kernel reads, the chunk-start states
RESIDUAL_NAMES = ("ssd_y", "ssd_final", "ssd_starts")


def _running_sum(x, reverse=False):
    """The running sum of ``x [heads, c]`` along the lanes (from the
    last lane down with ``reverse``): ``log2 c`` rotations."""
    c = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < c:
        if reverse:
            x = x + jnp.where(lane < c - k, pltpu.roll(x, c - k, 1), 0.0)
        else:
            x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    return x


def _columns(rows):
    """Lane-form rows ``[n, c]`` (a token a lane) as columns ``[c,
    ..]``: column ``i`` is row ``i``.  One transpose of whole tiles."""
    n, c = rows.shape
    pad = -n % c
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, c), F32)], axis=0)
    return rows.T


def _chunk(dt_ref, a_ref, b_ref, c_ref, exact):
    """What the forward and the backward share of one chunk of one
    group: each head's per-token scalars, a token a lane (``[r, c]``;
    ``write`` is ``dt exp(cum_last - cum)``, what a token's ``x B^T``
    is worth at the chunk's end), the running sums a token a row as
    well (``cols``), and the group's scores and ``B^T``."""
    dt = dt_ref[0, 0]                                     # [r, c]
    r, c = dt.shape
    cum = _running_sum(dt * a_ref[0])
    last = cum[:, c - 1:]                                 # [r, 1]
    to_end = jnp.exp(last - cum)
    b, cc = b_ref[0], c_ref[0]
    row, col = _iotas(c)
    return dict(
        r=r, c=c, dt=dt, cum=cum, cols=_columns(cum), to_end=to_end,
        write=dt * to_end, end=jnp.exp(last), b=b, cc=cc,
        c32=cc.astype(F32), bt=b.astype(F32).T, causal=row >= col,
        scores=_dot(cc, b, NT, exact),
    )


def _head(k, h, dtype):
    """One head's three matrices in the operands' type: ``m = (C B^T)
    * L * dt`` (``[c, c]``: what token ``j`` gives token ``i``),
    ``read = C * exp(cum)`` (``[c, N]``: what the start state gives
    token ``i``) and ``write = B^T * dt exp(cum_last - cum)`` (``[N,
    c]``: what token ``j`` leaves in the state), with the float32
    factors the backward needs again.  The ONE per-token factor that
    scales rows is ``cum_i``, a lane broadcast of its column; every
    other is a row of lanes."""
    c, n = k["c"], k["bt"].shape[0]

    def lanes(rows, height=c):
        return jnp.broadcast_to(rows[h:h + 1], (height, c))

    cum_i = jnp.broadcast_to(k["cols"][:, h:h + 1], (c, c))
    # (the exponent is masked, not the result: above the diagonal it
    # is positive)
    decay = jnp.exp(
        jnp.where(k["causal"], cum_i - lanes(k["cum"]), -jnp.inf)
    )
    grow = _lanes(jnp.exp(cum_i), n)
    scaled = decay * lanes(k["dt"])
    write = lanes(k["write"], n)
    return dict(
        scaled=scaled, grow=grow, write_j=write,
        m=(k["scores"] * scaled).astype(dtype),
        read=(k["c32"] * grow).astype(dtype),
        write=(k["bt"] * write).astype(dtype),
    )


def _heads_wide(values, width, p):
    """``values[j]`` (``[rows, width]`` or what broadcasts to it) in
    head ``j``'s ``p`` lanes of a block of ``width / p`` heads."""
    out = values[0]
    if len(values) > 1:
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (out.shape[0], width), 1
        )
        for j, value in enumerate(values[1:], 1):
            out = jnp.where(lane >= j * p, value, out)
    return out


def _end_row(k, first, heads, width, p):
    """``exp(cum_last)`` of each head over its lanes, ``[1, width]``."""
    return _heads_wide([
        jnp.broadcast_to(k["end"][first + j:first + j + 1], (1, width))
        for j in range(heads)
    ], width, p)


def _fwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,
    y_ref, final_ref, start_ref, state, *, exact, p, width,
):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    start_ref[0, 0, 0] = state[...]
    k = _chunk(dt_ref, a_ref, b_ref, c_ref, exact)
    dtype = x_ref.dtype
    heads = width // p
    for q in range(x_ref.shape[2] // width):
        at = slice(q * width, (q + 1) * width)
        s = state[:, at]                                  # [N, width]
        x = x_ref[0, :, at]
        # Y = [m | read] [x; S^T], a head at a time over the block's
        # lanes: a head's own lanes of each product are kept
        both = jnp.concatenate([x, s.astype(dtype)], axis=0)
        own, wrote = [], []
        for j in range(heads):
            head = _head(k, q * heads + j, dtype)
            own.append(_dot(
                jnp.concatenate([head["m"], head["read"]], axis=1), both,
                NN, exact,
            ))
            wrote.append(_dot(head["write"], x, NN, exact))
        y_ref[0, :, at] = _heads_wide(own, width, p).astype(y_ref.dtype)
        state[:, at] = _end_row(k, q * heads, heads, width, p) * s + (
            _heads_wide(wrote, width, p)
        )

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        final_ref[0, 0] = state[...]


def _head_sums(values, first, rows, p, exact):
    """``[.., width]`` summed over each head's ``p`` lanes, ``[rows,
    ..]`` with the block's head ``j`` in row ``first + j`` and zeros in
    the others: a row in, a column out; ``[c, width]`` in, a token a
    lane out (on the MXU, float32 as two bf16)."""
    width = values.shape[1]
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) - first
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    mine = (lane >= head * p) & (lane < (head + 1) * p)
    if values.shape[0] == 1:
        return jnp.sum(jnp.where(mine, values, 0.0), axis=1, keepdims=True)
    if exact:
        return _dot(mine.astype(F32), values, NT, True)
    mine = mine.astype(jnp.bfloat16)
    hi, lo = _split(values)
    return _dot(mine, hi, NT, False) + _dot(mine, lo, NT, False)


def _row(rows, h, value):
    """``rows [r, c]`` with ``value [1, c]`` in row ``h``."""
    at = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(at == h, value, rows)


def _bwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, start_ref, dy_ref, dfinal_ref,
    dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dstate, *, exact, p, width,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = dfinal_ref[0, 0]

    k = _chunk(dt_ref, a_ref, b_ref, c_ref, exact)
    r, c = k["r"], k["c"]
    dtype = x_ref.dtype
    heads = width // p
    d_scores = jnp.zeros((c, c), F32)
    dbt = jnp.zeros(k["bt"].shape, F32)
    dc = jnp.zeros(k["c32"].shape, F32)
    # a head a row, a token a lane: column sums of dm * m (dt times
    # dt's gradient through m), the gradient of write's per-token
    # factor, <dy, y> and the chunk's end's <dS', S>
    d_given = jnp.zeros((r, c), F32)
    d_write = jnp.zeros((r, c), F32)
    d_rows = jnp.zeros((r, c), F32)
    d_end = jnp.zeros((r, 1), F32)
    for q in range(x_ref.shape[2] // width):
        at = slice(q * width, (q + 1) * width)
        first = q * heads
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        s = start_ref[0, 0, 0, :, at]                     # [N, width]
        ds = dstate[:, at]
        s_in, ds_in = s.astype(dtype), ds.astype(dtype)
        x, dy = x_ref[0, :, at], dy_ref[0, :, at]
        both = jnp.concatenate([x, s_in], axis=0)
        d_both = jnp.concatenate([dy, ds_in], axis=0)
        own, back, kept = [], [], []
        for j in range(heads):
            h = first + j
            head = _head(k, h, dtype)
            mine = (lane >= j * p) & (lane < (j + 1) * p)
            dy_j = dy if heads == 1 else jnp.where(mine, dy, 0)
            ds_j = ds_in if heads == 1 else jnp.where(mine, ds_in, 0)
            # Y = [m | read] [x; S^T];  S'^T = end S^T + write x
            own.append(_dot(
                jnp.concatenate([head["m"], head["read"]], axis=1), both,
                NN, exact,
            ))
            back.append(_dot(
                jnp.concatenate([head["m"], head["write"]], axis=0),
                d_both, TN, exact,
            ))
            kept.append(_dot(head["read"], dy, TN, exact))
            dm = _dot(dy_j, x, NT, exact)                 # [c, c]
            d_scores = d_scores + dm * head["scaled"]
            dc = dc + head["grow"] * _dot(dy_j, s_in, NT, exact)
            d_wrote = _dot(ds_j, x, NT, exact)            # [N, c]
            dbt = dbt + d_wrote * head["write_j"]
            # (m as ROUNDED: what <dy, y> below is made of, so that
            # the pairs i >= j > t cancel out of dt A's gradient at t
            # as they do in exact arithmetic)
            d_given = _row(d_given, h, jnp.sum(
                dm * head["m"].astype(F32), axis=0, keepdims=True
            ))
            d_write = _row(d_write, h, jnp.sum(
                d_wrote * k["bt"], axis=0, keepdims=True
            ))
        dx_ref[0, :, at] = _heads_wide(back, width, p).astype(dx_ref.dtype)
        end = _end_row(k, first, heads, width, p)
        dstate[:, at] = end * ds + _heads_wide(kept, width, p)
        d_rows = d_rows + _head_sums(
            dy.astype(F32) * _heads_wide(own, width, p), first, r, p, exact
        )
        d_end = d_end + _head_sums(
            end * jnp.sum(ds * s, axis=0, keepdims=True), first, r, p,
            exact,
        )
    d_in = d_scores.astype(dtype)
    dc_ref[0] = (dc + _dot(d_in, k["b"], NN, exact)).astype(dc_ref.dtype)
    db_ref[0] = (
        dbt + _dot(k["cc"], d_in, TN, exact)
    ).T.astype(db_ref.dtype)
    # cum_i scales token i's y; cum_j shrinks what token j gives (m)
    # and leaves (write); the last one scales the whole new state
    d_leaves = k["write"] * d_write
    d_cum = d_rows - d_given - d_leaves
    # cum is a running sum: dt A at token t reaches every later cum
    da = _running_sum(d_cum, reverse=True) + (
        d_end + jnp.sum(d_leaves, axis=1, keepdims=True)
    )
    da_ref[0, 0] = da
    # (d_given holds dt's factor; a padded token's dt is 0, and so is
    # what it gives)
    ddt_ref[0, 0] = (
        d_given / jnp.where(k["dt"] > 0, k["dt"], 1.0)
        + k["to_end"] * d_write + a_ref[0] * da
    )


def _block_width(per_group: int, p: int) -> int:
    """Lanes of the heads taken together in the head's own product:
    whole 128-lane columns where heads are narrower."""
    heads = max(
        n for n in range(1, per_group + 1)
        if per_group % n == 0 and n * p <= max(p, 128)
    )
    return heads * p


def _specs(batch, groups, per_group, chunk, wide, n, chunk_of):
    """The block specs of both kernels over a grid of ``(batch x
    groups, chunks)``; ``chunk_of`` maps the grid's second index to
    the chunk it works on."""

    def at(i):
        return i // groups, i % groups

    def tokens(lanes):
        return pl.BlockSpec(
            (1, chunk, lanes),
            lambda i, j: (at(i)[0], chunk_of(j), at(i)[1]),
        )

    return dict(
        x=tokens(wide), bc=tokens(n),
        steps=pl.BlockSpec(
            (1, 1, per_group, chunk),
            lambda i, j: (*at(i), 0, chunk_of(j)),
        ),
        a=pl.BlockSpec((1, per_group, 1), lambda i, j: (at(i)[1], 0, 0)),
        state=pl.BlockSpec((1, 1, n, wide), lambda i, j: (*at(i), 0, 0)),
        starts=pl.BlockSpec(
            (1, 1, 1, n, wide),
            lambda i, j: (at(i)[0], chunk_of(j), at(i)[1], 0, 0),
        ),
    )


def _sizes(x, dt, b, chunk):
    batch, s, lanes = x.shape
    groups, per_group = dt.shape[1:3]
    return (
        batch, groups, per_group, chunk, lanes // groups,
        b.shape[2] // groups,
    ), s // chunk


# (jitted: traced once for all of a model's layers and call sites)
@functools.partial(jax.jit, static_argnames=("chunk", "p"))
def _forward(x, dt, a, b, c, *, chunk, p):
    """``x [b, s, H P]``, ``dt [b, G, H/G, s]`` float32, ``a [G, H/G,
    1]``, ``b, c [b, s, G N]`` -> ``(y, final state [b, G, N, H/G P],
    chunk-start states [b, s / chunk, G, N, H/G P])``, ``s`` whole
    chunks."""
    sizes, z = _sizes(x, dt, b, chunk)
    batch, groups, per_group, _, wide, n = sizes
    spec = _specs(*sizes, lambda j: j)
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, exact=x.dtype == F32, p=p,
            width=_block_width(per_group, p),
        ),
        grid=(batch * groups, z),
        in_specs=[
            spec["x"], spec["steps"], spec["a"], spec["bc"], spec["bc"]
        ],
        out_specs=[spec["x"], spec["state"], spec["starts"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, groups, n, wide), F32),
            jax.ShapeDtypeStruct((batch, z, groups, n, wide), F32),
        ],
        scratch_shapes=[pltpu.VMEM((n, wide), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ssd_fwd",
    )(x, dt, a, b, c)


@functools.partial(jax.jit, static_argnames=("chunk", "p"))
def _backward(x, dt, a, b, c, starts, dy, dfinal, *, chunk, p):
    """-> ``(dx, d dt, d (dt A), db, dc)`` in the layouts of
    :func:`_forward`."""
    sizes, z = _sizes(x, dt, b, chunk)
    batch, groups, per_group, _, wide, n = sizes
    # the chunks in reverse
    spec = _specs(*sizes, lambda j: z - 1 - j)
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel, exact=x.dtype == F32, p=p,
            width=_block_width(per_group, p),
        ),
        grid=(batch * groups, z),
        in_specs=[
            spec["x"], spec["steps"], spec["a"], spec["bc"], spec["bc"],
            spec["starts"], spec["x"], spec["state"],
        ],
        out_specs=[
            spec["x"], spec["steps"], spec["steps"], spec["bc"], spec["bc"]
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(dt.shape, F32),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, wide), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="ssd_bwd",
    )(x, dt, a, b, c, starts, dy, dfinal)


def _operands(x, dt, A, B, C, chunk):
    """The caller's arrays as the kernels take them: heads and groups
    folded into the lanes they already are, a tail that fills no chunk
    padded with ``dt = 0`` (it neither decays nor writes, so the final
    state is the last real token's), ``dt`` a token a lane."""
    b, s = dt.shape[:2]
    groups = B.shape[2]
    pad = -s % chunk

    def tokens(a):
        return jnp.pad(
            a.reshape(b, s, -1), ((0, 0), (0, pad), (0, 0))
        )

    steps = tokens(dt.astype(F32)).reshape(b, s + pad, groups, -1)
    return (
        tokens(x), steps.transpose(0, 2, 3, 1),
        A.astype(F32).reshape(groups, -1, 1), tokens(B), tokens(C),
    )


def _state_in(state, groups):
    """``[b, H, P, N]`` as the kernels hold it, ``[b, G, N, H/G P]``."""
    b, heads, p, n = state.shape
    return state.reshape(b, groups, -1, p, n).transpose(
        0, 1, 4, 2, 3
    ).reshape(b, groups, n, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, A, B, C, chunk):
    return _scan_fwd(x, dt, A, B, C, chunk)[0]


def _scan_fwd(x, dt, A, B, C, chunk):
    b, s, heads, p = x.shape
    groups, n = B.shape[2:]
    y, final, starts = _forward(
        *_operands(x, dt, A, B, C, chunk), chunk=chunk, p=p
    )
    y_name, final_name, starts_name = RESIDUAL_NAMES
    # ``y`` and the final state go on into the block, so they are
    # named as bits; the start states are the residuals' alone
    y, final = _named(y, y_name), _named(final, final_name)
    final = final.reshape(b, groups, n, -1, p).transpose(0, 1, 3, 4, 2)
    return (
        y[:, :s].reshape(x.shape), final.reshape(b, heads, p, n)
    ), (x, dt, A, B, C, checkpoint_name(starts, starts_name))


def _scan_bwd(chunk, kept, cotangents):
    *given, starts = kept
    dy, dfinal = cotangents
    operands, back = jax.vjp(
        functools.partial(_operands, chunk=chunk), *given
    )
    steps = operands[1]
    b, s = dy.shape[:2]
    dy = jnp.pad(
        dy.reshape(b, s, -1), ((0, 0), (0, steps.shape[-1] - s), (0, 0))
    )
    dx, ddt, da, db, dc = _backward(
        *operands, starts, dy, _state_in(dfinal, steps.shape[1]),
        chunk=chunk, p=given[0].shape[-1],
    )
    # A scales every step of its head
    dA = jnp.sum(steps * da, axis=(0, 3))[..., None]
    return back((dx, ddt, dA, db, dc))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(
    x: jax.Array,    # [b, s, H, P]
    dt: jax.Array,   # [b, s, H]  float32, > 0 (after the softplus)
    A: jax.Array,    # [H]        float32, < 0
    B: jax.Array,    # [b, s, G, N]
    C: jax.Array,    # [b, s, G, N]
    chunk: int = 128,
):
    """``(y [b, s, H, P] in x's type, final state [b, H, P, N]
    float32)`` of the recurrence above from a zero state; head ``h``
    reads group ``h // (H / G)``.  Differentiable in all five
    operands, and the final state's cotangent is read.  The skip ``D
    x`` is the caller's (one multiply-add, no part of the
    recurrence)."""
    heads, groups = x.shape[2], B.shape[2]
    if heads % groups or B.shape != C.shape:
        raise ValueError(
            f"{heads} heads over B {B.shape} and C {C.shape}"
        )
    return _scan(x, dt, A, B, C, chunk)
