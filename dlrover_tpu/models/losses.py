"""Memory-efficient losses: sequence-chunked fused lm_head + CE.

The classic long-context memory cliff is the logits tensor: a
50304-word head at batch 2 x seq 4096 materializes ``[2, 4096, 50304]``
fp32 logits (1.6 GB) plus the same again for the softmax backward,
often larger than the whole transformer's activations.

TPU-native fix: never build the full logits.  Both heads here scan
over sequence chunks; each step projects one chunk through the head
and reduces its float32 logits to cross entropies while they exist.
Under ``jax.grad`` the same step also forms the chunk's two gradients
(``d_hidden = d_logits @ W^T``, ``d_W += h^T @ d_logits``), so no
logits are stored and none are made a second time: three
vocabulary-sized matmuls a chunk, the number the algorithm requires,
and the backward rule only scales what the forward rule left.  Peak
logits memory drops from ``O(S * V)`` to ``O(S/num_chunks * V)``.

Two forms, one chunk body (:func:`_chunk_nll`):

``chunked_cross_entropy``
    the mean over all rows: one scalar out, a scalar cotangent in.
    The forward rule keeps the two gradients for a cotangent of 1
    (``hidden``'s and the kernel's shape and dtype).  Called by the
    GPT family through ``chunked_loss_fn`` (tied ``wte``,
    ``transpose=True``) and by the ``olmoe``, ``olmo_hybrid``,
    ``sarvam_mla`` and ``laguna`` losses (untied ``lm_head``): the
    benchmark's cells ``olmoe_steady_4k``, ``olmo_hybrid_steady_8k``,
    ``sarvam_steady_8k`` and ``laguna_steady_8k``.  The program it
    lowers to is pinned (``tests/test_ouro.py``).
``weighted_chunked_cross_entropy``
    per-row weights in, ``(sum of weight x nll, the per-row nll)``
    out: a loss that mixes several exits' cross entropies token by
    token (``models/ouro.py``: the rows are the exits stacked, the
    weights each token's exit distribution, and the per-row nll is
    the gradient with respect to the weights).  ``d_logits`` is scaled
    row by row inside the chunk; the forward rule keeps the two
    gradients and the per-row nll.  Cell ``ouro_steady_1x4k``.

Every operation of either head carries the device scope ``loss_head``.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from dlrover_tpu.telemetry.tracing import device_scope


def _split(a, num_chunks):
    """``[n, seq, ...]`` with the scan axis leading and a chunk's rows
    of every entry as one axis: ``[num_chunks, n * chunk, ...]``."""
    n, s = a.shape[:2]
    c = s // num_chunks
    return jnp.moveaxis(
        a.reshape((n, num_chunks, c) + a.shape[2:]), 1, 0
    ).reshape((num_chunks, n * c) + a.shape[2:])


def _join(chunks, n):
    """:func:`_split` undone: ``[n, seq, ...]``."""
    num_chunks, rows = chunks.shape[:2]
    return jnp.moveaxis(
        chunks.reshape((num_chunks, n, rows // n) + chunks.shape[2:]),
        0, 1,
    ).reshape((n, num_chunks * (rows // n)) + chunks.shape[2:])


def _chunks(hidden, targets, num_chunks):
    """Scan axis leading, a chunk's rows of every batch entry as one
    axis: ``[num_chunks, batch * chunk, hid]``, so each of the head's
    matmuls is one plain 2-D product."""
    return _split(hidden, num_chunks), _split(targets, num_chunks)


def _chunk_nll(h_chunk, kernel, t_chunk, transpose):
    """One chunk's float32 logits (a product in the activation dtype,
    accumulated in float32), their log-sum-exp pieces, and each row's
    NLL."""
    logits = jnp.einsum(
        "th,vh->tv" if transpose else "th,hv->tv", h_chunk, kernel,
        preferred_element_type=jnp.float32,
    )
    top = logits.max(axis=-1, keepdims=True)
    exp = jnp.exp(logits - top)
    norm = exp.sum(axis=-1, keepdims=True)
    hit = (
        jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        == t_chunk[:, None]
    )
    picked = jnp.where(hit, logits, 0.0).sum(axis=-1)
    nll = jnp.log(norm[:, 0]) + top[:, 0] - picked
    return nll, exp, norm, hit


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head(hidden, head_kernel, targets, num_chunks, transpose):
    """Value only: one matmul a chunk, no gradient formed."""
    b, s, _ = hidden.shape
    with device_scope("loss_head"):
        kernel = head_kernel.astype(hidden.dtype)

        def body(total, xs):
            h_chunk, t_chunk = xs
            nll, *_ = _chunk_nll(h_chunk, kernel, t_chunk, transpose)
            return total + nll.sum(), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            _chunks(hidden, targets, num_chunks),
        )
        return total / (b * s)


def _head_fwd(hidden, head_kernel, targets, num_chunks, transpose):
    """The value, and both gradients (for a cotangent of 1) formed
    where each chunk's logits already are."""
    b, s, h = hidden.shape
    d_hidden_spec = "tv,vh->th" if transpose else "tv,hv->th"
    d_kernel_spec = "th,tv->vh" if transpose else "th,tv->hv"
    with device_scope("loss_head"):
        # head matmuls in the activation dtype (bf16 on TPU) like the
        # models' own head paths; only the softmax is float32
        kernel = head_kernel.astype(hidden.dtype)

        def body(carry, xs):
            total, d_kernel = carry
            h_chunk, t_chunk = xs
            nll, exp, norm, hit = _chunk_nll(
                h_chunk, kernel, t_chunk, transpose
            )
            nll = nll.sum()
            # d(mean NLL) / d(logits), rounded once to the matmuls'
            # dtype and WRITTEN once: left to itself the TPU compiler
            # fuses this softmax into the operands of both matmuls
            # below, which then read the float32 logits and take the
            # exponentials twice (measured on a v5e, PR 33: 34.4 ->
            # 31.8 ms a call at [2, 4096, 2048] x 50304, 124.0 -> 119.1
            # at [1, 8192, 3840] x 100352)
            d_logits = jax.lax.optimization_barrier((
                (exp / norm - hit.astype(jnp.float32)) / (b * s)
            ).astype(hidden.dtype))
            d_chunk = jnp.einsum(d_hidden_spec, d_logits, kernel)
            # the carry keeps the kernel's dtype; the chunk's product
            # joins it before the rounding, in the matmul's epilogue
            d_kernel = (
                d_kernel.astype(jnp.float32) + jnp.einsum(
                    d_kernel_spec, h_chunk, d_logits,
                    preferred_element_type=jnp.float32,
                )
            ).astype(d_kernel.dtype)
            return (total + nll, d_kernel), d_chunk

        (total, d_kernel), d_chunks = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros_like(head_kernel)),
            _chunks(hidden, targets, num_chunks),
        )
        d_hidden = (
            d_chunks.reshape(num_chunks, b, s // num_chunks, h)
            .transpose(1, 0, 2, 3).reshape(b, s, h)
        )
        return total / (b * s), (d_hidden, d_kernel)


def _head_bwd(num_chunks, transpose, residuals, ct):
    d_hidden, d_kernel = residuals
    with device_scope("loss_head"):
        return (
            d_hidden * ct.astype(d_hidden.dtype),
            d_kernel * ct.astype(d_kernel.dtype),
            None,
        )


_head.defvjp(_head_fwd, _head_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_head(hidden, head_kernel, weights, targets, num_chunks):
    """Value only: ``(sum of weight x nll, the per-row nll [n, seq])``,
    one matmul a chunk, no gradient formed."""
    n = hidden.shape[0]
    with device_scope("loss_head"):
        kernel = head_kernel.astype(hidden.dtype)
        h_chunks, t_chunks, w_chunks = (
            _split(a, num_chunks) for a in (hidden, targets, weights)
        )

        def body(total, xs):
            h_chunk, t_chunk, w_chunk = xs
            nll, *_ = _chunk_nll(h_chunk, kernel, t_chunk, False)
            return total + (w_chunk * nll).sum(), nll

        total, nll = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            (h_chunks, t_chunks, w_chunks),
        )
        return total, _join(nll, n)


def _weighted_head_fwd(hidden, head_kernel, weights, targets, num_chunks):
    """The two outputs, and both gradients of the weighted sum (for a
    cotangent of 1) formed where each chunk's logits already are:
    :func:`_head_fwd` with ``d_logits`` scaled row by row."""
    n = hidden.shape[0]
    with device_scope("loss_head"):
        kernel = head_kernel.astype(hidden.dtype)
        h_chunks, t_chunks, w_chunks = (
            _split(a, num_chunks) for a in (hidden, targets, weights)
        )

        def body(carry, xs):
            total, d_kernel = carry
            h_chunk, t_chunk, w_chunk = xs
            nll, exp, norm, hit = _chunk_nll(
                h_chunk, kernel, t_chunk, False
            )
            # (written once, as in _head_fwd and for its reason)
            d_logits = jax.lax.optimization_barrier((
                (exp / norm - hit.astype(jnp.float32))
                * w_chunk[:, None]
            ).astype(hidden.dtype))
            d_chunk = jnp.einsum("tv,hv->th", d_logits, kernel)
            d_kernel = (
                d_kernel.astype(jnp.float32) + jnp.einsum(
                    "th,tv->hv", h_chunk, d_logits,
                    preferred_element_type=jnp.float32,
                )
            ).astype(d_kernel.dtype)
            return (total + (w_chunk * nll).sum(), d_kernel), (
                d_chunk, nll
            )

        (total, d_kernel), (d_chunks, nll) = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros_like(head_kernel)),
            (h_chunks, t_chunks, w_chunks),
        )
        nll = _join(nll, n)
        return (total, nll), (_join(d_chunks, n), d_kernel, nll)


def _weighted_head_bwd(num_chunks, residuals, cts):
    """Scales what the forward rule left by the weighted sum's
    cotangent.  The per-row nll's own cotangent is NOT carried back to
    ``hidden`` and the kernel (that would take the logits again):
    :func:`weighted_chunked_cross_entropy` hands it out under
    ``stop_gradient``."""
    d_hidden, d_kernel, nll = residuals
    ct, _ = cts
    with device_scope("loss_head"):
        return (
            d_hidden * ct.astype(d_hidden.dtype),
            d_kernel * ct.astype(d_kernel.dtype),
            nll * ct,
            None,
        )


_weighted_head.defvjp(_weighted_head_fwd, _weighted_head_bwd)


def chunked_cross_entropy(
    hidden: jax.Array,        # [batch, seq, hid]
    head_kernel: jax.Array,   # [hid, vocab] (or [vocab, hid] tied)
    targets: jax.Array,       # [batch, seq] int
    num_chunks: int = 8,
    transpose: bool = False,
) -> jax.Array:
    """Mean next-token CE without materializing full logits.

    ``transpose=True`` treats ``head_kernel`` as ``[vocab, hid]``
    (a tied embedding table).  ``seq`` must be divisible by
    ``num_chunks`` (callers pick a divisor; 1 degrades to the
    unchunked loss).

    Every operation of the projection and the cross entropy, in the
    value, in the forward rule that forms the gradients and in the
    backward rule that scales them, carries the device scope
    ``loss_head``, as the unchunked head in models/gpt.py does.

    Four of the benchmark's cells call this form, so the program it
    lowers to is pinned: ``tests/test_ouro.py`` holds the hash of its
    lowered value-and-gradient, and a change here that moves it has to
    be measured in those cells.
    """
    s = hidden.shape[1]
    if s % num_chunks:
        raise ValueError(
            f"seq {s} not divisible by num_chunks {num_chunks}"
        )
    return _head(hidden, head_kernel, targets, num_chunks, transpose)


def weighted_chunked_cross_entropy(
    hidden: jax.Array,        # [n, seq, hid]
    head_kernel: jax.Array,   # [hid, vocab]
    targets: jax.Array,       # [n, seq] int
    weights: jax.Array,       # [n, seq] float32
    num_chunks: int = 8,
):
    """``(sum over rows of weight x nll, nll [n, seq])`` through one
    chunked head, the full logits never materialized.

    The first output is differentiable in ``hidden``, the kernel and
    ``weights`` (whose gradient is the per-row nll); the second is the
    per-row cross entropy as a VALUE (``stop_gradient``), for that
    gradient's sake and for a caller's counters.  A caller that wants a
    mean puts its ``1 / rows`` into the weights: with every weight
    ``1 / (n x seq)`` the first output is
    :func:`chunked_cross_entropy`'s.
    """
    s = hidden.shape[1]
    if s % num_chunks:
        raise ValueError(
            f"seq {s} not divisible by num_chunks {num_chunks}"
        )
    total, nll = _weighted_head(
        hidden, head_kernel, weights.astype(jnp.float32), targets,
        num_chunks,
    )
    return total, jax.lax.stop_gradient(nll)


def chunked_loss_fn(
    model,
    batch_x_key: str = "x",
    batch_y_key: str = "y",
    num_chunks: int = 8,
    head_param: Optional[str] = None,
):
    """Build an ``auto_accelerate``-compatible loss for a model with a
    ``return_hidden`` forward flag (GPT, Llama).

    Resolves the head weights from the params: ``lm_head/kernel`` when
    present, else the tied ``wte/embedding`` table.
    """

    def loss_fn(params, batch, model=model):
        import inspect

        call_params = inspect.signature(
            type(model).__call__
        ).parameters
        if "return_hidden" not in call_params:
            # e.g. the stage-stacked pipelined models injected by
            # auto_accelerate when pipeline > 1: no hidden-state hook
            # and a different param layout
            raise ValueError(
                f"{type(model).__name__} has no return_hidden "
                "forward flag; the chunked loss is incompatible "
                "with pipelined models — use the full "
                "cross_entropy_loss there"
            )
        hidden = model.apply(
            {"params": params}, batch[batch_x_key],
            return_hidden=True,
        )
        name = head_param
        if name is None:
            name = "lm_head" if "lm_head" in params else "wte"
        if name == "wte":
            kernel, transpose = params["wte"]["embedding"], True
        else:
            kernel, transpose = params[name]["kernel"], False
        return chunked_cross_entropy(
            hidden, kernel, batch[batch_y_key],
            num_chunks=num_chunks, transpose=transpose,
        )

    return loss_fn
