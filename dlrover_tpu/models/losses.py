"""Memory-efficient losses: sequence-chunked fused lm_head + CE.

The classic long-context memory cliff is the logits tensor: a 32k-vocab
Llama at batch 8 x seq 4096 materializes ``[8, 4096, 32000]`` fp32
logits (~4.2 GB) plus the same again for the softmax backward — often
larger than the whole transformer's activations.  (Reference frame:
ATorch's pipeline/remat memory work targets activations; the vocab
axis is the TPU-side analog worth the same treatment.)

TPU-native fix: never build the full logits.  ``chunked_cross_entropy``
scans over sequence chunks; each step projects one chunk through the
head and reduces it to a scalar NLL.  Under ``jax.grad`` the same
step also forms the chunk's two gradients while its float32 logits
exist (``d_hidden = d_logits @ W^T``, ``d_W += h^T @ d_logits``), so
no logits are stored and none are made a second time: three
vocabulary-sized matmuls a chunk, the number the algorithm requires,
and the backward pass only scales what the forward pass left.  Peak
logits memory drops from ``O(S * V)`` to ``O(S/num_chunks * V)``; the
residuals are the two gradients themselves (``hidden``'s and the
kernel's shape and dtype).

Works with both head layouts in this repo: Llama's untied ``lm_head``
kernel and GPT's tied ``wte`` embedding (pass ``transpose=True``).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _chunks(hidden, targets, num_chunks):
    """Scan axis leading, a chunk's rows of every batch entry as one
    axis: ``[num_chunks, batch * chunk, hid]``, so each of the head's
    matmuls is one plain 2-D product."""
    b, s, h = hidden.shape
    c = s // num_chunks
    return (
        hidden.reshape(b, num_chunks, c, h).transpose(1, 0, 2, 3)
        .reshape(num_chunks, b * c, h),
        targets.reshape(b, num_chunks, c).transpose(1, 0, 2)
        .reshape(num_chunks, b * c),
    )


def _chunk_nll(h_chunk, kernel, t_chunk, transpose):
    """One chunk's float32 logits (a product in the activation dtype,
    accumulated in float32), their log-sum-exp pieces, and the chunk's
    summed NLL."""
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
    nll = (jnp.log(norm[:, 0]) + top[:, 0] - picked).sum()
    return nll, exp, norm, hit


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head(hidden, head_kernel, targets, num_chunks, transpose):
    """Value only: one matmul a chunk, no gradient formed."""
    b, s, _ = hidden.shape
    with jax.named_scope("loss_head"):
        kernel = head_kernel.astype(hidden.dtype)

        def body(total, xs):
            h_chunk, t_chunk = xs
            nll, *_ = _chunk_nll(h_chunk, kernel, t_chunk, transpose)
            return total + nll, None

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
    with jax.named_scope("loss_head"):
        # head matmuls in the activation dtype (bf16 on TPU) like the
        # models' own head paths; only the softmax is float32
        kernel = head_kernel.astype(hidden.dtype)

        def body(carry, xs):
            total, d_kernel = carry
            h_chunk, t_chunk = xs
            nll, exp, norm, hit = _chunk_nll(
                h_chunk, kernel, t_chunk, transpose
            )
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
    with jax.named_scope("loss_head"):
        return (
            d_hidden * ct.astype(d_hidden.dtype),
            d_kernel * ct.astype(d_kernel.dtype),
            None,
        )


_head.defvjp(_head_fwd, _head_bwd)


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
    """
    s = hidden.shape[1]
    if s % num_chunks:
        raise ValueError(
            f"seq {s} not divisible by num_chunks {num_chunks}"
        )
    return _head(hidden, head_kernel, targets, num_chunks, transpose)


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
