"""Memory-efficient losses: sequence-chunked fused lm_head + CE.

The classic long-context memory cliff is the logits tensor: a 32k-vocab
Llama at batch 8 x seq 4096 materializes ``[8, 4096, 32000]`` fp32
logits (~4.2 GB) plus the same again for the softmax backward — often
larger than the whole transformer's activations.  (Reference frame:
ATorch's pipeline/remat memory work targets activations; the vocab
axis is the TPU-side analog worth the same treatment.)

TPU-native fix: never build the full logits.  ``chunked_cross_entropy``
scans over sequence chunks; each step projects one chunk through the
head and reduces it to a scalar NLL under ``jax.checkpoint``, so the
backward recomputes that chunk's logits instead of storing them.  Peak
logits memory drops from ``O(S * V)`` to ``O(S/num_chunks * V)`` for
~one extra head matmul per chunk in the backward (MXU-cheap,
HBM-bound win).

Works with both head layouts in this repo: Llama's untied ``lm_head``
kernel and GPT's tied ``wte`` embedding (pass ``transpose=True``).
"""

from typing import Optional

import jax
import jax.numpy as jnp


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
    """
    b, s, h = hidden.shape
    if s % num_chunks:
        raise ValueError(
            f"seq {s} not divisible by num_chunks {num_chunks}"
        )
    c = s // num_chunks
    spec = "bch,vh->bcv" if transpose else "bch,hv->bcv"

    # head matmul in the activation dtype (bf16 on TPU) like the
    # models' own head paths; only the log_softmax reduction is fp32
    compute_dtype = hidden.dtype

    @jax.checkpoint
    def chunk_nll(h_chunk, t_chunk):
        logits = jnp.einsum(
            spec, h_chunk, head_kernel.astype(compute_dtype)
        ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, t_chunk[..., None], axis=-1
        ).sum()

    def body(acc, xs):
        h_chunk, t_chunk = xs
        return acc + chunk_nll(h_chunk, t_chunk), None

    # device scope "loss_head", as the unchunked head in models/gpt.py:
    # every operation of the projection and the cross entropy carries
    # it, forward, backward and recomputed
    with jax.named_scope("loss_head"):
        # scan axis leading: [num_chunks, batch, chunk, hid]
        hc = hidden.reshape(b, num_chunks, c, h).transpose(1, 0, 2, 3)
        tc = targets.reshape(b, num_chunks, c).transpose(1, 0, 2)
        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32), (hc, tc)
        )
        return total / (b * s)


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
