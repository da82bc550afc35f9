"""Pallas flash attention (forward + backward) for TPU.

The reference binds a prebuilt CUDA FMHA library
(``tfplus/tfplus/flash_attn/kernels/flash_attention_fwd_kernel.cc:29``,
ATorch's module swaps in ``atorch/modules/transformer/layers.py``);
the TPU rebuild implements the kernel itself in Pallas: online-softmax
tiling so the [seq, seq] score matrix never materializes in HBM, MXU
matmuls in bf16 with fp32 accumulators, causal block skipping.

Layout: q, k, v are [batch, seq, heads, head_dim] (the model's bqhd).
Internally folded to [batch*heads, seq, head_dim]; the grid walks
(batch*heads, q_block, k_block) with the k_block axis innermost so the
running max/denominator scratch carries across k steps.

On CPU (tests / virtual mesh) the kernel runs in interpreter mode.
"""

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

NEG_INF = -1e30
# v5e-measured fwd+bwd block sweep (bq x bk in {256,512,1024}^2, seq
# 1k/2k/4k, head_dim 64/128, constant token count): 1024x1024 wins or
# ties everywhere — e.g. seq 2048/d64: 10.6 ms vs 15.7 ms at the old
# 512x512 default (1.48x).  The table keeps the per-shape winners;
# unlisted shapes fall back to min(1024, seq).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_TUNED_BLOCKS = {
    # (seq, head_dim) -> (block_q, block_k)
    (1024, 64): (512, 1024),
    (2048, 64): (1024, 1024),
    (4096, 64): (1024, 1024),
    (1024, 128): (1024, 1024),
    (2048, 128): (1024, 1024),
    (4096, 128): (1024, 1024),
}


def tuned_blocks(seq: int, head_dim: int):
    """Measured-best (block_q, block_k) for this shape (v5e sweep);
    min(1024, seq) when unmeasured."""
    if (seq, head_dim) in _TUNED_BLOCKS:
        return _TUNED_BLOCKS[(seq, head_dim)]
    b = min(1024, seq)
    return b, b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref,      # [1, block_q, d], [1, block_k, d] x2
    o_ref,                    # [1, block_q, d]
    lse_ref,                  # [1, block_q]
    m_scr, l_scr, acc_scr,    # VMEM scratch
    *, scale: float, block_q: int, block_k: int, causal: bool,
):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: process only blocks with kv_start <= q_end
    run = True
    if causal:
        run = kv_idx * block_k <= q_idx * block_q + (block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        logits = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)

        m_prev = m_scr[:]
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        correction = jnp.exp(m_prev - m_new)
        l_new = l_prev * correction + jnp.sum(p, axis=1)
        acc_scr[:] = (
            acc_scr[:] * correction[:, None]
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(kv_idx == num_kv - 1)
    def _final():
        l = m_scr[:] * 0.0 + l_scr[:]  # keep shapes aligned
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / safe_l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:] + jnp.log(safe_l)


def _fwd(
    q, k, v, scale: float, causal: bool, block_q: int, block_k: int,
    group: int = 1,
):
    bh, seq, d = q.shape
    num_q = seq // block_q
    num_kv = seq // block_k
    grid = (bh, num_q, num_kv)

    # GQA: k/v carry bh//group rows; `group` consecutive q heads read
    # the same kv row through the index map — the repeated kv tensor
    # never materializes in HBM
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, i, j: (b // group, j, 0),
            ),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, i, j: (b // group, j, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # lse carried as [bh, 1, seq]: (1, 1, block_q) blocks satisfy
            # the TPU (8, 128) tiling rule on the last two dims
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q,), jnp.float32),
            _scratch((block_q,), jnp.float32),
            _scratch((block_q, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
    dq_scr,
    *, scale: float, block_q: int, block_k: int, causal: bool,
):
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)
    num_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = kv_idx * block_k <= q_idx * block_q + (block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        logits = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        p = jnp.exp(logits - lse[:, None])
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kv_idx == num_kv - 1)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale: float, block_q: int, block_k: int, causal: bool,
):
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(1)
    num_q = pl.num_programs(2)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        # q block must reach at least the kv block start
        run = q_idx * block_q + (block_q - 1) >= kv_idx * block_k

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        logits = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        p = jnp.exp(logits - lse[:, None])
        # dv += p^T @ do
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        # dk += ds^T @ q
        dk_scr[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(q_idx == num_q - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    scale, causal, block_q, block_k, group, residuals, dout
):
    q, k, v, out, lse = residuals
    bh, seq, d = q.shape
    delta = jnp.sum(
        out.astype(jnp.float32) * dout.astype(jnp.float32), axis=-1
    )[:, None, :]  # [bh, 1, seq] to match the lse tiling layout

    num_q = seq // block_q
    num_kv = seq // block_k

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal,
        ),
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, i, j: (b // group, j, 0),
            ),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, i, j: (b // group, j, 0),
            ),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda b, i, j: (b, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q,
            block_k=block_k, causal=causal,
        ),
        grid=(bh, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, j, i: (b // group, j, 0),
            ),
            pl.BlockSpec(
                (1, block_k, d),
                lambda b, j, i: (b // group, j, 0),
            ),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        scratch_shapes=[
            _scratch((block_k, d), jnp.float32),
            _scratch((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v, dout, lse, delta)
    if group > 1:
        # per-q-head kv grads -> per-kv-head (rows sharing a kv head
        # are the `group` consecutive q heads)
        dk = dk.reshape(bh // group, group, seq, d).astype(
            jnp.float32
        ).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh // group, group, seq, d).astype(
            jnp.float32
        ).sum(axis=1).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_mha(q, k, v, scale, causal, block_q, block_k, group=1):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, group)
    return out


def _flash_mha_fwd(q, k, v, scale, causal, block_q, block_k,
                   group=1):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, group)
    return out, (q, k, v, out, lse)


def _flash_mha_bwd(scale, causal, block_q, block_k, group,
                   residuals, dout):
    return _bwd(
        scale, causal, block_q, block_k, group, residuals, dout
    )


_flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


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
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    Drop-in for :func:`dlrover_tpu.models.gpt.xla_causal_attention`.
    Sequence length must be divisible by the block sizes (the caller
    pads; GPT training shapes are powers of two).

    GQA: ``k``/``v`` may carry fewer heads than ``q`` (``kv_heads``
    dividing ``heads``, kv-head-major q layout as in the Llama
    family); the forward and dq kernels read each kv head once per
    group through their index maps, so the repeated kv tensor never
    materializes there.  The dkv backward still emits per-q-head
    gradients (a transient group-x temporary) before the group
    reduction.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
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
    if block_q is None or block_k is None:
        tq, tk = tuned_blocks(s, d)
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
        hh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, d)

    out = _flash_mha(
        fold(q), fold(k), fold(v), scale, causal, block_q, block_k,
        group,
    )
    out = out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    if dtype is not None:
        out = out.astype(dtype)
    return out


# dispatch layers (LlamaAttention) key on this instead of the impl
# string: only the plain flash path accepts kv_heads < heads
# (ulysses all-to-alls heads across devices and needs the repeat)
flash_attention.gqa_aware = True
