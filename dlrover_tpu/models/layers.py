"""What the decoder families share: the norm, rope (also by a layer
kind's :class:`RopeRule`, rotating part of a head), the dense helper,
the SwiGLU and its PolyNorm form, the residual wrapper of a block
whose token carries several streams (:class:`StreamCoefficients`,
:func:`read_streams`, :func:`write_streams`), the causal depthwise
convolution of the recurrent mixers, the one call that maps
``attention_impl`` to a function, and the remat rule.  A family file
imports this module, ``losses``, ``ops`` and ``parallel``, and no
sibling; nothing here knows a family
(layers take widths and dtypes, never a config object).

Two SwiGLUs stay apart because their parameter names are a
checkpoint's format: ``llama.py::LlamaMLP`` (``gate`` / ``up`` /
``down``) and the shared expert of ``parallel/moe.py::DroplessMoE``
(three kernels among the expert layer's own parameters).
"""

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.ops.attention import (
    xla_causal_attention,
    xla_window_attention,
)
from dlrover_tpu.ops import flash_attention as fa
from dlrover_tpu.ops import gated_delta_rule, kda, selective_scan, ssd
from dlrover_tpu.ops.flash_attention import (
    RESIDUAL_NAMES,
    SINK_SCOPE,
    flash_attention,
)
from dlrover_tpu.parallel.moe import polynorm_glu, polynorm_params
from dlrover_tpu.parallel.mesh import (
    get_activation_constraint_mesh,
    get_global_mesh,
)
from dlrover_tpu.parallel.sequence import (
    ring_attention,
    shard_local_attention,
    ulysses_attention,
)
from dlrover_tpu.telemetry.tracing import device_scope

REMAT_POLICIES = ("full", "offload")


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x32 = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale).astype(x.dtype)


def dense(features, name, dtype, param_dtype, init_std):
    return nn.Dense(
        features, use_bias=False, dtype=dtype, param_dtype=param_dtype,
        kernel_init=nn.initializers.normal(init_std), name=name,
    )


class SwiGLU(nn.Module):
    mlp_dim: int
    hidden_dim: int
    dtype: Any
    param_dtype: Any
    init_std: float

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        proj = partial(
            dense, dtype=self.dtype, param_dtype=self.param_dtype,
            init_std=self.init_std,
        )
        gate = proj(self.mlp_dim, "gate_proj")(x)
        up = proj(self.mlp_dim, "up_proj")(x)
        return proj(self.hidden_dim, "down_proj")(nn.silu(gate) * up)


class PolyNormGLU(nn.Module):
    """:class:`SwiGLU` with PolyNorm (arXiv:2411.03884) in silu's
    place: ``down(P(gate(x)) * up(x))``, ``P``
    ``ops/grouped_matmul.py::poly_norm`` over the module's whole width
    with ONE learned ``polynorm_w [3]`` (1/3 each) and ``polynorm_b
    []`` (0), float32 (``parallel/moe.py::polynorm_params``)."""

    mlp_dim: int
    hidden_dim: int
    dtype: Any
    param_dtype: Any
    init_std: float
    output_scale: float = 0.5
    bias_clamp: float = 0.5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        proj = partial(
            dense, dtype=self.dtype, param_dtype=self.param_dtype,
            init_std=self.init_std,
        )
        coeffs = polynorm_params(
            self, "", self.output_scale, self.bias_clamp
        )
        hidden = polynorm_glu(
            proj(self.mlp_dim, "gate_proj")(x),
            proj(self.mlp_dim, "up_proj")(x), coeffs,
        )
        return proj(self.hidden_dim, "down_proj")(hidden)


# -- a token of several residual streams --------------------------------------
#
# Manifold-constrained hyper-connections (arXiv:2512.24880 over
# arXiv:2409.19606): a token's state is ``X [n, C]``, kept here as
# ``[b, s, n * C]`` (stream ``i`` in lanes ``[i C, (i + 1) C)``: a
# stream axis of 4 before the lanes would pad every tile four times).
# Round ONE sub-layer ``F``: ``u = H_pre X``, ``y = F(norm(u))``, ``X'
# = H_res X + H_post^T y``, the three mixes functions of the token's
# own ``n C`` numbers, ``H_res`` doubly stochastic by Sinkhorn's
# iterations.  The mixing is memory traffic and no matmul: a read of
# ``X`` for ``u``, a read and a write for ``X'``.


class StreamCoefficients(nn.Module):
    """``(H_pre [n, b, s], H_post [n, b, s], H_res [n, n, b, s],
    err)`` of ``x [b, s, n * C]``, float32, the token axes LAST (an
    ``[.., n, n]`` array would be 16 lanes of 128 wide)::

        [p | q | r] = RMSNorm(x) Phi        (one norm of all n C numbers,
                                             no learned scale)
        H_pre  = sigmoid(alpha_pre p + b_pre)
        H_post = 2 sigmoid(alpha_post q + b_post)
        M_0    = exp(alpha_res mat(r) + b_res); ``iters`` times: rows
                 divided by their sums, then columns; H_res = M_iters

    The norm's factor is a scalar a token, so it multiplies the
    product (bf16 operands, float32 sums) and ``x`` is read once.
    ``err`` is the worst ``|row or column sum - 1|`` of ``H_res`` (no
    gradient).  Parameters: ``phi [n C, 2 n + n^2]``, ``alpha [3]``
    (float32, 0.01 each: the mixes start near their biases) and
    ``bias [2 n + n^2]`` (float32, zeros: ``H_pre`` 1/2, ``H_post``
    1, ``H_res`` uniform ``1 / n``).  Device scopes ``mhc_coeff`` and
    ``mhc_sinkhorn``."""

    streams: int
    iters: int
    eps: float
    dtype: Any
    param_dtype: Any
    init_std: float
    alpha_init: float = 0.01

    @nn.compact
    def __call__(self, x: jax.Array):
        n = self.streams
        b, s, _ = x.shape
        phi = self.param(
            "phi", nn.initializers.normal(self.init_std),
            (x.shape[-1], 2 * n + n * n), self.param_dtype,
        )
        alpha = self.param(
            "alpha", nn.initializers.constant(self.alpha_init), (3,),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (2 * n + n * n,), jnp.float32
        )
        with device_scope("mhc_coeff"):
            x32 = x.astype(jnp.float32)
            inv_rms = jax.lax.rsqrt(
                jnp.mean(x32 * x32, axis=-1) + self.eps
            )
            raw = jnp.moveaxis(jnp.einsum(
                "bsk,kc->bsc", x.astype(self.dtype), phi.astype(self.dtype),
                preferred_element_type=jnp.float32,
            ), -1, 0) * inv_rms
            # alpha_pre | alpha_post | alpha_res, a column of phi each
            scale = alpha[np.repeat(np.arange(3), (n, n, n * n))]
            raw = raw * scale[:, None, None] + bias[:, None, None]
            h_pre = jax.nn.sigmoid(raw[:n])
            h_post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
        with device_scope("mhc_sinkhorn"):
            m = jnp.exp(raw[2 * n:]).reshape(n, n, b, s)
            for _ in range(self.iters):
                m = m / m.sum(axis=1, keepdims=True)   # rows
                m = m / m.sum(axis=0, keepdims=True)   # columns
            done = jax.lax.stop_gradient(m)
            err = jnp.maximum(
                jnp.max(jnp.abs(done.sum(axis=1) - 1.0)),
                jnp.max(jnp.abs(done.sum(axis=0) - 1.0)),
            )
        return h_pre, h_post, m, err


def _streams(x, n):
    # (split first: each consumer converts the lanes it reads, and no
    # float32 copy of all n streams is asked for)
    return [s.astype(jnp.float32) for s in jnp.split(x, n, axis=-1)]


def _dots(a, streams):
    """``[<a, stream>]`` over the lanes, float32 ``[len, b, s]``."""
    return jnp.stack([jnp.sum(a * stream, axis=-1) for stream in streams])


def sum_streams(x: jax.Array, n: int) -> jax.Array:
    """The ``n`` streams of ``x [b, s, n * C]`` added up in float32:
    ``[b, s, C]`` in ``x``'s type."""
    return sum(_streams(x, n)).astype(x.dtype)


@jax.custom_vjp
def read_streams(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """``u = H_pre X``: ``[b, s, C]`` from ``x [b, s, n * C]`` and
    ``h_pre [n, b, s]`` float32, summed in float32, ``x``'s type out.
    The gradient is written out (``dX_i = H_pre_i du``, ``dH_pre_i =
    <du, X_i>``) so that each pass is one read of what it needs and
    its results leave in ``x``'s type: left to autodiff the streams'
    cotangents stand in float32, 0.5 GB each at 8192 x 16384.  Device
    scope ``mhc_mix``, both ways."""
    with device_scope("mhc_mix"):
        return sum(
            h[..., None] * stream
            for h, stream in zip(h_pre, _streams(x, h_pre.shape[0]))
        ).astype(x.dtype)


def _read_fwd(x, h_pre):
    return read_streams(x, h_pre), (x, h_pre)


def _read_bwd(residuals, du):
    x, h_pre = residuals
    with device_scope("mhc_mix"):
        du = du.astype(jnp.float32)
        d_x = jnp.concatenate(
            [(h[..., None] * du).astype(x.dtype) for h in h_pre], axis=-1
        )
        return d_x, _dots(du, _streams(x, h_pre.shape[0]))


read_streams.defvjp(_read_fwd, _read_bwd)


@jax.custom_vjp
def write_streams(x, y, h_post, h_res) -> jax.Array:
    """``X' = H_res X + H_post^T y``: ``[b, s, n * C]`` from the
    streams ``x``, the sub-layer's output ``y [b, s, C]``, ``h_post
    [n, b, s]`` and ``h_res [n, n, b, s]`` (float32); float32 inside,
    ``x``'s type out.  The gradient is written out as
    :func:`read_streams`': ``dX_j = sum_i H_res_ij dX'_i``, ``dy =
    sum_i H_post_i dX'_i``, ``dH_res_ij = <dX'_i, X_j>``, ``dH_post_i
    = <dX'_i, y>``.  Device scope ``mhc_mix``, both ways."""
    with device_scope("mhc_mix"):
        streams = _streams(x, h_post.shape[0])
        y32 = y.astype(jnp.float32)
        return jnp.concatenate([
            (
                sum(h[..., None] * stream for h, stream in zip(row, streams))
                + post[..., None] * y32
            ).astype(x.dtype)
            for row, post in zip(h_res, h_post)
        ], axis=-1)


def _write_fwd(x, y, h_post, h_res):
    return write_streams(x, y, h_post, h_res), (x, y, h_post, h_res)


def _write_bwd(residuals, d_out):
    x, y, h_post, h_res = residuals
    n = h_post.shape[0]
    with device_scope("mhc_mix"):
        d_streams = _streams(d_out, n)
        streams = _streams(x, n)
        d_x = jnp.concatenate([
            sum(
                h_res[i, j][..., None] * d_streams[i] for i in range(n)
            ).astype(x.dtype) for j in range(n)
        ], axis=-1)
        d_y = sum(
            post[..., None] * d for post, d in zip(h_post, d_streams)
        ).astype(y.dtype)
        d_post = _dots(y.astype(jnp.float32), d_streams)
        d_res = jnp.stack([_dots(d, streams) for d in d_streams])
        return d_x, d_y, d_post, d_res


write_streams.defvjp(_write_fwd, _write_bwd)


def conv_init(key, shape, dtype):
    """torch ``Conv1d``'s default for a depthwise kernel of ``taps``:
    uniform in ``+-taps^-1/2``."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def rotate_half(x, cos, sin):
    """``x [b, s, heads, rope]``, half-split pairs, float32 inside."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding on [b, s, h, d]."""
    d = x.shape[-1]
    freqs = 1.0 / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    return rotate_half(x, cos, sin)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(
    dim: int, theta: float, original_len: int, beta_fast: float,
    beta_slow: float,
) -> Tuple[int, int]:
    """``(low, high)``: the rope pairs (of ``dim`` rotated lanes)
    between which the frequencies blend from extrapolated to
    interpolated."""

    def pair_of(rotations):
        return dim * math.log(
            original_len / (rotations * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = math.floor(pair_of(beta_fast))
    high = math.ceil(pair_of(beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_len: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """yarn (``deepseek_yarn``, HF ``_compute_yarn_parameters``): pair
    ``i`` keeps ``theta^(-2i/dim)`` below ``low``, takes it over
    ``factor`` above ``high``, a linear blend between.  A constant of
    the configuration, worked in float64 (at position 8191 a float32
    rounding of the frequency is 5e-4 rad)."""
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low, high = yarn_correction_range(
        dim, theta, original_len, beta_fast, beta_slow
    )
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return freq / factor * ramp + freq * (1.0 - ramp)


@dataclass(frozen=True)
class RopeRule:
    """One layer kind's rotary rule (HF ``rope_parameters[kind]``),
    for the families whose rope comes from the layer's kind.
    ``factor`` 1 is the default rule; above it yarn's."""

    theta: float = 10000.0            # rope_theta
    rotated: float = 1.0              # partial_rotary_factor
    factor: float = 1.0               # factor (yarn)
    original_len: int = 8192          # original_max_position_embeddings
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0     # scales cos and sin

    def inv_freq(self, head_dim: int) -> np.ndarray:
        dim = int(head_dim * self.rotated)
        if self.factor <= 1:
            return self.theta ** (
                -np.arange(0, dim, 2, dtype=np.float64) / dim
            )
        return yarn_inv_freq(
            dim, self.theta, self.factor, self.original_len,
            self.beta_fast, self.beta_slow,
        )

    def tables(self, seq: int, head_dim: int):
        """``(cos, sin)`` ``[1, seq, 1, rotated lanes / 2]`` float32,
        both times ``attention_factor``."""
        angles = (
            jnp.arange(seq, dtype=jnp.float32)[:, None]
            * jnp.asarray(self.inv_freq(head_dim), jnp.float32)[None, :]
        )
        m = self.attention_factor
        return (
            (jnp.cos(angles) * m)[None, :, None, :],
            (jnp.sin(angles) * m)[None, :, None, :],
        )


def rotate_partial(x, cos, sin):
    """``x [b, s, heads, d]``: the first ``2 x cos.shape[-1]`` lanes of
    every head rotate (:func:`rotate_half`), the rest pass through."""
    rotated = 2 * cos.shape[-1]
    if rotated == x.shape[-1]:
        return rotate_half(x, cos, sin)
    return jnp.concatenate([
        rotate_half(x[..., :rotated], cos, sin), x[..., rotated:],
    ], axis=-1)


def init_params(model, rng, batch_size: int = 2, seq_len: int = 0):
    """A decoder's ``params`` tree, initialised on a batch of zeros."""
    seq_len = seq_len or min(model.config.max_seq_len, 128)
    tokens = jnp.zeros((batch_size, seq_len), dtype=jnp.int32)
    return model.init(rng, tokens)["params"]


def _flash(q, k, v, **kw):
    """The Pallas kernel, per shard of the mesh its train step was
    built for (batch over the data axes, heads over ``tensor``): GSPMD
    cannot split the kernel itself."""
    # the mesh the enclosing train step was built for (scoped around
    # its trace by accelerate / make_train_step); in a manual region
    # (ulysses, pipeline) q/k/v are one shard's already
    mesh = get_activation_constraint_mesh()
    if (
        mesh is None or mesh.size == 1
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return flash_attention(q, k, v, **kw)
    return shard_local_attention(flash_attention, q, k, v, mesh, **kw)


def _xla(q, k, v, *, scale, window, dtype, **sinked):
    """The plain forms: the grouped one, its mask written out, for a
    window, a sink or fewer kv heads than query heads, else the causal
    one."""
    if window is None and k.shape[2] == q.shape[2] and not sinked:
        return xla_causal_attention(q, k, v, dtype=dtype, scale=scale)
    if scale is not None:
        raise ValueError("no scale in the plain grouped or windowed form")
    return xla_window_attention(q, k, v, window, dtype, **sinked)


def _with_sink(impl, q, k, v, sink, kw):
    """``(out, sink mass [heads])`` through the two forms that take a
    sink; the mass's reduction goes under the sink's device scope."""
    mesh = get_activation_constraint_mesh()
    if impl not in ("flash", "xla") or (
        impl == "flash" and mesh is not None and mesh.size > 1
    ):
        raise ValueError(
            f"no attention sink through {impl!r} over a mesh: xla, or "
            "flash on one device"
        )
    form = flash_attention if impl == "flash" else _xla
    out, lse = form(q, k, v, sink=sink, return_lse=True, **kw)
    with device_scope(SINK_SCOPE):
        mass = jnp.mean(
            jnp.exp(jax.lax.stop_gradient(
                sink.astype(jnp.float32)
            )[None, :, None] - lse),
            axis=(0, 2),
        )
    return out, mass


def attention(
    impl: str, q: jax.Array, k: jax.Array, v: jax.Array, *,
    scale: Optional[float] = None, window: Optional[int] = None,
    dtype: Any = None, sink: Optional[jax.Array] = None,
):
    """Causal attention through ``impl``: xla | flash | ring | ulysses
    | ulysses_flash, the only place that maps the name to a function.

    ``[b, s, heads, d]`` queries over ``[b, s, kv heads, d]`` keys and
    values (``v``'s head size may differ); ``scale`` defaults to
    ``d ** -0.5``; with ``window`` query ``i`` sees keys ``(i - window,
    i]``; ``dtype`` (default ``v``'s) is the output's.  ring/ulysses
    run over the global mesh's ``sequence`` axis (registered by
    auto_accelerate); activations must be sequence-sharded by the
    batch placement.

    ``sink`` (``[heads]`` float32; xla | flash on one device): a
    learned score a head that joins every row's softmax denominator
    and nothing else (``ops/flash_attention.py``).  The call then
    returns ``(out, sink mass)``: the share of the softmax the sink
    takes, ``exp(sink - lse)``, mean over batch and rows, ``[heads]``
    float32 with no gradient, for the caller's counter.
    """
    dtype = v.dtype if dtype is None else dtype
    kw = dict(scale=scale, window=window, dtype=dtype)
    if sink is not None:
        return _with_sink(impl, q, k, v, sink, kw)
    if impl == "flash":
        return _flash(q, k, v, **kw)
    if impl == "xla":
        return _xla(q, k, v, **kw)
    if k.shape[2] != q.shape[2]:
        # only flash and the grouped plain form read each kv head once
        # per group; the others need the materialized repeat
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if impl == "ring" and window is None:
        return ring_attention(
            q, k, v, get_global_mesh(), causal=True, scale=scale
        ).astype(dtype)
    if impl in ("ulysses", "ulysses_flash"):
        inner = _flash if impl == "ulysses_flash" else _xla
        return ulysses_attention(inner, q, k, v, get_global_mesh(), **kw)
    raise ValueError(f"no attention through {impl!r} (window {window})")


def window_tiles_share(seq: int, window: int, itemsize: int = 2) -> float:
    """Sub-blocks the flash kernels walk for a window over those a
    causal walk of the same tiles would (``block_schedule``, at the
    tiles a call that names none takes): what the window saves of the
    walk, a constant of the shapes."""
    block = fa._fit_block(seq, fa.default_blocks(seq, itemsize)[0])
    walked = fa.block_schedule(seq, block, block, window=window)
    return walked["visited"] / fa.block_schedule(seq, block, block)[
        "visited"
    ]


def remat_policy(name: str):
    """What a rematted block keeps, for every decoder family: its
    input (``jax.checkpoint``'s own: ``[b, s, h]``, or ``[b, s, n h]``
    where a token carries ``n`` residual streams, so ``n`` times the
    bytes a block boundary) and the
    five arrays its flash kernel's backward kernels read (``q``, ``k``
    and ``v`` as the kernel took them, ``out`` and ``lse`` as it wrote
    them: all in HBM for the forward already), by the names the
    kernel's forward rule gives them
    (``ops/flash_attention.py::RESIDUAL_NAMES``), and what a
    recurrent rule's forward kernel wrote that anything reads after
    it (``RESIDUAL_NAMES`` of ``ops/gated_delta_rule.py``,
    ``ops/kda.py``, ``ops/ssd.py`` and ``ops/selective_scan.py``:
    ``o`` | ``y``, the final
    state, the chunk-start states and, of the two delta rules, ``T``;
    their operands are NOT kept: gradients of their own read what
    produces them), and nothing else: the
    backward runs neither a forward kernel again nor the
    projections, RoPE and layouts that only feed it.  A name occurs
    only in a program that calls its kernel: with XLA attention and
    no recurrent rule none does and everything is recomputed.
    "offload" keeps nothing on the device, the kernel's five arrays
    neither (a layer's ``out`` alone is as many bytes as the
    ``block_in`` it moves off the device), and parks the per-block
    residual checkpoints that ``gpt.py`` and ``llama.py`` name in
    pinned_host between forward and backward (selective offloading
    checkpoint)."""
    if name in ("full", "", None):
        return jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES, *gated_delta_rule.RESIDUAL_NAMES,
            *kda.RESIDUAL_NAMES, *ssd.RESIDUAL_NAMES,
            *selective_scan.RESIDUAL_NAMES,
        )
    if name == "offload":
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["block_in"],
            offload_src="device",
            offload_dst="pinned_host",
        )
    raise ValueError(
        f"unknown remat_policy {name!r} ({' | '.join(REMAT_POLICIES)})"
    )


def rematted(block, prevent_cse: bool, policy: str = "full"):
    """``block`` (a module class) under ``jax.checkpoint`` with the one
    rule above.  Kept a block: its input, ``b x s x n x h`` values
    for ``n`` residual streams (1 in every family but ``motif``'s 4:
    268 MB a boundary at 8192 x 4096 in bf16 against 67), the
    flash kernel's five arrays, and a recurrent layer's results: 3 x
    67 MB of a KDA layer at 1 x 8192 x 32 x 128 (``o``, start states,
    ``T``), 67 + 134 MB of a state-space layer at 64 heads of 64 x
    128 (``y``, float32 start states), 94 + 71 + 63 MB of a gated
    delta layer at 30 heads of 96 | 192, 84 + 21 MB of a selective
    scan at 5120 channels x 16 lanes, and about 2 MB of float32
    final state each."""
    return nn.remat(
        block, prevent_cse=prevent_cse, policy=remat_policy(policy)
    )
