"""Mixture-of-Experts layer with expert parallelism.

Reference: ``MOELayer``/``Experts``/``_AllToAll`` + top-1/2 gating
(``atorch/modules/moe/moe_layer.py:29,87,116,161``) and expert process
groups (``set_experts_process_group:29``).  The torch design routes
tokens with an explicit autograd all-to-all between expert process
groups; the TPU-native design is GShard-style *dense dispatch*: the
routing is an einsum against a [tokens, experts, capacity] one-hot
dispatch tensor, expert weights carry a leading expert dim sharded
over the ``expert`` mesh axis, and GSPMD lowers the dispatch einsums
to the all-to-all — no hand-written collective, and the whole layer
stays jit/remat/scan-compatible.

Gating: top-1 (Switch) and top-2 (GShard) with capacity dropping and
the standard load-balancing auxiliary loss.

Beside it, for a chip that holds every expert of its layers:
:func:`dropless_moe` / :class:`DroplessMoE` (OLMoE's recipe).  The
token-to-expert assignments are sorted by expert and the experts run
as grouped matmuls over the sorted rows: no capacity, no dropped
token, no ``[t, e, c]`` tensor.  A chip that holds only a range of a
layer's experts tells the layer so (``held``): it routes over all of
them, computes its own and moves only the rows that exist (the last
section of this file).  Across chips a dropless layer needs a
ragged all-to-all (ROADMAP R3), which is not here; the
``expert``-mesh path stays with :class:`MoEMLP`.
"""

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.ops import grouped_matmul as gmm
from dlrover_tpu.telemetry.tracing import device_scope


def top_k_gating(
    gate_logits: jax.Array,  # [tokens, experts] f32
    k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Build dispatch/combine tensors.

    Returns (dispatch [t, e, c] bool-ish f32, combine [t, e, c] f32,
    aux_loss scalar).  Tokens beyond an expert's capacity are dropped
    (their combine weight is zero), matching the reference's capacity
    behaviour.
    """
    t, e = gate_logits.shape
    gates = jax.nn.softmax(gate_logits, axis=-1)  # [t, e]

    # top-k expert ids per token
    _, expert_ids = jax.lax.top_k(gates, k)  # [t, k]

    dispatch = jnp.zeros((t, e, capacity), dtype=gates.dtype)
    combine = jnp.zeros((t, e, capacity), dtype=gates.dtype)
    aux_loss = jnp.zeros((), dtype=jnp.float32)

    # fraction of tokens routed to each expert (first choice) for the
    # load-balancing loss: e * mean(gates_e) * mean(routed_e)
    first_choice = jax.nn.one_hot(expert_ids[:, 0], e, dtype=gates.dtype)
    density = first_choice.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux_loss = (density * density_proxy).sum() * (e**2) / k

    # per-expert occupancy from earlier choices: a choice-c token's
    # queue position starts after every token the expert received in
    # choices 0..c-1, so slots never collide across choices (GShard's
    # ``locations2 += sum(mask1)``, ref ``moe_layer.py`` topk gating)
    prev_counts = jnp.zeros((e,), dtype=gates.dtype)
    for choice in range(k):
        ids = expert_ids[:, choice]  # [t]
        onehot = jax.nn.one_hot(ids, e, dtype=gates.dtype)  # [t, e]
        # position of each token in its expert's queue (sequence order)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + prev_counts) * onehot
        prev_counts = prev_counts + onehot.sum(axis=0)
        in_cap = (pos < capacity).astype(gates.dtype) * onehot
        pos_clamped = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
        cap_onehot = jax.nn.one_hot(
            pos_clamped, capacity, dtype=gates.dtype
        )  # [t, e, c]
        slot = in_cap[..., None] * cap_onehot
        dispatch = dispatch + slot
        gate_k = jnp.take_along_axis(
            gates, ids[:, None], axis=1
        )[:, 0]  # [t]
        combine = combine + slot * gate_k[:, None, None]

    if k > 1:
        # renormalize combine weights over selected experts
        denom = combine.sum(axis=(1, 2), keepdims=True)
        combine = combine / jnp.maximum(denom, 1e-9)
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Expert-parallel MLP block (drop-in for the dense MLP).

    Expert kernels are named ``experts/w_in`` / ``experts/w_out`` with
    a leading expert dim so :func:`dlrover_tpu.parallel.sharding
    .moe_rules` shards them over the ``expert`` axis.
    """

    num_experts: int
    hidden_dim: int
    mlp_dim: int
    top_k: int = 2
    capacity_factor: float = 1.25
    # gated experts (SwiGLU, Mixtral-style): w_gate/w_in project to
    # mlp_dim, experts compute silu(gate) * up -> w_out
    gated: bool = False
    # decode/serving mode: for single-token decode steps and chunks
    # <= 512 tokens, capacity >= tokens so nothing is dropped (the
    # trained capacity formula collapses to ~1 slot/expert there and
    # silently zeroes overflow); longer prefill chunks keep the
    # trained capacity factor
    no_drop: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, s, d = x.shape
        e = self.num_experts
        tokens = x.reshape(b * s, d)
        t = b * s
        capacity = max(
            1, int(self.top_k * t * self.capacity_factor / e)
        )
        if self.no_drop:
            # each token's top-k choices are distinct experts, so t
            # slots per expert always suffice — but [t, e, t]
            # dispatch tensors are quadratic in t, so the hard
            # guarantee is bounded: up to 2048 tokens for one-token
            # decode steps, 512 for prefill chunks.  Beyond that the
            # trained capacity factor applies (the same dropping the
            # weights saw in training).  Shapes are static under
            # trace, so the warning fires at compile time.
            bound = 2048 if s == 1 else 512
            if t > bound:
                logger.warning(
                    "no_drop MoE: %d tokens exceeds the bounded "
                    "no-drop guarantee (%d); trained capacity "
                    "factor applies and overflow tokens may drop",
                    t, bound,
                )
            capacity = max(capacity, min(t, bound))

        # router in fp32 for stable softmax/top-k
        gate_logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32,
            param_dtype=self.param_dtype, name="router",
        )(tokens.astype(jnp.float32))
        dispatch, combine, aux = top_k_gating(
            gate_logits, self.top_k, capacity
        )
        self.sow("intermediates", "moe_aux_loss", aux)

        # per-expert fan-in scaling: the leading expert dim is a batch
        # axis, not receptive field (plain lecun_normal would count it
        # into fan_in and under-scale init std by sqrt(e))
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal",
            in_axis=-2, out_axis=-1, batch_axis=0,
        )
        w_in = self.param(
            "experts_w_in",
            expert_init,
            (e, d, self.mlp_dim),
            self.param_dtype,
        )
        w_out = self.param(
            "experts_w_out",
            expert_init,
            (e, self.mlp_dim, d),
            self.param_dtype,
        )
        # dispatch: [t,e,c] x [t,d] -> [e,c,d]; GSPMD inserts the
        # all-to-all when e is sharded over the expert axis
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(self.dtype),
            tokens.astype(self.dtype),
        )
        h = jnp.einsum(
            "ecd,edh->ech", expert_in, w_in.astype(self.dtype)
        )
        if self.gated:
            w_gate = self.param(
                "experts_w_gate",
                expert_init,
                (e, d, self.mlp_dim),
                self.param_dtype,
            )
            gate_h = jnp.einsum(
                "ecd,edh->ech", expert_in,
                w_gate.astype(self.dtype),
            )
            h = nn.silu(gate_h) * h
        else:
            h = nn.gelu(h)
        expert_out = jnp.einsum(
            "ech,ehd->ecd", h, w_out.astype(self.dtype)
        )
        out = jnp.einsum(
            "tec,ecd->td", combine.astype(self.dtype), expert_out
        )
        return out.reshape(b, s, d)


def collect_moe_aux_loss(intermediates) -> jax.Array:
    """Sum all sown moe_aux_loss values from a mutable-apply call."""
    total = jnp.zeros((), jnp.float32)
    leaves = jax.tree_util.tree_leaves(intermediates)
    for leaf in leaves:
        total = total + jnp.asarray(leaf, jnp.float32).sum()
    return total


# -- dropless routing over grouped matmuls ------------------------------------


@jax.custom_vjp
def _dispatch_rows(tokens, source, slot):
    """The rows of ``tokens [t, d]`` in the experts' tile-aligned
    order, ``[padded rows, d]``: ``source[p]`` is the flat assignment
    (token * k + choice) that lives at padded row ``p``, or ``t * k``
    for a row of padding, which reads the zero row appended to the
    tokens; ``slot[t, k]`` is its inverse.  The gradient is a gather
    through ``slot`` and a sum over the k choices, not a scatter-add
    over unsorted rows."""
    zero_row = jnp.zeros((1, tokens.shape[1]), tokens.dtype)
    return jnp.concatenate([tokens, zero_row])[source // slot.shape[1]]


def _dispatch_fwd(tokens, source, slot):
    return _dispatch_rows(tokens, source, slot), slot


def _dispatch_bwd(slot, g):
    with device_scope("moe_dispatch"):
        return g[slot].sum(axis=1).astype(g.dtype), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _collect_rows(rows, source, slot):
    """``rows[slot]``: the experts' outputs back in token order, ``[t,
    k, d]``; the gradient is the gather through ``source`` (the
    scatter-add of the combine, read from the other side: every
    padded row holds at most one assignment).  A row of padding gets
    the cotangent of the last assignment instead of a zero (no
    masking pass over the rows): it meets only that row's own
    activations, which are zero because its input was, so no weight's
    gradient sees it."""
    return rows[slot]


def _collect_fwd(rows, source, slot):
    return rows[slot], source


def _collect_bwd(source, g):
    with device_scope("moe_combine"):
        flat = g.reshape((-1, g.shape[-1]))
        return flat.at[source].get(mode="clip"), None, None


_collect_rows.defvjp(_collect_fwd, _collect_bwd)


def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(nn.relu(x))


EXPERT_FORMS = ("swiglu", "relu2", "polynorm")


def polynorm_coeffs(weight, bias, scale: float, clamp: float):
    """``gmm.poly_norm``'s four scalars from a module's learned
    ``weight [3]`` (cubic, square, linear) and ``bias []``: ``scale x
    weight`` and the bias held to ``+-clamp``, float32."""
    return jnp.concatenate([
        scale * weight.astype(jnp.float32),
        jnp.clip(bias.astype(jnp.float32), -clamp, clamp).reshape(1),
    ])


def polynorm_params(module, prefix: str, scale: float, clamp: float):
    """:func:`polynorm_coeffs` of a flax module's own learned pair,
    created here: ``<prefix>polynorm_w [3]`` (1/3 each) and
    ``<prefix>polynorm_b []`` (0), float32."""
    return polynorm_coeffs(
        module.param(
            prefix + "polynorm_w", nn.initializers.constant(1.0 / 3.0),
            (3,), jnp.float32,
        ),
        module.param(
            prefix + "polynorm_b", nn.initializers.zeros, (), jnp.float32
        ),
        scale, clamp,
    )


def polynorm_glu(gate, up, coeffs):
    """``poly_norm(gate) * up`` outside the kernels (a dense
    feed-forward, a shared expert), ``[..., width]``: float32 inside,
    ``up``'s type out, under the device scope ``polynorm``."""
    with device_scope("polynorm"):
        return (
            gmm.poly_norm(gate.astype(jnp.float32), coeffs)
            * up.astype(jnp.float32)
        ).astype(up.dtype)


def dropless_moe(
    tokens: jax.Array,         # [t, d]
    router_kernel: jax.Array,  # [d, e]
    w_gate: Optional[jax.Array],  # [held experts, d, m]; None: ungated
    w_up: jax.Array,           # [held experts, d, m]
    w_down: jax.Array,         # [held experts, m, d]
    top_k: int,
    dtype: Any = jnp.bfloat16,
    *,
    held: Optional[Tuple[int, int]] = None,
    score: str = "softmax",
    select_bias: Optional[jax.Array] = None,  # [e], no gradient
    renormalise: bool = False,
    scale: float = 1.0,
    polynorm: Optional[jax.Array] = None,  # [4] float32
):
    """Top-k routing without capacity: ``(out [t, d], stats)``.

    The defaults are OLMoE's router: logits and softmax in float32,
    the top-k probabilities weight the experts' outputs as they are,
    NOT renormalised (``norm_topk_prob: false``), and the chip holds
    all ``e`` experts.  ``score="sigmoid"`` scores each expert on its
    own; with ``select_bias`` the k experts are chosen by ``score +
    bias`` and weighted by the score alone (the bias only steers the
    load and takes no gradient); ``renormalise`` divides the k weights
    by their sum, ``scale`` multiplies them.  An expert computes
    ``down(silu(gate(x)) * up(x))`` or, with ``w_gate=None``,
    ``down(relu(up(x)) ** 2)``: an expert that has no gate has no
    gate matrix; with ``polynorm`` (:func:`polynorm_coeffs`) the
    gate's activation is ``gmm.poly_norm`` in silu's place, each norm
    over the expert's own width.  Every form is one call,
    ``gmm.grouped_expert``: the matmuls AND the activation between
    them, in the kernels.

    **Held experts.**  ``held=(lo, count)`` says that this chip holds
    experts ``[lo, lo + count)`` of the layer's ``e`` (the weights are
    ``[count, ...]``).  The router keeps its ``e`` outputs and its
    top-k over all of them; only assignments to a held expert get a
    row and are computed; what the other experts would have added is
    LEFT OUT of ``out``: nothing here stands in for the absent chips
    or their exchange.  The weights (and a renormalisation) are over
    all k choices, held or not, so the shares of all the chips that
    hold a layer add up to the whole layer.

    The ``t * k`` assignments are stable-sorted by expert (those to
    experts held elsewhere behind the rest), the rows gathered in that
    order with each expert's rows starting on a row tile of the
    grouped-matmul kernel (``ops/grouped_matmul.py``), and each expert
    computes its own rows as grouped matmuls.  Every shape is static
    (``t * k`` rows and one tile of padding an expert held, whatever
    the routing: a batch may send every assignment here); an expert
    without a token is one tile
    of zero rows, and the kernels skip the tiles past the last used
    one: no product, no fetch, no store, so those rows of each
    matmul's result are NOT WRITTEN, forward or backward.  Nothing
    here reads them: the activation and its derivative run inside the
    kernels, over the used tiles, and the gathers back go through
    ``slot``, which names only rows of an expert (a reduction over
    the padded rows would: ``tests/test_sarvam_mla.py`` fills them
    with NaN).

    **What runs at which size.**  The router and the index work (the
    sort, ``slot``, ``source``: ``[t * k]`` and ``[padded rows]``
    int32) run at the static size.  With every expert held, so do the
    two row gathers and the weighting, over ``[t, k, d]``: nearly
    every tile has rows.  With a held range most tiles have none, and
    the rows move from the row side (below the layer in this file):
    dispatch and combine walk the ``tiles_used`` tiles that hold a
    row, forward and backward; the token side keeps ``[t, d]`` arrays
    and nothing of ``[t, k, d]`` is made.  Their ``[padded rows, d]``
    results are, as the kernels', NOT WRITTEN past ``tiles_used``.

    ``stats`` carries what the auxiliary losses and the counters
    need: ``counts [e]`` (assignments per expert over ALL experts, no
    gradient), ``held_rows`` (assignments that reached a held
    expert), ``prob_sum [e]`` (sum over tokens of the router's
    scores), ``z_loss`` (mean over tokens of ``logsumexp(logits) **
    2``) and, with ``held``, ``tiles_used`` of the layout's ``tiles``
    row tiles."""
    t, _ = tokens.shape
    e = router_kernel.shape[-1]
    lo, count = (0, e) if held is None else held
    if w_up.shape[0] != count:
        raise ValueError(
            f"{w_up.shape[0]} experts' weights for {count} held"
        )
    assignments = t * top_k
    with device_scope("moe_router"):
        logits = jnp.dot(
            tokens.astype(jnp.float32),
            router_kernel.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown router score {score!r}")
        if select_bias is None:
            gate, expert_ids = jax.lax.top_k(probs, top_k)  # [t, k]
        else:
            _, expert_ids = jax.lax.top_k(
                probs + jax.lax.stop_gradient(select_bias), top_k
            )
            gate = jnp.take_along_axis(probs, expert_ids, axis=-1)
        if renormalise:
            gate = gate / (gate.sum(axis=-1, keepdims=True) + 1e-20)
        if scale != 1.0:
            gate = gate * scale
        flat_ids = expert_ids.reshape(-1)
        counts = jnp.bincount(flat_ids, length=e).astype(jnp.int32)
        group_sizes = counts if held is None else counts[lo:lo + count]
        stats = {
            "counts": counts.astype(jnp.float32),
            "held_rows": group_sizes.sum().astype(jnp.float32),
            "prob_sum": probs.sum(axis=0),
            "z_loss": jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2
            ),
        }
    with device_scope("moe_dispatch"):
        tile_group, tiles_used, padded_starts = gmm.group_layout(
            group_sizes, assignments
        )
        padded_rows = tile_group.shape[0] * gmm.ROW_TILE
        if held is not None:
            stats["tiles_used"] = tiles_used[0].astype(jnp.float32)
            stats["tiles"] = jnp.float32(tile_group.shape[0])
        if held is None:
            order = jnp.argsort(flat_ids, stable=True).astype(jnp.int32)
            sorted_ids = flat_ids[order]
        else:
            # an expert held elsewhere sorts as group ``count``
            local = flat_ids - lo
            local = jnp.where((local >= 0) & (local < count), local, count)
            order = jnp.argsort(local, stable=True).astype(jnp.int32)
            here = local[order] < count
            sorted_ids = jnp.minimum(local[order], count - 1)
        starts = jnp.cumsum(group_sizes) - group_sizes
        # the padded row of the assignment at sorted position i
        row = (
            padded_starts[sorted_ids] - starts[sorted_ids]
            + jnp.arange(assignments, dtype=jnp.int32)
        )
        if held is not None:
            # no row: a slot past the last one, each its own
            row = jnp.where(
                here, row,
                padded_rows + jnp.arange(assignments, dtype=jnp.int32),
            )
        slot = jnp.zeros_like(order).at[order].set(
            row, unique_indices=True
        ).reshape(t, top_k)
        source = jnp.full(
            (padded_rows,), assignments, jnp.int32
        ).at[row].set(
            order, unique_indices=True,
            mode=None if held is None else "drop",
        )
        if held is None:
            rows = _dispatch_rows(tokens.astype(dtype), source, slot)
        else:
            rows = _held_dispatch(
                tokens.astype(dtype), source, slot, tiles_used
            )
    with device_scope("moe_experts"):
        # the kernels' walks over the used tiles, the activation and
        # its derivative inside them: nothing here passes over the
        # padded rows
        # (the two older forms call it as they always did)
        rows = gmm.grouped_expert(
            rows, None if w_gate is None else w_gate.astype(dtype),
            w_up.astype(dtype), w_down.astype(dtype), tile_group,
            tiles_used, **({} if polynorm is None else {"coeffs": polynorm}),
        )
    with device_scope("moe_combine"):
        if held is None:
            out = jnp.einsum(
                "tkd,tk->td", _collect_rows(rows, source, slot), gate,
                preferred_element_type=jnp.float32,
            )
        else:
            out = _held_combine(rows, gate, source, slot, tiles_used)
    return out.astype(dtype), stats


class DroplessMoE(nn.Module):
    """:func:`dropless_moe` as a layer: ``x [b, s, d] -> (out, stats)``.
    Parameter names as :class:`MoEMLP`'s gated experts (``router``,
    ``experts_w_gate`` / ``experts_w_in`` / ``experts_w_out``, leading
    expert dim).  The defaults are OLMoE's layer.  With ``held`` the
    router keeps ``num_experts`` outputs and the expert weights have
    ``held[1]`` leading entries; ``select_bias`` adds the parameter of
    that name (``[num_experts]`` float32 zeros: no gradient reaches
    it, the train step moves it by the loss's ``state_updates``);
    ``shared_dim`` adds a SwiGLU of that width that every token takes
    (``shared_gate`` / ``shared_up`` / ``shared_down``), under the
    device scope ``moe_shared``.  ``expert_form="relu2"`` is a layer
    of experts WITHOUT a gate matrix, the shared one too
    (``down(relu(up(x)) ** 2)``): the tree then has no
    ``experts_w_gate`` and no ``shared_gate``.
    ``expert_form="polynorm"`` keeps the gate and puts PolyNorm in
    silu's place (:func:`polynorm_coeffs` with ``polynorm_scale`` and
    ``polynorm_clamp``): ONE learned ``experts_polynorm_w [3]`` /
    ``experts_polynorm_b []`` (float32) for the layer's routed experts,
    whose gradients sum over every row of the layer, and one more,
    ``shared_polynorm_w`` / ``shared_polynorm_b``, for the shared
    expert."""

    num_experts: int
    mlp_dim: int
    top_k: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.normal(0.02)
    held: Optional[Tuple[int, int]] = None
    score: str = "softmax"
    select_bias: bool = False
    renormalise: bool = False
    scale: float = 1.0
    shared_dim: int = 0
    expert_form: str = "swiglu"  # | "relu2" | "polynorm"
    polynorm_scale: float = 0.5
    polynorm_clamp: float = 0.5

    @nn.compact
    def __call__(self, x: jax.Array):
        b, s, d = x.shape
        e, m = self.num_experts, self.mlp_dim
        count = e if self.held is None else self.held[1]
        if self.expert_form not in EXPERT_FORMS:
            raise ValueError(
                f"no expert form {self.expert_form!r} "
                f"({' | '.join(EXPERT_FORMS)})"
            )
        gated = self.expert_form != "relu2"

        def polynorm(prefix):
            if self.expert_form != "polynorm":
                return None
            return polynorm_params(
                self, prefix, self.polynorm_scale, self.polynorm_clamp
            )

        router = self.param(
            "router", self.kernel_init, (d, e), self.param_dtype
        )
        w_gate = self.param(
            "experts_w_gate", self.kernel_init, (count, d, m),
            self.param_dtype,
        ) if gated else None
        w_up = self.param(
            "experts_w_in", self.kernel_init, (count, d, m),
            self.param_dtype,
        )
        w_down = self.param(
            "experts_w_out", self.kernel_init, (count, m, d),
            self.param_dtype,
        )
        bias = None
        if self.select_bias:
            bias = self.param(
                "select_bias", nn.initializers.zeros, (e,), jnp.float32
            )
        out, stats = dropless_moe(
            x.reshape(b * s, d), router, w_gate, w_up, w_down,
            self.top_k, self.dtype, held=self.held, score=self.score,
            select_bias=bias, renormalise=self.renormalise,
            scale=self.scale, polynorm=polynorm("experts_"),
        )
        out = out.reshape(b, s, d)
        if self.shared_dim:
            def dense(features, name):
                return nn.Dense(
                    features, use_bias=False, dtype=self.dtype,
                    param_dtype=self.param_dtype,
                    kernel_init=self.kernel_init, name=name,
                )

            with device_scope("moe_shared"):
                if self.expert_form == "polynorm":
                    hidden = polynorm_glu(
                        dense(self.shared_dim, "shared_gate")(x),
                        dense(self.shared_dim, "shared_up")(x),
                        polynorm("shared_"),
                    )
                elif gated:
                    hidden = nn.silu(
                        dense(self.shared_dim, "shared_gate")(x)
                    ) * dense(self.shared_dim, "shared_up")(x)
                else:
                    hidden = relu2(dense(self.shared_dim, "shared_up")(x))
                out = out + dense(d, "shared_down")(hidden)
        return out, stats


def bias_deltas(counts, rate: float):
    """The rule of a ``select_bias`` (the loss hands the deltas to the
    train step as ``state_updates``), a layer a row: ``rate x sign(mean(n) -
    n_e)``, ``counts [layers, e]`` the step's assignments."""
    return rate * jnp.sign(
        counts.mean(axis=1, keepdims=True) - counts
    )


# -- a held range: the rows move from the row side ----------------------------
#
# A chip that holds ``count`` of a layer's ``e`` experts gives a row to
# ``count / e`` of the assignments, so most of the static layout's row
# tiles hold none.  The movements below walk the used tiles, up to
# ``tiles_used`` (a trip count read on the device), and keep only ``[t,
# d]`` arrays on the token side: no ``[t, k, d]`` array is made and the
# rows past ``tiles_used`` are neither read nor written.  Rows from
# tokens is a loop of one tile's gather a trip; its transpose, the
# rows back to their tokens, is ``gmm.tokens_from_rows`` (a kernel:
# XLA's scatter-add walks its rows one by one).  Both live inside
# ``custom_vjp`` rules, so autodiff never meets a loop.


def _token_of_row(source, slot):
    """``[padded rows]``: the token whose assignment lives at each
    padded row.  A row of padding names a token past the last one,
    each its own and ascending, so that a gather fills it with zeros
    and a scatter drops it, and the indices of one tile (one expert's
    rows in token order, then its padding) are sorted and distinct."""
    t, k = slot.shape
    padding = t + jnp.arange(source.shape[0], dtype=jnp.int32)
    return jnp.where(source < t * k, source // k, padding)


def _tile(x, i):
    return jax.lax.dynamic_slice_in_dim(
        x, i * gmm.ROW_TILE, gmm.ROW_TILE
    )


def _rows_of(x, index):
    # ``x[index]`` for one tile's tokens; a row of padding reads zeros
    return x.at[index].get(
        mode="fill", fill_value=0, indices_are_sorted=True,
        unique_indices=True,
    )


def _rows_from_tokens(x, token_of_row, tiles_used, weight=None, after=None):
    """``weight[p] * x[token_of_row[p]]`` for the rows of the used
    tiles, ``[padded rows, d]``: a gather of one tile's rows a trip;
    the product in float32.  The result exists no sooner than
    ``after`` (``x`` where none is given)."""

    def move(i, rows):
        tile = _rows_of(x, _tile(token_of_row, i))
        if weight is not None:
            tile = tile.astype(jnp.float32) * _tile(weight, i)[:, None]
        return jax.lax.dynamic_update_slice_in_dim(
            rows, tile.astype(x.dtype), i * gmm.ROW_TILE, axis=0
        )

    return jax.lax.fori_loop(
        0, tiles_used[0], move,
        gmm.unwritten(
            (token_of_row.shape[0], x.shape[1]), x.dtype,
            x if after is None else after,
        ),
    )


def _row_dots(rows, x, token_of_row, tiles_used):
    """``<rows[p], x[token_of_row[p]]>`` in float32 for the rows of
    the used tiles, ``[padded rows]``; 0 for a row of padding."""

    def move(i, dots):
        tile = jnp.sum(
            _tile(rows, i).astype(jnp.float32)
            * _rows_of(x, _tile(token_of_row, i)).astype(jnp.float32),
            axis=-1,
        )
        return jax.lax.dynamic_update_slice_in_dim(
            dots, tile, i * gmm.ROW_TILE, axis=0
        )

    return jax.lax.fori_loop(
        0, tiles_used[0], move,
        jnp.zeros(token_of_row.shape, jnp.float32),
    )


@jax.custom_vjp
def _held_dispatch(tokens, source, slot, tiles_used):
    """:func:`_dispatch_rows` where only some assignments have a row
    (``slot`` names a row past the last one for the others)."""
    return _rows_from_tokens(
        tokens, _token_of_row(source, slot), tiles_used
    )


def _held_dispatch_fwd(tokens, source, slot, tiles_used):
    return (
        _held_dispatch(tokens, source, slot, tiles_used),
        (source, slot, tiles_used),
    )


def _held_dispatch_bwd(res, g):
    source, slot, tiles_used = res
    with device_scope("moe_dispatch"):
        d_tokens = gmm.tokens_from_rows(
            g, _token_of_row(source, slot), tiles_used, slot.shape[0]
        )
        return d_tokens, None, None, None


_held_dispatch.defvjp(_held_dispatch_fwd, _held_dispatch_bwd)


def _gate_of_row(gate, source):
    return gate.reshape(-1).at[source].get(mode="fill", fill_value=0)


@jax.custom_vjp
def _held_combine(rows, gate, source, slot, tiles_used):
    """``sum over a token's held choices of gate x row``, accumulated
    in float32 and cast once, ``[t, d]``: :func:`_collect_rows` and
    the weighting in one, from the row side.  Choices held elsewhere
    add nothing."""
    return gmm.tokens_from_rows(
        rows, _token_of_row(source, slot), tiles_used, slot.shape[0],
        _gate_of_row(gate, source),
    )


def _held_combine_fwd(rows, gate, source, slot, tiles_used):
    return (
        _held_combine(rows, gate, source, slot, tiles_used),
        (rows, gate, source, slot, tiles_used),
    )


def _held_combine_bwd(res, g):
    rows, gate, source, slot, tiles_used = res
    with device_scope("moe_combine"):
        token_of_row = _token_of_row(source, slot)
        # a row's gradient is its gate x its token's; the gate's is
        # the row's dot product with it, back in ``[t, k]`` through
        # ``slot`` (0 for a choice held elsewhere).  Two walks.  The
        # rows' gradient reads nothing of ``rows``, which the
        # backward pass has to make again, and still waits for the
        # dots that do: made before the experts run again it is a
        # third array of the padded rows beside their hidden rows,
        # and made beside ``rows`` a second where it can take their
        # place (the step's most bytes live at once: PERF.md, PR 52)
        dots = _row_dots(rows, g, token_of_row, tiles_used)
        d_gate = dots.at[slot].get(mode="fill", fill_value=0)
        d_rows = _rows_from_tokens(
            g, token_of_row, tiles_used, _gate_of_row(gate, source),
            after=dots,
        )
        return d_rows, d_gate.astype(gate.dtype), None, None, None


_held_combine.defvjp(_held_combine_fwd, _held_combine_bwd)
