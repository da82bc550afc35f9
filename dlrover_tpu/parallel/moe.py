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

import functools
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


def _layout_by_sorting(expert_ids, group_sizes, padded_starts, padded_rows):
    """``(source [padded rows], slot [t, k])`` of :func:`_dispatch_rows`
    where every expert is held: the ``t * k`` assignments
    stable-sorted by expert, each expert's rows from its padded
    start."""
    flat_ids = expert_ids.reshape(-1)
    assignments = flat_ids.shape[0]
    order = jnp.argsort(flat_ids, stable=True).astype(jnp.int32)
    sorted_ids = flat_ids[order]
    starts = jnp.cumsum(group_sizes) - group_sizes
    # the padded row of the assignment at sorted position i
    row = (
        padded_starts[sorted_ids] - starts[sorted_ids]
        + jnp.arange(assignments, dtype=jnp.int32)
    )
    slot = jnp.zeros_like(order).at[order].set(
        row, unique_indices=True
    ).reshape(expert_ids.shape)
    source = jnp.full((padded_rows,), assignments, jnp.int32).at[row].set(
        order, unique_indices=True
    )
    return source, slot


def _is_expert(expert_ids, experts):
    # ``[t, k, len(experts)]``: choice c of token t is that expert.
    # Never an array: a compare that fuses into the sum that reads it
    return expert_ids[..., None] == experts.astype(expert_ids.dtype)


def _assignments_of(expert_ids, e: int):
    """``[e]`` int32, how many of the ``t x k`` assignments each expert
    got: ``bincount``'s numbers as a compare with every expert and an
    integer sum (a scatter-add takes a TPU 7-10 ns an assignment)."""
    return jnp.sum(
        _is_expert(expert_ids, jnp.arange(e)), axis=(0, 1), dtype=jnp.int32
    )


def _scores_of(probs, expert_ids):
    """``probs[t, expert_ids[t, c]]``, ``[t, k]``, as a sum over the
    experts with ONE term that is not zero, so exact; its gradient is
    a sum over a token's k choices, distinct experts, with at most one
    (where ``take_along_axis`` gathers ``t x k`` scalars one by one
    and its transpose fills ``[t, e]`` with zeros and scatters)."""
    scores = jnp.sum(
        jnp.where(
            _is_expert(expert_ids, jnp.arange(probs.shape[-1])),
            probs[:, None, :], 0.0,
        ),
        axis=-1,
    )
    # an array of its own, as a gather's result is: a sum over the k
    # choices that reads it (``renormalise``) adds them in their order,
    # where the compiler would merge the two sums into one over ``[k,
    # e]`` that adds them in the experts' (the last bits of every
    # weight, and with them of the step's loss, would move)
    return jax.lax.optimization_barrier(scores)


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
    n_group: int = 1,
    topk_group: int = 1,
    renormalise_eps: float = 1e-20,
):
    """Top-k routing without capacity: ``(out [t, d], stats)``.

    The defaults are OLMoE's router: logits and softmax in float32,
    the top-k probabilities weight the experts' outputs as they are,
    NOT renormalised (``norm_topk_prob: false``), and the chip holds
    all ``e`` experts.  ``score="sigmoid"`` scores each expert on its
    own; with ``select_bias`` the k experts are chosen by ``score +
    bias`` and weighted by the score alone (the bias only steers the
    load and takes no gradient); ``renormalise`` divides the k weights
    by their sum (+ ``renormalise_eps``, a family's own guard),
    ``scale`` multiplies them.  An expert computes
    ``down(silu(gate(x)) * up(x))`` or, with ``w_gate=None``,
    ``down(relu(up(x)) ** 2)``: an expert that has no gate has no
    gate matrix.  ``n_group > 1`` limits the choice to groups
    (DeepSeek-V3's ``noaux_tc``, arXiv:2412.19437): the ``e`` outputs
    are ``n_group`` groups of consecutive experts, a group's score is
    the sum of its two largest ``score + bias``, only the
    ``topk_group`` best groups' experts stand for the top-k
    (:func:`_within_best_groups`, under the device scope
    ``moe_group_select``), and ``stats`` gains ``groups_per_token``,
    the mean number of distinct groups among a token's k choices (at
    most ``topk_group``).  With ``polynorm`` (:func:`polynorm_coeffs`) the
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

    The ``t * k`` assignments lie in the order of a stable sort by
    expert, an expert's rows in token order, each expert's rows
    starting on a row tile of the grouped-matmul kernel
    (``ops/grouped_matmul.py``), and each expert computes its own
    rows as grouped matmuls.  Every shape is static (``t * k`` rows
    and one tile of padding an expert held, whatever the routing: a
    batch may send every assignment here); an expert without a token
    is one tile of zero rows, and the kernels skip the tiles past the
    last used one: no product, no fetch, no store, so those rows of
    each matmul's result are NOT WRITTEN, forward or backward.
    Nothing here reads them: the activation and its derivative run
    inside the kernels, over the used tiles, and the ways back go
    through ``slot`` or ``token_of_row``, which name only rows of an
    expert (a reduction over the padded rows would:
    ``tests/test_sarvam_mla.py`` fills them with NaN).

    **What runs at which size.**  The router runs at the static size:
    the scores, the top-k, the chosen scores as a masked sum over the
    experts (:func:`_scores_of`: no gather, and no scatter-add for its
    gradient) and ``counts`` as a compare with every expert and an
    integer sum (:func:`_assignments_of`: no ``bincount``); neither
    makes an array of ``[t, k, e]``.  With every expert held, so do
    the index work (the sort, ``slot [t, k]`` and ``source [padded
    rows]`` by two scatters), the two row gathers and the weighting,
    over ``[t, k, d]``: nearly every tile has rows.  With a held range
    most tiles have none.  At the static size there are then only
    vector passes over ``[count, k, t]`` masks and ``[count, t]``
    prefix sums and weights (:func:`_held_choices`: no sort, no
    scatter, no gather); everything on the row side walks the
    ``tiles_used`` tiles that hold a row, forward and backward (below
    the layer in this file): which token a row holds and at what
    weight, that weight's gradient back to its token, dispatch and
    combine; the token side keeps ``[t, d]`` arrays and nothing of
    ``[t, k, d]`` is made.  The ``[padded rows, d]`` results are, as
    the kernels', NOT WRITTEN past ``tiles_used``.  The arrays are
    the sort's to the bit at every routing
    (``tests/test_moe_held_index.py``), so the two sides differ in
    cost alone: which one runs is ``held is not None``, a fact of the
    input.

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
    check_groups(e, top_k, n_group, topk_group)
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
        standing = (
            probs if select_bias is None
            else probs + jax.lax.stop_gradient(select_bias)
        )
    if n_group > 1:
        with device_scope("moe_group_select"):
            standing = _within_best_groups(standing, n_group, topk_group)
    with device_scope("moe_router"):
        _, expert_ids = jax.lax.top_k(standing, top_k)  # [t, k]
        gate = _scores_of(probs, expert_ids)
        if renormalise:
            gate = gate / (
                gate.sum(axis=-1, keepdims=True) + renormalise_eps
            )
        if scale != 1.0:
            gate = gate * scale
        counts = _assignments_of(expert_ids, e)
        group_sizes = counts if held is None else counts[lo:lo + count]
        stats = {
            "counts": counts.astype(jnp.float32),
            "held_rows": group_sizes.sum().astype(jnp.float32),
            "prob_sum": probs.sum(axis=0),
            "z_loss": jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2
            ),
        }
    if n_group > 1:
        with device_scope("moe_group_select"):
            stats["groups_per_token"] = _groups_chosen(
                expert_ids, e // n_group, n_group
            )
    with device_scope("moe_dispatch"):
        tile_group, tiles_used, padded_starts = gmm.group_layout(
            group_sizes, assignments
        )
        if held is None:
            source, slot = _layout_by_sorting(
                expert_ids, group_sizes, padded_starts,
                tile_group.shape[0] * gmm.ROW_TILE,
            )
            rows = _dispatch_rows(tokens.astype(dtype), source, slot)
        else:
            stats["tiles_used"] = tiles_used[0].astype(jnp.float32)
            stats["tiles"] = jnp.float32(tile_group.shape[0])
            token_of_row, gate_of_row = _held_layout(
                expert_ids, gate, lo, count, tile_group, tiles_used,
                padded_starts,
            )
            rows = _held_dispatch(
                tokens.astype(dtype), token_of_row, tiles_used, t
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
            out = _held_combine(
                rows, gate_of_row, token_of_row, tiles_used, t
            )
    return out.astype(dtype), stats


def check_groups(e: int, top_k: int, n_group: int, topk_group: int):
    """Raises where ``n_group`` groups do not divide the ``e`` outputs
    or the ``topk_group`` kept groups hold fewer than ``top_k``
    experts."""
    if n_group < 1 or e % n_group:
        raise ValueError(f"{e} experts do not divide into {n_group} groups")
    if not 1 <= topk_group <= n_group:
        raise ValueError(f"{topk_group} of {n_group} groups kept")
    if top_k > topk_group * (e // n_group):
        raise ValueError(
            f"top-{top_k} of {topk_group} groups of {e // n_group} experts"
        )


def _within_best_groups(standing, n_group: int, topk_group: int):
    """``standing [t, e]`` with every expert outside its token's
    ``topk_group`` best groups at ``-inf``: a group (``e / n_group``
    consecutive experts) scores the sum of its two largest entries."""
    t, e = standing.shape
    grouped = standing.reshape(t, n_group, e // n_group)
    best_two, _ = jax.lax.top_k(grouped, 2)
    _, best = jax.lax.top_k(best_two.sum(axis=-1), topk_group)
    # [t, n_group]: the group is one of the token's best
    kept = jnp.any(
        best[:, :, None] == jnp.arange(n_group, dtype=best.dtype), axis=1
    )
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e)


def _groups_chosen(expert_ids, group_size: int, n_group: int):
    """The mean over tokens of how many distinct groups a token's k
    choices lie in, float32."""
    of_choice = expert_ids // group_size                  # [t, k]
    hit = jnp.any(
        of_choice[:, :, None] == jnp.arange(n_group, dtype=of_choice.dtype),
        axis=1,
    )
    return jnp.mean(jnp.sum(hit, axis=-1, dtype=jnp.float32))


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
    expert.  ``n_group`` / ``topk_group`` limit the choice to groups
    (:func:`dropless_moe`); the defaults are no grouping.
    ``renormalise_eps`` is the guard in ``renormalise``'s denominator
    (a family's own: 1e-6 in ``models/lfm2_moe.py``)."""

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
    n_group: int = 1
    topk_group: int = 1
    renormalise_eps: float = 1e-20

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
            n_group=self.n_group, topk_group=self.topk_group,
            renormalise_eps=self.renormalise_eps,
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
# tiles hold none.  What has the static size is vector work on the
# token side: masks over the ``count`` held experts and prefix sums over
# the tokens (:func:`_held_layout`).  Everything on the row side walks
# the used tiles, up to ``tiles_used`` (a trip count read on the
# device): which token a row holds and at what weight, the rows from
# their tokens (a loop of one tile's gather a trip) and the rows back
# to their tokens (``gmm.tokens_from_rows``, a kernel: XLA's
# scatter-add walks its rows one by one).  Only ``[t, d]`` arrays stand
# on the token side, no ``[t, k, d]`` array is made and the ``[padded
# rows, d]`` rows past ``tiles_used`` are neither read nor written.
# No sort and no scatter: a stable sort of the assignments by expert
# keeps an expert's rows in token order, and a token's k choices are
# distinct experts, so an assignment's rank inside its expert is the
# number of earlier tokens that chose it.  The walks live inside
# ``custom_vjp`` rules, so autodiff never meets a loop.


def _tile(x, i):
    return jax.lax.dynamic_slice_in_dim(
        x, i * gmm.ROW_TILE, gmm.ROW_TILE
    )


def _put_tile(x, tile, i):
    return jax.lax.dynamic_update_slice_in_dim(
        x, tile, i * gmm.ROW_TILE, axis=0
    )


def _line(x, j):
    # ``x[j]`` of ``[count, t]``: one held expert's numbers, a token each
    return jax.lax.dynamic_index_in_dim(x, j, axis=0, keepdims=False)


def _rows_of(x, index):
    # ``x[index]`` for one tile's tokens; a row of padding reads zeros
    return x.at[index].get(
        mode="fill", fill_value=0, indices_are_sorted=True,
        unique_indices=True,
    )


def _held_choices(expert_ids, gate, lo: int, count: int):
    """``(reached [count, t] int32, gate_of_choice [count, t])``:
    how many of the tokens up to and with t chose held expert ``lo +
    j``, and the weight of token t's choice of it (0 where it chose
    another).  A stable sort of the assignments by expert keeps an
    expert's rows in token order, so the assignment of token t to
    expert j lives at padded row ``padded_starts[j] + reached[j, t] -
    1``.  Masks and sums over ``[count, k, t]`` with the tokens along
    the lanes, at the static size."""
    # [count, k, t]: choice c of token t is held expert j
    hit = _is_expert(expert_ids.T, lo + jnp.arange(count)).transpose(2, 0, 1)
    reached = jnp.cumsum(hit.any(axis=1), axis=1, dtype=jnp.int32)
    # a token's k choices are distinct experts: one term is not zero
    return reached, jnp.sum(jnp.where(hit, gate.T, 0.0), axis=1)


def _held_layout(
    expert_ids, gate, lo: int, count: int, tile_group, tiles_used,
    padded_starts,
):
    """``(token_of_row [padded rows] int32, gate_of_row [padded rows])``
    of the layout ``gmm.group_layout`` gives the held experts' counts:
    the token whose assignment lives at each padded row, and that
    assignment's weight (its gradient flows back to ``gate``).  EQUAL,
    on every tile and at every routing, to what a stable sort of the
    assignments by expert and two scatters give
    (``tests/test_moe_held_index.py`` keeps that form as the
    reference): what has the static size is :func:`_held_choices`, and
    the way back from a row to its token is made for the used tiles."""
    reached, gate_of_choice = _held_choices(expert_ids, gate, lo, count)
    token_of_row = _tokens_of_rows(
        reached, tile_group, tiles_used, padded_starts
    )
    return token_of_row, _gates_of_rows(
        gate_of_choice, token_of_row, tile_group, tiles_used
    )


def _tokens_of_rows(reached, tile_group, tiles_used, padded_starts):
    """``[padded rows]`` int32: the token at rank ``r`` of held expert
    ``j`` is the first whose ``reached[j]`` is ``r + 1``, which is the
    NUMBER of tokens with ``reached[j] <= r``: a compare of the
    expert's line with a tile's 256 ranks and a sum, a used tile a
    trip.  A row of padding names a token past the last one, each its
    own and ascending, so that a gather fills it with zeros and a
    scatter drops it, and the indices of one tile (one expert's rows
    in token order, then its padding) are sorted and distinct; so do
    the rows of the tiles that are not walked."""
    t = reached.shape[1]
    lane = jnp.arange(gmm.ROW_TILE, dtype=jnp.int32)

    def find(i, token_of_row):
        group = tile_group[i]
        upto = _line(reached, group)
        rank = i * gmm.ROW_TILE - padded_starts[group] + lane
        before = jnp.sum(
            upto[:, None] <= rank[None, :], axis=0, dtype=jnp.int32
        )
        # past the expert's last row every token is counted
        padding = t + i * gmm.ROW_TILE + lane
        return _put_tile(
            token_of_row, jnp.where(before < t, before, padding), i
        )

    return jax.lax.fori_loop(
        0, tiles_used[0], find,
        t + jnp.arange(
            tile_group.shape[0] * gmm.ROW_TILE, dtype=jnp.int32
        ),
    )


@jax.custom_vjp
def _gates_of_rows(gate_of_choice, token_of_row, tile_group, tiles_used):
    """``[padded rows]``: ``gate_of_choice[expert of the row, token of
    the row]`` for the rows of the used tiles (a tile's 256 out of its
    expert's line), 0 for a row of padding and past ``tiles_used``."""

    def find(i, gates):
        line = _line(gate_of_choice, tile_group[i])
        return _put_tile(gates, _rows_of(line, _tile(token_of_row, i)), i)

    return jax.lax.fori_loop(
        0, tiles_used[0], find,
        jnp.zeros(token_of_row.shape, gate_of_choice.dtype),
    )


def _gates_of_rows_fwd(gate_of_choice, token_of_row, tile_group, tiles_used):
    return (
        _gates_of_rows(gate_of_choice, token_of_row, tile_group, tiles_used),
        (gate_of_choice.shape, token_of_row, tile_group, tiles_used),
    )


def _gates_of_rows_bwd(res, g):
    """A used tile's 256 numbers back at their tokens in the expert's
    line, ``[count, t]``: a compare of the tile's tokens with all of
    them and a sum with at most one term that is not zero (a tile's
    tokens are distinct, and so are one expert's over its tiles)."""
    shape, token_of_row, tile_group, tiles_used = res
    every_token = jnp.arange(shape[1], dtype=jnp.int32)

    def back(i, lines):
        at_tokens = jnp.sum(
            jnp.where(
                _tile(token_of_row, i)[:, None] == every_token[None, :],
                _tile(g, i)[:, None], 0.0,
            ),
            axis=0,
        )
        group = tile_group[i]
        return jax.lax.dynamic_update_index_in_dim(
            lines, _line(lines, group) + at_tokens, group, axis=0
        )

    with device_scope("moe_dispatch"):
        return jax.lax.fori_loop(
            0, tiles_used[0], back, jnp.zeros(shape, g.dtype)
        ), None, None, None


_gates_of_rows.defvjp(_gates_of_rows_fwd, _gates_of_rows_bwd)


def _rows_from_tokens(x, token_of_row, tiles_used, weight=None, after=None):
    """``weight[p] * x[token_of_row[p]]`` for the rows of the used
    tiles, ``[padded rows, d]``: a gather of one tile's rows a trip;
    the product in float32.  The result exists no sooner than
    ``after`` (``x`` where none is given)."""

    def move(i, rows):
        tile = _rows_of(x, _tile(token_of_row, i))
        if weight is not None:
            tile = tile.astype(jnp.float32) * _tile(weight, i)[:, None]
        return _put_tile(rows, tile.astype(x.dtype), i)

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
        return _put_tile(dots, tile, i)

    return jax.lax.fori_loop(
        0, tiles_used[0], move,
        jnp.zeros(token_of_row.shape, jnp.float32),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _held_dispatch(tokens, token_of_row, tiles_used, t: int):
    """:func:`_dispatch_rows` where only some assignments have a row:
    ``tokens[token_of_row]`` over the used tiles, zeros for a row of
    padding (``t``: how many tokens there are, for the way back)."""
    return _rows_from_tokens(tokens, token_of_row, tiles_used)


def _held_dispatch_fwd(tokens, token_of_row, tiles_used, t):
    return (
        _held_dispatch(tokens, token_of_row, tiles_used, t),
        (token_of_row, tiles_used),
    )


def _held_dispatch_bwd(t, res, g):
    with device_scope("moe_dispatch"):
        return gmm.tokens_from_rows(g, *res, t), None, None


_held_dispatch.defvjp(_held_dispatch_fwd, _held_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _held_combine(rows, gate_of_row, token_of_row, tiles_used, t: int):
    """``sum over a token's held choices of gate x row``, accumulated
    in float32 and cast once, ``[t, d]``: :func:`_collect_rows` and
    the weighting in one, from the row side.  Choices held elsewhere
    add nothing."""
    return gmm.tokens_from_rows(
        rows, token_of_row, tiles_used, t, gate_of_row
    )


def _held_combine_fwd(rows, gate_of_row, token_of_row, tiles_used, t):
    return (
        _held_combine(rows, gate_of_row, token_of_row, tiles_used, t),
        (rows, gate_of_row, token_of_row, tiles_used),
    )


def _held_combine_bwd(t, res, g):
    rows, gate_of_row, token_of_row, tiles_used = res
    with device_scope("moe_combine"):
        # a row's gradient is its gate x its token's; the gate's is
        # the row's dot product with it (0 for a row of padding).  Two
        # walks.  The rows' gradient reads nothing of ``rows``, which
        # the backward pass has to make again, and still waits for the
        # dots that do: made before the experts run again it is a
        # third array of the padded rows beside their hidden rows,
        # and made beside ``rows`` a second where it can take their
        # place (the step's most bytes live at once: PERF.md, PR 52)
        dots = _row_dots(rows, g, token_of_row, tiles_used)
        d_rows = _rows_from_tokens(
            g, token_of_row, tiles_used, gate_of_row, after=dots
        )
        return d_rows, dots.astype(gate_of_row.dtype), None, None


_held_combine.defvjp(_held_combine_fwd, _held_combine_bwd)
