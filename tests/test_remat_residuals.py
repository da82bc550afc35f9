"""What a rematted block keeps and what it runs again for its
backward, in the seven families whose blocks sit behind
``prevent_cse=True``: with flash attention the one remat policy keeps
the kernel's five residuals
(``ops/flash_attention.py::RESIDUAL_NAMES``), so the forward kernel is
not run again and nothing that stands before it only to feed it is in
the rematted computation; a recurrent rule's forward kernel
(``gdn_fwd``, ``kda_fwd``, ``ssd_fwd``, ``s6_fwd``) names its own results and the
policy keeps those, so it runs once a layer; with XLA attention and no
such rule nothing is named and the program is the parent policy's.  A
family's toy is built, traced under both policies and run ONCE for the
tests that read it."""

import functools
import os
import sys

import jax
import numpy as np
import pytest
from conftest import REMAT_PRIMITIVE, equations, kernel_calls

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import layers  # noqa: E402

# family -> (attention's matmuls that feed only the kernel, those whose
# results something else's gradient reads and that are run again, the
# flash forward kernels of the toy's gradient, the forward kernel of
# the family's recurrent rule and the toy's layers that call it)
FAMILIES = {
    "laguna": (
        {"q_proj", "k_proj", "v_proj"},
        # the gate and the output projection read the normed input
        # and the kernel's output
        {"g_proj", "o_proj"},
        # one a layer: a full one, two sliding
        3, None,
    ),
    "sarvam_mla": (
        {"q_proj", "kv_up"},
        # ``kv_up``'s gradient reads the normed latent
        {"kv_down", "o_proj"},
        # one a layer (heads of 24 | 16)
        3, None,
    ),
    # one an application: two in the body of the scan over the passes
    "ouro": ({"q_proj", "k_proj", "v_proj"}, {"o_proj"}, 2, None),
    # QK-norm's gradient reads the un-normed q and k; one kernel in the
    # period's one full-attention layer, the rule in the three before
    "olmo_hybrid": (
        {"v_proj"}, {"q_proj", "k_proj", "o_proj"}, 1, ("gdn_fwd", 3)
    ),
    # (KDA, KDA, latent: as ``sarvam_mla`` plus the head-wise gate,
    # which reads the block's normed input)
    "bailing_hybrid": (
        {"q_proj", "kv_up"}, {"kv_down", "g_proj", "o_proj"}, 1,
        ("kda_fwd", 2),
    ),
    # (MEM*EM: nothing reads ``o_proj``'s result but the residual sum,
    # so no attention matmul is run again)
    "nemotron_h": (
        {"q_proj", "k_proj", "v_proj"}, set(), 1, ("ssd_fwd", 3)
    ),
    # (mamba, attention, mamba; the block's SwiGLU reads what
    # ``o_proj`` adds to the residual)
    "jamba": (
        {"q_proj", "k_proj", "v_proj"}, {"o_proj"}, 1, ("s6_fwd", 2)
    ),
}
# (a family's toy is ``configs/toy_<family>.json`` but for)
TOYS = {"bailing_hybrid": "toy_ling"}
RECURRENT_FORWARDS = ("gdn_fwd", "kda_fwd", "ssd_fwd", "s6_fwd")


def toy_loss(family, attention):
    """``(loss of the parameters alone, params)`` of the family's toy
    configuration in float32 with remat, at 2 x 128 tokens."""
    cfg = loader.load_json(os.path.join(
        REPO, "benchmarks", "configs",
        TOYS.get(family, f"toy_{family}") + ".json",
    ))
    cfg["recipe"].update(
        param_dtype="float32", compute_dtype="float32",
        attention=attention, remat=True,
    )
    model, _, loss_fn = loader.load_module("models", family).build(cfg)
    # (jitted: an eager init runs the whole model op by op)
    params = jax.jit(lambda key: model.init_params(key, seq_len=128))(
        jax.random.PRNGKey(7)
    )
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 129), 0, 256)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
    return lambda p: loss_fn(p, batch)[0], params


@pytest.fixture(scope="module", params=list(FAMILIES))
def traced(request):
    """``(family, ours, parents)``: the family's toy with flash
    attention; ``ours`` is the jaxpr of ``jax.value_and_grad`` of its
    loss and the values of its jitted call under
    ``models/layers.py::remat_policy``, ``parents`` the same under the
    parent's policy (``None``: keep nothing)."""
    loss, params = toy_loss(request.param, "flash")

    def trace():
        # a function of its own: a trace is cached by its function; ONE
        # trace gives the jaxpr and the program that is run
        traced = jax.jit(jax.value_and_grad(lambda p: loss(p))).trace(params)
        return traced.jaxpr.jaxpr, traced.lower().compile()(params)

    ours = trace()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(layers, "remat_policy", lambda name: None)
        parents = trace()
    return request.param, ours, parents


def recomputed_attention_matmuls(jaxpr):
    """The last name of every ``dot_general`` of an attention module
    that a ``checkpoint`` of a gradient's jaxpr runs AGAIN (jax puts
    ``rematted_computation`` at the head of such an equation's name
    stack; a gradient's own matmul carries the module's name too, but
    not that head)."""
    found = set()
    for under, eqn in equations(jaxpr):
        stack = str(eqn.source_info.name_stack).split("/")
        if (eqn.primitive.name == "dot_general"
                and REMAT_PRIMITIVE in under
                and stack[0] == "rematted_computation"
                and "attn" in stack):
            found.add(stack[-1])
    return found


def test_a_rematted_block_runs_nothing_again_only_to_feed_its_flash_kernel(
    traced
):
    """The toy configuration with ``remat`` and flash attention: the
    rematted computation of the gradient's jaxpr holds no matmul of
    the projections that only the kernel reads (it holds them under
    the parent's ``policy=None``, the control), it still holds the
    ones a gradient reads, and loss and every gradient leaf are the
    parent policy's numbers bit for bit."""
    family, (jaxpr, kept), (parents_jaxpr, parents) = traced
    dead, alive, _, _ = FAMILIES[family]
    again = recomputed_attention_matmuls(parents_jaxpr)
    assert again, (
        "no recomputed attention matmul found: does jax "
        f"{jax.__version__} still head a rematted equation's name "
        "stack with 'rematted_computation'?"
    )
    assert dead | alive <= again, again
    again = recomputed_attention_matmuls(jaxpr)
    assert not again & dead and alive <= again, again
    for ours, theirs in zip(
        jax.tree.leaves(kept), jax.tree.leaves(parents), strict=True
    ):
        np.testing.assert_array_equal(
            np.asarray(ours), np.asarray(theirs)
        )


def test_a_rematted_block_keeps_what_its_flash_backward_reads(
    traced, remat_keeps_what_flash_reads
):
    """One forward kernel a layer (an application in ``ouro``, whose
    block takes its policy where the other families take theirs), none
    of them run again for the backward; loss and gradients the parent
    policy's bit for bit."""
    family, ours, parents = traced
    remat_keeps_what_flash_reads(ours, parents, FAMILIES[family][2])


def test_a_rematted_block_runs_no_recurrent_forward_again(
    traced, remat_keeps_what_flash_reads
):
    """A recurrent rule's forward kernel runs once a layer that calls
    it and never in the rematted computation: the rule names what it
    writes (``o``, the final state, the chunk-start states, ``T``) and
    the policy keeps the names, all of one ``pallas_call``'s live
    results or the call stays.  Under the parent's policy each runs a
    second time there; the numbers are the same bit for bit.  A family
    with no such rule calls none of the four."""
    family, ours, parents = traced
    kernel, layers_with = FAMILIES[family][3] or (None, 0)
    for name in RECURRENT_FORWARDS:
        remat_keeps_what_flash_reads(
            ours, parents, layers_with if name == kernel else 0,
            calls=functools.partial(kernel_calls, name=name),
        )


@pytest.mark.parametrize(
    "family", [name for name, kept in FAMILIES.items() if not kept[3]]
)
def test_with_xla_attention_and_no_recurrent_rule_nothing_is_named(
    family, remat_with_xla_attention_is_the_parents
):
    """With XLA attention, in a family whose layers call no recurrent
    rule, no name occurs and the program is the parent's."""
    remat_with_xla_attention_is_the_parents(*toy_loss(family, "xla"))
