"""What a rematted block runs again for its backward, in the four
families whose blocks sit behind ``prevent_cse=True``: with flash
attention the one remat policy keeps the kernel's five residuals
(``ops/flash_attention.py::RESIDUAL_NAMES``), so nothing that stands
before the kernel only to feed it is in the rematted computation."""

import os
import sys

import jax
import numpy as np
import pytest
from conftest import REMAT_PRIMITIVE, equations

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.models import layers  # noqa: E402

# family -> (attention's matmuls that feed only the kernel, those whose
# results something else's gradient reads and that are run again)
FAMILIES = {
    "laguna": (
        {"q_proj", "k_proj", "v_proj"},
        # the gate and the output projection read the normed input
        # and the kernel's output
        {"g_proj", "o_proj"},
    ),
    "sarvam_mla": (
        {"q_proj", "kv_up"},
        # ``kv_up``'s gradient reads the normed latent
        {"kv_down", "o_proj"},
    ),
    "ouro": ({"q_proj", "k_proj", "v_proj"}, {"o_proj"}),
    # QK-norm's gradient reads the un-normed q and k
    "olmo_hybrid": ({"v_proj"}, {"q_proj", "k_proj", "o_proj"}),
}


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


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_rematted_block_runs_nothing_again_only_to_feed_its_flash_kernel(
    family, monkeypatch
):
    """The tiny configuration with ``remat`` and flash attention: the
    rematted computation of the gradient's jaxpr holds no matmul of
    the projections that only the kernel reads (it holds them under
    the parent's ``policy=None``, the control), it still holds the
    ones a gradient reads, and loss and every gradient leaf are the
    parent policy's numbers bit for bit."""
    dead, alive = FAMILIES[family]
    cfg = loader.load_json(
        os.path.join(REPO, "benchmarks", "configs", f"toy_{family}.json")
    )
    cfg["recipe"].update(
        param_dtype="float32", compute_dtype="float32",
        attention="flash", remat=True,
    )
    model, _, loss_fn = loader.load_module("models", family).build(cfg)
    params = model.init_params(jax.random.PRNGKey(7), seq_len=128)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 129), 0, 256)
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}

    def loss(p):
        return loss_fn(p, batch)[0]

    grad = jax.value_and_grad(loss)
    again = recomputed_attention_matmuls(jax.make_jaxpr(grad)(params).jaxpr)
    assert again, (
        "no recomputed attention matmul found: does jax "
        f"{jax.__version__} still head a rematted equation's name "
        "stack with 'rematted_computation'?"
    )
    assert not again & dead and alive <= again, again
    kept = jax.jit(grad)(params)

    monkeypatch.setattr(layers, "remat_policy", lambda name: None)
    # another function: a trace is cached by its function
    grad = jax.value_and_grad(lambda p: loss(p))
    again = recomputed_attention_matmuls(jax.make_jaxpr(grad)(params).jaxpr)
    assert dead | alive <= again, again
    for ours, parents in zip(
        jax.tree.leaves(kept), jax.tree.leaves(jax.jit(grad)(params))
    ):
        np.testing.assert_array_equal(
            np.asarray(ours), np.asarray(parents)
        )
