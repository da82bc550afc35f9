"""``models/layers.py`` is what the decoder families share, and the
arrows point one way: ``ops/`` <- ``parallel/`` <- ``models/layers.py``
<- ``models/<family>.py``."""

import ast
import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import layers
from dlrover_tpu.ops.attention import (
    xla_causal_attention,
    xla_window_attention,
)

PACKAGE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "dlrover_tpu",
)
FAMILIES = [
    "gpt", "llama", "olmoe", "olmo_hybrid", "sarvam_mla", "laguna", "ouro",
    "nemotron_h", "mimo_v2", "motif", "bailing_hybrid", "jamba",
]
# the one sideways import left (ROADMAP D22): the pipeline's adapter
# calls ``gpt.py::cross_entropy_loss``, which the benchmark imports by
# that path
SIDEWAYS = {"llama": {("dlrover_tpu.models.gpt", "PipelinedDecoder")}}


def imports(path):
    """``(module, name)`` of every import in a file, wherever it
    stands; a plain ``import a.b`` is ``("a.b", None)``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                yield node.module, alias.name
        elif isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, None


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_imports_layers_and_losses_and_no_sibling(family):
    found = list(imports(os.path.join(PACKAGE, "models", f"{family}.py")))
    private = [
        (module, name) for module, name in found
        if name is not None and name.startswith("_")
    ]
    assert not private, private
    from_models = {
        (module, name) for module, name in found
        if module.startswith("dlrover_tpu.models")
    }
    allowed = {
        ("dlrover_tpu.models", "layers"), ("dlrover_tpu.models", "losses"),
    }
    own = {
        pair for pair in from_models
        if pair[0] == "dlrover_tpu.models.losses"
    }
    assert from_models - allowed - own == SIDEWAYS.get(family, set())


@pytest.mark.parametrize("family", FAMILIES)
def test_a_family_takes_the_remat_rule_from_layers(family):
    """No family wraps its block itself, and ``remat_policy`` is a
    field only where a block names ``block_in`` for "offload"."""
    path = os.path.join(PACKAGE, "models", f"{family}.py")
    with open(path) as f:
        source = f.read()
    assert "nn.remat" not in source and "layers.rematted(" in source
    module = importlib.import_module(f"dlrover_tpu.models.{family}")
    configs = [
        cls for name, cls in vars(module).items()
        if name.endswith("Config") and dataclasses.is_dataclass(cls)
        and cls.__module__ == module.__name__
    ]
    assert len(configs) == 1
    fields = {f.name for f in dataclasses.fields(configs[0])}
    assert ("remat_policy" in fields) == ("block_in" in source)
    assert ("remat_policy" in fields) == (family in ("gpt", "llama"))


def test_ops_and_parallel_import_nothing_from_models():
    def imported(layer, package):
        return [
            (layer, name, module)
            for name in sorted(os.listdir(os.path.join(PACKAGE, layer)))
            if name.endswith(".py")
            for module, _ in imports(os.path.join(PACKAGE, layer, name))
            if module.startswith(package)
        ]

    assert not imported("ops", "dlrover_tpu.models")
    assert not imported("parallel", "dlrover_tpu.models")
    assert not imported("ops", "dlrover_tpu.parallel")


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("case", ["scale", "window"])
def test_attention_with_a_scale_or_a_window_is_the_plain_form(impl, case):
    """The two calls the latent and the windowed families make: a
    scale of the caller's over two head sizes (192 | 128 in
    ``sarvam_mla``), a window over grouped heads (``laguna``)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    if case == "scale":
        q = jax.random.normal(ks[0], (2, 64, 4, 24))
        k = jax.random.normal(ks[1], (2, 64, 4, 24))
        v = jax.random.normal(ks[2], (2, 64, 4, 16))
        got = layers.attention(impl, q, k, v, scale=0.31, dtype=jnp.float32)
        want = xla_causal_attention(
            q, k, v, dtype=jnp.float32, scale=0.31
        )
        # not the default's numbers
        assert np.abs(
            want - xla_causal_attention(q, k, v, dtype=jnp.float32)
        ).max() > 1e-2
    else:
        q = jax.random.normal(ks[0], (2, 64, 6, 16))
        k = jax.random.normal(ks[1], (2, 64, 2, 16))
        v = jax.random.normal(ks[2], (2, 64, 2, 16))
        got = layers.attention(impl, q, k, v, window=7, dtype=jnp.float32)
        want = xla_window_attention(q, k, v, 7, jnp.float32)
        assert np.abs(
            want - xla_window_attention(q, k, v, None, jnp.float32)
        ).max() > 1e-2
    assert got.shape == want.shape and got.dtype == want.dtype
    if impl == "xla":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_attention_refuses_what_no_form_computes():
    q = jnp.zeros((1, 8, 4, 8))
    kv = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(ValueError, match="no attention through"):
        layers.attention("nope", q, q, q)
    with pytest.raises(ValueError, match="no attention through 'ring'"):
        layers.attention("ring", q, q, q, window=4)
    with pytest.raises(ValueError, match="no scale"):
        layers.attention("xla", q, kv, kv, scale=0.5)
