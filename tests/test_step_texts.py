"""The lowered text of every decoder family's toy training loss,
pinned in ONE table: a change to a layer that several families share
(the held experts' layer, the grouped-matmul kernels, the flash
kernels, latent attention, the chunked head, the convolutions, the
delta rules' helpers) that was meant for one family shows here in
every other family whose program it moved, and a change that was
meant for all of them re-pins this one file."""

import hashlib
import importlib
import pkgutil

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

import dlrover_tpu.models  # noqa: E402
from dlrover_tpu.models.gpt import (  # noqa: E402
    GPT,
    GPTConfig,
    cross_entropy_loss,
)

# sha256 (16 hex digits) of the lowered text of value and gradient of
# the family's loss: its ``tiny`` configuration with the named
# attention, remat on, 2 x 64 tokens, ``num_chunks=4``.  ``laguna``
# with XLA attention is the text the window layers' plain form is in,
# ``sarvam_mla`` with XLA attention the one in which remat names
# nothing; ``gpt`` is the loss its two cells run
# (``benchmarks/models/gpt2.py::build``).
PINS = {
    ("bailing_hybrid", "flash"): "aff0de98befe6952",  # PR 65's tree
    ("gpt", "flash"): "d1d2b7b0b9ff02ee",  # PR 61's tree
    ("jamba", "flash"): "de7e5764941d4967",  # PR 68's tree
    ("laguna", "flash"): "322e01c44b37bb48",  # PR 61's tree
    ("laguna", "xla"): "4b80b17fe2120d48",  # PR 58's tree
    ("lfm2_moe", "flash"): "6554d5ab6a5a2c6b",  # PR 63's tree
    ("mimo_v2", "flash"): "899ec9a23ed03093",  # PR 58's tree
    ("motif", "flash"): "9a4b1e6bb3da9798",  # PR 58's tree
    ("nemotron_h", "flash"): "b0da0cfa3f14116c",  # PR 65's tree
    ("olmo_hybrid", "flash"): "0a5c5afab24c6619",  # PR 65's tree
    ("olmoe", "flash"): "6ca9f44ed37f52b2",  # PR 58's tree
    ("ouro", "flash"): "f1eaf19b556d3257",  # PR 61's tree
    ("sarvam_mla", "flash"): "1f4eb1e29d808c63",  # PR 58's tree
    ("sarvam_mla", "xla"): "3d4a8fb99a25444f",  # PR 61's tree
}


def tiny_configuration(module):
    """The class of ``module`` that has a ``tiny`` configuration, or
    ``None``."""
    for value in vars(module).values():
        if (
            isinstance(value, type) and value.__module__ == module.__name__
            and hasattr(value, "tiny")
        ):
            return value
    return None


def toy_loss(family, attention):
    """``(model, loss_fn, has_aux)`` of the family's tiny
    configuration with remat on."""
    if family == "gpt":
        model = GPT(GPTConfig.tiny(attention_impl=attention, remat=True))

        def loss_fn(params, batch):
            logits = model.apply({"params": params}, batch["x"])
            return cross_entropy_loss(logits, batch["y"])

        return model, loss_fn, False
    module = importlib.import_module(f"dlrover_tpu.models.{family}")
    config = tiny_configuration(module)
    model = getattr(module, config.__name__.removesuffix("Config"))(
        config.tiny(attention_impl=attention, remat=True)
    )
    make = getattr(module, f"make_{family}_loss")
    return model, make(model, num_chunks=4), True


def step_text(family, attention):
    model, loss_fn, has_aux = toy_loss(family, attention)
    params = jax.eval_shape(
        lambda key: model.init_params(key, seq_len=64),
        jax.random.PRNGKey(0),
    )
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32) for k in "xy"}
    return jax.jit(jax.value_and_grad(loss_fn, has_aux=has_aux)).lower(
        params, batch
    ).as_text()


@pytest.mark.parametrize("family, attention", list(PINS))
def test_a_familys_loss_lowers_to_the_text_it_did(family, attention):
    text = step_text(family, attention)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        PINS[family, attention]
    )


def test_every_family_with_a_toy_loss_has_a_pin():
    """A module of ``dlrover_tpu/models/`` with a ``tiny``
    configuration and a ``make_<family>_loss`` is a decoder family
    that shares layers with the others: it has a line above."""
    families = set()
    for found in pkgutil.iter_modules(dlrover_tpu.models.__path__):
        module = importlib.import_module(
            f"dlrover_tpu.models.{found.name}"
        )
        if tiny_configuration(module) and hasattr(
            module, f"make_{found.name}_loss"
        ):
            families.add(found.name)
    assert len(families) >= 9
    pinned = {family for family, _ in PINS}
    assert families <= pinned, families - pinned
