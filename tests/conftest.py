"""Test harness config: run JAX on a virtual 8-device CPU platform so
multi-chip sharding logic is exercised without TPU hardware (same trick
the driver's dryrun uses)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the tests never need a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DLROVER_LOG_LEVEL", "WARNING")

# per-run IPC/shm namespace so a test run never clobbers the shm
# segments of a concurrently running job (e.g. the driver's bench)
import tempfile  # noqa: E402

os.environ["DLROVER_SHARED_DIR"] = os.path.join(
    tempfile.mkdtemp(prefix="dlrover_test_"), "sockets"
)

# jax may have been imported (and have read its environment) before
# this file: pin the platform through the config API too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import functools  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def checkout(tmp_path):
    """A directory that stands for the checkout in a test that runs a
    whole job through ``benchmarks/run.py``: the program and the
    benchmark symlinked into it.  ``run.py`` fixes its compile cache
    and its run directories at its own checkout's root
    (``.jax_cache``, ``.bench_out``), so from here they are the
    test's own and no entry of another tree or another test is met
    (ROADMAP B7: four such tests read rc 1 on the driver's machine on
    a cache an earlier tree had filled)."""
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("benchmarks", "dlrover_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(REPO, name), root / name)
    return str(root)


# what ``jax.checkpoint`` binds, as the enclosing equation of a
# rematted block's second copy; an internal name like those below
REMAT_PRIMITIVE = "remat2"


def jax_internal(module, name):
    """``jax._src.<module>.<name>``.  The tests of what a rematted
    block keeps read what jax has no public API for: a jaxpr's
    sub-jaxprs (``core.jaxprs_in_params``), one equation's text
    (``core.pp_eqn``), what a ``checkpoint`` saves
    (``ad_checkpoint.saved_residuals``).  Written against jax 0.9.0:
    where an upgrade moves one, this says so and the test does not
    pass on something else."""
    import importlib

    try:
        return getattr(importlib.import_module(f"jax._src.{module}"), name)
    except (ImportError, AttributeError) as missing:
        pytest.fail(
            f"jax {jax.__version__} has no jax._src.{module}.{name} "
            f"({missing}); tests/conftest.py::jax_internal was written "
            "against jax 0.9.0.  Find its successor, then check that "
            f"jax.checkpoint still binds {REMAT_PRIMITIVE!r} and that "
            "test_a_saved_residual_costs_no_pass_over_it's control "
            "still finds the reduce_precision that "
            "ops/flash_attention.py::_named exists to avoid."
        )


def equations(jaxpr, under=()):
    """``(names of the enclosing equations' primitives, equation)`` of
    every equation of a jaxpr, sub-jaxprs included; a ``pallas_call``
    is one equation (a kernel's body is not the program's)."""
    jaxprs_in_params = jax_internal("core", "jaxprs_in_params")

    for eqn in jaxpr.eqns:
        yield under, eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jaxprs_in_params(eqn.params):
                yield from equations(sub, under + (eqn.primitive.name,))


def primitives_under(jaxpr, scope):
    """``{primitive: how often}`` of the equations that stand under
    the named scope ``scope`` of a jaxpr (:func:`equations`' walk)."""
    found = {}
    for _, eqn in equations(jaxpr):
        if scope in str(eqn.source_info.name_stack).split("/"):
            name = eqn.primitive.name
            found[name] = found.get(name, 0) + 1
    return found


def pallas_calls(jaxpr):
    """``(under, equation)`` of every ``pallas_call`` of a jaxpr."""
    return (
        (under, eqn) for under, eqn in equations(jaxpr)
        if eqn.primitive.name == "pallas_call"
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fill_past(x, tiles_used, fill):
    """``x`` with ``fill`` in the rows of the tiles from
    ``tiles_used`` on (``ops/grouped_matmul.py``'s layout), and the
    same done to its cotangent."""
    from dlrover_tpu.ops.grouped_matmul import ROW_TILE

    past = jnp.arange(x.shape[0]) >= tiles_used[0] * ROW_TILE
    return jnp.where(past[:, None], jnp.asarray(fill, x.dtype), x)


fill_past.defvjp(
    lambda x, tiles_used, fill: (fill_past(x, tiles_used, fill), tiles_used),
    lambda fill, tiles_used, g: (fill_past(g, tiles_used, fill), None),
)


def fill_inside_an_expert(monkeypatch, fill, names):
    """What ``grouped_expert`` keeps to itself, overwritten past
    ``tiles_used`` too: every row operand and every result of each of
    its kernels' calls (the hidden rows, the pre-activations the
    forward rule keeps, their gradients, the cotangents the matrices'
    gradients read).  ``names`` gets each call's kernel."""
    from dlrover_tpu.ops import grouped_matmul as gmm

    walk, to_weights = gmm._gmm, gmm._tgmm

    def filled(x, tiles_used):
        return x if fill is None else fill_past(x, tiles_used, fill)

    def _gmm(lhs, rhs, tile_group, tiles_used, *, beside=(), **how):
        names.append(how["name"])
        results = walk(
            [filled(x, tiles_used) for x in lhs], rhs, tile_group,
            tiles_used, beside=[filled(x, tiles_used) for x in beside],
            **how,
        )
        return [filled(x, tiles_used) for x in results]

    def _tgmm(rows, cotangent, tile_group, tiles_used, **how):
        names.append("gmm_drhs")
        return to_weights(
            filled(rows, tiles_used), filled(cotangent, tiles_used),
            tile_group, tiles_used, **how,
        )

    monkeypatch.setattr(gmm, "_gmm", _gmm)
    monkeypatch.setattr(gmm, "_tgmm", _tgmm)


def flash_forwards(jaxpr):
    """Where a jaxpr calls the flash FORWARD kernel: the primitives
    round each call.  The three flash kernels carry no name; the
    forward is the one that writes ``lse`` (float32 ``[b h, 1, s]``)
    beside its output."""
    return [
        under for under, eqn in pallas_calls(jaxpr)
        if eqn.params["name"] is None and len(eqn.outvars) == 2
        and eqn.outvars[1].aval.shape[1] == 1
    ]


def kernel_calls(jaxpr, name):
    """Where a jaxpr calls the Pallas kernel ``name``: the primitives
    round each call."""
    return [
        under for under, eqn in pallas_calls(jaxpr)
        if eqn.params["name"] == name
    ]


@pytest.fixture
def remat_keeps_what_flash_reads():
    """``check(ours, parents, forwards, calls=flash_forwards)``:
    ``jax.value_and_grad`` of the loss of a rematted model with flash
    attention, whose blocks take their policy from
    ``models/layers.py::remat_policy`` (``ours``: its jaxpr and the
    values of its jitted call), calls the forward kernel (the calls
    ``calls`` finds in a jaxpr: a recurrent rule's forward by its
    name) ``forwards`` times, never under a ``checkpoint``; with the
    parent's policy (``parents``; ``None``: keep nothing) each runs a
    second time there, and loss and every gradient leaf are the same
    numbers bit for bit."""
    import numpy as np

    def check(ours, parents, forwards, calls=flash_forwards):
        jaxpr, kept = ours
        where = calls(jaxpr)
        assert len(where) == forwards, where
        assert not any(REMAT_PRIMITIVE in under for under in where), where
        jaxpr, again = parents
        where = calls(jaxpr)
        assert len(where) == 2 * forwards, where
        assert sum(
            REMAT_PRIMITIVE in under for under in where
        ) == forwards, (where, REMAT_PRIMITIVE, jax.__version__)
        for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(again)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    return check


@pytest.fixture
def remat_with_xla_attention_is_the_parents(monkeypatch):
    """``check(loss, params)``: with XLA attention the names
    do not occur, and ``jax.grad(loss)`` is the jaxpr it is under the
    parent's policy (``None``), the ``policy=`` parameter apart."""
    import re

    from dlrover_tpu.models import layers

    def text(loss, params):
        # a function of its own: a trace is cached by its function
        return re.sub(r"policy=[^\n]*", "policy=", str(
            jax.make_jaxpr(jax.grad(lambda p: loss(p)))(params)
        ))

    def check(loss, params):
        ours = text(loss, params)
        assert "policy=" in ours and "name=flash" not in ours
        monkeypatch.setattr(layers, "remat_policy", lambda name: None)
        assert ours == text(loss, params)
        assert layers.remat_policy("full") is None

    return check


def pytest_configure(config):
    """Register the suite's custom markers (no pytest.ini in this
    repo): ``chaos`` tags fault-injection tests so they are runnable
    as a family (``-m chaos``); ``slow`` tags long scenarios tier-1
    excludes (the verify command runs ``-m 'not slow'``)."""
    config.addinivalue_line(
        "markers", "chaos: chaos fault-injection tests"
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 verify",
    )


def pytest_collection_modifyitems(config, items):
    """Run the stdlib-only telemetry + chaos unit tests AND the
    restore-pipeline equivalence tests before the jit/e2e
    heavyweights.  On a slow box a wall-clock-bounded CI window can
    truncate the (alphabetical) tail of the suite; these tests cost
    milliseconds-to-seconds, must never be the ones dropped (every
    other subsystem records through the registry/hooks they verify;
    the restore tests are the bit-identity net under the checkpoint
    recovery path), and are side-effect-free first (fresh registry/
    exporter/injector/engine instances, cleaned up by their own
    fixtures)."""
    early_files = (
        "test_telemetry.py", "test_otlp.py", "test_timeline.py",
        "test_goodput_ledger.py", "test_event_lint.py",
        "test_deep_diagnosis.py", "test_gcp_monitoring.py",
        "test_chaos.py",
        "test_restore_pipeline.py", "test_master_journal.py",
        "test_resize.py", "test_sparse_checkpoint.py",
        "test_serving.py", "test_serving_router.py",
        "test_streaming_sparse.py",
        "test_recovery.py", "test_aot_cache.py",
        "test_slo.py", "test_fleet.py", "test_rl_elastic.py",
        # the chaos acceptance e2e runs (worker kill, shm fallback,
        # master kill/restart) are the recovery regression net — a
        # truncated window must drop jit heavyweights, not these
        "test_chaos_e2e.py",
    )
    # ... and then the files measured longest, longest first (junit
    # of the driver's command, PR 61: 310 down to 100 s each; the
    # offline compiles among them use every core, so that none of them
    # is left for the run's end):
    # ``--dist loadfile`` hands whole files out in this order, and a
    # long file that starts late is the one the run ends on
    long_files = (
        "test_motif_tpu.py", "test_motif.py", "test_tpu_compile.py",
        "test_nemotron_h.py", "test_laguna_bench.py", "test_rl.py",
        "test_bailing_hybrid_tpu.py", "test_motif_bench.py",
        "test_moe_held_index.py", "test_bailing_hybrid.py",
        "test_sarvam_mla.py", "test_pipeline.py",
        "test_flash_attention_walk.py", "test_laguna.py", "test_moe_held.py",
        "test_laguna_tpu.py", "test_mimo_v2.py", "test_sarvam_mla_bench.py",
        "test_remat_residuals.py", "test_sarvam_mla_tpu.py", "test_llama.py",
        "test_accelerate.py", "test_flash_attention.py", "test_olmoe.py",
        "test_ouro.py", "test_bailing_hybrid_bench.py",
        "test_nemotron_h_tpu.py", "test_olmo_hybrid.py", "test_mimo_v2_tpu.py",
        "test_losses.py", "test_step_texts.py", "test_gated_delta_rule.py",
        "test_moe_held_tpu.py", "test_olmo_hybrid_tpu.py",
    )

    place = {name: 1 + n for n, name in enumerate(long_files)}

    def rank(item):
        path = item.nodeid.split("::", 1)[0]
        if path.endswith(early_files):
            return 0
        return place.get(os.path.basename(path), 1 + len(long_files))

    items.sort(key=rank)  # (stable: a file's tests keep their order)
