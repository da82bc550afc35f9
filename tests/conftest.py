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
        "test_bench_guard.py",
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
    early = [
        it for it in items
        if it.nodeid.split("::", 1)[0].endswith(early_files)
    ]
    if early:
        rest = [it for it in items if it not in early]
        items[:] = early + rest
