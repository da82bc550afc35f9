"""``chip_smoke.py``'s one-owner watch, without a chip: the /proc
scan is the smoke's own, only "has a TPU backend" is stood in for
(no process here has one)."""

import importlib.util
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a "worker" that forks a child which never execs (what a
# multiprocessing fork helper is) or execs at once (a subprocess)
_FORKS = """
import os, sys, time
if os.fork() == 0:
    if sys.argv[1] == "exec":
        os.execv(sys.executable, [sys.executable, "-c",
                                  "import time; time.sleep(60)"])
    time.sleep(60)
    os._exit(0)
time.sleep(60)
"""


@pytest.fixture()
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # stand-in for "libtpu is mapped": the worker's own code, which a
    # forked child inherits and an exec'd one drops
    monkeypatch.setattr(
        mod, "holds_tpu",
        lambda pid: b"os.fork()" in (mod.cmdline(pid) or b""),
    )
    monkeypatch.setattr(mod, "TWIN_GRACE_S", 0.3)
    return mod


@pytest.mark.parametrize("child,holders", [("stay", 2), ("exec", 1)])
def test_ownership_watch_bounds_the_fork_twin_exemption(
    smoke, child, holders
):
    """A child between fork and exec looks like its parent and is not
    a second owner; one that never execs keeps the parent's device
    fds and is — the orphan that would hold the chip after a
    SIGKILL."""
    worker = subprocess.Popen(
        [sys.executable, "-c", _FORKS, child], start_new_session=True
    )
    watch = smoke.OwnershipWatch(worker.pid, interval=0.02)
    watch.start()
    try:
        deadline = time.time() + 30
        while time.time() < deadline and not (
            watch.max_holders >= holders and watch.samples > 40
        ):
            time.sleep(0.05)
    finally:
        watch.stop()
        smoke.kill_session(worker.pid)
        worker.wait()
    assert watch.max_holders == holders
    assert bool(watch.violations) == (holders > 1)
    assert worker.pid in watch.holders_seen
