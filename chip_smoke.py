#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dlrover_tpu still starts on
the chip.

One TPU v5e, no arguments (what the driver runs)::

    python3 chip_smoke.py

- phase ``build``: the native libraries, from source;
- phase ``step``: one process, no agent — GPT-2-XL at its published
  widths and full depth (48 layers, hidden 1600, 25 heads of 64, seq
  1024, batch 4), three steps on a fixed batch, loss falling;
- phase ``elastic``: through ``python -m dlrover_tpu.run`` — local
  master, elastic agent (with ``--network-check``), one worker that
  owns the chip, XL widths at the depth whose train state fits the
  chip twice, steps, a flash save to shared memory, SIGKILL of the
  worker from outside, respawn, restore from shared memory, steps.

``--chips 4`` runs only the sharded path (``auto_accelerate`` with
fsdp on a 4-device mesh, through ``tpurun``) and its one-device
comparison.  ``--toy`` is the CPU rehearsal at toy size.

This process never imports jax: a process that has touched jax holds
the chip.  Each phase is a child that exits before the next starts,
and the device in the last line is what the worker itself reported
from ``jax.devices()``.  Any phase that fails makes the exit code
non-zero; no chip found is a failure, never a CPU run.
"""

import argparse
import glob
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(ROOT, "examples", "train_xl_elastic.py")

# GPT-2-XL's published widths (the worker's xl_config)
HIDDEN, VOCAB, SEQ, XL_LAYERS = 1600, 50304, 1024, 48
HBM_BYTES = 16 * 2**30
# bf16 params + bf16 Adam mu + bf16 Adam nu
STATE_BYTES_PER_PARAM = 6
# offline compile for a described v5e (memory_analysis of the step
# program, examples/train_xl_elastic.py's recipe): temporaries of the
# 12-layer and the 48-layer step
STEP_TEMP_BYTES = {12: 3.4e9, 48: 6.2e9}
ELASTIC_LAYERS = 12
SAVE_AT = 3          # flash save after this step
STEPS_AFTER = 3      # steps the respawned worker must take
BF16_REL_TOL = 2e-2  # one bf16 ulp is 2^-8; losses are O(10)
# how long a child may look like its parent (between fork and exec)
# before the ownership watch counts it as a second holder of the chip
TWIN_GRACE_S = 1.0


class PhaseFailed(Exception):
    def __init__(self, reason, log=""):
        super().__init__(reason)
        self.reason = reason
        self.log = log


def say(line=""):
    print(line, flush=True)


def xl_params(layers):
    per_layer = 12 * HIDDEN * HIDDEN + 13 * HIDDEN
    return VOCAB * HIDDEN + SEQ * HIDDEN + layers * per_layer + 2 * HIDDEN


def tail(path, lines=40, width=300):
    """The end of a child's log, without the XLA:CPU loader's
    multi-kilobyte feature dumps."""
    try:
        with open(path, errors="replace") as f:
            text = f.read()
    except OSError as e:
        return f"<no log: {e}>"
    keep = [
        ln[:width] for ln in text.splitlines()
        if "cpu_aot_loader" not in ln
    ]
    return "\n".join(keep[-lines:])


def proc_stat(pid):
    """The fields of /proc/<pid>/stat after the comm — state, ppid,
    pgrp, session, ... — or None when the process is gone."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            return f.read().rsplit(b")", 1)[1].split()
    except (OSError, IndexError):
        return None


def session_pids(sid):
    """Live (non-zombie) processes of session ``sid``."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        stat = proc_stat(int(name))
        if stat and int(stat[3]) == sid and stat[0] != b"Z":
            out.append(int(name))
    return out


def kill_session(sid):
    for _ in range(50):
        pids = session_pids(sid)
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        time.sleep(0.1)


def holds_tpu(pid):
    """Whether ``pid`` has a TPU backend, seen from outside: libtpu is
    mapped into it (jax loads it when it creates the backend, never at
    import) or it holds an accelerator device node open."""
    try:
        with open(f"/proc/{pid}/maps", errors="replace") as f:
            if "libtpu" in f.read():
                return True
    except OSError:
        return False
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith("/dev/accel") or (
                target.startswith("/dev/vfio/")
                and target != "/dev/vfio/vfio"
            ):
                return True
    except OSError:
        pass
    return False


def cmdline(pid):
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return f.read()
    except OSError:
        return None


def fork_twin(pid, mapped):
    """A child between fork and exec (spawning a helper from a
    process with GBs mapped takes tens of milliseconds) still shows
    its parent's mappings and command line: its parent's twin, not a
    backend of its own — for as long as TWIN_GRACE_S."""
    stat = proc_stat(pid)
    return (
        stat is not None and int(stat[1]) in mapped
        and cmdline(pid) == cmdline(int(stat[1]))
    )


class OwnershipWatch(threading.Thread):
    """Samples, while a job runs, which of its processes have a TPU
    backend: at no time may two of them."""

    def __init__(self, sid, interval=0.05):
        super().__init__(daemon=True, name="ownership-watch")
        self.sid = sid
        self.interval = interval
        self.max_holders = 0
        self.holders_seen = set()
        self.twins_seen = set()
        self.violations = []
        self.samples = 0
        self._halt = threading.Event()

    def run(self):
        twin_since = {}
        while not self._halt.wait(self.interval):
            now = time.monotonic()
            mapped = [
                p for p in session_pids(self.sid) if holds_tpu(p)
            ]
            twin_since = {
                p: twin_since.get(p, now)
                for p in mapped if fork_twin(p, mapped)
            }
            # a twin that stays is a child that forked and never
            # exec'd: it inherited libtpu and the device's fds and
            # would hold the chip after its parent died — a holder
            holders = [
                p for p in mapped if p not in twin_since
                or now - twin_since[p] > TWIN_GRACE_S
            ]
            self.twins_seen.update(set(mapped) - set(holders))
            self.samples += 1
            self.holders_seen.update(holders)
            self.max_holders = max(self.max_holders, len(holders))
            if len(holders) > 1 and len(self.violations) < 5:
                self.violations.append((time.time(), holders))

    def stop(self):
        self._halt.set()
        self.join(timeout=5)


class Smoke:
    def __init__(self, args):
        self.args = args
        self.t0 = time.time()
        self.run_dir = os.path.join(
            ROOT, ".smoke_run", f"run{os.getpid()}"
        )
        os.makedirs(self.run_dir)
        # unix socket paths are limited to ~107 bytes: keep the IPC
        # directory short, under TMPDIR when that is short enough
        self.sock_dir = tempfile.mkdtemp(prefix="dlsmoke_")
        if len(self.sock_dir) > 60:
            shutil.rmtree(self.sock_dir, ignore_errors=True)
            self.sock_dir = tempfile.mkdtemp(
                prefix="dlsmoke_", dir="/tmp"
            )
        self.job = f"smoke{os.getpid()}"
        self.sessions = []
        self.device = None
        self.summary = []

    # -- children ----------------------------------------------------------

    def env(self, **extra):
        env = dict(os.environ)
        path = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = ROOT + (os.pathsep + path if path else "")
        env["DLROVER_SHARED_DIR"] = self.sock_dir
        env["DLROVER_JOB_NAME"] = self.job
        env["DLROVER_METRICS_FILE"] = os.path.join(
            self.run_dir, "metrics.json"
        )
        env["TPU_LOG_DIR"] = "disabled"
        if self.args.toy:
            # the CPU rehearsal, asked for by argument
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count="
                f"{self.args.chips}"
            )
        env.update(extra)
        return env

    def worker_args(self, phase, **kw):
        argv = [sys.executable, WORKER, "--phase", phase,
                "--seed", str(self.args.seed)]
        if self.args.toy:
            argv.append("--toy")
        for key, val in kw.items():
            argv += [f"--{key.replace('_', '-')}", str(val)]
        return argv

    def run_child(self, name, argv, timeout, env=None):
        log = os.path.join(self.run_dir, f"{name}.log")
        with open(log, "w") as f:
            proc = subprocess.Popen(
                argv, env=env or self.env(), cwd=ROOT, stdout=f,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            self.sessions.append(proc.pid)
            try:
                rc = proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                kill_session(proc.pid)
                proc.wait()
                raise PhaseFailed(
                    f"{name} did not finish in {timeout}s", log
                )
        if rc != 0:
            raise PhaseFailed(f"{name} exited with code {rc}", log)
        return log

    def read_report(self, path, log):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            raise PhaseFailed(f"no report from the worker: {e}", log)

    def note_device(self, device):
        want = self.args.chips
        if not self.args.toy and device.get("platform") != "tpu":
            raise PhaseFailed(
                f"the worker ran on {device}, not on a TPU"
            )
        if device.get("count") != want:
            raise PhaseFailed(
                f"the worker saw {device.get('count')} device(s), "
                f"the run was asked for {want}"
            )
        if self.device is not None and device != self.device:
            raise PhaseFailed(
                f"device changed between phases: {self.device} "
                f"then {device}"
            )
        self.device = device

    # -- phases --------------------------------------------------------------

    def phase_build(self):
        build_dir = os.path.join(
            ROOT, "dlrover_tpu", "native", "_build"
        )
        before = set(glob.glob(os.path.join(build_dir, "*.so")))
        log = self.run_child(
            "build", [sys.executable, "-m", "dlrover_tpu.native"], 300
        )
        with open(log) as f:
            for line in f:
                if line.startswith(("compiler:", "built ")):
                    say("  " + line.rstrip())
        after = set(glob.glob(os.path.join(build_dir, "*.so")))
        say(f"  libraries built now: {len(after - before)}, "
            f"found already built: {len(after & before)}")
        check = (
            "from dlrover_tpu.ops import fastcopy; import sys; "
            "sys.exit(0 if fastcopy.native_available() else 3)"
        )
        self.run_child(
            "fastcopy", [sys.executable, "-c", check], 120
        )
        say("  native fastcopy: loaded")

    def phase_step(self):
        layers = 2 if self.args.toy else XL_LAYERS
        report_path = os.path.join(self.run_dir, "step.json")
        log = self.run_child(
            "step",
            self.worker_args(
                "step", layers=layers, steps=3, report=report_path
            ),
            900,
        )
        rep = self.read_report(report_path, log)
        self.note_device(rep["device"])
        losses = rep["losses"]
        say(f"  device: {rep['device']}")
        say(f"  model: {rep['layers']} layers, "
            f"{rep['params'] / 1e9:.3f} B params")
        say(f"  losses: {[round(x, 4) for x in losses]}")
        say(f"  step seconds: "
            f"{[round(x, 3) for x in rep['step_seconds']]}, "
            f"compile {rep['compile_s']:.1f} s")
        say(f"  program bytes: {rep['program_bytes']}")
        say(f"  peak device memory: {rep['peak_bytes']}")
        say(f"  compile cache: {rep['cache_dir']} entries "
            f"{rep['cache_entries_before']} -> "
            f"{rep['cache_entries_after']}")
        if any(x != x or abs(x) == float("inf") for x in losses):
            raise PhaseFailed(f"loss not finite: {losses}", log)
        if len(losses) < 3 or not losses[-1] < losses[0]:
            raise PhaseFailed(f"loss did not fall: {losses}", log)
        if self.args.toy:
            say("  kernels: interpreted (toy rehearsal on the CPU)")
        else:
            if rep["kernels"] < 1:
                raise PhaseFailed(
                    "no tpu_custom_call in the step program: the "
                    "Pallas flash attention is not in it", log,
                )
            say(f"  kernels: {rep['kernels']} tpu_custom_call in "
                "the step program")
            if rep["layers"] != XL_LAYERS:
                raise PhaseFailed("not the full-depth model", log)
        self.summary.append(
            f"step: {rep['layers']} layers, loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f}, peak {rep['peak_bytes']}"
        )

    def elastic_depth(self):
        """The depth at which TWO copies of the train state (the live
        one and the flash save's on-device snapshot, or a live restore
        target) plus the step's temporaries fit the chip, and the
        state fits /dev/shm."""
        shm_free = shutil.disk_usage("/dev/shm").free
        say(f"  /dev/shm free: {shm_free} bytes")
        if self.args.toy:
            say("  depth: 2 layers (toy rehearsal)")
            return 2
        for layers in (XL_LAYERS, ELASTIC_LAYERS):
            state = xl_params(layers) * STATE_BYTES_PER_PARAM
            need = 2 * state + STEP_TEMP_BYTES[layers]
            verdict = "fits" if need < HBM_BYTES else "does NOT fit"
            say(f"  depth {layers}: state {state / 1e9:.2f} GB x 2 + "
                f"temporaries {STEP_TEMP_BYTES[layers] / 1e9:.1f} GB"
                f" = {need / 1e9:.1f} GB, {verdict} "
                f"{HBM_BYTES / 1e9:.1f} GB of HBM")
        state = xl_params(ELASTIC_LAYERS) * STATE_BYTES_PER_PARAM
        if shm_free < 1.5 * state:
            raise PhaseFailed(
                f"/dev/shm has {shm_free} bytes free, the "
                f"{state}-byte state does not fit"
            )
        say(f"  depth: {ELASTIC_LAYERS} layers — the full 48 cannot "
            "take the default flash save (on-device snapshot) or a "
            "live restore target on 16 GB")
        return ELASTIC_LAYERS

    def launch_tpurun(self, name, worker_argv, extra_args=()):
        log = os.path.join(self.run_dir, f"{name}.log")
        events = os.path.join(self.run_dir, f"{name}.events.jsonl")
        argv = [
            sys.executable, "-m", "dlrover_tpu.run",
            "--nproc_per_node=1", "--max_restarts=2",
            "--monitor_interval=0.5", *extra_args,
            *worker_argv[1:],
        ]
        logf = open(log, "w")
        proc = subprocess.Popen(
            argv, cwd=ROOT, stdout=logf, stderr=subprocess.STDOUT,
            start_new_session=True,
            env=self.env(
                DLROVER_EVENT_LOG=events,
                # the agent's monitors (diagnosis collectors among
                # them) report every few seconds, not every minute
                DLROVER_MONITOR_REPORT_INTERVAL="1",
            ),
        )
        logf.close()
        self.sessions.append(proc.pid)
        return proc, log, events

    @staticmethod
    def read_events(path):
        out = []
        try:
            with open(path) as f:
                for line in f:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        pass  # a line still being written
        except OSError:
            pass
        return out

    def wait_events(self, path, proc, log, what, pred, timeout):
        """Poll the job's event log until ``pred(events)`` is truthy."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            found = pred(self.read_events(path))
            if found:
                return found
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"tpurun exited with code {proc.returncode} "
                    f"while waiting for {what}", log,
                )
            time.sleep(0.05)
        raise PhaseFailed(f"timed out waiting for {what}", log)

    def phase_elastic(self):
        layers = self.elastic_depth()
        ckpt_dir = os.path.join(self.run_dir, "ckpt")
        until = os.path.join(self.run_dir, "stop")
        argv = self.worker_args(
            "elastic", layers=layers, steps=100000,
            ckpt_dir=ckpt_dir, save_at=SAVE_AT, until_file=until,
        )
        if self.args.toy:
            # XLA:CPU cannot reload a serialized executable that
            # holds interpreted Pallas kernels; the respawn's AOT
            # path is rehearsed with the XLA attention
            say("  toy: XLA attention in this phase")
            argv.append("--xla-attention")
        proc, log, evpath = self.launch_tpurun(
            "elastic", argv, extra_args=("--network-check",),
        )
        watch = OwnershipWatch(proc.pid)
        watch.start()
        try:
            self._drive_elastic(proc, log, evpath, until, watch)
        finally:
            watch.stop()
            if proc.poll() is None:
                kill_session(proc.pid)
                proc.wait()

    def _drive_elastic(self, proc, log, evpath, until, watch):
        def of(events, type_, **match):
            return [
                e for e in events if e.get("type") == type_ and all(
                    e.get(k) == v for k, v in match.items()
                )
            ]

        def wait(what, pred, timeout):
            return self.wait_events(
                evpath, proc, log, what, pred, timeout
            )

        first = wait(
            "the first worker to report its backend",
            lambda ev: of(ev, "worker_backend", restart_count=0),
            300,
        )[0]
        self.note_device({
            k: first[k] for k in ("platform", "kind", "count")
        })
        say(f"  device: {self.device} (worker pid {first['pid']})")

        # steps, the flash save in shared memory, one more step
        def saved_and_stepped(ev):
            return of(
                ev, "checkpoint_shm_save", step=SAVE_AT
            ) and of(
                ev, "train_step", step=SAVE_AT + 1, restart_count=0
            )

        before = wait(
            f"the flash save of step {SAVE_AT} and step "
            f"{SAVE_AT + 1}", saved_and_stepped, 600,
        )
        ref_loss = before[0]["loss"]
        os.kill(first["pid"], signal.SIGKILL)
        t_kill = time.time()
        say(f"  SIGKILL worker pid {first['pid']} after step "
            f"{SAVE_AT + 1} (loss {ref_loss:.6f}); flash save of "
            f"step {SAVE_AT} is in shared memory")

        second = wait(
            "the respawned worker to report its backend",
            lambda ev: of(ev, "worker_backend", restart_count=1),
            300,
        )[0]
        if second["pid"] == first["pid"]:
            raise PhaseFailed("the worker was not replaced", log)
        last_step = SAVE_AT + STEPS_AFTER
        after = wait(
            f"step {last_step} of the respawned worker",
            lambda ev: of(
                ev, "train_step", step=last_step, restart_count=1
            ) and ev,
            600,
        )
        say(f"  respawned worker pid {second['pid']} on "
            f"{second['platform']} reached step {last_step} "
            f"{time.time() - t_kill:.1f} s after the kill")
        with open(until, "w"):
            pass
        try:
            rc = proc.wait(timeout=600)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("the agent did not exit", log)
        events = self.read_events(evpath)

        # -- what must hold ---------------------------------------------
        if rc != 0:
            raise PhaseFailed(f"the agent exited with code {rc}", log)
        restarts = of(events, "worker_restart")
        if len(restarts) != 1 or restarts[0]["restart_count"] != 1:
            raise PhaseFailed(
                f"expected exactly one restart, saw {restarts}", log
            )
        restores = [
            e for e in of(events, "checkpoint_restore")
            if e["pid"] == second["pid"]
        ]
        if len(restores) != 1:
            raise PhaseFailed(
                f"expected one restore, saw {restores}", log
            )
        restore = restores[0]
        if restore["tier"] != "shm" or restore["step"] != SAVE_AT:
            raise PhaseFailed(
                f"restore came from {restore['tier']} at step "
                f"{restore['step']}, not from shared memory at step "
                f"{SAVE_AT}", log,
            )
        resumed = of(
            after, "train_step", step=SAVE_AT + 1, restart_count=1
        )
        if not resumed:
            raise PhaseFailed(
                f"the respawned worker never logged step "
                f"{SAVE_AT + 1}", log,
            )
        got = resumed[0]["loss"]
        if abs(got - ref_loss) > BF16_REL_TOL * abs(ref_loss):
            raise PhaseFailed(
                f"loss at step {SAVE_AT + 1} after the restore is "
                f"{got}, the killed worker had {ref_loss}", log,
            )
        say(f"  restore: step {restore['step']} from tier "
            f"{restore['tier']} in {restore.get('total_s')} s; loss "
            f"at step {SAVE_AT + 1}: {got:.6f} after restore vs "
            f"{ref_loss:.6f} before the kill")
        persisted = of(events, "checkpoint_persist", ok=True)
        if not [e for e in persisted if e["step"] == SAVE_AT]:
            raise PhaseFailed(
                f"the agent never persisted step {SAVE_AT}", log
            )
        say(f"  agent-side persist to disk: steps "
            f"{sorted({e['step'] for e in persisted})}")
        if not of(events, "node_check"):
            raise PhaseFailed("the node check never ran", log)
        check = of(events, "node_check")[0]
        if check["pid"] in (proc.pid, first["pid"]) or (
            check["ts"] > first["ts"]
        ):
            raise PhaseFailed(
                "the node check did not run in a child of its own "
                "that ended before the first worker", log,
            )
        say(f"  node check: child pid {check['pid']}, "
            f"{check['elapsed_s']} s, before the first worker")
        cache = of(events, "compile_cache", restart_count=1)
        cold = of(events, "compile_cache", restart_count=0)
        say(f"  step executable, first worker: "
            f"{cold[0]['status'] if cold else 'not reported'}; "
            f"respawned worker: "
            f"{cache[0]['status'] if cache else 'not reported'}")
        if not cache:
            raise PhaseFailed(
                "the respawned worker reported no compile-cache "
                "status", log,
            )
        say(f"  compile cache: {cache[0]['dir']}, "
            f"{cache[0]['entries_after']} XLA entries, "
            f"{cache[0]['aot_entries']} AOT entries")
        try:
            with open(os.path.join(self.run_dir, "metrics.json")) as f:
                chip = json.load(f).get("chip_metrics", "")
        except (OSError, ValueError):
            chip = ""
        say(f"  device memory (the worker's last metrics record): "
            f"{chip or 'not reported by this backend'}")
        if not self.args.toy and not chip:
            raise PhaseFailed(
                "the worker's metrics file carries no chip metrics",
                log,
            )

        # -- one process per chip -----------------------------------------
        say(f"  chip ownership: {watch.samples} samples, at most "
            f"{watch.max_holders} process(es) with a TPU backend at "
            f"once; ever: {sorted(watch.holders_seen)} (children "
            f"seen between fork and exec: "
            f"{sorted(watch.twins_seen - watch.holders_seen)})")
        if watch.violations:
            raise PhaseFailed(
                f"two processes of the job had a TPU backend at "
                f"once: {watch.violations}", log,
            )
        if proc.pid in watch.holders_seen:
            raise PhaseFailed(
                "the agent process itself had a TPU backend", log
            )
        if not self.args.toy:
            for pid in (first["pid"], second["pid"]):
                if pid not in watch.holders_seen:
                    raise PhaseFailed(
                        f"worker {pid} was never seen with a TPU "
                        "backend: the ownership watch is blind", log,
                    )
        self.summary.append(
            f"elastic: {restore['step']} restored from "
            f"{restore['tier']}, respawn "
            f"{cache[0]['status']}, one restart, agent rc 0"
        )

    def phase_sharded(self):
        layers = 2 if self.args.toy else ELASTIC_LAYERS
        say(f"  depth: {layers} layers; mesh over "
            f"{self.args.chips} devices")
        report_path = os.path.join(self.run_dir, "sharded.json")
        kw = dict(
            layers=layers, steps=3, report=report_path,
            ckpt_dir=os.path.join(self.run_dir, "ckpt4"),
        )
        argv = self.worker_args("sharded", **kw)
        proc, log, evpath = self.launch_tpurun("sharded", argv)
        try:
            rc = proc.wait(timeout=1500)
        except subprocess.TimeoutExpired:
            kill_session(proc.pid)
            proc.wait()
            raise PhaseFailed("the sharded run did not finish", log)
        if rc != 0:
            raise PhaseFailed(f"the agent exited with code {rc}", log)
        rep = self.read_report(report_path, log)
        self.note_device(rep["device"])
        say(f"  device: {rep['device']}; mesh {rep['mesh']}")
        say(f"  strategy {rep['strategy']}; attention "
            f"{rep['attention']}")
        say(f"  step program: {rep['kernels']} tpu_custom_call, "
            f"{rep['all_gathers']} all-gather")
        say(f"  sharded losses:    "
            f"{[round(x, 4) for x in rep['losses']]}")
        say(f"  one-device losses: "
            f"{[round(x, 4) for x in rep['ref_losses']]}")
        total = rep["param_bytes"]
        shares = {
            dev: round(b / total, 4)
            for dev, b in rep["param_bytes_per_device"].items()
        }
        say(f"  parameter bytes: {total}; share per device: {shares}")
        say(f"  restore: step {rep['restored_step']} from tier "
            f"{rep['restore_tier']}, identical="
            f"{rep['restore_identical']}, same sharding="
            f"{rep['restore_same_sharding']}")
        say(f"  peak device memory: {rep['peak_bytes']}")
        n = self.args.chips
        if len(shares) != n or any(
            abs(s - 1 / n) > 0.1 / n + 0.02 for s in shares.values()
        ):
            raise PhaseFailed(
                f"parameters are not spread over {n} devices: "
                f"{shares}", log,
            )
        for a, b in zip(rep["losses"], rep["ref_losses"]):
            if abs(a - b) > BF16_REL_TOL * abs(b):
                raise PhaseFailed(
                    f"sharded and one-device losses differ: "
                    f"{rep['losses']} vs {rep['ref_losses']}", log,
                )
        if not rep["losses"][-1] < rep["losses"][0]:
            raise PhaseFailed(f"loss did not fall: {rep['losses']}")
        if rep["restore_tier"] != "shm" or rep["restored_step"] != 3:
            raise PhaseFailed(
                f"restore came from {rep['restore_tier']} at "
                f"{rep['restored_step']}", log,
            )
        if not (rep["restore_identical"]
                and rep["restore_same_sharding"]):
            raise PhaseFailed(
                "the restore did not come back identical in the "
                "sharded placement", log,
            )
        if not self.args.toy and rep["kernels"] < 1:
            raise PhaseFailed(
                "no tpu_custom_call in the sharded step program", log
            )
        self.summary.append(
            f"sharded: {n} devices, shares {sorted(shares.values())}"
        )

    # -- the run ---------------------------------------------------------------

    def cleanup(self):
        for sid in self.sessions:
            kill_session(sid)
        for path in glob.glob(f"/dev/shm/*{self.job}*"):
            try:
                os.unlink(path)
            except OSError:
                pass
        shutil.rmtree(self.sock_dir, ignore_errors=True)
        if not self.args.keep:
            shutil.rmtree(self.run_dir, ignore_errors=True)

    def run(self):
        phases = (
            ["build", "sharded"] if self.args.chips == 4
            else ["build", "step", "elastic"]
        )
        failure = None
        seconds = {}
        try:
            for name in phases:
                say(f"== phase {name}")
                t0 = time.time()
                try:
                    getattr(self, f"phase_{name}")()
                except PhaseFailed as e:
                    failure = (name, e)
                    break
                finally:
                    seconds[name] = round(time.time() - t0, 1)
                    say(f"  phase {name}: {seconds[name]} s")
        finally:
            log_tail = ""
            if failure and failure[1].log:
                log_tail = tail(failure[1].log)
            self.cleanup()
        say("== summary")
        for line in self.summary:
            say("  " + line)
        say(f"  seconds per phase: {seconds}; total "
            f"{time.time() - self.t0:.1f} s")
        if failure is None and "jax" in sys.modules:
            failure = ("smoke", PhaseFailed(
                "chip_smoke.py's own process imported jax"
            ))
        if failure is None and self.device is None:
            failure = ("smoke", PhaseFailed("no device was reported"))
        if failure is not None:
            name, err = failure
            say(f"FAILED in phase {name}: {err.reason}")
            if log_tail:
                say(f"-- end of the {name} log:")
                say(log_tail)
            say(f"chip_smoke: FAILED in phase {name}: {err.reason}")
            return 1
        say(json.dumps({"ok": True, "device": self.device}))
        return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--toy", action="store_true",
                    help="CPU rehearsal at toy size")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run directory (logs, events)")
    args = ap.parse_args()
    if not os.path.exists(WORKER):
        say(f"chip_smoke: FAILED: {WORKER} is missing — this is not "
            "a checkout of dlrover_tpu")
        return 1
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
