#!/usr/bin/env python3
"""The benchmark's one command::

    python benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell, through the path a user runs: ``python -m
dlrover_tpu.run --nproc_per_node=1 ... benchmarks/worker.py`` (local
master, elastic agent, one worker that owns the chip).  This process
never imports jax (a parent that touched jax holds the chip); the
launcher, the event polling and the session kill are copies of
``chip_smoke.py``'s.

A cell is data.  ``--workload`` names an entry of ``workloads`` in
``BENCHMARK.json`` (or of the file given with ``--cells``); from it:

- the configuration: ``configs[<config>].file``, a JSON of sizes,
  recipe and reference tolerance; its ``model_type`` names
  ``benchmarks/models/<model_type>.py``, which builds the system's
  model from it and holds the plain reference;
- the traffic: ``benchmarks/traffic/<traffic>.json`` (batch, save
  schedule, trace plan);
- with ``--trace 1``, each ``per_layer`` metric that lists the cell
  (or lists none): ``benchmarks/layer_metrics/<metric name>.py``,
  whose ``read(run)`` takes the number from the events, the worker's
  report or the reduced trace, or returns None to be left out.

Nothing in this file or in ``worker.py`` names a cell, so a later PR
adds a cell with new files and one entry.

The last line of standard output is the result, one JSON object.  No
TPU (or fewer chips than the cell asks for) is a failure with a
non-zero exit code and no result.  A configuration whose ``platform``
is ``cpu`` (``configs/toy.json``, through ``--cells
benchmarks/rehearsal.json``) is the CPU rehearsal: it prints counts
and ``correct`` but never a metric value, and exits with code 3.
"""

import argparse
import glob
import json
import math
import os
import shutil
import signal
import statistics
import subprocess
import sys
import tempfile
import time

T_LAUNCH = time.time()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
CACHE = os.path.join(ROOT, ".jax_cache")
sys.path.insert(0, BENCH)

import flops  # noqa: E402  (the benchmark's own, beside this file)
from loader import load_json, load_module  # noqa: E402

RUN_TIMEOUT_S = 1150


def say(line=""):
    print(line, flush=True)


# -- processes (copied from chip_smoke.py) ------------------------------------


def proc_stat(pid):
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
    for _ in range(100):
        pids = session_pids(sid)
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        time.sleep(0.1)


def read_events(path):
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    except OSError:
        pass
    return out


def tail(path, lines=30, width=300):
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


# -- the cell -----------------------------------------------------------------


class Cell:
    """One entry of ``workloads`` and the files it points at."""

    def __init__(self, cells_path, workload):
        spec = load_json(cells_path)
        by_name = {w["name"]: w for w in spec["workloads"]}
        if workload not in by_name:
            raise SystemExit(
                f"no workload {workload!r} in {cells_path}: "
                f"{sorted(by_name)}"
            )
        self.spec = spec
        self.workload = by_name[workload]
        self.name = workload
        self.chips = self.workload["chips"]
        config = {c["name"]: c for c in spec["configs"]}[
            self.workload["config"]
        ]
        self.config_path = os.path.join(ROOT, config["file"])
        self.traffic_path = os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"
        )
        self.config = load_json(self.config_path)
        self.traffic = load_json(self.traffic_path)

    def metrics(self, group):
        """The metrics of ``end_to_end`` or ``per_layer`` that this
        cell reports."""
        return [
            m for m in self.spec[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


class Run:
    """What a per-layer reader is handed: the job's events, the
    worker's report, the reduced trace (or None), the cell's files,
    the benchmark's arithmetic, and ``note`` for lines that belong
    above the result."""

    def __init__(self, cell, report, events, trace, t_launch):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.report = report
        self.events = events
        self.trace = trace
        self.t_launch = t_launch
        self.flops = flops

    def of(self, type_, **match):
        return [
            e for e in self.events if e.get("type") == type_ and all(
                e.get(k) == v for k, v in match.items()
            )
        ]

    @staticmethod
    def note(line):
        say("  " + line)

    # -- end to end ----------------------------------------------------------

    def window_seconds(self):
        return self.report["window_t1"] - self.report["window_t0"]

    def step_intervals(self):
        """Seconds between consecutive completed steps of the window,
        the opening step's completion first: every interval, the ones
        that carry a save included."""
        done = [self.report["window_t0"]] + [
            s["done"] for s in self.report["window"]["steps"]
        ]
        return [b - a for a, b in zip(done, done[1:])]

    def plain_step_intervals(self):
        """The intervals that carry no save."""
        saved = {s["step"] for s in self.report["window"]["saves"]}
        steps = self.report["window"]["steps"]
        return [
            dt for s, dt in zip(steps, self.step_intervals())
            if s["step"] - 1 not in saved
        ]

    def tokens_per_s(self):
        steps = len(self.report["window"]["steps"])
        return (
            steps * self.report["tokens_per_step"]
            / self.window_seconds()
        )

    def end_to_end(self):
        rep = self.report
        intervals = sorted(self.step_intervals())
        rank = math.ceil(0.95 * len(intervals)) - 1
        self.note(
            f"window: {len(intervals)} step intervals in "
            f"{self.window_seconds():.3f} s; median "
            f"{statistics.median(intervals) * 1e3:.3f} ms; p95 is "
            f"sample {rank + 1} of {len(intervals)} "
            f"({len(intervals) - rank - 1} beyond it)"
        )
        out = {
            "tokens_per_s": (self.tokens_per_s(), "tokens/s"),
            "step_ms_p95": (intervals[rank] * 1e3, "ms"),
            "setup_s": (rep["window_t0_epoch"] - self.t_launch, "s"),
        }
        stalls = [s["stall_s"] for s in rep["window"]["saves"]]
        if stalls:
            self.note(
                f"window: {len(stalls)} saves "
                f"({sum(s['kind'] == 'disk' for s in rep['window']['saves'])}"
                f" to disk); stall median "
                f"{statistics.median(stalls) * 1e3:.1f} ms, max "
                f"{max(stalls) * 1e3:.1f} ms"
            )
            out["save_stall_ms"] = (
                statistics.median(stalls) * 1e3, "ms"
            )
        return out

    # -- counts and correctness ------------------------------------------------

    def uncommitted_saves(self):
        """Window saves that were accepted but whose commit the event
        log does not show: the shm write of that step and, for a DISK
        save, the agent's persist."""
        in_shm = {e.get("step") for e in self.of("checkpoint_shm_save")}
        on_disk = {
            e.get("step") for e in self.of("checkpoint_persist", ok=True)
        }
        lost = []
        for s in self.report["window"]["saves"]:
            if not s["ok"]:
                continue
            if s["step"] not in in_shm or (
                s["kind"] == "disk" and s["step"] not in on_disk
            ):
                lost.append(s["step"])
        return lost

    def counts(self):
        window = self.report["window"]
        bad_steps = [
            s["step"] for s in window["steps"]
            if not math.isfinite(s["loss"])
        ]
        skipped = [s["step"] for s in window["saves"] if not s["ok"]]
        lost = self.uncommitted_saves()
        if bad_steps or skipped or lost:
            self.note(
                f"FAILED operations: non-finite loss at steps "
                f"{bad_steps}; saves skipped at {skipped}; saves never "
                f"committed at {lost}"
            )
        attempted = len(window["steps"]) + len(window["saves"])
        return attempted, len(bad_steps) + len(skipped) + len(lost)

    def correct(self):
        """(a) the backend the cell asks for; (b) the loss of the
        initial parameters on the fixed batch against the plain
        float32 reference; (c) losses finite, and lower after the
        window than at the start; (d) no compilation inside the
        window; (e) with saves: every set-up save committed and the
        final shared-memory copy equal to the device state bit for
        bit."""
        rep, cfg = self.report, self.config
        checks = {}
        checks["backend"] = (
            rep["device"]["platform"] == cfg["platform"]
            and rep["device"]["count"] == self.cell.chips
        )
        # the tolerance and its reason stand in the configuration's
        # file, beside the sizes it was measured at
        tol = cfg["reference"]["loss_tolerance"]
        diff = abs(rep["system_loss"] - rep["reference_loss"])
        self.note(
            f"loss of the initial parameters: system "
            f"{rep['system_loss']:.6f}, float32 reference "
            f"{rep['reference_loss']:.6f}, |difference| {diff:.2e} "
            f"(tolerance {tol:.1e})"
        )
        checks["reference"] = diff <= tol
        losses = [s["loss"] for s in rep["window"]["steps"]]
        checks["loss"] = (
            all(math.isfinite(x) for x in losses)
            and losses[-1] < rep["system_loss"]
        )
        checks["no_compile_in_window"] = rep["compiles_in_window"] == 0
        if self.traffic.get("saves"):
            back = rep.get("readback", {})
            self.note(f"read-back of the final save: {back}")
            checks["saves"] = bool(
                rep["setup_saves_ok"]
                and back.get("save_ok") and back.get("bit_identical")
                and back.get("tier") == "shm"
                and rep.get("window_persisted", True)
            )
        bad = [k for k, ok in checks.items() if not ok]
        if bad:
            self.note(f"NOT CORRECT: failed checks {bad}")
        return not bad


# -- the job ---------------------------------------------------------------------


class Job:
    def __init__(self, cell, args):
        self.cell = cell
        self.args = args
        self.run_dir = os.path.join(
            OUT, f"{cell.name}-s{args.seed}-t{args.trace}-{os.getpid()}"
        )
        os.makedirs(self.run_dir)
        # unix socket paths are limited to ~107 bytes: the IPC
        # directory goes under TMPDIR, else inside the checkout, and
        # to /tmp only where both are too long for a socket's name
        for base in (tempfile.gettempdir(), OUT, "/tmp"):
            if len(base) <= 45 or base == "/tmp":
                self.sock_dir = tempfile.mkdtemp(
                    prefix="dlbench_", dir=base
                )
                break
        self.job = f"bench{os.getpid()}"
        self.log = os.path.join(self.run_dir, "tpurun.log")
        self.events_path = os.path.join(self.run_dir, "events.jsonl")
        self.report_path = os.path.join(self.run_dir, "report.json")
        self.sid = None

    def env(self):
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        path = env.get("PYTHONPATH", "")
        env["PYTHONPATH"] = ROOT + (os.pathsep + path if path else "")
        env["DLROVER_SHARED_DIR"] = self.sock_dir
        env["DLROVER_JOB_NAME"] = self.job
        env["DLROVER_METRICS_FILE"] = os.path.join(
            self.run_dir, "metrics.json"
        )
        env["DLROVER_EVENT_LOG"] = self.events_path
        env["DLROVER_PARAL_CONFIG_PATH"] = os.path.join(
            self.run_dir, "paral_config.json"
        )
        env["TPU_LOG_DIR"] = "disabled"
        # one fixed directory inside the checkout for the XLA cache
        # and (beneath it) the AOT executables; never trimmed between
        # the runs of a cell, whatever the machine's own limit is
        env["JAX_COMPILATION_CACHE_DIR"] = CACHE
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        if self.cell.config["platform"] == "cpu":
            # the rehearsal's configuration asks for the CPU backend
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def launch(self):
        argv = [
            sys.executable, "-m", "dlrover_tpu.run",
            "--nproc_per_node=1", "--max_restarts=0",
            "--monitor_interval=0.5",
            os.path.join(BENCH, "worker.py"),
            "--config", self.cell.config_path,
            "--traffic", self.cell.traffic_path,
            "--seed", str(self.args.seed),
            "--seconds", str(self.args.seconds),
            "--trace", str(self.args.trace),
            "--out", self.run_dir,
        ]
        with open(self.log, "w") as logf:
            proc = subprocess.Popen(
                argv, cwd=ROOT, stdout=logf,
                stderr=subprocess.STDOUT, start_new_session=True,
                env=self.env(),
            )
        self.sid = proc.pid
        return proc

    def reduce_trace(self):
        """The trace reduction in a child of its own, held to the CPU
        backend, after the job has ended."""
        out = os.path.join(self.run_dir, "trace.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        subprocess.run(
            [sys.executable, os.path.join(BENCH, "xplane.py"), "reduce",
             os.path.join(self.run_dir, "trace"), out],
            check=True, env=env, cwd=ROOT, timeout=300,
        )
        return load_json(out)

    def cleanup(self):
        """Stop everything the run started and take its segments out
        of /dev/shm; returns what a run must not leave behind."""
        left = []
        if self.sid is not None:
            kill_session(self.sid)
            alive = session_pids(self.sid)
            if alive:
                left.append(f"processes still alive: {alive}")
        for path in glob.glob(f"/dev/shm/*{self.job}*"):
            try:
                os.unlink(path)
            except OSError:
                pass
        segments = glob.glob(f"/dev/shm/*{self.job}*")
        if segments:
            left.append(f"/dev/shm still holds {segments}")
        shutil.rmtree(self.sock_dir, ignore_errors=True)
        if not os.path.realpath(self.run_dir).startswith(
            os.path.realpath(OUT) + os.sep
        ):
            left.append(f"run directory {self.run_dir} is outside {OUT}")
        if not self.args.keep:
            shutil.rmtree(self.run_dir, ignore_errors=True)
        return left


def per_layer(cell, run, off_chip=False):
    """Every per-layer metric the cell lists, through its reader.
    ``off_chip`` (the rehearsal) lets a reader stop at the table of
    peaks, as it must for a device that is not in it."""
    out = {}
    for metric in cell.metrics("per_layer"):
        reader = load_module("layer_metrics", metric["name"])
        declared = (reader.NAME, reader.UNIT, reader.LAYER,
                    reader.MOVES, reader.SOURCE)
        listed = tuple(
            metric[k] for k in ("name", "unit", "layer", "moves", "source")
        )
        if declared != listed:
            raise SystemExit(
                f"layer_metrics/{metric['name']}.py declares "
                f"{declared}, the cells file lists {listed}"
            )
        try:
            value = reader.read(run)
        except KeyError as e:
            if not off_chip:
                raise
            say(f"  {metric['name']} stopped: {e}")
            continue
        if value is not None:
            out[metric["name"]] = (float(value), metric["unit"])
    return out


def breakdown(trace):
    """The device operations that took most time, summed by group
    (the instruction's name without the compiler's numbering), and
    the idle seconds by what the host was in."""
    groups = {}
    for op in trace["ops"].values():
        groups[op["group"]] = groups.get(op["group"], 0.0) + op["seconds"]
    ops = sorted(groups.items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[name, seconds] for name, seconds in ops],
        "idle_gaps": [
            [name, seconds] for name, seconds in sorted(
                trace["idle_s"].items(), key=lambda kv: -kv[1]
            )[:10]
        ],
    }


class RunFailed(Exception):
    """The run gives no result; ``log`` says whether the end of the
    job's log belongs under the reason."""

    def __init__(self, why, log=True):
        super().__init__(why)
        self.why = why
        self.log = log


def measure(cell, job, args):
    """Launch the job, wait for it, and reduce what it left to the
    result's keys."""
    rehearsal = cell.config["platform"] != "tpu"
    proc = job.launch()
    try:
        rc = proc.wait(timeout=RUN_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise RunFailed(f"the job did not end in {RUN_TIMEOUT_S} s")
    try:
        report = load_json(job.report_path)
    except (OSError, ValueError):
        raise RunFailed(
            f"tpurun exited with code {rc} and the worker left no "
            "report"
        )
    if rc != 0 or report.get("phase") != "done":
        raise RunFailed(
            f"tpurun exited with code {rc}, the worker reached phase "
            f"{report.get('phase')!r} on {report.get('device')}"
        )
    events = read_events(job.events_path)
    trace = None
    if args.trace and not rehearsal:
        # (the CPU backend's trace has no device plane to reduce)
        try:
            trace = job.reduce_trace()
        except (subprocess.SubprocessError, OSError, ValueError) as e:
            raise RunFailed(f"the trace could not be reduced: {e}")
    run = Run(cell, report, events, trace, T_LAUNCH)

    status = [e.get("status") for e in run.of("compile_cache")]
    say(f"  worker: {report['params'] / 1e9:.3f} B params; step "
        f"executable {status[0] if status else 'status not reported'}"
        f", resolved in {report['resolve_step_s']:.1f} s, "
        f"state made in {report['init_s']:.1f} s, reference in "
        f"{report['reference_s']:.1f} s; "
        f"{report['compiles_total']} compilations in all, "
        f"{report['compiles_in_window']} in the window")
    measured = run.end_to_end()
    correct = run.correct()
    attempted, failed = run.counts()
    device = dict(report["device"])
    device["memory_peak_bytes"] = report["memory_peak_bytes"]
    result = {"correct": correct, "attempted": attempted,
              "failed": failed}
    if rehearsal:
        # counts and correctness, never a number under a metric's
        # name; the readers that need no device are tried all the same
        if args.trace:
            found = per_layer(cell, run, off_chip=True)
            say(f"  readers that found a value: {sorted(found)}")
        listed = {}
    elif args.trace:
        listed = per_layer(cell, run)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = breakdown(trace)
    else:
        names = {m["name"] for m in cell.metrics("end_to_end")}
        listed = {k: v for k, v in measured.items() if k in names}
    result["metrics"] = {
        name: {"value": value, "unit": unit}
        for name, (value, unit) in listed.items()
    }
    result["device"] = device
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cells",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--keep", action="store_true",
                    help="keep the run directory (log, events, trace)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "dlrover_tpu")):
        say(f"benchmark run FAILED: {ROOT} holds no dlrover_tpu "
            "package: this is not a checkout of the system under test")
        return 1
    cell = Cell(args.cells, args.workload)
    job = Job(cell, args)
    say(f"cell {cell.name}: configuration {cell.config['name']}, "
        f"traffic {cell.traffic['name']}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}; run directory "
        f"{job.run_dir}")
    result = failure = None
    try:
        result = measure(cell, job, args)
    except RunFailed as e:
        failure = e.why
        say("-- end of the job's log:")
        say(tail(job.log))
    finally:
        left = job.cleanup()
    if "jax" in sys.modules:
        left.append("run.py's own process imported jax")
    for line in left:
        say("left behind: " + line)
    failure = failure or "; ".join(left)
    if failure:
        say(f"benchmark run FAILED: {failure}")
        # no result: a line without metrics can be taken for nothing
        say(json.dumps({"correct": False, "error": failure}))
        return 1
    say(json.dumps(result))
    if cell.config["platform"] != "tpu":
        say(f"rehearsal on {result['device']['platform']}: not a result")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
