"""What the program's OWN names say about a run: the second reducer,
beside ``xplane.py``.

``xplane.py`` reads a trace from outside (busy and idle time, seconds
per compiler-named operation).  Since PR 25 the program names things
itself, and this file reads those names:

device scopes   ``jax.named_scope`` names in each device operation's
                name stack (``optimizer``, ``loss_head``,
                ``forward_backward``, and jax's own
                ``rematted_computation``): device seconds per scope.
                The stack is the instruction's ``op_name`` metadata.
                The v5e trace as the worker takes it (no HLO proto)
                carries none (my chip run, PR 25: the only stats are
                the device offset and duration, the event's name is
                the HLO text without metadata), so it comes from the
                map the program writes beside the step executable's
                AOT entry (``<key>.opnames.json``: instruction ->
                name stack, from that very executable's text), found
                through the ``aot_cache`` event (a fixture carries it
                in a ``tf_op`` stat).  The instruction's name is only the
                join between the two, inside one executable; nobody
                reads a ``%fusion.<n>`` number by hand.
program spans   the ``dlrover.<span>`` / ``dlrover.step.<phase>``
                annotations the program's tracer and step profiler
                enter, each with the wall clock at its entry
                (``wall_ns``): the offset between the profiler's clock
                and the event log's ``ts`` / ``start_ts``, so the
                spans of EVERY process (agent and master write only
                the event log) can be laid on the trace's axis.
event spans     the ``span`` events of the job's log as trees (a
                ``ckpt.save`` and its children), for the readers that
                need no trace.

``python benchmarks/scopes.py reduce <trace dir or file> <out.json>
[<opnames.json>]`` runs in a child held to the CPU backend, once per
traced run (:func:`of_run`, called by the first reader that needs it;
the result is cached in the run directory for the others).  ``cut
<trace> <out.txt> <from s> <to s> [<opnames.json>]`` writes the small
text-proto excerpt that ``tests/fixtures`` holds.

A program without these names (the parent of PR 25) gives empty
results here, and every reader built on them returns None.
"""

import glob
import json
import os
import re
import statistics
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import xplane  # noqa: E402  (the benchmark's own, beside this file)

SCOPES = (
    "optimizer", "loss_head", "forward_backward",
    "rematted_computation",
)
PROGRAM_SPAN = "dlrover."
MODULES_LINE = "XLA Modules"
# the stat a fixture carries an operation's name stack in
STACK_STAT = "tf_op"
COMPILER_REMAT = re.compile(r"\.remat\d*(\.|$)")


# -- the device trace ---------------------------------------------------------


def name_stack(instruction, stats, op_names=None):
    """``(stack, source)`` of a device operation: the jax name stack
    its HLO instruction was lowered under
    (``jit(step_fn)/optimizer/mul``), and where it was found: the
    event's ``tf_op`` stat (what ``cut`` writes into a fixture) or
    the executable's own map ``op_names``; ``(None, None)`` where
    neither has it (the compiler's own copies carry no metadata)."""
    if stats.get(STACK_STAT):
        return str(stats[STACK_STAT]), STACK_STAT
    stack = (op_names or {}).get(instruction)
    if stack:
        return stack, "op_names_map"
    return None, None


WRAPPED = re.compile(r"^\w+\((.*)\)$")


def in_scope(stack, scope):
    """Whether ``scope`` is one of the stack's components, bare or
    inside jax's transformation wrappers: the scope that opens a
    differentiated function reads ``jvp(loss_head)`` forward and
    ``transpose(jvp(loss_head))`` backward."""
    for component in stack.split("/"):
        while component != scope:
            inner = WRAPPED.match(component)
            if not inner:
                break
            component = inner.group(1)
        else:
            return True
    return False


def program_spans(space):
    """The program's own annotations in a trace, sorted by start:
    ``[{"name", "start_ns", "dur_ns", "wall_ns", "step",
    "span_id"}]``, names without the ``dlrover.`` prefix."""
    spans = []
    for plane in space:
        if xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, end, stats in line["events"]:
                if not name.startswith(PROGRAM_SPAN):
                    continue
                span = {
                    "name": name[len(PROGRAM_SPAN):],
                    "start_ns": start, "dur_ns": end - start,
                }
                for key in ("wall_ns", "step"):
                    if key in stats:
                        span[key] = int(stats[key])
                if "span_id" in stats:
                    # ("s" + the event's span_id: see tracing.py)
                    span["span_id"] = str(stats["span_id"])[1:]
                spans.append(span)
    return sorted(spans, key=lambda s: s["start_ns"])


def module_intervals(space, plane_name, module):
    """``[(start, end)]`` of the runs of ``module`` on one device
    (its ``XLA Modules`` line: ``jit_step_fn(<fingerprint>)``)."""
    for plane in space:
        if plane["name"] != plane_name:
            continue
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                return [
                    (start, end)
                    for name, start, end, _ in line["events"]
                    if name.split("(")[0] == module
                ]
    return []


def reduce(space, op_map=None):
    """Everything the readers take from a trace through the
    program's names.  The traced span is ``xplane.py``'s: first
    ``bench.*`` start to last ``bench.*`` end.  ``op_map`` is the
    program's ``{"module", "op_names"}`` for the step executable; it
    names only operations that ran inside that module."""
    ops = xplane.device_ops(space)
    bench = xplane.host_spans(space)
    if not ops or not bench:
        raise ValueError("no device operations or no bench.* span")
    t0 = min(s[1] for s in bench)
    t1 = max(s[2] for s in bench)
    first = sorted(ops)[0]
    op_names = (op_map or {}).get("op_names")
    runs = module_intervals(
        space, first, (op_map or {}).get("module")
    ) if op_names else []
    run_at = 0
    scope_s = {scope: 0.0 for scope in SCOPES}
    scope_ops = {scope: 0 for scope in SCOPES}
    head_dots = {"forward": 0, "remat": 0, "backward": 0}
    sources, total, named = {}, 0, 0
    compiler_remat = {"seconds": 0.0, "ops": 0}
    recompute_s = 0.0
    for name, start, end, stats in ops[first]:
        if end <= t0 or start >= t1:
            continue
        total += 1
        seconds = (min(end, t1) - max(start, t0)) / 1e9
        instruction = xplane.describe(name, stats)[0]
        # XLA's own rematerialization pass copies an instruction as
        # <name>.remat<n>, under the original's name stack
        copied = bool(COMPILER_REMAT.search(instruction))
        if copied:
            compiler_remat["seconds"] += seconds
            compiler_remat["ops"] += 1
        # (operations and module runs are both sorted by start)
        while run_at < len(runs) and runs[run_at][1] <= start:
            run_at += 1
        inside = run_at < len(runs) and runs[run_at][0] <= start
        stack, source = name_stack(
            instruction, stats, op_names if inside else None
        )
        if stack is None:
            continue
        named += 1
        sources[source] = sources.get(source, 0) + 1
        if copied or in_scope(stack, "rematted_computation"):
            # computed a second time, whoever decided it: jax's
            # checkpoint or the compiler (named operations only, so
            # that a program without names reads nothing)
            recompute_s += seconds
        for scope in SCOPES:
            if in_scope(stack, scope):
                scope_s[scope] += seconds
                scope_ops[scope] += 1
        if in_scope(stack, "loss_head") and stack.endswith(
            "dot_general"
        ):
            # the [b, s, vocab] projection and its two gradients
            if "transpose(" not in stack:
                kind = "forward"
            elif in_scope(stack, "rematted_computation"):
                kind = "remat"
            else:
                kind = "backward"
            head_dots[kind] += 1
    spans = program_spans(space)
    offsets = [
        s["wall_ns"] - s["start_ns"] for s in spans if "wall_ns" in s
    ]
    return {
        "t0_ns": t0, "t1_ns": t1,
        "window_s": (t1 - t0) / 1e9,
        "steps": sum(1 for s in bench if s[0] == "compute"),
        "ops": total, "ops_with_stack": named,
        "stack_sources": sources,
        "scope_s": scope_s, "scope_ops": scope_ops,
        "loss_head_dots": head_dots,
        "compiler_remat": compiler_remat,
        "recompute_s": recompute_s,
        "program_spans": spans,
        # wall clock minus profiler clock: a reading of the event log
        # minus this is a time on the trace's axis
        "clock_offset_ns": (
            statistics.median(offsets) if offsets else None
        ),
    }


# -- the run's reduction, once -------------------------------------------------

_REDUCED = {}


def run_dir(run):
    """The run directory of the ``run.py`` process this reader runs
    in: ``.bench_out/<cell>-s<seed>-t1-<pid of run.py>``."""
    found = glob.glob(os.path.join(
        ROOT, ".bench_out", f"{run.cell.name}-s*-t1-{os.getpid()}"
    ))
    return found[0] if len(found) == 1 else None


def op_names_file(run):
    """The instruction -> name-stack map the program wrote beside the
    step executable's AOT entry, named by the worker's ``aot_cache``
    event ("" where the event names none: the parent of PR 25 writes
    no map, and the file is then simply not there)."""
    for event in run.of("aot_cache"):
        if event.get("key") and event.get("dir"):
            return os.path.join(
                event["dir"], event["key"] + ".opnames.json"
            )
    return ""


def of_run(run):
    """The reduction of this run's trace, or None: no trace was
    reduced (``run.trace``: the rehearsal, ``--trace 0``), no run
    directory, or a trace this file cannot read.  Made once, in a
    child held to the CPU backend, and cached in the run directory
    and in this process."""
    if not run.trace:
        return None
    directory = run_dir(run)
    if directory is None:
        return None
    if directory not in _REDUCED:
        out = os.path.join(directory, "scopes.json")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled"
        )
        try:
            if not os.path.exists(out):
                subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "reduce", os.path.join(directory, "trace"), out,
                     op_names_file(run)],
                    check=True, env=env, cwd=ROOT, timeout=300,
                )
            with open(out) as f:
                _REDUCED[directory] = json.load(f)
        except (subprocess.SubprocessError, OSError, ValueError) as e:
            run.note(f"scopes: the trace could not be reduced: {e}")
            _REDUCED[directory] = None
    return _REDUCED[directory]


def scope_ms_per_step(run, scope):
    """Device milliseconds per traced step under ``scope``; None
    where no operation of the trace has a name stack (the parent of
    PR 25 writes no map; a trace this file cannot read)."""
    reduced = of_run(run)
    if not reduced or not reduced["steps"]:
        return None
    if not reduced["ops_with_stack"]:
        return None
    return reduced["scope_s"][scope] / reduced["steps"] * 1e3


# -- the event log's spans -----------------------------------------------------


def span_events(run, source=None):
    """The ``span`` events that carry ``start_ts`` (PR 25 on)."""
    return [
        e for e in run.of("span")
        if "start_ts" in e and (source is None or e["source"] == source)
    ]


def interval(event):
    return event["start_ts"], event["start_ts"] + event["duration_s"]


def window_saves(run, kind=None):
    """``[(save record, ckpt.save span, its descendants)]`` for the
    window's saves (``kind``: "memory" / "disk" / None for all) whose
    ``ckpt.save`` span the log holds.  Descendants are the spans of
    the same trace in the trainer (the writer thread's included)."""
    spans = span_events(run, "trainer")
    roots = {
        e["attributes"].get("step"): e for e in spans
        if e["name"] == "ckpt.save"
    }
    out = []
    for save in run.report["window"]["saves"]:
        root = roots.get(save["step"])
        if root is None or (kind and save["kind"] != kind):
            continue
        children = [
            e for e in spans
            if e["trace_id"] == root["trace_id"] and e is not root
        ]
        out.append((save, root, children))
    return out


def child_seconds(children, name):
    return sum(
        e["duration_s"] for e in children
        if e["name"] == "ckpt.save." + name
    )


def covered_seconds(root, children):
    """Seconds of ``root``'s interval covered by the union of its
    descendants' intervals."""
    r0, r1 = interval(root)
    merged = xplane.union(xplane.clip(
        [interval(e) for e in children], r0, r1
    ))
    return sum(e - s for s, e in merged)


def overlap(a, b):
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def clock_note(run):
    """One line on how well the two clocks agree, for a traced run:
    every program span that is both an annotation of the trace and a
    ``span`` event (joined by ``span_id``) is laid on the trace's
    axis through the median offset; printed are the largest
    disagreement on start and on duration, the ``ckpt.save`` span's
    own, and where the agent's last ``ckpt.persist`` (event log
    only: the agent never imports jax) falls on that axis."""
    reduced = of_run(run)
    if not reduced or reduced["clock_offset_ns"] is None:
        return
    offset = reduced["clock_offset_ns"]
    events = {e["span_id"]: e for e in span_events(run)}
    pairs = [
        (s, events[s["span_id"]]) for s in reduced["program_spans"]
        if s.get("span_id") in events
    ]
    if not pairs:
        return

    def apart(pair):
        s, e = pair
        return (
            abs(e["start_ts"] * 1e9 - offset - s["start_ns"]) / 1e3,
            abs(e["duration_s"] * 1e9 - s["dur_ns"]) / 1e3,
        )

    line = (
        f"clock: {len(pairs)} program spans are in the trace and in "
        f"the event log; after the offset they differ by at most "
        f"{max(apart(p)[0] for p in pairs):.1f} us on start and "
        f"{max(apart(p)[1] for p in pairs):.1f} us on duration"
    )
    saves = [p for p in pairs if p[0]["name"] == "ckpt.save"]
    if saves:
        start, duration = apart(saves[0])
        line += f" (ckpt.save: {start:.1f} and {duration:.1f} us)"
    persists = [
        e for e in span_events(run, "agent")
        if e["name"] == "ckpt.persist"
    ]
    if persists:
        last = persists[-1]
        at = (last["start_ts"] * 1e9 - offset - reduced["t0_ns"]) / 1e9
        line += (
            f"; the agent's ckpt.persist of step "
            f"{last['attributes'].get('step')} lies at {at:+.3f} s "
            f"from the traced span's start, {last['duration_s']:.3f}"
            " s long"
        )
    run.note(line)


# -- fixtures and the command line -----------------------------------------------


def cut(space, out_path, t0_ns, t1_ns, op_map=None):
    """A text-proto excerpt: the device operations that START inside
    [t0, t1), each with its name stack as a stat (``tf_op``; looked
    up in the executable's ``op_map`` where the trace has none) and,
    for a custom call, its target, and the ``bench.*`` and
    ``dlrover.*`` host spans that
    start there, the latter with ``wall_ns`` and ``step``.  Small
    enough to commit as a fixture."""
    op_names = (op_map or {}).get("op_names")
    stat_ids = {"tf_op": 1, "wall_ns": 2, "step": 3, "long_name": 4}
    planes = []
    for plane in space:
        is_device = bool(xplane.DEVICE_PLANE.match(plane["name"]))
        lines_txt, names = [], {}
        for line_id, line in enumerate(plane["lines"], 1):
            if is_device and line["name"] != xplane.OPS_LINE:
                continue
            events_txt = []
            for name, start, end, stats in line["events"]:
                if not t0_ns <= start < t1_ns:
                    continue
                stat = ""
                if is_device:
                    name, _, target = xplane.describe(name, stats)
                    stack, _ = name_stack(name, stats, op_names)
                    if stack:
                        stat = (
                            " stats { metadata_id: 1 str_value: "
                            f"{json.dumps(stack)} }}"
                        )
                    if target:
                        # what kernels.py tells a kernel by
                        text = (
                            f"{name} = custom-call(), "
                            f'custom_call_target="{target}"'
                        )
                        stat += (
                            " stats { metadata_id: 4 str_value: "
                            f"{json.dumps(text)} }}"
                        )
                elif name.startswith(PROGRAM_SPAN):
                    for key in ("wall_ns", "step"):
                        if key in stats:
                            stat += (
                                f" stats {{ metadata_id: "
                                f"{stat_ids[key]} int64_value: "
                                f"{int(stats[key])} }}"
                            )
                elif not name.startswith(xplane.HOST_SPAN):
                    continue
                meta = names.setdefault(name, len(names) + 1)
                events_txt.append(
                    f"    events {{ metadata_id: {meta} offset_ps: "
                    f"{int(start * 1000)} duration_ps: "
                    f"{int((end - start) * 1000)}{stat} }}"
                )
            if events_txt:
                lines_txt.append(
                    f"  lines {{ id: {line_id} name: "
                    f"{json.dumps(line['name'])} timestamp_ns: 0\n"
                    + "\n".join(events_txt) + "\n  }"
                )
        if not lines_txt:
            continue
        meta_txt = [
            f"  event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}" for n, i in names.items()
        ] + [
            f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}" for n, i in stat_ids.items()
        ]
        planes.append(
            f"planes {{ id: {len(planes) + 1} name: "
            f"{json.dumps(plane['name'])}\n" + "\n".join(lines_txt)
            + "\n" + "\n".join(meta_txt) + "\n}"
        )
    with open(out_path, "w") as f:
        f.write("\n".join(planes) + "\n")


def main(argv):
    op_map = None
    if len(argv) >= 5 and os.path.isfile(argv[-1]) and argv[-1].endswith(
        ".json"
    ):
        with open(argv[-1]) as f:
            op_map = json.load(f)
    if len(argv) >= 4 and argv[1] == "reduce":
        result = reduce(xplane.read_space(argv[2]), op_map)
        with open(argv[3], "w") as f:
            json.dump(result, f)
        return 0
    if len(argv) >= 6 and argv[1] == "cut":
        space = xplane.read_space(argv[2])
        base = min(s[1] for s in xplane.host_spans(space))
        cut(space, argv[3], base + float(argv[4]) * 1e9,
            base + float(argv[5]) * 1e9, op_map)
        return 0
    print(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
