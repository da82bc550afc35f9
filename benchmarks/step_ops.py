"""Every device operation of a traced step under a name of the
program: the third reducer, beside ``xplane.py`` and ``scopes.py``.

``scopes.py`` and the family files join a trace with the ``op_names``
of the step executable's map (``<key>.opnames.json``, written by
``dlrover_tpu/common/aot_cache.py``), which holds the instructions
that carry ``op_name`` metadata.  What the COMPILER made (a layout
copy, a pad, the halves of an asynchronous copy or slice, a fusion of
such) carries none, and those readers drop its time.  Since PR 54 the
map names them too:

``inherited``   ``{instruction: [stack, rule]}``: the stack of the
                other half of its pair (``start``), of its fused
                computation (``body``), of its users (``user``) or of
                its operands' producers (``operand``);
``containers``  the ``while`` / ``conditional`` / ``call``
                instructions, whose time is their bodies';
``unnamed``     ``{instruction: "<opcode> <shape>"}``: what no rule
                reached;
``scopes``      the names the program opened device scopes under
                (``telemetry/tracing.py::device_scope``): what tells
                ``ssm_norm`` from a flax module's ``block_3``.

This file sums a traced step by them: each operation that ran inside
a run of the step's module once (a container left out, its body in),
as ``named`` (it has a stack of its own), ``inherited`` (the work the
compiler added to the program's own) or ``unnamed``.  ``python
benchmarks/step_ops.py reduce <trace dir or file> <out.json>
<opnames.json>`` runs in a child held to the CPU backend, once per
traced run (:func:`of_run`).  A map without ``inherited`` (a program
before PR 54) gives None, and the readers built on this return None.
"""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import scopes  # noqa: E402  (the benchmark's own, beside this file)
import xplane  # noqa: E402

PHASES = ("forward", "remat", "backward")
# a stack that does not start at a jit is a parameter's own name
# (``state.params['wte']['embedding']``): a copy of a weight or of an
# optimizer moment into another layout or memory
PARAMETER = "(a parameter)"
NO_SCOPE = "(no scope)"


def innermost_scope(stack, names):
    """The last component of ``stack`` that is one of the program's
    device scopes ``names``, bare or inside jax's transformation
    wrappers (``transpose(jvp(loss_head))``)."""
    if not stack.startswith("jit("):
        return PARAMETER
    for component in reversed(stack.split("/")):
        while component not in names:
            inner = scopes.WRAPPED.match(component)
            if not inner:
                break
            component = inner.group(1)
        else:
            return component
    return NO_SCOPE


def phase(stack):
    """Forward, remat copy or backward, as the family files tell."""
    if scopes.in_scope(stack, "rematted_computation"):
        return "remat"
    return "backward" if "transpose(" in stack else "forward"


def reduce(space, op_map):
    """Seconds of the traced span's device operations by what the map
    says of them, or None for a map without ``inherited``.  An
    operation is the step's where it starts inside a run of the map's
    module (every operation, where the trace has no modules line: a
    fixture)."""
    if "inherited" not in (op_map or {}):
        return None
    ops = xplane.device_ops(space)
    bench = xplane.host_spans(space)
    if not ops or not bench:
        raise ValueError("no device operations or no bench.* span")
    t0 = min(s[1] for s in bench)
    t1 = max(s[2] for s in bench)
    first = sorted(ops)[0]
    has_modules = any(
        line["name"] == scopes.MODULES_LINE
        for plane in space if plane["name"] == first
        for line in plane["lines"]
    )
    runs = scopes.module_intervals(space, first, op_map.get("module"))
    stacks = op_map.get("op_names") or {}
    inherited = op_map["inherited"]
    containers = set(op_map.get("containers") or ())
    names = set(op_map.get("scopes") or ())
    seconds = dict.fromkeys(
        ("named", "inherited", "unnamed", "containers", "outside"), 0.0
    )
    by_scope, by_group, by_rule, unnamed, intervals = {}, {}, {}, {}, []
    run_at = 0
    for name, start, end, stats in ops[first]:
        if end <= t0 or start >= t1:
            continue
        took = (min(end, t1) - max(start, t0)) / 1e9
        while run_at < len(runs) and runs[run_at][1] <= start:
            run_at += 1
        if has_modules and not (
            run_at < len(runs) and runs[run_at][0] <= start
        ):
            seconds["outside"] += took
            continue
        instruction, group, _ = xplane.describe(name, stats)
        if instruction in containers:
            seconds["containers"] += took
            continue
        intervals.append((start, end))
        if stacks.get(instruction):
            seconds["named"] += took
        elif instruction in inherited:
            stack, rule = inherited[instruction]
            seconds["inherited"] += took
            parts = by_scope.setdefault(
                innermost_scope(stack, names), dict.fromkeys(PHASES, 0.0)
            )
            parts[phase(stack)] += took
            by_group[group] = by_group.get(group, 0.0) + took
            by_rule[rule] = by_rule.get(rule, 0.0) + took
        else:
            seconds["unnamed"] += took
            entry = unnamed.setdefault(instruction, [0.0, group])
            entry[0] += took
    busy = sum(e - s for s, e in xplane.union(
        xplane.clip(intervals, t0, t1)
    )) / 1e9
    return {
        "steps": sum(1 for s in bench if s[0] == "compute"),
        "seconds": seconds,
        # the union of the counted operations' intervals: what their
        # sum is held against
        "busy_s": busy,
        "by_scope": by_scope, "by_group": by_group, "by_rule": by_rule,
        "unnamed": sorted(
            ([name, took, (op_map.get("unnamed") or {}).get(name, group)]
             for name, (took, group) in unnamed.items()),
            key=lambda row: -row[1],
        )[:5],
    }


_REDUCED = {}


def _reduce_once(directory, op_names):
    """``reduce`` of the run directory's trace, in a child held to the
    CPU backend, cached as ``step_ops.json`` beside it; None for a
    map without ``inherited`` (no child reads a trace to find
    nothing)."""
    with open(op_names) as f:
        if "inherited" not in json.load(f):
            return None
    out = os.path.join(directory, "step_ops.json")
    if not os.path.exists(out):
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "reduce",
             os.path.join(directory, "trace"), out, op_names],
            check=True, cwd=ROOT, timeout=300, env=dict(
                os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled"
            ),
        )
    with open(out) as f:
        return json.load(f)


def of_run(run):
    """The reduction of this run's trace, or None: no trace was
    reduced, no run directory, no map with ``inherited`` (the program
    before PR 54), or a trace this file cannot read.  Made once a
    run directory and cached in this process too."""
    if not run.trace or not run.trace.get("steps"):
        return None
    directory = scopes.run_dir(run)
    op_names = scopes.op_names_file(run)
    if directory is None or not os.path.isfile(op_names):
        return None
    if directory not in _REDUCED:
        try:
            _REDUCED[directory] = _reduce_once(directory, op_names)
        except (subprocess.SubprocessError, OSError, ValueError) as e:
            run.note(f"step_ops: the trace could not be reduced: {e}")
            _REDUCED[directory] = None
    return _REDUCED[directory]


def ms(reduced, seconds):
    """Milliseconds a traced step."""
    return seconds / reduced["steps"] * 1e3


def main(argv):
    if len(argv) == 5 and argv[1] == "reduce":
        with open(argv[4]) as f:
            op_map = json.load(f)
        result = reduce(xplane.read_space(argv[2]), op_map)
        with open(argv[3], "w") as f:
            json.dump(result, f)
        return 0
    print(__doc__)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv))
