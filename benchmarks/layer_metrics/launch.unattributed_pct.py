"""How much of ``setup_s`` nobody owns: of ``[run.t_launch,
report.window_t0_epoch]``, the share covered by NONE of

program span     the launch's own spans at ``restart_count`` 0
                 (``tpurun.boot``, ``tpurun.master_boot`` with
                 ``master.boot`` inside it, ``agent.init``,
                 ``rdzv.join``, ``agent.spawn_workers``,
                 ``trainer.distributed_init``,
                 ``trainer.backend_open``, ``trainer.init``) and, in a
                 save cell, the set-up's ``ckpt.save`` spans, their
                 ``ckpt.save.write`` on the writer thread (it ends
                 after the call) and the agent's ``ckpt.persist``;
recovery phase   ``spawn``, ``import`` and ``backend``, each at
                 ``[ts - seconds, ts]``;
program event    ``aot_cache`` (``[ts - seconds, ts]``: the step's
                 resolve) and the set-up's ``step_phases`` (each step
                 at ``[ts - total_s, ts]``);
harness          what no deployment pays: ``run.py``'s own start up
                 to tpurun's process, and the worker's
                 ``resolve_step_s``, ``init_s`` and ``reference_s``
                 from its report, laid end to end from the
                 ``aot_cache`` event (the report has durations only).

The note prints every stretch in order with its owner, the split of
``setup_s`` into program + harness + uncovered (the three sum to it),
and the three longest uncovered gaps with what lies on either side.
The other ``launch.*`` readers take their spans through this file.

A program without the launch spans (the parent of PR 37) gives None
here and in every reader built on :func:`span_of`.
"""

import scopes
from scopes import xplane

NAME = "launch.unattributed_pct"
UNIT = "%"
LAYER = "launcher / agent"
MOVES = "setup_s"
SOURCE = "program_span"

SPANS = (
    "tpurun.boot", "tpurun.master_boot", "master.boot", "agent.init",
    "rdzv.join", "agent.spawn_workers", "trainer.distributed_init",
    "trainer.backend_open", "trainer.init",
)
SAVES = ("ckpt.save", "ckpt.save.write", "ckpt.persist")
PHASES = ("spawn", "import", "backend")
PROGRAM, PHASE, EVENT, HARNESS = (
    "program span", "recovery phase", "program event", "harness",
)


def span_of(run, name):
    """The launch's ``name`` span (``restart_count`` 0), or None."""
    for e in scopes.span_events(run):
        if e["name"] == name and e["attributes"].get(
            "restart_count"
        ) == 0:
            return e
    return None


def phase_of(run, phase):
    """``(start, end)`` of the launch's recovery phase, or None."""
    found = run.of("recovery_phase", phase=phase, restart_count=0)
    if not found:
        return None
    return found[0]["ts"] - found[0]["seconds"], found[0]["ts"]


def stretches(run):
    """``[(start, end, owner, name)]`` of everything in the set-up
    that has a name, on the event log's clock, sorted by start; None
    where the program writes no launch span or the report names no
    window."""
    boot = span_of(run, "tpurun.boot")
    opened = run.report.get("window_t0_epoch")
    if boot is None or opened is None:
        return None
    out = [(run.t_launch, boot["start_ts"], HARNESS,
            "run.py -> tpurun's process")]
    for name in SPANS:
        e = span_of(run, name)
        if e is not None:
            out.append((*scopes.interval(e), PROGRAM, name))
    out += [
        (*scopes.interval(e), PROGRAM, e["name"])
        for e in scopes.span_events(run)
        if e["name"] in SAVES and e["start_ts"] < opened
    ]
    for phase in PHASES:
        at = phase_of(run, phase)
        if at is not None:
            out.append((*at, PHASE, phase))
    steps = [
        (e["ts"] - e["total_s"], e["ts"]) for e in run.of("step_phases")
        if e["ts"] - e["total_s"] < opened
    ]
    if steps:
        # each begins where the one before it ended: one stretch
        out.append((
            min(s for s, _ in steps), max(e for _, e in steps), EVENT,
            f"step_phases of {len(steps)} set-up steps",
        ))
    resolved = run.of("aot_cache")
    if resolved:
        at = resolved[0]["ts"]
        out.append(
            (at - resolved[0]["seconds"], at, EVENT, "aot_cache")
        )
        start = at - run.report["resolve_step_s"]
        for key in ("resolve_step_s", "init_s", "reference_s"):
            out.append(
                (start, start + run.report[key], HARNESS, key)
            )
            start += run.report[key]
    return sorted(out)


def seconds(intervals, t0, t1):
    return sum(
        e - s for s, e in xplane.union(xplane.clip(intervals, t0, t1))
    )


def split(run):
    """``setup_s`` as ``{"program", "harness", "uncovered", "setup",
    "gaps", "stretches"}``: seconds the program's own names cover,
    seconds only the harness's cover, seconds nothing covers (with
    the gaps themselves); None as :func:`stretches`."""
    found = stretches(run)
    if found is None:
        return None
    t0, t1 = run.t_launch, run.report["window_t0_epoch"]
    named = [(s, e) for s, e, _, _ in found]
    program = seconds(
        [(s, e) for s, e, owner, _ in found if owner != HARNESS],
        t0, t1,
    )
    covered = seconds(named, t0, t1)
    return {
        "setup": t1 - t0, "program": program,
        "harness": covered - program, "uncovered": t1 - t0 - covered,
        "gaps": xplane.gaps(
            xplane.union(xplane.clip(named, t0, t1)), t0, t1
        ),
        "stretches": found,
    }


def read(run):
    parts = split(run)
    if parts is None or parts["setup"] <= 0:
        return None
    t0 = run.t_launch
    share = 100.0 * parts["uncovered"] / parts["setup"]
    run.note(
        f"launch: setup_s {parts['setup']:.3f} = program "
        f"{parts['program']:.3f} + harness {parts['harness']:.3f} + "
        f"uncovered {parts['uncovered']:.3f} ({share:.2f}%)"
    )
    for start, end, owner, name in parts["stretches"]:
        run.note(
            f"launch:   {start - t0:+8.3f} .. {end - t0:+8.3f} "
            f"{end - start:8.3f} s  {owner:<14} {name}"
        )
    found = parts["stretches"]
    for start, end in sorted(
        parts["gaps"], key=lambda g: g[0] - g[1]
    )[:3]:
        before = max(
            (s for s in found if s[1] <= start + 1e-6),
            key=lambda s: s[1], default=None,
        )
        after = min(
            (s for s in found if s[0] >= end - 1e-6), default=None
        )
        run.note(
            f"launch: uncovered {end - start:.3f} s at "
            f"{start - t0:+.3f}: after "
            f"{before[3] if before else 'the launch'}, before "
            f"{after[3] if after else 'the window'}"
        )
    return share
