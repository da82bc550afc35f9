"""From tpurun's process start to its workers spawned: the start of
the ``tpurun.boot`` span to the end of ``agent.spawn_workers``, both
at ``restart_count`` 0.  Everything the launcher and the agent do
before a worker exists.  The note prints the parts: ``tpurun.boot``
(interpreter + imports), ``tpurun.master_boot`` with the master's
own ``master.boot`` inside it and the port poll's slack (``polls``,
``slept_s``), ``agent.init``, ``rdzv.join`` (``polls``,
``slept_s``), ``agent.spawn_workers``, and what lies between them
under no name."""

import loader

NAME = "launch.tpurun_boot_s"
UNIT = "s"
LAYER = "launcher / agent"
MOVES = "setup_s"
SOURCE = "program_span"

PARTS = (
    "tpurun.boot", "tpurun.master_boot", "master.boot", "agent.init",
    "rdzv.join", "agent.spawn_workers",
)


def read(run):
    launch = loader.load_module("layer_metrics", "launch.unattributed_pct")
    spans = {name: launch.span_of(run, name) for name in PARTS}
    first, last = spans["tpurun.boot"], spans["agent.spawn_workers"]
    if first is None or last is None:
        return None
    total = last["start_ts"] + last["duration_s"] - first["start_ts"]
    parts = []
    for name, e in spans.items():
        if e is None:
            parts.append(f"{name} missing")
            continue
        slept = e["attributes"].get("slept_s")
        parts.append(f"{name} {e['duration_s']:.3f}" + (
            f" ({e['attributes'].get('polls')} polls, slept "
            f"{slept:.1f})" if slept is not None else ""
        ))
    named = sum(
        e["duration_s"] for name, e in spans.items()
        if e is not None and name != "master.boot"
    )
    run.note(
        f"tpurun -> workers spawned {total:.3f} s: " + ", ".join(parts)
        + f"; between them, under no name, {total - named:.3f} s; "
        f"run.py's launch -> tpurun's process "
        f"{first['start_ts'] - run.t_launch:.3f} s"
    )
    return total
