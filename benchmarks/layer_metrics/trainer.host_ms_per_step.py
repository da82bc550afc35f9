"""What the trainer loop itself costs per step: the program's
``step_phases`` span of each window step, its total minus the
``compute`` and ``checkpoint`` phases (so: report, event log, metrics
file, loop overhead), median."""

import statistics

NAME = "trainer.host_ms_per_step"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    host = [
        e["total_s"] - e.get("compute", 0.0) - e.get("checkpoint", 0.0)
        for e in run.of("step_phases") if e.get("step") in steps
    ]
    if not host:
        return None
    return statistics.median(host) * 1e3
