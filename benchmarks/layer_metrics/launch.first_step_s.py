"""The first execution of the loaded step executable: ``total_s`` of
step 1's ``step_phases``, which begins at the step's first phase (a
program whose step profiler still starts at its construction, the
parent of PR 37, reads the whole set-up there: None where the
program writes no ``trainer.init`` span).  The note gives the step's
own phases, and what ``recovery_phase`` ``first_step`` holds in this
harness beside the steps: the report's ``init_s`` and ``reference_s``
(no deployment pays the second)."""

import loader

NAME = "launch.first_step_s"
UNIT = "s"
LAYER = "trainer loop"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    launch = loader.load_module("layer_metrics", "launch.unattributed_pct")
    if launch.span_of(run, "trainer.init") is None:
        return None
    first = run.of("step_phases", step=1)
    if not first:
        return None
    ph = first[0]
    line = (
        f"step 1: {ph['total_s']:.3f} s (compute "
        f"{ph.get('compute', 0):.3f}, report {ph.get('report', 0):.3f}, "
        f"gc {ph.get('gc', 0):.3f}, other {ph.get('other_s', 0):.3f})"
    )
    second = run.of("step_phases", step=2)
    if second:
        line += f"; step 2: {second[0]['total_s']:.3f} s"
    budget = run.of("recovery_phase", phase="first_step", restart_count=0)
    if budget:
        rep = run.report
        line += (
            f"; recovery_phase first_step {budget[0]['seconds']:.3f} s "
            f"= init_s {rep['init_s']:.3f} + reference_s "
            f"{rep['reference_s']:.3f} + "
            f"{budget[0]['seconds'] - rep['init_s'] - rep['reference_s']:.3f}"
            " of warm-up steps and what lies between"
        )
    run.note(line)
    return ph["total_s"]
