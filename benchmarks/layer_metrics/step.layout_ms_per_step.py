"""Device time per traced step of the operations the step
executable's map names ONLY by inheritance (``step_ops.py``): the
layout copies, pads, asynchronous copies and slices the compiler
added to the program's own work, each under the name stack of the
work it was made for.  The notes: by the innermost device scope of
the inherited stack (the ten largest; forward | remat copy | backward
where the stack tells), by kind of operation, and by the rule that
named them."""

import step_ops

NAME = "step.layout_ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def largest(table, count, size=lambda value: value):
    """The ``count`` largest rows of ``table`` (in ms), those that
    read 0.000 left out."""
    rows = sorted(table.items(), key=lambda kv: -size(kv[1]))[:count]
    return [kv for kv in rows if size(kv[1]) >= 0.0005]


def read(run):
    reduced = step_ops.of_run(run)
    if not reduced:
        return None
    by_scope = {
        scope: [step_ops.ms(reduced, parts[k]) for k in step_ops.PHASES]
        for scope, parts in reduced["by_scope"].items()
    }
    by_group, by_rule = (
        {k: step_ops.ms(reduced, v) for k, v in reduced[table].items()}
        for table in ("by_group", "by_rule")
    )
    run.note(
        "layouts by scope, ms a step forward | remat copy | backward: "
        + "; ".join(
            f"{scope} " + " | ".join(f"{part:.3f}" for part in parts)
            for scope, parts in largest(by_scope, 10, sum)
        )
    )
    run.note("layouts by operation, ms a step: " + "; ".join(
        f"{group} {took:.3f}" for group, took in largest(by_group, 8)
    ) + "; by rule: " + ", ".join(
        f"{rule} {took:.3f}" for rule, took in largest(by_rule, 4)
    ))
    return step_ops.ms(reduced, reduced["seconds"]["inherited"])
