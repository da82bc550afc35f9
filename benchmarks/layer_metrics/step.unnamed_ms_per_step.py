"""Device time per traced step of the step module's operations that
NO name of the program reaches: the step executable's map
(``<key>.opnames.json``) has neither a name stack for them nor an
inherited one (``step_ops.py``; a container left out, its body in,
each operation once).  The notes: the same sum over ``op_names``
alone, which is what every reader that joins by name stack is blind
to; the accounting ``named + inherited + unnamed`` against the union
of those operations' intervals; the five longest still unnamed."""

import step_ops

NAME = "step.unnamed_ms_per_step"
UNIT = "ms"
LAYER = "model + kernels"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    reduced = step_ops.of_run(run)
    if not reduced:
        return None
    named, inherited, unnamed, containers, outside = (
        step_ops.ms(reduced, reduced["seconds"][k]) for k in (
            "named", "inherited", "unnamed", "containers", "outside"
        )
    )
    busy = step_ops.ms(reduced, reduced["busy_s"])
    total = named + inherited + unnamed
    if not total:
        # (no operation of the trace ran inside the step's module)
        return None
    run.note(
        f"names: op_names ALONE leaves {inherited + unnamed:.3f} ms a "
        f"step without a name stack ({100 * (inherited + unnamed) / total:.2f}"
        "% of the step's operations): what the readers that join by "
        "name stack do not see"
    )
    run.note(
        f"names: named {named:.3f} + inherited {inherited:.3f} + "
        f"unnamed {unnamed:.3f} = {total:.3f} ms a step; those "
        f"operations' union is {busy:.3f} ms busy ({100 * total / busy:.3f}"
        f"% accounted for); left out: containers {containers:.3f} ms "
        f"(their bodies are in), other modules {outside:.3f} ms"
    )
    if reduced["unnamed"]:
        run.note("names: longest still unnamed, ms a step: " + "; ".join(
            f"{name} {step_ops.ms(reduced, seconds):.4f} ({what})"
            for name, seconds, what in reduced["unnamed"]
        ))
    return unnamed
