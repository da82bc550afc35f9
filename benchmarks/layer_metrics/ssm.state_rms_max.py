"""The largest root mean square of a state-space layer's final state:
the program's own counter ``ssm.state_rms_max`` on the ``train_step``
events, the largest of the window (the state has no norm of its own:
it grows with ``dt x`` and with how little the layer forgets).  The
note gives ``ssm.decay_mean``, the mean of ``exp(dt A)`` over tokens,
heads and layers: ``1 / (1 - a)`` tokens is how far back a layer
remembers."""

import statistics

NAME = "ssm.state_rms_max"
UNIT = "rms"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    steps = {s["step"] for s in run.report["window"]["steps"]}
    events = [
        e for e in run.of("train_step")
        if e.get("step") in steps and NAME in e
    ]
    if not events:
        return None
    decay = statistics.median(e["ssm.decay_mean"] for e in events)
    run.note(
        f"state-space counters over {len(events)} steps: "
        f"ssm.state_rms_max first {events[0][NAME]:.5f}, last "
        f"{events[-1][NAME]:.5f}; ssm.decay_mean median {decay:.5f} "
        f"(a layer remembers {1 / (1 - decay):.1f} tokens)"
    )
    return max(e[NAME] for e in events)
