"""The largest root mean square of a state-space layer's FINAL state
``h [E, N]``: the program's own counter ``s6.state_rms_max`` on the
``train_step`` events, the largest of the window.  The note gives
``s6.decay_mean`` (the mean of ``exp(dt A)`` over every 64th row, the
channels, the lanes and the layers), ``s6.dt_mean`` and how many
tokens a state lane remembers at that decay (``1 / (1 - decay)``)."""

import jamba_flops

NAME = "s6.state_rms_max"
UNIT = "rms"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    events = jamba_flops.counter_over_window(run, NAME)
    if not events:
        return None
    last = events[-1]
    note = (
        f"selective scan counters over {len(events)} steps: {NAME} "
        f"first {events[0][NAME]:.6f}, last {last[NAME]:.6f}"
    )
    decay = last.get("s6.decay_mean")
    if decay is not None:
        note += (
            f"; s6.decay_mean {decay:.4f} (a lane remembers "
            f"{1 / max(1 - decay, 1e-6):.1f} tokens in the mean), "
            f"s6.dt_mean {last.get('s6.dt_mean', float('nan')):.5f}"
        )
    run.note(note)
    return max(e[NAME] for e in events)
