"""The largest rms of a conv mixer's output ``y = C * conv3(B * u)``
over the conv layers: the program's own counter ``sconv.out_rms_max``
on the ``train_step`` events, the largest of the window.  ``y`` is a
product of three projections of one input: what grows first if the
mixer's scale drifts in bf16."""

import lfm2_flops

NAME = "sconv.out_rms_max"
UNIT = "rms"
LAYER = "short convolution"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    events = lfm2_flops.counter_over_window(run, NAME)
    if not events:
        return None
    run.note(
        f"short convolution counter over {len(events)} steps: {NAME} "
        f"first {events[0][NAME]:.6f}, last {events[-1][NAME]:.6f}"
    )
    return max(e[NAME] for e in events)
