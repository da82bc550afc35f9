"""The least log-decay a step of any channel, head, token and KDA
layer: the program's own counter ``kda.log_decay_min`` on the
``train_step`` events, the least of the window.  The family's safe
gate holds it at or above ``kda_lower_bound`` (-5), which is what the
kernels' float32 diagonal blocks are sized for.  The note gives
``kda.state_rms_max``, the largest rms of a layer's final state."""

import ling_flops

NAME = "kda.log_decay_min"
UNIT = "log_decay"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(run):
    events = ling_flops.counter_over_window(run, NAME)
    if not events:
        return None
    rms = max(e.get("kda.state_rms_max", float("nan")) for e in events)
    run.note(
        f"KDA counters over {len(events)} steps: {NAME} first "
        f"{events[0][NAME]:.5f}, last {events[-1][NAME]:.5f} (bound "
        f"{run.config['kda_lower_bound']}); kda.state_rms_max at most "
        f"{rms:.5f}"
    )
    return min(e[NAME] for e in events)
