"""In a cell with saves: the 95th percentile (nearest rank) of the
time between consecutive completed steps, the intervals that carry a
save left out.  It reads the steps that run beside the asynchronous
half of a DISK save and the agent's persist (0.37-0.46 s for the step
after a DISK save, my chip run, PR 24).  Recorded, not judged: a
handful of slowed steps among 150 move it by tens of per cent from run
to run."""

import math

NAME = "trainer.step_ms_p95_saving"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(run):
    if not run.report["window"]["saves"]:
        return None
    plain = sorted(run.plain_step_intervals())
    return plain[math.ceil(0.95 * len(plain)) - 1] * 1e3
