"""Median time between consecutive completed steps of the window, the
intervals that carry a save left out: the steady statistic beside the
end-to-end tail."""

import statistics

NAME = "trainer.step_ms_median"
UNIT = "ms"
LAYER = "trainer loop"
MOVES = "tokens_per_s"
SOURCE = "host_clock"


def read(run):
    plain = run.plain_step_intervals()
    if not plain:
        return None
    return statistics.median(plain) * 1e3
