"""Device time of the gated delta rule per traced step: the operations
under the program's ``gdn_rule`` scope (the chunk-wise rule: the
``C x C`` inverse, the hand-over scan, the outputs), forward, the
remat copies and backward together, a scan counted once
(``gdn_flops.py`` says how)."""

import gdn_flops

NAME = "gdn.rule_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = gdn_flops.seconds_per_step(run, gdn_flops.RULE_SCOPE)
    if found is None:
        return None
    seconds, count, bodies = found
    run.note(
        f"gated delta rule: {count:.1f} device operations a step, "
        f"{seconds * 1e3:.3f} ms; the bodies of its loops "
        f"({bodies * 1e3:.3f} ms) are inside their %while and not "
        "counted again"
    )
    return seconds * 1e3
