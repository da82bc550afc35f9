"""Device time of the channel-wise delta rule per traced step: the
operations under the program's ``kda_rule`` scope (the layouts into
heads-leading operands, the running sums of the log-decays, the
``kda_fwd`` / ``kda_bwd`` kernels), forward, the remat copies and
backward together."""

import ling_flops

NAME = "kda.rule_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = ling_flops.rule_seconds_per_step(run)
    if found is None:
        return None
    seconds, count, _ = found
    run.note(
        f"channel-wise delta rule: {count:.1f} device operations a "
        f"step, {seconds * 1e3:.3f} ms under {ling_flops.RULE_SCOPE}"
    )
    return seconds * 1e3
