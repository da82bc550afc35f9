"""What a KDA layer costs beside its matmuls and the rule: device time
per traced step under the program's ``kda_conv`` (three causal
depthwise convolutions + SiLU), ``kda_gates`` (L2 norms, write
strength, the log-decay a channel) and ``kda_norm`` (the head norm and
the sigmoid output gate) scopes."""

import ling_flops
import sarvam_flops

NAME = "kda.mix_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, ling_flops.MIX_SCOPES, "KDA mix"
    )
