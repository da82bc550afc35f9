"""What a state-space layer costs beside its projections and the scan:
device time per traced step under the program's ``ssm_conv`` (the
causal depthwise convolution of 4 taps over x, B and C, its bias and
SiLU), ``ssm_gates`` (softplus, the decay's mean, the ``D`` skip) and
``ssm_norm`` (the gate ``y * SiLU(z)`` and the grouped RMSNorm)
scopes; forward | remat copy | backward in the note."""

import nemotron_flops

NAME = "ssm.mix_ms_per_step"
UNIT = "ms"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = nemotron_flops.by_scope(run)
    seconds = found and nemotron_flops.scope_seconds(
        found, nemotron_flops.MIX_SCOPES
    )
    if not seconds:
        return None
    run.note("state-space mix, forward | remat copy | backward: "
             + nemotron_flops.parts_note(found, nemotron_flops.MIX_SCOPES))
    return seconds * 1e3
