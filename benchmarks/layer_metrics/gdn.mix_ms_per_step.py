"""What a linear-attention layer costs beside its matmuls and the
rule: device time per traced step under the program's ``gdn_conv``
(three causal depthwise convolutions + SiLU), ``gdn_gates`` (L2 norms,
write strength, log decay) and ``gdn_norm`` (the gated RMSNorm)
scopes."""

import gdn_flops

NAME = "gdn.mix_ms_per_step"
UNIT = "ms"
LAYER = "linear attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    parts = {
        name: gdn_flops.seconds_per_step(run, name)
        for name in gdn_flops.MIX_SCOPES
    }
    if not any(parts.values()):
        return None
    run.note("linear-attention mix: " + ", ".join(
        f"{name} {found[0] * 1e3:.3f} ms ({found[1]:.0f} operations)"
        for name, found in parts.items() if found
    ))
    return sum(found[0] for found in parts.values() if found) * 1e3
