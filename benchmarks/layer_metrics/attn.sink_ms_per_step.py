"""What a softmax's learned sink costs outside the flash kernels:
device time per traced step under the program's ``attn_sink`` scope
(the reduction of the sink's gradient from the saved ``lse`` and
``delta`` rows in the backward rule, and the counter's ``exp(sink -
lse)`` mean)."""

import mimo_flops

NAME = "attn.sink_ms_per_step"
UNIT = "ms"
LAYER = "window attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return mimo_flops.scopes_ms_per_step(
        run, (mimo_flops.SINK_SCOPE,), "the sink outside the kernels"
    )
