"""What routing costs a chip that holds a range of its layers'
experts: device time per traced step under ``moe_router`` (scores
over ALL the router's outputs, top-k, the bias's rule),
``moe_dispatch`` (sort, layout, the row gather at the static ``tokens
x k`` + one tile an expert held) and ``moe_combine`` (the gather back,
weighting)."""

import sarvam_flops

NAME = "moe.held_route_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, sarvam_flops.ROUTE_SCOPES, "held routing"
    )
