"""What the routing mechanism costs beyond its matmuls: device time per
traced step under the program's ``moe_router`` (logits, softmax,
top-k, the auxiliary losses), ``moe_dispatch`` (sort, gather) and
``moe_combine`` (weighting, the gather back) scopes."""

import moe_flops

NAME = "moe.route_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    parts = {
        name: moe_flops.seconds_per_step(run, name)
        for name in moe_flops.ROUTE_SCOPES
    }
    if not any(parts.values()):
        return None
    run.note("routing: " + ", ".join(
        f"{name} {found[0] * 1e3:.3f} ms ({found[1]:.0f} operations)"
        for name, found in parts.items() if found
    ))
    return sum(found[0] for found in parts.values() if found) * 1e3
