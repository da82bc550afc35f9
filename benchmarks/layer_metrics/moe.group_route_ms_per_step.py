"""What choosing costs a router whose top-k is limited to groups:
device time per traced step under ``moe_router`` (scores over ALL the
router's outputs, the final top-k, the chosen scores, counts, the
bias's rule) and ``moe_group_select`` (a group's two largest, the best
groups, the mask, the groups a token chose)."""

import ling_flops
import sarvam_flops

NAME = "moe.group_route_ms_per_step"
UNIT = "ms"
LAYER = "experts"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, ling_flops.GROUP_ROUTE_SCOPES, "group-limited routing"
    )
