"""A state-space layer's two projections: device time per traced step
under the program's ``ssm_in_proj`` (hidden -> z | xBC | dt) and
``ssm_out_proj`` (inner -> hidden) scopes; forward | remat copy |
backward in the note."""

import nemotron_flops

NAME = "ssm.proj_ms_per_step"
UNIT = "ms"
LAYER = "state-space layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    found = nemotron_flops.by_scope(run)
    seconds = found and nemotron_flops.scope_seconds(
        found, nemotron_flops.PROJ_SCOPES
    )
    if not seconds:
        return None
    run.note("state-space projections, forward | remat copy | backward: "
             + nemotron_flops.parts_note(found, nemotron_flops.PROJ_SCOPES))
    return seconds * 1e3
