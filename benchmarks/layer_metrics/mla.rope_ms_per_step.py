"""What latent attention costs between its projections and the flash
kernels: device time per traced step under the program's ``mla_rope``
scope (yarn rope on the query's and the one key head's rope part, the
key's assembly from its per-head part and the shared rope head, the
layouts into and out of the kernels)."""

import sarvam_flops

NAME = "mla.rope_ms_per_step"
UNIT = "ms"
LAYER = "latent attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, (sarvam_flops.ROPE_SCOPE,), "rope and key assembly"
    )
