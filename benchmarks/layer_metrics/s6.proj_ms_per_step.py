"""The state-space mixer's matmuls: device time per traced step under
``s6_in_proj`` (hidden x 2 E), ``s6_x_proj`` (E x (R + 2 N)) and
``s6_out_proj`` (E x hidden), forward, the remat copy and both
gradients (``dt_proj``'s R x E matmul is fused with the softplus under
``s6_params``: ``s6.mix_ms_per_step``)."""

import jamba_flops

NAME = "s6.proj_ms_per_step"
UNIT = "ms"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return jamba_flops.scopes_ms(
        run, jamba_flops.PROJ_SCOPES, "state-space mixer projections"
    )
