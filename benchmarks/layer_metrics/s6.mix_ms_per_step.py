"""What stands between the state-space mixer's matmuls and its scan:
device time per traced step under ``s6_conv`` (the depthwise
convolution's ``conv_fwd`` / ``conv_bwd`` kernels), ``s6_params`` (the
three inner norms, ``dt_proj``'s sum, softplus, ``A``, the decay's
mean) and ``s6_gate`` (``y * silu(z)``)."""

import jamba_flops

NAME = "s6.mix_ms_per_step"
UNIT = "ms"
LAYER = "selective scan layers"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return jamba_flops.scopes_ms(
        run, jamba_flops.MIX_SCOPES, "state-space mixer between matmuls"
    )
