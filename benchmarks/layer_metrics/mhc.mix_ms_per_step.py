"""What the residual streams' mixing costs: device time per traced
step under the program's ``mhc_coeff`` (the norm of a token's ``n C``
numbers, ``phi``'s product, the sigmoids), ``mhc_sinkhorn`` (the
exponential and the row and column normalisations) and ``mhc_mix``
(``u = H_pre X`` and ``X' = H_res X + H_post^T y``) scopes, round both
sub-layers of every block, forward, remat copy and backward."""

import motif_flops

NAME = "mhc.mix_ms_per_step"
UNIT = "ms"
LAYER = "residual streams"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return motif_flops.scopes_ms_per_step(
        run, motif_flops.MIX_SCOPES, "residual streams"
    )
