"""Differential latent attention's projections: device time per traced
step under the program's ``gdla_q_latent`` (hidden -> query latent,
its RMSNorm, latent -> every held head's query), ``gdla_kv`` (hidden
-> kv latent + the one rope key, the latent's RMSNorm, latent -> every
held kv head's key and value) and ``gdla_out`` scopes, forward, remat
copy and backward; the note gives ``gdla_rope`` beside them."""

import motif_flops

NAME = "gdla.proj_ms_per_step"
UNIT = "ms"
LAYER = "differential attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    value = motif_flops.scopes_ms_per_step(
        run, motif_flops.PROJ_SCOPES, "differential attention's projections"
    )
    if value is not None:
        motif_flops.scopes_ms_per_step(
            run, (motif_flops.ROPE_SCOPE,),
            "rope and the layouts into the kernels",
        )
    return value
