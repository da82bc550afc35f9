"""Latent attention's four projections: device time per traced step
under the program's ``mla_q`` (the query, one projection), ``mla_kv_down``
(hidden -> latent + rope key, and the latent's RMSNorm), ``mla_kv_up``
(latent -> every held head's key and value) and ``mla_out`` scopes,
forward, remat copy and backward."""

import sarvam_flops

NAME = "mla.proj_ms_per_step"
UNIT = "ms"
LAYER = "latent attention"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    return sarvam_flops.scopes_ms_per_step(
        run, sarvam_flops.PROJ_SCOPES, "latent projections"
    )
