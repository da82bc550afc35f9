"""The gated short convolution's share of its roofline: the least time
the chip could take for the bytes one step REQUIRES between the conv
layers' matmuls (forward ``B``, ``C``, ``u`` read and ``y`` written
once, backward those and ``dy`` read and the three gradients written
once; the remat copy not counted: ``lfm2_flops.py``) over the device
time under ``sconv_mix``: the same work whatever implements it, so a
plain form reads low, not absent."""

import lfm2_flops

NAME = "sconv.mix_roofline_pct"
UNIT = "%"
LAYER = "short convolution"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def read(run):
    cfg, traffic = run.config, run.traffic
    if "conv_L_cache" not in cfg:
        return None
    found = lfm2_flops.seconds_per_step(run, lfm2_flops.MIX_SCOPE)
    if found is None:
        return None
    seconds = found[0]
    batch, seq = traffic["batch"], traffic["seq"]
    nbytes = lfm2_flops.mix_bytes_per_step(cfg, batch, seq)
    least, bound = run.flops.roofline_seconds(
        lfm2_flops.mix_flops_per_step(cfg, batch, seq), nbytes,
        run.report["device"]["kind"],
    )
    run.note(
        f"short convolution roofline: least {least * 1e3:.3f} ms a "
        f"step, bound by {bound} ({nbytes / 1e9:.3f} GB required); "
        f"the operations took {seconds * 1e3:.3f} ms"
    )
    return 100.0 * least / seconds
