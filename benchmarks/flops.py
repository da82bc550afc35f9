"""The benchmark's own arithmetic: what a GPT-2 training step requires
of the chip, and the chip's published peaks.

Everything here is computed from a configuration file's sizes (the HF
key names: ``n_layer``, ``n_embd``, ``n_head``, ``n_inner``,
``vocab_size``) and the traffic's ``batch`` and ``seq``.  Required
means what the forward and backward passes need: recomputed operations
(remat, the backward kernels' second pass over QK^T) are NOT counted,
so a share of a peak built on these numbers cannot pass 100%.

Checked against hand-worked values in ``tests/test_flops.py``.
"""

# Published peaks of one chip, keyed by ``device_kind`` as jax reports
# it.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 16 GB HBM2e at 819 GB/s per chip).  A kind that is not here is
# an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg["n_embd"] // cfg["n_head"]


def matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied by: per block the fused
    qkv (h x 3h), the output projection (h x h) and the two MLP
    matrices (h x inner, inner x h); plus the output head (vocab x h,
    tied to the embedding but still a matmul).  Embedding ROWS (a
    lookup), biases and norms are not matmul parameters."""
    h, inner = cfg["n_embd"], cfg["n_inner"]
    per_block = 3 * h * h + h * h + 2 * h * inner
    return cfg["n_layer"] * per_block + cfg["vocab_size"] * h


def total_params(cfg: dict) -> int:
    """Every parameter the train state holds (tied head counted once):
    what the optimizer state and the checkpoint are sized by."""
    h, inner = cfg["n_embd"], cfg["n_inner"]
    per_block = (
        3 * h * h + 3 * h          # qkv kernel + bias
        + h * h + h                # o_proj
        + h * inner + inner        # fc_in
        + inner * h + h            # fc_out
        + 4 * h                    # two layernorms
    )
    return (
        cfg["vocab_size"] * h + cfg["n_positions"] * h
        + cfg["n_layer"] * per_block + 2 * h
    )


def attention_flops_per_token(cfg: dict, seq: int) -> float:
    """Causal self-attention, forward + backward, per token.

    Forward, full attention: QK^T and PV, 2 * (2 * seq * h) per token
    per layer.  Backward: twice the forward (dQ, dK, dV, dP).  Causal
    masking halves all of it: 12 * seq * h / 2 = 6 * seq * h."""
    return 6.0 * cfg["n_layer"] * seq * cfg["n_embd"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Required FLOPs per trained token: 6 per matmul parameter
    (2 forward, 4 backward) plus causal attention."""
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq)


def attention_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """What every flash-attention call of one step has to compute,
    all layers, forward and backward, recompute not counted."""
    return attention_flops_per_token(cfg, seq) * batch * seq


def attention_bytes_per_step(
    cfg: dict, batch: int, seq: int, itemsize: int = 2
) -> float:
    """HBM traffic attention cannot avoid, all layers of one step: the
    forward reads q, k, v and writes o (4 tensors of batch x seq x h);
    the backward reads q, k, v, o, do and writes dq, dk, dv (8).  The
    per-row softmax statistics (seq floats per head) are left out:
    under 1% of the rest."""
    tensor = batch * seq * cfg["n_embd"] * itemsize
    return 12.0 * tensor * cfg["n_layer"]


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """The least time the chip could take, and which peak bounds it:
    ``(seconds, "flops" | "bytes")``."""
    p = peak(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = nbytes / p["bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "flops"
    return t_bytes, "bytes"
