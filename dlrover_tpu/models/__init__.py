"""Model zoo: TPU-native reference models used by the trainer, the
strategy engine's dry-runner, and the benchmarks."""

from dlrover_tpu.models.bailing_hybrid import (
    BailingHybrid,
    BailingHybridConfig,
)
from dlrover_tpu.models.gpt import GPT, GPTConfig
from dlrover_tpu.models.jamba import Jamba, JambaConfig
from dlrover_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from dlrover_tpu.models.llama import Llama, LlamaConfig
from dlrover_tpu.models.mimo_v2 import MiMoV2, MiMoV2Config
from dlrover_tpu.models.motif import Motif, MotifConfig
from dlrover_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from dlrover_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from dlrover_tpu.models.ouro import Ouro, OuroConfig
from dlrover_tpu.models.sarvam_mla import SarvamMla, SarvamMlaConfig
from dlrover_tpu.models.losses import (
    chunked_cross_entropy,
    chunked_loss_fn,
)

__all__ = [
    "BailingHybrid",
    "BailingHybridConfig",
    "GPT",
    "GPTConfig",
    "Jamba",
    "JambaConfig",
    "Lfm2Moe",
    "Lfm2MoeConfig",
    "Llama",
    "LlamaConfig",
    "MiMoV2",
    "MiMoV2Config",
    "Motif",
    "MotifConfig",
    "NemotronH",
    "NemotronHConfig",
    "OlmoHybrid",
    "OlmoHybridConfig",
    "Ouro",
    "OuroConfig",
    "SarvamMla",
    "SarvamMlaConfig",
    "chunked_cross_entropy",
    "chunked_loss_fn",
]
