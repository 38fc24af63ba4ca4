"""Model zoo for the BASELINE workloads (SURVEY §6):
llama (flagship), ouro (looped llama-style stack), nemotron_h (hybrid of
Mamba-2, sparse-expert and attention blocks), gpt, ernie/bert, unet."""

from paddle_tpu.models.ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForSequenceClassification,
    ErnieModel,
)
from paddle_tpu.models.gpt import (  # noqa: F401
    GPTConfig,
    GPTForPretraining,
    GPTModel,
    build_gpt_pipeline,
    gpt_shard_fn,
)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from paddle_tpu.models.nemotron_h import NemotronHConfig, NemotronHForCausalLM, NemotronHModel  # noqa: F401
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM, OuroModel  # noqa: F401
from paddle_tpu.models.sd_unet import UNet2DConditionModel, UNetConfig  # noqa: F401
