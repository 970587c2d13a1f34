"""Model families. Imported here: the ones a workflow builds or trains
directly. The families served through ``PagedInferenceEngine`` answer
``models/serving.py``'s protocol from modules of their own, imported where
they are used: ``llama`` (Llama / Mistral), ``nemotron_h``, ``solar_open2``,
``deepseek_v3``, ``cohere2_moe``, ``jamba``, ``zaya``, ``minicpm_sala``,
``brumby`` and ``ouro``."""

from lzy_tpu.models import bert, llama, resnet
from lzy_tpu.models.common import (
    count_params,
    cross_entropy_loss,
    param_logical_axes,
    unbox,
)
from lzy_tpu.models.bert import BertConfig, BertMlm
from lzy_tpu.models.llama import Llama, LlamaConfig
from lzy_tpu.models.resnet import ResNet, ResNetConfig

__all__ = [
    "bert",
    "llama",
    "resnet",
    "count_params",
    "cross_entropy_loss",
    "param_logical_axes",
    "unbox",
    "BertConfig",
    "BertMlm",
    "Llama",
    "LlamaConfig",
    "ResNet",
    "ResNetConfig",
]

from lzy_tpu.models.generate import generate  # noqa: E402
from lzy_tpu.models.moe import MoeConfig, MoeMlp  # noqa: E402
from lzy_tpu.models.t5 import T5, T5Config, t5_generate  # noqa: E402

__all__ += ["generate", "MoeConfig", "MoeMlp", "T5", "T5Config", "t5_generate"]
