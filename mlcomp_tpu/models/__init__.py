"""Flax model zoo (parity: reference contrib/model/ + contrib/segmentation/;
selection-by-name parity: contrib/catalyst/register.py:17-41)."""

from mlcomp_tpu.models.base import (
    create_model, model_names, param_count, register_model,
)
from mlcomp_tpu.models.mlp import MLP
from mlcomp_tpu.models.resnet import ResNet, BasicBlock, Bottleneck
from mlcomp_tpu.models.pipelined import PipelinedTransformerLM
from mlcomp_tpu.models.segmentation import (
    DeepLabV3, FPN, LinkNet, PSPNet, ResNetEncoder,
)
from mlcomp_tpu.models.encoders import (
    DenseNetEncoder, EfficientNetEncoder, EncoderClassifier, VGGEncoder,
    make_family_encoder,
)
from mlcomp_tpu.models.transformer import (
    TransformerConfig, TransformerLM,
)
from mlcomp_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM
from mlcomp_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM
from mlcomp_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3LM
from mlcomp_tpu.models.ouro import OuroConfig, OuroLM
from mlcomp_tpu.models.unet import UNet
from mlcomp_tpu.models.vit import ViT

__all__ = [
    'create_model', 'model_names', 'param_count', 'register_model',
    'MLP', 'ResNet', 'BasicBlock', 'Bottleneck',
    'TransformerConfig', 'TransformerLM', 'UNet', 'ViT',
    'Qwen3NextConfig', 'Qwen3NextLM', 'Lfm2MoeConfig', 'Lfm2MoeLM',
    'DeepseekV3Config', 'DeepseekV3LM', 'OuroConfig', 'OuroLM',
    'ResNetEncoder', 'FPN', 'LinkNet', 'PSPNet', 'DeepLabV3',
    'PipelinedTransformerLM',
    'VGGEncoder', 'DenseNetEncoder', 'EfficientNetEncoder',
    'EncoderClassifier', 'make_family_encoder',
]
