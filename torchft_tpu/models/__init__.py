from torchft_tpu.models.mlp import MLP
from torchft_tpu.models.moe import MoEMLP, RoutedMoEMLP, ep_rules
from torchft_tpu.models.resnet import ResNet, ResNet18, ResNet34, ResNet50
from torchft_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    causal_lm_loss,
    chunked_causal_lm_loss,
    chunked_weighted_nll,
    head_kernel,
    llama2_7b_config,
    llama2_13b_config,
    llama2_70b_config,
    looped_causal_lm_loss,
    moe_lm_loss,
    mtp_causal_lm_loss,
    sparse_lm_loss,
    sparse_lm_losses,
    tiny_config,
    tp_rules,
)
from torchft_tpu.models.mla import LatentAttention
from torchft_tpu.models.linear_attention import GatedDeltaNet
from torchft_tpu.models.mamba2 import Mamba2Mixer
from torchft_tpu.models.short_conv import ShortConv

__all__ = [
    "GatedDeltaNet",
    "LatentAttention",
    "MLP",
    "Mamba2Mixer",
    "MoEMLP",
    "RoutedMoEMLP",
    "ShortConv",
    "ep_rules",
    "moe_lm_loss",
    "mtp_causal_lm_loss",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "Transformer",
    "TransformerConfig",
    "causal_lm_loss",
    "chunked_causal_lm_loss",
    "chunked_weighted_nll",
    "head_kernel",
    "llama2_7b_config",
    "llama2_13b_config",
    "llama2_70b_config",
    "looped_causal_lm_loss",
    "sparse_lm_loss",
    "sparse_lm_losses",
    "tiny_config",
    "tp_rules",
]
