"""Model families: MLP, CIFAR/ImageNet ResNets, Transformer LM, MoE, the
sparse hybrid LM (Gated DeltaNet + gated attention + top-k experts) and the
conv-hybrid sparse LM (gated short convolutions + grouped-query attention
+ sigmoid-routed experts behind leading dense layers) and the
latent-attention sparse LM (multi-head latent attention + scaled
sigmoid-routed experts beside ungated shared ones)."""

from kfac_tpu.models.conv_moe import ConvMoELM

from kfac_tpu.models.lora import LoRADense
from kfac_tpu.models.mla import LatentAttention, LatentMoELM
from kfac_tpu.models.mlp import MLP
from kfac_tpu.models.resnet import (
    CifarResNet,
    ImageNetResNet,
    resnet20,
    resnet32,
    resnet50,
    resnet56,
)
from kfac_tpu.models.deltanet import GatedDeltaNet
from kfac_tpu.models.moe import (
    MoEMLP,
    SparseMoE,
    expert_tp_overrides,
    load_balance_loss,
)
from kfac_tpu.models.transformer import (
    HybridLM,
    TransformerLM,
    hybrid_lm_loss,
    lm_loss,
)

__all__ = [
    'LoRADense',
    'MLP',
    'MoEMLP',
    'CifarResNet',
    'ConvMoELM',
    'GatedDeltaNet',
    'HybridLM',
    'ImageNetResNet',
    'LatentAttention',
    'LatentMoELM',
    'SparseMoE',
    'TransformerLM',
    'expert_tp_overrides',
    'hybrid_lm_loss',
    'lm_loss',
    'load_balance_loss',
    'resnet20',
    'resnet32',
    'resnet50',
    'resnet56',
]
