"""Model zoo: ResNet family + small CNNs (flax.linen, NHWC, bf16-ready).

TPU-native re-expression of the reference's L2 model layer (SURVEY.md §1):
from-scratch ResNet18 (`/root/reference/setup/resnet18.py`), torchvision-style
ResNet18/34/50 with ImageNet stems, the MNIST `Net` CNN
(`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:75-92`),
and frozen-backbone transfer-learning wrappers
(`/root/reference/01_torch_distributor/02_cifar_torch_distributor_resnet.py:141-159`).
Beside them the decoder LM (``TransformerLM``: GPT-2's layer, latent or
grouped-query attention, LFM2's gated short-convolution mixers and
Mellum2's sliding-window layers beside full-attention layers by
``layer_types``, rotary tables by kind of layer, dense or routed MLPs) and
``BlockDiffusionLM`` on its rows.
"""

from tpuframe.models.cnn import MnistNet
from tpuframe.models.transformer import TransformerLM, transformer_tp_rules
from tpuframe.models.resnet import (
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
)
from tpuframe.models.norm import ReplicaGroupedBatchNorm
from tpuframe.models.transfer import TransferClassifier, backbone_frozen_labels
from tpuframe.models.vit import ViT, ViT_B16, ViT_S16, vit_tp_rules

__all__ = [
    "MnistNet",
    "TransformerLM",
    "transformer_tp_rules",
    "BasicBlock",
    "Bottleneck",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ReplicaGroupedBatchNorm",
    "ViT",
    "ViT_S16",
    "ViT_B16",
    "vit_tp_rules",
    "TransferClassifier",
    "backbone_frozen_labels",
]

from tpuframe.models.moe import MoEMLP, moe_rules  # noqa: E402
__all__ += ["MoEMLP", "moe_rules"]

from tpuframe.models.block_diffusion import BlockDiffusionLM  # noqa: E402
__all__ += ["BlockDiffusionLM"]
