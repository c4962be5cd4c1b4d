"""Model zoo for benchmarks and examples.

The reference ships no model code of its own — its benchmark models come
from ``tf_cnn_benchmarks`` / torchvision (ResNet-50/101, VGG-16,
Inception V3 — ``docs/benchmarks.rst:16-83``, ``/root/reference/examples/
pytorch_synthetic_benchmark.py:24`` pulls ``models.resnet50``) and its
example nets are small MNIST CNNs (``examples/pytorch_mnist.py:44-60``).
This package provides TPU-first flax equivalents of that model surface so
the framework is benchmarkable and usable standalone:

* ``resnet``      — ResNet v1.5 family (18/34/50/101/152), the headline
  benchmark model (``BASELINE.md``).
* ``vgg``         — VGG-16, the bandwidth-bound scaling stress test.
* ``simple``      — MNIST-scale ConvNet/MLP for the example suite.
* ``transformer`` — decoder-only Transformer with sequence-parallel (ring
  attention) support; not in the 2019 reference, first-class here. A
  layer is a (mixer, feed-forward) pair read from a layer pattern.
* ``mla``         — multi-head latent attention (the DeepSeek-V3 family's
  mixer), through the one flash kernel at two head sizes.
* ``ssm``         — a Mamba-2 state-space mixer with a chunked scan (the
  ``nemotron_h`` family's).
* ``kda``         — Kimi Delta Attention (the ``kimi_linear`` family's
  gated delta rule with a decay a channel), chunked; the fourth mixer
  kind.
* ``moe``         — capacity-dispatch mixture of experts over an expert
  mesh axis.
* ``experts``     — a no-drop expert layer that holds one chip's share of
  the experts (SwiGLU or relu² bodies), and the dense and shared
  feed-forwards of the same bodies.

All models are NHWC, bf16-compute/fp32-param by default — the layout the
MXU wants.
"""

from horovod_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.simple import MNISTConvNet, MLP
from horovod_tpu.models.vgg import VGG16
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.models.moe import MoE
from horovod_tpu.models.mla import LatentAttention, LatentAttentionConfig
from horovod_tpu.models.experts import (ExpertShare, ExpertShareConfig,
                                        Relu2, SwiGLU)
from horovod_tpu.models.ssm import StateSpaceConfig, StateSpaceMixer
from horovod_tpu.models.kda import DeltaAttention, DeltaAttentionConfig

__all__ = [
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "MNISTConvNet", "MLP", "VGG16", "Transformer", "TransformerConfig",
    "MoE", "LatentAttention", "LatentAttentionConfig", "ExpertShare",
    "ExpertShareConfig", "SwiGLU", "Relu2", "StateSpaceConfig",
    "StateSpaceMixer", "DeltaAttention", "DeltaAttentionConfig",
]
