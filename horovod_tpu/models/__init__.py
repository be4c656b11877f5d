"""Model zoo for the framework's examples, benchmarks and tests.

The reference ships its models as examples (``examples/tensorflow2/
tensorflow2_synthetic_benchmark.py`` uses Keras ResNet-50;
``examples/pytorch`` BERT/ImageNet scripts). Here the models are first-class
library code, written in Flax with TPU-friendly defaults (bf16 compute,
static shapes, MXU-sized dims) so benchmarks and parallelism demos share
one implementation.
"""

from .mlp import MLP  # noqa: F401
from .resnet import ResNet18, ResNet34, ResNet50, ResNet101, ResNet152  # noqa: F401
from .transformer import Transformer, TransformerConfig  # noqa: F401
from .gpt2 import GPT2Config, GPT2LMModel  # noqa: F401
from .bert import BertConfig, BertModel  # noqa: F401
from .vit import ViT, ViTConfig  # noqa: F401
from .moe import MoEConfig, SwitchTransformerLM  # noqa: F401
from .latent_moe import LatentMoEConfig, LatentMoELM  # noqa: F401
from .window_moe import WindowMoEConfig, WindowMoELM  # noqa: F401
from .linear_moe import LinearMoEConfig, LinearMoELM  # noqa: F401
from .linear_dense import LinearDenseConfig, LinearDenseLM  # noqa: F401
