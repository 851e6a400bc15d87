"""First-class SPMD model definitions (beyond the gluon model_zoo).

The gluon `model_zoo.vision` covers the reference's CNN zoo
(`python/mxnet/gluon/model_zoo/`); this package holds TPU-first model
families built directly on `mxnet_tpu.parallel` — sharded transformers with
ring attention, the long-context/distributed flagships the mesh design
exists for.
"""
from . import transformer
from .transformer import TransformerLMConfig, TransformerLM
from . import hybrid
from .hybrid import HybridLMConfig, HybridLM
from . import latent_moe
from .latent_moe import LatentMoELMConfig, LatentMoELM
from . import window_moe
from .window_moe import WindowMoELMConfig, WindowMoELM
from . import resnet
from .resnet import resnet50_symbol

__all__ = ["transformer", "TransformerLMConfig", "TransformerLM",
           "hybrid", "HybridLMConfig", "HybridLM",
           "latent_moe", "LatentMoELMConfig", "LatentMoELM",
           "window_moe", "WindowMoELMConfig", "WindowMoELM",
           "resnet", "resnet50_symbol"]
