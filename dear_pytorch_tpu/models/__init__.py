"""Model zoo — by-name instantiation parity with the reference benchmarks.

The reference CNN benchmark instantiates ``torchvision.models.<name>()`` from
a ``--model`` string plus a vendored InceptionV4 (reference
dear/imagenet_benchmark.py:88-95, dear/inceptionv4.py); the BERT benchmark
builds HF ``BertForPreTraining`` from local JSON configs
(dear/bert_benchmark.py:63-86). `get_model(name)` covers the union of the
names the reference sweep uses (benchmarks.py:21-28) and the rest of each
family.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from dear_pytorch_tpu.models.bert import (  # noqa: F401
    BERT_BASE,
    BERT_LARGE,
    BertConfig,
    BertForPreTraining,
    bert_pretraining_loss,
)
from dear_pytorch_tpu.models.gpt import (  # noqa: F401
    GPT2_LARGE,
    GPT2_MEDIUM,
    GPT2_SMALL,
    GptConfig,
    GptLmHeadModel,
    generate,
    gpt_lm_loss,
)
from dear_pytorch_tpu.models import glm_moe as _glm_moe
from dear_pytorch_tpu.models import lfm2_moe as _lfm2_moe
from dear_pytorch_tpu.models.glm_moe import (  # noqa: F401
    GLM47_FLASH,
    GLM_MOE_TINY,
    GlmMoeConfig,
    GlmMoeLmHeadModel,
    glm_moe_lm_loss,
)
from dear_pytorch_tpu.models.granite_hybrid import (  # noqa: F401
    GRANITE_4_0_H_MICRO,
    GRANITE_HYBRID_TINY,
    GraniteHybridConfig,
    GraniteHybridLmHeadModel,
    granite_hybrid_lm_loss,
)
from dear_pytorch_tpu.models.lfm2_moe import (  # noqa: F401
    LFM2_8B_A1B,
    LFM2_MOE_TINY,
    Lfm2MoeConfig,
    Lfm2MoeLmHeadModel,
    lfm2_moe_lm_loss,
)
from dear_pytorch_tpu.models.densenet import (  # noqa: F401
    DenseNet121,
    DenseNet169,
    DenseNet201,
)
from dear_pytorch_tpu.models.inception import InceptionV4  # noqa: F401
from dear_pytorch_tpu.models.mnist import MnistNet  # noqa: F401
from dear_pytorch_tpu.models.resnet import (  # noqa: F401
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from dear_pytorch_tpu.models.vgg import VGG11, VGG16, VGG19  # noqa: F401
from dear_pytorch_tpu.models.vit import ViTB16, ViTS16  # noqa: F401

_CNN_REGISTRY: dict[str, Callable] = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "densenet121": DenseNet121,
    "densenet169": DenseNet169,
    "densenet201": DenseNet201,
    "inceptionv4": InceptionV4,
    "vgg11": VGG11,
    "vgg16": VGG16,
    "vgg19": VGG19,
    "mnistnet": MnistNet,
    # beyond the reference zoo: vision transformers (models/vit.py)
    "vit_s16": ViTS16,
    "vit_b16": ViTB16,
}

_BERT_REGISTRY: dict[str, Any] = {
    "bert_base": BERT_BASE,
    "bert": BERT_LARGE,       # the reference calls BERT-Large just "bert"
    "bert_large": BERT_LARGE,
}

# Beyond the reference zoo: decoder-only causal LMs (models/gpt.py).
_GPT_REGISTRY: dict[str, Any] = {
    "gpt2": GPT2_SMALL,
    "gpt2_medium": GPT2_MEDIUM,
    "gpt2_large": GPT2_LARGE,
}


# Sparse decoders with latent attention (models/glm_moe.py).
_GLM_REGISTRY: dict[str, Any] = {
    "glm47_flash": GLM47_FLASH,
    "glm_moe_tiny": GLM_MOE_TINY,   # CPU tests and smoke runs only
}


# Hybrid sparse decoders: short convolutions among grouped-query attention
# (models/lfm2_moe.py).
_LFM2_REGISTRY: dict[str, Any] = {
    "lfm2_8b_a1b": LFM2_8B_A1B,
    "lfm2_moe_tiny": LFM2_MOE_TINY,   # CPU tests and smoke runs only
}


# Dense Mamba-2 / attention hybrids with the Granite multipliers
# (models/granite_hybrid.py).
_GRANITE_REGISTRY: dict[str, Any] = {
    "granite_4_0_h_micro": GRANITE_4_0_H_MICRO,
    "granite_hybrid_tiny": GRANITE_HYBRID_TINY,   # CPU tests and smoke runs
}

#: decoders `benchmarks/glm.py` runs: (registry, model class)
_DECODERS = (
    (_GLM_REGISTRY, GlmMoeLmHeadModel),
    (_LFM2_REGISTRY, Lfm2MoeLmHeadModel),
    (_GRANITE_REGISTRY, GraniteHybridLmHeadModel),
)


def cnn_names() -> list[str]:
    return sorted(_CNN_REGISTRY)


def bert_names() -> list[str]:
    return sorted(_BERT_REGISTRY)


def gpt_names() -> list[str]:
    return sorted(_GPT_REGISTRY)


def glm_names() -> list[str]:
    return sorted(_GLM_REGISTRY)


def lfm2_names() -> list[str]:
    return sorted(_LFM2_REGISTRY)


def granite_names() -> list[str]:
    return sorted(_GRANITE_REGISTRY)


def expert_assignments(cfg, intermediates):
    """``[expert layers, experts held]`` assignments made to each held
    expert of either sparse decoder, from the ``intermediates`` collection
    of one ``model.apply(..., mutable=["intermediates"])``."""
    family = _lfm2_moe if isinstance(cfg, Lfm2MoeConfig) else _glm_moe
    return family.expert_assignments(cfg, intermediates)


def get_model(name: str, *, dtype=jnp.float32, **kwargs):
    """Instantiate a model by benchmark name.

    CNN names return a flax module taking NHWC images; BERT names return
    ``BertForPreTraining`` for the matching config. Raises KeyError with the
    valid names otherwise.
    """
    key = name.lower()
    if key in _CNN_REGISTRY:
        return _CNN_REGISTRY[key](dtype=dtype, **kwargs)
    if key in _BERT_REGISTRY or key in _GPT_REGISTRY:
        cfg = _BERT_REGISTRY.get(key) or _GPT_REGISTRY[key]
        if dtype is not jnp.float32:
            import dataclasses

            cfg = dataclasses.replace(cfg, dtype=dtype)
        cls = BertForPreTraining if key in _BERT_REGISTRY else GptLmHeadModel
        return cls(cfg, **kwargs)
    for registry, cls in _DECODERS:
        if key in registry:
            import dataclasses

            return cls(dataclasses.replace(registry[key], dtype=dtype),
                       **kwargs)
    raise KeyError(
        f"unknown model {name!r}; CNNs: {cnn_names()}, BERT: {bert_names()}, "
        f"GPT: {gpt_names()}, GLM: {glm_names()}, LFM2: {lfm2_names()}, "
        f"Granite: {granite_names()}"
    )


def is_bert(name: str) -> bool:
    return name.lower() in _BERT_REGISTRY


def is_gpt(name: str) -> bool:
    return name.lower() in _GPT_REGISTRY


def dropout_free(cfg):
    """``cfg`` with every ``*dropout*`` probability field zeroed — the ONE
    place that knows the dropout field list (the benchmark CLIs'
    ``--dropout0`` and bench.py's GPT headline all call this; a per-site
    field list would silently drift when a config grows a new dropout
    knob). Works for any of the model config dataclasses."""
    import dataclasses

    zeros = {f.name: 0.0 for f in dataclasses.fields(cfg)
             if "dropout" in f.name}
    return dataclasses.replace(cfg, **zeros)
