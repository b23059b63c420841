"""Model factory (reference component C2).

The reference selects any lowercase callable from
``torchvision.models.__dict__`` by name (reference 1.dataparallel.py:23-24,
97-102). tpu_dist keeps the same UX — ``create_model("resnet50")`` — over an
explicit registry (no torchvision on TPU). ``--pretrained`` takes a local
checkpoint PATH to warm-start from (engine.checkpoint.load_warmstart /
graft_params — fine-tune keeps fresh init for shape-mismatched heads);
boolean True still raises a clear error because a zero-egress environment
has no weights to download.

Each entry carries its *kind* ("image" classifier vs "lm") so construction
and engine dispatch stay in one place: image ctors take ``num_classes``, LM
ctors take vocab/layer kwargs, and the image Trainer refuses LM archs with a
clear error instead of crashing inside flax init.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax.numpy as jnp

from tpu_dist.models import (cnn_zoo, inception, lenet, mobile, moe, resnet,
                             transformer, vit)

# name -> (constructor, kind)
_REGISTRY: Dict[str, Tuple[Callable, str]] = {
    "resnet18": (resnet.ResNet18, "image"),
    "resnet34": (resnet.ResNet34, "image"),
    "resnet50": (resnet.ResNet50, "image"),
    "resnet101": (resnet.ResNet101, "image"),
    "resnet152": (resnet.ResNet152, "image"),
    "resnext50_32x4d": (resnet.ResNeXt50_32x4d, "image"),
    "resnext101_32x8d": (resnet.ResNeXt101_32x8d, "image"),
    "wide_resnet50_2": (resnet.WideResNet50_2, "image"),
    "wide_resnet101_2": (resnet.WideResNet101_2, "image"),
    "vgg11": (cnn_zoo.VGG11, "image"),
    "vgg13": (cnn_zoo.VGG13, "image"),
    "vgg16": (cnn_zoo.VGG16, "image"),
    "vgg19": (cnn_zoo.VGG19, "image"),
    "densenet121": (cnn_zoo.DenseNet121, "image"),
    "densenet161": (cnn_zoo.DenseNet161, "image"),
    "densenet169": (cnn_zoo.DenseNet169, "image"),
    "densenet201": (cnn_zoo.DenseNet201, "image"),
    "alexnet": (cnn_zoo.AlexNet, "image"),
    "googlenet": (inception.GoogLeNet, "image"),
    "inception_v3": (inception.InceptionV3, "image"),
    "mnasnet0_5": (mobile.MnasNet0_5, "image"),
    "mnasnet0_75": (mobile.MnasNet0_75, "image"),
    "mnasnet1_0": (mobile.MnasNet1_0, "image"),
    "mnasnet1_3": (mobile.MnasNet1_3, "image"),
    "mobilenet_v2": (cnn_zoo.MobileNetV2, "image"),
    "mobilenet_v3_large": (mobile.MobileNetV3Large, "image"),
    "mobilenet_v3_small": (mobile.MobileNetV3Small, "image"),
    "squeezenet1_0": (cnn_zoo.SqueezeNet1_0, "image"),
    "squeezenet1_1": (cnn_zoo.SqueezeNet, "image"),
    "shufflenet_v2_x0_5": (cnn_zoo.ShuffleNetV2_x0_5, "image"),
    "shufflenet_v2_x1_0": (cnn_zoo.ShuffleNetV2, "image"),
    "shufflenet_v2_x1_5": (cnn_zoo.ShuffleNetV2_x1_5, "image"),
    "shufflenet_v2_x2_0": (cnn_zoo.ShuffleNetV2_x2_0, "image"),
    "efficientnet_b0": (cnn_zoo.EfficientNet, "image"),
    "lenet": (lenet.LeNet, "image"),
    "mnist_net": (lenet.LeNet, "image"),  # reference 5.2 'Net' alias
    "vit_tiny": (vit.ViTTiny, "image"),
    "vit_small": (vit.ViTSmall, "image"),
    "vit_base": (vit.ViTBase, "image"),
    "vit_cifar": (vit.ViTCifar, "image"),
    "transformer_lm": (transformer.TransformerLM, "lm"),
    "tiny_lm": (transformer.tiny_lm, "lm"),
    "moe_lm": (moe.MoETransformerLM, "lm"),
    # the hybrid state-space / attention block (models.hybrid: Mamba-1
    # mixers beside grouped-head attention, RMSNorm, gated MLP, tied head).
    # SERVING ONLY so far: ServeEngine serves it (pages + per-slot state)
    # and the plain full-sequence forward runs; LMTrainer, the scan's
    # backward pass and tp/fsdp specs for it are not built. Imported on
    # first use, so that nothing that never asks for it pays for it.
    "hybrid_lm": (lambda **kw: _hybrid_lm(**kw), "lm"),
    # the decoder-hybrid-decoder block (models.phi4flash: Mamba-1, window
    # and full differential attention, cross layers over one shared KV
    # layer, gated memory units). Serving and the plain forward, like
    # hybrid_lm; no trainer holds it.
    "phi4flash_lm": (lambda **kw: _phi4flash_lm(**kw), "lm"),
    # one-sublayer blocks by a pattern string (models.nemotron_h: Mamba-2,
    # latent mixture-of-experts held as one rank of an expert-parallel
    # group, grouped-head attention). Serving and the plain forward; no
    # trainer holds it.
    "nemotron_h_lm": (lambda **kw: _nemotron_h_lm(**kw), "lm"),
}


def _hybrid_lm(**kw):
    from tpu_dist.models.hybrid import HybridLM

    return HybridLM(**kw)


def _phi4flash_lm(**kw):
    from tpu_dist.models.phi4flash import Phi4FlashLM

    return Phi4FlashLM(**kw)


def _nemotron_h_lm(**kw):
    from tpu_dist.models.nemotron_h import NemotronHLM

    return NemotronHLM(**kw)


model_names = sorted(_REGISTRY)  # reference 1.dataparallel.py:23-24 equivalent


def register(name: str, kind: str = "image"):
    def deco(ctor: Callable):
        _REGISTRY[name] = (ctor, kind)
        return ctor
    return deco


def model_kind(arch: str) -> str:
    if arch not in _REGISTRY:
        raise ValueError(f"unknown arch {arch!r}; choose from {model_names}")
    return _REGISTRY[arch][1]


def create_model(arch: str, num_classes: int = 10, dtype=jnp.float32,
                 pretrained=False, warmstart_handled: bool = False,
                 **kwargs):
    if pretrained is True:
        raise ValueError(
            "--pretrained without a path requires downloaded weights; this "
            "environment has no egress. Pass --pretrained PATH (a local "
            "checkpoint, e.g. an {arch}-model_best.msgpack from this repo) "
            "to warm-start, or train from scratch.")
    if pretrained and not warmstart_handled:
        # a str path is handled by the ENGINES (params live outside the
        # module in jax — this factory only builds architecture); they pass
        # warmstart_handled=True. Any other caller handing a path here
        # would get a fresh-init model while believing it loaded weights —
        # fail loudly instead of silently ignoring the request.
        raise ValueError(
            f"create_model does not load weights: pretrained={pretrained!r} "
            "would be silently ignored. Use Trainer/LMTrainer (which graft "
            "the checkpoint onto the init), or load it yourself via "
            "engine.checkpoint.load_warmstart + graft_params.")
    kind = model_kind(arch)
    ctor = _REGISTRY[arch][0]
    if kind == "lm":
        return ctor(dtype=dtype, **kwargs)  # vocab_size etc. via kwargs
    return ctor(num_classes=num_classes, dtype=dtype, **kwargs)
