"""ResNet family (reference component C2's main archs).

The reference pulls ResNets from ``torchvision.models.__dict__[arch]()``
(reference 1.dataparallel.py:23-24,97-102) and trains them on CIFAR10 (32x32
through the ImageNet stem) and ImageNet. This module provides the same family
— resnet18/34/50/101/152 with torchvision's layer plan — built TPU-first:

* NHWC layout (XLA:TPU native), channels padded to MXU-friendly multiples by
  XLA automatically;
* flax.linen with an fp32-master / configurable compute dtype split: conv and
  dense run in ``dtype`` (bf16 for the apex-AMP-equivalent variant), batch-norm
  statistics always accumulate in fp32 (SURVEY.md §7 'bf16 vs apex fp16');
* under ``jit`` over a data-sharded mesh, batch-norm batch statistics are
  computed over the *global* batch (XLA inserts the cross-device reduction),
  which is SyncBN semantics — strictly stronger than the reference's
  per-replica BN (documented capability delta);
* an optional CIFAR stem (3x3/s1, no maxpool) for the TPU-native CIFAR recipe;
  default stem matches torchvision (7x7/s2 + 3x3 maxpool) for parity.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BasicBlock(nn.Module):
    """torchvision BasicBlock: 3x3 -> 3x3 with identity shortcut (expansion 1)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    expansion: int = 1
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides, padding=[(1, 1), (1, 1)])(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)])(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)  # zero-init last BN gamma
        if residual.shape != y.shape:
            residual = self.conv(self.filters * self.expansion, (1, 1),
                                 self.strides, name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    """torchvision Bottleneck: 1x1 -> 3x3 -> 1x1 (expansion 4).

    ``groups``/``base_width`` follow torchvision's generalization: the inner
    width is ``filters * base_width/64 * groups`` and the 3x3 conv is
    grouped — resnext50_32x4d = (32, 4), wide_resnet50_2 = (1, 128)."""

    filters: int
    strides: Tuple[int, int] = (1, 1)
    expansion: int = 4
    groups: int = 1
    base_width: int = 64
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm

    @nn.compact
    def __call__(self, x):
        residual = x
        width = int(self.filters * (self.base_width / 64.0)) * self.groups
        y = self.conv(width, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(width, (3, 3), self.strides, padding=[(1, 1), (1, 1)],
                      feature_group_count=self.groups)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * self.expansion, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * self.expansion, (1, 1),
                                 self.strides, name="downsample_conv")(residual)
            residual = self.norm(name="downsample_bn")(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """torchvision-plan ResNet, NHWC, fp32 BN statistics.

    stage_sizes/block follow torchvision exactly (e.g. resnet50 = Bottleneck
    [3,4,6,3]); `cifar_stem=True` swaps the 7x7/s2+maxpool stem for 3x3/s1.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 10
    dtype: jnp.dtype = jnp.float32
    cifar_stem: bool = False
    stem: str = ""  # "" = cifar_stem bool decides (legacy); "imagenet" |
    # "cifar" | "s2d". "s2d" is the MLPerf-TPU space-to-depth stem
    # (VERDICT r4 #1): pad the image 3px, rearrange 2x2 spatial blocks into
    # channels ((B,38,38,3) -> (B,19,19,12)), then a 4x4/s1 VALID conv —
    # which spans exactly the function space of the 7x7/s2 pad-3 stem conv
    # (pad the 7x7 kernel to 8x8, split each tap index into (block, offset):
    # y[p,q] = sum_{a,b,u,v,c} w[2a+u,2b+v,c] x_pad[2(p+a)+u,2(q+b)+v,c] is
    # a 4x4 conv over the s2d channels (u,v,c)). Same 16x16x64 output
    # geometry into the same maxpool. The point: XLA lowers a stride-2
    # conv over 3 channels miserably (pad/space-to-batch, ~2% MXU fill);
    # the s2d form is a dense stride-1 contraction over 192 inputs.
    norm: str = "bn"  # bn = torchvision parity (SyncBN under jit);
                      # gn = GroupNorm(32): no running stats / batch coupling
                      # (identical math at any batch size or replica count)
    norm_dtype: Any = None
    # norm_dtype None = fp32 normalization OUTPUTS (torch parity: AMP keeps
    # the BN->relu->residual chain fp32). jnp.bfloat16 emits bf16 normalized
    # activations while BN/GN STATISTICS still accumulate in fp32 (flax
    # computes mean/var in f32 internally, and running stats/affine params
    # stay f32 param_dtype) — the MLPerf-TPU ResNet practice. A round-5
    # per-op profile showed the training
    # step HBM-bandwidth-bound with fp32 activation/cotangent tensors
    # between every bf16 conv; bf16 norm outputs halve that traffic.

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        ndt = self.norm_dtype or jnp.float32
        if self.norm == "gn":
            norm = partial(nn.GroupNorm, num_groups=32, epsilon=1e-5,
                           dtype=ndt)
        elif self.norm == "bn":
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5,
                           dtype=ndt)  # stats & affine always fp32
        else:
            raise ValueError(f"unknown norm {self.norm!r} (bn|gn)")

        stem = self.stem or ("cifar" if self.cifar_stem else "imagenet")
        x = x.astype(self.dtype)
        if stem == "cifar":
            x = conv(64, (3, 3), padding=[(1, 1), (1, 1)], name="conv1")(x)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
        elif stem == "s2d":
            b, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError(f"s2d stem needs even H,W, got {h}x{w}")
            x = jnp.pad(x, ((0, 0), (3, 3), (3, 3), (0, 0)))
            hp, wp = h + 6, w + 6
            x = x.reshape(b, hp // 2, 2, wp // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, hp // 2, wp // 2,
                                                      4 * c)
            x = conv(64, (4, 4), padding="VALID", name="conv1")(x)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        elif stem == "imagenet":
            x = conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)], name="conv1")(x)
            x = norm(name="bn1")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        else:
            raise ValueError(f"unknown stem {stem!r} (imagenet|cifar|s2d)")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(filters=64 * 2 ** i, strides=strides,
                                   conv=conv, norm=norm,
                                   name=f"layer{i + 1}_block{j}")(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc")(x)
        return x.astype(jnp.float32)


# torchvision layer plans (reference models.__dict__ factory surface)
ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=Bottleneck)
ResNeXt50_32x4d = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                          block_cls=partial(Bottleneck, groups=32,
                                            base_width=4))
ResNeXt101_32x8d = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                           block_cls=partial(Bottleneck, groups=32,
                                             base_width=8))
WideResNet50_2 = partial(ResNet, stage_sizes=[3, 4, 6, 3],
                         block_cls=partial(Bottleneck, base_width=128))
WideResNet101_2 = partial(ResNet, stage_sizes=[3, 4, 23, 3],
                          block_cls=partial(Bottleneck, base_width=128))
