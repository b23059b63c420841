"""Plain reference of torchvision's ResNet-50 (He et al. 2015, Table 1;
bottleneck blocks [3, 4, 6, 3], stride on the 3x3 convolution) on NHWC
images: float32 ``jax.numpy`` / ``lax.conv_general_dilated`` under
``jax.default_matmul_precision("highest")``. It imports nothing of
``tpu_dist`` and makes its own weights from the seed.

Training-mode BatchNorm: the batch's own mean and biased variance, epsilon
1e-5. Departures, which the program makes too: the ImageNet stem (7x7
stride 2, 3x3 max-pool) is kept on 32x32 CIFAR10 images, as the source
cookbook does by calling ``torchvision.models.resnet50()`` unchanged, and
each block's last BatchNorm gain starts at zero (torchvision's
``zero_init_residual=True``, the repo's default).

``quant="int8"`` is the CONTROL: both operands of every convolution and of
the classifier are rounded to int8 levels with straight-through gradients.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)

Weights = Dict[str, jax.Array]


def weight_shapes(sizes: dict) -> Dict[str, tuple]:
    stages = tuple(sizes.get("stage_sizes", STAGES))
    base = sizes.get("base_width", 64)
    shapes = {"conv1.w": (7, 7, 3, base), "bn1.g": (base,), "bn1.b": (base,)}
    cin = base
    for s, blocks in enumerate(stages):
        width, cout = base * 2 ** s, base * 2 ** s * 4
        for j in range(blocks):
            p = f"layer{s + 1}.{j}."
            shapes.update({
                p + "conv1.w": (1, 1, cin, width), p + "bn1.g": (width,),
                p + "bn1.b": (width,),
                p + "conv2.w": (3, 3, width, width), p + "bn2.g": (width,),
                p + "bn2.b": (width,),
                p + "conv3.w": (1, 1, width, cout), p + "bn3.g": (cout,),
                p + "bn3.b": (cout,)})
            if j == 0:
                shapes.update({p + "down.w": (1, 1, cin, cout),
                               p + "downbn.g": (cout,),
                               p + "downbn.b": (cout,)})
            cin = cout
    shapes.update({"fc.w": (cin, sizes["num_classes"]),
                   "fc.b": (sizes["num_classes"],)})
    return shapes


def make_weights(sizes: dict, key: jax.Array, dtype=jnp.float32) -> Weights:
    """torchvision's initialisation from ``key``: He-normal (fan-out)
    convolutions, unit BatchNorm gains but zero on each block's last one,
    zero biases, a uniform(+-1/sqrt(fan_in)) classifier. Call it under
    ``jax.jit``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(sizes).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith(".g"):
            w = (jnp.zeros if name.endswith("bn3.g") else jnp.ones)(
                shape, jnp.float32)
        elif name.endswith(".b"):
            w = jnp.zeros(shape, jnp.float32)
        elif name == "fc.w":
            bound = 1.0 / math.sqrt(shape[0])
            w = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            fan_out = shape[0] * shape[1] * shape[3]
            w = math.sqrt(2.0 / fan_out) * jax.random.normal(
                k, shape, jnp.float32)
        out[name] = w.astype(dtype)
    return out


def _int8(x, axes=None):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _operands(x, w, quant):
    """Both operands of a convolution or matrix product in the control's
    precision: per-tensor activations, per-output-channel weights."""
    if quant == "int8":
        return _int8(x), _int8(w, axes=tuple(range(w.ndim - 1)))
    if quant != "none":
        raise ValueError(f"unknown control precision {quant!r}")
    return x, w


def _conv(x, w, stride, pad, quant):
    x, w = _operands(x, w, quant)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, g, b):
    mu = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mu), (0, 1, 2))
    return (x - mu) * jax.lax.rsqrt(var + BN_EPS) * g + b


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])


def forward(w: Weights, x: jax.Array, quant: str = "none") -> jax.Array:
    """Logits (B, classes) for normalised float images (B, H, W, 3), with
    the batch's own BatchNorm statistics."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        x = x.astype(jnp.float32)
        x = jax.nn.relu(_bn(_conv(x, w["conv1.w"], 2, 3, quant),
                            w["bn1.g"], w["bn1.b"]))
        x = _max_pool_3x3_s2(x)
        s = 1
        while f"layer{s}.0.conv1.w" in w:
            j = 0
            while (p := f"layer{s}.{j}.") + "conv1.w" in w:
                stride = 2 if s > 1 and j == 0 else 1
                y = jax.nn.relu(_bn(_conv(x, w[p + "conv1.w"], 1, 0, quant),
                                    w[p + "bn1.g"], w[p + "bn1.b"]))
                y = jax.nn.relu(_bn(_conv(y, w[p + "conv2.w"], stride, 1,
                                          quant),
                                    w[p + "bn2.g"], w[p + "bn2.b"]))
                y = _bn(_conv(y, w[p + "conv3.w"], 1, 0, quant),
                        w[p + "bn3.g"], w[p + "bn3.b"])
                if p + "down.w" in w:
                    x = _bn(_conv(x, w[p + "down.w"], stride, 0, quant),
                            w[p + "downbn.g"], w[p + "downbn.b"])
                x = jax.nn.relu(y + x)
                j += 1
            s += 1
        x, fc = _operands(jnp.mean(x, (1, 2)), w["fc.w"], quant)
        return x @ fc + w["fc.b"]


def normalize(images_u8, mean, std):
    """torchvision's ToTensor + Normalize."""
    x = images_u8.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(mean, jnp.float32)) / jnp.asarray(std, jnp.float32)


def loss_fn(w: Weights, images, labels, quant: str = "none") -> jax.Array:
    """Mean cross-entropy of the batch."""
    logp = jax.nn.log_softmax(forward(w, images, quant))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def sgd_step(weights, buf, grads, *, lr, momentum, wd):
    """torch.optim.SGD: d = g + wd p; buf = momentum buf + d; p -= lr buf."""
    buf = jax.tree_util.tree_map(lambda b, g, p: momentum * b + g + wd * p,
                                 buf, grads, weights)
    weights = jax.tree_util.tree_map(lambda p, b: p - lr * b, weights, buf)
    return weights, buf
