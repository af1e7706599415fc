"""Plain reference of ``resnet50-imagenet``: ResNet-50 v1.5 forward and loss.

He et al. 2015 (arXiv:1512.03385) with the stride on the 3x3 convolution
(torchvision ``resnet50``), written from that description in float32
``jax.numpy``/``lax`` at ``highest`` matmul precision: no kernels, no
flax, nothing imported from the program.  The parameter tree uses the
torchvision-style names the program's checkpoints use (``conv1``,
``layer{i}_{j}``, ``fc``) so the harness can hand the same seeded weights
to both.  BatchNorm is in training mode (batch statistics, biased
variance, eps 1e-5); running averages do not enter a training step's loss.

Departures from the paper, as the configuration states: weights from the
seed, not trained; 0-255 uint8 input normalised by the ImageNet mean/std.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
_HI = lax.Precision.HIGHEST
_EXPANSION = 4


def _blocks(cfg):
    """(name, in_channels, filters, stride) of every bottleneck."""
    out, cin = [], cfg["num_filters"]
    for i, n in enumerate(cfg["stage_sizes"]):
        f = cfg["num_filters"] * 2 ** i
        for j in range(n):
            out.append((f"layer{i + 1}_{j}", cin, f, 2 if i > 0 and j == 0 else 1))
            cin = f * _EXPANSION
    return out


def param_shapes(cfg) -> dict:
    """{path: (shape, init)}; init is ('normal', std) | ('const', v) | 'ones' | 'zeros'.

    The last BatchNorm of every bottleneck starts at ``residual_bn_scale``
    (torchvision: 1, or 0 with ``zero_init_residual``, Goyal et al. 2017)."""
    def conv(k, cin, cout):
        return {"kernel": ((k, k, cin, cout), ("normal", (2.0 / (k * k * cin)) ** 0.5))}

    def bn(c, scale="ones"):
        return {"scale": ((c,), scale), "bias": ((c,), "zeros")}

    last = ("const", float(cfg["residual_bn_scale"]))

    tree = {"conv1": conv(7, 3, cfg["num_filters"]), "bn1": bn(cfg["num_filters"])}
    for name, cin, f, stride in _blocks(cfg):
        blk = {"conv1": conv(1, cin, f), "bn1": bn(f),
               "conv2": conv(3, f, f), "bn2": bn(f),
               "conv3": conv(1, f, f * _EXPANSION), "bn3": bn(f * _EXPANSION, last)}
        if stride != 1 or cin != f * _EXPANSION:
            blk["downsample_conv"] = conv(1, cin, f * _EXPANSION)
            blk["downsample_bn"] = bn(f * _EXPANSION)
        tree[name] = blk
    width = cfg["num_filters"] * 8 * _EXPANSION
    tree["fc"] = {"kernel": ((width, cfg["num_classes"]), ("normal", (1.0 / width) ** 0.5)),
                  "bias": ((cfg["num_classes"],), "zeros")}
    return tree


def _plain(f):
    return f


def _conv(x, w, stride, wrap):
    k = w.shape[0]
    return wrap(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), [(k // 2, k // 2)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HI))(x, w)


def _bn(x, p, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x), (0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(p, x, stride, wrap):
    y = jax.nn.relu(_bn(_conv(x, p["conv1"]["kernel"], 1, wrap), p["bn1"]))
    y = jax.nn.relu(_bn(_conv(y, p["conv2"]["kernel"], stride, wrap), p["bn2"]))
    y = _bn(_conv(y, p["conv3"]["kernel"], 1, wrap), p["bn3"])
    if "downsample_conv" in p:
        x = _bn(_conv(x, p["downsample_conv"]["kernel"], stride, wrap), p["downsample_bn"])
    return jax.nn.relu(y + x)


def logits(params, images, cfg, wrap=_plain, remat=True):
    """(N, H, W, 3) uint8 0-255 -> (N, classes) float32.  ``wrap`` decorates every
    convolution and matmul (the control rounds their operands)."""
    x = (images.astype(jnp.float32) / 255.0 - jnp.asarray(MEAN)) / jnp.asarray(STD)
    x = jax.nn.relu(_bn(_conv(x, params["conv1"]["kernel"], 2, wrap), params["bn1"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    block = jax.checkpoint(_bottleneck, static_argnums=(2, 3)) if remat else _bottleneck
    for name, _cin, _f, stride in _blocks(cfg):
        x = block(params[name], x, stride, wrap)
    x = jnp.mean(x, (1, 2))
    dense = wrap(lambda a, b: jnp.dot(a, b, precision=_HI))
    return dense(x, params["fc"]["kernel"]) + params["fc"]["bias"]


def loss(params, inputs, labels, cfg, wrap=_plain, remat=True):
    """Mean softmax cross entropy over the rows."""
    lg = logits(params, inputs, cfg, wrap, remat)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1))
