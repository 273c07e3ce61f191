"""Plain reference: the ImageNet bottleneck ResNet forward and its
softmax cross-entropy loss, in float32 jax.numpy.

The published description: He et al., "Deep Residual Learning for Image
Recognition" (arXiv:1512.03385), Table 1, 50-layer column - a 7x7/2
convolution, a 3x3/2 max pool, four stages of [3, 4, 6, 3] bottleneck
units at 256, 512, 1024, 2048 channels, global average pooling, a
1000-way classifier.

Departures from the published description (each one follows
mxnet_tpu/models/resnet.py, which follows the reference's
example/image-classification/symbols/resnet.py):
  * the units are the pre-activation form of He et al.,
    arXiv:1603.05027: BatchNorm-ReLU-convolution, the shortcut taken
    after the first BatchNorm-ReLU, a final BatchNorm-ReLU before the
    pool;
  * the stride of a down-sampling unit sits on its 3x3 convolution
    (and on the 1x1 shortcut), not on the first 1x1;
  * the input passes a BatchNorm with its gain fixed to 1 (bn_data);
  * BatchNorm uses eps 2e-5 and, in training, the batch's own mean and
    biased variance.

No kernels, no layout pass: NCHW activations, OIHW weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_EPS = 2e-5


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, _f32(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn_relu(x, params, name, fix_gamma=False, relu=True):
    """Training-mode BatchNorm over (N, H, W), then ReLU."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    y = (x - mean) / jnp.sqrt(var + _EPS)
    if not fix_gamma:
        y = y * _f32(params[f"{name}_gamma"]).reshape(1, -1, 1, 1)
    y = y + _f32(params[f"{name}_beta"]).reshape(1, -1, 1, 1)
    return jnp.maximum(y, 0.0) if relu else y


def _unit(x, params, name, stride, dim_match):
    a1 = _bn_relu(x, params, f"{name}_bn1")
    c1 = _conv(a1, params[f"{name}_conv1_weight"], 1, 0)
    a2 = _bn_relu(c1, params, f"{name}_bn2")
    c2 = _conv(a2, params[f"{name}_conv2_weight"], stride, 1)
    a3 = _bn_relu(c2, params, f"{name}_bn3")
    c3 = _conv(a3, params[f"{name}_conv3_weight"], 1, 0)
    shortcut = x if dim_match else \
        _conv(a1, params[f"{name}_sc_weight"], stride, 0)
    return c3 + shortcut


def logits(params, images, config):
    """(N, classes) float32 logits of ``images`` (N, 3, H, W) in
    training mode (batch statistics)."""
    with jax.default_matmul_precision("highest"):
        x = _bn_relu(_f32(images), params, "bn_data", fix_gamma=True,
                     relu=False)
        x = _conv(x, params["conv0_weight"], 2, 3)
        x = _bn_relu(x, params, "bn0")
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, n in enumerate(config["units"]):
            for unit in range(n):
                stride = 2 if (unit == 0 and stage > 0) else 1
                x = _unit(x, params, f"stage{stage + 1}_unit{unit + 1}",
                          stride, dim_match=unit > 0)
        x = _bn_relu(x, params, "bn1")
        x = jnp.mean(x, axis=(2, 3))
        return x @ _f32(params["fc1_weight"]).T + _f32(params["fc1_bias"])


def loss(params, images, labels, config):
    """Mean softmax cross-entropy of the batch."""
    lg = logits(params, images, config)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[:, None], axis=1)
    return -jnp.mean(picked)
