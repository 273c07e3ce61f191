"""Architecture "resnet": ``models/resnet.py``'s ImageNet bottleneck
ResNet trained as ``examples/train_imagenet.py`` trains it. The ``fit``
interface of chipbench/README.md."""
from __future__ import annotations

import numpy as np
from mxnet_tpu.models import resnet

from chipbench import costs as _costs
from chipbench.reference import resnet50 as _reference

#: |program loss - reference loss| <= LOSS_TOL * max(1, |reference|).
#: The program computes the forward in bfloat16 (relative step 2**-8)
#: through 50 layers with float32 BatchNorm statistics; the reference is
#: float32 at the highest precision on the same parameters and batch.
#: Measured on the v5e (PERF.md, Findings): a difference of a few
#: thousandths of the loss. An 8-bit float forward, or a dropped layer,
#: moves the loss by far more than 2 %.
LOSS_TOL = 0.02

#: What train_imagenet.py's parser has beyond ``fit.add_fit_args``.
PARSER_FLAGS = (("--network", str), ("--num-layers", int),
                ("--num-classes", int))

#: The classifier: every step's gradient reaches it.
UPDATED_PARAM = "fc1_weight"


def symbol(cfg):
    return resnet.get_symbol(
        num_classes=cfg["num_classes"], num_layers=cfg["num_layers"],
        image_shape=",".join(str(v) for v in cfg["image_shape"]))


def pool(n, cfg, seed):
    """Prototype-plus-noise images, as examples/common/data.py
    ``synthetic_classification`` makes them (class k = a fixed random
    pattern k), drawn in float32 so that set-up stays short."""
    image_shape = tuple(cfg["image_shape"])
    rng = np.random.default_rng([int(seed) % (1 << 32), 5])
    labels = rng.integers(0, cfg["num_classes"], n)
    used, inverse = np.unique(labels, return_inverse=True)
    protos = rng.random((len(used), *image_shape), np.float32) - 0.5
    imgs = rng.standard_normal((n, *image_shape), np.float32)
    imgs *= 0.35
    imgs += protos[inverse]
    return imgs, labels.astype(np.float32)


def reference_loss(params, x, y, cfg):
    return _reference.loss(params, x, y, cfg)


def costs(cfg, global_batch):
    return {"train_step": _costs.resnet_train_step(cfg, global_batch)}
