#!/usr/bin/env python
"""Train ResNet-50 (or friends) at ImageNet shapes — the flagship
throughput config.

reference config: example/image-classification/train_imagenet.py (the
BASELINE.json north-star row). Data is synthetic by default (zero-egress
environment); throughput numbers are identical either way since decode
happens off the measured path in NDArrayIter. Run:

    python examples/train_imagenet.py --network resnet --num-layers 50 \
        --batch-size 64 --num-epochs 1

On a TPU host name the chip(s) and the compute dtype (``--gpus`` is the
reference's flag; ``mx.gpu(i)`` is the accelerator alias):

    python examples/train_imagenet.py --gpus 0 --dtype bfloat16 \
        --batch-size 256 --num-examples 2560
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu.models import (resnet, alexnet, vgg, inception_bn,
                              inception_v3)
from common import data, fit


def build(argv=None):
    """``(args, network, (train, val))`` for a command line (default
    ``sys.argv[1:]``) — everything ``main`` hands to ``fit.fit``."""
    parser = argparse.ArgumentParser(description="train imagenet")
    parser.add_argument("--network", type=str, default="resnet",
                        choices=("resnet", "alexnet", "vgg", "inception-bn",
                                 "inception-v3"))
    parser.add_argument("--num-layers", type=int, default=50)
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--num-examples", type=int, default=2560)
    parser.add_argument("--num-val", type=int, default=256)
    parser.add_argument("--data-train", type=str, default=None,
                        help=".rec pack for real training data (routes "
                             "through ImageRecordIter: multiprocess "
                             "decode + augmentation)")
    parser.add_argument("--data-val", type=str, default=None)
    fit.add_fit_args(parser)
    parser.set_defaults(batch_size=64, num_epochs=1, lr=0.1,
                        disp_batches=10)
    args = parser.parse_args(argv)

    if args.network == "resnet":
        net = resnet.get_symbol(num_classes=args.num_classes,
                                num_layers=args.num_layers,
                                image_shape="3,224,224")
    elif args.network == "alexnet":
        net = alexnet.get_symbol(num_classes=args.num_classes)
    elif args.network == "vgg":
        net = vgg.get_symbol(num_classes=args.num_classes,
                             num_layers=args.num_layers)
    elif args.network == "inception-v3":
        net = inception_v3.get_symbol(num_classes=args.num_classes)
    else:
        net = inception_bn.get_symbol(num_classes=args.num_classes)

    # inception-v3 is a 299x299 architecture (its global pool is 8x8)
    image_shape = (3, 299, 299) if args.network == "inception-v3" \
        else (3, 224, 224)
    if args.data_train:
        # real data: RecordIO -> multiprocess decode + train augmentation
        # (reference: train_imagenet.py's ImageRecordIter config)
        import mxnet_tpu as mx
        kw = dict(data_shape=image_shape, batch_size=args.batch_size,
                  mean_r=123.68, mean_g=116.779, mean_b=103.939)
        train = mx.image.ImageRecordIter(
            args.data_train, shuffle=True, rand_crop=True,
            rand_mirror=True, resize=image_shape[-1] + 32, **kw)
        # no --data-val -> no validation (never score on the train pack)
        val = mx.image.ImageRecordIter(
            args.data_val, resize=image_shape[-1] + 32, **kw) \
            if args.data_val else None
        iters = (train, val)
    else:
        iters = data.imagenet_like_iters(args.batch_size,
                                         num_classes=args.num_classes,
                                         image_shape=image_shape,
                                         num_train=args.num_examples,
                                         num_val=args.num_val)
    return args, net, iters


def main(argv=None):
    return fit.fit(*build(argv))


if __name__ == "__main__":
    main()
