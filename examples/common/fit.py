"""Shared argparse + fit wiring for the example scripts.

API parity with reference example/image-classification/common/fit.py
(add_fit_args / fit): common hyperparameter flags, checkpoint resume via
--load-epoch, Speedometer logging, kvstore selection.
"""
from __future__ import annotations

import argparse
import logging
import os

import mxnet_tpu as mx


def add_fit_args(parser):
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--lr-factor", type=float, default=0.1)
    parser.add_argument("--lr-step-epochs", type=str, default="")
    parser.add_argument("--optimizer", type=str, default="sgd")
    parser.add_argument("--mom", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--disp-batches", type=int, default=20)
    parser.add_argument("--kv-store", type=str, default="local")
    parser.add_argument("--model-prefix", type=str, default=None)
    parser.add_argument("--load-epoch", type=int, default=None)
    parser.add_argument("--gpus", type=str, default="",
                        help="accelerator chips to train on, e.g. '0' or "
                        "'0,1,2,3' (TPU chips on a TPU host); empty "
                        "trains on the CPU")
    parser.add_argument("--num-devices", type=int, default=1,
                        help="without --gpus: data-parallel count of "
                        "(virtual) CPU devices")
    parser.add_argument("--dtype", type=str, default="float32",
                        help="compute dtype over float32 master params "
                        "(float32 | bfloat16)")
    return parser


def _contexts(args):
    if args.gpus:
        return [mx.gpu(int(i)) for i in args.gpus.split(",")]
    if args.num_devices <= 1:
        return [mx.current_context()]
    return [mx.Context(mx.current_context().device_type, i)
            for i in range(args.num_devices)]


def fit(args, network, data_iters, **fit_kwargs):
    """Bind + train ``network`` on (train, val) iterators per ``args``.

    ``batch_end_callback`` in ``fit_kwargs`` runs after the Speedometer
    (reference fit.py contract); the rest goes to ``Module.fit``."""
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    train, val = data_iters

    arg_params = aux_params = None
    begin_epoch = 0
    if args.model_prefix and args.load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, args.load_epoch)
        begin_epoch = args.load_epoch
        logging.info("resumed %s at epoch %d", args.model_prefix,
                     begin_epoch)

    lr_scheduler = None
    if args.lr_step_epochs:
        epoch_size = max(train.num_data // args.batch_size, 1) \
            if hasattr(train, "num_data") else 100
        steps = [epoch_size * int(e)
                 for e in args.lr_step_epochs.split(",") if e]
        lr_scheduler = mx.lr_scheduler.MultiFactorScheduler(
            steps, args.lr_factor)

    optimizer_params = {"learning_rate": args.lr, "wd": args.wd}
    if args.optimizer in ("sgd", "nag"):
        optimizer_params["momentum"] = args.mom
    if lr_scheduler is not None:
        optimizer_params["lr_scheduler"] = lr_scheduler

    checkpoint = mx.callback.do_checkpoint(args.model_prefix) \
        if args.model_prefix else None

    contexts = _contexts(args)
    # overlap input with compute: decode/augment runs ahead of the step
    # in a background thread (reference: PrefetcherIter always tops the
    # C++ iterator stack, iter_prefetcher.h:129), and Module.fit tells
    # that thread where the bound module wants its batches, so it stages
    # them there. Iterators that already prefetch pass through.
    if not isinstance(train, mx.io.PrefetchingIter):
        train = mx.io.PrefetchingIter(train)
    if val is not None and not isinstance(val, mx.io.PrefetchingIter):
        val = mx.io.PrefetchingIter(val)

    batch_end_callbacks = [mx.callback.Speedometer(args.batch_size,
                                                   args.disp_batches)]
    extra = fit_kwargs.pop("batch_end_callback", None)
    if extra is not None:
        batch_end_callbacks += extra if isinstance(extra, list) else [extra]

    mod = mx.mod.Module(
        network, context=contexts,
        compute_dtype=None if args.dtype == "float32" else args.dtype)
    mod.fit(train,
            eval_data=val,
            eval_metric=["acc"],
            optimizer=args.optimizer,
            optimizer_params=optimizer_params,
            arg_params=arg_params,
            aux_params=aux_params,
            begin_epoch=begin_epoch,
            num_epoch=args.num_epochs,
            batch_end_callback=batch_end_callbacks,
            epoch_end_callback=checkpoint,
            kvstore=args.kv_store,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            **fit_kwargs)
    return mod
