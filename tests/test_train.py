"""Metric-gated end-to-end training tests.

reference: tests/python/train/test_mlp.py:100 and test_conv.py — small
full-stack runs through Module.fit that must reach an accuracy
threshold; the convolution gate exercises Convolution/Pooling/BatchNorm
backward through a real optimizer, not just op-level numerics.
"""
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
from common import data as exdata  # noqa: E402
from mxnet_tpu.models import mlp, lenet  # noqa: E402

pytestmark = pytest.mark.slow


def _fit_and_score(net, imgs, labels, batch_size=50, num_epoch=2,
                   lr=0.05, optimizer="sgd"):
    it = mx.io.NDArrayIter(imgs, labels, batch_size, shuffle=True)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, eval_metric="acc", optimizer=optimizer,
            optimizer_params={"learning_rate": lr, "momentum": 0.9,
                              "wd": 1e-4},
            num_epoch=num_epoch,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2))
    it.reset()
    return mod.score(it, "acc")[0][1], mod


def test_mlp_convergence_gate():
    """MNIST-style MLP must exceed 0.95 train accuracy (reference
    test_mlp.py gates at 0.9+ on real MNIST)."""
    imgs, labels = exdata.synthetic_classification(2000, (784,), 10, seed=1)
    acc, _ = _fit_and_score(mlp.get_symbol(10), imgs, labels)
    assert acc >= 0.95, f"MLP convergence gate failed: acc={acc}"


def test_conv_convergence_gate():
    """LeNet (Convolution+Pooling+FC) must exceed 0.95 — the convolution
    backward path trained to a gate (reference test_conv.py)."""
    imgs, labels = exdata.synthetic_classification(1500, (1, 28, 28), 10,
                                                   seed=2)
    acc, _ = _fit_and_score(lenet.get_symbol(10), imgs, labels,
                            num_epoch=3, lr=0.02)
    assert acc >= 0.95, f"LeNet convergence gate failed: acc={acc}"


def test_checkpoint_resume_continues_training():
    """do_checkpoint + fit(begin_epoch) resume path (reference
    common/fit.py --load-epoch)."""
    imgs, labels = exdata.synthetic_classification(600, (784,), 10, seed=3)
    it = mx.io.NDArrayIter(imgs, labels, 50, shuffle=True)
    net = mlp.get_symbol(10)
    prefix = os.path.join("/tmp", "mxtpu_resume_test")
    opt_params = {"learning_rate": 0.1, "momentum": 0.9}
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=opt_params,
            epoch_end_callback=mx.callback.do_checkpoint(prefix),
            initializer=mx.initializer.Uniform(0.05))
    it.reset()
    acc1 = mod.score(it, "acc")[0][1]
    sym2, args2, aux2 = mx.model.load_checkpoint(prefix, 1)
    # params round-trip exactly through the reference-format container
    a1, _ = mod.get_params()
    np.testing.assert_array_equal(a1["fc1_weight"].asnumpy(),
                                  args2["fc1_weight"].asnumpy())
    it.reset()
    mod2 = mx.mod.Module(sym2, context=mx.cpu())
    mod2.fit(it, num_epoch=6, begin_epoch=1, optimizer="sgd",
             optimizer_params=opt_params,
             arg_params=args2, aux_params=aux2)
    it.reset()
    acc = mod2.score(it, "acc")[0][1]
    assert acc >= max(acc1, 0.9), \
        f"resumed training underperformed: {acc1} -> {acc}"


@pytest.mark.parametrize("script,args", [
    ("lstm_bucketing.py", ["--num-epochs", "1", "--num-hidden", "32",
                           "--num-embed", "32", "--num-layers", "1"]),
    ("dcgan.py", ["--num-epochs", "1", "--batches-per-epoch", "4",
                  "--batch-size", "8"]),
    ("train_mnist.py", ["--num-epochs", "1", "--batch-size", "32",
                        "--network", "mlp"]),
    ("train_cifar10.py", ["--num-epochs", "1", "--batch-size", "16",
                          "--num-layers", "20", "--num-classes", "4"]),
    ("train_imagenet.py", ["--num-epochs", "1", "--batch-size", "8",
                           "--num-layers", "18", "--num-classes", "4",
                           "--num-examples", "32"]),
    ("train_imagenet.py", ["--num-epochs", "1", "--batch-size", "2",
                           "--network", "inception-v3", "--num-classes",
                           "4", "--num-examples", "4", "--num-val", "2"]),
    ("ssd/train.py", ["--epochs", "1", "--batch-size", "8",
                      "--num-images", "16", "--width", "8",
                      "--data-size", "64"]),
    ("bi_lstm_sort.py", ["--num-epochs", "1", "--num-train", "256",
                         "--seq-len", "6", "--num-hidden", "24"]),
    ("model_parallel_lstm.py", ["--num-epochs", "3"]),
])
def test_example_scripts_smoke(script, args):
    """Every shipped example must run end-to-end (tiny settings)."""
    import subprocess
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(root, "examples", script)] + args,
        capture_output=True, text=True, timeout=900, env=env, cwd=root)
    assert res.returncode == 0, \
        f"{script} failed:\n{res.stdout[-2000:]}\n{res.stderr[-2000:]}"


def test_mlp_real_data_convergence_gate():
    """Val-accuracy gate on REAL handwritten digits (scikit-learn's
    vendored UCI scans — see exdata.real_digits). Unlike the
    prototype-synthetic gates above, a subtly-wrong BatchNorm/momentum
    cannot pass this: generalization to held-out real scans is required.
    Reference: tests/python/train/test_mlp.py:88-100 (MNIST >= 0.9;
    gated here at 0.95 per BASELINE.md CI gates)."""
    tr_img, tr_lbl, va_img, va_lbl = exdata.real_digits(seed=0)
    it = mx.io.NDArrayIter(tr_img.reshape(len(tr_img), -1), tr_lbl, 50,
                           shuffle=True)
    vit = mx.io.NDArrayIter(va_img.reshape(len(va_img), -1), va_lbl, 50)
    mod = mx.mod.Module(mlp.get_symbol(10), context=mx.cpu())
    mod.fit(it, eval_data=vit, eval_metric="acc", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            num_epoch=10,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2))
    vit.reset()
    acc = mod.score(vit, "acc")[0][1]
    assert acc >= 0.95, f"real-data MLP val-acc gate failed: {acc}"


def test_cifar_scale_real_data_gate(tmp_path, monkeypatch):
    """CIFAR-scale gate on REAL photographs through the FULL pipeline:
    JPEG RecordIO pack -> multiprocess decode -> random-crop/mirror
    augmentation -> ResNet-8 (conv/BN trunk) -> NHWC execution pass ON.
    Real 32x32 RGB patches of scikit-learn's two vendored photos,
    labeled by source photo, with a SPATIAL train/val split (no tile
    overlap across it) — mis-normalized BatchNorm statistics, a broken
    augmenter, or a layout-pass bug all fail this gate.
    Reference: tests/nightly/test_all.sh:42-55 (CIFAR-10 conv >= 0.86);
    threshold tuned to this 2-class subset (observed ~0.94)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import im2rec
    from mxnet_tpu import recordio
    from mxnet_tpu.models import resnet

    monkeypatch.setenv("MXNET_NHWC_LAYOUT", "1")
    tr, trl, va, val = exdata.real_photo_patches()

    def pack(prefix, imgs, lbls):
        rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                         "w")
        for i, (im, lb) in enumerate(zip(imgs, lbls)):
            rec.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(lb), i, 0),
                im2rec._encode(im, quality=95)))   # _encode takes RGB
        rec.close()
        return prefix

    trp = pack(str(tmp_path / "train"), tr, trl)
    vap = pack(str(tmp_path / "val"), va, val)
    kw = dict(mean_r=128, mean_g=128, mean_b=128, std_r=60, std_g=60,
              std_b=60, num_workers=2, prefetch=False)
    it = mx.image.ImageRecordIter(trp + ".rec", path_imgidx=trp + ".idx",
                                  data_shape=(3, 28, 28), batch_size=50,
                                  shuffle=True, rand_crop=True,
                                  rand_mirror=True, **kw)
    assert type(it).__name__ == "MPImageRecordIter"   # the MP decode path
    vit = mx.image.ImageRecordIter(vap + ".rec", path_imgidx=vap + ".idx",
                                   data_shape=(3, 28, 28), batch_size=50,
                                   **kw)
    net = resnet.get_symbol(num_classes=2, num_layers=8,
                            image_shape="3,28,28")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, eval_data=vit, eval_metric="acc", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            num_epoch=6,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2))
    vit.reset()
    acc = mod.score(vit, "acc")[0][1]
    it.close()
    vit.close()
    assert acc >= 0.88, f"real-photo CIFAR-scale gate failed: {acc}"


def test_conv_real_data_convergence_gate():
    """LeNet val-accuracy gate on real digit scans — convolution,
    pooling and BN backward trained against real image statistics
    (reference: tests/python/train/test_conv.py)."""
    tr_img, tr_lbl, va_img, va_lbl = exdata.real_digits(seed=0)
    it = mx.io.NDArrayIter(tr_img, tr_lbl, 50, shuffle=True)
    vit = mx.io.NDArrayIter(va_img, va_lbl, 50)
    mod = mx.mod.Module(lenet.get_symbol(10), context=mx.cpu())
    mod.fit(it, eval_metric="acc", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "wd": 1e-4},
            num_epoch=6,
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2))
    vit.reset()
    acc = mod.score(vit, "acc")[0][1]
    assert acc >= 0.95, f"real-data LeNet val-acc gate failed: {acc}"
