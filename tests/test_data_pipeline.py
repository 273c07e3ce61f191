"""Data-pipeline: im2rec CLI, prefetch-to-device, throughput floor.

reference: tools/im2rec.py packing contract + src/io/iter_prefetcher.h's
prefetch-to-staging behavior; the throughput floor guards against the
pipeline regressing into per-image device round-trips (which once cut
throughput ~80x).
"""
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import mxnet_tpu as mx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_image(path, arr):
    try:
        import cv2
        cv2.imwrite(path, arr[:, :, ::-1])
    except ImportError:
        from PIL import Image
        Image.fromarray(arr).save(path)


def test_im2rec_list_pack_read_roundtrip(tmp_path):
    rng = np.random.RandomState(0)
    root = tmp_path / "imgs"
    for cls in ("cats", "dogs"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = rng.randint(0, 255, (40, 48, 3), dtype=np.uint8)
            _write_image(str(root / cls / f"{i}.png"), arr)
    prefix = str(tmp_path / "pack")
    cli = os.path.join(ROOT, "tools", "im2rec.py")
    r = subprocess.run([sys.executable, cli, "--list", prefix, str(root)],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    lines = open(prefix + ".lst").read().strip().splitlines()
    assert len(lines) == 6
    r = subprocess.run([sys.executable, cli, prefix, str(root),
                        "--resize", "36"], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    it = mx.image.ImageIter(2, (3, 32, 32), path_imgrec=prefix + ".rec")
    seen, labels = 0, set()
    for batch in it:
        seen += batch.data[0].shape[0] - batch.pad
        labels.update(np.asarray(batch.label[0].asnumpy()).astype(
            int).tolist())
    assert seen == 6
    assert labels == {0, 1}


def test_prefetching_iter_to_device():
    X = np.random.rand(32, 3, 8, 8).astype("f")
    y = np.arange(32, dtype="f")
    base = mx.io.NDArrayIter(X, y, batch_size=8)
    it = mx.io.PrefetchingIter(base, device=mx.cpu())
    n = 0
    for batch in it:
        assert batch.data[0].shape == (8, 3, 8, 8)
        dev = next(iter(batch.data[0].asjax().devices()))
        assert dev.platform == "cpu"
        n += 1
    assert n == 4
    it.reset()
    assert sum(1 for _ in it) == 4


@pytest.mark.parametrize("K", [1, 2], ids=["per_batch", "scan_window"])
def test_fit_hands_its_placement_to_the_iterator(K):
    """The iterator is built for one device (as examples/common/fit.py
    built it with contexts[0]), then Module.fit binds over two and hands
    over the executor group's own placement. The producer restarts once,
    before the first batch; every batch then arrives once, in order,
    under the group's sharding; and the producer's spans keep the names
    chipbench/spans.py matches."""
    n, batch = 8, 16
    ctxs = [mx.cpu(0), mx.cpu(1)]
    X = np.random.RandomState(0).rand(n * batch, 6).astype("f")
    y = np.arange(n * batch, dtype="f")         # a row's label names it
    it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y % 3, batch_size=batch),
                               device=ctxs[0])
    log, real_reset, real_next = [], it.reset, it.next

    def reset():
        log.append("reset")
        real_reset()

    def next_():
        b = real_next()
        log.append((tuple(b.data[0].shape), b.data[0].asjax().sharding,
                    b.data[0].asnumpy().reshape(-1, 6)))
        return b

    it.reset, it.next = reset, next_
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.var("data"), num_hidden=3, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=ctxs)
    mx.telemetry.clear()
    mx.telemetry.enable()
    try:
        mod.fit(it, num_epoch=1, steps_per_dispatch=K, kvstore=None,
                optimizer_params={"learning_rate": 0.01})
        spans = [s for s in mx.telemetry.get_spans()
                 if s.name.startswith("io.prefetch.")]
    finally:
        mx.telemetry.disable()
        mx.telemetry.clear()
    group = mod._exec_group
    assert it._device == mod._input_placement() == group._data_sharding
    # one restart for the hand-over, one at the end of the epoch
    assert [e for e in log if e == "reset"] == ["reset"] * 2
    assert log[0] == "reset" and log[-1] == "reset"
    seen = [e for e in log if e != "reset"]
    want = group._data_sharding if K == 1 else group._stacked_sharding
    assert [shape for shape, _, _ in seen] == \
        [((batch, 6) if K == 1 else (K, batch, 6))] * (n // K)
    assert all(sharding == want for _, sharding, _ in seen)
    assert np.array_equal(np.concatenate([rows for _, _, rows in seen]), X)
    staging = "io.prefetch.to_device" if K == 1 else "io.prefetch.stack"
    names = {s.name for s in spans}
    assert {"io.prefetch.batch", "io.prefetch.fetch", "io.prefetch.put",
            staging} <= names
    # the placement of the batches the loop trained on is on the span
    assert str(group._data_sharding) in \
        {s.args.get("placement") for s in spans if s.name == staging}


def _img_per_sec(it):
    for _ in it:            # warm epoch: workers, threads, caches
        pass
    it.reset()
    tic = time.perf_counter()
    seen = 0
    for batch in it:
        seen += batch.data[0].shape[0] - batch.pad
    return seen / (time.perf_counter() - tic)


def test_pipeline_throughput_floor(tmp_path):
    """Guards the no-device-round-trips invariant: even one CPU core must
    sustain far more than single-digit img/s, through the thread pool
    and through the multiprocess decoders."""
    from test_mp_decode import _make_pack
    prefix = _make_pack(tmp_path, n=64, size=(128, 128))
    shape = (3, 112, 112)
    threads = mx.image.ImageIter(
        16, shape, path_imgrec=prefix + ".rec",
        aug_list=mx.image.CreateAugmenter(shape, rand_crop=True,
                                          rand_mirror=True),
        num_threads=os.cpu_count() or 4)
    img_s = _img_per_sec(mx.io.PrefetchingIter(threads))
    assert img_s > 25, f"pipeline throughput collapsed: {img_s:.1f} img/s"
    mp = mx.image.ImageRecordIter(
        prefix + ".rec", shape, 16, path_imgidx=prefix + ".idx",
        rand_crop=True, rand_mirror=True, num_workers=2, prefetch=False)
    assert type(mp).__name__ == "MPImageRecordIter"
    try:
        img_s = _img_per_sec(mx.io.PrefetchingIter(mp))
    finally:
        mp.close()
    assert img_s > 25, f"mp pipeline throughput collapsed: {img_s:.1f} img/s"
