"""IO tests (mirrors reference tests/python/unittest/test_io.py)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def test_ndarray_iter_basic():
    data = np.arange(100).reshape(25, 4).astype(np.float32)
    labels = np.arange(25).astype(np.float32)
    it = mx.io.NDArrayIter(data, labels, batch_size=5)
    batches = list(it)
    assert len(batches) == 5
    assert batches[0].data[0].shape == (5, 4)
    assert batches[0].label[0].shape == (5,)
    assert_almost_equal(batches[0].data[0], data[:5])
    it.reset()
    assert len(list(it)) == 5


def test_ndarray_iter_pad():
    data = np.arange(22 * 3).reshape(22, 3).astype(np.float32)
    it = mx.io.NDArrayIter(data, np.zeros(22, dtype=np.float32),
                           batch_size=5, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 5
    assert batches[-1].pad == 3
    it2 = mx.io.NDArrayIter(data, np.zeros(22, dtype=np.float32),
                            batch_size=5, last_batch_handle="discard")
    assert len(list(it2)) == 4


def test_ndarray_iter_dict_data():
    it = mx.io.NDArrayIter({"a": np.ones((10, 2), dtype=np.float32),
                            "b": np.zeros((10, 3), dtype=np.float32)},
                           batch_size=5)
    names = sorted(d.name for d in it.provide_data)
    assert names == ["a", "b"]


def test_resize_iter():
    data = np.zeros((12, 2), dtype=np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(12, dtype=np.float32),
                             batch_size=4)
    r = mx.io.ResizeIter(base, 10)
    assert len(list(r)) == 10


def test_prefetching_iter():
    data = np.random.rand(20, 3).astype(np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(20, dtype=np.float32),
                             batch_size=5)
    pf = mx.io.PrefetchingIter(base)
    batches = list(pf)
    assert len(batches) == 4
    pf.reset()
    batches2 = list(pf)
    assert len(batches2) == 4
    assert_almost_equal(batches[0].data[0], batches2[0].data[0])


def test_csv_iter():
    with tempfile.TemporaryDirectory() as d:
        data_path = os.path.join(d, "data.csv")
        label_path = os.path.join(d, "label.csv")
        data = np.random.rand(30, 4).astype(np.float32)
        labels = np.arange(30).astype(np.float32)
        np.savetxt(data_path, data, delimiter=",")
        np.savetxt(label_path, labels, delimiter=",")
        it = mx.io.CSVIter(data_csv=data_path, data_shape=(4,),
                           label_csv=label_path, batch_size=10)
        batches = list(it)
        assert len(batches) == 3
        assert_almost_equal(batches[0].data[0], data[:10], rtol=1e-5)


def test_mnist_iter():
    """Write a tiny idx-format file pair and read it back."""
    import struct
    with tempfile.TemporaryDirectory() as d:
        img_path = os.path.join(d, "images-idx3-ubyte")
        lab_path = os.path.join(d, "labels-idx1-ubyte")
        n = 20
        imgs = (np.random.rand(n, 28, 28) * 255).astype(np.uint8)
        labs = (np.arange(n) % 10).astype(np.uint8)
        with open(img_path, "wb") as f:
            f.write(struct.pack(">IIII", 2051, n, 28, 28))
            f.write(imgs.tobytes())
        with open(lab_path, "wb") as f:
            f.write(struct.pack(">II", 2049, n))
            f.write(labs.tobytes())
        it = mx.io.MNISTIter(image=img_path, label=lab_path, batch_size=5,
                             shuffle=False)
        batch = next(iter(it))
        assert batch.data[0].shape == (5, 1, 28, 28)
        assert batch.data[0].asnumpy().max() <= 1.0
        assert_almost_equal(batch.label[0],
                            labs[:5].astype(np.float32))
        flat_it = mx.io.MNISTIter(image=img_path, label=lab_path,
                                  batch_size=5, flat=True, shuffle=False)
        assert next(iter(flat_it)).data[0].shape == (5, 784)


def test_data_desc():
    d = mx.io.DataDesc("data", (32, 3, 224, 224))
    assert d.name == "data"
    assert d.shape == (32, 3, 224, 224)
    assert mx.io.DataDesc.get_batch_axis("NCHW") == 0
    assert mx.io.DataDesc.get_batch_axis("TNC") == 1


# ------------------------------------------------ PrefetchingIter staging
def _counter(name):
    return mx.telemetry.counter("io.prefetch." + name).value


_STAGE_CASES = {
    "unaligned_4d_float32": ((32, 3, 72, 64), np.float32),
    "rows_not_a_multiple_of_8": ((30, 3, 96, 32), np.float32),
    "int32_4d": ((64, 2, 40, 64), np.int32),
    "aligned_2d": ((64, 8192), np.float32),
    "tiled_4d": ((8, 3, 16, 1024), np.float32),
    "label_vector": ((256,), np.float32),
    "odd_minor_dimension": ((32, 3, 100, 30), np.float32),
    "small_uint8": ((8, 3, 8, 8), np.uint8),
}


@pytest.mark.parametrize("stacked", [False, True], ids=["batch", "window"])
@pytest.mark.parametrize("case", sorted(_STAGE_CASES))
def test_stage_lands_the_iterators_batch(case, stacked):
    """What the staging helper puts on the device is the iterator's
    array - shape, dtype and every bit, tile-aligned or not - and the
    counters say that it went that way."""
    shape, dtype = _STAGE_CASES[case]
    rs = np.random.RandomState(len(case))
    parts = [(rs.rand(*shape) * 200).astype(dtype)
             for _ in range(2 if stacked else 1)]
    want = np.stack(parts) if stacked else parts[0]
    batches, nbytes = _counter("staged_batches"), _counter("staged_bytes")
    out = mx.io._stage([mx.nd.array(p, dtype=dtype) for p in parts],
                       mx.cpu(1), stacked=stacked)
    assert isinstance(out, mx.nd.NDArray)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert np.array_equal(out.asnumpy(), want)
    assert out.asjax().devices() == {mx.cpu(1).jax_device()}
    assert _counter("staged_batches") == batches + 1
    assert _counter("staged_bytes") == nbytes + want.nbytes


def test_ndarray_iter_wraps_its_batches_without_a_copy():
    """A batch is a view of the iterator's (64-byte-aligned) storage:
    copied into fresh pages instead, a ResNet batch cost 0.24 s of page
    faults on the chip's host (PERF.md, PR 26)."""
    X = np.random.rand(65, 3, 16, 16).astype("f")[1:]   # off alignment
    y = np.arange(64, dtype="f")
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    for k, batch in enumerate(it):
        for got, (_, store) in ((batch.data[0], it.data[0]),
                                (batch.label[0], it.label[0])):
            view = np.asarray(got.asjax())
            assert np.shares_memory(view, store)
            assert np.array_equal(view, store[16 * k:16 * (k + 1)])
    assert np.array_equal(it.data[0][1], X)


def _flat_classifier():
    net = mx.sym.FullyConnected(mx.sym.Flatten(mx.sym.var("data")),
                                num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_stage_under_the_bound_groups_sharding():
    """Staged under the placement Module.fit hands over, a batch lies
    under the executor group's own data sharding, each device's rows on
    that device, and _load_batch moves nothing: the bound input holds
    the very buffers the producer staged."""
    shape = (32, 3, 72, 64)
    mod = mx.mod.Module(_flat_classifier(),
                        context=[mx.cpu(i) for i in range(4)])
    mod.bind([("data", shape)], [("softmax_label", shape[:1])])
    mod.init_params()
    group = mod._exec_group
    placement = mod._input_placement()
    assert placement == group._data_sharding
    X = np.random.rand(*shape).astype("f")
    y = np.arange(shape[0], dtype="f") % 4
    before = _counter("staged_batches")
    it = mx.io.PrefetchingIter(mx.io.NDArrayIter(X, y, batch_size=shape[0]),
                               device=placement)
    batch = it.next()
    assert _counter("staged_batches") >= before + 2     # data and label
    staged = {"data": batch.data[0].asjax(),
              "softmax_label": batch.label[0].asjax()}
    assert np.array_equal(np.asarray(staged["data"]), X)
    for arr in staged.values():
        assert arr.sharding == group._data_sharding
        assert [s.device for s in arr.addressable_shards] \
            == [c.jax_device() for c in group.contexts]
    group._load_batch(batch)
    for name, arr in staged.items():
        bound = group.executor.arg_dict[name].asjax()
        assert [(s.device, s.data.unsafe_buffer_pointer())
                for s in bound.addressable_shards] \
            == [(s.device, s.data.unsafe_buffer_pointer())
                for s in arr.addressable_shards]
