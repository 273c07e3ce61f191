"""Deployment/predict surface (mxnet_tpu/predict.py).

Reference parity target: the standalone predict API
(src/c_api/c_predict_api.cc:1-334) — build from serialized artifacts,
run inference without the training stack. Gates: (a) Predictor output
== Module.predict bitwise-close, (b) the artifact loads and runs in a
FRESH subprocess that never constructs a Symbol or Module, (c) shape
mismatches error per the fixed-shape contract.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import lenet


def _trained_module(batch=8):
    net = lenet.get_symbol(num_classes=4)
    it = mx.io.NDArrayIter(
        np.random.rand(32, 1, 28, 28).astype(np.float32),
        (np.random.rand(32) * 4).astype(np.float32), batch)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.01})
    return net, mod


def test_export_roundtrip_matches_module_predict(tmp_path):
    net, mod = _trained_module()
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "lenet.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (8, 1, 28, 28)})

    x = np.random.rand(8, 1, 28, 28).astype(np.float32)
    it = mx.io.NDArrayIter(x, None, 8)
    expect = mod.predict(it).asnumpy()

    pred = mx.Predictor(path)
    assert pred.output_names == net.list_outputs()
    got = pred.forward(data=x)[0].asnumpy()
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    # get_output mirrors MXPredGetOutput
    np.testing.assert_allclose(pred.get_output(0).asnumpy(), got)


def test_predictor_runs_in_fresh_process(tmp_path):
    """The artifact must be servable by a process that never builds a
    Symbol/Module (the reference's deployment story: amalgamated predict
    lib + params blob)."""
    net, mod = _trained_module()
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "lenet.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (8, 1, 28, 28)})
    x = np.random.rand(8, 1, 28, 28).astype(np.float32)
    np.save(str(tmp_path / "x.npy"), x)
    it = mx.io.NDArrayIter(x, None, 8)
    expect = mod.predict(it).asnumpy()
    np.save(str(tmp_path / "expect.npy"), expect)

    script = f"""
import numpy as np
from mxnet_tpu.predict import Predictor
import mxnet_tpu.symbol as _sym_mod
import mxnet_tpu.module as _mod_mod
# prove the loader path itself never constructs graph objects
_sym_mod.Symbol.__init__ = lambda *a, **k: (_ for _ in ()).throw(
    RuntimeError("Symbol constructed in predictor process"))
p = Predictor({str(tmp_path / 'lenet.mxp')!r})
x = np.load({str(tmp_path / 'x.npy')!r})
out = p.forward(data=x)[0].asnumpy()
expect = np.load({str(tmp_path / 'expect.npy')!r})
np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
print("PREDICTOR_SUBPROCESS_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "PREDICTOR_SUBPROCESS_OK" in r.stdout, r.stderr[-2000:]


def test_predictor_rejects_wrong_shape(tmp_path):
    net, mod = _trained_module()
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "lenet.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (8, 1, 28, 28)})
    pred = mx.Predictor(path)
    with pytest.raises(mx.base.MXNetError):
        pred.forward(data=np.zeros((4, 1, 28, 28), np.float32))


@pytest.mark.slow
def test_export_resnet50(tmp_path):
    """Flagship round-trip (VERDICT r3 #4: 'export ResNet-50, reload,
    outputs match Module.predict') at a reduced image size so the CPU
    trace stays test-sized."""
    from mxnet_tpu.models import resnet
    net = resnet.get_symbol(num_classes=10, num_layers=50,
                            image_shape="3,32,32")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind([("data", (4, 3, 32, 32))], [("softmax_label", (4,))],
             for_training=False)
    mod.init_params(mx.initializer.Xavier())
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "resnet50.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (4, 3, 32, 32)})
    x = np.random.rand(4, 3, 32, 32).astype(np.float32)
    it = mx.io.NDArrayIter(x, None, 4)
    expect = mod.predict(it).asnumpy()
    got = mx.Predictor(path).forward(data=x)[0].asnumpy()
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-5)


def test_manifest_records_input_dtypes_int_roundtrip(tmp_path):
    """Satellite (ISSUE 8): the manifest records each input's dtype and
    ``Predictor.forward`` respects it instead of hard-coding float32 —
    an int32 embedding-id input must round-trip through the artifact."""
    ids_sym = mx.sym.var("data")
    emb = mx.sym.Embedding(ids_sym, input_dim=10, output_dim=4,
                           name="embed")
    weight = np.random.RandomState(0).rand(10, 4).astype(np.float32)
    path = str(tmp_path / "embed.mxp")
    mx.export_model(path, emb, {"embed_weight": weight}, {},
                    {"data": (3, 5)}, data_dtypes={"data": np.int32})

    pred = mx.Predictor(path)
    assert pred.input_dtypes == {"data": np.dtype(np.int32)}
    ids = np.random.RandomState(1).randint(0, 10, (3, 5))
    out = pred.forward(data=ids)[0].asnumpy()
    np.testing.assert_allclose(out, weight[ids], rtol=1e-6)
    # a float array of ids still works (cast to the recorded dtype)
    out2 = pred.forward(data=ids.astype(np.float64))[0].asnumpy()
    np.testing.assert_allclose(out2, out)


def test_manifest_bf16_input_dtype(tmp_path):
    """bf16-exported inputs: the program's avals are bf16, so the old
    float32 coercion would be rejected at call time; the recorded-dtype
    cast must make float32 host arrays servable."""
    import jax.numpy as jnp
    net, mod = _trained_module()
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "lenet_bf16.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (8, 1, 28, 28)},
                    data_dtypes={"data": jnp.bfloat16})
    pred = mx.Predictor(path)
    assert pred.input_dtypes["data"] == np.dtype(jnp.bfloat16)

    x = np.random.rand(8, 1, 28, 28).astype(np.float32)
    got = pred.forward(data=x)[0].asnumpy()
    it = mx.io.NDArrayIter(x, None, 8)
    expect = mod.predict(it).asnumpy()
    # bf16 input quantization: close, not bitwise
    np.testing.assert_allclose(got, expect, rtol=0.1, atol=0.05)


def test_predictor_batch_forward_dynamic_rows(tmp_path):
    """Satellite (ISSUE 8): ``batch_forward`` takes a dynamic leading
    batch dim, windows it through the fixed exported batch with the
    serving pad/slice helpers, and matches Module.predict."""
    net, mod = _trained_module(batch=4)
    arg_params, aux_params = mod.get_params()
    path = str(tmp_path / "lenet_b4.mxp")
    mx.export_model(path, net, arg_params, aux_params,
                    {"data": (4, 1, 28, 28)})
    pred = mx.Predictor(path)

    x = np.random.rand(10, 1, 28, 28).astype(np.float32)
    got = pred.batch_forward(data=x)[0].asnumpy()
    assert got.shape[0] == 10
    expect = mod.predict(mx.io.NDArrayIter(x, None, 4)).asnumpy()
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    # full-window rows are the exported program's output verbatim
    direct = pred.forward(data=x[:4])[0].asnumpy()
    assert np.array_equal(got[:4], direct)
    # fewer rows than the exported batch also work (one padded window)
    small = pred.batch_forward(data=x[:2])[0].asnumpy()
    np.testing.assert_allclose(small, expect[:2], rtol=1e-5, atol=1e-6)
