"""Module tests (mirrors reference tests/python/unittest/test_module.py)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def _softmax_net(num_hidden=4, num_classes=3):
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data=data, num_hidden=num_hidden, name="fc1")
    act = mx.sym.Activation(fc, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _toy_iter(n=120, dim=6, classes=3, batch=20, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, dim).astype(np.float32)
    w = rng.randn(dim, classes).astype(np.float32)
    y = X.dot(w).argmax(axis=1).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=True)


def test_module_input_names():
    data = mx.sym.var("data")
    out = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    with pytest.raises(ValueError):
        mx.mod.Module(out, data_names=["wrong_name"], label_names=[])


def test_module_fit_and_score():
    it = _toy_iter()
    mod = mx.mod.Module(_softmax_net(), context=mx.cpu())
    mod.fit(it, num_epoch=15, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5})
    acc = mod.score(it, "acc")[0][1]
    assert acc > 0.9, f"accuracy {acc}"


def test_module_predict_shapes():
    it = _toy_iter()
    mod = mx.mod.Module(_softmax_net(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label, for_training=False)
    mod.init_params()
    out = mod.predict(it)
    assert out.shape == (120, 3)
    np.testing.assert_allclose(out.asnumpy().sum(axis=1),
                               np.ones(120), rtol=1e-4)


def test_module_get_set_params():
    it = _toy_iter()
    mod = mx.mod.Module(_softmax_net(), context=mx.cpu())
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    args, auxs = mod.get_params()
    assert "fc1_weight" in args
    mod2 = mx.mod.Module(_softmax_net(), context=mx.cpu())
    mod2.bind(it.provide_data, it.provide_label)
    mod2.set_params(args, auxs)
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        assert_almost_equal(a1[k], a2[k])


def test_module_checkpoint_roundtrip():
    it = _toy_iter()
    mod = mx.mod.Module(_softmax_net(), context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer_params={"learning_rate": 0.1})
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "model")
        mod.save_checkpoint(prefix, 2, save_optimizer_states=True)
        assert os.path.exists(f"{prefix}-symbol.json")
        assert os.path.exists(f"{prefix}-0002.params")
        assert os.path.exists(f"{prefix}-0002.states")
        mod2 = mx.mod.Module.load(prefix, 2)
        mod2.bind(it.provide_data, it.provide_label, for_training=False)
        it.reset()
        p1 = mod.predict(it, num_batch=1).asnumpy()
        it.reset()
        p2 = mod2.predict(it, num_batch=1).asnumpy()
        assert_almost_equal(p1, p2, rtol=1e-5)


def test_module_fixed_params():
    it = _toy_iter()
    mod = mx.mod.Module(_softmax_net(), context=mx.cpu(),
                        fixed_param_names=["fc1_weight"])
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 1.0})
    before = mod._exec_group.executor.arg_dict["fc1_weight"].asnumpy().copy()
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    after = mod._exec_group.executor.arg_dict["fc1_weight"].asnumpy()
    assert_almost_equal(before, after)  # frozen
    # non-fixed params did move
    fc2b = mod._exec_group.executor.arg_dict["fc2_weight"].asnumpy()
    assert not np.allclose(
        fc2b, mod._arg_params["fc2_weight"].asnumpy())


def test_module_input_grads():
    data = mx.sym.var("data")
    loss = mx.sym.LinearRegressionOutput(
        data=mx.sym.FullyConnected(data, num_hidden=1, name="fc"),
        name="lin")
    mod = mx.mod.Module(loss, label_names=["lin_label"], context=mx.cpu())
    mod.bind([("data", (4, 3))], [("lin_label", (4, 1))],
             inputs_need_grad=True)
    mod.init_params()
    batch = mx.io.DataBatch(data=[mx.nd.ones((4, 3))],
                            label=[mx.nd.zeros((4, 1))])
    mod.forward_backward(batch)
    grads = mod.get_input_grads()
    assert grads[0].shape == (4, 3)
    assert np.abs(grads[0].asnumpy()).sum() > 0


def test_bucketing_module():
    def sym_gen(seq_len):
        # params must be seq-length independent (shared across buckets)
        data = mx.sym.var("data")
        emb = mx.sym.Embedding(data, input_dim=20, output_dim=4, name="emb")
        pooled = mx.sym.sum(emb, axis=1)
        fc = mx.sym.FullyConnected(pooled, num_hidden=3, name="fc")
        out = mx.sym.SoftmaxOutput(fc, name="softmax")
        return out, ["data"], ["softmax_label"]

    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=10,
                                 context=mx.cpu())
    mod.bind([("data", (8, 10))], [("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    for key in [10, 6, 10, 6]:
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rng.randint(0, 20, (8, key))
                              .astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 3, 8).astype(np.float32))],
            bucket_key=key,
            provide_data=[mx.io.DataDesc("data", (8, key))],
            provide_label=[mx.io.DataDesc("softmax_label", (8,))])
        mod.forward_backward(batch)
        mod.update()
    assert set(mod._buckets) == {10, 6}
    # params shared across buckets (identity of the cells)
    e10 = mod._buckets[10]._exec_group.executor
    e6 = mod._buckets[6]._exec_group.executor
    assert e10.arg_dict["fc_bias"] is e6.arg_dict["fc_bias"]


def test_sequential_module():
    net1 = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                                 name="fc1")
    net2 = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3, name="fc2"),
        name="softmax")
    mod = mx.mod.SequentialModule()
    mod.add(mx.mod.Module(net1, label_names=[], context=mx.cpu()))
    mod.add(mx.mod.Module(net2, context=mx.cpu()), take_labels=True,
            auto_wiring=True)
    it = _toy_iter(dim=6)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.5})
    metric = mx.metric.create("acc")
    for _ in range(10):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(metric, batch.label)
    assert metric.get()[1] > 0.5


def test_module_multi_device_matches_single():
    """DP over 4 virtual devices must match single-device numerics
    bit-for-bit (up to f32 reduction order): same init -> same params
    after an epoch."""
    def make_iter():
        rng = np.random.RandomState(3)
        X = rng.randn(120, 6).astype(np.float32)
        w = rng.randn(6, 3).astype(np.float32)
        y = X.dot(w).argmax(axis=1).astype(np.float32)
        return mx.io.NDArrayIter(X, y, batch_size=24, shuffle=False)

    args = None
    params_out = []
    for ctxs in [[mx.cpu(0)], [mx.cpu(i) for i in range(4)]]:
        it = make_iter()
        mod = mx.mod.Module(_softmax_net(), context=ctxs)
        mod.bind(it.provide_data, it.provide_label)
        if args is None:
            mx.random.seed(7)
            mod.init_params(mx.initializer.Xavier())
            a, _ = mod.get_params()
            args = {k: v.asnumpy() for k, v in a.items()}
        else:
            mod.init_params(
                arg_params={k: mx.nd.array(v) for k, v in args.items()},
                aux_params={})
        mod.init_optimizer(optimizer_params={"learning_rate": 0.5})
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
        p, _ = mod.get_params()
        params_out.append(p["fc2_weight"].asnumpy())
    assert np.abs(params_out[0] - params_out[1]).max() < 1e-4


def _mlp_sym():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc1, act_type="tanh")
    fc2 = mx.sym.FullyConnected(act, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, mx.sym.Variable("softmax_label"),
                                name="softmax")


def _run_steps(fused, optimizer, opt_params, steps=5):
    rs = np.random.RandomState(42)
    init_args = {
        "fc1_weight": rs.randn(8, 6).astype(np.float32) * 0.1,
        "fc1_bias": np.zeros(8, np.float32),
        "fc2_weight": rs.randn(3, 8).astype(np.float32) * 0.1,
        "fc2_bias": np.zeros(3, np.float32),
    }
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params(arg_params={k: mx.nd.array(v)
                                for k, v in init_args.items()})
    mod.init_optimizer(kvstore=None, optimizer=optimizer,
                       optimizer_params=opt_params)
    if fused:
        assert mod._fused_armed, "fused path should arm for " + optimizer
    else:
        mod._fused_armed = False
    for step in range(steps):
        srs = np.random.RandomState(100 + step)
        batch = mx.io.DataBatch(
            data=[mx.nd.array(srs.rand(4, 6).astype(np.float32))],
            label=[mx.nd.array(srs.randint(0, 3, (4,)).astype(np.float32))])
        mod.forward_backward(batch)
        mod.update()
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", (("learning_rate", 0.1), ("momentum", 0.9), ("wd", 1e-4))),
    ("adam", (("learning_rate", 0.01), ("wd", 1e-4))),
])
def test_fused_step_matches_staged(optimizer, opt_params):
    """VERDICT r2 #2: the fused fwd+bwd+update program must reproduce the
    staged forward/backward/update numerics over several steps."""
    fused = _run_steps(True, optimizer, opt_params)
    staged = _run_steps(False, optimizer, opt_params)
    for k in fused:
        np.testing.assert_allclose(fused[k], staged[k], rtol=2e-5,
                                   atol=2e-6, err_msg=k)


def test_fused_step_optimizer_state_roundtrip():
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9)))
    assert mod._fused_armed
    rs = np.random.RandomState(3)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, (4,)).astype(np.float32))])
    mod.forward_backward(batch)
    mod.update()
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "opt.states")
        mod.save_optimizer_states(fname)
        before = {k: np.asarray(v) for k, v in
                  mod._exec_group._fused_states.items()}
        mod.forward_backward(batch)
        mod.update()
        mod.load_optimizer_states(fname)
        after = {k: np.asarray(v) for k, v in
                 mod._exec_group._fused_states.items()}
    for k in before:
        np.testing.assert_allclose(before[k], after[k])


def test_fused_keep_grads_env(monkeypatch):
    """MXNET_FUSED_KEEP_GRADS=1 makes the fused program emit per-param
    gradients into grad_dict (off by default: they cost ~5%/step)."""
    def grads_after_step(keep):
        monkeypatch.setenv("MXNET_FUSED_KEEP_GRADS", "1" if keep else "0")
        rs = np.random.RandomState(11)
        mx.random.seed(5)                 # identical params every variant
        mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
        mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
        mod.init_params(mx.initializer.Xavier())
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.0),))
        assert mod._fused_armed
        gd = mod._exec_group.executor.grad_dict
        before = {k: v.asnumpy().copy() for k, v in gd.items()
                  if v is not None}
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
            label=[mx.nd.array(rs.randint(0, 3, (4,)).astype(np.float32))])
        mod.forward_backward(batch)
        after = {k: v.asnumpy() for k, v in gd.items() if v is not None}
        changed = any(np.abs(after[k] - before[k]).max() > 0
                      for k in after)
        return changed, after

    changed_off, grads_off = grads_after_step(False)
    assert not changed_off, "default fused step must not write grad_dict"
    # ADVICE r5: with KEEP_GRADS unset the fused path never emits grads —
    # the buffers are NaN-poisoned at arm time so a stale read fails
    # loudly instead of returning plausible pre-step values
    for k, v in grads_off.items():
        assert np.isnan(v).all(), f"{k} not poisoned"
    changed_on, grads_fused = grads_after_step(True)
    assert changed_on, "KEEP_GRADS=1 must populate grad_dict"
    # and the emitted gradients match the staged path's
    rs = np.random.RandomState(11)
    mx.random.seed(5)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.0),))
    mod._fused_armed = False                      # staged path
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, (4,)).astype(np.float32))])
    mod.forward_backward(batch)
    for k, v in mod._exec_group.executor.grad_dict.items():
        if v is not None:
            np.testing.assert_allclose(grads_fused[k], v.asnumpy(),
                                       rtol=2e-5, atol=2e-6, err_msg=k)


def test_fused_metric_scalars_match_staged_accuracy():
    """The fused program's in-step top-1 counts must reproduce exactly
    what Accuracy computes from the outputs (zero-dispatch metric)."""
    rs = np.random.RandomState(9)
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (8, 6))], [("softmax_label", (8,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.0),))
    assert mod._fused_armed
    fused_acc = mx.metric.create("acc")
    ref_acc = mx.metric.create("acc")
    for _ in range(3):
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rs.rand(8, 6).astype(np.float32))],
            label=[mx.nd.array(rs.randint(0, 3, (8,)).astype(np.float32))])
        mod.forward_backward(batch)
        assert mod._exec_group._fused_metric_scalars is not None
        mod.update_metric(fused_acc, batch.label)
        assert mod._exec_group._fused_metric_scalars is None  # consumed
        ref_acc.update(batch.label, mod.get_outputs())
    assert fused_acc.get() == ref_acc.get()
    # an eval pass right after a fused step must not consume train counts
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(8, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, (8,)).astype(np.float32))])
    mod.forward_backward(batch)                 # scalars armed...
    mod.forward(batch, is_train=False)          # ...invalidated by eval
    assert mod._exec_group._fused_metric_scalars is None


def test_fused_rng_reseed_mid_training():
    """mx.random.seed() between steps must re-draw the fused step's
    device-chained rng key (reference seed semantics: seeding is
    effective at any point, not just before arming)."""
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    assert mod._fused_armed
    eg = mod._exec_group
    rs = np.random.RandomState(3)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, (4,)).astype(np.float32))])
    mod.forward_backward(batch)
    key_before = np.asarray(eg._fused_key).copy()
    mx.random.seed(42)
    mod.forward_backward(batch)        # must re-draw from new chain
    mx.random.seed(42)
    fresh = np.asarray(mx.random.next_key())
    # the chain was re-drawn at the step boundary: the key in use after
    # reseed+step is the successor of the reseeded chain's first subkey,
    # not a continuation of the pre-seed chain
    assert not np.array_equal(np.asarray(eg._fused_key), key_before)
    import jax
    expect = np.asarray(jax.random.split(fresh)[0])
    np.testing.assert_array_equal(np.asarray(eg._fused_key), expect)


def test_set_params_after_arming_does_not_donate_caller_buffer():
    """set_params after the fused step is armed must copy: astype/
    device_put are identity when dtype+placement match, and the next
    step's donation would otherwise delete a buffer the caller holds."""
    mod = mx.mod.Module(_mlp_sym(), context=mx.cpu())
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(kvstore=None, optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    assert mod._fused_armed
    rs = np.random.RandomState(7)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(4, 6).astype(np.float32))],
        label=[mx.nd.array(rs.randint(0, 3, (4,)).astype(np.float32))])
    mod.forward_backward(batch)
    mod.update()
    # caller-held arrays, already in matching dtype/placement
    args, aux = mod.get_params()
    held = {k: v.asjax() for k, v in args.items()}
    mod.set_params(args, aux)
    mod.forward_backward(batch)          # donated step runs again
    mod.update()
    for k, v in held.items():            # caller buffers must survive
        np.asarray(v)


def test_fused_step_matches_staged_with_scheduler():
    """lr scheduler must see the same update count in both paths."""
    def params():
        return (("learning_rate", 0.2), ("momentum", 0.9),
                ("lr_scheduler",
                 mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)))
    fused = _run_steps(True, "sgd", params())
    staged = _run_steps(False, "sgd", params())
    for k in fused:
        np.testing.assert_allclose(fused[k], staged[k], rtol=2e-5,
                                   atol=2e-6, err_msg=k)


# ------------------------------------------------------------------------
# _load_batch: an input that already lies where the executor wants it is
# taken as it is; everything else is converted and put (ISSUE 39)
# ------------------------------------------------------------------------
def _infer_module(contexts, batch=8, dtype=np.float32):
    net = mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3, name="fc")
    mod = mx.mod.Module(net, label_names=[], context=contexts)
    mod.bind([mx.io.DataDesc("data", (batch, 6), dtype)], None,
             for_training=False)
    mod.init_params(mx.initializer.Xavier())
    return mod


def _load_counts():
    from mxnet_tpu import telemetry as tm
    out = []
    for nm in ("io.load_batch.aliased", "io.load_batch.puts"):
        m = tm.get_metric(nm)
        out.append(0 if m is None else m.value)
    return out


def _one_device_inputs():
    import jax
    import jax.numpy as jnp
    host = np.random.RandomState(5).rand(8, 6)
    dev0, dev1 = jax.devices()[:2]
    return {
        # (what the batch holds, is it taken as it is)
        "ndarray_in_place": (lambda: mx.nd.array(host.astype("f")), True),
        "jax_array_in_place": (
            lambda: jax.device_put(host.astype("f"), dev0), True),
        "numpy": (lambda: host.astype("f"), False),
        "numpy_float64": (lambda: host, False),
        "wrong_dtype": (lambda: mx.nd.array(host, dtype=np.float16), False),
        "other_device": (
            lambda: mx.nd.NDArray(jax.device_put(host.astype("f"), dev1)),
            False),
        "uncommitted": (lambda: jnp.asarray(host.astype("f")), False),
    }


@pytest.mark.parametrize("case", sorted(_one_device_inputs()))
def test_load_batch_takes_an_input_in_place_as_it_is(case, counting):
    """One device: an array of the cell's dtype committed to the
    executor's device becomes the cell's buffer (same pointer, counted
    as aliased); a numpy array, another dtype, another device or an
    uncommitted array goes through astype + device_put as before. The
    values the program reads are the caller's either way."""
    make, in_place = _one_device_inputs()[case]
    mod = _infer_module(mx.cpu(0))
    arr = make()
    before = _load_counts()
    mod.forward(mx.io.DataBatch(data=[arr], label=[]), is_train=False)
    aliased, puts = (a - b for a, b in zip(_load_counts(), before))
    assert (aliased, puts) == ((1, 0) if in_place else (0, 1))
    cell = mod._exec_group.executor.arg_dict["data"]
    given = arr.asjax() if isinstance(arr, mx.nd.NDArray) else arr
    if in_place:
        assert cell.asjax() is given
        assert cell.asjax().unsafe_buffer_pointer() == \
            given.unsafe_buffer_pointer()
    else:
        assert cell.asjax() is not given
        assert cell.dtype == np.float32
        dev = next(iter(cell.asjax().devices()))
        assert dev == mx.cpu(0).jax_device() and cell.asjax().committed
    want = np.asarray(given).astype(np.float32)
    np.testing.assert_array_equal(cell.asnumpy(), want)
    w = mod.get_params()[0]
    np.testing.assert_allclose(
        mod.get_outputs()[0].asnumpy(),
        want @ w["fc_weight"].asnumpy().T + w["fc_bias"].asnumpy(),
        rtol=1e-5, atol=1e-6)


def _mesh_inputs(group):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    host = np.random.RandomState(6).rand(8, 6).astype("f")
    mesh = group._mesh
    return {
        "data_sharding": (group._data_sharding, True),
        "equivalent_sharding": (NamedSharding(mesh, P("data", None)), True),
        "replicated": (NamedSharding(mesh, P()), False),
        "one_device": (jax.devices()[0], False),
    }, host


@pytest.mark.parametrize("case", ["data_sharding", "equivalent_sharding",
                                  "replicated", "one_device"])
def test_load_batch_on_a_mesh_takes_an_equivalent_sharding(case, counting):
    """The dp tests' CPU mesh: rows already laid over the mesh's data
    axis are taken as they are; a replicated array or one on a single
    device is sharded as before."""
    import jax
    mod = _infer_module([mx.cpu(i) for i in range(4)])
    group = mod._exec_group
    cases, host = _mesh_inputs(group)
    placement, in_place = cases[case]
    given = jax.device_put(host, placement)
    before = _load_counts()
    mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(given)], label=[]),
                is_train=False)
    aliased, puts = (a - b for a, b in zip(_load_counts(), before))
    assert (aliased, puts) == ((1, 0) if in_place else (0, 1))
    cell = group.executor.arg_dict["data"].asjax()
    assert (cell is given) == in_place
    assert cell.sharding.is_equivalent_to(group._data_sharding, 2)
    np.testing.assert_array_equal(np.asarray(cell), host)
    w = mod.get_params()[0]
    np.testing.assert_allclose(
        mod.get_outputs()[0].asnumpy(),
        host @ w["fc_weight"].asnumpy().T + w["fc_bias"].asnumpy(),
        rtol=1e-5, atol=1e-6)


def test_load_batch_counts_nothing_while_telemetry_is_off():
    from mxnet_tpu import telemetry as tm
    tm.disable()
    mod = _infer_module(mx.cpu(0))
    before = _load_counts()
    mod.forward(mx.io.DataBatch(
        data=[mx.nd.array(np.ones((8, 6), "f"))], label=[]), is_train=False)
    assert _load_counts() == before


def test_aliased_input_outlives_a_forward_that_donates_its_aux():
    """A decode graph's ``fwd_infer`` donates every aux array
    (``donate_argnums=(1,)``) and no data entry: the caller's array
    that a cell aliased is alive after the next forwards and reads as
    it was written."""
    from mxnet_tpu.models import transformer as tfm
    sym = tfm.get_decode_symbol(vocab_size=32, d_model=16, n_layer=1,
                                n_head=2, capacity=8, per_slot=True,
                                max_seq_len=8)
    mod = mx.mod.Module(sym, data_names=("data", "fed"), label_names=[])
    mod.bind([mx.io.DataDesc("data", (2, 1), np.int32),
              mx.io.DataDesc("fed", (2,), np.int32)], None,
             for_training=False)
    exe = mod._exec_group.executor
    mod.init_params(mx.initializer.Xavier(), aux_params={
        nm: mx.nd.zeros(c.shape, dtype=c.dtype)
        for nm, c in exe.aux_dict.items()})
    assert exe.donates_aux
    ids = mx.nd.array(np.array([[3], [5]], np.int32))
    held = ids.asjax()
    pools = [c.asjax() for c in exe.aux_arrays]
    fed = mx.nd.array(np.ones(2, np.int32))
    for _ in range(3):
        mod.forward(mx.io.DataBatch(data=[ids, fed], label=[]),
                    is_train=False)
        assert exe.arg_dict["data"].asjax() is held
    assert all(p.is_deleted() for p in pools)     # the aux WAS donated
    assert not held.is_deleted()
    np.testing.assert_array_equal(np.asarray(held), [[3], [5]])
    np.testing.assert_array_equal(ids.asnumpy(), [[3], [5]])
