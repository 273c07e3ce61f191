"""Executor tests (mirrors reference tests/python/unittest/test_executor.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import assert_almost_equal


def test_bind_forward():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    c = a + b
    a_np = np.random.rand(4, 4).astype(np.float32)
    b_np = np.random.rand(4, 4).astype(np.float32)
    ex = c.bind(mx.cpu(), args={"a": mx.nd.array(a_np),
                                "b": mx.nd.array(b_np)})
    out = ex.forward()
    assert_almost_equal(out[0], a_np + b_np)


def test_bind_backward():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    c = a * b
    a_np = np.random.rand(3, 3).astype(np.float32)
    b_np = np.random.rand(3, 3).astype(np.float32)
    ga = mx.nd.zeros((3, 3))
    gb = mx.nd.zeros((3, 3))
    ex = c.bind(mx.cpu(), args={"a": mx.nd.array(a_np),
                                "b": mx.nd.array(b_np)},
                args_grad={"a": ga, "b": gb})
    ex.forward(is_train=True)
    head = np.random.rand(3, 3).astype(np.float32)
    ex.backward([mx.nd.array(head)])
    assert_almost_equal(ga, head * b_np, rtol=1e-5)
    assert_almost_equal(gb, head * a_np, rtol=1e-5)


def test_grad_req_add():
    a = mx.sym.var("a")
    c = a * 2
    a_np = np.random.rand(3,).astype(np.float32)
    ga = mx.nd.ones((3,))
    ex = c.bind(mx.cpu(), args={"a": mx.nd.array(a_np)},
                args_grad={"a": ga}, grad_req="add")
    ex.forward(is_train=True)
    ex.backward([mx.nd.ones((3,))])
    assert_almost_equal(ga, np.ones(3) + 2)  # 1 (initial) + 2 (grad)


def test_grad_req_null():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    c = a * b
    ex = c.bind(mx.cpu(), args={"a": mx.nd.ones((2,)),
                                "b": mx.nd.ones((2,))},
                args_grad={"a": mx.nd.zeros((2,))},
                grad_req={"a": "write", "b": "null"})
    ex.forward(is_train=True)
    ex.backward([mx.nd.ones((2,))])
    assert ex.grad_dict["b"] is None
    assert_almost_equal(ex.grad_dict["a"], np.ones(2))


def test_simple_bind():
    net = mx.sym.FullyConnected(data=mx.sym.var("data"), num_hidden=4,
                                name="fc")
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 8))
    assert ex.arg_dict["fc_weight"].shape == (4, 8)
    assert ex.arg_dict["fc_bias"].shape == (4,)
    ex.arg_dict["data"][:] = 1
    ex.arg_dict["fc_weight"][:] = 1
    ex.arg_dict["fc_bias"][:] = 0
    out = ex.forward()
    assert_almost_equal(out[0], np.full((2, 4), 8.0))


def test_executor_arg_aliasing():
    """Param mutation through the shared NDArray cell must be visible to
    the executor (the aliasing property executor_group relies on)."""
    net = mx.sym.FullyConnected(data=mx.sym.var("data"), num_hidden=2,
                                name="fc", no_bias=True)
    w = mx.nd.ones((2, 3))
    ex = net.bind(mx.cpu(), args={"data": mx.nd.ones((1, 3)),
                                  "fc_weight": w})
    out1 = ex.forward()[0].asnumpy()
    w *= 2  # in-place through the alias
    out2 = ex.forward()[0].asnumpy()
    assert_almost_equal(out2, out1 * 2)


def test_loss_head_backward_no_outgrads():
    net = mx.sym.SoftmaxOutput(mx.sym.var("data"), name="softmax")
    data = np.random.rand(4, 5).astype(np.float32)
    label = np.array([0, 1, 2, 3], dtype=np.float32)
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 5))
    ex.arg_dict["data"][:] = data
    ex.arg_dict["softmax_label"][:] = label
    ex.forward(is_train=True)
    ex.backward()
    prob = ex.outputs[0].asnumpy()
    onehot = np.eye(5, dtype=np.float32)[label.astype(int)]
    assert_almost_equal(ex.grad_dict["data"], prob - onehot, rtol=1e-5)


def test_reshape_executor():
    net = mx.sym.FullyConnected(data=mx.sym.var("data"), num_hidden=4,
                                name="fc")
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 8))
    ex.arg_dict["fc_weight"][:] = 1
    ex2 = ex.reshape(data=(5, 8))
    assert ex2.arg_dict["data"].shape == (5, 8)
    # params carried over (same shape -> same cells)
    assert ex2.arg_dict["fc_weight"] is ex.arg_dict["fc_weight"]


def test_forward_override_kwargs():
    net = mx.sym.var("x") * 3
    ex = net.simple_bind(ctx=mx.cpu(), grad_req="null", x=(2, 2))
    out = ex.forward(x=mx.nd.ones((2, 2)))
    assert_almost_equal(out[0], np.full((2, 2), 3.0))


def test_multi_output_executor():
    data = mx.sym.var("data")
    parts = mx.sym.SliceChannel(data, num_outputs=3, axis=1, name="slice")
    ex = parts.bind(mx.cpu(), args={"data": mx.nd.array(
        np.arange(12).reshape(2, 6).astype(np.float32))})
    outs = ex.forward()
    assert len(outs) == 3
    assert outs[0].shape == (2, 2)


def test_monitor_taps_per_op_during_training():
    """ADVICE r2 (low): fit-style forward(is_train=True)+backward must
    still fire the per-op monitor tap (reference ExecuteMonCallback)."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    out = mx.sym.SoftmaxOutput(fc, mx.sym.Variable("sm_label"), name="sm")
    exe = out.simple_bind(mx.cpu(), data=(2, 4), sm_label=(2,))
    seen = []
    exe.set_monitor_callback(lambda name, arr: seen.append(name))
    exe.forward(is_train=True,
                data=mx.nd.array(np.random.rand(2, 4).astype(np.float32)))
    exe.backward()
    assert any("fc" in n for n in seen), seen
    assert any("sm" in n for n in seen), seen
    # exactly once per op per step — no duplicate taps
    from collections import Counter
    assert all(c == 1 for c in Counter(seen).values()), Counter(seen)


def test_naive_engine_serial_replay(monkeypatch):
    """MXNET_ENGINE_TYPE=NaiveEngine routes executor programs through the
    un-jitted serial runner (reference: env_var.md:33-40, the documented
    deterministic-debug switch) and must match the jitted path bitwise-
    close on forward outputs and gradients."""
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    out = mx.sym.SoftmaxOutput(fc, mx.sym.Variable("sm_label"), name="sm")

    x = np.random.rand(4, 5).astype(np.float32)
    y = np.array([0, 1, 2, 0], dtype=np.float32)

    def run_step():
        mx.random.seed(7)
        exe = out.simple_bind(mx.cpu(), data=(4, 5), sm_label=(4,))
        for nm, arr in exe.arg_dict.items():
            if nm not in ("data", "sm_label"):
                arr[:] = 0.1
        exe.forward(is_train=True, data=mx.nd.array(x),
                    sm_label=mx.nd.array(y))
        exe.backward()
        return (exe.outputs[0].asnumpy(),
                exe.grad_dict["fc_weight"].asnumpy())

    ref_out, ref_grad = run_step()
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    naive_out, naive_grad = run_step()
    assert_almost_equal(naive_out, ref_out)
    assert_almost_equal(naive_grad, ref_grad)


def test_naive_engine_disables_fused_fit(monkeypatch):
    """Under NaiveEngine Module.fit must fall back to the imperative
    per-phase path (per-op serial replay), not the fused XLA step."""
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    n = 16
    x = np.random.rand(n, 4).astype(np.float32)
    y = (x.sum(axis=1) > 2).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8, label_name="sm_label")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=2, name="fc"),
        mx.sym.var("sm_label"), name="sm")
    mod = mx.mod.Module(net, label_names=("sm_label",))
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert not mod._fused_armed


# ------------------------------------------------------------------------
# the key of a forward (ISSUE 39): a graph without an op that reads the
# key draws none; a graph with one draws a fresh key a call, as before
# ------------------------------------------------------------------------
def _keyless_exec():
    x = mx.sym.var("x")
    net = mx.sym.Activation(mx.sym.FullyConnected(x, num_hidden=4,
                                                  name="fc"),
                            act_type="tanh")
    return net.simple_bind(mx.cpu(), x=(3, 5))


def _dropout_exec():
    net = mx.sym.Dropout(mx.sym.var("x") * 2.0, p=0.5)
    ex = net.simple_bind(mx.cpu(), x=(16, 16))
    ex.arg_dict["x"][:] = 1.0
    return ex


def _sampler_exec():
    # one sampler among ops that read no key
    net = mx.sym.var("x") * 2.0 + mx.sym.random_uniform(shape=(4, 4))
    ex = net.simple_bind(mx.cpu(), x=(4, 4))
    ex.arg_dict["x"][:] = 0.0
    return ex


def _chain():
    st = mx.random.get_state()
    return None if st["key"] is None else st["key"].tolist()


def _draws():
    from mxnet_tpu import telemetry as tm
    m = tm.get_metric("executor.rng.draws")
    return 0 if m is None else m.value


def _programs_launched(tmp, body):
    """Names of the jitted calls (``PjitFunction(<fn>)`` host events of
    the JAX profiler) made while ``body()`` runs."""
    import glob
    import os
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            end = -1
            for ev in sorted(line.events, key=lambda e: e.start_ns):
                # the call's own event nests a second of its name
                if ev.name.startswith("PjitFunction(") \
                        and ev.start_ns >= end:
                    names.append(ev.name)
                    end = ev.start_ns + ev.duration_ns
    return names


@pytest.mark.parametrize("is_train", [False, True],
                         ids=["infer", "train"])
def test_keyless_graph_draws_no_key(is_train, counting, tmp_path):
    """No op of the graph reads the key: N forwards of either mode
    leave ``mx.random``'s host chain where it was, count no draw, and a
    forward launches one program - its own (a drawn key is two more:
    ``_threefry_split`` and ``_unstack``)."""
    ex = _keyless_exec()
    assert not ex._reads_rng
    mx.random.seed(11)
    before, draws = _chain(), _draws()

    def forward():
        ex.forward(is_train=is_train)
        return ex.outputs[0].asnumpy()

    outs = [forward() for _ in range(4)]
    assert _chain() == before
    assert _draws() == draws
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
    launched = _programs_launched(tmp_path, forward)
    assert len(launched) == 1 and "fwd_" in launched[0], launched
    if is_train:                 # the gradient program takes that key too
        ex.backward([mx.nd.ones((3, 4))])
        assert _chain() == before


def test_keyless_graph_takes_the_program_a_drawn_key_compiled():
    """The constant key has a drawn key's shape, dtype and placement:
    the program compiled under one is the program called under the
    other (no second trace)."""
    import jax
    from mxnet_tpu import executor as _executor
    drawn, const = mx.random.next_key(), _executor._unread_key()
    assert (drawn.shape, drawn.dtype, drawn.committed) == \
        (const.shape, const.dtype, const.committed)
    ex = _keyless_exec()
    ex.forward()
    prog = ex._get_program("fwd_infer").__wrapped__
    misses = prog._cache_size()
    prog(ex._arg_vals(), ex._aux_vals(), drawn)
    assert prog._cache_size() == misses == 1


@pytest.mark.parametrize("make,is_train", [
    (_dropout_exec, True), (_dropout_exec, False), (_sampler_exec, False),
    (_sampler_exec, True)],
    ids=["dropout-train", "dropout-infer", "sampler-infer", "sampler-train"])
def test_graph_with_a_key_reader_draws_a_key_a_call(make, is_train,
                                                    counting, tmp_path):
    """``Dropout``, or one sampler among key-less ops: a fresh key
    every forward, training or not - two calls under one seed differ
    (where the mode uses the key) and the pair repeats under
    ``mx.random.seed``; the host chain advances by one split a call."""
    import jax
    ex = make()
    assert ex._reads_rng
    uses_key = is_train or make is _sampler_exec

    def forward():
        ex.forward(is_train=is_train)
        return ex.outputs[0].asnumpy()

    forward()                                   # compile
    runs = []
    for _ in range(2):
        mx.random.seed(5)
        start, draws = _chain(), _draws()
        runs.append([forward(), forward()])
        assert _draws() == draws + 2
        key = jax.numpy.asarray(np.asarray(start, np.uint32))
        for _i in range(2):
            key = jax.random.split(key)[0]
        assert _chain() == np.asarray(key).tolist()
    (a1, a2), (b1, b2) = runs
    np.testing.assert_array_equal(a1, b1)
    np.testing.assert_array_equal(a2, b2)
    assert np.array_equal(a1, a2) == (not uses_key)
    launched = _programs_launched(tmp_path, forward)
    assert len(launched) == 3, launched          # the split's two + its own
