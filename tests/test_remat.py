"""Remat policy: residual shrink, numerics parity, cache keying,
donation, batch-bucket headroom.

All on the CPU mesh: ``remat.residual_bytes`` is a pure trace
(jax.make_jaxpr), so the memory gate is exact and backend-independent.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import program_cache, remat
from mxnet_tpu.models import resnet


@pytest.fixture(autouse=True)
def _clean_policy(monkeypatch):
    monkeypatch.delenv("MXNET_REMAT_POLICY", raising=False)
    remat.set_active(None)
    yield
    remat.set_active(None)


def test_policy_resolution(monkeypatch):
    assert remat.active() == "none"
    monkeypatch.setenv("MXNET_REMAT_POLICY", "dots")
    assert remat.active() == "dots"
    monkeypatch.setenv("MXNET_REMAT_POLICY", "garbage")
    assert remat.active() == "none"
    assert remat.set_active("all") == "all"
    monkeypatch.setenv("MXNET_REMAT_POLICY", "dots")
    assert remat.active() == "all"        # explicit override wins
    remat.set_active(None)
    assert remat.active() == "dots"
    with pytest.raises(ValueError):
        remat.resolve("sometimes")


RESNET_BATCH = 16


def _resnet_symbol(num_layers=20):
    return resnet.get_symbol(num_classes=10, num_layers=num_layers,
                             image_shape="3,32,32")


def _arm_resnet(policy, batch=RESNET_BATCH, num_layers=20):
    """Bind + arm the fused step WITHOUT running it: jit is lazy, and
    fused_memory_report is a pure trace — the memory-gate tests at the
    resnet20 bench point never pay a compile."""
    mx.random.seed(0)
    mod = mx.mod.Module(_resnet_symbol(num_layers), context=mx.cpu())
    mod.bind([("data", (batch, 3, 32, 32))],
             [("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    remat.set_active(policy)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    remat.set_active(None)
    assert mod._fused_armed
    assert mod._exec_group._remat_policy == (policy or "none")
    return mod


def _fit_resnet(policy, batches=4, batch=8, K=1, num_layers=8):
    """Short real training run (compiles) — the numerics-parity tests;
    resnet8/b8 keeps per-policy compile time inside the tier-1 budget
    while exercising the same BN/conv graph structure."""
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    imgs = rng.rand(batches * batch, 3, 32, 32).astype(np.float32)
    labels = (rng.rand(batches * batch) * 10).astype(np.float32)
    it = mx.io.NDArrayIter(imgs, labels, batch_size=batch)
    mod = mx.mod.Module(_resnet_symbol(num_layers), context=mx.cpu())
    mod.fit(it, num_epoch=1, initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            remat=policy, steps_per_dispatch=K)
    assert mod._fused_armed
    return mod


def test_residual_bytes_drop_on_resnet20():
    """The memory-accountant gate: peak live bytes between fwd and bwd
    measurably drop under the non-none policies (acceptance: remat=all
    reduces peak live bytes at the resnet20 bench point)."""
    reports = {}
    for policy in ("none", "dots", "all"):
        mod = _arm_resnet(policy)
        reports[policy] = mod._exec_group.fused_memory_report()
        program_cache.clear()
    r_none = reports["none"]["residual_bytes"]
    r_dots = reports["dots"]["residual_bytes"]
    r_all = reports["all"]["residual_bytes"]
    assert r_all < r_dots < r_none
    # `all` saves only the inputs: the drop is drastic, not marginal
    assert r_all < 0.1 * r_none
    assert reports["none"]["policy"] == "none"
    assert reports["all"]["policy"] == "all"


def test_headroom_admits_next_larger_bucket():
    """The freed residual bytes convert into batch: with a budget
    calibrated so `none` just fits the bench batch, the accountant
    admits the NEXT-LARGER bucket under a remat policy."""
    from mxnet_tpu.telemetry.memory import batch_headroom
    per_sample, fixed = {}, None
    for policy in ("none", "all"):
        mod = _arm_resnet(policy)
        rep = mod._exec_group.fused_memory_report()
        per_sample[policy] = (rep["residual_bytes"]
                              + rep["batch_bytes"]) / RESNET_BATCH
        fixed = rep["param_bytes"] + rep["state_bytes"]
        program_cache.clear()
    buckets = (RESNET_BATCH, 2 * RESNET_BATCH, 4 * RESNET_BATCH)
    budget = fixed + per_sample["none"] * RESNET_BATCH
    assert batch_headroom(budget, fixed, per_sample["none"],
                          buckets) == RESNET_BATCH
    assert batch_headroom(budget, fixed, per_sample["all"],
                          buckets) > RESNET_BATCH
    assert batch_headroom(0, fixed, per_sample["all"], buckets) is None


def _params(mod):
    ap, xp = mod.get_params()
    out = {k: v.asnumpy() for k, v in ap.items()}
    out.update({f"aux:{k}": v.asnumpy() for k, v in xp.items()})
    return out


def _assert_params_close(ref, got, tol, what):
    """Every array of ``got`` within ``tol`` of ``ref``, relative to
    that array's largest magnitude (the betas start at zero and hold
    nothing but summed gradients, so an element-wise relative error is
    unbounded near their zeros)."""
    for k, v in ref.items():
        np.testing.assert_allclose(
            got[k], v, rtol=0, atol=tol * np.abs(v).max(),
            err_msg=f"{what} diverged at {k}")


def test_fit_bit_identical_across_policies():
    """Remat recomputes the same ops, so four steps of resnet8 end at
    the same parameters and aux states under every policy (donation of
    rng/aux changes nothing) - to float32 rounding, not to the bit: XLA
    fuses the recomputed forward differently from the saved one, the
    sums round differently and BatchNorm's 1/sqrt(var) carries that
    into the next step. Read on jax 0.9.0 (CPU), per array and relative
    to its largest magnitude: ``dots`` 2.1e-5 (bn_data_beta), ``all``
    8.5e-4 (stage2_unit1_bn2_beta; 9.5e-5 on the worst weight). The
    bound is 4x the worst reading; a policy that dropped or changed an
    op is off by orders of magnitude more. What does hold to the bit
    is one policy run twice from one seed."""
    digests = {}
    for policy in ("none", "dots", "all"):
        digests[policy] = _params(_fit_resnet(policy))
        program_cache.clear()
    again = _params(_fit_resnet("dots"))
    program_cache.clear()
    for k, v in digests["dots"].items():
        np.testing.assert_array_equal(
            v, again[k], err_msg=f"dots twice diverged at {k}")
    for policy in ("dots", "all"):
        _assert_params_close(digests["none"], digests[policy], 3.5e-3,
                             policy)


def test_scan_window_bit_identical_under_remat():
    """K-step scan inherits the policy through step_core: K=4 windows
    under remat=all match K=4 under none (same dispatch shape —
    scan-vs-single is a separate, policy-independent program and XLA's
    float scheduling differs between them) to float32 rounding, for
    the reason given above: 2.2e-5 of an array's largest magnitude at
    worst on jax 0.9.0 (bn_data_beta), bound at 4x that; and to the
    bit when ``all`` runs twice."""
    ref = _fit_resnet("none", batches=4, K=4)
    assert ref._exec_group._scan_K == 4
    p_ref = _params(ref)
    program_cache.clear()
    got = _fit_resnet("all", batches=4, K=4)
    assert got._exec_group._scan_K == 4
    p_got = _params(got)
    program_cache.clear()
    p_again = _params(_fit_resnet("all", batches=4, K=4))
    for k, v in p_got.items():
        np.testing.assert_array_equal(
            v, p_again[k], err_msg=f"all twice diverged at {k}")
    _assert_params_close(p_ref, p_got, 1e-4, "all")


def test_policy_keys_program_cache():
    """A fused program traced under one policy is never reused under
    another: the cache keys differ in the remat token."""
    mod_a = _arm_resnet("none")
    key_a = mod_a._exec_group._fused_cache_key
    program_cache.clear()
    mod_b = _arm_resnet("all")
    key_b = mod_b._exec_group._fused_cache_key
    assert key_a is not None and key_b is not None
    assert key_a != key_b
    assert ("remat", "none") in key_a
    assert ("remat", "all") in key_b


def test_donation_set_per_policy():
    """none keeps the pre-knob donation (params, states); a policy adds
    the rng chain and — resnet's BN refreshes every aux — the aux
    buffers."""
    mod = _arm_resnet("none")
    assert mod._exec_group._fused_donate == (0, 4)
    program_cache.clear()
    mod = _arm_resnet("dots")
    assert mod._exec_group._fused_donate == (0, 2, 3, 4)


def test_env_policy_drives_fit(monkeypatch):
    """MXNET_REMAT_POLICY alone (no kwarg) arms the policy."""
    monkeypatch.setenv("MXNET_REMAT_POLICY", "all")
    mod = _fit_resnet(None, batches=2)
    assert mod._exec_group._remat_policy == "all"
    rep = mod._exec_group.fused_memory_report()
    assert rep["policy"] == "all"


def test_eval_after_remat_step_reads_fresh_aux():
    """Aux donation must not break the eval path: score() right after
    remat-policy training reads valid (fresh) aux buffers."""
    mod = _fit_resnet("all", batches=2)
    rng = np.random.RandomState(1)
    imgs = rng.rand(8, 3, 32, 32).astype(np.float32)
    labels = (rng.rand(8) * 10).astype(np.float32)
    it = mx.io.NDArrayIter(imgs, labels, batch_size=8)
    res = mod.score(it, "acc")
    assert 0.0 <= dict(res)["accuracy"] <= 1.0
