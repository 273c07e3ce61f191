"""``chip_smoke.py`` without a chip, kept as tests:

* ``python chip_smoke.py`` itself failing at the device check on a host
  with no TPU;
* its phase bodies at tiny sizes on ``mx.cpu()`` - arguments and control
  flow;
* what the bring-up removed: an accelerator context that silently became
  the CPU or another chip, an autotune that swallowed compiler refusals,
  a compile cache with two spellings.

The kernels compiled for a described v5e are
``tests/test_chip_compile.py``'s (a file of its own so that two workers
take them: ROADMAP D22).
"""
import argparse
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.ops import pallas_kernels
from mxnet_tpu.ops.registry import get_op

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SITES = chip_smoke.kernel_sites(chip_smoke.SMOKE_WIDTHS)


# ------------------------------------------- chip_smoke.py without a chip
def test_chip_smoke_fails_at_device_check_without_a_chip():
    res = subprocess.run([sys.executable, os.path.join(ROOT,
                                                       "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         cwd=ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""           # no result line of any kind


_TINY_WIDTHS = dict(batch=8, classes=10, conv=(2, 4, 8, 8), fc=(50, 33),
                    slots=2, window=4, vocab=64, d_model=32, n_head=4,
                    capacity=16, lm_batch=1, lm_seq=16)


def _phase_line(capsys, phase):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return [l for l in lines if l["phase"] == phase][-1]


def test_kernels_phase_body_tiny(capsys):
    """Every registered Pallas variant has a site, and each passes the
    numerics gate (interpret mode here)."""
    chip_smoke.kernels_phase(chip_smoke.kernel_sites(_TINY_WIDTHS),
                             require_mosaic=False)
    line = _phase_line(capsys, "kernels")
    assert line["ok"] and not line["compiled"]
    assert len(line["kernels"]) == len(_SITES)


def test_kernels_phase_demands_mosaic_on_the_chip():
    with pytest.raises(SystemExit, match="interpret mode"):
        chip_smoke.kernels_phase([], require_mosaic=True)


def _tiny_fit_args(extra=()):
    _, fit = chip_smoke._imagenet_example()
    parser = fit.add_fit_args(argparse.ArgumentParser())
    return parser.parse_args(["--batch-size", "8", "--num-epochs", "1",
                              "--lr", "0.05", *extra])


def _tiny_train_setup(extra=()):
    from mxnet_tpu.models import mlp
    rs = np.random.RandomState(0)
    x = rs.rand(32, 16).astype("f")
    y = rs.randint(0, 4, 32).astype("f")
    iters = (mx.io.NDArrayIter(x, y, 8), mx.io.NDArrayIter(x[:8], y[:8], 8))
    return _tiny_fit_args(extra), mlp.get_symbol(num_classes=4), iters


def test_train_phase_body_tiny(capsys):
    mod, watch = chip_smoke.train_phase(
        *_tiny_train_setup(["--dtype", "bfloat16"]),
        devices=[mx.cpu().jax_device()], seed=0)
    line = _phase_line(capsys, "train")
    assert line["ok"] and line["steps"] == 4 == len(watch.losses)
    assert mod._compute_dtype == "bfloat16"          # --dtype is wired
    # the placement check is real: buffers on cpu(0) are not on cpu(1)
    checked, stray = chip_smoke.stray_buffers(mod, [mx.cpu(1).jax_device()])
    assert checked == line["buffers_checked"] and len(stray) == checked


def test_serve_phase_body_tiny(capsys):
    answers = chip_smoke.serve_phase(
        dict(vocab_size=64, d_model=32, n_layer=1, n_head=4), capacity=32,
        ladder=[2], prompt_lens=(3, 12), n_requests=3, max_new=5,
        context=mx.cpu(), compute_dtype=None, seed=0, prefill_chunk=4)
    line = _phase_line(capsys, "serve")
    assert line["ok"] and line["compiles_since_warmup"] == 0
    assert line["agreed_tokens"] == [5, 5, 5] and line["windows"] == [4]
    assert [len(a) for a in answers] == [5, 5, 5]


def test_layer_pair_phase_body_tiny(capsys, monkeypatch):
    """The smoke's layer pair (ISSUE 54: a mamba layer and the attention
    layer with routed experts, half held) at tiny widths on the CPU,
    float32: a packed window with riders, then the S = 1 step they are
    held to."""
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    model = dict(vocab_size=64, d_model=32, n_layer=2, n_head=4,
                 granite=dict(chip_smoke.GRANITE_SMALL_PAIR["granite"],
                              num_key_value_heads=1, mamba_n_heads=8,
                              mamba_d_head=8, mamba_d_state=16,
                              mamba_chunk_size=8,
                              shared_intermediate_size=24,
                              num_local_experts=8, num_experts_per_tok=3,
                              intermediate_size=16, held=(0, 4)))
    try:
        chip_smoke.layer_pair_phase(model, slots=4, window=16, capacity=64,
                                    context=mx.cpu(), compute_dtype=None,
                                    seed=0)
    finally:
        kernel_tier.clear()
    line = _phase_line(capsys, "pair")
    assert line["ok"] and line["packed_rows"] == 24
    assert (line["prefill_rows"], line["riders"]) == (16, 3)
    assert line["assignments"] == 2 * 3 * 19
    assert 0 < line["held_assignments"] < line["assignments"]
    assert line["rider_vs_step_max_abs_err"] <= line["tolerance"]
    pair = chip_smoke.GRANITE_SMALL_PAIR
    assert (pair["d_model"], pair["granite"]["mamba_n_heads"],
            pair["granite"]["num_local_experts"],
            pair["granite"]["held"]) == (4096, 128, 72, (0, 36))


def test_multichip_phase_body_on_virtual_devices(capsys, monkeypatch):
    """The --multichip body over four virtual CPU devices (``mx.gpu`` is
    steered to them: this host has no accelerator to name)."""
    monkeypatch.setattr(mx.context, "_accelerator_devices",
                        mx.context._local_cpu_devices)

    def build(gpus):
        return _tiny_train_setup(["--gpus", gpus])

    chip_smoke.multichip_phase(build, "0,1,2,3", seed=0, rtol=1e-4)
    line = _phase_line(capsys, "multichip")
    assert line["ok"] and line["chips"] == 4 and line["all_reduce_in_hlo"]
    assert line["data_shard_shapes"] == ["(2, 16)"]


# ------------------------------------------------ the fallbacks are gone
def test_accelerator_context_raises_without_an_accelerator():
    assert mx.num_gpus() == 0
    for ctx in (mx.tpu(0), mx.gpu(0)):
        with pytest.raises(mx.base.MXNetError, match="0 accelerator"):
            ctx.jax_device()


def test_accelerator_context_raises_beyond_the_last_chip(monkeypatch):
    monkeypatch.setattr(mx.context, "_accelerator_devices",
                        lambda: mx.context._local_cpu_devices()[:2])
    assert mx.tpu(1).jax_device() == mx.context._local_cpu_devices()[1]
    with pytest.raises(mx.base.MXNetError, match="2 accelerator"):
        mx.tpu(9).jax_device()


def test_autotune_raises_what_the_compiler_refuses(monkeypatch):
    """A lowering error is a defect of a registered variant, not an
    ``xla`` outcome: it raises with op, shapes and dtypes."""
    def refuse(*_a, **_k):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: erf")
    monkeypatch.setattr(kernel_tier, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_tier, "_device_kind", lambda: "TPU test")
    monkeypatch.setattr(kernel_tier, "numerics_gate", refuse)
    kernel_tier.clear()
    bg = get_op("FusedBiasGeLU")
    with pytest.raises(mx.base.MXNetError) as exc:
        kernel_tier.resolve(bg, {}, [(16, 64), (64,)],
                            ["float32", "float32"], True)
    msg = str(exc.value)
    assert "FusedBiasGeLU" in msg and "[16, 64]" in msg
    assert "float32" in msg and "erf" in msg
    kernel_tier.clear()


def test_autotune_measures_inside_an_enclosing_trace(monkeypatch):
    """``resolve`` runs while the enclosing program is being traced; the
    measurement must still see concrete arrays (jax 0.9 stages every op
    issued under a trace)."""
    monkeypatch.setattr(kernel_tier, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_tier, "_device_kind", lambda: "TPU test")
    kernel_tier.clear()
    sm = get_op("SoftmaxOutput")
    attrs = sm.normalize_attrs({})

    @jax.jit
    def program(x, label):
        return kernel_tier.dispatch(sm, attrs, [x, label], [], True,
                                    None)[0][0]

    program(jnp.ones((8, 10)), jnp.zeros((8,)))
    dec = kernel_tier.decisions()[-1]
    assert dec["source"] == "autotune" and "xla_ms" in dec, dec
    kernel_tier.clear()


def test_kernel_erf_matches_lax_erf():
    x = jnp.asarray(np.linspace(-6, 6, 20001).astype("f"))
    err = jnp.max(jnp.abs(pallas_kernels._erf32(x) - jax.lax.erf(x)))
    assert float(err) < 1e-6


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_has_one_place(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset: the
    cache is <checkout>/.jax_cache."""
    from mxnet_tpu import context
    saved = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        context._init_compilation_cache()
        want = "untouched" if env_dir else os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_mesh_binding_keeps_mosaic_kernels_out(monkeypatch):
    """XLA cannot partition a Mosaic kernel: over more than one device a
    TPU backend resolves the composition, and says so."""
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    kernel_tier.clear()
    sm = get_op("SoftmaxOutput")
    site = (sm, sm.normalize_attrs({}), [(8, 10), (8,)],
            ["float32", "float32"], True)
    assert kernel_tier.resolve(*site) == "pallas"
    assert kernel_tier.resolve(*site, n_devices=4) == "xla"
    dec = kernel_tier.decisions()[-1]
    assert dec["source"] == "mesh" and "4 devices" in dec["reason"]
    kernel_tier.clear()
