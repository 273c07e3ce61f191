"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the driver's multi-chip dry-run environment: sharding/collective
tests exercise real SPMD partitioning over 8 XLA CPU devices (SURVEY.md §4:
"distributed tests = N local processes" -> here N virtual devices).
"""
import os
import tempfile

# Tests that deliberately crash executors/fit would otherwise drop
# flight-recorder crash reports into the working tree; tests asserting
# on dumps point the recorder at their own tmp_path via configure().
os.environ.setdefault(
    "MXNET_CRASH_DIR",
    os.path.join(tempfile.gettempdir(), f"mxnet_crash_{os.getpid()}"))

# Bind-time graph validation in warn mode across the whole suite: every
# executor the tier-1 tests bind runs the static-analysis passes for
# free (findings log as warnings, never raise). Tests that assert on
# validation behavior set the env/kwargs themselves.
os.environ.setdefault("MXNET_GRAPH_VALIDATE", "warn")

# Force, don't setdefault: the outer environment may carry JAX_PLATFORMS=tpu
# (or another accelerator), and the suite's numerics are written for f32 CPU
# execution on the virtual 8-device mesh.
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# The persistent compile cache is off for the suite (and, through the
# environment, for every worker process a test spawns): tier-1 neither
# pays the cache's file I/O nor writes into the checkout's .jax_cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

assert jax.devices()[0].platform == "cpu", (
    "test suite must run on the virtual CPU mesh, got "
    f"{jax.devices()[0].platform}")
assert jax.device_count() >= 8, "expected 8 virtual CPU devices"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    import random
    random.seed(0)          # augmenters draw from stdlib random
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    # process-wide program cache: cleared per test so compile/hit/miss
    # counter assertions stay deterministic regardless of test order
    # (tests exercising cross-bind reuse re-populate it themselves)
    mx.program_cache.clear()
    # ``fit(health=...)`` pins the training-health plane's arming
    # process-wide: a test file that armed it must not decide what the
    # next file on the same worker records (which files share a worker
    # changes whenever a test file is added)
    from mxnet_tpu.telemetry import health
    health.configure(armed=None)


@pytest.fixture(autouse=True)
def _kernel_tier_as_found():
    """``MXNET_KERNEL_TIER`` is put back behind a test that set it: the
    benchmark's own quick tests (``chipbench/tests``, which the
    architecture files' tests import) ``setdefault`` it to ``xla`` for
    the rest of the process, and whichever file shared their worker
    afterwards resolved no other tier (``tests/test_transformer.py``'s
    ring lowering: three failures in one whole run of PR 49, none in the
    file alone). A module's own fixture that sets the tier is set up
    before this one and so is what a test finds."""
    found = os.environ.get("MXNET_KERNEL_TIER")
    yield
    if os.environ.get("MXNET_KERNEL_TIER") != found:
        if found is None:
            del os.environ["MXNET_KERNEL_TIER"]
        else:
            os.environ["MXNET_KERNEL_TIER"] = found
        from mxnet_tpu import kernel_tier
        kernel_tier.clear()


@pytest.fixture
def counting():
    """Telemetry on for one test: the counters that count only while
    ``telemetry.enabled()`` (``io.load_batch.*``, ``executor.rng.draws``,
    ``executor.jit_cache.*``) move."""
    from mxnet_tpu import telemetry as tm
    tm.enable()
    yield
    tm.disable()


@pytest.fixture(scope="module")
def v5e_chip():
    """One described v5e device. The persistent compile cache cannot
    read such executables back without a chip, so it stays off
    (above). ``tests/test_chip_compile.py`` and
    ``test_chip_compile_programs.py`` compile for it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # nothing is attached, so several test workers may load libtpu at once
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def v5e(v5e_chip, monkeypatch):
    """The described chip, with Pallas steered off interpret mode: the
    program picks the mode from ``jax.default_backend()``, which is the
    CPU here."""
    from mxnet_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    return v5e_chip
