"""Device context abstraction.

The reference models devices as ``Context(device_type, device_id)`` with
``mx.cpu()`` / ``mx.gpu(i)`` (reference: python/mxnet/context.py,
include/mxnet/base.h Context struct). Here a Context wraps a JAX device:
``mx.cpu()`` -> the host CPU backend, ``mx.tpu(i)`` -> TPU chip *i*.
``mx.gpu`` is kept as a compatibility alias for the accelerator so
reference scripts run unchanged on TPU.

Unlike the reference there is no stream/device-ordinal plumbing to do —
XLA owns placement — so a Context is a value object used for:
  * selecting where NDArray buffers live (``jax.device_put``),
  * the ``with ctx:`` current-context scope,
  * the ``group2ctx``/model-parallel mapping onto mesh axes (see
    mxnet_tpu/parallel/).
"""
from __future__ import annotations

import os
import threading

import jax

from .base import MXNetError
from .telemetry import core as _telemetry_core

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus"]

#: the fixed default location of the persistent XLA compile cache: the
#: path is part of the cache key, so it never comes from tempfile, a pid
#: or the clock
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _init_compilation_cache():
    """Point jax's persistent compilation cache at one fixed place.

    Runs once, at ``import mxnet_tpu``. ``JAX_COMPILATION_CACHE_DIR`` in
    the environment wins and nothing is set in code; otherwise the cache
    lives in ``<checkout>/.jax_cache`` so a warm restart of the same
    program skips its XLA compiles.
    """
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)


class Context:
    """Device context. reference: python/mxnet/context.py:15-120."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _local = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise ValueError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- JAX mapping ------------------------------------------------------
    def jax_device(self):
        """Resolve this context to a concrete jax.Device.

        Multi-process: a Context names a device of THIS process —
        ``jax.devices()`` would enumerate the whole job's devices and
        hand other processes' (non-addressable) ones to low ids."""
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _local_cpu_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        # "gpu" is a compat alias for the accelerator backend: on a TPU
        # machine it resolves to TPU chips so reference scripts using
        # mx.gpu(i) run unchanged. An accelerator context never degrades
        # to the CPU or to another chip: num_gpus() is the way to ask.
        devs = _accelerator_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: this host has {len(devs)} accelerator "
                f"device(s); jax.local_devices() found "
                f"{jax.local_devices()}")
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._local, "stack"):
            Context._local.stack = []
        Context._local.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._local.stack.pop()


def _local_cpu_devices():
    """THIS process's CPU devices. ``jax.local_devices()`` with no
    backend only enumerates the default backend, so on an accelerator
    machine the cpu devices must be asked for explicitly."""
    try:
        return jax.local_devices(backend="cpu")
    except RuntimeError:
        return jax.devices("cpu")


def _accelerator_devices():
    # this process's chips only (multi-process: remote chips are
    # non-addressable and must not be bind targets)
    devs = [d for d in jax.local_devices() if d.platform not in ("cpu",)]
    return devs


def current_context():
    stack = getattr(Context._local, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def cpu(device_id=0):
    """Return a CPU context. reference: python/mxnet/context.py cpu()."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator context (compat alias -> TPU on TPU hosts)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """TPU context — the native accelerator of this framework."""
    return Context("tpu", device_id)


def num_gpus():
    """Number of accelerator devices visible (compat helper)."""
    return len(_accelerator_devices())


_init_compilation_cache()
_telemetry_core.watch_compiles()
