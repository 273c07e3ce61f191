"""NDArray: the imperative tensor of the framework.

The reference NDArray (reference: include/mxnet/ndarray.h:58-400,
src/ndarray/ndarray.cc) is a ref-counted handle over device storage whose
every mutation is pushed to the dependency engine with the handle's ``var()``
as a write dependency; ``WaitToRead``/``asnumpy`` are the sync points.

TPU-native design: an NDArray is a *mutable cell holding an immutable
jax.Array*. JAX's async dispatch IS the dependency engine — ops return
futures immediately and XLA orders them by data dependence, so there is no
Var/Opr machinery to rebuild (SURVEY.md §7 design mapping). Mutation
(``+=``, slice assignment, optimizer updates) is realized by computing a new
immutable array and swapping it into the cell, which keeps every Python alias
coherent — the exact property the executor's arg_dict aliasing relies on
(reference: python/mxnet/module/executor_group.py:233-268).

Sync points: ``asnumpy()``/``wait_to_read()`` -> ``block_until_ready`` —
matching MXNet's "async everywhere, sync on read" contract.
"""
from __future__ import annotations

import struct

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import Context, current_context
from .ops.registry import OP_REGISTRY, get_op
from . import random as _random
from .telemetry import memory as _memory

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "concatenate", "load", "save", "waitall", "imperative_invoke",
           "add", "subtract", "multiply", "divide", "true_divide",
           "power", "maximum", "minimum", "equal", "not_equal", "greater",
           "greater_equal", "lesser", "lesser_equal", "moveaxis",
           "onehot_encode", "imdecode"]

# Registry op functions (slice, abs, sum, ...) are injected into this module
# at package init (_op_gen), shadowing python builtins of the same name —
# capture the builtins first.
_py_slice, _py_abs, _py_sum, _py_max, _py_min = slice, abs, sum, max, min


class NDArray:
    """Mutable handle over an immutable jax.Array."""

    __slots__ = ("_data", "_ctx", "writable", "_acct")

    def __init__(self, data, ctx=None, writable=True):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            if ctx is not None:
                # host data goes straight to the target device — going
                # through jnp.asarray first would land it on the DEFAULT
                # device and turn this into a cross-device round-trip
                data = jax.device_put(np.asarray(data), ctx.jax_device())
            else:
                data = jnp.asarray(data)
        elif ctx is not None and not _placement_matches(data, ctx):
            # move only across platforms; within a platform keep the
            # array's existing (possibly mesh-sharded) placement — a
            # Context names the logical home, not a single shard
            data = jax.device_put(data, ctx.jax_device())
        self._data = data
        self._ctx = ctx if ctx is not None else _infer_ctx(data)
        self.writable = writable
        _memory.on_alloc(self)   # per-context live/peak byte accounting

    # ------------------------------------------------------------------ core
    def asjax(self):
        """The underlying immutable jax.Array."""
        return self._data

    def _set(self, new_data):
        """Swap in a new buffer (the mutation primitive)."""
        if not self.writable:
            raise MXNetError("trying to write to a read-only NDArray")
        self._data = new_data
        _memory.on_swap(self)    # re-account only when the size changed

    def __del__(self):
        try:
            _memory.on_free(self._acct)
        except Exception:
            pass                 # interpreter shutdown / half-built handle

    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(np.prod(self.shape, dtype=np.int64)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self):
        return self._ctx

    ctx = context

    def asnumpy(self):
        """Copy to host numpy — THE sync point (block_until_ready)."""
        return np.asarray(jax.device_get(self._data))

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def wait_to_read(self):
        self._data.block_until_ready()

    wait_to_write = wait_to_read

    def copyto(self, other):
        """Copy into another NDArray or Context.

        reference: ndarray.cc CopyFromTo 4-way device dispatch; here
        jax.device_put covers every direction (host<->TPU, TPU<->TPU).
        """
        if isinstance(other, NDArray):
            if other.shape != self.shape:
                raise ValueError(
                    f"copyto shape mismatch {self.shape} vs {other.shape}")
            # land in the destination's existing placement (preserves
            # mesh shardings; moves across platforms when needed)
            other._set(jax.device_put(
                self._data.astype(other.dtype), other._data.sharding))
            return other
        if isinstance(other, Context):
            return NDArray(self._data, ctx=other)
        raise TypeError(f"copyto does not support type {type(other)}")

    def as_in_context(self, ctx):
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def copy(self):
        return NDArray(self._data, ctx=self._ctx)

    def astype(self, dtype):
        return NDArray(self._data.astype(np.dtype(dtype)), ctx=self._ctx)

    def reshape(self, shape, **kwargs):
        if isinstance(shape, int):
            shape = (shape,)
        if kwargs.get("reverse"):
            raise NotImplementedError("reshape(reverse=True)")
        shape = tuple(int(s) for s in shape)
        # -1 / 0 special values per reference Reshape semantics
        shape = _resolve_reshape(self.shape, shape)
        return NDArray(jnp.reshape(self._data, shape), ctx=self._ctx)

    @property
    def T(self):
        return NDArray(self._data.T, ctx=self._ctx)

    # --------------------------------------------------------------- getters
    def __getitem__(self, key):
        out = self._data[key]
        return NDArray(out, ctx=self._ctx)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, _py_slice) and key == _py_slice(None):
            new = jnp.broadcast_to(jnp.asarray(value, dtype=self.dtype),
                                   self.shape).astype(self.dtype)
        else:
            new = self._data.at[key].set(
                value if not np.isscalar(value) else value)
        self._set(new.astype(self.dtype))

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return bool(self.asscalar())

    def __repr__(self):
        return (f"{self.asnumpy()!r}\n<NDArray {'x'.join(map(str, self.shape))}"
                f" @{self._ctx}>")

    # ----------------------------------------------------------- arithmetic
    def _binary(self, other, fn, rfn=None):
        if isinstance(other, NDArray):
            out = NDArray(fn(self._data, other._data), ctx=self._ctx)
            _maybe_tape(fn, [self, other], out)
            return out
        if isinstance(other, (int, float, np.generic)):
            out = NDArray(fn(self._data, other), ctx=self._ctx)
            _maybe_tape(lambda a, _o=other: fn(a, _o), [self], out)
            return out
        return NotImplemented

    def __add__(self, o): return self._binary(o, jnp.add)
    __radd__ = __add__
    def __sub__(self, o): return self._binary(o, jnp.subtract)
    def __rsub__(self, o): return self._binary(o, lambda a, b: jnp.subtract(b, a))
    def __mul__(self, o): return self._binary(o, jnp.multiply)
    __rmul__ = __mul__
    def __truediv__(self, o): return self._binary(o, jnp.divide)
    def __rtruediv__(self, o): return self._binary(o, lambda a, b: jnp.divide(b, a))
    __div__, __rdiv__ = __truediv__, __rtruediv__
    def __mod__(self, o): return self._binary(o, jnp.mod)
    def __pow__(self, o): return self._binary(o, jnp.power)
    def __rpow__(self, o): return self._binary(o, lambda a, b: jnp.power(b, a))
    def __neg__(self): return NDArray(-self._data, ctx=self._ctx)
    def __abs__(self): return NDArray(jnp.abs(self._data), ctx=self._ctx)

    def __iadd__(self, o):
        self._set((self + o)._data)
        return self

    def __isub__(self, o):
        self._set((self - o)._data)
        return self

    def __imul__(self, o):
        self._set((self * o)._data)
        return self

    def __itruediv__(self, o):
        self._set((self / o)._data)
        return self

    def __eq__(self, o): return self._binary(o, lambda a, b: (a == b).astype(a.dtype))
    def __ne__(self, o): return self._binary(o, lambda a, b: (a != b).astype(a.dtype))
    def __gt__(self, o): return self._binary(o, lambda a, b: (a > b).astype(a.dtype))
    def __ge__(self, o): return self._binary(o, lambda a, b: (a >= b).astype(a.dtype))
    def __lt__(self, o): return self._binary(o, lambda a, b: (a < b).astype(a.dtype))
    def __le__(self, o): return self._binary(o, lambda a, b: (a <= b).astype(a.dtype))
    __hash__ = object.__hash__


def _maybe_tape(fn, input_handles, out_handle):
    """Record an NDArray operator on the autograd tape while training."""
    from . import autograd as _ag
    if not _ag._STATE["train"]:
        return
    _ag._record_fn(lambda vals: [fn(*vals)], input_handles,
                   [h.asjax() for h in input_handles], [out_handle])


def _placement_matches(data, ctx):
    try:
        plat = next(iter(data.devices())).platform
    except Exception:
        return False
    want_cpu = ctx.device_type in ("cpu", "cpu_pinned")
    return (plat == "cpu") == want_cpu


def _infer_ctx(data):
    try:
        dev = list(data.devices())[0]
    except Exception:
        return current_context()
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", dev.id)


def _resolve_reshape(old, new):
    out = []
    for i, s in enumerate(new):
        if s == 0:
            out.append(old[i])
        else:
            out.append(s)
    if -1 in out:
        known = int(np.prod([s for s in out if s != -1], dtype=np.int64))
        total = int(np.prod(old, dtype=np.int64))
        out[out.index(-1)] = total // _py_max(known, 1)
    return tuple(out)


# ---------------------------------------------------------------- factories
def _default_dtype(dtype):
    return np.dtype(dtype if dtype is not None else np.float32)


def array(source_array, ctx=None, dtype=None):
    """Create an NDArray from any array-like. reference: ndarray.py array()."""
    if isinstance(source_array, NDArray):
        src = source_array.asjax()
        if dtype is not None:
            src = src.astype(np.dtype(dtype))
        return NDArray(src, ctx=ctx or source_array.context)
    arr = np.asarray(source_array)
    if dtype is None:
        dtype = arr.dtype if arr.dtype != np.float64 else np.float32
    return NDArray(arr.astype(np.dtype(dtype), copy=False),
                   ctx=ctx or current_context())


def zeros(shape, ctx=None, dtype=None):
    return NDArray(jnp.zeros(shape, _default_dtype(dtype)),
                   ctx=ctx or current_context())


def ones(shape, ctx=None, dtype=None):
    return NDArray(jnp.ones(shape, _default_dtype(dtype)),
                   ctx=ctx or current_context())


def full(shape, val, ctx=None, dtype=None):
    return NDArray(jnp.full(shape, val, _default_dtype(dtype)),
                   ctx=ctx or current_context())


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    arr = jnp.arange(start, stop, step, _default_dtype(dtype))
    if repeat > 1:
        arr = jnp.repeat(arr, repeat)
    return NDArray(arr, ctx=ctx or current_context())


def concatenate(arrays, axis=0, always_copy=True):
    if not arrays:
        raise ValueError("need at least one array")
    if len(arrays) == 1 and not always_copy:
        return arrays[0]
    return NDArray(jnp.concatenate([a.asjax() for a in arrays], axis=axis),
                   ctx=arrays[0].context)


# ------------------------------------------------------------- save / load
# Byte-compatible with the reference's .params container so checkpoints are
# interchangeable (reference: ndarray.cc:605-695 NDArray::Save/Load over
# dmlc::Stream; c_api.h:272-299). Layout, little-endian:
#   uint64 magic=0x112, uint64 reserved=0
#   uint64 narr; per array:
#     uint32 ndim, uint32[ndim] shape          (mshadow TShape::Save)
#     [if ndim>0] int32 dev_type, int32 dev_id (Context::Save)
#                 int32 type_flag, raw bytes   (mshadow type codes)
#   uint64 nkeys; per key: uint64 len, bytes
_MAGIC = 0x112
# mshadow type flags (mshadow/base.h): kFloat32..kInt64
_DTYPE_CODE = {np.dtype(d): i for i, d in enumerate(
    ["float32", "float64", "float16", "uint8", "int32", "int8", "int64"])}
# extension codes for the fp8 storage dtypes, parked far outside the
# reference range (0-6 here, <=12 in later mshadow revisions): a file
# carrying fp8 cells has no reference-framework reading anyway, while
# files restricted to the standard dtypes stay byte-for-byte compatible
try:
    _DTYPE_CODE[np.dtype("float8_e4m3fn")] = 100
    _DTYPE_CODE[np.dtype("float8_e5m2")] = 101
except TypeError:       # numpy without ml_dtypes registration
    pass
_CODE_DTYPE = {v: k for k, v in _DTYPE_CODE.items()}


def save(fname, data):
    """Save a list or str->NDArray dict. reference: mx.nd.save.

    The on-disk container matches the reference's dmlc::Stream format
    byte-for-byte for the standard dtypes, so ``prefix-XXXX.params``
    checkpoints round-trip between the two frameworks. bfloat16 arrays are
    widened to float32 on save (the 2017 format predates bf16).
    """
    if isinstance(data, dict):
        names, arrays = list(data.keys()), list(data.values())
    elif isinstance(data, (list, tuple)):
        names, arrays = [], list(data)
    elif isinstance(data, NDArray):
        names, arrays = [], [data]
    else:
        raise TypeError("save requires dict/list/NDArray")
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQQ", _MAGIC, 0, len(arrays)))
        for arr in arrays:
            np_arr = arr.asnumpy() if isinstance(arr, NDArray) \
                else np.asarray(arr)
            dt = np.dtype(np_arr.dtype)
            if dt not in _DTYPE_CODE:
                np_arr = np_arr.astype(np.float32)
                dt = np.dtype(np.float32)
            f.write(struct.pack("<I", np_arr.ndim))
            f.write(struct.pack(f"<{np_arr.ndim}I", *np_arr.shape))
            f.write(struct.pack("<ii", 1, 0))  # Context: cpu(0)
            f.write(struct.pack("<i", _DTYPE_CODE[dt]))
            f.write(np.ascontiguousarray(np_arr).tobytes())
        f.write(struct.pack("<Q", len(names)))
        for name in names:
            b = name.encode()
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def load(fname):
    """Load NDArrays saved by :func:`save` or by the reference's mx.nd.save."""
    with open(fname, "rb") as f:
        magic, _reserved, n_arr = struct.unpack("<QQQ", f.read(24))
        if magic != _MAGIC:
            raise MXNetError(f"invalid NDArray file {fname}")
        arrays = []
        for _ in range(n_arr):
            ndim, = struct.unpack("<I", f.read(4))
            shape = struct.unpack(f"<{ndim}I", f.read(4 * ndim))
            if ndim == 0:  # is_none() array: shape only
                arrays.append(array(np.zeros((0,), np.float32)))
                continue
            struct.unpack("<ii", f.read(8))  # Context (ignored)
            dcode, = struct.unpack("<i", f.read(4))
            dt = _CODE_DTYPE[dcode]
            count = int(np.prod(shape, dtype=np.int64))
            buf = f.read(count * dt.itemsize)
            arrays.append(array(np.frombuffer(buf, dtype=dt).reshape(shape)))
        n_names, = struct.unpack("<Q", f.read(8))
        names = []
        for _ in range(n_names):
            ln, = struct.unpack("<Q", f.read(8))
            names.append(f.read(ln).decode())
    if names:
        return dict(zip(names, arrays))
    return arrays


def waitall():
    """Block until all async work is done. reference: MXNDArrayWaitAll."""
    (jax.device_put(0.0) + 0).block_until_ready()


# ------------------------------------------------------ imperative dispatch
def imperative_invoke(op_name, *inputs, out=None, **kwargs):
    """Run a registered op eagerly on NDArrays.

    The analog of MXImperativeInvoke (reference: c_api_ndarray.cc:322-420):
    resolve op -> normalize attrs -> run the JAX kernel (async) -> wrap/swap
    outputs. Ops that declare ``mutate_inputs`` (optimizer updates) have the
    new buffers swapped into the corresponding input handles.
    """
    opdef = get_op(op_name)
    attrs = opdef.normalize_attrs(kwargs)
    in_names = opdef.input_names(attrs)
    aux_n = len(opdef.aux_names(attrs))
    arrs = [x.asjax() if isinstance(x, NDArray) else jnp.asarray(x)
            for x in inputs]
    regular, aux = (arrs[:len(arrs) - aux_n], arrs[len(arrs) - aux_n:]) \
        if aux_n else (arrs, [])
    rng = _random.next_key() if opdef.need_rng else None
    from . import kernel_tier as _kernel_tier
    outputs, new_aux = _kernel_tier.dispatch(opdef, attrs, regular, aux,
                                             False, rng)
    ctx = inputs[0].context if inputs and isinstance(inputs[0], NDArray) \
        else current_context()
    # mutate-input ops (sgd_update etc.): swap new buffer into input handle
    if opdef.mutate_inputs:
        for mname, new_val in zip(opdef.mutate_inputs, outputs):
            idx = in_names.index(mname)
            if idx < len(inputs) and isinstance(inputs[idx], NDArray):
                inputs[idx]._set(new_val)
    if aux_n:
        for handle, new_val in zip(inputs[len(arrs) - aux_n:], new_aux):
            if isinstance(handle, NDArray):
                handle._set(new_val)
    results = [NDArray(o, ctx=ctx) for o in outputs]
    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(outs, results):
            dst._set(src.asjax())
        results = list(outs)
    # autograd tape (reference: RecordImperativeFCompute, autograd.cc:70)
    from . import autograd as _ag
    _ag._record(opdef, attrs, list(inputs), arrs, results, rng)
    if out is not None:
        return out
    if len(results) == 1:
        return results[0]
    return results


# ---------------------------------------------------------------------
# module-level arithmetic/comparison helpers (reference: ndarray.py's
# add/maximum/... — scalar-or-array dispatch over the broadcast ops)
def _binary_fn(jnp_op, name):
    def fn(lhs, rhs):
        a = lhs.asjax() if isinstance(lhs, NDArray) else lhs
        b = rhs.asjax() if isinstance(rhs, NDArray) else rhs
        ctx = lhs.context if isinstance(lhs, NDArray) else \
            rhs.context if isinstance(rhs, NDArray) else None
        out = jnp_op(a, b)
        if out.dtype == jnp.bool_:        # reference comparisons return
            out = out.astype(jnp.float32)  # 0/1 floats, not bools
        return NDArray(out, ctx=ctx)
    fn.__name__ = name
    fn.__doc__ = (f"Element-wise broadcasting ``{name}`` of scalar/array "
                  "operands (reference: ndarray.py module helpers).")
    return fn


add = _binary_fn(jnp.add, "add")
subtract = _binary_fn(jnp.subtract, "subtract")
multiply = _binary_fn(jnp.multiply, "multiply")
divide = _binary_fn(jnp.divide, "divide")
true_divide = _binary_fn(jnp.true_divide, "true_divide")
power = _binary_fn(jnp.power, "power")
maximum = _binary_fn(jnp.maximum, "maximum")
minimum = _binary_fn(jnp.minimum, "minimum")
equal = _binary_fn(jnp.equal, "equal")
not_equal = _binary_fn(jnp.not_equal, "not_equal")
greater = _binary_fn(jnp.greater, "greater")
greater_equal = _binary_fn(jnp.greater_equal, "greater_equal")
lesser = _binary_fn(jnp.less, "lesser")
lesser_equal = _binary_fn(jnp.less_equal, "lesser_equal")


def moveaxis(tensor, source, destination):
    """Move ``source`` axis to ``destination`` (reference: ndarray.py
    moveaxis)."""
    return NDArray(jnp.moveaxis(tensor.asjax(), source, destination),
                   ctx=tensor.context)


def onehot_encode(indices, out):
    """One-hot encode indices into ``out`` (reference: ndarray.py
    onehot_encode -> _internal._onehot_encode; depth = out.shape[1])."""
    depth = out.shape[1]
    idx = indices.asjax().astype(jnp.int32).ravel()
    out._set(jax.nn.one_hot(idx, depth, dtype=out.dtype))
    return out


def imdecode(str_img, clip_rect=(0, 0, 0, 0), out=None, index=0,
             channels=3, mean=None):
    """Decode an image bytestring to a (H, W, C) float NDArray
    (reference: ndarray.py imdecode over the opencv plugin). With a
    batched ``out`` (N, H, W, C), writes slot ``index``."""
    from .image import _imdecode_np          # cv2-or-PIL, raises MXNetError
    img = _imdecode_np(np.frombuffer(str_img, dtype=np.uint8),
                       to_rgb=channels == 3)
    if channels == 1 and img.ndim == 3:
        img = img.mean(axis=2, keepdims=True)
    elif img.ndim == 2:
        img = img[:, :, None]
    x0, y0, x1, y1 = clip_rect
    if x1 > 0 and y1 > 0:
        img = img[y0:y1, x0:x1]
    img = img.astype(np.float32)
    if mean is not None:
        img = img - (mean.asnumpy() if isinstance(mean, NDArray)
                     else np.asarray(mean, np.float32))
    if out is not None:
        if out.ndim == img.ndim + 1:         # batched buffer: one slot
            out[index] = img
        else:
            out._set(jnp.asarray(img.reshape(out.shape), dtype=out.dtype))
        return out
    return NDArray(jnp.asarray(img))
