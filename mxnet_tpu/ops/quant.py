"""Post-training quantization tiers (weight-only, per-channel):
int8 and fp8 (``float8_e4m3fn`` storage + f32 scales).

Serving is memory-bound: the bucket-ladder programs stream every weight
matrix out of HBM per dispatch, so halving/quartering weight bytes
multiplies serving capacity without new hardware (ROADMAP 4's
"low-precision inference tier"). This module implements the
post-training-quantized (PTQ) path:

* **per-channel scale capture** — ``quantize_per_channel`` maps a float
  weight to ``int8`` values plus one f32 scale per output channel
  (symmetric, amax/127); ``export_model(quantize="int8")`` captures
  scales at export time and bakes int8 weights + in-program dequant
  into the ``.mxp`` artifact;
* **quantized ops** — ``QuantizedFullyConnected`` / ``Quantized
  Convolution``: forward is the exact XLA composition (dequantize in
  f32, then the stock matmul/conv), and each carries a ``pallas``
  variant in the kernel tier — dense fuses the dequant into the matmul
  tile pass (int8 weight tiles decoded in VMEM, never materialized in
  HBM at f32 width), conv fuses the dequant into one tiled VMEM pass
  ahead of the MXU conv. Both ride the SAME numerics gate as every
  tier kernel: a failing kernel can never be selected;
* **graph rewrite** — ``quantize_symbol`` rewrites a trained symbol's
  FullyConnected/Convolution nodes onto the quantized ops and splits
  each weight param into ``<w>_q`` (int8, declared via the var's
  ``__dtype__`` so the executor binds an int8 cell) + ``<w>_scale``
  (f32). ``serve.BucketEngine(compute_dtype="int8")`` runs this
  rewrite at registration, so the bucket ladder pins quantized rungs
  and warm restarts rebuild from the already-quantized payload.

Accuracy contract: int8 outputs sit within ``INT8_TOL`` of the float
composition (per-channel symmetric weight-only PTQ; activations stay in
the incoming float dtype). The serve gate (tests/test_quant.py) pins
``compile_count()`` delta == 0 after warmup plus the tolerance class
against the float ladder.

Quantized graphs are an **inference tier**: binding is
``for_training=False`` everywhere they are produced (export, serving).
The int8 weights carry no gradient path.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, parse_bool, parse_int
from .registry import OP_REGISTRY, get_op, register

__all__ = ["INT8_TOL", "FP8_TOL", "FP8_MAX", "quantize_per_channel",
           "dequantize", "quantize_symbol", "quantizable_weights"]

#: tolerance class for int8-vs-float OUTPUT comparison (per-channel
#: symmetric weight-only PTQ introduces <= 1/254 relative weight error;
#: tests and the serve gate compare against the float ladder with this)
INT8_TOL = {"atol": 0.05, "rtol": 0.05}

#: tolerance class for fp8-vs-float OUTPUT comparison: e4m3's 3-bit
#: mantissa bounds per-weight relative error at 2^-4 (6.25%) after the
#: per-channel amax/448 scaling, so outputs sit a bit wider than int8's
FP8_TOL = {"atol": 0.15, "rtol": 0.15}

#: max finite magnitude of float8_e4m3fn (the fp8 serving storage type)
FP8_MAX = 448.0

#: dtype aliases quantize surfaces accept -> canonical storage dtype
_QUANT_DTYPES = {"int8": "int8",
                 "fp8": "float8_e4m3fn",
                 "float8_e4m3fn": "float8_e4m3fn"}

#: ops the rewrite lowers, old op name -> quantized op name
_QUANT_OPS = {"FullyConnected": "QuantizedFullyConnected",
              "Convolution": "QuantizedConvolution"}


# ----------------------------------------------------------- numerics
def quantize_per_channel(arr, axis=0, dtype="int8"):
    """Symmetric per-channel narrow-dtype quantization.

    Returns ``(q, scale)``: ``q`` shaped like ``arr`` in the storage
    dtype (``int8`` or ``fp8``/``float8_e4m3fn``), ``scale`` f32 shaped
    ``(arr.shape[axis],)`` with ``arr ≈ q * scale`` along ``axis``.
    int8 maps amax to 127 with round-to-nearest; fp8 maps amax to the
    e4m3 max finite (448) and lets the cast's mantissa rounding land
    the rest. All-zero channels get scale 1.0 (q is zero anyway).
    """
    storage = _QUANT_DTYPES.get(str(dtype))
    if storage is None:
        raise MXNetError(f"quantize: unsupported dtype {dtype!r} "
                         "(int8 or fp8)")
    a = np.asarray(arr.asnumpy() if hasattr(arr, "asnumpy") else arr,
                   dtype=np.float32)
    red = tuple(i for i in range(a.ndim) if i != axis)
    amax = np.max(np.abs(a), axis=red) if red else np.abs(a)
    bshape = [1] * a.ndim
    bshape[axis] = -1
    if storage == "int8":
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.round(a / scale.reshape(bshape)), -127, 127)
        return q.astype(np.int8), scale
    scale = np.where(amax > 0, amax / FP8_MAX, 1.0).astype(np.float32)
    q = np.clip(a / scale.reshape(bshape), -FP8_MAX, FP8_MAX)
    return q.astype(np.dtype("float8_e4m3fn")), scale


def dequantize(q, scale, axis=0):
    """f32 reconstruction of a per-channel quantized array."""
    bshape = [1] * q.ndim
    bshape[axis] = -1
    return q.astype(jnp.float32) * scale.reshape(bshape)


# ------------------------------------------------- quantized dense op
def _qfc_inputs(attrs):
    if parse_bool(attrs.get("no_bias", False)):
        return ["data", "weight", "scale"]
    return ["data", "weight", "scale", "bias"]


def _qfc_infer(attrs, in_shapes, out_known=None):
    num_hidden = parse_int(attrs["num_hidden"])
    no_bias = parse_bool(attrs.get("no_bias", False))
    data_s = in_shapes[0]
    w_s, out_s = None, (0, num_hidden)
    if data_s is not None:
        if all(d > 0 for d in data_s[1:]):
            w_s = (num_hidden, int(np.prod(data_s[1:], dtype=np.int64)))
        out_s = (data_s[0], num_hidden)
    new_in = [data_s, w_s, (num_hidden,)] + \
        ([] if no_bias else [(num_hidden,)])
    return new_in, [out_s], []


def _qfc_flatten(attrs, data):
    if data.ndim > 2 and parse_bool(attrs.get("flatten", True)):
        data = data.reshape((data.shape[0], -1))
    return data


def _qfc_xla(attrs, data, weight, scale, bias=None):
    """The exact composition: f32 dequant, f32 matmul, cast back —
    the reference both tiers are gated against. HIGHEST, like the
    kernel: the TPU's default precision multiplies f32 operands in one
    bf16 pass, which puts the reference itself outside NUMERIC_TOL."""
    data = _qfc_flatten(attrs, data)
    wf = dequantize(weight, scale, axis=0)
    out = jnp.dot(data.astype(jnp.float32), wf.T,
                  precision=jax.lax.Precision.HIGHEST)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(data.dtype)


def _qfc_kernel(x_ref, w_ref, s_ref, o_ref):
    # x (bm, K) — w (bn, K) int8 decoded in VMEM: the f32-width weight
    # never exists in HBM, which is the whole win on a memory-bound rung
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32) * s_ref[...].reshape(-1, 1)
    o_ref[...] = jnp.dot(x, w.T,
                         precision=jax.lax.Precision.HIGHEST)


def _pl_qfc_matmul(x2, wq, scale):
    from .pallas_kernels import pallas_call, _divisor_block
    import jax.experimental.pallas as pl
    m, k = x2.shape
    n = wq.shape[0]
    bm = _divisor_block(m, 256)
    bn = _divisor_block(n, 256)
    return pallas_call(
        _qfc_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)))(
            x2, wq, scale.reshape(1, n))


def _qfc_pallas_variant(attrs, inputs, aux, is_train, rng):
    data, weight, scale = inputs[:3]
    bias = inputs[3] if len(inputs) > 3 else None
    data = _qfc_flatten(attrs, data)
    out = _pl_qfc_matmul(data, weight, scale)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return [out.astype(data.dtype)], []


def _qfc_eligible(attrs, in_shapes, in_dtypes):
    data_s, w_s = in_shapes[0], in_shapes[1]
    if len(data_s) != 2 or len(w_s) != 2:
        return False
    if str(in_dtypes[1]) not in ("int8", "float8_e4m3fn"):
        return False
    if w_s[1] > 16384 or str(in_dtypes[0]) not in (
            "float32", "bfloat16", "float16"):
        return False
    # whole-K tiles must fit VMEM alongside the (bm, bn) accumulator:
    # bound the ACTUAL block working set (x f32 + w int8 + out f32),
    # mirroring _pl_qfc_matmul's block choice — the declared
    # _QFC_KSPEC is validated against the same ceiling at registration
    from .pallas_kernels import _divisor_block
    k = w_s[1]
    bm = _divisor_block(data_s[0], 256)
    bn = _divisor_block(w_s[0], 256)
    return bm * k * 4 + bn * k * 1 + bm * bn * 4 <= 12 << 20


# -------------------------------------------------- quantized conv op
def _qconv_inputs(attrs):
    if parse_bool(attrs.get("no_bias", False)):
        return ["data", "weight", "scale"]
    return ["data", "weight", "scale", "bias"]


def _qconv_infer(attrs, in_shapes):
    from .nn import _conv_infer
    nf = parse_int(attrs["num_filter"])
    no_bias = parse_bool(attrs.get("no_bias", False))
    new_in, out_s, _ = _conv_infer(dict(attrs, no_bias=True),
                                   in_shapes[:2])
    new_in = [new_in[0], new_in[1], (nf,)] + \
        ([] if no_bias else [(nf,)])
    return new_in, out_s, []


def _qconv_xla(attrs, data, weight, scale, bias=None):
    from .nn import _convolution
    bshape = (-1,) + (1,) * (weight.ndim - 1)
    wf = weight.astype(jnp.float32) * scale.reshape(bshape)
    return _convolution(dict(attrs, no_bias=bias is None), data, wf,
                        bias)


def _dequant_rows_kernel(w_ref, s_ref, o_ref):
    o_ref[...] = w_ref[...].astype(jnp.float32) * \
        s_ref[...].reshape(-1, 1)


def _qconv_pallas_variant(attrs, inputs, aux, is_train, rng):
    # the conv itself stays on the MXU (XLA is already optimal there,
    # same split as FusedConvBNReLU); the Pallas half is the dequant —
    # ONE tiled VMEM pass over the int8 rows
    from .pallas_kernels import pallas_call, _divisor_block
    import jax.experimental.pallas as pl
    from .nn import _convolution
    data, weight, scale = inputs[:3]
    bias = inputs[3] if len(inputs) > 3 else None
    o = weight.shape[0]
    cols = int(np.prod(weight.shape[1:]))
    bo = _divisor_block(o, 256)
    wf = pallas_call(
        _dequant_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((o, cols), jnp.float32),
        grid=(o // bo,),
        in_specs=[pl.BlockSpec((bo, cols), lambda i: (i, 0)),
                  pl.BlockSpec((1, bo), lambda i: (0, i))],
        out_specs=pl.BlockSpec((bo, cols), lambda i: (i, 0)))(
            weight.reshape(o, cols), scale.reshape(1, o))
    out = _convolution(dict(attrs, no_bias=bias is None), data,
                       wf.reshape(weight.shape), bias)
    return [out], []


def _qconv_eligible(attrs, in_shapes, in_dtypes):
    w_s = in_shapes[1]
    if len(in_shapes[0]) != 4 or len(w_s) != 4:
        return False
    if str(in_dtypes[1]) not in ("int8", "float8_e4m3fn"):
        return False
    if int(np.prod(w_s[1:])) > 65536 or str(in_dtypes[0]) not in (
            "float32", "bfloat16", "float16"):
        return False
    # the dequant pass keeps (bo, cols) int8-in + f32-out resident:
    # bound the block working set like _qconv_pallas_variant builds it
    from .pallas_kernels import _divisor_block
    cols = int(np.prod(w_s[1:]))
    bo = _divisor_block(w_s[0], 256)
    return bo * cols * 5 <= 8 << 20


#: worst-case VMEM residency at the _qfc_eligible bound (<= 12 MiB):
#: x rows f32, int8 weight tile decoded in VMEM, f32 accumulator
_QFC_KSPEC = {
    "tiles": [((256, 8192), "float32"), ((256, 16384), "int8"),
              ((256, 256), "float32")],
    "dtypes": ("float32", "bfloat16", "float16", "int8",
               "float8_e4m3fn"),
}

#: dequant rows pass at the _qconv_eligible bound: 1-B weights in
#: (int8 or fp8 — same residency) + f32 out
_QCONV_KSPEC = {
    "tiles": [((256, 6144), "int8"), ((256, 6144), "float32")],
    "dtypes": ("float32", "bfloat16", "float16", "int8",
               "float8_e4m3fn"),
}


def _register_quant_ops():
    if "QuantizedFullyConnected" in OP_REGISTRY:
        return
    from .nn import _CONV_ATTRS
    register("QuantizedFullyConnected", inputs=_qfc_inputs,
             simple=_qfc_xla, infer_shape=_qfc_infer,
             attr_spec={"num_hidden": (parse_int, None),
                        "no_bias": (parse_bool, False),
                        "flatten": (parse_bool, True)},
             variants={"pallas": (_qfc_pallas_variant, _qfc_eligible,
                                  _QFC_KSPEC)})
    register("QuantizedConvolution", inputs=_qconv_inputs,
             simple=_qconv_xla, infer_shape=_qconv_infer,
             attr_spec=dict(_CONV_ATTRS),
             variants={"pallas": (_qconv_pallas_variant,
                                  _qconv_eligible, _QCONV_KSPEC)})


_register_quant_ops()


# ----------------------------------------------------- graph rewrite
def quantizable_weights(symbol, arg_params):
    """Weight params eligible for the int8 rewrite: variables that feed
    ONLY FullyConnected/Convolution nodes at the weight slot (a weight
    shared with any other consumer stays float), are present in
    ``arg_params``, and have >= 2 dims."""
    ok, bad = set(), set()
    for node in symbol._topo_nodes():
        if node.is_variable:
            continue
        for i, (inp, _idx) in enumerate(node.inputs):
            if not inp.is_variable:
                continue
            if node.op in _QUANT_OPS and i == 1:
                ok.add(inp.name)
            else:
                bad.add(inp.name)
    out = []
    for name in sorted(ok - bad):
        p = arg_params.get(name)
        if p is not None and len(p.shape) >= 2:
            out.append(name)
    return out


def quantize_symbol(symbol, arg_params, dtype="int8"):
    """Rewrite a trained graph onto the quantized ops.

    Returns ``(qsymbol, qarg_params)``: every quantizable weight ``w``
    is replaced in the params by ``w_q`` (the storage dtype — int8 or
    fp8/float8_e4m3fn) + ``w_scale`` (f32) and its consumer nodes
    become Quantized* nodes (same node names, so output names and
    downstream wiring are unchanged). Aux params are untouched — pass
    the originals alongside.
    """
    from ..ndarray import NDArray
    from ..symbol import Node, Symbol
    storage = _QUANT_DTYPES.get(str(dtype))
    if storage is None:
        raise MXNetError(f"quantize: unsupported dtype {dtype!r} "
                         "(int8 or fp8)")
    targets = set(quantizable_weights(symbol, arg_params))
    if not targets:
        raise MXNetError(
            "quantize: no quantizable weights (needs FullyConnected/"
            "Convolution nodes with their weight in arg_params)")

    qvars = {}          # weight name -> (q_node, scale_node)

    def qvar(name):
        if name not in qvars:
            qvars[name] = (
                Node(None, f"{name}_q", extra={"__dtype__": storage}),
                Node(None, f"{name}_scale",
                     extra={"__dtype__": "float32"}))
        return qvars[name]

    rebuilt = {}

    def rebuild(node):
        if id(node) in rebuilt:
            return rebuilt[id(node)]
        if node.is_variable:
            rebuilt[id(node)] = node        # var nodes are shared as-is
            return node
        new_inputs = [(rebuild(inp), idx) for inp, idx in node.inputs]
        wnode = node.inputs[1][0] if len(node.inputs) > 1 else None
        if (node.op in _QUANT_OPS and wnode is not None
                and wnode.is_variable and wnode.name in targets):
            q_node, s_node = qvar(wnode.name)
            new_inputs = ([new_inputs[0], (q_node, 0), (s_node, 0)]
                          + new_inputs[2:])
            new = Node(_QUANT_OPS[node.op], node.name,
                       dict(node.attrs), new_inputs, dict(node._extra))
        else:
            new = Node(node.op, node.name, dict(node.attrs),
                       new_inputs, dict(node._extra))
        rebuilt[id(node)] = new
        return new

    qsym = Symbol([(rebuild(n), i) for n, i in symbol._outputs])

    qargs = {}
    for name, val in arg_params.items():
        if name in qvars:
            q, s = quantize_per_channel(val, axis=0, dtype=storage)
            qargs[f"{name}_q"] = NDArray(jnp.asarray(q))
            qargs[f"{name}_scale"] = NDArray(jnp.asarray(s))
        else:
            qargs[name] = val
    return qsym, qargs
