"""Tensor-op library: the "numpy layer" of the framework.

Covers the reference's NNVM tensor op surface (reference:
src/operator/tensor/, ~10.9k LoC of mshadow kernels + cub sorts) as thin
declarative mappings onto jax.numpy/lax. There are no hand-written kernels
here on purpose: every op is an XLA HLO producer, so elementwise chains fuse
into matmul/conv epilogues and reductions tile onto the VPU — the work the
reference does with mshadow expression templates is done by the XLA compiler.

Inventory mirrors SURVEY.md Appendix A.2/A.3: unary math, binary (+scalar,
broadcast, logic) families, reductions, indexing (Embedding/take/one_hot/
pick), ordering (sort/topk/argsort), matrix ops (dot/batch_dot/transpose/
slice/...), init ops, control flow (where), and sampling ops.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..base import (parse_tuple, parse_bool, parse_int, parse_float,
                    str_to_attr, merge_shape)
from .registry import register, alias

# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _axis_param(val):
    if val is None or val == "None" or val == "()":
        return None
    if isinstance(val, str):
        val = str_to_attr(val)
    if isinstance(val, (int, np.integer)):
        return int(val)
    return tuple(int(v) for v in val)


def _reduce(fn):
    def impl(attrs, x):
        axis = attrs.get("axis", None)
        keepdims = attrs.get("keepdims", False)
        exclude = attrs.get("exclude", False)
        if axis is not None and exclude:
            ax = (axis,) if isinstance(axis, int) else axis
            axis = tuple(i for i in range(x.ndim) if i not in
                         tuple(a % x.ndim for a in ax))
        return fn(x, axis=axis, keepdims=keepdims)
    return impl


_REDUCE_ATTRS = {"axis": (_axis_param, None), "keepdims": (parse_bool, False),
                 "exclude": (parse_bool, False)}


def _infer_elemwise(attrs, in_shapes, out_known=None):
    """Identity-shape inference: merge partials across inputs AND outputs
    (bidirectional fill — the mechanism that back-propagates batch dims
    into RNN begin_state vars)."""
    merged = None
    for s in list(in_shapes) + list(out_known or []):
        merged = merge_shape(merged, s)
    return [merged] * len(in_shapes), [merged], []


# --------------------------------------------------------------------------
# unary math family (reference: src/operator/tensor/elemwise_unary_op.cc,
# mshadow_op.h functor structs)
# --------------------------------------------------------------------------
_GAMMALN = lambda x: lax.lgamma(x.astype(jnp.float32)).astype(x.dtype)

_UNARY = {
    "abs": jnp.abs, "arccos": jnp.arccos, "arccosh": jnp.arccosh,
    "arcsin": jnp.arcsin, "arcsinh": jnp.arcsinh, "arctan": jnp.arctan,
    "arctanh": jnp.arctanh, "ceil": jnp.ceil, "cos": jnp.cos,
    "cosh": jnp.cosh, "degrees": jnp.degrees, "exp": jnp.exp,
    "expm1": jnp.expm1, "fix": jnp.trunc, "floor": jnp.floor,
    "gamma": lambda x: jnp.exp(_GAMMALN(x)), "gammaln": _GAMMALN,
    "log": jnp.log, "log10": jnp.log10, "log1p": jnp.log1p,
    "log2": jnp.log2, "negative": jnp.negative, "radians": jnp.radians,
    "relu": lambda x: jnp.maximum(x, 0), "rint": jnp.rint,
    "round": jnp.round, "rsqrt": lambda x: 1.0 / jnp.sqrt(x),
    "sigmoid": jax.nn.sigmoid, "sign": jnp.sign, "sin": jnp.sin,
    "sinh": jnp.sinh, "sqrt": jnp.sqrt, "square": jnp.square,
    "tan": jnp.tan, "tanh": jnp.tanh,
}

for _name, _fn in _UNARY.items():
    register(_name, inputs=("data",),
             simple=(lambda attrs, x, _f=_fn: _f(x)),
             infer_shape=_infer_elemwise)

register("_copy", inputs=("data",), simple=lambda attrs, x: x,
         infer_shape=_infer_elemwise)
alias("identity", "_copy")


@register("BlockGrad", inputs=("data",), infer_shape=_infer_elemwise)
def _block_grad(attrs, x):
    return lax.stop_gradient(x)

alias("stop_gradient", "BlockGrad")


@register("make_loss", inputs=("data",), infer_shape=_infer_elemwise)
def _make_loss_t(attrs, x):
    return x


@register("smooth_l1", inputs=("data",),
          attr_spec={"scalar": (parse_float, 1.0)},
          infer_shape=_infer_elemwise)
def _smooth_l1(attrs, x):
    sigma2 = attrs.get("scalar", 1.0) ** 2
    absx = jnp.abs(x)
    return jnp.where(absx < 1.0 / sigma2, 0.5 * sigma2 * x * x,
                     absx - 0.5 / sigma2)


@register("Cast", inputs=("data",), attr_spec={"dtype": (None, "float32")},
          infer_shape=_infer_elemwise)
def _cast(attrs, x):
    return x.astype(np.dtype(attrs.get("dtype", "float32")))

alias("cast", "Cast")


# --------------------------------------------------------------------------
# binary family: elemwise, broadcast, scalar (reference:
# elemwise_binary_{op,broadcast_op}*.cc)
# --------------------------------------------------------------------------
_BINARY = {
    "add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
    "div": jnp.divide, "power": jnp.power,
    "hypot": jnp.hypot, "maximum": jnp.maximum, "minimum": jnp.minimum,
    "equal": lambda a, b: (a == b).astype(a.dtype),
    "not_equal": lambda a, b: (a != b).astype(a.dtype),
    "greater": lambda a, b: (a > b).astype(a.dtype),
    "greater_equal": lambda a, b: (a >= b).astype(a.dtype),
    "lesser": lambda a, b: (a < b).astype(a.dtype),
    "lesser_equal": lambda a, b: (a <= b).astype(a.dtype),
    "mod": jnp.mod,
}

for _name, _fn in _BINARY.items():
    register(f"elemwise_{_name}" if _name in ("add", "sub", "mul", "div")
             else f"_{_name}",
             inputs=("lhs", "rhs"),
             simple=(lambda attrs, a, b, _f=_fn: _f(a, b)),
             infer_shape=_infer_elemwise)
    register(f"broadcast_{_name}", inputs=("lhs", "rhs"),
             simple=(lambda attrs, a, b, _f=_fn: _f(a, b)))
    register(f"_{_name}_scalar", inputs=("data",),
             attr_spec={"scalar": (parse_float, 0.0)},
             simple=(lambda attrs, a, _f=_fn: _f(a, jnp.asarray(
                 attrs.get("scalar", 0.0), dtype=a.dtype))),
             infer_shape=_infer_elemwise)

for _name, _fn in (("rsub", lambda a, b: b - a), ("rdiv", lambda a, b: b / a),
                   ("rpower", lambda a, b: jnp.power(b, a)),
                   ("rmod", lambda a, b: jnp.mod(b, a))):
    register(f"_{_name}_scalar", inputs=("data",),
             attr_spec={"scalar": (parse_float, 0.0)},
             simple=(lambda attrs, a, _f=_fn: _f(a, jnp.asarray(
                 attrs.get("scalar", 0.0), dtype=a.dtype))),
             infer_shape=_infer_elemwise)

for _short, _canon in (("_plus", "elemwise_add"), ("_minus", "elemwise_sub"),
                       ("_mul", "elemwise_mul"), ("_div", "elemwise_div"),
                       ("_grad_add", "elemwise_add"),
                       ("_plus_scalar", "_add_scalar"),
                       ("_minus_scalar", "_sub_scalar"),
                       ("_rminus_scalar", "_rsub_scalar"),
                       ("_mul_scalar", "_mul_scalar2"),
                       ("_div_scalar", "_div_scalar2")):
    if _canon.endswith("2"):
        continue
    alias(_short, _canon)


@register("add_n", inputs=lambda attrs: [f"arg{i}" for i in range(
    int(attrs.get("num_args", 2)))],
    attr_spec={"num_args": (parse_int, 2)})
def _add_n(attrs, *xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out

alias("ElementWiseSum", "add_n")
alias("_sum", "add_n")


@register("broadcast_axis", inputs=("data",),
          attr_spec={"axis": (_axis_param, None), "size": (_axis_param, None)})
def _broadcast_axis(attrs, x):
    axes = attrs.get("axis")
    sizes = attrs.get("size")
    axes = (axes,) if isinstance(axes, int) else axes
    sizes = (sizes,) if isinstance(sizes, int) else sizes
    shape = list(x.shape)
    for ax, sz in zip(axes, sizes):
        shape[ax] = sz
    return jnp.broadcast_to(x, tuple(shape))


@register("broadcast_to", inputs=("data",),
          attr_spec={"shape": (parse_tuple, None)})
def _broadcast_to(attrs, x):
    tgt = list(attrs["shape"])
    for i, s in enumerate(tgt):
        if s == 0:
            tgt[i] = x.shape[i]
    return jnp.broadcast_to(x, tuple(tgt))


# --------------------------------------------------------------------------
# reductions (reference: broadcast_reduce_op_{value,index}.cc)
# --------------------------------------------------------------------------
for _name, _fn in (("sum", jnp.sum), ("mean", jnp.mean), ("prod", jnp.prod),
                   ("nansum", jnp.nansum), ("nanprod", jnp.nanprod),
                   ("max", jnp.max), ("min", jnp.min)):
    register(_name, inputs=("data",), attr_spec=dict(_REDUCE_ATTRS),
             simple=_reduce(_fn))

alias("sum_axis", "sum")
alias("max_axis", "max")
alias("min_axis", "min")


def _arg_reduce(fn):
    def impl(attrs, x):
        axis = attrs.get("axis", None)
        keepdims = attrs.get("keepdims", False)
        if axis is None:
            out = fn(jnp.ravel(x), axis=0)
            return out.astype(jnp.float32)
        out = fn(x, axis=axis)
        if keepdims:
            out = jnp.expand_dims(out, axis)
        return out.astype(jnp.float32)
    return impl


register("argmax", inputs=("data",), attr_spec=dict(_REDUCE_ATTRS),
         simple=_arg_reduce(jnp.argmax))
register("argmin", inputs=("data",), attr_spec=dict(_REDUCE_ATTRS),
         simple=_arg_reduce(jnp.argmin))


@register("argmax_channel", inputs=("data",))
def _argmax_channel(attrs, x):
    return jnp.argmax(x, axis=-1).astype(jnp.float32)


@register("norm", inputs=("data",), attr_spec=dict(_REDUCE_ATTRS))
def _norm(attrs, x):
    axis = attrs.get("axis", None)
    keepdims = attrs.get("keepdims", False)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=keepdims))


@register("softmax_cross_entropy", inputs=("data", "label"))
def _softmax_xent(attrs, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


# --------------------------------------------------------------------------
# init ops (reference: init_op.cc)
# --------------------------------------------------------------------------
def _init_shape_infer(attrs, in_shapes):
    return [], [tuple(attrs.get("shape", ()))], []


_INIT_ATTRS = {"shape": (parse_tuple, ()), "dtype": (None, "float32")}


@register("_zeros", inputs=(), attr_spec=dict(_INIT_ATTRS),
          infer_shape=_init_shape_infer)
def _zeros_op(attrs):
    return jnp.zeros(attrs.get("shape", ()), np.dtype(attrs.get("dtype", "float32")))


@register("_ones", inputs=(), attr_spec=dict(_INIT_ATTRS),
          infer_shape=_init_shape_infer)
def _ones_op(attrs):
    return jnp.ones(attrs.get("shape", ()), np.dtype(attrs.get("dtype", "float32")))


@register("_full", inputs=(), attr_spec={**_INIT_ATTRS,
                                         "value": (parse_float, 0.0)},
          infer_shape=_init_shape_infer)
def _full_op(attrs):
    return jnp.full(attrs.get("shape", ()), attrs.get("value", 0.0),
                    np.dtype(attrs.get("dtype", "float32")))


@register("_arange", inputs=(),
          attr_spec={"start": (parse_float, 0.0), "stop": (None, None),
                     "step": (parse_float, 1.0), "repeat": (parse_int, 1),
                     "dtype": (None, "float32")})
def _arange_op(attrs):
    stop = attrs.get("stop")
    stop = None if stop in (None, "None") else float(stop)
    arr = jnp.arange(attrs.get("start", 0.0), stop, attrs.get("step", 1.0),
                     np.dtype(attrs.get("dtype", "float32")))
    if attrs.get("repeat", 1) > 1:
        arr = jnp.repeat(arr, attrs["repeat"])
    return arr


@register("zeros_like", inputs=("data",), infer_shape=_infer_elemwise)
def _zeros_like(attrs, x):
    return jnp.zeros_like(x)


@register("ones_like", inputs=("data",), infer_shape=_infer_elemwise)
def _ones_like(attrs, x):
    return jnp.ones_like(x)


@register("_identity_with_attr_like_rhs", inputs=("lhs", "rhs"))
def _ident_like(attrs, lhs, rhs):
    return lhs


# --------------------------------------------------------------------------
# matrix ops (reference: matrix_op.cc)
# --------------------------------------------------------------------------
@register("dot", inputs=("lhs", "rhs"),
          attr_spec={"transpose_a": (parse_bool, False),
                     "transpose_b": (parse_bool, False)})
def _dot(attrs, a, b):
    if attrs.get("transpose_a"):
        a = a.T if a.ndim == 2 else jnp.moveaxis(a, -1, -2)
    if attrs.get("transpose_b"):
        b = b.T if b.ndim == 2 else jnp.moveaxis(b, -1, -2)
    # MXNet dot on >2d: collapses [a1..an-1, an] x [b1, b2..bm] over an==b1
    if a.ndim > 2 or b.ndim > 2:
        return jnp.tensordot(a, b, axes=([a.ndim - 1], [0]))
    return jnp.dot(a, b)


@register("batch_dot", inputs=("lhs", "rhs"),
          attr_spec={"transpose_a": (parse_bool, False),
                     "transpose_b": (parse_bool, False)})
def _batch_dot(attrs, a, b):
    if attrs.get("transpose_a"):
        a = jnp.swapaxes(a, -1, -2)
    if attrs.get("transpose_b"):
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


@register("transpose", inputs=("data",),
          attr_spec={"axes": (parse_tuple, None)})
def _transpose(attrs, x):
    axes = attrs.get("axes")
    if not axes:
        axes = None
    return jnp.transpose(x, axes)


@register("expand_dims", inputs=("data",), attr_spec={"axis": (parse_int, 0)})
def _expand_dims(attrs, x):
    return jnp.expand_dims(x, attrs["axis"])


@register("Reshape", inputs=("data",),
          attr_spec={"shape": (parse_tuple, None),
                     "target_shape": (parse_tuple, None),
                     "keep_highest": (parse_bool, False),
                     "reverse": (parse_bool, False)})
def _reshape(attrs, x):
    shape = attrs.get("shape") or attrs.get("target_shape")
    out = []
    src = list(x.shape)
    i = 0
    for s in shape:
        if s == 0:
            out.append(src[i]); i += 1
        elif s == -1:
            out.append(-1); i += 1
        elif s == -2:
            out.extend(src[i:]); i = len(src)
        elif s == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif s == -4:
            continue  # handled by following -1/explicit pair; rare — fallthrough
        else:
            out.append(s); i += 1
    return jnp.reshape(x, tuple(out))

alias("reshape", "Reshape")


def _reshape_like_infer(attrs, in_shapes):
    return in_shapes, [in_shapes[1]], []


@register("reshape_like", inputs=("lhs", "rhs"),
          infer_shape=_reshape_like_infer)
def _reshape_like(attrs, lhs, rhs):
    """``lhs`` in the shape of ``rhs`` (reference: matrix_op.cc)."""
    return jnp.reshape(lhs, rhs.shape)


def _flatten_infer(attrs, in_shapes):
    # pure-python inference keeps Flatten off the jax.eval_shape
    # fallback — the static memory planner's trace-free guarantee
    # walks these shapes for every bundled model
    s = in_shapes[0]
    if s is None or any(d == 0 for d in s[1:]):
        return in_shapes, [None], []
    n = 1
    for d in s[1:]:
        n *= int(d)
    return in_shapes, [(s[0], n)], []


@register("Flatten", inputs=("data",), infer_shape=_flatten_infer)
def _flatten(attrs, x):
    return jnp.reshape(x, (x.shape[0], -1))

alias("flatten", "Flatten")


@register("slice", inputs=("data",),
          attr_spec={"begin": (parse_tuple, None), "end": (parse_tuple, None)})
def _slice(attrs, x):
    begin, end = attrs["begin"], attrs["end"]
    idx = tuple(slice(b, e) for b, e in zip(begin, end))
    return x[idx]

alias("crop", "slice")


@register("slice_axis", inputs=("data",),
          attr_spec={"axis": (parse_int, 0), "begin": (parse_int, 0),
                     "end": (None, None)})
def _slice_axis(attrs, x):
    axis, begin = attrs["axis"], attrs["begin"]
    end = attrs.get("end")
    end = x.shape[axis] if end in (None, "None") else int(end)
    if end < 0:
        end += x.shape[axis]
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(begin, end)
    return x[tuple(idx)]


@register("_slice_assign", inputs=("lhs", "rhs"),
          attr_spec={"begin": (parse_tuple, None), "end": (parse_tuple, None)})
def _slice_assign(attrs, lhs, rhs):
    idx = tuple(slice(b, e) for b, e in zip(attrs["begin"], attrs["end"]))
    return lhs.at[idx].set(rhs)


@register("_crop_assign_scalar", inputs=("data",),
          attr_spec={"begin": (parse_tuple, None), "end": (parse_tuple, None),
                     "scalar": (parse_float, 0.0)})
def _crop_assign_scalar(attrs, x):
    idx = tuple(slice(b, e) for b, e in zip(attrs["begin"], attrs["end"]))
    return x.at[idx].set(attrs.get("scalar", 0.0))


@register("clip", inputs=("data",),
          attr_spec={"a_min": (parse_float, 0.0), "a_max": (parse_float, 0.0)},
          infer_shape=_infer_elemwise)
def _clip(attrs, x):
    return jnp.clip(x, attrs["a_min"], attrs["a_max"])


@register("repeat", inputs=("data",),
          attr_spec={"repeats": (parse_int, 1), "axis": (_axis_param, None)})
def _repeat(attrs, x):
    return jnp.repeat(x, attrs["repeats"], axis=attrs.get("axis"))


@register("tile", inputs=("data",), attr_spec={"reps": (parse_tuple, None)})
def _tile(attrs, x):
    return jnp.tile(x, attrs["reps"])


@register("reverse", inputs=("data",), shape_passthrough=True,
          attr_spec={"axis": (_axis_param, 0)})
def _reverse(attrs, x):
    ax = attrs.get("axis", 0)
    ax = (ax,) if isinstance(ax, int) else ax
    return jnp.flip(x, axis=ax)

alias("flip", "reverse")


@register("SwapAxis", inputs=("data",),
          attr_spec={"dim1": (parse_int, 0), "dim2": (parse_int, 0)})
def _swapaxis(attrs, x):
    return jnp.swapaxes(x, attrs["dim1"], attrs["dim2"])

alias("swapaxes", "SwapAxis")


@register("Pad", inputs=("data",),
          attr_spec={"mode": (None, "constant"),
                     "pad_width": (parse_tuple, None),
                     "constant_value": (parse_float, 0.0)})
def _pad(attrs, x):
    pw = attrs["pad_width"]
    pairs = [(pw[2 * i], pw[2 * i + 1]) for i in range(len(pw) // 2)]
    mode = attrs.get("mode", "constant")
    if mode == "constant":
        return jnp.pad(x, pairs, constant_values=attrs.get("constant_value", 0.0))
    if mode == "edge":
        return jnp.pad(x, pairs, mode="edge")
    if mode == "reflect":
        return jnp.pad(x, pairs, mode="reflect")
    raise ValueError(f"Pad mode {mode}")

alias("pad", "Pad")


# --------------------------------------------------------------------------
# indexing (reference: indexing_op.cc)
# --------------------------------------------------------------------------
def _embedding_infer(attrs, in_shapes):
    data_s, w_s = in_shapes
    in_dim = int(attrs["input_dim"])
    out_dim = int(attrs["output_dim"])
    w = (in_dim, out_dim)
    out = None
    if data_s is not None:
        out = tuple(data_s) + (out_dim,)
    return [data_s, w], [out], []


@register("Embedding", inputs=("data", "weight"),
          attr_spec={"input_dim": (parse_int, None),
                     "output_dim": (parse_int, None),
                     "dtype": (None, "float32"),
                     "scale": (parse_float, 1.0)},
          infer_shape=_embedding_infer)
def _embedding(attrs, data, weight):
    out = jnp.take(weight, data.astype(jnp.int32), axis=0)
    # optional post-lookup scale (transformer embedding-sharing wants
    # sqrt(d_model)); the 1.0 default is skipped so pre-scale graphs
    # stay bit-exact
    scale = parse_float(attrs.get("scale", 1.0))
    if scale != 1.0:
        out = out * jnp.asarray(scale, out.dtype)
    return out


@register("take", inputs=("a", "indices"),
          attr_spec={"axis": (parse_int, 0), "mode": (None, "clip")})
def _take(attrs, a, indices):
    mode = attrs.get("mode", "clip")
    return jnp.take(a, indices.astype(jnp.int32), axis=attrs.get("axis", 0),
                    mode="clip" if mode == "clip" else "wrap")


@register("batch_take", inputs=("a", "indices"))
def _batch_take(attrs, a, indices):
    return jnp.take_along_axis(
        a, indices.astype(jnp.int32)[:, None], axis=1)[:, 0]


@register("one_hot", inputs=("indices",),
          attr_spec={"depth": (parse_int, None), "on_value": (parse_float, 1.0),
                     "off_value": (parse_float, 0.0), "dtype": (None, "float32")})
def _one_hot(attrs, idx):
    depth = attrs["depth"]
    oh = jax.nn.one_hot(idx.astype(jnp.int32), depth,
                        dtype=np.dtype(attrs.get("dtype", "float32")))
    on, off = attrs.get("on_value", 1.0), attrs.get("off_value", 0.0)
    if on != 1.0 or off != 0.0:
        oh = oh * (on - off) + off
    return oh


@register("pick", inputs=("data", "index"),
          attr_spec={"axis": (parse_int, -1), "keepdims": (parse_bool, False)})
def _pick(attrs, data, index):
    axis = attrs.get("axis", -1)
    idx = jnp.expand_dims(index.astype(jnp.int32), axis)
    out = jnp.take_along_axis(data, idx, axis=axis)
    if not attrs.get("keepdims", False):
        out = jnp.squeeze(out, axis=axis)
    return out


@register("where", inputs=("condition", "x", "y"))
def _where(attrs, cond, x, y):
    if cond.ndim == 1 and x.ndim > 1:
        cond = cond.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(cond != 0, x, y)


# --------------------------------------------------------------------------
# ordering (reference: ordering_op.cc over cub sorts)
# --------------------------------------------------------------------------
@register("sort", inputs=("data",),
          attr_spec={"axis": (_axis_param, -1), "is_ascend": (parse_bool, True)})
def _sort(attrs, x):
    axis = attrs.get("axis", -1)
    out = jnp.sort(x, axis=axis)
    if not attrs.get("is_ascend", True):
        out = jnp.flip(out, axis=axis)
    return out


@register("argsort", inputs=("data",),
          attr_spec={"axis": (_axis_param, -1), "is_ascend": (parse_bool, True)})
def _argsort(attrs, x):
    axis = attrs.get("axis", -1)
    out = jnp.argsort(x, axis=axis)
    if not attrs.get("is_ascend", True):
        out = jnp.flip(out, axis=axis)
    return out.astype(jnp.float32)


def _topk_num_outputs(attrs):
    return 2 if attrs.get("ret_typ", "indices") == "both" else 1


@register("topk", inputs=("data",),
          attr_spec={"axis": (_axis_param, -1), "k": (parse_int, 1),
                     "ret_typ": (None, "indices"), "is_ascend": (parse_bool, False)},
          num_outputs=_topk_num_outputs)
def _topk(attrs, x):
    axis = attrs.get("axis", -1)
    if axis is None:
        x = jnp.ravel(x)
        axis = 0
    k = attrs.get("k", 1)
    ret = attrs.get("ret_typ", "indices")
    neg = attrs.get("is_ascend", False)
    xv = jnp.moveaxis(x, axis, -1)
    vals, idxs = lax.top_k(-xv if neg else xv, k)
    if neg:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, axis)
    idxs = jnp.moveaxis(idxs, -1, axis).astype(jnp.float32)
    if ret == "value":
        return vals
    if ret == "both":
        return vals, idxs
    if ret == "mask":
        mask = jnp.zeros_like(jnp.moveaxis(x, axis, -1))
        mask = mask.at[..., :].set(0)
        oh = jax.nn.one_hot(jnp.moveaxis(idxs, axis, -1).astype(jnp.int32),
                            x.shape[axis], dtype=x.dtype).sum(-2)
        return jnp.moveaxis(oh, -1, axis)
    return idxs


# --------------------------------------------------------------------------
# sampling (reference: sample_op.cc) — functional JAX RNG under the hood
# --------------------------------------------------------------------------
def _sample_attr():
    return {"shape": (parse_tuple, ()), "dtype": (None, "float32")}


def _reg_sampler(name, draw):
    def fwd(attrs, inputs, aux, is_train, rng):
        shape = attrs.get("shape", ())
        dtype = np.dtype(attrs.get("dtype", "float32"))
        return [draw(attrs, rng, shape, dtype)], []
    register(name, inputs=(), full=fwd, need_rng=True,
             attr_spec={**_sample_attr(), **_SAMPLER_EXTRA.get(name, {})},
             infer_shape=_init_shape_infer)


_SAMPLER_EXTRA = {
    "_random_uniform": {"low": (parse_float, 0.0), "high": (parse_float, 1.0)},
    "_random_normal": {"loc": (parse_float, 0.0), "scale": (parse_float, 1.0)},
    "_random_gamma": {"alpha": (parse_float, 1.0), "beta": (parse_float, 1.0)},
    "_random_exponential": {"lam": (parse_float, 1.0)},
    "_random_poisson": {"lam": (parse_float, 1.0)},
    "_random_negative_binomial": {"k": (parse_int, 1), "p": (parse_float, 1.0)},
    "_random_generalized_negative_binomial": {
        "mu": (parse_float, 1.0), "alpha": (parse_float, 1.0)},
}

_reg_sampler("_random_uniform", lambda attrs, rng, shape, dtype:
             jax.random.uniform(rng, shape, dtype=dtype,
                                minval=attrs.get("low", 0.0),
                                maxval=attrs.get("high", 1.0)))
_reg_sampler("_random_normal", lambda attrs, rng, shape, dtype:
             attrs.get("loc", 0.0) + attrs.get("scale", 1.0) *
             jax.random.normal(rng, shape, dtype=dtype))
_reg_sampler("_random_gamma", lambda attrs, rng, shape, dtype:
             jax.random.gamma(rng, attrs.get("alpha", 1.0), shape,
                              dtype=dtype) * attrs.get("beta", 1.0))
_reg_sampler("_random_exponential", lambda attrs, rng, shape, dtype:
             jax.random.exponential(rng, shape, dtype=dtype) /
             attrs.get("lam", 1.0))
_reg_sampler("_random_poisson", lambda attrs, rng, shape, dtype:
             jax.random.poisson(rng, attrs.get("lam", 1.0), shape)
             .astype(dtype))
_reg_sampler("_random_negative_binomial", lambda attrs, rng, shape, dtype:
             _neg_binomial(rng, attrs.get("k", 1), attrs.get("p", 0.5),
                           shape).astype(dtype))
_reg_sampler("_random_generalized_negative_binomial",
             lambda attrs, rng, shape, dtype:
             _gen_neg_binomial(rng, attrs.get("mu", 1.0),
                               attrs.get("alpha", 1.0), shape).astype(dtype))

alias("uniform", "_random_uniform")
alias("random_uniform", "_random_uniform")
alias("normal", "_random_normal")
alias("random_normal", "_random_normal")
alias("random_gamma", "_random_gamma")
alias("random_exponential", "_random_exponential")
alias("random_poisson", "_random_poisson")
alias("random_negative_binomial", "_random_negative_binomial")
alias("random_generalized_negative_binomial",
      "_random_generalized_negative_binomial")


def _neg_binomial(rng, k, p, shape):
    k1, k2 = jax.random.split(rng)
    lam = jax.random.gamma(k1, k, shape) * (1 - p) / p
    return jax.random.poisson(k2, lam, shape)


def _gen_neg_binomial(rng, mu, alpha, shape):
    k1, k2 = jax.random.split(rng)
    r = 1.0 / alpha
    p = r / (r + mu)
    lam = jax.random.gamma(k1, r, shape) * (1 - p) / p
    return jax.random.poisson(k2, lam, shape)
