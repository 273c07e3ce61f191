"""Production Pallas kernels, shipped through the kernel tier.

Three fused kernels the paper's L1 story names as "Pallas where XLA
fusion loses" (SURVEY §7), each registered as a ``variants["pallas"]``
alternative on an op whose ``forward`` stays the exact XLA composition:

* **fused softmax-cross-entropy** — a ``SoftmaxOutput`` variant: one
  row-block kernel for the forward softmax and one for the loss-head
  backward ``(p - onehot) * mask * scale`` (the op's custom-VJP
  contract: the incoming head cotangent is ignored);
* **fused conv+BN+ReLU** — a new ``FusedConvBNReLU`` op consuming the
  existing BatchNorm aux-state contract (moving_mean/moving_var swap
  after every training forward). The convolution itself stays on the
  MXU through ``lax.conv`` (XLA is already optimal there); the Pallas
  half fuses the whole BN epilogue — per-channel statistics reduction
  plus normalize+affine+ReLU — into two HBM passes instead of XLA's
  stat/normalize/activation chain;
* **fused optimizer updates** — ``sgd_mom_update`` (promoted from the
  rtc.py correctness demo) and ``adam_update`` variants: the whole
  elementwise update in one tiled VMEM pass per parameter.

The memory-bound sweep (ROADMAP 4) widened the tier with three more
families, each fusing what the roofline section of diagnose.py names as
HBM-round-trip chains:

* **fused LayerNorm** — a ``LayerNorm`` variant: one row-block VMEM
  pass for the forward (whole rows resident, f32 statistics) and
  hand-written backward kernels (a dx row pass plus a dgamma/dbeta
  accumulation pass) instead of XLA's mean/var/normalize chain;
* **fused bias+GeLU** — the ``FusedBiasGeLU`` op: the dense→GeLU
  epilogue as one VMEM pass (bias add + erf GeLU), with a hand dx
  kernel; composes with ``FullyConnected(no_bias=True)`` so the matmul
  output is touched exactly once more;
* **fused embedding lookup** — an ``Embedding`` variant: scalar-
  prefetched ids drive the weight BlockSpec's index map (one-pass
  gather + optional scale), backward is a scatter-add.

Every kernel carries a custom VJP. Where a hand backward kernel exists
(softmax-CE) it is used; elsewhere the backward recomputes through the
XLA composition under ``jax.custom_vjp`` (the flash-attention recompute
pattern — numerics match training through either tier by construction).
Selection is never static: the tier autotunes per shape on TPU and
falls back to XLA everywhere else (kernel_tier.py).
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..base import parse_bool, parse_float, parse_int
from .registry import OP_REGISTRY, get_op, register

__all__ = ["pallas_call", "pallas_sgd_mom_update", "pallas_adam_update",
           "fused_softmax_ce", "fused_conv_bn_relu", "fused_layernorm",
           "fused_bias_gelu", "fused_embedding", "decode_attention",
           "grouped_matmul", "grouped_expert_ffn"]


def _interpret():
    """Mosaic-compile on TPU; interpret elsewhere (CPU test mesh)."""
    return jax.default_backend() != "tpu"


def pallas_call(kernel, out_shape, **kwargs):
    """``pl.pallas_call`` with backend-appropriate compile/interpret."""
    kwargs.setdefault("interpret", _interpret())
    return pl.pallas_call(kernel, out_shape=out_shape, **kwargs)


def _divisor_block(n, cap):
    """Largest divisor of n that is <= cap (grid blocks must tile n)."""
    b = min(int(cap), int(n))
    while n % b:
        b -= 1
    return b


def _xla_recompute_vjp(pallas_fn, xla_fn, n_diff):
    """custom_vjp wrapper: Pallas forward, XLA-composition backward.

    ``n_diff`` positional args are differentiable; both fns map them to
    the same output pytree. The recompute keeps training numerics
    identical through either tier without a hand-written backward."""
    @jax.custom_vjp
    def fn(*args):
        return pallas_fn(*args)

    def fwd(*args):
        return fn(*args), args

    def bwd(args, cts):
        _, vjp_fn = jax.vjp(lambda *a: xla_fn(*a), *args[:n_diff])
        return vjp_fn(cts) + (None,) * (len(args) - n_diff)

    fn.defvjp(fwd, bwd)
    return fn


# ==========================================================================
# fused softmax cross-entropy (SoftmaxOutput pallas variant)
# ==========================================================================
def _softmax_fwd_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(
        o_ref.dtype)


def _softmax_ce_bwd_kernel(scale, use_ignore, ignore_label):
    def kernel(p_ref, l_ref, g_ref):
        p = p_ref[...].astype(jnp.float32)
        lab = l_ref[...].astype(jnp.int32)            # (block_n, 1)
        classes = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        onehot = (classes == lab).astype(jnp.float32)
        g = p - onehot
        if use_ignore:
            keep = (l_ref[...].astype(jnp.float32) !=
                    ignore_label).astype(jnp.float32)
            g = g * keep                              # broadcasts (n, 1)
        g_ref[...] = (g * scale).astype(g_ref.dtype)
    return kernel


def _row_blocks(n, c, block_bytes=2 << 20):
    """Row-block size bounding one f32 (rows, c) block to
    ``block_bytes``."""
    cap = max(8, block_bytes // max(1, 4 * c))
    return _divisor_block(n, min(256, cap))


def _pl_softmax(data):
    n, c = data.shape
    bn = _row_blocks(n, c)
    spec = pl.BlockSpec((bn, c), lambda i: (i, 0))
    return pallas_call(
        _softmax_fwd_kernel,
        out_shape=jax.ShapeDtypeStruct(data.shape, data.dtype),
        grid=(n // bn,), in_specs=[spec], out_specs=spec)(data)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _softmax_ce_fn(data, label, attrs_tuple):
    return _pl_softmax(data)


def _softmax_ce_fwd(data, label, attrs_tuple):
    prob = _pl_softmax(data)
    return prob, (prob, label)


def _softmax_ce_bwd(attrs_tuple, res, g):
    # loss-head contract (ops/loss.py): the incoming cotangent is
    # ignored; the backward IS the cross-entropy gradient
    prob, label = res
    attrs = dict(attrs_tuple)
    grad_scale = parse_float(attrs.get("grad_scale", 1.0))
    use_ignore = parse_bool(attrs.get("use_ignore", False))
    ignore_label = parse_float(attrs.get("ignore_label", -1.0))
    normalization = attrs.get("normalization", "null")
    n, c = prob.shape
    scale = grad_scale / (n if normalization == "batch" else 1.0)
    bn = _row_blocks(n, c)
    lab2 = label.reshape(n, 1).astype(jnp.float32)
    grad = pallas_call(
        _softmax_ce_bwd_kernel(scale, use_ignore, ignore_label),
        out_shape=jax.ShapeDtypeStruct(prob.shape, prob.dtype),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, c), lambda i: (i, 0)))(prob, lab2)
    if normalization == "valid":
        valid = jnp.sum((label != ignore_label).astype(jnp.float32)) \
            if use_ignore else jnp.asarray(float(n), jnp.float32)
        grad = grad / jnp.maximum(valid, 1.0).astype(grad.dtype)
    return grad, jnp.zeros_like(label)


_softmax_ce_fn.defvjp(_softmax_ce_fwd, _softmax_ce_bwd)


def fused_softmax_ce(data, label, **attrs):
    """Functional surface of the fused softmax-CE kernel (2-D data)."""
    return _softmax_ce_fn(data, label, tuple(sorted(attrs.items())))


def _softmax_ce_variant(attrs, inputs, aux, is_train, rng):
    data, label = inputs
    return [_softmax_ce_fn(data, label, tuple(sorted(attrs.items())))], []


def _softmax_ce_eligible(attrs, in_shapes, in_dtypes):
    if parse_bool(attrs.get("multi_output", False)):
        return False
    if len(in_shapes) < 2 or len(in_shapes[0]) != 2:
        return False
    n, c = in_shapes[0]
    if tuple(in_shapes[1]) != (n,):
        return False
    return c <= 65536 and str(in_dtypes[0]) in ("float32", "bfloat16",
                                                "float16")


#: worst-case VMEM residency at the eligibility bounds (c <= 65536 ->
#: 8-row blocks; small c -> 256-row blocks at ~2 MiB): prob in + out.
#: Validated at registration by analysis/kernelcheck.py (PK9xx).
_SOFTMAX_CE_KSPEC = {
    "tiles": [((8, 65536), "float32"), ((8, 65536), "float32")],
    "dtypes": ("float32", "bfloat16", "float16"),
}


# ==========================================================================
# fused conv + BatchNorm + ReLU
# ==========================================================================
def _bn_stats_kernel(x_ref, sum_ref, sq_ref):
    n = pl.program_id(1)
    xb = pl.program_id(2)

    @pl.when((n == 0) & (xb == 0))
    def _init():
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        sq_ref[...] = jnp.zeros(sq_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)                # (block_c, block_x)
    sum_ref[...] += jnp.sum(x, axis=-1)[None, :]
    sq_ref[...] += jnp.sum(x * x, axis=-1)[None, :]


def _bn_apply_relu_kernel(x_ref, scale_ref, shift_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                # (block_c, block_x)
    scale = scale_ref[...].reshape(-1, 1)             # (block_c, 1)
    shift = shift_ref[...].reshape(-1, 1)
    o_ref[...] = jnp.maximum(x * scale + shift, 0.0).astype(o_ref.dtype)


def _channel_blocks(n, c, hw):
    block_c = _divisor_block(c, 128)
    cap_x = max(128, (2 << 20) // max(1, 4 * block_c))
    block_x = _divisor_block(hw, cap_x)
    return block_c, block_x


def _pl_channel_stats(x4):
    """Per-channel (sum, sum of squares) of an NCHW tensor, f32."""
    n, c, h, w = x4.shape
    hw = h * w
    x3 = x4.reshape(n, c, hw)
    block_c, block_x = _channel_blocks(n, c, hw)
    # channel blocks outermost so the (1, block_c) output tile stays
    # resident while the sequential grid walks batch and spatial blocks
    grid = (c // block_c, n, hw // block_x)
    in_spec = pl.BlockSpec((None, block_c, block_x),
                           lambda cb, nb, xb: (nb, cb, xb))
    out_spec = pl.BlockSpec((1, block_c), lambda cb, nb, xb: (0, cb))
    s, sq = pallas_call(
        _bn_stats_kernel,
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        grid=grid, in_specs=[in_spec], out_specs=[out_spec, out_spec])(x3)
    return s.reshape(c), sq.reshape(c)


def _pl_apply_bn_relu(x4, scale, shift):
    n, c, h, w = x4.shape
    hw = h * w
    x3 = x4.reshape(n, c, hw)
    block_c, block_x = _channel_blocks(n, c, hw)
    grid = (n, c // block_c, hw // block_x)
    x_spec = pl.BlockSpec((None, block_c, block_x),
                          lambda nb, cb, xb: (nb, cb, xb))
    p_spec = pl.BlockSpec((1, block_c), lambda nb, cb, xb: (0, cb))
    out = pallas_call(
        _bn_apply_relu_kernel,
        out_shape=jax.ShapeDtypeStruct(x3.shape, x4.dtype),
        grid=grid, in_specs=[x_spec, p_spec, p_spec],
        out_specs=x_spec)(x3, scale.reshape(1, c), shift.reshape(1, c))
    return out.reshape(n, c, h, w)


_FUSED_CBR_ATTRS = None        # populated at registration below


def _cbr_conv(attrs, data, weight):
    from .nn import _convolution
    return _convolution(attrs, data, weight)


def _cbr_xla_impl(attrs, data, weight, gamma, beta, moving_mean,
                  moving_var, is_train):
    """The exact XLA composition: Convolution -> BatchNorm -> ReLU,
    sharing ops/nn.py's kernels so numerics are the composition's."""
    from .nn import _bn_fwd
    conv = _cbr_conv(attrs, data, weight)
    # _bn_fwd returns ([out, mean, var], [new_mean, new_var])
    outs, new_aux = _bn_fwd(attrs, [conv, gamma, beta],
                            [moving_mean, moving_var], is_train, None)
    y = jnp.maximum(outs[0], 0)
    return y, new_aux


def _cbr_scale_shift(attrs, gamma, mean, var, beta):
    eps = parse_float(attrs.get("eps", 1e-3))
    if parse_bool(attrs.get("fix_gamma", True)):
        gamma = jnp.ones_like(gamma)
    inv = jax.lax.rsqrt(var + eps)
    scale = (inv * gamma.astype(jnp.float32))
    shift = beta.astype(jnp.float32) - mean * scale
    return scale, shift


def _cbr_pallas_impl(attrs, data, weight, gamma, beta, moving_mean,
                     moving_var, is_train):
    conv = _cbr_conv(attrs, data, weight)
    use_global = parse_bool(attrs.get("use_global_stats", False))
    momentum = parse_float(attrs.get("momentum", 0.9))
    if is_train and not use_global:
        n, c, h, w = conv.shape
        cnt = float(n * h * w)
        s, sq = _pl_channel_stats(conv)
        mean = s / cnt
        var = jnp.maximum(sq / cnt - mean * mean, 0.0)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    scale, shift = _cbr_scale_shift(attrs, gamma, mean, var, beta)
    y = _pl_apply_bn_relu(conv, scale, shift)
    return y, [new_mean, new_var]


def _cbr_make(attrs, is_train):
    """custom_vjp closure over (static) attrs + train flag: Pallas
    forward emitting ``(y, new_mean, new_var)`` in one pass, backward
    recomputed through the XLA composition (aux cotangents discarded —
    moving statistics are side state, exactly as in BatchNorm)."""
    def xla_out(data, weight, gamma, beta, mm, mv):
        return _cbr_xla_impl(attrs, data, weight, gamma, beta,
                             jax.lax.stop_gradient(mm),
                             jax.lax.stop_gradient(mv), is_train)[0]

    @jax.custom_vjp
    def fn(data, weight, gamma, beta, mm, mv):
        y, new_aux = _cbr_pallas_impl(attrs, data, weight, gamma, beta,
                                      mm, mv, is_train)
        return y, new_aux[0], new_aux[1]

    def fwd(data, weight, gamma, beta, mm, mv):
        return fn(data, weight, gamma, beta, mm, mv), \
            (data, weight, gamma, beta, mm, mv)

    def bwd(res, cts):
        data, weight, gamma, beta, mm, mv = res
        ct_y = cts[0]                 # aux-state cotangents are zeros
        _, vjp_fn = jax.vjp(
            lambda d, w, g, b: xla_out(d, w, g, b, mm, mv),
            data, weight, gamma, beta)
        return vjp_fn(ct_y) + (jnp.zeros_like(mm), jnp.zeros_like(mv))

    fn.defvjp(fwd, bwd)
    return fn


def fused_conv_bn_relu(data, weight, gamma, beta, moving_mean,
                       moving_var, is_train=False, **attrs):
    """Functional surface of the fused conv+BN+ReLU Pallas kernel.

    Returns ``(out, [new_moving_mean, new_moving_var])`` — the same
    aux-state contract as BatchNorm (the executor swaps new aux after a
    training forward)."""
    y, nm, nv = _cbr_make(attrs, bool(is_train))(
        data, weight, gamma, beta, moving_mean, moving_var)
    return y, [nm, nv]


def _cbr_xla_variant(attrs, inputs, aux, is_train, rng):
    data, weight, gamma, beta = inputs
    y, new_aux = _cbr_xla_impl(attrs, data, weight, gamma, beta,
                               aux[0], aux[1], is_train)
    return [y], new_aux


def _cbr_pallas_variant(attrs, inputs, aux, is_train, rng):
    data, weight, gamma, beta = inputs
    y, nm, nv = _cbr_make(attrs, bool(is_train))(
        data, weight, gamma, beta, aux[0], aux[1])
    return [y], [nm, nv]


def _cbr_eligible(attrs, in_shapes, in_dtypes):
    kern = attrs.get("kernel")
    if kern is None or len(tuple(kern)) != 2:
        return False
    if len(in_shapes) < 1 or len(in_shapes[0]) != 4:
        return False
    return str(in_dtypes[0]) in ("float32", "bfloat16", "float16")


#: stats + normalize passes: (block_c<=128, block_x<=2MiB/4/block_c)
#: data tile twice resident (in + normalized out) plus the per-channel
#: accumulator rows
_CBR_KSPEC = {
    "tiles": [((128, 4096), "float32"), ((128, 4096), "float32"),
              ((8, 128), "float32")],
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _cbr_infer(attrs, in_shapes):
    from .nn import _conv_infer
    conv_attrs = dict(attrs, no_bias=True)
    new_in, out_s, _ = _conv_infer(conv_attrs, in_shapes[:2])
    nf = parse_int(attrs["num_filter"])
    c = (nf,)
    return [new_in[0], new_in[1], c, c], out_s, [c, c]


def _register_fused_conv_bn_relu():
    if "FusedConvBNReLU" in OP_REGISTRY:
        return
    from .nn import _CONV_ATTRS
    attrs = {k: v for k, v in _CONV_ATTRS.items() if k != "no_bias"}
    attrs.update({"eps": (parse_float, 1e-3),
                  "momentum": (parse_float, 0.9),
                  "fix_gamma": (parse_bool, True),
                  "use_global_stats": (parse_bool, False)})
    register("FusedConvBNReLU",
             inputs=("data", "weight", "gamma", "beta"),
             aux=("moving_mean", "moving_var"),
             full=_cbr_xla_variant,
             attr_spec=attrs, infer_shape=_cbr_infer,
             variants={"pallas": (_cbr_pallas_variant, _cbr_eligible,
                                  _CBR_KSPEC)})


_register_fused_conv_bn_relu()


# ==========================================================================
# fused optimizer updates (promoted from rtc.py's correctness demo)
# ==========================================================================
_TILE_ROWS = 256
_LANES = 128


def _pad_to_tiles(v):
    n = v.size
    cols = _LANES
    rows = -(-n // cols)
    rows_pad = -(-rows // 16) * 16        # bf16-safe sublane multiple
    flat = jnp.ravel(v)
    flat = jnp.pad(flat, (0, rows_pad * cols - n))
    return flat.reshape(rows_pad, cols), n


def _tiled_elementwise(kernel, arrays, n_out):
    """Run an elementwise kernel over same-shaped operands: flatten,
    pad to (16k, 128) tiles, grid over row blocks, un-pad."""
    shape = arrays[0].shape
    padded = []
    n = None
    for a in arrays:
        p, n = _pad_to_tiles(a)
        padded.append(p)
    rows = padded[0].shape[0]
    # block rows: a 16-multiple divisor so the grid tiles rows exactly
    block = 16 * _divisor_block(rows // 16, _TILE_ROWS // 16)
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    outs = pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(padded[0].shape,
                                        padded[0].dtype)] * n_out,
        grid=(rows // block,),
        in_specs=[spec] * len(padded),
        out_specs=[spec] * n_out)(*padded)
    return tuple(o.reshape(-1)[:n].reshape(shape) for o in outs)


def _hyper(attrs):
    lr = parse_float(attrs["lr"])
    wd = parse_float(attrs.get("wd", 0.0))
    rescale = parse_float(attrs.get("rescale_grad", 1.0))
    clip = attrs.get("clip_gradient")
    clip = parse_float(clip) if clip is not None and \
        parse_float(clip) > 0 else None
    return lr, wd, rescale, clip


def _prep(g, w, wd, rescale, clip):
    g = g * rescale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    return g + wd * w


def _sgd_mom_kernel(attrs):
    lr, wd, rescale, clip = _hyper(attrs)
    momentum = parse_float(attrs.get("momentum", 0.0))

    def kernel(w_ref, g_ref, m_ref, ow_ref, om_ref):
        g = _prep(g_ref[...], w_ref[...], wd, rescale, clip)
        m = momentum * m_ref[...] - lr * g
        om_ref[...] = m
        ow_ref[...] = w_ref[...] + m
    return kernel


def _adam_kernel(attrs):
    lr, wd, rescale, clip = _hyper(attrs)
    b1 = parse_float(attrs.get("beta1", 0.9))
    b2 = parse_float(attrs.get("beta2", 0.999))
    eps = parse_float(attrs.get("epsilon", 1e-8))

    def kernel(w_ref, g_ref, mean_ref, var_ref, ow_ref, omean_ref,
               ovar_ref):
        w = w_ref[...]
        g = _prep(g_ref[...], w, wd, rescale, clip)
        mean = b1 * mean_ref[...] + (1 - b1) * g
        var = b2 * var_ref[...] + (1 - b2) * g * g
        omean_ref[...] = mean
        ovar_ref[...] = var
        ow_ref[...] = w - lr * mean / (jnp.sqrt(var) + eps)
    return kernel


def pallas_sgd_mom_update(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                          rescale_grad=1.0, clip_gradient=None):
    """Fused SGD-momentum update on jax arrays: (weight', mom')."""
    attrs = {"lr": lr, "momentum": momentum, "wd": wd,
             "rescale_grad": rescale_grad, "clip_gradient": clip_gradient}
    return _tiled_elementwise(_sgd_mom_kernel(attrs),
                              [weight, grad, mom], 2)


def pallas_adam_update(weight, grad, mean, var, lr, beta1=0.9,
                       beta2=0.999, epsilon=1e-8, wd=0.0,
                       rescale_grad=1.0, clip_gradient=None):
    """Fused Adam update on jax arrays: (weight', mean', var')."""
    attrs = {"lr": lr, "beta1": beta1, "beta2": beta2, "epsilon": epsilon,
             "wd": wd, "rescale_grad": rescale_grad,
             "clip_gradient": clip_gradient}
    return _tiled_elementwise(_adam_kernel(attrs),
                              [weight, grad, mean, var], 3)


def _opt_variant(op_name, kernel_builder, n_in, n_out):
    """Pallas variant of a registered optimizer op, with the uniform
    XLA-recompute custom VJP (updates are rarely differentiated, but
    the contract holds through either tier)."""
    xla_fwd = get_op(op_name).forward

    def variant(attrs, inputs, aux, is_train, rng):
        def pallas_fn(*vals):
            return _tiled_elementwise(kernel_builder(attrs), list(vals),
                                      n_out)

        def xla_fn(*vals):
            outs, _ = xla_fwd(attrs, list(vals), [], is_train, rng)
            return tuple(outs)

        fn = _xla_recompute_vjp(pallas_fn, xla_fn, n_in)
        return list(fn(*inputs)), []

    def eligible(attrs, in_shapes, in_dtypes):
        if len(set(tuple(s) for s in in_shapes)) != 1:
            return False
        return all(str(d) in ("float32", "bfloat16", "float16")
                   for d in in_dtypes)

    return variant, eligible


def _opt_kspec(n_arrays):
    """n_arrays (256, 128) f32 tiles resident per grid step — the
    flattened elementwise update's whole working set."""
    return {"tiles": [((_TILE_ROWS, _LANES), "float32")] * n_arrays,
            "dtypes": ("float32", "bfloat16", "float16")}


# ==========================================================================
# fused LayerNorm (LayerNorm pallas variant): one VMEM pass forward
# (whole rows resident, f32 statistics), hand-written backward kernels
# ==========================================================================
def _ln_fwd_kernel(eps):
    def kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref):
        x = x_ref[...].astype(jnp.float32)            # (block_n, C)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        d = x - mean
        var = jnp.mean(d * d, axis=-1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        g = g_ref[...].astype(jnp.float32)            # (1, C)
        b = b_ref[...].astype(jnp.float32)
        y_ref[...] = (d * rstd * g + b).astype(y_ref.dtype)
        mean_ref[...] = mean
        rstd_ref[...] = rstd
    return kernel


def _ln_bwd_dx_kernel(x_ref, g_ref, ct_ref, mean_ref, rstd_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    ct = ct_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)                # (1, C)
    rstd = rstd_ref[...]                              # (block_n, 1)
    xh = (x - mean_ref[...]) * rstd
    gy = ct * g
    m1 = jnp.mean(gy, axis=-1, keepdims=True)
    m2 = jnp.mean(gy * xh, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gy - m1 - xh * m2)).astype(dx_ref.dtype)


def _ln_bwd_dparams_kernel(x_ref, ct_ref, mean_ref, rstd_ref,
                           dg_ref, db_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros(dg_ref.shape, jnp.float32)
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    ct = ct_ref[...].astype(jnp.float32)
    xh = (x - mean_ref[...]) * rstd_ref[...]
    dg_ref[...] += jnp.sum(ct * xh, axis=0)[None, :]
    db_ref[...] += jnp.sum(ct, axis=0)[None, :]


def _ln_specs(n, c, block_bytes=2 << 20):
    bn = _row_blocks(n, c, block_bytes)
    row = pl.BlockSpec((bn, c), lambda i: (i, 0))
    stat = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    par = pl.BlockSpec((1, c), lambda i: (0, 0))
    return bn, row, stat, par


def _pl_layernorm_fwd(x2, gamma, beta, eps):
    n, c = x2.shape
    bn, row, stat, par = _ln_specs(n, c)
    f32 = jnp.float32
    return pallas_call(
        _ln_fwd_kernel(eps),
        out_shape=[jax.ShapeDtypeStruct((n, c), x2.dtype),
                   jax.ShapeDtypeStruct((n, 1), f32),
                   jax.ShapeDtypeStruct((n, 1), f32)],
        grid=(n // bn,), in_specs=[row, par, par],
        out_specs=[row, stat, stat])(
            x2, gamma.reshape(1, c), beta.reshape(1, c))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_pl_fn(x2, gamma, beta, eps):
    return _pl_layernorm_fwd(x2, gamma, beta, eps)


def _ln_pl_fwd_rule(x2, gamma, beta, eps):
    y, mean, rstd = _pl_layernorm_fwd(x2, gamma, beta, eps)
    return (y, mean, rstd), (x2, gamma, mean, rstd)


def _ln_pl_bwd_rule(eps, res, cts):
    # mean/std are statistic outputs (hidden unless output_mean_var);
    # their cotangents are treated as zero, like BatchNorm's
    x2, gamma, mean, rstd = res
    ct = cts[0]
    n, c = x2.shape
    bn, row, stat, par = _ln_specs(n, c)
    dx = pallas_call(
        _ln_bwd_dx_kernel,
        out_shape=jax.ShapeDtypeStruct((n, c), x2.dtype),
        grid=(n // bn,), in_specs=[row, par, row, stat, stat],
        out_specs=row)(x2, gamma.reshape(1, c), ct, mean, rstd)
    dg, db = pallas_call(
        _ln_bwd_dparams_kernel,
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32)] * 2,
        grid=(n // bn,), in_specs=[row, row, stat, stat],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0))] * 2)(
            x2, ct, mean, rstd)
    return (dx, dg.reshape(c).astype(gamma.dtype),
            db.reshape(c).astype(gamma.dtype))


_ln_pl_fn.defvjp(_ln_pl_fwd_rule, _ln_pl_bwd_rule)


def fused_layernorm(data, gamma, beta, eps=1e-5):
    """Functional surface of the fused LayerNorm kernel (last axis).

    Returns ``(out, mean, std)`` with mean/std shaped like
    ``data.shape[:-1]`` — the LayerNorm op's output contract."""
    c = data.shape[-1]
    x2 = data.reshape(-1, c)
    y, mean, rstd = _ln_pl_fn(x2, gamma, beta, float(eps))
    lead = data.shape[:-1]
    return (y.reshape(data.shape), mean.reshape(lead),
            (1.0 / rstd).reshape(lead))


def _layernorm_variant(attrs, inputs, aux, is_train, rng):
    data, gamma, beta = inputs
    eps = parse_float(attrs.get("eps", 1e-5))
    y, mean, std = fused_layernorm(data, gamma, beta, eps)
    return [y, mean, std], []


def _layernorm_eligible(attrs, in_shapes, in_dtypes):
    data_s = in_shapes[0]
    if len(data_s) < 2:
        return False
    axis = parse_int(attrs.get("axis", -1))
    if axis not in (-1, len(data_s) - 1):
        return False
    return data_s[-1] <= 65536 and str(in_dtypes[0]) in (
        "float32", "bfloat16", "float16")


#: whole rows resident (C <= 65536 -> 8-row blocks): x in, y out, and
#: the f32 statistics columns
_LN_KSPEC = {
    "tiles": [((8, 65536), "float32"), ((8, 65536), "float32"),
              ((8, 128), "float32")],
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _register_layernorm_variant():
    ln = get_op("LayerNorm")
    if "pallas" not in ln.variants:
        ln.add_variant("pallas", _layernorm_variant,
                       eligible=_layernorm_eligible,
                       kernel_spec=_LN_KSPEC)


# ==========================================================================
# fused bias + GeLU epilogue (FusedBiasGeLU op): the dense→GeLU pattern
# collapses to ONE VMEM pass over the matmul output instead of XLA's
# bias-add / erf / mul chain each re-touching HBM
# ==========================================================================
_INV_SQRT2 = 0.7071067811865476
_INV_SQRT2PI = 0.3989422804014327


# Mosaic lowers no ``erf`` primitive, so the kernels evaluate the f32
# rational approximation x * P(x^2) / Q(x^2) (the Eigen/XLA float erf
# coefficients, highest degree first) on the argument clamped to
# +-erfinv(1 - 2^-23), beyond which erf is +-1 in f32. It agrees with
# ``jax.lax.erf`` to a few f32 ulp (tests/test_kernel_tier.py).
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)
_ERF_CLAMP = 3.7439211627767994


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf32(x32):
    x = jnp.clip(x32, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    return x * _horner(x2, _ERF_P) / _horner(x2, _ERF_Q)


def _bias_gelu_kernel(x_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = (0.5 * x * (1.0 + _erf32(x * _INV_SQRT2))).astype(
        o_ref.dtype)


def _bias_gelu_dx_kernel(x_ref, b_ref, ct_ref, dx_ref):
    z = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    phi = jnp.exp(-0.5 * z * z) * _INV_SQRT2PI
    dgelu = 0.5 * (1.0 + _erf32(z * _INV_SQRT2)) + z * phi
    dx_ref[...] = (ct_ref[...].astype(jnp.float32) * dgelu).astype(
        dx_ref.dtype)


def _pl_bias_gelu(x2, bias, kernel):
    n, c = x2.shape
    # 512 KiB blocks: besides the double-buffered operands the erf
    # polynomial keeps several block-sized f32 temporaries live, and at
    # 2 MiB blocks they overrun the chip's 16 MiB scoped VMEM
    bn, row, _stat, par = _ln_specs(n, c, block_bytes=512 << 10)
    in_specs = [row, par] + ([row] if kernel is _bias_gelu_dx_kernel
                             else [])

    def call(*ops):
        return pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((n, c), x2.dtype),
            grid=(n // bn,), in_specs=in_specs, out_specs=row)(*ops)
    return call


@jax.custom_vjp
def _bias_gelu_fn(x2, bias):
    return _pl_bias_gelu(x2, bias, _bias_gelu_kernel)(
        x2, bias.reshape(1, -1))


def _bias_gelu_fwd_rule(x2, bias):
    return _bias_gelu_fn(x2, bias), (x2, bias)


def _bias_gelu_bwd_rule(res, ct):
    x2, bias = res
    # dx: one hand-written VMEM pass; dbias: the (C,) column reduce of
    # dx, left to XLA (a single well-fused reduction)
    dx = _pl_bias_gelu(x2, bias, _bias_gelu_dx_kernel)(
        x2, bias.reshape(1, -1), ct)
    db = jnp.sum(dx.astype(jnp.float32), axis=0).astype(bias.dtype)
    return dx, db


_bias_gelu_fn.defvjp(_bias_gelu_fwd_rule, _bias_gelu_bwd_rule)


def fused_bias_gelu(data, bias):
    """Functional surface of the fused bias+GeLU epilogue kernel."""
    c = data.shape[-1]
    return _bias_gelu_fn(data.reshape(-1, c), bias).reshape(data.shape)


def _bias_gelu_xla(attrs, data, bias):
    # the exact composition (bias add + erf GeLU), accumulated in f32
    # like the kernel so both tiers share one numeric definition
    bshape = (1,) * (data.ndim - 1) + (-1,)
    x32 = data.astype(jnp.float32) + \
        bias.astype(jnp.float32).reshape(bshape)
    return (0.5 * x32 * (1.0 + jax.lax.erf(x32 * _INV_SQRT2))).astype(
        data.dtype)


def _bias_gelu_variant(attrs, inputs, aux, is_train, rng):
    data, bias = inputs
    return [fused_bias_gelu(data, bias)], []


def _bias_gelu_eligible(attrs, in_shapes, in_dtypes):
    data_s, bias_s = in_shapes[0], in_shapes[1]
    if len(data_s) < 2 or tuple(bias_s) != (data_s[-1],):
        return False
    # an 8-row block must stay within _pl_bias_gelu's 512 KiB block bound
    return data_s[-1] <= 16384 and str(in_dtypes[0]) in (
        "float32", "bfloat16", "float16")


def _bias_gelu_infer(attrs, in_shapes, out_known=None):
    data_s = in_shapes[0]
    if out_known and out_known[0] is not None and data_s is None:
        data_s = out_known[0]
    c = (data_s[-1],) if data_s is not None else None
    return [data_s, c], [data_s], []


#: row blocks with whole channels resident (C <= 16384): x, bias
#: broadcast rows, and the GeLU output
_BIAS_GELU_KSPEC = {
    "tiles": [((8, 16384), "float32"), ((8, 16384), "float32"),
              ((8, 16384), "float32")],
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _register_bias_gelu():
    if "FusedBiasGeLU" in OP_REGISTRY:
        return
    register("FusedBiasGeLU", inputs=("data", "bias"),
             simple=_bias_gelu_xla, infer_shape=_bias_gelu_infer,
             variants={"pallas": (_bias_gelu_variant,
                                  _bias_gelu_eligible,
                                  _BIAS_GELU_KSPEC)})


_register_bias_gelu()


# ==========================================================================
# fused embedding lookup (Embedding pallas variant): one-pass gather
# (+ optional scale) driven by scalar-prefetched ids, with a scatter-add
# backward. Mosaic moves whole (sublane, lane) tiles, never one table
# row, so the table is blocked in tile-aligned groups of ``sub`` rows and
# handed to the kernel ``sub`` times: operand r's index map fetches the
# group that holds the row output row r wants, and the kernel selects
# that row out of the group.
# ==========================================================================
def _emb_sublanes(dtype):
    """Rows in one tile of ``dtype``: 8 for 4-byte, 16 for 2-byte."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _emb_gather_kernel(sub, scale):
    def kernel(ids_ref, *refs):
        w_refs, o_ref = refs[:sub], refs[sub]
        base = pl.program_id(0) * sub
        rows = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for r in range(sub):
            group = w_refs[r][...].astype(jnp.float32)        # (sub, D)
            pick = rows == ids_ref[base + r] % sub
            row = jnp.sum(jnp.where(pick, group, 0.0), axis=0,
                          keepdims=True)
            acc = jnp.where(rows == r, row, acc)
        if scale != 1.0:
            acc = acc * scale
        o_ref[...] = acc.astype(o_ref.dtype)
    return kernel


def _pl_embedding(ids, weight, scale):
    from jax.experimental.pallas import tpu as pltpu
    n = ids.shape[0]
    v, d = weight.shape
    sub = _emb_sublanes(weight.dtype)
    n_pad = -(-n // sub) * sub
    # negative ids count from the end, as in the composition's jnp.take;
    # a row index still outside the table would send the block fetch
    # outside the array: clamp (the XLA gather fills such rows instead)
    ids = jnp.clip(jnp.where(ids < 0, ids + v, ids), 0, v - 1)
    ids = jnp.pad(ids, (0, n_pad - n))
    # the composition multiplies by the scale rounded to the table dtype
    scale = float(np.asarray(scale, dtype=weight.dtype))

    def group_of(r):
        return lambda i, ids_ref: (ids_ref[i * sub + r] // sub, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n_pad // sub,),
        in_specs=[pl.BlockSpec((sub, d), group_of(r)) for r in range(sub)],
        out_specs=pl.BlockSpec((sub, d), lambda i, ids_ref: (i, 0)))
    out = pallas_call(
        _emb_gather_kernel(sub, scale),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), weight.dtype),
        grid_spec=grid_spec)(ids, *([weight] * sub))
    return out[:n]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _emb_fn(data, weight, scale):
    ids = data.astype(jnp.int32).ravel()
    out = _pl_embedding(ids, weight, scale)
    return out.reshape(tuple(data.shape) + (weight.shape[1],))


def _emb_fwd_rule(data, weight, scale):
    # weight rides the residuals only for its shape/dtype (it is a live
    # parameter either way — no extra buffer is stored)
    return _emb_fn(data, weight, scale), (data, weight)


def _emb_bwd_rule(scale, res, ct):
    data, weight = res
    ids = data.astype(jnp.int32).ravel()
    ct32 = ct.reshape(-1, weight.shape[1]).astype(jnp.float32)
    if scale != 1.0:
        ct32 = ct32 * scale
    dw = jnp.zeros(weight.shape, jnp.float32).at[ids].add(ct32)
    return jnp.zeros_like(data), dw.astype(weight.dtype)


_emb_fn.defvjp(_emb_fwd_rule, _emb_bwd_rule)


def fused_embedding(data, weight, scale=1.0):
    """Functional surface of the fused embedding-lookup kernel."""
    return _emb_fn(data, weight, float(scale))


def _embedding_variant(attrs, inputs, aux, is_train, rng):
    data, weight = inputs
    return [_emb_fn(data, weight,
                    parse_float(attrs.get("scale", 1.0)))], []


def _embedding_eligible(attrs, in_shapes, in_dtypes):
    w_s = in_shapes[1] if len(in_shapes) > 1 else None
    if w_s is None or len(w_s) != 2 or len(in_shapes[0]) < 1:
        return False
    if str(in_dtypes[1]) not in ("float32", "bfloat16", "float16"):
        return False
    if w_s[1] > 2048:
        # the row groups must fit the declared VMEM tiles (PK901's
        # eligibility-side bound); wider tables keep the XLA gather
        return False
    # Mosaic wants lane-aligned rows; interpret mode (off-TPU) takes any
    return w_s[1] % 128 == 0 or _interpret()


#: at the D <= 2048 eligibility bound and the 2-byte worst case: 16
#: row groups of (16, D) in, one (16, D) block out, the f32 accumulator
_EMB_KSPEC = {
    "tiles": [((16, 2048), "bfloat16")] * 17 + [((16, 2048), "float32")],
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _register_embedding_variant():
    emb = get_op("Embedding")
    if "pallas" not in emb.variants:
        emb.add_variant("pallas", _embedding_variant,
                        eligible=_embedding_eligible,
                        kernel_spec=_EMB_KSPEC)


# ==========================================================================
# flash-decode attention (the attention_decode pallas variant — rtc.py
# owns the op, the RoPE/cache-write prologue, and the registration; the
# kernel here is only the cursor-bounded attention READ)
# ==========================================================================
#: bytes the read may keep in VMEM: the double-buffered K and V blocks,
#: the q and out windows, the float32 scratch and one head's float32
#: working set (under the 16 MiB a kernel gets with no limit of its own)
_READ_VMEM_BUDGET = 12 << 20
#: keys a grid step covers at most: a slot's live prefix is read in
#: whole blocks, so a longer one reads more dead rows than it saves steps
_READ_BLOCK_K = 512
#: heads one turn of the read's loop covers: a head is a chain of two
#: small products with reductions between them, and the chains of a
#: turn's heads overlap where one head's would wait on itself
_READ_UNROLL = 8


def _narrow(dtype):
    """Is every value of ``dtype`` a bfloat16 value?"""
    return jnp.dtype(dtype).itemsize == 1 or dtype == jnp.bfloat16


def _decode_attn_resident(S, Dh, block_k, q_dtype, cache_dtype):
    """``(fixed, per_head)`` bytes of VMEM one grid step of the read
    holds at a key block of ``block_k``: what does not grow with the
    head group (one head's working set: its K and V block widened, a few
    (S, block_k) float32 arrays of scores) and what each head of the
    group adds (K and V blocks, q and out windows, all double-buffered,
    and the m, l, acc scratch, an (S, 1) array lying in 128 lanes)."""
    q_size = jnp.dtype(q_dtype).itemsize
    rows = -(-S // 8) * 8                    # sublanes a window fills
    q_rows = -(-S // (32 // q_size)) * (32 // q_size)
    wide = 2 if _narrow(cache_dtype) else 4
    fixed = 2 * block_k * Dh * wide + 8 * max(rows, 8) * block_k * 4
    per_head = (2 * 2 * block_k * Dh * jnp.dtype(cache_dtype).itemsize
                + 2 * q_rows * Dh * q_size + 2 * rows * Dh * 4
                + rows * (Dh + 2 * 128) * 4)
    return fixed, per_head


def _decode_attn_blocks(H, S, Dh, C, q_dtype, cache_dtype):
    """``(hb, block_k)``: the heads and the keys one grid step of the
    read covers, from the shapes and the dtypes alone. The longest key
    block first (whole 128-row tiles where the capacity is made of
    them, at most ``_READ_BLOCK_K``), halved while one head does not
    fit; then as many heads as ``_READ_VMEM_BUDGET`` holds, a divisor of
    ``H`` (12 heads go as 12, 6, 4...). At 16 heads of 128 in bfloat16
    that is all 16 heads x 512 keys at S=1 and 8 x 512 at S=64."""
    unit = 128 if C % 128 == 0 else 1
    keys = max(_READ_BLOCK_K, unit)
    while True:
        block_k = unit * _divisor_block(C // unit, keys // unit)
        fixed, per_head = _decode_attn_resident(S, Dh, block_k, q_dtype,
                                                cache_dtype)
        if fixed + per_head <= _READ_VMEM_BUDGET or keys <= unit:
            break
        keys //= 2
    hb = _divisor_block(H, max(1, (_READ_VMEM_BUDGET - fixed) // per_head))
    return hb, block_k


def _live_blocks(lo, hi, block_k, n_blocks, ring):
    """``(first, count)`` of the key blocks that hold the positions
    ``lo..hi`` of one slot: of a pool with a row per position the blocks
    ``lo // block_k .. hi // block_k``; of a ring (``ring`` rows, a
    position at its value modulo that) the ``count`` blocks from
    ``first`` on, modulo ``n_blocks`` - at most all of them, each once,
    whatever the wrap."""
    if not ring:
        first = lo // block_k
        return first, hi // block_k - first + 1
    at = jax.lax.rem(lo, ring)
    count = (jax.lax.rem(at, block_k) + hi - lo) // block_k + 1
    return at // block_k, jnp.minimum(count, n_blocks)


def _key_positions(block, block_k, last, shape, ring):
    """The stream position of every key of a block, along ``shape``'s
    last axis: its address in a pool with a row per position; in a ring
    the newest position at or before ``last`` (the newest row written)
    that lies at that address - negative while the ring has not been
    filled that far, which no query attends."""
    at = block * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, len(shape) - 1)
    if not ring:
        return at
    return last - jax.lax.rem(last - at + ring, ring)


def _decode_attn_kernel(hb, block_k, s_len, scale, narrow_q, narrow_kv,
                        period=0, window=0, ring=0, block=0):
    """Grid (slot, head group, key block); the group's heads in a loop
    of ``_READ_UNROLL`` heads a turn. ``period``: a head of the pool is
    read by a group of query heads, whose rows lie one head after
    another, ``period`` positions each (row ``r`` is the query at
    ``cursor + r % period``); 0: a row a position. ``window``: a query
    at ``t`` attends ``j > t - window`` alone, and the steps walk the
    blocks from the first that holds such a key (``_live_blocks``);
    ``ring``: the pool is a ring of that many rows. ``block``: a model
    that decodes by blocks of that many positions - a query attends up
    to the end of its own block (``j < (t // block + 1) * block``) and
    never past the newest row written. ``narrow_q``: q and the cache
    rows are bfloat16 values, so q.K is one bfloat16 product with
    float32 accumulation, exact as the composition's; ``narrow_kv``:
    the rows are, so p.V is the float32 p split in three against the
    bfloat16 rows - the three passes of a full-precision float32
    product that are not multiplications by zero. Anything else is
    widened to float32 and multiplied at HIGHEST."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    exact = jax.lax.Precision.HIGHEST
    turn = _divisor_block(hb, _READ_UNROLL)
    step = block                # (``block`` is a key block's index below)

    def widen(ref, h, to):
        x = ref[h]
        if x.dtype.itemsize == 1 and to != f32:   # fp8: dequantise on read
            x = x.astype(f32)
        return x.astype(to)

    def p_times_v(p, v_ref, h):
        if not narrow_kv:
            return jnp.dot(p, widen(v_ref, h, f32), precision=exact)
        v = widen(v_ref, h, bf16)
        if s_len == 1:
            p = jnp.broadcast_to(p, (8, block_k))
        # p = bf16(p) + bf16(rest) + bf16(last), to float32's last bit
        rest = p - p.astype(bf16).astype(f32)
        last = rest - rest.astype(bf16).astype(f32)
        # the three parts ride one product as rows of one operand where
        # they stack on whole sublane tiles: V crosses to the MXU once
        if s_len == 1:
            row = jax.lax.broadcasted_iota(jnp.int32, (8, block_k), 0)
            lhs = jnp.where(row == 0, p, jnp.where(
                row == 1, rest, jnp.where(row == 2, last, 0.0)))
            return jnp.sum(jnp.dot(lhs.astype(bf16), v,
                                   preferred_element_type=f32),
                           axis=0, keepdims=True)
        parts = [x.astype(bf16) for x in (p, rest, last)]
        if s_len % 16:
            return sum(jnp.dot(part, v, preferred_element_type=f32)
                       for part in parts)
        out = jnp.dot(jnp.concatenate(parts, axis=0), v,
                      preferred_element_type=f32)
        return out[:s_len] + out[s_len:2 * s_len] + out[2 * s_len:]

    def kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s):
        b = pl.program_id(0)
        kb = pl.program_id(2)
        n_kb = pl.num_programs(2)

        @pl.when(kb == 0)
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, f32)
            l_s[...] = jnp.zeros(l_s.shape, f32)
            acc_s[...] = jnp.zeros(acc_s.shape, f32)

        cursor = pos_ref[b]                  # this slot's write position
        k_start = kb * block_k
        if window:
            last = cursor + ((period or s_len) - 1)   # the newest row
            first, count = _live_blocks(jnp.maximum(cursor - window + 1, 0),
                                        last, block_k, n_kb, ring)

        def update():
            # query row i sits at stream position cursor + i and attends
            # key positions <= that (the same comparison as the XLA mask)
            row = jax.lax.broadcasted_iota(jnp.int32, (s_len, block_k), 0)
            if period:
                row = jax.lax.rem(row, period)
            q_pos = cursor + row
            if window:
                block = first + kb
                if ring:
                    block = jax.lax.rem(block, n_kb)
                k_pos = _key_positions(block, block_k, last,
                                       (s_len, block_k), ring)
                attends = (k_pos <= q_pos) & (k_pos > q_pos - window) \
                    & (k_pos >= 0)
            else:
                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (s_len, block_k), 1)
                if step:
                    attends = k_pos < jnp.minimum(
                        (q_pos // step + 1) * step,
                        cursor + (period or s_len))
                else:
                    attends = k_pos <= q_pos

            def head(h):
                if narrow_q:
                    s = jax.lax.dot_general(
                        q_ref[h], widen(k_ref, h, bf16),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
                else:
                    s = jax.lax.dot_general(
                        q_ref[h].astype(f32), widen(k_ref, h, f32),
                        (((1,), (1,)), ((), ())), precision=exact)
                s = jnp.where(attends, s * scale, -jnp.inf)
                m = m_s[h]                   # (S, 1) f32
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - m_safe)      # exp(-inf) = 0: masked keys
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                m_s[h] = m_new
                l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
                acc_s[h] = acc_s[h] * corr + p_times_v(p, v_ref, h)

            def heads(i, carry):
                for r in range(turn):
                    head(i * turn + r)
                return carry
            # a loop of turns, not hb copies of the body: what a step
            # program spends lowering its kernels is set-up time
            jax.lax.fori_loop(0, hb // turn, heads, 0)

        # blocks wholly past the live prefix [0, cursor + S) mask to
        # nothing: skip their FLOPs (their index map names the block the
        # next live step reads, so they cost no HBM traffic of their
        # own either). Block 0 always runs — cursor >= 0 keys at least
        # one position, so l is never zero at emit.
        pl.when(kb < count if window
                else k_start <= cursor + (period or s_len) - 1)(update)

        @pl.when(kb == n_kb - 1)
        def _emit():
            l = jnp.maximum(l_s[...], 1e-30)
            o_ref[...] = (acc_s[...] / l).astype(o_ref.dtype)
    return kernel


def decode_attention(q, k_cache, v_cache, pos, window=0, ring=False,
                     scale=None, riding=None, block=0):
    """Cursor-bounded flash-decode read over a fixed-capacity KV cache.

    ``q`` is (B, H, S, Dh) already-rotated queries, the caches are
    (B, H_kv, C, Dh) with the step's rows already written, and ``pos``
    is the (B,) per-row cursor (a scalar-cursor engine broadcasts before
    calling). The grid is (B, H_kv // hb, C // block_k) over the pools
    as they lie: a step takes a group of ``hb`` heads of one slot and
    ``block_k`` keys (``_decode_attn_blocks``: all 16 heads x 512 keys
    at the serving shapes, 32-64 steps a layer), the heads in a loop.
    The scalar-prefetched cursor bounds the K/V index maps to the live
    blocks — a dead step names the block the next live step reads
    (fetched once, behind the last live step's work) and is skipped, so
    HBM traffic is proportional to the live prefix ``[0, cursor_b + S)``
    in whole blocks, not the capacity, and a dead step costs a step's
    overhead alone. Online-softmax (m, l, acc)
    accumulates in f32 VMEM scratch; fp8 cache rows dequantize on read
    inside the kernel. Returns f32 (B, H, S, Dh) — the caller casts.

    **Grouped heads**: with ``H_kv < H`` query head ``i`` reads K/V
    head ``i // (H // H_kv)``, and the group's query heads go as the
    rows of one head of the pool: one fetched K/V block serves
    ``H // H_kv x S`` query rows. **A window** (``window`` > 0): the
    query at ``t`` attends ``t - window < j <= t``, and the live blocks
    start at the first that holds such a key, so the traffic is the
    window's, whatever the context. ``ring``: the pools are rings, a
    position at its value modulo ``C`` (``C >= window + S``).
    **Blocks** (``block`` > 0, no window): the query at ``t`` attends
    ``j < (t // block + 1) * block``, every position of its own block,
    and none past the ``S`` rows the dispatch wrote.

    **The riders of a window program** (``riding``, a (B,) mask, with
    every slot's first query as ``q``): a slot fed one row of a long
    window is read here, by the kernel, the blocks and the rounding that
    serve it in the S = 1 program (``window_attn_ride`` in the device
    trace), and a slot that does not ride is dead to the launch - its
    cursor goes in as -1, under which no block is live: no step of it
    computes, none of its own blocks is fetched (its steps stand on the
    next slot's first) and zeros come out.

    The call is a jitted function of its own (the kernel is
    ``decode_attn`` in the device trace), so that a step program lowers
    it once and calls it from every layer."""
    more = {} if scale is None else {"scale": float(scale)}
    if block:
        more["block"] = int(block)
    pos = pos.astype(jnp.int32)
    if riding is not None:
        pos = jnp.where(riding, pos, -1)
        more.update(name="window_attn_ride", dead_slots=True)
    return _decode_attention(pos, q, k_cache, v_cache,
                             interpret=_interpret(), window=int(window),
                             ring=bool(ring), **more)


@partial(jax.jit, static_argnames=("interpret", "name", "window", "ring",
                                    "scale", "dead_slots", "block"))
def _decode_attention(pos, q, k_cache, v_cache, interpret,
                      name="decode_attn", window=0, ring=False, scale=None,
                      dead_slots=False, block=0):
    """``decode_attention`` as one jitted program; ``name`` is the
    kernel's in the device trace. ``dead_slots``: a cursor may be -1, a
    slot no step of which is live (the kernel's own rule: no key lies
    at or before -1), and the index maps keep such a slot off its own
    blocks."""
    from jax.experimental.pallas import tpu as pltpu

    B, heads, S, Dh = q.shape
    H, C = k_cache.shape[1:3]
    period, step = 0, block     # (``block`` is a BlockSpec below)
    if heads != H:
        # the group's query heads as the rows of their K/V head
        period, q = S, q.reshape(B, H, heads // H * S, Dh)
    rows_n = q.shape[2]
    hb, block_k = _decode_attn_blocks(H, rows_n, Dh, C, q.dtype,
                                      k_cache.dtype)
    n_kb = C // block_k
    narrow_kv = _narrow(k_cache.dtype)

    def _rows_map(b, g, j, pos_ref):
        return (b, g, 0, 0)

    def _kv_map(b, g, j, pos_ref):
        """A live step's block; a dead step points at what the next
        live step reads - block 0 of the next head group or slot - so
        that its copy rides behind the last live step's work and is
        there when the dead steps, which take no time, are over. The
        last group of the last slot stays on its last live block."""
        last_live = (pos_ref[b] + (S - 1)) // block_k
        more = g + 1 < H // hb
        if dead_slots:
            # -1 under a dead slot, every step of which looks ahead: to
            # the next slot, not to a further group of its own (the
            # last slot has none to look to, and stays on its block 0)
            more = more & (last_live >= 0)
        ahead = (j > last_live) & (more | (b + 1 < B))
        return (jnp.where(ahead & ~more, b + 1, b),
                jnp.where(ahead, jnp.where(more, g + 1, 0), g),
                jnp.where(ahead, 0, jnp.minimum(
                    j, jnp.maximum(last_live, 0) if dead_slots
                    else last_live)), 0)

    def _window_map(b, g, j, pos_ref):
        """Under a window: the live blocks from the first on, a dead
        step on the last live one (no copy)."""
        if dead_slots:
            # a dead slot stands on the first block the next slot reads
            dead = (pos_ref[b] < 0) & (b + 1 < B)
            b, g, j = (jnp.where(dead, x, y)
                       for x, y in ((b + 1, b), (0, g), (0, j)))
        cursor = pos_ref[b]
        first, count = _live_blocks(
            jnp.maximum(cursor - window + 1, 0), cursor + (S - 1), block_k,
            n_kb, C if ring else 0)
        if dead_slots:              # no live block: 0, its block 0
            count = jnp.maximum(count, 1)
        block = first + jnp.minimum(j, count - 1)
        return (b, g, jax.lax.rem(block, n_kb) if ring else block, 0)

    rows = pl.BlockSpec((None, hb, rows_n, Dh), _rows_map)
    block = pl.BlockSpec((None, hb, block_k, Dh),
                         _window_map if window else _kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb, n_kb),
        in_specs=[rows, block, block], out_specs=rows,
        scratch_shapes=[pltpu.VMEM((hb, rows_n, 1), jnp.float32),
                        pltpu.VMEM((hb, rows_n, 1), jnp.float32),
                        pltpu.VMEM((hb, rows_n, Dh), jnp.float32)])
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    geometry = {} if not (period or window) else {
        "period": period, "window": window, "ring": C if ring else 0}
    if step:
        geometry["block"] = step
    out = pallas_call(
        _decode_attn_kernel(hb, block_k, rows_n,
                            float(Dh) ** -0.5 if scale is None else scale,
                            narrow_kv and q.dtype == jnp.bfloat16,
                            narrow_kv, **geometry),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        grid_spec=grid_spec, name=name, interpret=interpret,
        **kwargs)(pos, q, k_cache, v_cache)
    return out.reshape(B, heads, S, Dh) if period else out


#: query rows one grid step of the window read holds (a group's heads x
#: a block of positions): its scores against a key block are a float32
#: array of that many rows, and a few of them live at once
_WINDOW_ROWS = 1024
#: what the window read may keep in VMEM (a v5e core has 128 MiB)
_WINDOW_VMEM_LIMIT = 64 << 20


def _window_attn_blocks(G, S, C):
    """``(block_q, block_k)`` of the window read, from the shapes
    alone: positions a query block so that a group's ``G`` heads of
    them are at most ``_WINDOW_ROWS`` rows (a divisor of ``S``, whole
    sublane tiles of 16 where ``S`` is made of them), and the longest
    key block of whole 128-row tiles up to ``_READ_BLOCK_K``."""
    unit_q = 16 if S % 16 == 0 else 1
    block_q = unit_q * _divisor_block(
        S // unit_q, max(1, min(_WINDOW_ROWS // G, 512) // unit_q))
    unit = 128 if C % 128 == 0 else 1
    return block_q, unit * _divisor_block(C // unit,
                                          max(1, _READ_BLOCK_K // unit))


def _window_attn_kernel(G, block_q, block_k, scale, narrow, window, ring,
                        block=0):
    """Grid (slot, K/V head, query block, key block): the online softmax
    of one query block - ``G`` query heads x ``block_q`` positions as
    the rows of one product - against one key block of their K/V head.
    The steps of a query block walk the key blocks that hold a key one
    of its queries attends (``_live_blocks``: bounded below by the
    window, above by causality), and a query block wholly past ``fed``
    (pads) walks none and comes out zero. ``block``: a model that
    decodes by blocks of that many positions - the upper bound is the
    end of the query's own block (and the newest row written), which
    moves the mask of the diagonal key blocks alone."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    exact = jax.lax.Precision.HIGHEST
    rows = G * block_q
    step = block                # (``block`` is a key block's index below)

    def kernel(pos_ref, fed_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
               acc_s):
        b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
        n_kb, n_q = pl.num_programs(3), pl.num_programs(2)
        cursor = pos_ref[b]
        q_lo = cursor + i * block_q
        q_hi = q_lo + (block_q - 1)
        last = cursor + (n_q * block_q - 1)      # the newest row written
        lo = jnp.maximum(q_lo - window + 1, 0) if window else 0
        if step:        # the last query's block may end past it
            q_hi = jnp.minimum((q_hi // step + 1) * step - 1, last)
        first, count = _live_blocks(lo, q_hi, block_k, n_kb, ring)
        count = jnp.where(i * block_q < fed_ref[b], count, 0)

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, f32)
            l_s[...] = jnp.zeros(l_s.shape, f32)
            acc_s[...] = jnp.zeros(acc_s.shape, f32)

        @pl.when(j < count)
        def _update():
            block = first + j
            if ring:
                block = jax.lax.rem(block, n_kb)
            k_pos = _key_positions(block, block_k, last, (1, block_k), ring)
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            q_pos = q_lo + (jax.lax.rem(row, block_q) if G > 1 else row)
            if step:
                attends = k_pos < jnp.minimum(
                    (q_pos // step + 1) * step,
                    cursor + jnp.maximum(fed_ref[b], 1))
            else:
                attends = (k_pos <= q_pos) & (k_pos >= 0)
            if window:
                attends = attends & (k_pos > q_pos - window)
            q = q_ref[...].reshape(rows, q_ref.shape[-1])
            k, v = k_ref[...], v_ref[...]
            if narrow:
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=f32)
            else:
                s = jax.lax.dot_general(
                    q.astype(f32), k.astype(f32), (((1,), (1,)), ((), ())),
                    precision=exact)
            s = jnp.where(attends, s * scale, -jnp.inf)
            m = m_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)          # exp(-inf) = 0: masked keys
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            m_s[...] = m_new
            l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if narrow:
                pv = jnp.dot(p.astype(bf16), v, preferred_element_type=f32)
            else:
                pv = jnp.dot(p, v.astype(f32), precision=exact)
            acc_s[...] = acc_s[...] * corr + pv

        @pl.when(j == n_kb - 1)
        def _emit():
            out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)
            o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)
    return kernel


def window_attention(q, k_cache, v_cache, pos, fed, window=0, ring=False,
                     scale=None, block=0):
    """The read of a long window (chunked prefill): ``q`` (B, H, S, Dh)
    already-rotated queries of a slot's ``S`` positions from its cursor
    ``pos`` (B,) on, of which ``fed`` (B,) are real; the caches (B,
    H_kv, C, Dh) with those rows already written. Flash-style over key
    blocks bounded on both sides: a query block of ``block_q`` positions
    (all the ``H // H_kv`` query heads of a K/V head at once, so one
    fetched K/V block serves them all) visits the key blocks from the
    one that holds its first query's oldest attended key (position 0,
    or ``t - window + 1``) to the one that holds its last query's own
    position, and no other is fetched or computed; a query block wholly
    past ``fed`` (a slot fed nothing, the pads behind a ragged chunk)
    computes nothing, fetches no key block but its first and comes out
    zero. ``ring``: the pools are rings of ``C >= window + S`` rows.
    p.V is one bfloat16 product where the rows are bfloat16 values
    (float32 at HIGHEST otherwise). ``block`` (no window): a query
    attends up to the end of its own block of that many positions, and
    never past ``pos + fed``.
    Returns (B, H, S, Dh) at ``q``'s dtype.

    A jitted function of its own: the kernel is ``window_attn`` in the
    device trace, lowered once a step program."""
    more = {} if scale is None else {"scale": float(scale)}
    if block:
        more["block"] = int(block)
    return _window_attention(pos.astype(jnp.int32), fed.astype(jnp.int32),
                             q, k_cache, v_cache, interpret=_interpret(),
                             window=int(window), ring=bool(ring), **more)


@partial(jax.jit, static_argnames=("interpret", "window", "ring", "scale",
                                    "block"))
def _window_attention(pos, fed, q, k_cache, v_cache, interpret, window,
                      ring, scale=None, block=0):
    from jax.experimental.pallas import tpu as pltpu

    B, heads, S, Dh = q.shape
    H, C = k_cache.shape[1:3]
    G = heads // H
    block_q, block_k = _window_attn_blocks(G, S, C)
    n_kb = C // block_k
    qg = q.reshape(B, H, G, S, Dh)
    step = block                # (``block`` is a BlockSpec below)

    def _q_map(b, h, i, j, pos_ref, fed_ref):
        return (b, h, 0, i, 0)

    def _kv_map(b, h, i, j, pos_ref, fed_ref):
        """The live blocks from the first on; a dead step stays on the
        last live one (no copy), a query block of pads (wholly past
        ``fed``: the kernel walks none of its blocks) on its first."""
        q_lo = pos_ref[b] + i * block_q
        lo = jnp.maximum(q_lo - window + 1, 0) if window else 0
        q_hi = q_lo + (block_q - 1)
        if step:
            q_hi = jnp.minimum((q_hi // step + 1) * step - 1,
                               pos_ref[b] + (S - 1))
        first, count = _live_blocks(lo, q_hi, block_k, n_kb,
                                    C if ring else 0)
        count = jnp.where(i * block_q < fed_ref[b], count, 1)
        block = first + jnp.minimum(j, count - 1)
        return (b, h, jax.lax.rem(block, n_kb) if ring else block, 0)

    tile = pl.BlockSpec((None, None, G, block_q, Dh), _q_map)
    block = pl.BlockSpec((None, None, block_k, Dh), _kv_map)
    rows = G * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, H, S // block_q, n_kb),
        in_specs=[tile, block, block], out_specs=tile,
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, Dh), jnp.float32)])
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_WINDOW_VMEM_LIMIT)}
    narrow = _narrow(k_cache.dtype) and q.dtype == jnp.bfloat16 \
        and k_cache.dtype == jnp.bfloat16
    out = pallas_call(
        _window_attn_kernel(G, block_q, block_k,
                            float(Dh) ** -0.5 if scale is None else scale,
                            narrow, window, C if ring else 0,
                            **({"block": step} if step else {})),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        grid_spec=grid_spec, name="window_attn", interpret=interpret,
        **kwargs)(pos, fed, qg, k_cache, v_cache)
    return out.reshape(B, heads, S, Dh)


#: bytes of double-buffered blocks ``cache_write`` may keep in VMEM
#: (under the 16 MiB a kernel gets with no limit of its own)
_WRITE_BLOCK_BUDGET = 8 << 20


def _cache_write_kernel(hb, S, bt, n_blocks, capacity, n_pools,
                        ring=False):
    """Grid (slot, head group, target block): block ``cursor // bt + j``
    of each pool comes in, the slot's new rows are laid at their
    positions by a one-hot matrix product (exact in any dtype, and no
    store is ever unaligned), and the block goes back where it came
    from. A block index past the last wraps to a block whose positions
    match no new row, and goes back as it came. ``ring``: the pool is a
    ring of ``capacity`` rows, a position lies at its value modulo
    that, the rows wrap with it and every slot's rows land."""
    def kernel(p_ref, *refs):
        news, olds = refs[:n_pools], refs[n_pools:2 * n_pools]
        outs = refs[2 * n_pools:]
        b, j = pl.program_id(0), pl.program_id(2)
        p = p_ref[b]
        if ring:
            p = jax.lax.rem(p, capacity)
        block = (p // bt + j) % n_blocks
        at = block * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, S), 0)
        if ring:
            select = jax.lax.rem(at - p + capacity, capacity) \
                == jax.lax.broadcasted_iota(jnp.int32, (bt, S), 1)
        else:
            select = (at - p == jax.lax.broadcasted_iota(
                jnp.int32, (bt, S), 1)) & (p + S <= capacity)
        hit = jnp.sum(select.astype(jnp.int32), axis=-1, keepdims=True) > 0
        # an fp8 row is exact in bfloat16, a bfloat16 one as it is
        wide = jnp.float32 if outs[0].dtype == jnp.float32 else jnp.bfloat16
        exact = jax.lax.Precision.HIGHEST if wide == jnp.float32 else None
        sel = select.astype(wide)

        def head(h, carry):
            for new, old, out in zip(news, olds, outs):
                placed = jnp.dot(sel, new[h].astype(wide), precision=exact,
                                 preferred_element_type=jnp.float32)
                out[h] = jnp.where(hit, placed.astype(out.dtype), old[h])
            return carry
        # a loop, not hb copies of the body: what a step program spends
        # lowering its kernels is set-up time
        jax.lax.fori_loop(0, hb, head, 0)
    return kernel


def cache_write(news, pools, pos, ring=False):
    """Each slot's S new rows into its own ``[b, :, cursor:cursor + S]``
    of every pool, in place: ``news`` are (B, H, S, Dh) arrays at the
    pools' dtype, ``pools`` the matching (B, H, C, Dh) caches (K and V
    of one layer ride one launch), ``pos`` the (B,) cursors. Only the
    aligned blocks that hold those rows move (one of 32 rows at S=1,
    two of 128 for a 64-row window), through ``input_output_aliases``:
    with the pools donated to the step program nothing else of them is
    read or written. A slot whose S rows do not fit below the capacity
    writes nothing - ``rtc._write_rows``' rule, which this is the
    kernel of. ``ring``: the pools are rings (``rtc._write_ring``' rule:
    position ``t`` at row ``t % C``, every slot writes). Returns the
    pools.

    The call is a jitted function of its own, so that a step program
    traces and lowers the kernel once and calls it from every layer:
    lowered layer by layer, 24 layers of six programs spent a minute
    of set-up on it."""
    return list(_cache_write(pos.astype(jnp.int32), tuple(news),
                             tuple(pools), interpret=_interpret(),
                             ring=bool(ring)))


@partial(jax.jit, static_argnames=("interpret", "name", "ring"))
def _cache_write(pos, news, pools, interpret, name="cache_write",
                 ring=False):
    """``cache_write`` as one jitted program; ``name`` is the kernel's
    in the device trace (``ops/mla.py`` lands its latent rows and index
    keys through this under names of its own)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, S, Dh = news[0].shape
    C = pools[0].shape[2]
    n = len(pools)
    bt = _divisor_block(C, 32 if S <= 32 else 128)
    n_blocks = C // bt
    per_head = n * Dh * pools[0].dtype.itemsize * (2 * S + 4 * bt)
    hb = _divisor_block(H, max(1, _WRITE_BLOCK_BUDGET // per_head))
    spanned = 1 if S == 1 else min(n_blocks, (S - 1) // bt + 2)

    def _rows_map(b, g, j, pos_ref):
        return (b, g, 0, 0)

    def _block_map(b, g, j, pos_ref):
        p = jax.lax.rem(pos_ref[b], C) if ring else pos_ref[b]
        return (b, g, (p // bt + j) % n_blocks, 0)

    rows = pl.BlockSpec((None, hb, S, Dh), _rows_map)
    block = pl.BlockSpec((None, hb, bt, Dh), _block_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, H // hb, spanned),
        in_specs=[rows] * n + [block] * n, out_specs=tuple([block] * n))
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))}
    return pallas_call(
        _cache_write_kernel(hb, S, bt, n_blocks, C, n,
                            **({"ring": True} if ring else {})),
        out_shape=tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                        for p in pools),
        grid_spec=grid_spec, name=name, interpret=interpret,
        input_output_aliases={1 + n + i: i for i in range(n)},
        **kwargs)(pos, *news, *pools)


# ==========================================================================
# grouped expert feed-forward (the MoEFFN pallas variant - ops/moe.py owns
# the op, the router, the sort and the weighted combine; the kernels here
# are the grouped matmuls over the sorted rows, the tier's first ragged
# shape: the group sizes are data)
# ==========================================================================
#: a weight tile streamed from HBM is at most this many bytes: long
#: contiguous rows at a decode step, where the kernel is bandwidth-bound
_GMM_TILE_BYTES = 1 << 20
#: an ``(N, bk)`` block of a weight that lies with its contracted
#: dimension last (``grouped_matmul(n_major=True)``) is at most this
#: many bytes: its rows are ``bk`` numbers long, not N. Set by
#: arithmetic, not by a sweep on the chip: of K = 2,688 = 21 x 128 the
#: lane-aligned divisors are 128, 384, 896 and 2,688, and under
#: ``_GMM_TILE_BYTES`` an expert of N = 1,856 would be read in blocks
#: whose rows are 128 numbers (256 B) long; 4 MB admits 896 (a block
#: of 3.3 MB, three an expert). The one reading with it (a v5e, PR 63's
#: traced runs): ``moe_gmm_up`` 4.62 ms an S = 1 step of 32 slots for
#: the 3.6 GB of the experts it touched, 0.95 of the HBM peak; no other
#: size was tried
_GMM_NMAJOR_TILE_BYTES = 4 << 20
#: rows of the sorted assignments a grid step multiplies, and the rows
#: of an output tile
_GMM_ROWS = 128
#: rows are addressed in granules of this many: a whole tile of the
#: narrowest dtype the kernels take (bfloat16: 16 sublanes), which is
#: what the chip's compiler lets a block or a slice start on
_GMM_GRANULE = 16
#: the widest model (D) or expert (F) the grouped kernels are offered:
#: an output tile of that many float32 columns beside its accumulator
_GMM_MAX_WIDTH = 7168


def _gmm_geometry(M, E):
    """The static sizes of a grouped matmul over ``M`` sorted rows of
    ``E`` groups: ``(rows, window, chunk, W)``. The rows are padded to
    whole granules; a work item multiplies a ``window`` of rows that
    starts on a granule, so it carries ``chunk = window - granule``
    rows of one group wherever they begin (all of them where one window
    spans the call); the output is cut into tiles of ``window`` rows;
    ``W`` bounds the work items (a group has one, and one more for
    every ``chunk`` rows past the first)."""
    rows = -(-M // _GMM_GRANULE) * _GMM_GRANULE
    window = min(_GMM_ROWS, rows)
    chunk = window if rows == window else window - _GMM_GRANULE
    W = min(E, M) + (M - 1) // chunk
    return rows, window, chunk, W


def _gmm_work_items(group_sizes, M):
    """The work items of a grouped matmul: which rows of which group a
    grid step multiplies.

    A group's rows are cut, from the group's own first row, into chunks
    of ``chunk`` rows (``_gmm_geometry``), and a chunk is an item; an
    empty group has none, so the items are in row order and each begins
    where the one before ends. Returns int32 ``(items (4, W + 1),
    n_items (1,))``; the rows of ``items`` are the item's group, the
    granule its window of rows starts on (the one its first row lies
    in, or as far back as keeps the window inside the rows), and its
    rows ``[lo, hi)``. The grid runs ``n_items + 1`` items: the entries
    from ``n_items`` on repeat the last item's group and window, so
    that the one behind the last item moves no data, with the last
    item's last row as theirs, which puts them on its last output
    tile."""
    E = group_sizes.shape[0]
    rows, window, chunk, W = _gmm_geometry(M, E)
    ends = jnp.cumsum(group_sizes)
    chunks = -(-group_sizes // chunk)
    chunk_ends = jnp.cumsum(chunks)
    n_items = chunk_ends[-1]
    j = jnp.arange(W + 1, dtype=jnp.int32)
    live = j < n_items
    i = jnp.minimum(j, jnp.maximum(n_items, 1) - 1)
    # a handful of items against a handful of groups: one comparison of
    # all with all, where a binary search is a loop of launches
    group = jnp.minimum(
        jnp.searchsorted(chunk_ends, i, side="right", method="compare_all"),
        E - 1).astype(jnp.int32)
    nth = i - (chunk_ends[group] - chunks[group])
    first = ends[group] - group_sizes[group] + nth * chunk
    hi = jnp.minimum(first + chunk, ends[group])
    lo = jnp.where(live, first, hi - 1)
    start = jnp.minimum(first, rows - window) // _GMM_GRANULE
    items = jnp.stack([group, start, jnp.maximum(lo, 0), hi])
    return items.astype(jnp.int32), n_items.reshape((1,)).astype(jnp.int32)


def _silu_gate(g, u):
    return g * jax.nn.sigmoid(g) * u


def _identity(y):
    return y


def _gmm_kernel(n_rhs, window, epilogue, n_major=False):
    """One work item's ``k`` step (``_gmm_work_items``): the window's
    rows times the group's ``(bk, N)`` block of each of ``n_rhs``
    stacked weights (``n_major``: its ``(N, bk)`` block, contracted
    over the last dimension of both), float32 accumulation over the k
    steps; at the last
    one ``epilogue`` of the accumulators goes to the item's rows of the
    output tile its first row lies in, granule by granule, and to no
    other row. Where the item's rows reach into the next tile, the next
    item - the one behind the last included, which does nothing else -
    begins in that tile and writes them from the accumulators before it
    takes them over."""
    G = _GMM_GRANULE

    def kernel(items_ref, n_ref, x_ref, *refs):
        w_refs, o_ref, accs = refs[:n_rhs], refs[n_rhs], refs[n_rhs + 1:]
        i, kk = pl.program_id(0), pl.program_id(1)
        tile_row = items_ref[2, i] // window * window

        def write(item):
            """``item``'s rows of this step's output tile."""
            first_row = items_ref[1, item] * G      # of the accumulators
            lo, hi = items_ref[2, item], items_ref[3, item]
            g0 = jnp.maximum(lo, tile_row) // G
            g1 = (jnp.minimum(hi, tile_row + window) + G - 1) // G

            def granule(g, carry):
                src = pl.multiple_of(g * G - first_row, G)
                dst = pl.multiple_of(g * G - tile_row, G)
                rows = g * G + jax.lax.broadcasted_iota(
                    jnp.int32, (G, o_ref.shape[1]), 0)
                val = epilogue(*[acc[pl.ds(src, G), :] for acc in accs])
                o_ref[pl.ds(dst, G), :] = jnp.where(
                    (rows >= lo) & (rows < hi), val.astype(o_ref.dtype),
                    o_ref[pl.ds(dst, G), :])
                return carry

            jax.lax.fori_loop(g0, g1, granule, 0)

        before = jnp.maximum(i - 1, 0)

        @pl.when((kk == 0) & (i > 0) & (items_ref[2, before] < tile_row)
                 & (items_ref[3, before] > tile_row))
        def _reach():
            write(before)

        live = i < n_ref[0]

        @pl.when(live)
        def _accumulate():
            x = x_ref[...]
            for w_ref, acc in zip(w_refs, accs):
                part = jax.lax.dot_general(
                    x, w_ref[...], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) if n_major \
                    else jnp.dot(x, w_ref[...],
                                 preferred_element_type=jnp.float32)
                # the first step finds whatever the accumulator held
                acc[...] = jnp.where(kk == 0, part, acc[...] + part)

        @pl.when(live & (kk == pl.num_programs(1) - 1))
        def _store():
            write(i)
    return kernel


def _gmm_block_k(K, N, itemsize, tile_bytes=_GMM_TILE_BYTES):
    """Rows of a (block_k, N) weight tile: the largest divisor of K
    that is a multiple of 128 and keeps the tile within ``tile_bytes``;
    K itself where it has no such divisor."""
    cap = max(128, tile_bytes // (N * itemsize))
    for b in range(min(K, cap) // 128 * 128, 0, -128):
        if K % b == 0:
            return b
    return K


def grouped_matmul(x, weights, items, window, epilogue, out_dtype, name,
                   interpret, n_major=False):
    """``epilogue(x @ w[g] for w in weights)`` row group by row group.

    ``x`` (rows, K) holds the sorted rows, whole granules of them;
    ``weights`` are stacked (E, K, N) matrices read K-major - or, with
    ``n_major``, (E, N, K) matrices read in ``(N, bk)`` blocks of up to
    ``_GMM_NMAJOR_TILE_BYTES`` (a block's rows are ``bk`` numbers long,
    so they are kept long) and contracted over their last dimension;
    ``items`` is ``_gmm_work_items``' pair. A work item is a chunk of one group's
    own rows - ``window`` less a granule of them, counted from the
    group's first row, wherever that lies - so **each ``(bk, N)`` block
    of a group's weights crosses HBM once for every such chunk the
    group has**: once a call for a group of up to 112 rows (up to 128
    where the call has no more), and never for a group without rows.
    The grid has as many items as the groups' rows make, and one: its
    first bound is data, and no step is spent on an item that is not
    there. The rows reach the kernel as a window that starts on the
    granule the chunk starts in (an element-indexed block), the output
    as tiles of ``window`` rows of which the items write their own
    rows: a row of no group keeps what the buffer held (both callers
    mask them), and a tile without a group's row is not written at
    all. Returns ``(tiles_m * window, N)``; ``name`` is the kernel's
    name in the device trace."""
    from jax.experimental.pallas import tpu as pltpu

    rows, K = x.shape
    N = weights[0].shape[1 if n_major else 2]
    itemsize = weights[0].dtype.itemsize
    bk = _gmm_block_k(K, N, itemsize, _GMM_NMAJOR_TILE_BYTES if n_major
                      else _GMM_TILE_BYTES)
    n_k = K // bk
    tiles_m = -(-rows // window)
    table, n_items = items

    def k_of(i, kk, n):
        # the item behind the last stays on the block that one held
        return jnp.where(i < n[0], kk, n_k - 1)

    # a K without a divisor of whole lanes (an expert of 1,856) is one
    # block, which the compiler takes as the whole dimension alone
    whole_k = bk % 128 != 0

    def x_map(i, kk, item, n):
        return item[1, i] * _GMM_GRANULE, \
            0 if whole_k else k_of(i, kk, n) * bk

    def w_map(i, kk, item, n):
        if n_major:
            return item[0, i], 0, k_of(i, kk, n)
        return item[0, i], k_of(i, kk, n), 0

    def o_map(i, kk, item, n):
        return item[2, i] // window, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_items[0] + 1, n_k),
        in_specs=[pl.BlockSpec((pl.Element(window), pl.Element(bk)), x_map)]
        + [pl.BlockSpec((None, N, bk) if n_major else (None, bk, N), w_map)
           for _ in weights],
        out_specs=pl.BlockSpec((window, N), o_map),
        scratch_shapes=[pltpu.VMEM((window, N), jnp.float32)
                        for _ in weights])
    # double-buffered blocks and the accumulators; past the 16 MiB a
    # kernel gets unasked (rows of 6,144: a 3 MiB output tile) it asks
    resident = 2 * window * bk * itemsize + len(weights) * (
        2 * bk * N * itemsize + window * N * 4) \
        + 2 * window * N * jnp.dtype(out_dtype).itemsize
    kwargs = {} if interpret or resident <= (10 << 20) else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=4 * resident)}
    return pallas_call(
        _gmm_kernel(len(weights), window, epilogue, n_major),
        out_shape=jax.ShapeDtypeStruct((tiles_m * window, N), out_dtype),
        grid_spec=grid_spec, name=name, interpret=interpret,
        **kwargs)(table, n_items, x, *weights)


def grouped_expert_ffn(xs, group_sizes, *mats):
    """The experts of ``MoEFFN`` over the sorted assignment rows:
    ``xs`` (M, D) in the compute dtype, expert ``e`` owning
    ``group_sizes[e]`` consecutive rows -> (M, D) float32. Two kernels.
    Of the gated form's ``(gate, up, down)``: ``moe_gmm_gate_up`` (both
    matmuls over one read of the rows, SiLU gate fused) and
    ``moe_gmm_down``. Of the ungated form's ``(up, down)``, ``up``
    (E, F, D) (ops/moe.py, **The expert's form**): ``moe_gmm_up`` (one
    matmul, relu squared fused) and ``moe_gmm_down``. An expert's
    weights are read once a call and kernel wherever its rows lie - once
    more for every 112 rows past the first 112 (``grouped_matmul``) -
    and an expert without rows is not read. Rows past
    ``sum(group_sizes)`` come out undefined.

    The pair is a jitted function of its own, so that a step program
    lowers the two kernels once and calls them from every layer."""
    ffn = _grouped_ungated_ffn if len(mats) == 2 else _grouped_expert_ffn
    return ffn(xs, group_sizes, *mats, interpret=_interpret())


def _gmm_rows(xs, group_sizes):
    """The sorted rows padded to whole granules, the work items over
    them and the rows of a window."""
    M = xs.shape[0]
    rows, window = _gmm_geometry(M, group_sizes.shape[0])[:2]
    if rows != M:
        xs = jnp.pad(xs, ((0, rows - M), (0, 0)))
    return xs, _gmm_work_items(group_sizes.astype(jnp.int32), M), window


@partial(jax.jit, static_argnames=("interpret",))
def _grouped_expert_ffn(xs, group_sizes, gate, up, down, interpret):
    M = xs.shape[0]
    xs, items, window = _gmm_rows(xs, group_sizes)
    h = grouped_matmul(
        xs, (gate.astype(xs.dtype), up.astype(xs.dtype)), items, window,
        _silu_gate, xs.dtype, "moe_gmm_gate_up", interpret)
    y = grouped_matmul(h, (down.astype(xs.dtype),), items, window,
                       _identity, jnp.float32, "moe_gmm_down", interpret)
    return y[:M]


@partial(jax.jit, static_argnames=("interpret",))
def _grouped_ungated_ffn(xs, group_sizes, up, down, interpret):
    from .moe import _relu2
    M = xs.shape[0]
    xs, items, window = _gmm_rows(xs, group_sizes)
    h = grouped_matmul(xs, (up.astype(xs.dtype),), items, window, _relu2,
                       xs.dtype, "moe_gmm_up", interpret, n_major=True)
    y = grouped_matmul(h, (down.astype(xs.dtype),), items, window,
                       _identity, jnp.float32, "moe_gmm_down", interpret)
    return y[:M]


def _moe_variant(attrs, inputs, aux, is_train, rng):
    from .moe import moe_ffn
    return moe_ffn(attrs, inputs, grouped_expert_ffn)


def _moe_eligible(attrs, in_shapes, in_dtypes):
    """Lane-aligned widths (any in interpret mode), float rows, and
    weight tiles of full output width within the declared tile set."""
    # the first of an expert's matrices follows the router's (fed
    # before it and its bias behind it, if any)
    first = next((s for s in in_shapes[2:5] if len(s) == 3), None)
    if len(in_shapes) < 4 or len(in_shapes[0]) != 2 or first is None:
        return False
    if str(in_dtypes[0]) not in ("float32", "bfloat16", "float16"):
        return False
    # the ungated form's matrices lie (E, F, D) both: an expert's width
    # is a whole block of either product, whole sublanes of it
    ungated = str(attrs.get("act", "silu")) == "relu2"
    D, F = first[1:][::-1] if ungated else first[1:]
    if max(D, F) > _GMM_MAX_WIDTH:
        return False
    return (D % 128 == 0 and F % (16 if ungated else 128) == 0) \
        or _interpret()


#: worst case at the eligibility bounds (widths <= ``_GMM_MAX_WIDTH``,
#: the down matmul of a 7,168-wide model: weight tiles of 128 rows):
#: the row tile, the weight tile and the output tile double buffered by
#: the pipeline, and the float32 accumulator
_MOE_KSPEC = {
    "tiles": [((_GMM_ROWS, 128), "float32")] * 2        # rows
    + [((128, _GMM_MAX_WIDTH), "bfloat16")] * 2         # down tiles
    + [((_GMM_ROWS, _GMM_MAX_WIDTH), "float32")] * 2    # out tile
    + [((_GMM_ROWS, _GMM_MAX_WIDTH), "float32")],       # accumulator
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _register_moe_variant():
    from . import moe  # noqa: F401 - registers RMSNorm and MoEFFN
    op = get_op("MoEFFN")
    if "pallas" not in op.variants:
        op.add_variant("pallas", _moe_variant, eligible=_moe_eligible,
                       kernel_spec=_MOE_KSPEC)


def _register_opt_variants():
    sgd = get_op("sgd_mom_update")
    if "pallas" not in sgd.variants:
        sgd.add_variant("pallas",
                        *_opt_variant("sgd_mom_update", _sgd_mom_kernel,
                                      3, 2),
                        kernel_spec=_opt_kspec(5))
    adam = get_op("adam_update")
    if "pallas" not in adam.variants:
        adam.add_variant("pallas",
                         *_opt_variant("adam_update", _adam_kernel, 4, 3),
                         kernel_spec=_opt_kspec(7))


def _register_softmax_ce_variant():
    sm = get_op("SoftmaxOutput")
    if "pallas" not in sm.variants:
        sm.add_variant("pallas", _softmax_ce_variant,
                       eligible=_softmax_ce_eligible,
                       kernel_spec=_SOFTMAX_CE_KSPEC)


_register_opt_variants()
_register_softmax_ce_variant()
_register_layernorm_variant()
_register_embedding_variant()
_register_moe_variant()
