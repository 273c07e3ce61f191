"""mx.rtc: user-supplied accelerator kernels at runtime.

Reference counterpart: ``mx.rtc.Rtc`` compiles CUDA C source through nvrtc
and pushes it onto NDArrays (reference: src/common/mxrtc.cc:1-141,
c_api.h:1471-1491, python/mxnet/rtc.py). The TPU has no user-facing
runtime-compiled C — the native kernel language is **Pallas** (Mosaic), so
here a "kernel" is a Python Pallas function compiled for the TPU at trace
time (interpret mode on CPU keeps kernels testable everywhere):

  * ``Rtc(name, inputs, outputs, kernel)`` — imperative push, API-shaped
    like the reference class;
  * ``register_pallas_op(...)`` — the deeper integration the reference
    never had: a user kernel becomes a first-class registry op, visible as
    ``mx.nd.<name>`` / ``mx.sym.<name>``, optionally differentiable via a
    user VJP kernel, and fusable into jitted executor graphs.

A built-in fused SGD-momentum update kernel doubles as the reference
implementation and the numerics test target (vs the XLA composition in
ops/optimizer_op.py).
"""
from __future__ import annotations

from collections import namedtuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .base import MXNetError
from .ops.registry import (register as _register_op, OP_REGISTRY,
                           read_counts)
# the production kernels (and the shared interpret-gated pallas_call)
# live in ops/pallas_kernels.py; rtc re-exports the public surfaces so
# the reference-shaped mx.rtc API is unchanged
from .ops.pallas_kernels import (pallas_call, _interpret,  # noqa: F401
                                 pallas_sgd_mom_update)

__all__ = ["Rtc", "register_pallas_op", "pallas_call",
           "pallas_sgd_mom_update", "flash_attention",
           "flash_attention_partial"]


class Rtc:
    """Imperative kernel handle (reference API: mx.rtc.Rtc(name, inputs,
    outputs, kernel); push(ins, outs, grid, block)).

    ``inputs``/``outputs`` are (name, NDArray) example pairs fixing
    shapes/dtypes like the reference; ``kernel`` is a Pallas kernel
    function taking one ref per input followed by one ref per output.
    Grid/block dims are Pallas grid/BlockSpecs — pass ``grid=`` if the
    kernel tiles; the default maps whole arrays into VMEM.
    """

    def __init__(self, name, inputs, outputs, kernel, grid=None,
                 in_specs=None, out_specs=None):
        self.name = name
        self._in_shapes = [(nm, tuple(a.shape), a.dtype)
                           for nm, a in inputs]
        self._out_struct = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                            for _, a in outputs]
        kwargs = {}
        if grid is not None:
            kwargs["grid"] = grid
        if in_specs is not None:
            kwargs["in_specs"] = in_specs
        if out_specs is not None:
            kwargs["out_specs"] = out_specs
        self._fn = jax.jit(pallas_call(kernel, out_shape=self._out_struct,
                                       **kwargs))

    def push(self, ins, outs, grid_dims=None, block_dims=None):
        """Run the kernel. grid/block dims are fixed at construction in
        Pallas (they shape the compiled program); passing different ones
        here raises, matching the spirit of the reference's checks."""
        if grid_dims is not None or block_dims is not None:
            raise MXNetError("Pallas grids are fixed at Rtc construction; "
                             "rebuild the Rtc to change tiling")
        if len(ins) != len(self._in_shapes):
            raise MXNetError(f"{self.name}: expected "
                             f"{len(self._in_shapes)} inputs")
        if len(outs) != len(self._out_struct):
            raise MXNetError(f"{self.name}: expected "
                             f"{len(self._out_struct)} outputs, "
                             f"got {len(outs)}")
        vals = [a.asjax() for a in ins]
        for v, (nm, shp, dt) in zip(vals, self._in_shapes):
            if tuple(v.shape) != shp:
                raise MXNetError(f"{self.name}: input {nm!r} shape "
                                 f"{v.shape} != declared {shp}")
        for i, (o, st) in enumerate(zip(outs, self._out_struct)):
            if tuple(o.shape) != tuple(st.shape):
                raise MXNetError(f"{self.name}: output {i} shape "
                                 f"{tuple(o.shape)} != declared "
                                 f"{tuple(st.shape)}")
        results = self._fn(*vals)
        if not isinstance(results, (list, tuple)):
            results = [results]
        for dst, r in zip(outs, results):
            dst._set(r)
        return outs


def register_pallas_op(name, kernel, out_shapes, inputs=("data",),
                       vjp_kernel=None, grid=None, in_specs=None,
                       out_specs=None, vjp_grid=None, vjp_in_specs=None,
                       vjp_out_specs=None, attr_spec=None,
                       reference=None):
    """Register a Pallas kernel as a graph operator.

    Parameters
    ----------
    kernel : fn(attrs) -> pallas kernel fn(*in_refs, *out_refs). Attrs are
        closed over so hyper-parameters stay compile-time scalars.
    out_shapes : fn(attrs, in_shapes) -> list of (shape, dtype-str|None);
        None dtype inherits input 0's dtype.
    vjp_kernel : optional fn(attrs) -> pallas kernel for the backward:
        fn(*in_refs, *cotangent_refs, *grad_refs). When given, the op is
        differentiable and the executor's jax.vjp sees a custom_vjp.
    grid / in_specs / out_specs : tiling for the forward call; each may be
        a value or fn(attrs, in_shapes). A tiled op MUST also tile its
        backward: vjp_grid/vjp_in_specs/vjp_out_specs (the vjp kernel's
        inputs are *vals + *cotangents, outputs one grad per input);
        omitting them for a gridded forward raises at registration.
    reference : optional XLA composition ``fn(attrs, *inputs) -> out``
        with the kernel's exact semantics. When given, the op registers
        with the reference as its ``forward`` and the Pallas kernel as
        the ``variants["pallas"]`` alternative — the SAME fallback +
        numerics-gate codepath the built-in production kernels use
        (kernel_tier.py): ``MXNET_KERNEL_TIER=xla`` forces the
        reference, ``auto`` autotunes per shape on TPU, and
        ``kernel_tier.numerics_gate`` can verify the pair. Without a
        reference the Pallas kernel is the only implementation and runs
        under every tier (interpret mode off-TPU).
    """
    if vjp_kernel is not None and grid is not None and vjp_grid is None:
        raise MXNetError(
            f"pallas op {name!r}: forward is tiled (grid=...) but the vjp "
            "has no vjp_grid — a whole-array backward would overflow VMEM "
            "or misread tile-shaped refs; pass vjp_grid/vjp_in_specs/"
            "vjp_out_specs")

    def _resolve(spec, attrs, in_shapes):
        return spec(attrs, in_shapes) if callable(spec) else spec

    def _build_call(attrs, in_vals):
        in_shapes = [tuple(v.shape) for v in in_vals]
        outs = []
        for shp, dt in out_shapes(attrs, in_shapes):
            outs.append(jax.ShapeDtypeStruct(
                tuple(shp), np.dtype(dt) if dt else in_vals[0].dtype))
        kwargs = {}
        for k, spec in (("grid", grid), ("in_specs", in_specs),
                        ("out_specs", out_specs)):
            if spec is not None:
                kwargs[k] = _resolve(spec, attrs, in_shapes)
        return pallas_call(kernel(attrs), out_shape=outs, **kwargs), outs

    # cache compiled callables per (attrs, input shapes/dtypes): eager
    # call sites would otherwise re-trace the kernel (and rebuild the
    # custom_vjp wrapper) on every invocation
    _cache = {}

    def _cache_key(attrs, in_vals):
        try:
            akey = tuple(sorted(attrs.items()))
            hash(akey)
        except TypeError:
            return None
        return (akey, tuple((tuple(v.shape), str(v.dtype))
                            for v in in_vals))

    def _make_op(attrs):
        if vjp_kernel is None:
            def op(*vals):
                call, _ = _build_call(attrs, vals)
                out = call(*vals)
                return tuple(out) if isinstance(out, (list, tuple)) else out
            return op

        @jax.custom_vjp
        def op(*vals):
            call, _ = _build_call(attrs, vals)
            out = call(*vals)
            return tuple(out) if isinstance(out, (list, tuple)) else out

        def fwd(*vals):
            return op(*vals), vals

        def bwd(vals, cts):
            if not isinstance(cts, (list, tuple)):
                cts = (cts,)
            in_shapes = [tuple(v.shape) for v in vals]
            grads_struct = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                            for v in vals]
            kwargs = {}
            for k, spec in (("grid", vjp_grid),
                            ("in_specs", vjp_in_specs),
                            ("out_specs", vjp_out_specs)):
                if spec is not None:
                    kwargs[k] = _resolve(spec, attrs, in_shapes)
            bw = pallas_call(vjp_kernel(attrs), out_shape=grads_struct,
                             **kwargs)
            return tuple(bw(*vals, *cts))

        op.defvjp(fwd, bwd)
        return op

    def simple_forward(attrs, *in_vals):
        key = _cache_key(attrs, in_vals)
        op = _cache.get(key) if key is not None else None
        if op is None:
            op = jax.jit(_make_op(attrs))
            if key is not None:
                _cache[key] = op
        return op(*in_vals)

    if reference is None:
        return _register_op(name, inputs=inputs, simple=simple_forward,
                            attr_spec=attr_spec or {})

    # with a reference composition, the user kernel rides the SAME
    # variants/tier mechanism as the built-in production kernels
    def pallas_variant(attrs, in_list, aux, is_train, rng):
        out = simple_forward(attrs, *in_list)
        if isinstance(out, (tuple, list)):
            return list(out), []
        return [out], []

    return _register_op(name, inputs=inputs, simple=reference,
                        attr_spec=attr_spec or {},
                        variants={"pallas": pallas_variant})


# --------------------------------------------------------------------------
# built-in: fused SGD-momentum update (the reference ships this fused on
# the GPU as sgd_mom_update, optimizer_op.cc:17-60). The kernel itself is
# PROMOTED to ops/pallas_kernels.py as a production variant of the
# sgd_mom_update registry op; this public op name keeps the explicit
# surface — forward is the XLA composition, the Pallas kernel rides the
# variants table, so MXNET_KERNEL_TIER selects per backend/shape like
# every other tiered op. Same convention as ops/optimizer_op.py:
# g = wd*w + clip(rescale*grad); mom' = momentum*mom - lr*g;
# weight' = weight + mom'.
# --------------------------------------------------------------------------
def _register_builtin():
    if "pallas_sgd_mom_update" in OP_REGISTRY:
        return

    def _hyper(attrs):
        return dict(
            lr=float(attrs["lr"]),
            momentum=float(attrs.get("momentum", 0.0)),
            wd=float(attrs.get("wd", 0.0)),
            rescale_grad=float(attrs.get("rescale_grad", 1.0)),
            clip_gradient=attrs.get("clip_gradient"))

    def xla_forward(attrs, weight, grad, mom):
        h = _hyper(attrs)
        g = grad * h["rescale_grad"]
        if h["clip_gradient"] is not None and \
                float(h["clip_gradient"]) > 0:
            c = float(h["clip_gradient"])
            g = jnp.clip(g, -c, c)
        g = g + h["wd"] * weight
        new_m = h["momentum"] * mom - h["lr"] * g
        return weight + new_m, new_m

    def pallas_variant(attrs, inputs, aux, is_train, rng):
        w, g, m = inputs
        return list(pallas_sgd_mom_update(w, g, m, **_hyper(attrs))), []

    # 3 inputs + 2 outputs resident as (256, 128) f32 tiles
    kspec = {"tiles": [((256, 128), "float32")] * 5,
             "dtypes": ("float32", "bfloat16", "float16")}
    _register_op("pallas_sgd_mom_update",
                 inputs=("weight", "grad", "mom"),
                 simple=xla_forward, num_outputs=2,
                 output_names=["weight_out", "mom_out"],
                 attr_spec={"lr": (float, None),
                            "momentum": (float, 0.0),
                            "wd": (float, 0.0),
                            "rescale_grad": (float, 1.0),
                            "clip_gradient": (lambda v: float(v), None)},
                 variants={"pallas": (pallas_variant, None, kspec)})


_register_builtin()


# --------------------------------------------------------------------------
# built-in: flash attention (the framework's marquee Pallas kernel — the
# reference's attention-era gap filled TPU-first). Forward is a Pallas
# online-softmax kernel on a (batch*heads, q blocks, k blocks) grid: K/V
# are tiled *through the grid* so VMEM only ever holds one
# (block, D) tile of each (running max/normalizer/accumulator persist in
# VMEM scratch across the sequential k dimension). Backward recomputes
# attention via the XLA composition under jax.custom_vjp (flash recompute
# strategy — no T x T tensor is ever stored for fwd). ``partial=True``
# returns the *unnormalized* (acc, m, l) triple instead, which is what
# ring attention (parallel/ring_attention.py) folds into its cross-device
# online-softmax carry — the kernel is the local block of the ring.
# --------------------------------------------------------------------------
def _flash_kernel(block_q, block_k, causal, scale, partial=False):
    def kernel(offs_ref, q_ref, k_ref, v_ref, *refs):
        # offs_ref: scalar-prefetch (2,) int32 — absolute sequence offsets
        # of this q shard and k shard (zero for self-attention; ring-step
        # shard offsets in partial mode, where device order = seq order)
        if partial:
            o_ref, m_ref, l_ref, m_s, l_s, acc_s = refs
        else:
            o_ref, m_s, l_s, acc_s = refs
        qi = pl.program_id(1)
        kb = pl.program_id(2)
        n_kb = pl.num_programs(2)

        @pl.when(kb == 0)
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
            l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
            acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        q_start = offs_ref[0] + qi * block_q
        k_start = offs_ref[1] + kb * block_k

        def update():
            q = q_ref[...].astype(jnp.float32) * scale
            k = k_ref[...].astype(jnp.float32)
            v = v_ref[...].astype(jnp.float32)
            # HIGHEST: match the XLA composition's f32 accumulation (the
            # default would multiply in bf16 on the MXU)
            s = jnp.dot(q, k.T, precision=jax.lax.Precision.HIGHEST)
            if causal:
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = k_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
            m = m_s[...]                       # (block_q, 1) f32
            m_blk = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m, m_blk)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            m_s[...] = m_new
            l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * corr + jnp.dot(
                p, v, precision=jax.lax.Precision.HIGHEST)

        if causal:
            # K blocks wholly above the diagonal contribute nothing —
            # skip their FLOPs instead of exp(-inf)-ing them
            pl.when(k_start <= q_start + block_q - 1)(update)
        else:
            update()

        @pl.when(kb == n_kb - 1)
        def _emit():
            if partial:
                o_ref[...] = acc_s[...].astype(o_ref.dtype)
                m_ref[...] = m_s[...]
                l_ref[...] = l_s[...]
            else:
                l = jnp.maximum(l_s[...], 1e-30)
                o_ref[...] = (acc_s[...] / l).astype(o_ref.dtype)
    return kernel


def _flash_call(qf, kf, vf, q_off, k_off, causal, scale, block_q, block_k,
                partial=False):
    """Launch the flash kernel on flattened (BH, T, D) operands.

    Returns the normalized output, or in partial mode the unnormalized
    (acc, m, l) with m/l shaped (BH, Tq, 1) float32.
    """
    from jax.experimental.pallas import tpu as pltpu

    BH, Tq, D = qf.shape
    Tk = kf.shape[1]
    grid = (BH, Tq // block_q, Tk // block_k)
    # index maps take the grid ids plus the scalar-prefetch ref (unused)
    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i, j, offs: (b, i, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j, offs: (b, j, 0)),
        pl.BlockSpec((None, block_k, D), lambda b, i, j, offs: (b, j, 0)),
    ]
    o_spec = pl.BlockSpec((None, block_q, D), lambda b, i, j, offs: (b, i, 0))
    ml_spec = pl.BlockSpec((None, block_q, 1), lambda b, i, j, offs: (b, i, 0))
    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, D), jnp.float32)]
    # under shard_map (ring attention) outputs vary over the same mesh
    # axes as the operands — propagate vma so check_vma stays on
    vma = jax.typeof(qf).vma | jax.typeof(kf).vma | jax.typeof(vf).vma

    def _struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    if partial:
        out_shape = [_struct((BH, Tq, D), jnp.float32),
                     _struct((BH, Tq, 1), jnp.float32),
                     _struct((BH, Tq, 1), jnp.float32)]
        out_specs = [o_spec, ml_spec, ml_spec]
    else:
        out_shape = _struct((BH, Tq, D), qf.dtype)
        out_specs = o_spec
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch)
    offs = jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])
    if vma:
        # match the tensor operands' varying axes (pallas requires all
        # operands to agree under shard_map's check_vma)
        missing = tuple(vma - jax.typeof(offs).vma)
        if missing:
            offs = jax.lax.pvary(offs, missing)
    return pallas_call(
        _flash_kernel(block_q, block_k, causal, scale, partial),
        out_shape=out_shape, grid_spec=grid_spec)(offs, qf, kf, vf)


def flash_attention_partial(q, k, v, q_off, k_off, causal=False,
                            block_q=128, block_k=128, scale=None):
    """Unnormalized flash attention block for ring composition.

    q: (B, H, Tq, D) local query shard; k/v: (B, H, Tk, D) the K/V shard
    currently held. ``q_off``/``k_off`` are the shards' absolute sequence
    offsets (traced values are fine — they ride the kernel's scalar
    prefetch). Returns (acc, m, l): acc (B,H,Tq,D) f32 unnormalized,
    m/l (B,H,Tq) f32 running max / normalizer — exactly the carry terms
    of the online softmax, mergeable across shards.
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        raise MXNetError("flash_attention_partial: T must divide blocks")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    acc, m, l = _flash_call(
        q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D), q_off, k_off, causal, scale,
        block_q, block_k, partial=True)
    return (acc.reshape(B, H, Tq, D), m.reshape(B, H, Tq),
            l.reshape(B, H, Tq))


def flash_attention(q, k, v, causal=False, block_q=128, block_k=128):
    """Pallas flash attention. q/k/v: (B, H, T, D) -> (B, H, T, D).

    Differentiable: backward recomputes standard attention (XLA) under
    custom_vjp, so training numerics match ``parallel.ring_attention
    .attention`` while forward never materializes the (T, T) matrix.
    """
    from .parallel.ring_attention import attention as _xla_attention

    B, H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    if T % block_q or T % block_k:
        raise MXNetError(f"flash_attention: T={T} must be a multiple of "
                         f"block sizes ({block_q}, {block_k})")
    scale = 1.0 / float(np.sqrt(D))

    @jax.custom_vjp
    def _flash(q, k, v):
        out = _flash_call(
            q.reshape(B * H, T, D), k.reshape(B * H, T, D),
            v.reshape(B * H, T, D), 0, 0, causal, scale, block_q, block_k)
        return out.reshape(B, H, T, D)

    def fwd(q, k, v):
        return _flash(q, k, v), (q, k, v)

    def bwd(res, ct):
        q, k, v = res
        _, vjp_fn = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, causal=causal), q, k, v)
        return vjp_fn(ct)

    _flash.defvjp(fwd, bwd)
    return _flash(q, k, v)


def _attention_xla_forward(attrs, q, k, v):
    # the exact composition the flash kernel is gated against; the tier
    # autotunes between the two instead of trusting the kernel's name
    from .base import parse_bool
    from .parallel.ring_attention import attention as xla_attention
    return xla_attention(q, k, v,
                         causal=parse_bool(attrs.get("causal", False)))


def _attention_pallas_variant(attrs, inputs, aux, is_train, rng):
    from .base import parse_bool
    q, k, v = inputs
    out = flash_attention(q, k, v,
                          causal=parse_bool(attrs.get("causal",
                                                      False)),
                          block_q=int(attrs.get("block_q", 128)),
                          block_k=int(attrs.get("block_k", 128)))
    return [out], []


def _attention_eligible(attrs, in_shapes, in_dtypes):
    if len(in_shapes[0]) != 4:
        return False
    t = in_shapes[0][2]
    if in_shapes[0][3] > 512:
        # q/k/v/acc blocks keep whole head rows in VMEM — the declared
        # _ATTENTION_KSPEC tile bound (PK901's eligibility side)
        return False
    bq = min(int(attrs.get("block_q", 128)), t)
    bk = min(int(attrs.get("block_k", 128)), t)
    return t % bq == 0 and t % bk == 0


_ATTENTION_ATTRS = {"causal": (None, False),
                    "block_q": (int, 128),
                    "block_k": (int, 128)}

#: q/k/v blocks plus the f32 accumulator at the d <= 512 bound
_ATTENTION_KSPEC = {
    "tiles": [((128, 512), "float32")] * 4,
    "dtypes": ("float32", "bfloat16", "float16"),
}


def _register_flash():
    if "pallas_flash_attention" in OP_REGISTRY:
        return
    _register_op("pallas_flash_attention", inputs=("q", "k", "v"),
                 simple=_attention_xla_forward,
                 attr_spec=dict(_ATTENTION_ATTRS),
                 variants={"pallas": (_attention_pallas_variant,
                                      _attention_eligible,
                                      _ATTENTION_KSPEC)})


def _attention_ring_variant(attrs, inputs, aux, is_train, rng):
    """Sequence-sharded lowering: ring attention over the active
    SpmdPlan's ``seq`` mesh axis (parallel/ring_attention.py — K/V
    shards rotate over ``lax.ppermute``, flash-style online softmax).
    Runs inside ``kernel_tier``'s plan_scope, so the mesh and axis
    names come from the binding's plan; the shard_map composes inside
    the jitted program and XLA partitions everything around it."""
    import functools
    from .base import parse_bool
    from .parallel import spmd as _spmd
    from .parallel.collectives import shard_map as _shard_map
    from .parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    plan = _spmd.active_plan()
    if plan is None:
        raise MXNetError("attention ring variant dispatched without an "
                         "active SpmdPlan (kernel_tier arms the scope)")
    q, k, v = inputs
    causal = parse_bool(attrs.get("causal", False))
    seq_ax = plan.seq_axis
    batch_ax = plan.data_axis if (plan.n_data_shards() > 1 and
                                  q.shape[0] % plan.n_data_shards() == 0) \
        else None
    spec = P(batch_ax, None, seq_ax, None)
    run = _shard_map(
        functools.partial(ring_attention, axis_name=seq_ax, causal=causal),
        mesh=plan.mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return [run(q, k, v)], []


def _attention_ring_eligible(attrs, in_shapes, in_dtypes):
    """Eligible only under an active seq-sharded plan whose shard counts
    divide (B, T); self-attention shapes only (q/k/v agree)."""
    from .parallel import spmd as _spmd
    plan = _spmd.active_plan()
    if plan is None:
        return False
    n_seq = plan.n_seq_shards()
    if n_seq <= 1:
        return False
    if len(in_shapes) < 3 or len(in_shapes[0]) != 4:
        return False
    if not (tuple(in_shapes[0]) == tuple(in_shapes[1])
            == tuple(in_shapes[2])):
        return False
    b, _h, t, _d = in_shapes[0]
    if t < n_seq or t % n_seq:
        return False
    nd = plan.n_data_shards()
    return not (nd > 1 and b % nd)


def _register_attention():
    """``attention``: the graph-level attention OpDef the transformer
    workload (ROADMAP 1) binds, with THREE gated lowerings:

    * ``xla`` — the exact composition (``parallel.ring_attention
      .attention``), always present, always correct;
    * ``pallas`` — the flash kernel (fused lowering), numerics-gated and
      autotuned per shape by kernel_tier on TPU;
    * ``ring`` — the sequence-sharded lowering: when the binding's
      SpmdPlan carries a nonempty ``seq`` mesh axis, the op lowers to
      ring attention over ``lax.ppermute`` (kernel_tier selects it from
      the plan; ``MXNET_KERNEL_TIER=xla`` still forces the composition).
    """
    if "attention" in OP_REGISTRY:
        return
    _register_op("attention", inputs=("q", "k", "v"),
                 simple=_attention_xla_forward,
                 shape_passthrough=True,
                 attr_spec=dict(_ATTENTION_ATTRS),
                 variants={"pallas": (_attention_pallas_variant,
                                      _attention_eligible,
                                      _ATTENTION_KSPEC),
                           "ring": (_attention_ring_variant,
                                    _attention_ring_eligible)})


# --------------------------------------------------------------------------
# attention_decode: the KV-cache inference path. The cache is op AUX
# state carried through the executor (fixed capacity, f32/compute-width
# K/V arrays + an int32 cursor), read AND written on inference forwards
# (OpDef.stateful_infer) — N incremental single-token steps reproduce
# the length-N full-sequence forward.
#
# Two cursor layouts, one op:
#
# * scalar (default) — ONE (1,) cursor: all B rows decode the same
#   sequence position (the single-session KVCacheDecoder path);
# * ``per_slot=True`` — a (B, 1) int32 cursor VECTOR: each batch row is
#   an independent decode *slot* at its own position in its own slice
#   of the slot-pooled (B, H, C, Dh) cache. Every write, the S=1 step's
#   and the S>1 windows' (chunked prefill, speculative verify), is the
#   same per-slot window update (``_write_rows``): slot b's S rows land
#   at its own cursor inside its own ``[b]``, untouched positions keep
#   their bytes, and a slot with no room for S rows writes nothing. The
#   op donates its aux arrays to the step program (``donate_aux``), so
#   the pools are updated in place: a step moves the rows it writes,
#   not the pool. The causal mask is per slot AND per window offset
#   (key_pos <= cursor[b] + s), and the softmax runs over each slot's
#   own prefix — so ONE pinned program advances B independent staggered
#   sequences by S tokens per dispatch. A retired slot keeps advancing
#   harmlessly (its row is garbage nobody reads, and past the capacity
#   it writes nothing); rejoining resets only the
#   cursor, because positions beyond a slot's prefix are exp(-inf)-
#   masked to exactly zero weight and every attended position has been
#   rewritten by the new sequence before its first read — slot reuse is
#   bit-clean without touching the cache rows.
#
# Four more things a slot-pooled graph can ask of the op, each by an
# attribute whose default leaves the programs above as they are:
#
# * ``kv_heads`` — grouped K/V heads: q has H heads, k and v and the
#   pools ``kv_heads`` of them, and query head i reads K/V head
#   ``i // (H // kv_heads)``;
# * ``scale`` — the scores' multiplier where a model states one in place
#   of ``1 / sqrt(head width)``;
# * ``window`` — a sliding layer: the query at t attends
#   ``t - window < j <= t``. With ``ring`` (rows) its pools are RINGS of
#   that many rows, position t at row ``t % ring``, whatever the
#   ``capacity`` (which stays the context's bound): family ``"ring"``,
#   cells ``k_ring`` / ``v_ring``. ``ring`` is at least ``window`` + the
#   largest dispatch's rows, so that the S rows a dispatch writes fall
#   on rows no query of it, or after it, attends;
# * ``fed`` — a fourth input ``fed`` (B,) int32: how many of each
#   slot's S rows are real. The cursor advances by that (by 0 where S
#   rows do not fit under the capacity), so nothing runs ahead and
#   nothing is rewound after a window; the pads' rows are written
#   behind the cursor, where the next dispatch writes over them;
# * ``block`` — a model that decodes by blocks of that many positions
#   (counted from position 0): the query at t attends every key up to
#   the END of its own block, ``j < (t // block + 1) * block``, and
#   never past the rows the dispatch wrote (``j < cursor + fed``). Inside
#   a block every position sees every other; across blocks the mask is
#   the causal one. S is a multiple of ``block``; no window, no ring.
# --------------------------------------------------------------------------
_Geometry = namedtuple("_Geometry",
                       "groups window ring fed capacity scale block",
                       defaults=(0,))


def _decode_geometry(attrs, q, k_cache):
    """What the attributes ask beyond a pool of a row per position and
    head (the comment above)."""
    from .base import parse_bool
    H, Hkv = q.shape[1], k_cache.shape[1]
    window, ring = (int(attrs.get(k) or 0) for k in ("window", "ring"))
    fed = parse_bool(attrs.get("fed") or False)
    if H % Hkv or (ring and not window) \
            or (ring and ring < window + q.shape[2]):
        raise MXNetError(
            f"attention_decode: {H} query heads on {Hkv} K/V heads, "
            f"window {window}, ring {ring}, {q.shape[2]} rows a dispatch: "
            "the K/V heads divide the query heads, and a ring holds its "
            "window and one dispatch's rows")
    if (H != Hkv or window or fed or attrs.get("scale") is not None) \
            and not parse_bool(attrs.get("per_slot", False)):
        raise MXNetError("attention_decode: kv_heads, window, fed and scale "
                         "are the slot-pooled lowering's (per_slot=True)")
    scale = attrs.get("scale")
    block = int(attrs.get("block") or 0)
    if block and (window or ring
                  or q.shape[2] != 1 and q.shape[2] % block
                  or not parse_bool(attrs.get("per_slot", False))):
        raise MXNetError(
            f"attention_decode: block={block} with window {window}, ring "
            f"{ring}, {q.shape[2]} rows a dispatch: a model that decodes "
            "by blocks has no window= and no ring=, its dispatches are "
            "whole blocks (S a multiple of block, or the one row of the "
            "S = 1 program) and its graph is the slot-pooled one "
            "(per_slot=True)")
    return _Geometry(H // Hkv, window, ring, fed,
                     int(attrs.get("capacity", 256)),
                     None if scale is None else float(scale), block)


def _fed_cursor(geo, pos, S, fed):
    """``(rows really fed (B,), the new cursor (B, 1))`` of a graph
    with a ``fed`` input: each slot's count, none where S rows do not
    fit under the capacity - the write's own rule."""
    fed = jnp.where(pos + S <= geo.capacity,
                    jnp.clip(fed.reshape(pos.shape).astype(jnp.int32), 0, S),
                    0)
    return fed, (pos + fed).reshape((-1, 1)).astype(jnp.int32)


def _decode_check_overflow(pos, S, capacity, per_slot):
    """Overflow raises cleanly whenever the cursor is concrete (eager
    dispatch); jitted paths enforce it host-side via the decode drivers
    (models.transformer) for every live sequence. Inside a program the
    scalar layout's dynamic_update_slice would clamp the write; the
    per-slot write drops it (``_write_rows``)."""
    if isinstance(pos, jax.core.Tracer):
        return
    if per_slot:
        over = [int(i) for i in np.nonzero(
            np.asarray(pos) + S > capacity)[0]]
        if over:
            raise MXNetError(
                f"attention_decode: cache overflow in slot(s) {over} "
                f"(cursor + {S} > capacity {capacity}); retire the "
                "sequence or re-bind with a larger capacity=")
    elif int(pos) + S > capacity:
        raise MXNetError(
            f"attention_decode: cache overflow (pos {int(pos)} + {S} new "
            f"tokens > capacity {capacity}); re-bind with a larger "
            "capacity= or reset the cache")


def _write_rows(news, pools, pos):
    """The per-slot cache write, for every S: land each slot's S new
    rows ``news[i][b]`` at its own cursor ``pos[b]`` inside its own
    ``pools[i][b]``, one window update a slot, and return the pools (a
    layer's K and V ride one call). The pools are donated to the step
    program (``donate_aux``), so the updates are in place and a step
    moves S rows a slot, not the pool. A slot only ever writes its own
    ``[b, :, :, :]``; a slot whose S rows do not fit below the capacity
    writes nothing (``FILL_OR_DROP``: a ``dynamic_update_slice`` would
    clamp the write onto live rows).

    This is the composition's lowering. For the TPU the compiler turns
    it into a loop of one ``dynamic-update-slice`` a slot (it re-lays no
    pool, which it does for a gather of the old rows): in place, but 48
    such loops cost a 24-layer S=1 step 2.1 ms on a v5e. The Pallas
    variant lands the same rows by the same rule through one kernel a
    layer (``pallas_kernels.cache_write``, 0.4 ms for those 24)."""
    at = jnp.stack([jnp.arange(pos.shape[0], dtype=jnp.int32),
                    pos.astype(jnp.int32)], axis=1)         # (B, 2)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 2))
    return [jax.lax.scatter(pool, at, new, dnums, indices_are_sorted=True,
                            unique_indices=True,
                            mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
            for new, pool in zip(news, pools)]


def _write_ring(news, pools, pos):
    """The write of a sliding layer's rings (the composition's
    lowering; ``pallas_kernels.cache_write(ring=True)`` is the kernel of
    it): slot b's row s lands at ``(pos[b] + s) % ring`` of its own
    ``pools[i][b]``, row by row since the rows wrap. Every slot writes:
    what its S rows cover is S positions older than any window reaches."""
    B, S = news[0].shape[0], news[0].shape[2]
    at = (pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]) \
        % pools[0].shape[2]                                    # (B, S)
    slot = jnp.arange(B, dtype=jnp.int32)[:, None]
    return [pool.at[slot, :, at].set(new.transpose(0, 2, 1, 3))
            for new, pool in zip(news, pools)]


def _decode_rope_write(attrs, q, k, v, k_cache, v_cache, pos, per_slot,
                       write=None):
    """RoPE + cache write, shared by the XLA composition and the Pallas
    decode variant, so the cache contents stay bit-identical across
    tiers. ``pos`` is a scalar (single-session) or a (B,) vector (slot
    pool); ``write`` is the tier's lowering of the per-slot write
    (None: ``_write_rows``, the composition's; the Pallas variant hands
    in its kernel of the same signature). Returns the rotated q and
    the updated caches."""
    from .base import parse_bool, parse_float
    from .ops.nn import rope_apply

    S = q.shape[2]
    if parse_bool(attrs.get("rope", False)):
        base = parse_float(attrs.get("rope_base", 10000.0))
        if per_slot:
            positions = pos[:, None] + jnp.arange(S)[None, :]   # (B, S)
        else:
            positions = pos + jnp.arange(S)
        q = rope_apply(q, positions, base)
        k = rope_apply(k, positions, base)
    k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
    if per_slot:
        k_cache, v_cache = (write or (
            _write_ring if int(attrs.get("ring") or 0) else _write_rows))(
            [k, v], [k_cache, v_cache], pos)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, 0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, 0, pos, 0))
    return q, k_cache, v_cache


def _attention_decode_fwd(attrs, inputs, aux, is_train, rng):
    from .base import parse_bool

    q, k, v = inputs[:3]                   # (B, H, S, Dh), S new tokens
    k_cache, v_cache, cursor = aux         # (B,H,C,Dh) x2 + cursor
    if is_train:
        raise MXNetError("attention_decode is an inference op (train "
                         "with the full-sequence `attention` graph)")
    geo = _decode_geometry(attrs, q, k_cache)
    if parse_bool(attrs.get("per_slot", False)):
        return _attention_decode_per_slot(
            attrs, q, k, v, k_cache, v_cache, cursor, geo,
            inputs[3] if geo.fed else None)
    B, H, S, Dh = q.shape
    capacity = k_cache.shape[2]
    pos = cursor.reshape(()).astype(jnp.int32)
    _decode_check_overflow(pos, S, capacity, per_slot=False)
    scale = 1.0 / float(np.sqrt(Dh))
    q, k_cache, v_cache = _decode_rope_write(attrs, q, k, v, k_cache,
                                             v_cache, pos,
                                             per_slot=False)
    # same numerics shape as the full forward (ring_attention.attention):
    # f32 logits at HIGHEST precision, -inf causal mask, f32 softmax
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache.astype(q.dtype),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) * scale
    key_pos = jnp.arange(capacity)[None, :]
    q_pos = (pos + jnp.arange(S))[:, None]
    mask = key_pos <= q_pos                           # (S, C)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs,
                     v_cache.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    new_cursor = (pos + S).reshape((1,)).astype(jnp.int32)
    return [out.astype(q.dtype)], [k_cache, v_cache, new_cursor]


def _attention_decode_per_slot(attrs, q, k, v, k_cache, v_cache, cursor,
                               geo, fed=None):
    """The slot-pooled lowering: cursor (B, 1), an S-token window per
    slot. S=1 is the steady-state decode program, S>1 the chunked-
    prefill / speculative-verify window; in both each slot writes its S
    tokens at its OWN cursor (``_write_rows``) and the causal mask runs
    over ``cursor[b] + arange(S)``, so one pinned program advances B
    staggered sequences by S positions per dispatch. ``geo``
    (``_decode_geometry``): a group's query heads go as further rows of
    their K/V head, a window adds the mask's lower bound, a ring reads
    each row's position off the newest one written."""
    B, H, S, Dh = q.shape
    pool_rows = k_cache.shape[2]
    pos = cursor.reshape((B,)).astype(jnp.int32)          # (B,)
    _decode_check_overflow(pos, S, geo.capacity if geo.ring else pool_rows,
                           per_slot=True)
    scale = 1.0 / float(np.sqrt(Dh)) if geo.scale is None else geo.scale
    q, k_cache, v_cache = _decode_rope_write(attrs, q, k, v, k_cache,
                                             v_cache, pos, per_slot=True)
    if geo.groups > 1:
        q = q.reshape(B, k_cache.shape[1], geo.groups * S, Dh)
    key_pos = jnp.arange(pool_rows)                        # (C,)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache.astype(q.dtype),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32) * scale
    # per-slot causal mask: query s of slot b sits at stream position
    # cursor[b] + s and attends key_pos <= that — within-window
    # causality falls out of the same comparison
    q_pos = pos[:, None] + jnp.arange(S)[None, :]          # (B, S)
    if geo.groups > 1:
        q_pos = jnp.tile(q_pos, (1, geo.groups))
    if geo.ring:
        # row a of a ring holds the newest position at or before the
        # last one written that lies there (negative: not yet filled)
        last = (pos + (S - 1))[:, None]
        key_pos = (last - (last - key_pos[None, :]) % geo.ring)[:, None, :]
        mask = (key_pos <= q_pos[:, :, None]) & (key_pos >= 0)
    elif geo.block:
        # up to the end of the query's own block, and never past what
        # this dispatch wrote
        written = pos + (S if fed is None else
                         _fed_cursor(geo, pos, S, fed)[0])
        edge = jnp.minimum((q_pos // geo.block + 1) * geo.block,
                           jnp.maximum(written, pos + 1)[:, None])
        mask = key_pos[None, None, :] < edge[:, :, None]
    else:
        mask = key_pos[None, None, :] <= q_pos[:, :, None]
    if geo.window:
        mask = mask & (key_pos > q_pos[:, :, None] - geo.window)
    mask = mask[:, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs,
                     v_cache.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    if geo.groups > 1:
        out = out.reshape(B, H, S, Dh)
    if geo.fed:
        new_cursor = _fed_cursor(geo, pos, S, fed)[1]
    else:
        new_cursor = (pos + S).reshape((B, 1)).astype(jnp.int32)
    return [out.astype(q.dtype)], [k_cache, v_cache, new_cursor]


def _attention_decode_pallas_variant(attrs, inputs, aux, is_train, rng):
    """Flash-decode lowering. RoPE is the shared XLA helper; the
    per-slot cache write is ``pallas_kernels.cache_write`` (the rows
    and the rule of ``_write_rows``, so the cache contents are
    bit-identical across tiers; only the aligned blocks that hold the
    new rows move); the attention READ — the cache-bandwidth-bound
    part — is ``pallas_kernels.decode_attention`` (``decode_attn`` in
    the device trace, lowered once a step program), whose grid step
    takes a group of heads and a long key block of one slot and whose
    scalar-prefetched cursor bounds the K/V blocks actually fetched
    from HBM to the live prefix ``[0, cursor_b + S)`` instead of the
    full capacity. A window of more than ``_DECODE_ROWS`` query rows a
    K/V head is read by ``pallas_kernels.window_attention``
    (``window_attn``), which tiles the queries too - but for its slots
    fed one row, which take the S = 1 read inside the window program
    (``window_attn_ride``)."""
    from functools import partial
    from .base import parse_bool
    from .ops.pallas_kernels import (cache_write, decode_attention,
                                     window_attention)

    q, k, v = inputs[:3]
    k_cache, v_cache, cursor = aux
    if is_train:
        raise MXNetError("attention_decode is an inference op (train "
                         "with the full-sequence `attention` graph)")
    B, H, S, Dh = q.shape
    geo = _decode_geometry(attrs, q, k_cache)
    capacity = geo.capacity if geo.ring else k_cache.shape[2]
    per_slot = parse_bool(attrs.get("per_slot", False))
    fed = None
    if per_slot:
        pos = cursor.reshape((B,)).astype(jnp.int32)
        if geo.fed:
            fed, new_cursor = _fed_cursor(geo, pos, S, inputs[3])
        else:
            new_cursor = (pos + S).reshape((B, 1)).astype(jnp.int32)
    else:
        pos = cursor.reshape(()).astype(jnp.int32)
        new_cursor = (pos + S).reshape((1,)).astype(jnp.int32)
    _decode_check_overflow(pos, S, capacity, per_slot=per_slot)
    q, k_cache, v_cache = _decode_rope_write(
        attrs, q, k, v, k_cache, v_cache, pos, per_slot=per_slot,
        write=partial(cache_write, ring=True) if geo.ring else cache_write)
    # the kernel is row-cursor uniform: the scalar layout is the
    # per-slot layout with every row at the same position
    pos_rows = pos if per_slot else jnp.broadcast_to(pos, (B,))
    geometry = _read_geometry(geo)
    if geo.groups * S <= _DECODE_ROWS:
        out = decode_attention(q, k_cache, v_cache, pos_rows, **geometry)
    elif fed is None or geo.block:
        # a long window: the read that tiles the queries too (a graph
        # that decodes by blocks feeds whole blocks: nobody rides with
        # one row)
        out = window_attention(
            q, k_cache, v_cache, pos_rows,
            jnp.full((B,), S, jnp.int32) if fed is None else fed,
            **geometry)
    else:
        # which read a slot of a long window takes is read from ``fed``:
        # a slot fed one row (a decoding slot riding a window in which
        # another prefills) is dead to ``window_attn``, where a whole
        # query block would be scored for it, and is read as the one
        # query it is, as in the S = 1 program (``window_attn_ride``,
        # to which every other slot is dead); the window's zeros in its
        # row 0 take the result
        riding = fed == 1
        out = window_attention(q, k_cache, v_cache, pos_rows,
                               jnp.where(riding, 0, fed), **geometry)
        ride = decode_attention(q[:, :, :1], k_cache, v_cache, pos_rows,
                                riding=riding, **geometry)
        row = jnp.where(riding[:, None, None, None], ride.astype(out.dtype),
                        out[:, :, :1])
        out = jax.lax.dynamic_update_slice(out, row, (0, 0, 0, 0))
    return [out.astype(q.dtype)], [k_cache, v_cache, new_cursor]


#: query rows of one K/V head (a group's heads x S) that ``decode_attn``
#: keeps resident; a longer window goes to ``window_attn``
_DECODE_ROWS = 64


def _read_geometry(geo):
    """The reads' keywords: none where the pool is a row per position,
    every key at or before the query is attended and the scores are
    scaled by the head's width."""
    more = {} if geo.scale is None else {"scale": geo.scale}
    if geo.block:
        more["block"] = geo.block
    return {"window": geo.window, "ring": bool(geo.ring), **more} \
        if geo.window else more


def _attention_decode_eligible(attrs, in_shapes, in_dtypes):
    """Decode windows up to the declared kspec bounds: up to 64 query
    rows of a K/V head (its group's heads x S) resident, Dh <= 512,
    cache blocks tiling the capacity (``decode_attn`` sizes the group
    and the block inside them); past that, a window of whole sublane
    tiles at a lane-aligned head (``window_attn``, which tiles the
    queries too). The cache may be the compute width or an fp8 storage
    dtype (dequantized in-kernel on read). On a real TPU the head dim
    must be lane-aligned; interpret mode (off-TPU parity tests) takes
    any."""
    from .ops.pallas_kernels import _interpret
    n_in = len(in_shapes) - 3
    if n_in < 3 or len(in_shapes[0]) != 4 or len(in_shapes[n_in]) != 4:
        return False
    b, h, s, dh = in_shapes[0]
    hkv, c = in_shapes[n_in][1:3]
    if dh > 512 or c < 1 or h % hkv:
        return False
    if h // hkv * s > _DECODE_ROWS and (
            s % (8 if _interpret() else 128) or dh > 256):
        return False
    if str(in_dtypes[0]) not in ("float32", "bfloat16", "float16"):
        return False
    if str(in_dtypes[n_in]) not in ("float32", "bfloat16", "float16",
                                    "float8_e4m3fn", "float8_e5m2"):
        return False
    return (dh % 128 == 0 and c % 128 == 0) or _interpret()


def _attention_decode_infer(attrs, in_shapes):
    from .base import parse_bool
    q_s = in_shapes[0]
    c = int(attrs.get("ring") or 0) or int(attrs.get("capacity", 256))
    per_slot = parse_bool(attrs.get("per_slot", False))
    fed = parse_bool(attrs.get("fed") or False)
    if q_s is None:
        return in_shapes, [None], [None, None,
                                   None if per_slot else (1,)]
    b, h, s, dh = q_s
    kv_s = (b, int(attrs.get("kv_heads") or 0) or h, s, dh)
    cache = kv_s[:2] + (c, dh)
    cur = (b, 1) if per_slot else (1,)
    return [q_s, kv_s, kv_s] + ([(b,)] if fed else []), [q_s], \
        [cache, cache, cur]


def _attention_decode_inputs(attrs):
    from .base import parse_bool
    return ("q", "k", "v") + (
        ("fed",) if parse_bool((attrs or {}).get("fed") or False) else ())


def _attention_decode_aux(attrs):
    """A sliding layer's rings are cells of another name, and so of
    another family (``slot_state``), than a pool of a row per
    position."""
    if int((attrs or {}).get("ring") or 0):
        return ("k_ring", "v_ring", "cache_pos")
    return ("k_cache", "v_cache", "cache_pos")


#: the S>1 window path (chunked prefill / speculative verify): q and
#: the f32 out accumulator hold one 64-token chunk of head rows while
#: two cache blocks stream K-major — declared and PK9xx-validated at
#: registration so the decode window variant is gated by the same
#: import-time contract as the Pallas kernels, even while its lowering
#: is the XLA composition
_ATTENTION_DECODE_KSPEC = {
    "tiles": [((64, 512), "float32"),      # q window (S=64 x Dh<=512)
              ((128, 512), "float32"),     # k_cache block
              ((128, 512), "float32"),     # v_cache block
              ((64, 512), "float32")],     # f32 out accumulator
    "dtypes": ("float32", "bfloat16", "float16"),
}

#: the flash-decode kernel's worst-case VMEM set at the eligibility
#: bounds (S<=64, Dh<=512, a float32 cache), where
#: ``pallas_kernels._decode_attn_blocks`` shrinks the head group to one
#: head and keeps 512 keys a step under ``_READ_VMEM_BUDGET``: the q
#: window, a K and a V block and the out window, each double-buffered,
#: the f32 m/l/acc scratch, and one head's working set (its K and V
#: block widened, the scores, the mask, p and its parts). Narrower
#: dtypes and smaller shapes trade the room for more heads a step (16
#: heads x 512 keys of bfloat16 at Dh 128: 8.8 MiB at S=1). fp8 cache
#: dtypes are in the gate set — the kernel dequantizes storage rows on
#: read. (The write kernel before it sizes its own blocks, aligned to
#: every dtype's sublanes, under ``pallas_kernels._WRITE_BLOCK_BUDGET``.)
_ATTENTION_DECODE_PALLAS_KSPEC = {
    "tiles": [((2, 64, 512), "float32"),     # q window, both buffers
              ((2, 512, 512), "float32"),    # k_cache block, both buffers
              ((2, 512, 512), "float32"),    # v_cache block, both buffers
              ((64, 512), "float32"),        # acc scratch
              ((2, 64, 128), "float32"),     # m + l scratch (lane-padded)
              ((2, 64, 512), "float32"),     # out window, both buffers
              ((2, 512, 512), "float32"),    # one head's K and V, widened
              ((8, 64, 512), "float32")],    # its scores, mask, p, parts
    "dtypes": ("float32", "bfloat16", "float16",
               "float8_e4m3fn", "float8_e5m2"),
}

#: aliases accepted by the ``cache_dtype`` attr (fp8 KV storage)
_CACHE_DTYPE_ALIASES = {"fp8": "float8_e4m3fn",
                        "e4m3": "float8_e4m3fn",
                        "e5m2": "float8_e5m2"}


def _cache_dtype_of(attrs):
    """Resolve the declared KV-cache storage dtype, or None for the
    default (compute-width) cells. Used as a callable aux_dtypes entry
    so only non-default graphs stamp ``__dtype__`` on the cache cells —
    existing serialized graphs stay byte-identical."""
    val = str(attrs.get("cache_dtype", "") or "").strip()
    if not val:
        return None
    return _CACHE_DTYPE_ALIASES.get(val, val)


#: what one execution reads of its K/V pools, for each fed slot's last
#: query (``OpDef.state_reads``): the positions at or before it, the
#: rows the pools hold and the positions it attends. live / capacity is
#: the share of the pools the traffic keeps live, attended / live what
#: the windows leave of the keys
_ATTENTION_DECODE_COUNTS = read_counts(
    ("attn.live_rows", "attn_live"), ("attn.capacity_rows", None),
    ("attn.attended_rows", "attn_attended"))


def _attention_decode_reads(attrs, capacity, sources):
    """Live rows are clipped to the capacity (a slot with no room
    writes nothing and stays); the pools' rows are counted for every
    slot, fed or not: ``slots x capacity``, or ``x`` a ring's rows; a
    sliding layer attends at most its window."""
    window = int(attrs.get("window") or 0)
    rows = int(attrs.get("ring") or 0) or capacity

    def reads(pos, fed):
        live = np.minimum((pos + fed)[fed > 0], capacity)
        total = int(live.sum())
        return {"attn.live_rows": total,
                "attn.capacity_rows": pos.size * rows,
                "attn.attended_rows":
                    int(np.minimum(live, window).sum()) if window else total}

    return reads


def _register_attention_decode():
    if "attention_decode" in OP_REGISTRY:
        return
    from .analysis.kernelcheck import validate_kernel_spec
    validate_kernel_spec("attention_decode", "window",
                         _ATTENTION_DECODE_KSPEC)
    _register_op("attention_decode", inputs=_attention_decode_inputs,
                 aux=_attention_decode_aux,
                 full=_attention_decode_fwd,
                 stateful_infer=True, donate_aux=True,
                 aux_dtypes={"cache_pos": "int32",
                             "k_cache": _cache_dtype_of,
                             "v_cache": _cache_dtype_of,
                             "k_ring": _cache_dtype_of,
                             "v_ring": _cache_dtype_of},
                 infer_shape=_attention_decode_infer,
                 slot_state={"k_cache": "rows", "v_cache": "rows",
                             "k_ring": "ring", "v_ring": "ring",
                             "cache_pos": "cursor"},
                 state_reads=(_ATTENTION_DECODE_COUNTS,
                              _attention_decode_reads),
                 attr_spec={"capacity": (int, 256),
                            "rope": (None, False),
                            "rope_base": (float, 10000.0),
                            "per_slot": (None, False),
                            "cache_dtype": (str, ""),
                            # absent unless a graph asks: an
                            # existing graph's attributes stay as
                            # they are
                            "kv_heads": (int, None),
                            "window": (int, None),
                            "ring": (int, None),
                            "fed": (None, None),
                            "scale": (float, None),
                            "block": (int, None)},
                 variants={"pallas": (_attention_decode_pallas_variant,
                                      _attention_decode_eligible,
                                      _ATTENTION_DECODE_PALLAS_KSPEC)})


_register_flash()
_register_attention()
_register_attention_decode()

# rtc's ops register after ops/cost.py's import-time pass — re-seed so
# pallas_sgd_mom_update / pallas_flash_attention carry their estimators
from .ops import cost as _cost          # noqa: E402
_cost.seed_costs()
