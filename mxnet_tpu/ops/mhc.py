"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): the read (``mhc_pre``) and the
join (``mhc_post``) of a residual stream of ``n`` copies.

A token's stream is ``X`` in R^{n x C}, kept as ONE row of ``n * C``
numbers ``[X_0 | X_1 | .. | X_{n-1}]`` (a ``(rows, n, C)`` array would
put ``n`` = 4 on the TPU's sublanes and quadruple every pass over it).
A sub-layer ``F`` does not read ``x`` and add to it: it reads a mix of
the copies and is written back beside a mix of them, and the mixes are
functions of the token's whole stream. With the sub-layer's own ``W``
in R^{(2n + n^2) x nC}, ``b`` in R^{2n + n^2}, ``a`` in R^3 (the rows of
``W`` and ``b``: ``n`` for the read, ``n`` for the write-back, ``n^2``
for the mix, row-major):

    r     = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)      one statistic, no gain
    Hpre  = sigmoid(a_0 (W_pre r) + b_pre)                            (n,)
    Hpost = 2 sigmoid(a_1 (W_post r) + b_post)                        (n,)
    M     = exp(clip(a_2 mat(W_res r) + b_res, clamp_min, clamp_max)) (n, n)
    ``iters`` times:  M <- M / (rowsum(M) + eps);  M <- M / (colsum(M) + eps)
    Hres  = M                                  doubly stochastic up to ``eps``
    u     = sum_i Hpre[i] X_i                               what F reads
    X'_i  = sum_j Hres[i, j] X_j + Hpost[i] F(u)            the join

``mhc_pre(X, W, b, a) -> (u, Hpost, Hres)`` is everything above ``u``
and ``u``; ``mhc_post(X, y, Hpost, Hres) -> X'`` is the last line. ``X``
is ``(.., n C)`` and ``u`` ``(.., C)`` with the same leading axes, ``y``
any array of as many rows of ``C``; ``Hpost (rows, n)`` and ``Hres
(rows, n n)`` are float32 whatever the stream's dtype. Both ops are
row-wise: in a window's packed view (``ops/rows.py``) they run over the
packed rows like the norms.

**Precision.** The stream is held at the compute width; the statistic,
the projection (the stream's own numbers against ``W``'s, accumulated in
float32 and scaled by the statistic afterwards: the same sum as ``W r``),
the sigmoids, ``exp``, the Sinkhorn iterations and the accumulation of
``u`` and ``X'`` are float32.

**Lowerings.** ``forward`` is the plain ``jax.numpy`` statement of the
above. The ``pallas`` variants (kernels ``mhc_pre`` and ``mhc_post`` in
the device trace) take a block of rows through VMEM once: ``mhc_pre``
reads ``X`` (``n C`` numbers a row) and writes ``u`` (``C``) and the 20
numbers of the mapping, ``mhc_post`` reads ``X``, ``y`` and the mapping
and writes ``X'`` - ``14 C`` numbers a row a sub-layer between them,
against the ``10 C`` of a join fused with the next read, which reads
``X'`` out of VMEM and not back from HBM (not built: ROADMAP B10). The
Sinkhorn iterations run on 16 vectors of one number a row; where a
block is whole tiles of 128 rows they are laid along the lanes (one
transpose of the logits there, one of the mapping back), else - the
S = 1 program's handful of rows - down the sublanes as they come.

What a dispatch mixes is declared, not found out by name
(``OpDef.state_reads``): ``mhc_pre`` counts one row for every token a
slot is fed, under the counter ``serve.decode.mhc.rows`` and the ring
field ``mhc_rows`` (rows x sub-layers a dispatch).
"""
from __future__ import annotations

from math import prod

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import parse_float, parse_int
from . import pallas_kernels as _pk
from .moe import cpu_wide
from .registry import read_counts, register

__all__ = ["mapping", "sinkhorn"]

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_VMEM_LIMIT = 96 << 20
#: rows a block of ``mhc_pre`` takes where there are whole blocks of it
#: (the transposes want whole tiles), and of ``mhc_post``, whose blocks
#: are the stream's twice over
_ROWS, _POST_ROWS = 128, 64


#: ``mhc_pre``'s attributes, in ``_geometry``'s order: the copies, the
#: Sinkhorn rounds and their epsilon, the statistic's, the clamp's ends
_ATTRS = {"n": (parse_int, None), "iters": (parse_int, 20),
          "eps": (parse_float, 1e-6), "rms_eps": (parse_float, 1e-6),
          "clamp_min": (parse_float, -30.0),
          "clamp_max": (parse_float, 30.0)}


def _geometry(attrs):
    return tuple(parse(attrs.get(key, default))
                 for key, (parse, default) in _ATTRS.items())


def _rows(shape):
    """The rows of a stream ``(.., n C)``: every axis but the last."""
    return prod(shape[:-1])


def sinkhorn(m, iters, eps):
    """``iters`` rounds of row then column normalisation of the matrix
    ``m[i][j]`` (lists of equally shaped arrays, one entry of every
    token's matrix each): ``M / (rowsum + eps)``, then ``M / (colsum +
    eps)``, the divisions as one reciprocal a sum."""
    n = len(m)
    for _ in range(iters):
        for i in range(n):
            inv = 1.0 / (sum(m[i][1:], m[i][0]) + eps)
            m[i] = [v * inv for v in m[i]]
        for j in range(n):
            inv = 1.0 / (sum((m[i][j] for i in range(1, n)), m[0][j]) + eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
    return m


def mapping(logits, n, iters, eps, lo, hi):
    """``(Hpre, Hpost, Hres)`` as lists of ``n``, ``n`` and ``n n``
    arrays from the ``2n + n^2`` logits ``a (W r) + b`` (a list of
    equally shaped float32 arrays: the module docstring's equations,
    entry by entry, whatever axis the tokens lie along)."""
    hpre = [jax.nn.sigmoid(v) for v in logits[:n]]
    hpost = [2.0 * jax.nn.sigmoid(v) for v in logits[n:2 * n]]
    m = [[jnp.exp(jnp.clip(logits[2 * n + n * i + j], lo, hi))
          for j in range(n)] for i in range(n)]
    m = sinkhorn(m, iters, eps)
    return hpre, hpost, [m[i][j] for i in range(n) for j in range(n)]


def _scales(a, n):
    """``a (3,)`` as the factor of each of the ``2n + n^2`` logits."""
    a = a.astype(_F32).reshape(3)
    return jnp.concatenate([jnp.broadcast_to(a[i], (m,))
                            for i, m in enumerate((n, n, n * n))])


# ------------------------------------------------------------ the read
def _pre_fwd(attrs, x, w, b, a):
    n, iters, eps, rms_eps, lo, hi = _geometry(attrs)
    C = x.shape[-1] // n
    rows = x.reshape(-1, n * C)
    x32 = rows.astype(_F32)
    inv = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                    + rms_eps)
    lhs, rhs = cpu_wide(rows, w.astype(rows.dtype))
    proj = jnp.dot(lhs, rhs.T, preferred_element_type=_F32,
                   precision=_HI if rows.dtype == _F32 else None) * inv
    logits = proj * _scales(a, n) + b.astype(_F32)
    hpre, hpost, hres = mapping(
        [logits[:, c] for c in range(2 * n + n * n)], n, iters, eps, lo, hi)
    u = sum(hpre[i][:, None] * x32[:, i * C:(i + 1) * C] for i in range(n))
    return (u.astype(x.dtype).reshape(x.shape[:-1] + (C,)),
            jnp.stack(hpost, axis=-1), jnp.stack(hres, axis=-1))


def _pre_kernel(n, C, iters, eps, rms_eps, lo, hi, dense):
    """One block of rows: the statistic and the projection in one pass
    over the block's ``n`` copies, the mapping, ``u`` in a second pass
    over the same VMEM. ``dense``: the block is whole tiles of 128 rows
    and the mapping's vectors are laid along the lanes."""
    k = 2 * n + n * n

    def kernel(x_ref, w_ref, b_ref, a_ref, u_ref, hpost_ref, hres_ref,
               *scratch):
        tr = x_ref.shape[0]
        ss = jnp.zeros((tr, 1), _F32)
        proj = jnp.zeros((tr, k), _F32)
        for i in range(n):
            xi = x_ref[:, i * C:(i + 1) * C]
            x32 = xi.astype(_F32)
            ss = ss + jnp.sum(x32 * x32, axis=1, keepdims=True)
            proj = proj + lax.dot_general(
                xi, w_ref[:, i * C:(i + 1) * C], (((1,), (1,)), ((), ())),
                preferred_element_type=_F32,
                precision=_HI if xi.dtype == _F32 else None)
        inv = lax.rsqrt(ss * (1.0 / (n * C)) + rms_eps)
        lane = lax.broadcasted_iota(jnp.int32, (1, k), 1)
        av = a_ref[...].astype(_F32)
        scale = jnp.where(lane < n, av[:, 0:1],
                          jnp.where(lane < 2 * n, av[:, 1:2], av[:, 2:3]))
        logits = proj * inv * scale + b_ref[...].astype(_F32)   # (tr, k)
        if dense:
            wide, tall = scratch           # lanes past k: never read
            wide[:, 0:k] = logits
            tall[...] = wide[...].T                             # (128, tr)
            hpre, hpost, hres = mapping(
                [tall[c:c + 1, :] for c in range(k)], n, iters, eps, lo, hi)
            for c, v in enumerate(hpre + hpost + hres):
                tall[c:c + 1, :] = v
            wide[...] = tall[...].T                             # (tr, 128)
            hpre = [wide[:, i:i + 1] for i in range(n)]
            hpost_ref[...] = wide[:, n:2 * n]
            hres_ref[...] = wide[:, 2 * n:k]
        else:
            hpre, hpost, hres = mapping(
                [logits[:, c:c + 1] for c in range(k)], n, iters, eps, lo, hi)
            for i, v in enumerate(hpost):
                hpost_ref[:, i:i + 1] = v
            for i, v in enumerate(hres):
                hres_ref[:, i:i + 1] = v
        u = hpre[0] * x_ref[:, 0:C].astype(_F32)
        for i in range(1, n):
            u = u + hpre[i] * x_ref[:, i * C:(i + 1) * C].astype(_F32)
        u_ref[...] = u.astype(u_ref.dtype)

    return kernel


def _row_block(rows, block=_ROWS):
    return block if rows % _ROWS == 0 else rows


def _compiler_params():
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT)}


def _pre_pallas(attrs, inputs, aux, is_train, rng):
    x, w, b, a = inputs
    n, iters, eps, rms_eps, lo, hi = _geometry(attrs)
    C = x.shape[-1] // n
    k = 2 * n + n * n
    rows = x.reshape(-1, n * C)
    N = rows.shape[0]
    tr = _row_block(N)
    dense = tr == _ROWS
    by_row = lambda width: pl.BlockSpec((tr, width),      # noqa: E731
                                        lambda r: (r, 0))
    whole = lambda shape: pl.BlockSpec(shape,             # noqa: E731
                                       lambda r: (0, 0))
    u, hpost, hres = _pk.pallas_call(
        _pre_kernel(n, C, iters, eps, rms_eps, lo, hi, dense),
        name="mhc_pre",
        out_shape=(jax.ShapeDtypeStruct((N, C), x.dtype),
                   jax.ShapeDtypeStruct((N, n), _F32),
                   jax.ShapeDtypeStruct((N, n * n), _F32)),
        grid=(N // tr,),
        in_specs=[by_row(n * C), whole((k, n * C)), whole((1, k)),
                  whole((1, 3))],
        out_specs=(by_row(C), by_row(n), by_row(n * n)),
        scratch_shapes=[pltpu.VMEM((tr, 128), _F32),
                        pltpu.VMEM((128, tr), _F32)] if dense else [],
        **_compiler_params())(
            rows, w.astype(x.dtype), b.reshape(1, k), a.reshape(1, 3))
    return [u.reshape(x.shape[:-1] + (C,)), hpost, hres], []


def _eligible(attrs, in_shapes, in_dtypes):
    """Rows in whole blocks of 128, or few enough for one block; a copy
    in whole lanes; ``2n + n^2`` logits inside one tile."""
    if str(in_dtypes[0]) not in ("float32", "bfloat16"):
        return False
    if _pk._interpret():
        return True
    n = parse_int(attrs["n"])
    width, rows = in_shapes[0][-1], _rows(in_shapes[0])
    return (width // n) % 128 == 0 and 2 * n + n * n <= 128 \
        and (rows % _ROWS == 0 or rows <= 64)


def _pre_infer(attrs, in_shapes):
    x_s = in_shapes[0]
    n = parse_int(attrs["n"])
    k = 2 * n + n * n
    if x_s is None:
        return in_shapes, [None, None, None], []
    if x_s[-1] % n:
        raise ValueError(f"mhc_pre: a stream of {x_s[-1]} numbers a row is "
                         f"not {n} copies")
    rows = _rows(x_s)
    return ([x_s, (k, x_s[-1]), (k,), (3,)],
            [tuple(x_s[:-1]) + (x_s[-1] // n,), (rows, n), (rows, n * n)],
            [])


#: one block at the published sizes (128 rows of 4 copies of 3,584): the
#: stream's block and ``u``'s, double-buffered, ``W``, a copy in
#: float32 and the mapping's two tiles
_PRE_KSPEC = {
    "tiles": [((128, 14336), "bfloat16")] * 2
    + [((128, 3584), "bfloat16")] * 2 + [((32, 14336), "bfloat16")] * 2
    + [((128, 3584), "float32")] * 3 + [((128, 128), "float32")] * 4,
    "dtypes": ("float32", "bfloat16"),
}

#: what a dispatch mixes (``OpDef.state_reads``): a row for every token
#: a slot is fed, once for each ``mhc_pre`` of the graph - rows x
#: sub-layers a dispatch
_MHC_COUNTS = read_counts(("mhc.rows", "mhc_rows"))


def _pre_reads(attrs, capacity, sources):
    return lambda pos, fed: {"mhc.rows": int(fed.sum())}


def _scoped(name, fwd):
    """``fwd`` as an op's plain lowering under ``jax.named_scope(name)``:
    the operations XLA makes of it carry the name in the device trace,
    as the kernels carry theirs."""
    def forward(attrs, inputs, aux, is_train, rng):
        with jax.named_scope(name):
            out = fwd(attrs, *inputs)
        return list(out) if isinstance(out, tuple) else [out], []
    return forward


register("mhc_pre", inputs=("data", "weight", "bias", "scale"),
         full=_scoped("mhc_pre", _pre_fwd),
         num_outputs=3, output_names=["output", "post", "res"],
         attr_spec=dict(_ATTRS), infer_shape=_pre_infer,
         state_reads=(_MHC_COUNTS, _pre_reads),
         variants={"pallas": (_pre_pallas, _eligible, _PRE_KSPEC)},
         doc="The read of a stream of n copies under hyper-connections: "
             "the mix a sub-layer reads and the mapping its output is "
             "joined by (ops/mhc.py).")


# ------------------------------------------------------------ the join
def _joined(x, y32, hpost, hres, n, C, i):
    """Copy ``i`` of the joined stream in float32: ``Hpost[i] y + sum_j
    Hres[i, j] X_j``, ``x`` an array or a kernel's reference of the
    rows' ``n C`` numbers."""
    acc = hpost[:, i:i + 1] * y32
    for j in range(n):
        acc = acc + hres[:, n * i + j:n * i + j + 1] \
            * x[:, j * C:(j + 1) * C].astype(_F32)
    return acc


def _post_fwd(attrs, x, y, hpost, hres):
    n = parse_int(attrs["n"])
    C = x.shape[-1] // n
    rows, y32 = x.reshape(-1, n * C), y.reshape(-1, C).astype(_F32)
    parts = [_joined(rows, y32, hpost, hres, n, C, i) for i in range(n)]
    return jnp.concatenate(parts, axis=-1).astype(x.dtype).reshape(x.shape)


def _post_kernel(n, C):
    def kernel(x_ref, y_ref, hpost_ref, hres_ref, o_ref):
        hpost, hres = hpost_ref[...], hres_ref[...]
        y32 = y_ref[...].astype(_F32)
        for i in range(n):
            o_ref[:, i * C:(i + 1) * C] = _joined(
                x_ref, y32, hpost, hres, n, C, i).astype(o_ref.dtype)

    return kernel


def _post_pallas(attrs, inputs, aux, is_train, rng):
    x, y, hpost, hres = inputs
    n = parse_int(attrs["n"])
    C = x.shape[-1] // n
    rows = x.reshape(-1, n * C)
    N = rows.shape[0]
    tr = _row_block(N, _POST_ROWS)
    by_row = lambda width: pl.BlockSpec((tr, width),      # noqa: E731
                                        lambda r: (r, 0))
    out = _pk.pallas_call(
        _post_kernel(n, C), name="mhc_post",
        out_shape=jax.ShapeDtypeStruct((N, n * C), x.dtype),
        grid=(N // tr,),
        in_specs=[by_row(n * C), by_row(C), by_row(n), by_row(n * n)],
        out_specs=by_row(n * C), **_compiler_params())(
            rows, y.reshape(N, C).astype(x.dtype), hpost, hres)
    return [out.reshape(x.shape)], []


def _post_infer(attrs, in_shapes):
    x_s = in_shapes[0]
    n = parse_int(attrs["n"])
    if x_s is None:
        return in_shapes, [None], []
    rows = _rows(x_s)
    y_s = in_shapes[1]
    if y_s is not None and prod(y_s) != rows * (x_s[-1] // n):
        raise ValueError(f"mhc_post: a sub-layer's output {y_s} is not "
                         f"{rows} rows of {x_s[-1] // n}")
    return [x_s, y_s, (rows, n), (rows, n * n)], [x_s], []


_POST_KSPEC = {
    "tiles": [((64, 14336), "bfloat16")] * 4
    + [((64, 3584), "bfloat16")] * 2 + [((64, 3584), "float32")] * 6
    + [((64, 128), "float32")] * 4,
    "dtypes": ("float32", "bfloat16"),
}

register("mhc_post", inputs=("data", "sublayer", "post", "res"),
         full=_scoped("mhc_post", _post_fwd),
         attr_spec={"n": (parse_int, None)}, infer_shape=_post_infer,
         variants={"pallas": (_post_pallas, _eligible, _POST_KSPEC)},
         doc="The join of a stream of n copies under hyper-connections: "
             "the copies mixed by Hres beside the sub-layer's output "
             "weighted by Hpost (ops/mhc.py).")

