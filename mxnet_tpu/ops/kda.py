"""Kimi Delta Attention's recurrent part with its decode state
(``kda_mixer_decode``): three causal depthwise convolutions over a
token's last ``d_conv`` inputs and a delta-rule update of one matrix a
head under a decay a key channel (KDA, the Kimi Linear report,
arXiv:2510.26692), for slot-pooled serving.

Per token ``t`` of one sequence, ``H`` heads of ``D`` key and ``D``
value channels, the row ``[q~ | k~ | v~ | f | g | b]_t`` (``5 H D + H``
numbers) being the mixer's input projections:

    [q, k, v]_t = silu(sum_j w_conv[:, j] * [q~, k~, v~]_{t-(K-1)+j})
    q_t[h] = q_t[h] / |q_t[h]| * D^-0.5          k_t[h] = k_t[h] / |k_t[h]|
    log a_t = lower_bound * sigmoid(exp(A_log[h]) * (f_t + dt_bias))
    b_t[h] = sigmoid(b_t)[h]
    S_t[h] = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1}[h] + b_t k_t v_t^T
    o_t[h] = S_t[h]^T q_t[h]
    out_t  = gamma * rmsnorm_D(o_t[h]) * sigmoid(g_t)[h]

``a_t`` a vector of ``D`` a head, ``S`` in ``R^{D x D}`` (key x value),
``|x| = sqrt(sum x^2 + 1e-6)``; all of it in float32 whatever the rows'
dtype. Unlike ``ssm_mixer_decode``'s update this one READS the state
through the key before it writes (the erase ``k_t^T Diag(a_t) S``). The
output projection stays in the graph.

**State**, slot-pooled, two families no cursor indexes (``slot_state``):

    conv_tail  (slots, K - 1, 3 H D)   float32   family "conv": the last
               K - 1 inputs of the three convolutions, the newest last
    kda_state  (slots, H, D, D)        float32   family "recurrent": S,
               keys down the sublanes and values along the lanes, so
               that both contractions over the key are sums down
               sublanes and land lane-dense
    cache_pos  (slots, 1)              int32     family "cursor"

**A slot whose cursor is 0 at the start of a dispatch reads both as
zeros**, whatever its last occupant left. The state is constant in the
context: 2.1 MB a slot and layer at the published sizes (32 heads of
128 x 128), rewritten whole by every dispatch (``donate_aux``).

**Rows** are ``ops/ssm.py``'s: ``data (rows, width)`` is every slot's S
rows or a budget of the real ones, ``fed (slots,)`` says how many of a
slot's S tokens are real (``ssm.fed_rows_layout``). Three costs:

* a slot fed ONE row takes one step of the recurrence (``kda_update``):
  one read and one write of its state;
* a slot fed more takes the chunked (WY) form, ``chunk`` rows a trip
  (``kda_chunk``). With ``G_i = sum_{j<=i} log a_j`` inside the chunk,
  rows as matrices, ``Kb = Diag(b) K``, ``Vb = Diag(b) V``:

      N = strict_lower((Kb e^G) (K e^-G)^T)       A = (I + N)^-1
      W = A (Kb e^G)      U = A Vb      V' = U - W S_in
      O = (Q e^G) S_in + tril((Q e^G) (K e^-G)^T) V'
      S_out = Diag(e^{G_C}) S_in + (K e^{G_C - G})^T V'

  ``e^{G_i - G_j}`` is never formed from ``e^{-G}`` (a log decay goes
  down to ``lower_bound`` a token: 64 rows reach e^320): rows go in
  sub-blocks of 16, a sub-block's rows are scaled from its first row's
  ``G`` and the keys they meet towards it, so every exponent is at most
  ``-16 lower_bound`` = 80 and what underflows is below float32 beside
  what it is added to. ``A`` is the product form of the inverse of a
  unit lower-triangular matrix: the diagonal 16 x 16 blocks by ``(I -
  N)(I + N^2)(I + N^4)(I + N^8)`` (exact: ``N^16 = 0``), the blocks
  below them the same way over the block structure. A ragged last
  chunk's pads are steps with ``a = 1`` and ``b = 0``;
* a slot fed nothing keeps state, tails and cursor.

``forward`` is plain ``jax.numpy``. The ``pallas`` variant replaces the
step by the kernel ``kda_update`` (every slot's state through VMEM
once, in place) and a chunk's arithmetic by the kernel ``kda_chunk``
(one head a grid step, its five blocks of lanes of the rows as they
lie; the running sum of log decays, the pads and the scaled keys are
made inside, so that a trip of XLA's loop is a slice of the rows, the
kernel and two updates in place); the prologue
(``kda_conv``) is shared. The three names are ``jax.named_scope``s too;
in the device trace only the two kernels are operations of their own
names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError, parse_float, parse_int
from . import pallas_kernels as _pk
from .registry import read_counts, register
from .rows import _at
from .ssm import causal_conv_rows, fed_rows_layout

__all__ = ["kda_recurrence", "kda_chunk_math"]

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
#: rows of a sub-block of the chunked form; ``-_SUB lower_bound`` must
#: stay an exponent float32 holds (80 at the published -5)
_SUB = 16
_EXP_MAX = 80.0
_NORM_EPS = 1e-6


def _geometry(attrs):
    geo = tuple(parse_int(attrs[k]) for k in (
        "heads", "head_dim", "d_conv", "chunk", "step_len", "capacity"))
    if min(geo) < 1 or geo[2] < 2:
        raise MXNetError(f"kda_mixer_decode: sizes {geo} (heads, head_dim, "
                         "d_conv >= 2, chunk, step_len, capacity)")
    chunk, S = geo[3], geo[4]
    if S > 1 and chunk > _SUB and chunk % _SUB:
        raise MXNetError(f"kda_mixer_decode: a chunk of {chunk} rows is "
                         f"not whole sub-blocks of {_SUB}")
    if not 0.0 <= -_SUB * _lower_bound(attrs) <= _EXP_MAX:
        raise MXNetError(
            f"kda_mixer_decode: lower_bound {_lower_bound(attrs)} is "
            f"positive, or over {_SUB} rows passes e^{_EXP_MAX:.0f}")
    return geo


def _lower_bound(attrs):
    return parse_float(attrs.get("lower_bound", -5.0))


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=_F32)


def _dot_nt(a, b):
    """``a b^T``."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _dot_tn(a, b):
    """``a^T b``."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _unit(x, H, D):
    """Each head's ``D`` numbers of the rows ``x (rows, H D)`` over
    their 2-norm."""
    x = x.reshape(x.shape[0], H, D)
    x = x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _NORM_EPS)
    return x.reshape(x.shape[0], H * D)


def _prologue(attrs, inputs, aux, is_train):
    """What both lowerings share before they touch the state
    (``kda_conv``): where each slot's real rows lie, the convolutions
    over those rows and the slot's tail, the new tail, and per row the
    normed ``q`` (scaled) and ``k``, ``v``, the log decay ``g`` (0 on a
    pad) and ``b`` (0 on a pad) in float32, side by side in ``feat``."""
    if is_train:
        raise MXNetError("kda_mixer_decode is an inference op")
    data, fed, conv_w, a_log, dt_bias, _gamma = inputs
    tail, _state, cursor = aux
    H, D, _K, _chunk, S, capacity = _geometry(attrs)
    HD, NR = H * D, data.shape[0]
    lay = fed_rows_layout(fed, cursor, NR, S, capacity)
    valid = lay["valid"][:, None]
    tail = jnp.where(lay["fresh"][:, None, None], 0.0, tail.astype(_F32))
    conv, new_tail = causal_conv_rows(data[:, :3 * HD].astype(_F32),
                                      conv_w, tail, lay)
    act = jax.nn.silu(conv)
    q = _unit(act[:, :HD], H, D) * float(D) ** -0.5
    k = _unit(act[:, HD:2 * HD], H, D)
    rate = jnp.repeat(jnp.exp(a_log.astype(_F32)), D)        # (H D,)
    g = _lower_bound(attrs) * jax.nn.sigmoid(
        rate[None, :] * (data[:, 3 * HD:4 * HD].astype(_F32)
                         + dt_bias.astype(_F32)[None, :]))
    beta = jax.nn.sigmoid(data[:, 5 * HD:].astype(_F32))     # (NR, H)
    # b along its head's D lanes, so that a head's five operands are
    # five blocks of lanes of one array
    feat = jnp.concatenate(
        [q, k, act[:, 2 * HD:], jnp.where(valid, g, 0.0),
         jnp.repeat(jnp.where(valid, beta, 0.0), D, axis=1)],
        axis=1)                                    # [q | k | v | g | b]
    return dict(H=H, D=D, S=S, NR=NR, pos=lay["pos"], fed=lay["fed"],
                off=lay["off"], idx=lay["idx"], feat=feat,
                new_tail=new_tail)


def _split(feat, H, D):
    """``q, k, v, g, b (rows, H, D)`` of ``feat`` (``b`` the same along
    a head's D)."""
    HD = H * D
    return tuple(feat[:, j * HD:(j + 1) * HD].reshape(-1, H, D)
                 for j in range(5))


def _step_operands(p):
    """The one row of every slot that is fed exactly one, picked out of
    the rows by a one-hot product: ``q, k, v, a (slots, H, D)`` and ``b
    (slots, H)`` (``a`` 1 and ``b`` 0 for a slot fed any other number:
    its state passes); and ``lay``, the matrix that lays a slot's
    result back at its row."""
    one = p["fed"] == 1
    lay = (p["idx"][None, :] == p["off"][:, None]) & one[:, None]
    lay = lay.astype(_F32)                                   # (slots, NR)
    q, k, v, g, b = _split(_dot(lay, p["feat"]), p["H"], p["D"])
    return (q, k, v, jnp.exp(g), b[..., 0]), lay


def _update_xla(state, pos, q, k, v, a, b):
    """One step of the recurrence for every slot, a slot at cursor 0
    from zeros: ``(state', o (slots, H, D))``."""
    s0 = jnp.where((pos == 0)[:, None, None, None], 0.0, state)
    sd = a[..., None] * s0
    delta = b[..., None] * (v - jnp.sum(k[..., None] * sd, axis=2))
    sn = sd + k[..., None] * delta[:, :, None, :]
    return sn, jnp.sum(q[..., None] * sn, axis=2)


def kda_recurrence(q, k, v, g, b, s0):
    """The recurrence of the module docstring, step by step, for one
    sequence: ``q``, ``k``, ``v``, ``g`` (log decays) ``(T, H, D)``,
    ``b (T, H)``, ``s0 (H, D, D)`` -> ``(o (T, H, D), the last
    state)``. What the chunked form is tested against."""
    def step(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        sd = jnp.exp(g_t)[..., None] * s
        delta = b_t[:, None] * (v_t - jnp.sum(k_t[..., None] * sd, axis=1))
        s = sd + k_t[..., None] * delta[:, None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=1)
    s, o = lax.scan(step, s0.astype(_F32), (q, k, v, g, b))
    return o, s


def _block_of(i, sub):
    """``i // sub``, as a shift where ``sub`` is a power of two (what a
    kernel's integer arithmetic has)."""
    if sub & (sub - 1):
        return i // sub
    return lax.shift_right_logical(i, sub.bit_length() - 1)


def _series_inverse(m, eye, order):
    """``(I + m)^-1`` of a matrix with ``m^order = 0``: ``(I - m)(I +
    m^2)(I + m^4)...`` up to the last power below ``order``, exact."""
    inv, power = eye - m, m
    for _ in range(max(0, math.ceil(math.log2(max(order, 1))) - 1)):
        power = _dot(power, power)
        inv = _dot(inv, eye + power)
    return inv


def _column(row):
    """A row ``(1, n)`` stood up as a column ``(n, 1)`` by a sum along
    the diagonal (what a kernel has in place of a transpose of one
    row)."""
    n = row.shape[1]
    i = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    j = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(i == j, row, 0.0), axis=1, keepdims=True)


def kda_chunk_math(q, k, v, g, b, s_in, sub=_SUB):
    """One chunk of one head in the chunked form (module docstring):
    ``q``, ``k``, ``v``, the log decays ``g`` (0 on a pad) and ``b``
    (the same along a row; 0 on a pad) ``(C, D)``, the incoming state
    ``s_in (D, D)`` -> ``(o (C, D), the state after the chunk)``.
    Two-dimensional products and elementwise arithmetic alone: the body
    of the kernel ``kda_chunk`` and, under ``vmap``, of the plain
    forward."""
    C = q.shape[0]
    sub = min(sub, C)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _dot((i >= j).astype(_F32), g)           # the running log decay
    kb, vb = k * b, v * b
    mqk, mkk = [], []
    for r in range(0, C, sub):
        first = G[r:r + 1, :]                    # the sub-block's anchor
        own = jnp.exp(G[r:r + sub] - first)                  # <= 1
        met = k * jnp.exp(jnp.minimum(first - G, _EXP_MAX))
        both = _dot_nt(jnp.concatenate([q[r:r + sub] * own,
                                        kb[r:r + sub] * own], axis=0), met)
        mqk.append(both[:sub])
        mkk.append(both[sub:])
    mqk = jnp.where(i >= j, jnp.concatenate(mqk, axis=0), 0.0)
    n = jnp.where(i > j, jnp.concatenate(mkk, axis=0), 0.0)
    same = _block_of(i, sub) == _block_of(j, sub)
    eye = (i == j).astype(_F32)
    a_inv = _series_inverse(jnp.where(same, n, 0.0), eye, sub)
    if C > sub:                 # the blocks below the diagonal ones
        below = _dot(a_inv, jnp.where(same, 0.0, n))
        a_inv = _dot(_series_inverse(below, eye, C // sub), a_inv)
    eg = jnp.exp(G)
    w, u = _dot(a_inv, kb * eg), _dot(a_inv, vb)
    read = _dot(jnp.concatenate([w, q * eg], axis=0), s_in)
    vn = u - read[:C]
    o = read[C:] + _dot(mqk, vn)
    last = G[C - 1:C, :]
    return o, _column(jnp.exp(last)) * s_in \
        + _dot_tn(k * jnp.exp(last - G), vn)


def _masked(rows, n_real):
    """The five blocks ``[q, k, v, g, b]`` of a chunk's rows (rows
    first, of one head or of all) with the rows from ``n_real`` on made
    pads: ``g = 0`` (no decay), ``b = 0`` (no write)."""
    q, k, v, g, b = rows
    real = lax.broadcasted_iota(jnp.int32, g.shape, 0) < n_real
    return q, k, v, jnp.where(real, g, 0.0), jnp.where(real, b, 0.0)


def _chunk_xla(rows, n_real, state):
    """One chunk of one slot, every head: its rows ``(C, 5 H D)`` as
    ``feat`` lays them, of which the first ``n_real`` are real, and
    ``state (H, D, D)`` -> ``(o (C, H D), the state after)``."""
    H, D, _ = state.shape
    blocks = _masked(_split(rows, H, D), n_real)      # (C, H, D) each
    o, state = jax.vmap(kda_chunk_math, in_axes=(1,) * 5 + (0,),
                        out_axes=(1, 0))(*blocks, state)
    return o.reshape(rows.shape[0], H * D), state


def _scan(p, state, o, chunk, chunk_step):
    """The chunked form for every slot fed more than one row: one trip
    of ``chunk_step`` a chunk, slot after slot, ``state`` and the rows'
    results ``o (NR, H D)`` updated in place."""
    Q, NR, HD = chunk, p["NR"], p["H"] * p["D"]
    fed, off = p["fed"], p["off"]
    trips = jnp.where(fed > 1, (fed + Q - 1) // Q, 0)
    ends = jnp.cumsum(trips)
    feat = jnp.pad(p["feat"], ((0, Q), (0, 0)))
    o = jnp.pad(o, ((0, Q), (0, 0)))
    at = jnp.arange(Q, dtype=jnp.int32)[:, None]

    def trip(n, carry):
        state, o = carry
        slot = jnp.sum((ends <= n).astype(jnp.int32))
        i = n - (_at(ends, slot) - _at(trips, slot))
        start = _at(off, slot) + i * Q
        n_real = jnp.minimum(_at(fed, slot) - i * Q, Q)
        o_new, s_out = chunk_step(
            lax.dynamic_slice(feat, (start, 0), (Q, feat.shape[1])),
            n_real, _at(state, slot))
        old = lax.dynamic_slice(o, (start, 0), (Q, HD))
        o = lax.dynamic_update_slice(o, jnp.where(at < n_real, o_new, old),
                                     (start, 0))
        return lax.dynamic_update_index_in_dim(state, s_out, slot, 0), o

    state, o = lax.fori_loop(0, ends[-1], trip, (state, o))
    return state, o[:NR]


def _forward(attrs, inputs, aux, is_train, update, chunk_step):
    with jax.named_scope("kda_conv"):
        p = _prologue(attrs, inputs, aux, is_train)
        operands, lay = _step_operands(p)
    H, D = p["H"], p["D"]
    state = aux[1].astype(_F32)
    with jax.named_scope("kda_update"):
        state, o_step = update(state, p["pos"], *operands)
        o = _dot(lay.T, o_step.reshape(-1, H * D))           # (NR, H D)
    if p["S"] > 1:
        with jax.named_scope("kda_chunk"):
            state, o = _scan(p, state, o, parse_int(attrs["chunk"]),
                             chunk_step)
    with jax.named_scope("kda_conv"):
        data = inputs[0]
        o = o.reshape(-1, H, D)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + parse_float(attrs.get("rms_eps", 1e-6))) \
            * inputs[5].astype(_F32)[None, None, :]
        gate = jax.nn.sigmoid(data[:, 4 * H * D:5 * H * D].astype(_F32))
        out = (o.reshape(-1, H * D) * gate).astype(data.dtype)
        cursor = (p["pos"] + p["fed"]).reshape(aux[2].shape) \
            .astype(aux[2].dtype)
    return [out], [p["new_tail"].astype(aux[0].dtype),
                   state.astype(aux[1].dtype), cursor]


def _lowering(update, chunk_step):
    """The op's forward with ``update`` as its one step of the
    recurrence and ``chunk_step`` as its one chunk of the chunked
    form."""
    def forward(attrs, inputs, aux, is_train, rng):
        return _forward(attrs, inputs, aux, is_train, update, chunk_step)
    return forward


# -------------------------------------------------------------------- kernel
#: bytes of the state one grid step of ``kda_update`` takes (and hands
#: back): a slot's whole state at the published sizes; in and out,
#: double-buffered, four such blocks lie in VMEM
_UPDATE_BLOCK = 2 << 20
_VMEM_LIMIT = 48 << 20


def _update_kernel(hb):
    """Grid (slot, block of ``hb`` heads): ``_update_xla`` for the
    block's heads, keys down the sublanes. A head's decay, key and
    query arrive as COLUMNS of one tile (``[a | k | q]``, ``hb`` lanes
    each): what scales the state's rows and what the state is summed
    against down its sublanes. A slot at cursor 0 reads zeros."""
    def kernel(pos_ref, s_ref, akq_ref, v_ref, b_ref, so_ref, o_ref):
        fresh = pos_ref[pl.program_id(0)] == 0
        akq = akq_ref[...]                                   # (D, lanes)
        for h in range(hb):
            a, k, q = (akq[:, n * hb + h:n * hb + h + 1] for n in range(3))
            sd = a * jnp.where(fresh, 0.0, s_ref[h])
            delta = b_ref[h:h + 1, :] * (
                v_ref[h:h + 1, :] - jnp.sum(k * sd, axis=0, keepdims=True))
            sn = sd + k * delta
            so_ref[h] = sn
            o_ref[h:h + 1, :] = jnp.sum(q * sn, axis=0, keepdims=True)
    return kernel


def _update_pallas(state, pos, q, k, v, a, b):
    """``_update_xla`` as the kernel ``kda_update``: a slot's state
    through VMEM once, in place. The three key-side vectors of a block
    of heads are transposed here to the columns of one tile of 128
    lanes (3 % of the state's bytes at the published sizes)."""
    slots, H, D, _ = state.shape
    # a block of heads whose rows are whole sublanes (8), or all of them
    fit = [h for h in range(8, H, 8)
           if H % h == 0 and h * D * D * 4 <= _UPDATE_BLOCK]
    hb = max(fit) if fit and H * D * D * 4 > _UPDATE_BLOCK else H
    G = H // hb
    lanes = -(-3 * hb // 128) * 128
    akq = jnp.stack([a, k, q], axis=1).reshape(slots, 3, G, hb, D)
    akq = jnp.moveaxis(akq, (2, 4), (1, 2)).reshape(slots, G, D, 3 * hb)
    akq = jnp.pad(akq, ((0, 0),) * 3 + ((0, lanes - 3 * hb),))

    def block(s, g, pos_ref):
        return s, g, 0, 0

    def rows(s, g, pos_ref):
        return s, g, 0

    st = pl.BlockSpec((None, hb, D, D), block)
    cols = pl.BlockSpec((None, None, D, lanes), block)
    vec = pl.BlockSpec((None, hb, D), rows)
    kwargs = {} if _pk._interpret() else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}
    state, o = _pk.pallas_call(
        _update_kernel(hb), name="kda_update",
        out_shape=(jax.ShapeDtypeStruct(state.shape, _F32),
                   jax.ShapeDtypeStruct((slots, H, D), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots, G),
            in_specs=[st, cols, vec, vec], out_specs=(st, vec)),
        input_output_aliases={1: 0}, **kwargs)(
            pos, state, akq, v, jnp.broadcast_to(b[..., None], v.shape))
    return state, o


def _chunk_kernel(n_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref,
                  so_ref):
    """Grid (head): ``kda_chunk_math`` for the head whose channels lie
    in this block of lanes, the rows from ``n_ref[0]`` on made pads."""
    o_ref[...], so_ref[...] = kda_chunk_math(
        *_masked(tuple(r[...] for r in (q_ref, k_ref, v_ref, g_ref, b_ref)),
                 n_ref[0]), s_ref[...])


def _chunk_pallas(rows, n_real, state):
    """``_chunk_xla`` as the kernel ``kda_chunk``: a head's five blocks
    of the rows as they lie and its state through VMEM a grid step; the
    running log decay, the pads and the scaled keys are made inside.
    (Several heads a grid step, so that their chains of small products
    could interleave, measured no faster on a v5e: PERF.md, PR 52.)"""
    H, D, _ = state.shape
    C = rows.shape[0]
    lanes = [pl.BlockSpec((C, D), lambda h, n, j=j: (0, j * H + h))
             for j in range(5)]
    cell = pl.BlockSpec((None, D, D), lambda h, n: (h, 0, 0))
    kwargs = {} if _pk._interpret() else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT)}
    return _pk.pallas_call(
        _chunk_kernel, name="kda_chunk",
        out_shape=(jax.ShapeDtypeStruct((C, H * D), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H,), in_specs=lanes + [cell],
            out_specs=(pl.BlockSpec((C, D), lambda h, n: (0, h)), cell)),
        **kwargs)(jnp.reshape(n_real, (1,)).astype(jnp.int32),
                  *([rows] * 5), state)


def _kda_eligible(attrs, in_shapes, in_dtypes):
    """A float32 state and, on the chip, whole tiles of it: 128 value
    lanes across, keys a multiple of 8 sublanes down, and where chunks
    run a chunk of whole sub-blocks; anything in interpret mode."""
    if len(in_shapes) != 9 or str(in_dtypes[7]) != "float32":
        return False
    if _pk._interpret():
        return True
    D = in_shapes[7][-1]
    chunked = parse_int(attrs["step_len"]) > 1
    return D % 128 == 0 \
        and not (chunked and parse_int(attrs["chunk"]) % _SUB)


def _kda_infer(attrs, in_shapes):
    data_s, fed_s = in_shapes[:2]
    H, D, K, _chunk, _S, _capacity = _geometry(attrs)
    HD = H * D
    if data_s is None:
        return in_shapes, [None], [None] * 3
    if len(data_s) != 2 or data_s[1] != 5 * HD + H:
        raise ValueError(f"kda_mixer_decode: rows {data_s} are not (rows, "
                         f"[q | k | v | f | g | b] = {5 * HD + H})")
    params = [(3 * HD, K), (H,), (HD,), (D,)]
    out = [(data_s[0], HD)]
    if fed_s is None:                   # fed alone says how many slots
        return [data_s, None] + params, out, [None] * 3
    slots = fed_s[0]
    return ([data_s, fed_s] + params, out,
            [(slots, K - 1, 3 * HD), (slots, H, D, D), (slots, 1)])


#: one grid step of ``kda_update`` at the published sizes (32 heads of
#: 128 x 128): the slot's state in and out, double-buffered, the tile
#: of columns and the rows (a grid step of ``kda_chunk`` holds less: a
#: head's 64 rows, its state and a dozen 64 x 64 matrices)
_KDA_KSPEC = {
    "tiles": [((32 * 128, 128), "float32")] * 4
    + [((128, 128), "float32")] * 2 + [((32, 128), "float32")] * 6,
    "dtypes": ("float32", "bfloat16"),
}

#: which aux cell holds what, per decode slot (``OpDef.slot_state``)
KDA_SLOT_STATE = {"conv_tail": "conv", "kda_state": "recurrent",
                  "cache_pos": "cursor"}

#: what one execution does to the state, from the host's cursors alone
#: (``OpDef.state_reads``): the slots that take one step, the slots
#: that take chunks, their trips, the rows those trips run over (trips
#: x chunk) and the real rows among them
_KDA_COUNTS = read_counts(("kda.step_slots", "kda_step_slots"),
                          ("kda.chunk_slots", "kda_chunk_slots"),
                          ("kda.chunk_trips", "kda_chunk_trips"),
                          ("kda.chunk_rows", "kda_chunk_rows"),
                          ("kda.real_rows", "kda_real_rows"))


def _kda_reads(attrs, capacity, sources):
    chunk = parse_int(attrs["chunk"])

    def reads(pos, fed):
        many = np.asarray(fed)[np.asarray(fed) > 1]
        trips = int(np.sum(-(-many // chunk)))
        return {"kda.step_slots": int(np.sum(np.asarray(fed) == 1)),
                "kda.chunk_slots": int(many.size),
                "kda.chunk_trips": trips,
                "kda.chunk_rows": trips * chunk,
                "kda.real_rows": int(np.sum(many))}
    return reads


register("kda_mixer_decode",
         inputs=("data", "fed", "conv_weight", "A_log", "dt_bias",
                 "norm_weight"),
         aux=tuple(KDA_SLOT_STATE), full=_lowering(_update_xla, _chunk_xla),
         stateful_infer=True,
         aux_dtypes={"conv_tail": "float32", "kda_state": "float32",
                     "cache_pos": "int32"},
         infer_shape=_kda_infer,
         attr_spec={**{k: (parse_int, None) for k in (
             "heads", "head_dim", "d_conv", "chunk", "step_len",
             "capacity")},
             "lower_bound": (parse_float, -5.0),
             "rms_eps": (parse_float, 1e-6)},
         slot_state=KDA_SLOT_STATE, state_reads=(_KDA_COUNTS, _kda_reads),
         donate_aux=True,
         variants={"pallas": (_lowering(_update_pallas, _chunk_pallas),
                              _kda_eligible,
                              _KDA_KSPEC)},
         doc="The recurrent part of a Kimi Delta Attention mixer - causal "
             "convolutions and a delta-rule update under a decay a channel "
             "- over a per-slot decode state that is constant in the "
             "context (ops/kda.py).")
