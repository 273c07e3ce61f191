"""The recurrent part of a Mamba-2 mixer with its decode state
(``ssm_mixer_decode``): the causal depthwise convolution over a token's
last ``d_conv`` inputs and the selective state update (state-space
duality, arXiv:2405.21060), for slot-pooled serving.

Per token ``t`` of one sequence, ``H`` heads of ``P`` channels, a state
of ``N`` numbers a channel, B and C in ``groups`` groups of ``N`` (head
``h`` reads group ``h // (H / groups)``: one group, Granite 4.0-H's, is
B and C shared by all heads; Nemotron-H has eight), the row ``[z_t |
xBC_t | dt_t]`` being the mixer's input projection:

    c_t   = silu(sum_k w_conv[:, k] * xBC_{t-(K-1)+k} + b_conv)
    [x_t | B_t | C_t] = c_t                    B_t, C_t: (groups, N)
    dlt_t = softplus(dt_t + dt_bias)           a_t = exp(dlt_t * -exp(A_log))
    H_t[h] = a_t[h] H_{t-1}[h] + dlt_t[h] x_t[h] (outer) B_t[group of h]
    y_t[h] = H_t[h] C_t[group of h] + D[h] x_t[h]
    out_t = y_t * silu(z_t)

all of it in float32 whatever the rows' dtype. The gated norm and the
output projection stay in the graph. ``groups`` 1 is the op as it was
before it had the attribute, to the lowered text.

**State**, slot-pooled, two families no cursor indexes (``slot_state``):

    conv_tail  (slots, K - 1, C)       float32   family "conv": the last
               K - 1 inputs of the convolution, the newest last
    ssm_state  (slots, G, N, W)        float32   family "recurrent": H, laid
               with N down the sublanes and ``W = P x`` as many heads as
               fill 128 lanes across (channel ``c = h P + p`` at ``[c //
               W, :, c % W]``), so that the read-out ``H C`` is a sum
               down sublanes and lands lane-dense; the heads of a lane
               group lie inside one group of B and C (``lane_width``)
    cache_pos  (slots, 1)              int32     family "cursor"

Nothing of it is read by position: **a slot whose cursor is 0 at the
start of a dispatch reads both as zeros**, whatever its last occupant
left (``join`` moves the cursor alone). The state is constant in the
context: 2.15 MB a slot and layer at the published sizes, rewritten
whole by every dispatch that feeds the slot (``donate_aux``).

**Rows.** ``data`` is the rows of a window as the graph's row-wise
operations see them (``ops/rows.py``), folded to ``(rows, width)``:
``slots x S`` of them, slot ``b``'s at ``b S``, or under a budget R of
them, slot ``b``'s ``fed[b]`` real rows at ``fed[0] + ... + fed[b-1]``
(any other number of rows than ``slots x S`` is a budget). ``fed``
``(slots,)`` says how many of a slot's S tokens are real (none where S
rows have no room under ``capacity``: the attention layers' rule, so
that every layer's cursor agrees). The op costs by the rows that are
real:

* a slot fed ONE row (an S = 1 step, or a decoding slot riding a
  window) takes one step of the recurrence: ``ssm_update``, one read
  and one write of its state;
* a slot fed more takes the chunked form, ``chunk`` rows a trip
  (``ssm_scan``): inside a chunk ``Y = (L o C B^T) (dlt X)`` with
  ``L[i, j] = prod_{j<k<=i} a_k``, the incoming state decayed to every
  row, the chunk's own state handed to the next trip. As many trips as
  the fed slots have chunks between them, whatever S and the slot
  count; a ragged last chunk's pads are steps with ``dlt = 0`` (no
  decay, no input);
* a slot fed nothing keeps state, tail and cursor.

``forward`` is plain ``jax.numpy``. The ``pallas`` variant replaces the
step by the kernel ``ssm_update`` (every slot's state through VMEM
once, in place) and a chunk's arithmetic by the kernel ``ssm_scan`` (a
lane group of the chunk's rows and of the slot's state a grid step; the
loop over the trips, the slices of the rows and the running sum of log
decays stay XLA's); the prologue (``ssm_conv``) is shared. The three
names are ``jax.named_scope``s too, which reach an operation's
metadata; in the device trace only the two kernels are operations of
their own names.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError, parse_int
from . import pallas_kernels as _pk
from .registry import read_counts, register
from .rows import _at

__all__ = ["lane_width", "ssm_recurrence", "fed_rows_layout",
           "causal_conv_rows"]

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def lane_width(heads, head_dim, groups=1):
    """W of the state's layout: ``head_dim`` times the most heads (a
    divisor of the heads of one group of B and C) that lie side by side
    in 128 lanes."""
    pack = max(1, 128 // head_dim)
    while (heads // groups) % pack:
        pack -= 1
    return pack * head_dim


def _geometry(attrs):
    geo = tuple(parse_int(attrs[k]) for k in (
        "heads", "head_dim", "d_state", "d_conv", "chunk", "step_len",
        "capacity"))
    if min(geo) < 1 or geo[3] < 2:
        raise MXNetError(f"ssm_mixer_decode: sizes {geo} (heads, head_dim, "
                         "d_state, d_conv >= 2, chunk, step_len, capacity)")
    return geo


def _groups(attrs):
    """The groups of B and C, whole numbers of heads each."""
    groups, heads = parse_int(attrs.get("groups", 1)), parse_int(
        attrs["heads"])
    if groups < 1 or heads % groups:
        raise MXNetError(f"ssm_mixer_decode: {groups} groups of B and C "
                         f"over {heads} heads")
    return groups


def _dot(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=_F32)


def fed_rows_layout(fed, cursor, NR, S, capacity):
    """Where each slot's real rows lie among ``NR`` folded rows (module
    docstring, **Rows**): ``pos`` the cursors, ``fed`` the rows a slot
    is really fed (none where S rows have no room under ``capacity``),
    ``off`` a slot's first row, and per row its slot ``seg``, its index
    ``t`` inside the slot's rows and whether it is real (``valid``);
    ``fresh``: the slots whose cursor is 0."""
    slots = cursor.shape[0]
    pos = cursor.reshape((slots,)).astype(jnp.int32)
    fed = jnp.where(pos + S <= capacity,
                    jnp.clip(fed.reshape((slots,)).astype(jnp.int32), 0, S),
                    0)
    idx = jnp.arange(NR, dtype=jnp.int32)
    if NR == slots * S:                 # every slot's S rows, b at b S
        off = jnp.arange(slots, dtype=jnp.int32) * S
        seg = idx // S
    else:                               # a budget: the real rows packed
        ends = jnp.cumsum(fed)
        off = jnp.minimum(ends - fed, NR)
        seg = jnp.minimum(jnp.sum(ends[None, :] <= idx[:, None], axis=1),
                          slots - 1).astype(jnp.int32)
    t = idx - off[seg]                  # a row's index inside its slot's
    valid = (t >= 0) & (t < fed[seg])
    return dict(pos=pos, fed=fed, off=off, idx=idx, seg=seg, t=t,
                valid=valid, fresh=pos == 0)


def causal_conv_rows(xbc, conv_w, tail, lay):
    """The causal depthwise convolution of the rows ``xbc (NR, C)``
    under ``conv_w (C, K)``, each slot's first taps reaching into its
    ``tail (slots, K - 1, C)`` (zeros already where the slot is fresh),
    before any bias or activation, and the new tail: the last K - 1 of
    (old tail, the fed rows). ``lay``: ``fed_rows_layout``."""
    NR, C = xbc.shape
    slots, K = tail.shape[0], tail.shape[1] + 1
    fed, off, seg, t, valid = (lay[k] for k in (
        "fed", "off", "seg", "t", "valid"))
    w = conv_w.astype(_F32)                                  # (C, K)
    # the taps inside the slot's own rows: row i - (K-1) + k, where that
    # is not before the slot's first
    xp = jnp.concatenate([jnp.zeros((K - 1, C), _F32), xbc], axis=0)
    conv = xbc * w[None, :, K - 1]
    for k in range(K - 1):
        conv = conv + jnp.where((t >= K - 1 - k)[:, None],
                                xp[k:k + NR], 0.0) * w[None, :, k]
    # the taps that reach the tail: only a slot's first K-1 rows have
    # any. corr[b, j] is what the tail adds to the slot's row j, laid
    # at that row by a one-hot product (exact in float32)
    corr = jnp.stack([
        sum(tail[:, j + k] * w[None, :, k] for k in range(K - 1 - j))
        for j in range(K - 1)], axis=1)                      # (slots, K-1, C)
    key = jnp.where(valid & (t < K - 1), seg * (K - 1) + t, -1)
    first = key[:, None] == jnp.arange(slots * (K - 1))[None, :]
    conv = conv + _dot(first.astype(_F32), corr.reshape(-1, C))

    # the new tail: the last K-1 of (old tail, the fed rows)
    src = jnp.concatenate([tail.reshape(-1, C), xbc], axis=0)
    j = jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    at = fed[:, None] + j                                    # (slots, K-1)
    b = jnp.arange(slots, dtype=jnp.int32)[:, None]
    take = jnp.where(at < K - 1, b * (K - 1) + at,
                     slots * (K - 1) + off[:, None] + at - (K - 1))
    pick = take.reshape(-1)[:, None] == jnp.arange(src.shape[0])[None, :]
    new_tail = _dot(pick.astype(_F32), src).reshape(slots, K - 1, C)
    return conv, new_tail


def _prologue(attrs, inputs, aux, is_train):
    """What both lowerings share before they touch the state
    (``ssm_conv``): each slot's cursor, the rows it is really fed and
    where they lie, the convolution over those rows and the slot's tail,
    the new tail, and per row ``x``, ``B``, ``C`` and ``dlt`` (0 on a
    pad) in float32."""
    if is_train:
        raise MXNetError("ssm_mixer_decode is an inference op")
    data, fed, conv_w, conv_b, dt_bias, a_log, _d = inputs
    tail, _state, cursor = aux
    H, P, N, K, _chunk, S, capacity = _geometry(attrs)
    groups = _groups(attrs)
    d_in, C = H * P, H * P + 2 * groups * N
    NR = data.shape[0]
    lay = fed_rows_layout(fed, cursor, NR, S, capacity)
    pos, fed, off, idx, valid = (lay[k] for k in (
        "pos", "fed", "off", "idx", "valid"))
    tail = jnp.where(lay["fresh"][:, None, None], 0.0, tail.astype(_F32))
    xbc = data[:, d_in:d_in + C].astype(_F32)
    conv, new_tail = causal_conv_rows(xbc, conv_w, tail, lay)
    act = jax.nn.silu(conv + conv_b.astype(_F32)[None, :])

    dlt = jax.nn.softplus(data[:, d_in + C:].astype(_F32)
                          + dt_bias.astype(_F32)[None, :])
    dlt = jnp.where(valid[:, None], dlt, 0.0)                # (NR, H)
    A = -jnp.exp(a_log.astype(_F32))                         # (H,)
    # one array of everything a row brings to the recurrence
    feat = jnp.concatenate([act, dlt], axis=1)     # [x | B | C | dlt]
    return dict(P=P, BC=groups * N, S=S, d_in=d_in, NR=NR, pos=pos, fed=fed,
                off=off, idx=idx, feat=feat, A=A, new_tail=new_tail)


def _split(feat, d_in, BC):
    """``[x | B | C | dlt]``, B and C ``BC = groups x N`` wide."""
    return (feat[:, :d_in], feat[:, d_in:d_in + BC],
            feat[:, d_in + BC:d_in + 2 * BC], feat[:, d_in + 2 * BC:])


def _step_operands(p):
    """The one row of every slot that is fed exactly one, picked out of
    the rows by a one-hot product: per slot and channel the decay ``a``
    and the input ``u = dlt x`` (1 and 0 for a slot fed any other
    number: its state passes), per slot ``B`` and ``C``; and ``lay``,
    the matrix that lays a slot's result back at its row."""
    one = p["fed"] == 1
    lay = (p["idx"][None, :] == p["off"][:, None]) & one[:, None]
    lay = lay.astype(_F32)                                   # (slots, NR)
    x, B, C, dlt = _split(_dot(lay, p["feat"]), p["d_in"], p["BC"])
    a = jnp.repeat(jnp.exp(dlt * p["A"][None, :]), p["P"], axis=1)
    u = jnp.repeat(dlt, p["P"], axis=1) * x
    return a, u, B, C, lay


def _update_xla(state, pos, a, u, B, C, groups=1):
    """One step of the recurrence for every slot, a slot at cursor 0
    from zeros: ``(state', y)``."""
    slots, G, N, W = state.shape
    h0 = jnp.where((pos == 0)[:, None, None, None], 0.0, state)
    if groups > 1:                      # each lane group's own B and C
        B, C = (jnp.repeat(v.reshape(slots, groups, N), G // groups, axis=1)
                for v in (B, C))
        spread = lambda v: v[:, :, :, None]                  # noqa: E731
    else:
        spread = lambda v: v[:, None, :, None]               # noqa: E731
    hn = a.reshape(slots, G, 1, W) * h0 \
        + spread(B) * u.reshape(slots, G, 1, W)
    y = jnp.sum(hn * spread(C), axis=2)                      # (slots, G, W)
    return hn, y.reshape(slots, G * W)


def ssm_recurrence(x, dlt, A, B, C, h0):
    """The recurrence of the module docstring, step by step, for one
    sequence: ``x (T, H, P)``, ``dlt (T, H)``, ``A (H,)``, ``B``, ``C``
    ``(T, N)`` - or ``(T, groups, N)``, head ``h`` reading group ``h //
    (H / groups)`` -, ``h0 (H, P, N)`` -> ``(y (T, H, P) without the D
    term, the last state)``. What the chunked form is tested against."""
    H = x.shape[1]
    if B.ndim == 2:
        B, C = B[:, None], C[:, None]
    B, C = (jnp.repeat(v, H // v.shape[1], axis=1) for v in (B, C))

    def step(h, row):
        x_t, d_t, b_t, c_t = row                             # b, c: (H, N)
        h = jnp.exp(d_t * A)[:, None, None] * h \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)
    h, y = lax.scan(step, h0.astype(_F32), (x, dlt, B, C))
    return y, h


def _log_decays(tri, dlt, A):
    """The running sum of a chunk's log decays as a product at HIGHEST
    precision (the chip's cumsum is a product too, at its default:
    bfloat16)."""
    return _dot(tri.astype(_F32), dlt * A[None, :])


def _chunk_step(x, dlt, A, B, C, hb, groups=1):
    """One chunk of one slot in the chunked form: ``x (Q, H P)``, ``dlt
    (Q, H)`` (0 on a pad), ``B``, ``C`` ``(Q, groups N)``, the incoming
    state ``hb (G, N, W)`` -> ``(y (Q, H P), the state after the
    chunk)``. ``C B^T`` is made once a group of B and C."""
    Q, H = dlt.shape
    P = x.shape[1] // H
    G, N, W = hb.shape
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    cs = _log_decays(tri, dlt, A)                            # (Q, H)
    u = (dlt[:, :, None] * x.reshape(Q, H, P))               # (Q, H, P)
    diff = cs.T[:, :, None] - cs.T[:, None, :]               # (H, i, j)
    decay = jnp.exp(jnp.where(tri[None], diff, -jnp.inf))
    if groups > 1:
        B, C = B.reshape(Q, groups, N), C.reshape(Q, groups, N)
        lanes = (groups, G // groups)   # a group's lane groups
        scores = jnp.repeat(jnp.einsum("ibn,jbn->bij", C, B, precision=_HI),
                            H // groups, axis=0)             # (H, i, j)
        read = lambda: jnp.einsum(                           # noqa: E731
            "qbn,blnw->qblw", C, hb.reshape(lanes + (N, W)), precision=_HI)
        wrote = lambda uw: jnp.einsum(                       # noqa: E731
            "qbn,qblw->blnw", B, uw.reshape((Q,) + lanes + (W,)),
            precision=_HI).reshape(G, N, W)
    else:
        scores = jnp.einsum("in,jn->ij", C, B, precision=_HI)[None]
        read = lambda: jnp.einsum("qn,gnw->qgw", C, hb,      # noqa: E731
                                  precision=_HI)
        wrote = lambda uw: jnp.einsum("qn,qgw->gnw", B, uw,  # noqa: E731
                                      precision=_HI)
    y = jnp.einsum("hij,jhp->ihp", scores * decay, u, precision=_HI)
    y = y.reshape(Q, H * P) + jnp.repeat(jnp.exp(cs), P, axis=1) \
        * read().reshape(Q, G * W)
    last = cs[-1]
    uw = (u * jnp.exp(last[None, :] - cs)[:, :, None]).reshape(Q, G, W)
    hn = jnp.repeat(jnp.exp(last), P).reshape(G, 1, W) * hb + wrote(uw)
    return y, hn


def _scan(p, state, y, chunk, chunk_step):
    """The chunked form for every slot fed more than one row: one trip
    of ``chunk_step`` a chunk, slot after slot, ``state`` and the rows'
    results ``y (NR, H P)`` updated in place."""
    Q, NR, d_in, BC = chunk, p["NR"], p["d_in"], p["BC"]
    fed, off = p["fed"], p["off"]
    trips = jnp.where(fed > 1, (fed + Q - 1) // Q, 0)
    ends = jnp.cumsum(trips)
    feat = jnp.pad(p["feat"], ((0, Q), (0, 0)))
    y = jnp.pad(y, ((0, Q), (0, 0)))
    at = jnp.arange(Q, dtype=jnp.int32)

    def trip(n, carry):
        state, y = carry
        b = jnp.sum((ends <= n).astype(jnp.int32))
        i = n - (_at(ends, b) - _at(trips, b))
        start = _at(off, b) + i * Q
        real = i * Q + at < _at(fed, b)
        x, B, C, dlt = _split(
            lax.dynamic_slice(feat, (start, 0), (Q, feat.shape[1])),
            d_in, BC)
        y_new, hn = chunk_step(x, jnp.where(real[:, None], dlt, 0.0),
                               p["A"], B, C, _at(state, b))
        old = lax.dynamic_slice(y, (start, 0), (Q, d_in))
        y = lax.dynamic_update_slice(
            y, jnp.where(real[:, None], y_new, old), (start, 0))
        state = lax.dynamic_update_index_in_dim(state, hn, b, 0)
        return state, y

    state, y = lax.fori_loop(0, ends[-1], trip, (state, y))
    return state, y[:NR]


def _forward(attrs, inputs, aux, is_train, update, chunk_step):
    groups = _groups(attrs)
    if groups > 1:
        update, chunk_step = (partial(f, groups=groups)
                              for f in (update, chunk_step))
    with jax.named_scope("ssm_conv"):
        p = _prologue(attrs, inputs, aux, is_train)
        a, u, B, C, lay = _step_operands(p)
    state = aux[1].astype(_F32)
    with jax.named_scope("ssm_update"):
        state, y_step = update(state, p["pos"], a, u, B, C)
        y = _dot(lay.T, y_step)                              # (NR, H P)
    if p["S"] > 1:
        with jax.named_scope("ssm_scan"):
            state, y = _scan(p, state, y, parse_int(attrs["chunk"]),
                             chunk_step)
    with jax.named_scope("ssm_conv"):
        data, d_in = inputs[0], p["d_in"]
        x = p["feat"][:, :d_in]
        y = y + jnp.repeat(inputs[6].astype(_F32), p["P"])[None, :] * x
        out = (y * jax.nn.silu(data[:, :d_in].astype(_F32))) \
            .astype(data.dtype)
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
#: bytes of the state one grid step of ``ssm_update`` takes (and hands
#: back): in and out, double-buffered, four such blocks lie in VMEM
_UPDATE_BLOCK = 1 << 20
_VMEM_LIMIT = 32 << 20


def _update_kernel(gb, per=0):
    """Grid (slot, block of ``gb`` lane groups): ``H <- a H + B u`` and
    ``y = sum_n H C`` for every channel of the block, N down the
    sublanes; a slot at cursor 0 reads zeros. ``per`` 0: one group of B
    and C, spread along the lanes; else the block's groups of B and C
    arrive a row each, ``per`` lane groups read one, and the row is
    stood up as a column by a sum along the diagonal."""
    def kernel(pos_ref, s_ref, a_ref, u_ref, b_ref, c_ref, so_ref, y_ref):
        fresh = pos_ref[pl.program_id(0)] == 0
        if per:
            N = s_ref.shape[1]
            diag = lax.broadcasted_iota(jnp.int32, (N, N), 0) \
                == lax.broadcasted_iota(jnp.int32, (N, N), 1)

            def column(ref, j):                              # (N, 1)
                return jnp.sum(jnp.where(diag, ref[j], 0.0), axis=1,
                               keepdims=True)
        else:
            bb, cb = b_ref[...], c_ref[...]                  # (N, W)
        for g in range(gb):
            if per and g % per == 0:
                bb, cb = column(b_ref, g // per), column(c_ref, g // per)
            h0 = jnp.where(fresh, 0.0, s_ref[g])
            hn = a_ref[g:g + 1, :] * h0 + bb * u_ref[g:g + 1, :]
            so_ref[g] = hn
            y_ref[g:g + 1, :] = jnp.sum(hn * cb, axis=0, keepdims=True)
    return kernel


def _update_pallas(state, pos, a, u, B, C, groups=1):
    """``_update_xla`` as the kernel ``ssm_update``: a slot's state
    through VMEM once, in place (one group of ``B`` and ``C`` arrives
    spread along the lanes, 3 % of the state's bytes; several arrive as
    they are, a row a group)."""
    slots, G, N, W = state.shape
    per = G // groups                   # lane groups that read one group
    cap = max(1, _UPDATE_BLOCK // (N * W * 4))
    # a block is whole groups of B and C, or lies inside one
    gb = max(d for d in range(1, min(G, cap) + 1)
             if G % d == 0 and (d % per == 0 or per % d == 0))

    def block(b, g, pos_ref):
        return b, g, 0, 0

    def group(b, g, pos_ref):
        return b, g, 0

    st = pl.BlockSpec((None, gb, N, W), block)
    vec = pl.BlockSpec((None, gb, W), group)
    if groups > 1:
        nb = max(1, gb // per)          # the block's groups of B and C
        wide = pl.BlockSpec((None, nb, 1, N),
                            lambda b, g, pos_ref: (b, g * gb // per // nb,
                                                   0, 0))
        B, C = (v.reshape(slots, groups, 1, N) for v in (B, C))
        kernel = _update_kernel(gb, per)
    else:
        wide = pl.BlockSpec((None, N, W), lambda b, g, pos_ref: (b, 0, 0))
        B, C = (jnp.broadcast_to(v[:, :, None], (slots, N, W))
                for v in (B, C))
        kernel = _update_kernel(gb)
    kwargs = {} if _pk._interpret() else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)}
    state, y = _pk.pallas_call(
        kernel, name="ssm_update",
        out_shape=(jax.ShapeDtypeStruct(state.shape, _F32),
                   jax.ShapeDtypeStruct((slots, G, W), _F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots, G // gb),
            in_specs=[st, vec, vec, wide, wide], out_specs=(st, vec)),
        input_output_aliases={1: 0}, **kwargs)(
            pos, state, a.reshape(slots, G, W), u.reshape(slots, G, W), B, C)
    return state, y.reshape(slots, G * W)


def _chunk_kernel(P, per=0):
    """Grid (lane group): ``_chunk_step`` for the ``W / P`` heads whose
    channels lie in the group's W lanes. ``C B^T`` is made once, at the
    first group (``per`` > 0: at the first of every ``per`` lane groups,
    which read one group of B and C); a head's running log decay arrives
    as a row ``(1, Q)`` and is stood up as a column by a sum along the
    diagonal."""
    def kernel(x_ref, cs_ref, dl_ref, bt_ref, c_ref, h_ref, y_ref, hn_ref,
               scores_ref):
        g = pl.program_id(0)
        Q, W = x_ref.shape

        @pl.when((g % per if per else g) == 0)
        def _():
            scores_ref[...] = _dot(c_ref[...], bt_ref[...])

        i = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        j = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)

        def column(row):
            return jnp.sum(jnp.where(i == j, row, 0.0), axis=1,
                           keepdims=True)

        x = x_ref[...]
        y = uw = decayed = jnp.zeros((Q, W), _F32)
        whole = jnp.zeros((1, W), _F32)
        for k in range(W // P):
            h = g * (W // P) + k
            cs_row = cs_ref[pl.ds(h, 1), :]                  # (1, Q)
            cs = column(cs_row)                              # (Q, 1)
            mine = (lane >= k * P) & (lane < (k + 1) * P)
            u = jnp.where(mine, x * column(dl_ref[pl.ds(h, 1), :]), 0.0)
            decay = jnp.where(i >= j,
                              jnp.exp(jnp.minimum(cs - cs_row, 0.0)), 0.0)
            y = y + _dot(scores_ref[...] * decay, u)
            last = jnp.sum(jnp.where(j[:1] == Q - 1, cs_row, 0.0), axis=1,
                           keepdims=True)                    # (1, 1)
            uw = uw + u * jnp.exp(last - cs)
            decayed = jnp.where(mine, jnp.exp(cs), decayed)
            whole = jnp.where(mine, jnp.exp(last), whole)
        hb = h_ref[...]
        y_ref[...] = y + decayed * _dot(c_ref[...], hb)
        hn_ref[...] = whole * hb + _dot(bt_ref[...], uw)
    return kernel


def _chunk_pallas(x, dlt, A, B, C, hb, groups=1):
    """``_chunk_step`` as the kernel ``ssm_scan``: a lane group of the
    chunk's rows and of the state through VMEM a grid step."""
    Q, H = dlt.shape
    G, N, W = hb.shape
    per = G // groups if groups > 1 else 0
    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    cs = _log_decays(tri, dlt, A)                            # (Q, H)
    if per:                             # a lane group's own B^T and C
        Bt = B.reshape(Q, groups, N).transpose(1, 2, 0)
        C = C.reshape(Q, groups, N).transpose(1, 0, 2)
        whole = lambda a: pl.BlockSpec(                      # noqa: E731
            a.shape if a.ndim == 2 else (None,) + a.shape[1:],
            (lambda g: (0, 0)) if a.ndim == 2
            else (lambda g: (g // per, 0, 0)))
    else:
        Bt = B.T
        whole = lambda a: pl.BlockSpec(a.shape, lambda g: (0, 0))  # noqa
    rows = pl.BlockSpec((Q, W), lambda g: (0, g))
    cell = pl.BlockSpec((None, N, W), lambda g: (g, 0, 0))
    operands = (x, cs.T, dlt.T, Bt, C, hb)
    kwargs = {} if _pk._interpret() else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT)}
    return _pk.pallas_call(
        _chunk_kernel(x.shape[1] // H, per), name="ssm_scan",
        out_shape=(jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(hb.shape, _F32)),
        grid=(G,),
        in_specs=[rows] + [whole(a) for a in operands[1:5]] + [cell],
        out_specs=(rows, cell),
        scratch_shapes=[pltpu.VMEM((Q, Q), _F32)], **kwargs)(*operands)


def _ssm_eligible(attrs, in_shapes, in_dtypes):
    """A float32 state and, on the chip, whole tiles of it: 128 lanes
    across and a multiple of 8 sublanes down, and where chunks run a
    chunk of whole sublanes; anything in interpret mode."""
    if len(in_shapes) != 10 or str(in_dtypes[8]) != "float32":
        return False
    if _pk._interpret():
        return True
    _slots, _g, N, W = in_shapes[8]
    chunked = parse_int(attrs["step_len"]) > 1
    return W % 128 == 0 and N % 8 == 0 \
        and not (chunked and parse_int(attrs["chunk"]) % 8)


def _ssm_infer(attrs, in_shapes):
    data_s, fed_s = in_shapes[:2]
    H, P, N, K, _chunk, S, _capacity = _geometry(attrs)
    groups = _groups(attrs)
    d_in, C = H * P, H * P + 2 * groups * N
    if data_s is None:
        return in_shapes, [None], [None] * 3
    if len(data_s) != 2 or data_s[1] != d_in + C + H:
        raise ValueError(f"ssm_mixer_decode: rows {data_s} are not (rows, "
                         f"[z | xBC | dt] = {d_in + C + H})")
    params = [(C, K), (C,), (H,), (H,), (H,)]
    out = [(data_s[0], d_in)]
    if fed_s is None:                   # fed alone says how many slots
        return [data_s, None] + params, out, [None] * 3
    slots, W = fed_s[0], lane_width(H, P, groups)
    return ([data_s, fed_s] + params, out,
            [(slots, K - 1, C), (slots, d_in // W, N, W), (slots, 1)])


#: one grid step of ``ssm_update`` at the published sizes (N 128, W
#: 128, 16 lane groups): the state's block in and out, double-buffered,
#: B and C spread, the vectors (a grid step of ``ssm_scan`` holds less:
#: 2 MB of a chunk's rows, ``C B^T`` and its decayed copy)
_SSM_KSPEC = {
    "tiles": [((16 * 128, 128), "float32")] * 4
    + [((128, 128), "float32")] * 4 + [((16, 128), "float32")] * 6,
    "dtypes": ("float32", "bfloat16"),
}

#: which aux cell holds what, per decode slot (``OpDef.slot_state``)
SSM_SLOT_STATE = {"conv_tail": "conv", "ssm_state": "recurrent",
                  "cache_pos": "cursor"}

#: what one execution does to the state, from the host's cursors alone
#: (``OpDef.state_reads``): the real rows it advances, and the slots
#: whose state it reads and writes
_SSM_COUNTS = read_counts(("ssm.rows", "ssm_rows"),
                          ("ssm.touched", "ssm_touched"))


def _ssm_reads(attrs, capacity, sources):
    return lambda pos, fed: {"ssm.rows": int(np.sum(fed)),
                             "ssm.touched": int(np.sum(fed > 0))}


register("ssm_mixer_decode",
         inputs=("data", "fed", "conv_weight", "conv_bias", "dt_bias",
                 "A_log", "D"),
         aux=tuple(SSM_SLOT_STATE), full=_lowering(_update_xla, _chunk_step),
         stateful_infer=True,
         aux_dtypes={"conv_tail": "float32", "ssm_state": "float32",
                     "cache_pos": "int32"},
         infer_shape=_ssm_infer,
         attr_spec={**{k: (parse_int, None) for k in (
             "heads", "head_dim", "d_state", "d_conv", "chunk", "step_len",
             "capacity")}, "groups": (parse_int, None)},
         slot_state=SSM_SLOT_STATE, state_reads=(_SSM_COUNTS, _ssm_reads),
         donate_aux=True,
         variants={"pallas": (_lowering(_update_pallas, _chunk_pallas),
                              _ssm_eligible,
                              _SSM_KSPEC)},
         doc="The recurrent part of a Mamba-2 mixer - causal convolution "
             "and selective state update - over a per-slot decode state "
             "that is constant in the context (ops/ssm.py).")
