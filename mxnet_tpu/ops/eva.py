"""EVA chunked linearized attention with its decode state
(``eva_attention_decode``) and the dense gated-SiLU epilogue
(``GatedSiLU``): the attention of EvaByte (EVA, "Efficient Attention
via Control Variates", ICLR 2023, in the deterministic chunked form).

Per head (width d, s = d**-0.5), with a chunk of C positions and a
window of W (W a multiple of C), q and k rotated at their absolute
positions, and two learned vectors per head, ``phi`` and ``mu``:

    chunk c = positions C*c .. C*c + C-1
    a_j   = softmax over j in c of (s * phi . k_j)
    k~_c  = sum_j a_j k_j + mu          v~_c = sum_j a_j v_j
    query t, window w = t // W, attends in ONE softmax
        the exact rows {j : j // W == w, j <= t}  and
        the summaries  {c : (C*c) // W < w}

so a closed window is seen only through its W/C summaries, and the
windows are blocks, not a sliding window.

The decode state of one slot and layer is therefore not "a row per
position". The op's aux cells, slot-pooled like ``attention_decode``'s:

    singles_k, singles_v  (slots, H, W, d)           the open window's
                          exact rows, a ring indexed by position mod W
    summary_k, summary_v  (slots, H, capacity/C, d)  one pair per closed
                          chunk, at index position // C
    cache_pos             (slots, 1) int32           the context length

Everything else follows from the cursor: the open window holds
``cursor mod W`` rows, ``(cursor // W) * (W/C)`` summaries are visible.
Inputs: q, k, v ``(slots, H, S, d)``, ``fed`` ``(slots,)`` int32 - how
many of the slot's S tokens are real, 0..S - and ``phi``, ``mu``
``(H, d)``. One dispatch appends exactly ``fed`` rows at the slot's own
position, writes the summary of every chunk those rows complete, and
moves the cursor by ``fed``; a window boundary may fall anywhere inside
the S positions (S <= W), any number of slots at a time. Positions past
``fed`` change no state and their outputs are don't-cares. A slot whose
cursor + S would pass ``capacity`` is fed nothing (the driver retires
it before; ``BatchedKVCacheDecoder.overflowing``).

Because a window's rows are overwritten by the next window's, the
attention of a dispatch reads the rows as they were BEFORE it (for the
queries still in the slot's starting window), the S new rows themselves
(causal, same window only), and the summaries AFTER this dispatch's
chunks were written (a query past the boundary sees the chunks the
same dispatch closed). The rows are written last.

``forward`` is the XLA composition (scores over a whole pool, masked;
one slot at a time for S > 1 so that the scores stay small; per-slot
gathers and scatters, for which the TPU's compiler re-lays the pools).
The ``pallas`` variant is the served path: three kernels, named in the
device trace, do every read and write of a pool in aligned blocks -
``eva_summarise`` (the pooling, laid into the summary pool),
``eva_attn_decode`` (S = 1) / ``eva_attn_window`` (S > 1) (one
flash-style pass over the live blocks of both pools and the new rows,
scores never materialised, dead blocks neither fetched nor computed),
and ``eva_write`` (the new rows into the ring). Rows land at ragged
offsets through a one-hot matrix product, never an unaligned store.

Which launches a slot takes is read from ``fed``, and from nothing
else. In a program of S > 1 the three kernels run at S for the slots fed
more than one row, and once more at the S = 1 program's geometry -
``eva_summarise_ride``, ``eva_attn_ride``, ``eva_write_ride`` - for the
slots fed exactly one (a decoding slot that rides another's prefill
window is one query, not a window of which one row is real). A launch's
grid runs the slots it is fed and spends no step on another (its first
bound is data): a slot costs the triple it does not take nothing, a
slot fed nothing costs neither anything, and its state comes back bit
for bit. Each kernel-calling function is jitted by itself, so a program
lowers a shape of it once, whatever its layers.

The op asks the executor to donate its aux arrays to the step program
(``donate_aux``): the pools are updated in place, a few blocks a
dispatch, instead of being copied whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError, parse_float, parse_int
from . import pallas_kernels as _pk
from .registry import read_counts, register

__all__ = ["eva_pool", "eva_attend", "gated_silu"]

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


# ----------------------------------------------------------------- GatedSiLU
def gated_silu(x):
    """(rows, 2F) -> (rows, F): ``silu(x[:, :F]) * x[:, F:]`` in
    float32, cast back (``ops/moe.py``'s expert arithmetic for a dense
    feed-forward whose gate and up projections are one matmul)."""
    f = x.shape[-1] // 2
    g, u = x[..., :f].astype(_F32), x[..., f:].astype(_F32)
    return (jax.nn.silu(g) * u).astype(x.dtype)


def _gated_infer(attrs, in_shapes):
    data_s = in_shapes[0]
    if data_s is None:
        return in_shapes, [None], []
    if data_s[-1] % 2:
        raise ValueError(f"GatedSiLU halves its last axis, got {data_s}")
    return [data_s], [tuple(data_s[:-1]) + (data_s[-1] // 2,)], []


@register("GatedSiLU", inputs=("data",), infer_shape=_gated_infer)
def _gated_silu_op(attrs, data):
    """Gate and up halves of one projection -> silu(gate) * up."""
    return gated_silu(data)


# ------------------------------------------------------- per-slot row access
def _rows(pool, start, n):
    """``pool[b, :, start[b]:start[b]+n]`` for every slot: (B, H, n, d)."""
    H, d = pool.shape[1], pool.shape[3]
    return jax.vmap(lambda c, s: lax.dynamic_slice(
        c, (0, s, 0), (H, n, d)))(pool, start)


def _put(pool, rows, start):
    """``pool`` with ``rows`` (B, H, n, d) written at every slot's own
    ``start[b]``."""
    return jax.vmap(lambda c, r, s: lax.dynamic_update_slice(
        c, r, (0, s, 0)))(pool, rows.astype(pool.dtype), start)


def _write_ring(pool, new, r0, fed):
    """The first ``fed[b]`` of ``new``'s S rows into the ring ``pool``
    (B, H, W, d) at ``(r0[b] + s) mod W``; every other row of the pool
    keeps its value. Two read-merge-write passes over S rows each: the
    part before the ring's end, and (S > 1 only) the part that wraps."""
    S, W = new.shape[2], pool.shape[2]
    idx = jnp.arange(S, dtype=jnp.int32)[None, :]
    roll = jax.vmap(lambda a, n: jnp.roll(a, n, axis=1))

    def merge(pool, start, src, rolled):
        # ring row start + i takes new row src[b, i], where that is fed
        ok = ((src >= 0) & (src < fed[:, None]))[:, None, :, None]
        return _put(pool, jnp.where(ok, rolled.astype(pool.dtype),
                                    _rows(pool, start, S)), start)

    start = jnp.minimum(r0, W - S)
    shift = r0 - start
    pool = merge(pool, start, idx - shift[:, None], roll(new, shift))
    if S > 1:
        ahead = W - r0                       # rows before the ring's end
        pool = merge(pool, jnp.zeros_like(r0), idx + ahead[:, None],
                     roll(new, (-ahead) % S))
    return pool


# ------------------------------------------------------------------- pooling
def eva_pool(rows_k, rows_v, phi, mu, chunk):
    """(B, H, n, d) rows, n a multiple of ``chunk`` -> the summaries
    ``(k~, v~)``, each (B, H, n/chunk, d) float32."""
    B, H, n, d = rows_k.shape
    k = rows_k.astype(_F32).reshape(B, H, n // chunk, chunk, d)
    v = rows_v.astype(_F32).reshape(B, H, n // chunk, chunk, d)
    logit = jnp.einsum("bhncd,hd->bhnc", k, phi.astype(_F32),
                       precision=_HI) * (float(d) ** -0.5)
    a = jax.nn.softmax(logit, axis=-1)
    ks = jnp.einsum("bhnc,bhncd->bhnd", a, k, precision=_HI) \
        + mu.astype(_F32)[None, :, None, :]
    vs = jnp.einsum("bhnc,bhncd->bhnd", a, v, precision=_HI)
    return ks, vs


# ----------------------------------------------------------------- attention
def _attend_slots(q, k, v, sk, sv, mk, mv, p, window, chunk):
    """The XLA composition for the slots given: scores of every query
    against a whole window of rows, the S new rows and the whole summary
    pool, masked by position, one softmax."""
    B, H, S, d = q.shape
    W, n_sum, per_window = window, mk.shape[2], window // chunk
    scale = float(d) ** -0.5

    def scores(keys):
        return jnp.einsum("bhsd,bhkd->bhsk", q, keys.astype(q.dtype),
                          precision=_HI,
                          preferred_element_type=_F32) * scale

    s_idx = jnp.arange(S, dtype=jnp.int32)
    pos = p[:, None] + s_idx[None, :]                       # (B, S)
    qwin = pos // W
    old = (jnp.arange(W)[None, None, :] < (p % W)[:, None, None]) \
        & (qwin == (p // W)[:, None])[:, :, None]
    new = (s_idx[None, None, :] <= s_idx[None, :, None]) \
        & (qwin[:, None, :] == qwin[:, :, None])
    summ = jnp.arange(n_sum)[None, None, :] \
        < (qwin * per_window)[:, :, None]
    mask = jnp.concatenate([old, new, summ], axis=-1)[:, None]
    logits = jnp.concatenate([scores(sk), scores(k), scores(mk)], axis=-1)
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    values = jnp.concatenate([sv, v.astype(sv.dtype), mv], axis=2)
    return jnp.einsum("bhsk,bhkd->bhsd", probs, values.astype(_F32),
                      precision=_HI, preferred_element_type=_F32)


def eva_attend(q, k, v, sk, sv, mk, mv, p, window, chunk):
    """Attention of the S new positions of every slot (module
    docstring): float32 (B, H, S, d)."""
    if q.shape[2] == 1:
        return _attend_slots(q, k, v, sk, sv, mk, mv, p, window, chunk)
    one = functools.partial(_attend_slots, window=window, chunk=chunk)
    out = lax.map(lambda a: one(*[x[None] for x in a]),
                  (q, k, v, sk, sv, mk, mv, p))
    return out[:, 0]


# ------------------------------------------------------------------- the op
def _geometry(attrs):
    capacity = parse_int(attrs["capacity"])
    window = parse_int(attrs["window"])
    chunk = parse_int(attrs["chunk"])
    if chunk < 1 or window % chunk or capacity % chunk \
            or capacity < window:
        raise MXNetError(
            f"eva_attention_decode: window {window} and capacity "
            f"{capacity} must be multiples of chunk {chunk}, and the "
            "capacity at least one window")
    return capacity, window, chunk


def _prologue(attrs, inputs, aux, is_train):
    """What both lowerings share before they touch the pools: the
    geometry, each slot's cursor and the rows it is really fed, q and k
    rotated at their positions, k and v at the pools' dtype."""
    from .nn import rope_apply

    if is_train:
        raise MXNetError("eva_attention_decode is an inference op")
    q, k, v, fed, phi, mu = inputs
    sk, sv, mk, mv, cursor = aux
    capacity, W, C = _geometry(attrs)
    B, H, S, d = q.shape
    if S > W:
        raise MXNetError(f"eva_attention_decode: a dispatch of {S} "
                         f"positions is longer than the window {W}")
    p = cursor.reshape((B,)).astype(jnp.int32)
    fed = jnp.where(p + S <= capacity,
                    jnp.clip(fed.reshape((B,)).astype(jnp.int32), 0, S), 0)
    base = parse_float(attrs.get("rope_base", 10000.0))
    pos = p[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    q = rope_apply(q, pos, base)
    k = rope_apply(k, pos, base).astype(sk.dtype)
    return q, k, v.astype(sv.dtype), fed, p, W, C


def _chunk_rows(k, off, chunk):
    """The new rows (B, H, S, d) laid at their offset ``off[b]`` inside
    the chunk that holds the cursor: (B, H, n_c * chunk, d), zero where
    no new row lies; n_c chunks can end inside S positions."""
    B, H, S, d = k.shape
    n_c = -(-S // chunk)
    grid = jnp.zeros((B, H, (n_c + 1) * chunk, d), k.dtype)
    return _put(grid, k, off)[:, :, :n_c * chunk]


def _epilogue(out, q, sk, sv, mk, mv, p, fed):
    B = p.shape[0]
    new_cursor = (p + fed).reshape((B, 1)).astype(jnp.int32)
    return [out.astype(q.dtype)], [sk, sv, mk, mv, new_cursor]


def _eva_fwd(attrs, inputs, aux, is_train, rng):
    """The XLA composition. Its per-slot slices and updates of the
    pools are gathers and scatters, for which the TPU compiler re-lays
    the pools: it is the fallback and the statement of what the
    kernels compute, not the served path."""
    q, k, v, fed, p, W, C = _prologue(attrs, inputs, aux, is_train)
    phi, mu = inputs[4:]
    sk, sv, mk, mv, _cursor = aux
    S = q.shape[2]

    # the chunks this dispatch completes: chunk c0 holds the cursor and
    # may have its first rows in the ring already; at most n_c chunks
    # end inside [p, p + S)
    n_c = -(-S // C)
    c0 = p // C
    off = p - c0 * C
    first = (c0 * C) % W
    head = (jnp.arange(n_c * C)[None, :] < off[:, None])[:, None, :, None]
    grow = ((0, 0), (0, 0), (0, (n_c - 1) * C), (0, 0))
    rows_k = jnp.where(head, jnp.pad(_rows(sk, first, C), grow),
                       _chunk_rows(k, off, C))
    rows_v = jnp.where(head, jnp.pad(_rows(sv, first, C), grow),
                       _chunk_rows(v, off, C))
    ks, vs = eva_pool(rows_k, rows_v, phi, mu, C)
    done = ((c0[:, None] + jnp.arange(n_c, dtype=jnp.int32)[None, :] + 1)
            * C <= (p + fed)[:, None])[:, None, :, None]
    mk = _put(mk, jnp.where(done, ks.astype(mk.dtype),
                            _rows(mk, c0, n_c)), c0)
    mv = _put(mv, jnp.where(done, vs.astype(mv.dtype),
                            _rows(mv, c0, n_c)), c0)

    out = eva_attend(q, k, v, sk, sv, mk, mv, p, W, C)
    sk = _write_ring(sk, k, p % W, fed)
    sv = _write_ring(sv, v, p % W, fed)
    return _epilogue(out, q, sk, sv, mk, mv, p, fed)


def _eva_infer(attrs, in_shapes):
    q_s = in_shapes[0]
    if q_s is None:
        return in_shapes, [None], [None] * 5
    capacity, window, chunk = _geometry(attrs)
    b, h, _s, d = q_s
    singles = (b, h, window, d)
    summaries = (b, h, capacity // chunk, d)
    return ([q_s, q_s, q_s, (b,), (h, d), (h, d)], [q_s],
            [singles, singles, summaries, summaries, (b, 1)])


# ------------------------------------------------------------------- kernels
def _head_group(H, cap, multiple_of=1):
    """Heads a grid step takes: the largest divisor of ``H`` that is
    <= cap and a multiple of ``multiple_of`` (or ``H`` itself)."""
    for hb in range(min(H, max(1, int(cap))), 0, -1):
        if H % hb == 0 and (hb % multiple_of == 0 or hb == H):
            return hb
    return H


#: bytes of double-buffered input blocks a kernel may keep in VMEM, and
#: the limit the compiler is given (a v5e core has 128 MiB)
_BLOCK_BUDGET = 12 << 20
_VMEM_LIMIT = 64 << 20


def _kernel_call(kernel, name, interpret, **kwargs):
    """``pallas_call`` of a kernel over a (slot, head group, block)
    grid: the first two axes parallel, and on the chip the VMEM limit
    the blocks were budgeted against."""
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT)
    return _pk.pallas_call(kernel, name=name, interpret=interpret, **kwargs)


#: the launches of one kernel-calling function, by the program and the
#: slots they serve: ``decode`` is the S = 1 program's; in a program of
#: S > 1 a slot fed more than one row takes the ``window`` launch and a
#: slot fed exactly one the ``ride`` launch, the S = 1 geometry again
#: (``_eva_pallas``). The last two run the slots they are fed and no
#: other (``_FedSlots``). What a launch is called in the device trace:
_NAMES = {"summarise": {"decode": "eva_summarise", "window": "eva_summarise",
                        "ride": "eva_summarise_ride"},
          "attend": {"decode": "eva_attn_decode", "window": "eva_attn_window",
                     "ride": "eva_attn_ride"},
          "write": {"decode": "eva_write", "window": "eva_write",
                    "ride": "eva_write_ride"}}


class _EverySlot:
    """The first axis of the S = 1 program's grids: every slot in its
    turn, one fed nothing too (it selects no row and writes none)."""
    operands = ()

    def __init__(self, fed):
        self.bound = fed.shape[0]

    @staticmethod
    def at(index_map):
        return index_map

    @staticmethod
    def slot(refs):
        return pl.program_id(0)


class _FedSlots:
    """The first axis of a grid of a program of S > 1: the slots the
    launch is fed something, and no step for another - the axis' bound
    is data, as ``grouped_matmul``'s is. ``operands`` is what the launch
    prefetches behind ``p`` and ``fed``: the slots in the order of the
    axis, the fed ones first. A slot the grid does not reach keeps its
    pools, which the writers' outputs alias, bit for bit, and its rows
    of ``attend``'s output are whatever the buffer held (``_eva_pallas``
    masks them). Where no slot is fed the bound is 1 and slot 0 runs as
    a slot fed nothing runs in the S = 1 program."""

    def __init__(self, fed):
        live = fed > 0
        self.operands = (jnp.argsort(jnp.logical_not(live), stable=True)
                         .astype(jnp.int32),)
        self.bound = jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1)

    @staticmethod
    def at(index_map):
        def mapped(i, g, j, p_ref, fed_ref, order_ref):
            return index_map(order_ref[i], g, j, p_ref, fed_ref)
        return mapped

    @staticmethod
    def slot(refs):
        return refs[0][pl.program_id(0)]


def _slots(form, fed):
    return (_EverySlot if form == "decode" else _FedSlots)(fed)


def _place(select, rows, old):
    """``old`` (n, d) with row i replaced by ``rows[j]`` where
    ``select[i, j]``: the rows are laid by a one-hot matrix product,
    exact in any dtype, so that no store is ever unaligned."""
    exact = _HI if rows.dtype == _F32 else None   # bfloat16 is exact as is
    placed = jnp.dot(select.astype(rows.dtype), rows, precision=exact,
                     preferred_element_type=_F32)
    hit = jnp.sum(select.astype(jnp.int32), axis=-1, keepdims=True) > 0
    return jnp.where(hit, placed.astype(old.dtype), old)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _summarise_kernel(hb, n_c, n_pad, chunk, bt, n_blocks, scale, slots,
                      as_fed):
    """Grid (slot, head group, target block of the summary pool): pool
    the chunks of the head group and lay those that real rows complete
    into the pool's block. ``as_fed``: the new rows come as the op was
    fed them and are rotated to their offset in the cursor's chunk
    here (what wraps around lies under the ring's rows)."""
    def kernel(p_ref, fed_ref, *refs):
        (nk_ref, nv_ref, sk_ref, sv_ref, phi_ref, mu_ref, mk_ref, mv_ref,
         ok_ref, ov_ref) = refs[-10:]
        b, j = slots.slot(refs), pl.program_id(2)
        p = p_ref[b]
        c0 = p // chunk
        off = p - c0 * chunk
        block = jnp.minimum(c0 // bt + j, n_blocks - 1)
        # pool row block * bt + i takes chunk c0 + c where that chunk
        # is completed by this dispatch's real rows
        c = block * bt + _iota((bt, n_pad), 0) - c0
        select = (c == _iota((bt, n_pad), 1)) & (c < n_c) \
            & ((c0 + c + 1) * chunk <= p + fed_ref[b])
        head = _iota((chunk, 1), 0) < off

        def padded(x):                  # (n_c, d) -> (n_pad, d)
            if n_c == 1:
                return jnp.broadcast_to(x, (n_pad, x.shape[1]))
            if n_pad == n_c:
                return x
            return jnp.concatenate(
                [x, jnp.zeros((n_pad - n_c, x.shape[1]), x.dtype)], axis=0)

        for h in range(hb):
            pooled = []
            for new_ref, ring_ref in ((nk_ref, sk_ref), (nv_ref, sv_ref)):
                rows = new_ref[h].astype(_F32)              # (n, d)
                if as_fed:
                    rows = pltpu.roll(rows, off, 0)
                first = jnp.where(head, ring_ref[h].astype(_F32),
                                  rows[:chunk])
                pooled.append(first if n_c == 1 else jnp.concatenate(
                    [first, rows[chunk:]], axis=0))
            k, v = pooled
            d = k.shape[-1]
            phi = phi_ref[pl.ds(h, 1), :].astype(_F32)      # (1, d)
            logit = jnp.sum(k * phi, axis=-1, keepdims=True) * scale
            l3 = logit.reshape(n_c, chunk, 1)
            e = jnp.exp(l3 - jnp.max(l3, axis=1, keepdims=True))
            a = e / jnp.sum(e, axis=1, keepdims=True)
            ks = jnp.sum(a * k.reshape(n_c, chunk, d), axis=1) \
                + mu_ref[pl.ds(h, 1), :].astype(_F32)
            vs = jnp.sum(a * v.reshape(n_c, chunk, d), axis=1)
            ok_ref[h] = _place(select, padded(ks).astype(ok_ref.dtype),
                               mk_ref[h])
            ov_ref[h] = _place(select, padded(vs).astype(ov_ref.dtype),
                               mv_ref[h])
    return kernel


def _spanned(rows, block, n_blocks):
    """Blocks of ``block`` rows that ``rows`` consecutive rows can
    touch, at most all of them."""
    return 1 if rows == 1 else min(n_blocks, (rows - 1) // block + 2)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "form"))
def summarise(k, v, sk, sv, phi, mu, mk, mv, p, fed, chunk, interpret,
              form="decode"):
    """The kernel ``eva_summarise``: pools the chunks that this
    dispatch's rows ``k``, ``v`` complete (``eva_pool``; the chunk that
    holds the cursor takes its first rows from the ring) and lays them
    into the summary pools in place. Returns the pools. The kernel
    takes the rows at their offset in the cursor's chunk: a window of
    whole chunks it rotates there itself, any other comes laid out
    (``_chunk_rows``: passes over ``slots x S`` rows outside the kernel,
    1.1 ms a layer at the published sizes: PERF.md, PR 62). Like
    ``attend`` and ``write_rows`` a jitted function of its own, so that
    a program lowers each of its shapes once and calls it from every
    layer; ``form``: ``_NAMES``."""
    as_fed = form == "window" and k.shape[2] % chunk == 0
    new_k, new_v = (k, v) if as_fed else (
        _chunk_rows(x, p % chunk, chunk) for x in (k, v))
    B, H, n, d = new_k.shape
    W, n_sum = sk.shape[2], mk.shape[2]
    n_c = n // chunk
    n_pad = -(-n_c // 16) * 16
    bt = _pk._divisor_block(n_sum, 16)
    n_blocks = n_sum // bt
    per_head = 2 * d * mk.dtype.itemsize * (2 * n + 2 * chunk + 4 * bt)
    hb = _head_group(H, _BLOCK_BUDGET // per_head, multiple_of=8)
    slots = _slots(form, fed)

    def fixed(b, g, j, p_ref, fed_ref):
        return b, g, 0, 0

    def ring(b, g, j, p_ref, fed_ref):
        return b, g, ((p_ref[b] // chunk) * chunk % W) // chunk, 0

    def target(b, g, j, p_ref, fed_ref):
        return b, g, jnp.minimum(p_ref[b] // chunk // bt + j,
                                 n_blocks - 1), 0

    def vec(b, g, j, p_ref, fed_ref):
        return g, 0

    rows = pl.BlockSpec((None, hb, n, d), slots.at(fixed))
    old = pl.BlockSpec((None, hb, chunk, d), slots.at(ring))
    vecs = pl.BlockSpec((hb, d), slots.at(vec))
    pool = pl.BlockSpec((None, hb, bt, d), slots.at(target))
    n_prefetch = 2 + len(slots.operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(slots.bound, H // hb, _spanned(n_c, bt, n_blocks)),
        in_specs=[rows, rows, old, old, vecs, vecs, pool, pool],
        out_specs=(pool, pool))
    return _kernel_call(
        _summarise_kernel(hb, n_c, n_pad, chunk, bt, n_blocks,
                          float(d) ** -0.5, slots, as_fed),
        _NAMES["summarise"][form], interpret,
        out_shape=(jax.ShapeDtypeStruct(mk.shape, mk.dtype),
                   jax.ShapeDtypeStruct(mv.shape, mv.dtype)),
        grid_spec=grid_spec,
        input_output_aliases={n_prefetch + 6: 0, n_prefetch + 7: 1})(
            p, fed, *slots.operands, new_k, new_v, sk, sv, phi, mu, mk, mv)


def _write_kernel(hb, S, bt, n_blocks, window, slots):
    """Grid (slot, head group, target block of the ring): lay the fed
    rows at ``(cursor + s) mod window``."""
    def kernel(p_ref, fed_ref, *refs):
        nk_ref, nv_ref, sk_ref, sv_ref, ok_ref, ov_ref = refs[-6:]
        b, j = slots.slot(refs), pl.program_id(2)
        r0 = p_ref[b] % window
        block = (r0 // bt + j) % n_blocks
        s = block * bt + _iota((bt, S), 0) - r0
        s = jnp.where(s < 0, s + window, s)
        select = (s == _iota((bt, S), 1)) & (s < fed_ref[b])
        for h in range(hb):
            ok_ref[h] = _place(select, nk_ref[h], sk_ref[h])
            ov_ref[h] = _place(select, nv_ref[h], sv_ref[h])
    return kernel


@functools.partial(jax.jit, static_argnames=("interpret", "form"))
def write_rows(k, v, sk, sv, p, fed, interpret, form="decode"):
    """The kernel ``eva_write``: the first ``fed`` of each slot's new
    rows into the rings in place, at ``(cursor + s) mod W``. Returns
    the rings."""
    B, H, S, d = k.shape
    W = sk.shape[2]
    bt = _pk._divisor_block(W, 16 if S <= 16 else 128)
    n_blocks = W // bt
    per_head = 2 * d * sk.dtype.itemsize * (2 * S + 4 * bt)
    hb = _head_group(H, _BLOCK_BUDGET // per_head)
    slots = _slots(form, fed)

    def fixed(b, g, j, p_ref, fed_ref):
        return b, g, 0, 0

    def target(b, g, j, p_ref, fed_ref):
        return b, g, (p_ref[b] % W // bt + j) % n_blocks, 0

    rows = pl.BlockSpec((None, hb, S, d), slots.at(fixed))
    ring = pl.BlockSpec((None, hb, bt, d), slots.at(target))
    n_prefetch = 2 + len(slots.operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(slots.bound, H // hb, _spanned(S, bt, n_blocks)),
        in_specs=[rows, rows, ring, ring], out_specs=(ring, ring))
    return _kernel_call(
        _write_kernel(hb, S, bt, n_blocks, W, slots),
        _NAMES["write"][form], interpret,
        out_shape=(jax.ShapeDtypeStruct(sk.shape, sk.dtype),
                   jax.ShapeDtypeStruct(sv.shape, sv.dtype)),
        grid_spec=grid_spec,
        input_output_aliases={n_prefetch + 2: 0, n_prefetch + 3: 1})(
            p, fed, *slots.operands, k, v, sk, sv)


def _attn_kernel(hb, S, bs, bm, nb_s, nb_m, window, per_window, scale,
                 slots):
    """Grid (slot, head group, key block): the ring's blocks, then the
    summary pool's, then the S new rows and the division. Online
    softmax per head in float32 scratch."""
    def kernel(p_ref, fed_ref, *refs):
        (q_ref, kn_ref, vn_ref, sk_ref, sv_ref, mk_ref, mv_ref, o_ref, m_s,
         l_s, acc_s) = refs[-11:]
        b, j = slots.slot(refs), pl.program_id(2)
        p = p_ref[b]
        r0 = p % window
        w0 = p // window
        bound = (w0 + 1) * window          # first position of the next window
        last = p + jnp.maximum(fed_ref[b], 1) - 1
        n_vis = (last // window) * per_window

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, _F32)
            l_s[...] = jnp.zeros(l_s.shape, _F32)
            acc_s[...] = jnp.zeros(acc_s.shape, _F32)

        def row_iota(n):
            return _iota((S, n), 0)

        def col_iota(n):
            return _iota((S, n), 1)

        def accumulate(k_ref, v_ref, mask):
            for h in range(hb):
                s = lax.dot_general(
                    q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                    preferred_element_type=_F32) * scale
                s = jnp.where(mask, s, -jnp.inf)
                m = m_s[h]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                e = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
                m_s[h] = m_new
                l_s[h] = l_s[h] * corr + jnp.sum(e, axis=-1, keepdims=True)
                v_blk = v_ref[h]
                acc_s[h] = acc_s[h] * corr + jnp.dot(
                    e.astype(v_blk.dtype), v_blk,
                    preferred_element_type=_F32)

        @pl.when((j < nb_s) & (j * bs < r0))
        def _ring():
            in_start = p + row_iota(bs) < bound
            accumulate(sk_ref, sv_ref,
                       (j * bs + col_iota(bs) < r0) & in_start)

        jm = j - nb_s

        @pl.when((jm >= 0) & (jm < nb_m) & (jm * bm < n_vis))
        def _summaries():
            seen = jnp.where(p + row_iota(bm) < bound,
                             w0 * per_window, (w0 + 1) * per_window)
            accumulate(mk_ref, mv_ref, jm * bm + col_iota(bm) < seen)

        @pl.when(j == nb_s + nb_m)
        def _new_rows():
            q_next = p + row_iota(S) >= bound
            k_prev = p + col_iota(S) < bound
            accumulate(kn_ref, vn_ref,
                       (col_iota(S) <= row_iota(S)) & ~(q_next & k_prev))
            for h in range(hb):
                o_ref[h] = (acc_s[h] / l_s[h]).astype(o_ref.dtype)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("window", "chunk", "interpret", "form"))
def attend(q, k, v, sk, sv, mk, mv, p, fed, window, chunk, interpret,
           form="decode"):
    """``eva_attend`` as one kernel, ``eva_attn_decode`` at S = 1 and
    ``eva_attn_window`` beyond: per slot and head group, the blocks of
    the ring below the cursor, the blocks of the summary pool that a
    real query of the dispatch can see, and the new rows. A block past
    either bound re-references the last live one, so it moves no data
    and computes nothing. The rows are padded to 16 so that the scores
    are matrix products at S = 1 too; a pad row is a later query that
    nobody reads. In a program of S > 1 the rows of a slot the launch
    is fed nothing come out undefined (``_FedSlots``)."""
    B, H, S, d = q.shape
    W, n_sum, per_window = sk.shape[2], mk.shape[2], window // chunk
    S_pad = k.shape[2]                  # the rows come padded to 16
    q = jnp.pad(q.astype(sk.dtype),
                ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    bs, bm = _pk._divisor_block(W, 512), _pk._divisor_block(n_sum, 512)
    nb_s, nb_m = W // bs, n_sum // bm
    per_head = 2 * d * sk.dtype.itemsize * (3 * S_pad + 2 * bs + 2 * bm)
    hb = _head_group(H, _BLOCK_BUDGET // per_head)
    slots = _slots(form, fed)

    def new_map(b, g, j, p_ref, fed_ref):
        return b, g, 0, 0

    def ring_map(b, g, j, p_ref, fed_ref):
        live = jnp.maximum((p_ref[b] % window + bs - 1) // bs, 1)
        return b, g, jnp.minimum(j, live - 1), 0

    def summary_map(b, g, j, p_ref, fed_ref):
        last = p_ref[b] + jnp.maximum(fed_ref[b], 1) - 1
        live = jnp.maximum(
            ((last // window) * per_window + bm - 1) // bm, 1)
        return b, g, jnp.clip(j - nb_s, 0, live - 1), 0

    new = pl.BlockSpec((None, hb, S_pad, d), slots.at(new_map))
    ring = pl.BlockSpec((None, hb, bs, d), slots.at(ring_map))
    summary = pl.BlockSpec((None, hb, bm, d), slots.at(summary_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(slots.operands),
        grid=(slots.bound, H // hb, nb_s + nb_m + 1),
        in_specs=[new, new, new, ring, ring, summary, summary],
        out_specs=new,
        scratch_shapes=[pltpu.VMEM((hb, S_pad, 1), _F32),
                        pltpu.VMEM((hb, S_pad, 1), _F32),
                        pltpu.VMEM((hb, S_pad, d), _F32)])
    out = _kernel_call(
        _attn_kernel(hb, S_pad, bs, bm, nb_s, nb_m, window, per_window,
                     float(d) ** -0.5, slots),
        _NAMES["attend"][form], interpret,
        out_shape=jax.ShapeDtypeStruct((B, H, S_pad, d), _F32),
        grid_spec=grid_spec)(
            p, fed, *slots.operands, q, k, v, sk, sv, mk, mv)
    return out[:, :, :S]


def _serve(q, k, v, fed, sk, sv, mk, mv, phi, mu, p, W, C, form):
    """The three launches of one form, in the op's order: the summaries
    written, then the read, then the rows written. Returns the read and
    the four pools."""
    interpret = _pk._interpret()
    mk, mv = summarise(k, v, sk, sv, phi, mu, mk, mv, p, fed, chunk=C,
                       interpret=interpret, form=form)
    grow = ((0, 0), (0, 0), (0, -q.shape[2] % 16), (0, 0))
    k, v = jnp.pad(k, grow), jnp.pad(v, grow)
    out = attend(q, k, v, sk, sv, mk, mv, p, fed, window=W, chunk=C,
                 interpret=interpret, form=form)
    sk, sv = write_rows(k, v, sk, sv, p, fed, interpret=interpret, form=form)
    return out, sk, sv, mk, mv


def _eva_pallas(attrs, inputs, aux, is_train, rng):
    """The kernels' lowering: every read and write of a pool is a
    kernel's aligned block, so the pools keep their layout and, their
    arrays donated, are updated in place. Which launches a slot takes
    in a program of S > 1 is read from ``fed``: more than one row, the
    window's; exactly one (a decoding slot that rides a prefill
    window), the S = 1 program's geometry on its first row, whose
    result is row 0 of the slot's output; nothing, neither, and its
    rows come out zero. Slots are independent, so the two triples
    follow each other."""
    q, k, v, fed, p, W, C = _prologue(attrs, inputs, aux, is_train)
    phi, mu = inputs[4:]
    pools = aux[:4]
    S = q.shape[2]
    if S == 1:
        out, *pools = _serve(q, k, v, fed, *pools, phi, mu, p, W, C,
                             "decode")
        return _epilogue(out, q, *pools, p, fed)
    riding = fed == 1
    out, *pools = _serve(q, k, v, jnp.where(riding, 0, fed), *pools, phi,
                         mu, p, W, C, "window")
    ride, *pools = _serve(q[:, :, :1], k[:, :, :1], v[:, :, :1],
                          riding.astype(jnp.int32), *pools, phi, mu, p, W,
                          C, "ride")
    # one elementwise pass, which the cast behind it takes in: a slot's
    # rows are the launch's that ran it, and what no launch wrote is 0
    first = (jnp.arange(S) == 0)[None, None, :, None]
    out = jnp.where((fed > 1)[:, None, None, None], out,
                    jnp.where(riding[:, None, None, None] & first, ride, 0.0))
    return _epilogue(out, q, *pools, p, fed)


def _eva_eligible(attrs, in_shapes, in_dtypes):
    """Float rows, and on the chip lane-aligned heads, a chunk of whole
    sublane tiles and pools of whole 128-row blocks; anything in
    interpret mode."""
    if len(in_shapes) < 11 or len(in_shapes[0]) != 4:
        return False
    if any(str(in_dtypes[i]) not in ("float32", "bfloat16")
           for i in (0, 6, 8)):
        return False
    if _pk._interpret():
        return True
    d, window, n_sum = in_shapes[0][3], in_shapes[6][2], in_shapes[8][2]
    chunk = parse_int(attrs["chunk"])
    return d % 128 == 0 and chunk % 16 == 0 and window % 128 == 0 \
        and n_sum % 128 == 0


#: a head group's blocks at the published sizes (d 128, S 512, blocks
#: of 512 rows): q, the new rows, two ring blocks, two summary blocks,
#: the float32 accumulator and the output
_EVA_KSPEC = {
    "tiles": [((512, 128), "float32")] * 9,
    "dtypes": ("float32", "bfloat16"),
}

#: which of the op's aux cells hold what, per decode slot: the cache
#: driver (``models.transformer.BatchedKVCacheDecoder``) and
#: ``DecodeEngine.migrate`` read this and know no cell by its name
EVA_SLOT_STATE = {"singles_k": "window", "singles_v": "window",
                  "summary_k": "summary", "summary_v": "summary",
                  "cache_pos": "cursor"}

#: what one execution reads and writes of the state
#: (``OpDef.state_reads``): the exact rows and the summaries that each
#: fed slot's last real query attends, the chunks summarised and the
#: windows closed; and of the slots a dispatch with a slot fed more
#: than one row feeds, those that take the window launches and those
#: that ride (``_eva_pallas``)
_EVA_COUNTS = read_counts(
    ("eva.layer_steps", None), ("eva.exact_rows", "eva_exact"),
    ("eva.summary_rows", "eva_summary"), ("eva.chunks_summarised", None),
    ("eva.windows_closed", None), ("eva.window_slots", None),
    ("eva.ride_slots", None), ("eva.fed_slots", None))


def _eva_reads(attrs, capacity, sources):
    _, W, C = _geometry(attrs)

    def reads(pos, fed):
        live = fed > 0
        start, end = pos[live], (pos + fed)[live]
        last = end - 1
        window = int(np.sum(fed > 1))
        ride = int(np.sum(fed == 1)) if window else 0
        return {"eva.layer_steps": 1,
                "eva.exact_rows": int(np.sum(last % W + 1)),
                "eva.summary_rows": int(np.sum(last // W * (W // C))),
                "eva.chunks_summarised": int(np.sum(end // C - start // C)),
                "eva.windows_closed": int(np.sum(end // W - start // W)),
                "eva.window_slots": window, "eva.ride_slots": ride,
                "eva.fed_slots": window + ride}

    return reads


register("eva_attention_decode",
         inputs=("q", "k", "v", "fed", "phi", "mu"),
         aux=tuple(EVA_SLOT_STATE), full=_eva_fwd, stateful_infer=True,
         aux_dtypes={"cache_pos": "int32"}, infer_shape=_eva_infer,
         attr_spec={"capacity": (parse_int, None),
                    "window": (parse_int, None),
                    "chunk": (parse_int, None),
                    "rope_base": (parse_float, 10000.0)},
         slot_state=EVA_SLOT_STATE, state_reads=(_EVA_COUNTS, _eva_reads),
         donate_aux=True,
         variants={"pallas": (_eva_pallas, _eva_eligible, _EVA_KSPEC)},
         doc="EVA chunked linearized attention over a per-slot decode "
             "state of exact window rows and chunk summaries "
             "(ops/eva.py).")
