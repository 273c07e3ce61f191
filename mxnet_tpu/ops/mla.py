"""Multi-head latent attention over a latent cache
(``mla_attention_decode``) and the learned sparse selection that feeds
it (``dsa_index_select``): the attention of DeepSeek-V2/V3 (MLA,
arXiv:2405.04434) with DeepSeek-V3.2's sparse attention (DSA: a
lightning indexer and a top-k) as GLM-5.2 runs it, one selection for a
period of layers (IndexShare).

**The latent cache.** A position's keys and values of all H heads are
functions of one row ``[c_kv ; k_r]`` (``kv_rank`` + ``rope_dim``
numbers): ``[k_n ; v]_h = W_kvb[h] c_kv`` and the rotary key ``k_r`` is
shared by the heads. The op keeps that row and nothing else, as a
``"rows"`` pool ``latent (slots, 1, capacity, width)`` beside a
``cache_pos (slots, 1)`` cursor, so the driver's whole positional
contract holds (rewind, row capture, migrate). ``width`` is
``kv_rank + rope_dim`` rounded up to whole 128-lane tiles, the rest
zeros (512 + 64 -> 640): the TPU lays an array whose last axis is not
a multiple of 128 with another axis minor - here the capacity - and
every kernel would copy the pool into rows and back. With
``s = (nope_dim + rope_dim) ** -0.5``:

    s_tj = s * (q_n[t,h] . k_n[j,h] + RoPE(q_r[t,h]) . RoPE(k_r[j]))
    o_th = sum_{j in S_t} softmax_j(s_tj) v[j,h]

``forward`` (the XLA composition, and the statement of the above)
expands ``k_n`` and ``v`` of the whole pool. The ``pallas`` variant
walks the live blocks of the pool once for all heads with a flash-style
kernel, in one of two forms of the same sums, and takes for each
geometry the one that geometry pays least for:

* **absorbed**, at S = 1 (``mla_attn_decode``): ``q_n W_kb`` is scored
  against ``c_kv`` itself and the weighted sum of ``c_kv`` goes through
  ``W_vb`` afterwards. A (query, key) pair of a head costs 2 x (640 +
  512) FLOP where the expanded widths would cost 2 x (192 + 128), but
  the 64 heads of a slot's one query are the rows of ONE product a key
  block, nothing is expanded, and the step is the read of the pool.
* **expanded**, for a window's chunks (``mla_attn_window``): a key
  block is expanded once a head to ``k_n = c_kv W_kb[h]^T`` and ``v =
  c_kv W_vb[h]^T``, rounded to the pool's dtype as the composition
  rounds them, and every query block of the slot's chunk - whose
  softmax state stays in VMEM across the key blocks - is scored against
  it at ``nope_dim + rope_dim`` lanes and sums ``v``. The expansion is
  2 x 512 x (``nope_dim`` + ``v_dim``) FLOP a key a head whatever the
  chunk, so over ``n`` fed rows a pair costs 640 + 262,144 / n at the
  DeepSeek-V3 widths (128 + 64, 128) against the absorbed 2,176 at 576
  real lanes, and 1,024 + 458,752 / n at GLM-5.2's (192 + 64, 256)
  against 2,304: even at 171 and 358 fed rows, 2.4 and 1.56 times
  cheaper at 1,024. Measured on a v5e the whole launch is ahead from
  128 fed rows on at 12 k and 17 k keys at all three published widths
  (``PERF.md`` section 6, PR 49), so the form is unconditional.

A slot that a window feeds ONE row (a decoding slot riding another's
prefill: most slots of most windows) has nothing to amortise an
expansion of its whole context over, so it is dead to the window form -
its steps re-reference their live neighbour's blocks and move nothing
but the zeros they write - and takes the absorbed one inside the window
program (``mla_attn_ride``, the S = 1 kernel); ``fed`` says which.

**The selection.** ``S_t`` arrives as ``selection (slots, S, capacity)``
int8, 1 where position j is attended by query t - an input, because it
crosses layers outside the residual stream: a layer with an indexer
(``dsa_index_select``) computes it and the layers after it that have
none take the same array. The indexer keeps its own ``"rows"`` pool
``index_k (slots, 1, capacity, head_dim)`` and cursor, and computes

    I_tj = sum_h w_th * relu(q_th . k_j)        j <= t
    S_t  = {j : I_tj >= the topk-th largest of I_t.}    (all j <= t
                                              while t < topk)

The set is a mask over the pool and the attention kernel reads every
live block under it: at a block of 512 rows a block without a selected
row is rare, so a gather by block would read the same rows, and a
gather by row is ``topk`` copies of a kilobyte a query. What the
selection saves here is nothing but the softmax's support; the kernel's
roofline share says so.

Both ops take ``fed (slots,)``: how many of a slot's S tokens are real.
All S rows are written at the cursor (a row past ``fed`` is overwritten
by the next dispatch before anything attends it) and the cursor moves
by ``fed``; a slot whose S rows do not fit below the capacity writes
nothing and stays (``cache_write``'s rule).
"""
from __future__ import annotations

import math
from collections import namedtuple
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import MXNetError, parse_bool, parse_float, parse_int
from . import pallas_kernels as _pk
from .moe import cpu_wide, rms_norm
from .registry import read_counts, register

__all__ = ["rope_interleaved", "yarn_inv_freq", "yarn_mscale",
           "RopeScaling", "dsa_scores", "dsa_threshold_mask", "latent_width"]

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_VMEM_LIMIT = 96 << 20


def yarn_mscale(factor, mscale):
    """YaRN's attention-magnitude factor ``m(a) = 0.1 a ln(factor) + 1``
    (1 without scaling)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def yarn_inv_freq(d, base, factor, original_positions, beta_fast=32.0,
                  beta_slow=1.0):
    """The ``d // 2`` rotary frequencies under YaRN (arXiv:2309.00071,
    as the DeepSeek-V3 family computes it) and its ramp's ``(low,
    high)``: ``theta_i = base ** (-2i / d)``; a pair that turns more
    than ``beta_fast`` times over ``original_positions`` keeps
    ``theta_i``, one that turns less than ``beta_slow`` times gets
    ``theta_i / factor``, and the pairs between ``low`` and ``high``
    blend the two linearly. float64 on the host, so that whoever states
    the same formula rounds to the same float32."""
    i = np.arange(d // 2, dtype=np.float64)
    theta = float(base) ** (-2.0 * i / d)

    def pair_turning(turns):
        return d * math.log(original_positions / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(float(base)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / float(factor) * ramp, (low, high)


#: ``(factor, original_positions, beta_fast, beta_slow, trig_scale)`` of
#: a rotary under YaRN; None is the plain rotary
RopeScaling = namedtuple(
    "RopeScaling", "factor original_positions beta_fast beta_slow trig_scale")


def rope_interleaved(x, positions, base, scaling=None):
    """Rotate the adjacent pairs ``(x[2i], x[2i+1])`` of ``x``
    (..., T, d) by ``positions[..., t] * base ** (-2i / d)``;
    ``positions`` broadcasts against ``x``'s leading axes up to T. Trig
    in float32, cast back. ``scaling`` (``RopeScaling``) turns the
    pairs by YaRN's blended frequencies instead and scales cos and sin
    by its ``trig_scale``."""
    d = x.shape[-1]
    if scaling is None:
        inv = jnp.asarray(base, _F32) ** (
            -jnp.arange(0, d // 2, dtype=_F32) * (2.0 / d))
    else:
        inv = jnp.asarray(yarn_inv_freq(
            d, base, scaling.factor, scaling.original_positions,
            scaling.beta_fast, scaling.beta_slow)[0], _F32)
    ang = positions.astype(_F32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None and scaling.trig_scale != 1.0:
        cos, sin = cos * scaling.trig_scale, sin * scaling.trig_scale
    pair = x.astype(_F32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _cursor(fed, cursor, S, capacity):
    """Each slot's position and the rows it is really fed (module
    docstring)."""
    B = cursor.shape[0]
    p = cursor.reshape((B,)).astype(jnp.int32)
    fed = jnp.where(p + S <= capacity,
                    jnp.clip(fed.reshape((B,)).astype(jnp.int32), 0, S), 0)
    return p, (p + fed).reshape((B, 1)).astype(jnp.int32)


def _positions(p, S):
    return p[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]   # (B, S)


def _mm(spec, a, b):
    """``einsum`` of two arrays of one dtype with float32 accumulation
    and result."""
    return jnp.einsum(spec, *cpu_wide(a, b), preferred_element_type=_F32)


def _compiler_params(semantics):
    if _pk._interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)}


# ------------------------------------------------------------ the selection
def dsa_scores(q, w, keys, p):
    """``I (B, S, C)`` float32 of the module docstring for queries
    ``q (B, S, Hi, d)`` with weights ``w (B, S, Hi)`` against every row
    of ``keys (B, C, d)``, ``-inf`` where ``j > t``. The plain form."""
    S = q.shape[1]
    dots = _mm("bshd,bkd->bshk", q, keys.astype(q.dtype))
    score = jnp.einsum("bshk,bsh->bsk", jnp.maximum(dots, 0.0),
                       w.astype(_F32), precision=_HI)
    causal = jnp.arange(keys.shape[1])[None, None, :] \
        <= _positions(p, S)[:, :, None]
    return jnp.where(causal, score, -jnp.inf)


def dsa_threshold_mask(score, topk):
    """int8 ``(B, S, C)``: 1 where a finite score is at least the
    ``topk``-th largest of its row (every finite one where the row has
    fewer). Scores that tie with the ``topk``-th are all kept."""
    k = min(int(topk), score.shape[-1])
    kth = lax.top_k(score, k)[0][..., -1:]
    return ((score >= kth) & (score > -jnp.inf)).astype(jnp.int8)


def _index_geometry(attrs):
    return (parse_int(attrs["capacity"]), parse_int(attrs["n_heads"]),
            parse_int(attrs["head_dim"]), parse_int(attrs["rope_dim"]),
            parse_int(attrs["topk"]))


def _index_prologue(attrs, inputs, aux, is_train):
    """What both lowerings share: the cursor, q and k rotated on their
    first ``rope_dim`` dimensions, the weights with both scale factors
    folded in, k at the pool's dtype as a pool-shaped row block."""
    if is_train:
        raise MXNetError("dsa_index_select is an inference op")
    q, k, w, fed = inputs
    pool, cursor = aux
    capacity, Hi, d, dr, topk = _index_geometry(attrs)
    B, S = q.shape[:2]
    p, new_cursor = _cursor(fed, cursor, S, capacity)
    base = parse_float(attrs.get("rope_base", 10000.0))
    pos = _positions(p, S)
    q = q.reshape(B, S, Hi, d)
    q = jnp.concatenate(
        [rope_interleaved(q[..., :dr], pos[:, :, None], base),
         q[..., dr:]], axis=-1)
    k = jnp.concatenate(
        [rope_interleaved(k[..., :dr], pos, base), k[..., dr:]], axis=-1)
    w = w.astype(_F32) * (float(Hi) ** -0.5 * float(d) ** -0.5)
    return q, k.astype(pool.dtype)[:, None], w, p, new_cursor, topk


def _index_fwd(attrs, inputs, aux, is_train, rng):
    from ..rtc import _write_rows
    q, k, w, p, new_cursor, topk = _index_prologue(attrs, inputs, aux,
                                                   is_train)
    pool, = _write_rows([k], [aux[0]], p)
    score = dsa_scores(q, w, pool[:, 0], p)
    return [dsa_threshold_mask(score, topk)], [pool, new_cursor]


def _index_infer(attrs, in_shapes):
    q_s = in_shapes[0]
    if q_s is None:
        return in_shapes, [None], [None, None]
    capacity, Hi, d, _dr, _topk = _index_geometry(attrs)
    B, S = q_s[:2]
    return ([(B, S, Hi * d), (B, S, d), (B, S, Hi), (B,)],
            [(B, S, capacity)], [(B, 1, capacity, d), (B, 1)])


# ------------------------------------------------------ the selection, kernels
def _index_window_kernel(Hi, tq, bk):
    """Grid (slot, query block, key block): the scores of ``tq``
    queries against ``bk`` keys, head by head; a block no query of the
    block can see, or whose queries are all past ``fed`` (pads), is
    ``-inf`` without a product."""
    def kernel(p_ref, fed_ref, q_ref, w_ref, k_ref, o_ref):
        b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        t0 = p_ref[b] + i * tq
        live = (j * bk <= t0 + tq - 1) & (i * tq < fed_ref[b])

        @pl.when(jnp.logical_not(live))
        def _dead():
            o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, _F32)

        @pl.when(live)
        def _live():
            k = k_ref[...]
            w = w_ref[...]
            acc = jnp.zeros((tq, bk), _F32)
            for h in range(Hi):
                dots = lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                       preferred_element_type=_F32)
                acc = acc + w[:, h:h + 1] * jnp.maximum(dots, 0.0)
            t = t0 + lax.broadcasted_iota(jnp.int32, (tq, bk), 0)
            key = j * bk + lax.broadcasted_iota(jnp.int32, (tq, bk), 1)
            o_ref[...] = jnp.where(key <= t, acc, -jnp.inf)
    return kernel


def _index_decode_kernel(bk):
    """Grid (slot, key block) at S = 1: the heads are the rows of one
    product, weighted and summed over."""
    def kernel(p_ref, fed_ref, q_ref, w_ref, k_ref, o_ref):
        b, j = pl.program_id(0), pl.program_id(1)
        t = p_ref[b]

        @pl.when(j * bk > t)
        def _dead():
            o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, _F32)

        @pl.when(j * bk <= t)
        def _live():
            dots = lax.dot_general(q_ref[...], k_ref[...],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=_F32)
            acc = jnp.sum(w_ref[...] * jnp.maximum(dots, 0.0), axis=0,
                          keepdims=True)
            key = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            o_ref[...] = jnp.where(key <= t, acc, -jnp.inf)
    return kernel


@partial(jax.jit, static_argnames=("interpret",))
def _index_scores(p, fed, q, w, pool, interpret):
    """The kernel ``dsa_index_scores``: ``dsa_scores`` over the live
    blocks of the pool. A jitted function of its own, so that a step
    program lowers it once and calls it from every layer that has an
    indexer."""
    B, S, Hi, d = q.shape
    C = pool.shape[2]
    keys = pool.reshape(B, C, d)
    q = q.astype(pool.dtype)
    if S == 1:
        bk = _pk._divisor_block(C, 2048)

        def live(b, j, p_ref, fed_ref):
            return b, jnp.minimum(j, p_ref[b] // bk), 0

        def fixed(b, j, p_ref, fed_ref):
            return b, 0, 0

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, C // bk),
            in_specs=[pl.BlockSpec((None, Hi, d), fixed),
                      pl.BlockSpec((None, Hi, 1), fixed),
                      pl.BlockSpec((None, bk, d), live)],
            out_specs=pl.BlockSpec((None, 1, bk),
                                   lambda b, j, p, f: (b, 0, j)))
        return _pk.pallas_call(
            _index_decode_kernel(bk), name="dsa_index_scores",
            out_shape=jax.ShapeDtypeStruct((B, 1, C), _F32),
            grid_spec=grid_spec, interpret=interpret,
            **_compiler_params(("parallel", "arbitrary")))(
                p, fed, q.reshape(B, Hi, d), w.reshape(B, Hi, 1), keys)
    tq, bk = _pk._divisor_block(S, 256), _pk._divisor_block(C, 512)

    def live(b, i, j, p_ref, fed_ref):
        # a dead block re-references the last live one: no copy
        last = jnp.where(i * tq < fed_ref[b],
                         (p_ref[b] + (i + 1) * tq - 1) // bk, 0)
        return b, jnp.minimum(j, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, S // tq, C // bk),
        in_specs=[pl.BlockSpec((None, Hi, tq, d),
                               lambda b, i, j, p, f: (b, 0, i, 0)),
                  pl.BlockSpec((None, tq, Hi),
                               lambda b, i, j, p, f: (b, i, 0)),
                  pl.BlockSpec((None, bk, d), live)],
        out_specs=pl.BlockSpec((None, tq, bk),
                               lambda b, i, j, p, f: (b, i, j)))
    return _pk.pallas_call(
        _index_window_kernel(Hi, tq, bk), name="dsa_index_scores",
        out_shape=jax.ShapeDtypeStruct((B, S, C), _F32),
        grid_spec=grid_spec, interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")))(
            p, fed, q.transpose(0, 2, 1, 3), w, keys)


def _sortable(x):
    """float32 -> int32 whose order is the floats' (``-inf`` lowest)."""
    u = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(u < 0, u ^ jnp.int32(0x7FFFFFFF), u)


def _topk_kernel(tq, C, chunk, topk):
    """Grid (slot, query block): the ``topk``-th largest of each row by
    a search over the bits of its sortable integer form - 32 counts of
    the keys at or above a candidate, each over the chunks a query of
    the block can see - and the mask at or above it."""
    n_chunks = C // chunk
    k = min(topk, C)

    def search(last, s_ref, o_ref, key_s):
        key_s[...] = _sortable(s_ref[...])

        def enough(cand):
            count = jnp.zeros((tq, 1), jnp.int32)
            for c in range(n_chunks):
                part = lax.cond(
                    c * chunk <= last,
                    lambda c=c: jnp.sum(
                        (key_s[:, c * chunk:(c + 1) * chunk] >= cand)
                        .astype(jnp.int32), axis=1, keepdims=True),
                    lambda: jnp.zeros((tq, 1), jnp.int32))
                count = count + part
            return count >= k

        lowest = jnp.full((tq, 1), -2 ** 31, jnp.int32)
        thr = jnp.where(enough(jnp.zeros((tq, 1), jnp.int32)), 0, lowest)

        def bit(n, thr):
            cand = thr + lax.shift_left(jnp.int32(1), 30 - n)
            return jnp.where(enough(cand), cand, thr)

        thr = lax.fori_loop(0, 31, bit, thr)
        keep = (key_s[...] >= thr) & (s_ref[...] > -jnp.inf)
        o_ref[...] = keep.astype(jnp.int32).astype(jnp.int8)

    def kernel(p_ref, fed_ref, s_ref, o_ref, key_s):
        b, i = pl.program_id(0), pl.program_id(1)
        last = p_ref[b] + (i + 1) * tq - 1

        @pl.when(i * tq >= fed_ref[b])
        def _pads():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

        pl.when(i * tq < fed_ref[b])(
            lambda: search(last, s_ref, o_ref, key_s))

    return kernel


@partial(jax.jit, static_argnames=("topk", "interpret"))
def _topk_mask(p, fed, score, topk, interpret):
    """The kernel ``dsa_topk``: ``dsa_threshold_mask`` a block of rows
    at a time, the rows resident in VMEM for all 32 passes."""
    B, S, C = score.shape
    tq = _pk._divisor_block(S, 32)
    chunk = _pk._divisor_block(C, 2048)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, S // tq),
        in_specs=[pl.BlockSpec((None, tq, C), lambda b, i, p, f: (b, i, 0))],
        out_specs=pl.BlockSpec((None, tq, C), lambda b, i, p, f: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((tq, C), jnp.int32)])
    return _pk.pallas_call(
        _topk_kernel(tq, C, chunk, topk), name="dsa_topk",
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.int8),
        grid_spec=grid_spec, interpret=interpret,
        **_compiler_params(("parallel", "parallel")))(p, fed, score)


def _index_pallas(attrs, inputs, aux, is_train, rng):
    q, k, w, p, new_cursor, topk = _index_prologue(attrs, inputs, aux,
                                                   is_train)
    interpret = _pk._interpret()
    pool, = _pk._cache_write(p, (k,), (aux[0],), interpret=interpret,
                             name="dsa_write")
    # a slot at S = 1 is scored whether it is fed or not (one row)
    fed = jnp.maximum(new_cursor.reshape(-1) - p, 1 if q.shape[1] == 1 else 0)
    score = _index_scores(p, fed, q, w, pool, interpret=interpret)
    return ([_topk_mask(p, fed, score, topk=topk, interpret=interpret)],
            [pool, new_cursor])


def _aligned(*sizes):
    return all(s % 128 == 0 for s in sizes)


def _index_eligible(attrs, in_shapes, in_dtypes):
    if len(in_shapes) < 6 or len(in_shapes[0]) != 3:
        return False
    if str(in_dtypes[0]) not in ("float32", "bfloat16"):
        return False
    capacity, _Hi, d, _dr, _topk = _index_geometry(attrs)
    return _pk._interpret() or _aligned(capacity, d)


DSA_SLOT_STATE = {"index_k": "rows", "cache_pos": "cursor"}

#: one head's blocks of the window kernel at the published sizes: 256
#: queries, a key block of 512, the scores and their sum; the top-k's
#: rows are declared by the chunk it counts at a time
_DSA_KSPEC = {
    "tiles": [((256, 128), "bfloat16")] * 2
    + [((512, 128), "bfloat16")] * 2 + [((256, 512), "float32")] * 4
    + [((32, 2048), "float32")] * 4,
    "dtypes": ("float32", "bfloat16"),
}

#: what a dispatch reads under a learned selection
#: (``OpDef.state_reads``), for each fed slot's last real query: the
#: index keys the indexer scores (here), and on every attention layer
#: under the selection (``mla_attention_decode``) the positions at or
#: before the query - what attention without a selection would read -
#: and those it attends, at most the ``topk`` of the indexer that made
#: the selection
_DSA_COUNTS = read_counts(
    ("dsa.layer_steps", None), ("dsa.live_rows", None),
    ("dsa.selected_rows", "dsa_selected"), ("dsa.scored_rows", "dsa_scored"))


def _index_reads(attrs, capacity, sources):
    def reads(pos, fed):
        return {"dsa.scored_rows": int(np.sum((pos + fed)[fed > 0]))}

    return reads


register("dsa_index_select", inputs=("q", "k", "weights", "fed"),
         aux=tuple(DSA_SLOT_STATE), full=_index_fwd, stateful_infer=True,
         aux_dtypes={"cache_pos": "int32"}, infer_shape=_index_infer,
         attr_spec={"capacity": (parse_int, None),
                    "n_heads": (parse_int, None),
                    "head_dim": (parse_int, None),
                    "rope_dim": (parse_int, None),
                    "topk": (parse_int, None),
                    "rope_base": (parse_float, 10000.0)},
         slot_state=DSA_SLOT_STATE,
         state_reads=(_DSA_COUNTS, _index_reads), donate_aux=True,
         variants={"pallas": (_index_pallas, _index_eligible, _DSA_KSPEC)},
         doc="DSA lightning indexer over a per-slot pool of index keys: "
             "the top-k positions of every query, as a mask (ops/mla.py).")


# ------------------------------------------------------------ the attention
def _mla_geometry(attrs):
    return (parse_int(attrs["capacity"]), parse_int(attrs["n_heads"]),
            parse_int(attrs["nope_dim"]), parse_int(attrs["rope_dim"]),
            parse_int(attrs["v_dim"]), parse_int(attrs["kv_rank"]))


def latent_width(kv_rank, rope_dim):
    """Lanes of a latent row: ``kv_rank + rope_dim`` in whole tiles of
    128 (module docstring)."""
    return -(-(int(kv_rank) + int(rope_dim)) // 128) * 128


def _lanes(x, width):
    """``x`` with zeros appended to its last axis up to ``width``."""
    grow = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, grow) if width > x.shape[-1] else x


def _mla_selected(attrs):
    """Does the op attend under a selection (an input), or every
    position at or before the query (``selected=False``)?"""
    return parse_bool(attrs.get("selected", True))


def _mla_rope(attrs):
    """``(base, RopeScaling or None, softmax scale factor)`` of the op's
    rotary: YaRN by the ``rope_*`` attributes, as the DeepSeek-V3 family
    applies it - cos and sin by ``m(mscale) / m(mscale_all_dim)``, the
    softmax scale by ``m(mscale_all_dim) ** 2``. ``rope_factor`` 1 is
    the plain rotary and the plain scale."""
    base = parse_float(attrs.get("rope_base", 10000.0))
    factor = parse_float(attrs.get("rope_factor", 1.0))
    if factor == 1.0:
        return base, None, 1.0
    all_dim = yarn_mscale(factor, parse_float(
        attrs.get("rope_mscale_all_dim", 0.0)))
    trig = yarn_mscale(factor, parse_float(attrs.get("rope_mscale", 1.0))) \
        / all_dim
    return base, RopeScaling(
        factor, parse_int(attrs.get("rope_original_positions", 4096)),
        parse_float(attrs.get("rope_beta_fast", 32.0)),
        parse_float(attrs.get("rope_beta_slow", 1.0)), trig), all_dim ** 2


def _mla_prologue(attrs, inputs, aux, is_train):
    """What both lowerings share: the cursor, the new latent rows
    (``c_kv`` normalised, ``k_r`` rotated) at the pool's dtype, the
    queries split and rotated, ``W_kvb`` by head; ``selection`` is None
    for an op that takes none."""
    if is_train:
        raise MXNetError("mla_attention_decode is an inference op")
    if _mla_selected(attrs):
        q, kv, selection, fed, gamma, kvb = inputs
    else:
        (q, kv, fed, gamma, kvb), selection = inputs, None
    pool, cursor = aux
    capacity, H, dn, dr, dv, rank = _mla_geometry(attrs)
    B, S = q.shape[:2]
    p, new_cursor = _cursor(fed, cursor, S, capacity)
    base, scaling, softmax_factor = _mla_rope(attrs)
    pos = _positions(p, S)
    c = rms_norm(kv[..., :rank], gamma,
                 parse_float(attrs.get("rms_eps", 1e-5)))
    row = _lanes(jnp.concatenate(
        [c, rope_interleaved(kv[..., rank:], pos, base, scaling)], axis=-1),
        pool.shape[-1])
    q = q.reshape(B, S, H, dn + dr)
    q_r = rope_interleaved(q[..., dn:], pos[:, :, None], base, scaling)
    kvb = kvb.reshape(H, dn + dv, rank)
    return (q[..., :dn], q_r, row.astype(pool.dtype)[:, None], selection,
            kvb[:, :dn], kvb[:, dn:], p, new_cursor,
            float(dn + dr) ** -0.5 * softmax_factor)


def _mla_fwd(attrs, inputs, aux, is_train, rng):
    """The expanded form: every pool row's ``k_n`` and ``v`` of every
    head, dense scores, the selection - or, without one, ``j <= t`` -
    as the softmax's mask."""
    from ..rtc import _write_rows
    q_n, q_r, row, sel, w_kb, w_vb, p, new_cursor, scale = _mla_prologue(
        attrs, inputs, aux, is_train)
    pool, = _write_rows([row], [aux[0]], p)
    rank = w_kb.shape[-1]
    B, S, H, _ = q_n.shape
    c_all = pool[:, 0, :, :rank].astype(q_n.dtype)
    r_all = pool[:, 0, :, rank:rank + q_r.shape[-1]].astype(q_n.dtype)
    dtype = q_n.dtype
    k_n = _mm("bkc,hnc->bhkn", c_all, w_kb.astype(dtype)).astype(dtype)
    v = _mm("bkc,hvc->bhkv", c_all, w_vb.astype(dtype)).astype(dtype)
    logits = (_mm("bshn,bhkn->bhsk", q_n, k_n)
              + _mm("bshr,bkr->bhsk", q_r, r_all)) * scale
    attended = sel != 0 if sel is not None else \
        jnp.arange(pool.shape[2])[None, None, :] \
        <= _positions(p, S)[:, :, None]
    probs = jax.nn.softmax(
        jnp.where(attended[:, None], logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bhsk,bhkv->bshv", probs, v.astype(_F32),
                     precision=_HI)
    return [out.reshape(B, S, -1).astype(q_n.dtype)], [pool, new_cursor]


def _mla_infer(attrs, in_shapes):
    q_s = in_shapes[0]
    if q_s is None:
        return in_shapes, [None], [None, None]
    capacity, H, dn, dr, dv, rank = _mla_geometry(attrs)
    B, S = q_s[:2]
    selection = [(B, S, capacity)] if _mla_selected(attrs) else []
    return ([(B, S, H * (dn + dr)), (B, S, rank + dr)] + selection
            + [(B,), (rank,), (H * (dn + dv), rank)],
            [(B, S, H * dv)],
            [(B, 1, capacity, latent_width(rank, dr)), (B, 1)])


def _softmax_step(s, mask, v, m_s, l_s, acc_s, at, selected):
    """One block of the online softmax: float32 scores ``s`` (rows,
    keys) under ``mask`` (None: every key attended) against the values
    ``v`` (keys, .) into the running maximum, sum and weighted sum at
    index ``at`` of the scratch. Under a selection a row may have
    attended nothing yet, so its maximum may be infinite; without one
    key 0 is at or before every query and it never is."""
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = m_s[at]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    if selected:
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        e = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    else:
        e = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
    m_s[at] = m_new
    l_s[at] = l_s[at] * corr + jnp.sum(e, axis=-1, keepdims=True)
    acc_s[at] = acc_s[at] * corr + jnp.dot(
        e.astype(v.dtype), v, preferred_element_type=_F32)


def _mla_attn_kernel(bk, rank, scale, form, selected):
    """The absorbed form, grid (slot, 1, 1, key block): online softmax
    of one query's heads - the rows of one product, which share the
    query's mask row - against a block of latent rows, the weighted
    sum of ``c_kv`` itself. ``"decode"`` is the S = 1 program's;
    ``"ride"`` is the same for the one query of a slot that a window
    feeds a single row, live where ``fed`` is 1 and zero elsewhere.
    Without a selection (``selected`` False: no mask operand) the query
    attends the keys at or before its own position, told from the
    cursor: a block that lies wholly before it takes no mask at all.
    The S = 1 program's text is pinned to its parent's
    (``tests/test_chip_compile.py``), which this kernel shared with a
    window form: the loop of one trip, the additions of 0 and the two
    grid axes of one step are what that form left behind."""
    def attend(q_ref, k_ref, mask, m_s, l_s, acc_s):
        k = k_ref[...]
        c = k[:, :rank]

        def group(g, carry):
            s = lax.dot_general(q_ref[g], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
            _softmax_step(s, mask, c, m_s, l_s, acc_s, g, selected)
            return carry
        lax.fori_loop(0, 1, group, 0)

    def kernel(p_ref, fed_ref, q_ref, k_ref, *rest):
        sel_ref = rest[0] if selected else None
        o_ref, m_s, l_s, acc_s = rest[-4:]
        b, j = pl.program_id(0), pl.program_id(3)
        first = p_ref[b] + 0
        last = first + 0
        fed = fed_ref[b] == 1 if form == "ride" else True
        live = (j * bk <= last) & fed

        @pl.when(j == 0)
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, _F32)
            l_s[...] = jnp.zeros(l_s.shape, _F32)
            acc_s[...] = jnp.zeros(acc_s.shape, _F32)

        if selected:
            @pl.when(live)
            def _block():
                attend(q_ref, k_ref, sel_ref[...].astype(jnp.int32) != 0,
                       m_s, l_s, acc_s)
        else:
            before = (j + 1) * bk - 1 <= first

            @pl.when(live & before)
            def _whole():
                attend(q_ref, k_ref, None, m_s, l_s, acc_s)

            @pl.when(live & jnp.logical_not(before))
            def _diagonal():
                key = j * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
                attend(q_ref, k_ref, key <= first + 0, m_s, l_s, acc_s)

        @pl.when(j == pl.num_programs(3) - 1)
        def _emit():
            o_ref[...] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)) \
                .astype(o_ref.dtype)
    return kernel


def _mla_launch(p, fed, q, keys, sel, rank, scale, interpret, form):
    """One launch of the absorbed kernel (``_mla_attn_kernel``) over the
    one query a slot ``q (B, H, 1, rank + rope_dim)`` -> (B, H, 1,
    rank)."""
    B, H, S, dq = q.shape
    C = keys.shape[1]
    q = q.reshape(B, 1, H, dq)
    bk = _pk._divisor_block(C, 2048)
    selected = sel is not None

    def q_map(b, g, i, j, p_ref, fed_ref):
        return b, g, i, 0

    def last_block(b, p_ref, fed_ref):
        # a dead block re-references the last live one: no copy
        last = p_ref[b] + 0
        if form == "ride":
            last = jnp.where(fed_ref[b] == 1, last, 0)
        return last // bk

    def k_map(b, g, i, j, p_ref, fed_ref):
        return b, jnp.minimum(j, last_block(b, p_ref, fed_ref)), 0

    def sel_map(b, g, i, j, p_ref, fed_ref):
        return b, i, jnp.minimum(j, last_block(b, p_ref, fed_ref))

    in_specs = [pl.BlockSpec((None, 1, H, dq), q_map),
                pl.BlockSpec((None, bk, dq), k_map)]
    if selected:
        in_specs.append(pl.BlockSpec((None, 1, bk), sel_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, 1, 1, C // bk), in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, H, rank), q_map),
        scratch_shapes=[pltpu.VMEM((1, H, 1), _F32),
                        pltpu.VMEM((1, H, 1), _F32),
                        pltpu.VMEM((1, H, rank), _F32)])
    out = _pk.pallas_call(
        _mla_attn_kernel(bk, rank, scale, form, selected),
        name="mla_attn_" + form,
        out_shape=jax.ShapeDtypeStruct(q.shape[:3] + (rank,), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        **_compiler_params(("parallel", "parallel", "parallel",
                            "arbitrary")))(
            p, fed, q, keys, *((sel,) if selected else ()))
    return out.reshape(B, H, S, rank)


#: the window form's blocks: the rows of a query block - what a chunk's
#: pads are skipped by; two that both hold real rows are the rows of one
#: product - and the latent rows of a key block. The row-wise part of a
#: softmax step is paid a product, so a wide one is cheaper: a layer of
#: 1,024 rows at 17 k keys at A.X-K1's widths took 12.7 ms at 256 x
#: 512, 9.3 at 256 x 1,024, 8.6 at 512 x 1,024 and at 256 x 2,048 (v5e;
#: PERF.md section 6, PR 49) - but Mosaic unrolls a product's body, so
#: the pairs are a loop, and a key block of 2,048 would compute twice
#: the keys past a chunk's diagonal
_WINDOW_BLOCKS = (256, 1024)


def _mla_window_kernel(hg, S, R, bk, rank, dn, dv, scale, selected):
    """The expanded form, grid (slot, head group, key block): a block
    of latent rows is expanded to a head's ``k_n`` and ``v`` once -
    ``c_kv W_kb[h]^T`` and ``c_kv W_vb[h]^T`` at the pool's dtype, as
    the composition rounds them, beside the row's own ``k_r`` lanes -
    and every query block of the slot's chunk that can see it is scored
    against it at ``nope_dim + rope_dim`` lanes and sums ``v``, two
    blocks at a time where both hold real rows. The chunk's softmax
    state - all ``S`` rows of the group's ``hg`` heads - stays in
    scratch across the key blocks. A query block whose positions are
    all past ``fed`` (pads) comes out zero without a product, and a
    slot fed nothing (or riding) costs its steps and the zeros it
    writes. Under a selection the mask is the selection's;
    without one a row attends the keys at or before its own position,
    told from the cursor, and a key block wholly before a query block
    takes no mask at all."""
    contract_last = (((1,), (1,)), ((), ()))

    def kernel(p_ref, fed_ref, src_ref, q_ref, w_ref, k_ref, *rest):
        sel_ref = rest[0] if selected else None
        o_ref, m_s, l_s, acc_s, k_s, v_s = rest[-6:]
        b, j = pl.program_id(0), pl.program_id(2)
        p, n = p_ref[b], fed_ref[b]

        @pl.when((j == 0) & (n > 0))
        def _init():
            m_s[...] = jnp.full(m_s.shape, -jnp.inf, _F32)
            l_s[...] = jnp.zeros(l_s.shape, _F32)
            acc_s[...] = jnp.zeros(acc_s.shape, _F32)

        def attend(h, rows, mask):
            s = lax.dot_general(q_ref[h, rows], k_s[...], contract_last,
                                preferred_element_type=_F32) * scale
            _softmax_step(s, mask, v_s[...], m_s, l_s, acc_s, (h, rows),
                          selected)

        def span(h, start, count, live):
            """``count`` query rows from row ``start`` of the chunk as
            the rows of one product, where ``live`` and they see the
            key block."""
            rows = pl.ds(start, count)
            first = p + start
            seen = live & (j * bk <= first + count - 1)
            if selected:
                pl.when(seen)(lambda: attend(
                    h, rows, sel_ref[rows].astype(jnp.int32) != 0))
                return
            before = (j + 1) * bk - 1 <= first
            pl.when(seen & before)(lambda: attend(h, rows, None))

            @pl.when(seen & jnp.logical_not(before))
            def _diagonal():
                key = j * bk + lax.broadcasted_iota(jnp.int32, (count, bk), 1)
                t = first + lax.broadcasted_iota(jnp.int32, (count, bk), 0)
                attend(h, rows, key <= t)

        def pair(h, i, carry):
            # two query blocks are the rows of one product where the
            # second has a real row (the key block's tiles are loaded
            # once for both), else the first alone
            start = pl.multiple_of(i * 2 * R, 2 * R)
            both = start + R < n
            span(h, start, 2 * R, both)
            span(h, start, R, (start < n) & jnp.logical_not(both))
            return carry

        def head(h, carry):
            c = k_ref[:, :rank]
            k_s[:, :dn] = lax.dot_general(
                c, w_ref[h, :dn], contract_last,
                preferred_element_type=_F32).astype(k_s.dtype)
            v_s[...] = lax.dot_general(
                c, w_ref[h, dn:], contract_last,
                preferred_element_type=_F32).astype(v_s.dtype)
            if S // R > 1:      # a loop of no trips is traced all the same
                lax.fori_loop(0, S // R // 2, partial(pair, h), 0)
            if S // R % 2:
                span(h, S - R, R, S - R < n)
            return carry

        @pl.when((n > 0) & (j * bk <= p + n - 1))
        def _block():
            # the shared rotary key and zeros up to q's width, then head
            # by head k_n before them
            tail = k_s.shape[1] - dn
            rope = k_ref[:, rank:rank + tail]
            k_s[:, dn:dn + rope.shape[1]] = rope
            if rope.shape[1] < tail:
                k_s[:, dn + rope.shape[1]:] = jnp.zeros(
                    (bk, tail - rope.shape[1]), k_s.dtype)
            lax.fori_loop(0, hg, head, 0)

        @pl.when(j == pl.num_programs(2) - 1)
        def _emit():
            @pl.when(n == 0)
            def _dead():
                o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

            @pl.when(n > 0)
            def _live():
                for h in range(hg):
                    o_ref[:, h * dv:(h + 1) * dv] = (
                        acc_s[h] / jnp.maximum(l_s[h], 1e-30)) \
                        .astype(o_ref.dtype)
    return kernel


def _mla_window_launch(p, fed, q, w_kvb, dn, keys, sel, scale, interpret,
                       blocks):
    """One launch of the expanded kernel (``_mla_window_kernel``):
    ``q (B, H, S, .)`` holding ``[q_n ; q_r]`` in whole tiles of lanes
    and ``w_kvb (H, nope_dim + v_dim, rank)``, a head's ``[W_kb ;
    W_vb]`` with ``nope_dim`` = ``dn``, against ``keys (B, C, width)``
    -> (B, S, H * v_dim), head-major as the op hands it on. A slot
    with ``fed`` 0 is dead: its steps re-reference the blocks of the
    live step before them (ahead of the first live slot: after them),
    so they move nothing but the zeros they write."""
    B, H, S, dqk = q.shape
    C, width = keys.shape[1:]
    rank, dv = w_kvb.shape[2], w_kvb.shape[1] - dn
    hg = _pk._divisor_block(H, 8)
    R, bk = _pk._divisor_block(S, blocks[0]), _pk._divisor_block(C, blocks[1])
    G = H // hg
    selected = sel is not None
    live = fed > 0
    before = lax.cummax(jnp.where(live, jnp.arange(B), -1))
    src = jnp.where(before >= 0, before, jnp.argmax(live)).astype(jnp.int32)

    def step(b, g, j, p_ref, fed_ref, src_ref):
        """The (slot, head group, key block) whose blocks a step takes:
        its own, a key block past the slot's last live one being that
        one again; a dead slot's are its neighbour's."""
        s = src_ref[b]
        last = jnp.maximum(p_ref[s] + fed_ref[s] - 1, 0) // bk
        dead, after = fed_ref[b] == 0, s < b
        return (s, jnp.where(dead, jnp.where(after, G - 1, 0), g),
                jnp.where(dead, jnp.where(after, last, 0),
                          jnp.minimum(j, last)))

    def q_map(*at):
        s, g, _ = step(*at)
        return s, g, 0, 0

    def w_map(*at):
        return step(*at)[1], 0, 0

    def k_map(*at):
        s, _, j = step(*at)
        return s, j, 0

    def sel_map(*at):
        s, _, j = step(*at)
        return s, 0, j

    in_specs = [pl.BlockSpec((None, hg, S, dqk), q_map),
                pl.BlockSpec((hg, dn + dv, rank), w_map),
                pl.BlockSpec((None, bk, width), k_map)]
    if selected:
        in_specs.append(pl.BlockSpec((None, S, bk), sel_map))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B, G, C // bk), in_specs=in_specs,
        out_specs=pl.BlockSpec((None, S, hg * dv),
                               lambda b, g, j, *_: (b, 0, g)),
        scratch_shapes=[pltpu.VMEM((hg, S, 1), _F32),
                        pltpu.VMEM((hg, S, 1), _F32),
                        pltpu.VMEM((hg, S, dv), _F32),
                        pltpu.VMEM((bk, dqk), keys.dtype),
                        pltpu.VMEM((bk, dv), keys.dtype)])
    return _pk.pallas_call(
        _mla_window_kernel(hg, S, R, bk, rank, dn, dv, scale, selected),
        name="mla_attn_window",
        out_shape=jax.ShapeDtypeStruct((B, S, H * dv), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        **_compiler_params(("parallel", "parallel", "arbitrary")))(
            p, fed, src, q, w_kvb, keys, *((sel,) if selected else ()))


@partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _mla_attend(p, fed, q, pool, sel, rank, scale, interpret):
    """The kernel ``mla_attn_decode``, the S = 1 program's: queries
    ``q (B, H, 1, rank + rope_dim)`` in the latent space against the
    pool under ``sel`` (None: every position at or before the query, no
    mask operand) -> the weighted sums of ``c_kv``, (B, H, 1, rank) at
    ``q``'s dtype. Blocks past the query's position are neither fetched
    nor computed."""
    B, _, _, dq = q.shape
    return _mla_launch(p, fed, q, pool.reshape(B, pool.shape[2], dq), sel,
                       rank, scale, interpret, "decode")


@partial(jax.jit, static_argnames=("dn", "scale", "interpret", "blocks"))
def _mla_attend_window(p, fed, q, q_ride, pool, sel, w_kvb, dn, scale,
                       interpret, blocks):
    """The kernels ``mla_attn_window`` and ``mla_attn_ride`` of a window
    program: ``q (B, H, S, .)`` in the expanded widths and ``q_ride (B,
    H, 1, rank + rope_dim)``, every slot's first query in the latent
    space, against the pool under ``sel``, with ``w_kvb (H, nope_dim +
    v_dim, rank)`` (``nope_dim`` = ``dn``) -> (B, S, H * v_dim) at
    ``q``'s dtype. Which form a slot takes is read from ``fed``: a slot
    fed one row (a decoding slot riding a prefill window) is dead to
    the window form, where the expansion of its whole context would
    serve one query, and goes through the absorbed one, the heads as the
    rows of one group, as in the S = 1 program; the window's zeros in
    that slot's row 0 take the result, in place."""
    B = q.shape[0]
    keys = pool.reshape(B, pool.shape[2], pool.shape[3])
    riding = fed == 1
    out = _mla_window_launch(p, jnp.where(riding, 0, fed), q,
                             w_kvb.astype(pool.dtype), dn, keys, sel, scale,
                             interpret, blocks)
    ride = _mla_launch(p, fed, q_ride, keys,
                       None if sel is None else sel[:, :1], w_kvb.shape[-1],
                       scale, interpret, "ride")
    ride = _mm("bhsc,hvc->bshv", ride, w_kvb[:, dn:].astype(ride.dtype)) \
        .reshape(B, 1, -1).astype(out.dtype)
    row = jnp.where(riding[:, None, None], ride, out[:, :1])
    return lax.dynamic_update_slice(out, row, (0, 0, 0))


def _mla_pallas(attrs, inputs, aux, is_train, rng):
    """One pass over the live blocks of the pool for all heads, in the
    form the geometry pays least for (module docstring): absorbed at
    S = 1 and for a window's riding slots, expanded for its chunks."""
    q_n, q_r, row, sel, w_kb, w_vb, p, new_cursor, scale = _mla_prologue(
        attrs, inputs, aux, is_train)
    interpret = _pk._interpret()
    pool, = _pk._cache_write(p, (row,), (aux[0],), interpret=interpret,
                             name="mla_write")
    B, S, H, _ = q_n.shape
    rank = w_kb.shape[-1]
    dtype = q_n.dtype

    def latent(q_n, q_r):
        q_c = _mm("bshn,hnc->bhsc", q_n, w_kb.astype(dtype))
        return _lanes(jnp.concatenate(
            [q_c.astype(pool.dtype),
             q_r.transpose(0, 2, 1, 3).astype(pool.dtype)], axis=-1),
            pool.shape[-1])

    if S == 1:
        q = latent(q_n, q_r)
        o_c = _mla_attend(p, new_cursor.reshape(-1) - p, q, pool, sel,
                          rank=rank, scale=scale, interpret=interpret)
        out = _mm("bhsc,hvc->bshv", o_c.astype(dtype), w_vb.astype(dtype))
        return [out.reshape(B, S, -1).astype(dtype)], [pool, new_cursor]
    q = jnp.concatenate([q_n, q_r], axis=-1)
    q = _lanes(q, -(-q.shape[-1] // 128) * 128).transpose(0, 2, 1, 3)
    out = _mla_attend_window(
        p, new_cursor.reshape(-1) - p, q.astype(pool.dtype),
        latent(q_n[:, :1], q_r[:, :1]), pool, sel,
        inputs[-1].reshape(H, -1, rank).astype(dtype), dn=q_n.shape[-1],
        scale=scale, interpret=interpret, blocks=_WINDOW_BLOCKS)
    return [out.astype(dtype)], [pool, new_cursor]


def _mla_inputs(attrs):
    """The op's inputs: ``selection`` only where it attends under one."""
    return ["q", "kv"] + (["selection"] if _mla_selected(attrs) else []) \
        + ["fed", "kv_norm_weight", "kv_b_weight"]


def _mla_eligible(attrs, in_shapes, in_dtypes):
    if len(in_shapes) < len(_mla_inputs(attrs)) + 2 \
            or len(in_shapes[0]) != 3:
        return False
    if str(in_dtypes[0]) not in ("float32", "bfloat16"):
        return False
    capacity, _H, _dn, dr, _dv, rank = _mla_geometry(attrs)
    return _pk._interpret() or (_aligned(capacity, rank) and dr % 64 == 0)


MLA_SLOT_STATE = {"latent": "rows", "cache_pos": "cursor"}

#: one head's blocks of the window form at GLM-5.2's widths, the widest
#: published: the chunk's 1,024 queries of 192 + 64 and its values of
#: 256 (both buffers), their accumulator and the running maximum and sum
#: (a lane each, laid in tiles of 128), the head's ``[W_kb ; W_vb]``
#: (both buffers), a key block of 1,024 latent rows of 576 in 640 lanes
#: (both buffers) and its mask, the block expanded to k and v, and the
#: scores of two query blocks with their exponentials. The kernel holds
#: eight heads of the first five at once, inside ``_VMEM_LIMIT``; the
#: S = 1 and riding forms' blocks (64 heads as rows, a key block of
#: 2,048) are smaller than one head's here
_MLA_KSPEC = {
    "tiles": [((1024, 256), "bfloat16")] * 4 + [((1024, 256), "float32")]
    + [((1024, 128), "float32")] * 2 + [((448, 512), "bfloat16")] * 2
    + [((1024, 640), "bfloat16")] * 2 + [((1024, 1024), "int8")] * 2
    + [((1024, 256), "bfloat16")] * 2 + [((512, 1024), "float32")] * 2,
    "dtypes": ("float32", "bfloat16"),
}

#: a latent layer without a selection reads a slot's pool up to its
#: cursor like ``attention_decode`` without a window and counts under
#: the same names; the ring record carries its share of the attended
#: rows as ``mla_attended`` and the (query, key) pairs of ALL its fed
#: queries as ``mla_pairs`` (query t of a slot at position p attends
#: p + t + 1 keys; at S = 1 the two are equal)
_MLA_COUNTS = read_counts(
    ("attn.live_rows", "attn_live"), ("attn.capacity_rows", None),
    ("attn.attended_rows", "attn_attended"), (None, "mla_attended"),
    (None, "mla_pairs"))


def _mla_reads(attrs, capacity, sources):
    if _mla_selected(attrs):
        topk = parse_int(sources["selection"]["topk"])

        def reads(pos, fed):
            live = (pos + fed)[fed > 0]
            return {"dsa.layer_steps": 1, "dsa.live_rows": int(live.sum()),
                    "dsa.selected_rows": int(np.minimum(live, topk).sum())}

        return reads

    def reads(pos, fed):
        on = fed > 0
        total = int(np.minimum((pos + fed)[on], capacity).sum())
        n = fed[on]
        return {"attn.live_rows": total,
                "attn.capacity_rows": pos.size * capacity,
                "attn.attended_rows": total, "mla_attended": total,
                "mla_pairs": int(np.sum(n * pos[on] + n * (n + 1) // 2))}

    return reads


register("mla_attention_decode", inputs=_mla_inputs,
         aux=tuple(MLA_SLOT_STATE), full=_mla_fwd, stateful_infer=True,
         aux_dtypes={"cache_pos": "int32"}, infer_shape=_mla_infer,
         attr_spec={"capacity": (parse_int, None),
                    "n_heads": (parse_int, None),
                    "nope_dim": (parse_int, None),
                    "rope_dim": (parse_int, None),
                    "v_dim": (parse_int, None),
                    "kv_rank": (parse_int, None),
                    "rms_eps": (parse_float, 1e-5),
                    "rope_base": (parse_float, 10000.0),
                    "selected": (parse_bool, True),
                    "rope_factor": (parse_float, 1.0),
                    "rope_original_positions": (parse_int, 4096),
                    "rope_beta_fast": (parse_float, 32.0),
                    "rope_beta_slow": (parse_float, 1.0),
                    "rope_mscale": (parse_float, 1.0),
                    "rope_mscale_all_dim": (parse_float, 0.0)},
         slot_state=MLA_SLOT_STATE,
         state_reads=(lambda attrs: _DSA_COUNTS if _mla_selected(attrs)
                      else _MLA_COUNTS, _mla_reads),
         donate_aux=True,
         variants={"pallas": (_mla_pallas, _mla_eligible, _MLA_KSPEC)},
         doc="Multi-head latent attention over a per-slot pool of latent "
             "rows, under a selection of positions or over every position "
             "at or before the query (ops/mla.py).")
