"""RMSNorm and the routed expert feed-forward (``MoEFFN``).

The sparse-expert block of today's open decoders (OLMoE, Mixtral,
Qwen-MoE): a router picks ``top_k`` of ``num_experts`` gated-SiLU
feed-forwards for every token, and the token's output is their
weighted sum. The published computation, which both lowerings follow:

    logits = x @ router_weight.T                 (compute dtype)
    probs  = softmax(float32(logits))            over ALL experts
    w, e   = top_k(probs)                        float32; renormalised
                                                 only under norm_topk
    y      = sum_j w_j * down[e_j]( silu(x @ gate[e_j]) * (x @ up[e_j]) )

Parameter layout (every expert stacked on a leading axis, each matrix
K-major for the matmul that reads it, so no lowering transposes 400 M
weights a layer):

    router_weight (E, D)     the published ``mlp.gate.weight`` (out, in)
    gate_weight   (E, D, F)  ``experts[e].gate_proj.weight.T``
    up_weight     (E, D, F)  ``experts[e].up_proj.weight.T``
    down_weight   (E, F, D)  ``experts[e].down_proj.weight.T``

**The expert's form** (``act``). ``"silu"``, the default, is the gated
SiLU of three matrices above. ``"relu2"`` is the ungated expert of two
(Nemotron-H): ``y = down[e](relu(x @ up[e]) ** 2)``, and the op has no
``gate_weight`` input (a shared expert likewise: no
``shared_gate_weight``). Both of its stacked matrices lie with the
model's width last,

    up_weight     (E, F, D)  ``experts[e].up_proj.weight`` (out, in)
    down_weight   (E, F, D)  ``experts[e].down_proj.weight.T``

because an expert's width need not be whole lanes (1,856 = 14.5 x 128)
and the model's is: the chip lays a ``(D, 1,856)`` matrix out
transposed, and a kernel that wants it row-major gets a copy of all the
experts in front of every call. The first product contracts the last
dimension of both operands (``moe_gmm_up``, one matrix, relu squared in
its epilogue), the second is ``moe_gmm_down`` as above.

``forward`` (the XLA composition and fallback) sorts the (token,
expert) assignments by expert and runs ``jax.lax.ragged_dot`` over
exactly the routed rows: no expert is computed for a token not routed
to it, no assignment is dropped (no capacity factor). The ``pallas``
variant (ops/pallas_kernels.grouped_expert_ffn) runs the same sorted
rows through two grouped-matmul kernels named ``moe_gmm_*`` in the
device trace; a call reads each touched expert's weights once wherever
its rows lie (once more for every 112 rows past its first 112) and no
untouched expert's.

**A chip's share of a wider layer** (the DeepSeek-V3 family as GLM-5.2
runs it; expert parallelism without its exchange). With
``scoring="sigmoid"`` the router is ``noaux_tc``: ``sc = sigmoid(float32(
x @ router_weight.T))``, the ``top_k`` largest of ``sc + router_bias``
are chosen (``router_bias``), and their weights are ``sc`` itself,
normalised over the chosen (``norm_topk``) and times ``scaling`` - the
bias steers the choice and never the weights; ``router_bias=False`` is
the same router without a bias (an auxiliary-loss router). ``n_group`` /
``topk_group`` limit the choice to the experts of the ``topk_group``
best of ``n_group`` consecutive groups (``moe_route_sigmoid``; 1 / 1:
no limit). ``held_first`` /
``held_count`` say which experts this layer holds: ``num_experts`` stays
the router's published width and every token is routed over all of
them, but the stacked weights are the held experts' alone
(``(held_count, D, F)``) and the grouped matmuls run over the
assignments that landed on them; what the absent experts would add is
left out. ``shared_hidden`` adds a shared expert of that width
(``shared_gate_weight`` / ``shared_up_weight`` (D, Fs),
``shared_down_weight`` (Fs, D)) that every token passes through.
``step_len`` (with the input ``fed`` (slots,) after the rows: how many
of each slot's ``step_len`` rows are real tokens, the decode ops'
``fed``) keeps the pads of a window out of the routed experts: a pad's
choices land nowhere and are not counted (its output is the shared
expert's alone, a don't-care). Without it every pad is a token, and
the pads of one window, all alike, pile onto the same experts. The
sorted held rows are taken ``_segment_rows`` at a time in a loop of as
many trips as the load needs - one at the expected load - so that no
assignment is dropped and no buffer is sized for all of them. With none
of these set the op is the one above, to the bit. The softmax router
takes a share and a shared expert alike (Granite 4.0-H Small: ``top_k``
10 of 72 under ``norm_topk`` - the published softmax over the chosen
logits -, 36 held, ``shared_hidden`` 1,536): ``moe_route``'s choice and
weights over all experts, then the held experts' part as above.

``step_len`` alone (OLMoE's slot-pooled graph) makes no share: the
plain layer takes ``fed`` too, sends a pad's choices to a dead group
behind the last expert - sorted last, no expert's rows, weight zero -
and is otherwise the sort, the grouped matmuls and the combine above.

Where the tokens went is counted on the device: the op's ``moe_stats``
aux cell (int32 ``[1, assignments, experts_touched, max_expert_load]``
of the latest forward) is overwritten by every forward, inference
included (``stateful_infer``); ``serve.decode`` reads it after the
fetch it already makes and feeds the ``serve.decode.moe.*`` counters.
A layer that holds a share counts its held experts there, all the
assignments the router made, and fifth the assignments that landed
here.
"""
from __future__ import annotations

from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax

from ..base import parse_bool, parse_float, parse_int
from .registry import read_counts, register

__all__ = ["rms_norm", "moe_route", "moe_sort", "moe_combine",
           "moe_route_sigmoid", "cpu_wide"]


def cpu_wide(*arrays):
    """Operands of a product that accumulates in float32
    (``preferred_element_type``): as they are, but float32 on the CPU
    backend, which has no bfloat16 product of that kind - the same
    numbers, since a bfloat16 product is exact in float32."""
    if jax.default_backend() == "cpu":
        return [a.astype(jnp.float32) for a in arrays]
    return list(arrays)


# ------------------------------------------------------------------ RMSNorm
def rms_norm(x, gamma, eps, unit_offset=False, cast_to_gain=False,
             groups=1):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis; the
    statistics in float32 whatever the input dtype (LayerNorm's rule).
    ``unit_offset`` scales by ``1 + gamma`` (a gain stored as its
    distance from one); ``cast_to_gain`` hands the result over at the
    gain's dtype, not the input's: a float32 residual stream is
    normalised into the compute width the matmuls behind it run at.
    ``groups`` > 1 takes the statistic over each of that many equal
    parts of the last axis by itself, under the one gain (Mamba-2's
    gated norm where B and C come in groups)."""
    x32 = x.astype(jnp.float32)
    if groups > 1:
        parts = x32.reshape(x32.shape[:-1] + (groups, -1))
        var = jnp.repeat(jnp.mean(jnp.square(parts), axis=-1),
                         parts.shape[-1], axis=-1)
    else:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    gain = gamma.astype(jnp.float32)
    out = x32 * lax.rsqrt(var + eps) * (1.0 + gain if unit_offset else gain)
    return out.astype(gamma.dtype if cast_to_gain else x.dtype)


def _rms_infer(attrs, in_shapes):
    data_s = in_shapes[0]
    if data_s is None:
        return in_shapes, [None], []
    return [data_s, (data_s[-1],)], [data_s], []


@register("RMSNorm", inputs=("data", "gamma"),
          attr_spec={"eps": (parse_float, 1e-5),
                     "unit_offset": (parse_bool, False),
                     "cast_to_gain": (parse_bool, False),
                     "groups": (parse_int, None)},
          infer_shape=_rms_infer)
def _rms_norm_op(attrs, data, gamma):
    """Root-mean-square normalisation over the last axis with a gain
    and no bias (arXiv:1910.07467)."""
    return rms_norm(data, gamma, parse_float(attrs.get("eps", 1e-5)),
                    parse_bool(attrs.get("unit_offset", False)),
                    parse_bool(attrs.get("cast_to_gain", False)),
                    parse_int(attrs.get("groups", 1)))


# ------------------------------------------------------------------- MoEFFN
def moe_route(x, router_weight, top_k, norm_topk):
    """``(weights (T, k) float32, experts (T, k) int32)``: router
    logits in ``x``'s dtype, softmax over all experts and top-k in
    float32, as published."""
    logits = jnp.dot(x, router_weight.astype(x.dtype).T)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def moe_sort(experts, num_experts):
    """Group the (token, expert) assignments by expert.

    Returns ``(token_of_row (M,), inverse (M,), group_sizes (E,))``:
    sorted row ``r`` is an assignment of token ``token_of_row[r]``,
    assignment ``a`` (token ``a // k``, choice ``a % k``) sits at sorted
    row ``inverse[a]``, and expert ``e`` owns ``group_sizes[e]``
    consecutive rows. M = tokens * top_k: every assignment once."""
    k = experts.shape[1]
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    group_sizes = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    return order // k, inverse, group_sizes


def moe_combine(y_sorted, inverse, weights, dtype):
    """Each token's weighted sum of its k expert outputs, in float32:
    ``y_sorted`` (M, D) float32 rows in sorted order -> (T, D)."""
    T, k = weights.shape
    y = y_sorted[inverse].reshape(T, k, -1)
    return jnp.sum(y * weights[:, :, None], axis=1).astype(dtype)


def moe_stats(group_sizes):
    """int32 ``[1, assignments, experts_touched, max_expert_load]`` of
    one forward."""
    return jnp.stack([jnp.int32(1), jnp.sum(group_sizes),
                      jnp.sum((group_sizes > 0).astype(jnp.int32)),
                      jnp.max(group_sizes)]).astype(jnp.int32)


def moe_route_sigmoid(x, router_weight, router_bias, top_k, norm_topk,
                      scaling, n_group=1, topk_group=1):
    """``(weights (T, k) float32, experts (T, k) int32)`` of the
    ``noaux_tc`` router (module docstring): scores in float32 from
    float32-accumulated logits, the bias in the choice alone. With
    ``n_group`` > 1 the choice is group-limited: the experts lie in
    ``n_group`` consecutive groups, a group's score is the sum of its
    two largest choice scores, and the ``top_k`` are taken among the
    experts of the ``topk_group`` best groups alone (ties: the lowest
    index, of groups and of experts)."""
    rows, router = cpu_wide(x, router_weight.astype(x.dtype))
    score = jax.nn.sigmoid(jnp.dot(rows, router.T,
                                   preferred_element_type=jnp.float32))
    choice = score if router_bias is None \
        else score + router_bias.astype(jnp.float32)
    if n_group > 1:
        T, E = choice.shape
        groups = choice.reshape(T, n_group, E // n_group)
        best = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)     # (T, n_group)
        _, kept = lax.top_k(best, topk_group)
        keep = jnp.any(kept[:, :, None]
                       == jnp.arange(n_group, dtype=kept.dtype), axis=1)
        choice = jnp.where(keep[:, :, None], groups, -jnp.inf) \
            .reshape(T, E)
    _, experts = lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(score, experts, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * scaling, experts.astype(jnp.int32)


def _segment_rows(assignments):
    """Sorted held rows a trip of ``_held_experts``' loop takes: all of
    them up to 1,024, an eighth beyond (a sixteenth is the expected
    load of a sixteenth of the experts)."""
    return max(min(assignments, 1024), assignments // 8)


def _held_experts(x, weights, experts, real, first, count, mats,
                  experts_fn):
    """The held experts' part of every real token's weighted sum,
    float32 (T, D), and the held group sizes (count,); ``real`` (T,)
    bool, or None for every row."""
    T, k = experts.shape
    M = T * k
    local = experts - first
    here = (local >= 0) & (local < count)
    if real is not None:
        here = here & real[:, None]
    local = jnp.where(here, local, count)
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[flat].add(1)[:count]
    ends = jnp.cumsum(sizes)
    starts, total = ends - sizes, ends[-1]
    R = _segment_rows(M)
    order = jnp.pad(order, (0, R))
    flat_w = weights.reshape(-1)

    def trip(i, out):
        lo = i * R
        rows = lax.dynamic_slice(order, (lo,), (R,))
        token = rows // k
        part = jnp.clip(ends, lo, lo + R) - jnp.clip(starts, lo, lo + R)
        y = experts_fn(x[token], part, *mats)
        live = (lo + jnp.arange(R, dtype=jnp.int32) < total)[:, None]
        y = jnp.where(live, y * flat_w[rows][:, None], 0.0)
        return out.at[token].add(y)

    out = lax.fori_loop(0, (total + R - 1) // R, trip,
                        jnp.zeros((T, x.shape[1]), jnp.float32))
    return out, sizes


def _relu2(u):
    return jnp.square(jnp.maximum(u, 0.0))


def _dense_expert(x, *mats):
    """One expert over every row, float32 accumulation: the gated SiLU
    of ``(gate, up, down)``, or relu squared between ``(up, down)``
    (K-major both: a shared expert's matrices)."""
    f32 = jnp.float32
    if len(mats) == 2:
        rows, up, down = cpu_wide(x, *(w.astype(x.dtype) for w in mats))
        h, = cpu_wide(_relu2(jnp.dot(rows, up, preferred_element_type=f32))
                      .astype(x.dtype))
        return jnp.dot(h, down, preferred_element_type=f32)
    rows, gate, up, down = cpu_wide(x, *(w.astype(x.dtype) for w in mats))
    g = jnp.dot(rows, gate, preferred_element_type=f32)
    u = jnp.dot(rows, up, preferred_element_type=f32)
    h, = cpu_wide((jax.nn.silu(g) * u).astype(x.dtype))
    return jnp.dot(h, down, preferred_element_type=f32)


_Share = namedtuple("_Share", "sigmoid bias scaling first count shared "
                    "n_group topk_group")


def _n_mats(attrs):
    """The matrices of one expert: three (gate, up, down) of the gated
    SiLU, two (up, down) of ``act="relu2"``. **The layout is part of
    the form** (module docstring, The expert's form): the gated form's
    ``up`` lies ``(E, D, F)`` and needs F in whole lanes, the ungated
    form's ``(E, F, D)`` - read with ``grouped_matmul(n_major=True)`` -
    and needs F in whole sublanes alone. What forced the second layout
    is a width that is not whole lanes (1,856), not the activation; the
    op keys it on ``act`` because the one block with such a width is
    the ungated one, so a gated expert of such a width is still refused
    by the Pallas tier (``_moe_eligible``; the XLA composition runs it)
    and an ungated one of whole lanes takes the transposed read without
    needing it. Everything that reads the pairing asks here."""
    act = str(attrs.get("act", "silu"))
    if act not in ("silu", "relu2"):
        raise ValueError(f"MoEFFN: act {act!r} is 'silu' (gated, three "
                         "matrices) or 'relu2' (ungated, two)")
    return 2 if act == "relu2" else 3


def _step_len(attrs):
    """The rows a slot has where the layer takes ``fed`` behind its
    rows and keeps a window's pads out of its experts, else 0."""
    return parse_int(attrs.get("step_len", 0))


def _share_spec(attrs):
    """What makes a layer a share of a wider one (module docstring),
    None for the plain layer."""
    E = parse_int(attrs["num_experts"])
    sigmoid = str(attrs.get("scoring", "softmax")) == "sigmoid"
    bias = parse_bool(attrs.get("router_bias", False))
    scaling = parse_float(attrs.get("scaling", 1.0))
    first = parse_int(attrs.get("held_first", 0))
    count = parse_int(attrs.get("held_count", 0)) or E
    shared = parse_int(attrs.get("shared_hidden", 0))
    n_group = parse_int(attrs.get("n_group", 1))
    topk_group = parse_int(attrs.get("topk_group", 1))
    # ``step_len`` alone makes no share: the plain layer keeps the pads
    # out by itself (``moe_ffn``)
    if not (sigmoid or bias or shared or scaling != 1.0
            or (first, count) != (0, E) or n_group > 1):
        return None
    if first < 0 or first + count > E:
        raise ValueError(f"MoEFFN holds experts {first}..{first + count} "
                         f"of {E}")
    if n_group > 1:
        top_k = parse_int(attrs.get("top_k", 1))
        if not sigmoid or E % n_group or E // n_group < 2 \
                or not 1 <= topk_group <= n_group \
                or top_k > topk_group * (E // n_group):
            raise ValueError(
                f"MoEFFN: group-limited routing (n_group {n_group}, "
                f"topk_group {topk_group}) is the sigmoid router's, over "
                f"{E} experts in whole groups of at least 2, with top_k "
                f"{top_k} experts inside the groups kept")
    return _Share(sigmoid, bias, scaling, first, count, shared, n_group,
                  topk_group)


def _real_rows(x, fed):
    """(T,) bool: which of the rows ``x`` are real tokens. The rows are
    whole slots of as many rows as ``fed`` says there are slots:
    ``step_len`` of a window, or all of them under one count where the
    window's rows are packed (``ops/rows.py``)."""
    fed = fed.astype(jnp.int32)
    return (jnp.arange(x.shape[0] // fed.shape[0], dtype=jnp.int32)[None, :]
            < fed[:, None]).reshape(-1)


def moe_share_ffn(attrs, inputs, experts_fn):
    """``moe_ffn`` for a layer that is a share of a wider one (module
    docstring): ``([out, experts], [stats])``, ``stats`` with the
    assignments that landed here fifth."""
    share = _share_spec(attrs)
    x, rest = inputs[0], list(inputs[1:])
    real = _real_rows(x, rest.pop(0)) if _step_len(attrs) else None
    router = rest.pop(0)
    router_bias = rest.pop(0) if share.bias else None
    n = _n_mats(attrs)
    top_k = parse_int(attrs.get("top_k", 1))
    norm_topk = parse_bool(attrs.get("norm_topk", False))
    if share.sigmoid:
        weights, experts = moe_route_sigmoid(
            x, router, router_bias, top_k, norm_topk, share.scaling,
            share.n_group, share.topk_group)
    else:
        weights, experts = moe_route(x, router, top_k, norm_topk)
        weights = weights * share.scaling
    out, sizes = _held_experts(x, weights, experts, real, share.first,
                               share.count, rest[:n], experts_fn)
    if share.shared:
        out = out + _dense_expert(x, *rest[n:2 * n])
    routed = experts.size if real is None \
        else top_k * jnp.sum(real.astype(jnp.int32))
    stats = jnp.stack([jnp.int32(1), jnp.asarray(routed, jnp.int32),
                       jnp.sum((sizes > 0).astype(jnp.int32)),
                       jnp.max(sizes), jnp.sum(sizes)]).astype(jnp.int32)
    return [out.astype(x.dtype), experts], [stats]


def _experts_ragged(xs, group_sizes, *mats):
    """(M, D) sorted rows -> (M, D) float32: the three grouped matmuls
    (two of the ungated form, whose ``up`` lies (E, F, D)) over exactly
    the routed rows, float32 accumulation."""
    f32 = jnp.float32
    if len(mats) == 2:
        up, down = mats
        u = lax.ragged_dot(xs, jnp.swapaxes(up, 1, 2).astype(xs.dtype),
                           group_sizes, preferred_element_type=f32)
        return lax.ragged_dot(_relu2(u).astype(xs.dtype),
                              down.astype(xs.dtype), group_sizes,
                              preferred_element_type=f32)
    gate, up, down = mats
    g = lax.ragged_dot(xs, gate.astype(xs.dtype), group_sizes,
                       preferred_element_type=f32)
    u = lax.ragged_dot(xs, up.astype(xs.dtype), group_sizes,
                       preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(xs.dtype)
    return lax.ragged_dot(h, down.astype(xs.dtype), group_sizes,
                          preferred_element_type=f32)


def moe_ffn(attrs, inputs, experts_fn):
    """The op's body with the grouped expert computation supplied:
    ``experts_fn(xs, group_sizes, *mats) -> (M, D) float32``, ``mats``
    an expert's stacked matrices (``_n_mats``). Returns ``([out,
    experts], [stats])``."""
    if _share_spec(attrs) is not None:
        return moe_share_ffn(attrs, inputs, experts_fn)
    x, *rest = inputs
    real = _real_rows(x, rest.pop(0)) if _step_len(attrs) else None
    router, *mats = rest
    num_experts = mats[0].shape[0]
    top_k = parse_int(attrs.get("top_k", 1))
    weights, experts = moe_route(x, router, top_k,
                                 parse_bool(attrs.get("norm_topk", False)))
    if real is None:
        token_of_row, inverse, group_sizes = moe_sort(experts, num_experts)
    else:
        # a pad's choices go to a dead group behind the last expert:
        # sorted last, no expert's rows, and of weight zero
        token_of_row, inverse, group_sizes = moe_sort(
            jnp.where(real[:, None], experts, num_experts), num_experts + 1)
        group_sizes = group_sizes[:num_experts]
        weights = jnp.where(real[:, None], weights, 0.0)
    y = experts_fn(x[token_of_row], group_sizes, *mats)
    if real is not None:
        # what the grouped matmuls leave in rows of no group is not read
        routed = jnp.arange(y.shape[0], dtype=jnp.int32) \
            < jnp.sum(group_sizes)
        y = jnp.where(routed[:, None], y, 0.0)
    out = moe_combine(y, inverse, weights, x.dtype)
    return [out, experts], [moe_stats(group_sizes)]


def _moe_fwd(attrs, inputs, aux, is_train, rng):
    return moe_ffn(attrs, inputs, _experts_ragged)


def _moe_infer(attrs, in_shapes):
    data_s = in_shapes[0]
    E = parse_int(attrs["num_experts"])
    F = parse_int(attrs["num_hidden"])
    k = parse_int(attrs.get("top_k", 1))
    share = _share_spec(attrs)
    stats = [(4,) if share is None else (5,)]
    if data_s is None:
        return in_shapes, [None, None], stats
    if len(data_s) != 2:
        raise ValueError(f"MoEFFN takes (tokens, width) rows, got {data_s}")
    T, D = data_s
    held = E if share is None else share.count
    shared = 0 if share is None else share.shared
    shapes = [data_s]
    step_len = _step_len(attrs)
    if step_len:
        # as many slots as ``fed`` has entries; ``step_len`` rows each
        # where its shape is not known yet
        slots = in_shapes[1][0] if in_shapes[1] is not None \
            else T // step_len
        if T % slots or (in_shapes[1] is None and T % step_len):
            raise ValueError(f"MoEFFN: {T} rows are no whole slots of "
                             f"step_len {step_len} ({slots} slots)")
        shapes.append((slots,))
    shapes.append((E, D))
    if share is not None and share.bias:
        shapes.append((E,))
    if _n_mats(attrs) == 2:             # the model's width last in both
        shapes += [(held, F, D), (held, F, D)]
        shared_shapes = [(D, shared), (shared, D)]
    else:
        shapes += [(held, D, F), (held, D, F), (held, F, D)]
        shared_shapes = [(D, shared), (D, shared), (shared, D)]
    if shared:
        shapes += shared_shapes
    return shapes, [data_s, (T, k)], stats


def _moe_inputs(attrs):
    """The op's inputs: the plain layer's five, ``fed`` behind the
    rows where pads are kept out (``step_len``), a ``router_bias``
    where the router has one, a shared expert's three weights where it
    has one."""
    share = _share_spec(attrs) if attrs.get("num_experts") else None
    names = ["data"]
    if _step_len(attrs):
        names.append("fed")
    names.append("router_weight")
    if share is not None and share.bias:
        names.append("router_bias")
    mats = ["gate_weight", "up_weight", "down_weight"][-_n_mats(attrs):]
    names += mats
    if share is not None and share.shared:
        names += ["shared_" + m for m in mats]
    return names


#: the entries of ``moe_stats``, in its order (``OpDef.state_reads``):
#: layer executions, the (token, expert) assignments they made, the
#: experts that got at least one, each execution's busiest expert's
#: assignments and, where the layer holds a share of its experts (the
#: counts are then of the held ones), the assignments that landed on
#: them - in the ring too (``moe_held``), so that a window's record says
#: how many rows its grouped matmuls ran over beside the experts they
#: read
_MOE_COUNTS = read_counts(
    ("moe.layer_steps", "moe_layer_steps"), ("moe.assignments", None),
    ("moe.experts_touched", "moe_touched"), ("moe.max_expert_load", None),
    ("moe.held_assignments", "moe_held"))

register("MoEFFN", inputs=_moe_inputs, aux=("moe_stats",), full=_moe_fwd,
         num_outputs=2, output_names=["output", "experts"], num_visible=1,
         stateful_infer=True, aux_dtypes={"moe_stats": "int32"},
         state_reads=(_MOE_COUNTS, None),
         attr_spec={"num_experts": (parse_int, None),
                    "num_hidden": (parse_int, None),
                    "top_k": (parse_int, 1),
                    "norm_topk": (parse_bool, False),
                    "scoring": (None, "softmax"),
                    "router_bias": (parse_bool, False),
                    "scaling": (parse_float, 1.0),
                    "held_first": (parse_int, 0),
                    "held_count": (parse_int, 0),
                    "shared_hidden": (parse_int, 0),
                    "step_len": (parse_int, 0),
                    "n_group": (parse_int, 1),
                    "topk_group": (parse_int, 1),
                    "act": (None, None)},
         infer_shape=_moe_infer,
         doc="Routed expert feed-forward: top_k of num_experts experts of "
             "width num_hidden per token, gated SiLU or (act='relu2') "
             "ungated relu squared (ops/moe.py).")
