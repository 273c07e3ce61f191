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

``forward`` (the XLA composition and fallback) sorts the (token,
expert) assignments by expert and runs ``jax.lax.ragged_dot`` over
exactly the routed rows: no expert is computed for a token not routed
to it, no assignment is dropped (no capacity factor). The ``pallas``
variant (ops/pallas_kernels.grouped_expert_ffn) runs the same sorted
rows through two grouped-matmul kernels named ``moe_gmm_*`` in the
device trace; at a decode step it reads each touched expert's weights
once and no untouched expert's.

Where the tokens went is counted on the device: the op's ``moe_stats``
aux cell (int32 ``[1, assignments, experts_touched, max_expert_load]``
of the latest forward) is overwritten by every forward, inference
included (``stateful_infer``); ``serve.decode`` reads it after the
fetch it already makes and feeds the ``serve.decode.moe.*`` counters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import parse_bool, parse_float, parse_int
from .registry import register

__all__ = ["rms_norm", "moe_route", "moe_sort", "moe_combine"]


# ------------------------------------------------------------------ RMSNorm
def rms_norm(x, gamma, eps, unit_offset=False, cast_to_gain=False):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis; the
    statistics in float32 whatever the input dtype (LayerNorm's rule).
    ``unit_offset`` scales by ``1 + gamma`` (a gain stored as its
    distance from one); ``cast_to_gain`` hands the result over at the
    gain's dtype, not the input's: a float32 residual stream is
    normalised into the compute width the matmuls behind it run at."""
    x32 = x.astype(jnp.float32)
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
                     "cast_to_gain": (parse_bool, False)},
          infer_shape=_rms_infer)
def _rms_norm_op(attrs, data, gamma):
    """Root-mean-square normalisation over the last axis with a gain
    and no bias (arXiv:1910.07467)."""
    return rms_norm(data, gamma, parse_float(attrs.get("eps", 1e-5)),
                    parse_bool(attrs.get("unit_offset", False)),
                    parse_bool(attrs.get("cast_to_gain", False)))


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


def _experts_ragged(xs, group_sizes, gate, up, down):
    """(M, D) sorted rows -> (M, D) float32: the three grouped matmuls
    over exactly the routed rows, float32 accumulation."""
    f32 = jnp.float32
    g = lax.ragged_dot(xs, gate.astype(xs.dtype), group_sizes,
                       preferred_element_type=f32)
    u = lax.ragged_dot(xs, up.astype(xs.dtype), group_sizes,
                       preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(xs.dtype)
    return lax.ragged_dot(h, down.astype(xs.dtype), group_sizes,
                          preferred_element_type=f32)


def moe_ffn(attrs, inputs, experts_fn):
    """The op's body with the grouped expert computation supplied:
    ``experts_fn(xs, group_sizes, gate, up, down) -> (M, D) float32``.
    Returns ``([out, experts], [stats])``."""
    x, router, gate, up, down = inputs
    num_experts = gate.shape[0]
    top_k = parse_int(attrs.get("top_k", 1))
    weights, experts = moe_route(x, router, top_k,
                                 parse_bool(attrs.get("norm_topk", False)))
    token_of_row, inverse, group_sizes = moe_sort(experts, num_experts)
    y = experts_fn(x[token_of_row], group_sizes, gate, up, down)
    out = moe_combine(y, inverse, weights, x.dtype)
    return [out, experts], [moe_stats(group_sizes)]


def _moe_fwd(attrs, inputs, aux, is_train, rng):
    return moe_ffn(attrs, inputs, _experts_ragged)


def _moe_infer(attrs, in_shapes):
    data_s = in_shapes[0]
    E = parse_int(attrs["num_experts"])
    F = parse_int(attrs["num_hidden"])
    k = parse_int(attrs.get("top_k", 1))
    if data_s is None:
        return in_shapes, [None, None], [(4,)]
    if len(data_s) != 2:
        raise ValueError(f"MoEFFN takes (tokens, width) rows, got {data_s}")
    T, D = data_s
    return ([data_s, (E, D), (E, D, F), (E, D, F), (E, F, D)],
            [data_s, (T, k)], [(4,)])


_MOE_INPUTS = ("data", "router_weight", "gate_weight", "up_weight",
               "down_weight")

register("MoEFFN", inputs=_MOE_INPUTS, aux=("moe_stats",), full=_moe_fwd,
         num_outputs=2, output_names=["output", "experts"], num_visible=1,
         stateful_infer=True, aux_dtypes={"moe_stats": "int32"},
         attr_spec={"num_experts": (parse_int, None),
                    "num_hidden": (parse_int, None),
                    "top_k": (parse_int, 1),
                    "norm_topk": (parse_bool, False)},
         infer_shape=_moe_infer,
         doc="Routed expert feed-forward: top_k of num_experts gated-SiLU "
             "experts of width num_hidden per token (ops/moe.py).")
