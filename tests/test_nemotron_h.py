"""Nemotron-H's block (``block="nemotron_h"``, ISSUE 63:
NVIDIA-Nemotron-3-Nano-30B-A3B) on the CPU at tiny sizes, float32. What
every served block does is ``tests/decode_block_suite.py``'s, over the
row ``nemotron_h`` of ``tests/decode_blocks.py`` against the benchmark's
plain reference (``chipbench/reference/nemotron_h.py``: the recurrence
step by step with B and C indexed by group, the published router, one
expert at a time, the same share). Below that the block's own: layers
that are one sub-layer, the grouped state update against the recurrence
(and one group lowering as it did), the group-wise gated norm, the
ungated expert against a dense loop, the two shares of an ``E`` layer
adding up to the uncut layer, the router as published, the records'
counts over the layers that have the mechanism, and the reference's
controls each moving the logits. Five layers (M E M * E) of width 64: 8
Mamba heads of 8 in two groups with a state of 16, a chunk of 8 under a
window of 16, 4 query heads on one K/V head of 24, 8 experts of 24, 3 a
token, 4 held (2..6), a shared expert of 40."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import moe, pallas_kernels, ssm
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

from chipbench.reference import nemotron_h as ref  # noqa: E402

BLOCK = "nemotron_h"
N = blocks.config(BLOCK)["nemotron_h"]
TOL = blocks.TOL[BLOCK]
_W = (WINDOW, [WINDOW] * SLOTS)
_ONES = [(1, [1] * SLOTS)]


# ------------------------------------------------------------- the graph
def test_a_layer_is_one_sub_layer():
    """M E M * E: a mixer's layer has ``ln1``, its mixer and ``proj``
    and no feed-forward; an expert layer ``ln2`` and ``MoEFFN`` and no
    mixer; an untied head, no positions, no gate matrix anywhere."""
    sym = blocks.symbol(BLOCK, 1)
    args = set(sym.list_arguments())
    for i, kind in enumerate(N["hybrid_override_pattern"]):
        mine = {a for a in args if a.startswith(f"lm_l{i}_")}
        if kind == "E":
            assert mine == {f"lm_l{i}_{n}" for n in (
                "ln2_gamma", "moe_router_weight", "moe_router_bias",
                "moe_up_weight", "moe_down_weight", "moe_shared_up_weight",
                "moe_shared_down_weight")}
        elif kind == "M":
            assert mine == {f"lm_l{i}_{n}" for n in (
                "ln1_gamma", "mamba_in_weight", "mamba_conv_weight",
                "mamba_conv_bias", "mamba_dt_bias", "mamba_A_log", "mamba_D",
                "mamba_norm_gamma", "proj_weight")}
        else:
            assert mine == {f"lm_l{i}_{n}" for n in (
                "ln1_gamma", "qkv_weight", "proj_weight")}
    assert "lm_head_weight" in args and "pos_ids" not in args
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert (ops.count("ssm_mixer_decode"), ops.count("attention_decode"),
            ops.count("MoEFFN")) == (2, 1, 2)
    # five residual joins, one a layer
    assert sum(1 for n in sym._topo_nodes() if not n.is_variable
               and n.name.endswith(("_proj_unfold", "_ffn_unfold"))) == 5
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(SLOTS, 1), fed=(SLOTS,))[0]))
    # [z | xBC | dt] = 64 | 64 + 2 x 2 x 16 | 8; q, k, v of a head of 24
    assert shapes["lm_l0_mamba_in_weight"] == (64 + 128 + 8, 64)
    assert shapes["lm_l0_mamba_conv_weight"] == (128, 4)
    assert shapes["lm_l3_qkv_weight"] == ((4 + 2) * 24, 64)
    assert shapes["lm_l3_proj_weight"] == (64, 4 * 24)
    # an expert's two matrices, the model's width last in both
    assert shapes["lm_l1_moe_up_weight"] == (4, 24, 64) \
        == shapes["lm_l1_moe_down_weight"]
    assert shapes["lm_l1_moe_shared_up_weight"] == (64, 40)
    assert shapes["lm_l1_moe_router_weight"] == (8, 64)


def test_the_ops_declare_their_sizes_and_their_counts(driver):
    # two mixers' states of 8 x 8 x 16 and tails of 3 x 128; one
    # attention layer's K/V head of 24, unpaired
    assert driver.state_bytes["recurrent"] == 2 * SLOTS * 8 * 8 * 16 * 4
    assert driver.state_bytes["conv"] == 2 * SLOTS * 3 * 128 * 4
    assert driver.state_bytes["rows"] == 2 * SLOTS * CAPACITY * 24 * 4
    blocks.reset(driver)
    driver.step(np.zeros((SLOTS, WINDOW), np.int32),
                fed=[16, 1] + [0] * (SLOTS - 2))
    # counted over the layers that have the mechanism: 2 of 5, and 1
    assert driver.last_reads["ssm.rows"] == 2 * 17
    assert driver.last_reads["ssm.touched"] == 2 * 2
    assert driver.last_reads["attn.live_rows"] == 17
    stats = driver.moe_stats(driver.moe_stats_begin())
    assert stats["moe.layer_steps"] == 2
    assert stats["moe.assignments"] == 2 * 3 * 17
    blocks.reset(driver)
    for n in blocks.symbol(BLOCK, 1)._topo_nodes():
        if n.op == "MoEFFN":
            attrs = get_op("MoEFFN").normalize_attrs(dict(n.attrs))
            assert (attrs["num_experts"], attrs["top_k"],
                    attrs["num_hidden"], attrs["held_first"],
                    attrs["held_count"], attrs["shared_hidden"],
                    attrs["act"], attrs["scoring"], attrs["scaling"]) \
                == (8, 3, 24, 2, 4, 40, "relu2", "sigmoid", 2.5)
        if n.op == "ssm_mixer_decode":
            assert int(n.attrs["groups"]) == 2


def test_every_ring_record_counts_the_layers_that_have_the_mechanism(engine):
    """Four requests of ragged lengths through the scheduler: a record's
    ``moe_layer_steps`` is the two ``E`` layers', ``ssm_touched`` the
    two ``M`` layers' fed slots - of five layers - and they add up to
    the counters."""
    prompts, grew, steps = blocks.counted(BLOCK, engine, (
        "moe.assignments", "moe.held_assignments", "moe.layer_steps",
        "moe.experts_touched", "ssm.rows", "ssm.touched"))
    fed_rows = sum(len(p) + 11 for p in prompts)
    assert grew["ssm.rows"] == 2 * fed_rows
    assert grew["moe.assignments"] == 2 * 3 * fed_rows       # no pad's
    assert 0 < grew["moe.held_assignments"] < grew["moe.assignments"]
    assert steps and all(
        f in r for r in steps for f in (
            "moe_layer_steps", "moe_touched", "moe_held", "ssm_rows",
            "ssm_touched"))
    assert all(r["moe_layer_steps"] == 2 for r in steps)
    assert sum(r["moe_held"] for r in steps) == grew["moe.held_assignments"]
    assert sum(r["moe_touched"] for r in steps) \
        == grew["moe.experts_touched"]
    assert sum(r["ssm_touched"] for r in steps) == grew["ssm.touched"]
    assert all(r["ssm_touched"] % 2 == 0 and r["ssm_touched"]
               <= 2 * r["slots"] for r in steps if "slots" in r)
    assert all(r["moe_touched"] <= 2 * 4 for r in steps)     # held: 4


def test_the_named_scopes_reach_the_lowered_programs():
    """``ssm_conv``, ``ssm_update``, ``ssm_scan`` and, under the Pallas
    tier, the kernels ``moe_gmm_up`` and ``moe_gmm_down`` are in the
    window program's text (what the device trace names operations by);
    no ``moe_gmm_gate_up``: the expert has no gate."""
    with blocks.tier("pallas"):
        text = blocks.lowered_text(blocks.symbol(BLOCK, WINDOW), SLOTS,
                                   WINDOW, debug_info=True)
    for scope in ("ssm_conv", "ssm_update", "ssm_scan", "moe_gmm_up",
                  "moe_gmm_down"):
        assert scope in text, scope
    assert "moe_gmm_gate_up" not in text


# ------------------------------------------------------ the grouped state
def _op_case(variant, S, fed, H, P, Nst, groups, packed_rows=None, chunk=8,
             T=40, seed=0):
    """The op alone over three sequences from scratch: two dispatches
    (the second reads the first's state) against the recurrence with B
    and C indexed by group; returns the largest difference."""
    K = 4
    d_in, C = H * P, H * P + 2 * groups * Nst
    width = d_in + C + H
    rng = np.random.RandomState(seed)
    conv_w = (0.3 * rng.randn(C, K)).astype("f")
    conv_b = (0.1 * rng.randn(C)).astype("f")
    dt_bias = rng.randn(H).astype("f")
    a_log = np.log(rng.uniform(1, 16, H)).astype("f")
    D = np.ones(H, "f")
    slots = len(fed[0])
    seqs = rng.randn(slots, T, width).astype("f")
    opdef = get_op("ssm_mixer_decode")
    attrs = opdef.normalize_attrs(dict(
        heads=H, head_dim=P, d_state=Nst, d_conv=K, chunk=chunk, step_len=S,
        capacity=1000, groups=groups))
    fn = opdef.variant_fn(variant)
    W = ssm.lane_width(H, P, groups)
    aux = [jnp.full((slots, K - 1, C), 7.0),       # a last occupant's
           jnp.full((slots, d_in // W, Nst, W), 3.0),
           jnp.zeros((slots, 1), jnp.int32)]
    got, at = [[] for _ in range(slots)], [0] * slots
    for counts in fed:
        if packed_rows is None:
            data = np.full((slots, S, width), 99.0, "f")
            for b, n in enumerate(counts):
                data[b, :n] = seqs[b, at[b]:at[b] + n]
        else:
            data = np.full((1, packed_rows, width), 99.0, "f")
            o = 0
            for b, n in enumerate(counts):
                data[0, o:o + n] = seqs[b, at[b]:at[b] + n]
                o += n
        outs, aux = fn(attrs, [jnp.asarray(data.reshape(-1, width)),
                               jnp.asarray(counts, jnp.int32), conv_w,
                               conv_b, dt_bias, a_log, D], aux, False, None)
        out = np.asarray(outs[0]).reshape(data.shape[:2] + (d_in,))
        o = 0
        for b, n in enumerate(counts):
            got[b].append(out[b, :n] if packed_rows is None
                          else out[0, o:o + n])
            o, at[b] = o + n, at[b] + n
        assert np.asarray(aux[2]).reshape(-1).tolist() == at
    worst = 0.0
    for b in range(slots):
        rows = seqs[b, :at[b]]
        z, xbc, dt = rows[:, :d_in], rows[:, d_in:d_in + C], \
            rows[:, d_in + C:]
        xp = np.concatenate([np.zeros((K - 1, C), "f"), xbc])
        conv = sum(xp[k:k + at[b]] * conv_w[None, :, k]
                   for k in range(K)) + conv_b
        act = conv / (1 + np.exp(-conv))
        x = act[:, :d_in].reshape(-1, H, P)
        GN = groups * Nst
        y, _h = ssm.ssm_recurrence(
            jnp.asarray(x), jnp.asarray(np.log1p(np.exp(dt + dt_bias))),
            jnp.asarray(-np.exp(a_log)),
            jnp.asarray(act[:, d_in:d_in + GN].reshape(-1, groups, Nst)),
            jnp.asarray(act[:, d_in + GN:].reshape(-1, groups, Nst)),
            jnp.zeros((H, P, Nst)))
        want = ((np.asarray(y) + D[None, :, None] * x).reshape(-1, d_in)
                * (z / (1 + np.exp(-z))))
        worst = max(worst, float(np.abs(np.concatenate(got[b]) - want).max()))
    return worst


_LAYOUTS = {
    "steps": (1, [[1, 1, 1], [1, 0, 1], [1, 1, 1]], None),
    "whole_two_chunks": (16, [[16, 16, 16], [16, 16, 16]], None),
    "whole_ragged": (16, [[13, 3, 16], [9, 16, 1]], None),
    "packed_riders": (16, [[16, 1, 1], [16, 1, 1]], 24),
    "packed_parts": (16, [[5, 0, 12], [1, 11, 9]], 24),
}
#: (heads, head_dim, d_state, groups): the row's own (a lane group a
#: group of B and C); two lane groups a group of B and C (the published
#: geometry has four); four groups of four heads
_GEOMETRIES = {"a_lane_group_a_group": (8, 8, 16, 2),
               "two_lane_groups_a_group": (16, 32, 16, 2),
               "four_groups": (16, 16, 8, 4)}


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_the_grouped_update_is_the_recurrence_indexed_by_group(
        variant, layout, geometry):
    """``ssm_mixer_decode(groups=)`` alone, both lowerings (the kernels
    in interpret mode): steps, chunks, a ragged last chunk and packed
    rows with riders against ``ssm_recurrence`` with head ``h`` reading
    group ``h // (H / groups)``."""
    S, fed, rows = _LAYOUTS[layout]
    assert _op_case(variant, S, fed, *_GEOMETRIES[geometry],
                    packed_rows=rows) <= 2e-5


def test_a_head_that_reads_another_groups_b_and_c_is_seen():
    """The comparison tells the groups apart: the op run with one group
    over the same rows (every head reading group 0's B and C, the
    rows' second group read as nothing) is far from the recurrence by
    group."""
    H, P, Nst = 8, 8, 16
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(12, H, P).astype("f"))
    dlt = jnp.asarray(np.abs(rng.randn(12, H)).astype("f") * 0.1)
    A = jnp.asarray(-np.ones(H, "f"))
    B, C = (jnp.asarray(rng.randn(12, 2, Nst).astype("f")) for _ in "bc")
    h0 = jnp.zeros((H, P, Nst))
    by_group, _ = ssm.ssm_recurrence(x, dlt, A, B, C, h0)
    group0, _ = ssm.ssm_recurrence(x, dlt, A, B[:, 0], C[:, 0], h0)
    np.testing.assert_allclose(by_group[:, :4], group0[:, :4], atol=1e-6)
    assert np.abs(np.asarray(by_group[:, 4:] - group0[:, 4:])).max() > 0.1


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("S", [1, 16])
def test_one_group_lowers_to_what_it_lowered_to(variant, S):
    """``groups=1`` said aloud is the op without the attribute, to the
    lowered text (the Granite rows' whole programs are held to the
    parent's digests in ``tests/test_chip_compile.py``)."""
    opdef = get_op("ssm_mixer_decode")
    H, P, Nst, K = 8, 16, 16, 4
    C = H * P + 2 * Nst
    ins = [jnp.zeros((4 * S, 2 * H * P + 2 * Nst + H)),
           jnp.ones((4,), jnp.int32), jnp.zeros((C, K)), jnp.zeros((C,)),
           jnp.zeros((H,)), jnp.zeros((H,)), jnp.zeros((H,))]
    aux = [jnp.zeros((4, K - 1, C)), jnp.zeros((4, 1, Nst, 128)),
           jnp.zeros((4, 1), jnp.int32)]
    texts = []
    for more in ({}, {"groups": 1}):
        attrs = opdef.normalize_attrs(dict(
            heads=H, head_dim=P, d_state=Nst, d_conv=K, chunk=8, step_len=S,
            capacity=64, **more))
        fn = opdef.variant_fn(variant)
        texts.append(jax.jit(lambda r, a: fn(attrs, r, a, False, None))
                     .lower(ins, aux).as_text())
    assert texts[0] == texts[1]


def test_the_gated_norms_statistic_is_a_groups_own():
    rng = np.random.RandomState(1)
    x = rng.randn(6, 64).astype("f") * np.repeat([1.0, 30.0], 32)[None, :]
    gamma = (1 + 0.3 * rng.randn(64)).astype("f")
    got = np.asarray(moe.rms_norm(jnp.asarray(x), jnp.asarray(gamma), 1e-5,
                                  groups=2))
    parts = x.reshape(6, 2, 32)
    want = (parts / np.sqrt((parts ** 2).mean(-1, keepdims=True) + 1e-5)) \
        .reshape(6, 64) * gamma
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    one = np.asarray(moe.rms_norm(jnp.asarray(x), jnp.asarray(gamma), 1e-5))
    assert np.abs(one - want).max() > 0.5       # one statistic is another


# -------------------------------------------------------- the expert layer
def _layer_inputs(E=8, D=32, F=24, Fs=40, T=40, seed=4):
    rs = np.random.RandomState(seed)
    f = lambda *s: np.asarray(rs.randn(*s) * 0.3, np.float32)  # noqa: E731
    params = {"p_moe_router_weight": f(E, D), "p_moe_router_bias": f(E),
              "p_moe_up_weight": f(E, F, D), "p_moe_down_weight": f(E, F, D),
              "p_moe_shared_up_weight": f(D, Fs),
              "p_moe_shared_down_weight": f(Fs, D)}
    return params, f(T, D)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
def test_the_two_shares_add_up_to_the_uncut_layer(variant):
    """Two chips' shares of a small ``E`` layer (8 experts, 3 a token;
    experts 0..4 and 4..8, the shared expert counted once) through the
    program's ``MoEFFN(act="relu2")`` add up to the uncut layer as the
    reference computes it, each share equals the reference's own share,
    every chip routes alike (over all 8, the bias in the choice), and
    the gates keep their normalisation over the three chosen."""
    E, D, F, Fs, T, k = 8, 32, 24, 40, 40, 3
    params, x = _layer_inputs(E, D, F, Fs, T)
    cfg = {"n_routed_experts": E, "num_experts_per_tok": k,
           "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        whole, shared, chosen = ref.expert_layer(
            jnp.asarray(x), "p", params, cfg, lambda a: a, held=(0, E))
    op = get_op("MoEFFN")
    total = np.zeros((T, D), np.float32)
    landed = 0
    for first in (0, E // 2):
        attrs = op.normalize_attrs(dict(
            num_experts=E, num_hidden=F, top_k=k, norm_topk=True,
            scoring="sigmoid", router_bias=True, scaling=2.5,
            held_first=first, held_count=E // 2, shared_hidden=Fs,
            step_len=1, act="relu2"))
        assert op.input_names(attrs) == [
            "data", "fed", "router_weight", "router_bias", "up_weight",
            "down_weight", "shared_up_weight", "shared_down_weight"]
        mine = {m: params[f"p_moe_{m}_weight"][first:first + E // 2]
                for m in ("up", "down")}
        ins = [jnp.asarray(x), jnp.ones((T,), jnp.int32),
               params["p_moe_router_weight"], params["p_moe_router_bias"],
               jnp.asarray(mine["up"]), jnp.asarray(mine["down"]),
               params["p_moe_shared_up_weight"],
               params["p_moe_shared_down_weight"]]
        (out, experts), (stats,) = op.variant_fn(variant)(
            attrs, ins, [jnp.zeros((5,), jnp.int32)], False, None)
        np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                      np.sort(np.asarray(chosen), -1))
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref.expert_layer(jnp.asarray(x), "p", {
                **params, **{f"p_moe_{m}_weight": w
                             for m, w in mine.items()}},
                cfg, lambda a: a, held=(first, E // 2))
        np.testing.assert_allclose(np.asarray(out) - np.asarray(shared),
                                   np.asarray(part), atol=5e-5, rtol=5e-5)
        total += np.asarray(out) - np.asarray(shared)
        stats = np.asarray(stats).tolist()
        assert stats[:2] == [1, T * k] and stats[4] == int(
            ((np.asarray(chosen) >= first)
             & (np.asarray(chosen) < first + E // 2)).sum())
        landed += stats[4]
    assert landed == T * k                   # every assignment, once
    np.testing.assert_allclose(total, np.asarray(whole), atol=1e-4, rtol=1e-4)
    assert np.max(np.abs(np.asarray(whole))) > 0.1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["even", "ragged", "one_expert", "empty"])
def test_the_ungated_expert_is_a_dense_loop(case, dtype):
    """``grouped_expert_ffn`` of two matrices (``moe_gmm_up`` with relu
    squared in its epilogue, ``moe_gmm_down``; an expert's width 24 and
    the model's 32, neither whole lanes) and the XLA composition
    against ``down[e](relu(x up[e]^T) ** 2)`` an expert at a time."""
    E, D, F = 6, 32, 24
    sizes = {"even": [8] * 6, "ragged": [1, 0, 17, 3, 0, 30],
             "one_expert": [0, 0, 0, 40, 0, 0], "empty": [0] * 6}[case]
    M = max(sum(sizes), 8)
    rs = np.random.RandomState(2)
    xs = jnp.asarray(rs.randn(M, D), dtype)
    up = jnp.asarray(rs.randn(E, F, D) * 0.3, dtype)
    down = jnp.asarray(rs.randn(E, F, D) * 0.3, dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(pallas_kernels.grouped_expert_ffn(xs, group_sizes, up,
                                                       down))
    xla = np.asarray(moe._experts_ragged(xs, group_sizes, up, down))
    f32 = lambda a: np.asarray(a, np.float32)               # noqa: E731
    at = 0
    for e, n in enumerate(sizes):
        h = np.square(np.maximum(f32(xs[at:at + n]) @ f32(up[e]).T, 0.0))
        want = f32(jnp.asarray(h, dtype)) @ f32(down[e])
        tol = 1e-4 if dtype == "float32" else 0.15
        np.testing.assert_allclose(got[at:at + n], want, atol=tol, rtol=tol)
        np.testing.assert_allclose(xla[at:at + n], want, atol=tol, rtol=tol)
        at += n


def test_the_router_is_the_published_one():
    """Sigmoid scores in float32, the bias in the choice alone, the
    weights the scores over their sum times 2.5: the program's
    ``moe_route_sigmoid`` and the reference's ``route`` choose and
    weigh alike; each of the reference's two router controls does
    not."""
    rs = np.random.RandomState(7)
    E, D, T, k = 128, 64, 200, 6
    x = jnp.asarray(rs.randn(T, D).astype(np.float32))
    router = jnp.asarray((rs.randn(E, D) * 0.05).astype(np.float32))
    bias = jnp.asarray((rs.randn(E) * 0.01).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        weights, experts = moe.moe_route_sigmoid(x, router, bias, k, True,
                                                 2.5)
        chosen, dense = ref.route(x @ router.T, bias, k, 2.5)
        unbiased, _ = ref.route(x @ router.T, bias, k, 2.5,
                                choice_bias=False)
        _, unscaled = ref.route(x @ router.T, bias, k, 2.5, scaled=False)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(np.asarray(chosen), -1))
    got = np.zeros((T, E), np.float32)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, np.asarray(dense), atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dense).sum(-1), 2.5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(unscaled).sum(-1), 1.0, atol=1e-5)
    # the bias moves some choices, not all
    moved = (np.sort(np.asarray(unbiased), -1)
             != np.sort(np.asarray(chosen), -1)).any(-1).mean()
    assert 0.05 < moved < 0.95


def test_the_held_share_changes_the_result_and_the_reference_follows():
    """The share is not a no-op: with every expert held the logits
    differ from the half's by far more than the bound, and the
    reference given the same share agrees with each."""
    seqs = blocks.seqs(BLOCK, 40, seed=21)
    whole = {"nemotron_h": {"held": (0, 8)}}
    rs = np.random.default_rng(5)
    params = blocks.params(BLOCK)
    whole_params = dict(params)
    for name, arr in params.items():
        if name.endswith(("_moe_up_weight", "_moe_down_weight")):
            more = 0.25 * rs.standard_normal((2,) + arr.shape[1:]) \
                .astype(np.float32)
            whole_params[name] = np.concatenate([more, arr, more])
    sched = [_W] * 2 + _ONES * 4
    half, _, _ = blocks.run(blocks.driver(BLOCK), seqs, sched)
    full, at, _ = blocks.run(
        blocks.driver(BLOCK, arg_params=whole_params, **whole), seqs, sched)
    want_half = blocks.reference(BLOCK, seqs)
    want_whole = blocks.reference(BLOCK, seqs, whole_params, whole)
    for slot in range(SLOTS):
        n = at[slot]
        assert blocks.err(half[slot, :n], want_half[slot, :n]) <= TOL
        assert blocks.err(full[slot, :n], want_whole[slot, :n]) <= TOL
    assert np.abs(want_whole[:, :36] - want_half[:, :36]).max() > 100 * TOL


# ------------------------------------------- what the chip's comparison sees
_CONTROLS = {
    "experts_out": {"routed": False}, "relu": {"act": "relu"},
    "gates_unscaled": {"scaled": False},
    "bias_out_of_the_choice": {"choice_bias": False},
    "group_0_for_every_head": {"one_group": True},
    "one_statistic": {"group_norm": False}, "state_none": {"state_every": 1},
}


@pytest.mark.parametrize("control", sorted(_CONTROLS))
def test_a_control_of_the_reference_moves_the_logits(control):
    """Each switch of the reference that the chip's comparison runs as a
    control is far outside the float32 bound of the served path at tiny
    sizes: the comparison can see what it breaks."""
    seqs = blocks.seqs(BLOCK, 48, seed=13)
    want = blocks.reference(BLOCK, seqs)
    broken = blocks.reference(BLOCK, seqs, **_CONTROLS[control])
    assert np.abs(broken - want).max() > 100 * TOL


def test_a_pad_advances_nothing(driver):
    """A slot fed nothing, inside a window and in an S = 1 step, keeps
    tail, state and cursor to the bit, whatever tokens ride its rows."""
    seqs = blocks.seqs(BLOCK, 80, seed=9)
    blocks.run(driver, seqs, [blocks.window(11, 16, 5)])
    carried = lambda: [nc for family in ("conv", "recurrent", "cursor")  # noqa
                       for nc in driver._cells(family)]
    before = {nm: np.asarray(cell.asjax())[1].copy()
              for nm, cell in carried()}
    assert len(before) == 2 + 2 + 3     # two mixers; three cursors
    blocks.run(driver, seqs, [blocks.window(16, 0),
                              (1, [1, 0] + [1] * (SLOTS - 2)),
                              blocks.window(2, 0, 7)],
               start=list(driver.pos))
    for nm, cell in carried():
        assert np.array_equal(np.asarray(cell.asjax())[1], before[nm]), nm
    blocks.reset(driver)


def test_the_block_is_served_not_trained():
    with pytest.raises(MXNetError, match="served, not trained"):
        tfm.get_symbol(block="nemotron_h")
    with pytest.raises(MXNetError, match="per_slot"):
        tfm.get_decode_symbol(block="nemotron_h", nemotron_h=N)
