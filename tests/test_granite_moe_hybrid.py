"""Granite 4.0-H's block WITH routed experts (``block="granite_hybrid"``
with ``num_local_experts`` > 0, ISSUE 54: Granite 4.0-H Small) on the
CPU at tiny sizes, float32. What every served block does is
``tests/decode_block_suite.py``'s, over the row ``granite_moe_hybrid``
of ``tests/decode_blocks.py`` against the benchmark's plain reference
(``chipbench/reference/granite_moe_hybrid.py``: the recurrence step by
step, the published router, one expert at a time, the same share).
Below that the routed layer's own: a share of the experts held that
changes the result, a pad's choices landing nowhere, the two shares of
a layer adding up to the uncut layer, the published router against
``norm_topk`` over all. Three layers (mamba, attention, mamba) of width
48: 12 Mamba heads of 8 with a state of 16, a chunk of 8 under a window
of 16, 6 query heads on 3 K/V heads of 8 that are NOT paired (an odd
number; the published 8 heads of 128 fill their lanes alone), 8 experts
of 16, 3 a token, 4 held, a shared feed-forward of 24."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

from chipbench.reference import granite_moe_hybrid as ref  # noqa: E402

BLOCK = "granite_moe_hybrid"
G = blocks.config(BLOCK)["granite"]
TOL = blocks.TOL[BLOCK]
_W = (WINDOW, [WINDOW] * SLOTS)
_ONES = [(1, [1] * SLOTS)]


def test_the_held_share_changes_the_result_and_the_reference_follows():
    """The share is not a no-op: with every expert held the logits
    differ from the half's by far more than the bound, and the
    reference given the same share agrees with each."""
    seqs = blocks.seqs(BLOCK, 40, seed=21)
    whole = {"granite": {"held": (0, 8)}}
    rs = np.random.default_rng(5)
    params = blocks.params(BLOCK)
    whole_params = dict(params)
    for name, arr in params.items():
        if name.endswith(("_moe_gate_weight", "_moe_up_weight",
                          "_moe_down_weight")):
            more = 0.25 * rs.standard_normal(arr.shape).astype(np.float32)
            whole_params[name] = np.concatenate([arr, more])
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


def test_a_pads_choices_land_nowhere(driver):
    """A window's pads are routed nowhere: the layers count the real
    rows' assignments alone (``moe.assignments`` = layers x top_k x real
    rows, whatever junk rides the pads), the assignments that landed
    here are fewer, and a slot's logits do not move when the pads
    beside it change."""
    seqs = blocks.seqs(BLOCK, 80, seed=9)
    k, layers = G["num_experts_per_tok"], blocks.config(BLOCK)["n_layer"]
    outs = []
    fed = [11, 0, 5] + [0] * (SLOTS - 3)
    for junk in (7, 31):
        blocks.run(driver, seqs, [])                    # every slot anew
        tokens = np.full((SLOTS, WINDOW), junk, np.int32)
        for slot, n in enumerate(fed):
            tokens[slot, :n] = seqs[slot, :n]
        out = driver.step(tokens, fed=fed)
        stats = driver.moe_stats(driver.moe_stats_begin())
        outs.append(out.asnumpy())
        assert driver.last_program_rows == 24
        assert stats["moe.layer_steps"] == layers
        assert stats["moe.assignments"] == layers * k * 16
        assert 0 < stats["moe.held_assignments"] < stats["moe.assignments"]
        assert stats["moe.experts_touched"] <= layers * 4
    assert np.array_equal(outs[0][[0, 2]], outs[1][[0, 2]])
    # the whole-window program over the same rows: pads inside every
    # slot's S rows, the same counts
    blocks.run(driver, seqs, [])
    driver._packed_hidden = driver._packed.pop(WINDOW)
    try:
        driver.step(tokens, fed=fed)
        assert driver.last_program_rows == SLOTS * WINDOW
        stats = driver.moe_stats(driver.moe_stats_begin())
        assert stats["moe.assignments"] == layers * k * 16
    finally:
        driver._packed[WINDOW] = driver._packed_hidden
        del driver._packed_hidden
    blocks.reset(driver)


def test_the_ops_declare_their_sizes_and_their_counts(driver):
    assert driver.state_bytes["recurrent"] == 2 * SLOTS * 12 * 8 * 16 * 4
    # three K/V heads of 8, a row each: not paired
    assert driver.state_bytes["rows"] == 2 * SLOTS * 3 * CAPACITY * 8 * 4
    assert driver.read_counts["moe.held_assignments"] == (
        "moe.held_assignments", "moe_held")
    assert driver.read_counts["moe.experts_touched"][1] == "moe_touched"
    sym = blocks.symbol(BLOCK, 1)
    nodes = [n for n in sym._topo_nodes() if n.op == "MoEFFN"]
    assert len(nodes) == 3
    for n in nodes:
        attrs = get_op("MoEFFN").normalize_attrs(dict(n.attrs))
        assert (attrs["num_experts"], attrs["top_k"], attrs["num_hidden"],
                attrs["held_first"], attrs["held_count"],
                attrs["shared_hidden"], attrs["norm_topk"]) \
            == (8, 3, 16, 0, 4, 24, True)
        assert str(attrs.get("scoring", "softmax")) == "softmax"
    args = sym.list_arguments()
    assert "lm_l0_moe_shared_gate_weight" in args
    assert not [a for a in args if "ffn_gate_up" in a or "router_bias" in a]


# ---------------------------------------------------------- the layer alone
def _layer_inputs(E=8, D=32, F=16, Fs=24, T=40, seed=4):
    rs = np.random.RandomState(seed)
    f = lambda *s: np.asarray(rs.randn(*s) * 0.3, np.float32)  # noqa: E731
    params = {"p_moe_router_weight": f(E, D),
              "p_moe_gate_weight": f(E, D, F), "p_moe_up_weight": f(E, D, F),
              "p_moe_down_weight": f(E, F, D),
              "p_moe_shared_gate_weight": f(D, Fs),
              "p_moe_shared_up_weight": f(D, Fs),
              "p_moe_shared_down_weight": f(Fs, D)}
    return params, f(T, D)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
def test_the_two_shares_add_up_to_the_uncut_layer(variant):
    """Two chips' shares of a small layer (8 experts, 3 a token;
    experts 0..4 and 4..8, the shared feed-forward counted once)
    through the program's ``MoEFFN`` add up to the uncut layer as the
    reference computes it, each share equals the reference's own share,
    every chip routes alike, and the gates keep their normalisation
    over the three chosen."""
    E, D, F, Fs, T, k = 8, 32, 16, 24, 40, 3
    params, x = _layer_inputs(E, D, F, Fs, T)
    cfg = {"num_local_experts": E, "num_experts_per_tok": k}
    with jax.default_matmul_precision("highest"):
        whole, shared, chosen = ref.expert_layer(
            jnp.asarray(x), "p", params, cfg, lambda a: a, held=(0, E))
    op = get_op("MoEFFN")
    total = np.zeros((T, D), np.float32)
    landed = 0
    for first in (0, E // 2):
        attrs = op.normalize_attrs(dict(
            num_experts=E, num_hidden=F, top_k=k, norm_topk=True,
            held_first=first, held_count=E // 2, shared_hidden=Fs,
            step_len=1))
        assert op.input_names(attrs) == [
            "data", "fed", "router_weight", "gate_weight", "up_weight",
            "down_weight", "shared_gate_weight", "shared_up_weight",
            "shared_down_weight"]
        ins = [jnp.asarray(x), jnp.ones((T,), jnp.int32),
               params["p_moe_router_weight"]] \
            + [jnp.asarray(params[f"p_moe_{m}_weight"][first:first + E // 2])
               for m in ("gate", "up", "down")] \
            + [params[f"p_moe_shared_{m}_weight"]
               for m in ("gate", "up", "down")]
        (out, experts), (stats,) = op.variant_fn(variant)(
            attrs, ins, [jnp.zeros((5,), jnp.int32)], False, None)
        np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                      np.sort(np.asarray(chosen), -1))
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref.expert_layer(jnp.asarray(x), "p", {
                **params, **{f"p_moe_{m}_weight":
                             params[f"p_moe_{m}_weight"][first:first + E // 2]
                             for m in ("gate", "up", "down")}},
                cfg, lambda a: a, held=(first, E // 2))
        np.testing.assert_allclose(np.asarray(out) - np.asarray(shared),
                                   np.asarray(part), atol=2e-5, rtol=2e-5)
        total += np.asarray(out) - np.asarray(shared)
        stats = np.asarray(stats).tolist()
        assert stats[:2] == [1, T * k] and stats[4] == int(
            ((np.asarray(chosen) >= first)
             & (np.asarray(chosen) < first + E // 2)).sum())
        landed += stats[4]
    assert landed == T * k                   # every assignment, once
    np.testing.assert_allclose(total, np.asarray(whole), atol=5e-5, rtol=5e-5)
    assert np.max(np.abs(np.asarray(whole))) > 0.1


def test_the_published_router_is_norm_topk_over_all():
    """The published router - the top-k of the LOGITS, a softmax over
    those k - and the program's - a softmax over all experts, its k
    largest, renormalised (``moe_route(norm_topk=True)``) - choose the
    same experts and weigh them alike; without the renormalisation
    (``gates_raw``, a control of the chip's comparison) they do not."""
    rs = np.random.RandomState(7)
    E, D, T, k = 72, 64, 200, 10
    x = jnp.asarray(rs.randn(T, D).astype(np.float32))
    router = jnp.asarray((rs.randn(E, D) * 0.2).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        weights, experts = moe.moe_route(x, router, k, True)
        chosen, dense = ref.route(x @ router.T, k)
        _, raw = ref.route(x @ router.T, k, renorm=False)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(chosen))
    got = np.zeros((T, E), np.float32)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    np.testing.assert_allclose(got, np.asarray(dense), atol=1e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dense).sum(-1), 1.0, atol=1e-5)
    assert np.asarray(raw).sum(-1).max() < 0.9
    # ten weights a token, the largest first, none equal
    w = np.asarray(weights)
    assert w.shape == (T, k) and (np.diff(w, axis=1) < 0).all()




def test_every_ring_record_carries_the_assignments_that_landed_here(engine):
    """Four requests of ragged lengths through the scheduler: every ring
    record carries the assignments that landed here (``moe_held``)
    beside the experts touched, and they add up to the counter."""
    prompts, grew, steps = blocks.counted(BLOCK, engine, (
        "moe.assignments", "moe.held_assignments", "moe.layer_steps",
        "ssm.rows"))
    fed_rows = sum(len(p) + 11 for p in prompts)
    assert grew["ssm.rows"] == 2 * fed_rows
    assert grew["moe.assignments"] == 3 * 3 * fed_rows       # no pad's
    assert 0 < grew["moe.held_assignments"] < grew["moe.assignments"]
    assert steps and all("moe_held" in r and "moe_touched" in r
                         and "ssm_touched" in r for r in steps)
    assert sum(r["moe_held"] for r in steps) == grew["moe.held_assignments"]
    assert sum(r["moe_layer_steps"] for r in steps) \
        == grew["moe.layer_steps"] == 3 * len(steps)
    windows = [r for r in steps if r["window"] > 1]
    assert windows and max(r["moe_held"] for r in windows) \
        > max(r["moe_held"] for r in steps if r["window"] == 1)
    assert mx.telemetry.get_metric("serve.decode.moe.held_assignments",
                                   model=engine.name).value > 0
