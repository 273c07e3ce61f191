"""Granite 4.0-H's block WITH routed experts (``block="granite_hybrid"``
with ``num_local_experts`` > 0, ISSUE 54: Granite 4.0-H Small) on the
CPU at tiny sizes, float32: the routed layer - softmax over the chosen
logits, a share of the experts held, a shared feed-forward beside them -
through the slot-pooled driver, the engine, the scheduler and
``serve_decoder`` against the benchmark's plain reference
(``chipbench/reference/granite_moe_hybrid.py``): prefill in ragged
packed windows, then S = 1 steps through the state; riders beside a
prefill; a slot reused; the two shares of a layer adding up to the
uncut layer; the published router against ``norm_topk`` over all; a
pad's choices landing nowhere. Three layers (mamba, attention, mamba)
of width 48: 12 Mamba heads of 8 with a state of 16, a chunk of 8 under
a window of 16, 6 query heads on 3 K/V heads of 8 that are NOT paired
(an odd number; the published 8 heads of 128 fill their lanes alone), 8
experts of 16, 3 a token, 4 held, a shared feed-forward of 24."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the driver's helpers are Micro's tests' (the same three slots, window
# of 16 and vocabulary of 96): a module bound over ``data`` and ``fed``,
# a schedule of dispatches fed to a driver, what it handed back
from test_granite_hybrid import (  # noqa: E402
    CAPACITY, SLOTS, WINDOW, _bound, _err, _full, _ones, _run)
from chipbench.archs import granite_moe_hybrid as arch  # noqa: E402
from chipbench.reference import granite_moe_hybrid as ref  # noqa: E402

CFG = {"vocab_size": 96, "hidden_size": 48, "num_attention_heads": 6,
       "num_key_value_heads": 3, "num_hidden_layers": 3,
       "layer_types": ["mamba", "attention", "mamba", "mamba"],
       "layers_run": [0, 1, 2],
       "mamba_n_heads": 12, "mamba_d_head": 8, "mamba_d_state": 16,
       "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
       "mamba_chunk_size": 8, "mamba_conv_bias": True,
       "mamba_proj_bias": False, "shared_intermediate_size": 24,
       "num_local_experts": 8, "num_experts_per_tok": 3,
       "intermediate_size": 16, "position_embedding_type": "nope",
       "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
       "attention_multiplier": 0.125, "logits_scaling": 8.0,
       "rms_norm_eps": 1e-5, "hidden_act": "silu", "attention_bias": False,
       "tie_word_embeddings": True, "num_experts_held": 4, "held_first": 0,
       "capacity": 128}
#: float32 served against the float32 reference through 3 layers, on
#: logits of magnitude about 1 (measured here: 1e-6 to 3e-5; the chunked
#: form sums in another order than the recurrence, the grouped matmuls
#: in another than one expert at a time)
TOL = 2e-4


def _symbol(step_len, cfg=CFG):
    return arch.decode_symbol(cfg, step_len)


def _params(cfg=CFG, seed=5):
    """Matrices of deviation 0.25 (the router's too: logits a few
    tenths apart, no two equal), gains about 1; the mixer's own as
    Mamba-2 draws them."""
    symbol = _symbol(1, cfg)
    shapes, _, _ = symbol.infer_shape(data=(SLOTS, 1), fed=(SLOTS,))
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(symbol.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        draw = rng.standard_normal(shape)
        if name.endswith("_gamma"):
            draw = 1.0 + 0.3 * draw
        elif name.endswith("_A_log"):
            draw = np.log(rng.uniform(1, 16, shape))
        elif name.endswith("_dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            draw = dt + np.log(-np.expm1(-dt))
        elif name.endswith("_mamba_D"):
            draw = np.ones(shape)
        else:
            draw = 0.25 * draw
        out[name] = draw.astype(np.float32)
    return out


PARAMS = _params()


def _driver(cfg=CFG, params=PARAMS):
    base = _bound(_symbol(1, cfg), 1, params=params)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
    packed, budget = tfm.packed_window(_symbol(WINDOW, cfg), SLOTS)
    assert budget == 24
    drv.add_window(WINDOW, _bound(_symbol(WINDOW, cfg), WINDOW, shared=base),
                   packed=(_bound(packed, WINDOW, shared=base), budget))
    return drv


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request):
    """A three-slot pool with its S = 16 window program, whole and
    packed (24 rows), under one kernel tier (``ssm_update``,
    ``ssm_scan``, the grouped expert matmuls and the attention kernels
    in interpret mode)."""
    old = os.environ.get("MXNET_KERNEL_TIER")
    os.environ["MXNET_KERNEL_TIER"] = request.param
    kernel_tier.clear()
    yield _driver()
    if old is None:
        os.environ.pop("MXNET_KERNEL_TIER", None)
    else:
        os.environ["MXNET_KERNEL_TIER"] = old
    kernel_tier.clear()


def _reference(seqs, cfg=CFG, params=PARAMS, **kw):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, cfg, **kw))
    return np.asarray(fwd(params, jnp.asarray(seqs)))


SCHEDULES = {
    # whole windows (48 rows: the whole-window program, what the chip's
    # check_reference runs), then S = 1 through the state
    "whole_windows_then_decode": _full(3) + _ones(6),
    # the packed program (at most 24 rows): a prefill and riders beside
    # it, a part of a chunk beside another, a ragged last chunk, a slot
    # fed nothing
    "ragged_packed_windows_with_riders": [
        (WINDOW, [16, 1, 1]), (WINDOW, [16, 1, 1]), (WINDOW, [5, 1, 13]),
        (WINDOW, [1, 11, 9]), (WINDOW, [1, 16, 0]), (WINDOW, [1, 3, 1])]
    + _ones(4),
    # two chunks in one dispatch with a ragged second (13 = 8 + 5)
    "two_chunks_and_a_ragged_last": [
        (WINDOW, [13, 16, 9]), (WINDOW, [16, 7, 12]), (WINDOW, [2, 1, 3])]
    + _ones(3, fed=(1, 0, 1)) + [(WINDOW, [9, 2, 1])],
    # decode first (the state starts by steps), then windows over it
    "decode_then_windows": _ones(5) + [(WINDOW, [16, 1, 1]),
                                       (WINDOW, [10, 12, 2])] + _ones(2),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_prefill_and_decode_match_the_reference_full_forward(driver, case):
    """Every fed position's logits against the plain reference's full
    forward (the recurrence step by step, the published router, one
    expert at a time, the same share), within the float32 bound - which
    is inside the architecture's ``LOGIT_TOL``."""
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    got, at, rows = _run(driver, seqs, SCHEDULES[case])
    want = _reference(seqs)
    for slot in range(SLOTS):
        err = _err(got[slot, :at[slot]], want[slot, :at[slot]])
        assert err <= TOL <= arch.LOGIT_TOL, (case, slot, err)
    if case == "ragged_packed_windows_with_riders":
        assert rows[:6] == [24] * 6      # the packed program ran them
    if case == "whole_windows_then_decode":
        assert rows[:3] == [SLOTS * WINDOW] * 3


def test_the_held_share_changes_the_result_and_the_reference_follows():
    """The share is not a no-op: with every expert held the logits
    differ from the half's by far more than the bound, and the
    reference given the same share agrees with each."""
    rng = np.random.default_rng(21)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 40)).astype(np.int32)
    whole_cfg = dict(CFG, num_experts_held=8)
    rs = np.random.default_rng(5)
    whole_params = dict(PARAMS)
    for name, arr in PARAMS.items():
        if name.endswith(("_moe_gate_weight", "_moe_up_weight",
                          "_moe_down_weight")):
            more = 0.25 * rs.standard_normal(arr.shape).astype(np.float32)
            whole_params[name] = np.concatenate([arr, more])
    half, _, _ = _run(_driver(), seqs, _full(2) + _ones(4))
    whole, at, _ = _run(_driver(whole_cfg, whole_params), seqs,
                        _full(2) + _ones(4))
    want_half = _reference(seqs)
    want_whole = _reference(seqs, whole_cfg, whole_params)
    for slot in range(SLOTS):
        n = at[slot]
        assert _err(half[slot, :n], want_half[slot, :n]) <= TOL
        assert _err(whole[slot, :n], want_whole[slot, :n]) <= TOL
    assert np.abs(want_whole[:, :36] - want_half[:, :36]).max() > 100 * TOL


def test_a_slot_left_and_joined_again_reads_a_clean_state(driver):
    """A slot that carried 60 tokens of another sequence serves a new
    one as a fresh pool does, through a window and through S = 1 steps
    first."""
    rng = np.random.default_rng(4)
    old = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    _run(driver, old, _full(3) + _ones(6))
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    got, at, _ = _run(driver, seqs, [(WINDOW, [16, 1, 1])] + _ones(3)
                      + _full(1))                        # leaves, joins
    want = _reference(seqs)
    for slot in range(SLOTS):
        assert _err(got[slot, :at[slot]], want[slot, :at[slot]]) <= TOL


def test_a_pads_choices_land_nowhere(driver):
    """A window's pads are routed nowhere: the layers count the real
    rows' assignments alone (``moe.assignments`` = layers x top_k x real
    rows, whatever junk rides the pads), the assignments that landed
    here are fewer, and a slot's logits do not move when the pads
    beside it change."""
    rng = np.random.default_rng(9)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    k, layers = CFG["num_experts_per_tok"], CFG["num_hidden_layers"]
    outs = []
    for junk in (7, 31):
        for slot in range(SLOTS):
            if driver.active[slot]:
                driver.leave(slot)
            driver.join(slot)
        tokens = np.full((SLOTS, WINDOW), junk, np.int32)
        fed = [11, 0, 5]
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
    for slot in range(SLOTS):
        driver.leave(slot)
        driver.join(slot)
    driver._packed_hidden = driver._packed.pop(WINDOW)
    try:
        driver.step(tokens, fed=fed)
        assert driver.last_program_rows == SLOTS * WINDOW
        stats = driver.moe_stats(driver.moe_stats_begin())
        assert stats["moe.assignments"] == layers * k * 16
    finally:
        driver._packed[WINDOW] = driver._packed_hidden
        del driver._packed_hidden
    driver.active[:] = False
    driver.rewind_many(list(range(SLOTS)), [0] * SLOTS)


def test_the_ops_declare_their_state_and_their_counts(driver):
    assert sorted(driver._state) == ["conv", "cursor", "recurrent", "rows"]
    assert driver._carried == ["conv", "recurrent"]
    assert driver.state_bytes["recurrent"] == 2 * SLOTS * 12 * 8 * 16 * 4
    # three K/V heads of 8, a row each: not paired
    assert driver.state_bytes["rows"] == 2 * SLOTS * 3 * CAPACITY * 8 * 4
    assert driver.read_counts["moe.held_assignments"] == (
        "moe.held_assignments", "moe_held")
    assert driver.read_counts["moe.experts_touched"][1] == "moe_touched"
    sym = _symbol(1)
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


# --------------------------------------------------- engine and scheduler
def _gen(step_len):
    return _symbol(step_len)


@pytest.fixture(scope="module")
def engine():
    return mx.serve.DecodeEngine(
        "tiny-granite-small", _gen(1), PARAMS, capacity=CAPACITY,
        ladder=[2, 4], symbol_gen=_gen, window_lens=[WINDOW])


def _served(sched, prompts, max_new):
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    sched.pump()
    return [[int(t) for t in h.result(timeout=5)] for h in handles]


def test_mixed_prefill_and_decode_equals_one_request_at_a_time(engine):
    """Four requests of ragged lengths admitted together through the
    scheduler (packed windows with riders, a rung switch, run-ahead):
    the greedy tokens of each request served alone; every ring record
    carries the assignments that landed here (``moe_held``) beside the
    experts touched, and they add up to the counter."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import flightrec
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG["vocab_size"], n).tolist()
               for n in (45, 9, 30, 70)]
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW)
    alone = [_served(sched, [p], 12)[0] for p in prompts]
    flightrec.clear()
    keys = ("moe.assignments", "moe.held_assignments", "moe.layer_steps",
            "ssm.rows")
    before = {k: sched._counter(k).value for k in keys}
    mixed = _served(sched, prompts, 12)
    assert mixed == alone and all(len(t) == 12 for t in mixed)
    grew = {k: sched._counter(k).value - v for k, v in before.items()}
    fed_rows = sum(len(p) + 11 for p in prompts)
    assert grew["ssm.rows"] == 2 * fed_rows
    assert grew["moe.assignments"] == 3 * 3 * fed_rows       # no pad's
    assert 0 < grew["moe.held_assignments"] < grew["moe.assignments"]
    steps = [r for r in flightrec.get_records()
             if r.get("kind") == "serve.decode.step"
             and r.get("model") == "tiny-granite-small"]
    assert steps and all("moe_held" in r and "moe_touched" in r
                         and "ssm_touched" in r for r in steps)
    assert sum(r["moe_held"] for r in steps) == grew["moe.held_assignments"]
    assert sum(r["moe_layer_steps"] for r in steps) \
        == grew["moe.layer_steps"] == 3 * len(steps)
    windows = [r for r in steps if r["window"] > 1]
    assert windows and max(r["moe_held"] for r in windows) \
        > max(r["moe_held"] for r in steps if r["window"] == 1)
    assert sched.stats()["compiles_since_warmup"] == 0
    assert sched.stats()["runahead"]["launched"] > 0
    assert telemetry.get_metric("serve.decode.moe.held_assignments",
                                model="tiny-granite-small").value > 0


def test_serve_decoder_serves_the_block_with_no_side_script():
    sched = mx.serve.serve_decoder(
        _gen(1), PARAMS, name="tiny-granite-small-front", capacity=CAPACITY,
        ladder=[1, 2], symbol_gen=_gen, prefill_chunk=WINDOW, start=False,
        clock=mx.serve.FakeClock())
    assert sched.prefix_store is None and sched.engine.feeds
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    tokens = _served(sched, [prompt.tolist()], 6)[0]
    seq = np.concatenate([prompt, tokens[:-1]])[None].astype(np.int32)
    want = _reference(seq)[0]
    assert tokens == np.argmax(want[40:], axis=-1).tolist()
