"""GLM-5.2's block behind the serving path (ops/mla.py, the share of
ops/moe.py, block="glm_dsa" of models/transformer.py, the selection
reads of BatchedKVCacheDecoder and serve/decode.py) against the plain
reference chipbench/reference/glm_dsa.py, at small widths on the CPU:
``index_topk`` 16 and contexts of some 80 positions, so that the
selection drops most keys; three layers (dense + full, sparse + full,
sparse + shared) holding experts 4-7 of 16. Sixteen index heads: with
four, one key in sixteen scores exactly 0 (every head's dot product
negative under the relu), the 16th largest is often one of several
zeros, and the two sides then differ by their rule for ties (the served
path keeps all, the reference's sort the lowest positions)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import mla
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import glm_dsa as ref  # noqa: E402
import mla_window_cases  # noqa: E402
# the quick cases of the benchmark's own tests of the architecture file
# run here as they stand (its CPU rehearsals stay by hand)
from chipbench.tests.test_glm_dsa import (  # noqa: E402,F401
    test_both_controls_are_further_than_the_emulation,
    test_costs_against_a_count_by_hand)

CFG = {"vocab_size": 48, "hidden_size": 64, "num_attention_heads": 4,
       "num_hidden_layers": 3, "q_lora_rank": 48, "kv_lora_rank": 64,
       "qk_nope_head_dim": 24, "qk_rope_head_dim": 16, "v_head_dim": 32,
       "index_n_heads": 16, "index_head_dim": 32, "index_topk": 16,
       "indexer_types": ["full", "full", "shared"],
       "first_k_dense_replace": 1, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 16,
       "num_experts_per_tok": 4, "n_shared_experts": 1,
       "routed_scaling_factor": 2.5, "norm_topk_prob": True,
       "n_routed_experts_held": 4, "held_first": 4,
       "rope_parameters": {"rope_theta": 8000000}, "rms_norm_eps": 1e-5}
CAPACITY, WINDOW, SLOTS = 128, 16, 3            # WINDOW: the S > 1 program
#: float32 served against the float32 reference through 3 layers, on
#: logits of magnitude about 2 (measured here: 2e-6 to 6e-6)
TOL = 5e-5


def _glm(held=None):
    glm = {k: CFG[k] for k in tfm.GLM_KEYS}
    glm["held"] = held or (CFG["held_first"], CFG["n_routed_experts_held"])
    return glm


def _symbol(step_len, capacity=CAPACITY):
    return tfm.get_decode_symbol(
        vocab_size=CFG["vocab_size"], d_model=CFG["hidden_size"],
        n_layer=CFG["num_hidden_layers"],
        n_head=CFG["num_attention_heads"], pos_embed="rotary",
        rope_base=8e6, capacity=capacity, step_len=step_len, per_slot=True,
        block="glm_dsa", rms_eps=CFG["rms_norm_eps"], tie_head=False,
        embed_scale=False, glm=_glm())


def _params(seed=5):
    symbol = _symbol(1)
    shapes, _, _ = symbol.infer_shape(data=(SLOTS, 1), fed=(SLOTS,))
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(symbol.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        draw = rng.standard_normal(shape)
        if name.endswith(("_gamma", "_kv_norm_weight")):
            draw = 1.0 + 0.3 * draw
        out[name] = (draw if "gamma" in name or "norm_weight" in name
                     else 0.25 * draw).astype(np.float32)
    return out


PARAMS = _params()


def _bound(step_len, shared=None, slots=SLOTS, params=None, dtype=None):
    mod = mx.mod.Module(_symbol(step_len), data_names=("data", "fed"),
                        label_names=[], compute_dtype=dtype)
    mod.bind([mx.io.DataDesc("data", (slots, step_len), np.int32),
              mx.io.DataDesc("fed", (slots,), np.int32)],
             None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None,
                        arg_params=dict(params or PARAMS), aux_params={},
                        allow_missing=True)
    return mod


def _tier(name):
    old = os.environ.get("MXNET_KERNEL_TIER")
    os.environ["MXNET_KERNEL_TIER"] = name
    kernel_tier.clear()
    return old


def _restore(old):
    if old is None:
        os.environ.pop("MXNET_KERNEL_TIER", None)
    else:
        os.environ["MXNET_KERNEL_TIER"] = old
    kernel_tier.clear()


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request):
    """A three-slot pool with its S = 16 window program under one
    kernel tier (the Pallas kernels in interpret mode)."""
    old = _tier(request.param)
    base = _bound(1)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
    drv.add_window(WINDOW, _bound(WINDOW, shared=base))
    yield drv
    _restore(old)


def _reference(seqs, **kw):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, CFG, **kw))
    return np.asarray(fwd(PARAMS, jnp.asarray(seqs)))


def _reference_one(seq):
    return np.asarray(ref.forward(PARAMS, jnp.asarray(seq)[None], CFG))[0]


def _run(drv, seqs, schedule, start=None):
    """Feed ``seqs`` (slots, T) through ``schedule``, a list of (S, fed
    counts a slot): the logits of every fed position, (slots, T, V)."""
    if start is None:
        for slot in range(drv.slots):
            if drv.active[slot]:
                drv.leave(slot)
            drv.join(slot)
        start = [0] * drv.slots
    got = np.zeros(seqs.shape + (CFG["vocab_size"],), np.float32)
    at = np.asarray(start)
    for S, fed in schedule:
        tokens = np.full((drv.slots, S), 7, np.int32)
        for slot, n in enumerate(fed):
            tokens[slot, :n] = seqs[slot, at[slot]:at[slot] + n]
        out = drv.step(tokens, fed=fed).asnumpy()
        for slot, n in enumerate(fed):
            got[slot, at[slot]:at[slot] + n] = out[slot, :n]
        at = at + np.asarray(fed)
        assert list(drv.pos) == list(at)
    return got, at


def _seqs(T, seed=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (SLOTS, T)).astype(np.int32)


def test_prefill_in_windows_then_decode_equals_the_reference(driver):
    """Four windows and sixteen S = 1 steps, 80 positions, of which a
    query attends 16: the cache, both lowerings of the selection and of
    the attention, the share of the experts."""
    seqs = _seqs(80)
    got, at = _run(driver, seqs, [(WINDOW, [WINDOW] * SLOTS)] * 4
                   + [(1, [1] * SLOTS)] * 16)
    assert list(at) == [80] * SLOTS
    want = _reference(seqs)
    assert np.max(np.abs(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # the selection matters at these positions: without it, not correct
    dense = _reference(seqs, select=False)
    assert np.max(np.abs(dense[:, 40:] - want[:, 40:])) > 100 * TOL


def test_ragged_slots_and_fed_keep_both_pools_right(driver):
    """Slots at their own lengths, windows that feed 16, 5 and 0 real
    tokens, a slot that decodes while another prefills: every fed
    position equals the reference, the cursors of both pools move by
    ``fed`` alone."""
    seqs = _seqs(60, seed=2)
    schedule = [(WINDOW, [16, 5, 0]), (1, [1, 1, 1]), (WINDOW, [16, 16, 9]),
                (WINDOW, [1, 16, 16]), (1, [1, 0, 1]), (WINDOW, [7, 3, 16])]
    got, at = _run(driver, seqs, schedule)
    want = _reference(seqs)
    for slot in range(SLOTS):
        np.testing.assert_allclose(got[slot, :at[slot]],
                                   want[slot, :at[slot]], atol=TOL, rtol=TOL)
    exe = driver._mod._exec_group.executor
    for name in driver._state["cursor"]:
        assert list(exe.aux_dict[name].asnumpy().ravel()) == list(at), name
    assert sorted(driver._state) == ["cursor", "rows"]
    assert len(driver._state["rows"]) == 5      # 3 latent + 2 index pools


def test_leave_join_and_rewind_reuse_a_slot(driver):
    """A slot that leaves and joins again attends nothing of its old
    rows; a positional rewind (both pools are a row per position) puts
    a slot back where the reference is."""
    seqs = _seqs(48, seed=3)
    _run(driver, _seqs(48, seed=4), [(WINDOW, [WINDOW] * SLOTS)] * 3)
    got, at = _run(driver, seqs, [(WINDOW, [WINDOW] * SLOTS)] * 2)
    driver.rewind_many([0, 2], [20, 32])
    more, at = _run(driver, seqs, [(WINDOW, [16, 0, 16])],
                    start=[20, 32, 32])
    want = _reference(seqs)
    np.testing.assert_allclose(got[:, :32], want[:, :32], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(more[0, 20:36], want[0, 20:36], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(more[2, 32:48], want[2, 32:48], atol=TOL,
                               rtol=TOL)


def test_selection_reads_are_counted_from_the_cursors(driver):
    _run(driver, _seqs(40), [(WINDOW, [16, 16, 8])] * 2)
    driver.step(np.zeros((SLOTS, 1), np.int32), fed=[1, 0, 1])
    # slots at 32, 32, 16 fed 1, 0, 1: last queries see 33 and 17 keys
    layers, indexed, topk = 3, 2, 16
    assert driver.last_reads == {
        "dsa.layer_steps": layers, "dsa.live_rows": layers * (33 + 17),
        "dsa.selected_rows": layers * 2 * topk,
        "dsa.scored_rows": indexed * (33 + 17)}
    assert driver.read_counts["dsa.selected_rows"] == (
        "dsa.selected_rows", "dsa_selected")
    assert driver.positional and driver.feeds


# ----------------------------------------------------------- the two ops
def _op_inputs(S, dtype, seed=0):
    rs = np.random.RandomState(seed)
    B, H, dn, dr, dv, rank = 2, 4, 24, 16, 32, 64
    Hi, d = 16, 32
    f = lambda *s: jnp.asarray(rs.randn(*s), dtype)      # noqa: E731
    fed = jnp.asarray([S, max(S - 1, 1)], jnp.int32)
    cur = jnp.asarray([[40], [7]], jnp.int32)
    idx = ([f(B, S, Hi * d), f(B, S, d), f(B, S, Hi), fed],
           [f(B, 1, CAPACITY, d), cur])
    att = ([f(B, S, H * (dn + dr)), f(B, S, rank + dr), None, fed,
            jnp.ones((rank,), dtype), f(H * (dn + dv), rank) * 0.2],
           [f(B, 1, CAPACITY, mla.latent_width(rank, dr)), cur])
    return idx, att


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S", [1, 16], ids=["decode", "window"])
def test_absorbed_kernels_equal_the_expanded_composition(S, dtype):
    """``dsa_index_select``: the kernels choose the composition's set,
    write its rows and move its cursor. ``mla_attention_decode``: the
    kernels - absorbed at S = 1, in the expanded widths in a window -
    equal the expanded composition (in bfloat16 within the rounding of
    q W_kb and of the latent sum, or of the softmax's weights)."""
    index, attend = get_op("dsa_index_select"), \
        get_op("mla_attention_decode")
    ia = index.normalize_attrs(dict(
        capacity=CAPACITY, n_heads=16, head_dim=32, rope_dim=16, topk=16,
        rope_base=8e6))
    aa = attend.normalize_attrs(dict(
        capacity=CAPACITY, n_heads=4, nope_dim=24, rope_dim=16, v_dim=32,
        kv_rank=64, rope_base=8e6))
    (i_in, i_aux), (a_in, a_aux) = _op_inputs(S, jnp.dtype(dtype))
    sel, aux = index.variant_fn("xla")(ia, i_in, i_aux, False, None)
    sel_k, aux_k = index.variant_fn("pallas")(ia, i_in, i_aux, False, None)
    assert sel[0].dtype == jnp.int8 and sel[0].shape == (2, S, CAPACITY)
    np.testing.assert_array_equal(np.asarray(sel[0]), np.asarray(sel_k[0]))
    for a, b in zip(aux, aux_k):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    kept = np.asarray(sel[0]).sum(-1)
    assert kept[0].max() == 16 and kept[1, 0] == 8      # t = 7: all 8
    assert list(np.asarray(aux[1]).ravel()) == [40 + S, 7 + max(S - 1, 1)]
    a_in[2] = sel[0]
    out, aux = attend.variant_fn("xla")(aa, a_in, a_aux, False, None)
    out_k, aux_k = attend.variant_fn("pallas")(aa, a_in, a_aux, False, None)
    tol = 1e-5 if dtype == "float32" else 0.04
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               np.asarray(out_k[0], np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(aux[0], np.float32),
                                  np.asarray(aux_k[0], np.float32))


@pytest.mark.parametrize("case", sorted(mla_window_cases.CASES))
def test_a_window_attends_a_slot_fed_one_row_as_the_s1_program_does(case):
    """Under a selection: a window whose slots are fed a whole window,
    one row, none and a ragged few (``tests/mla_window_cases.py``)
    equals the expanded form at every fed position; the row of a slot
    fed one - ``mla_attn_ride`` under row 0 of the selection - is the
    S = 1 dispatch's to the bit; a window in which every slot rides and
    one in which none does."""
    riding = mla_window_cases.check(case, selected=True, rope_base=8e6)
    assert len(riding) == {"mixed": 3, "all_riding": 6,
                           "none_riding": 0}[case]


@pytest.mark.parametrize("case", sorted(mla_window_cases.WINDOW_CASES))
def test_the_window_form_attends_in_the_expanded_widths(case):
    """Under a selection: ``mla_attn_window`` - a key block expanded
    once a head, every query block of the chunk scored against it under
    its rows of the mask - equals the expanded composition at every fed
    position (``mla_window_cases.WINDOW_CASES``): several query blocks
    against tiny key blocks, a slot fed 2 rows beside one fed all of
    them, dead slots around the live ones, GLM-5.2's unequal
    ``nope_dim`` and ``v_dim``."""
    fed, blocks, geometry = mla_window_cases.WINDOW_CASES[case]
    mla_window_cases.check_window(
        fed, True, blocks, dict(mla_window_cases._GEOMETRY, **geometry),
        rope_base=8e6)


def test_a_shared_layer_attends_the_set_its_full_layer_chose():
    """The graph hands layer 1's selection to layer 2: one
    ``dsa_index_select`` output feeds both ``mla_attention_decode``
    nodes, and layer 2 has no indexer parameters and no index pool."""
    symbol = _symbol(4)
    consumers = {}
    for node in symbol._topo_nodes():
        if not node.is_variable and node.op == "mla_attention_decode":
            consumers[node.name] = node.inputs[2][0].name
    assert consumers == {"lm_l0_attn": "lm_l0_idx", "lm_l1_attn": "lm_l1_idx",
                         "lm_l2_attn": "lm_l1_idx"}
    assert not [n for n in symbol.list_arguments() if n.startswith("lm_l2_idx")]
    assert "lm_l2_idx_index_k" not in symbol.list_auxiliary_states()
    # three attention layers under the selections of two indexers, each
    # of which says what a dispatch reads (``OpDef.state_reads``)
    declared = [(node.op, node.attrs.get("topk"), sorted(
        opdef.state_reads[1](node.attrs, CAPACITY, {"selection": {"topk": 16}})(
            np.zeros(1, np.int64), np.ones(1, np.int64))))
        for node, opdef, _cells in tfm._stateful_nodes(symbol)
        if node.op != "MoEFFN"]
    assert declared == [
        ("dsa_index_select", 16, ["dsa.scored_rows"]),
        ("mla_attention_decode", None,
         ["dsa.layer_steps", "dsa.live_rows", "dsa.selected_rows"])] * 2 \
        + [declared[-1]] and declared[-1][0] == "mla_attention_decode"
    assert not [c for _n, opdef, _c in tfm._stateful_nodes(
        tfm.get_decode_symbol(per_slot=True)) for c in opdef.state_reads[0]
        if c.startswith("dsa.")]


def test_bfloat16_serving_is_inside_a_bound_the_dense_control_is_not():
    """Parameters and both caches in bfloat16 (the Pallas lowering):
    the logits stay within bfloat16's rounding of the float32 reference
    through the cache, and the reference WITHOUT the selection is an
    order of magnitude further away."""
    old = _tier("pallas")
    try:
        import ml_dtypes
        params = {k: v.astype(ml_dtypes.bfloat16) for k, v in PARAMS.items()}
        base = _bound(1, params=params, dtype="bfloat16")
        drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
        drv.add_window(WINDOW, _bound(WINDOW, shared=base, dtype="bfloat16"))
        exe = base._exec_group.executor
        assert str(exe.aux_dict["lm_l0_attn_latent"].dtype) == "bfloat16"
        assert str(exe.aux_dict["lm_l1_idx_index_k"].dtype) == "bfloat16"
        seqs = _seqs(72, seed=6)
        got, _ = _run(drv, seqs, [(WINDOW, [WINDOW] * SLOTS)] * 4
                      + [(1, [1] * SLOTS)] * 8)
        fwd = jax.jit(lambda p, t, s: ref.forward(p, t, CFG, select=s),
                      static_argnums=2)
        want = np.asarray(fwd(params, jnp.asarray(seqs), True))
        dense = np.asarray(fwd(params, jnp.asarray(seqs), False))
    finally:
        _restore(old)
    # 16 keys of some 50: a key swapped at the threshold by bfloat16's
    # rounding moves a sixteenth of a query's attention, so the worst
    # position says nothing here (at 2,048 keys it does: the chip's
    # comparison); the median position is rounding alone
    err = np.median(np.max(np.abs(got[:, 32:] - want[:, 32:]), axis=-1))
    gap = np.median(np.max(np.abs(dense[:, 32:] - want[:, 32:]), axis=-1))
    print("bfloat16 median err", err, "dense control", gap)
    assert err < 0.3 < gap, (err, gap)


# -------------------------------------------------- the engine's contract
def test_engine_migrates_both_pools_across_rungs_and_counts_reads():
    """``serve_decoder`` over the block: ladder 1, 2, a window of 8;
    requests of ragged lengths grow the rung (``migrate`` copies every
    ``rows`` pool and cursor), their tokens equal greedy decoding of
    the reference, and the selection's counters move."""
    from mxnet_tpu import telemetry
    server = mx.serve.serve_decoder(
        _symbol(1), PARAMS, name="glm-tiny", capacity=CAPACITY,
        ladder=[1, 2], symbol_gen=_symbol, prefill_chunk=8, start=True)
    try:
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, CFG["vocab_size"], n).astype(np.int32)
                   for n in (21, 9, 34)]
        handles = [server.submit(p, max_new_tokens=6) for p in prompts]
        answers = [h.result(timeout=300) for h in handles]
        engine = server.engine if hasattr(server, "engine") \
            else server._engine
        assert engine.positional and engine.feeds
    finally:
        server.stop()
    for prompt, answer in zip(prompts, answers):
        # one forward over prompt + answer: position len(prompt) - 1 + i
        # predicts the i-th answered token
        seq = np.concatenate([prompt, np.asarray(answer, np.int32)])
        logits = _reference_one(seq)
        for i, tok in enumerate(answer):
            row = logits[len(prompt) - 1 + i]
            top = np.sort(row)[-2:]
            if top[1] - top[0] > 1e-3:            # no rounding-level tie
                assert int(np.argmax(row)) == tok
    counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                if isinstance(m, telemetry.Counter)
                and ("model", "glm-tiny") in m.labels}
    assert counters["serve.decode.dsa.selected_rows"] \
        < counters["serve.decode.dsa.live_rows"]
    assert counters["serve.decode.dsa.scored_rows"] * 3 \
        == counters["serve.decode.dsa.live_rows"] * 2
    assert 0 < counters["serve.decode.moe.held_assignments"] \
        < counters["serve.decode.moe.assignments"]
    assert counters["serve.decode.state.donated_bytes"] > 0
    # no attention_decode layer in this graph: its counters do not exist
    assert not [k for k in counters if k.startswith("serve.decode.attn.")]
