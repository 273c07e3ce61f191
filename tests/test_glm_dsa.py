"""GLM-5.2's block behind the serving path (ops/mla.py, the share of
ops/moe.py, block="glm_dsa" of models/transformer.py, the selection
reads of BatchedKVCacheDecoder and serve/decode.py). What every served
block does is ``tests/decode_block_suite.py``'s, over the row
``glm_dsa`` of ``tests/decode_blocks.py`` against the plain reference
chipbench/reference/glm_dsa.py: ``index_topk`` 16 and contexts of some
80 positions, so that the selection drops most keys; three layers
(dense + full, sparse + full, sparse + shared) holding experts 4-7 of
16. Sixteen index heads: with four, one key in sixteen scores exactly 0
(every head's dot product negative under the relu), the 16th largest is
often one of several zeros, and the two sides then differ by their rule
for ties (the served path keeps all, the reference's sort the lowest
positions). Below that the block's own: the selection that matters,
both pools' cursors, what the selection reads, the two ops, a shared
layer, bfloat16 serving against the dense control."""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import mla
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

import mla_window_cases  # noqa: E402

BLOCK = "glm_dsa"
TOL = blocks.TOL[BLOCK]
_W = (WINDOW, [WINDOW] * SLOTS)
_IDLE = [0] * (SLOTS - 3)


def test_the_selection_matters_at_these_positions(driver):
    """Four windows and sixteen S = 1 steps, 80 positions, of which a
    query attends 16: the served logits are the reference's, and the
    reference without the selection is not correct."""
    seqs = blocks.seqs(BLOCK, 80)
    got, at, _ = blocks.run(driver, seqs, [_W] * 4 + [(1, [1] * SLOTS)] * 16)
    assert list(at) == [80] * SLOTS
    want = blocks.reference(BLOCK, seqs)
    assert np.max(np.abs(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    dense = blocks.reference(BLOCK, seqs, select=False)
    assert np.max(np.abs(dense[:, 40:] - want[:, 40:])) > 100 * TOL


def test_selection_reads_are_counted_from_the_cursors(driver):
    blocks.run(driver, blocks.seqs(BLOCK, 40),
               [(WINDOW, [16, 16, 8] + _IDLE)] * 2)
    driver.step(np.zeros((SLOTS, 1), np.int32), fed=[1, 0, 1] + _IDLE)
    # slots at 32, 32, 16 fed 1, 0, 1: last queries see 33 and 17 keys
    layers, indexed, topk = 3, 2, 16
    assert driver.last_reads == {
        "dsa.layer_steps": layers, "dsa.live_rows": layers * (33 + 17),
        "dsa.selected_rows": layers * 2 * topk,
        "dsa.scored_rows": indexed * (33 + 17)}
    assert driver.read_counts["dsa.selected_rows"] == (
        "dsa.selected_rows", "dsa_selected")
    assert len(driver._state["rows"]) == 5      # 3 latent + 2 index pools


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
    symbol = blocks.symbol(BLOCK, 4)
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
    import ml_dtypes
    params = {k: v.astype(ml_dtypes.bfloat16)
              for k, v in blocks.params(BLOCK).items()}
    with blocks.tier("pallas"):
        drv = blocks.driver(BLOCK, packed=False, slots=3, arg_params=params,
                            dtype="bfloat16")
        exe = drv._mod._exec_group.executor
        assert str(exe.aux_dict["lm_l0_attn_latent"].dtype) == "bfloat16"
        assert str(exe.aux_dict["lm_l1_idx_index_k"].dtype) == "bfloat16"
        seqs = blocks.seqs(BLOCK, 72, seed=6, slots=3)
        got, _, _ = blocks.run(drv, seqs, [(WINDOW, [WINDOW] * 3)] * 4
                               + [(1, [1] * 3)] * 8)
        want = blocks.reference(BLOCK, seqs, params)
        dense = blocks.reference(BLOCK, seqs, params, select=False)
    # 16 keys of some 50: a key swapped at the threshold by bfloat16's
    # rounding moves a sixteenth of a query's attention, so the worst
    # position says nothing here (at 2,048 keys it does: the chip's
    # comparison); the median position is rounding alone
    err = np.median(np.max(np.abs(got[:, 32:] - want[:, 32:]), axis=-1))
    gap = np.median(np.max(np.abs(dense[:, 32:] - want[:, 32:]), axis=-1))
    print("bfloat16 median err", err, "dense control", gap)
    assert err < 0.3 < gap, (err, gap)


# -------------------------------------------------- the engine's contract
def test_the_selections_counters_move_through_the_scheduler(engine):
    """Requests of ragged lengths through the scheduler over the
    suite's engine: the selection's counters move, a share of the
    assignments lands here, and no ``attention_decode`` layer counts."""
    from mxnet_tpu import telemetry
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW, prefix_store=None)
    rng = np.random.default_rng(9)
    blocks.served(sched, [rng.integers(0, 48, n).tolist()
                          for n in (21, 9, 34)], 6)
    counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                if isinstance(m, telemetry.Counter)
                and ("model", engine.name) in m.labels}
    assert counters["serve.decode.dsa.selected_rows"] \
        < counters["serve.decode.dsa.live_rows"]
    assert counters["serve.decode.dsa.scored_rows"] * 3 \
        == counters["serve.decode.dsa.live_rows"] * 2
    assert 0 < counters["serve.decode.moe.held_assignments"] \
        < counters["serve.decode.moe.assignments"]
    assert counters["serve.decode.state.donated_bytes"] > 0
    # no attention_decode layer in this graph: its counters do not exist
    assert not [k for k in counters if k.startswith("serve.decode.attn.")]
