"""A.X-K1's block behind the serving path (``block="axk1"`` of
models/transformer.py: GLM-5.2's latent attention without an indexer,
under YaRN, and a sigmoid router that chooses inside groups). What every
served block does is ``tests/decode_block_suite.py``'s, over the row
``axk1`` of ``tests/decode_blocks.py`` against the plain reference
chipbench/reference/axk1.py: three layers (dense, sparse, sparse) of 24
experts in 4 groups, 8 a token inside 2 of them, 3 held from the 3rd;
YaRN of factor 8 over 16 original positions. Below that the block's
own: each part of YaRN, the graph without an indexer, the latent rows a
dispatch counts, the unselected kernel, the window form, YaRN at the
published values, the group-limited choice, the shares that add up, and
the reuse plane (a prefix joined at a common head, a rider counted)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import mla, moe
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serve.prefix import PrefixStore

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

import mla_window_cases  # noqa: E402
from chipbench.reference import axk1 as ref  # noqa: E402
import mla_window_cases  # noqa: E402

BLOCK = "axk1"
AXK1 = blocks.config(BLOCK)["axk1"]
YARN = AXK1["rope_scaling"]
VOCAB = blocks.config(BLOCK)["vocab_size"]
TOL = blocks.TOL[BLOCK]


# ------------------------------------------------- the block, end to end
def test_each_part_of_yarn_matters_past_the_original_positions(driver):
    """Four windows and sixteen S = 1 steps, 80 positions past YaRN's 16
    original ones: the served logits are the reference's, and the
    reference without the softmax scale or with the plain rotary is not
    correct at these positions."""
    seqs = blocks.seqs(BLOCK, 80)
    got, at, _ = blocks.run(driver, seqs, [(WINDOW, [WINDOW] * SLOTS)] * 4
                            + [(1, [1] * SLOTS)] * 16)
    assert list(at) == [80] * SLOTS
    want = blocks.reference(BLOCK, seqs)
    assert np.max(np.abs(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    # each part of YaRN matters at these positions: without it, not correct
    for control in ("no_scale", "plain"):
        other = blocks.reference(BLOCK, seqs, yarn=control)
        assert np.max(np.abs(other[:, 40:] - want[:, 40:])) > 100 * TOL


def test_the_graph_has_no_indexer_and_no_selection():
    """One block for both models: the same nodes as ``glm_dsa``'s but
    the indexer's, no ``selection`` input, no router bias; the driver
    finds no selection to mirror and counts the latent rows attended."""
    symbol = blocks.symbol(BLOCK, 4)
    nodes = [n for n in symbol._topo_nodes() if not n.is_variable]
    ops = [n.op for n in nodes]
    assert ops.count("mla_attention_decode") == 3
    assert ops.count("MoEFFN") == 2 and "dsa_index_select" not in ops
    for n in nodes:
        if n.op == "mla_attention_decode":
            assert [v.name.rsplit("_", 1)[-1] for v, _ in n.inputs[:3]] \
                == ["unfold", "unfold", "fed"]
            assert float(n.attrs["rope_factor"]) == 8.0
    args = symbol.list_arguments()
    assert not [a for a in args if "idx" in a or a.endswith("router_bias")]
    assert "lm_l1_moe_router_weight" in args
    # no layer of it counts under a selection
    assert not [count for node, opdef, _cells in tfm._stateful_nodes(symbol)
                if node.op == "mla_attention_decode"
                for count in opdef.state_reads[0](node.attrs)
                if count.startswith("dsa.")]
    with pytest.raises(mx.MXNetError, match="n_group"):
        tfm.get_decode_symbol(per_slot=True, block="axk1", axk1={})


def test_latent_rows_attended_are_counted_from_the_cursors(driver):
    idle = [0] * (SLOTS - 3)
    blocks.run(driver, blocks.seqs(BLOCK, 40),
               [(WINDOW, [16, 16, 8] + idle)] * 2)
    driver.step(np.zeros((SLOTS, 1), np.int32), fed=[1, 0, 1] + idle)
    # slots at 32, 32, 16 fed 1, 0, 1: last queries see 33 and 17 keys,
    # and at S = 1 the pairs of all fed queries are those keys
    layers = 3
    assert driver.last_reads == {
        "attn.live_rows": layers * (33 + 17),
        "attn.capacity_rows": SLOTS * layers * CAPACITY,
        "attn.attended_rows": layers * (33 + 17),
        "mla_attended": layers * (33 + 17), "mla_pairs": layers * (33 + 17)}
    assert driver.read_counts["mla_pairs"] == (None, "mla_pairs")
    assert len(driver._state["rows"]) == 3          # 3 latent pools, no index
    assert not [c for c in driver.read_counts if c.startswith("dsa.")]
    # a window that feeds 16, 0 and 5 rows at 33, 32 and 17: the last
    # queries see 49 and 22 keys; query t of a slot at p sees p + t + 1
    driver.step(np.zeros((SLOTS, WINDOW), np.int32), fed=[16, 0, 5] + idle)
    pairs = sum(33 + t + 1 for t in range(16)) \
        + sum(17 + t + 1 for t in range(5))
    assert (driver.last_reads["mla_attended"],
            driver.last_reads["mla_pairs"]) == (layers * (49 + 22),
                                                layers * pairs)
    # the row programs clamp what they cannot reach: the host refuses it
    with pytest.raises(mx.MXNetError, match="slot"):
        driver.capture_rows(SLOTS, 4)
    with pytest.raises(mx.MXNetError, match="rows of a capacity"):
        driver.capture_rows(0, CAPACITY + 1)


# ------------------------------------------------------------- the op
def _op_inputs(S, dtype, seed=0):
    rs = np.random.RandomState(seed)
    B, H, dn, dr, dv, rank = 2, 4, 24, 16, 16, 64
    f = lambda *s: jnp.asarray(rs.randn(*s), dtype)      # noqa: E731
    fed = jnp.asarray([S, max(S - 1, 1)], jnp.int32)
    cur = jnp.asarray([[40], [7]], jnp.int32)
    return ([f(B, S, H * (dn + dr)), f(B, S, rank + dr), fed,
             jnp.ones((rank,), dtype), f(H * (dn + dv), rank) * 0.2],
            [f(B, 1, CAPACITY, mla.latent_width(rank, dr)), cur])


_GEOMETRY = dict(capacity=CAPACITY, n_heads=4, nope_dim=24, rope_dim=16,
                 v_dim=16, kv_rank=64, rope_base=10000.0)
_YARN_ATTRS = dict(rope_factor=8.0, rope_original_positions=16,
                   rope_beta_fast=4.0, rope_beta_slow=1.0, rope_mscale=1.0,
                   rope_mscale_all_dim=1.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S", [1, 16], ids=["decode", "window"])
def test_the_unselected_kernel_equals_the_composition_and_an_all_ones_selection(
        S, dtype):
    """``mla_attention_decode(selected=False)``: the kernels that read
    no mask - absorbed at S = 1, in the expanded widths in a window -
    equal the expanded composition (in bfloat16 within the rounding of
    q W_kb and of the latent sum, or of the softmax's weights), and
    both equal the selected op under a selection of every position at
    or before the query."""
    op = get_op("mla_attention_decode")
    dense = op.normalize_attrs(dict(_GEOMETRY, selected=False,
                                    **_YARN_ATTRS))
    chosen = op.normalize_attrs(dict(_GEOMETRY, **_YARN_ATTRS))
    assert op.input_names(dense) == ["q", "kv", "fed", "kv_norm_weight",
                                     "kv_b_weight"]
    assert op.input_names(chosen)[2] == "selection"
    ins, aux = _op_inputs(S, jnp.dtype(dtype))
    out, new = op.variant_fn("xla")(dense, ins, aux, False, None)
    out_k, new_k = op.variant_fn("pallas")(dense, ins, aux, False, None)
    tol = 1e-5 if dtype == "float32" else 0.04
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               np.asarray(out_k[0], np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(np.asarray(new[0], np.float32),
                                  np.asarray(new_k[0], np.float32))
    assert list(np.asarray(new[1]).ravel()) == [40 + S, 7 + max(S - 1, 1)]
    pos = np.asarray([40, 7])[:, None] + np.arange(S)[None, :]
    ones = (np.arange(CAPACITY)[None, None, :] <= pos[:, :, None]) \
        .astype(np.int8)
    with_sel = ins[:2] + [jnp.asarray(ones)] + ins[2:]
    for variant, got in (("xla", out), ("pallas", out_k)):
        sel_out, _ = op.variant_fn(variant)(chosen, with_sel, aux, False,
                                            None)
        # the same sums in the same order: to the bit in the composition
        np.testing.assert_allclose(
            np.asarray(sel_out[0], np.float32),
            np.asarray(got[0], np.float32),
            atol=0 if variant == "xla" else tol / 10,
            rtol=0 if variant == "xla" else tol / 10)


@pytest.mark.parametrize("case", sorted(mla_window_cases.CASES))
def test_a_window_attends_a_slot_fed_one_row_as_the_s1_program_does(case):
    """No selection, YaRN: a window whose slots are fed a whole window,
    one row, none and a ragged few (``tests/mla_window_cases.py``)
    equals the expanded form at every fed position; the row of a slot
    fed one - attended heads-as-rows by ``mla_attn_ride`` - is the S = 1
    dispatch's to the bit, at cursors inside, at the end of and past a
    key block; a window in which every slot rides and one in which none
    does."""
    riding = mla_window_cases.check(case, selected=False, **_YARN_ATTRS)
    assert len(riding) == {"mixed": 3, "all_riding": 6,
                           "none_riding": 0}[case]


@pytest.mark.parametrize("case", sorted(mla_window_cases.WINDOW_CASES))
def test_the_window_form_attends_in_the_expanded_widths(case):
    """No selection, YaRN: ``mla_attn_window`` - a key block expanded
    once a head, every query block of the chunk scored against it -
    equals the expanded composition at every fed position
    (``mla_window_cases.WINDOW_CASES``): several query blocks against
    tiny key blocks with cursors on a block's last and first row, a slot
    fed 2 rows beside one fed all of them, dead slots around the live
    ones, unequal ``nope_dim`` and ``v_dim``."""
    fed, blocks, geometry = mla_window_cases.WINDOW_CASES[case]
    mla_window_cases.check_window(
        fed, False, blocks, dict(mla_window_cases._GEOMETRY, **geometry),
        **_YARN_ATTRS)


def test_yarn_at_the_published_values():
    """``low`` / ``high`` 10 / 23, the blended frequencies and the
    softmax scale 0.130861 of A.X-K1's ``rope_scaling``; ``factor`` 1 is
    today's rotary to the bit."""
    inv, (low, high) = mla.yarn_inv_freq(64, 10000.0, 32.0, 4096, 32.0, 1.0)
    assert (low, high) == (10, 23)
    theta = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:11], theta[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], theta[23:] / 32, rtol=1e-12)
    r = (np.arange(11, 23) - 10) / 13.0
    np.testing.assert_allclose(
        inv[11:23], theta[11:23] * (1 - r) + theta[11:23] / 32 * r,
        rtol=1e-12)
    ref_inv, trig, scale, ramp = ref.yarn(
        {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
         "rope_theta": 10000,
         "rope_scaling": dict(YARN, factor=32, beta_fast=32,
                              original_max_position_embeddings=4096)})
    np.testing.assert_array_equal(np.float32(ref_inv), np.float32(inv))
    assert ramp == (10, 23) and trig == 1.0
    assert round(scale, 6) == 0.130861
    assert mla.yarn_mscale(32.0, 1.0) ** 2 == pytest.approx(1.813260, abs=1e-6)
    op = get_op("mla_attention_decode")
    attrs = op.normalize_attrs(dict(
        _GEOMETRY, nope_dim=128, rope_dim=64, selected=False,
        **dict(_YARN_ATTRS, rope_factor=32.0, rope_original_positions=4096,
               rope_beta_fast=32.0)))
    _base, scaling, factor = mla._mla_rope(attrs)
    assert scaling.trig_scale == 1.0
    assert round(192 ** -0.5 * factor, 6) == 0.130861
    # no scaling: the plain rotary's own expression, untouched
    plain = op.normalize_attrs(dict(_GEOMETRY))
    assert mla._mla_rope(plain) == (10000.0, None, 1.0)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 16), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 7], [90, 91, 92, 93, 94]])
    np.testing.assert_array_equal(
        np.asarray(mla.rope_interleaved(x, pos, 10000.0)),
        np.asarray(mla.rope_interleaved(x, pos, 10000.0, None)))
    turned = mla.rope_interleaved(x, pos, 10000.0, mla.RopeScaling(
        8.0, 16, 4.0, 1.0, 1.0))
    assert np.max(np.abs(np.asarray(turned)
                         - np.asarray(mla.rope_interleaved(
                             x, pos, 10000.0)))) > 0.1


# --------------------------------------------------------- the router
def _route(sc_logits, **kw):
    """Scores through the program's router: an identity router weight
    turns the rows into the logits."""
    E = sc_logits.shape[1]
    return moe.moe_route_sigmoid(
        jnp.asarray(sc_logits, jnp.float32), jnp.eye(E, dtype=jnp.float32),
        None, kw.pop("top_k", 4), True, 2.5, **kw)


def test_the_group_limited_choice_equals_the_reference_on_ties_and_seconds():
    """12 experts in 4 groups of 3, 2 groups kept, 4 a token. Row 0: group 1 is kept by its SECOND-best
    score (its best is lower than group 2's best). Row 1: groups tie
    (the lowest index wins) and experts tie inside the kept groups.
    Row 2: the 4 largest scores overall lie in three groups; one of
    them is not kept."""
    cfg = {"num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    logits = np.asarray([
        # g0            g1            g2            g3
        [-3., -3., -3.,  1., 0.9, -2.,  1.2, -3., -3.,  2., 1.9, -1.],
        [0.5, 0.5, -1.,  0.5, 0.5, -1.,  0.5, 0.5, -1.,  0.5, 0.5, -1.],
        [3., -4., -4.,  2.9, -4., -4.,  1., 0.9, 0.8,  -1., -1., -1.]],
        np.float32)
    weights, experts = _route(logits, n_group=4, topk_group=2)
    sc = jax.nn.sigmoid(jnp.asarray(logits))
    chosen, weight = ref.route(sc, dict(cfg))
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(chosen))
    assert np.asarray(experts).tolist() == [
        [9, 10, 3, 4],            # groups 3 and 1: 1 + 0.9 beats 1.2 - 3
        [0, 1, 3, 4],             # groups 0 and 1, experts by index
        [0, 6, 7, 8]]             # groups 0 and 2; expert 3 (2.9) is out
    picked = np.take_along_axis(np.asarray(weight), np.asarray(chosen), 1)
    np.testing.assert_allclose(np.asarray(weights), picked, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)


def test_one_group_is_todays_choice_to_the_bit():
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(64, 32), jnp.float32)
    router = jnp.asarray(rs.randn(24, 32) * 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(24) * 0.02, jnp.float32)
    for b in (None, bias):
        old = moe.moe_route_sigmoid(x, router, b, 8, True, 2.5)
        new = moe.moe_route_sigmoid(x, router, b, 8, True, 2.5, 1, 1)
        for a, c in zip(old, new):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    # every group kept: the limit limits nothing
    free = moe.moe_route_sigmoid(x, router, None, 8, True, 2.5)
    all_kept = moe.moe_route_sigmoid(x, router, None, 8, True, 2.5, 4, 4)
    np.testing.assert_array_equal(np.asarray(free[1]),
                                  np.asarray(all_kept[1]))
    op = get_op("MoEFFN")
    with pytest.raises(ValueError, match="group-limited"):
        op.input_names(op.normalize_attrs(dict(
            num_experts=24, num_hidden=8, top_k=8, n_group=5, topk_group=2,
            scoring="sigmoid")))
    attrs = op.normalize_attrs(dict(
        num_experts=24, num_hidden=8, top_k=8, n_group=4, topk_group=2,
        scoring="sigmoid", router_bias=False))
    assert "router_bias" not in op.input_names(attrs)


def test_the_shares_add_up_to_the_uncut_layer_under_group_limited_routing():
    """Eight chips' shares (experts 3 a chip of 24, the shared expert
    counted once) through the program's ``MoEFFN`` add up to the uncut
    layer as the reference computes it, and each share equals the
    reference's own share."""
    rs = np.random.RandomState(4)
    D, F, E, T = 64, 32, 24, 40
    cfg = blocks.reference_cfg(BLOCK, d_model=D)
    f = lambda *s: np.asarray(rs.randn(*s) * 0.3, np.float32)  # noqa: E731
    params = {"p_moe_router_weight": f(E, D),
              "p_moe_gate_weight": f(E, D, F), "p_moe_up_weight": f(E, D, F),
              "p_moe_down_weight": f(E, F, D),
              "p_moe_shared_gate_weight": f(D, F),
              "p_moe_shared_up_weight": f(D, F),
              "p_moe_shared_down_weight": f(F, D)}
    x = f(T, D)
    with jax.default_matmul_precision("highest"):
        whole, shared, chosen = ref.expert_layer(
            jnp.asarray(x), "p", params, cfg, lambda a: a, held=(0, E))
    op = get_op("MoEFFN")
    total = np.zeros((T, D), np.float32)
    for first in range(0, E, 3):
        attrs = op.normalize_attrs(dict(
            num_experts=E, num_hidden=F, top_k=8, norm_topk=True,
            scoring="sigmoid", scaling=2.5, held_first=first, held_count=3,
            shared_hidden=F, n_group=4, topk_group=2, step_len=1))
        ins = [jnp.asarray(x), jnp.ones((T,), jnp.int32),
               params["p_moe_router_weight"]] + [
            jnp.asarray(params[f"p_moe_{k}_weight"][first:first + 3])
            for k in ("gate", "up", "down")] + [
            params[f"p_moe_shared_{k}_weight"]
            for k in ("gate", "up", "down")]
        (out, experts), (stats,) = op.variant_fn("xla")(
            attrs, ins, [jnp.zeros((5,), jnp.int32)], False, None)
        np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                      np.sort(np.asarray(chosen), -1))
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref.expert_layer(jnp.asarray(x), "p", {
                **params, **{f"p_moe_{k}_weight":
                             params[f"p_moe_{k}_weight"][first:first + 3]
                             for k in ("gate", "up", "down")}},
                cfg, lambda a: a, held=(first, 3))
        np.testing.assert_allclose(np.asarray(out) - np.asarray(shared),
                                   np.asarray(part), atol=2e-5, rtol=2e-5)
        total += np.asarray(out) - np.asarray(shared)
        assert int(stats[4]) == int(np.sum(
            (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + 3)))
    np.testing.assert_allclose(total, np.asarray(whole), atol=5e-5, rtol=5e-5)
    assert np.max(np.abs(np.asarray(whole))) > 0.1


# ------------------------------------------------------ the reuse plane
def test_the_store_joins_at_the_longest_common_head():
    rows = {"target": {"c": np.zeros((1, 12, 4), np.float32)}}
    store = PrefixStore(budget_bytes=1 << 20)
    doc = list(range(100, 110))
    assert store.put("doc", doc + [1, 2], rows)
    # another question about the same document: the document's rows
    c, entry = store.lookup("doc", np.asarray(doc + [7, 8, 9]), least=4)
    assert c == 10 and entry is not None and store.hits == 1
    # it shares more than the document by chance: all of it
    assert store.lookup("doc", np.asarray(doc + [1, 5]), least=4)[0] == 11
    # a prompt that differs at token 0, or inside one dispatch: a miss
    assert store.lookup("doc", np.asarray([9] + doc[1:] + [1]),
                        least=4) == (0, None)
    assert store.lookup("doc", np.asarray(doc[:3] + [0] * 9),
                        least=4) == (0, None)
    assert store.mismatches == 2 and store.misses == 2
    # the entry has served 2 and failed 2: a cold prompt may now take
    # its place; while it served more than it failed it stayed
    assert store.lookup("doc", np.asarray(doc + [3]), least=4)[0] == 10
    assert not store.put("doc", [9] * 12, rows)
    assert store.lookup("doc", np.asarray([9] * 12), least=4) == (0, None)
    assert store.put("doc", [9] * 12, rows)
    assert store.lookup("doc", np.asarray([9] * 12), least=4)[0] == 11


def _tiny_server(name, prefix_mb):
    return mx.serve.serve_decoder(
        blocks.symbol(BLOCK, 1), blocks.params(BLOCK), name=name,
        capacity=CAPACITY, ladder=[2],
        symbol_gen=lambda s: blocks.symbol(BLOCK, s), prefill_chunk=8,
        start=False, prefix_cache_mb=prefix_mb)


def test_a_join_at_a_common_head_is_a_cold_prefill_and_compiles_once():
    """Two prompts that open with one document of 24 tokens and differ
    after it, then a third with another tail: the second and third join
    at the common head (24 and, cut at a longer stored prompt, 24), the
    latent pools of a joined slot equal a cold prefill's bitwise, the
    answers equal a store-less server's, and two joins of different
    lengths compile nothing after warm-up."""
    from mxnet_tpu import telemetry
    with blocks.tier("xla"):
        rng = np.random.default_rng(12)
        doc = rng.integers(0, VOCAB, 24)
        tails = [rng.integers(0, VOCAB, n) for n in (5, 9, 13)]
        prompts = [np.concatenate([doc, t]).astype(np.int32) for t in tails]
        other = prompts[0].copy()
        other[0] = (other[0] + 1) % VOCAB
        answers, rows = {}, {}
        for name, budget in (("cold", 0), ("warm", 4)):
            # the store-less server first: ``compiles_since_warmup`` is
            # the process's count from the later server's warm mark
            server = _tiny_server(f"axk1-tiny-{name}", budget)
            assert (server.prefix_store is None) == (name == "cold")
            backend = telemetry.core.backend_compiles()
            out = []
            for prompt in prompts + [other]:
                h = server.submit(prompt, max_new_tokens=4, prefix_id="doc")
                server.pump()
                out.append(list(h.result(timeout=60)))
                if len(out) in (2, 3):
                    # the slot the request just left holds its rows
                    rows[name, len(out)] = server.engine.driver(2) \
                        .capture_rows(0, len(prompt))
            answers[name] = out
        warm = server
        for n in (2, 3):
            for nm, want in rows["cold", n].items():
                assert np.array_equal(
                    np.asarray(rows["warm", n][nm], np.float32),
                    np.asarray(want, np.float32)), (n, nm)
        assert answers["warm"] == answers["cold"]
        stats = warm.stats()
        assert stats["prefix"]["hits"] == 2
        assert stats["prefix"]["mismatches"] == 1        # token 0 differs
        assert stats["compiles_since_warmup"] == 0
        assert telemetry.core.backend_compiles() == backend
        counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                    if isinstance(m, telemetry.Counter)
                    and ("model", "axk1-tiny-warm") in m.labels}
        assert counters["serve.decode.prefix.joined_tokens"] == 48
        assert counters["serve.decode.prompt_tokens"] == sum(
            len(p) for p in prompts) + len(other)
        assert counters["serve.decode.attn.attended_rows"] \
            == counters["serve.decode.attn.live_rows"] > 0
        spans = [r for r in telemetry.flightrec.get_records()
                 if r.get("kind") == "trace.span"
                 and r.get("name") == "serve.decode.prefix.join"]
        ring = [r for r in telemetry.flightrec.get_records()
                if r.get("kind") == "serve.decode.step"
                and r.get("model") == "axk1-tiny-warm"]
        assert ring and all(r["mla_attended"] == r["attn_attended"] > 0
                            for r in ring)
        assert all((r["mla_pairs"] == r["mla_attended"]) == (r["window"] == 1)
                   for r in ring)
        for r in spans:
            assert r["cursor"] == 24 and r["dur_us"] > 0
            assert r["bytes"] == 3 * 3 * 8 * mla.latent_width(64, 16) * 4


def test_the_scheduler_counts_the_slots_a_window_feeds_one_row():
    """A request decodes while another prefills 20 tokens in windows of
    8: three windows feed the decoding slot one row each - the riding
    form of the kernel, interpreted - and ``serve.decode.window.*``
    count them from the plan; its answer is the one it gives alone."""
    from mxnet_tpu import telemetry
    with blocks.tier("pallas"):
        rng = np.random.default_rng(21)
        short, long_ = (rng.integers(0, VOCAB, n)
                        .astype(np.int32) for n in (3, 20))
        server = _tiny_server("axk1-tiny-ride", 0)
        riding = server.submit(short, max_new_tokens=10)
        assert server.pump(max_iterations=2) == 2   # a window of 3, a step
        server.submit(long_, max_new_tokens=2)
        server.pump()
        counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                    if isinstance(m, telemetry.Counter)
                    and ("model", "axk1-tiny-ride") in m.labels}
        # windows feed (3), (1, 8), (1, 8), (1, 4) rows; the rest are steps
        assert counters["serve.decode.window.fed_slots"] == 7
        assert counters["serve.decode.window.riding_slots"] == 3
        assert counters["serve.decode.prefill.chunks"] == 4
        alone = server.submit(short, max_new_tokens=10)
        server.pump()
        assert list(riding.result(timeout=60)) \
            == list(alone.result(timeout=60))
