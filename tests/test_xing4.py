"""Xing4.0's block behind the serving path (``residual="hyper"`` of
models/transformer.py: a stream of four copies a token read and joined
through ops/mhc.py's ``mhc_pre`` / ``mhc_post``, around A.X-K1's latent
attention under YaRN and a sigmoid router with a correction bias over
experts that are all held) against the plain reference
chipbench/reference/xing4.py, at small widths on the CPU: three layers
(dense, sparse, sparse) of 16 experts, 4 a token in one group; YaRN of
factor 8 over 16 original positions."""
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

import mla_window_cases  # noqa: E402
from chipbench.reference import xing4 as ref  # noqa: E402
# the quick cases of the benchmark's own tests of the architecture file
# run here as they stand (its CPU rehearsals stay by hand)
from chipbench.tests.test_xing4 import (  # noqa: E402,F401
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_scripted_trace,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_controls_are_further_than_the_emulation,
    test_the_traffic_is_the_issues)

YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 16,
        "beta_fast": 4, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
CFG = {"vocab_size": 48, "hidden_size": 64, "num_attention_heads": 4,
       "num_hidden_layers": 3, "q_lora_rank": 48, "kv_lora_rank": 64,
       "qk_nope_head_dim": 24, "qk_rope_head_dim": 16, "v_head_dim": 16,
       "first_k_dense_replace": 1, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 16,
       "num_experts_per_tok": 4, "n_shared_experts": 1, "n_group": 1,
       "topk_group": 1, "routed_scaling_factor": 2.0,
       "norm_topk_prob": True, "hc_mult": 4, "hc_sinkhorn_iters": 20,
       "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
       "mhc_h_res_clamp_max": 30, "rope_theta": 10000,
       "rope_scaling": YARN, "rms_norm_eps": 1e-6}
CAPACITY, WINDOW, SLOTS = 128, 16, 3            # WINDOW: the S > 1 program
#: float32 served against the float32 reference through 3 layers (6
#: mappings), on logits of magnitude about 8 (measured here: 3e-5 to
#: 2e-4; the mapping's exp and 20 Sinkhorn rounds carry a rounding of
#: the stream further than a plain residual add does)
TOL = 1e-3


def _symbol(step_len):
    return tfm.get_decode_symbol(
        vocab_size=CFG["vocab_size"], d_model=CFG["hidden_size"],
        n_layer=CFG["num_hidden_layers"],
        n_head=CFG["num_attention_heads"], pos_embed="rotary",
        rope_base=float(CFG["rope_theta"]), capacity=CAPACITY,
        step_len=step_len, per_slot=True, block="xing4",
        rms_eps=CFG["rms_norm_eps"], tie_head=False, embed_scale=False,
        xing4={k: CFG[k] for k in tfm.XING4_KEYS})


def _params(seed=5):
    """Mapping weights of deviation 0.15 over 256 numbers of unit RMS:
    logits of deviation 2.4, the published widths' under N(0, 0.02)."""
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
        elif name.endswith("_mhc_scale"):
            draw = 1.0 + 0.2 * draw
        elif name.endswith("_mhc_weight"):
            draw = 0.15 * draw
        else:
            draw = 0.25 * draw
        out[name] = draw.astype(np.float32)
    return out


PARAMS = _params()


def _bound(symbol, step_len, shared=None):
    mod = mx.mod.Module(symbol, data_names=("data", "fed"), label_names=[])
    mod.bind([mx.io.DataDesc("data", (SLOTS, step_len), np.int32),
              mx.io.DataDesc("fed", (SLOTS,), np.int32)],
             None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None, arg_params=dict(PARAMS),
                        aux_params={}, allow_missing=True)
    return mod


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request):
    """A three-slot pool with its S = 16 window program, whole and
    packed (24 rows), under one kernel tier (the Pallas kernels in
    interpret mode)."""
    old = os.environ.get("MXNET_KERNEL_TIER")
    os.environ["MXNET_KERNEL_TIER"] = request.param
    kernel_tier.clear()
    base = _bound(_symbol(1), 1)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
    packed, budget = tfm.packed_window(_symbol(WINDOW), SLOTS)
    assert budget == 24
    drv.add_window(WINDOW, _bound(_symbol(WINDOW), WINDOW, shared=base),
                   packed=(_bound(packed, WINDOW, shared=base), budget))
    yield drv
    if old is None:
        os.environ.pop("MXNET_KERNEL_TIER", None)
    else:
        os.environ["MXNET_KERNEL_TIER"] = old
    kernel_tier.clear()


def _reference(seqs, **kw):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, CFG, **kw))
    return np.asarray(fwd(PARAMS, jnp.asarray(seqs)))


def _run(drv, seqs, schedule):
    """Feed ``seqs`` (slots, T) through ``schedule``, a list of (S, fed
    counts a slot): the logits of every fed position that a dispatch
    hands back (of a packed window each slot's last fed row alone,
    ISSUE 51: the others stay NaN), the cursors, the rows each
    dispatch's program ran over and what it counted."""
    for slot in range(drv.slots):
        if drv.active[slot]:
            drv.leave(slot)
        drv.join(slot)
    got = np.full(seqs.shape + (CFG["vocab_size"],), np.nan, np.float32)
    at = np.zeros(drv.slots, int)
    ran = []
    for S, fed in schedule:
        tokens = np.full((drv.slots, S), 7, np.int32)
        for slot, n in enumerate(fed):
            tokens[slot, :n] = seqs[slot, at[slot]:at[slot] + n]
        out = drv.step(tokens, fed=fed).asnumpy()
        ran.append((drv.last_program_rows, drv.last_reads["mhc.rows"]))
        assert out.shape[1] == (S if ran[-1][0] == drv.slots * S else 1)
        for slot, n in enumerate(fed):
            if out.shape[1] == S:
                got[slot, at[slot]:at[slot] + n] = out[slot, :n]
            elif n:
                got[slot, at[slot] + n - 1] = out[slot, 0]
        at = at + np.asarray(fed)
        assert list(drv.pos) == list(at)
    return got, at, ran


def _seqs(T, seed=1):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (SLOTS, T)).astype(np.int32)


# ------------------------------------------------- the block, end to end
def test_windows_packed_windows_with_riders_then_decode_equal_the_reference(
        driver):
    """Whole windows (every slot fed 16: 48 rows), packed windows in
    which one slot prefills and the others ride with a token each (24
    rows), ragged windows, then S = 1 through the latent cache, 90
    positions past YaRN's 16 original ones: every fed position that
    a program hands back (a packed window's: each slot's last) equals
    the reference, and the dispatch counts a row for every token fed,
    once a sub-layer (6)."""
    seqs = _seqs(96)
    schedule = ([(WINDOW, [16, 16, 16]), (WINDOW, [16, 1, 1]),
                 (WINDOW, [1, 16, 1]), (WINDOW, [5, 3, 16]),
                 (1, [1, 1, 1])] * 2 + [(1, [1, 1, 1])] * 6)
    got, at, ran = _run(driver, seqs, schedule)
    assert list(at) == [84, 80, 76]
    assert ran[:5] == [(48, 6 * 48), (24, 6 * 18), (24, 6 * 18),
                       (24, 6 * 24), (3, 6 * 3)]
    want = _reference(seqs)
    assert np.max(np.abs(want)) > 2.0
    held = ~np.isnan(got).any(axis=-1)
    # 16 a slot of the whole windows, 3 of the packed ones, the steps
    assert held.sum(axis=1).tolist() == [2 * 19 + 8] * 3
    for slot in range(SLOTS):
        assert held[slot, at[slot] - 1] and not held[slot, at[slot]:].any()
        np.testing.assert_allclose(got[slot][held[slot]],
                                   want[slot][held[slot]],
                                   atol=TOL, rtol=TOL)
    assert driver.read_counts["mhc.rows"] == ("mhc.rows", "mhc_rows")
    assert sorted(driver._state) == ["cursor", "rows"]
    assert driver.positional and driver.feeds and driver.routed


@pytest.mark.parametrize("case", sorted(mla_window_cases.WINDOW_CASES))
def test_the_latent_window_form_attends_in_the_expanded_widths(case):
    """Xing4.0's latent attention (no selection, YaRN as the fixture
    scales it) in a window: ``mla_attn_window`` equals the expanded
    composition at every fed position, case by case
    (``mla_window_cases.WINDOW_CASES``)."""
    fed, blocks, geometry = mla_window_cases.WINDOW_CASES[case]
    mla_window_cases.check_window(
        fed, False, blocks, dict(mla_window_cases._GEOMETRY, **geometry),
        rope_base=float(CFG["rope_theta"]),
        **tfm._yarn_rope({"rope_scaling": YARN}, "xing4"))


def test_a_mapping_rounded_to_bfloat16_misses_the_tolerance():
    """The tolerance would catch a lower precision: the reference with
    the mappings' own arithmetic in bfloat16 is hundreds of tolerances
    from the float32 one."""
    seqs = _seqs(48, seed=3)
    want = _reference(seqs)
    low = _reference(seqs, mapping_dtype=jnp.bfloat16)
    err = np.abs(low - want)
    assert np.max(err / (TOL + TOL * np.abs(want))) > 50


def test_the_graph_joins_through_the_mapping_and_the_older_blocks_do_not():
    symbol = _symbol(4)
    nodes = [n for n in symbol._topo_nodes() if not n.is_variable]
    ops = [n.op for n in nodes]
    assert ops.count("mhc_pre") == ops.count("mhc_post") == 6
    assert ops.count("mla_attention_decode") == 3
    assert ops.count("MoEFFN") == 2 and "dsa_index_select" not in ops
    assert "reshape_like" not in ops and "_plus" not in ops
    for n in nodes:
        if n.op == "mhc_pre":
            assert (n.attrs["n"], n.attrs["iters"]) == (4, 20)
            assert float(n.attrs["clamp_max"]) == 30.0
        if n.op == "MoEFFN":
            assert (n.attrs["n_group"], n.attrs["router_bias"],
                    n.attrs["held_count"]) == (1, True, 16)
    args = symbol.list_arguments()
    assert "lm_l1_moe_router_bias" in args
    shapes = dict(zip(args, symbol.infer_shape(data=(SLOTS, 4),
                                               fed=(SLOTS,))[0]))
    assert shapes["lm_l0_proj_mhc_weight"] == (24, 256)
    assert shapes["lm_l2_ffn_mhc_bias"] == (24,)
    assert shapes["lm_l2_ffn_mhc_scale"] == (3,)
    # neither the scheduler nor the engine knows the block by name
    for module in ("decode", "engine"):
        with open(os.path.join(ROOT, "mxnet_tpu", "serve",
                               module + ".py")) as f:
            text = f.read()
        assert "xing4" not in text and "mhc" not in text, module
    with pytest.raises(mx.MXNetError, match="hc_mult"):
        tfm.get_decode_symbol(per_slot=True, block="xing4", xing4={})
    with pytest.raises(mx.MXNetError, match="served, not trained"):
        tfm.get_symbol(block="xing4")
    # an older block's graph has neither op
    older = tfm.get_decode_symbol(per_slot=True, block="gpt2")
    assert not [n for n in older._topo_nodes()
                if str(n.op).startswith("mhc")]


# ------------------------------------------------------------- the ops
def _streams(rows, C, dtype, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), dtype)      # noqa: E731
    return (f(2, rows // 2, 4 * C), f(24, 4 * C) * 0.15, f(24) * 0.3,
            jnp.asarray([1.0, 0.7, 1.3], dtype), f(rows, C))


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("rows,dtype", [(6, "float32"), (128, "float32"),
                                        (256, "bfloat16")],
                         ids=["few", "tile", "tiles_bf16"])
def test_the_read_and_the_join_equal_the_references_mapping(rows, dtype,
                                                            variant):
    """``mhc_pre`` / ``mhc_post``, plain and as kernels (interpret mode;
    a handful of rows down the sublanes, whole tiles of 128 along the
    lanes), against the reference's mapping: Hpost, Hres, the mix a
    sub-layer reads and the joined stream."""
    C = 128
    x, w, b, a, y = _streams(rows, C, jnp.dtype(dtype))
    pre, post = get_op("mhc_pre"), get_op("mhc_post")
    attrs = pre.normalize_attrs({"n": 4})
    (u, hpost, hres), _ = pre.variant_fn(variant)(
        attrs, [x, w, b, a], [], False, None)
    (joined,), _ = post.variant_fn(variant)(
        post.normalize_attrs({"n": 4}), [x, y, hpost, hres], [], False, None)
    assert u.shape == (2, rows // 2, C) and joined.shape == x.shape
    assert (hpost.dtype, hres.dtype) == (jnp.float32, jnp.float32)
    X = jnp.asarray(x, jnp.float32).reshape(rows, 4, C)
    with jax.default_matmul_precision("highest"):
        want_pre, want_post, want_res = ref.mhc_mapping(
            X, w, b, a, {"rms_norm_eps": 1e-6, "hc_eps": 1e-6,
                         "hc_sinkhorn_iters": 20,
                         "mhc_h_res_clamp_min": -30,
                         "mhc_h_res_clamp_max": 30})
    want_u = jnp.einsum("ti,tic->tc", want_pre, X)
    want_x = jnp.einsum("tij,tjc->tic", want_res, X) \
        + want_post[:, :, None] * jnp.asarray(y, jnp.float32)[:, None, :]
    tol = 2e-5 if dtype == "float32" else 0.03
    np.testing.assert_allclose(np.asarray(hpost), np.asarray(want_post),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(hres).reshape(rows, 4, 4),
                               np.asarray(want_res), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(u, np.float32).reshape(rows, C), np.asarray(want_u),
        atol=tol, rtol=tol)
    np.testing.assert_allclose(
        np.asarray(joined, np.float32).reshape(rows, 4, C),
        np.asarray(want_x), atol=tol, rtol=tol)


def test_hres_is_doubly_stochastic_and_the_mapping_is_alive():
    """20 iterations end on the columns, so those sum to 1 to rounding
    and the rows to within what 20 rounds leave (a few percent under
    logits of deviation 2.4); no row of ``Hres`` is the identity's or
    the uniform one's: the mean largest entry lies between."""
    x, w, b, a, _y = _streams(512, 128, jnp.float32, seed=4)
    pre = get_op("mhc_pre")
    (_u, hpost, hres), _ = pre.variant_fn("xla")(
        pre.normalize_attrs({"n": 4}), [x, w, b, jnp.ones(3)], [], False,
        None)
    m = np.asarray(hres).reshape(-1, 4, 4)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(axis=2), 1.0, atol=0.1)
    assert m.min() > 0.0
    assert 0.35 < m.max(axis=2).mean() < 0.9
    hp = np.asarray(hpost)
    assert 0.0 < hp.min() and hp.max() < 2.0 and 0.3 < hp.std() < 0.8
    # the clamp: logits far outside it give a finite mapping
    (_u, _hp, far), _ = pre.variant_fn("xla")(
        pre.normalize_attrs({"n": 4}),
        [x, w, b, jnp.asarray([1.0, 1.0, 500.0])], [], False, None)
    assert np.isfinite(np.asarray(far)).all()


def test_one_group_with_a_bias_chooses_what_an_ungrouped_top4_chooses():
    """``n_group`` 1, ``topk_group`` 1 with a correction bias: the
    experts are the 4 largest ``score + bias``, the weights the
    unbiased scores normalised over them times 2 - to the bit what the
    router without groups gives, and what the reference's sort gives."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(64, 32), jnp.float32)
    router = jnp.asarray(rs.randn(16, 32) * 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(16) * 0.3, jnp.float32)
    grouped = moe.moe_route_sigmoid(x, router, bias, 4, True, 2.0,
                                    n_group=1, topk_group=1)
    plain = moe.moe_route_sigmoid(x, router, bias, 4, True, 2.0)
    for a, b in zip(grouped, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    weights, experts = grouped
    score = np.asarray(jax.nn.sigmoid(x @ router.T))
    top4 = np.argsort(-(score + np.asarray(bias)), axis=-1,
                      kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(experts), top4)
    chosen, weight = ref.route(jnp.asarray(score), bias, CFG)
    np.testing.assert_array_equal(np.asarray(chosen), top4)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(np.asarray(weight), top4, 1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.0, rtol=1e-6)
    # the bias moves choices: without it another set
    _, unbiased = moe.moe_route_sigmoid(x, router, None, 4, True, 2.0)
    assert (np.sort(np.asarray(unbiased), -1)
            != np.sort(np.asarray(experts), -1)).any()
