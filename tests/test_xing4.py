"""Xing4.0's block behind the serving path (``residual="hyper"`` of
models/transformer.py: a stream of four copies a token read and joined
through ops/mhc.py's ``mhc_pre`` / ``mhc_post``, around A.X-K1's latent
attention under YaRN and a sigmoid router with a correction bias over
experts that are all held). What every served block does is
``tests/decode_block_suite.py``'s, over the row ``xing4`` of
``tests/decode_blocks.py`` against the plain reference
chipbench/reference/xing4.py: two layers (dense, sparse) of 16
experts, 4 a token in one group; YaRN of factor 8 over 16 original
positions. Below that the block's own: the rows the mappings count, the
latent window form, the mappings against the reference's, the router."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

import mla_window_cases  # noqa: E402
from chipbench.reference import xing4 as ref  # noqa: E402

BLOCK = "xing4"
XING4 = blocks.config(BLOCK)["xing4"]
TOL = blocks.TOL[BLOCK]


# ------------------------------------------------- the block, end to end
def test_a_dispatch_counts_a_row_for_every_token_fed_once_a_sub_layer(driver):
    """A whole window (every slot fed 16: 64 rows), packed windows in
    which one slot prefills and the others ride with a token each or
    prefill beside it (24 rows), then S = 1 through the latent cache,
    past YaRN's 16 original positions: every fed position that a
    program hands back equals the reference, and the dispatch counts a
    row for every token fed, once a sub-layer (4)."""
    seqs = blocks.seqs(BLOCK, 96)
    schedule = [(WINDOW, [16] * SLOTS), (WINDOW, [16] + [1] * (SLOTS - 1)),
                (WINDOW, [5, 3, 16] + [0] * (SLOTS - 3)),
                (1, [1] * SLOTS)] * 2
    ran = []
    step = driver.step

    def counted(tokens, fed=None):
        out = step(tokens, fed=fed)
        ran.append((driver.last_program_rows, driver.last_reads["mhc.rows"]))
        return out

    driver.step = counted
    try:
        got, at, _ = blocks.run(driver, seqs, schedule)
    finally:
        del driver.step
    assert list(at[:3]) == [76, 42, 68]
    assert ran[:4] == [(SLOTS * 16, 4 * SLOTS * 16), (24, 4 * (15 + SLOTS)),
                       (24, 4 * 24), (SLOTS, 4 * SLOTS)]
    want = blocks.reference(BLOCK, seqs)
    assert np.max(np.abs(want)) > 2.0
    held = ~np.isnan(got).any(axis=-1)
    for slot in range(SLOTS):
        assert held[slot, at[slot] - 1] and not held[slot, at[slot]:].any()
        np.testing.assert_allclose(got[slot][held[slot]],
                                   want[slot][held[slot]],
                                   atol=TOL, rtol=TOL)
    assert driver.read_counts["mhc.rows"] == ("mhc.rows", "mhc_rows")
    assert driver.routed


@pytest.mark.parametrize("case", sorted(mla_window_cases.WINDOW_CASES))
def test_the_latent_window_form_attends_in_the_expanded_widths(case):
    """Xing4.0's latent attention (no selection, YaRN as the fixture
    scales it) in a window: ``mla_attn_window`` equals the expanded
    composition at every fed position, case by case
    (``mla_window_cases.WINDOW_CASES``)."""
    fed, form, geometry = mla_window_cases.WINDOW_CASES[case]
    mla_window_cases.check_window(
        fed, False, form, dict(mla_window_cases._GEOMETRY, **geometry),
        rope_base=float(blocks.config(BLOCK)["rope_base"]),
        **tfm._yarn_rope({"rope_scaling": XING4["rope_scaling"]}, "xing4"))


def test_a_mapping_rounded_to_bfloat16_misses_the_tolerance():
    """The tolerance would catch a lower precision: the reference with
    the mappings' own arithmetic in bfloat16 is hundreds of tolerances
    from the float32 one."""
    seqs = blocks.seqs(BLOCK, 48, seed=3, slots=3)
    want = blocks.reference(BLOCK, seqs)
    low = blocks.reference(BLOCK, seqs, mapping_dtype=jnp.bfloat16)
    err = np.abs(low - want)
    assert np.max(err / (TOL + TOL * np.abs(want))) > 50


def test_the_graph_joins_through_the_mapping_and_the_older_blocks_do_not():
    symbol = blocks.symbol(BLOCK, 4)
    nodes = [n for n in symbol._topo_nodes() if not n.is_variable]
    ops = [n.op for n in nodes]
    assert ops.count("mhc_pre") == ops.count("mhc_post") == 4
    assert ops.count("mla_attention_decode") == 2
    assert ops.count("MoEFFN") == 1 and "dsa_index_select" not in ops
    assert "reshape_like" not in ops and "_plus" not in ops
    for n in nodes:
        if n.op == "mhc_pre":
            assert (n.attrs["n"], n.attrs["iters"]) \
                == (4, XING4["hc_sinkhorn_iters"])
            assert float(n.attrs["clamp_max"]) == 30.0
        if n.op == "MoEFFN":
            assert (n.attrs["n_group"], n.attrs["router_bias"],
                    n.attrs["held_count"]) == (1, True, 16)
    args = symbol.list_arguments()
    assert "lm_l1_moe_router_bias" in args
    shapes = dict(zip(args, symbol.infer_shape(data=(SLOTS, 4),
                                               fed=(SLOTS,))[0]))
    assert shapes["lm_l0_proj_mhc_weight"] == (24, 256)
    assert shapes["lm_l1_ffn_mhc_bias"] == (24,)
    assert shapes["lm_l1_ffn_mhc_scale"] == (3,)
    # neither the scheduler nor the engine knows the block by name
    for module in ("decode", "engine"):
        with open(os.path.join(blocks.ROOT, "mxnet_tpu", "serve",
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
    chosen, weight = ref.route(jnp.asarray(score), bias,
                               blocks.reference_cfg(BLOCK))
    np.testing.assert_array_equal(np.asarray(chosen), top4)
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(np.asarray(weight), top4, 1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.0, rtol=1e-6)
    # the bias moves choices: without it another set
    _, unbiased = moe.moe_route_sigmoid(x, router, None, 4, True, 2.0)
    assert (np.sort(np.asarray(unbiased), -1)
            != np.sort(np.asarray(experts), -1)).any()
