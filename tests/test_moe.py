"""The sparse-expert block (ISSUE 28): ``RMSNorm`` and ``MoEFFN``
against the plain reference's expert layer (chipbench/reference/
olmoe.py: every expert for every token, masked), the Pallas grouped
matmul against the XLA composition in interpret mode on ragged groups,
the OLMoE decoder served through the window program and the cache
against the reference's full forward, and the bind-at-the-dtype-given
rule. Small sizes, CPU."""
import hashlib
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import moe, pallas_kernels
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import olmoe as ref  # noqa: E402

#: the issue's small size: 2 layers, width 64, 4 heads of 16, 8 experts
#: top-2 of width 32, vocabulary 128
CFG = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 4, "intermediate_size": 32, "num_experts": 8,
       "num_experts_per_tok": 2, "norm_topk_prob": False,
       "rms_norm_eps": 1e-5, "rope_theta": 10000, "vocab_size": 128}
CAPACITY, WINDOW = 48, 8


def _moe_inputs(rng, T, D, F, E, dtype=jnp.float32, router_scale=0.5):
    return [jnp.asarray(rng.normal(size=(T, D)), dtype),
            jnp.asarray(rng.normal(size=(E, D)) * router_scale, dtype),
            jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, dtype),
            jnp.asarray(rng.normal(size=(E, D, F)) * 0.1, dtype),
            jnp.asarray(rng.normal(size=(E, F, D)) * 0.1, dtype)]


def _run(variant, attrs, inputs):
    op = get_op("MoEFFN")
    fn = op.forward if variant == "xla" else op.variant_fn(variant)
    (out, experts), (stats,) = fn(op.normalize_attrs(attrs), inputs,
                                  [jnp.zeros((4,), jnp.int32)], False, None)
    return np.asarray(out), np.asarray(experts), np.asarray(stats)


# ------------------------------------------------ the op against the reference
@pytest.mark.parametrize("norm_topk", [False, True])
@pytest.mark.parametrize("T,D,F,E,k", [(24, 64, 32, 8, 2),
                                       (40, 64, 32, 64, 8),
                                       (1, 64, 32, 8, 2)])
def test_moe_ffn_matches_the_reference_expert_layer(T, D, F, E, k,
                                                    norm_topk):
    inputs = _moe_inputs(np.random.default_rng(T + E), T, D, F, E)
    attrs = dict(num_experts=E, num_hidden=F, top_k=k, norm_topk=norm_topk)
    out, experts, stats = _run("xla", attrs, inputs)
    x, router, gate, up, down = inputs
    with jax.default_matmul_precision("highest"):
        free, chosen = ref.expert_layer(x, router, gate, up, down, k,
                                        norm_topk)
        forced, _ = ref.expert_layer(x, router, gate, up, down, k,
                                     norm_topk, routing=experts)
    # both sides route in float32 from the same rows: the same sets
    assert float(ref.routing_flip_share(experts, chosen)) == 0.0
    # float32 rounding of sums of 64-term products of O(1) values
    np.testing.assert_allclose(out, np.asarray(free), atol=2e-5, rtol=0)
    np.testing.assert_allclose(out, np.asarray(forced), atol=2e-5, rtol=0)
    # every assignment counted once
    assert stats[0] == 1 and stats[1] == T * k
    assert experts.shape == (T, k)
    assert all(len(set(row)) == k for row in experts.tolist())
    sizes = np.bincount(experts.reshape(-1), minlength=E)
    assert sizes.sum() == T * k
    assert stats[2] == (sizes > 0).sum() and stats[3] == sizes.max()


@pytest.mark.parametrize("case", ["one_expert_takes_all", "some_get_none"])
def test_moe_ffn_under_skewed_routing(case):
    T, D, F, E, k = 16, 64, 32, 8, 2
    rng = np.random.default_rng(5)
    inputs = _moe_inputs(rng, T, D, F, E, router_scale=0.01)
    x = jnp.abs(inputs[0])                  # positive rows
    router = np.asarray(inputs[1]).copy()
    if case == "one_expert_takes_all":
        router[3] += 1.0                    # every token's first choice
        router[5] += 0.5                    # and every token's second
    else:
        router[[0, 2, 4, 6]] -= 1.0         # nobody's choice
    inputs = [x, jnp.asarray(router)] + inputs[2:]
    attrs = dict(num_experts=E, num_hidden=F, top_k=k)
    out, experts, stats = _run("xla", attrs, inputs)
    sizes = np.bincount(experts.reshape(-1), minlength=E)
    if case == "one_expert_takes_all":
        assert sizes[3] == T and sizes[5] == T and stats[2] == 2
        assert stats[3] == T
    else:
        assert (sizes[[0, 2, 4, 6]] == 0).all() and stats[2] <= 4
    assert stats[1] == T * k == sizes.sum()
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(*inputs, k, False)
    np.testing.assert_allclose(out, np.asarray(want), atol=2e-5, rtol=0)
    got, experts_p, stats_p = _run("pallas", attrs, inputs)
    np.testing.assert_allclose(got, out, atol=2e-5, rtol=0)
    assert (experts_p == experts).all() and (stats_p == stats).all()


def test_rmsnorm_op_and_shapes():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 5, 64)),
                    jnp.float32)
    g = jnp.linspace(0.5, 1.5, 64)
    out = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(g), eps=1e-5).asnumpy()
    want = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(out, want, atol=1e-6)
    s = mx.sym.RMSNorm(mx.sym.var("data"), name="n")
    args, outs, _ = s.infer_shape(data=(3, 5, 64))
    assert args == [(3, 5, 64), (64,)] and outs == [(3, 5, 64)]
    m = mx.sym.MoEFFN(mx.sym.var("data"), num_experts=8, num_hidden=32,
                      top_k=2, name="m")
    args, outs, aux = m.infer_shape(data=(10, 64))
    assert args == [(10, 64), (8, 64), (8, 64, 32), (8, 64, 32),
                    (8, 32, 64)]
    assert outs == [(10, 64)] and aux == [(4,)]
    for name in ("RMSNorm", "MoEFFN"):          # rule GV107, MF601
        assert get_op(name).infer_shape is not None
        assert get_op(name).has_cost()


# ------------------------------------- the Pallas variant, interpret mode
def _spread(rows, groups, seed):
    """``rows`` assignments over ``groups`` experts, uneven."""
    rng = np.random.default_rng(seed)
    return rng.multinomial(rows, rng.dirichlet(np.ones(groups))).tolist()


#: (group sizes, rows behind the last group's)
_GMM_CASES = {
    "empty_groups_and_one_row": ([0, 1, 37, 0, 5, 16, 0, 21], 0),
    "the_last_group_only": ([0, 0, 0, 0, 0, 0, 0, 8], 0),
    "groups_across_row_tiles": ([130, 0, 1, 127, 0, 40, 2, 0], 0),
    "a_row_each": ([1, 1, 1, 1, 1, 1, 1, 1], 0),
    "longer_than_two_tiles": ([7, 300, 0, 5, 20, 0, 0, 1], 0),
    "ends_on_a_tile_edge": ([100, 28, 0, 128, 3, 0, 125, 0], 0),
    "all_in_the_first_group": ([200, 0, 0, 0, 0, 0, 0, 0], 0),
    "all_in_the_last_group": ([0, 0, 0, 0, 0, 0, 0, 200], 0),
    "a_window_of_2048_rows_over_64": (_spread(2048, 64, 7), 0),
    "rows_past_the_groups": ([9, 0, 120, 31, 0, 0, 2, 0], 75),
    "no_row_in_any_group": ([0, 0, 0, 0], 40),
}


@pytest.mark.parametrize("case", list(_GMM_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_expert_ffn_matches_the_xla_composition(case, dtype):
    sizes, behind = _GMM_CASES[case]
    E, D, F = len(sizes), 64, 32
    routed = sum(sizes)
    M = routed + behind
    rng = np.random.default_rng(M)
    _, _, gate, up, down = _moe_inputs(rng, 1, D, F, E, jnp.dtype(dtype))
    xs = jnp.asarray(rng.normal(size=(M, D)), jnp.dtype(dtype))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    want = moe._experts_ragged(xs, group_sizes, gate, up, down)
    got = pallas_kernels.grouped_expert_ffn(xs, group_sizes, gate, up, down)
    assert got.shape == (M, D) and got.dtype == jnp.float32
    # the same products in another order of summation; bfloat16 rounds
    # the gated activations once more on both sides. Rows of no group
    # are the callers' to mask
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got)[:routed],
                               np.asarray(want)[:routed], atol=tol, rtol=0)


def _work_items(sizes, M):
    items, n = pallas_kernels._gmm_work_items(
        jnp.asarray(sizes, jnp.int32), M)
    grp, win, lo, hi = np.asarray(items)
    return grp, win, lo, hi, int(n[0])


def test_gmm_work_items_cut_the_rows_at_the_groups_edges():
    sizes = [130, 0, 1, 127, 0, 40, 2, 0]
    rows, window, chunk, W = pallas_kernels._gmm_geometry(300, 8)
    assert (rows, window, chunk) == (304, 128, 112)
    grp, win, lo, hi, n = _work_items(sizes, 300)
    live = list(zip(grp[:n].tolist(), lo[:n].tolist(), hi[:n].tolist()))
    assert live == [
        (0, 0, 112), (0, 112, 130),         # chunks of the group's own
        (2, 130, 131),                      # no empty group
        (3, 131, 243), (3, 243, 258),       # across a tile's edge: one
        (5, 258, 298), (6, 298, 300)]
    # a window starts on the granule its chunk starts in, and never so
    # late that it would leave the rows
    assert win[:n].tolist() == [0, 7, 8, 8, 11, 11, 11]
    # the entries behind repeat the last item's group and window, on the
    # tile of its last row
    assert set(zip(grp[n:].tolist(), win[n:].tolist(), lo[n:].tolist(),
                   hi[n:].tolist())) == {(6, 11, 299, 300)}
    assert len(grp) == W + 1 == 8 + 299 // 112 + 1


@pytest.mark.parametrize("sizes,behind", [
    ([130, 0, 1, 127, 0, 40, 2, 0], 0),
    ([0, 0, 0, 0, 0, 0, 0, 8], 0),
    ([1, 1, 1, 1, 1, 1, 1, 1], 5),
    ([7, 300, 0, 5, 20, 0, 0, 1], 0),
    ([100, 28, 0, 128, 3, 0, 125, 0], 0),
    ([0, 0, 0, 0, 0, 0, 0, 1000], 24),
    (_spread(2048, 64, 7), 0),
    (_spread(512, 64, 3), 0),               # an S=1 step: 64 slots x 8
    (_spread(1100, 128, 5), 52),            # a held segment, dead rows
    ([0, 0, 0, 0], 40)],
    ids=["across_tiles", "last_only", "a_row_each", "long_group",
         "on_a_tile_edge", "one_long_group", "window_2048", "decode_512",
         "segment_1152", "no_rows"])
def test_gmm_work_items_read_a_touched_group_once_a_chunk(sizes, behind):
    """What the kernels' grid does with the items, step by step: the
    block indices the index maps hand the pipeline (a block is fetched
    when its index changes) and the rows the items write."""
    E, M = len(sizes), sum(sizes) + behind
    rows, window, chunk, W = pallas_kernels._gmm_geometry(M, E)
    grp, win, lo, hi, n = _work_items(sizes, M)
    assert len(grp) == W + 1 and n <= W
    n_k = 4
    weights, row_blocks, tiles, at = [], [], [], [None] * 3
    for i in range(n + 1):                  # the grid: the items and one
        for kk in range(n_k):
            k = kk if i < n else n_k - 1
            for seen, idx, to in ((0, (grp[i], k), weights),
                                  (1, (win[i], k), row_blocks),
                                  (2, lo[i] // window, tiles)):
                if idx != at[seen]:
                    at[seen] = idx
                    to.append(idx)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    chunks = [-(-s // chunk) for s in sizes]
    # every block of a touched group once for each chunk of its rows,
    # in group order, and no block of a group without rows; the item
    # behind the last fetches nothing and stays on the last output tile
    want = [(g, k) for g in range(E) for _ in range(chunks[g])
            for k in range(n_k)]
    if n == 0:
        assert weights == [(grp[0], n_k - 1)]   # where the grid starts
    else:
        assert weights == want and n == sum(chunks)
        assert len(row_blocks) == n * n_k
        assert (grp[n], win[n]) == (grp[n - 1], win[n - 1])
        assert lo[n] // window == (hi[n - 1] - 1) // window
    # an output tile is taken up once, the tiles in order
    assert tiles == sorted(set(tiles)) and max(tiles) < -(-rows // window)
    # each routed row is written once: by its item into the tile the
    # item begins in, or by the next item into the tile that one begins
    # in - which is the one the rows reach into
    written = np.zeros(rows, int)
    for i in range(n):
        g = grp[i]
        assert offs[g] <= lo[i] < hi[i] <= offs[g + 1]
        assert hi[i] - lo[i] <= chunk and lo[i] == (hi[i - 1] if i else 0)
        assert 0 <= win[i] * 16 <= lo[i] and hi[i] <= win[i] * 16 + window
        assert win[i] * 16 + window <= rows
        edge = (lo[i] // window + 1) * window
        written[lo[i]:min(hi[i], edge)] += 1
        if hi[i] > edge:
            assert lo[i + 1] // window * window == edge
            assert hi[i] <= edge + window
            written[edge:hi[i]] += 1
    assert (written[:sum(sizes)] == 1).all() and not written[sum(sizes):].any()


@pytest.mark.parametrize("M,E,want", [
    (512, 64, (512, 128, 112, 64 + 4)),     # OLMoE, S=1 at rung 64
    (2048, 64, (2048, 128, 112, 64 + 18)),  # ... a window
    (1152, 128, (1152, 128, 112, 128 + 10)),    # a held segment
    (64, 64, (64, 64, 64, 64)),             # one window spans the call
    (8, 64, (16, 16, 16, 8)),
    (300, 8, (304, 128, 112, 8 + 2))])
def test_gmm_geometry_follows_the_rows_of_a_call(M, E, want):
    assert pallas_kernels._gmm_geometry(M, E) == want


def test_grouped_expert_ffn_is_traced_once_for_equal_shapes():
    """The pair is entered through ``jax.jit``: a second call with the
    same shapes finds the first one's trace, whoever calls."""
    rng = np.random.default_rng(0)
    _, _, gate, up, down = _moe_inputs(rng, 1, 64, 32, 4, jnp.float32)
    xs = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    sizes = jnp.asarray([3, 0, 20, 1], jnp.int32)

    def two_layers(xs, sizes):
        y = pallas_kernels.grouped_expert_ffn(xs, sizes, gate, up, down)
        return pallas_kernels.grouped_expert_ffn(
            y.astype(xs.dtype), sizes, gate, up, down)

    text = jax.jit(two_layers).lower(xs, sizes).as_text()
    assert text.count("call @_grouped_expert_ffn") == 2
    assert len([line for line in text.splitlines()
                if "func.func private @_grouped_expert_ffn" in line]) == 1


def test_pallas_variant_eligible_at_lane_aligned_widths(monkeypatch):
    op = get_op("MoEFFN")
    attrs = op.normalize_attrs(dict(num_experts=64, num_hidden=1024,
                                    top_k=8))
    def shapes(D, F):
        return [(8, D), (64, D), (64, D, F), (64, D, F), (64, F, D), (4,)]
    dtypes = ["bfloat16"] * 5 + ["int32"]
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    assert op.variant_eligible("pallas", attrs, shapes(2048, 1024), dtypes)
    assert not op.variant_eligible("pallas", attrs, shapes(2048, 1000),
                                   dtypes)
    assert not op.variant_eligible("pallas", attrs, shapes(96, 1024), dtypes)
    assert not op.variant_eligible("pallas", attrs, shapes(2048, 1024),
                                   ["int8"] * 5 + ["int32"])


# -------------------------- the decoder through the window and the cache
def _builder_kwargs(cfg=CFG):
    return dict(vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
                n_layer=cfg["num_hidden_layers"],
                n_head=cfg["num_attention_heads"], pos_embed="rotary",
                rope_base=float(cfg["rope_theta"]), block="olmoe",
                n_expert=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                expert_width=cfg["intermediate_size"],
                norm_topk=cfg["norm_topk_prob"],
                rms_eps=cfg["rms_norm_eps"], tie_head=False,
                embed_scale=False)


def _decode_symbol(step_len, cfg=CFG, with_routing=True):
    s = tfm.get_decode_symbol(capacity=CAPACITY, per_slot=True,
                              step_len=step_len, **_builder_kwargs(cfg))
    if not with_routing:
        return s
    inner = s.get_internals()
    return mx.sym.Group([s] + [inner[f"lm_l{i}_moe_experts"]
                               for i in range(cfg["num_hidden_layers"])])


def _params(cfg=CFG, seed=11, dtype="float32"):
    from chipbench import weights
    return weights.normal_init(_decode_symbol(1, cfg, False),
                               {"data": (2, 1), "fed": (2,)}, seed,
                               dtype=dtype)


def _driver(params, compute_dtype=None, slots=2, cfg=CFG):
    """A two-slot pool with its window program, outputs = logits and
    every layer's chosen experts."""
    def bound(step_len, shared=None):
        mod = mx.mod.Module(_decode_symbol(step_len, cfg),
                            data_names=("data", "fed"), label_names=[],
                            compute_dtype=compute_dtype)
        mod.bind([mx.io.DataDesc("data", (slots, step_len), np.int32),
                  mx.io.DataDesc("fed", (slots,), np.int32)],
                 None, for_training=False, shared_module=shared)
        if shared is None:
            mod.init_params(initializer=None, arg_params=dict(params),
                            aux_params={}, allow_missing=True)
        return mod
    base = bound(1)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=slots)
    drv.add_window(WINDOW, bound(WINDOW, shared=base))
    return drv, base


def _serve(params, seqs, compute_dtype=None, cfg=CFG):
    """Prefill through the window program, then S=1 decode through the
    cache: slot 0 runs sequence 0 from position 0; slot 1 first takes a
    window of other tokens, is rewound to 0, and runs sequence 1 one
    window behind slot 0 - two slots at different positions, a rewind
    in between. Returns ``(logits (2, T, V), experts (L, 2, T, k))``
    of every position of both sequences."""
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    n_seq, T = seqs.shape
    n_pre = (T // WINDOW - 1) * WINDOW       # the rest decodes at S=1
    drv, base = _driver(params, compute_dtype, cfg=cfg)
    mods = {1: base, WINDOW: drv._windows[WINDOW]}
    logits = np.zeros((n_seq, T, cfg["vocab_size"]), np.float32)
    experts = np.zeros((L, n_seq, T, k), np.int32)

    def step(tokens, rows):                  # rows: {slot: first position}
        S = tokens.shape[1]
        out = drv.step(tokens).asnumpy().astype(np.float32)
        routed = [o.asnumpy().reshape(2, S, k)
                  for o in mods[S].get_outputs()[1:]]
        for slot, t0 in rows.items():
            logits[slot, t0:t0 + S] = out[slot]
            for layer in range(L):
                experts[layer, slot, t0:t0 + S] = routed[layer][slot]

    drv.join(0), drv.join(1)
    junk = np.full((WINDOW,), 7, np.int32)
    step(np.stack([seqs[0, :WINDOW], junk]), {0: 0})
    drv.rewind(1, 0)                         # slot 1 starts over
    for w in range(1, n_pre // WINDOW + 1):
        a = seqs[0, w * WINDOW:(w + 1) * WINDOW] if w * WINDOW < n_pre \
            else None
        b = seqs[1, (w - 1) * WINDOW:w * WINDOW]
        if a is None:                        # slot 0 has left the windows:
            step(np.stack([junk, b]), {1: (w - 1) * WINDOW})
            drv.rewind(0, n_pre)             # it rode along; pull it back
        else:
            step(np.stack([a, b]), {0: w * WINDOW, 1: (w - 1) * WINDOW})
    assert list(drv.pos) == [n_pre, n_pre]
    for t in range(n_pre, T):
        step(seqs[:, t:t + 1], {0: t, 1: t})
    return logits, experts


#: float32 served against the float32 reference, both summing 64- and
#: 32-term products of O(1) values through 2 layers to logits of
#: magnitude up to 0.8: a few float32 ulps of the largest partial sums
#: (measured here: 1.5e-7 to 6e-7). Forced to the served path's routing
#: the comparison keeps the same rounding but no discontinuity, so it is
#: held several times tighter; free, a flipped decision would add an
#: expert's share of the output (1e-3 and more), which neither bound
#: lets through - at float32 no decision flips at this size.
TOL_FREE, TOL_FORCED = 1e-5, 2e-6


@pytest.mark.parametrize("big", [False, True],
                         ids=["top2_of_8", "top8_of_64"])
def test_prefill_then_decode_matches_the_reference_full_forward(big):
    cfg = dict(CFG, num_experts=64, num_experts_per_tok=8) if big else CFG
    params = _params(cfg)
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, cfg["vocab_size"], (2, 4 * WINDOW)).astype(
        np.int32)
    got, routed = _serve(params, seqs, cfg=cfg)
    want, chosen = ref.forward(params, jnp.asarray(seqs), cfg,
                               return_routing=True)
    forced = ref.forward(params, jnp.asarray(seqs), cfg,
                         routing=jnp.asarray(routed))
    flips = float(ref.routing_flip_share(routed, chosen))
    err_free = np.abs(got - np.asarray(want)).max()
    err_forced = np.abs(got - np.asarray(forced)).max()
    print(f"routing decisions that differ: {flips:.4f}; max error free "
          f"{err_free:.2e}, forced {err_forced:.2e}")
    assert flips <= 0.01
    assert err_forced <= TOL_FORCED
    assert err_free <= TOL_FREE

    # the control: the same parameters served at bfloat16 where float32
    # is stated must NOT pass
    low, low_routed = _serve(
        {n: np.asarray(v).astype(jnp.bfloat16) for n, v in params.items()},
        seqs, compute_dtype="bfloat16", cfg=cfg)
    assert np.abs(low - np.asarray(want)).max() > 20 * TOL_FREE
    forced_low = ref.forward(params, jnp.asarray(seqs), cfg,
                             routing=jnp.asarray(low_routed))
    assert np.abs(low - np.asarray(forced_low)).max() > 20 * TOL_FORCED


def test_decode_symbol_matches_the_programs_own_full_forward():
    """``get_symbol`` gains the same block: the cache path against the
    program's own full-sequence graph (``attention`` + ``RoPE``)."""
    params = _params()
    seqs = np.random.default_rng(4).integers(
        0, CFG["vocab_size"], (2, 4 * WINDOW)).astype(np.int32)
    got, _ = _serve(params, seqs)
    full = mx.mod.Module(
        tfm.get_symbol(seq_len=seqs.shape[1], include_loss=False,
                       **_builder_kwargs()),
        data_names=("data",), label_names=[])
    full.bind([mx.io.DataDesc("data", seqs.shape, np.int32)], None,
              for_training=False)
    full.init_params(initializer=None, arg_params=dict(params),
                     aux_params={}, allow_missing=True)
    full.forward(mx.io.DataBatch(data=[mx.nd.array(seqs)], label=[]),
                 is_train=False)
    want = full.get_outputs()[0].asnumpy()
    assert "pos_ids" not in _decode_symbol(1, with_routing=False) \
        .list_arguments()
    np.testing.assert_allclose(got, want, atol=TOL_FREE, rtol=0)


def test_the_gpt2_block_is_untouched_by_the_new_one():
    base = dict(vocab_size=64, d_model=32, n_layer=2, n_head=4)
    s = tfm.get_decode_symbol(capacity=16, per_slot=True, **base)
    ops = {n.op for n in s._topo_nodes() if not n.is_variable}
    assert "LayerNorm" in ops and "FusedBiasGeLU" in ops
    assert not ops & {"RMSNorm", "MoEFFN"}
    assert "lm_head_weight" not in s.list_arguments()
    with pytest.raises(mx.base.MXNetError, match="olmoe"):
        tfm.get_decode_symbol(block="olmoe", **base)       # no experts
    with pytest.raises(mx.base.MXNetError, match="rotary"):
        tfm.get_decode_symbol(block="olmoe", pos_embed="learned",
                              n_expert=8, top_k=2, expert_width=16, **base)


# --------------------------------------- counters through serve_decoder
def test_serve_decoder_counts_where_the_tokens_went():
    from mxnet_tpu import telemetry
    gen = lambda s: _decode_symbol(s, with_routing=False)   # noqa: E731
    sched = mx.serve.serve_decoder(
        gen(1), _params(), name="tiny-olmoe-counts", capacity=CAPACITY,
        ladder=[1, 2], context=mx.cpu(0), symbol_gen=gen,
        prefill_chunk=WINDOW, start=False)
    h = sched.submit(list(range(1, 21)), max_new_tokens=4)
    sched.pump(max_iterations=20)
    assert len(h.result(timeout=5)) == 4
    sched.stop()
    got = {m.name: m.value for m in telemetry.metrics.all_metrics()
           if isinstance(m, telemetry.Counter)
           and ("model", "tiny-olmoe-counts") in m.labels
           and m.name.startswith("serve.decode.moe.")}
    its = sched.iterations
    L, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    assert got["serve.decode.moe.layer_steps"] == its * L
    # three prefill windows of 8 and three S=1 steps on rung 1: the
    # prompt's 20 tokens and the three fed back, no pad among them
    assert got["serve.decode.moe.assignments"] == (20 + 3) * L * k
    assert 0 < got["serve.decode.moe.experts_touched"] <= its * L * 8
    assert got["serve.decode.moe.max_expert_load"] >= its * L
    step = [r for r in telemetry.flightrec.get_records()
            if r.get("kind") == "serve.decode.step"
            and r.get("model") == "tiny-olmoe-counts"]
    assert all(r["moe_layer_steps"] == L and 0 < r["moe_touched"] <= L * 8
               for r in step) and len(step) == its


# ------------------------------------------ bind at the dtype given
def _lowered_s1(exe):
    """The step program of a bound executor (the S=1 one, or a window's)
    as StableHLO text."""
    def prog(arg_vals, aux_vals):
        return exe._runner(arg_vals, aux_vals, False, None)
    return jax.jit(prog).lower(exe._arg_vals(), exe._aux_vals()).as_text()


def _engine(params, compute_dtype, gen, name="bind-dtype"):
    from mxnet_tpu.serve.decode import DecodeEngine
    return DecodeEngine(name, gen(1), params, capacity=CAPACITY,
                        ladder=[2], context=mx.cpu(0),
                        compute_dtype=compute_dtype, symbol_gen=gen,
                        window_lens=(WINDOW,))


def _param_dtypes(mod, inputs=("data", "fed")):
    return {n: str(c.dtype)
            for n, c in mod._exec_group.executor.arg_dict.items()
            if n not in inputs}


@pytest.mark.parametrize("handed", ["bfloat16", "float32"])
def test_bfloat16_parameters_bind_at_bfloat16_and_are_not_cast(handed):
    """A serving binding holds every floating parameter at the compute
    width: handed at it (PR 28) or handed float32 and cast once at bind
    (PR 37) - the leader's cells and every window module's, and neither
    the S=1 program nor a window program takes a float32 argument or
    converts a matrix."""
    gen = lambda s: _decode_symbol(s, with_routing=False)   # noqa: E731
    eng = _engine(_params(dtype=handed), "bfloat16", gen)
    mods = [eng._bm._leader] + list(eng._window_mods.values())
    assert len(mods) == 2
    for mod in mods:
        dtypes = _param_dtypes(mod)
        assert set(dtypes.values()) == {"bfloat16"}, dtypes
        text = _lowered_s1(mod._exec_group.executor)
        # no matrix among the arguments is converted: only the norm
        # gains, upcast for their float32 statistics, are
        converted = re.findall(
            r"stablehlo\.convert %arg\d+ : "
            r"\(tensor<([0-9x]*)x(?:bf16|f32)>\)", text)
        assert all("x" not in dims for dims in converted), converted
        signature = text[text.index("@main("):].split(") -> ", 1)[0]
        assert "xbf16>" in signature and "xf32>" not in signature, \
            "a float32 argument"
    # what the module hands back is what it serves
    args, _aux = eng._bm.get_params()
    assert {str(v.dtype) for v in args.values()} == {"bfloat16"}


def test_float32_parameters_bind_as_before():
    """The training contract (PR 28's rule): a ``Module`` keeps float32
    masters for float32 parameters and re-allocates a cell only for a
    parameter handed over at the compute width. ``DecodeEngine`` alone
    narrows what it is handed (the test above)."""
    gen = lambda s: _decode_symbol(s, with_routing=False)   # noqa: E731
    mod = mx.mod.Module(gen(1), data_names=("data", "fed"), label_names=[],
                        compute_dtype="bfloat16")
    mod.bind([mx.io.DataDesc("data", (2, 1), np.int32),
              mx.io.DataDesc("fed", (2,), np.int32)], None,
             for_training=False)
    mod.init_params(initializer=None, arg_params=_params(),
                    aux_params={}, allow_missing=True)
    assert set(_param_dtypes(mod).values()) == {"float32"}
    # and a module bound first and handed bfloat16 parameters afterwards
    # re-allocates the cells (Module.init_params)
    mod = mx.mod.Module(gen(1), data_names=("data", "fed"), label_names=[],
                        compute_dtype="bfloat16")
    mod.bind([mx.io.DataDesc("data", (2, 1), np.int32),
              mx.io.DataDesc("fed", (2,), np.int32)], None,
             for_training=False)
    cells = mod._exec_group.executor.arg_dict
    assert str(cells["lm_head_weight"].dtype) == "float32"
    mod.init_params(initializer=None, arg_params=_params(dtype="bfloat16"),
                    aux_params={}, allow_missing=True)
    assert {str(c.dtype) for n, c in cells.items()
            if n not in ("data", "fed")} == {"bfloat16"}
    key = mod._exec_group.executor.program_cache_key("fwd_infer")
    assert ("lm_head_weight", (128, 64), "bfloat16") in key[1]


def test_a_training_binding_keeps_its_float32_masters():
    """``for_training=True`` under ``compute_dtype="bfloat16"``, handed
    float32: the cells stay float32 (the optimizer updates masters; the
    cast has a gradient) and so do the gradients."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, compute_dtype="bfloat16")
    mod.bind([("data", (4, 16))], [("softmax_label", (4,))],
             for_training=True)
    rng = np.random.default_rng(0)
    mod.init_params(initializer=None, arg_params={
        "fc_weight": rng.normal(size=(8, 16)).astype(np.float32),
        "fc_bias": np.zeros(8, np.float32)}, aux_params={})
    exe = mod._exec_group.executor
    assert _param_dtypes(mod, ("data", "softmax_label")) == \
        {"fc_weight": "float32", "fc_bias": "float32"}
    assert {str(g.dtype) for g in exe.grad_dict.values()
            if g is not None} == {"float32"}
    args, _aux = mod.get_params()
    assert {str(v.dtype) for v in args.values()} == {"float32"}


#: sha256 of the lowered S=1 program of the Cerebras-shaped tiny decoder
#: below (learned positions, parameters handed float32, compute_dtype
#: bfloat16): a PR that leaves the GPT-2 block's program alone leaves
#: these bytes alone, and the Cerebras cells hit their compile cache. A
#: PR that changes that program on purpose computes it anew: PR 33 did
#: (the cache write is one window update a slot, no longer a
#: ``jnp.where`` over the pool); before it the bytes were PR 28's
#: parent's (d8b22cc). PR 37 did (a serving binding narrows its
#: parameters once, at bind: every parameter argument is ``bf16`` and
#: the converts of the float32 masters are gone; until then
#: 9a63a96316cb...e367ab). ISSUE 47 did (the slot-pooled graph takes
#: ``fed``: one more ``(slots,)`` argument, the cursor advanced by it;
#: and ``DecodeEngine`` binds ``pos_ids`` as int32, no longer a float32
#: that the graph's entry cast to bfloat16; until then
#: 8cee1c781915...b3eda).
GPT2_S1_SHA256 = \
    "0f159ffaae0ac3ad050d47a44dd6d08b004415e200d2660a15e80b3e450c7b13"

_GPT2_KW = dict(vocab_size=96, d_model=64, n_layer=2, n_head=4,
                pos_embed="learned", capacity=32, max_seq_len=32,
                per_slot=True)


def _gpt2_gen(step_len):
    return tfm.get_decode_symbol(step_len=step_len, **_GPT2_KW)


def _gpt2_params(seed=5):
    from chipbench import weights
    return weights.normal_init(
        _gpt2_gen(1), {"data": (2, 1), "pos_ids": (2, 1), "fed": (2,)}, seed)


def _gpt2_engine(params, name="gpt2-pin"):
    from mxnet_tpu.serve.decode import DecodeEngine
    return DecodeEngine(name, _gpt2_gen(1), params, capacity=32,
                        ladder=[2], context=mx.cpu(0),
                        compute_dtype="bfloat16", symbol_gen=_gpt2_gen,
                        window_lens=(8,))


def _gpt2_lowered_s1():
    eng = _gpt2_engine(_gpt2_params())
    return _lowered_s1(eng._bm._leader._exec_group.executor)


def test_the_cerebras_shaped_step_program_is_the_parents_bytes():
    text = _gpt2_lowered_s1()
    assert hashlib.sha256(text.encode()).hexdigest() == GPT2_S1_SHA256


def _gpt2_logits(eng, seed=3):
    """A window of 8 tokens, then three S=1 steps, on both slots: the
    raw bytes of every step's logits."""
    drv = eng.driver(2)
    drv.leave(0), drv.leave(1)
    drv.join(0), drv.join(1)
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, _GPT2_KW["vocab_size"], (2, 11)).astype(np.int32)
    outs = [drv.step(toks[:, :8]).asnumpy()]
    outs += [drv.step(toks[:, t:t + 1]).asnumpy() for t in range(8, 11)]
    return outs


def _precast(params):
    """The parameters as PR 28's path takes them: cast on the device by
    the convert a step program ran."""
    return {n: jnp.asarray(v).astype(jnp.bfloat16)
            for n, v in params.items()}


def test_narrowing_at_bind_serves_the_same_bits():
    """Float32 parameters narrowed at bind against the same values
    handed over pre-cast with ``astype(bfloat16)``: the programs are
    the same text and every logit, through a window and at S=1, is
    bit-equal."""
    params = _gpt2_params()
    narrowed = _gpt2_engine(params, name="gpt2-narrowed")
    handed = _gpt2_engine(_precast(params), name="gpt2-handed")
    assert narrowed.params_narrowed == len(params)
    assert handed.params_narrowed == 0
    for a, b in zip([narrowed._bm._leader, narrowed._window_mods[2, 8]],
                    [handed._bm._leader, handed._window_mods[2, 8]]):
        assert _lowered_s1(a._exec_group.executor) == \
            _lowered_s1(b._exec_group.executor)
    got, want = _gpt2_logits(narrowed), _gpt2_logits(handed)
    assert [g.shape for g in got] == [(2, 8, 96)] + [(2, 1, 96)] * 3
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    assert np.abs(got[-1].astype(np.float32)).max() > 0


@pytest.mark.parametrize("swapped_in", ["float32", "bfloat16"])
def test_a_hot_swap_stores_into_the_narrow_cells(swapped_in):
    """New values into a warm engine, float32 or pre-cast: cast as they
    are stored, no cell re-allocated at float32, no program re-keyed,
    and the new values are what is served."""
    eng = _gpt2_engine(_gpt2_params(5), name=f"gpt2-swap-{swapped_in}")
    leader = eng._bm._leader
    keys = eng.program_keys()
    cells = dict(leader._exec_group.executor.arg_dict)
    before = _gpt2_logits(eng)
    new = _gpt2_params(6)
    handed = new if swapped_in == "float32" else _precast(new)
    leader._exec_group.adopt_param_dtypes(handed)     # init_params' step
    leader.set_params(handed, {}, allow_missing=True)
    after_cells = leader._exec_group.executor.arg_dict
    assert all(after_cells[n] is c for n, c in cells.items())
    for mod in [leader] + list(eng._window_mods.values()):
        assert set(_param_dtypes(mod, eng.data_names).values()) == \
            {"bfloat16"}
    assert eng.program_keys() == keys
    got = _gpt2_logits(eng)
    want = _gpt2_logits(_gpt2_engine(_precast(new),
                                     name=f"gpt2-swapped-{swapped_in}"))
    for g, w, b in zip(got, want, before):
        assert g.tobytes() == w.tobytes() and g.tobytes() != b.tobytes()
    args, _aux = leader.get_params()
    assert {str(v.dtype) for v in args.values()} == {"bfloat16"}


@pytest.mark.parametrize("handed,narrowed", [("bfloat16", 0),
                                             ("float32", None)])
def test_the_engine_says_what_it_holds_and_what_it_narrowed(handed,
                                                            narrowed):
    """``serve.decode.params.bytes{dtype=}`` and
    ``serve.decode.params.narrowed``, set once at bind and shown by
    ``stats()``: nothing narrowed for parameters handed at the compute
    width, every floating parameter for float32 ones."""
    from mxnet_tpu import telemetry
    gen = lambda s: _decode_symbol(s, with_routing=False)   # noqa: E731
    params = _params(dtype=handed)
    name = f"tiny-olmoe-params-{handed}"
    sched = mx.serve.serve_decoder(
        gen(1), params, name=name, capacity=CAPACITY, ladder=[2],
        context=mx.cpu(0), compute_dtype="bfloat16", symbol_gen=gen,
        prefill_chunk=WINDOW, start=False)
    sched.stop()
    n_bytes = 2 * sum(int(np.prod(v.shape)) for v in params.values())
    want = len(params) if narrowed is None else narrowed
    stats = sched.stats()
    assert stats["params_bytes"] == {"bfloat16": n_bytes}
    assert stats["params_narrowed"] == want
    gauge = telemetry.get_metric("serve.decode.params.bytes", model=name,
                                 dtype="bfloat16")
    assert gauge.value == n_bytes
    assert telemetry.get_metric("serve.decode.params.bytes", model=name,
                                dtype="float32") is None
    assert telemetry.get_metric("serve.decode.params.narrowed",
                                model=name).value == want


# --------------------------------------- a chip's share of a wider layer
def _share_layer(rng, T, D, F, E, Fs):
    """Inputs of the whole sigmoid-routed layer with a shared expert,
    and the reference's parameter names for them."""
    x, router, gate, up, down = _moe_inputs(rng, T, D, F, E)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.3, jnp.float32)
    shared = [jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
              for s in ((D, Fs), (D, Fs), (Fs, D))]
    named = dict(zip(
        ["p_moe_router_weight", "p_moe_router_bias", "p_moe_gate_weight",
         "p_moe_up_weight", "p_moe_down_weight", "p_moe_shared_gate_weight",
         "p_moe_shared_up_weight", "p_moe_shared_down_weight"],
        [router, bias, gate, up, down] + shared))
    return x, router, bias, (gate, up, down), shared, named


_SHARE = dict(top_k=4, norm_topk=True, scoring="sigmoid", router_bias=True,
              scaling=2.5)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(variant):
    """Thirty-two experts divided over sixteen shares of two: every
    share routes over all 32, computes its own experts' part and adds
    the shared expert; the parts, with the shared expert counted once,
    equal the reference's uncut layer (chipbench/reference/glm_dsa.py).
    The held assignments of all shares are all the assignments."""
    from chipbench.reference import glm_dsa
    rng = np.random.default_rng(3)
    T, D, F, E, Fs = 40, 64, 32, 32, 48
    x, router, bias, experts, shared, named = _share_layer(rng, T, D, F, E,
                                                           Fs)
    cfg = {"n_routed_experts": E, "num_experts_per_tok": 4,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        routed, alone, chosen = glm_dsa.expert_layer(
            x, "p", named, cfg, lambda a: a, held=(0, E))
    total, landed = np.zeros((T, D), np.float32), 0
    for first in range(0, E, 2):
        held = [w[first:first + 2] for w in experts]
        out, picked, stats = _run(
            variant, dict(num_experts=E, num_hidden=F, held_first=first,
                          held_count=2, shared_hidden=Fs, **_SHARE),
            [x, router, bias] + held + shared)
        total += out
        landed += stats[4]
        assert stats[1] == T * 4 and stats[2] <= 2
        np.testing.assert_array_equal(np.sort(picked, -1),
                                      np.sort(np.asarray(chosen), -1))
    assert landed == T * 4
    np.testing.assert_allclose(total - 15 * np.asarray(alone),
                               np.asarray(routed + alone), atol=2e-4,
                               rtol=2e-4)


def test_the_bias_steers_the_choice_and_never_the_weights():
    """A bias of +10 on expert 5 puts it among every token's choices;
    the weights are the sigmoid scores of the chosen, normalised and
    scaled, with no trace of the bias."""
    rng = np.random.default_rng(4)
    T, D, E, k = 16, 64, 8, 2
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(E, D)) * 0.5, jnp.float32)
    bias = jnp.zeros((E,), jnp.float32).at[5].set(10.0)
    w, chosen = moe.moe_route_sigmoid(x, router, bias, k, True, 2.5)
    assert np.all(np.any(np.asarray(chosen) == 5, axis=1))
    score = 1.0 / (1.0 + np.exp(-np.asarray(x) @ np.asarray(router).T))
    picked = np.take_along_axis(score, np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)
    # without the bias, expert 5 is chosen only where its score says so
    _, free = moe.moe_route_sigmoid(x, router, None, k, True, 2.5)
    assert not np.all(np.any(np.asarray(free) == 5, axis=1))


def test_holding_all_experts_without_a_shared_one_is_the_old_layer():
    """The OLMoE guard: the share attributes at their neutral values
    (all experts held, softmax scores, no bias, no shared expert, a
    scaling of 1) take the layer's old path - the same program, the
    same output to the bit, four counts."""
    rng = np.random.default_rng(5)
    inputs = _moe_inputs(rng, 24, 64, 32, 8)
    plain = dict(num_experts=8, num_hidden=32, top_k=2)
    spelt = dict(plain, held_first=0, held_count=8, scoring="softmax",
                 router_bias=False, scaling=1.0, shared_hidden=0)
    op = get_op("MoEFFN")
    assert moe._share_spec(op.normalize_attrs(spelt)) is None
    assert op.input_names(op.normalize_attrs(spelt)) == [
        "data", "router_weight", "gate_weight", "up_weight", "down_weight"]
    for variant in ("xla", "pallas"):
        a, b = _run(variant, plain, inputs), _run(variant, spelt, inputs)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert a[2].shape == (4,)

    def lowered(attrs):
        attrs = op.normalize_attrs(attrs)
        return jax.jit(lambda i: op.forward(
            attrs, i, [jnp.zeros((4,), jnp.int32)], False, None)
        ).lower(inputs).as_text()
    assert lowered(plain) == lowered(spelt)


def test_a_load_past_one_segment_takes_further_trips():
    """Every token routed to the two held experts (8 x the expected
    load): the loop over segments of the sorted held rows takes as many
    trips as the load needs and drops nothing."""
    rng = np.random.default_rng(6)
    T, D, F, E = 1024, 64, 32, 32
    x, router, gate, up, down = _moe_inputs(rng, T, D, F, E)
    bias = jnp.zeros((E,), jnp.float32).at[jnp.asarray([6, 7])].set(10.0)
    attrs = dict(num_experts=E, num_hidden=F, top_k=2, norm_topk=True,
                 scoring="sigmoid", router_bias=True, held_first=6,
                 held_count=2)
    assert moe._segment_rows(T * 2) == 1024
    out, picked, stats = _run("xla", attrs,
                              [x, router, bias, gate[6:8], up[6:8],
                               down[6:8]])
    assert stats[4] == 2 * T and set(np.unique(picked)) == {6, 7}
    score = 1.0 / (1.0 + np.exp(-np.asarray(x) @ np.asarray(router).T))
    w = score[:, 6:8] / score[:, 6:8].sum(1, keepdims=True)
    want = sum(w[:, e:e + 1] * np.asarray(
        (jax.nn.silu(x @ gate[6 + e]) * (x @ up[6 + e])) @ down[6 + e])
        for e in range(2))
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("fed,rows", [([8, 3, 0], 8), ([11], 24),
                                      ([1, 1, 1], 1)])
def test_the_plain_layer_routes_the_pads_of_a_window_to_a_dead_group(
        variant, fed, rows):
    """ISSUE 47: ``step_len`` alone (OLMoE's slot-pooled graph) makes no
    share - the plain layer takes ``fed``, keeps ``moe_sort`` and
    ``moe_combine``, and sends a pad's choices to a group behind the
    last expert: the real rows come out as they do without pads, bit
    for bit, a pad's row is zero, and only the real rows' assignments
    are counted. Slots of ``rows`` rows (a window, or an S = 1 step),
    or one block of packed rows under one count."""
    rng = np.random.default_rng(47)
    D, F, E, k = 64, 32, 8, 2
    T = rows * len(fed)
    inputs = _moe_inputs(rng, T, D, F, E)
    attrs = dict(num_experts=E, num_hidden=F, top_k=k)
    op = get_op("MoEFFN")
    fed_attrs = op.normalize_attrs(dict(attrs, step_len=rows))
    assert op.input_names(fed_attrs)[:3] == ["data", "fed", "router_weight"]
    assert moe._share_spec(fed_attrs) is None
    every, picked_every, stats_every = _run(variant, attrs, inputs)
    out, picked, stats = _run(
        variant, dict(attrs, step_len=rows),
        inputs[:1] + [jnp.asarray(fed, jnp.int32)] + inputs[1:])
    real = (np.arange(rows)[None, :] < np.asarray(fed)[:, None]).reshape(-1)
    np.testing.assert_array_equal(out[real], every[real])
    assert not out[~real].any() and np.isfinite(out).all()
    np.testing.assert_array_equal(picked, picked_every)   # the router's
    assert stats.shape == (4,) and stats[1] == k * real.sum()
    assert stats_every[1] == k * T and stats[2] <= stats_every[2]
    assert stats[3] == np.bincount(picked[real].reshape(-1)).max()


def test_pads_of_a_window_are_routed_nowhere():
    """``step_len`` with ``fed``: the rows past each slot's real tokens
    take no routed expert and count nowhere - their output is the
    shared expert's alone -, and the real rows' outputs are what they
    are without the pads."""
    rng = np.random.default_rng(7)
    slots, S, D, F, E, Fs = 3, 8, 64, 32, 16, 48
    x, router, bias, experts, shared, _ = _share_layer(rng, slots * S, D, F,
                                                       E, Fs)
    held = [w[4:8] for w in experts]
    attrs = dict(num_experts=E, num_hidden=F, held_first=4, held_count=4,
                 shared_hidden=Fs, **_SHARE)
    fed = jnp.asarray([8, 3, 0], jnp.int32)
    out, _, stats = _run("xla", dict(attrs, step_len=S),
                         [x, fed, router, bias] + held + shared)
    every, _, all_stats = _run("xla", attrs, [x, router, bias] + held
                               + shared)
    real = (np.arange(S)[None, :] < np.asarray(fed)[:, None]).reshape(-1)
    np.testing.assert_array_equal(out[real], every[real])
    alone = np.asarray(moe._dense_expert(x, *shared))
    np.testing.assert_allclose(out[~real], alone[~real], atol=1e-6)
    assert stats[1] == 11 * 4 and all_stats[1] == slots * S * 4
    assert 0 < stats[4] < all_stats[4]
    op = get_op("MoEFFN")
    assert op.input_names(op.normalize_attrs(dict(attrs, step_len=S)))[:3] \
        == ["data", "fed", "router_weight"]
