"""Ling-3.0's block (``block="ling_hybrid"``, ISSUE 52) on the CPU at
tiny sizes: ``ops/kda.py`` - the chunked (WY) form against the delta
rule step by step, the plain forward against the ``pallas`` variant in
interpret mode - and the block through the slot-pooled driver, the
engine, the scheduler and ``serve_decoder`` against the benchmark's
plain reference (``chipbench/reference/ling_hybrid.py``): prefill in
chunks, then decode, riders beside a window; three families of state in
one graph; what ``_ling_spec`` refuses; the four shares of an expert
layer adding up; a decay near 1 that a bfloat16 state fails."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import kda
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.archs import ling_hybrid as arch  # noqa: E402
from chipbench.reference import ling_hybrid as ref  # noqa: E402
# the benchmark's own tests of the architecture file run here as they
# stand, the cell's two CPU rehearsals (the new architecture resolving
# and rehearsing as files at a tiny size) with them
from chipbench.tests.test_ling_hybrid import (  # noqa: E402,F401
    copy_with_ling, test_tiny_ling_rehearses,
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_draws_decays_that_span_the_bound,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_but_for_what_reduced_lists,
    test_the_traffic_is_the_issues)

#: the published keys at a tiny size: published layers 0, 3, 4, 5 of a
#: model in groups of three (KDA with the dense feed-forward, then KDA,
#: KDA, latent attention with experts)
CFG = {"vocab_size": 96, "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 8, "num_hidden_layers": 4,
       "layers_run": [0, 3, 4, 5], "layer_group_size": 3,
       "short_conv_kernel_size": 4, "kda_lower_bound": -5,
       "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
       "linear_silu": True, "group_norm_size": 1,
       "num_kv_heads_for_linear_attn": 0, "q_lora_rank": None,
       "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
       "v_head_dim": 8, "rope_scaling": None, "rope_theta": 6000000,
       "gated_attention_proj_granularity_type": "head_wise",
       "use_mla_nope": False, "first_k_dense_replace": 1,
       "intermediate_size": 48, "moe_intermediate_size": 16,
       "moe_shared_expert_intermediate_size": 16, "num_experts": 16,
       "num_experts_per_tok": 4, "num_shared_experts": 1,
       "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 4,
       "topk_group": 2, "moe_router_enable_expert_bias": True,
       "scale_router_input": False,
       "expert_swiglu_limit_list": [0] * 6,
       "share_expert_swiglu_limit_list": [0] * 6, "up_proj_norm": False,
       "value_norm": False, "use_nGPT": False, "rms_norm_eps": 1e-6,
       "num_experts_held": 8, "held_first": 4, "kda_chunk": 8}
CAPACITY, WINDOW, SLOTS = 128, 16, 3            # WINDOW: the S > 1 program
#: float32 served against the float32 reference through 4 layers, on
#: logits of magnitude about 1 (measured here: 1e-6 to 3e-5; the chunked
#: form sums in another order than the recurrence)
TOL = 2e-4


def _symbol(step_len, cfg=CFG, capacity=CAPACITY, **ling):
    return tfm.get_decode_symbol(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layer=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"], capacity=capacity,
        step_len=step_len, per_slot=True, block="ling_hybrid",
        rope_base=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_head=False, embed_scale=False, ling=dict(arch._ling(cfg), **ling))


def _params(cfg=CFG, seed=5, log_decay=None):
    """Matrices of deviation 0.25, gains about 1; KDA's own so that the
    decays span the bound (``A_log`` about 0, ``dt_bias = U(-6, 3)``),
    or with ``log_decay`` the same log decay for every channel and
    token (the decay's projection zero, ``A_log`` zero)."""
    symbol = _symbol(1, cfg)
    shapes, _, _ = symbol.infer_shape(data=(SLOTS, 1), fed=(SLOTS,))
    rng = np.random.default_rng(seed)
    HD = cfg["num_attention_heads"] * cfg["head_dim"]
    out = {}
    for name, shape in zip(symbol.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        draw = rng.standard_normal(shape)
        if name.endswith(("_gamma", "_kv_norm_weight", "_kda_norm_weight")):
            draw = 1.0 + 0.3 * draw
        elif name.endswith("_kda_A_log"):
            draw = 0.0 * draw if log_decay else 0.3 * draw
        elif name.endswith("_kda_dt_bias"):
            share = (log_decay or 0.0) / cfg["kda_lower_bound"]
            draw = np.full(shape, np.log(share / (1.0 - share))) \
                if log_decay else rng.uniform(-6.0, 3.0, shape)
        else:
            draw = 0.25 * draw
            if log_decay and name.endswith("_kda_in_weight"):
                draw[3 * HD:4 * HD] = 0.0           # f = 0
        out[name] = draw.astype(np.float32)
    return out


PARAMS = _params()


def _bound(symbol, step_len, shared=None, slots=SLOTS, params=PARAMS):
    mod = mx.mod.Module(symbol, data_names=("data", "fed"), label_names=[])
    mod.bind([mx.io.DataDesc("data", (slots, step_len), np.int32),
              mx.io.DataDesc("fed", (slots,), np.int32)],
             None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None, arg_params=dict(params),
                        aux_params={}, allow_missing=True)
    return mod


def _driver(cfg=CFG, params=PARAMS, capacity=CAPACITY):
    base = _bound(_symbol(1, cfg, capacity), 1, params=params)
    drv = tfm.BatchedKVCacheDecoder(base, capacity, slots=SLOTS)
    window = _symbol(WINDOW, cfg, capacity)
    packed, budget = tfm.packed_window(window, SLOTS)
    assert budget == 24
    drv.add_window(WINDOW, _bound(window, WINDOW, shared=base),
                   packed=(_bound(packed, WINDOW, shared=base), budget))
    return drv


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request):
    """A three-slot pool with its S = 16 window program, whole and
    packed (24 rows), under one kernel tier (``kda_update``,
    ``kda_chunk`` and the latent-attention kernels in interpret
    mode)."""
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


def _run(drv, seqs, schedule, start=None, vocab=CFG["vocab_size"]):
    """Feed ``seqs`` (slots, T) through ``schedule``, a list of (S, fed
    counts a slot): the logits of every fed position that a dispatch
    hands back, (slots, T, V) - all of an S = 1 step's and a
    whole-window program's, of a packed window's each slot's last fed
    row alone (the others stay NaN; ``_err`` compares what is there) -
    and the rows each dispatch's program ran over. Every slot joins
    fresh first, or goes on from ``start``; a pad is a junk token."""
    if start is None:
        for slot in range(drv.slots):
            if drv.active[slot]:
                drv.leave(slot)
            drv.join(slot)
        start = [0] * drv.slots
    got = np.full(seqs.shape + (vocab,), np.nan, np.float32)
    at, rows = np.asarray(start), []
    for S, fed in schedule:
        tokens = np.full((drv.slots, S), 7, np.int32)
        for slot, n in enumerate(fed):
            tokens[slot, :n] = seqs[slot, at[slot]:at[slot] + n]
        out = drv.step(tokens, fed=fed).asnumpy()
        rows.append(drv.last_program_rows)
        assert out.shape[1] == (S if rows[-1] == drv.slots * S else 1)
        for slot, n in enumerate(fed):
            if out.shape[1] == S:
                got[slot, at[slot]:at[slot] + n] = out[slot, :n]
            elif n:
                got[slot, at[slot] + n - 1] = out[slot, 0]
        at = at + np.asarray(fed)
        assert list(drv.pos) == list(at)
    return got, at, rows


def _err(got, want):
    held = ~np.isnan(got).any(axis=-1)
    assert held.any()
    return np.abs(got[held] - want[held]).max()


def _full(n):                       # n full windows for every slot
    return [(WINDOW, [WINDOW] * SLOTS)] * n


def _ones(n, fed=(1,) * SLOTS):
    return [(1, list(fed))] * n


SCHEDULES = {
    # whole windows (48 rows: the whole-window program, two chunks a
    # slot a dispatch), then S = 1 through the state
    "whole_windows_then_decode": _full(3) + _ones(6),
    # the packed program (at most 24 rows): a chunk and riders, a part
    # of a chunk beside another, a ragged last chunk, a slot fed nothing
    "packed_windows_with_riders": [
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


# ------------------------------------------------------------- the op alone
def _draw(T, H, D, seed, low=-5.0):
    """Normed q and k, v, log decays down to ``low`` (every third row
    at the bound itself), b in (0, 1), a state to start from."""
    r = np.random.RandomState(seed)
    q, k, v = (r.randn(T, H, D).astype("f") for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = (low * r.rand(T, H, D)).astype("f")
    g[::3] = low
    return tuple(jnp.asarray(x) for x in (
        q, k, v, g, r.rand(T, H).astype("f"), r.randn(H, D, D).astype("f")))


@pytest.mark.parametrize("step", ["xla", "pallas"])
@pytest.mark.parametrize("C,T,low", [
    (16, 16, -5.0), (64, 64, -5.0), (64, 40, -5.0), (16, 7, -5.0),
    (64, 64, -0.01), (32, 21, -1.0)],
    ids=["c16", "c64", "c64_ragged", "c16_ragged", "c64_slow_decay",
         "c32_ragged"])
def test_the_chunked_form_is_the_delta_rule(step, C, T, low):
    """One chunk of the WY form - plain, and the kernel's body in
    interpret mode - against the recurrence one token at a time, with
    log decays drawn down to the bound of -5 a token (64 rows: e^-320
    inside the chunk, which no exponent forms), pads behind a ragged
    last chunk (a = 1, b = 0), and a decay near 1 where nothing
    underflows and the triangular inverse does all the work."""
    q, k, v, g, b, s0 = _draw(T, 2, 32, 100 * C + T, low)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, b, s0)
    # the rows as ``feat`` lays them: [q | k | v | g | b], b along its
    # head's lanes; the pads behind T carry junk that ``n_real`` masks
    rows = jnp.concatenate(
        [x.reshape(T, -1) for x in (q, k, v, g, jnp.repeat(b, 32, axis=1))],
        axis=1)
    rows = jnp.concatenate([rows, jnp.full((C - T, rows.shape[1]), 0.3)])
    fn = kda._chunk_xla if step == "xla" else kda._chunk_pallas
    with jax.default_matmul_precision("highest"):
        o, s = fn(rows, jnp.int32(T), s0)
    assert np.abs(np.asarray(o)[:T].reshape(T, 2, 32)
                  - np.asarray(want_o)).max() <= 5e-6
    assert np.abs(np.asarray(s) - np.asarray(want_s)).max() <= 5e-6
    assert np.abs(np.asarray(want_o)).max() > 0.1


def _op_inputs(H, D, K, T, slots, seed):
    HD = H * D
    rng = np.random.RandomState(seed)
    return dict(
        conv_w=(0.4 * rng.randn(3 * HD, K)).astype("f"),
        a_log=(0.3 * rng.randn(H)).astype("f"),
        dt_bias=rng.uniform(-6, 3, HD).astype("f"),
        gamma=(1 + 0.3 * rng.randn(D)).astype("f"),
        seqs=rng.randn(slots, T, 5 * HD + H).astype("f"))


def _op_want(p, H, D, K, n, b):
    """The op's output for the first ``n`` rows of sequence ``b``, by
    the module docstring's equations and ``kda_recurrence``."""
    HD = H * D
    rows = p["seqs"][b, :n]
    xp = np.concatenate([np.zeros((K - 1, 3 * HD), "f"), rows[:, :3 * HD]])
    conv = sum(xp[j:j + n] * p["conv_w"][None, :, j] for j in range(K))
    act = conv / (1 + np.exp(-conv))
    heads = lambda x: x.reshape(n, H, D)                     # noqa: E731
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)  # noqa
    q = unit(heads(act[:, :HD])) * D ** -0.5
    k = unit(heads(act[:, HD:2 * HD]))
    sig = lambda x: 1 / (1 + np.exp(-x))                     # noqa: E731
    g = -5.0 * sig(np.repeat(np.exp(p["a_log"]), D)[None]
                   * (rows[:, 3 * HD:4 * HD] + p["dt_bias"][None]))
    o, _s = kda.kda_recurrence(
        *(jnp.asarray(x, jnp.float32) for x in (
            q, k, heads(act[:, 2 * HD:]), heads(g), sig(rows[:, 5 * HD:]))),
        jnp.zeros((H, D, D)))
    o = np.asarray(o)
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * p["gamma"]
    return o.reshape(n, HD) * sig(rows[:, 4 * HD:5 * HD])


def _op_case(variant, S, fed, packed_rows=None, chunk=8, T=40, seed=0):
    """The op alone over three sequences from scratch: dispatches that
    read what the last left (the first reads a last occupant's junk as
    zeros: cursor 0) against the recurrence."""
    H, D, K = 4, 8, 4
    HD, width = H * D, 5 * H * D + H
    slots = len(fed[0])
    p = _op_inputs(H, D, K, T, slots, seed)
    opdef = get_op("kda_mixer_decode")
    attrs = opdef.normalize_attrs(dict(
        heads=H, head_dim=D, d_conv=K, chunk=chunk, step_len=S,
        capacity=1000, lower_bound=-5.0, rms_eps=1e-6))
    fn = opdef.variant_fn(variant)
    aux = [jnp.full((slots, K - 1, 3 * HD), 7.0),  # a last occupant's
           jnp.full((slots, H, D, D), 3.0),
           jnp.zeros((slots, 1), jnp.int32)]
    got, at = [[] for _ in range(slots)], [0] * slots
    for counts in fed:
        if packed_rows is None:
            data = np.full((slots, S, width), 99.0, "f")
            for b, n in enumerate(counts):
                data[b, :n] = p["seqs"][b, at[b]:at[b] + n]
        else:
            data = np.full((1, packed_rows, width), 99.0, "f")
            o = 0
            for b, n in enumerate(counts):
                data[0, o:o + n] = p["seqs"][b, at[b]:at[b] + n]
                o += n
        before = [np.asarray(a) for a in aux]
        outs, aux = fn(attrs, [jnp.asarray(data.reshape(-1, width)),
                               jnp.asarray(counts, jnp.int32), p["conv_w"],
                               p["a_log"], p["dt_bias"], p["gamma"]],
                       aux, False, None)
        out = np.asarray(outs[0]).reshape(data.shape[:2] + (HD,))
        o = 0
        for b, n in enumerate(counts):
            got[b].append(out[b, :n] if packed_rows is None
                          else out[0, o:o + n])
            if n == 0 and at[b]:        # fed nothing: kept to the bit
                for old, new in zip(before, aux):
                    assert np.array_equal(old[b], np.asarray(new)[b])
            o, at[b] = o + n, at[b] + n
        assert np.asarray(aux[2]).reshape(-1).tolist() == at
    for b in range(slots):
        want = _op_want(p, H, D, K, at[b], b)
        assert np.abs(np.concatenate(got[b]) - want).max() <= 2e-5, b
    return aux


_LAYOUTS = {
    "steps": (1, [[1, 1, 1], [1, 0, 1], [1, 1, 1]], None),
    "whole_two_chunks": (16, [[16, 16, 16], [16, 16, 16]], None),
    "whole_ragged": (16, [[13, 3, 16], [9, 16, 1]], None),
    "packed_riders": (16, [[16, 1, 1], [16, 1, 1]], 24),
    "packed_parts": (16, [[5, 0, 12], [1, 11, 9], [3, 0, 1]], 24),
}


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_kda_mixer_decode_is_the_recurrence(variant, layout):
    """``kda_mixer_decode`` alone, both lowerings (``kda_update`` and
    ``kda_chunk`` in interpret mode): steps, two chunks of 8 in a
    dispatch of 16, a ragged last chunk, the packed rows with riders, a
    slot fed nothing (kept to the bit) - the first dispatch of each
    reads a dirty state at cursor 0 as zeros."""
    S, fed, rows = _LAYOUTS[layout]
    _op_case(variant, S, fed, packed_rows=rows)


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
def test_the_plain_forward_and_the_pallas_variant_leave_the_same_state(
        layout):
    S, fed, rows = _LAYOUTS[layout]
    plain = _op_case("xla", S, fed, packed_rows=rows, seed=3)
    kernels = _op_case("pallas", S, fed, packed_rows=rows, seed=3)
    for a, b in zip(plain, kernels):
        assert np.abs(np.asarray(a, "f") - np.asarray(b, "f")).max() <= 1e-5


def test_the_op_refuses_sizes_it_does_not_run():
    opdef = get_op("kda_mixer_decode")
    base = dict(heads=4, head_dim=8, d_conv=4, chunk=16, step_len=16,
                capacity=64)
    for wrong, match in (({"chunk": 24}, "sub-blocks"),
                         ({"lower_bound": -6.0}, "passes e"),
                         ({"lower_bound": 1.0}, "positive"),
                         ({"d_conv": 1}, "d_conv >= 2")):
        attrs = opdef.normalize_attrs(dict(base, **wrong))
        with pytest.raises(MXNetError, match=match):
            opdef.infer_shape(attrs, [(64, 164), (4,)] + [None] * 4)
    with pytest.raises(ValueError, match=r"q \| k \| v \| f \| g \| b"):
        opdef.infer_shape(opdef.normalize_attrs(base),
                          [(64, 160), (4,)] + [None] * 4)


# ------------------------------------------------- the block, by the driver
@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_prefill_and_decode_match_the_reference_full_forward(driver, case):
    """Every fed position's logits against the plain reference's full
    forward (the delta rule one token at a time, latent attention
    un-absorbed, the held experts in a loop), within the float32
    bound."""
    rng = np.random.default_rng(3)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    got, at, rows = _run(driver, seqs, SCHEDULES[case])
    want = _reference(seqs)
    assert np.abs(want).max() > 0.5
    for slot in range(SLOTS):
        err = _err(got[slot, :at[slot]], want[slot, :at[slot]])
        assert err <= TOL <= arch.LOGIT_TOL, (case, slot, err)
    if case == "packed_windows_with_riders":
        assert rows[:6] == [24] * 6      # the packed program ran them
    if case == "whole_windows_then_decode":
        assert rows[:3] == [SLOTS * WINDOW] * 3


def test_a_slot_left_and_joined_again_reads_a_clean_state(driver):
    rng = np.random.default_rng(4)
    old = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    _run(driver, old, _full(3) + _ones(6))
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    got, at, _ = _run(driver, seqs, [(WINDOW, [16, 1, 1])] + _ones(3)
                      + _full(1))                        # leaves, joins
    want = _reference(seqs)
    for slot in range(SLOTS):
        assert _err(got[slot, :at[slot]], want[slot, :at[slot]]) <= TOL


def test_three_families_of_state_live_in_one_graph(driver):
    """``"rows"`` (one latent pool), ``"conv"`` and ``"recurrent"`` (three
    KDA layers) beside the cursors; the counts a dispatch declares."""
    assert sorted(driver._state) == ["conv", "cursor", "recurrent", "rows"]
    assert not driver.positional and driver.feeds
    assert driver._carried == ["conv", "recurrent"]
    assert driver.state_bytes["recurrent"] == 3 * SLOTS * 4 * 8 * 8 * 4
    assert driver.state_bytes["conv"] == 3 * SLOTS * 3 * 96 * 4
    # one latent pool: 16 + 8 numbers in a row of 128 lanes
    assert driver.state_bytes["rows"] == SLOTS * CAPACITY * 128 * 4
    driver.active[:] = False
    driver.rewind_many(list(range(SLOTS)), [0] * SLOTS)
    driver.step(np.zeros((SLOTS, WINDOW), np.int32), fed=[16, 1, 0])
    reads = driver.last_reads
    assert (reads["kda.step_slots"], reads["kda.chunk_slots"],
            reads["kda.chunk_trips"], reads["kda.chunk_rows"],
            reads["kda.real_rows"]) == (3, 3, 6, 48, 48)
    assert reads["mla_attended"] == 17
    driver.step(np.zeros((SLOTS, WINDOW), np.int32), fed=[13, 0, 2])
    reads = driver.last_reads                    # 13 = 8 + 5, 2 = one trip
    assert (reads["kda.step_slots"], reads["kda.chunk_slots"],
            reads["kda.chunk_trips"], reads["kda.chunk_rows"],
            reads["kda.real_rows"]) == (0, 6, 9, 72, 45)
    driver.active[:] = False
    driver.rewind_many(list(range(SLOTS)), [0] * SLOTS)


def test_rewind_capture_and_restore_name_the_families(driver):
    rng = np.random.default_rng(2)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    _run(driver, seqs, _full(1) + [(WINDOW, [8, 0, 16])])   # 24, 16, 32
    for move in ((0, 23), (0, 8), (2, 33)):
        with pytest.raises(MXNetError, match=r"conv.*recurrent.*goes to 0"):
            driver.rewind(*move)
    assert list(driver.pos) == [24, 16, 32]      # a refusal moves nothing
    driver.rewind(2, 0)
    assert list(driver.pos) == [24, 16, 0]
    for call in (lambda: driver.capture_rows(0, 8),
                 lambda: driver.restore_rows(0, {})):
        with pytest.raises(MXNetError, match=r"conv.*recurrent"):
            call()
    driver.active[:] = False
    driver.rewind_many(list(range(SLOTS)), [0] * SLOTS)


def test_the_state_is_alive():
    """With the latent layer cut out, the last logits move when a token
    64 positions back changes: the matrix state carries it."""
    cfg = dict(CFG, layer_group_size=100)               # every layer KDA
    params = _params(cfg, seed=6, log_decay=-0.03)      # 0.97 a token
    drv = _driver(cfg, params)
    rng = np.random.default_rng(12)
    seqs = rng.integers(0, cfg["vocab_size"], (SLOTS, 80)).astype(np.int32)
    other = seqs.copy()
    other[:, 15] = (other[:, 15] + 1) % cfg["vocab_size"]
    sched = _full(4) + _ones(16)
    a, at, _ = _run(drv, seqs, sched)
    b, _, _ = _run(drv, other, sched)
    moved = np.abs(a[:, 79] - b[:, 79]).max(axis=-1)
    assert np.array_equal(a[:, :15], b[:, :15])
    noise = np.abs(b - _reference(other, cfg, params)).max()
    assert noise <= TOL / 2 and (moved > 20 * noise).all(), (moved, noise)


def test_a_bfloat16_state_fails_where_a_float32_state_passes():
    """A decay near 1 (log a = -0.001 a token and channel) over a
    thousand tokens: the reference with its state rounded to bfloat16
    after every token - the precision below the float32 the
    configuration states - is far outside the float32 bound of itself
    (the state drops what is under 2^-8 of itself, so 0.999 S rounds
    back to S and nothing is forgotten), and the served float32 state,
    a thousand tokens through windows and steps, is inside it. The
    chip's comparison may not see the state's width (``PERF.md``
    section 7, PR 48); this one does."""
    cfg = dict(CFG, layer_group_size=100)               # every layer KDA
    params = _params(cfg, seed=6, log_decay=-0.001)
    rng = np.random.default_rng(13)
    seqs = rng.integers(0, cfg["vocab_size"], (SLOTS, 1000)) \
        .astype(np.int32)
    want = _reference(seqs, cfg, params, tail=32)
    low = _reference(seqs, cfg, params, state_dtype=jnp.bfloat16, tail=32)
    assert np.abs(low - want).max() > 50 * TOL
    drv = _driver(cfg, params, capacity=1024)
    got, at, _ = _run(drv, seqs, _full(61) + [(WINDOW, [16, 8, 1])]
                      + _ones(8, fed=(1, 1, 0)))
    assert list(at) == [1000, 992, 977]
    for slot in range(SLOTS):
        tail = slice(at[slot] - 8, at[slot])
        full = _reference(seqs[slot:slot + 1, :at[slot]], cfg, params,
                          tail=8)[0]
        assert np.abs(got[slot, tail] - full).max() <= 5 * TOL, slot


_REFUSED = {
    "q_lora_rank": 24, "gated_attention_proj_granularity_type": "element",
    "expert_swiglu_limit_list": [0, 0, 0, 0, 4, 4],
    "share_expert_swiglu_limit_list": [0, 0, 0, 5, 0, 0],
    "num_kv_heads_for_linear_attn": 2, "use_mla_nope": True,
    "scale_router_input": True, "up_proj_norm": True, "value_norm": True,
    "use_nGPT": True, "kda_safe_gate": False, "use_kda_lora": True,
    "linear_silu": False, "group_norm_size": 4,
    "moe_router_enable_expert_bias": False,
    "moe_shared_expert_intermediate_size": 32}


@pytest.mark.parametrize("key", sorted(_REFUSED))
def test_the_builder_refuses_each_key_it_does_not_build(key):
    with pytest.raises(MXNetError, match=rf"ling_hybrid.*{key}"):
        _symbol(1, dict(CFG, **{key: _REFUSED[key]}))


def test_the_builder_refuses_a_graph_it_does_not_have():
    with pytest.raises(MXNetError, match="'kda' or 'mla'"):
        _symbol(1, layer_types=["kda", "kda", "mla"])
    with pytest.raises(MXNetError, match="'kda' or 'mla'"):
        _symbol(1, layer_types=["kda", "kda", "mamba", "mla"])
    with pytest.raises(MXNetError, match="needs ling="):
        tfm.get_decode_symbol(block="ling_hybrid", per_slot=True)
    with pytest.raises(MXNetError, match="served, not trained"):
        tfm.get_symbol(block="ling_hybrid")
    # a limit of 0 on a layer that is not run is nobody's business
    _symbol(1, dict(CFG, expert_swiglu_limit_list=[0, 4, 4, 0, 0, 0]))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a small layer (16 experts in 8 groups of
    2, two groups a chip, the choice inside 4 of the 8 under a
    correction bias, the shared expert counted once) through the
    program's ``MoEFFN`` add up to the uncut layer as the reference
    computes it, and each share equals the reference's own share."""
    rs = np.random.RandomState(4)
    D, F, E, T = 32, 16, 16, 40
    cfg = dict(CFG, hidden_size=D, n_group=8, topk_group=4)
    f = lambda *s: np.asarray(rs.randn(*s) * 0.3, np.float32)  # noqa: E731
    params = {"p_moe_router_weight": f(E, D), "p_moe_router_bias": f(E),
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
    for first in range(0, E, 4):
        attrs = op.normalize_attrs(dict(
            num_experts=E, num_hidden=F, top_k=4, norm_topk=True,
            scoring="sigmoid", router_bias=True, scaling=2.5,
            held_first=first, held_count=4, shared_hidden=F, n_group=8,
            topk_group=4, step_len=1))
        ins = [jnp.asarray(x), jnp.ones((T,), jnp.int32),
               params["p_moe_router_weight"], params["p_moe_router_bias"]] \
            + [jnp.asarray(params[f"p_moe_{k}_weight"][first:first + 4])
               for k in ("gate", "up", "down")] \
            + [params[f"p_moe_shared_{k}_weight"]
               for k in ("gate", "up", "down")]
        assert "router_bias" in op.input_names(attrs)
        (out, experts), _aux = op.variant_fn("xla")(
            attrs, ins, [jnp.zeros((5,), jnp.int32)], False, None)
        np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                      np.sort(np.asarray(chosen), -1))
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref.expert_layer(jnp.asarray(x), "p", {
                **params, **{f"p_moe_{k}_weight":
                             params[f"p_moe_{k}_weight"][first:first + 4]
                             for k in ("gate", "up", "down")}},
                cfg, lambda a: a, held=(first, 4))
        np.testing.assert_allclose(np.asarray(out) - np.asarray(shared),
                                   np.asarray(part), atol=2e-5, rtol=2e-5)
        total += np.asarray(out) - np.asarray(shared)
    np.testing.assert_allclose(total, np.asarray(whole), atol=5e-5, rtol=5e-5)
    assert np.max(np.abs(np.asarray(whole))) > 0.1


# --------------------------------------------------- engine and scheduler
def _gen(step_len):
    return _symbol(step_len)


@pytest.fixture(scope="module")
def engine():
    return mx.serve.DecodeEngine(
        "tiny-ling", _gen(1), PARAMS, capacity=CAPACITY,
        ladder=[2, 4], symbol_gen=_gen, window_lens=[WINDOW])


def test_migrate_mid_sequence_continues_as_the_reference(engine):
    """Two slots at positions 37 and 50 of the 2-slot pool move to the
    4-slot pool, swapped, with their tails, matrix states, latent rows
    and cursors, and decode on: the reference's logits."""
    rng = np.random.default_rng(6)
    seqs = rng.integers(0, CFG["vocab_size"], (2, 60)).astype(np.int32)
    want = _reference(seqs)
    small, big = engine.driver(2), engine.driver(4)
    for drv in (big, small):
        drv.active[:] = False
    small.join(0), small.join(1)
    lens, at = [37, 50], [0, 0]
    while any(a < n for a, n in zip(at, lens)):
        tokens = np.zeros((2, WINDOW), np.int32)
        fed = np.zeros(2, np.int32)
        for s in range(2):
            n = min(WINDOW, lens[s] - at[s])
            tokens[s, :n] = seqs[s, at[s]:at[s] + n]
            fed[s], at[s] = n, at[s] + n
        small.step(tokens, fed=fed)
    engine.migrate(2, 4, [(0, 3), (1, 1)])
    assert list(big.pos) == [0, 50, 0, 37] and not small.active.any()
    for j in range(5):
        tokens = np.zeros((4, 1), np.int32)
        tokens[3, 0], tokens[1, 0] = seqs[0, 37 + j], seqs[1, 50 + j]
        out = big.step(tokens, fed=[0, 1, 0, 1]).asnumpy()
        assert np.abs(out[3, 0] - want[0, 37 + j]).max() <= TOL
        assert np.abs(out[1, 0] - want[1, 50 + j]).max() <= TOL
    big.active[:] = False
    assert sorted(engine.state_bytes) == ["conv", "cursor", "recurrent",
                                          "rows"]


def _served(sched, prompts, max_new):
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    sched.pump()
    return [[int(t) for t in h.result(timeout=5)] for h in handles]


def test_mixed_prefill_and_decode_equals_one_request_at_a_time(engine):
    """Four requests of ragged lengths admitted together through the
    scheduler (packed windows with riders, a rung switch, run-ahead):
    the greedy tokens of each request served alone; the counters and
    the ring's fields say what the state was asked for."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import flightrec
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG["vocab_size"], n).tolist()
               for n in (45, 9, 30, 70)]
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW)
    alone = [_served(sched, [p], 12)[0] for p in prompts]
    names = ("kda.step_slots", "kda.chunk_slots", "kda.chunk_trips",
             "kda.chunk_rows", "kda.real_rows")
    before = {k: sched._counter(k).value for k in names}
    mixed = _served(sched, prompts, 12)
    assert mixed == alone and all(len(t) == 12 for t in mixed)
    grew = {k: sched._counter(k).value - v for k, v in before.items()}
    # positions 0..n+10 of each request are fed, in three KDA layers,
    # by a step or inside a chunk's trip
    assert grew["kda.step_slots"] + grew["kda.real_rows"] \
        == 3 * sum(len(p) + 11 for p in prompts)
    assert grew["kda.chunk_rows"] == 8 * grew["kda.chunk_trips"] \
        > grew["kda.real_rows"] > 0
    assert grew["kda.chunk_trips"] >= grew["kda.chunk_slots"] > 0
    steps = [r for r in flightrec.get_records()
             if r.get("kind") == "serve.decode.step"
             and r.get("model") == "tiny-ling"]
    assert steps and all("kda_step_slots" in r and "kda_chunk_rows" in r
                         and "mla_attended" in r for r in steps)
    assert any(r["window"] > 1 and r["kda_chunk_trips"] for r in steps)
    assert sched.stats()["compiles_since_warmup"] == 0
    assert sched.stats()["runahead"]["launched"] > 0
    assert telemetry.get_metric("serve.decode.kda.real_rows",
                                model="tiny-ling").value > 0
    for family in ("conv", "recurrent", "rows"):
        assert telemetry.get_metric("serve.decode.state.bytes",
                                    model="tiny-ling",
                                    family=family).value > 0


def test_the_scheduler_refuses_drafts_and_prefix_stores(engine):
    from mxnet_tpu.serve.prefix import PrefixStore
    with pytest.raises(MXNetError, match=r"prefix_store.*conv.*recurrent"):
        mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                 prefix_store=PrefixStore(1 << 20))
    with pytest.raises(MXNetError, match=r"spec_k.*conv.*recurrent"):
        mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                 draft_engine=engine, spec_k=4)


def test_serve_decoder_serves_the_block_with_no_side_script():
    sched = mx.serve.serve_decoder(
        _gen(1), PARAMS, name="tiny-ling-front", capacity=CAPACITY,
        ladder=[1, 2], symbol_gen=_gen, prefill_chunk=WINDOW, start=False,
        clock=mx.serve.FakeClock())
    assert sched.prefix_store is None and sched.engine.feeds
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    tokens = _served(sched, [prompt.tolist()], 6)[0]
    seq = np.concatenate([prompt, tokens[:-1]])[None].astype(np.int32)
    want = _reference(seq)[0]
    assert tokens == np.argmax(want[40:], axis=-1).tolist()
