"""Ling-3.0's block (``block="ling_hybrid"``, ISSUE 52) on the CPU at
tiny sizes. What every served block does is
``tests/decode_block_suite.py``'s, over the row ``ling_hybrid`` of
``tests/decode_blocks.py`` against the benchmark's plain reference
(``chipbench/reference/ling_hybrid.py``: the delta rule one token at a
time, latent attention un-absorbed, the held experts in a loop). Below
that the block's own: ``ops/kda.py`` - the chunked (WY) form against
the delta rule step by step, the plain forward against the ``pallas``
variant in interpret mode -, three families of state in one graph and
what a dispatch counts of them, the four shares of an expert layer
adding up, a decay near 1 that a bfloat16 state fails."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import kda
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

from chipbench.reference import ling_hybrid as ref  # noqa: E402
# (the benchmark's own tests of the architecture file, the cell's two
# CPU rehearsals with them: ``tests/test_chipbench_ling_hybrid.py``)

BLOCK = "ling_hybrid"
LING = blocks.config(BLOCK)["ling"]
TOL = blocks.TOL[BLOCK]
_W = (WINDOW, [WINDOW] * SLOTS)
#: every layer KDA: the latent layer cut out
_ALL_KDA = {"ling": {"layer_types": ["kda"] * 3}}


_ones = blocks.steps


def _decay(log_decay):
    """``draws`` of ``blocks.params`` with the same log decay for every
    channel and token: the decay's projection zero, ``A_log`` zero."""
    HD = blocks.config(BLOCK)["n_head"] * LING["head_dim"]
    share = log_decay / LING["kda_lower_bound"]

    def in_weight(draw, rng):
        draw = 0.25 * draw
        draw[3 * HD:4 * HD] = 0.0                           # f = 0
        return draw

    return {"_kda_A_log": lambda draw, rng: 0.0 * draw,
            "_kda_dt_bias": lambda draw, rng: np.full(
                draw.shape, np.log(share / (1.0 - share))),
            "_kda_in_weight": in_weight}



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
def test_three_families_of_state_live_in_one_graph(driver):
    """``"rows"`` (one latent pool), ``"conv"`` and ``"recurrent"`` (two
    KDA layers) beside the cursors; the counts a dispatch declares."""
    H, D, K = blocks.config(BLOCK)["n_head"], LING["head_dim"], 2
    assert driver.state_bytes["recurrent"] == K * SLOTS * H * D * D * 4
    assert driver.state_bytes["conv"] == K * SLOTS * 3 * 3 * H * D * 4
    # one latent pool: 64 + 16 numbers in a row of 128 lanes
    assert driver.state_bytes["rows"] == SLOTS * CAPACITY * 128 * 4
    blocks.reset(driver)
    names = ("kda.step_slots", "kda.chunk_slots", "kda.chunk_trips",
             "kda.chunk_rows", "kda.real_rows")
    driver.step(np.zeros((SLOTS, WINDOW), np.int32),
                fed=[16, 1] + [0] * (SLOTS - 2))
    reads = driver.last_reads
    assert [reads[n] for n in names] == [K * n for n in (1, 1, 2, 16, 16)]
    assert reads["mla_attended"] == 17
    driver.step(np.zeros((SLOTS, WINDOW), np.int32),
                fed=[13, 0, 2] + [0] * (SLOTS - 3))
    reads = driver.last_reads                    # 13 = 8 + 5, 2 = one trip
    assert [reads[n] for n in names] == [K * n for n in (0, 2, 3, 24, 15)]
    blocks.reset(driver)


def test_the_state_is_alive():
    """With the latent layer cut out, the last logits move when a token
    64 positions back changes: the matrix state carries it."""
    params = blocks.params(BLOCK, seed=6, draws=_decay(-0.03), **_ALL_KDA)
    drv = blocks.driver(BLOCK, arg_params=params, **_ALL_KDA)  # 0.97 a token
    seqs = blocks.seqs(BLOCK, 80, seed=12)
    other = seqs.copy()
    other[:, 15] = (other[:, 15] + 1) % seqs.max()
    sched = [_W] * 4 + _ones(16)
    a, at, _ = blocks.run(drv, seqs, sched)
    b, _, _ = blocks.run(drv, other, sched)
    moved = np.abs(a[:, 79] - b[:, 79]).max(axis=-1)
    assert np.array_equal(a[:, :15], b[:, :15])
    noise = np.abs(b - blocks.reference(BLOCK, other, params, _ALL_KDA)).max()
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
    params = blocks.params(BLOCK, seed=6, draws=_decay(-0.001), **_ALL_KDA)
    seqs = blocks.seqs(BLOCK, 1000, seed=13, slots=3)
    want = blocks.reference(BLOCK, seqs, params, _ALL_KDA, tail=32)
    low = blocks.reference(BLOCK, seqs, params, _ALL_KDA,
                           state_dtype=jnp.bfloat16, tail=32)
    assert np.abs(low - want).max() > 50 * TOL
    drv = blocks.driver(BLOCK, slots=3, capacity=1024, arg_params=params,
                        **_ALL_KDA)
    got, at, _ = blocks.run(drv, seqs, [(WINDOW, [WINDOW] * 3)] * 61
                            + [(WINDOW, [16, 8, 1])] + [(1, [1, 1, 0])] * 8)
    assert list(at) == [1000, 992, 977]
    for slot in range(3):
        tail = slice(at[slot] - 8, at[slot])
        full = blocks.reference(BLOCK, seqs[slot:slot + 1, :at[slot]],
                                params, _ALL_KDA, tail=8)[0]
        assert np.abs(got[slot, tail] - full).max() <= 5 * TOL, slot


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a small layer (16 experts in 8 groups of
    2, two groups a chip, the choice inside 4 of the 8 under a
    correction bias, the shared expert counted once) through the
    program's ``MoEFFN`` add up to the uncut layer as the reference
    computes it, and each share equals the reference's own share."""
    rs = np.random.RandomState(4)
    D, F, E, T = 32, 16, 16, 40
    cfg = blocks.reference_cfg(BLOCK, d_model=D,
                               ling={"n_group": 8, "topk_group": 4})
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




def test_the_scheduler_counts_what_the_state_was_asked_for(engine):
    """Four requests of ragged lengths through the scheduler: the
    counters and the ring's fields say what the state was asked for."""
    prompts, grew, steps = blocks.counted(BLOCK, engine, (
        "kda.step_slots", "kda.chunk_slots", "kda.chunk_trips",
        "kda.chunk_rows", "kda.real_rows"))
    # positions 0..n+10 of each request are fed, in two KDA layers, by
    # a step or inside a chunk's trip
    assert grew["kda.step_slots"] + grew["kda.real_rows"] \
        == 2 * sum(len(p) + 11 for p in prompts)
    assert grew["kda.chunk_rows"] == 8 * grew["kda.chunk_trips"] \
        > grew["kda.real_rows"] > 0
    assert grew["kda.chunk_trips"] >= grew["kda.chunk_slots"] > 0
    assert steps and all("kda_step_slots" in r and "kda_chunk_rows" in r
                         and "mla_attended" in r for r in steps)
    assert any(r["window"] > 1 and r["kda_chunk_trips"] for r in steps)
    assert mx.telemetry.get_metric("serve.decode.kda.real_rows",
                                   model=engine.name).value > 0
