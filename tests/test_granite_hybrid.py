"""Granite 4.0-H's block behind the serving path (``block=
"granite_hybrid"`` of models/transformer.py: Mamba-2 mixers whose state
is constant in the context - ``ssm_mixer_decode``, ops/ssm.py - beside
attention layers without positions, the four Granite multipliers). What
every served block does is ``tests/decode_block_suite.py``'s, over the
row ``granite_hybrid`` of ``tests/decode_blocks.py`` against the plain
reference chipbench/reference/granite_hybrid.py: three layers (mamba,
attention, mamba), 8 heads of 16 with a state of 16, a chunk of 8 under
a window of 16 - two chunks in one dispatch -, 4 query heads on 2 K/V
heads of 16 that lie paired in one row of 32. Below that the block's
own: the op against its recurrence, a pad, the state's sizes and counts,
the bfloat16-state control."""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import ssm
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

from chipbench.archs import granite_hybrid as arch  # noqa: E402
# the quick cases of the benchmark's own tests of the architecture file
# run here as they stand (its CPU rehearsal stays by hand)
from chipbench.tests.test_granite_hybrid import (  # noqa: E402,F401
    test_costs_against_a_count_by_hand,
    test_every_new_reader_on_a_synthetic_obs,
    test_make_params_draws_decays_of_one_to_a_thousand_tokens,
    test_the_architecture_file_has_the_interface_and_builds_the_block,
    test_the_configuration_is_the_catalogs_and_nothing_is_reduced)
from chipbench.tests import test_granite_hybrid as _bench  # noqa: E402


def test_the_traffic_is_the_issues(monkeypatch):
    """The benchmark's own case as it stands, over the manifest up to
    Granite's cell: it counts the cells (eleven when PR 48 wrote it) and
    holds the ``ssm.*`` metrics to this cell alone, and a later cell is
    appended behind Granite's and may join their lists (PR 54's does) -
    the file is the benchmark's and a `benchmark` PR's to edit
    (`PERF.md` section 7)."""
    from chipbench import manifest
    whole = manifest.load()
    at = [w["name"] for w in whole["workloads"]].index(_bench.REAL_CELL)
    kept = whole["workloads"][:at + 1]
    names = {w["name"] for w in kept}
    trimmed = dict(whole, workloads=kept, **{
        part: [dict(m, workloads=[w for w in m["workloads"] if w in names])
               if "workloads" in m else m for m in whole[part]]
        for part in ("end_to_end", "per_layer")})
    monkeypatch.setattr(manifest, "load", lambda *a, **kw: trimmed)
    _bench.test_the_traffic_is_the_issues()


BLOCK = "granite_hybrid"
G = blocks.config(BLOCK)["granite"]
TOL = blocks.TOL[BLOCK]
_W = blocks.window(*[WINDOW] * SLOTS)
_fed, _ones = blocks.window, blocks.steps


def _decays(decay, skip=1.0):
    """``draws`` of ``blocks.params`` with ``decay = (A, dt)`` the same
    for every head: (1, 1e-3) is a decay of 0.999 a token, a memory of
    a thousand tokens. ``skip`` is ``D``."""
    dt = decay[1]
    return {"_A_log": lambda draw, rng: np.log(np.full(draw.shape, decay[0])),
            "_dt_bias": lambda draw, rng: np.full(
                draw.shape, dt + np.log(-np.expm1(-dt))),
            "_mamba_D": lambda draw, rng: np.full(draw.shape, skip)}


def test_an_odd_number_of_kv_heads_is_served_unpaired():
    """One K/V head (the published 8 pair up two to a row; an odd number
    or a head of 128 cannot): the pools hold it as it is and the
    queries are not widened - windows, the packed program with riders,
    then S = 1, against the reference."""
    over = {"granite": {"num_key_value_heads": 1}}
    params = blocks.params(BLOCK, seed=8, **over)
    drv = blocks.driver(BLOCK, arg_params=params, **over)
    assert drv.state_bytes["rows"] == 2 * SLOTS * CAPACITY * 16 * 4
    seqs = blocks.seqs(BLOCK, 80, seed=14)
    got, at, rows = blocks.run(drv, seqs, [_W, _fed(16), _fed(3, 1, 12)]
                               + _ones(4))
    assert rows[:3] == [SLOTS * WINDOW, 24, 24]
    want = blocks.reference(BLOCK, seqs, params, over)
    for slot in range(SLOTS):
        assert blocks.err(got[slot, :at[slot]], want[slot, :at[slot]]) <= TOL


def _op_case(variant, S, fed, packed_rows=None, chunk=8, T=40, seed=0):
    """The op alone over three sequences from scratch: two dispatches
    (the second reads the first's state) against the recurrence."""
    H, P, N, K = 4, 8, 16, 4
    d_in, C = H * P, H * P + 2 * N
    width = 2 * d_in + 2 * N + H
    rng = np.random.RandomState(seed)
    conv_w = (0.3 * rng.randn(C, K)).astype("f")
    conv_b = (0.1 * rng.randn(C)).astype("f")
    dt_bias = rng.randn(H).astype("f")
    a_log = np.log(rng.uniform(1, 16, H)).astype("f")
    D = np.ones(H, "f")
    slots = len(fed[0])
    seqs = rng.randn(slots, T, width).astype("f")
    opdef = get_op("ssm_mixer_decode")
    attrs = opdef.normalize_attrs(dict(
        heads=H, head_dim=P, d_state=N, d_conv=K, chunk=chunk, step_len=S,
        capacity=1000))
    fn = opdef.variant_fn(variant)
    W = ssm.lane_width(H, P)
    aux = [jnp.full((slots, K - 1, C), 7.0),       # a last occupant's
           jnp.full((slots, d_in // W, N, W), 3.0),
           jnp.zeros((slots, 1), jnp.int32)]
    got, at = [[] for _ in range(slots)], [0] * slots
    for counts in fed:
        if packed_rows is None:
            data = np.full((slots, S, width), 99.0, "f")
            for b, n in enumerate(counts):
                data[b, :n] = seqs[b, at[b]:at[b] + n]
        else:
            data = np.full((1, packed_rows, width), 99.0, "f")
            o = 0
            for b, n in enumerate(counts):
                data[0, o:o + n] = seqs[b, at[b]:at[b] + n]
                o += n
        outs, aux = fn(attrs, [jnp.asarray(data.reshape(-1, width)),
                               jnp.asarray(counts, jnp.int32), conv_w,
                               conv_b, dt_bias, a_log, D], aux, False, None)
        out = np.asarray(outs[0]).reshape(data.shape[:2] + (d_in,))
        o = 0
        for b, n in enumerate(counts):
            got[b].append(out[b, :n] if packed_rows is None
                          else out[0, o:o + n])
            o, at[b] = o + n, at[b] + n
        assert np.asarray(aux[2]).reshape(-1).tolist() == at
    for b in range(slots):
        rows = seqs[b, :at[b]]
        z, xbc, dt = rows[:, :d_in], rows[:, d_in:d_in + C], \
            rows[:, d_in + C:]
        xp = np.concatenate([np.zeros((K - 1, C), "f"), xbc])
        conv = sum(xp[k:k + at[b]] * conv_w[None, :, k]
                   for k in range(K)) + conv_b
        act = conv / (1 + np.exp(-conv))
        x = act[:, :d_in].reshape(-1, H, P)
        y, _h = ssm.ssm_recurrence(
            jnp.asarray(x), jnp.asarray(np.log1p(np.exp(dt + dt_bias))),
            jnp.asarray(-np.exp(a_log)), jnp.asarray(act[:, d_in:d_in + N]),
            jnp.asarray(act[:, d_in + N:]), jnp.zeros((H, P, N)))
        want = ((np.asarray(y) + D[None, :, None] * x).reshape(-1, d_in)
                * (z / (1 + np.exp(-z))))
        assert np.abs(np.concatenate(got[b]) - want).max() <= 2e-5, b


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("layout", [
    "steps", "whole_two_chunks", "whole_ragged", "packed_riders",
    "packed_parts"])
def test_the_chunked_form_is_the_recurrence(variant, layout):
    """``ssm_mixer_decode`` alone, both lowerings (``ssm_update`` in
    interpret mode): steps, two chunks of 8 in a dispatch of 16, a
    ragged last chunk, and the packed rows with riders - the second
    dispatch of each reads what the first left, the first reads a last
    occupant's junk as zeros (cursor 0)."""
    S, fed, rows = {
        "steps": (1, [[1, 1, 1], [1, 0, 1], [1, 1, 1]], None),
        "whole_two_chunks": (16, [[16, 16, 16], [16, 16, 16]], None),
        "whole_ragged": (16, [[13, 3, 16], [9, 16, 1]], None),
        "packed_riders": (16, [[16, 1, 1], [16, 1, 1]], 24),
        "packed_parts": (16, [[5, 0, 12], [1, 11, 9]], 24),
    }[layout]
    _op_case(variant, S, fed, packed_rows=rows)



def test_a_pad_advances_nothing(driver):
    """A slot fed nothing, inside a window and in an S = 1 step, keeps
    tail, state and cursor to the bit, whatever tokens ride its rows."""
    seqs = blocks.seqs(BLOCK, 80, seed=9)
    blocks.run(driver, seqs, [_fed(11, 16, 5)])
    # (the attention layer writes a window's pads behind the cursor,
    # where the next dispatch writes over them: not its pools)
    carried = lambda: [nc for family in ("conv", "recurrent", "cursor")  # noqa
                       for nc in driver._cells(family)]
    before = {nm: np.asarray(cell.asjax())[1].copy()
              for nm, cell in carried()}
    assert len(before) == 7
    blocks.run(driver, seqs, [_fed(16, 0), (1, [1, 0] + [1] * (SLOTS - 2)),
                              _fed(2, 0, 7)], start=list(driver.pos))
    for nm, cell in carried():
        assert np.array_equal(np.asarray(cell.asjax())[1], before[nm]), nm
    # and the pads of a fed slot's window: 5 real rows, 11 pads, then on
    got, at, _ = blocks.run(driver, seqs, _ones(3), start=list(driver.pos))
    want = blocks.reference(BLOCK, seqs)
    assert np.abs(got[1, 16:19] - want[1, 16:19]).max() <= TOL


def test_the_state_is_sized_and_counted_by_hand(driver):
    H, P, N = G["mamba_n_heads"], G["mamba_d_head"], G["mamba_d_state"]
    # two kinds of stateful layer: two mamba layers alike, one attention
    assert sorted(n for n, _reads in driver._reads) == [1, 2]
    assert driver.state_bytes["recurrent"] == 2 * SLOTS * H * P * N * 4
    assert driver.state_bytes["conv"] == 2 * SLOTS * 3 * (H * P + 2 * N) * 4
    # two K/V heads of 16 lie paired in one row of 32
    assert driver.state_bytes["rows"] == 2 * SLOTS * CAPACITY * 32 * 4
    blocks.reset(driver)
    driver.step(np.zeros((SLOTS, WINDOW), np.int32),
                fed=[16, 1] + [0] * (SLOTS - 2))
    assert driver.last_reads["ssm.rows"] == 2 * 17
    assert driver.last_reads["ssm.touched"] == 2 * 2
    assert driver.last_reads["attn.live_rows"] == 17
    blocks.reset(driver)


def test_the_state_is_alive():
    """With the attention layers cut out, the last logits move when a
    token 64 positions back changes: the state carries it."""
    over = {"granite": {"layer_types": ["mamba"] * 3}}
    params = blocks.params(BLOCK, seed=6, draws=_decays((1.0, 0.03)), **over)
    drv = blocks.driver(BLOCK, arg_params=params, **over)   # 0.97 a token
    seqs = blocks.seqs(BLOCK, 80, seed=12)
    other = seqs.copy()
    other[:, 15] = (other[:, 15] + 1) % seqs.max()
    sched = [_W] * 4 + _ones(16)
    a, at, _ = blocks.run(drv, seqs, sched)
    b, _, _ = blocks.run(drv, other, sched)
    moved = np.abs(a[:, 79] - b[:, 79]).max(axis=-1)
    assert np.array_equal(a[:, :15], b[:, :15])
    want = blocks.reference(BLOCK, other, params, over)
    noise = np.abs(b - want).max()
    # measured: moved 2e-4 to 1.5e-3, float32 noise 1e-6 to 2e-5
    assert noise <= TOL / 4 and (moved > 1e-4).all(), (moved, noise)


def test_a_bfloat16_state_misses_the_tolerance():
    """The reference with its state rounded to bfloat16 after every
    token - the precision below the float32 the configuration states -
    is NOT inside ``LOGIT_TOL`` of itself where a head remembers a
    thousand tokens (decay 0.999): the state drops what is under 2^-8
    of itself: 0.999 H rounds back to H, so nothing is ever forgotten.
    (``D = 0`` and multipliers of 1: the mixer's output is the state's
    read-out alone and weighs in the stream what the embedding does, as
    at the published widths; measured 0.18 on logits up to 0.8.)"""
    # the widths the control was measured at: heads of 8 in a stream of
    # 32 over a vocabulary of 96
    over = {"d_model": 32, "vocab_size": 96,
            "granite": {"layer_types": ["mamba"] * 3, "mamba_d_head": 8,
                        "shared_intermediate_size": 48,
                        "intermediate_size": 48,
                        "embedding_multiplier": 1.0,
                        "residual_multiplier": 1.0}}
    params = blocks.params(BLOCK, seed=6, draws=_decays((1.0, 1e-3), 0.0),
                           **over)
    seqs = blocks.seqs(BLOCK, 1000, seed=13, slots=2, **over)
    want = blocks.reference(BLOCK, seqs, params, over, tail=32)
    low = blocks.reference(BLOCK, seqs, params, over,
                           state_dtype=jnp.bfloat16, tail=32)
    bound = arch.LOGIT_TOL + arch.LOGIT_TOL * np.abs(want)
    assert (np.abs(low - want) / bound).max() > 1.0
    # and the bound is not met by accident of the scale: float32 again
    again = blocks.reference(BLOCK, seqs, params, over,
                             state_dtype=jnp.float32, tail=32)
    assert (np.abs(again - want) / bound).max() <= 0.01


def test_routed_experts_are_built_every_one_held_or_a_share():
    """ISSUE 54: ``num_local_experts`` > 0 builds ``MoEFFN``."""
    routed = {"num_local_experts": 4, "num_experts_per_tok": 2,
              "intermediate_size": 16}
    for held in (None, (1, 2)):
        sym = blocks.symbol(BLOCK, 1, granite=dict(
            routed, **({"held": held} if held else {})))
        moe = [n for n in sym._topo_nodes() if n.op == "MoEFFN"]
        assert len(moe) == 3
        assert int(moe[0].attrs["held_count"]) == (2 if held else 4)


def test_the_scheduler_counts_the_rows_the_state_was_asked_for(engine):
    """Four requests of ragged lengths through the scheduler: the
    counters and the ring's fields say what the state was asked for."""
    prompts, grew, steps = blocks.counted(BLOCK, engine,
                                          ("ssm.rows", "ssm.touched"))
    # positions 0..n+10 of each request are fed, in two mamba layers
    assert grew["ssm.rows"] == 2 * sum(len(p) + 11 for p in prompts)
    assert grew["ssm.rows"] > grew["ssm.touched"] > 0
    assert steps and all("ssm_rows" in r and "ssm_touched" in r
                         for r in steps)
    assert any(r["window"] > 1 and r["ssm_rows"] > r["ssm_touched"]
               for r in steps)
    assert mx.telemetry.get_metric("serve.decode.ssm.rows",
                                   model=engine.name).value > 0
