"""Trinity's block behind the serving path (``block="afmoe"`` of
models/transformer.py: grouped K/V heads, sliding layers whose pools are
rings beside full layers, a gated attention output, four norms a layer,
sigmoid-routed experts). What every served block does is
``tests/decode_block_suite.py``'s, over the row ``afmoe`` of
``tests/decode_blocks.py`` against the plain reference
chipbench/reference/afmoe.py: three layers (sliding, full, sliding), 8
query heads on 2 K/V heads of 16, a sliding window of 16 under a window
program of 16 - rings of 32 rows, ``window_attn``'s read. Below that the
block's own: the controls, the rings' sizes, what a dispatch attends, a
sliced head, the window program of 8 that ``decode_attn`` reads, the
scheduler's counts."""
import numpy as np

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

BLOCK = "afmoe"
AFMOE = blocks.config(BLOCK)["afmoe"]
TOL = blocks.TOL[BLOCK]
RING = tfm.ring_rows(AFMOE["sliding_window"], WINDOW)
_W = (WINDOW, [WINDOW] * SLOTS)
_IDLE = [0] * (SLOTS - 3)


def test_each_control_is_far_outside_the_bound(driver):
    """Four windows and sixteen S = 1 steps, 80 positions through rings
    of 32 rows (wrapped twice) beside one pool of a row per position:
    grouped heads, the window's lower bound, rotary on the sliding
    layers alone, the gate, the four norms, the experts. Each of the
    controls is far outside the bound at these positions."""
    seqs = blocks.seqs(BLOCK, 80)
    got, at, _ = blocks.run(driver, seqs, [_W] * 4 + [(1, [1] * SLOTS)] * 16)
    assert list(at) == [80] * SLOTS and RING == 32
    want = blocks.reference(BLOCK, seqs)
    assert np.max(np.abs(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    for control in ({"window": False}, {"rope_full": True},
                    {"round_to": jnp.float8_e4m3fn}):
        other = blocks.reference(BLOCK, seqs, **control)
        assert np.max(np.abs(other[:, 40:] - want[:, 40:])) > 100 * TOL, \
            control


def test_the_rings_are_sized_by_the_window_whatever_the_capacity(driver):
    """Two sliding layers' K and V are rings of 32 rows whatever the
    capacity, the full layer's a pool of a row per position; a cursor
    goes back inside the ring's slack and no further."""
    exe = driver._mod._exec_group.executor
    assert len(driver._state["ring"]) == 4 and len(driver._state["rows"]) == 2
    for name in driver._state["ring"]:
        assert exe.aux_dict[name].shape == (SLOTS, 2, RING, 16), name
    for name in driver._state["rows"]:
        assert exe.aux_dict[name].shape == (SLOTS, 2, CAPACITY, 16), name
    assert driver.state_bytes["ring"] == 4 * SLOTS * 2 * RING * 16 * 4
    assert driver.state_bytes["rows"] == 2 * SLOTS * 2 * CAPACITY * 16 * 4
    assert driver._ring == (RING, 16)
    assert driver.ring_slack == RING - 16 - WINDOW + 1 == 1
    blocks.run(driver, blocks.seqs(BLOCK, 80), [_W] * 3)
    driver.rewind(0, 47)                        # inside the ring
    driver.rewind(1, 0)                         # a fresh slot
    assert list(driver.pos[:3]) == [47, 0, 48]
    # a ring as long as the context is a pool of a row per position
    short = blocks.symbol(BLOCK, 1, capacity=24)
    assert sorted(tfm.slot_state(short)) == ["cursor", "rows"]
    blocks.reset(driver)


def test_attended_rows_are_counted_from_the_cursors(driver):
    blocks.run(driver, blocks.seqs(BLOCK, 40),
               [(WINDOW, [16, 16, 8] + _IDLE)] * 2)
    driver.step(np.zeros((SLOTS, 1), np.int32), fed=[1, 0, 1] + _IDLE)
    # slots at 32, 32, 16 fed 1, 0, 1: last queries see 33 and 17 keys,
    # of which a sliding layer attends 16
    live, attended = 33 + 17, 2 * 2 * 16 + 33 + 17
    assert driver.last_reads == {
        "attn.live_rows": 3 * live,
        "attn.capacity_rows": SLOTS * (2 * RING + CAPACITY),
        "attn.attended_rows": attended}
    blocks.reset(driver)


def test_a_sliced_heads_logits_are_the_uncut_heads_first_columns():
    """The head over the first rows of the vocabulary (the cut the
    configuration makes) gives the uncut head's first columns, to
    float32's rounding (a wider product is blocked otherwise): no
    column depends on another."""
    params = blocks.params(BLOCK)
    wide = dict(params)
    rng = np.random.default_rng(9)
    V = blocks.config(BLOCK)["vocab_size"]
    for name in ("lm_head_weight", "lm_tok_embed_weight"):
        wide[name] = np.concatenate(
            [params[name],
             0.25 * rng.standard_normal((V, 64)).astype(np.float32)])
    seqs = blocks.seqs(BLOCK, 48, seed=7, slots=3)
    outs = []
    with blocks.tier("xla"):
        for given, vocab in ((params, V), (wide, 2 * V)):
            drv = blocks.driver(BLOCK, packed=False, slots=3,
                                arg_params=given, vocab_size=vocab)
            for slot in range(3):
                drv.join(slot)
            outs.append(np.concatenate(
                [drv.step(seqs[:, w * 16:(w + 1) * 16]).asnumpy()
                 for w in range(3)], axis=1))
    assert outs[1].shape[-1] == 2 * V
    np.testing.assert_allclose(outs[0], outs[1][..., :V], atol=1e-5,
                               rtol=1e-5)


def test_decode_attn_reads_a_short_window_through_the_rings():
    """A window program of 8 (8 heads x 8 rows a K/V head: what
    ``decode_attn`` keeps resident; the suite's program of 16 goes to
    ``window_attn``, the read that tiles the queries) over rings of 24:
    slots at their own lengths, windows that feed 8, 5 and 0 real
    tokens, a slot that decodes while another prefills, pads written
    behind a cursor and written over by the next dispatch, every ring
    wrapped: every fed position equals the reference."""
    with blocks.tier("pallas"):
        drv = blocks.driver(BLOCK, packed=False, slots=3, window=8,
                            max_step_len=8)
        assert drv._ring == (24, 16)
        seqs = blocks.seqs(BLOCK, 60, seed=2, slots=3)
        got, at, _ = blocks.run(drv, seqs, [
            (8, [8, 5, 0]), (1, [1, 1, 1]), (8, [8, 8, 3]), (8, [1, 8, 8]),
            (1, [1, 0, 1]), (8, [7, 3, 8]), (8, [8, 8, 8]), (8, [8, 2, 8]),
            (1, [1, 1, 1]), (8, [8, 8, 8]), (8, [5, 8, 8])])
    assert min(at) > 24                         # every ring has wrapped
    want = blocks.reference(BLOCK, seqs)
    for slot in range(3):
        np.testing.assert_allclose(got[slot, :at[slot]],
                                   want[slot, :at[slot]], atol=TOL, rtol=TOL)


def test_the_scheduler_counts_what_it_attends(engine):
    """Requests through the scheduler over the suite's engine:
    ``attn.attended_rows`` under ``attn.live_rows``; the state's bytes
    by family in ``stats()``."""
    from mxnet_tpu import telemetry
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW, prefix_store=None)
    blocks.served(sched, [list(map(int, blocks.seqs(BLOCK, 45, seed=s)[0]))
                          for s in (3, 4)], 6)
    stats = sched.stats()
    counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                if isinstance(m, telemetry.Counter)
                and ("model", engine.name) in m.labels}
    assert 0 < counters["serve.decode.attn.attended_rows"] \
        < counters["serve.decode.attn.live_rows"]
    assert counters["serve.decode.moe.layer_steps"] > 0
    assert set(stats["state_bytes"]) == {"cursor", "ring", "rows"}
    gauge = telemetry.get_metric("serve.decode.state.bytes",
                                 model=engine.name, family="ring")
    assert gauge.value == stats["state_bytes"]["ring"] > 0
