"""Trinity's block behind the serving path (``attention_decode`` with
grouped K/V heads, a window and rings - rtc.py, ops/pallas_kernels.py -,
block="afmoe" of models/transformer.py, the ring family of
BatchedKVCacheDecoder and serve/decode.py) against the plain reference
chipbench/reference/afmoe.py, at small widths on the CPU:
``sliding_window`` 16 and a window program of 8, so rings of 24 rows
that wrap three times in contexts of some 80 positions; 8 query heads
on 2 K/V heads; a dense layer, two sliding layers and one full layer
without positions; 16 experts of which 4 a token beside a shared one."""
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

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import afmoe as ref  # noqa: E402

CFG = {"vocab_size": 48, "hidden_size": 64, "num_attention_heads": 8,
       "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
       "sliding_window": 16,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "num_dense_layers": 1, "intermediate_size": 96,
       "moe_intermediate_size": 32, "num_experts": 16,
       "num_experts_per_tok": 4, "num_shared_experts": 1,
       "route_norm": True, "route_scale": 2.826, "rope_theta": 10000,
       "rms_norm_eps": 1e-5}
CAPACITY, WINDOW, SLOTS = 128, 8, 3             # WINDOW: the S > 1 program
RING = tfm.ring_rows(CFG["sliding_window"], WINDOW)
#: float32 served against the float32 reference through 4 layers, on
#: logits of magnitude about 3 (measured here: 2e-6 to 5e-6)
TOL = 5e-5


def _symbol(step_len, capacity=CAPACITY, vocab=None, **kw):
    return tfm.get_decode_symbol(
        vocab_size=vocab or CFG["vocab_size"], d_model=CFG["hidden_size"],
        n_layer=CFG["num_hidden_layers"],
        n_head=CFG["num_attention_heads"], pos_embed="rotary",
        rope_base=1e4, capacity=capacity, step_len=step_len, per_slot=True,
        block="afmoe", rms_eps=CFG["rms_norm_eps"], tie_head=False,
        afmoe={k: CFG[k] for k in tfm.AFMOE_KEYS}, max_step_len=WINDOW,
        **kw)


def _params(seed=5):
    symbol = _symbol(1)
    shapes, _, _ = symbol.infer_shape(data=(SLOTS, 1), fed=(SLOTS,))
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(symbol.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        draw = rng.standard_normal(shape)
        out[name] = (1.0 + 0.3 * draw if name.endswith("_gamma")
                     else 0.25 * draw).astype(np.float32)
    return out


PARAMS = _params()


def _bound(step_len, shared=None, slots=SLOTS, params=None, dtype=None,
           symbol=None):
    mod = mx.mod.Module(symbol or _symbol(step_len),
                        data_names=("data", "fed"), label_names=[],
                        compute_dtype=dtype)
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
    """A three-slot pool with its S = 8 window program under one kernel
    tier (the Pallas kernels in interpret mode: 8 heads x 8 rows go to
    ``decode_attn``; the S = 16 program of ``wide_driver`` to
    ``window_attn``)."""
    old = _tier(request.param)
    base = _bound(1)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
    drv.add_window(WINDOW, _bound(WINDOW, shared=base))
    yield drv
    _restore(old)


def _reference(seqs, params=None, **kw):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, CFG, **kw))
    return np.asarray(fwd(params or PARAMS, jnp.asarray(seqs)))


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
    """Eight windows and sixteen S = 1 steps, 80 positions through rings
    of 24 rows (wrapped three times) beside one pool of a row per
    position: grouped heads, the window's lower bound, rotary on the
    sliding layers alone, the gate, the four norms, the experts. Each
    of the controls is far outside the bound at these positions."""
    seqs = _seqs(80)
    got, at = _run(driver, seqs, [(WINDOW, [WINDOW] * SLOTS)] * 8
                   + [(1, [1] * SLOTS)] * 16)
    assert list(at) == [80] * SLOTS and RING == 24
    want = _reference(seqs)
    assert np.max(np.abs(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    for control in ({"window": False}, {"rope_full": True},
                    {"round_to": jnp.float8_e4m3fn}):
        other = _reference(seqs, **control)
        assert np.max(np.abs(other[:, 40:] - want[:, 40:])) > 100 * TOL, \
            control


def test_ragged_slots_and_fed_keep_rings_and_pool_right(driver):
    """Slots at their own lengths, windows that feed 8, 5 and 0 real
    tokens, a slot that decodes while another prefills, pads written
    behind a cursor and written over by the next dispatch: every fed
    position equals the reference, every cursor moves by ``fed``
    alone."""
    seqs = _seqs(60, seed=2)
    schedule = [(WINDOW, [8, 5, 0]), (1, [1, 1, 1]), (WINDOW, [8, 8, 3]),
                (WINDOW, [1, 8, 8]), (1, [1, 0, 1]), (WINDOW, [7, 3, 8]),
                (WINDOW, [8, 8, 8]), (WINDOW, [8, 2, 8]), (1, [1, 1, 1]),
                (WINDOW, [8, 8, 8]), (WINDOW, [5, 8, 8])]
    got, at = _run(driver, seqs, schedule)
    assert min(at) > RING                       # every ring has wrapped
    want = _reference(seqs)
    for slot in range(SLOTS):
        np.testing.assert_allclose(got[slot, :at[slot]],
                                   want[slot, :at[slot]], atol=TOL, rtol=TOL)
    exe = driver._mod._exec_group.executor
    for name in driver._state["cursor"]:
        assert list(exe.aux_dict[name].asnumpy().ravel()) == list(at), name


def test_the_ring_family_is_told_by_the_ops_and_refuses_by_name(driver):
    """Three sliding layers' K and V are rings of 24 rows whatever the
    capacity, the full layer's a pool of a row per position: the driver
    knows by the families, is not positional, rewinds to 0 or inside
    the ring, and refuses a row copy, a prefix store and speculation
    with errors that name the family."""
    exe = driver._mod._exec_group.executor
    assert sorted(driver._state) == ["cursor", "ring", "rows"]
    assert len(driver._state["ring"]) == 6 and len(driver._state["rows"]) == 2
    for name in driver._state["ring"]:
        assert exe.aux_dict[name].shape == (SLOTS, 2, RING, 16), name
    for name in driver._state["rows"]:
        assert exe.aux_dict[name].shape == (SLOTS, 2, CAPACITY, 16), name
    assert not driver.positional and not driver.summarises and driver.feeds
    assert driver.state_bytes["ring"] == 6 * SLOTS * 2 * RING * 16 * 4
    assert driver.state_bytes["rows"] == 2 * SLOTS * 2 * CAPACITY * 16 * 4
    assert driver.ring_slack == RING - 16 - WINDOW + 1 == 1
    _run(driver, _seqs(40), [(WINDOW, [WINDOW] * SLOTS)] * 5)
    driver.rewind(0, 39)                        # inside the ring
    driver.rewind(1, 0)                         # a fresh slot
    with pytest.raises(MXNetError, match="ring"):
        driver.rewind(2, 20)                    # rows written over
    with pytest.raises(MXNetError, match="ring"):
        driver.rewind(2, 41)                    # ahead of the cursor
    with pytest.raises(MXNetError, match="'ring'"):
        driver.capture_rows(0, 8)
    with pytest.raises(MXNetError, match="'ring'"):
        driver.restore_rows(0, {})


def test_attended_rows_are_counted_from_the_cursors(driver):
    _run(driver, _seqs(40), [(WINDOW, [8, 8, 8])] * 3 + [(WINDOW, [8, 8, 0])])
    driver.step(np.zeros((SLOTS, 1), np.int32), fed=[1, 0, 1])
    # slots at 32, 32, 24 fed 1, 0, 1: last queries see 33 and 25 keys,
    # of which a sliding layer attends 16
    live, attended = 33 + 25, 3 * 2 * 16 + 33 + 25
    assert driver.last_reads == {
        "attn.live_rows": 4 * live,
        "attn.capacity_rows": SLOTS * (3 * RING + CAPACITY),
        "attn.attended_rows": attended}


def test_a_sliced_heads_logits_are_the_uncut_heads_first_columns():
    """The head over the first rows of the vocabulary (the cut the
    configuration makes) gives the uncut head's first columns, to
    float32's rounding (a wider product is blocked otherwise): no
    column depends on another."""
    old = _tier("xla")
    try:
        wide = dict(PARAMS)
        rng = np.random.default_rng(9)
        V = CFG["vocab_size"]
        wide["lm_head_weight"] = np.concatenate(
            [PARAMS["lm_head_weight"],
             0.25 * rng.standard_normal((V, 64)).astype(np.float32)])
        wide["lm_tok_embed_weight"] = np.concatenate(
            [PARAMS["lm_tok_embed_weight"],
             0.25 * rng.standard_normal((V, 64)).astype(np.float32)])
        seqs = _seqs(24, seed=7)
        outs = []
        for params, vocab in ((PARAMS, V), (wide, 2 * V)):
            base = _bound(1, params=params, symbol=_symbol(1, vocab=vocab))
            drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
            drv.add_window(WINDOW, _bound(
                WINDOW, shared=base, symbol=_symbol(WINDOW, vocab=vocab)))
            for slot in range(SLOTS):
                drv.join(slot)
            outs.append(np.concatenate(
                [drv.step(seqs[:, w * 8:(w + 1) * 8]).asnumpy()
                 for w in range(3)], axis=1))
    finally:
        _restore(old)
    assert outs[1].shape[-1] == 2 * V
    np.testing.assert_allclose(outs[0], outs[1][..., :V], atol=1e-5,
                               rtol=1e-5)


def test_window_attn_reads_a_long_window_through_the_rings():
    """A window program of 16 (8 heads x 16 rows a K/V head: past what
    ``decode_attn`` keeps resident, so ``window_attn``, the read that
    tiles the queries) over rings of 32: prefill in windows with ragged
    ``fed``, then decode, against the reference."""
    old = _tier("pallas")
    try:
        sym_of = lambda S: tfm.get_decode_symbol(      # noqa: E731
            vocab_size=CFG["vocab_size"], d_model=CFG["hidden_size"],
            n_layer=CFG["num_hidden_layers"],
            n_head=CFG["num_attention_heads"], pos_embed="rotary",
            rope_base=1e4, capacity=CAPACITY, step_len=S, per_slot=True,
            block="afmoe", rms_eps=CFG["rms_norm_eps"], tie_head=False,
            afmoe={k: CFG[k] for k in tfm.AFMOE_KEYS}, max_step_len=16)
        base = _bound(1, symbol=sym_of(1))
        drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
        drv.add_window(16, _bound(16, shared=base, symbol=sym_of(16)))
        assert drv._ring == (32, 16)
        seqs = _seqs(90, seed=8)
        got, at = _run(drv, seqs, [(16, [16, 16, 9])] * 2
                       + [(16, [16, 5, 16])] + [(16, [16] * 3)] * 2
                       + [(1, [1] * SLOTS)] * 6)
    finally:
        _restore(old)
    want = _reference(seqs)
    for slot in range(SLOTS):
        np.testing.assert_allclose(got[slot, :at[slot]],
                                   want[slot, :at[slot]], atol=TOL, rtol=TOL)


def test_the_block_is_served_not_trained_and_checks_its_spec():
    with pytest.raises(MXNetError, match="served, not trained"):
        tfm.get_symbol(block="afmoe")
    with pytest.raises(MXNetError, match="layer_types"):
        tfm.get_decode_symbol(
            block="afmoe", per_slot=True, n_layer=2, n_head=8,
            afmoe={k: CFG[k] for k in tfm.AFMOE_KEYS})
    with pytest.raises(MXNetError, match="per_slot"):
        _symbol(1, cache_dtype="fp8")
    # a ring as long as the context is a pool of a row per position
    short = _symbol(1, capacity=24)
    assert sorted(tfm.slot_state(short)) == ["cursor", "rows"]


def test_scheduler_serves_the_block_and_counts_what_it_attends():
    """``serve_decoder`` over the block: the ladder, chunked prefill
    through the window program, greedy decoding equal to the
    reference's argmax; ``attn.attended_rows`` under ``attn.live_rows``;
    the state's bytes by family in ``stats()``; a prefix store and a
    draft engine refused by the family's name."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler
    old = _tier("xla")
    try:
        sched = mx.serve.serve_decoder(
            _symbol(1), dict(PARAMS), name="afmoe-serve", capacity=CAPACITY,
            ladder=[1, 2], symbol_gen=_symbol, prefill_chunk=WINDOW,
            start=True)
        try:
            prompts = [list(map(int, _seqs(45, seed=s)[0])) for s in (3, 4)]
            handles = [sched.submit(p, max_new_tokens=6) for p in prompts]
            outs = [h.result(timeout=600) for h in handles]
            stats = sched.stats()
        finally:
            sched.stop()
        for prompt, out in zip(prompts, outs):
            seq = np.asarray(prompt + list(out), np.int32)[None]
            want = np.asarray(ref.forward(PARAMS, jnp.asarray(seq), CFG))[0]
            assert list(out) == list(np.argmax(want[44:50], axis=-1))
        counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                    if isinstance(m, telemetry.Counter)
                    and ("model", "afmoe-serve") in m.labels}
        live = counters["serve.decode.attn.live_rows"]
        attended = counters["serve.decode.attn.attended_rows"]
        assert 0 < attended < live
        assert counters["serve.decode.moe.layer_steps"] > 0
        assert set(stats["state_bytes"]) == {"cursor", "ring", "rows"}
        gauge = telemetry.get_metric("serve.decode.state.bytes",
                                     model="afmoe-serve", family="ring")
        assert gauge.value == stats["state_bytes"]["ring"] > 0
        engine = DecodeEngine("afmoe-refuse", _symbol(1), dict(PARAMS),
                              capacity=CAPACITY, ladder=[2],
                              symbol_gen=_symbol, window_lens=(WINDOW,))
        with pytest.raises(MXNetError, match="'ring'"):
            DecodeScheduler(engine, prefix_store=object())
        with pytest.raises(MXNetError, match="'ring'"):
            DecodeScheduler(engine, draft_engine=engine, spec_k=WINDOW)
    finally:
        _restore(old)
