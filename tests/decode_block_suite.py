"""What every served block does, once: each test here runs over the row
of ``tests/decode_blocks.py`` that the importing module names
(``BLOCK = "<row>"`` and ``from decode_block_suite import *`` in
``tests/test_<arch>.py``, so that under ``--dist loadfile`` a block is
a file and a worker of its own; this file's name is not collected).
One driver a block and kernel tier and one engine a block,
module-scoped, serve all of them: prefill in whole and packed windows
then decode against the plain reference, a slot left and joined again,
the state families the ops declare, what rewind / capture / restore can
and cannot do, the builder's refusals (a graph without ``fed`` among
them), ``migrate`` in mid-sequence, mixed prefill and decode through the
scheduler, drafts and prefix stores where the state is carried, and
``serve_decoder`` with no side script."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW

__all__ = [
    "pytest_generate_tests", "block", "driver", "front", "engine",
    "test_prefill_and_decode_match_the_reference_full_forward",
    "test_a_slot_left_and_joined_again_reads_a_clean_state",
    "test_the_ops_declare_their_state_families",
    "test_rewind_capture_and_restore_name_the_families",
    "test_the_builder_refuses_what_the_block_is_not",
    "test_a_graph_without_fed_is_refused_at_construction",
    "test_migrate_mid_sequence_continues_as_the_reference",
    "test_mixed_prefill_and_decode_equals_one_request_at_a_time",
    "test_the_scheduler_takes_drafts_and_prefix_stores_or_says_why_not",
    "test_serve_decoder_serves_the_block_with_no_side_script",
]

BUDGET = tfm.packed_rows(SLOTS, WINDOW)
T = 120                                 # positions a sequence of a test


def _step(block):
    """The positions a decode step of the block feeds: 1, or the length
    of its blocks (``blocks.STEP``)."""
    return blocks.STEP.get(block, 1)


def _common(block):
    return blocks.schedules(WINDOW, SLOTS, BUDGET, step=_step(block))


def _schedules(block):
    return {**_common(block), **blocks.OWN_SCHEDULES.get(block, {})}


def pytest_generate_tests(metafunc):
    """A block's schedules and refusals are its row's: the cases are
    made where the importing module is known."""
    block = metafunc.module.BLOCK
    if "schedule" in metafunc.fixturenames:
        metafunc.parametrize("schedule", sorted(_schedules(block)))
    if "refused" in metafunc.fixturenames:
        metafunc.parametrize("refused", sorted(
            set(blocks.REFUSED.get(block, {})) | {"a_training_graph"}))


@pytest.fixture(scope="module")
def block(request):
    return request.module.BLOCK


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request, block):
    """A pool of ``SLOTS`` with its window program of ``WINDOW`` rows a
    slot, whole and packed, under one kernel tier (the Pallas kernels
    in interpret mode)."""
    with blocks.tier(request.param):
        yield blocks.driver(block)


@pytest.fixture(scope="module")
def front(block):
    """The block behind ``serve_decoder``, nothing else: the scheduler
    it hands back, over rungs of 2 and 4."""
    gen = lambda s: blocks.symbol(block, s)                 # noqa: E731
    with blocks.tier("xla"):
        return mx.serve.serve_decoder(
            gen(1), blocks.params(block), name=f"tiny-{block}",
            capacity=CAPACITY, ladder=[2, 4], symbol_gen=gen,
            prefill_chunk=WINDOW, start=False, clock=mx.serve.FakeClock())


@pytest.fixture(scope="module")
def engine(front):
    return front.engine


def _within(block, got, want, at):
    for slot, n in enumerate(at):
        err = blocks.err(got[slot, :n], want[slot, :n])
        assert err <= blocks.TOL[block], (slot, err)


def test_prefill_and_decode_match_the_reference_full_forward(
        driver, block, schedule):
    """Every fed position's logits that a dispatch hands back against
    the plain reference's full forward, whatever the dispatches'
    shapes, within the block's float32 bound."""
    table = _schedules(block)
    seqs = blocks.seqs(block, T, seed=3 + sorted(table).index(schedule))
    got, at, rows = blocks.run(driver, seqs, table[schedule])
    _within(block, got, blocks.reference(block, seqs), at)
    if schedule == "packed_windows_with_riders":
        assert rows[:6] == [BUDGET] * 6  # the packed program ran them
    if schedule == "whole_windows_then_decode":
        assert rows[:3] == [SLOTS * WINDOW] * 3


def test_a_slot_left_and_joined_again_reads_a_clean_state(driver, block):
    """A slot that carried 54 tokens of another sequence serves a new
    one as a fresh pool does - ``join`` moves the cursor alone, and
    whatever the state holds at cursor 0 is read as nothing - both
    through a window and through S = 1 steps first."""
    table = _common(block)
    blocks.run(driver, blocks.seqs(block, T, seed=4),
               table["whole_windows_then_decode"])
    seqs = blocks.seqs(block, T, seed=5)
    got, at, _ = blocks.run(driver, seqs, table["decode_then_windows"])
    _within(block, got, blocks.reference(block, seqs), at)
    # every layer's cursor moved by ``fed`` alone
    exe = driver._mod._exec_group.executor
    for name in driver._state["cursor"]:
        assert list(exe.aux_dict[name].asnumpy().ravel()) == list(at), name


def test_the_ops_declare_their_state_families(driver, block):
    families = blocks.FAMILIES[block]
    assert sorted(driver._state) == sorted(driver.state_bytes) == families
    assert all(driver.state_bytes[family] > 0 for family in families)
    assert driver.positional == blocks.positional(block)
    assert driver._carried == sorted(set(families) - tfm._INDEXED_FAMILIES)
    assert driver.summarises == ("summary" in families)
    assert len(driver.slot_cells()) == sum(map(len, driver._state.values()))


def test_rewind_capture_and_restore_name_the_families(driver, block):
    """A positional block goes back anywhere and its rows are copied
    from slot to slot (a prefix joined at a cursor decodes as the
    reference); any other refuses a move it cannot make and a row copy
    by the families in its way, and moves nothing when it refuses."""
    seqs = blocks.seqs(block, T, seed=2)
    whole = (WINDOW, [WINDOW] * SLOTS)
    blocks.run(driver, seqs, [whole] * 4)
    if blocks.positional(block):
        step = _step(block)             # a block's edge where it has blocks
        back = 23 if step == 1 else 24
        rows = driver.capture_rows(0, 40)
        driver.restore_rows(1, rows)
        driver.rewind_many([0, 1], [back, 40])
        assert list(driver.pos[:2]) == [back, 40]
        seqs[1] = seqs[0]
        got, at, _ = blocks.run(
            driver, seqs, [(step, [step, step] + [0] * (SLOTS - 2))] * 3,
            start=[back, 40] + [64] * (SLOTS - 2))
        want = blocks.reference(block, seqs)
        for slot, t0 in ((0, back), (1, 40)):
            n = 3 * step
            assert np.abs(got[slot, t0:t0 + n] - want[slot, t0:t0 + n]) \
                .max() <= blocks.TOL[block]
    else:
        named = ".*".join(blocks.FAMILIES[block])
        with pytest.raises(MXNetError, match="cannot move"):
            driver.rewind(0, 3)                 # what stood there is gone
        with pytest.raises(MXNetError, match="cannot move"):
            driver.rewind_many([1, 2], [0, 3])
        with pytest.raises(MXNetError, match="cannot move"):
            driver.rewind(0, 65)                # ahead of the cursor
        assert list(driver.pos) == [64] * SLOTS  # a refusal moves nothing
        driver.rewind(1, 64)                    # where it is
        driver.rewind(2, 0)
        assert list(driver.pos[:3]) == [64, 64, 0]
        for call in (lambda: driver.capture_rows(0, 8),
                     lambda: driver.restore_rows(0, {})):
            with pytest.raises(MXNetError, match=named):
                call()
    # overflowing is about the context, not about a pool's rows
    driver.pos[:] = [CAPACITY - 16, CAPACITY - 15] + [5] * (SLOTS - 2)
    assert driver.overflowing(WINDOW) == [1]
    assert driver.overflowing(1) == []
    blocks.reset(driver)


def test_the_builder_refuses_what_the_block_is_not(block, refused):
    """Each key of the row's ``REFUSED`` that the block does not build,
    by the error that names it; and a training graph of a block that is
    served alone."""
    if refused == "a_training_graph":
        kw = blocks.config(block)
        if block in blocks.FUSED:
            assert tfm.get_symbol(seq_len=8, **kw).list_arguments()
            return
        with pytest.raises(MXNetError, match="served, not trained"):
            tfm.get_symbol(block=kw["block"])
        return
    over, match = blocks.REFUSED[block][refused]
    with pytest.raises(MXNetError, match=match):
        sym = blocks.symbol(block, 1, **over)
        sym.infer_shape(**{d.name: d.shape
                           for d in blocks.inputs(sym, SLOTS, 1)})


def _without_fed(block, step_len):
    """A slot-pooled graph of the block built by hand without the
    ``fed`` input: the builder makes none."""
    import inspect
    given = {k: p.default for k, p in inspect.signature(
        tfm.get_decode_symbol).parameters.items()}
    given.update(blocks.config(block), step_len=step_len, capacity=CAPACITY,
                 per_slot=True)
    spec = dict(tfm._spec(given, decode=True), fed=False)
    if spec["moe"]:
        spec["moe"] = {k: v for k, v in spec["moe"].items()
                       if k != "step_len"}
    logits, fed = tfm._logits(spec)
    assert fed is None and "fed" not in logits.list_arguments()
    return logits


@pytest.mark.parametrize("by", ["driver", "engine"])
def test_a_graph_without_fed_is_refused_at_construction(block, by):
    """A slot-pooled graph takes ``fed`` and there is no other kind.
    One built by hand without it is refused by
    ``BatchedKVCacheDecoder`` and by ``DecodeEngine`` with the builder's
    name, before anything is compiled; so is a graph that takes it and
    was bound with it among the parameters."""
    if block not in blocks.FUSED:
        # only the trained blocks have a form without the input to
        # build by hand: the builder's graph takes it, and is refused
        # where it was left among the parameters
        sym = blocks.symbol(block, 1)
        assert "fed" in sym.list_arguments()
        if by == "engine":              # binds it as data itself
            return
        mod = mx.mod.Module(sym, data_names=["data"], label_names=[])
        mod.bind([d for d in blocks.inputs(sym, SLOTS, 1)
                  if d.name == "data"], None, for_training=False)
        with pytest.raises(MXNetError, match="bind it as data"):
            tfm.BatchedKVCacheDecoder(mod, CAPACITY, slots=SLOTS)
        return
    sym = _without_fed(block, 1)
    match = r"fed.*get_decode_symbol\(per_slot=True\)"
    if by == "driver":
        mod = blocks.bound(sym, 1, arg_params=blocks.params(block))
        with pytest.raises(MXNetError, match=match):
            tfm.BatchedKVCacheDecoder(
                mod, CAPACITY, slots=SLOTS,
                pos_embed=blocks.config(block)["pos_embed"])
    else:
        with pytest.raises(MXNetError, match=match):
            mx.serve.DecodeEngine(
                f"unfed-{block}", sym, blocks.params(block),
                capacity=CAPACITY, ladder=[SLOTS],
                symbol_gen=lambda s: _without_fed(block, s))


def test_migrate_mid_sequence_continues_as_the_reference(engine, block):
    """Two slots at positions 37 and 50 of the 2-slot pool move to the
    4-slot pool, swapped, with everything the families hold and their
    cursors, and decode on: the reference's logits."""
    seqs = blocks.seqs(block, 60, seed=6, slots=2)
    want = blocks.reference(block, seqs)
    small, big = engine.driver(2), engine.driver(4)
    for drv in (big, small):
        drv.active[:] = False
    small.join(0), small.join(1)
    step = _step(block)                 # whole blocks where it has blocks
    lens, at = [37 // step * step, 50 // step * step], [0, 0]
    while any(a < n for a, n in zip(at, lens)):
        tokens = np.zeros((2, WINDOW), np.int32)
        fed = np.zeros(2, np.int32)
        for s in range(2):
            n = min(WINDOW, lens[s] - at[s])
            tokens[s, :n] = seqs[s, at[s]:at[s] + n]
            fed[s], at[s] = n, at[s] + n
        small.step(tokens, fed=fed)
    engine.migrate(2, 4, [(0, 3), (1, 1)])
    assert list(big.pos) == [0, lens[1], 0, lens[0]] \
        and not small.active.any()
    for j in range(0, (5 if step == 1 else 3) * step, step):
        tokens = np.zeros((4, step), np.int32)
        tokens[3] = seqs[0, lens[0] + j:lens[0] + j + step]
        tokens[1] = seqs[1, lens[1] + j:lens[1] + j + step]
        out = big.step(tokens, fed=[0, step, 0, step]).asnumpy()
        for row, (seq, t0) in ((3, (0, lens[0])), (1, (1, lens[1]))):
            assert np.abs(out[row] - want[seq, t0 + j:t0 + j + step]) \
                .max() <= blocks.TOL[block]
    big.active[:] = False
    assert sorted(engine.state_bytes) == blocks.FAMILIES[block]


def test_mixed_prefill_and_decode_equals_one_request_at_a_time(engine, block):
    """Four requests of ragged lengths admitted together through the
    scheduler (packed windows with riders, a rung switch, run-ahead):
    the greedy tokens of each request served alone, which are the
    reference's; nothing compiles behind the warm-up and nobody is
    rewound after a window."""
    rng = np.random.default_rng(8)
    vocab = blocks.config(block)["vocab_size"]
    prompts = [rng.integers(0, vocab, n).tolist() for n in (45, 9, 30, 70)]
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW, prefix_store=None)
    alone = [blocks.served(sched, [p], 12)[0] for p in prompts]
    before = sched._counter("cursor.rows").value
    mixed = blocks.served(sched, prompts, 12)
    assert mixed == alone and all(len(t) == 12 for t in mixed)
    assert sched.stats()["compiles_since_warmup"] == 0
    if block in blocks.STEP:
        # a feed that keeps nothing is taken back, a block dispatch
        # runs ahead of the one on the chip, and the tokens are the
        # reference's own procedure's
        assert sched._counter("cursor.rows").value - before > 4
        ahead = sched.stats()["runahead"]
        assert 0 < ahead["blocks"] <= ahead["launched"]
        assert alone[1] == blocks.plain_greedy(block, prompts[1], 12)
    else:
        # the joins; nothing rewound
        assert sched._counter("cursor.rows").value - before == 4
        assert sched.stats()["runahead"]["launched"] > 0
        seq = np.asarray([prompts[1] + alone[1][:-1]], np.int32)
        want = blocks.reference(block, seq)[0]
        assert alone[1] == np.argmax(want[len(prompts[1]) - 1:],
                                     axis=-1).tolist()
    for family in blocks.FAMILIES[block]:
        assert mx.telemetry.get_metric(
            "serve.decode.state.bytes", model=engine.name,
            family=family).value > 0


def test_the_scheduler_takes_drafts_and_prefix_stores_or_says_why_not(
        engine, block):
    """Speculation rolls the cursor back and a prefix store copies rows:
    a positional block's scheduler takes both, any other's refuses each
    by the families in its way."""
    from mxnet_tpu.serve.prefix import PrefixStore
    clock = mx.serve.FakeClock()
    if blocks.positional(block):
        sched = mx.serve.DecodeScheduler(engine, clock=clock,
                                         prefix_store=PrefixStore(1 << 20))
        assert sched.prefix_store is not None
        if block in blocks.STEP:        # a draft proposes a token a step
            with pytest.raises(MXNetError, match=r"spec_k.*by blocks of"):
                mx.serve.DecodeScheduler(engine, clock=clock,
                                         draft_engine=engine, spec_k=4)
        return
    named = ".*".join(sorted(set(blocks.FAMILIES[block]) - {"cursor"}))
    with pytest.raises(MXNetError, match=rf"prefix_store.*{named}"):
        mx.serve.DecodeScheduler(engine, clock=clock,
                                 prefix_store=PrefixStore(1 << 20))
    with pytest.raises(MXNetError, match=rf"spec_k.*{named}"):
        mx.serve.DecodeScheduler(engine, clock=clock, draft_engine=engine,
                                 spec_k=4)


def test_serve_decoder_serves_the_block_with_no_side_script(front, block):
    """One prompt through the scheduler ``serve_decoder`` built: the
    reference's greedy tokens."""
    assert (front.prefix_store is None) or blocks.positional(block)
    prompt = np.random.default_rng(1).integers(
        0, blocks.config(block)["vocab_size"], 41)
    tokens = blocks.served(front, [prompt.tolist()], 6)[0]
    if block in blocks.STEP:
        assert tokens == blocks.plain_greedy(block, prompt.tolist(), 6)
        return
    seq = np.concatenate([prompt, tokens[:-1]])[None].astype(np.int32)
    want = blocks.reference(block, seq)[0]
    assert tokens == np.argmax(want[40:], axis=-1).tolist()
