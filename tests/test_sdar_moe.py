"""SDAR's block behind the serving path (``block="sdar_moe"`` of
models/transformer.py: Trinity's head geometry without gate, window or
ring, OLMoE's router with the chosen weights normed, and ONE changed
rule - a query attends every key up to the end of its own block of 4
positions - under which a decode step is a block: fed with some
positions the mask id, its rows thrown away, some positions decided
from the logits on the device, fed again until none is undecided and
once more to keep its keys and values; a block dispatch launched while
the one before it is on the chip, its ids, its mask and who goes back
taken from there). What every served block does is
``tests/decode_block_suite.py``'s, over the row ``sdar_moe`` of
``tests/decode_blocks.py`` against the plain reference
chipbench/reference/sdar_moe.py (its schedules in whole blocks:
``blocks.STEP``). Below that the block's own: the programs
``check_reference`` drives, a masked feed that leaves nothing behind,
the composition against the kernels, the causal control, and the
scheduler's procedure against the reference's ``generate``."""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import rtc
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serve.sampling import SamplingParams

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

BLOCK = "sdar_moe"
SDAR = blocks.config(BLOCK)["sdar"]
L, MASK = SDAR["block_length"], SDAR["mask_token_id"]
TOL = blocks.TOL[BLOCK]
VOCAB = blocks.config(BLOCK)["vocab_size"]
_STATIC = SamplingParams(remasking="low_confidence_static")


def _pools(drv, upto):
    """Every layer's K and V rows below ``upto`` and every cursor."""
    return [np.asarray(cell.asjax())[:, :, :upto]
            for _nm, cell in drv._cells("rows")] \
        + [np.asarray(c.asjax()) for c in drv._cursor_cells()]


def test_the_programs_check_reference_drives_match_forward_on_every_row(
        driver):
    """Whole windows without ``fed``, block steps fed masked, taken
    back and fed clean, then a chunk beside a rider's block and the
    roles swapped: every row of every dispatch within the bound of the
    reference's forward over the same ids (``serve_runner
    .check_reference``'s three parts at a tiny size)."""
    seqs = blocks.seqs(BLOCK, 3 * WINDOW + 4 * L, seed=11)
    want = blocks.reference(BLOCK, seqs)
    blocks.reset(driver)
    for slot in range(SLOTS):
        driver.join(slot)
    for w in range(2):
        out = driver.step(seqs[:, w * WINDOW:(w + 1) * WINDOW]).asnumpy()
        np.testing.assert_allclose(out, want[:, w * WINDOW:(w + 1) * WINDOW],
                                   atol=TOL, rtol=TOL)
    rng = np.random.default_rng(3)
    at = 2 * WINDOW
    for _ in range(2):
        hidden = rng.random((SLOTS, L)) < 0.5
        masked = np.where(hidden, MASK, seqs[:, at:at + L])
        ids = seqs.copy()
        ids[:, at:at + L] = masked
        out = driver.step(masked).asnumpy()
        np.testing.assert_allclose(
            out, blocks.reference(BLOCK, ids)[:, at:at + L], atol=TOL,
            rtol=TOL)
        driver.rewind_many(list(range(SLOTS)), [at] * SLOTS)
        out = driver.step(seqs[:, at:at + L]).asnumpy()
        np.testing.assert_allclose(out, want[:, at:at + L], atol=TOL,
                                   rtol=TOL)
        at += L
    cursors = [at] * SLOTS
    for widths in ((WINDOW, L, 0, L), (L, WINDOW, L, 0)):
        tokens = np.zeros((SLOTS, WINDOW), np.int32)
        for slot, n in enumerate(widths):
            tokens[slot, :n] = seqs[slot, cursors[slot]:cursors[slot] + n]
        out = driver.step(tokens, fed=list(widths)).asnumpy()
        assert out.shape[1] == 1        # the packed program's last rows
        for slot, n in enumerate(widths):
            cursors[slot] += n
            if n:
                np.testing.assert_allclose(
                    out[slot, 0], want[slot, cursors[slot] - 1], atol=TOL,
                    rtol=TOL)
    assert list(driver.pos) == cursors
    blocks.reset(driver)


def test_a_masked_feed_leaves_nothing_behind(driver):
    """The pools and the cursors after {a masked feed, the cursors put
    back, the clean feed} are bit for bit those after the clean feed
    alone, and so are the clean feed's logits: a feed that is taken
    back is as if it had not been."""
    seqs = blocks.seqs(BLOCK, WINDOW + L, seed=12)
    clean = seqs[:, WINDOW:]
    seen = []
    for masked in (True, False):
        blocks.reset(driver)
        for slot in range(SLOTS):
            driver.join(slot)
        driver.step(seqs[:, :WINDOW])
        if masked:
            driver.step(np.where(np.arange(L) % 2 == 0, MASK, clean))
            driver.rewind_many(list(range(SLOTS)), [WINDOW] * SLOTS)
        out = driver.step(clean).asnumpy()
        seen.append([out] + _pools(driver, WINDOW + L))
    for a, b in zip(*seen):
        np.testing.assert_array_equal(a, b)
    blocks.reset(driver)


def test_the_mirror_follows_a_rewind_whose_mask_is_the_devices(driver):
    """``rewind_many(where=)`` sends back whom a mask on the device
    names; the host's mirror goes back with every slot named until
    ``kept`` says who stayed, and is then the device's cells. A second
    ``where`` before that, a ``kept`` of a slot not named and a
    ``kept`` with nothing before it are refused."""
    seqs = blocks.seqs(BLOCK, WINDOW + L, seed=13)
    blocks.reset(driver)
    for slot in range(SLOTS):
        driver.join(slot)
    driver.step(seqs[:, :WINDOW])
    driver.step(seqs[:, WINDOW:])
    named = list(range(SLOTS - 1))              # the last slot: nobody's
    back = np.arange(SLOTS) % 2 == 0
    back[-1] = False
    driver.rewind_many(named, [WINDOW] * len(named), where=jnp.asarray(back))
    assert list(driver.pos) == [WINDOW] * len(named) + [WINDOW + L]
    with pytest.raises(MXNetError, match="before kept"):
        driver.rewind_many(named, [WINDOW] * len(named),
                           where=jnp.asarray(back))
    stayed = [slot for slot in named if not back[slot]]
    with pytest.raises(MXNetError, match="did not"):
        driver.kept(stayed + [SLOTS - 1])
    driver.kept(stayed)
    with pytest.raises(MXNetError, match="no rewind_many"):
        driver.kept(stayed)
    want = np.where(back, WINDOW, WINDOW + L)
    assert list(driver.pos) == list(want)
    for cell in driver._cursor_cells():
        assert (np.asarray(cell.asjax()).reshape(SLOTS, -1)
                == want[:, None]).all()
    blocks.reset(driver)


@pytest.mark.parametrize("S,fed", [(1, None), (L, None), (L, [L, 0, L]),
                                   (16, None), (16, [16, 8, 4]),
                                   (16, [0, 12, 16])])
def test_the_composition_equals_the_kernels_under_the_block_mask(S, fed):
    """``attention_decode(block=4)``'s composition against its Pallas
    lowering in interpret mode: the S = 1 program, a block
    (``decode_attn``: 8 query heads x 4 rows of one K/V head), and a
    window of 16 (``window_attn``: 128 rows a K/V head) with ragged
    ``fed`` in whole blocks; the pools, the cursors and every real row
    of the result agree."""
    rs = np.random.RandomState(S + 7 * len(fed or ()))
    B, H, Hkv, Dh, C = 3, 8, 1, 16, 64
    attrs = {"capacity": C, "rope": True, "rope_base": 1e6,
             "per_slot": True, "kv_heads": Hkv, "fed": True, "block": L}
    q = jnp.asarray(rs.randn(B, H, S, Dh), jnp.float32)
    k, v = (jnp.asarray(rs.randn(B, Hkv, S, Dh), jnp.float32)
            for _ in range(2))
    pools = [jnp.asarray(rs.randn(B, Hkv, C, Dh), jnp.float32)
             for _ in range(2)]
    cursor = jnp.asarray([[20], [0], [36]], jnp.int32)
    given = jnp.asarray([S] * B if fed is None else fed, jnp.int32)
    plain = rtc._attention_decode_fwd(
        attrs, [q, k, v, given], pools + [cursor], False, None)
    with blocks.tier("pallas"):
        kernel = rtc._attention_decode_pallas_variant(
            attrs, [q, k, v, given], pools + [cursor], False, None)
    for a, b in zip(plain[1], kernel[1]):       # pools and cursors
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert list(np.asarray(plain[1][2]).ravel()) \
        == [c + n for c, n in zip([20, 0, 36], np.asarray(given))]
    for slot, n in enumerate(np.asarray(given)):
        np.testing.assert_allclose(
            np.asarray(plain[0][0])[slot, :, :n],
            np.asarray(kernel[0][0])[slot, :, :n], atol=2e-5, rtol=2e-5)
    # and the rule itself: row s of a slot at cursor c attends the keys
    # below the end of its block, whatever lies in the pool past it
    again = rtc._attention_decode_fwd(
        attrs, [q, k, v, given], [p.at[:, :, 44:].set(1e3) for p in pools]
        + [cursor], False, None)
    for slot in (0, 1):                         # blocks end below 44
        np.testing.assert_array_equal(
            np.asarray(plain[0][0])[slot], np.asarray(again[0][0])[slot])


def test_the_op_refuses_a_block_with_a_window_a_ring_or_ragged_rows():
    q = jnp.zeros((2, 4, 6, 8))
    pool = jnp.zeros((2, 4, 32, 8))
    base = {"capacity": 32, "per_slot": True, "block": 4}
    for over, shape in (({"window": 8}, (2, 4, 4, 8)),
                        ({"window": 8, "ring": 16}, (2, 4, 4, 8)),
                        ({}, q.shape), ({"per_slot": False}, (2, 4, 4, 8))):
        with pytest.raises(MXNetError, match="block=4.*no window= and no "
                           "ring="):
            rtc._decode_geometry(dict(base, **over), jnp.zeros(shape), pool)
    assert rtc._decode_geometry(base, jnp.zeros((2, 4, 1, 8)), pool).block \
        == 4


def test_the_same_rows_under_a_causal_mask_are_outside_the_bound(driver):
    """The control: the reference with the causal mask in place of the
    block mask differs from the block's forward by far more than the
    bound at these rows, which the served rows are inside."""
    seqs = blocks.seqs(BLOCK, 2 * WINDOW, seed=13)
    got, at, _ = blocks.run(driver, seqs, [(WINDOW, [WINDOW] * SLOTS)]
                            + blocks.steps(4, step=L))
    want = blocks.reference(BLOCK, seqs)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)
    causal = blocks.reference(BLOCK, seqs, causal=True)
    assert np.max(np.abs(causal - want)) > 100 * TOL
    assert np.max(np.abs(causal - got)) > 100 * TOL
    blocks.reset(driver)


# ------------------------------------------------------------ the scheduler
def _scheduler(engine, **kw):
    return mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                    prefill_chunk=WINDOW, prefix_store=None,
                                    **kw)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, VOCAB - 1, n).tolist()


def _grew(sched, keys=("feeds", "blocks", "decided", "rows_dropped",
                       "undelivered")):
    return {k: sched._counter(f"diffusion.{k}").value for k in keys}


def _ahead(sched):
    """What ran ahead, by the scheduler's own count."""
    return dict(sched.stats()["runahead"])


def _records(engine):
    from mxnet_tpu.telemetry import flightrec
    return [r for r in flightrec.get_records()
            if r.get("kind") == "serve.decode.step"
            and r.get("model") == engine.name]


def _behind(sched):
    """The synchronous order: every dispatch planned after the commit
    of the one before it."""
    sched._plan_ahead = lambda d, now: None
    return sched


_LOW = SamplingParams(confidence_threshold=0.08)


@pytest.mark.parametrize("P,N", [(16, 7), (13, 10), (6, 5), (23, 9),
                                 (2, 3)])
@pytest.mark.parametrize("request_", ["quota", "threshold"])
@pytest.mark.parametrize("order", ["ahead", "behind"])
def test_the_served_stream_is_the_references_generate(engine, P, N, request_,
                                                      order):
    """``P mod L`` in {0, 1, 2, 3}, ``N`` no multiple of ``L``: under
    the quota schedule (a position a feed) a request costs exactly the
    feeds of its blocks, a feed more than a block has undecided
    positions; under a threshold low enough that some feeds decide two
    or more positions fewer - the feed behind such a one is the commit,
    and only the chip's mask says so when it is launched. Either way
    the reference's tokens and the same feeds, whether each block
    dispatch is launched behind the one on the chip (``ahead``) or
    after its commit."""
    from mxnet_tpu.telemetry import flightrec
    sampling = _STATIC if request_ == "quota" else _LOW
    more = {} if request_ == "quota" else {"confidence_threshold": 0.08}
    sched = _scheduler(engine)
    if order == "behind":
        _behind(sched)
    prompt = _prompt(P, seed=P)
    before, ran = _grew(sched), _ahead(sched)
    flightrec.clear()
    handle = sched.submit(prompt, max_new_tokens=N, sampling=sampling)
    sched.pump()
    trace = []
    want = blocks.plain_greedy(BLOCK, prompt, N, trace=trace,
                               remasking=sampling.remasking, **more)
    assert handle.result(timeout=5).tolist() == want and len(want) == N
    grew = {k: v - before[k] for k, v in _grew(sched).items()}
    n_blocks = -(-(P + N) // L) - P // L
    undecided = n_blocks * L - P % L
    assert grew["blocks"] == n_blocks and grew["decided"] == undecided
    assert grew["undelivered"] == -(P + N) % L
    assert grew["feeds"] == len(trace) + n_blocks
    assert grew["rows_dropped"] == L * len(trace)
    if request_ == "quota":
        assert grew["feeds"] == undecided + n_blocks
        if P % L == 0:
            assert grew["feeds"] == n_blocks * (L + 1)
    else:
        assert any(decided.sum() > 1 for *_feed, decided in trace)
        assert grew["feeds"] < undecided + n_blocks
    assert sched.stats()["compiles_since_warmup"] == 0
    ran = {k: v - ran[k] for k, v in _ahead(sched).items()}
    records = [r for r in _records(engine) if "block" in r]
    assert ran["blocks"] == sum(r["ahead"] for r in records)
    assert ran["dropped"] == 0
    if order == "behind":
        assert ran["launched"] == 0
    else:
        # one request alone: every block dispatch but the first behind
        # a window (or the first of all) was launched ahead
        assert ran["blocks"] == ran["launched"] == len(records) - 1


def test_staggered_slots_a_window_among_block_steps_and_a_rung_switch(
        engine):
    """Requests that arrive while others are in mid-block: the late
    ones are prefilled by a window in which the decoding slots wait,
    the rung grows from 2 to 4 with blocks in flight and shrinks again,
    and every stream is the stream of the request served alone."""
    from mxnet_tpu.telemetry import flightrec
    sched = _scheduler(engine)
    prompts = [_prompt(n, seed=40 + n) for n in (21, 6, 37, 18)]
    alone = [blocks.plain_greedy(BLOCK, p, 9) for p in prompts]
    flightrec.clear()
    migrations = sched.migrations
    handles = [sched.submit(p, max_new_tokens=9) for p in prompts[:2]]
    sched.pump(max_iterations=3)        # both in mid-block on rung 2
    assert sched._rung == 2 and all(
        s.block is not None and s.block.undecided.any()
        for s in sched._active())
    handles += [sched.submit(p, max_new_tokens=9) for p in prompts[2:]]
    sched.pump()
    assert [h.result(timeout=5).tolist() for h in handles] == alone
    assert sched.migrations - migrations >= 2
    records = _records(engine)
    kinds = ["block" if "block" in r else "window" for r in records]
    first = kinds.index("window", 3)    # a window among the block steps
    assert "block" in kinds[:first] and "block" in kinds[first:]
    for before, r in zip([None] + records, records):
        # a block dispatch behind a block dispatch runs ahead; a window
        # and the block dispatch behind it wait for a commit
        if "block" not in r or before is None or "block" not in before:
            assert r["ahead"] == 0
        if "block" in r:
            assert r["block"] == r["window"] == L
            assert 0 <= r["decided"] <= L * r["tentative"]
            assert r["denoise_us"] >= 0 and r["tentative"] <= r["rung"]
    assert sched.stats()["compiles_since_warmup"] == 0


def _cursor_cells(drv):
    """Every layer's cursor as it lies on the device, a column a
    layer."""
    return np.stack([np.asarray(c.asjax()).reshape(-1)
                     for c in drv._cursor_cells()])


@pytest.mark.parametrize("request_", ["quota", "threshold"])
def test_run_ahead_serves_the_streams_of_the_synchronous_order(
        engine, request_):
    """Four requests together (``P mod L`` over 0..3, answers no
    multiple of ``L``, the rung grown to 4 and shrunk with blocks in
    flight), under the quota schedule and under a threshold that the
    tiny model's confidences pass at some positions, so that a block
    commits early - the case only the chip's mask can plan: token for
    token the streams of the scheduler that plans every dispatch after
    a commit, which are the reference's; after every commit the host's
    cursor mirror is the device's cursor cells, a feed launched ahead
    included; nothing compiles, in the program cache or behind it."""
    sampling = _STATIC if request_ == "quota" else _LOW
    more = {} if request_ == "quota" else {"confidence_threshold": 0.08}
    prompts = [_prompt(n, seed=60 + n) for n in (16, 13, 6, 23)]
    lens = (7, 10, 5, 9)
    want = [blocks.plain_greedy(BLOCK, p, n, remasking=sampling.remasking,
                                **more) for p, n in zip(prompts, lens)]
    streams = {}
    for order in ("behind", "ahead"):
        sched = _scheduler(engine)
        if order == "behind":
            _behind(sched)
        ran, grew = _ahead(sched), _grew(sched)
        compiled = engine.backend_compiles_since_warmup()
        migrations = sched.migrations
        handles = [sched.submit(p, max_new_tokens=n, sampling=sampling)
                   for p, n in zip(prompts, lens)]
        while sched.pump(max_iterations=1):
            drv = engine.driver(sched._rung)
            cells = _cursor_cells(drv)
            assert (cells == drv.pos[None, :]).all(), (order, cells, drv.pos)
            for seq in sched._active():
                # behind the cursor of its sequence by nothing but the
                # rows of a feed on the chip that is kept
                ahead = drv.pos[seq.slot] - seq.fed
                assert ahead in ((0, L) if sched._ahead else (0,))
        streams[order] = [h.result(timeout=5).tolist() for h in handles]
        assert sched.migrations - migrations >= 2
        assert sched.stats()["compiles_since_warmup"] == 0
        if order == "ahead":
            # (the first order through compiles ``migrate``'s eager
            # copies; the forms fed from the chip were warmed)
            assert engine.backend_compiles_since_warmup() == compiled
        ran = {k: v - ran[k] for k, v in _ahead(sched).items()}
        grew = {k: v - grew[k] for k, v in _grew(sched).items()}
        streams[order + ".feeds"] = grew
        assert (ran["launched"] > 0) == (order == "ahead")
    assert streams["ahead"] == streams["behind"] == want
    # and the same feeds: blocks, positions decided, rows dropped - a
    # position a feed under the quota, fewer feeds where a confidence
    # passed the threshold
    fed = streams["ahead.feeds"]
    assert fed == streams["behind.feeds"]
    assert (fed["feeds"] == fed["decided"] + fed["blocks"]) \
        == (request_ == "quota")


def test_a_deadline_that_passes_drops_the_feed_on_the_chip(engine):
    """A sequence whose time runs out while its next feed is on the
    chip leaves with what it was delivered, that feed is dropped and
    counted, and the request that takes its slot starts clean."""
    sched = _scheduler(engine)
    prompt, after = _prompt(10, seed=71), _prompt(7, seed=72)
    dropped = _ahead(sched)["dropped"]
    handle = sched.submit(prompt, max_new_tokens=12, deadline_ms=1000)
    sched.pump(max_iterations=5)
    assert sched._ahead is not None and sched._ahead.block
    sched._clock.advance(2.0)
    behind = sched.submit(after, max_new_tokens=6)
    sched.pump()
    assert handle.finish_reason == "deadline"
    got = handle.result(timeout=5).tolist()
    assert 0 < len(got) < 12
    assert got == blocks.plain_greedy(BLOCK, prompt, 12)[:len(got)]
    assert _ahead(sched)["dropped"] - dropped == 1
    assert behind.result(timeout=5).tolist() \
        == blocks.plain_greedy(BLOCK, after, 6)
    drv = engine.driver(sched._rung)
    assert (_cursor_cells(drv) == drv.pos[None, :]).all()


def test_a_slot_that_overflows_fails_alone(engine):
    sched = _scheduler(engine)
    long, short = _prompt(CAPACITY - 8, seed=5), _prompt(10, seed=6)
    doomed = sched.submit(long, max_new_tokens=12)
    fine = sched.submit(short, max_new_tokens=7)
    sched.pump()
    with pytest.raises(MXNetError, match="overflowed its KV-cache slice"):
        doomed.result(timeout=5)
    # what it was delivered before is the reference's
    assert doomed.tokens == blocks.plain_greedy(BLOCK, long, 8)
    assert fine.result(timeout=5).tolist() \
        == blocks.plain_greedy(BLOCK, short, 7)


def test_an_eos_inside_a_block_ends_the_request_at_its_commit(engine):
    """The stream stops at the id and the request retires at that
    block's commit. The host has the block's ids a feed before the one
    that keeps it, so it plans the slot as gone behind that feed:
    nothing was launched for it that has to be dropped, and the
    request that takes the slot starts clean."""
    sched = _scheduler(engine)
    prompt, after = _prompt(9, seed=21), _prompt(11, seed=22)
    stream = blocks.plain_greedy(BLOCK, prompt, 14)
    at = next(i for i, t in enumerate(stream)
              if i >= 2 and t not in stream[:i])
    before, ran = _grew(sched), _ahead(sched)
    handle = sched.submit(prompt, max_new_tokens=14, eos_id=stream[at])
    handle.add_done_callback(
        lambda _h: behind.append(sched.submit(after, max_new_tokens=6)))
    behind = []
    sched.pump()
    assert handle.result(timeout=5).tolist() == stream[:at]
    assert handle.finish_reason == "eos"
    assert stream[:at] == blocks.plain_greedy(BLOCK, prompt, 14,
                                              eos_id=stream[at])
    # the block that holds it was committed whole
    grew = {k: v - before[k] for k, v in _grew(sched).items()}
    assert grew["blocks"] - (-(-(11 + 6) // L) - 11 // L) \
        == (9 + at) // L + 1 - 9 // L
    ran = {k: v - ran[k] for k, v in _ahead(sched).items()}
    assert ran["blocks"] > 0 and ran["dropped"] == 0
    assert behind[0].result(timeout=5).tolist() \
        == blocks.plain_greedy(BLOCK, after, 6)
    assert sched._slots == [None] * sched._rung


def test_a_prompt_may_hold_the_mask_id(engine):
    """Undecided positions are tracked by position: a prompt with the
    mask id inside its whole blocks and in the tail its first block
    holds is served as the reference serves it."""
    sched = _scheduler(engine)
    prompt = _prompt(14, seed=31)
    prompt[3] = prompt[12] = prompt[13] = MASK
    got = blocks.served(sched, [prompt], 6)[0]
    assert got == blocks.plain_greedy(BLOCK, prompt, 6)


def test_a_prefix_join_lands_on_a_blocks_edge(engine):
    from mxnet_tpu.serve.prefix import PrefixStore
    sched = mx.serve.DecodeScheduler(
        engine, clock=mx.serve.FakeClock(), prefill_chunk=WINDOW,
        prefix_store=PrefixStore(1 << 20))
    shared = _prompt(38, seed=50)
    first, second = shared + _prompt(5, seed=51), shared + _prompt(7, seed=52)
    sched.submit(first, max_new_tokens=5, prefix_id="doc")
    sched.pump()
    joined = sched._counter("prefix.joined_tokens").value
    handle = sched.submit(second, max_new_tokens=6, prefix_id="doc")
    sched.pump()
    # 38 shared tokens: 36 of them whole blocks
    assert sched._counter("prefix.joined_tokens").value - joined == 36
    assert handle.result(timeout=5).tolist() \
        == blocks.plain_greedy(BLOCK, second, 6)


def test_what_the_engine_cannot_serve_is_refused_by_name(engine):
    sched = _scheduler(engine)
    with pytest.raises(MXNetError, match="is not greedy.*by blocks of 4"):
        sched.submit([1, 2, 3], sampling=SamplingParams(temperature=0.7))
    with pytest.raises(MXNetError, match="denoising_steps 5 of a block of 4"):
        sched.submit([1, 2, 3], sampling=SamplingParams(denoising_steps=5))
    with pytest.raises(MXNetError, match="spec_k.*by blocks of 4"):
        _scheduler(engine, draft_engine=engine, spec_k=4)
    for kw, match in (({"remasking": "random"}, "remasking 'random'"),
                      ({"confidence_threshold": 1.5}, "confidence_threshold"),
                      ({"denoising_steps": 0}, "denoising_steps 0")):
        with pytest.raises(MXNetError, match=match):
            SamplingParams(**kw)
    gen = lambda s: blocks.symbol(BLOCK, s)                 # noqa: E731
    with pytest.raises(MXNetError, match="decodes by blocks of 4.*symbol_gen"):
        mx.serve.DecodeEngine("no-gen", gen(1), blocks.params(BLOCK),
                              capacity=CAPACITY, ladder=[2])
    with pytest.raises(MXNetError, match=r"blocks of 4 positions.*\[18\]"):
        mx.serve.DecodeEngine("off-chunk", gen(1), blocks.params(BLOCK),
                              capacity=CAPACITY, ladder=[2], symbol_gen=gen,
                              window_lens=[18])
    with pytest.raises(MXNetError, match="block_length 4 divides neither"):
        blocks.symbol(BLOCK, 6)
    # and of an engine that decodes a token a step, a denoising request
    other = mx.serve.DecodeEngine(
        "tiny-gpt2", blocks.symbol("gpt2_rotary", 1),
        blocks.params("gpt2_rotary"), capacity=CAPACITY, ladder=[2])
    plain = mx.serve.DecodeScheduler(other, clock=mx.serve.FakeClock())
    with pytest.raises(MXNetError, match="sets denoising parameters"):
        plain.submit([1, 2, 3], sampling=SamplingParams(denoising_steps=2))


def test_the_graph_says_how_it_decodes_and_the_engine_reads_it(engine):
    sym = blocks.symbol(BLOCK, WINDOW)
    assert tfm.decode_procedure(sym) == {k: SDAR[k] for k in tfm.DECODE_KEYS}
    assert tfm.decode_procedure(tfm.packed_window(sym, SLOTS)[0]) \
        == tfm.decode_procedure(sym)
    assert tfm.decode_procedure(blocks.symbol("afmoe", 1)) is None
    assert engine.block == tfm.decode_procedure(sym)
    assert engine.window_lens == [L, WINDOW] and engine.block_len == L
    for rung in engine.ladder:
        drv = engine.driver(rung)
        assert drv.window_lens == [L, WINDOW]
        assert drv.window_budget(L) is None     # every row of it is read
        assert (drv.window_budget(WINDOW) or 0) % L == 0
    nodes = [n for n in sym._topo_nodes() if n.op == "attention_decode"]
    assert len(nodes) == 3 and all(n.attrs["block"] == L for n in nodes)


def test_denoise_select_decides_as_the_reference_decides():
    """The device's decision over a block's rows against the
    reference's ``decide``: the threshold, the quota's most confident,
    the earlier position among equals, a slot with nothing undecided
    left as it was."""
    from chipbench.reference import sdar_moe as ref
    with blocks.tier("xla"):
        drv = blocks.driver(BLOCK, packed=False, slots=3)
    rng = np.random.default_rng(2)
    rows = rng.standard_normal((3, L, VOCAB)).astype(np.float32) * 3
    rows[1, 2] = rows[1, 0]                     # equal confidences
    ids = rng.integers(0, VOCAB, (3, L)).astype(np.int32)
    undecided = np.asarray([[1, 1, 0, 1], [1, 0, 1, 1], [0, 0, 0, 0]], bool)
    quota, threshold = [2, 1, 3], [0.3, np.inf, 0.0]
    state = np.asarray(drv.denoise_select(
        mx.nd.array(rows), ids, undecided, quota, threshold))
    assert state.shape == (2, 3, L) and state.dtype == np.int32
    for slot in range(3):
        x0, decided = ref.decide(rows[slot], undecided[slot], quota[slot],
                                 threshold[slot])
        np.testing.assert_array_equal(
            state[0, slot], np.where(decided, x0, ids[slot]))
        np.testing.assert_array_equal(
            state[1, slot].astype(bool), undecided[slot] & ~decided)
    assert state[1, 1].tolist() == [0, 0, 1, 1]  # the earlier of equals
    assert (state[0, 2] == ids[2]).all() and not state[1, 2].any()
