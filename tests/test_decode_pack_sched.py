"""A window's rows, packed, through the scheduler
(``DecodeScheduler._plan_window`` over ``BatchedKVCacheDecoder.step``'s
choice between the two forms of a window program): the plan inside the
budget against the plan without one, the trained blocks' tokens against
their plain reference, a draft stepped with the target's ``fed``, a
serving window's plan at rung 8 through the packed program, the whole
one and S = 1 steps. The ops, the two forms of the program alone and
what one script counts for each block are
``tests/test_decode_pack.py``'s."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serve.clock import FakeClock
from mxnet_tpu.serve.decode import DecodeScheduler

import decode_blocks as cases
from test_decode_pack import R, S, SLOTS, TOL


# ------------------------------------------------------------- the scheduler
def _engine(block, name, ladder=(1, SLOTS)):
    return cases.engine(block, name, ladder=ladder)


def _serve(engine, prompts, max_new, budget=True):
    """Every window dispatch's ``fed`` and the requests' tokens, under
    greedy sampling on a fake clock; ``budget=False`` plans as an engine
    without packed programs is planned."""
    if not budget:
        engine.window_budget = lambda rung, step_len: None
    sched = DecodeScheduler(engine, clock=FakeClock(), prefill_chunk=S,
                            prefix_store=None)
    feds, first = [], {}
    for rung in engine.ladder:
        drv = engine.driver(rung)

        def step(tokens, fed=None, now=None, _step=drv.step, _drv=drv):
            out = _step(tokens, fed=fed, now=now)
            if np.asarray(tokens).shape[1:] == (S,):
                feds.append((None if fed is None else list(map(int, fed)),
                             _drv.last_program_rows))
            return out

        drv.step = step
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    for i, h in enumerate(handles):
        h.add_token_callback(
            lambda _h, _t, index, i=i: first.setdefault(i, len(feds))
            if index == 0 else None)
    sched.pump()
    if not budget:
        del engine.window_budget
    return feds, [h.result(timeout=0).tolist() for h in handles], \
        [first[i] for i in range(len(handles))], sched


@pytest.mark.parametrize("block", ["evabyte", "axk1", "gpt2", "olmoe"])
def test_the_scheduler_plans_inside_the_budget_oldest_first(block):
    rs = np.random.RandomState(7)
    vocab = cases.config(block)["vocab_size"]
    # three prompts of three chunks and a short one, admitted together
    prompts = [rs.randint(0, vocab, n) for n in (40, 40, 40, 5)]
    engine = _engine(block, f"pack-{block}")
    assert engine.window_budget(SLOTS, S) == R
    assert engine.window_budget(1, S) is None
    feds, tokens, first, sched = _serve(engine, prompts, max_new=6)
    windows = [(fed, ran) for fed, ran in feds if fed is not None]
    assert windows and len(windows) == len(feds)
    for fed, ran in windows:
        assert sum(fed) <= R and ran == R       # never the whole window
    # the oldest prefilling slot takes its whole chunk, the next what is
    # left of the budget, the others wait their turn
    assert windows[0][0] == [S, R - S, 0, 0]
    # oldest first: equal prompts reach their first token in the order
    # they were admitted, and nobody starves
    assert first[0] < first[1] < first[2]
    assert [len(t) for t in tokens] == [6] * 4
    assert sched.stats()["compiles_since_warmup"] == 0
    # the counters: real rows over the rows the programs ran
    real = mx.telemetry.get_metric("serve.decode.window.real_rows",
                                   model=engine.name).value
    ran = mx.telemetry.get_metric("serve.decode.window.program_rows",
                                  model=engine.name).value
    heads = mx.telemetry.get_metric("serve.decode.window.head_rows",
                                    model=engine.name).value
    assert real == sum(sum(fed) for fed, _ in windows)
    assert ran == R * len(windows)
    # every window selected in front of its head: a row a slot
    assert heads == SLOTS * len(windows)
    # ISSUE 65: every window launched the packed form, whose copies of
    # rows are counted a launch, and at one chunk a slot every one of
    # them holds no loop; the S = 1 steps behind them added nothing
    sites = tfm.copy_sites(
        tfm.packed_window(cases.symbol(block, S), SLOTS)[0], S)[0]
    assert sites > 2 and len(feds) < sched.iterations
    assert _copies(engine.name) == (sites * len(windows),) * 2

    # the same requests planned without a budget (every active slot
    # min(S, remaining) a window): the same tokens, in fewer and wider
    # windows
    feds0, tokens0, _first, _sched = _serve(
        _engine(block, f"pack-{block}-whole"), prompts, max_new=6,
        budget=False)
    assert tokens0 == tokens
    assert feds0[0][0] == [S, S, S, 5] and feds0[0][1] == SLOTS * S
    assert len(feds0) < len(windows)
    # a window past the budget runs its head over every row
    whole = f"pack-{block}-whole"
    assert mx.telemetry.get_metric("serve.decode.window.head_rows",
                                   model=whole).value \
        == mx.telemetry.get_metric("serve.decode.window.program_rows",
                                   model=whole).value > 0
    # and copies no rows: the whole-window program has no such site
    assert _copies(whole) == (0, 0)


def _copies(model):
    return tuple(mx.telemetry.get_metric(f"serve.decode.window.{key}",
                                         model=model).value
                 for key in ("copy_sites", "static_copy_sites"))


def test_a_window_of_several_chunks_a_slot_counts_no_static_copy():
    """256 rows a slot are two chunks: the packed form (8 slots, 384
    rows) keeps the loops, so its launches add their sites to
    ``window.copy_sites`` alone."""
    step_len, slots = 256, 8
    engine = cases.engine("gpt2", "pack-gpt2-256", ladder=(slots,),
                          windows=(step_len,), capacity=2 * step_len)
    assert engine.window_budget(slots, step_len) == 384
    sched = DecodeScheduler(engine, clock=FakeClock(),
                            prefill_chunk=step_len, prefix_store=None)
    rs = np.random.RandomState(9)
    vocab = cases.config("gpt2")["vocab_size"]
    cases.served(sched, [rs.randint(0, vocab, n) for n in (300, 20)], 3)
    drv = engine.driver(slots)
    sites = 2 * cases.config("gpt2")["n_layer"] + 2
    assert drv._packed[step_len][3] == (sites, 0)
    copied, static = _copies(engine.name)
    assert copied > 0 and copied % sites == 0 and static == 0
    assert copied == sites * mx.telemetry.get_metric(
        "serve.decode.window.dispatches", model=engine.name).value


def _spy_on_rewinds(engine):
    """Every ``rewind_many`` of every rung's driver from here on:
    ``[(rows, positions)]`` of the calls that moved a cursor."""
    calls = []
    for rung in engine.ladder:
        drv = engine.driver(rung)

        def rewind_many(rows, positions, _inner=drv.rewind_many):
            if len(rows):
                calls.append((list(rows), list(positions)))
            _inner(rows, positions)

        drv.rewind_many = rewind_many
    return calls


@pytest.mark.parametrize("block", sorted(cases.FUSED))
def test_a_fed_graph_serves_the_tokens_of_the_graph_it_was(block):
    """ISSUE 47: the slot-pooled GPT-2 and OLMoE graphs take ``fed``.
    Requests that join a window in flight, through the fed graph
    (windows planned inside the budget, cursors advanced by ``fed``):
    request by request the tokens of the block's plain reference
    forward (``cases.plain_greedy``), and the engine rewinds nobody."""
    rs = np.random.RandomState(47)
    vocab = cases.config(block)["vocab_size"]
    prompts = [rs.randint(0, vocab, n) for n in (40, 23, 5, 1, 17)]
    engine = _engine(block, f"was-{block}-fed")
    sched = DecodeScheduler(engine, clock=FakeClock(), prefill_chunk=S,
                            prefix_store=None)
    rewound = _spy_on_rewinds(engine)
    handles = [sched.submit(p, max_new_tokens=6) for p in prompts[:3]]
    sched.pump(max_iterations=2)
    handles += [sched.submit(p, max_new_tokens=6) for p in prompts[3:]]
    sched.pump()
    streams = [h.result(timeout=0).tolist() for h in handles]
    assert not rewound
    assert sched.stats()["compiles_since_warmup"] == 0
    assert streams == [cases.plain_greedy(block, p, 6) for p in prompts]
    assert [len(t) for t in streams] == [6] * 5


@pytest.mark.parametrize("block", ["gpt2", "olmoe"])
def test_a_draft_shadows_a_window_with_the_targets_fed(block):
    """A draft engine is stepped with the target's ``fed``: a window of
    a drafted scheduler is planned inside the budget and runs the packed
    program of both engines, both cursors advance by the real tokens
    alone and neither is rewound after it. The verify window of a
    speculative iteration feeds every slot all K rows, which is the
    whole-window program's: the scheduler compiled it before the
    engine's warm-up, which compiles the packed form of that length in
    its place, so nothing compiles in steady state. The tokens are the
    undrafted scheduler's."""
    K = 4

    def engine(name):
        return cases.engine(block, name, ladder=[SLOTS], windows=[S, K])

    rs = np.random.RandomState(48)
    vocab = cases.config(block)["vocab_size"]
    prompts = [rs.randint(0, vocab, n) for n in (40, 23, 5, 17)]
    plain = DecodeScheduler(engine(f"undrafted-{block}"), clock=FakeClock(),
                            prefill_chunk=S, prefix_store=None)
    want = [plain.submit(p, max_new_tokens=9) for p in prompts]
    plain.pump()

    target, draft = engine(f"drafted-{block}"), engine(f"draft-{block}")
    assert target.window_budget(SLOTS, S) == R
    assert target.window_budget(SLOTS, K) == 8      # a packed verify form
    sched = DecodeScheduler(target, clock=FakeClock(), draft_engine=draft,
                            prefill_chunk=S, spec_k=K, prefix_store=None)
    rewound = _spy_on_rewinds(target) + _spy_on_rewinds(draft)
    drv, ddrv = target.driver(SLOTS), draft.driver(SLOTS)
    mx.telemetry.flightrec.configure(capacity=4096)
    handles = [sched.submit(p, max_new_tokens=9) for p in prompts]
    modes = []
    while not all(h.done() for h in handles):
        assert sched.pump(max_iterations=1) == 1
        rec = [r for r in mx.telemetry.flightrec.get_records()
               if r.get("kind") == "serve.decode.step"
               and r.get("model") == target.name][-1]
        modes.append((rec["mode"], rec["window"]))
        if modes[-1] == ("window", S):
            assert drv.last_program_rows == ddrv.last_program_rows == R
            assert not rewound
        elif rec["mode"] == "spec":
            assert drv.last_program_rows == SLOTS * K
        live = [seq.slot for seq in sched._active()]
        np.testing.assert_array_equal(drv.pos[live], ddrv.pos[live])
    assert modes.count(("window", S)) >= 3 and modes.count(("spec", K)) >= 1
    assert target.compiles_since_warmup() == 0
    assert draft.compiles_since_warmup() == 0
    assert draft.backend_compiles_since_warmup() == 0
    assert [h.result(timeout=0).tolist() for h in handles] == \
        [h.result(timeout=0).tolist() for h in want]


#: eight slots of one window: two prefilling, five riding, one idle
_PLAN8 = [12, 7, 1, 1, 1, 1, 1, 0]


@pytest.mark.parametrize("block", sorted(cases.FUSED))
def test_a_packed_window_equals_the_whole_window_and_s1_steps(block):
    """The plan of a serving window at rung 8 - two prefilling slots,
    five riders, one idle slot, 24 rows of 128 real - through the
    packed program, through the whole-window program fed the same, and
    token by token through the S = 1 program: the same logits on every
    real row - of the packed program, which hands back a row a slot, on
    each slot's last - within the block's tolerance, the same greedy
    tokens, the same cursors, and the same logits from the S = 1 step
    after; and the whole-window program's are the plain reference's."""
    slots, fed = len(_PLAN8), np.asarray(_PLAN8)
    assert tfm.packed_rows(slots, S) == int(fed.sum()) == 24
    rs = np.random.RandomState(8)
    vocab = cases.config(block)["vocab_size"]
    tokens = rs.randint(0, vocab, (slots, 2 + S + 1))
    start = np.asarray([2, 1, 2, 0, 1, 2, 0, 1])

    def walk(drv, window):
        """Slots joined at staggered cursors, the window, one more
        S = 1 step: the window's real rows, the cursors after it, the
        step's logits."""
        for slot in range(slots):
            drv.join(slot)
        for step in range(2):
            drv.step(tokens[:, step], fed=(start > step).astype(np.int64))
        out = window(drv)
        after = drv.pos.copy()
        live = fed > 0
        nxt = drv.step(tokens[:, -1], fed=live.astype(np.int64)).asnumpy()
        return out, after, nxt[live]

    def by_window(drv):
        out = drv.step(tokens[:, 2:2 + S], fed=fed).asnumpy()
        if out.shape[1] == 1:       # the packed program's row a slot
            return [out[slot, :min(n, 1)] for slot, n in enumerate(fed)]
        return [out[slot, :n] for slot, n in enumerate(fed)]

    def by_steps(drv):
        rows = [[] for _ in range(slots)]
        for t in range(int(fed.max())):
            out = drv.step(tokens[:, 2 + t],
                           fed=(fed > t).astype(np.int64)).asnumpy()
            for slot in np.nonzero(fed > t)[0]:
                rows[slot].append(out[slot, 0])
        return [np.asarray(r).reshape(-1, vocab) for r in rows]

    packed = cases.driver(block, slots=slots)
    whole = cases.driver(block, packed=False, slots=slots)
    got = {"packed": walk(packed, by_window), "whole": walk(whole, by_window),
           "steps": walk(cases.driver(block, slots=slots), by_steps)}
    assert packed.last_program_rows == whole.last_program_rows == slots
    assert packed.window_budget(S) == 24 and whole.window_budget(S) is None
    want_rows, want_pos, want_next = got["whole"]
    np.testing.assert_array_equal(want_pos, start + fed)
    # each slot's own sequence - what it was fed before the window, the
    # window's real rows, the token after - through the plain reference
    seqs = np.zeros((slots, 2 + S + 1), np.int64)
    for slot, (n0, n) in enumerate(zip(start, fed)):
        seqs[slot, :n0 + n + 1] = np.concatenate([
            tokens[slot, :n0], tokens[slot, 2:2 + n], tokens[slot, -1:]])
    plain = cases.reference(block, seqs)
    for slot, (n0, n) in enumerate(zip(start, fed)):
        np.testing.assert_allclose(want_rows[slot], plain[slot, n0:n0 + n],
                                   rtol=0, atol=cases.TOL[block])
    np.testing.assert_allclose(
        want_next[:, 0], plain[fed > 0, (start + fed)[fed > 0]], rtol=0,
        atol=cases.TOL[block])
    # against the S = 1 program a window differs by the order of its
    # sums, the packed program's head (over 8 rows) by the block's bit
    tol = {"packed": TOL[block], "whole": 0.0, "steps": 2e-5}
    for form, (rows, pos, nxt) in got.items():
        np.testing.assert_array_equal(pos, want_pos, err_msg=form)
        for slot, n in enumerate(fed):
            # of the packed program each slot's last fed row alone
            want = want_rows[slot][-1:] if form == "packed" \
                else want_rows[slot]
            assert rows[slot].shape == want.shape
            np.testing.assert_allclose(rows[slot], want, rtol=0,
                                       atol=tol[form], err_msg=form)
            np.testing.assert_array_equal(
                rows[slot].argmax(-1), want.argmax(-1), form)
        np.testing.assert_allclose(nxt, want_next, rtol=0, atol=tol[form],
                                   err_msg=form)


@pytest.mark.parametrize("block", ["gpt2_rotary", "olmoe"])
def test_two_chunks_and_the_riders_fit_one_window_at_rung_8(block):
    """The Cerebras and OLMoE cells' shapes, 8 slots of 64 rows: the
    budget is the 256 rows a weight-bound matmul carries for free, so
    two prefilling slots get a whole chunk each beside six riders in
    one packed window; nobody is rewound after it; the second and the
    third such window are launched before their predecessor's ids are
    on the host (ISSUE 53: the chunks from the host, the riders' tokens
    from the chip), and so is the first S = 1 step behind the last
    (ISSUE 46), with no rewind before it. The synchronous order runs
    the same windows and serves the same tokens, which are the plain
    reference's."""
    chunk, slots, capacity = 64, 8, 320
    rs = np.random.RandomState(64)
    vocab = cases.config(block)["vocab_size"]
    riders = [rs.randint(0, vocab, n) for n in (3, 4, 5, 3, 4, 5)]
    long = [rs.randint(0, vocab, 150) for _ in range(2)]
    mx.telemetry.flightrec.configure(capacity=4096)
    streams = {}
    for order in ("ahead", "sync"):
        name = f"rung8-{block}-fed"
        if order == "sync":
            # the same scheduler, every dispatch planned after its
            # predecessor's commit
            sched._plan_ahead = lambda d, now: None
        else:
            engine = cases.engine(block, name, ladder=[slots],
                                  windows=[chunk], capacity=capacity)
            assert engine.window_budget(slots, chunk) == 256
            sched = DecodeScheduler(engine, clock=FakeClock(),
                                    prefill_chunk=chunk, prefix_store=None)
        drv = engine.driver(slots)
        windows = []

        def step(tokens, fed=None, now=None, _step=type(drv).step,
                 _drv=drv):
            out = _step(_drv, tokens, fed=fed, now=now)
            if tokens.shape[1:] == (chunk,):
                windows.append((None if fed is None else sorted(fed),
                                _drv.last_program_rows))
            return out

        drv.step = step
        n_rec = sched.iterations
        handles = [sched.submit(p, max_new_tokens=30) for p in riders]
        sched.pump(max_iterations=3)             # the riders decode
        rewound = _spy_on_rewinds(engine)
        handles += [sched.submit(p, max_new_tokens=4) for p in long]
        sched.pump()
        streams[order] = [h.result(timeout=0).tolist() for h in handles]
        ring = [r for r in mx.telemetry.flightrec.get_records()
                if r["kind"] == "serve.decode.step" and r["model"] == name
                and r["iter"] >= n_rec]
        last = max(i for i, r in enumerate(ring) if r["window"] == chunk)
        ahead = [r["ahead"] for r in ring if r["window"] == chunk]
        assert sched.stats()["compiles_since_warmup"] == 0
        if order == "sync":
            assert not any(r["ahead"] for r in ring)
        else:
            # the step behind the last window was launched ahead of
            # its ids
            assert ring[last + 1]["window"] == 1 and \
                ring[last + 1]["ahead"] == 1
        assert not rewound
        assert windows[1:] == [([1] * 6 + [64, 64], 256)] * 2 \
            + [([1] * 6 + [22, 22], 256)]
        # the long prompts are admitted while an S = 1 step is on the
        # chip, their first window launched behind it; the two behind
        # that need no id's value either
        assert ahead[1:] == [int(order == "ahead")] * 3
    assert streams["ahead"] == streams["sync"]
    assert [len(t) for t in streams["ahead"]] == [30] * 6 + [4] * 2
    # a rider's first tokens and both long prompts' answers
    for i, n in ((0, 6), (6, 4), (7, 4)):
        assert streams["ahead"][i][:n] == cases.plain_greedy(
            block, (riders + long)[i], n, T=192)


def test_a_fed_graph_bound_without_fed_is_refused():
    """``fed`` left among the parameters would stay what it was set to
    and every step would advance the slots by that."""
    sym = cases.symbol("gpt2_rotary", 1)
    mod = mx.mod.Module(sym, data_names=["data"], label_names=[])
    mod.bind([mx.io.DataDesc("data", (SLOTS, 1), np.int32)], None,
             for_training=False)
    with pytest.raises(mx.base.MXNetError, match="bind it as data"):
        tfm.BatchedKVCacheDecoder(mod, cases.CAPACITY, slots=SLOTS)
