"""A window's rows, packed (``ops/rows.py``, ``models/transformer.py``'s
``packed_window`` and ``BatchedKVCacheDecoder.step``'s choice between
the two forms of a window program, ``DecodeScheduler._plan_window``):
the packed program against the whole-window program of the same graph,
for each block that takes ``fed``, at tiny sizes on the CPU in float32;
and the scheduler's plan inside the budget against the plan without
one."""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import rows
from mxnet_tpu.serve.clock import FakeClock
from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler

import window_pack_cases as cases
from decode_counts_parent import COUNTS

S, SLOTS = cases.WINDOW, cases.SLOTS
R = tfm.packed_rows(SLOTS, S)                   # 24 of 64

#: rows fed to each of the four slots of one window
MIXES = {
    "one_prefilling_rest_riding": [S, 1, 1, 1],
    "two_short_prompts_share": [7, 9, 1, 1],
    "cut_by_the_budget_mid_chunk": [S, R - S - 2, 1, 1],    # sum == R
    "fed_nothing_while_active": [S, 0, 1, 1],
    "all_riding": [1, 1, 1, 1],
    "one_over_the_budget": [S, R - S - 1, 1, 1],            # sum == R + 1
    "every_slot_a_whole_chunk": [S, S, S, S],
}

#: float32 on the CPU. A row's product does not depend on its
#: neighbours in exact arithmetic, and EvaByte's block comes out equal
#: to the bit. The blocks with a shared expert do not: XLA's CPU backend
#: picks its matmul by the operands' shape, and ``_dense_expert``'s
#: products over 24 rows and over 64 round a last bit differently
#: (4e-7 on one layer's output, measured by itself; up to 4e-6 on the
#: logits, of magnitude 2; Xing4.0's mappings - an exp and 20 Sinkhorn
#: rounds of the stream a sub-layer - carry such a bit ten times as far)
TOL = {"evabyte": 0.0, "glm_dsa": 2e-5, "axk1": 2e-5, "afmoe": 2e-5,
       "xing4": 2e-4}


def test_the_budget_follows_from_the_shapes():
    assert (R, tfm.packed_rows(8, 1024), tfm.packed_rows(4, 1024),
            tfm.packed_rows(8, 512)) == (24, 1152, 1152, 640)
    sym = cases.symbol("glm_dsa", S)
    packed, budget = tfm.packed_window(sym, SLOTS)
    assert budget == R
    marked = {n.op: n.attrs["rows"] for n in packed._topo_nodes()
              if n.op in ("pack_rows", "unpack_rows")}
    assert marked == {"pack_rows": R, "unpack_rows": R}
    # the graph it was derived from is untouched
    assert not any(n.attrs.get("rows") for n in sym._topo_nodes()
                   if not n.is_variable)
    # nobody rides at rung 1, an S = 1 graph has nothing to pack, and a
    # block without ``fed`` has no such nodes
    assert tfm.packed_window(sym, 1) is None
    assert tfm.packed_window(cases.symbol("glm_dsa", 1), SLOTS) is None
    plain = tfm.get_decode_symbol(vocab_size=32, d_model=16, n_layer=1,
                                  n_head=2, capacity=32, step_len=S,
                                  per_slot=True)
    assert tfm.packed_window(plain, SLOTS) is None


@pytest.mark.parametrize("fed", sorted(MIXES.values()),
                         ids=sorted(MIXES, key=MIXES.get))
def test_pack_and_unpack_are_each_others_inverse_on_the_real_rows(fed):
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(SLOTS, S, 3, 5), jnp.float32)
    fed = jnp.asarray(fed, jnp.int32)
    if int(fed.sum()) > R:
        return
    packed, total = rows.pack(x, fed, R)
    assert packed.shape == (1, R, 3, 5) and int(total[0]) == int(fed.sum())
    at = 0
    for b, n in enumerate(np.asarray(fed)):
        np.testing.assert_array_equal(packed[0, at:at + n], x[b, :n])
        at += n
    back = rows.unpack(packed[0].reshape(R, 15), fed, S, R, (3, 5))
    real = np.arange(S)[None, :] < np.asarray(fed)[:, None]
    np.testing.assert_array_equal(
        back, np.where(real[:, :, None, None], x, 0.0))


@pytest.mark.parametrize("fed", [[256, 1, 0], [130, 129, 1], [0, 0, 0],
                                 [200, 184, 0], [1, 1, 1]])
def test_rows_are_copied_a_chunk_at_a_time_where_a_slot_holds_several(fed):
    """256 rows a slot are two chunks of 128: a slot's real rows take
    one copy or two, an unfed slot none, and a later slot's first chunk
    lands on the pad tail of the one before."""
    slots, step_len = 3, 256
    budget = tfm.packed_rows(slots, step_len)
    assert budget == 384 and rows._chunk(step_len) == 128
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(slots, step_len, 6), jnp.float32)
    fed = jnp.asarray(fed, jnp.int32)
    packed, total = rows.pack(x, fed, budget)
    assert int(total[0]) == int(fed.sum()) <= budget
    at = 0
    for b, n in enumerate(np.asarray(fed)):
        np.testing.assert_array_equal(packed[0, at:at + n], x[b, :n])
        at += n
    back = rows.unpack(packed[0], fed, step_len, budget, (6,))
    real = np.arange(step_len)[None, :] < np.asarray(fed)[:, None]
    np.testing.assert_array_equal(back, np.where(real[:, :, None], x, 0.0))


@pytest.fixture(scope="module", params=sorted(cases.BLOCKS))
def pair(request):
    """``(block, whole, packed)``: two drivers of one block and one
    parameter set, the second with the packed form of its window
    program beside the whole one."""
    block = request.param
    return block, cases.driver(block, packed=False), cases.driver(block)


def _fresh(drv, tokens):
    """Every slot joined anew and walked a few S = 1 steps, a different
    number each where the state allows it, so that the window starts at
    four different cursors."""
    drv.active[:] = False
    drv.rewind_many(list(range(SLOTS)), [0] * SLOTS)
    for slot in range(SLOTS):
        drv.join(slot)
    for step in range(3):
        feed = {"fed": (np.arange(SLOTS) <= step + 1).astype(np.int64)} \
            if drv.feeds else {}
        drv.step(tokens[:, step], **feed)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_packed_program_equals_the_whole_window_program(pair, mix):
    block, whole, packed = pair
    fed = np.asarray(MIXES[mix])
    rs = np.random.RandomState(len(mix))
    vocab = cases.BLOCKS[block]["vocab_size"]
    tokens = rs.randint(0, vocab, (SLOTS, 3 + S + 1))
    got = []
    for drv in (whole, packed):
        _fresh(drv, tokens)
        start = drv.pos.copy()
        out = drv.step(tokens[:, 3:3 + S], fed=fed).asnumpy()
        ran = drv.last_program_rows
        after = drv.pos.copy()
        cursors = [np.asarray(c.asjax()).reshape(-1)
                   for c in drv._cursor_cells()]
        state = [np.asarray(cell.asjax())[slot, :, :after[slot]]
                 for slot in range(SLOTS)
                 for _nm, cell in drv._cells("rows")] \
            if drv.positional else []
        nxt = drv.step(tokens[:, -1]).asnumpy()
        got.append((out, after, cursors, state, nxt, ran, start))
    (out_w, pos_w, cur_w, rows_w, next_w, ran_w, start), \
        (out_p, pos_p, cur_p, rows_p, next_p, ran_p, _) = got
    # the driver's switch: the packed program inside the budget
    assert ran_w == SLOTS * S
    assert ran_p == (R if fed.sum() <= R else SLOTS * S)
    assert packed.window_budget(S) == R and whole.window_budget(S) is None
    np.testing.assert_array_equal(pos_p, start + fed)
    np.testing.assert_array_equal(pos_w, pos_p)
    for a, b in zip(cur_w, cur_p):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, pos_p)
    tol = TOL[block]
    for slot, n in enumerate(fed):
        np.testing.assert_allclose(out_p[slot, :n], out_w[slot, :n],
                                   rtol=0, atol=tol)
    # every pool's live rows, and what the next S = 1 step reads of the
    # state whatever family it is
    for a, b in zip(rows_w, rows_p):
        np.testing.assert_allclose(b, a, rtol=0, atol=tol)
    np.testing.assert_allclose(next_p, next_w, rtol=0, atol=tol)


def test_a_step_without_fed_takes_the_whole_window_program(pair):
    _block, _whole, packed = pair
    packed.active[:] = False
    packed.rewind_many(list(range(SLOTS)), [0] * SLOTS)
    packed.step(np.zeros((SLOTS, S), np.int32))
    assert packed.last_program_rows == SLOTS * S
    assert packed.pos.tolist() == [S] * SLOTS
    packed.step(np.zeros((SLOTS, S), np.int32), fed=[1] * SLOTS)
    assert packed.last_program_rows == R
    packed.step(np.zeros((SLOTS, 1), np.int32))
    assert packed.last_program_rows == SLOTS


# ------------------------------------------------------------- the scheduler
def _engine(block, name):
    kw = dict(cases.BLOCKS[block], block=block, capacity=cases.CAPACITY,
              per_slot=True, pos_embed="rotary", tie_head=False,
              embed_scale=block == "afmoe")
    gen = lambda s: tfm.get_decode_symbol(step_len=s, **kw)  # noqa: E731
    return DecodeEngine(name, gen(1), cases.params(block),
                        capacity=cases.CAPACITY, ladder=[1, SLOTS],
                        symbol_gen=gen, window_lens=[S])


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


@pytest.mark.parametrize("block", ["evabyte", "axk1"])
def test_the_scheduler_plans_inside_the_budget_oldest_first(block):
    rs = np.random.RandomState(7)
    vocab = cases.BLOCKS[block]["vocab_size"]
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
    assert real == sum(sum(fed) for fed, _ in windows)
    assert ran == R * len(windows)

    # the same requests planned without a budget (every active slot
    # min(S, remaining) a window): the same tokens, in fewer and wider
    # windows
    feds0, tokens0, _first, _sched = _serve(
        _engine(block, f"pack-{block}-whole"), prompts, max_new=6,
        budget=False)
    assert tokens0 == tokens
    assert feds0[0][0] == [S, S, S, 5] and feds0[0][1] == SLOTS * S
    assert len(feds0) < len(windows)


def test_an_engine_without_fed_is_planned_as_before():
    kw = dict(vocab_size=32, d_model=16, n_layer=1, n_head=2, capacity=64,
              per_slot=True)
    gen = lambda s: tfm.get_decode_symbol(step_len=s, **kw)  # noqa: E731
    shapes, _, _ = gen(1).infer_shape(data=(2, 1))
    rs = np.random.RandomState(0)
    params = {nm: (0.2 * rs.randn(*shape)).astype(np.float32)
              for nm, shape in zip(gen(1).list_arguments(), shapes)
              if nm != "data"}
    engine = DecodeEngine("pack-plain", gen(1), params, capacity=64,
                          ladder=[1, SLOTS], symbol_gen=gen,
                          window_lens=[S])
    assert not engine.feeds
    assert engine.window_budget(SLOTS, S) is None
    assert not [k for k in engine._window_mods if len(k) == 3]
    sched = DecodeScheduler(engine, clock=FakeClock(), prefill_chunk=S,
                            prefix_store=None)
    handles = [sched.submit(rs.randint(0, 32, n), max_new_tokens=3)
               for n in (40, 40, 20, 5)]
    with sched._lock:
        sched._admit_locked(0.0)
        plan = sched._plan_window(S)
    assert [(row, n) for row, _seq, n in plan] == [(0, S), (1, S), (2, S),
                                                   (3, 5)]
    sched.pump()
    assert [len(h.result(timeout=0)) for h in handles] == [3] * 4
    ran = mx.telemetry.get_metric("serve.decode.window.program_rows",
                                  model="pack-plain").value
    windows = mx.telemetry.get_metric("serve.decode.prefill.chunks",
                                      model="pack-plain").value
    assert ran % (SLOTS * S) == 0 and ran and windows


# ------------------------------------------- what a dispatch reads, counted
def _counts_script(block):
    """One fixed script through the scheduler for any block: three
    prompts admitted together (windows with ragged ``fed``, a slot that
    retires early and rides on), two more joined mid-flight (one of a
    single token), the S = 1 steps they decode in, and after an idle
    moment a last request into a used slot at the smallest rung. On a
    block without ``fed`` every window is followed by a rewind of the
    slots it ran ahead of; every join sets a cursor. Returns the model's
    whole ``serve.decode.*`` counter set, what every iteration's
    ring record says beside its times, and the bytes that one S = 1
    step of every rung takes over."""
    name = f"counts-{block}"
    gen = lambda s: cases.symbol(block, s)          # noqa: E731
    engine = DecodeEngine(name, gen(1), cases.params(block),
                          capacity=cases.CAPACITY, ladder=[1, SLOTS],
                          symbol_gen=gen, window_lens=[S])
    sched = DecodeScheduler(engine, clock=FakeClock(), prefill_chunk=S,
                            prefix_store=None)
    mx.telemetry.flightrec.clear()
    rs = np.random.RandomState(11)
    prompt = lambda n: rs.randint(0, 40, n)         # noqa: E731
    handles = [sched.submit(prompt(n), max_new_tokens=m)
               for n, m in ((60, 10), (23, 12), (5, 2))]
    sched.pump(max_iterations=3)
    handles += [sched.submit(prompt(n), max_new_tokens=m)
                for n, m in ((1, 3), (17, 5))]
    sched.pump()
    handles.append(sched.submit(prompt(9), max_new_tokens=2))
    sched.pump()
    assert [len(h.result(timeout=0)) for h in handles] == [10, 12, 2, 3, 5, 2]
    label = f'model="{name}"'
    counters = {
        key.split("{")[0][len("serve.decode."):]: value
        for key, value in mx.telemetry.snapshot()["counters"].items()
        if key.startswith("serve.decode.") and label in key
        and "dtype=" not in key}
    steps = [r for r in mx.telemetry.flightrec.get_records()
             if r["kind"] == "serve.decode.step" and r["model"] == name]
    fields = lambda r: {                            # noqa: E731
        k: v for k, v in r.items()
        if not k.endswith("_us") and k not in ("kind", "model", "mode",
                                               "compiles_since_warmup")}
    return counters, [fields(r) for r in steps], sum(
        engine.driver(rung).donated_bytes for rung in engine.ladder)


@pytest.mark.parametrize("block", sorted(COUNTS))
def test_the_counters_and_the_ring_fields_are_the_parents(block):
    """What PR 44's parent counted, and beside it what ISSUE 46 added:
    the two ``runahead`` counters, the ring's ``ahead``, and in
    ``state.donated_bytes`` the one S = 1 step a rung more that warm-up
    runs (fed from the chip). The script has no EOS, so every dispatch
    launched ahead is committed: same dispatches, same counts."""
    counters, ring, step_bytes = _counts_script(block)
    launched = counters.pop("runahead.launched")
    assert counters.pop("runahead.dropped") == 0
    assert launched == sum(r.pop("ahead") for r in ring) > 0
    want = dict(COUNTS[block][0])
    want["state.donated_bytes"] += step_bytes
    assert counters == want
    assert ring == COUNTS[block][1]
