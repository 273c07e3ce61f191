"""A window's rows, packed (``ops/rows.py``, ``models/transformer.py``'s
``packed_window`` and ``BatchedKVCacheDecoder.step``'s choice between
the two forms of a window program, ``DecodeScheduler._plan_window``):
the packed program - whose head runs over each slot's last fed row and
which returns that row alone, ISSUE 51 - against the whole-window
program of the same graph at that row, for each block - all take
``fed`` -, at tiny sizes on the CPU in
float32; and what one script through the scheduler counts for each
block (``tests/decode_counts_parent.py``). The scheduler's plan inside
the budget is ``tests/test_decode_pack_sched.py``'s (a file of its own
so that two workers take them: ROADMAP D22)."""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops import rows
from mxnet_tpu.serve.clock import FakeClock
from mxnet_tpu.serve.decode import DecodeEngine, DecodeScheduler

import decode_blocks as cases
from decode_counts_parent import COUNTS

S, SLOTS = cases.WINDOW, cases.SLOTS
R = tfm.packed_rows(SLOTS, S)                   # 24 of 64

#: rows fed to each of the four slots of one window: between them every
#: row of a chunk is some slot's last fed row inside the budget (fed 1
#: to S), in the first slot, in the last, behind a slot fed nothing and
#: in front of one
MIXES = {
    "one_prefilling_rest_riding": [S, 1, 1, 1],
    "two_short_prompts_share": [7, 9, 1, 1],
    "cut_by_the_budget_mid_chunk": [S, R - S - 2, 1, 1],    # sum == R
    "fed_nothing_while_active": [S, 0, 1, 1],
    "all_riding": [1, 1, 1, 1],
    "one_over_the_budget": [S, R - S - 1, 1, 1],            # sum == R + 1
    "every_slot_a_whole_chunk": [S, S, S, S],
    "four_short_prompts": [2, 3, 4, 5],
    "unfed_first_and_last": [0, 8, 10, 0],
    "two_long_one_unfed_between": [11, 12, 0, 1],
    "unfed_in_the_middle": [13, 0, 0, 11],                  # sum == R
    "a_chunk_short_by_two": [S - 2, 5, 3, 2],               # sum == R
    "a_chunk_short_by_one": [S - 1, 4, 2, 3],               # sum == R
}
assert {n for fed in MIXES.values() if sum(fed) <= R for n in fed} \
    >= set(range(S + 1))

#: float32 on the CPU. A row's product does not depend on its
#: neighbours in exact arithmetic, and EvaByte's block comes out equal
#: to the bit. The blocks with a shared expert do not: XLA's CPU backend
#: picks its matmul by the operands' shape, and ``_dense_expert``'s
#: products over 24 rows and over 64 round a last bit differently
#: (4e-7 on one layer's output, measured by itself; up to 4e-6 on the
#: logits, of magnitude 2; Xing4.0's mappings - an exp and 20 Sinkhorn
#: rounds of the stream a sub-layer - carry such a bit ten times as far)
TOL = {"evabyte": 0.0, "glm_dsa": 2e-5, "axk1": 2e-5, "afmoe": 2e-5,
       # ISSUE 51: a tied head's product over 4 rows and over 64 (4e-7
       # on logits of 3; the untied heads, ``FullyConnected``, are equal)
       "xing4": 2e-4, "gpt2": 2e-6, "gpt2_rotary": 2e-6, "olmoe": 0.0,
       # the segmented convolution and the one-hot products sum the
       # same terms over 24 rows and over 64
       "granite_hybrid": 2e-5,
       # the same, and the shared expert's (the state itself goes through
       # the same chunks in either form)
       "ling_hybrid": 2e-5,
       # Trinity's head geometry and OLMoE's router; the packed and the
       # whole program mask a ragged window alike, wherever it starts
       "sdar_moe": 2e-5,
       # Granite's convolution and a shared expert, as above
       "nemotron_h": 2e-5}

#: ``packed_rows`` before ISSUE 47: a chunk and a token a slot, rounded
#: up to the tile (128 rows; 8 under that)
_CHUNK_AND_RIDERS = {
    (2, 16): 24, (4, 16): 24, (8, 16): 24,
    (2, 64): 72, (4, 64): 72, (8, 64): 72,
    (2, 248): 256, (4, 248): 256, (8, 248): 256,
    (2, 256): 384, (4, 256): 384, (8, 256): 384,
    (2, 512): 640, (4, 512): 640, (8, 512): 640,
    (2, 1024): 1152, (4, 1024): 1152, (8, 1024): 1152,
}


#: what ISSUE 47 made of them: where the whole window is more than a
#: tile, never under the rows that cost nothing - the ridge, or the
#: whole window where that is under the ridge
_FREE_ROWS = {(8, 64): 256, (4, 64): 256}


@pytest.mark.parametrize("slots,step_len", sorted(_CHUNK_AND_RIDERS))
def test_the_budget_is_a_chunk_and_the_riders_or_the_rows_that_are_free(
        slots, step_len):
    """Every entry is what it was but those whose chunk and riders are
    under the rows that cost nothing (256: the v5e's peak over its
    bandwidth, rounded up to the tile, from the table of peaks whatever
    the host): 8 x 64 packs to the ridge; 4 x 64 - a whole window of no
    more than the ridge - gets its own rows and so no packed form at
    all (as 6 x 32 does, 192 rows); up to one tile (the tests' 4 x 16
    and 8 x 16) a chunk and the riders stand."""
    assert tfm.ridge_rows() == 256
    want = _FREE_ROWS.get((slots, step_len),
                          _CHUNK_AND_RIDERS[slots, step_len])
    assert tfm.packed_rows(slots, step_len) == want
    assert tfm.packed_rows(6, 32) == 192 and tfm.packed_rows(8, 32) == 256
    gen = lambda s: cases.symbol("gpt2_rotary", s)      # noqa: E731
    form = tfm.packed_window(gen(step_len), slots)
    if slots * step_len >= 2 * want:
        assert form is not None and form[1] == want
    else:
        assert form is None


def test_the_ridge_is_the_tables_whatever_chip_is_here(monkeypatch):
    """The budget is read off ``telemetry.mfu.PEAKS``' entry of the chip
    the programs are written for, never off ``jax.devices()``: a graph,
    its budget and its digest are the same on every host."""
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a, **k: 1 / 0)
    assert tfm.ridge_rows() == 256 and tfm.packed_rows(8, 64) == 256


def test_the_budget_follows_from_the_shapes():
    assert (R, tfm.packed_rows(8, 1024), tfm.packed_rows(4, 1024),
            tfm.packed_rows(8, 512)) == (24, 1152, 1152, 640)
    sym = cases.symbol("glm_dsa", S)
    packed, budget = tfm.packed_window(sym, SLOTS)
    assert budget == R
    out = packed._outputs[0][0]
    marked = {n.op: n.attrs["rows"] for n in packed._topo_nodes()
              if n.op in ("pack_rows", "unpack_rows", "last_rows")
              and n is not out}
    assert marked == {"pack_rows": R, "unpack_rows": R, "last_rows": R}
    # the logits leave as a row a slot: the reshape of an S = 1 graph
    assert (out.op, out.attrs["step_len"], out.attrs.get("rows", 0)) \
        == ("unpack_rows", 1, 0)
    assert packed.infer_shape(data=(SLOTS, S), fed=(SLOTS,))[1] \
        == [(SLOTS, 1, 48)]
    # the graph it was derived from is untouched
    assert not any(n.attrs.get("rows") or n.op == "last_rows"
                   for n in sym._topo_nodes() if not n.is_variable)
    assert sym.infer_shape(data=(SLOTS, S), fed=(SLOTS,))[1] \
        == [(SLOTS, S, 48)]
    # nobody rides at rung 1, and an S = 1 graph has nothing to pack
    assert tfm.packed_window(sym, 1) is None
    assert tfm.packed_window(cases.symbol("glm_dsa", 1), SLOTS) is None
    for case in cases.FUSED:
        assert tfm.packed_window(cases.symbol(case, S), SLOTS)[1] == R


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


#: ISSUE 65: a slot of one chunk is packed and unpacked without a loop.
#: (slots, rows a slot, budget) and what each slot is fed: the Cerebras
#: / OLMoE window's eight slots of 64 rows against its budget of 256,
#: and the tests' own four of 16 against 24
_ONE_CHUNK = {
    "all_riding": (8, 64, 256, [1] * 8),
    "a_chunk_and_riders": (8, 64, 256, [64, 1, 1, 1, 1, 1, 1, 1]),
    "every_slot_full_and_so_over": (8, 64, 256, [64] * 8),
    "four_chunks_fill_the_budget": (8, 64, 256, [64, 64, 64, 64, 0, 0, 0, 0]),
    "fed_nothing_first": (8, 64, 256, [0, 64, 1, 1, 7, 1, 1, 1]),
    "fed_nothing_in_the_middle": (8, 64, 256, [64, 1, 0, 0, 33, 1, 0, 1]),
    "fed_nothing_last": (8, 64, 256, [5, 64, 1, 1, 1, 1, 1, 0]),
    "a_ragged_last_chunk": (8, 64, 256, [37, 1, 1, 1, 1, 1, 1, 1]),
    "nothing_fed": (8, 64, 256, [0] * 8),
    "one_row_over": (8, 64, 256, [64, 64, 64, 63, 1, 1, 0, 0]),
    "half_over_a_small_budget": (4, 64, 128, [64, 50, 40, 3]),
}
_ONE_CHUNK.update({f"tiny_{name}": (SLOTS, S, R, fed)
                   for name, fed in MIXES.items()})

#: what a site carries: the token ids and the learned positions (no
#: trailing dimension), a layer's merged heads and its split into them
_ROWS = {"ids": (jnp.int32, ()), "rows": (jnp.bfloat16, (48,)),
         "heads": (jnp.bfloat16, (3, 16))}


@pytest.mark.parametrize("kind", sorted(_ROWS))
@pytest.mark.parametrize("mix", sorted(_ONE_CHUNK))
def test_a_slot_of_one_chunk_is_packed_as_the_loop_packs_it(mix, kind):
    """``pack`` / ``unpack`` where ``one_chunk(step_len)`` against the
    loop's own form at the same shape (``_pack_looped``,
    ``_unpack_looped``: ``_copy_real``, what the parent ran): the packed
    real rows ``[0, total)`` equal to the bit and every row past them
    finite, the whole ``(slots, step_len, ...)`` equal after ``unpack``
    (pads zero), and ``unpack(pack(x))`` the real rows again - inside
    the budget, at it and over it."""
    slots, step_len, budget, fed = _ONE_CHUNK[mix]
    dtype, tail = _ROWS[kind]
    assert rows.one_chunk(step_len) and len(fed) == slots
    rs = np.random.RandomState(sum(fed))
    real = (np.arange(step_len)[None, :] < np.asarray(fed)[:, None]) \
        .reshape((slots, step_len) + (1,) * len(tail))
    if dtype == jnp.int32:
        x = jnp.asarray(rs.randint(0, 1 << 20, (slots, step_len)), dtype)
    else:
        # a pad row may hold anything, a NaN too: nothing of it is packed
        x = jnp.where(real, jnp.asarray(
            rs.randn(slots, step_len, *tail), dtype), jnp.nan)
    fed = jnp.asarray(fed, jnp.int32)
    total = min(int(fed.sum()), budget)
    packed, count = rows.pack(x, fed, budget)
    want, want_count = rows._pack_looped(x, fed, budget)
    assert packed.shape == (1, budget) + tail and packed.dtype == dtype
    assert int(count[0]) == int(want_count[0]) == total
    np.testing.assert_array_equal(packed[:, :total], want[:, :total])
    assert np.isfinite(np.asarray(packed, np.float32)).all()
    # as a layer hands them on: one row a row, the heads merged
    flat = packed[0].reshape((budget, -1) if tail else (budget,))
    back = rows.unpack(flat, fed, step_len, budget, tail)
    assert back.shape == x.shape and back.dtype == dtype
    np.testing.assert_array_equal(
        back, rows._unpack_looped(flat, fed, step_len, budget, tail))
    if int(fed.sum()) <= budget:
        np.testing.assert_array_equal(
            back, jnp.where(real, x, jnp.zeros((), dtype)))


def _lowered(op, slots, step_len, budget):
    import jax
    fed = jax.ShapeDtypeStruct((slots,), jnp.int32)
    if op == "pack":
        x = jax.ShapeDtypeStruct((slots, step_len, 48), jnp.bfloat16)
        return jax.jit(lambda x, fed: rows.pack(x, fed, budget)) \
            .lower(x, fed).as_text()
    y = jax.ShapeDtypeStruct((budget, 48), jnp.bfloat16)
    return jax.jit(lambda y, fed: rows.unpack(
        y, fed, step_len, budget, (48,))).lower(y, fed).as_text()


#: the first 16 hex digits of the sha256 of what ``jax.jit`` lowers
#: ``_lowered``'s two functions to where a slot holds several chunks,
#: recorded on PR 65's parent (7c770d7) before ``ops/rows.py`` changed:
#: the loop's text stands as it was, to the letter
_PARENT_LOOP_SHA256 = {
    ("pack", 3, 256, 384): "ce7f23eede12985e",
    ("unpack", 3, 256, 384): "666aec16d32909e4",
    ("pack", 8, 256, 384): "518a3bfa561ae884",
    ("unpack", 8, 256, 384): "77abc68c3fd1d0f7",
    ("pack", 8, 1024, 1152): "9f52f86d279b513e",
    ("unpack", 8, 1024, 1152): "2a6c94c52b485ef7",
}


@pytest.mark.parametrize("op", ["pack", "unpack"])
def test_the_shape_decides_the_lowering(op):
    """From the lowered text: at ``(8, 64, .)`` against 256 rows (the
    Cerebras and OLMoE window) neither op holds a ``while``; at ``(3,
    256, .)`` against 384 each holds one, and there and at ``(8, 256,
    .)`` and ``(8, 1024, .)`` the text is the parent's, by digest."""
    import hashlib
    assert rows.one_chunk(64) and rows.one_chunk(16) \
        and not any(map(rows.one_chunk, (256, 512, 1024)))
    text = _lowered(op, 8, 64, 256)
    assert "while" not in text
    # one gather of rows packs; a slice a slot unpacks
    assert text.count('"stablehlo.gather"') == 1 if op == "pack" \
        else text.count("stablehlo.dynamic_slice") >= 8
    for case, digest in _PARENT_LOOP_SHA256.items():
        if case[0] == op:
            text = _lowered(*case)
            assert text.count("stablehlo.while") == 1
            assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("step_len,static", [
    (16, True), (64, True), (256, False), (512, False), (1024, False)])
def test_copy_sites_counts_the_budgeted_nodes_and_asks_the_shape(step_len,
                                                                 static):
    """What ``serve.decode.window.copy_sites`` / ``.static_copy_sites``
    count a launch: nothing of a whole-window graph, of its packed form
    two copies a layer (the split into heads, the merge), the tokens
    and the learned positions - all without a loop where a slot's rows
    are one chunk, none where they are several."""
    whole = cases.symbol("gpt2", step_len, capacity=2 * step_len)
    assert tfm.copy_sites(whole, step_len) == (0, 0)
    packed, _budget = tfm.packed_window(whole, 8)
    sites = 2 * cases.config("gpt2")["n_layer"] + 2
    assert tfm.copy_sites(packed, step_len) \
        == (sites, sites if static else 0)


@pytest.mark.parametrize("fed", sorted(MIXES.values()),
                         ids=sorted(MIXES, key=MIXES.get))
def test_last_rows_reads_each_slots_last_fed_row_in_both_views(fed):
    """``last_rows`` alone: from all ``slots x S`` rows (``rows = 0``)
    row ``fed - 1`` of each slot, and from the packed block under a
    budget the same row where ``pack_rows`` laid it; a slot fed nothing
    takes its own row 0 in the one view and, in the other, a row of the
    block - the row before its offset, or row 0."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(SLOTS, S, 3, 5), jnp.float32)
    fed = np.asarray(fed)
    want = np.stack([x[b, max(n - 1, 0)] for b, n in enumerate(fed)])
    got = rows.last(x, jnp.asarray(fed, jnp.int32))
    assert got.shape == (SLOTS, 1, 3, 5)
    np.testing.assert_array_equal(got[:, 0], want)
    if fed.sum() > R:
        return
    packed, _total = rows.pack(x, jnp.asarray(fed, jnp.int32), R)
    got = np.asarray(rows.last(packed, jnp.asarray(fed, jnp.int32), S, R))
    assert got.shape == (SLOTS, 1, 3, 5)
    ends = np.cumsum(fed)
    for b, n in enumerate(fed):
        np.testing.assert_array_equal(
            got[b, 0], want[b] if n else packed[0, max(ends[b] - 1, 0)])


def test_last_rows_is_an_op_of_both_views_and_stays_in_range():
    """The registered op: its shapes in either view, the budget's view
    checked, and counts that no host would send (past S, below 0, a
    sum past the budget) clipped as ``pack_rows`` clips them - a row of
    the block whatever ``fed`` holds."""
    x = mx.sym.var("x")
    fed = mx.sym.var("fed")
    whole = mx.sym.last_rows(x, fed)
    assert whole.infer_shape(x=(SLOTS, S, 6))[1] == [(SLOTS, 1, 6)]
    packed = mx.sym.last_rows(x, fed, step_len=S, rows=R)
    assert packed.infer_shape(x=(1, R, 6), fed=(SLOTS,))[1] \
        == [(SLOTS, 1, 6)]
    with pytest.raises(Exception, match="budget of 24"):
        packed.infer_shape(x=(SLOTS, S, 6), fed=(SLOTS,))
    block = jnp.arange(R * 2, dtype=jnp.float32).reshape(1, R, 2)
    for counts, at in (([S + 9, -3, 1, 0], [S - 1, S - 1, S, S]),
                       ([S, S, S, S], [S - 1, R - 1, R - 1, R - 1]),
                       ([0, 0, 0, 0], [0, 0, 0, 0])):
        got = rows.last(block, jnp.asarray(counts, jnp.int32), S, R)
        np.testing.assert_array_equal(got[:, 0], block[0, np.asarray(at)])
    every = jnp.arange(SLOTS * S, dtype=jnp.float32).reshape(SLOTS, S)
    got = rows.last(every, jnp.asarray([S + 9, -3, 1, 0], jnp.int32))
    np.testing.assert_array_equal(got[:, 0], every[np.arange(SLOTS),
                                                   [S - 1, 0, 0, 0]])


@pytest.mark.parametrize("block", cases.FED + ["evabyte_multibyte"])
def test_the_packed_form_selects_in_front_of_the_head(block):
    """``packed_window`` puts ``last_rows`` where ``_head`` starts - the
    final norm, or for a stream of copies their sum - whatever the
    block: everything behind it runs over ``slots`` rows, nothing in
    front of it does, and the graph's output is a row a slot."""
    if block == "evabyte_multibyte":
        sym = tfm.get_decode_symbol(
            block="evabyte", step_len=S, capacity=cases.CAPACITY,
            per_slot=True, tie_head=False, embed_scale=False,
            multibyte=True, **cases.BLOCKS["evabyte"])
        want = (SLOTS, 1, 2, 40)
    else:
        sym = cases.symbol(block, S)
        want = (SLOTS, 1, cases.config(block)["vocab_size"])
    packed, budget = tfm.packed_window(sym, SLOTS)
    nodes = packed._topo_nodes()
    (last,) = [n for n in nodes if n.op == "last_rows"]
    assert (last.attrs["rows"], last.attrs["step_len"]) == (budget, S)
    readers = [n.name for n in nodes
               if any(src is last for src, _ in n.inputs)]
    assert readers == ["lm_copies" if block == "xing4" else "lm_ln_f"]
    # what it reads is the last layer's join, a node of several
    # computed inputs: the head's own nodes read one each
    join = last.inputs[0][0]
    assert sum(not src.is_variable for src, _ in join.inputs) > 1
    given = {d.name: d.shape for d in cases.inputs(packed, SLOTS, S)}
    shapes = dict(zip(packed.get_internals().list_outputs(),
                      packed.get_internals().infer_shape(**given)[1]))
    assert shapes[f"{last.name}_output"] == (SLOTS, 1) \
        + shapes[f"{join.name}_output"][2:]
    assert shapes[f"{join.name}_output"][:2] == (1, budget)
    assert packed.infer_shape(**given)[1] == [want]


@pytest.fixture(scope="module", params=cases.FED)
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
        drv.step(tokens[:, step],
                 fed=(np.arange(SLOTS) <= step + 1).astype(np.int64))


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_packed_program_equals_the_whole_window_program(pair, mix):
    """Inside the budget the packed program returns ``(slots, 1, V)``:
    of every slot the whole-window program's row ``fed - 1`` (ISSUE 51:
    the head runs over that row alone; this stands where the comparison
    of every real row stood), finite for a slot fed nothing; past the
    budget the driver launches the whole-window program and hands back
    every row. Cursors, pools and the S = 1 step behind it as before."""
    block, whole, packed = pair
    fed = np.asarray(MIXES[mix])
    rs = np.random.RandomState(len(mix))
    vocab = cases.config(block)["vocab_size"]
    tokens = rs.randint(0, vocab, (SLOTS, 3 + S + 1))
    got = []
    for drv in (whole, packed):
        _fresh(drv, tokens)
        start = drv.pos.copy()
        out = drv.step(tokens[:, 3:3 + S], fed=fed).asnumpy()
        ran = drv.last_program_rows, drv.last_head_rows
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
    # the driver's switch: the packed program inside the budget, and
    # its head over a row a slot
    inside = fed.sum() <= R
    assert ran_w == (SLOTS * S, SLOTS * S)
    assert ran_p == ((R, SLOTS) if inside else (SLOTS * S, SLOTS * S))
    assert packed.window_budget(S) == R and whole.window_budget(S) is None
    np.testing.assert_array_equal(pos_p, start + fed)
    np.testing.assert_array_equal(pos_w, pos_p)
    for a, b in zip(cur_w, cur_p):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, pos_p)
    tol = TOL[block]
    assert out_w.shape == (SLOTS, S, vocab)
    assert out_p.shape == ((SLOTS, 1, vocab) if inside else out_w.shape)
    assert np.isfinite(out_p).all()
    for slot, n in enumerate(fed):
        if n:
            np.testing.assert_allclose(
                out_p[slot, 0 if inside else n - 1], out_w[slot, n - 1],
                rtol=0, atol=tol)
    # every pool's live rows, and what the next S = 1 step reads of the
    # state whatever family it is
    for a, b in zip(rows_w, rows_p):
        np.testing.assert_allclose(b, a, rtol=0, atol=tol)
    np.testing.assert_allclose(next_p, next_w, rtol=0, atol=tol)


@pytest.mark.parametrize("mix", ["one_prefilling_rest_riding",
                                 "unfed_first_and_last",
                                 "a_chunk_short_by_one"])
def test_select_rows_takes_a_packed_window_as_an_s1_step(pair, mix):
    """What the scheduler does behind a window: ``select_rows`` at each
    slot's last fed row. Of the packed program's one row a slot it
    picks that row - through ``select_rows_<slots>x1``, the S = 1
    step's program, no other - and hands on the ids, the rows and the
    next step's tokens that the whole-window program's ``(slots, S,
    V)`` gives."""
    block, whole, packed = pair
    fed = np.asarray(MIXES[mix])
    last = np.maximum(fed - 1, 0)
    rs = np.random.RandomState(len(mix))
    tokens = rs.randint(0, cases.config(block)["vocab_size"],
                        (SLOTS, 3 + S))
    got = {}
    for name, drv in (("whole", whole), ("packed", packed)):
        _fresh(drv, tokens)
        drv.select_rows(drv.step(tokens[:, 0], fed=np.zeros(SLOTS, int)),
                        np.zeros(SLOTS, int))          # the S = 1 program
        out = drv.step(tokens[:, 3:], fed=fed)
        picked, ids, nxt = drv.select_rows(out, last, feed=fed > 0)
        got[name] = (np.asarray(picked), np.asarray(ids), np.asarray(nxt))
        assert set(drv._select_programs) == \
            ({1, S} if name == "whole" else {1})
    live = fed > 0
    np.testing.assert_allclose(got["packed"][0][live], got["whole"][0][live],
                               rtol=0, atol=TOL[block])
    np.testing.assert_array_equal(got["packed"][1][live],
                                  got["whole"][1][live])
    np.testing.assert_array_equal(got["packed"][2], got["whole"][2])
    with pytest.raises(mx.base.MXNetError, match="row indices"):
        whole.select_rows(whole.step(tokens[:, 3:], fed=fed * 0),
                          last + S)


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
    engine = cases.engine(block, name, ladder=[1, SLOTS])
    sched = DecodeScheduler(engine, clock=FakeClock(), prefill_chunk=S,
                            prefix_store=None)
    mx.telemetry.flightrec.clear()
    rs = np.random.RandomState(11)
    prompt = lambda n: rs.randint(0, 40, n)         # noqa: E731
    handles = [sched.submit(prompt(n), max_new_tokens=m)
               for n, m in ((60, 10), (23, 12), (5, 2))]
    # until three dispatches have been launched: the third is on the
    # chip, so the newcomers are admitted by the fourth's plan whether
    # or not that one could have been launched ahead
    while sched.iterations + (sched._ahead is not None) < 3:
        sched.pump(max_iterations=1)
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
    """What PR 44's parent counted, and beside it what ISSUEs 46 and 53
    added: the ``runahead`` counters, ``window.dispatches``, the ring's
    ``ahead``, and in ``state.donated_bytes`` the steps a rung more
    that warm-up runs fed from the chip (two S = 1 steps and a window,
    in ``cursor.updates`` and ``cursor.rows`` the rewind before it).
    The script has no EOS, so every dispatch launched ahead, window or
    S = 1 step, is committed: same dispatches, same counts."""
    counters, ring, step_bytes = _counts_script(block)
    # ISSUE 51: the rows the windows' heads ran over, a row a slot of
    # a packed launch (rung 4: the script's windows are all inside the
    # budget but those at rung 1, which has no packed form)
    heads = counters.pop("window.head_rows")
    windows = [r for r in ring if r["window"] > 1]
    assert heads == sum(SLOTS if r["rung"] == SLOTS else S
                        for r in windows) > 0
    # ISSUE 65: the copies of rows in the launched programs - the packed
    # form's ``pack_rows`` / ``unpack_rows`` nodes under the budget, a
    # whole-window launch (rung 1) holds none - and every one of them
    # without a loop at the tests' one chunk a slot
    sites, static = tfm.copy_sites(
        tfm.packed_window(cases.symbol(block, S), SLOTS)[0], S)
    assert sites == static > 2
    assert counters.pop("window.copy_sites") \
        == counters.pop("window.static_copy_sites") \
        == sites * sum(r["rung"] == SLOTS for r in windows) > 0
    launched = counters.pop("runahead.launched")
    assert counters.pop("runahead.dropped") == 0
    assert counters.pop("window.dispatches") == len(windows)
    assert counters.pop("runahead.windows") == \
        sum(r["ahead"] for r in windows) > 0
    assert launched == sum(r.pop("ahead") for r in ring) > 0
    want = dict(COUNTS[block][0])
    want["state.donated_bytes"] += 3 * step_bytes
    # the rewind in front of warm-up's window fed from the chip: every
    # slot of each rung
    want["cursor.updates"] += 2
    want["cursor.rows"] += 1 + SLOTS
    assert counters == want
    # the fourth request is admitted by the plan behind the third
    # dispatch, while that is on the chip, and is active at its commit:
    # a dispatch early (its first chunk lies where the parent's did)
    ring[2]["active"] -= 1
    # ISSUE 54: a layer that holds a share says in the ring too how many
    # assignments landed here (``moe_held``), dispatch by dispatch what
    # the counter sums; the plain layer (OLMoE's) counts none and its
    # counter stays 0
    held = [r.pop("moe_held") for r in ring if "moe_held" in r]
    assert sum(held) == want.get("moe.held_assignments", 0)
    assert len(held) == (len(ring) if sum(held) else 0)
    assert ring == COUNTS[block][1]


def test_learned_positions_past_256_are_exact_at_bfloat16():
    """ISSUE 47, found on the chip: ``DecodeEngine`` bound ``pos_ids``
    as float32, every float input is cast to the compute width at graph
    entry, and bfloat16 holds only every second position past 256 and
    every eighth past 1,024. The S = 1 and whole-window programs read
    the right row of the table on the chip all the same (its compiler
    drops the cast in front of the gather), the packed window, which
    copies the positions into its block, did not: 0.14 on logits of 8
    from position 257 on. The positions are int32 now, like the tokens:
    at far cursors a bfloat16 engine is within rounding of a float32
    one, in both forms of the window and at S = 1."""
    capacity, slots = 2048, 8
    kw = dict(vocab_size=64, d_model=32, n_layer=2, n_head=2,
              pos_embed="learned", max_seq_len=capacity, capacity=capacity,
              per_slot=True)
    gen = lambda s: tfm.get_decode_symbol(step_len=s, **kw)  # noqa: E731
    shapes, _, _ = gen(1).infer_shape(data=(slots, 1), pos_ids=(slots, 1),
                                      fed=(slots,))
    rs = np.random.RandomState(2)
    params = {nm: (0.3 * rs.randn(*shape)).astype(np.float32)
              for nm, shape in zip(gen(1).list_arguments(), shapes)
              if nm not in ("data", "pos_ids", "fed")}
    tokens = rs.randint(0, 64, (slots, S))
    cursors = [257, 701, 1203, 1999, 5, 0, 0, 0]
    fed = np.asarray([S, 1, 1, 1, 1, 0, 0, 0])
    got = {}
    for dtype in (None, "bfloat16"):
        engine = DecodeEngine(f"pos-{dtype}", gen(1), params,
                              capacity=capacity, ladder=[slots],
                              symbol_gen=gen, window_lens=[S],
                              compute_dtype=dtype)
        drv = engine.driver(slots)
        cells = drv._mod._exec_group.executor.arg_dict
        assert str(cells["pos_ids"].dtype) == "int32"
        for form in ("packed", "whole"):
            drv.rewind_many(list(range(slots)), cursors)
            out = drv.step(tokens, fed=fed if form == "packed"
                           else np.where(fed, S, 0)).asnumpy()
            assert drv.last_program_rows == \
                (R if form == "packed" else slots * S)
            # the riders' one row (a packed window hands back each
            # slot's last fed row alone, ISSUE 51), and beside it the
            # last row of the slot that is fed its whole chunk
            got[dtype, form] = np.stack(
                [out[slot, 0] for slot in range(1, 5)]
                + [out[0, 0 if form == "packed" else S - 1]]
            ).astype(np.float32)
        drv.rewind_many(list(range(slots)), cursors)
        got[dtype, "s1"] = drv.step(
            tokens[:, :1], fed=(fed > 0).astype(np.int64)) \
            .asnumpy()[1:5, 0].astype(np.float32)
    np.testing.assert_allclose(got[None, "packed"], got[None, "whole"],
                               rtol=0, atol=1e-5)
    for form in ("packed", "whole", "s1"):
        np.testing.assert_allclose(got[None, form][:4], got[None, "s1"],
                                   rtol=0, atol=1e-5, err_msg=form)
        # bfloat16 against float32: rounding, not a neighbour's row
        # (which reads 0.5 and more on these logits of 3)
        np.testing.assert_allclose(got["bfloat16", form], got[None, form],
                                   rtol=0, atol=0.08, err_msg=form)
