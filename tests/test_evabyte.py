"""EvaByte behind the serving path (ops/eva.py, block="evabyte" of
models/transformer.py, the fed contract of BatchedKVCacheDecoder and
serve/decode.py) against the plain reference chipbench/reference/
evabyte.py, at small widths on the CPU: window 32 and chunk 4, so that
window boundaries are cheap to cross; phi and mu drawn at N(0, 1), so
that the pooling is no mean."""
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
from mxnet_tpu.ops import eva
from mxnet_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.reference import evabyte as ref  # noqa: E402

CFG = {"vocab_size": 40, "hidden_size": 32, "num_attention_heads": 2,
       "num_hidden_layers": 2, "intermediate_size": 48,
       "window_size": 32, "chunk_size": 4, "num_pred_heads": 8,
       "rope_theta": 100000, "rms_norm_eps": 1e-5}
CAPACITY, WINDOW, SLOTS = 128, 16, 3            # WINDOW: the S > 1 program
W, C = CFG["window_size"], CFG["chunk_size"]
#: float32 served against the float32 reference through 2 layers, on
#: logits of magnitude about 1: a few float32 ulps of the partial sums
#: (measured here: 4e-7 to 2e-6)
TOL = 2e-5


def _symbol(step_len, multibyte=True, capacity=CAPACITY):
    return tfm.get_decode_symbol(
        vocab_size=CFG["vocab_size"], d_model=CFG["hidden_size"],
        n_layer=CFG["num_hidden_layers"],
        n_head=CFG["num_attention_heads"], pos_embed="rotary",
        rope_base=float(CFG["rope_theta"]), capacity=capacity,
        step_len=step_len, per_slot=True, block="evabyte", window=W,
        chunk=C, n_pred_heads=CFG["num_pred_heads"],
        ffn_width=CFG["intermediate_size"],
        rms_eps=CFG["rms_norm_eps"], tie_head=False, embed_scale=False,
        multibyte=multibyte)


def _params(seed=5):
    symbol = _symbol(1)
    shapes, _, _ = symbol.infer_shape(data=(SLOTS, 1), fed=(SLOTS,))
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(symbol.list_arguments(), shapes):
        if name in ("data", "fed"):
            continue
        scale = 1.0 if name.endswith(("_phi", "_mu")) else \
            0.3 if name.endswith("_gamma") else 0.25
        out[name] = (scale * rng.standard_normal(shape)).astype(np.float32)
    return out


PARAMS = _params()


def _bound(step_len, shared=None, slots=SLOTS, multibyte=True):
    mod = mx.mod.Module(_symbol(step_len, multibyte),
                        data_names=("data", "fed"), label_names=[])
    mod.bind([mx.io.DataDesc("data", (slots, step_len), np.int32),
              mx.io.DataDesc("fed", (slots,), np.int32)],
             None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None, arg_params=dict(PARAMS),
                        aux_params={}, allow_missing=True)
    return mod


@pytest.fixture(scope="module", params=["xla", "pallas"])
def driver(request):
    """A three-slot pool with its S = 16 window program, all 8 heads as
    the output, under one kernel tier (the Pallas kernels in interpret
    mode)."""
    old = os.environ.get("MXNET_KERNEL_TIER")
    os.environ["MXNET_KERNEL_TIER"] = request.param
    kernel_tier.clear()
    base = _bound(1)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=SLOTS)
    drv.add_window(WINDOW, _bound(WINDOW, shared=base))
    yield drv
    if old is None:
        os.environ.pop("MXNET_KERNEL_TIER", None)
    else:
        os.environ["MXNET_KERNEL_TIER"] = old
    kernel_tier.clear()


def _reference(seqs):
    fwd = jax.jit(lambda p, t: ref.forward(p, t, CFG, all_heads=True))
    return np.asarray(fwd(PARAMS, jnp.asarray(seqs)))


def _run(drv, seqs, schedule, start=None):
    """Feed ``seqs`` (slots, T) through ``schedule``, a list of (S, fed
    counts a slot): the logits of every fed position, (slots, T, 8, V).
    Every slot joins fresh first, or goes on from ``start``; a pad is a
    junk token."""
    if start is None:
        for slot in range(drv.slots):
            if drv.active[slot]:
                drv.leave(slot)
            drv.join(slot)
        start = [0] * drv.slots
    got = np.zeros(seqs.shape + (CFG["num_pred_heads"], CFG["vocab_size"]),
                   np.float32)
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


def _full(n):                       # n full windows for every slot
    return [(WINDOW, [WINDOW] * SLOTS)] * n


def _ones(n, fed=(1,) * SLOTS):
    return [(1, list(fed))] * n


SCHEDULES = {
    # (a) the third and fourth windows read the first 32 positions as
    # 8 summaries
    "window_reads_summaries": _full(4),
    # (b) S = 1 at positions 48.. reads 8 summaries and 16.. exact rows
    "decode_reads_summaries": _full(3) + _ones(6),
    # (c) decode walks over the boundaries at 32 and 64
    "decode_closes_a_window": _full(1) + _ones(20)
    + [(WINDOW, [WINDOW] * SLOTS)] + _ones(14),
    # (d) ragged windows: boundaries at 32, 64 and 96 fall inside
    # dispatches, at another row for each slot
    "window_closes_mid_dispatch": [(WINDOW, [9, 13, 16]),
                                  (WINDOW, [16, 16, 11])] * 4,
    # (e) slot 0 prefills while slot 1 rides with 1 token and slot 2
    # with 1 or none, from just before the boundary at 32 across it
    "riders_before_a_boundary": [(WINDOW, [16, 15, 15]),
                                 (WINDOW, [16, 15, 14]),
                                 (WINDOW, [16, 1, 0]), (WINDOW, [16, 1, 1]),
                                 (WINDOW, [16, 1, 1]), (WINDOW, [16, 1, 0]),
                                 (WINDOW, [3, 1, 1])],
    # (f) lengths that are no multiple of the chunk of 4, then decode
    "lengths_off_the_chunk": [(WINDOW, [13, 7, 16]), (WINDOW, [16, 10, 5]),
                              (WINDOW, [9, 16, 14]), (WINDOW, [1, 2, 3])]
    + _ones(5) + _ones(3, fed=(1, 0, 1)),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_prefill_and_decode_match_the_reference_full_forward(driver, case):
    """All 8 heads of every fed position against the plain reference's
    full forward, whatever the dispatches' shapes."""
    rng = np.random.default_rng(sorted(SCHEDULES).index(case))
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 120)).astype(np.int32)
    got, at = _run(driver, seqs, SCHEDULES[case])
    want = _reference(seqs)
    assert at.max() > W                 # somebody left the first window
    for slot, n in enumerate(at):
        err = np.abs(got[slot, :n] - want[slot, :n])
        assert err.max() <= TOL, (case, slot, float(err.max()))


def test_a_rider_just_before_the_boundary_decodes_as_if_alone(driver):
    """A slot at position W - 1 that rides a window dispatch with one
    real token and 15 pads closes its window on the real token alone:
    the logits of a slot that took the same steps at S = 1."""
    rng = np.random.default_rng(9)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 96)).astype(np.int32)
    seqs[2] = seqs[1]                   # slot 2 decodes slot 1's bytes alone
    lead = [(WINDOW, [16, 16, 16]), (WINDOW, [16, 15, 15])]   # 1, 2 at 31
    rode, _ = _run(driver, seqs,
                   lead + [(WINDOW, [16, 1, 0])] * 3)
    alone, _ = _run(driver, seqs, lead + _ones(3, fed=(0, 0, 1)))
    np.testing.assert_allclose(rode[1, 31:34], alone[2, 31:34],
                               rtol=0, atol=2e-6)
    assert np.abs(rode[1, 31:34]).max() > 0.1


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("S", [1, 6, 16])
def test_the_op_matches_the_references_layer(variant, S):
    """The op alone, both lowerings, against the reference's attention:
    ragged ``fed`` (0 included), garbage in the pads, boundaries inside
    dispatches."""
    B, H, d, T = 3, 2, 16, 90
    rng = np.random.default_rng(S)
    q, k, v = (rng.standard_normal((B, H, T, d)).astype("f")
               for _ in range(3))
    phi, mu = (rng.standard_normal((H, d)).astype("f") for _ in range(2))
    want = np.asarray(ref.eva_attention(
        ref.rope(jnp.asarray(q), 1e4), ref.rope(jnp.asarray(k), 1e4),
        jnp.asarray(v), phi, mu, 16, 4))
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs(
        {"capacity": 112, "window": 16, "chunk": 4, "rope_base": 1e4})
    fn = opdef.variant_fn(variant)
    run = jax.jit(lambda ins, aux: fn(attrs, ins, aux, False, None))
    aux = [jnp.zeros((B, H, 16, d))] * 2 + [jnp.zeros((B, H, 28, d))] * 2 \
        + [jnp.zeros((B, 1), jnp.int32)]
    got = np.zeros_like(want)
    at = np.zeros(B, int)
    step = 0
    while at.min() < T:
        fed = np.array([min(S, T - at[b], 1 + (step + b) % S)
                        for b in range(B)])
        if step % 3 == 1:
            fed[0] = 0
        new = [np.full((B, H, S, d), junk, "f") for junk in (7., -9., 5.)]
        for b, n in enumerate(fed):
            for dst, src in zip(new, (q, k, v)):
                dst[b, :, :n] = src[b, :, at[b]:at[b] + n]
        out, aux = run(new + [jnp.asarray(fed, jnp.int32), phi, mu], aux)
        for b, n in enumerate(fed):
            got[b, :, at[b]:at[b] + n] = np.asarray(out[0])[b, :, :n]
        at += fed
        step += 1
        assert list(np.asarray(aux[4])[:, 0]) == list(at)
    assert np.abs(got - want).max() <= 5e-6


def test_a_slot_without_room_is_fed_nothing():
    """cursor + S past the capacity: the op leaves the slot where it
    is, and so does the driver's mirror."""
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs({"capacity": 32, "window": 16, "chunk": 4})
    x = jnp.ones((2, 1, 8, 8))
    aux = [jnp.zeros((2, 1, 16, 8))] * 2 + [jnp.zeros((2, 1, 8, 8))] * 2 \
        + [jnp.asarray([[20], [28]], jnp.int32)]
    _, new_aux = opdef.forward(
        attrs, [x, x, x, jnp.asarray([8, 8], jnp.int32),
                jnp.ones((1, 8)), jnp.ones((1, 8))], aux, False, None)
    assert np.asarray(new_aux[4]).ravel().tolist() == [28, 28]
    with pytest.raises(MXNetError, match="longer than the window"):
        opdef.forward(attrs, [jnp.ones((2, 1, 17, 8))] * 3
                      + [jnp.asarray([1, 1]), jnp.ones((1, 8)),
                         jnp.ones((1, 8))], aux, False, None)


# ------------------------------------------------------ the driver's contract
def test_the_ops_declare_their_state_families(driver):
    assert sorted(driver._state) == ["cursor", "summary", "window"]
    assert not driver.positional and driver.feeds
    assert driver.window == W and driver.summarises
    assert [n for n, _reads in driver._reads] == [2]    # two layers alike
    assert len(driver.slot_cells()) == 5 * CFG["num_hidden_layers"]
    # the K/V decoder's families, by the same declaration
    sym = tfm.get_decode_symbol(vocab_size=16, d_model=16, n_layer=2,
                                n_head=2, capacity=8, per_slot=True)
    state = tfm.slot_state(sym)
    assert sorted(state) == ["cursor", "rows"]
    assert state["rows"] == ["lm_l0_attn_k_cache", "lm_l0_attn_v_cache",
                             "lm_l1_attn_k_cache", "lm_l1_attn_v_cache"]
    assert state["cursor"] == ["lm_l0_attn_cache_pos", "lm_l1_attn_cache_pos"]


def test_rewind_restore_and_overflow_say_what_they_cannot_do(driver):
    rng = np.random.default_rng(2)
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    _run(driver, seqs, _full(2) + [(WINDOW, [8, 0, 16])])   # 40, 32, 48
    with pytest.raises(MXNetError, match="cannot move"):
        driver.rewind(0, 31)            # behind the closed window
    with pytest.raises(MXNetError, match="cannot move"):
        driver.rewind_many([0, 2], [36, 20])
    with pytest.raises(MXNetError, match="cannot move"):
        driver.rewind(0, 44)            # ahead of the cursor
    assert list(driver.pos) == [40, 32, 48]      # a refusal moves nothing
    driver.rewind(0, 33)                # inside the open window
    driver.rewind(1, 30)                # the window that has just closed:
    driver.rewind(2, 0)                 # its rows are all still there
    assert list(driver.pos) == [33, 30, 0]
    got, _ = _run(driver, seqs, _ones(4), start=[33, 30, 0])
    want = _reference(seqs)
    for slot, t0 in enumerate([33, 30, 0]):
        assert np.abs(got[slot, t0:t0 + 4]
                      - want[slot, t0:t0 + 4]).max() <= TOL
    with pytest.raises(MXNetError, match="row per position"):
        driver.capture_rows(0, 8)
    with pytest.raises(MXNetError, match="row per position"):
        driver.restore_rows(0, {})
    # overflowing is about the context, not about a pool's rows
    driver.pos[:] = [CAPACITY - 16, CAPACITY - 15, 5]
    assert driver.overflowing(WINDOW) == [1]
    assert driver.overflowing(1) == []
    driver.pos[:] = [37, 34, 4]


def test_join_after_leave_starts_clean(driver):
    """A slot that held 70 positions (two closed windows of summaries,
    a ring of rows) serves a new sequence as a fresh pool would: only
    the cursor is reset."""
    rng = np.random.default_rng(4)
    old = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    _run(driver, old, _full(4) + _ones(6))
    seqs = rng.integers(0, CFG["vocab_size"], (SLOTS, 80)).astype(np.int32)
    got, at = _run(driver, seqs, _full(3) + _ones(3))       # leaves, joins
    want = _reference(seqs)
    assert np.abs(got[:, :51] - want[:, :51]).max() <= TOL


def _gpt2_module():
    """GPT-2's two-slot decode graph as it was before it took ``fed``
    (built by hand: ``window_pack_cases.unfed_symbol``), bound and
    initialised."""
    from chipbench import weights
    import window_pack_cases as cases
    sym = cases.unfed_symbol("gpt2_rotary", 1, vocab_size=16, d_model=16,
                             n_layer=1, n_head=2, capacity=8,
                             rope_base=10000.0)
    assert "fed" not in sym.list_arguments()
    mod = mx.mod.Module(sym, data_names=("data",), label_names=[])
    mod.bind([mx.io.DataDesc("data", (2, 1), np.int32)], None,
             for_training=False)
    mod.init_params(initializer=None, aux_params={}, allow_missing=True,
                    arg_params=weights.normal_init(sym, {"data": (2, 1)}, 3))
    return mod


def test_step_refuses_fed_for_a_graph_that_takes_none():
    mod = _gpt2_module()
    drv = tfm.BatchedKVCacheDecoder(mod, 8, slots=2)
    assert drv.positional and not drv.feeds
    with pytest.raises(MXNetError, match="no fed input"):
        drv.step(np.zeros((2, 1), np.int32), fed=[1, 1])


def test_the_builder_refuses_what_the_block_is_not():
    base = dict(vocab_size=16, d_model=16, n_head=2, block="evabyte",
                ffn_width=8)
    with pytest.raises(MXNetError, match="served, not trained"):
        tfm.get_symbol(vocab_size=16, d_model=16, n_head=2, block="evabyte")
    with pytest.raises(MXNetError, match="per_slot"):
        tfm.get_decode_symbol(capacity=32, window=16, chunk=4, **base)
    with pytest.raises(MXNetError, match="rotary"):
        tfm.get_decode_symbol(capacity=32, per_slot=True,
                              pos_embed="learned", **base)
    with pytest.raises(MXNetError, match="multiples of chunk"):
        tfm.get_decode_symbol(capacity=32, per_slot=True, window=18,
                              chunk=4, **base).infer_shape(
            data=(1, 1), fed=(1,))


# --------------------------------------------------- engine and scheduler
def _gen(step_len):
    return _symbol(step_len, multibyte=False)


@pytest.fixture(scope="module")
def engine():
    return mx.serve.DecodeEngine(
        "tiny-evabyte", _gen(1), PARAMS, capacity=CAPACITY,
        ladder=[1, 2, 4], symbol_gen=_gen, window_lens=[WINDOW])


def test_migrate_carries_both_pools_and_the_cursor(engine):
    """Two slots at positions 37 and 50 of the 4-slot pool move to the
    2-slot pool, swapped, and decode on: the reference's logits."""
    rng = np.random.default_rng(6)
    seqs = rng.integers(0, CFG["vocab_size"], (2, 60)).astype(np.int32)
    want = np.asarray(jax.jit(lambda p, t: ref.forward(p, t, CFG))(
        PARAMS, jnp.asarray(seqs)))
    big, small = engine.driver(4), engine.driver(2)
    for drv in (big, small):
        drv.active[:] = False
    big.join(1), big.join(3)
    lens = {1: 37, 3: 50}
    at = {1: 0, 3: 0}
    while any(at[s] < lens[s] for s in lens):
        tokens = np.zeros((4, WINDOW), np.int32)
        fed = np.zeros(4, np.int32)
        for s, row in ((1, 0), (3, 1)):
            n = min(WINDOW, lens[s] - at[s])
            tokens[s, :n] = seqs[row, at[s]:at[s] + n]
            fed[s] = n
            at[s] += n
        big.step(tokens, fed=fed)
    engine.migrate(4, 2, [(1, 1), (3, 0)])
    assert list(small.pos) == [50, 37] and small.active.all()
    assert not big.active.any()
    for j in range(5):
        out = small.step(np.asarray([[seqs[1, 50 + j]], [seqs[0, 37 + j]]],
                                    np.int32)).asnumpy()
        assert np.abs(out[0, 0] - want[1, 50 + j]).max() <= TOL
        assert np.abs(out[1, 0] - want[0, 37 + j]).max() <= TOL
    small.active[:] = False


def _served(sched, prompts, max_new):
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    sched.pump()
    return [[int(t) for t in h.result(timeout=5)] for h in handles]


def test_mixed_prefill_and_decode_equals_one_request_at_a_time(engine):
    """Four requests of ragged lengths, admitted together (windows with
    riders, rung switches, windows closing while others prefill): the
    greedy tokens of each request served alone, and the counters of
    what the state was asked for."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import flightrec
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG["vocab_size"], n).tolist()
               for n in (45, 9, 30, 70)]
    clock = mx.serve.FakeClock()
    sched = mx.serve.DecodeScheduler(engine, clock=clock,
                                     prefill_chunk=WINDOW)
    assert sched.prefill_chunk == WINDOW
    alone = [_served(sched, [p], 12)[0] for p in prompts]
    before = {k: sched._counter(k).value
              for k in ("eva.layer_steps", "eva.exact_rows",
                        "eva.summary_rows", "eva.chunks_summarised",
                        "eva.windows_closed", "cursor.rows")}
    mixed = _served(sched, prompts, 12)
    assert mixed == alone
    assert all(len(t) == 12 for t in mixed)
    grew = {k: sched._counter(k).value - v for k, v in before.items()}
    layers = CFG["num_hidden_layers"]
    # positions 0..n+10 of each request are fed (the last token is
    # sampled, not fed): chunks and windows by arithmetic
    fed = [len(p) + 11 for p in prompts]
    assert grew["eva.chunks_summarised"] == layers * sum(n // C for n in fed)
    assert grew["eva.windows_closed"] == layers * sum(n // W for n in fed)
    assert grew["eva.summary_rows"] > 0 and grew["eva.exact_rows"] > 0
    # a fed decoder rewinds nothing after a window: the only cursor
    # moves are the four joins
    assert grew["cursor.rows"] == 4
    steps = [r for r in flightrec.get_records()
             if r.get("kind") == "serve.decode.step"
             and r.get("model") == "tiny-evabyte"]
    assert steps and all("eva_exact" in r and "eva_summary" in r
                         for r in steps)
    assert any(r["eva_summary"] > 0 and r["window"] == 1 for r in steps)
    assert sched.stats()["compiles_since_warmup"] == 0
    assert telemetry.get_metric("serve.decode.eva.layer_steps",
                                model="tiny-evabyte").value > 0


def test_the_scheduler_refuses_drafts_and_prefix_stores(engine):
    from mxnet_tpu.serve.prefix import PrefixStore
    with pytest.raises(MXNetError, match="prefix_store"):
        mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                 prefix_store=PrefixStore(1 << 20))
    with pytest.raises(MXNetError, match="speculative"):
        mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                 draft_engine=engine, spec_k=4)
    with pytest.raises(MXNetError, match="prefix_store"):
        mx.serve.serve_decoder(
            _gen(1), PARAMS, name="tiny-evabyte-refused",
            capacity=CAPACITY, ladder=[1], symbol_gen=_gen,
            prefill_chunk=WINDOW, prefix_cache_mb=1, start=False,
            clock=mx.serve.FakeClock())


def test_serve_decoder_serves_the_block_with_no_side_script():
    """The one-call front end, default arguments but the sizes: no
    prefix store is made for a state it could not reuse."""
    sched = mx.serve.serve_decoder(
        _gen(1), PARAMS, name="tiny-evabyte-front", capacity=CAPACITY,
        ladder=[1, 2], symbol_gen=_gen, prefill_chunk=WINDOW, start=False,
        clock=mx.serve.FakeClock())
    assert sched.prefix_store is None and sched.engine.feeds
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    tokens = _served(sched, [prompt.tolist()], 6)[0]
    seq = np.concatenate([prompt, tokens[:-1]])[None].astype(np.int32)
    want = np.asarray(ref.forward(PARAMS, jnp.asarray(seq), CFG))[0]
    assert tokens == np.argmax(want[40:], axis=-1).tolist()


def test_the_older_blocks_step_programs_take_no_fed_and_donate_their_pools():
    """GPT-2's decode graph without the ``fed`` input it has had since
    ISSUE 47, and a positional state;
    like this block's, its step program takes over every aux array (an
    array read from a cell before the step is deleted by it) and the
    cell holds the new one."""
    mod = _gpt2_module()
    exe = mod._exec_group.executor
    drv = tfm.BatchedKVCacheDecoder(mod, 8, slots=2)
    assert drv.positional and not drv.feeds and exe.donates_aux
    assert drv.donated_bytes == 2 * (2 * 2 * 8 * 8 * 4) + 2 * 4
    pool = exe.aux_dict["lm_l0_attn_k_cache"]
    held = pool.asjax()
    drv.step(np.zeros((2, 1), np.int32))
    assert held.is_deleted() and not pool.asjax().is_deleted()
    assert np.asarray(pool.asjax())[:, :, 0].any()      # row 0 written
    assert not np.asarray(pool.asjax())[:, :, 1:].any()  # and no other
    eva_mod = _bound(1, slots=1)
    ring = eva_mod._exec_group.executor.aux_dict["lm_l0_attn_singles_k"]
    held = ring.asjax()
    tfm.BatchedKVCacheDecoder(eva_mod, CAPACITY, slots=1).step(
        np.zeros((1, 1), np.int32))
    assert held.is_deleted() and not ring.asjax().is_deleted()
