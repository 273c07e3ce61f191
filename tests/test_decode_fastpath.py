"""Decode fast paths (ISSUE 18): chunked prefill, prefix-cache reuse,
speculative decoding.

Pins the tentpole's correctness contracts: chunked prefill is bit-exact
against token-at-a-time greedy at every chunk size (windowed S>1 cache
writes land the same bytes), prefix-cache joins restore rows bitwise
equal to a cold prefill, speculative decoding never emits a token the
target wouldn't sample (and is bit-identical to target-only decode
under greedy, even with a DIFFERENT draft model), sampled decode
replays byte-deterministically on a recorded per-request rng chain
across rung migrations, and the zero-steady-state-compile gate holds
with all three fast paths armed across join/leave at every rung.
Satellites ride along: the ttft/ttft_exec split, ``serve.decode.
prefill`` trace spans per chunk, memplan's prefix-store charge + ME801
on a toy budget, and PK9xx coverage of the S>1 window spec.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.serve import FakeClock, PrefixStore, SamplingParams
from mxnet_tpu.serve.sampling import (sample_token, speculative_verify,
                                      token_probs)

V, D, L, H, T = 64, 32, 2, 4, 32      # tiny LM; T doubles as capacity


def _train_params(d_model, n_layer, seed):
    np.random.seed(seed)
    sym = tfm.get_symbol(vocab_size=V, d_model=d_model, n_layer=n_layer,
                         n_head=H, seq_len=8, include_loss=False,
                         max_seq_len=T)
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind([("data", (1, 8))], None, for_training=False)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


@pytest.fixture(scope="module")
def target_params():
    return _train_params(D, L, seed=0)


@pytest.fixture(scope="module")
def draft_params():
    """A genuinely different (smaller) draft model: the rejection rule
    must keep greedy output identical anyway."""
    return _train_params(D, 1, seed=1)


def _nd(params):
    return {k: mx.nd.array(v) for k, v in params.items()}


def _gen(d_model=D, n_layer=L):
    return lambda s: tfm.get_decode_symbol(
        vocab_size=V, d_model=d_model, n_layer=n_layer, n_head=H,
        capacity=T, per_slot=True, step_len=s, max_seq_len=T)


_names = [0]


def _sched(target_params, ladder=(1, 2, 4), chunk=1, draft=None,
           spec_k=None, prefix_mb=0, clock=None, **kw):
    _names[0] += 1
    gen = _gen()
    return mx.serve.serve_decoder(
        gen(1), _nd(target_params), name=f"fast{_names[0]}", capacity=T,
        ladder=list(ladder), clock=clock or FakeClock(), start=False,
        symbol_gen=gen if (chunk > 1 or draft is not None) else None,
        prefill_chunk=chunk,
        draft_symbol_gen=_gen(n_layer=1) if draft is not None else None,
        draft_params=_nd(draft) if draft is not None else None,
        spec_k=spec_k, prefix_cache_mb=prefix_mb, **kw)


def _ref_greedy(params, prompt, n):
    """Token-at-a-time greedy through the scalar KVCacheDecoder — the
    PR-15 reference path every fast path must reproduce bitwise."""
    m = mx.mod.Module(
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=T, max_seq_len=T),
        label_names=[])
    m.bind([("data", (1, 1))], None, for_training=False)
    m.init_params(initializer=None, arg_params=_nd(params),
                  aux_params={}, allow_missing=True)
    d = tfm.KVCacheDecoder(m, capacity=T)
    for t in prompt[:-1]:
        d.step(np.asarray([[t]], np.int32))
    cur, out = int(prompt[-1]), []
    for _ in range(n):
        lg = d.step(np.asarray([[cur]], np.int32)).asnumpy()[0, 0]
        cur = int(np.argmax(lg))
        out.append(cur)
    return out


def _prompts(seed, n, lo=2, hi=12):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, V, rs.randint(lo, hi)).tolist()
            for _ in range(n)]


# ===================================================== chunked prefill
@pytest.mark.parametrize("chunk", [2, 3, 5, 8, 16])
def test_chunked_prefill_bit_exact_every_chunk_size(target_params,
                                                    chunk):
    """Acceptance: greedy output under chunked prefill is bit-identical
    to the token-at-a-time PR-15 path at every chunk size, including
    sizes that don't divide the prompt (padded final chunk + rewind)."""
    sched = _sched(target_params, ladder=(1, 2), chunk=chunk)
    prompts = _prompts(10 + chunk, 3)
    hs = [sched.submit(p, max_new_tokens=6) for p in prompts]
    sched.pump()
    outs = [list(h.result(timeout=5)) for h in hs]
    # stats snapshot NOW: compile_count is process-global and the
    # reference decoders below compile their own programs
    st = sched.stats()
    for p, out in zip(prompts, outs):
        assert out == _ref_greedy(target_params, p, 6)
    assert st["compiles_since_warmup"] == 0
    assert st["prefill_chunks"] >= 3


def test_chunked_prefill_mixed_decode_slots_ride_along(target_params):
    """A slot mid-decode rides a batchmate's chunk dispatch with one
    real token + pads and rewinds after — its stream is unchanged."""
    sched = _sched(target_params, ladder=(2,), chunk=8)
    a = [3, 5, 7]
    b = list(np.random.RandomState(2).randint(1, V, 20))
    ha = sched.submit(a, max_new_tokens=10)
    sched.pump(max_iterations=2)          # a reaches steady state
    hb = sched.submit(b, max_new_tokens=4)
    sched.pump()
    out_a = list(ha.result(timeout=5))
    out_b = list(hb.result(timeout=5))
    st = sched.stats()
    assert out_a == _ref_greedy(target_params, a, 10)
    assert out_b == _ref_greedy(target_params, b, 4)
    assert st["compiles_since_warmup"] == 0


def test_chunk_dispatch_count_and_prefill_spans(target_params):
    """A T-token prompt prefills in ceil(T/S) window dispatches, each
    recording one ``serve.decode.prefill`` span."""
    from mxnet_tpu.telemetry import trace as _trace
    _trace.clear()
    _trace.configure(sample=1)
    try:
        sched = _sched(target_params, ladder=(1,), chunk=8)
        prompt = list(np.random.RandomState(3).randint(1, V, 20))
        h = sched.submit(prompt, max_new_tokens=2)
        sched.pump()
        h.result(timeout=5)
        spans = [s for s in _trace.spans(h.trace_id)
                 if s["name"] == "serve.decode.prefill"]
        # 20 prompt tokens in chunks of 8 -> 8 + 8 + 4 dispatches (the
        # final chunk's last row doubles as the first sampling feed)
        assert len(spans) == 3
        assert sorted(s["tokens"] for s in spans) == [4, 8, 8]
        assert {s["chunk"] for s in spans} == {8}
    finally:
        _trace.configure(sample=_trace._env_sample(), reset_ids=False)


def test_ttft_and_ttft_exec_split(target_params):
    """Bugfix satellite: ``ttft`` counts from submit (queue wait
    included), ``ttft_exec`` from the first dispatch that covered the
    sequence — under queueing they must differ."""
    clock = FakeClock()
    sched = _sched(target_params, ladder=(1,), chunk=4, clock=clock)
    p1 = list(range(2, 8))
    h1 = sched.submit(p1, max_new_tokens=2)
    h2 = sched.submit(p1, max_new_tokens=2)   # queued behind h1
    assert h1.ttft is None and h1.ttft_exec is None
    while not h2.done():
        clock.advance(0.01)
        sched.pump(max_iterations=1)
    assert h1.ttft is not None and h1.ttft_exec is not None
    assert h1.ttft >= h1.ttft_exec
    # h2 sat in the queue while h1 decoded: wait shows up only in ttft
    assert h2.ttft - h2.ttft_exec > h1.ttft - h1.ttft_exec
    assert h2.ttft > h2.ttft_exec


# ===================================================== sampled decode
def test_sampling_filters_and_greedy_draws():
    rs = np.random.RandomState(0)
    logits = rs.randn(V).astype(np.float32)
    g = token_probs(logits, SamplingParams())
    assert g[int(np.argmax(logits))] == 1.0 and g.sum() == 1.0
    k3 = token_probs(logits, SamplingParams(temperature=1.0, top_k=3))
    assert (k3 > 0).sum() == 3 and abs(k3.sum() - 1.0) < 1e-12
    assert set(np.nonzero(k3)[0]) == set(np.argsort(-logits)[:3])
    p = SamplingParams(temperature=0.7, top_p=0.5)
    tp = token_probs(logits, p)
    full = token_probs(logits, SamplingParams(temperature=0.7))
    kept = np.nonzero(tp)[0]
    # minimal prefix: kept mass >= 0.5, dropping the smallest kept
    # token goes under
    assert full[kept].sum() >= 0.5
    assert full[kept].sum() - full[kept].min() < 0.5
    # greedy consumes NO rng draws
    rng = SamplingParams().make_rng()
    sample_token(logits, SamplingParams(), rng)
    assert rng.random() == SamplingParams().make_rng().random()
    with pytest.raises(mx.base.MXNetError):
        SamplingParams(temperature=-1)
    with pytest.raises(mx.base.MXNetError):
        SamplingParams(top_p=0.0)


def test_sampled_decode_byte_deterministic_replay(target_params):
    """Acceptance: a sampled run replays byte-for-byte given the same
    seeds — across staggered arrivals forcing rung migrations — and a
    different seed diverges."""
    def run(seed):
        sched = _sched(target_params, ladder=(1, 2, 4), chunk=4)
        prompts = _prompts(20, 5, lo=3, hi=10)
        hs = []
        for i, p in enumerate(prompts):
            hs.append(sched.submit(
                p, max_new_tokens=6,
                sampling=SamplingParams(temperature=0.9, top_k=20,
                                        top_p=0.95, seed=seed + i)))
            sched.pump(max_iterations=1 + i % 2)
        sched.pump()
        st = sched.stats()
        return [list(h.result(timeout=5)) for h in hs], st

    outs1, st1 = run(100)
    outs2, _ = run(100)
    assert outs1 == outs2                     # byte-deterministic
    assert st1["compiles_since_warmup"] == 0
    assert st1["migrations"] >= 1             # replay spans migrations
    outs3, _ = run(999)
    assert outs3 != outs1                     # the chain is the seed


# ================================================== speculative decode
def test_spec_verify_never_emits_untargeted_token():
    """The rejection rule's safety contract: every emitted token has
    nonzero target probability, accepted prefixes match proposals, and
    a rejection ends the window with a residual-sampled token."""
    rs = np.random.RandomState(5)
    params = SamplingParams(temperature=1.0, seed=7)
    for _ in range(50):
        K = rs.randint(1, 5)
        t_rows = rs.randn(K, V).astype(np.float32) * 3
        d_rows = rs.randn(K, V).astype(np.float32) * 3
        props = [sample_token(d_rows[j], params,
                              SamplingParams(seed=rs.randint(9)).
                              make_rng()) for j in range(K)]
        acc, toks = speculative_verify(t_rows, d_rows, props, params,
                                       params.make_rng())
        assert 0 <= acc <= K and 1 <= len(toks) <= K
        assert toks[:acc] == props[:acc]
        for j, tok in enumerate(toks):
            assert token_probs(t_rows[j], params)[tok] > 0.0
        if acc < K:
            assert len(toks) == acc + 1
    # greedy degeneracy: accept while argmaxes agree, then emit the
    # target argmax
    t_rows = rs.randn(3, V).astype(np.float32)
    d_rows = t_rows.copy()
    d_rows[1] += np.eye(V, dtype=np.float32)[0] * 100   # diverge at j=1
    g = SamplingParams()
    props = [int(np.argmax(r)) for r in d_rows]
    acc, toks = speculative_verify(t_rows, d_rows, props, g,
                                   g.make_rng())
    assert acc == 1 and toks == [int(np.argmax(t_rows[0])),
                                 int(np.argmax(t_rows[1]))]


def test_spec_greedy_bit_identical_with_foreign_draft(target_params,
                                                      draft_params):
    """Acceptance: greedy output with speculation armed (draft = a
    DIFFERENT model) is bit-identical to the PR-15 token-at-a-time
    path, at staggered per-slot positions, with zero steady-state
    compiles and live acceptance telemetry."""
    sched = _sched(target_params, ladder=(1, 2, 4), chunk=4,
                   draft=draft_params, spec_k=3)
    prompts = _prompts(30, 5, lo=2, hi=9)
    hs = []
    for i, p in enumerate(prompts):      # staggered: slots at
        hs.append(sched.submit(p, max_new_tokens=7))   # different pos
        sched.pump(max_iterations=1 + i % 2)
    sched.pump()
    outs = [list(h.result(timeout=5)) for h in hs]
    st = sched.stats()
    for p, out in zip(prompts, outs):
        assert out == _ref_greedy(target_params, p, 7)
    assert st["compiles_since_warmup"] == 0
    assert st["spec"]["k"] == 3
    assert st["spec"]["proposed"] > 0
    assert st["spec"]["acceptance"] is not None
    assert st["spec"]["rollbacks"] >= 0


def test_draft_shadow_fetches_nothing_and_spec_fetches_every_row(
        target_params, draft_params):
    """ISSUE 31 (e): on a window or S=1 dispatch the draft is launched
    behind the target and nothing of it is selected or brought to the
    host (the iteration fetches the target's ids alone); a speculative
    iteration still fetches every row of draft and target, and its
    streams are the token-at-a-time reference's."""
    sched = _sched(target_params, ladder=(2,), chunk=4,
                   draft=draft_params, spec_k=3)
    name, rung, K = sched.engine.name, 2, 3

    def select_rows(out, idx):
        raise AssertionError("the draft's output was selected from")
    sched.draft.driver(rung).select_rows = select_rows

    def count(key):
        return mx.telemetry.counter(f"serve.decode.{key}",
                                    model=name).value

    prompts = _prompts(31, 2, lo=6, hi=9)
    hs = [sched.submit(p, max_new_tokens=7) for p in prompts]
    per_iter = []
    while not all(h.done() for h in hs):
        before = count("fetch.bytes"), count("sample.host")
        assert sched.pump(max_iterations=1) == 1
        per_iter.append((count("fetch.bytes") - before[0],
                         count("sample.host") - before[1]))
    steps = [r for r in mx.telemetry.flightrec.get_records()
             if r.get("kind") == "serve.decode.step"
             and r.get("model") == name]
    assert len(steps) == len(per_iter)
    modes = [r["mode"] for r in steps]
    assert modes.count("window") >= 2 and modes.count("spec") >= 2
    spec_bytes = 4 * rung * V * (K + K)     # K draft rows, K target rows
    for r, (nbytes, on_host) in zip(steps, per_iter):
        if r["mode"] == "spec":
            assert nbytes == spec_bytes and on_host >= rung
        else:
            assert nbytes == 4 * rung and on_host == 0
    assert sched.engine.compiles_since_warmup() == 0
    assert sched.draft.backend_compiles_since_warmup() == 0
    for p, h in zip(prompts, hs):
        assert list(h.result(timeout=5)) == _ref_greedy(target_params, p, 7)


def test_spec_self_draft_accepts_everything(target_params):
    """Draft == target weights: every proposal verifies, acceptance is
    1.0 and no rollbacks happen — the acceptance-telemetry fixture."""
    draft = {k: v for k, v in target_params.items()}
    _names[0] += 1
    gen = _gen()
    sched = mx.serve.serve_decoder(
        gen(1), _nd(target_params), name=f"fast{_names[0]}", capacity=T,
        ladder=[1], clock=FakeClock(), start=False, symbol_gen=gen,
        prefill_chunk=1, draft_symbol_gen=gen, draft_params=_nd(draft),
        spec_k=4, prefix_cache_mb=0)
    p = [2, 9, 4]
    h = sched.submit(p, max_new_tokens=8)
    sched.pump()
    assert list(h.result(timeout=5)) == _ref_greedy(target_params, p, 8)
    st = sched.stats()["spec"]
    assert st["acceptance"] == 1.0 and st["rollbacks"] == 0
    # 8 tokens in ceil(8/4)=2 speculative iterations after prefill
    assert st["proposed"] == 8


def test_spec_validation_errors(target_params, draft_params):
    with pytest.raises(mx.base.MXNetError, match="draft_params"):
        mx.serve.serve_decoder(_gen()(1), _nd(target_params),
                               draft_symbol_gen=_gen(n_layer=1))
    with pytest.raises(mx.base.MXNetError, match="symbol_gen"):
        mx.serve.serve_decoder(_gen()(1), _nd(target_params),
                               draft_symbol_gen=_gen(n_layer=1),
                               draft_params=_nd(draft_params))


# ================================================== prefix-cache reuse
def test_prefix_join_rows_bitwise_equal_cold_prefill(target_params):
    """Acceptance: the rows a prefix hit restores are bitwise the rows
    a cold token-at-a-time prefill writes, and the warm sequence's
    output is identical."""
    sched = _sched(target_params, ladder=(1,), chunk=4, prefix_mb=4)
    prompt = list(np.random.RandomState(8).randint(1, V, 11))
    h_cold = sched.submit(prompt, max_new_tokens=5, prefix_id="sys")
    sched.pump()
    cold = list(h_cold.result(timeout=5))
    store = sched.prefix_store
    assert len(store) == 1 and store.misses == 1

    # warm join: same output, hit counted, zero steady-state compiles
    h_warm = sched.submit(prompt, max_new_tokens=5, prefix_id="sys")
    sched.pump()
    warm = list(h_warm.result(timeout=5))
    st = sched.stats()            # snapshot before the refs compile
    assert warm == cold
    assert store.hits >= 1
    assert st["prefix"]["hit_rate"] > 0
    assert st["compiles_since_warmup"] == 0

    assert cold == _ref_greedy(target_params, prompt, 5)
    # bitwise reference: a cold prefill of the SAME configuration in a
    # fresh scheduler — the stored rows are exactly what it writes
    # (decode only touches positions past the prompt, so the slot's
    # first len(prompt) rows still hold the prefill bytes)
    sched2 = _sched(target_params, ladder=(1,), chunk=4, prefix_mb=0)
    h2 = sched2.submit(prompt, max_new_tokens=5)
    sched2.pump()
    assert list(h2.result(timeout=5)) == cold
    ref_rows = sched2.engine.driver(1).capture_rows(0, len(prompt))
    entry = store.lookup("sys", np.asarray(prompt + [0]),
                         tags=("target",))[1]
    assert entry is not None
    for nm, ref in ref_rows.items():
        assert np.array_equal(entry.payloads["target"][nm], ref), nm
    # and within float tolerance of the token-at-a-time path (XLA may
    # reduce the S>1 einsum in a different order — low bits only;
    # greedy OUTPUT equality above is the bit-exactness contract)
    eng = mx.serve.DecodeEngine(
        f"fastref{_names[0]}", _gen()(1), _nd(target_params),
        capacity=T, ladder=[1])
    drv = eng.driver(1)
    drv.join(0)
    for t in prompt:
        drv.step(np.asarray([[t]], np.int32))
    for nm, ref in drv.capture_rows(0, len(prompt)).items():
        assert np.allclose(entry.payloads["target"][nm], ref,
                           rtol=1e-4, atol=1e-5), nm


def test_a_prefix_is_captured_behind_a_window_and_joined_after_a_commit(
        target_params):
    """ISSUE 53: with a dispatch in flight ahead of the host, a prompt's
    rows are captured at the commit of its last window while the step
    behind is on the chip - rows below the cursor, which that step only
    appends to - and are bitwise the synchronous order's. A request
    that names a prefix to the store waits for a commit (its join at a
    cursor restores rows); one that names none is admitted behind the
    dispatch on the chip."""
    prompt = list(np.random.RandomState(9).randint(1, V, 11))
    rider = list(np.random.RandomState(10).randint(1, V, 3))
    rows, outs = {}, {}
    for order in ("ahead", "sync"):
        sched = _sched(target_params, ladder=(2,), chunk=4, prefix_mb=4)
        if order == "sync":
            sched._plan_ahead = lambda d, now: None
        joins = lambda: sched.stats()["joins"]              # noqa: E731
        first = sched.submit(rider, max_new_tokens=12)
        sched.pump(max_iterations=2)
        assert (sched._ahead is not None) == (order == "ahead")
        cold = sched.submit(prompt, max_new_tokens=3, prefix_id="sys")
        sched.pump(max_iterations=1)
        if order == "ahead":        # what was on the chip commits first
            assert joins() == 1 and sched._ahead is None
        sched.pump()
        assert sched.prefix_store.misses == 1 and len(sched.prefix_store) == 1
        if order == "ahead":
            assert sched.stats()["runahead"]["windows"] >= 2
        entry = sched.prefix_store.lookup(
            "sys", np.asarray(prompt + [0]), tags=("target",))[1]
        rows[order] = entry.payloads["target"]
        # a warm join, and beside it a request that names no prefix
        again = sched.submit(rider, max_new_tokens=12)
        sched.pump(max_iterations=2)
        warm = sched.submit(prompt, max_new_tokens=3, prefix_id="sys")
        sched.pump(max_iterations=1)
        assert joins() == (3 if order == "ahead" else 4)
        sched.pump()
        plain = sched.submit(prompt, max_new_tokens=3)
        late = sched.submit(rider, max_new_tokens=2)
        sched.pump()
        assert sched.prefix_store.hits == 2      # this test's, the join's
        outs[order] = [list(h.result(timeout=5)) for h in
                       (first, cold, again, warm, plain, late)]
        assert outs[order][1] == outs[order][3] == outs[order][4]
        assert sched.stats()["compiles_since_warmup"] == 0
    assert outs["ahead"] == outs["sync"]
    assert outs["ahead"][1] == _ref_greedy(target_params, prompt, 3)
    for nm, ref in rows["sync"].items():
        assert np.array_equal(rows["ahead"][nm], ref), nm


def test_prefix_store_lru_mismatch_and_budget():
    rows = {"target": {"c": np.zeros((2, 8, 4), np.float32)}}
    entry_bytes = 2 * 8 + 2 * 8 * 4 * 4       # 2 int64 tokens + rows
    store = PrefixStore(budget_bytes=3 * entry_bytes)
    assert store.put("a", [1, 2], rows)
    assert store.put("b", [3, 4], rows)
    assert store.put("c", [5, 6], rows)
    store.lookup("a", np.asarray([1, 2, 9]))          # refresh a's LRU
    assert store.put("d", [7, 8], rows)               # evicts b
    assert store.lookup("b", np.asarray([3, 4, 9]))[1] is None
    assert store.lookup("a", np.asarray([1, 2, 9]))[1] is not None
    assert store.evictions >= 1
    # token mismatch: a miss (and a tick), never a wrong join
    c, e = store.lookup("a", np.asarray([9, 9, 9]))
    assert e is None and store.mismatches == 1
    # a missing engine payload (draft armed later) is a miss
    assert store.lookup("a", np.asarray([1, 2, 9]),
                        tags=("target", "draft"))[1] is None
    # full-prompt hits cap at len(prompt) - 1: one token always left
    c, e = store.lookup("a", np.asarray([1, 2]))
    assert e is not None and c == 1
    # oversized entries are dropped whole
    tiny = PrefixStore(budget_bytes=8)
    assert not tiny.put("x", [1], rows)
    assert len(tiny) == 0


# ===================================== all three armed: zero compiles
def test_zero_compiles_all_fastpaths_across_every_rung(target_params,
                                                       draft_params):
    """Acceptance: compile_count() delta == 0 after warmup with
    chunking + prefix reuse + speculation all armed, across join/leave
    churn forcing migrations through every rung."""
    sched = _sched(target_params, ladder=(1, 2, 4), chunk=4,
                   draft=draft_params, spec_k=3, prefix_mb=4)
    mark = mx.program_cache.compile_count()
    rs = np.random.RandomState(11)
    hs = [sched.submit(rs.randint(1, V, 6).tolist(), max_new_tokens=3,
                       prefix_id="war")]
    sched.pump()
    hs += [sched.submit(rs.randint(1, V, 4 + i).tolist(),
                        max_new_tokens=3 + i,
                        sampling=SamplingParams(temperature=0.8,
                                                seed=i))
           for i in range(4)]
    sched.pump()
    for i in range(5):
        hs.append(sched.submit(rs.randint(1, V, 5).tolist(),
                               max_new_tokens=3,
                               prefix_id="war" if i % 2 else None))
        sched.pump(max_iterations=2)
    sched.pump()
    for h in hs:
        h.result(timeout=5)
    assert mx.program_cache.compile_count() - mark == 0
    assert sched.engine.compiles_since_warmup() == 0
    assert sched.draft.compiles_since_warmup() == 0
    assert sched.stats()["migrations"] >= 2
    assert sched.engine.programs_resident()
    assert sched.draft.programs_resident()
    # 3 rungs x (S=1 + chunk window + verify window) on the target
    assert len(sched.engine.program_keys()) == 9


def test_stats_show_a_serving_window_puts_nothing_and_draws_no_key(
        target_params):
    """ISSUE 39: with telemetry on, ``stats()`` shows what the
    forwards did besides their programs since warm-up - windows, S=1
    steps, joins and a rung migration put no input a second time and
    draw no key; every input of every dispatch is taken as it is."""
    from mxnet_tpu import telemetry as tm
    tm.enable()
    try:
        sched = _sched(target_params, ladder=(1, 2), chunk=4)
        assert sched.stats()["launch_work_since_warmup"] == {
            "io.load_batch.puts": 0, "io.load_batch.aliased": 0,
            "executor.rng.draws": 0}
        chain = mx.random.get_state()["key"]
        for h in [sched.submit(p, max_new_tokens=4)
                  for p in _prompts(3, 3, lo=5, hi=9)]:
            sched.pump()
            h.result(timeout=5)
        st = sched.stats()
    finally:
        tm.disable()
    work = st["launch_work_since_warmup"]
    assert work["io.load_batch.puts"] == 0
    assert work["executor.rng.draws"] == 0
    # two inputs (the tokens and ``fed``: rotary positions) a dispatch
    assert work["io.load_batch.aliased"] == 2 * st["iterations"] > 0
    after = mx.random.get_state()["key"]
    assert chain is after or np.array_equal(chain, after)
    assert st["compiles_since_warmup"] == 0


def test_window_aux_cells_are_shared(target_params):
    """The S>1 window module advances the SAME device cache/cursor
    cells as the rung's S=1 module — the seam everything above rides."""
    eng = mx.serve.DecodeEngine(
        f"fastaux{_names[0]}", _gen()(1), _nd(target_params),
        capacity=T, ladder=[2], symbol_gen=_gen(), window_lens=(4,))
    base = eng._bm._buckets[2]._exec_group.executor
    win = eng._window_mods[(2, 4)]._exec_group.executor
    for nm, cell in base.aux_dict.items():
        assert win.aux_dict[nm] is cell, nm
    drv = eng.driver(2)
    assert drv.window_lens == [4]
    with pytest.raises(mx.base.MXNetError, match="window"):
        drv.step(np.zeros((2, 3), np.int32))   # no S=3 module


# ================================================= memplan satellites
def test_memplan_prefix_store_bytes_and_me801(target_params):
    """The prefix-store budget is charged as fixed device bytes on
    per-slot decode graphs (and ONLY there), and ME801 trips on a toy
    budget that fits the model but not model + store."""
    from mxnet_tpu.analysis import memplan
    sym = _gen()(1)
    plan0 = memplan.plan_symbol(sym, {"data": (2, 1)}, policy="none",
                                for_training=False)
    assert plan0["prefix_store_bytes"] == 0      # env unset -> uncharged
    budget = 1 << 20
    plan = memplan.plan_symbol(sym, {"data": (2, 1)}, policy="none",
                               for_training=False,
                               prefix_cache_bytes=budget)
    assert plan["prefix_store_bytes"] == budget
    assert plan["fixed_bytes"] == plan0["fixed_bytes"] + budget
    assert plan["per_op_bytes"].get("prefix_store") == budget
    # a non-decode graph never charges the store
    full = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=1, n_head=H,
                          seq_len=8, include_loss=False)
    planf = memplan.plan_symbol(full, {"data": (2, 8)}, policy="none",
                                for_training=False,
                                prefix_cache_bytes=budget)
    assert planf["prefix_store_bytes"] == 0
    # ME801: fits without the store, trips with it
    cap = plan0["peak_bytes_per_device"] + budget // 2
    assert not any(d.rule == "ME801" for d in
                   memplan.plan_findings(plan0, capacity_bytes=cap))
    assert any(d.rule == "ME801" for d in
               memplan.plan_findings(plan, capacity_bytes=cap))


def test_memplan_prefix_env(monkeypatch, target_params):
    from mxnet_tpu.analysis import memplan
    monkeypatch.setenv("MXNET_SERVE_PREFIX_CACHE_MB", "2")
    plan = memplan.plan_symbol(_gen()(1), {"data": (2, 1)},
                               policy="none", for_training=False)
    assert plan["prefix_store_bytes"] == 2 << 20


# ==================================================== PK9xx satellite
def test_attention_decode_window_kernel_spec():
    """PK9xx covers the S>1 window path: the declared tile set is
    VMEM-clean, lane/sublane aligned, and registration would refuse a
    misaligned one."""
    from mxnet_tpu.analysis.kernelcheck import validate_kernel_spec
    from mxnet_tpu.rtc import _ATTENTION_DECODE_KSPEC
    validate_kernel_spec("attention_decode", "window",
                         _ATTENTION_DECODE_KSPEC)   # idempotent: clean
    bad = dict(_ATTENTION_DECODE_KSPEC,
               tiles=[((64, 100), "float32")])      # lanes % 128 != 0
    with pytest.raises(mx.base.MXNetError, match="PK902"):
        validate_kernel_spec("attention_decode", "window", bad)


# ======================== the step programs take over the pools (PR 33)
def _lively(params, seed):
    """``params`` with every matrix redrawn (N(0, 0.5), the embedding
    N(0, 0.1)): the Xavier set of this file decodes to one token
    whatever the cache holds, this one to a varied sequence that a
    wrong cache row changes."""
    rs = np.random.RandomState(seed)
    return {k: rs.randn(*v.shape).astype(np.float32)
            * (0.1 if "embed" in k else 0.5) if v.ndim == 2 else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def lively_params(target_params):
    return _lively(target_params, 4)


def _engine33(target_params, ladder=(2, 4)):
    _names[0] += 1
    return mx.serve.DecodeEngine(
        f"donate{_names[0]}", _gen()(1), _nd(target_params), capacity=T,
        ladder=list(ladder), symbol_gen=_gen(), window_lens=(4,))


def _greedy(drv, slot, cur, n):
    """``n`` greedy tokens of ``slot`` from ``cur``, the token to feed
    next, by S=1 steps (the other slots ride along with token 0)."""
    out = []
    for _ in range(n):
        feed = np.zeros((drv.slots, 1), np.int32)
        feed[slot, 0] = cur
        cur = int(np.argmax(drv.step(feed).asnumpy()[slot, 0]))
        out.append(cur)
    return out


def _prefill(drv, slot, prompt):
    """Join ``slot`` and feed ``prompt[:-1]`` by S=1 steps."""
    drv.join(slot)
    for t in prompt[:-1]:
        feed = np.zeros((drv.slots, 1), np.int32)
        feed[slot, 0] = t
        drv.step(feed)


def _pool_arrays(drv):
    return [cell.asjax() for _nm, cell in drv.slot_cells()]


def _rewind_after_junk(target_params, prompt, n):
    drv = _engine33(target_params).driver(2)
    _prefill(drv, 0, prompt)
    held = _pool_arrays(drv)
    for junk in (9, 17, 4):                      # a rejected tail
        drv.step(np.full((2, 1), junk, np.int32))
    assert all(a.is_deleted() for a in held)
    drv.rewind(0, len(prompt) - 1)
    return _greedy(drv, 0, prompt[-1], n)


def _rewind_many_after_a_padded_window(target_params, prompt, n):
    drv = _engine33(target_params).driver(2)
    other = [5, 6, 7]
    drv.join(0), drv.join(1)
    for start in range(0, len(prompt) - 1, 4):   # windows of 4, padded
        win = np.zeros((2, 4), np.int32)
        for row, toks in ((0, prompt[:-1]), (1, other)):
            part = toks[start:start + 4]
            win[row, :len(part)] = part
        held = _pool_arrays(drv)
        drv.step(win)
        assert all(a.is_deleted() for a in held)
        drv.rewind_many([0, 1], [min(start + 4, len(prompt) - 1),
                                 min(start + 4, len(other))])
    return _greedy(drv, 0, prompt[-1], n)


def _restore_rows_into_another_slot(target_params, prompt, n):
    drv = _engine33(target_params).driver(2)
    _prefill(drv, 0, prompt)
    rows = drv.capture_rows(0, len(prompt) - 1)
    drv.step(np.zeros((2, 1), np.int32))         # the pools move on
    drv.leave(0)
    drv.join(1)
    drv.restore_rows(1, rows)
    drv.rewind(1, len(prompt) - 1)
    return _greedy(drv, 1, prompt[-1], n)


def _prefix_capture_and_hit(target_params, prompt, n):
    sched = _sched(target_params, ladder=(1, 2), chunk=4, prefix_mb=4)
    cold = sched.submit(prompt, max_new_tokens=n, prefix_id="sys")
    sched.pump()
    warm = sched.submit(prompt, max_new_tokens=n, prefix_id="sys")
    sched.pump()
    assert sched.prefix_store.hits >= 1
    assert list(cold.result(timeout=5)) == list(warm.result(timeout=5))
    return list(warm.result(timeout=5))


def _rung_migration(target_params, prompt, n):
    sched = _sched(target_params, ladder=(1, 2, 4), chunk=4)
    first = sched.submit(prompt, max_new_tokens=n)
    sched.pump(max_iterations=4)                 # decoding on rung 1
    rest = [sched.submit(p, max_new_tokens=3) for p in _prompts(33, 3)]
    sched.pump()
    for h in rest:
        h.result(timeout=5)
    assert sched.stats()["migrations"] >= 1
    return list(first.result(timeout=5))


def _speculative_rollback(target_params, prompt, n):
    sched = _sched(target_params, ladder=(1, 2), chunk=4,
                   draft=_lively(_train_params(D, 1, seed=1), 12),
                   spec_k=3)
    h = sched.submit(prompt, max_new_tokens=n)
    sched.pump()
    st = sched.stats()["spec"]
    assert st["proposed"] > 0 and st["rollbacks"] > 0
    return list(h.result(timeout=5))


def _a_second_warmup(target_params, prompt, n):
    sched = _sched(target_params, ladder=(1, 2), chunk=4)
    first = sched.submit(prompt[:3], max_new_tokens=2)
    sched.pump()
    assert len(first.result(timeout=5)) == 2
    sched.engine.warmup(FakeClock())             # over pools in use
    h = sched.submit(prompt, max_new_tokens=n)
    sched.pump()
    return list(h.result(timeout=5))


def _one_slot_decoder_reset(target_params, prompt, n):
    m = mx.mod.Module(
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=T, max_seq_len=T),
        label_names=[])
    m.bind([("data", (1, 1))], None, for_training=False)
    m.init_params(initializer=None, arg_params=_nd(target_params),
                  aux_params={}, allow_missing=True)
    d = tfm.KVCacheDecoder(m, capacity=T)
    exe = m._exec_group.executor
    held = [cell.asjax() for cell in exe.aux_arrays]
    for t in (3, 1, 4, 1, 5):                    # another sequence first
        d.step(np.asarray([[t]], np.int32))
    assert all(a.is_deleted() for a in held)
    d.reset()
    for t in prompt[:-1]:
        d.step(np.asarray([[t]], np.int32))
    cur, out = int(prompt[-1]), []
    for _ in range(n):
        cur = int(np.argmax(d.step(np.asarray([[cur]], np.int32))
                            .asnumpy()[0, 0]))
        out.append(cur)
    return out


_AFTER_DONATED_STEPS = {
    "rewind": _rewind_after_junk,
    "rewind_many": _rewind_many_after_a_padded_window,
    "restore_rows": _restore_rows_into_another_slot,
    "prefix_store": _prefix_capture_and_hit,
    "migrate": _rung_migration,
    "spec_rollback": _speculative_rollback,
    "warmup": _a_second_warmup,
    "one_slot": _one_slot_decoder_reset,
}


@pytest.mark.parametrize("what", sorted(_AFTER_DONATED_STEPS))
def test_the_host_reads_cells_never_arrays_held_across_a_step(
        lively_params, what):
    """ISSUE 33: every step program takes over the K and V pools and
    the cursors (an array read from a cell before a step is deleted by
    it), so whatever the host does to them between steps goes through
    the cell. Each path, after donated steps, ends in the tokens of an
    undisturbed token-at-a-time run."""
    prompt = list(np.random.RandomState(41).randint(1, V, 10))
    n = 8
    want = _ref_greedy(lively_params, prompt, n)
    assert len(set(want)) > 4                    # no fixed point
    assert _AFTER_DONATED_STEPS[what](lively_params, prompt, n) == want
