"""Continuous decode batching (ISSUE 15, ROADMAP 3b).

Pins the tentpole end to end: the per-slot ``attention_decode``
lowering ((B, 1) cursor vector, per-slot masked softmax, per-slot
writes in place), the ``BatchedKVCacheDecoder`` driver (staggered sequences
reproduce independent ``KVCacheDecoder`` runs, bit-clean slot reuse,
host-side per-slot overflow), the ``DecodeScheduler`` (FakeClock-
deterministic staggered arrivals/finishes, streaming delivery,
EOS/max-new/deadline retirement, an overflowing slot failing alone),
and the zero-steady-state-compile contract: ``compile_count()`` delta
== 0 across arbitrary join/leave at every slot rung, including rung
migrations. Satellites ride along: slot-pooled export artifacts
(``Predictor.reset_slot``), memplan's slot-pool KV bytes + an ME801
trip at a toy capacity x slot count, and the telemetry surface.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.serve import FakeClock, QueueFullError

import decode_blocks as cases

V, D, L, H, T = 64, 32, 2, 4, 16      # tiny LM; T doubles as capacity


@pytest.fixture(scope="module")
def trained():
    """One trained parameter set shared by every pool/reference pair."""
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=H,
                         seq_len=8, include_loss=False, max_seq_len=T)
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind([("data", (1, 8))], None, for_training=False)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def _args_nd(trained):
    return {k: mx.nd.array(v) for k, v in trained.items()}


def _pooled_module(trained, slots, compute_dtype=None,
                   pos_embed="rotary", capacity=T):
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=capacity,
                                per_slot=True, pos_embed=pos_embed,
                                max_seq_len=capacity)
    descs = cases.inputs(sym, slots, 1)     # tokens, positions, ``fed``
    dec = mx.mod.Module(sym, data_names=[d.name for d in descs],
                        label_names=[], compute_dtype=compute_dtype)
    dec.bind(descs, None, for_training=False)
    dec.init_params(initializer=None, arg_params=_args_nd(trained),
                    aux_params={}, allow_missing=True)
    return dec


def _scalar_decoder(trained, compute_dtype=None, pos_embed="rotary",
                    capacity=T):
    m = mx.mod.Module(
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=capacity,
                              pos_embed=pos_embed,
                              max_seq_len=capacity),
        data_names=("data", "pos_ids") if pos_embed == "learned"
        else ("data",), label_names=[], compute_dtype=compute_dtype)
    shapes = [("data", (1, 1))] + ([("pos_ids", (1,))]
                                   if pos_embed == "learned" else [])
    m.bind(shapes, None, for_training=False)
    m.init_params(initializer=None, arg_params=_args_nd(trained),
                  aux_params={}, allow_missing=True)
    return tfm.KVCacheDecoder(m, capacity=capacity, pos_embed=pos_embed)


def _ref_logits(trained, tokens, **kw):
    """Per-step logits of ONE sequence through the scalar decoder."""
    d = _scalar_decoder(trained, **kw)
    return [d.step(np.asarray([[t]], np.int32)).asnumpy()[0, 0]
            for t in tokens]


def _ref_greedy(trained, prompt, n, **kw):
    d = _scalar_decoder(trained, **kw)
    for t in prompt[:-1]:
        d.step(np.asarray([[t]], np.int32))
    cur, out = int(prompt[-1]), []
    for _ in range(n):
        lg = d.step(np.asarray([[cur]], np.int32)).asnumpy()[0, 0]
        cur = int(np.argmax(lg))
        out.append(cur)
    return out


_sched_seq = [0]


def _sched(trained, ladder, clock=None, pos_embed="rotary",
           compute_dtype=None, capacity=T, name=None, **kw):
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=capacity,
                                per_slot=True, pos_embed=pos_embed,
                                max_seq_len=capacity)
    # unique engine name per scheduler: the serve.decode.* counters are
    # process-global per model label, so stats() stays per-instance
    _sched_seq[0] += 1
    eng = mx.serve.DecodeEngine(name or f"lmdec{_sched_seq[0]}", sym,
                                _args_nd(trained), capacity=capacity,
                                ladder=ladder,
                                compute_dtype=compute_dtype)
    return mx.serve.DecodeScheduler(
        eng, clock=clock if clock is not None else FakeClock(), **kw)


# ================================================ per-slot op lowering
def test_per_slot_infer_shape_and_cursor_binding():
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=T, per_slot=True)
    _args, outs, auxs = sym.infer_shape(data=(4, 1))
    assert outs == [(4, 1, V)]
    by_name = dict(zip(sym.list_auxiliary_states(), auxs))
    cursors = {n: s for n, s in by_name.items()
               if n.endswith("cache_pos")}
    assert len(cursors) == L
    assert set(cursors.values()) == {(4, 1)}       # per-slot vector
    caches = {n: s for n, s in by_name.items() if n.endswith("k_cache")}
    assert set(caches.values()) == {(4, H, T, D // H)}


def test_per_slot_cursor_binds_int32(trained):
    dec = _pooled_module(trained, slots=3, compute_dtype="bfloat16")
    exe = dec._exec_group.executor
    cursors = [nm for nm in exe.aux_dict if nm.endswith("cache_pos")]
    assert cursors
    for nm in cursors:
        cell = exe.aux_dict[nm]
        assert cell.asjax().dtype == jnp.int32
        assert tuple(cell.shape) == (3, 1)


def test_per_slot_window_lowering():
    """S>1 per-slot windows (ISSUE 18): each slot writes S cache rows
    at its own cursor, the causal mask staggers per slot, and the
    cursor vector advances by S."""
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1,
                                n_head=H, per_slot=True, step_len=2)
    _args, outs, _auxs = sym.infer_shape(data=(4, 2))
    assert outs == [(4, 2, V)]
    op = get_op("attention_decode")
    rs = np.random.RandomState(3)
    B, Hh, S, Dh, C = 2, 1, 2, 4, 8
    q, k, v = (jnp.asarray(rs.randn(B, Hh, S, Dh).astype(np.float32))
               for _ in range(3))
    kc = jnp.asarray(rs.randn(B, Hh, C, Dh).astype(np.float32))
    vc = jnp.asarray(rs.randn(B, Hh, C, Dh).astype(np.float32))
    cur = jnp.asarray([[0], [3]], jnp.int32)
    outs, auxs = op.forward({"capacity": C, "per_slot": True},
                            [q, k, v], [kc, vc, cur], False, None)
    k2, v2, cur2 = auxs
    assert np.array_equal(np.asarray(cur2), [[2], [5]])
    # slot 0 wrote rows 0..1, slot 1 rows 3..4; everything else intact
    assert np.array_equal(np.asarray(k2[0, :, :2]), np.asarray(k[0]))
    assert np.array_equal(np.asarray(k2[1, :, 3:5]), np.asarray(k[1]))
    assert np.array_equal(np.asarray(k2[0, :, 2:]),
                          np.asarray(kc[0, :, 2:]))
    assert np.array_equal(np.asarray(v2[1, :, :3]),
                          np.asarray(vc[1, :, :3]))


def test_per_slot_eager_overflow_names_slots():
    op = get_op("attention_decode")
    q = jnp.zeros((3, 1, 1, 4))
    cache = jnp.zeros((3, 1, 4, 4))
    cur = jnp.asarray([[4], [1], [4]], jnp.int32)
    with pytest.raises(mx.base.MXNetError, match=r"slot\(s\) \[0, 2\]"):
        op.forward({"capacity": 4, "per_slot": True}, [q, q, q],
                   [cache, cache, cur], False, None)


def _one_hot_write(news, pools, pos):
    """The per-slot write as it was until PR 33: ``jnp.where`` over the
    whole pool, one selection a window row; a row at or past the
    capacity matches nothing."""
    key_pos = jnp.arange(pools[0].shape[2])
    out = []
    for new, cache in zip(news, pools):
        for s in range(new.shape[2]):
            write = (key_pos[None, :] == pos[:, None] + s)[:, None, :, None]
            cache = jnp.where(write, new[:, :, s:s + 1], cache)
        out.append(cache)
    return out


def _decode_op_case(S, cache_dtype, seed=7):
    rs = np.random.RandomState(seed)
    B, Hh, Dh, C = 4, 2, 8, 16
    qkv = [jnp.asarray(rs.randn(B, Hh, S, Dh).astype(np.float32))
           for _ in range(3)]
    pools = [jnp.asarray(rs.randn(B, Hh, C, Dh).astype(np.float32))
             .astype(cache_dtype) for _ in range(2)]
    attrs = {"capacity": C, "per_slot": True, "rope": True}
    if cache_dtype != "float32":
        attrs["cache_dtype"] = cache_dtype
    return get_op("attention_decode").normalize_attrs(attrs), qkv, pools, C


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("cache_dtype", ["float32", "float8_e4m3fn"])
@pytest.mark.parametrize("S", [1, 4])
def test_in_place_write_equals_the_one_hot_write(S, cache_dtype, variant,
                                                 monkeypatch):
    """ISSUE 33: one per-slot write for every S. At ragged cursors it
    leaves the pools and the outputs bit-equal to the one-hot
    ``jnp.where`` rule it replaced, at the compute width and with an
    fp8 ``cache_dtype``, in the composition and in the kernel's
    variant."""
    from mxnet_tpu import rtc
    from mxnet_tpu.ops import pallas_kernels
    op = get_op("attention_decode")
    attrs, qkv, pools, C = _decode_op_case(S, cache_dtype)
    cur = jnp.asarray([[0], [5], [C - S], [9]], jnp.int32)
    fn = op.forward if variant == "xla" else op.variant_fn("pallas")
    run = lambda: jax.jit(                                   # noqa: E731
        lambda r, a: fn(attrs, r, a, False, None))(qkv, pools + [cur])
    outs, (k2, v2, cur2) = run()
    # the same op over the old rule, in place of either tier's write
    monkeypatch.setattr(rtc, "_write_rows", _one_hot_write)
    monkeypatch.setattr(pallas_kernels, "cache_write", _one_hot_write)
    want_outs, (wk, wv, wcur) = run()
    assert k2.dtype == wk.dtype == jnp.dtype(cache_dtype)
    for got, want in ((outs[0], want_outs[0]), (k2, wk), (v2, wv),
                      (cur2, wcur)):
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
    assert np.array_equal(np.asarray(cur2)[:, 0],
                          np.asarray(cur)[:, 0] + S)
    for b, p in enumerate(np.asarray(cur)[:, 0]):   # and it did write
        assert not np.array_equal(
            np.asarray(k2[b, :, p:p + S].astype(jnp.float32)),
            np.asarray(pools[0][b, :, p:p + S].astype(jnp.float32)))


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("S", [1, 4])
def test_a_cursor_at_or_past_capacity_writes_nothing(S, variant):
    """A slot whose S rows end one past the capacity (at S=1: a cursor
    at the capacity) and a slot one past it (a free slot riding along
    keeps advancing) leave every row of every slot as it was: nothing is
    clamped onto a live row. The slots beside them, one of them a free
    slot at a position of its own, write their own S rows and no
    other."""
    op = get_op("attention_decode")
    attrs, qkv, pools, C = _decode_op_case(S, "float32", seed=11)
    cur = np.asarray([[2], [C - S + 1], [C + 1], [C - S]], np.int32)
    fn = op.forward if variant == "xla" else op.variant_fn("pallas")
    _outs, (k2, v2, cur2) = jax.jit(
        lambda r, a: fn(attrs, r, a, False, None))(
            qkv, pools + [jnp.asarray(cur)])
    assert np.array_equal(np.asarray(cur2), cur + S)
    for new, old in ((k2, pools[0]), (v2, pools[1])):
        new, old = np.asarray(new), np.asarray(old)
        for b in (1, 2):
            np.testing.assert_array_equal(new[b], old[b])
        for b in (0, 3):
            p = cur[b, 0]
            keep = np.ones(C, bool)
            keep[p:p + S] = False
            np.testing.assert_array_equal(new[b][:, keep], old[b][:, keep])
            assert not np.array_equal(new[b][:, ~keep], old[b][:, ~keep])
    if S == 1:                  # where the one-hot rule says the same
        want, = _one_hot_write([qkv[2]], [pools[1]], jnp.asarray(cur[:, 0]))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(want))


def test_rope_per_batch_positions():
    """rope_apply over (B, T) positions == per-row application of the
    (T,) path at each row's positions."""
    from mxnet_tpu.ops.nn import rope_apply
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(3, 2, 1, 8).astype(np.float32))
    pos = jnp.asarray([[5], [0], [11]], jnp.int32)
    got = rope_apply(x, pos)
    for b in range(3):
        ref = rope_apply(x[b:b + 1], pos[b])
        np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                      np.asarray(ref))


# ============================================== batched driver parity
@pytest.mark.parametrize("compute_dtype,tol", [
    (None, 2e-6), ("bfloat16", 2e-2)])
def test_staggered_batched_decode_matches_independent(trained,
                                                      compute_dtype,
                                                      tol):
    """Acceptance (parity gate): SLOTS sequences decoded concurrently
    with staggered join/leave reproduce per-sequence KVCacheDecoder
    outputs — f32 ~1e-6, bf16 2e-2 — including a slot reused by a
    later sequence."""
    slots = 3
    dec = _pooled_module(trained, slots, compute_dtype=compute_dtype)
    drv = tfm.BatchedKVCacheDecoder(dec, capacity=T)
    rs = np.random.RandomState(1)
    seqs = [rs.randint(0, V, 6).astype(np.int32) for _ in range(4)]
    refs = [_ref_logits(trained, s, compute_dtype=compute_dtype)
            for s in seqs]

    got = {i: [] for i in range(4)}
    live = {}                       # slot -> [seq_index, next_pos]
    joins = {0: (0, 0), 2: (1, 1), 3: (2, 2)}   # iteration -> (seq, slot)
    for it in range(64):
        if it in joins:
            si, slot = joins[it]
            drv.join(slot)
            live[slot] = [si, 0]
        if not live:
            break
        toks = np.zeros((slots, 1), np.int32)
        for slot, (si, k) in live.items():
            toks[slot, 0] = seqs[si][k]
        out = drv.step(toks).asnumpy()
        for slot, (si, k) in list(live.items()):
            got[si].append(out[slot, 0])
            live[slot][1] += 1
            if live[slot][1] >= len(seqs[si]):
                drv.leave(slot)
                del live[slot]
                if si == 0:         # slot reuse mid-flight
                    drv.join(slot)
                    live[slot] = [3, 0]
    for i in range(4):
        assert len(got[i]) == len(seqs[i])
        for t in range(len(seqs[i])):
            np.testing.assert_allclose(
                np.asarray(got[i][t], np.float32),
                np.asarray(refs[i][t], np.float32),
                rtol=tol, atol=tol, err_msg=f"seq {i} step {t}")


def test_slot_reuse_is_bit_clean(trained):
    """A sequence decoded in a slot that previously held (and retired)
    another sequence is BITWISE identical to the same sequence on a
    fresh pool — the masked softmax zeroes stale positions exactly."""
    slots = 2
    rs = np.random.RandomState(2)
    a = rs.randint(0, V, T).astype(np.int32)        # fills the slot
    b = rs.randint(0, V, 5).astype(np.int32)

    dec1 = _pooled_module(trained, slots)
    drv1 = tfm.BatchedKVCacheDecoder(dec1, capacity=T)
    drv1.join(0)
    for t in range(T):
        drv1.step(np.asarray([[a[t]], [0]], np.int32))
    drv1.leave(0)
    drv1.join(0)                                    # reuse
    reused = [drv1.step(np.asarray([[tok], [0]], np.int32))
              .asnumpy()[0, 0] for tok in b]

    dec2 = _pooled_module(trained, slots)
    drv2 = tfm.BatchedKVCacheDecoder(dec2, capacity=T)
    drv2.join(0)
    fresh = [drv2.step(np.asarray([[tok], [0]], np.int32))
             .asnumpy()[0, 0] for tok in b]
    for t in range(len(b)):
        np.testing.assert_array_equal(reused[t], fresh[t])


def test_driver_overflow_raises_before_dispatch(trained):
    """Satellite: the host-side per-slot overflow check — the pinned
    program can never see a concrete cursor, so the driver raises
    BEFORE dispatch, naming the slot, and batchmates are untouched."""
    dec = _pooled_module(trained, 2)
    drv = tfm.BatchedKVCacheDecoder(dec, capacity=T)
    drv.join(0)
    drv.join(1)
    toks = np.zeros((2, 1), np.int32)
    for _ in range(T):
        drv.step(toks)
    with pytest.raises(mx.base.MXNetError, match=r"slot\(s\) \[0, 1\]"):
        drv.step(toks)
    # retiring the overflowing slot unblocks its batchmate... which
    # here means retiring 0 still leaves 1 overflowing
    drv.leave(0)
    with pytest.raises(mx.base.MXNetError, match=r"slot\(s\) \[1\]"):
        drv.step(toks)
    drv.leave(1)
    drv.join(0)                     # fresh sequence decodes fine
    out = drv.step(toks)
    assert out.shape == (2, 1, V)


def test_learned_positions_per_slot(trained):
    """Per-slot pos_ids feed: staggered learned-position decode matches
    the scalar driver."""
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=1, n_head=H,
                         seq_len=8, include_loss=False,
                         pos_embed="learned", max_seq_len=T)
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind([("data", (1, 8))], None, for_training=False)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))
    args, _ = mod.get_params()
    args = {k: v.asnumpy() for k, v in args.items()}

    def scalar_ref(tokens):
        m = mx.mod.Module(
            tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1,
                                  n_head=H, capacity=T,
                                  pos_embed="learned", max_seq_len=T),
            data_names=("data", "pos_ids"), label_names=[])
        m.bind([("data", (1, 1)), ("pos_ids", (1,))], None,
               for_training=False)
        m.init_params(initializer=None,
                      arg_params={k: mx.nd.array(v)
                                  for k, v in args.items()},
                      aux_params={}, allow_missing=True)
        d = tfm.KVCacheDecoder(m, capacity=T, pos_embed="learned")
        return [d.step(np.asarray([[t]], np.int32)).asnumpy()[0, 0]
                for t in tokens]

    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1,
                                n_head=H, capacity=T, per_slot=True,
                                pos_embed="learned", max_seq_len=T)
    descs = cases.inputs(sym, 2, 1)
    assert [d.name for d in descs] == ["data", "pos_ids", "fed"]
    dec = mx.mod.Module(sym, data_names=[d.name for d in descs],
                        label_names=[])
    dec.bind(descs, None, for_training=False)
    dec.init_params(initializer=None,
                    arg_params={k: mx.nd.array(v)
                                for k, v in args.items()},
                    aux_params={}, allow_missing=True)
    drv = tfm.BatchedKVCacheDecoder(dec, capacity=T,
                                    pos_embed="learned")
    rs = np.random.RandomState(3)
    s0 = rs.randint(0, V, 5).astype(np.int32)
    s1 = rs.randint(0, V, 4).astype(np.int32)
    r0, r1 = scalar_ref(s0), scalar_ref(s1)
    drv.join(0)
    got0, got1 = [], []
    for it in range(7):
        if it == 2:
            drv.join(1)             # staggered: slot 1 two steps later
        toks = np.zeros((2, 1), np.int32)
        if it < len(s0):
            toks[0, 0] = s0[it]
        if 2 <= it < 2 + len(s1):
            toks[1, 0] = s1[it - 2]
        out = drv.step(toks).asnumpy()
        if it < len(s0):
            got0.append(out[0, 0])
        if 2 <= it < 2 + len(s1):
            got1.append(out[1, 0])
    for got, ref in ((got0, r0), (got1, r1)):
        for t in range(len(ref)):
            np.testing.assert_allclose(got[t], ref[t], rtol=1e-5,
                                       atol=2e-6)


# ===================================== one program moves every cursor
def _pool_symbol(kind, step_len=1):
    """``(symbol, data names)`` of a slot pool: the dense
    learned-position block (3 layers), or the rotary block with routed
    experts (2 layers)."""
    kw = dict(vocab_size=V, d_model=D, n_head=H, capacity=T,
              per_slot=True, max_seq_len=T, step_len=step_len)
    if kind == "dense-learned":
        return tfm.get_decode_symbol(n_layer=3, pos_embed="learned",
                                     **kw), ("data", "pos_ids", "fed")
    return tfm.get_decode_symbol(
        n_layer=2, pos_embed="rotary", block="olmoe", n_expert=4,
        top_k=2, expert_width=16, tie_head=False, embed_scale=False,
        **kw), ("data", "fed")


def _bound_pool(kind, slots):
    """A bound (never stepped) slot pool of ``_pool_symbol(kind)``."""
    sym, names = _pool_symbol(kind)
    descs = cases.inputs(sym, slots, 1)
    assert tuple(d.name for d in descs) == names
    mod = mx.mod.Module(sym, data_names=names, label_names=[])
    mod.bind(descs, None, for_training=False)
    return tfm.BatchedKVCacheDecoder(
        mod, capacity=T, pos_embed="learned" if "pos_ids" in names
        else "rotary")


_CURSOR_MOVES = {                 # op -> (rows, positions) of 5 slots
    "join": ([3], [0]),
    "rewind": ([1], [9]),
    "rewind_many-1": ([4], [2]),
    "rewind_many-some": ([3, 0, 2], [5, 11, 0]),
    "rewind_many-all": ([0, 1, 2, 3, 4], [7, 0, 3, 12, 1]),
}


@pytest.mark.parametrize("move", sorted(_CURSOR_MOVES))
@pytest.mark.parametrize("kind", ["dense-learned", "rotary-routed"])
def test_cursor_moves_equal_eager_scatter(kind, move):
    """ISSUE 29: ``join`` / ``rewind`` / ``rewind_many`` launch one
    program over all layers' cursor cells. Afterwards every cell equals
    what the eager ``.at[idx, 0].set(val)`` wrote, for the rows named
    and for no other, the host mirror agrees, and each cell has its
    own buffer, dtype and placement."""
    slots = 5
    drv = _bound_pool(kind, slots)
    assert drv.routed == (kind == "rotary-routed")
    cells = drv._cursor_cells()
    assert len(cells) == (3 if kind == "dense-learned" else 2)
    start = np.asarray([4, 6, 8, 10, 13], np.int32)
    placed = [c.asjax().sharding for c in cells]
    for cell, sharding in zip(cells, placed):      # as a step leaves them
        cell._set(jax.device_put(start[:, None], sharding))
    drv.pos[:] = start
    rows, vals = _CURSOR_MOVES[move]
    want = np.asarray(jnp.asarray(start[:, None]).at[
        np.asarray(rows, np.int32), 0].set(jnp.asarray(vals, jnp.int32)))
    if move == "join":
        assert drv.join(rows[0]) == rows[0] and drv.active[rows[0]]
    elif move == "rewind":
        drv.rewind(rows[0], vals[0])
    else:
        drv.rewind_many(rows, vals)
    untouched = [r for r in range(slots) if r not in rows]
    for cell, sharding in zip(cells, placed):
        got = cell.asjax()
        assert got.dtype == jnp.int32 and got.sharding == sharding
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(got)[untouched, 0],
                                      start[untouched])
    np.testing.assert_array_equal(drv.pos, want[:, 0])
    assert len({c.asjax().unsafe_buffer_pointer() for c in cells}) \
        == len(cells)
    # any number of rows is the same program: no second compile
    drv.rewind_many([0], [1])
    drv.rewind_many([1, 2], [1, 1])
    assert drv._cursor_program._cache_size() == 1
    with pytest.raises(mx.base.MXNetError, match="named twice"):
        drv.rewind_many([2, 2], [3, 4])
    drv.rewind_many([], [])                        # nothing to move


def test_cursor_program_never_compiles_after_warmup(trained):
    """ISSUE 29: chunked prefill at every rung — joins, window rewinds
    of 1..rung rows, retirements, rung switches — compiles nothing
    after warm-up outside ``DecodeEngine.migrate`` (whose eager per-row
    copies are not steady state), and ``cursor.updates`` / ``.rows``
    count exactly the joins plus the non-empty rewinds: none after a
    window (the graph is fed its real tokens and advances by them),
    1..rung rows where somebody asks for them (speculation's rollback,
    a prefix joined at a cursor)."""
    def gen(s):
        return cases.symbol(
            "gpt2_rotary", s, capacity=T, vocab_size=V, d_model=D,
            n_layer=L, n_head=H, rope_base=10000.0)
    sched = mx.serve.serve_decoder(
        gen(1), _args_nd(trained), name="cursor29", capacity=T,
        ladder=[1, 2, 4], clock=FakeClock(), start=False,
        symbol_gen=gen, prefill_chunk=4)
    eng = sched.engine
    backend = mx.telemetry.core.backend_compiles

    def counters():
        return {k: mx.telemetry.counter(f"serve.decode.{k}",
                                        model="cursor29").value
                for k in ("cursor.updates", "cursor.rows", "joins",
                          "prefill.chunks")}

    moved, targets, in_migrate = [], [], [0]
    for rung in eng.ladder:
        drv = eng.driver(rung)

        def rewind_many(rows, positions, inner=drv.rewind_many):
            if len(rows):
                moved.append(len(rows))
                targets.extend(int(p) for p in positions)
            inner(rows, positions)
        drv.rewind_many = rewind_many
    migrate = eng.migrate

    def counted_migrate(*args):
        before = backend()
        migrate(*args)
        in_migrate[0] += backend() - before
    eng.migrate = counted_migrate

    before, mark = counters(), backend()
    rs = np.random.RandomState(29)
    handles = [sched.submit(rs.randint(0, V, 6).tolist(),
                            max_new_tokens=3)]
    sched.pump()                                   # rung 1
    for n in (4, 2, 3):          # grow to 4, drain through 2 to 1
        handles += [sched.submit(rs.randint(0, V, 3 + 2 * i).tolist(),
                                 max_new_tokens=2 + i) for i in range(n)]
        sched.pump(max_iterations=3)
        handles.append(sched.submit(rs.randint(0, V, 9).tolist(),
                                    max_new_tokens=4))
        sched.pump()
    for h in handles:
        h.result(timeout=5)
    # nobody is rewound after a window: a row nobody owns goes back to
    # 0 where it stands too near the capacity, and that is all
    assert not any(targets)
    for rung in eng.ladder:             # a rollback of 1..rung rows
        for n in range(1, rung + 1):
            eng.driver(rung).rewind_many(list(range(n)), [0] * n)
    got = {k: v - before[k] for k, v in counters().items()}
    assert sched.stats()["migrations"] >= 4
    assert got["joins"] == len(handles) and got["prefill.chunks"] >= 10
    assert set(moved) >= {1, 2, 3}                 # rows moved varied
    assert got["cursor.updates"] == got["joins"] + len(moved)
    assert got["cursor.rows"] == got["joins"] + sum(moved)
    assert eng.compiles_since_warmup() == 0
    assert backend() - mark == in_migrate[0]
    assert eng.backend_compiles_since_warmup() == in_migrate[0]


# ================================= token ids cross to the host, not logits
_S31 = 4                              # the fixtures' prefill window


def _served(gen, name, ladder, seed, capacity=T, chunk=_S31):
    """``serve_decoder`` with a window program over ``gen(step_len)``'s
    graph, weights from one seed (scaled up so that the logits
    spread)."""
    from chipbench import weights
    shapes = {n: (1,) if n == "fed" else (1, 1)
              for n in ("data", "pos_ids", "fed")
              if n in gen(1).list_arguments()}
    params = {k: v if k.endswith(("_gamma", "_beta", "_bias")) else 12 * v
              for k, v in weights.normal_init(gen(1), shapes, seed).items()}
    return mx.serve.serve_decoder(
        gen(1), params, name=name, capacity=capacity, ladder=list(ladder),
        clock=FakeClock(), start=False, symbol_gen=gen,
        prefill_chunk=chunk, prefix_cache_mb=0)


def _sched31(kind, name, ladder):
    """``_served`` over ``_pool_symbol(kind)``."""
    return _served(lambda s: _pool_symbol(kind, s)[0], name, ladder, 31)


def _fetch_whole_logits(sched):
    """The reference loop: every dispatch's whole logits come to the
    host through ``drv.step(...).asnumpy()`` and ``sample_token`` is
    applied to ``[row, n - 1]`` (of a packed window's output, which is
    that row alone, to ``[row, 0]``), greedy and sampled requests
    alike."""
    from mxnet_tpu.serve.sampling import SamplingParams, sample_token
    greedy = SamplingParams()

    def launch(drv, tokens, phases, t=None, last=None, rows=False,
               fed=None, feed=None):
        logits = drv.step(tokens, fed=fed).asnumpy()
        picked = logits[np.arange(len(last)),
                        np.minimum(last, logits.shape[1] - 1)]
        ids = [sample_token(row, greedy, None) for row in picked]
        return (np.asarray(ids, np.int32), picked), t

    def fetch(drv, launched, phases, t, rows=False):
        return launched + (sched._clock.now(),)
    sched._launch, sched._fetch = launch, fetch
    # every token through the host: no dispatch is fed from the chip
    sched._plan_ahead = lambda d, now: None


def _counters31(name):
    return {k: mx.telemetry.counter(f"serve.decode.{k}", model=name).value
            for k in ("fetch.bytes", "sample.device", "sample.host",
                      "tokens", "iterations", "prefill.chunks")}


@pytest.mark.parametrize("kind", ["dense-learned", "rotary-routed"])
def test_token_streams_equal_host_sampling_of_whole_logits(kind):
    """ISSUE 31 (a): greedy and seeded temperature / top-k / top-p
    requests mixed in one batch, through S=1 iterations and window
    iterations whose rows feed fewer than S tokens (last chunks, slots
    decoding beside a prefill), across rung switches: the streams are
    byte for byte those of a scheduler that fetches the whole logits
    and samples every row on the host."""
    from mxnet_tpu.serve import SamplingParams
    policies = [None, SamplingParams(temperature=0.9, seed=5),
                SamplingParams(temperature=1.3, top_k=7, seed=6), None,
                SamplingParams(temperature=0.7, top_p=0.8, seed=7),
                SamplingParams(temperature=1.0, top_k=20, top_p=0.9,
                               seed=8), None]
    lengths = [3, 5, 6, 9, 2, 7, 10]

    def run(name, reference):
        sched = _sched31(kind, name, ladder=(1, 2, 4))
        if reference:
            _fetch_whole_logits(sched)
        rs = np.random.RandomState(31)
        before, hs = _counters31(name), []
        for i, (n, pol) in enumerate(zip(lengths, policies)):
            hs.append(sched.submit(rs.randint(1, V, n).tolist(),
                                   max_new_tokens=4 + i % 3,
                                   sampling=pol))
            sched.pump(max_iterations=1 + i % 3)   # arrivals staggered
        sched.pump()
        outs = [h.result(timeout=5).tobytes() for h in hs]
        got = {k: v - before[k] for k, v in _counters31(name).items()}
        return outs, got, sched

    want, _, _ = run(f"ids31-{kind}-ref", True)
    outs, got, sched = run(f"ids31-{kind}", False)
    assert outs == want
    assert got["sample.device"] > 0 and got["sample.host"] > 0
    assert got["sample.device"] + got["sample.host"] == got["tokens"]
    steps = [r for r in mx.telemetry.flightrec.get_records()
             if r.get("kind") == "serve.decode.step"
             and r.get("model") == f"ids31-{kind}"]
    assert {r["window"] for r in steps} == {1, _S31}
    # windows that fed some slot fewer than S tokens were among them
    fed_slots, real_rows = (
        mx.telemetry.counter(f"serve.decode.window.{k}",
                             model=f"ids31-{kind}").value
        for k in ("fed_slots", "real_rows"))
    assert 0 < real_rows < _S31 * fed_slots
    assert sched.stats()["migrations"] >= 1
    assert sched.engine.compiles_since_warmup() == 0


def test_select_rows_is_numpys_index_and_argmax():
    """ISSUE 31 (b): the rows are the bytes the host would have indexed
    and the ids are ``np.argmax`` of them: the first of tied maxima
    (signed zeros and infinities among them), the first NaN where a row
    holds one; one program per step length whatever the indices."""
    slots, S = 5, 3
    drv = _bound_pool("dense-learned", slots)
    rs = np.random.RandomState(2)
    out = rs.randn(slots, S, V).astype(np.float32)
    out[0, 1, [9, 40, 41]] = out[0, 1].max() + 1.0      # tied maxima
    out[1, 2, :] = 0.25                                 # a flat row
    out[2, 0, 7], out[2, 0, 33] = np.nan, np.nan        # NaN wins
    out[2, 0, 50] = np.inf
    out[3, 2, [3, 60]] = np.inf                         # tied infinities
    out[4, 1, :] = -np.inf
    out[4, 1, [12, 13]] = [-0.0, 0.0]                   # signed zeros tie
    idx = np.asarray([1, 2, 0, 2, 1])
    rows, ids, _tokens = drv.select_rows(mx.nd.array(out), idx)
    want = out[np.arange(slots), idx]
    assert rows.dtype == jnp.float32 and ids.dtype == jnp.int32
    assert np.asarray(rows).tobytes() == want.tobytes()
    np.testing.assert_array_equal(np.asarray(ids), np.argmax(want, -1))
    assert np.asarray(ids).tolist()[:3] == [9, 0, 7]
    for other in ([0, 0, 0, 0, 0], [2, 1, 2, 0, 0]):
        rows, ids, _tokens = drv.select_rows(mx.nd.array(out), other)
        want = out[np.arange(slots), other]
        assert np.asarray(rows).tobytes() == want.tobytes()
        np.testing.assert_array_equal(np.asarray(ids),
                                      np.argmax(want, -1))
    assert drv._select_programs[S]._cache_size() == 1
    assert drv._select_programs[S].__name__ == f"select_rows_{slots}x{S}"
    for bad in ([0, 0, 0, 0, S], [0, -1, 0, 0, 0], [0, 0]):
        with pytest.raises(mx.base.MXNetError, match="select_rows"):
            drv.select_rows(mx.nd.array(out), bad)


@pytest.mark.parametrize("kind", ["dense-learned", "rotary-routed"])
def test_fetch_bytes_and_no_compile_on_every_rung_and_window(kind):
    """ISSUE 31 (c, d): after warm-up no iteration on any rung and
    step length compiles, in the program cache or in the backend,
    whether rows are fetched or not. An iteration brings 4 bytes a slot
    to the host (+ 16 a layer of ``moe_stats``), and the selected rows
    besides, 4 x V a slot, only when a slot that samples in it is not
    greedy; every token is counted as sampled from ids or from rows."""
    from mxnet_tpu.serve import SamplingParams
    # rungs whose windows are not packed (3 x 4 rows under twice the
    # budget of 8): every slot is fed its chunk in the same window
    for rung in (1, 2, 3):
        name = f"bytes31-{kind}-{rung}"
        sched = _sched31(kind, name, ladder=[rung])   # nothing migrates
        drv = sched.engine.driver(rung)
        assert sorted(drv._select_programs) == [1, _S31]
        ids_bytes = 4 * rung + (16 * 2 if kind == "rotary-routed" else 0)
        rows_bytes = 4 * rung * V
        mark = mx.program_cache.compile_count()
        backend = mx.telemetry.core.backend_compiles()
        rs = np.random.RandomState(rung)
        # prompts of 6 = a full window and a window of 2 rows; the
        # last request draws with a temperature, the others are greedy
        hs = [sched.submit(rs.randint(1, V, 6).tolist(), max_new_tokens=3,
                           sampling=SamplingParams(temperature=0.8, seed=i)
                           if i == rung - 1 else None)
              for i in range(rung)]
        seen = []
        while not all(h.done() for h in hs):
            before = _counters31(name)
            assert sched.pump(max_iterations=1) == 1
            seen.append({k: v - before[k]
                         for k, v in _counters31(name).items()})
        # window (nobody samples), window of 2 (all sample), 2 x S=1
        assert [d["fetch.bytes"] for d in seen] == \
            [ids_bytes] + [ids_bytes + rows_bytes] * 3
        assert [d["sample.host"] for d in seen] == [0, 1, 1, 1]
        assert [d["sample.device"] for d in seen] == [0] + [rung - 1] * 3
        # an all-greedy batch never fetches rows, at either step length
        before = _counters31(name)
        hs = [sched.submit(rs.randint(1, V, 6).tolist(), max_new_tokens=3)
              for _ in range(rung)]
        assert sched.pump() == 4
        got = {k: v - before[k] for k, v in _counters31(name).items()}
        assert got["fetch.bytes"] == 4 * ids_bytes
        assert got["sample.host"] == 0
        assert got["sample.device"] == got["tokens"] == 3 * rung
        assert mx.program_cache.compile_count() == mark
        assert mx.telemetry.core.backend_compiles() == backend
        assert sched.engine.backend_compiles_since_warmup() == 0
        assert all(prog._cache_size() == 1
                   for prog in drv._select_programs.values())


# ========================================== scheduler (FakeClock path)
def test_scheduler_staggered_arrivals_deterministic(trained):
    """Acceptance: FakeClock-scripted staggered arrivals/finishes —
    batched greedy outputs match N independent KVCacheDecoder runs,
    and a rerun of the same script is bit-identical."""
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, V, 2 + i % 3).tolist() for i in range(6)]
    lens = [3 + i % 4 for i in range(6)]

    def run():
        clock = FakeClock()
        sched = _sched(trained, ladder=[1, 2, 4], clock=clock)
        outs = [None] * 6
        hs = []
        for i, p in enumerate(prompts):
            hs.append(sched.submit(p, max_new_tokens=lens[i]))
            sched.pump(max_iterations=1 + i % 2)   # staggered progress
            clock.advance(0.001)
        sched.pump()
        for i, h in enumerate(hs):
            outs[i] = list(h.result(timeout=5))
            assert h.finish_reason == "length"
        # stats snapshot NOW: compile_count is process-global, and the
        # reference decoders bound below compile their own programs
        return outs, sched.stats()

    outs, st = run()
    assert st["responses"] == 6 and st["errors"] == 0
    assert st["compiles_since_warmup"] == 0
    for i, p in enumerate(prompts):
        assert outs[i] == _ref_greedy(trained, p, lens[i]), i
    outs2, st2 = run()
    assert outs2 == outs                       # deterministic replay
    assert st2["responses"] == 6 and st2["compiles_since_warmup"] == 0


def test_zero_compiles_across_join_leave_every_rung(trained):
    """Acceptance: compile_count() delta == 0 after warmup across
    arbitrary join/leave on every slot rung, including the rung
    migrations the churn forces."""
    sched = _sched(trained, ladder=[1, 2, 4])
    assert sched.engine.warmup_compiles >= 3      # one per rung
    mark = mx.program_cache.compile_count()
    rs = np.random.RandomState(5)
    # wave 1: single sequence (rung 1)
    h = sched.submit(rs.randint(0, V, 2).tolist(), max_new_tokens=2)
    sched.pump()
    # wave 2: four at once (grow 1 -> 4), retire down through 2 -> 1
    hs = [sched.submit(rs.randint(0, V, 2).tolist(),
                       max_new_tokens=2 + i) for i in range(4)]
    sched.pump()
    # wave 3: churn — overlapping arrivals while others finish
    for i in range(5):
        hs.append(sched.submit(rs.randint(0, V, 2).tolist(),
                               max_new_tokens=3))
        sched.pump(max_iterations=2)
    sched.pump()
    for hh in [h] + hs:
        hh.result(timeout=5)
    assert mx.program_cache.compile_count() - mark == 0
    assert sched.engine.compiles_since_warmup() == 0
    assert sched.stats()["migrations"] >= 2
    assert sched.engine.programs_resident()
    # every rung's program stayed pinned
    assert len(sched.engine.program_keys()) == 3


def test_scheduler_overflow_fails_alone(trained):
    """Satellite: a sequence overflowing its slot's cache slice errors
    ALONE — its batchmates' outputs are unaffected."""
    sched = _sched(trained, ladder=[2])
    rs = np.random.RandomState(6)
    long_prompt = rs.randint(0, V, T).tolist()     # fills capacity
    ok_prompt = rs.randint(0, V, 3).tolist()
    h_over = sched.submit(long_prompt, max_new_tokens=8)
    h_ok = sched.submit(ok_prompt, max_new_tokens=4)
    sched.pump()
    with pytest.raises(mx.base.MXNetError, match="overflow"):
        h_over.result(timeout=5)
    st = sched.stats()
    assert st["errors"] == 1 and st["responses"] == 1
    assert list(h_ok.result(timeout=5)) == _ref_greedy(
        trained, ok_prompt, 4)


def test_scheduler_streaming_eos_and_limits(trained):
    """Streaming callbacks fire in order (late subscribers replay);
    EOS retires without emitting; max_new_tokens caps length; submit
    validation rejects bad prompts; the queue bound rejects with
    QueueFullError."""
    sched = _sched(trained, ladder=[1, 2], max_queue=3)
    rs = np.random.RandomState(7)
    prompt = rs.randint(0, V, 3).tolist()
    ref = _ref_greedy(trained, prompt, 4)

    seen = []
    h = sched.submit(prompt, max_new_tokens=4)
    h.add_token_callback(lambda hh, tok, i: seen.append((i, tok)))
    sched.pump()
    assert [t for _, t in sorted(seen)] == list(h.result()) == ref
    assert h.finish_reason == "length" and h.latency is not None
    late = []
    h.add_token_callback(lambda hh, tok, i: late.append(tok))
    assert late == ref                         # replay on registration

    # EOS: use the first greedy token as the eos id -> zero emitted
    h2 = sched.submit(prompt, max_new_tokens=8, eos_id=ref[0])
    sched.pump()
    assert list(h2.result()) == [] and h2.finish_reason == "eos"

    with pytest.raises(mx.base.MXNetError, match="empty"):
        sched.submit([])
    with pytest.raises(mx.base.MXNetError, match="capacity"):
        sched.submit(list(range(T + 1)))
    with pytest.raises(mx.base.MXNetError, match="max_new_tokens"):
        sched.submit(prompt, max_new_tokens=0)

    for _ in range(3):
        sched.submit(prompt, max_new_tokens=2)
    with pytest.raises(QueueFullError):
        sched.submit(prompt, max_new_tokens=2)
    sched.pump()


def test_scheduler_deadline_retires_partial(trained):
    """A deadline passing mid-decode retires the sequence with its
    partial output and finish_reason='deadline' (the iteration-level
    analog of the server's deadline flush)."""
    clock = FakeClock()
    sched = _sched(trained, ladder=[1], clock=clock)
    prompt = [1, 2]
    h = sched.submit(prompt, max_new_tokens=50, deadline_ms=100)
    sched.pump(max_iterations=4)               # 3 emitted (2 prefill-1)
    emitted = len(h.tokens)
    assert emitted >= 1 and not h.done()
    clock.advance(0.2)                         # past the deadline
    sched.pump()
    assert h.done() and h.finish_reason == "deadline"
    assert list(h.result()) == h.tokens and len(h.tokens) == emitted
    assert h.missed_deadline()
    # a queued request past its deadline completes empty, never runs
    h2 = sched.submit(prompt, max_new_tokens=4, deadline_ms=1)
    clock.advance(1.0)
    sched.pump()
    assert h2.done() and h2.finish_reason == "deadline"
    assert list(h2.result()) == []


def test_scheduler_traces_and_telemetry(trained):
    """Per-sequence session traces survive batching: each sequence
    keeps its own tree under its root, iterations share ONE step span
    id across batchmates, and the occupancy/counter surface is live."""
    mx.telemetry.reset()
    from mxnet_tpu.telemetry import trace as _trace
    _trace.clear()
    _trace.configure(sample=1)
    try:
        sched = _sched(trained, ladder=[2])
        rs = np.random.RandomState(8)
        h1 = sched.submit(rs.randint(0, V, 2).tolist(), max_new_tokens=3)
        h2 = sched.submit(rs.randint(0, V, 2).tolist(), max_new_tokens=3)
        sched.pump()
        h1.result(timeout=5), h2.result(timeout=5)
        assert h1.trace_id and h2.trace_id
        assert h1.trace_id != h2.trace_id
        t1 = {s["name"]: s for s in _trace.spans(h1.trace_id)}
        assert "serve.decode.sequence" in t1
        s1 = [s for s in _trace.spans(h1.trace_id)
              if s["name"] == "serve.decode.step"]
        s2 = [s for s in _trace.spans(h2.trace_id)
              if s["name"] == "serve.decode.step"]
        shared = {s["span"] for s in s1} & {s["span"] for s in s2}
        assert shared, "batchmates share the iteration step span id"
        st = sched.stats()
        assert st["tokens"] == 6 and st["joins"] == 2
        g = mx.telemetry.get_metric("serve.decode.occupancy",
                                    model=sched.engine.name)
        assert g is not None
        kinds = [r.get("kind")
                 for r in mx.telemetry.flightrec.get_records()]
        assert "serve.decode.step" in kinds
    finally:
        _trace.configure(sample=_trace._env_sample(), reset_ids=False)


def test_slot_ladder_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_DECODE_SLOTS", "2, 8,4")
    assert mx.serve.default_slot_ladder() == [2, 4, 8]
    monkeypatch.setenv("MXNET_SERVE_DECODE_SLOTS", "zero")
    with pytest.raises(mx.base.MXNetError):
        mx.serve.default_slot_ladder()
    monkeypatch.delenv("MXNET_SERVE_DECODE_SLOTS")
    assert mx.serve.default_slot_ladder() == [1, 4, 8]


def test_scheduler_thread_drive_mode(trained):
    """The real-clock dispatch thread serves submits end to end (the
    production drive mode; the benchmark's serving cells use it)."""
    sched = _sched(trained, ladder=[1, 2],
                   clock=mx.serve.MonotonicClock())
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, V, 2).tolist() for _ in range(3)]
    with sched:
        hs = [sched.submit(p, max_new_tokens=3) for p in prompts]
        outs = [list(h.result(timeout=60)) for h in hs]
    assert sched.stats()["compiles_since_warmup"] == 0
    for p, o in zip(prompts, outs):
        assert o == _ref_greedy(trained, p, 3)


def test_stop_without_drain_fails_pending(trained):
    sched = _sched(trained, ladder=[1])
    h = sched.submit([1, 2], max_new_tokens=4)
    sched.stop(drain=False)
    with pytest.raises(mx.base.MXNetError, match="stopped"):
        h.result(timeout=1)


# =========================================== export / memplan satellites
def test_slot_pooled_export_artifact(trained, tmp_path):
    """Satellite: a per-slot decode graph exports as a slot-pooled
    stateful artifact — the Predictor carries the pooled cache, matches
    the module driver step for step, and Predictor.reset_slot rewinds
    ONE slot without disturbing its batchmates."""
    slots = 3
    path = str(tmp_path / "lm_slots.mxp")
    mx.export_model(
        path,
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=T, per_slot=True,
                              max_seq_len=T),
        _args_nd(trained), {}, {"data": (slots, 1), "fed": (slots,)},
        data_dtypes={"data": np.int32, "fed": np.int32})
    p = mx.Predictor(path)
    assert p.stateful
    fed = np.ones(slots, np.int32)          # a token a slot and step

    dec = _pooled_module(trained, slots)
    drv = tfm.BatchedKVCacheDecoder(dec, capacity=T)
    for s in range(slots):
        drv.join(s)
    rs = np.random.RandomState(10)
    toks = rs.randint(0, V, (slots, 6)).astype(np.int32)
    for t in range(4):
        ref = drv.step(toks[:, t:t + 1]).asnumpy()
        got = p.forward(data=toks[:, t:t + 1], fed=fed)[0].asnumpy()
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6)

    # reset slot 1 only: slot 1 restarts from position 0 while slots
    # 0/2 keep their in-flight state — matched by the module driver
    p.reset_slot(1)
    drv.leave(1)
    drv.join(1)
    step5 = toks[:, 4:5].copy()
    ref = drv.step(step5).asnumpy()
    got = p.forward(data=step5, fed=fed)[0].asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6)


def test_memplan_slot_pool_kv_bytes_and_me801(trained):
    """Satellite: the planner charges the slot-pooled KV cache per
    rung under attention_decode — slots x layers x 2 caches + the
    (slots, 1) int32 cursor — and ME801 trips at a toy capacity x slot
    count."""
    from mxnet_tpu.analysis import memplan
    slots, cap = 8, 32
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=cap, per_slot=True,
                                max_seq_len=cap)
    plan = memplan.plan_symbol(sym, {"data": (slots, 1)}, policy="none",
                               for_training=False)
    expect = L * (2 * slots * H * cap * (D // H) * 4 + slots * 1 * 4)
    assert plan["kv_cache_bytes"] == expect
    assert plan["per_op_bytes"].get("attention_decode") == expect
    assert plan["aux_bytes"] >= expect
    # the pool scales linearly with the slot rung
    plan1 = memplan.plan_symbol(
        tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                              n_head=H, capacity=cap, per_slot=True,
                              max_seq_len=cap),
        {"data": (1, 1)}, policy="none", for_training=False)
    assert plan["kv_cache_bytes"] == slots * plan1["kv_cache_bytes"]
    # ME801 at a toy capacity x slot count
    found = memplan.plan_findings(plan, capacity_bytes=expect // 2)
    assert any(d.rule == "ME801" for d in found)


def test_scalar_decode_unchanged(trained):
    """Regression: the scalar (single-session) decode path is
    untouched — same cursor shape, same outputs as ever."""
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=T)
    _args, _outs, auxs = sym.infer_shape(data=(2, 1))
    by_name = dict(zip(sym.list_auxiliary_states(), auxs))
    assert {s for n, s in by_name.items()
            if n.endswith("cache_pos")} == {(1,)}
    d = _scalar_decoder(trained)
    out = d.step(np.asarray([[1]], np.int32))
    assert out.shape == (1, 1, V)


# ------------------------------------------------------------------------
# a step's launch is one jitted call (ISSUE 39): the driver puts a step's
# inputs once, where and as wide as its module's cells want them, and a
# decode graph draws no key
# ------------------------------------------------------------------------
_LAUNCH_S = 4                                    # the window program


#: a ``gpt2`` block with learned positions beside tokens and ``fed``,
#: and EVA attention's (rotary: tokens and ``fed``): rows of the table
_LAUNCH = {"learned": ("gpt2", dict(capacity=T, vocab_size=V, d_model=D,
                                    n_layer=L, n_head=H, max_seq_len=T)),
           "fed": ("evabyte", dict(capacity=64, n_layer=1, n_pred_heads=1))}


def _launch_symbol(kind, step_len):
    case, over = _LAUNCH[kind]
    return cases.symbol(case, step_len, **over)


def _launch_driver(kind, slots=2):
    """A two-slot pool with its S=4 window program, bound as
    ``DecodeEngine`` binds (tokens and ``fed`` int32, learned positions
    float32)."""
    case, over = _LAUNCH[kind]
    return cases.driver(case, packed=False, slots=slots, window=_LAUNCH_S,
                        **over)


def _launch_schedule(drv):
    """Two joins, S=1 steps, a window (padded: one slot feeds 2 of its
    4), a rewind, S=1 steps again: every step's logits."""
    for slot in range(drv.slots):
        if drv.active[slot]:
            drv.leave(slot)
        drv.join(slot)
    rs = np.random.RandomState(8)
    outs = []

    def step(S, fed=None):
        tokens = rs.randint(1, 40, (drv.slots, S))
        outs.append(drv.step(tokens, fed=fed).asnumpy())

    for _ in range(3):
        step(1)
    step(_LAUNCH_S, fed=[_LAUNCH_S, 2])  # the window's two pads: not fed
    drv.rewind(0, 6)                     # one real token taken back
    for _ in range(2):
        step(1)
    return outs


def _launch_counts():
    from mxnet_tpu import telemetry as tm
    out = []
    for nm in ("io.load_batch.aliased", "io.load_batch.puts",
               "executor.rng.draws"):
        m = tm.get_metric(nm)
        out.append(0 if m is None else m.value)
    return out


@pytest.mark.parametrize("kind", ["learned", "fed"])
def test_a_decode_steps_launch_puts_nothing_and_draws_no_key(kind):
    """After warm-up, over a join, S=1 steps, a window and a rewind:
    ``io.load_batch.puts`` and ``executor.rng.draws`` stay 0,
    ``io.load_batch.aliased`` counts inputs x steps, ``mx.random``'s
    chain stays where it was, and the logits are bit for bit those of a
    driver whose inputs arrive as numpy through ``_load_batch``'s
    convert-and-put branch."""
    from mxnet_tpu import telemetry as tm
    drv = _launch_driver(kind)
    _launch_schedule(drv)                        # warm-up
    chain = mx.random.get_state()["key"]
    tm.enable()
    try:
        before = _launch_counts()
        got = _launch_schedule(drv)
        aliased, puts, draws = (
            a - b for a, b in zip(_launch_counts(), before))
    finally:
        tm.disable()
    assert (puts, draws) == (0, 0)
    n_inputs = 3 if kind == "learned" else 2     # tokens, positions, fed
    assert aliased == n_inputs * len(got)        # every input, six steps
    after = mx.random.get_state()["key"]
    assert chain is after or np.array_equal(chain, after)

    old = _launch_driver(kind)
    old._stagers = {S: list for S in old._stagers}    # numpy, as handed
    tm.enable()
    try:
        before = _launch_counts()
        want = _launch_schedule(old)
        aliased, puts, draws = (
            a - b for a, b in zip(_launch_counts(), before))
    finally:
        tm.disable()
    assert (aliased, puts, draws) == (0, n_inputs * len(want), 0)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert np.abs(got[-1]).max() > 0


# ============ ISSUE 46: the next S=1 step is launched before the last
# one's ids reach the host
_S46 = 4                              # the fixtures' prefill window
_T46 = {"gpt2": T, "fed": 64, "routed": T}
_V46 = {"gpt2": V, "fed": 40, "routed": V}


def _symbol46(kind, step_len=1):
    """A ``gpt2`` block with learned positions, EVA attention's graph
    (``"fed"``: the first that was), or the routed block of
    ``_pool_symbol``."""
    if kind == "fed":
        return _launch_symbol("fed", step_len)
    return _pool_symbol({"gpt2": "dense-learned",
                         "routed": "rotary-routed"}[kind], step_len)[0]


_SCHEDS46 = {}


def _sched46(kind, order, ladder=(4,)):
    """One scheduler a ``(kind, order, ladder)``, built once: ``"ahead"`` is the
    scheduler as it is, ``"sync"`` the same with every dispatch planned
    after its predecessor's commit, ``"plain"`` a pool of one for the
    plain loop. All of a kind serve the same weights."""
    key = (kind, order, tuple(ladder))
    if key in _SCHEDS46:
        return _SCHEDS46[key]
    if order != "plain":
        # nothing may compile behind a scheduler's warm-up mark but what
        # it serves with: the plain loop's pool is bound first
        _sched46(kind, "plain")
    sched = _served(lambda s: _symbol46(kind, s),
                    f"ahead46-{kind}-{order}-{max(ladder)}",
                    [1] if order == "plain" else ladder, 46,
                    capacity=_T46[kind], chunk=_S46)
    if order == "sync":
        sched._plan_ahead = lambda d, now: None
    _SCHEDS46[key] = sched
    return sched


def _plain_greedy(kind, prompt, max_new, eos_id=None):
    """A plain greedy loop over ``drv.step`` with host tokens: one
    sequence in a pool of one, a token a step, every id through
    ``np.argmax`` on the host."""
    drv = _sched46(kind, "plain").engine.driver(1)
    drv.join(0)
    fed = [1]
    for t in prompt[:-1]:
        drv.step(np.asarray([[t]], np.int32), fed=fed)
    cur, out = int(prompt[-1]), []
    for _ in range(max_new):
        cur = int(np.argmax(drv.step(np.asarray([[cur]], np.int32),
                                     fed=fed).asnumpy()[0, 0]))
        if cur == eos_id:
            break
        out.append(cur)
    drv.leave(0)
    return out


def _count46(sched, key):
    return mx.telemetry.counter(f"serve.decode.{key}",
                                model=sched.engine.name).value


def _steps46(sched, since=0):
    """The scheduler's ring records from iteration ``since`` on."""
    return [r for r in mx.telemetry.flightrec.get_records()
            if r.get("kind") == "serve.decode.step"
            and r.get("model") == sched.engine.name and r["iter"] >= since]


def _prompts46(kind, seed, n, lo=3, hi=7):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, _V46[kind], rs.randint(lo, hi)).tolist()
            for _ in range(n)]


@pytest.mark.parametrize("kind", ["gpt2", "fed", "routed"])
def test_a_long_s1_run_is_fed_from_the_chip(kind):
    """ISSUE 46 (a): three greedy requests decode side by side; every
    S=1 dispatch but the first after the windows is launched before its
    predecessor's ids are on the host, and the tokens are those of the
    plain loop and of the synchronous order. One ring record a
    dispatch, ``ahead`` set on those launched so; nothing compiles."""
    mx.telemetry.flightrec.configure(capacity=4096)
    prompts = _prompts46(kind, 1, 3)
    outs = {}
    for order in ("ahead", "sync"):
        sched = _sched46(kind, order)
        n_rec, before = sched.iterations, {
            k: _count46(sched, k) for k in
            ("runahead.launched", "runahead.dropped", "iterations",
             "runahead.windows", "window.dispatches")}
        hs = [sched.submit(p, max_new_tokens=9) for p in prompts]
        sched.pump()
        outs[order] = [h.result(timeout=5).tolist() for h in hs]
        got = {k: _count46(sched, k) - v for k, v in before.items()}
        recs = _steps46(sched, n_rec)
        assert len(recs) == got["iterations"]
        assert [r["iter"] for r in recs] == sorted(r["iter"] for r in recs)
        assert sum(r["ahead"] for r in recs) == got["runahead.launched"]
        # a window runs ahead like an S=1 step (ISSUE 53), and is
        # counted beside every window there was
        windows = [r for r in recs if r["window"] > 1]
        assert len(windows) == got["window.dispatches"] > 0
        assert sum(r["ahead"] for r in windows) == got["runahead.windows"]
        assert got["runahead.dropped"] == 0
        if order == "sync":
            assert got["runahead.launched"] == 0
        else:
            # every S=1 dispatch is launched ahead but one whose
            # predecessor finishes somebody (by length: known ahead)
            s1 = [r for r in recs if r["window"] == 1]
            assert len(s1) >= sum(r["ahead"] for r in s1) >= len(s1) - 2 > 4
            assert sched.stats()["runahead"]["launched"] == \
                _count46(sched, "runahead.launched")
        assert sched.engine.compiles_since_warmup() == 0
        assert sched.engine.backend_compiles_since_warmup() == 0
    assert outs["ahead"] == outs["sync"]
    assert outs["ahead"] == [_plain_greedy(kind, p, 9) for p in prompts]


@pytest.mark.parametrize("kind", ["gpt2", "fed"])
def test_a_finish_by_length_is_known_ahead_and_the_caller_resubmits(kind):
    """ISSUE 46 (b): a request finishes by length in mid-run and its
    done callback submits the next one (a closed loop): the dispatch
    that finishes it has nothing launched behind it, the newcomer's
    window is planned after the commit, and every request's tokens are
    the plain loop's."""
    prompts = _prompts46(kind, 2, 4)
    lens = [3, 11, 7, 5]
    sched = _sched46(kind, "ahead", ladder=(2,))
    dropped = _count46(sched, "runahead.dropped")
    launched = _count46(sched, "runahead.launched")
    handles = {}

    def send(i):
        h = handles[i] = sched.submit(prompts[i], max_new_tokens=lens[i])
        if i + 2 < len(prompts):
            h.add_done_callback(lambda _h, i=i: send(i + 2))

    send(0), send(1)
    sched.pump()
    assert len(handles) == 4
    for i, h in handles.items():
        assert h.finish_reason == "length"
        assert h.result(timeout=5).tolist() == \
            _plain_greedy(kind, prompts[i], lens[i])
    assert _count46(sched, "runahead.dropped") == dropped
    assert _count46(sched, "runahead.launched") > launched
    assert sched.engine.compiles_since_warmup() == 0


@pytest.mark.parametrize("kind", ["gpt2", "fed"])
def test_an_eos_drops_the_token_computed_past_it(kind):
    """ISSUE 46 (c): the host cannot know an EOS ahead. The slot that
    hits ``eos_id`` at dispatch n has had a token computed at n+1: it
    is dropped and counted, the neighbour's stream is untouched, and
    the next request in that slot decodes clean."""
    # a prompt whose stream shows, some tokens in, an id it has not
    # shown before: nothing ends earlier
    for seed in range(3, 40):
        prompts = _prompts46(kind, seed, 3)
        free = _plain_greedy(kind, prompts[0], 9)
        k = next((i for i in range(3, 9) if free[i] not in free[:i]), None)
        if k is not None:
            break
    eos = free[k]
    sched = _sched46(kind, "ahead", ladder=(2,))
    dropped = _count46(sched, "runahead.dropped")
    h0 = sched.submit(prompts[0], max_new_tokens=9, eos_id=eos)
    h1 = sched.submit(prompts[1], max_new_tokens=10)
    after = []
    h0.add_done_callback(lambda _h: after.append(
        sched.submit(prompts[2], max_new_tokens=6)))
    sched.pump()
    assert h0.finish_reason == "eos"
    assert h0.result(timeout=5).tolist() == free[:k]
    assert _count46(sched, "runahead.dropped") == dropped + 1
    assert sched.stats()["runahead"]["dropped"] == dropped + 1
    assert h1.result(timeout=5).tolist() == \
        _plain_greedy(kind, prompts[1], 10)
    assert after[0].result(timeout=5).tolist() == \
        _plain_greedy(kind, prompts[2], 6)


@pytest.mark.parametrize("kind", ["gpt2", "fed"])
def test_nothing_runs_ahead_beside_a_slot_that_samples_on_the_host(kind):
    """ISSUE 46 (d): one request that is not greedy among greedy ones:
    nothing is launched behind a dispatch at which it samples (its row
    has to be on the host first) and stays; behind one in which it
    still prefills, or samples its last token by length, the next is
    (ISSUE 53), and once it has left every one is."""
    from mxnet_tpu.serve import SamplingParams
    prompts = _prompts46(kind, 4, 2)
    sched = _sched46(kind, "ahead", ladder=(2,))
    launched = _count46(sched, "runahead.launched")
    hot = sched.submit(prompts[0], max_new_tokens=4,
                       sampling=SamplingParams(temperature=0.8, seed=3))
    cold = sched.submit(prompts[1], max_new_tokens=10)
    quiet = 0
    while not hot.done():
        had = len(hot.tokens)
        sched.pump(max_iterations=1)
        assert sched._ahead is None or len(hot.tokens) == had or hot.done()
        quiet += sched._ahead is not None
    before_left = _count46(sched, "runahead.launched")
    assert before_left == launched + quiet
    sched.pump()
    assert _count46(sched, "runahead.launched") > before_left
    assert cold.result(timeout=5).tolist() == \
        _plain_greedy(kind, prompts[1], 10)
    assert len(hot.result(timeout=5)) == 4


@pytest.mark.parametrize("kind", ["gpt2", "fed"])
def test_a_submit_while_a_dispatch_is_in_flight(kind):
    """ISSUE 46 (e): a request arrives while a dispatch launched ahead
    is on the chip: that dispatch commits first and feeds the newcomer
    nothing; the plan behind it admits the newcomer into the free slot
    (ISSUE 53: while that dispatch is still on the chip, the join
    behind it on the device) and its first window is launched ahead
    too; nobody's tokens change."""
    prompts = _prompts46(kind, 5, 2)
    sched = _sched46(kind, "ahead", ladder=(2,))
    first = sched.submit(prompts[0], max_new_tokens=10)
    while sched._ahead is None:
        assert sched.pump(max_iterations=1) == 1
    joins = _count46(sched, "joins")
    in_flight = sched._ahead
    late = sched.submit(prompts[1], max_new_tokens=8)
    assert late.request not in [seq for _row, seq, _n in in_flight.meta]
    assert sched.pump(max_iterations=1) == 1     # commits what was ahead
    assert _count46(sched, "joins") == joins + 1
    assert late.request in [seq for _row, seq, _n in sched._ahead.meta]
    assert not late.tokens
    sched.pump()
    assert _count46(sched, "joins") == joins + 1
    assert first.result(timeout=5).tolist() == \
        _plain_greedy(kind, prompts[0], 10)
    assert late.result(timeout=5).tolist() == \
        _plain_greedy(kind, prompts[1], 8)


@pytest.mark.parametrize("room", ["fits", "overflows"])
@pytest.mark.parametrize("kind", ["gpt2", "fed"])
def test_a_slot_one_position_short_of_capacity(kind, room):
    """ISSUE 46 (f): a request whose last token is fed at the last
    position of its cache finishes by length with the plain loop's
    tokens; one token more and it fails alone where the synchronous
    order fails it, its neighbour untouched."""
    cap = _T46[kind]
    prompts = _prompts46(kind, 6, 2, lo=5, hi=6)
    n = cap - len(prompts[0]) + (1 if room == "fits" else 2)
    outs = {}
    for order in ("ahead", "sync"):
        sched = _sched46(kind, order, ladder=(2,))
        long = sched.submit(prompts[0], max_new_tokens=n)
        short = sched.submit(prompts[1], max_new_tokens=6)
        sched.pump()
        assert short.result(timeout=5).tolist() == \
            _plain_greedy(kind, prompts[1], 6)
        if room == "fits":
            outs[order] = long.result(timeout=5).tolist()
        else:
            with pytest.raises(mx.base.MXNetError, match="overflowed"):
                long.result(timeout=5)
            outs[order] = long.tokens
            assert len(long.tokens) == n - 1
    assert outs["ahead"] == outs["sync"]
    if room == "fits":
        assert outs["ahead"] == _plain_greedy(kind, prompts[0], n)


def test_the_routed_counters_follow_their_dispatch():
    """ISSUE 46 (g): ``moe_stats`` belongs to the dispatch that wrote
    it. With the next step launched before it is read (and every aux
    array taken over by that step), the ``serve.decode.moe.*`` counters
    and the ring's fields equal the synchronous order's, with slots
    free beside the busy ones."""
    mx.telemetry.flightrec.configure(capacity=4096)
    prompts = _prompts46("routed", 7, 3)
    got = {}
    for order in ("ahead", "sync"):
        sched = _sched46("routed", order)
        keys = sorted(k for k, _f in sched.engine.driver(4)
                      .read_counts.values() if k)
        assert any(k.startswith("moe.") for k in keys)
        before = {k: _count46(sched, k) for k in keys}
        n_rec = sched.iterations
        hs = [sched.submit(p, max_new_tokens=8) for p in prompts]
        sched.pump()
        fields = sorted(f for _k, f in sched.engine.driver(4)
                        .read_counts.values() if f)
        got[order] = (
            [h.result(timeout=5).tolist() for h in hs],
            {k: _count46(sched, k) - v for k, v in before.items()},
            [[r.get(f) for f in fields] for r in _steps46(sched, n_rec)])
    assert got["ahead"] == got["sync"]
    assert got["ahead"][1]["moe.assignments"] > 0


# ============ ISSUE 53: a window is launched before its predecessor's
# ids reach the host
_KEYS53 = ("runahead.launched", "runahead.windows", "runahead.dropped",
           "window.dispatches", "iterations", "joins")


def _orders53(kind, script, ladder=(4,)):
    """``script(sched)`` (submits, pumps to the end, returns the
    handles) through the scheduler as it is and through the synchronous
    one: ``{order: (each handle's tokens and finish reason, [(window,
    fed, ahead)] of every dispatch, the counters' increase)}``.
    Nothing may compile."""
    mx.telemetry.flightrec.configure(capacity=4096)
    out = {}
    # the count of compiles is the process's: both are built before
    # either's is read, and read as an increase (a scheduler of another
    # test, built since this one warmed, has compiled its own)
    scheds = {order: _sched46(kind, order, ladder)
              for order in ("ahead", "sync")}
    for order, sched in scheds.items():
        drv = sched.engine.driver(max(ladder))
        feds, step = [], drv.step
        compiled = (sched.engine.compiles_since_warmup(),
                    sched.engine.backend_compiles_since_warmup())

        def spy(tokens, fed=None, now=None):
            feds.append(None if fed is None else [int(n) for n in fed])
            return step(tokens, fed=fed, now=now)

        before = {k: _count46(sched, k) for k in _KEYS53}
        n_rec = sched.iterations
        drv.step = spy
        try:
            handles = script(sched)
        finally:
            del drv.step
        assert sched._ahead is None and not sched._active()
        recs = _steps46(sched, n_rec)
        assert len(recs) == len(feds)
        out[order] = (
            [(h.tokens, h.finish_reason) for h in handles],
            [(r["window"], fed, r["ahead"]) for r, fed in zip(recs, feds)],
            {k: _count46(sched, k) - v for k, v in before.items()})
        assert compiled == (sched.engine.compiles_since_warmup(),
                            sched.engine.backend_compiles_since_warmup())
    sync = out["sync"][2]
    assert sync["runahead.launched"] == sync["runahead.windows"] == 0
    return out["ahead"], out["sync"]


def _same_dispatches53(ahead, sync):
    """The same dispatches in the same order, each feeding every slot
    what the synchronous order feeds it: only when they were launched
    differs."""
    assert [(w, fed) for w, fed, _a in ahead[1]] == \
        [(w, fed) for w, fed, _a in sync[1]]
    assert ahead[2]["iterations"] == sync[2]["iterations"]
    assert ahead[2]["window.dispatches"] == sync[2]["window.dispatches"]


def _prompt53(kind, seed, n):
    return np.random.RandomState(seed).randint(1, _V46[kind], n).tolist()


@pytest.mark.parametrize("kind", ["fed", "routed"])
def test_windows_follow_windows_without_the_host(kind):
    """ISSUE 53 (a): four prompts of one to three chunks, admitted
    together and planned inside the budget of 8 rows: every window but
    the first is launched while its predecessor is on the chip - slots
    in mid-prompt fed by the host, slots that sampled there by the
    chip - and so is the S=1 step behind the last. The dispatches and
    the tokens are the synchronous order's, and the plain loop's."""
    prompts = [_prompt53(kind, 53 + i, n) for i, n in enumerate((11, 9, 6, 3))]

    def script(sched):
        assert sched.engine.window_budget(4, _S46) == 8
        hs = [sched.submit(p, max_new_tokens=4) for p in prompts]
        sched.pump()
        return hs

    ahead, sync = _orders53(kind, script)
    assert ahead[0] == sync[0]
    _same_dispatches53(ahead, sync)
    assert [t for t, _why in ahead[0]] == \
        [_plain_greedy(kind, p, 4) for p in prompts]
    windows = [a for w, _fed, a in ahead[1] if w > 1]
    assert len(windows) >= 4 and windows == [0] + [1] * (len(windows) - 1)
    assert ahead[2]["runahead.windows"] == len(windows) - 1
    # a window fed two prefilling slots, and one fed a chunk beside
    # riders, ran ahead
    fed_ahead = [fed for w, fed, a in ahead[1] if w > 1 and a]
    assert any(sorted(fed)[-2] > 1 for fed in fed_ahead)
    assert any(1 in fed and max(fed) > 1 for fed in fed_ahead)
    # the first S=1 step lies behind a window, and runs ahead too
    first = next(i for i, (w, _f, _a) in enumerate(ahead[1]) if w == 1)
    assert ahead[1][first - 1][0] == _S46 and ahead[1][first][2] == 1


@pytest.mark.parametrize("tail", [0, 1])
@pytest.mark.parametrize("kind", ["fed", "routed"])
def test_a_prompts_last_chunk_is_followed_by_an_s1_step(kind, tail):
    """ISSUE 53 (c): a prompt of two chunks (``tail`` 0: its first
    token is sampled at the second window) or of two chunks and a token
    (``tail`` 1: at the S=1 step behind them, fed that token by the
    host) prefills beside a slot that decodes from the first window on:
    the S=1 step behind the last window is launched ahead, the rider's
    token from the chip and, with a ``tail``, merged into the host's."""
    rider = _prompt53(kind, 60, 3)
    prompt = _prompt53(kind, 61 + tail, 2 * _S46 + tail)

    def script(sched):
        hs = [sched.submit(rider, max_new_tokens=8),
              sched.submit(prompt, max_new_tokens=3)]
        sched.pump()
        return hs

    ahead, sync = _orders53(kind, script)
    assert ahead[0] == sync[0]
    _same_dispatches53(ahead, sync)
    assert [t for t, _why in ahead[0]] == [
        _plain_greedy(kind, rider, 8), _plain_greedy(kind, prompt, 3)]
    last = max(i for i, (w, _f, _a) in enumerate(ahead[1]) if w > 1)
    assert ahead[1][last][:2] == (_S46, [1, _S46, 0, 0])
    assert ahead[1][last + 1] == (1, [1, 1, 0, 0], 1)
    assert ahead[2]["runahead.windows"] >= 1


@pytest.mark.parametrize("kind", ["fed", "routed"])
def test_a_finish_by_length_between_windows_and_the_caller_resubmits(kind):
    """ISSUE 53 (d): a short request finishes by length while a long
    prompt prefills, and its done callback submits the next (a closed
    loop). That it finishes the host knows ahead: the window behind is
    planned without it and launched before its last token is on the
    host; whom the callback submits the host cannot know, so the
    newcomer is admitted by the plan behind that window - a join behind
    it on the device, ahead as well. No window beside the long prompt
    waits for a commit but the first, every request's tokens are the
    synchronous order's and
    the plain loop's, and the newcomers' first chunks come one window
    later than where every plan waits for a commit."""
    n_long = {"fed": 30, "routed": 11}[kind]
    long = _prompt53(kind, 70, n_long)
    shorts = [_prompt53(kind, 71 + i, 3) for i in range(3)]
    handles = {}

    def script(sched):
        handles.clear()

        def send(i):
            h = handles[i] = sched.submit(shorts[i], max_new_tokens=2)
            if i + 1 < len(shorts):
                h.add_done_callback(lambda _h: send(i + 1))

        hs = [sched.submit(long, max_new_tokens=3)]
        send(0)
        sched.pump()
        return hs + [handles[i] for i in range(len(shorts))]

    ahead, sync = _orders53(kind, script)
    assert ahead[0] == sync[0]
    assert [t for t, _why in ahead[0]] == [_plain_greedy(kind, long, 3)] + [
        _plain_greedy(kind, p, 2) for p in shorts]
    assert {why for _t, why in ahead[0]} == {"length"}
    assert ahead[2]["joins"] == 4 and ahead[2]["runahead.dropped"] == 0
    windows = [a for w, _fed, a in ahead[1] if w > 1]
    # (a window planned with nobody left beside a newcomer waits too)
    assert windows[:4] == [0, 1, 1, 1]
    assert ahead[2]["runahead.windows"] == sum(windows) >= len(windows) - 2
    # synchronous: a short one's second token and its successor's first
    # chunk lie in consecutive windows; ahead: a window between them in
    # which the freed slot is fed nothing
    assert [fed[1] for _w, fed, _a in sync[1][:4]] == [3, 1, 3, 1]
    assert [fed[1] for _w, fed, _a in ahead[1][:5]] == [3, 1, 0, 3, 1]


def _eos53(kind):
    """A rider's prompt whose second token differs from its first, and
    that token: the stream ends where it shows."""
    for seed in range(80, 120):
        rider = _prompt53(kind, seed, 3)
        free = _plain_greedy(kind, rider, 4)
        if free[1] != free[0]:
            return rider, free
    raise AssertionError("no such prompt")


@pytest.mark.parametrize("how", ["eos", "deadline"])
@pytest.mark.parametrize("kind", ["fed", "routed"])
def test_a_slot_retires_while_a_window_launched_ahead_is_on_the_chip(kind,
                                                                     how):
    """ISSUE 53 (e): the host cannot know an EOS ahead, nor a deadline
    that passes while the chip works. A rider that retires at a
    window's commit, or before it, has been fed a row of the window
    behind: that row's token is dropped and counted, the prompt beside
    it prefills on, and every stream is the synchronous order's."""
    rider, free = _eos53(kind)
    long = _prompt53(kind, 79, 11)

    def script(sched):
        hs = [sched.submit(long, max_new_tokens=3),
              sched.submit(rider, max_new_tokens=4,
                           eos_id=free[1] if how == "eos" else None,
                           deadline_ms=500 if how == "deadline" else None)]
        sched.pump(max_iterations=1)
        if how == "deadline":
            # with the second window on the chip (or, synchronous, not
            # yet planned) the rider's time runs out
            sched._clock.advance(1.0)
        sched.pump()
        return hs

    ahead, sync = _orders53(kind, script)
    assert ahead[0] == sync[0]
    assert ahead[0][0] == (_plain_greedy(kind, long, 3), "length")
    assert ahead[0][1] == (free[:1], how)
    assert [w for w, _f, _a in ahead[1]] == [w for w, _f, _a in sync[1]]
    # the rider's row of the window behind: fed ahead, not otherwise
    assert ahead[2]["runahead.dropped"] == 1
    assert sync[2]["runahead.dropped"] == 0
    assert ahead[2]["runahead.windows"] >= 2
    at, n = (2, 3) if how == "eos" else (1, _S46)
    assert ahead[1][at] == (_S46, [n, 1, 0, 0], 1)
    assert sync[1][at] == (_S46, [n, 0, 0, 0], 0)
