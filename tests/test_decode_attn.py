"""Decode-attention Pallas kernel (ISSUE 19): flash-decode parity,
selection discipline, and the zero-compile serving contract.

The kernel replaces only the attention READ of ``attention_decode`` —
RoPE and the cache writes stay the shared XLA helpers — so the parity
gates here assert three things at once: outputs within the tier
tolerance, cache contents BIT-identical across tiers, and cursors
equal. Both cursor layouts (scalar single-session and per_slot pool),
both window sizes (S=1 steady state, S>1 chunked prefill), staggered
cursors including slot reuse, and the fp8 KV-cache storage tier all
run through the same harness. Selection rides the standard kernel-tier
rules: a scripted slower measurement can never pick the kernel, and
with the kernel + fp8 cache armed the decode engine compiles nothing
after warmup at any rung.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier, program_cache
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.registry import get_op

OP = get_op("attention_decode")
B, H, DH, C = 2, 2, 8, 32


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("MXNET_KERNEL_TIER", raising=False)
    monkeypatch.delenv("MXNET_LM_CACHE_DTYPE", raising=False)
    kernel_tier.clear()
    yield
    kernel_tier.clear()


def _attrs(per_slot=False, cache_dtype="", rope=False, capacity=C):
    return OP.normalize_attrs({"capacity": capacity, "per_slot": per_slot,
                               "cache_dtype": cache_dtype, "rope": rope})


def _state(S=1, dtype="float32", per_slot=False, cursors=None,
           cache_dtype=None, seed=0, capacity=C, heads=H, head_dim=DH):
    """Random q/k/v + a cache whose live prefix holds real rows."""
    rng = np.random.RandomState(seed)
    dt = np.dtype(dtype)
    q, k, v = (jnp.asarray(rng.randn(B, heads, S, head_dim), dt)
               for _ in range(3))
    cdt = np.dtype(cache_dtype) if cache_dtype else dt
    k_cache = jnp.asarray(rng.randn(B, heads, capacity, head_dim), cdt)
    v_cache = jnp.asarray(rng.randn(B, heads, capacity, head_dim), cdt)
    if cursors is None:
        cursors = [3] * B if per_slot else 3
    cur = jnp.asarray(np.reshape(cursors, (B, 1)), jnp.int32) \
        if per_slot else jnp.asarray([cursors], jnp.int32)
    return [q, k, v], [k_cache, v_cache, cur]


def _both(attrs, inputs, aux, jit=False):
    """``jit``: as a step program runs them, where a cursor is data and
    a slot past its capacity drops its write instead of raising."""
    wrap = jax.jit if jit else (lambda f: f)
    ref_o, ref_a = wrap(lambda i, a: OP.forward(
        attrs, i, a, False, None))(inputs, aux)
    pal_o, pal_a = wrap(lambda i, a: OP.variants["pallas"]["fn"](
        attrs, i, a, False, None))(inputs, aux)
    return ref_o[0], ref_a, pal_o[0], pal_a


def _assert_parity(attrs, inputs, aux, tol, jit=False):
    ref, ref_aux, pal, pal_aux = _both(attrs, inputs, aux, jit=jit)
    assert ref.dtype == pal.dtype
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(pal, np.float32), atol=tol,
                               rtol=tol)
    # cache writes are the SHARED helper: bit-identical, dtype kept
    for r, p in zip(ref_aux[:2], pal_aux[:2]):
        assert r.dtype == p.dtype
        assert np.array_equal(np.asarray(r, np.float32),
                              np.asarray(p, np.float32))
    assert np.array_equal(np.asarray(ref_aux[2]), np.asarray(pal_aux[2]))


# ------------------------------------------------------------- parity
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("S", [1, 4])
def test_decode_kernel_parity(dtype, tol, per_slot, S):
    cursors = [1, 9] if per_slot else 5
    inputs, aux = _state(S=S, dtype=dtype, per_slot=per_slot,
                         cursors=cursors)
    _assert_parity(_attrs(per_slot=per_slot), inputs, aux, tol)


def test_decode_kernel_parity_rope():
    inputs, aux = _state(S=1, per_slot=True, cursors=[2, 7])
    _assert_parity(_attrs(per_slot=True, rope=True), inputs, aux, 2e-4)


# ------------------------------- grouped K/V heads, a window, rings
#: (S, window, ring, context capacity, cursors, fed): 8 query heads on 2
#: K/V heads of 8, rotary. S=1 and S=4 are ``decode_attn``'s (at most 64
#: rows a K/V head), S=16 and 32 ``window_attn``'s; cursors before the
#: window fills, past it and past several turns of the ring
_GROUPED_CASES = {
    "full-s1": (1, 0, 0, 64, [3, 40], None),
    "window-rows-s1": (1, 16, 0, 64, [3, 40], None),
    "ring-s1-unfilled": (1, 16, 32, 256, [3, 17], None),
    "ring-s1-wrapped": (1, 16, 32, 256, [70, 200], None),
    "ring-s4-ragged-fed": (4, 16, 32, 256, [70, 30], [2, 4]),
    "ring-s16-window-attn": (16, 16, 32, 256, [70, 129], [16, 5]),
    "ring-s16-from-zero": (16, 16, 32, 256, [0, 20], None),
    "full-s16-window-attn": (16, 0, 0, 256, [0, 120], None),
    "window-rows-s16": (16, 16, 0, 256, [0, 120], None),
    "ring-s32-two-query-blocks": (32, 16, 48, 256, [90, 200], [32, 0]),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", sorted(_GROUPED_CASES))
def test_grouped_window_ring_parity(case, dtype, tol):
    """``kv_heads``, ``window``, ``ring`` and ``fed``: the Pallas reads
    (``decode_attn`` with a group's heads as rows and a first live
    block, ``window_attn``) and the ring write against the composition:
    outputs within the tier tolerance, pools bit-identical, the cursor
    advanced by ``fed``. A window of pads alone (fed 0) comes out zero
    from ``window_attn`` and is compared nowhere."""
    S, window, ring, capacity, cursors, fed = _GROUPED_CASES[case]
    heads, kv_heads = 8, 2
    attrs = OP.normalize_attrs({
        "capacity": capacity, "per_slot": True, "rope": True,
        "kv_heads": kv_heads, "fed": True,
        **({"window": window} if window else {}),
        **({"ring": ring} if ring else {})})
    rng = np.random.RandomState(1)
    dt = np.dtype(dtype)
    q = jnp.asarray(rng.randn(B, heads, S, DH), dt)
    k, v = (jnp.asarray(rng.randn(B, kv_heads, S, DH), dt) for _ in "kv")
    pools = [jnp.asarray(rng.randn(B, kv_heads, ring or capacity, DH), dt)
             for _ in "kv"]
    cur = jnp.asarray(np.reshape(cursors, (B, 1)), jnp.int32)
    fed = [S] * B if fed is None else fed
    inputs = [q, k, v, jnp.asarray(fed, jnp.int32)]
    assert OP.aux_names(attrs)[0] == ("k_ring" if ring else "k_cache")
    assert OP.variants["pallas"]["eligible"](
        attrs, [x.shape for x in inputs + pools + [cur]],
        [str(x.dtype) for x in inputs + pools + [cur]])
    ref, ref_aux, pal, pal_aux = _both(attrs, inputs, pools + [cur],
                                       jit=True)
    assert ref.dtype == pal.dtype and ref.shape == (B, heads, S, DH)
    for slot, n in enumerate(fed):
        np.testing.assert_allclose(
            np.asarray(ref[slot, :, :n], np.float32),
            np.asarray(pal[slot, :, :n], np.float32), atol=tol, rtol=tol)
    for r, p in zip(ref_aux[:2], pal_aux[:2]):
        assert r.dtype == p.dtype and r.shape[2] == (ring or capacity)
        assert np.array_equal(np.asarray(r, np.float32),
                              np.asarray(p, np.float32))
    assert np.asarray(pal_aux[2]).ravel().tolist() == \
        np.asarray(ref_aux[2]).ravel().tolist() == \
        [c + n for c, n in zip(cursors, fed)]


def test_the_window_is_a_lower_bound_and_the_ring_forgets_nothing_in_it():
    """Against a plain softmax over the keys a window keeps: a sliding
    layer fed 70 positions one dispatch of 4 at a time through a ring
    of 24 rows attends, at every position, exactly the 16 newest keys -
    the ring has then turned almost three times."""
    window, ring, S, heads, kv_heads = 16, 24, 4, 4, 2
    attrs = OP.normalize_attrs({
        "capacity": 128, "per_slot": True, "kv_heads": kv_heads,
        "window": window, "ring": ring, "fed": True})
    rng = np.random.RandomState(2)
    T = 72
    q = rng.randn(1, heads, T, DH).astype("f")
    k, v = (rng.randn(1, kv_heads, T, DH).astype("f") for _ in "kv")
    aux = [jnp.zeros((1, kv_heads, ring, DH), jnp.float32)] * 2 \
        + [jnp.zeros((1, 1), jnp.int32)]
    step = jax.jit(lambda i, a: OP.variants["pallas"]["fn"](
        attrs, i, a, False, None))
    outs = []
    for t in range(0, T, S):
        out, aux = step([jnp.asarray(x[:, :, t:t + S]) for x in (q, k, v)]
                        + [jnp.asarray([S], jnp.int32)], aux)
        outs.append(np.asarray(out[0]))
    got = np.concatenate(outs, axis=2)[0]                 # (heads, T, DH)
    for h in range(heads):
        kh, vh = k[0, h // 2], v[0, h // 2]
        for t in range(T):
            lo = max(0, t - window + 1)
            s = q[0, h, t] @ kh[lo:t + 1].T / np.sqrt(DH)
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vh[lo:t + 1]
            np.testing.assert_allclose(got[h, t], want, atol=2e-4, rtol=2e-4)
    assert int(np.asarray(aux[2])[0, 0]) == T


def test_grouped_heads_and_rings_are_the_slot_pools_and_check_their_sizes():
    qs, kvs = (B, 8, 1, DH), (B, 2, 1, DH)
    with pytest.raises(MXNetError, match="per_slot"):
        OP.forward(OP.normalize_attrs({"capacity": C, "kv_heads": 2}),
                   [jnp.zeros(qs), jnp.zeros(kvs), jnp.zeros(kvs)],
                   [jnp.zeros((B, 2, C, DH))] * 2
                   + [jnp.zeros((1,), jnp.int32)], False, None)
    with pytest.raises(MXNetError, match="ring holds"):
        OP.forward(OP.normalize_attrs({"capacity": C, "kv_heads": 2,
                                       "per_slot": True, "window": 16,
                                       "ring": 16}),
                   [jnp.zeros(qs), jnp.zeros(kvs), jnp.zeros(kvs)],
                   [jnp.zeros((B, 2, 16, DH))] * 2
                   + [jnp.zeros((B, 1), jnp.int32)], False, None)
    # the op's shapes: k, v and the pools at kv_heads, a ring's rows
    attrs = OP.normalize_attrs({"capacity": 4 * C, "kv_heads": 2,
                                "per_slot": True, "window": 16, "ring": 24,
                                "fed": True})
    ins, outs, aux = OP.infer_shape(attrs, [qs, None, None, None])
    assert ins == [qs, kvs, kvs, (B,)] and outs == [qs]
    assert aux == [(B, 2, 24, DH), (B, 2, 24, DH), (B, 1)]
    assert OP.input_names(attrs) == ["q", "k", "v", "fed"]
    assert [OP.slot_state[n] for n in OP.aux_names(attrs)] == [
        "ring", "ring", "cursor"]


# ------------------------------------- a window's riders (ISSUE 58)
#: (heads, K/V heads, S, window, ring, context capacity, cursors, fed):
#: one long window (``window_attn``'s: more than 64 query rows a K/V
#: head) of mixed slots, at key blocks of 16 - a slot fed all S rows, one
#: fed a ragged chunk, three riders fed one row (at a key block's first
#: position, at a block's last, and far on: past several turns of a
#: ring), a slot fed nothing, and a rider with no room left for S rows,
#: which writes nothing and stays
_RIDING_CASES = {
    "plain": (2, 2, 80, 0, 0, 192,
              [16, 5, 32, 47, 111, 20, 190], [80, 33, 1, 1, 1, 0, 1]),
    "grouped": (8, 2, 32, 0, 0, 96,
                [16, 5, 32, 47, 63, 20, 94], [32, 7, 1, 1, 1, 0, 1]),
    "window": (8, 2, 32, 16, 0, 96,
               [16, 5, 32, 47, 63, 20, 94], [32, 2, 1, 1, 1, 0, 1]),
    "ring": (8, 2, 32, 16, 48, 256,
             [16, 5, 96, 47, 203, 20, 250], [32, 31, 1, 1, 1, 0, 1]),
}
_RIDERS = [2, 3, 4]


def _riding_state(case, dtype="float32", S=None, overflow=True):
    """``(attrs, inputs, aux, cursors, fed)`` of a case; ``S`` 1: the
    S = 1 program's view of the same slots (every slot's first row, fed
    one); without ``overflow`` the last slot, whose cursor is past the
    room, is left out (an eager call raises for it)."""
    heads, kv_heads, s_len, window, ring, capacity, cursors, fed = \
        _RIDING_CASES[case]
    slots = len(cursors)            # drawn for all: a slot's draws stay
    n = slots - (0 if overflow else 1)
    cursors, fed = cursors[:n], fed[:n]
    attrs = OP.normalize_attrs({
        "capacity": capacity, "per_slot": True, "rope": True, "fed": True,
        **({"kv_heads": kv_heads} if kv_heads != heads else {}),
        **({"window": window} if window else {}),
        **({"ring": ring} if ring else {})})
    rng = np.random.RandomState(3)
    dt = np.dtype(dtype)
    q = jnp.asarray(rng.randn(slots, heads, s_len,
                              DH), dt)[:n]
    k, v = (jnp.asarray(rng.randn(slots, kv_heads,
                                  s_len, DH), dt)[:n] for _ in "kv")
    pools = [jnp.asarray(rng.randn(slots, kv_heads,
                                   ring or capacity, DH), dt)[:n]
             for _ in "kv"]
    if S == 1:
        q, k, v = (x[:, :, :1] for x in (q, k, v))
        fed = [1] * n
    cur = jnp.asarray(np.reshape(cursors, (n, 1)), jnp.int32)
    return attrs, [q, k, v, jnp.asarray(fed, jnp.int32)], pools + [cur], \
        cursors, fed


@pytest.fixture
def key_blocks_of_16(monkeypatch):
    """Several key blocks a slot at a test's sizes, and one K/V head a
    grid step of the S = 1 read (two head groups a slot)."""
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_READ_BLOCK_K", 16)
    monkeypatch.setattr(pk, "_READ_VMEM_BUDGET", 20000)
    pk._decode_attention.clear_cache()
    pk._window_attention.clear_cache()
    yield pk
    pk._decode_attention.clear_cache()
    pk._window_attention.clear_cache()


def _pallas(attrs, inputs, aux):
    return jax.jit(lambda i, a: OP.variants["pallas"]["fn"](
        attrs, i, a, False, None))(inputs, aux)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4),
                                       ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", sorted(_RIDING_CASES))
def test_a_window_of_mixed_slots_matches_the_composition(
        case, dtype, tol, key_blocks_of_16):
    """A prefilling slot, a ragged chunk, riders at staggered cursors,
    an idle slot and a rider without room through one window program:
    each slot's fed rows within the tier tolerance of the composition's,
    both pools bit-identical, every cursor advanced by what was fed."""
    attrs, inputs, aux, cursors, fed = _riding_state(case, dtype)
    S = inputs[0].shape[2]
    assert OP.variants["pallas"]["eligible"](
        attrs, [x.shape for x in inputs + aux],
        [str(x.dtype) for x in inputs + aux])
    assert inputs[0].shape[1] // inputs[1].shape[1] * S > 64
    ref, ref_aux, pal, pal_aux = _both(attrs, inputs, aux, jit=True)
    fed[-1] = 0                     # no room under the capacity
    for slot, n in enumerate(fed):
        np.testing.assert_allclose(
            np.asarray(ref[slot, :, :n], np.float32),
            np.asarray(pal[slot, :, :n], np.float32), atol=tol, rtol=tol)
    # a rider's pads come out zero, as before
    assert not np.asarray(pal[_RIDERS, :, 1:], np.float32).any()
    for r, p in zip(ref_aux[:2], pal_aux[:2]):
        assert r.dtype == p.dtype
        assert np.array_equal(np.asarray(r, np.float32),
                              np.asarray(p, np.float32))
    assert np.asarray(pal_aux[2]).ravel().tolist() == \
        np.asarray(ref_aux[2]).ravel().tolist() == \
        [c + n for c, n in zip(cursors, fed)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_RIDING_CASES))
def test_a_riders_row_is_the_s1_programs_bit_for_bit(case, dtype,
                                                     key_blocks_of_16):
    """The same slot at the same cursor over the same pools: row 0 of a
    rider in the window program and the S = 1 program's output are the
    same bits (the same kernel over the same blocks; the window's pads
    lie past the rider's position and are masked)."""
    attrs, inputs, aux, _, _ = _riding_state(case, dtype)
    window, _ = _pallas(attrs, inputs, aux)
    attrs, inputs, aux, _, _ = _riding_state(case, dtype, S=1)
    single, _ = _pallas(attrs, inputs, aux)
    assert window[0].dtype == single[0].dtype == np.dtype(dtype)
    got = np.asarray(window[0][_RIDERS, :, 0], np.float32)
    assert np.abs(got).min() > 0
    assert np.array_equal(got,
                          np.asarray(single[0][_RIDERS, :, 0], np.float32))


@pytest.mark.parametrize("case", sorted(_RIDING_CASES))
def test_each_read_of_a_window_is_dead_to_the_other_reads_slots(
        case, key_blocks_of_16, monkeypatch):
    """``window_attn`` is launched with a rider's ``fed`` at 0 and gives
    zeros for it; ``window_attn_ride`` is launched over every slot's
    first query with the riders marked, gives zeros for every other
    slot, and its K/V index map names none of such a slot's blocks but
    what a dead step names: the first block of the slot after it (the
    last slot, which has none after it, its own first)."""
    pk = key_blocks_of_16
    attrs, inputs, aux, cursors, fed = _riding_state(case, overflow=False)
    slots, S = len(fed), inputs[0].shape[2]
    riding = np.asarray(fed) == 1
    assert np.flatnonzero(riding).tolist() == _RIDERS
    seen, specs = {}, {}
    for name in ("window_attention", "decode_attention"):
        def spy(*args, _fn=getattr(pk, name), _name=name, **kw):
            seen[_name] = (args, kw)
            return _fn(*args, **kw)
        monkeypatch.setattr(pk, name, spy)
    call = pk.pallas_call

    def pallas_call(kernel, out_shape, **kw):
        specs[kw["name"]] = kw["grid_spec"]
        return call(kernel, out_shape, **kw)
    monkeypatch.setattr(pk, "pallas_call", pallas_call)
    out, new_aux = OP.variants["pallas"]["fn"](attrs, inputs, aux, False,
                                               None)
    assert {"window_attn", "window_attn_ride"} <= set(specs)
    (_q, k_cache, v_cache, pos, fed_in), geometry = seen["window_attention"]
    assert np.asarray(fed_in).tolist() == np.where(riding, 0, fed).tolist()
    (q1, _k, _v, pos1), kw = seen["decode_attention"]
    assert q1.shape[2] == 1 and np.array_equal(q1, _q[:, :, :1])
    assert np.asarray(kw.pop("riding")).tolist() == riding.tolist()
    assert kw == geometry and np.array_equal(pos, pos1)
    assert np.asarray(pos).tolist() == cursors
    # each launch alone: zeros where the other read serves
    tiled = np.asarray(pk.window_attention(_q, k_cache, v_cache, pos,
                                           fed_in, **geometry))
    assert not tiled[riding].any() and not tiled[np.asarray(fed) == 0].any()
    assert np.abs(tiled[0]).min() > 0
    ride = np.asarray(pk.decode_attention(q1, k_cache, v_cache, pos,
                                          riding=jnp.asarray(riding), **kw))
    assert not ride[~riding].any() and np.abs(ride[riding]).min() > 0
    assert np.array_equal(ride[riding].astype(np.float32),
                          np.asarray(out[0])[riding][:, :, :1])
    # the window read fetches none of a dead query block's keys (a
    # rider's, an idle slot's, a ragged chunk's pads): its steps stay
    # on one block, where a live query block walks its live ones
    grid_spec = specs["window_attn"]
    _, n_heads, n_q, n_kb = grid_spec.grid
    index_map = grid_spec.in_specs[1].index_map
    block_q = S // n_q
    for b in range(slots):
        for i in range(n_q):
            named = {tuple(int(x) for x in index_map(
                b, h, i, j, pos, fed_in)[:3])
                for h in range(n_heads) for j in range(n_kb)}
            assert all(x[0] == b for x in named)
            if i * block_q >= int(fed_in[b]):
                assert len(named) == n_heads, (b, i, named)
    assert len({tuple(int(x) for x in index_map(0, 0, 0, j, pos, fed_in)[:3])
                for j in range(n_kb)}) > 1
    # the ride's K/V index map, step by step
    grid_spec = specs["window_attn_ride"]
    n_slots, n_groups, n_kb = grid_spec.grid
    block_k = grid_spec.in_specs[1].block_shape[2]
    assert (n_slots, n_groups, block_k) == (slots, 2, 16)
    index_map = grid_spec.in_specs[1].index_map
    cursor = jnp.where(riding, pos, -1)
    window = int(attrs.get("window") or 0)
    for b in range(slots):
        named = {tuple(int(x) for x in index_map(b, g, j, cursor)[:3])
                 for g in range(n_groups) for j in range(n_kb)}
        def lo(b):
            return max(cursors[b] - window + 1, 0) if window else 0
        if not riding[b]:
            ahead = lo(b + 1) // block_k % n_kb \
                if b + 1 < slots and riding[b + 1] else 0
            assert named == ({(b + 1, 0, ahead)} if b + 1 < slots else
                             {(b, g, 0) for g in range(n_groups)}), (b, named)
            continue
        lo = lo(b)
        live = {(b, g, (t // block_k) % n_kb) for g in range(n_groups)
                for t in range(lo, cursors[b] + 1)}
        assert live <= named
        # and nothing else of its own: a trailing dead step looks ahead
        assert {x for x in named if x[0] == b} == live, (b, named)


@pytest.mark.parametrize("case", sorted(_RIDING_CASES))
def test_a_graph_without_fed_has_no_ride(case, key_blocks_of_16):
    """The whole-window program (``check_reference``'s: every slot fed
    ``S``) has no rider: ``cache_write`` and ``window_attn`` and no
    second read; the same graph with ``fed`` has all three, and its
    S = 1 program ``decode_attn`` alone."""
    def kernels(attrs, inputs, aux):
        text = str(jax.make_jaxpr(lambda i, a: OP.variants["pallas"]["fn"](
            attrs, i, a, False, None))(inputs, aux))
        return [name for name in re.findall(r"name=(\w+)", text)
                if name in ("cache_write", "decode_attn", "window_attn",
                            "window_attn_ride")]
    attrs, inputs, aux, _, _ = _riding_state(case)
    assert kernels(attrs, inputs, aux) == [
        "cache_write", "window_attn", "window_attn_ride"]
    whole = OP.normalize_attrs({k: v for k, v in attrs.items()
                                if k != "fed"})
    assert kernels(whole, inputs[:3], aux) == ["cache_write", "window_attn"]
    attrs, inputs, aux, _, _ = _riding_state(case, S=1)
    assert kernels(attrs, inputs, aux) == ["cache_write", "decode_attn"]


def test_decode_kernel_parity_staggered_and_edge_cursors():
    """Slots at position 0, mid-stream, and at the last legal window
    start — the cursor-bounded HBM read must still cover exactly the
    live prefix of every row."""
    inputs, aux = _state(S=1, per_slot=True, cursors=[0, C - 1])
    _assert_parity(_attrs(per_slot=True), inputs, aux, 2e-4)


def test_decode_kernel_parity_slot_reuse():
    """Retire-and-rejoin: advance both slots, reset slot 0's cursor to
    0 (the pool's join path resets ONLY the cursor), decode again —
    the kernel's bounded read must mask the stale suffix exactly like
    the XLA composition's -inf mask."""
    attrs = _attrs(per_slot=True)
    inputs, aux = _state(S=1, per_slot=True, cursors=[4, 11])
    _, ref_aux, _, pal_aux = _both(attrs, inputs, aux)
    rng = np.random.RandomState(9)
    nxt = [jnp.asarray(rng.randn(B, H, 1, DH), jnp.float32)
           for _ in range(3)]
    rejoin = jnp.asarray([[0], [12]], jnp.int32)    # slot 0 reused
    _assert_parity(attrs, nxt, [ref_aux[0], ref_aux[1], rejoin], 2e-4)


def test_decode_kernel_fp8_cache():
    """The fp8 storage tier: cache cells stay float8_e4m3fn through the
    step (writes cast on store, reads dequantize), and the kernel
    matches the XLA composition reading the SAME fp8 cells."""
    inputs, aux = _state(S=1, per_slot=True, cursors=[2, 6],
                         cache_dtype="float8_e4m3fn")
    attrs = _attrs(per_slot=True, cache_dtype="fp8")
    ref, ref_aux, pal, pal_aux = _both(attrs, inputs, aux)
    assert ref_aux[0].dtype == np.dtype("float8_e4m3fn")
    assert pal_aux[0].dtype == np.dtype("float8_e4m3fn")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=2e-4, rtol=2e-4)
    assert np.array_equal(np.asarray(ref_aux[0], np.float32),
                          np.asarray(pal_aux[0], np.float32))


# a step of the read takes a group of heads and up to 512 keys: several
# key blocks a slot, cursors in the first block, astride a block edge, in
# the last block and at capacity - S; head counts the group does not
# divide evenly; a retired slot that ran past the capacity (its write is
# dropped, every key is read) beside a live one
_BLOCK_CASES = {
    # id: (heads, S, head_dim, capacity, dtype, cache_dtype, cursors, tol)
    "first_block_12_heads": (12, 1, 128, 2048, "bfloat16", None,
                             [5, 511], 2e-2),
    "astride_an_edge_S4": (12, 4, 128, 2048, "bfloat16", None,
                           [510, 1022], 2e-2),
    "last_block_float32": (12, 1, 128, 2048, "float32", None,
                           [1600, 2047], 2e-4),
    "capacity_less_S_S64": (16, 64, 128, 2048, "bfloat16", None,
                            [2048 - 64, 0], 2e-2),
    "5_heads_of_512_S64": (5, 64, 512, 1024, "float32", None,
                           [500, 960], 2e-4),
    "fp8_cache_three_blocks": (12, 1, 128, 1536, "float32",
                               "float8_e4m3fn", [700, 1535], 2e-4),
    "fp8_cache_bfloat16_S64": (5, 64, 128, 1024, "bfloat16",
                               "float8_e4m3fn", [300, 513], 2e-2),
    "retired_slot_past_capacity": (12, 4, 128, 1024, "float32", None,
                                   [1022, 100], 2e-4),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_decode_kernel_parity_over_blocks(case):
    heads, S, head_dim, capacity, dtype, cache_dtype, cursors, tol = \
        _BLOCK_CASES[case]
    from mxnet_tpu.ops.pallas_kernels import _decode_attn_blocks
    hb, block_k = _decode_attn_blocks(
        heads, S, head_dim, capacity, jnp.dtype(dtype),
        jnp.dtype(cache_dtype or dtype))
    assert capacity // block_k >= 2 and heads % hb == 0
    inputs, aux = _state(S=S, dtype=dtype, per_slot=True, cursors=cursors,
                         cache_dtype=cache_dtype, capacity=capacity,
                         heads=heads, head_dim=head_dim)
    attrs = _attrs(per_slot=True, capacity=capacity,
                   cache_dtype="fp8" if cache_dtype else "")
    _assert_parity(attrs, inputs, aux, tol, jit=case.startswith("retired"))


@pytest.mark.parametrize("budget,block_k,S,group", [(24000, 8, 1, 2),
                                                    (40000, 16, 4, 3),
                                                    (14000, 8, 8, 1)])
def test_decode_kernel_parity_small_groups(monkeypatch, budget, block_k, S,
                                           group):
    """The same walk at toy sizes: a budget that holds ``group`` of a
    slot's 6 heads and key blocks of 8 or 16 over a capacity of 32, so
    every (head group, key block) boundary is crossed."""
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(pk, "_READ_VMEM_BUDGET", budget)
    monkeypatch.setattr(pk, "_READ_BLOCK_K", block_k)
    pk._decode_attention.clear_cache()
    try:
        for dtype, tol in (("float32", 2e-4), ("bfloat16", 2e-2)):
            assert pk._decode_attn_blocks(
                6, S, DH, C, jnp.dtype(dtype), jnp.dtype(dtype)) == (
                    group, block_k)
            inputs, aux = _state(S=S, dtype=dtype, per_slot=True,
                                 cursors=[block_k - 1, C - S], heads=6)
            _assert_parity(_attrs(per_slot=True), inputs, aux, tol)
    finally:
        pk._decode_attention.clear_cache()


def test_decode_attn_blocks_fit_the_budget():
    """What a grid step covers, from the shapes and the dtype alone: at
    the serving shapes a layer's read is at most 128 steps (it was
    2,048 and 4,096) and its resident set is under the budget; at the
    eligibility bounds one head still fits."""
    from mxnet_tpu.ops import pallas_kernels as pk
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    for slots, heads, S, dh, cap, dt in ((8, 16, 1, 128, 2048, bf16),
                                         (8, 16, 64, 128, 4096, bf16),
                                         (1, 1, 64, 512, 128, f32),
                                         (8, 12, 64, 512, 4096, f32),
                                         (8, 5, 4, 256, 640, bf16)):
        hb, bk = pk._decode_attn_blocks(heads, S, dh, cap, dt, dt)
        assert heads % hb == 0 and cap % bk == 0
        if dh == 128:
            assert slots * (heads // hb) * (cap // bk) <= 128
        fixed, per_head = pk._decode_attn_resident(S, dh, bk, dt, dt)
        assert fixed + hb * per_head <= pk._READ_VMEM_BUDGET
    assert pk._decode_attn_blocks(16, 1, 128, 2048, bf16, bf16) == (16, 512)
    assert pk._READ_VMEM_BUDGET < 16 << 20


def test_pallas_variant_rejects_training():
    inputs, aux = _state()
    with pytest.raises(MXNetError, match="inference"):
        OP.variants["pallas"]["fn"](_attrs(), inputs, aux, True, None)


# -------------------------------------------------- eligibility + gate
def test_decode_eligibility_bounds():
    elig = OP.variants["pallas"]["eligible"]
    qs = (B, H, 1, DH)
    cs = (B, H, C, DH)
    shapes = [qs, qs, qs, cs, cs, (B, 1)]
    f32 = ["float32"] * 5 + ["int32"]
    assert elig(_attrs(), shapes, f32)
    # fp8 cache cells are in the gate set
    fp8 = ["float32"] * 3 + ["float8_e4m3fn"] * 2 + ["int32"]
    assert elig(_attrs(cache_dtype="fp8"), shapes, fp8)
    # bounds: window rows, head dim, q dtype
    big_s = [(B, H, 65, DH)] + shapes[1:]
    assert not elig(_attrs(), big_s, f32)
    wide = [(B, H, 1, 513)] * 3 + [(B, H, C, 513)] * 2 + [(B, 1)]
    assert not elig(_attrs(), wide, f32)
    assert not elig(_attrs(), shapes, ["int8"] + f32[1:])


def test_decode_numerics_gate():
    qs, cs = (B, H, 1, DH), (B, H, C, DH)
    ok, err = kernel_tier.numerics_gate(
        OP, _attrs(per_slot=True), [qs, qs, qs, cs, cs, (B, 1)],
        ["float32"] * 5 + ["int32"], is_train=False)
    assert ok, f"max_abs_err={err}"


def test_decode_pallas_never_selected_when_slower(monkeypatch):
    """The decode kernel rides the same scripted-timer autotune as every
    other variant: a slower measurement can never select it."""
    qs, cs = (B, H, 1, DH), (B, H, C, DH)
    shapes = [qs, qs, qs, cs, cs, (B, 1)]
    dtypes = ["float32"] * 5 + ["int32"]
    times = iter([1.0, 3.0])                   # xla 1ms, pallas 3ms
    monkeypatch.setattr(kernel_tier, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_tier, "_device_kind", lambda: "TPU test")
    monkeypatch.setattr(kernel_tier, "_time_variant",
                        lambda run, r, x, reps: next(times) / 1e3)
    assert kernel_tier.resolve(OP, _attrs(per_slot=True), shapes,
                               dtypes, False) == "xla"
    assert "slower" in kernel_tier.decisions()[-1]["reason"]


# ------------------------------------------- serving: zero compiles
V, D, L, NH, CAP = 64, 32, 2, 4, 32


def _decoder_args():
    from mxnet_tpu.models import transformer as tfm
    np.random.seed(0)
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=NH,
                         seq_len=8, include_loss=False, max_seq_len=CAP)
    mod = mx.mod.Module(sym, label_names=[])
    mod.bind([("data", (1, 8))], None, for_training=False)
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))
    args, _ = mod.get_params()
    return args


@pytest.mark.parametrize("cache_dtype", [None, "fp8"])
def test_decode_engine_zero_compiles_with_kernel_armed(monkeypatch,
                                                       cache_dtype):
    """The acceptance gate: MXNET_KERNEL_TIER=pallas (+ the fp8 cache
    tier) armed, compile_count() delta == 0 after warmup at EVERY
    ladder rung, with requests joining and retiring across rungs."""
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    args = _decoder_args()
    dsym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                 n_head=NH, capacity=CAP, per_slot=True,
                                 max_seq_len=CAP,
                                 cache_dtype=cache_dtype)
    sched = mx.serve.serve_decoder(dsym, args, name=f"za{cache_dtype}",
                                   ladder=[1, 2, 4], start=True)
    try:
        rs = np.random.RandomState(0)
        # warmup pinned every rung at engine build; steady state now
        mark = program_cache.compile_count()
        handles = [sched.submit(rs.randint(0, V, 4).tolist(),
                                max_new_tokens=6) for _ in range(6)]
        outs = [h.result(timeout=600) for h in handles]
        assert all(len(o) == 6 for o in outs)
        assert program_cache.compile_count() - mark == 0
        assert sched.stats()["compiles_since_warmup"] == 0
    finally:
        sched.stop()


def test_decode_driver_kernel_vs_xla_logits(monkeypatch):
    """End to end through Module + KVCacheDecoder: the forced-kernel
    decode chain reproduces the default chain's logits step for step."""
    from mxnet_tpu.models import transformer as tfm
    args = _decoder_args()
    tokens = np.random.RandomState(3).randint(0, V, (2, 8))

    def _run():
        dsym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                     n_head=NH, capacity=CAP,
                                     max_seq_len=CAP)
        dec = mx.mod.Module(dsym, label_names=[])
        dec.bind([("data", (2, 1))], None, for_training=False)
        dec.init_params(initializer=None, arg_params=args,
                        aux_params={}, allow_missing=True)
        drv = tfm.KVCacheDecoder(dec, capacity=CAP)
        return [drv.step(tokens[:, t:t + 1]).asnumpy()
                for t in range(tokens.shape[1])]

    base = _run()
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    forced = _run()
    for a, b in zip(base, forced):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)
        assert np.array_equal(a.argmax(-1), b.argmax(-1))


# ------------------------------------ what a dispatch reads of the pools
def test_driver_counts_live_rows_from_its_cursors(monkeypatch):
    """``serve.decode.attn.live_rows`` / ``.capacity_rows`` /
    ``.attended_rows``: per dispatch, over the fed slots and the
    ``attention_decode`` layers, the rows at or before each slot's last
    query, the rows the pools hold and the rows that query attends (all
    of the live ones: no layer here has a window) - from the cursors
    the host mirrors, whichever tier reads."""
    from mxnet_tpu.models import transformer as tfm
    args = _decoder_args()
    dsym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                 n_head=NH, capacity=CAP, per_slot=True,
                                 max_seq_len=CAP)
    dec = mx.mod.Module(dsym, data_names=("data", "fed"), label_names=[])
    dec.bind([mx.io.DataDesc("data", (3, 1), np.int32),
              mx.io.DataDesc("fed", (3,), np.int32)], None,
             for_training=False)
    dec.init_params(initializer=None, arg_params=args, aux_params={},
                    allow_missing=True)
    drv = tfm.BatchedKVCacheDecoder(dec, capacity=CAP, slots=3)
    assert drv.last_reads is None and sorted(drv.read_counts) == [
        "attn.attended_rows", "attn.capacity_rows", "attn.live_rows"]
    drv.join(0)
    drv.join(2)
    drv.rewind(2, 9)
    drv.step(np.zeros((3, 1), np.int32), fed=[1, 0, 1])
    # slot 0 reads row 0, slot 2 rows 0-9; slot 1 is nobody's
    assert drv.last_reads == {"attn.live_rows": L * (1 + 10),
                              "attn.capacity_rows": L * 3 * CAP,
                              "attn.attended_rows": L * (1 + 10)}
    drv.leave(0)
    drv.step(np.zeros((3, 1), np.int32), fed=[0, 0, 1])
    assert list(drv.last_reads.values()) == [L * 11, L * 3 * CAP, L * 11]


def test_scheduler_registers_the_attention_counters(monkeypatch):
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import transformer as tfm
    args = _decoder_args()
    dsym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                 n_head=NH, capacity=CAP, per_slot=True,
                                 max_seq_len=CAP)
    sched = mx.serve.serve_decoder(dsym, args, name="attn-count",
                                   ladder=[2], start=True)
    try:
        handles = [sched.submit([1, 2, 3], max_new_tokens=4)
                   for _ in range(2)]
        assert all(len(h.result(timeout=600)) == 4 for h in handles)
    finally:
        sched.stop()
    counters = {m.name: m.value for m in telemetry.metrics.all_metrics()
                if isinstance(m, telemetry.Counter)
                and ("model", "attn-count") in m.labels}
    live = counters["serve.decode.attn.live_rows"]
    held = counters["serve.decode.attn.capacity_rows"]
    assert held % (L * 2 * CAP) == 0 and 0 < live < held
    assert counters["serve.decode.attn.attended_rows"] == live
    # two requests of 3 + 4 tokens: no slot ever reads past row 6
    assert live <= held // CAP * 7
