"""A window dispatch of ``mla_attention_decode`` whose slots are fed
different numbers of rows, for ``tests/test_axk1.py`` and
``tests/test_xing4.py`` (no selection) and ``tests/test_glm_dsa.py``
(under one): what the kernels' three forms - ``mla_attn_window`` in the
expanded widths, ``mla_attn_ride`` and, at S = 1, ``mla_attn_decode`` in
the latent space - have to agree on. Interpreted kernels, float32."""
import numpy as np

import jax.numpy as jnp

from mxnet_tpu.ops import mla
from mxnet_tpu.ops.registry import get_op

#: the window feeds 32 rows; a capacity of 2,560 is four key blocks of
#: 640 in the window form (one query block) and two of 1,280 in the
#: riding and S = 1 forms; at ``TINY_BLOCKS`` the window form takes the
#: 32 rows as four query blocks of 8 - two pairs, each the rows of one
#: product where its second block has a real row - against forty key
#: blocks of 64
S, CAPACITY = 32, 2560
TINY_BLOCKS = (8, 64)
#: each slot's cursor: inside the first key block of either form, on a
#: block's last row and first row (of 1,280 and of 64), inside the last
#: block, at 0
CURSORS = [700, 1279, 1280, 1500, 2100, 0]
#: rows fed to each slot - a whole window, one (riding), none, a ragged
#: few - by case
CASES = {
    "mixed": [S, 1, 0, 5, 1, 1],
    "all_riding": [1, 1, 1, 1, 1, 1],
    "none_riding": [S, 5, 0, S, 2, 7],
}
#: the window form against the expanded composition, by case: the rows
#: fed, the blocks (None: the published 256 x 1,024) and what of the
#: geometry differs - a chunk of four query blocks that starts on a key
#: block's last and first row and ends inside a query block, a slot fed
#: 2 rows beside one fed all S, dead slots before, between and behind
#: the live ones, and GLM-5.2's unequal ``nope_dim`` and ``v_dim``
WINDOW_CASES = {
    "four_query_blocks": ([S, S, S, 19, 9, S], TINY_BLOCKS, {}),
    "two_rows_beside_a_whole_chunk": ([2, S, 2, 1, 0, 17], TINY_BLOCKS, {}),
    "dead_slots_around_live_ones": ([0, 1, S, 0, 12, 1], TINY_BLOCKS, {}),
    "unequal_nope_and_v": ([S, 2, 11, 1, 0, S], TINY_BLOCKS,
                           dict(nope_dim=40, v_dim=24)),
    "unequal_nope_and_v_one_query_block": ([S, 2, 11, 1, 0, S], None,
                                           dict(nope_dim=40, v_dim=24)),
}
_GEOMETRY = dict(capacity=CAPACITY, n_heads=4, nope_dim=24, rope_dim=16,
                 v_dim=16, kv_rank=64)


def _inputs(step_len, selected, fed, geometry=_GEOMETRY, seed=0):
    """The op's inputs for a dispatch of ``step_len`` rows a slot; the
    first row of every array is the same whatever ``step_len`` is."""
    rs = np.random.RandomState(seed)
    B = len(CURSORS)
    H, dn, dr, dv, rank = (geometry[k] for k in (
        "n_heads", "nope_dim", "rope_dim", "v_dim", "kv_rank"))

    def f(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    pool = f(B, 1, CAPACITY, mla.latent_width(rank, dr))
    gamma, kvb = jnp.ones((rank,), jnp.float32), f(H * (dn + dv), rank) * 0.2
    q, kv = f(B, S, H * (dn + dr)), f(B, S, rank + dr)
    pos = np.asarray(CURSORS)[:, None] + np.arange(S)[None, :]
    keep = rs.rand(B, S, CAPACITY) < 0.5
    keep |= np.arange(CAPACITY)[None, None, :] == pos[:, :, None]
    keep &= np.arange(CAPACITY)[None, None, :] <= pos[:, :, None]
    sel = [jnp.asarray(keep[:, :step_len].astype(np.int8))] if selected \
        else []
    return ([q[:, :step_len], kv[:, :step_len]] + sel
            + [jnp.asarray(fed, jnp.int32), gamma, kvb],
            [pool, jnp.asarray(CURSORS, jnp.int32)[:, None]])


def check_window(fed, selected, blocks=None, geometry=_GEOMETRY, **attrs):
    """The Pallas lowering of a window whose slots are fed ``fed`` rows,
    its window form at ``blocks``, against the expanded composition at
    every fed position, with the composition's pools and cursors; a
    slot fed nothing comes out zero. -> the op's attributes, the
    lowering's rows ``(slots, S, .)`` and the inputs."""
    op = get_op("mla_attention_decode")
    attrs = op.normalize_attrs(dict(geometry, selected=selected, **attrs))
    ins, aux = _inputs(S, selected, fed, geometry)
    want, want_aux = op.variant_fn("xla")(attrs, ins, aux, False, None)
    published = mla._WINDOW_BLOCKS
    mla._WINDOW_BLOCKS = blocks or published
    try:
        got, got_aux = op.variant_fn("pallas")(attrs, ins, aux, False, None)
    finally:
        mla._WINDOW_BLOCKS = published
    want, got = (np.asarray(o[0]).reshape(len(fed), S, -1)
                 for o in (want, got))
    assert got.shape[-1] == geometry["n_heads"] * geometry["v_dim"]
    for slot, n in enumerate(fed):
        assert np.abs(want[slot, :n]).max(initial=1.0) > 0.1
        np.testing.assert_allclose(got[slot, :n], want[slot, :n],
                                   atol=2e-5, rtol=2e-5)
        assert n or not got[slot].any()
    np.testing.assert_array_equal(np.asarray(got_aux[0]),
                                  np.asarray(want_aux[0]))
    assert list(np.asarray(got_aux[1]).ravel()) == \
        [p + n for p, n in zip(CURSORS, fed)]
    return attrs, got, (ins, aux)


def check(case, selected, **attrs):
    """A window whose slots are fed ``CASES[case]`` rows
    (``check_window``); and a slot fed one row against the S = 1
    dispatch of the same slot at the same cursor - the kernels' rows to
    the bit."""
    fed = CASES[case]
    attrs, got, (ins, aux) = check_window(fed, selected, **attrs)
    op = get_op("mla_attention_decode")
    # the same slots, cursors and first rows through the S = 1 program:
    # the op's last product rounds by its shape, the kernels do not
    riding = [slot for slot, n in enumerate(fed) if n == 1]
    ins1, aux1 = _inputs(1, selected, [1] * len(fed))
    step, _ = op.variant_fn("pallas")(attrs, ins1, aux1, False, None)
    np.testing.assert_allclose(got[riding, 0], np.asarray(step[0])[riding, 0],
                               atol=1e-6, rtol=1e-6)
    rs = np.random.RandomState(1)
    pool = aux[0]
    q = jnp.asarray(rs.randn(len(fed), 4, 1, pool.shape[-1]), jnp.float32)
    p = jnp.asarray(CURSORS, jnp.int32)
    sel = ins[2][:, :1] if selected else None
    keys = pool.reshape(len(fed), CAPACITY, -1)

    def launch(fed, form):
        return np.asarray(mla._mla_launch(p, fed, q, keys, sel, 64, 0.2, True,
                                          form))

    ride = launch(jnp.asarray(fed, jnp.int32), "ride")
    step = launch(jnp.ones_like(p), "decode")
    np.testing.assert_array_equal(ride[riding], step[riding])
    assert not ride[[s for s, n in enumerate(fed) if n != 1]].any()
    return riding
