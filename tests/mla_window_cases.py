"""A window dispatch of ``mla_attention_decode`` whose slots are fed
different numbers of rows, for ``tests/test_axk1.py`` (no selection) and
``tests/test_glm_dsa.py`` (under one): what the kernels' three forms -
``mla_attn_window``, ``mla_attn_ride`` and, at S = 1, ``mla_attn_decode``
- have to agree on. Interpreted kernels, float32."""
import numpy as np

import jax.numpy as jnp

from mxnet_tpu.ops import mla
from mxnet_tpu.ops.registry import get_op

#: the window feeds 32 rows (one query block); a capacity of 2,560 is
#: five key blocks of 512 in the window form and two of 1,280 in the
#: riding and S = 1 forms
S, CAPACITY = 32, 2560
#: each slot's cursor: inside the first key block of either form, on a
#: block's last row and first row, inside the last block, at 0
CURSORS = [700, 1279, 1280, 1500, 2100, 0]
#: rows fed to each slot - a whole window, one (riding), none, a ragged
#: few - by case
CASES = {
    "mixed": [S, 1, 0, 5, 1, 1],
    "all_riding": [1, 1, 1, 1, 1, 1],
    "none_riding": [S, 5, 0, S, 2, 7],
}
_GEOMETRY = dict(capacity=CAPACITY, n_heads=4, nope_dim=24, rope_dim=16,
                 v_dim=16, kv_rank=64)


def _inputs(step_len, selected, fed, seed=0):
    """The op's inputs for a dispatch of ``step_len`` rows a slot; the
    first row of every array is the same whatever ``step_len`` is."""
    rs = np.random.RandomState(seed)
    B, H, dn, dr, dv, rank = len(CURSORS), 4, 24, 16, 16, 64

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


def check(case, selected, **attrs):
    """The Pallas lowering of a window whose slots are fed ``CASES[case]``
    rows against the expanded composition at every fed position, with
    the composition's pools and cursors; and a slot fed one row against
    the S = 1 dispatch of the same slot at the same cursor - the kernels'
    rows to the bit."""
    op = get_op("mla_attention_decode")
    attrs = op.normalize_attrs(dict(_GEOMETRY, selected=selected, **attrs))
    fed = CASES[case]
    ins, aux = _inputs(S, selected, fed)
    want, want_aux = op.variant_fn("xla")(attrs, ins, aux, False, None)
    got, got_aux = op.variant_fn("pallas")(attrs, ins, aux, False, None)
    want, got = (np.asarray(o[0]).reshape(len(fed), S, -1)
                 for o in (want, got))
    for slot, n in enumerate(fed):
        assert np.abs(want[slot, :n]).max(initial=1.0) > 0.1
        np.testing.assert_allclose(got[slot, :n], want[slot, :n],
                                   atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(got_aux[0]),
                                  np.asarray(want_aux[0]))
    assert list(np.asarray(got_aux[1]).ravel()) == \
        [p + n for p, n in zip(CURSORS, fed)]
    # the same slots, cursors and first rows through the S = 1 program:
    # the op's last product rounds by its shape, the kernels do not
    riding = [slot for slot, n in enumerate(fed) if n == 1]
    ins1, aux1 = _inputs(1, selected, [1] * len(fed))
    step, _ = op.variant_fn("pallas")(attrs, ins1, aux1, False, None)
    np.testing.assert_allclose(got[riding, 0], np.asarray(step[0])[riding, 0],
                               atol=1e-6, rtol=1e-6)
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(len(fed), 4, S, aux[0].shape[-1]), jnp.float32)
    p = jnp.asarray(CURSORS, jnp.int32)
    sel = ins[2] if selected else None
    kw = dict(rank=64, scale=0.2, interpret=True)
    window = mla._mla_attend(p, jnp.asarray(fed, jnp.int32), q, q[:, :, :1],
                             aux[0], sel, **kw)
    step = mla._mla_attend(p, jnp.ones_like(p), q[:, :, :1], None, aux[0],
                           None if sel is None else sel[:, :1], **kw)
    np.testing.assert_array_equal(np.asarray(window)[riding, :, 0],
                                  np.asarray(step)[riding, :, 0])
    assert not np.asarray(window)[[s for s, n in enumerate(fed) if n == 0]] \
        .any()
    return riding
