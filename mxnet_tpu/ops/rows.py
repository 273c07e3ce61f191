"""The rows of a window, packed: ``pack_rows``, ``unpack_rows`` and
``last_rows``.

A window graph of a fed decoder (``models/transformer.py``: the blocks
that take ``fed``) gives every slot S rows, of which ``fed[b]`` are real
tokens and the rest pads. Its row-wise operations - norms, projections,
feed-forwards, the router, the head - do not care which slot a row
belongs to; attention does. The two ops stand where the graph passes
from one view to the other, and carry the row budget ``rows``:

* ``rows = 0`` (the default; every S = 1 graph, and the whole-window
  program): all ``slots x S`` rows are kept. ``pack_rows`` hands its
  inputs on as they are and ``unpack_rows`` is the reshape
  ``(slots * S, ...) -> (slots, S, ...)``: the program's text is what it
  was before the ops existed.
* ``rows = R`` (``DecodeEngine`` sets it on a copy of a window graph):
  ``pack_rows`` lays slot ``b``'s ``fed[b]`` real rows at ``offset[b] =
  fed[0] + ... + fed[b-1]`` of one ``(1, R, ...)`` block - one
  pseudo-slot of R rows, so that the graph's own folds and norms take it
  as they take ``(slots, S, ...)`` - and ``unpack_rows`` lays them back
  as ``(slots, S, ...)``, pads zero. Whoever launches the program keeps
  ``fed.sum() <= R``; rows past the budget would be dropped.

A slot's real rows are a prefix of its S, and the shape says how they
are moved (``one_chunk``, the one predicate, known when the program is
traced):

* a slot of several chunks (``step_len`` 256, 512, 1,024): copies of
  contiguous blocks and no gather. Packing copies each slot's real rows
  in place, ``_chunk`` rows a copy and as many copies as the slot has
  real rows for (one loop whose trips follow ``fed``: a riding slot
  costs one copy of 128 rows and not its slot's 1,024, an unfed one
  none), in slot order - each later block lands on the pad tail of the
  one before - and unpacking copies them back the same way under a row
  mask. Neither touches a pad row beyond a copy's tail;
* a slot of one chunk (``step_len`` 64, and every size the CPU tests
  run): no loop, because there the trips are a copy a fed slot whatever
  ``fed`` holds and a trip costs several times its copy (5.5 us for 64
  rows, 50 loops a Cerebras window program). Unpacking is the loop's
  body once a slot, laid out in the program's text: a slice of the block
  at the slot's offset under the loop's row mask, the ``slots`` slices
  stacked, nothing carried and no output updated in place. Packing is
  one gather of whole rows by an index computed from ``fed`` - a packed
  row past the real ones reads nothing and is zero - which the compiler
  fuses into its reader; ``slots`` in-place updates in the text cost the
  loop's trips over again, a 64-row block laid at an odd row offset of a
  tiled buffer each (``PERF.md`` section 6, PR 65: the forms timed).
  The real rows are the loop's, bit for bit.

``pack_rows`` also returns ``fed`` in the packed view: itself at ``rows
= 0``, the one pseudo-slot's count of real rows ``(1,)`` under a budget
- what ``MoEFFN`` keeps the pads out of its experts by.

``last_rows`` reads, from the view the row-wise operations run in, the
one row of each slot that a serving window's caller reads: its last fed
row, as ``(slots, 1, ...)`` - row ``fed[b] - 1`` of slot ``b`` at ``rows
= 0``, row ``offset[b] + fed[b] - 1`` of the one block under a budget. A
gather of ``slots`` rows and no loop. A slot fed nothing takes a row
that nobody reads: its own row 0, or under a budget the row before its
offset (row 0 for the first) - a real row or a zero pad, finite either
way. ``packed_window`` puts it in front of a packed window graph's head,
so that the final norm and the product with the vocabulary run over
``slots`` rows and the program hands back ``(slots, 1, V)``: no whole
window graph has the node (``models/transformer.py``).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..base import parse_int, parse_tuple
from .registry import register

__all__ = ["pack", "unpack", "last", "one_chunk"]


def _chunk(step_len):
    """Rows a copy: 128 where a slot's rows are whole chunks of that,
    else all of a slot's."""
    return 128 if step_len % 128 == 0 else step_len


def one_chunk(step_len):
    """Whether a slot's ``step_len`` rows are one copy's: ``pack`` and
    ``unpack`` then hold no loop (module docstring)."""
    return _chunk(step_len) == step_len


def _offsets(fed, step_len, rows):
    """Each slot's count of real rows and where its block starts, both
    inside the budget whatever ``fed`` holds, and the real rows in all."""
    fed = jnp.clip(fed.astype(jnp.int32), 0, step_len)
    ends = jnp.cumsum(fed)
    return fed, jnp.minimum(ends - fed, rows), jnp.minimum(ends[-1:], rows)


def _copy_real(out, fed, chunk, copy):
    """``out`` after ``copy(out, b, i)`` for every chunk ``i`` that slot
    ``b`` has a real row in, slot after slot: one loop of as many trips
    as there are such chunks, ``out`` updated in place."""
    chunks = (fed + chunk - 1) // chunk
    ends = jnp.cumsum(chunks)

    def trip(t, out):
        b = jnp.sum((ends <= t).astype(jnp.int32))     # the slot of copy t
        first = _at(ends, b) - _at(chunks, b)
        return copy(out, b, t - first)

    return lax.fori_loop(0, ends[-1], trip, out)


def _at(values, b):
    return lax.dynamic_index_in_dim(values, b, keepdims=False)


def pack(x, fed, rows):
    """``x (slots, S, ...)`` -> ``(1, rows, ...)`` and the packed view's
    ``fed (1,)`` (module docstring)."""
    form = _pack_gathered if one_chunk(x.shape[1]) else _pack_looped
    return form(x, fed, rows)


def _pack_looped(x, fed, rows):
    """``pack`` as copies of chunks, as many as hold a real row."""
    step_len = x.shape[1]
    chunk = _chunk(step_len)
    fed, starts, total = _offsets(fed, step_len, rows)
    zero = (0,) * (x.ndim - 2)

    def copy(out, b, i):
        block = lax.dynamic_slice(x, (b, i * chunk) + zero,
                                  (1, chunk) + x.shape[2:])
        return lax.dynamic_update_slice(
            out, block, (0, _at(starts, b) + i * chunk) + zero)

    # a chunk of spare rows: the last copy's pad tail (and every copy
    # of a dispatch over the budget) lands there and is cut off
    out = jnp.zeros((1, rows + chunk) + x.shape[2:], x.dtype)
    return _copy_real(out, fed, chunk, copy)[:, :rows], total


def _pack_gathered(x, fed, rows):
    """``pack`` as one gather of whole rows, the rows past the real ones
    zero."""
    slots, step_len = x.shape[:2]
    fed, starts, total = _offsets(fed, step_len, rows)
    at = jnp.arange(rows, dtype=jnp.int32)
    # a packed row lies as far behind its place in ``x`` as the slots
    # that end at or before it have pads (an unfed slot ends where it
    # starts)
    pads = jnp.where((starts + fed)[:, None] <= at, (step_len - fed)[:, None],
                     0).sum(0)
    at = jnp.where(at < total, at + pads, slots * step_len)   # else no row
    out = x.reshape((-1,) + x.shape[2:]).at[at].get(mode="fill",
                                                    fill_value=0)
    return out[None], total


def unpack(x, fed, step_len, rows, tail):
    """``x (rows, ...)`` -> ``(slots, step_len) + tail`` (module
    docstring)."""
    form = _unpack_sliced if one_chunk(step_len) else _unpack_looped
    return form(x, fed, step_len, rows, tail)


def _chunk_reader(x, fed, step_len, rows, tail):
    """``real, fed``: ``real(b, i)`` is chunk ``i`` of slot ``b`` out of
    the packed ``x``, its rows past ``fed[b]`` zero; ``fed`` inside the
    budget."""
    chunk = _chunk(step_len)
    fed, starts, _ = _offsets(fed, step_len, rows)
    zero = (0,) * len(tail)
    # a chunk of spare rows, so that no copy is clamped back onto
    # another slot's rows
    x = jnp.pad(x.reshape((rows,) + tail),
                ((0, chunk),) + ((0, 0),) * len(tail))
    at = jnp.arange(chunk, dtype=jnp.int32) \
        .reshape((chunk,) + (1,) * len(tail))

    def real(b, i):
        block = lax.dynamic_slice(
            x, (_at(starts, b) + i * chunk,) + zero, (chunk,) + tail)
        return jnp.where(i * chunk + at < _at(fed, b), block,
                         jnp.zeros((), x.dtype))

    return real, fed


def _unpack_looped(x, fed, step_len, rows, tail):
    """``unpack`` as copies of chunks, as many as hold a real row."""
    chunk = _chunk(step_len)
    real, fed = _chunk_reader(x, fed, step_len, rows, tail)

    def copy(out, b, i):
        return lax.dynamic_update_slice(out, real(b, i)[None],
                                        (b, i * chunk) + (0,) * len(tail))

    out = jnp.zeros((fed.shape[0], step_len) + tail, x.dtype)
    return _copy_real(out, fed, chunk, copy)


def _unpack_sliced(x, fed, step_len, rows, tail):
    """``unpack`` of one chunk a slot: the loop's body once a slot."""
    real, fed = _chunk_reader(x, fed, step_len, rows, tail)
    return jnp.stack([real(b, 0) for b in range(fed.shape[0])])


def last(x, fed, step_len=None, rows=0):
    """``x (slots, S, ...)``, or the packed ``(1, rows, ...)`` of windows
    of ``step_len`` under a budget, -> each slot's last fed row
    ``(slots, 1, ...)`` (module docstring)."""
    if not rows:
        at = jnp.clip(fed.astype(jnp.int32), 1, x.shape[1]) - 1
        return x[jnp.arange(x.shape[0]), at][:, None]
    fed, starts, _ = _offsets(fed, step_len, rows)
    return x[0, jnp.clip(starts + fed - 1, 0, rows - 1)][:, None]


def _pack_infer(attrs, in_shapes):
    data_s, fed_s = in_shapes
    rows = parse_int(attrs.get("rows", 0))
    if data_s is None:
        return in_shapes, [None, (1,) if rows else fed_s], []
    fed_s = (data_s[0],)
    if not rows:
        return [data_s, fed_s], [data_s, fed_s], []
    return [data_s, fed_s], [(1, rows) + tuple(data_s[2:]), (1,)], []


@register("pack_rows", inputs=("data", "fed"), num_outputs=2,
          output_names=["output", "fed"],
          attr_spec={"rows": (parse_int, 0)}, infer_shape=_pack_infer)
def _pack_rows(attrs, data, fed):
    """``(slots, S, ...)`` rows and ``fed (slots,)`` in the packed view
    of ``rows`` rows; both as they are at ``rows = 0``."""
    rows = parse_int(attrs.get("rows", 0))
    return pack(data, fed, rows) if rows else (data, fed)


def _unpack_tail(attrs, data_s):
    return tuple(parse_tuple(attrs.get("shape")) or data_s[1:])


def _unpack_infer(attrs, in_shapes):
    data_s, fed_s = in_shapes
    step_len = parse_int(attrs["step_len"])
    rows = parse_int(attrs.get("rows", 0))
    if data_s is None:
        return in_shapes, [None], []
    if rows and data_s[0] != rows:
        raise ValueError(f"unpack_rows: {data_s[0]} rows under a budget "
                         f"of {rows}")
    if fed_s is None:
        if rows:
            return in_shapes, [None], []
        fed_s = (data_s[0] // step_len,)
    if not rows and fed_s[0] * step_len != data_s[0]:
        raise ValueError(f"unpack_rows: {data_s[0]} rows are not "
                         f"{fed_s[0]} slots of step_len {step_len}")
    return [data_s, fed_s], \
        [(fed_s[0], step_len) + _unpack_tail(attrs, data_s)], []


@register("unpack_rows", inputs=("data", "fed"),
          attr_spec={"step_len": (parse_int, None),
                     "rows": (parse_int, 0),
                     "shape": (parse_tuple, None)},
          infer_shape=_unpack_infer)
def _unpack_rows(attrs, data, fed):
    """Rows ``(N, ...)`` as ``(slots, step_len, ...)``, the trailing
    dimensions as ``shape`` where given: a reshape at ``rows = 0``, each
    slot's ``fed`` rows out of a packed block of ``rows`` otherwise."""
    step_len = parse_int(attrs["step_len"])
    rows = parse_int(attrs.get("rows", 0))
    tail = _unpack_tail(attrs, data.shape)
    if not rows:
        return jnp.reshape(data, (-1, step_len) + tail)
    return unpack(data, fed, step_len, rows, tail)


def _last_infer(attrs, in_shapes):
    data_s, fed_s = in_shapes
    rows = parse_int(attrs.get("rows", 0))
    if data_s is None:
        return in_shapes, [None], []
    if not rows:
        fed_s = (data_s[0],)
    elif tuple(data_s[:2]) != (1, rows):
        raise ValueError(f"last_rows: {tuple(data_s[:2])} rows under a "
                         f"budget of {rows}: the packed view is (1, {rows})")
    if fed_s is None:
        return in_shapes, [None], []
    return [data_s, fed_s], [(fed_s[0], 1) + tuple(data_s[2:])], []


@register("last_rows", inputs=("data", "fed"),
          attr_spec={"step_len": (parse_int, None),
                     "rows": (parse_int, 0)},
          infer_shape=_last_infer)
def _last_rows(attrs, data, fed):
    """Each slot's last fed row ``(slots, 1, ...)`` of ``(slots, S,
    ...)`` rows, or under a budget (``rows``, with the windows'
    ``step_len``) of the packed ``(1, rows, ...)``."""
    rows = parse_int(attrs.get("rows", 0))
    return last(data, fed, rows and parse_int(attrs["step_len"]), rows)
