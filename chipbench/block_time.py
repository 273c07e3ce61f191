"""The program of one block a slot in the profiler's trace, for the
readers of the per-layer metrics of a model that decodes by blocks
(``layers/block_step_roofline.py``, ``moe_block_roofline.py``,
``gqa_block_roofline.py``): its name from the ring's own records, so
that a run whose program has no such dispatch (every parent of PR 60)
reads nothing."""
from __future__ import annotations

import re

from . import trace


def block_records(obs):
    """The ring's records of dispatches of one block a slot (the field
    ``block``, which no other dispatch has)."""
    return [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step" and r.get("block")]


def top_rung_block_module(obs):
    """The name of the block program of the largest slot count in the
    trace - ``Executor.program_name`` calls it ``fwd_infer_<slots>x<L>``
    and the ``XLA Modules`` line ``jit_fwd_infer_<slots>x<L>(<
    fingerprint>)`` - with ``L`` the ring's ``block``; None where the
    ring has no block dispatch or the trace no such program."""
    records = block_records(obs)
    if not records:
        return None
    pattern = re.compile(rf"fwd_infer_(\d+)x{int(records[0]['block'])}$")
    best = None
    for name in trace.modules(obs.get("events") or []):
        m = pattern.search(name.split("(")[0])
        if m and (best is None or int(m.group(1)) > best[0]):
            best = (int(m.group(1)), name)
    return None if best is None else best[1]
