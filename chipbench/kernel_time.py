"""Device time of named kernels inside the runs of one program, from
the profiler's trace (chipbench/trace.py's flat events). For the
readers of per-layer metrics that a kernel's name identifies
(``layers/moe.expert_share_of_step.py``, ``layers/moe_expert_roofline
.py``); a trace without the program or without the kernel gives None,
and the metric is left out of the line."""
from __future__ import annotations

import bisect
import re

from . import trace

_S1_PROGRAM = re.compile(r"fwd_infer_(\d+)x1$")


def top_rung_decode_module(events):
    """The name of the S=1 decode program of the largest slot count in
    the trace: ``Executor.program_name`` calls it
    ``fwd_infer_<slots>x1`` and the ``XLA Modules`` line
    ``jit_fwd_infer_<slots>x1(<fingerprint>)``."""
    best = None
    for name in trace.modules(events):
        m = _S1_PROGRAM.search(name.split("(")[0])
        if m and (best is None or int(m.group(1)) > best[0]):
            best = (int(m.group(1)), name)
    return None if best is None else best[1]


def kernel_ms_in_module(events, module, kernel_substring):
    """``(kernel_ms, module_ms, runs)`` on the first chip: the summed
    device time of the ``XLA Ops`` whose name contains
    ``kernel_substring`` and that start inside a run of ``module``, the
    summed time of those runs, and their number. None when the module
    did not run or no such operation ran inside it."""
    planes = trace.device_planes(events)
    if not planes or module is None:
        return None
    runs = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events if e["plane"] == planes[0]
                  and e["line"] == trace.MODULE_LINE
                  and e["name"] == module)
    if not runs:
        return None
    starts = [a for a, _b in runs]
    kernel_ns = 0
    for e in events:
        if e["plane"] != planes[0] or e["line"] != trace.OP_LINE \
                or kernel_substring not in e["name"]:
            continue
        i = bisect.bisect_right(starts, e["start_ns"]) - 1
        if i >= 0 and e["start_ns"] < runs[i][1]:
            kernel_ns += e["dur_ns"]
    if not kernel_ns:
        return None
    return (kernel_ns / 1e6, sum(b - a for a, b in runs) / 1e6, len(runs))
