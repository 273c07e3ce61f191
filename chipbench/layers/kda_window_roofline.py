"""Least time for what the KDA mixers' recurrent part does in a window dispatch over the device time of the XLA Ops whose name carries kda_ (the kernels kda_update, a step of the delta rule for every slot fed one row, and kda_chunk, a chunk of the chunked form for the slots that prefill) per run of the top rung's longest window program, in percent. The least, from the median ring record (serve.decode.step, window > 1, top rung): kda_step_slots + kda_chunk_slots (fed slots x KDA layers) x the cost kda_decode's bytes - one read and one write of a state and its convolution tails - and kda_step_slots + kda_real_rows (real rows x KDA layers) x the cost kda_window (the recurrence's three head_dim x head_dim products a head and row - what the equations ask; the chunked form's triangular inverse and its chunk-local scores are its implementation's and are NOT counted, so they lower the share and cannot raise it - and a row's operands once); max(FLOPs / peak, bytes / HBM peak). The mixer's row-wise prologue, norm and gate (scope kda_conv) are XLA fusions without a name in the device trace: their time is not in the denominator. Never clipped."""
import re

from chipbench import costs, kernel_time, trace
from chipbench.stats import median

_WINDOW_PROGRAM = re.compile(r"fwd_infer_(\d+)x(\d+)$")
_FIELDS = ("kda_step_slots", "kda_chunk_slots", "kda_real_rows")


def _top_rung_window_module(events):
    best = None
    for name in trace.modules(events):
        m = _WINDOW_PROGRAM.search(name.split("(")[0])
        if m and int(m.group(2)) > 1:
            key = (int(m.group(1)), int(m.group(2)))
            if best is None or key > best[0]:
                best = (key, name)
    return None if best is None else best[1]


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, _top_rung_window_module(events), "kda_")
    cost = obs.get("cost") or {}
    state, row = cost.get("kda_decode"), cost.get("kda_window")
    recs = [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window", 1) > 1 and all(f in r for f in _FIELDS)]
    if found is None or state is None or row is None or not recs:
        return None
    rung = max(r.get("rung", 0) for r in recs)
    recs = [r for r in recs if r.get("rung", 0) == rung]
    steps, slots, rows = (median([r[f] for r in recs]) for f in _FIELDS)
    least_s, _bound = costs.roofline(
        {"flops": (steps + rows) * row["flops"],
         "bytes": (steps + slots) * state["bytes"]
         + (steps + rows) * row["bytes"]},
        obs["device_kind"])
    return 100.0 * 1e3 * least_s / (found[0] / found[2])
