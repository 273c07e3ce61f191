"""Least time for what the mixers' recurrent part does in a window dispatch over the device time of the XLA Ops whose name carries ssm_ (the kernels ssm_update, a step of the recurrence for every slot fed one row, and ssm_scan, a chunk of the chunked form for the slots that prefill) per run of the top rung's longest window program, in percent. The least, from the median ring record (serve.decode.step, window > 1, top rung): ssm_touched (fed slots x mamba layers) x the cost ssm_state's bytes - one read and one write of a state and its convolution tail - and ssm_rows (real rows x mamba layers) x the cost ssm_row (the chunked form's operations a row and a row's operands once); max(FLOPs / peak, bytes / HBM peak). A riding slot costs one step of the recurrence and a prefilling slot its chunks: lower than ssm_decode_roofline by what the chunks' kernel costs beyond its rows' share. The mixer's row-wise prologue and gate (scope ssm_conv) are XLA fusions without a name in the device trace: their time is not in the denominator, and of the numerator they move a row's operands, 6 % of its bytes. Never clipped."""
import re

from chipbench import costs, kernel_time, trace
from chipbench.stats import median

_WINDOW_PROGRAM = re.compile(r"fwd_infer_(\d+)x(\d+)$")


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
        events, _top_rung_window_module(events), "ssm_")
    cost = obs.get("cost") or {}
    state, row = cost.get("ssm_state"), cost.get("ssm_row")
    recs = [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window", 1) > 1
            and "ssm_touched" in r and "ssm_rows" in r]
    if found is None or state is None or row is None or not recs:
        return None
    rung = max(r.get("rung", 0) for r in recs)
    recs = [r for r in recs if r.get("rung", 0) == rung]
    touched = median([r["ssm_touched"] for r in recs])
    rows = median([r["ssm_rows"] for r in recs])
    least_s, _bound = costs.roofline(
        {"flops": rows * row["flops"],
         "bytes": touched * state["bytes"] + rows * row["bytes"]},
        obs["device_kind"])
    return 100.0 * 1e3 * least_s / (found[0] / found[2])
