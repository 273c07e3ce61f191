"""max(FLOPs / peak, bytes / bandwidth) of the attention reads of one window dispatch (the architecture's cost attn_window: all layers, at the prefill chunk and the median live context, the attended keys alone counted - a sliding layer's at most its window) over the device time of the XLA Ops named window_attn per run of the top rung's window program, in percent. Never clipped. A program without the kernel (the parent's) reports nothing."""
import re

from chipbench import costs, kernel_time, trace

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
    cost = (obs.get("cost") or {}).get("attn_window")
    found = kernel_time.kernel_ms_in_module(
        events, _top_rung_window_module(events), "window_attn")
    if found is None or cost is None:
        return None
    least_s, _bound = costs.roofline(cost, obs["device_kind"],
                                     obs.get("chips", 1))
    kernel_ms, _program_ms, runs = found
    return 100.0 * least_s * 1e3 / (kernel_ms / runs)
