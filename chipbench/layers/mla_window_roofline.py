"""max(FLOPs / peak, bytes / bandwidth) of the latent-attention and selection kernels of one window dispatch (the architecture's cost mla_window: all layers, at the prefill chunk and the median live context, the selected keys alone counted) over the device time of the XLA Ops named mla_* and dsa_* per run of the top rung's window program, in percent. Never clipped."""
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
    cost = (obs.get("cost") or {}).get("mla_window")
    module = _top_rung_window_module(events)
    found = [kernel_time.kernel_ms_in_module(events, module, prefix)
             for prefix in ("mla_", "dsa_")]
    if None in found or cost is None:
        return None
    least_s, _bound = costs.roofline(cost, obs["device_kind"],
                                     obs.get("chips", 1))
    kernel_ms = sum(f[0] for f in found) / found[0][2]
    return 100.0 * least_s * 1e3 / kernel_ms
