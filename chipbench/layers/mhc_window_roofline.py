"""Least time to move the rows the PROGRAM mixed in a window dispatch (ring: serve.decode.step records with window > 1 of the top rung carry mhc_rows, the real rows x sub-layers; the packed window program runs its row-wise operations over its whole budget of rows, so the median record's real rows x the cost mhc_row's bytes - a row's least traffic a sub-layer, a join fused with the next read - over the HBM peak) over the hyper-connection kernels' (XLA Ops named mhc_*) device time per run of the top rung's longest window program, in percent. Never clipped; pads are no work, so a kernel that mixes the budget's pad rows reads lower."""
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
        events, _top_rung_window_module(events), "mhc_")
    row = (obs.get("cost") or {}).get("mhc_row")
    recs = [r for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window", 1) > 1 and "mhc_rows" in r]
    if found is None or row is None or not recs:
        return None
    rung = max(r.get("rung", 0) for r in recs)
    rows = [r["mhc_rows"] for r in recs if r.get("rung", 0) == rung]
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (found[0] / found[2])
