"""Least time to move the rows MEASURED as mixed in an S=1 dispatch (ring: serve.decode.step records with window 1, mhc_rows = rows x sub-layers, x the cost mhc_row's bytes - a row's least traffic a sub-layer, a join fused with the next read - over the HBM peak) over the hyper-connection kernels' (XLA Ops named mhc_*) device time per S=1 dispatch, in percent. Never clipped; counted as the least any implementation needs. At 8 rows a dispatch it reads what 24 launches cost, not bandwidth."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "mhc_")
    row = (obs.get("cost") or {}).get("mhc_row")
    rows = [r["mhc_rows"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window") == 1 and "mhc_rows" in r]
    if found is None or row is None or not rows:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
