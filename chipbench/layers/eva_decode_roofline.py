"""Least time to read the state MEASURED as attended in an S=1 dispatch (ring: serve.decode.step records with window 1, eva_exact + eva_summary rows over slots and layers x one row's K and V bytes, over the HBM peak) over the attention read kernel's (XLA Ops named eva_attn_decode) device time per S=1 dispatch, in percent. Never clipped."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events),
        "eva_attn_decode")
    row = (obs.get("cost") or {}).get("eva_row")
    rows = [r["eva_exact"] + r["eva_summary"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window") == 1 and "eva_exact" in r]
    if found is None or row is None or not rows:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
