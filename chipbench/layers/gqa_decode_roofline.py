"""Least time to read the K and V rows MEASURED as attended in an S=1 dispatch (ring: serve.decode.step records with window 1, attn_attended positions over slots and layers x one position's K and V bytes of one layer, the architecture's cost gqa_row, over the HBM peak) over the attention read kernel's (XLA Ops named decode_attn) device time per S=1 dispatch, in percent. Never clipped. A program whose records lack attn_attended (the parent's) reports nothing."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "decode_attn")
    row = (obs.get("cost") or {}).get("gqa_row")
    rows = [r["attn_attended"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window") == 1 and "attn_attended" in r]
    if found is None or row is None or not rows:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
