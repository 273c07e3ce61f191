"""Least time to read the latent rows MEASURED as attended in an S=1 dispatch (ring: serve.decode.step records with window 1, mla_attended rows over slots and layers x one latent row's bytes, over the HBM peak) over the attention read kernel's (XLA Ops named mla_attn_decode) device time per S=1 dispatch, in percent. Never clipped. The kernel reads whole blocks of rows of 640 lanes for the 576 counted, so a perfect read is about 85."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events),
        "mla_attn_decode")
    row = (obs.get("cost") or {}).get("mla_row")
    rows = [r["mla_attended"] for r in obs.get("ring") or []
            if r.get("kind") == "serve.decode.step"
            and r.get("window") == 1 and "mla_attended" in r]
    if found is None or row is None or not rows:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(rows) * row["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
