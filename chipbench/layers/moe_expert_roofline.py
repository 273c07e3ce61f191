"""Least time to read the experts MEASURED as touched in an S=1 dispatch (ring: serve.decode.step records with window 1, moe_touched x one expert's bytes, over the HBM peak) over the grouped expert kernels' (XLA Ops named moe_gmm*) device time per S=1 dispatch, in percent. Never clipped."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "moe_gmm")
    expert = (obs.get("cost") or {}).get("moe_expert")
    touched = [r["moe_touched"] for r in obs.get("ring") or []
               if r.get("kind") == "serve.decode.step"
               and r.get("window") == 1 and "moe_touched" in r]
    if found is None or expert is None or not touched:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(touched) * expert["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
