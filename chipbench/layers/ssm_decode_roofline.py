"""Least time to read and write the recurrent states MEASURED as touched in an S=1 dispatch (ring: serve.decode.step records with window 1, ssm_touched = fed slots x mamba layers, x the cost ssm_state's bytes - one read and one write of a state and its convolution tail - over the HBM peak) over the device time of the XLA Ops whose name carries ssm_ (the kernel ssm_update) per S=1 dispatch of the top rung, in percent. Never clipped; counted as the least any implementation needs."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "ssm_")
    state = (obs.get("cost") or {}).get("ssm_state")
    touched = [r["ssm_touched"] for r in obs.get("ring") or []
               if r.get("kind") == "serve.decode.step"
               and r.get("window") == 1 and "ssm_touched" in r]
    if found is None or state is None or not touched:
        return None
    kernel_ms, _program_ms, runs = found
    peak = costs.peaks(obs["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * median(touched) * state["bytes"] / peak
    return 100.0 * least_ms / (kernel_ms / runs)
