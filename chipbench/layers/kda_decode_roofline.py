"""Least time for the steps of the delta rule MEASURED as taken in an S=1 dispatch (ring: serve.decode.step records with window 1, kda_step_slots = fed slots x KDA layers, x the cost kda_decode - one read and one write of a slot's float32 state and convolution tails, and the recurrence's three head_dim x head_dim products a head; max(FLOPs / peak, bytes / HBM peak): the bytes bind) over the device time of the XLA Ops whose name carries kda_ (the kernel kda_update) per S=1 dispatch of the top rung, in percent. Never clipped; counted as the least any implementation needs: the kernel's own tile of transposed columns (3 % of the state's bytes) and its rows are not in the numerator."""
from chipbench import costs, kernel_time
from chipbench.stats import median


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "kda_")
    state = (obs.get("cost") or {}).get("kda_decode")
    steps = [r["kda_step_slots"] for r in obs.get("ring") or []
             if r.get("kind") == "serve.decode.step"
             and r.get("window") == 1 and "kda_step_slots" in r]
    if found is None or state is None or not steps:
        return None
    kernel_ms, _program_ms, runs = found
    n = median(steps)
    least_s, _bound = costs.roofline(
        {"flops": n * state["flops"], "bytes": n * state["bytes"]},
        obs["device_kind"])
    return 100.0 * 1e3 * least_s / (kernel_ms / runs)
