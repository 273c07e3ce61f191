"""% of the top rung's S=1 program's device time spent in the hyper-connection kernels (XLA Ops named mhc_*: mhc_pre, mhc_post): what a residual stream of copies mixed per token costs a decode step."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "mhc_")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]
