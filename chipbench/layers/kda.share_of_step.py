"""% of the top rung's S=1 program's device time spent in the XLA Ops whose name carries kda_ - the kernel kda_update, every slot's matrix state read and written once a KDA layer: what six states a slot cost a decode step beside its weights and experts. (The mixer's row-wise prologue, scope kda_conv, is XLA fusions that carry no name of their own in the device trace; at S=1 it is a few rows.) A program without such operations (every parent of PR 52) reads nothing."""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "kda_")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]
