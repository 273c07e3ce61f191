"""% of the top rung's S=1 program's device time spent in the XLA Ops whose name carries ssm_ - the kernel ssm_update, every slot's recurrent state read and written once a mamba layer: what 36 states a slot cost a decode step beside its weights. (The mixer's row-wise prologue, scope ssm_conv, is XLA fusions that carry no name of their own in the device trace; at S=1 it is a few rows.)"""
from chipbench import kernel_time


def read(obs):
    events = obs.get("events") or []
    found = kernel_time.kernel_ms_in_module(
        events, kernel_time.top_rung_decode_module(events), "ssm_")
    if found is None:
        return None
    return 100.0 * found[0] / found[1]
