"""Device ms a step in the fused train step's instructions of phase unattributed (the program's operator table over the traced events, chipbench/op_time.py), on the chip step.device_ms reads."""
from chipbench import op_time


def read(obs):
    return op_time.phase_ms(obs, "unattributed")
