"""Device ms a step in the instructions whose heaviest member belongs to a Convolution or FullyConnected node, forward and backward (chipbench/op_time.py)."""
from chipbench import op_time


def read(obs):
    return op_time.op_ms(obs)
