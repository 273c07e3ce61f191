"""The Convolution and FullyConnected nodes' train FLOPs (the program's mfu.cost_table, one chip's share on four) at peaks.json's bf16 peak, over step.conv_ms, in percent. Never clipped."""
from chipbench import op_time


def read(obs):
    return op_time.op_roofline(obs)
