"""The slot-pooled ``gpt2_rotary`` block, trained too, through what every
served block does (row ``gpt2_rotary`` of ``tests/decode_blocks.py``)."""
from decode_block_suite import *  # noqa: F401,F403

BLOCK = "gpt2_rotary"
