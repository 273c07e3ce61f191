"""The slot-pooled ``gpt2`` block, trained too, through what every
served block does (row ``gpt2`` of ``tests/decode_blocks.py``)."""
from decode_block_suite import *  # noqa: F401,F403

BLOCK = "gpt2"
