"""The slot-pooled ``olmoe`` block, trained too, through what every
served block does (row ``olmoe`` of ``tests/decode_blocks.py``)."""
from decode_block_suite import *  # noqa: F401,F403

BLOCK = "olmoe"
