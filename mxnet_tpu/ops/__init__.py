"""Operator library: importing this package populates the registry."""
from .registry import OP_REGISTRY, get_op, list_ops, register, alias
from . import tensor  # noqa: F401 — registers tensor ops
from . import nn  # noqa: F401 — registers layer ops
from . import loss  # noqa: F401 — registers loss heads
from . import optimizer_op  # noqa: F401 — registers fused updates
from . import rnn_op  # noqa: F401 — registers the fused RNN
from . import moe  # noqa: F401 — registers RMSNorm and MoEFFN
from . import eva  # noqa: F401 — registers eva_attention_decode, GatedSiLU
from . import mla  # noqa: F401 — registers mla_attention_decode, dsa_index_select
from . import rows  # noqa: F401 — registers pack_rows, unpack_rows, last_rows
from . import mhc  # noqa: F401 — registers mhc_pre, mhc_post
from . import ssm  # noqa: F401 — registers ssm_mixer_decode
from . import kda  # noqa: F401 — registers kda_mixer_decode
from .. import operator as _custom_op  # noqa: F401 — registers Custom
from . import pallas_kernels  # noqa: F401 — Pallas kernel-tier variants
from . import quant  # noqa: F401 — int8 PTQ ops + graph rewrite
from . import cost  # noqa: F401 — seeds flops/bytes metadata (MFU)
