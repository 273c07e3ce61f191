"""Decoder-only transformer LM + KV-cache incremental decoder.

The model-zoo keystone (ROADMAP 1): a pre-LN, tied-embedding language
model composed entirely from the framework's fused ops — ``Embedding``
(fused-gather tier), ``LayerNorm`` (fused row-pass tier), ``attention``
(three gated lowerings: xla composition / Pallas flash / sequence-
sharded ring over the mesh's ``seq`` axis), ``FusedBiasGeLU`` (fused
dense epilogue) — so every hot op rides the kernel tier's numerics-gated
autotune, and ``Module.fit(spmd=True)`` on a (data x seq) mesh trains it
data+sequence-parallel with activations sharded ``P('data', 'seq')``.

Two graphs, one parameter set:

* ``get_symbol`` — the training/full-sequence forward: data ``(B, T)``
  token ids, label ``(B*T,)`` next-token ids (flat so the loss head's
  label slot is fed directly by the variable — exact class ids under
  mixed precision), softmax-CE loss over the tied embedding.
* ``get_decode_symbol`` — the inference decoder: ``(B, S)`` new tokens
  per step (S=1 for autoregressive generation), attention replaced by
  ``attention_decode`` whose fixed-capacity K/V cache rides executor
  AUX state (read+written on inference forwards), so N incremental
  steps reproduce the length-N full forward.

``KVCacheDecoder`` drives a bound decode module: host-side position
tracking (capacity overflow raises before the program clamps), learned-
position id feeding, cache reset. ``SyntheticLMIter`` is the synthetic
next-token data source the tests train against.
"""
from __future__ import annotations

import os

import numpy as np

from .. import symbol as sym
from .. import telemetry as _telemetry
from ..base import MXNetError
from ..ops.rows import one_chunk

__all__ = ["get_symbol", "get_decode_symbol", "SyntheticLMIter",
           "KVCacheDecoder", "BatchedKVCacheDecoder", "slot_state",
           "decode_procedure",
           "default_cache_capacity", "default_cache_dtype"]


def default_cache_capacity():
    """Decode cache capacity default: ``MXNET_LM_CACHE_CAPACITY``
    (docs/env_var.md), else 256 positions."""
    try:
        return int(os.environ.get("MXNET_LM_CACHE_CAPACITY", "256"))
    except ValueError:
        return 256


def default_cache_dtype():
    """Decode KV-cache storage dtype default: ``MXNET_LM_CACHE_DTYPE``
    (docs/env_var.md — ``fp8`` stores cache rows as float8_e4m3fn,
    quantized on write and dequantized on read), else None for
    compute-width cells."""
    return os.environ.get("MXNET_LM_CACHE_DTYPE") or None


def _proj(x, num_hidden, name, no_bias=False):
    """FullyConnected over the flattened (B*T, D) token axis: the
    reference FC contracts all non-batch dims, so sequence models fold
    (B, T) into rows first and unfold after."""
    flat = sym.Reshape(x, shape=(-3, 0), name=f"{name}_fold")
    return sym.FullyConnected(flat, num_hidden=num_hidden, name=name,
                              no_bias=no_bias)


def _qkv_heads(qkv, j, nm, pfx, split, d_model, norm=None):
    """Row block ``j`` of a fused (B*T, 3D) q/k/v projection as
    (B, H, T, dh) heads, ``norm`` applied to the whole projection
    before ``split(rows, name)`` lays it out as (B, T, H, dh)."""
    rows = sym.slice_axis(qkv, axis=1, begin=j * d_model,
                          end=(j + 1) * d_model, name=f"{pfx}_{nm}_rows")
    if norm is not None:
        rows = norm(rows, f"{pfx}_{nm}_norm")
    return sym.transpose(split(rows, f"{pfx}_{nm}_split"), axes=(0, 2, 1, 3),
                         name=f"{pfx}_{nm}")                 # (B, H, T, dh)


def _slots(rows, fed, T, name, shape=None):
    """The rows of a fed window graph as ``(slots, T, ...)``, where
    attention needs a slot's rows side by side (``ops/rows.py``; the
    trailing dimensions as ``shape`` where given)."""
    return sym.unpack_rows(rows, fed, step_len=T, name=name,
                           **({"shape": shape} if shape else {}))


def _packed_rows(x, fed, name, fold=(-3, 0)):
    """Attention's ``(slots, T, ...)`` back among the rows of a fed
    window graph, folded to ``(rows, width)`` by ``Reshape``'s ``fold``."""
    return sym.Reshape(sym.pack_rows(x, fed, name=f"{name}_pack")[0],
                       shape=fold, name=name)


# ---------------------------------------------------------------- a block
# A block is a record (``_spec``): what the caller asked for and, as data
# or as one function, what a model's layers differ in - the norm, the
# attention of layer i, its feed-forward, how a sub-layer's output joins
# the stream, the ends of the graph. ``_layer`` and ``_logits`` walk
# it; nothing below the spec constructors knows a model by its name.

def _norm(x, name, spec, **more):
    """The block's normalisation: ``spec["norm"]``, an operation and its
    attributes (and ``more``, a call's own)."""
    op, attrs = spec["norm"]
    return getattr(sym, op)(x, name=name, **attrs, **more)


def _fused_attention(x, fed, carry, i, spec):
    """GPT-2's and OLMoE's attention, the one kind with a training
    form: q, k and v as one projection of ``3 x d_model`` (OLMoE's
    without bias and with the whole q and k projections normed before
    the split into heads), then the KV-cache ``attention_decode`` in a
    decode graph (``per_slot``: a (B, 1) cursor vector, every batch row
    at its own position) or the full causal ``attention`` in a training
    one, under the same parameter names. A slot-pooled decode graph is
    a fed one (``fed``; None in the other two): the projection and the
    whole-width norms run over the packed rows, q, k and v are unpacked
    where they are split into heads, ``attention_decode`` advances each
    slot by its ``fed`` and its result is packed again."""
    pfx, T = f"{spec['name']}_l{i}", spec["T"]
    d_model, n_head = spec["d_model"], spec["n_head"]
    rotary = spec["pos_embed"] == "rotary"
    qkv = _proj(x, 3 * d_model, f"{pfx}_qkv",
                no_bias=not spec["bias"])                    # (B*T, 3D)

    def heads(rows, n, name):                                # (B, T, n, dh)
        if fed is not None:
            return _slots(rows, fed, T, name, shape=(n, d_model // n_head))
        return sym.Reshape(rows, shape=(-1, T, n, d_model // n_head),
                           name=name)

    if spec["qk_norm"]:
        qk_norm = lambda rows, name: _norm(rows, name, spec)  # noqa: E731
        split = lambda rows, name: heads(rows, n_head, name)  # noqa: E731
        q, k, v = (_qkv_heads(qkv, j, nm, pfx, split, d_model, norm)
                   for j, (nm, norm) in enumerate(
                       (("q", qk_norm), ("k", qk_norm), ("v", None))))
    else:
        qkv = heads(qkv, 3 * n_head, f"{pfx}_qkv_split")
        qkv = sym.transpose(qkv, axes=(0, 2, 1, 3),
                            name=f"{pfx}_qkv_t")             # (B, 3H, T, dh)
        q, k, v = (sym.slice_axis(qkv, axis=1, begin=j * n_head,
                                  end=(j + 1) * n_head, name=f"{pfx}_{nm}")
                   for j, nm in enumerate("qkv"))
    if spec["decode"]:
        att = sym.attention_decode(
            q, k, v, *(() if fed is None else (fed,)),
            capacity=spec["capacity"], rope=rotary,
            rope_base=spec["rope_base"], per_slot=spec["per_slot"],
            cache_dtype=spec["cache_dtype"] or "", name=f"{pfx}_attn",
            **({} if fed is None else {"fed": True}))
    else:
        if rotary:
            q = sym.RoPE(q, base=spec["rope_base"], name=f"{pfx}_rope_q")
            k = sym.RoPE(k, base=spec["rope_base"], name=f"{pfx}_rope_k")
        att = sym.attention(q, k, v, causal=True, name=f"{pfx}_attn")
    att = sym.transpose(att, axes=(0, 2, 1, 3),
                        name=f"{pfx}_attn_t")                # (B, T, H, dh)
    if fed is not None:
        return _packed_rows(att, fed, f"{pfx}_attn_merge", fold=(-3, -3)), \
            None
    return sym.Reshape(att, shape=(-3, -3), name=f"{pfx}_attn_merge"), None


def _eva_attention(x, fed, carry, i, spec):
    """EvaByte's: EVA attention with its state (``eva_attention_decode``:
    rotary inside the op, ``fed`` real tokens a slot) over q, k and v
    of one projection, unpacked for the op and its result packed
    again."""
    pfx, d_model, n_head = f"{spec['name']}_l{i}", spec["d_model"], \
        spec["n_head"]
    qkv = _proj(x, 3 * d_model, f"{pfx}_qkv", no_bias=True)  # (B*T, 3D)
    split = lambda rows, name: _slots(                       # noqa: E731
        rows, fed, spec["T"], name, shape=(n_head, d_model // n_head))
    q, k, v = (_qkv_heads(qkv, j, nm, pfx, split, d_model)
               for j, nm in enumerate("qkv"))
    att = sym.eva_attention_decode(
        q, k, v, fed, capacity=spec["capacity"], window=spec["window"],
        chunk=spec["chunk"], rope_base=spec["rope_base"],
        name=f"{pfx}_attn")
    att = sym.transpose(att, axes=(0, 2, 1, 3), name=f"{pfx}_attn_t")
    return _packed_rows(att, fed, f"{pfx}_attn_merge", fold=(-3, -3)), None


def _latent_attention(x, fed, selection, i, spec):
    """GLM-5.2's, A.X-K1's and Ling-3.0's (``spec["cfg"]``:
    ``_latent_spec``), no bias anywhere but the indexer's LayerNorm - the
    query through a normed bottleneck of ``q_lora_rank``, or where that
    is None one projection, and where ``cfg["head_gate"]`` is set the
    heads' outputs times a sigmoid of one more projection of the rows
    (one number a head) -: multi-head latent
    attention over a latent cache (``mla_attention_decode``) under the
    selection of positions that this layer's indexer computes
    (``dsa_index_select``, layers whose ``indexer_types`` entry is
    ``"full"``) or that ``selection`` brings from the nearest earlier
    such layer (``"shared"``: IndexShare), or over every position at or
    before the query (``"none"``: a model without an indexer; the
    rotary then under ``cfg["rope"]``'s scaling). The operands of the
    two decode ops alone are laid out ``(slots, S, .)`` (``_slots``)
    and attention's result is packed again (``_packed_rows``). The
    selection is what this kind carries to the next layer: it crosses
    layers outside the residual stream."""
    pfx, cfg, n_head = f"{spec['name']}_l{i}", spec["cfg"], spec["n_head"]
    capacity, rope_base = spec["capacity"], spec["rope_base"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    unfold = lambda rows, nm: _slots(                        # noqa: E731
        rows, fed, spec["T"], f"{pfx}_{nm}_unfold")

    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_attn_fold")  # (B*T, D)
    if cfg["q_lora_rank"] is None:      # the query projected directly
        q = sym.FullyConnected(rows, num_hidden=n_head * dq, no_bias=True,
                               name=f"{pfx}_q")
    else:
        c_q = _norm(
            sym.FullyConnected(rows, num_hidden=cfg["q_lora_rank"],
                               no_bias=True, name=f"{pfx}_q_a"),
            f"{pfx}_q_a_norm", spec)
        q = sym.FullyConnected(c_q, num_hidden=n_head * dq, no_bias=True,
                               name=f"{pfx}_q_b")
    kv = sym.FullyConnected(
        rows, num_hidden=cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
        no_bias=True, name=f"{pfx}_kv_a")
    if cfg["indexer_types"][i] == "full":
        n_idx, d_idx = cfg["index_n_heads"], cfg["index_head_dim"]
        q_idx = sym.FullyConnected(c_q, num_hidden=n_idx * d_idx,
                                   no_bias=True, name=f"{pfx}_idx_q")
        k_idx = sym.LayerNorm(
            sym.FullyConnected(rows, num_hidden=d_idx, no_bias=True,
                               name=f"{pfx}_idx_k"),
            name=f"{pfx}_idx_k_norm")
        w_idx = sym.FullyConnected(rows, num_hidden=n_idx, no_bias=True,
                                   name=f"{pfx}_idx_w")
        selection = sym.dsa_index_select(
            unfold(q_idx, "idx_q"), unfold(k_idx, "idx_k"),
            unfold(w_idx, "idx_w"),
            fed, capacity=capacity, n_heads=n_idx, head_dim=d_idx,
            rope_dim=cfg["qk_rope_head_dim"], topk=cfg["index_topk"],
            rope_base=rope_base, name=f"{pfx}_idx")
    dense = cfg["indexer_types"][i] == "none"
    att = sym.mla_attention_decode(
        unfold(q, "q"), unfold(kv, "kv"),
        *(() if dense else (selection,)), fed, capacity=capacity,
        n_heads=n_head, nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        kv_rank=cfg["kv_lora_rank"], rms_eps=spec["rms_eps"],
        rope_base=rope_base, name=f"{pfx}_attn",
        **({"selected": False} if dense else {}), **cfg["rope"])
    att = _packed_rows(att, fed, f"{pfx}_attn_merge")
    if cfg.get("head_gate"):            # one sigmoid a head and row
        gate = sym.Activation(
            sym.FullyConnected(rows, num_hidden=n_head, no_bias=True,
                               name=f"{pfx}_gate"),
            act_type="sigmoid", name=f"{pfx}_gate_sigmoid")
        att = sym.Reshape(
            sym.broadcast_mul(
                sym.Reshape(att, shape=(0, n_head, -1),
                            name=f"{pfx}_attn_heads"),
                sym.Reshape(gate, shape=(0, n_head, 1),
                            name=f"{pfx}_gate_heads"),
                name=f"{pfx}_attn_gated"),
            shape=(0, -1), name=f"{pfx}_attn_gated_merge")
    return att, selection


def _grouped_attention(x, fed, carry, i, spec):
    """Attention over grouped K/V heads (``n_head`` query heads on
    ``num_key_value_heads``, q and k normed per head), what differs a
    record (``spec["cfg"]``). Trinity's (``_afmoe_spec``): on a layer
    ``layer_types`` marks ``sliding_attention`` rotary positions and a
    window whose pools are rings, on a ``full_attention`` layer
    neither; its output gated by a sigmoid of a projection of the
    layer's input. SDAR's (``_sdar_spec``): no gate (``gate`` False),
    rotary on every layer (``rope_full``), no window, and the mask's
    upper edge the end of the query's block (``block_length``). q, k
    and v are unpacked before their per-head norms, where the S = 1
    program's text has them."""
    pfx, cfg, n_head = f"{spec['name']}_l{i}", spec["cfg"], spec["n_head"]
    n_kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    sliding = cfg["layer_types"][i] == "sliding_attention"
    gated = cfg.get("gate", True)

    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_attn_fold")  # (B*T, D)
    # q, k, v and the gate as the row blocks of one projection
    wide = sym.FullyConnected(
        rows, num_hidden=((2 if gated else 1) * n_head + 2 * n_kv) * dh,
        no_bias=True, name=f"{pfx}_qkvg" if gated else f"{pfx}_qkv")
    at, heads = 0, {}
    for nm, n in (("q", n_head), ("k", n_kv), ("v", n_kv)):
        part = sym.slice_axis(wide, axis=1, begin=at, end=at + n * dh,
                              name=f"{pfx}_{nm}_rows")
        at += n * dh
        part = _slots(part, fed, spec["T"], f"{pfx}_{nm}_split",
                      shape=(n, dh))
        if nm != "v":                      # normed per head, over dh
            part = _norm(part, f"{pfx}_{nm}_norm", spec)
        heads[nm] = sym.transpose(part, axes=(0, 2, 1, 3),
                                  name=f"{pfx}_{nm}")        # (B, n, T, dh)
    att = sym.attention_decode(
        heads["q"], heads["k"], heads["v"], fed, capacity=spec["capacity"],
        rope=sliding or cfg.get("rope_full", False),
        rope_base=spec["rope_base"], per_slot=True,
        kv_heads=n_kv, fed=True, name=f"{pfx}_attn",
        **({"window": cfg["sliding_window"], "ring": cfg["ring"]}
           if sliding else {}),
        **({"block": cfg["block_length"]} if cfg.get("block_length") else {}))
    att = sym.transpose(att, axes=(0, 2, 1, 3), name=f"{pfx}_attn_t")
    att = _packed_rows(att, fed, f"{pfx}_attn_merge", fold=(-3, -3))
    if not gated:
        return att, None
    gate = sym.slice_axis(wide, axis=1, begin=at, end=at + n_head * dh,
                          name=f"{pfx}_gate_rows")
    return att * sym.Activation(gate, act_type="sigmoid",
                                name=f"{pfx}_gate"), None


def _paired_heads(q, n_pairs, group, dh, name):
    """Query heads ``(B, T, 2 n_pairs group, dh)`` for K/V heads that
    lie two to a row of ``2 dh`` numbers (``[k_2p | k_2p+1]``, as the
    projection has them): each query widened to ``2 dh`` with its own
    numbers on its K/V head's half and zeros on the other, so that a
    score against the pair's row is the score against its own head."""
    q = sym.Reshape(q, shape=(0, 0, n_pairs, 2, group, dh),
                    name=f"{name}_pairs")
    halves = []
    for j, pad in enumerate(((0, dh), (dh, 0))):
        half = sym.slice_axis(q, axis=3, begin=j, end=j + 1,
                              name=f"{name}_half{j}")
        halves.append(sym.Pad(half, mode="constant",
                              pad_width=(0,) * 10 + pad,
                              name=f"{name}_half{j}_wide"))
    wide = sym.Concat(*halves, dim=3, name=f"{name}_wide")
    return sym.Reshape(wide, shape=(0, 0, -1, 2 * dh), name=f"{name}_heads")


def _unpaired_heads(att, n_pairs, group, dh, name):
    """Attention's ``(B, T, heads, 2 dh)`` over paired K/V heads back as
    ``(B, T, heads, dh)``: of each head's result the half its own V
    head lies on."""
    att = sym.Reshape(att, shape=(0, 0, n_pairs, 2, group, 2 * dh),
                      name=f"{name}_pairs")
    halves = [sym.slice_axis(
        sym.slice_axis(att, axis=3, begin=j, end=j + 1,
                       name=f"{name}_half{j}"),
        axis=5, begin=j * dh, end=(j + 1) * dh, name=f"{name}_half{j}_own")
        for j in range(2)]
    own = sym.Concat(*halves, dim=3, name=f"{name}_own")
    return sym.Reshape(own, shape=(0, 0, -1, dh), name=f"{name}_heads")


def _nope_attention(x, fed, i, spec):
    """Granite 4.0-H's and Nemotron-H's attention layers
    (``spec["cfg"]``: ``_granite_spec``, ``_nemotron_h_spec``):
    ``n_head`` query heads on ``num_key_value_heads`` K/V heads of
    ``head_dim`` (whatever ``d_model / n_head`` is), no bias, no norm,
    **no positions of any kind**, the scores times
    ``attention_multiplier`` in place of ``1 / sqrt(head width)`` where
    the block has one; q, k and v the row blocks of one projection. Where two
    K/V heads fit a row of 128 lanes or less (the published 8 heads of
    64) the pools hold them side by side - half the heads of twice the
    width, the same bytes - and each query is widened with zeros
    (``_paired_heads``): the decode kernels read whole lanes."""
    pfx, cfg, n_head = f"{spec['name']}_l{i}", spec["cfg"], spec["n_head"]
    n_kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    paired = n_kv % 2 == 0 and 2 * dh <= 128
    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_attn_fold")  # (B*T, D)
    wide = sym.FullyConnected(rows, num_hidden=(n_head + 2 * n_kv) * dh,
                              no_bias=True, name=f"{pfx}_qkv")
    at, heads = 0, {}
    for nm, n in (("q", n_head), ("k", n_kv), ("v", n_kv)):
        part = sym.slice_axis(wide, axis=1, begin=at, end=at + n * dh,
                              name=f"{pfx}_{nm}_rows")
        at += n * dh
        shape = (n // 2, 2 * dh) if paired and nm != "q" else (n, dh)
        part = _slots(part, fed, spec["T"], f"{pfx}_{nm}_split", shape=shape)
        if paired and nm == "q":
            part = _paired_heads(part, n_kv // 2, n_head // n_kv, dh,
                                 f"{pfx}_q")
        heads[nm] = sym.transpose(part, axes=(0, 2, 1, 3),
                                  name=f"{pfx}_{nm}")        # (B, n, T, dh)
    att = sym.attention_decode(
        heads["q"], heads["k"], heads["v"], fed, capacity=spec["capacity"],
        rope=False, per_slot=True, kv_heads=n_kv // 2 if paired else n_kv,
        fed=True, name=f"{pfx}_attn",
        **({"scale": cfg["attention_multiplier"]}
           if "attention_multiplier" in cfg else {}))
    att = sym.transpose(att, axes=(0, 2, 1, 3), name=f"{pfx}_attn_t")
    if paired:
        att = _unpaired_heads(att, n_kv // 2, n_head // n_kv, dh,
                              f"{pfx}_attn")
    return _packed_rows(att, fed, f"{pfx}_attn_merge", fold=(-3, -3))


def _mamba_mixer(x, fed, i, spec):
    """Granite 4.0-H's and Nemotron-H's Mamba-2 layers: one projection
    of the normed rows to ``[z | xBC | dt]``, the convolution and the
    selective state update over the rows as they lie
    (``ssm_mixer_decode``, ``ops/ssm.py``: it finds each slot's rows by
    ``fed`` in either view and costs by the real ones; B and C in
    ``mamba_n_groups`` groups), and RMSNorm of the gated numbers with a
    gain over all ``heads x head_dim`` of them, the statistic over each
    group's own; the layer's output projection follows in ``_layer``."""
    pfx, cfg = f"{spec['name']}_l{i}", spec["cfg"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    groups = cfg["mamba_n_groups"]
    grouped = {"groups": groups} if groups > 1 else {}
    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_mamba_fold")   # (B*T, D)
    wide = sym.FullyConnected(
        rows, num_hidden=2 * H * P + 2 * groups * N + H, no_bias=True,
        name=f"{pfx}_mamba_in")
    y = sym.ssm_mixer_decode(
        wide, fed, heads=H, head_dim=P, d_state=N,
        d_conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
        step_len=spec["T"], capacity=spec["capacity"], name=f"{pfx}_mamba",
        **grouped)
    return _norm(y, f"{pfx}_mamba_norm", spec, **grouped)


def _hybrid_mixer(x, fed, carry, i, spec):
    """Layer ``i``'s mixer by ``layer_types[i]``: ``"mamba"`` or
    ``"attention"``."""
    mixer = _mamba_mixer if spec["cfg"]["layer_types"][i] == "mamba" \
        else _nope_attention
    return mixer(x, fed, i, spec), None


def _kda_mixer(x, fed, i, spec):
    """Ling-3.0's linear layers, Kimi Delta Attention: one projection
    of the normed rows to ``[q | k | v | f | g | b]`` - query, key,
    value, the decay's and the output gate's full projections, one
    ``b`` a head -, then the convolutions, the delta-rule update and the
    gated per-head norm over the rows as they lie (``kda_mixer_decode``,
    ``ops/kda.py``); the layer's output projection follows in
    ``_layer``."""
    pfx, cfg = f"{spec['name']}_l{i}", spec["cfg"]
    H, D = spec["n_head"], cfg["head_dim"]
    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_kda_fold")    # (B*T, D)
    wide = sym.FullyConnected(rows, num_hidden=5 * H * D + H, no_bias=True,
                              name=f"{pfx}_kda_in")
    return sym.kda_mixer_decode(
        wide, fed, heads=H, head_dim=D, d_conv=cfg["short_conv_kernel_size"],
        chunk=cfg["kda_chunk"], step_len=spec["T"],
        capacity=spec["capacity"], lower_bound=cfg["kda_lower_bound"],
        rms_eps=spec["rms_eps"], name=f"{pfx}_kda")


def _ling_mixer(x, fed, carry, i, spec):
    """Layer ``i``'s mixer by ``layer_types[i]``: ``"kda"`` or
    ``"mla"`` (latent attention over every earlier position)."""
    if spec["cfg"]["layer_types"][i] == "kda":
        return _kda_mixer(x, fed, i, spec), None
    return _latent_attention(x, fed, None, i, spec)


def _ffn(x, fed_rows, i, spec):
    """Layer ``i``'s feed-forward over the rows of the normed stream
    ``x``: on the first ``spec["dense_layers"]`` layers the dense one
    (``spec["dense"]``: GPT-2's GeLU pair, or the gated-SiLU triple
    whose gate and up projections are one matmul), after them the
    routed experts (``MoEFFN`` with ``spec["moe"]``'s attributes; in a
    fed graph it takes ``fed_rows``, so that the pads of a window are
    routed nowhere)."""
    pfx, d_model = f"{spec['name']}_l{i}", spec["d_model"]
    if i >= spec["dense_layers"]:
        rows = sym.Reshape(x, shape=(-3, 0),
                           name=f"{pfx}_{spec['moe_fold']}")
        return sym.MoEFFN(rows, *(() if fed_rows is None else (fed_rows,)),
                          name=f"{pfx}_moe", **spec["moe"])  # (B*T, D)
    kind, width = spec["dense"]
    if kind == "gelu":
        # dense -> GeLU as the fused epilogue pair: the matmul emits raw
        # rows (no_bias) and FusedBiasGeLU folds bias+erf-GeLU in one pass
        h = _proj(x, width, f"{pfx}_ffn1", no_bias=True)
        h = sym.FusedBiasGeLU(h, name=f"{pfx}_ffn_gelu")
        return sym.FullyConnected(h, num_hidden=d_model, name=f"{pfx}_ffn2")
    rows = sym.Reshape(x, shape=(-3, 0), name=f"{pfx}_ffn_fold")
    h = sym.FullyConnected(rows, num_hidden=2 * width, no_bias=True,
                           name=f"{pfx}_ffn_gate_up")
    h = sym.GatedSiLU(h, name=f"{pfx}_ffn_act")
    return sym.FullyConnected(h, num_hidden=d_model, no_bias=True,
                              name=f"{pfx}_ffn_down")


#: a sub-layer's names: its output projection or last matmul, its dropout
#: and, where a block norms what it adds, that norm
_SUB_LAYERS = {"proj": ("drop1", "post_attn_ln"),
               "ffn": ("drop2", "post_ffn_ln")}


def _residual(x, h, pfx, sub, spec):
    """``x + h`` for the rows ``h`` of sub-layer ``sub``: back in the
    stream's shape (``reshape_like`` in a fed graph, whose view of the
    rows only ``ops/rows.py`` knows), through dropout in a training
    graph, then added as they are, after a cast to a float32 stream
    (``spec["residual"] == "float32"``: EvaByte) or normed
    (``"normed"``: Trinity), and times the block's residual multiplier
    where it has one (``spec["multipliers"]``: Granite)."""
    drop, post_norm = _SUB_LAYERS[sub]
    h = sym.reshape_like(h, x, name=f"{pfx}_{sub}_unfold") if spec["fed"] \
        else sym.Reshape(h, shape=(-1, spec["T"], spec["d_model"]),
                         name=f"{pfx}_{sub}_unfold")
    if spec["dropout"]:
        h = sym.Dropout(h, p=spec["dropout"], name=f"{pfx}_{drop}")
    if spec["residual"] == "float32":
        h = sym.Cast(h, dtype="float32", name=f"{pfx}_{sub}_f32")
    elif spec["residual"] == "normed":
        h = _norm(h, f"{pfx}_{post_norm}", spec)
    if spec["multipliers"]:             # Granite's residual_multiplier
        h = h * spec["multipliers"]["residual"]
    return x + h


def _sub_layer(x, pfx, sub, spec):
    """``(u, join)`` of sub-layer ``sub`` over the stream ``x``, as the
    record's ``residual`` has it: ``u`` is what the sub-layer reads and
    ``join(h)`` the stream after its output ``h``. Every value but one
    reads the stream itself and adds to it (``_residual``). ``"hyper"``
    (``spec["hyper"]``: Xing4.0's manifold-constrained hyper-
    connections, ``ops/mhc.py``): the stream is ``n`` copies a row, the
    sub-layer reads ``mhc_pre``'s mix of them and ``mhc_post`` writes
    its output back beside their doubly-stochastic mix, both by the
    sub-layer's own mapping of the token's whole stream."""
    if spec["residual"] != "hyper":
        return x, lambda h: _residual(x, h, pfx, sub, spec)
    hyper = spec["hyper"]
    u, post, res = sym.mhc_pre(x, name=f"{pfx}_{sub}_mhc", **hyper)
    return u, lambda h: sym.mhc_post(x, h, post, res, n=hyper["n"],
                                     name=f"{pfx}_{sub}_mhc_join")


def _layer(x, fed, fed_rows, carry, i, spec):
    """Layer ``i`` of any block, pre-norm: ``x = x + proj(attention(
    norm(x)))``, then ``x = x + ffn(norm(x))`` - or the one of the two
    that ``spec["sub_layers"][i]`` names, where a block's layers are a
    mixer alone or a feed-forward alone (Nemotron-H) -, what a sub-layer
    reads and how its output joins the stream as ``_sub_layer`` has
    them. In
    a fed graph ``x`` and every row-wise operation are in the packed
    view of the window's rows (``ops/rows.py``: all ``slots x S`` of
    them, or the real ones under a budget, ``fed_rows`` their count as
    ``MoEFFN`` takes it). Returns ``(x, carry)``: what the attention
    hands its next layer."""
    pfx = f"{spec['name']}_l{i}"
    subs = spec["sub_layers"][i] if spec["sub_layers"] else _SUB_LAYERS
    if "proj" in subs:
        u, join = _sub_layer(x, pfx, "proj", spec)
        att, carry = spec["attention"](_norm(u, f"{pfx}_ln1", spec), fed,
                                       carry, i, spec)
        proj = sym.FullyConnected(
            att, num_hidden=spec["d_model"], name=f"{pfx}_proj",
            **({} if spec["bias"] else {"no_bias": True}))
        x = join(proj)
    if "ffn" in subs:
        u, join = _sub_layer(x, pfx, "ffn", spec)
        x = join(_ffn(_norm(u, f"{pfx}_ln2", spec), fed_rows, i, spec))
    return x, carry


def _head(x, tok_w, spec):
    """Final norm and the output head over the folded (B*T, D) rows:
    tied to the token embedding (one weight, two gradients), or the
    untied ``{name}_head_weight`` of ``spec["heads"]`` consecutive
    blocks of ``vocab_size`` rows. A stream of copies (``"hyper"``) is
    summed to one first; a block with multipliers (Granite: tied)
    divides the logits by its ``logits_scaling``."""
    name = spec["name"]
    if spec["residual"] == "hyper":     # the copies summed: one stream
        x = sym.sum(sym.Reshape(x, shape=(0, 0, spec["hyper"]["n"], -1),
                                name=f"{name}_copies"),
                    axis=2, name=f"{name}_copies_sum")
    flat = sym.Reshape(_norm(x, f"{name}_ln_f", spec), shape=(-3, 0),
                       name=f"{name}_head_fold")
    if spec["multipliers"]:             # Granite's logits_scaling
        return sym.dot(flat, tok_w, transpose_b=True,
                       name=f"{name}_logits_raw") \
            / spec["multipliers"]["logits"]
    if spec["tie_head"]:
        return sym.dot(flat, tok_w, transpose_b=True,
                       name=f"{name}_logits")                # (B*T, V)
    return sym.FullyConnected(
        flat, weight=sym.var(f"{name}_head_weight"),
        num_hidden=spec["heads"] * spec["vocab_size"], no_bias=True,
        name=f"{name}_logits")                               # (B*T, P*V)


# -------------------------------------------------- the spec constructors
def _rms(spec, **more):
    return ("RMSNorm", {"eps": float(spec["rms_eps"]), **more})


def _check_heads(spec):
    d_model, n_head, pos_embed = (spec[k] for k in
                                  ("d_model", "n_head", "pos_embed"))
    if d_model % n_head:
        raise MXNetError(f"d_model {d_model} must divide n_head {n_head}")
    if (d_model // n_head) % 2:
        raise MXNetError("head dim must be even (RoPE rotates pairs)")
    if pos_embed not in ("rotary", "learned"):
        raise MXNetError(f"pos_embed {pos_embed!r}: 'rotary' or 'learned'")


def _check_served(spec):
    """The blocks whose attention exists as decode ops alone: the
    slot-pooled decode graph and nothing else builds them."""
    block = spec["block"]
    if not spec["decode"]:
        raise MXNetError(
            f"block={block!r} is served, not trained: its attention "
            "exists as decode ops alone (get_decode_symbol(per_slot=True); "
            "the plain full forward is the benchmark's reference)")
    if not spec["per_slot"] or spec["cache_dtype"]:
        raise MXNetError(f"block={block!r} is the slot-pooled decode "
                         "graph (per_slot=True) with state at the "
                         "compute width (no cache_dtype)")


def _gpt2_spec(spec):
    """GPT-2's block: LayerNorm, fused q/k/v with bias, a GeLU
    feed-forward of four times the width; learned or rotary positions,
    tied or untied head."""
    _check_heads(spec)
    return dict(spec, norm=("LayerNorm", {}), attention=_fused_attention,
                bias=True, qk_norm=False, dense=("gelu", 4 * spec["d_model"]),
                dense_layers=spec["n_layer"], fed=spec["per_slot"])


def _olmoe_spec(spec):
    """OLMoE's block (arXiv:2409.02060): RMSNorm, fused q/k/v without
    bias, RMSNorm of the whole q and k projections before the split
    into heads, and on every layer the routed expert feed-forward
    ``MoEFFN`` (``n_expert`` experts of ``expert_width``, ``top_k`` a
    token); no bias anywhere, rotary, so the graph has no ``pos_ids``
    input. OLMoE also unties the head (``tie_head=False``) and leaves
    the embedding unscaled (``embed_scale=False``)."""
    n_expert, top_k, width = (spec[k] for k in
                              ("n_expert", "top_k", "expert_width"))
    if spec["pos_embed"] != "rotary":
        raise MXNetError("block='olmoe' is rotary (no position table)")
    if not (n_expert and top_k and width) or top_k > n_expert:
        raise MXNetError(
            "block='olmoe' needs n_expert >= top_k >= 1 and "
            f"expert_width (got {n_expert}, {top_k}, {width})")
    _check_heads(spec)
    fed = spec["per_slot"]
    return dict(spec, norm=_rms(spec), attention=_fused_attention,
                bias=False, qk_norm=True, dense_layers=0,
                moe_fold="moe_fold", fed=fed,
                moe=dict(**({"step_len": spec["T"]} if fed else {}),
                         num_experts=int(n_expert), num_hidden=int(width),
                         top_k=int(top_k),
                         norm_topk=bool(spec["norm_topk"])))


def _eva_spec(spec):
    """EvaByte's block (per-slot only): ``h = x + Attn(N(x))``, ``y = h
    + FFN(N(h))`` with the residual stream and both adds in float32,
    the matmuls at the compute width; RMSNorm whose gain is stored as
    its distance from one, handed on at the compute width; EVA
    attention over ``window`` exact positions and one summary per
    ``chunk`` of everything older (``ops/eva.py``); a dense gated-SiLU
    feed-forward of ``ffn_width``; an untied head of ``n_pred_heads``
    consecutive blocks of ``vocab_size`` columns; no bias anywhere. Its
    state is not a row per position, so the graph takes one more input,
    ``fed`` ``(slots,)`` int32: how many of each slot's ``step_len``
    tokens are real. The program advances a slot's state by exactly
    that. The output is head 0's ``(B, step_len, vocab)`` logits - the
    next byte, what a scheduler samples - or, with ``multibyte``, all
    heads' ``(B, step_len, n_pred_heads, vocab)``."""
    _check_served(spec)
    if spec["pos_embed"] != "rotary":
        raise MXNetError("block='evabyte' is rotary (no position table)")
    if not spec["ffn_width"] or int(spec["n_pred_heads"]) < 1:
        raise MXNetError("block='evabyte' needs ffn_width and "
                         "n_pred_heads >= 1")
    _check_heads(spec)
    return dict(spec, norm=_rms(spec, unit_offset=True, cast_to_gain=True),
                attention=_eva_attention, window=int(spec["window"]),
                chunk=int(spec["chunk"]), bias=False,
                dense=("gated", int(spec["ffn_width"])),
                dense_layers=spec["n_layer"], fed=True, residual="float32",
                tie_head=False, embed_scale=False,
                heads=int(spec["n_pred_heads"]), next_byte=True)


#: the keys of GLM-5.2's published ``config.json`` that
#: ``block="glm_dsa"`` reads (``get_decode_symbol(glm=...)``), and
#: ``held``: the (first, count) of the routed experts this graph holds
GLM_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "index_n_heads",
            "index_head_dim", "index_topk", "indexer_types",
            "first_k_dense_replace", "intermediate_size",
            "moe_intermediate_size", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "norm_topk_prob")


#: the keys of A.X-K1's published ``config.json`` (``model_type axk1``)
#: that ``block="axk1"`` reads (``get_decode_symbol(axk1=...)``): the
#: latent-attention block's without an indexer, the router's groups and
#: the rotary's scaling (``rope_scaling``: YaRN's ``factor``,
#: ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
#: ``mscale``, ``mscale_all_dim``, or None); ``held`` as above
AXK1_KEYS = tuple(k for k in GLM_KEYS if not k.startswith("index")) \
    + ("n_group", "topk_group", "rope_scaling")


def _latent_spec(spec, given, kinds, router, rope):
    """The latent-attention block (per-slot only), one block for two
    models: pre-norm, ``_latent_attention`` over one row of
    ``kv_lora_rank + qk_rope_head_dim`` numbers a position
    (``ops/mla.py``), a dense gated-SiLU feed-forward on the first
    ``first_k_dense_replace`` layers and sigmoid-routed experts beside
    a shared one after (``MoEFFN``, the choice by ``router``), of which
    this graph holds ``held``; untied head, unscaled embedding. What
    the two models differ in arrives as ``kinds`` (``indexer_types``),
    ``router`` (``MoEFFN``'s attributes of the choice) and ``rope``
    (``mla_attention_decode``'s rotary scaling, empty for the plain
    rotary). The graph takes ``fed`` like EvaByte's and advances by it,
    but its state is a row per position in every pool (``"rows"``), so
    the driver rewinds, captures and restores it as it does a K/V
    cache."""
    first, count = given.get("held") or (0, int(given["n_routed_experts"]))
    cfg = dict(given, indexer_types=kinds, rope=rope,
               held=(int(first), int(count)))
    return dict(
        spec, cfg=cfg, norm=_rms(spec), attention=_latent_attention,
        bias=False, dense=("gated", cfg["intermediate_size"]),
        dense_layers=cfg["first_k_dense_replace"], moe_fold="ffn_fold",
        moe=dict(step_len=spec["T"], num_experts=cfg["n_routed_experts"],
                 num_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 norm_topk=cfg["norm_topk_prob"], scoring="sigmoid",
                 scaling=cfg["routed_scaling_factor"],
                 held_first=int(first), held_count=int(count),
                 shared_hidden=cfg["n_shared_experts"]
                 * cfg["moe_intermediate_size"], **router),
        fed=True, pos_embed="rotary", tie_head=False, embed_scale=False)


def _given_keys(spec, arg, keys):
    """The published keys a served block was handed as ``arg``, once
    the graph asked for is one it has (``_check_served``)."""
    _check_served(spec)
    given = dict(spec[arg] or {})
    missing = [k for k in keys if k not in given]
    if missing:
        raise MXNetError(
            f"block={spec['block']!r} needs {arg}= with {missing}")
    return given


def _glm_spec(spec):
    """GLM-5.2's block from ``glm``, the published config's keys
    (``GLM_KEYS``) and optionally ``held``: the latent block
    (``_latent_spec``) attended under the ``index_topk`` positions a
    learned indexer selects on the layers ``indexer_types`` marks
    ``"full"`` and the layers marked ``"shared"`` reuse; a sigmoid
    router with a correction bias."""
    glm = _given_keys(spec, "glm", GLM_KEYS)
    kinds, n_layer = list(glm["indexer_types"]), spec["n_layer"]
    if len(kinds) != n_layer or not kinds or kinds[0] != "full" \
            or set(kinds) - {"full", "shared"}:
        raise MXNetError(
            f"block='glm_dsa': indexer_types {kinds} must name "
            f"'full' or 'shared' for each of {n_layer} layers, the "
            "first 'full' (a shared layer attends the set the nearest "
            "earlier full layer chose)")
    return _latent_spec(spec, glm, kinds, {"router_bias": True}, {})


def _yarn_rope(given, block):
    """``mla_attention_decode``'s rotary attributes from a published
    ``rope_scaling`` (YaRN's, or None: the plain rotary)."""
    yarn = dict(given["rope_scaling"] or {})
    if yarn and yarn.get("type", yarn.get("rope_type")) != "yarn":
        raise MXNetError(f"block={block!r}: rope_scaling {yarn} is not "
                         "YaRN's")
    return {} if not yarn else {
        "rope_factor": float(yarn["factor"]),
        "rope_original_positions":
            int(yarn["original_max_position_embeddings"]),
        "rope_beta_fast": float(yarn.get("beta_fast", 32)),
        "rope_beta_slow": float(yarn.get("beta_slow", 1)),
        "rope_mscale": float(yarn.get("mscale", 1)),
        "rope_mscale_all_dim": float(yarn.get("mscale_all_dim", 0))}


def _axk1_spec(spec):
    """A.X-K1's block from ``axk1``, its published keys (``AXK1_KEYS``)
    and optionally ``held``: the same latent block without an indexer
    - latent attention over every position at or before the query, its
    rotary under ``rope_scaling`` (YaRN: blended frequencies and a
    larger softmax scale) - and a sigmoid router without a correction
    bias that chooses inside the ``topk_group`` best of ``n_group``
    groups of experts. Inputs, state and driver contract are
    ``glm_dsa``'s."""
    axk1 = _given_keys(spec, "axk1", AXK1_KEYS)
    return _latent_spec(
        spec, axk1, ["none"] * spec["n_layer"],
        {"router_bias": False, "n_group": int(axk1["n_group"]),
         "topk_group": int(axk1["topk_group"])}, _yarn_rope(axk1, "axk1"))


#: the keys of Xing4.0's published ``config.json`` (``model_type
#: xing4_0``) that ``block="xing4"`` reads (``get_decode_symbol(xing4=
#: ...)``): A.X-K1's and the hyper-connections' - the copies of the
#: stream, the Sinkhorn iterations and their epsilon, the clamp of the
#: mix's logits; ``held`` as above
XING4_KEYS = AXK1_KEYS + ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                          "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def _xing4_spec(spec):
    """Xing4.0's block from ``xing4``, its published keys
    (``XING4_KEYS``): the latent block without an indexer under YaRN as
    A.X-K1 has it, a sigmoid router with a correction bias (GLM-5.2's)
    over ``n_group`` groups (1 as published: an ungrouped choice), and
    the one thing neither has - a residual stream of ``hc_mult`` copies
    a token, read and joined through manifold-constrained
    hyper-connections (``residual="hyper"``: ``_sub_layer``,
    ``ops/mhc.py``), copied in from the embedding and summed before the
    final norm. Inputs, state and driver contract are ``glm_dsa``'s."""
    xing4 = _given_keys(spec, "xing4", XING4_KEYS)
    if int(xing4["hc_mult"]) < 1:
        raise MXNetError(f"block='xing4': hc_mult {xing4['hc_mult']} "
                         "copies of the stream")
    latent = _latent_spec(
        spec, xing4, ["none"] * spec["n_layer"],
        {"router_bias": True, "n_group": int(xing4["n_group"]),
         "topk_group": int(xing4["topk_group"])},
        _yarn_rope(xing4, "xing4"))
    return dict(latent, residual="hyper", hyper=dict(
        n=int(xing4["hc_mult"]), iters=int(xing4["hc_sinkhorn_iters"]),
        eps=float(xing4["hc_eps"]), rms_eps=float(spec["rms_eps"]),
        clamp_min=float(xing4["mhc_h_res_clamp_min"]),
        clamp_max=float(xing4["mhc_h_res_clamp_max"])))


#: the keys of Trinity's published ``config.json`` (``model_type
#: afmoe``) that ``block="afmoe"`` reads (``get_decode_symbol(afmoe=...)``);
#: ``layer_types`` names the layers that are run, one entry each
AFMOE_KEYS = ("num_key_value_heads", "head_dim", "sliding_window",
              "layer_types", "num_dense_layers", "intermediate_size",
              "moe_intermediate_size", "num_experts", "num_experts_per_tok",
              "num_shared_experts", "route_norm", "route_scale")


def ring_rows(window, step_len):
    """Rows of a sliding layer's ring: the window and the largest
    dispatch's rows, rounded up to the read's key block (512 rows; 8
    at sizes under that)."""
    rows = int(window) + int(step_len)
    unit = 512 if rows >= 512 else 8
    return -(-rows // unit) * unit


def _afmoe_spec(spec):
    """Trinity's block (per-slot only) from ``afmoe``, the published
    config's keys (``AFMOE_KEYS``; ``layer_types`` one entry a layer
    that is run), no bias anywhere: ``x = x + N(Attn(N(x)))``, then
    ``x = x + N(FF(N(x)))`` - each sub-layer's output is normed before
    it is added, four norms a layer. ``_grouped_attention``: layers
    marked ``sliding_attention`` are rotary and attend a window of
    ``sliding_window`` positions, layers marked ``full_attention`` have
    no positions and attend everything. A scaled embedding
    (``embed_scale``), a dense gated-SiLU feed-forward on the first
    ``num_dense_layers`` layers and sigmoid-routed experts, all held,
    beside a shared one after; untied head. **Capacity per kind of
    layer**: a full layer's pools hold ``capacity`` rows (``"rows"``);
    a sliding layer's are rings of ``ring_rows(sliding_window,
    max_step_len)`` rows, whatever the capacity (``"ring"``; a ring
    that would be as long as the capacity is a pool of a row per
    position instead). ``max_step_len`` is the largest ``step_len`` of
    the graphs that share the pools (default: this graph's): every
    graph of one engine names the same. The graph takes ``fed`` and
    advances by it; with a ring it is not positional (see
    ``BatchedKVCacheDecoder``)."""
    cfg = _given_keys(spec, "afmoe", AFMOE_KEYS)
    kinds, n_layer, n_head = list(cfg["layer_types"]), spec["n_layer"], \
        spec["n_head"]
    if len(kinds) != n_layer or \
            set(kinds) - {"sliding_attention", "full_attention"}:
        raise MXNetError(
            f"block='afmoe': layer_types {kinds} must name "
            "'sliding_attention' or 'full_attention' for each of "
            f"{n_layer} layers")
    if n_head % cfg["num_key_value_heads"] or cfg["head_dim"] % 2:
        raise MXNetError(
            f"block='afmoe': {n_head} query heads on "
            f"{cfg['num_key_value_heads']} K/V heads of "
            f"{cfg['head_dim']}: the K/V heads divide the query "
            "heads, and a head's width is even (rotary pairs)")
    ring = ring_rows(cfg["sliding_window"],
                     max(spec["T"], spec["max_step_len"] or 1))
    # a ring as long as the context would hold a row per position
    cfg.update(layer_types=kinds, ring=ring if ring < spec["capacity"] else 0)
    return dict(
        spec, cfg=cfg, norm=_rms(spec), attention=_grouped_attention,
        bias=False, dense=("gated", cfg["intermediate_size"]),
        dense_layers=cfg["num_dense_layers"], moe_fold="ffn_fold",
        moe=dict(step_len=spec["T"], num_experts=cfg["num_experts"],
                 num_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 norm_topk=cfg["route_norm"], scoring="sigmoid",
                 router_bias=True, scaling=cfg["route_scale"],
                 shared_hidden=cfg["num_shared_experts"]
                 * cfg["moe_intermediate_size"]),
        fed=True, pos_embed="rotary", residual="normed", tie_head=False)


#: the keys of SDAR's published ``config.json`` (``model_type sdar_moe``)
#: that ``block="sdar_moe"`` reads (``get_decode_symbol(sdar=...)``), and
#: after them how the model decodes, which the file does not state and
#: the published generation procedure does (``DECODE_KEYS``)
SDAR_KEYS = ("num_key_value_heads", "head_dim", "num_experts",
             "num_experts_per_tok", "moe_intermediate_size",
             "norm_topk_prob", "block_length", "mask_token_id",
             "denoising_steps", "remasking", "confidence_threshold")

#: how a graph that decodes by blocks says so (``decode_procedure``):
#: the block's positions, the id that stands for a position not yet
#: decided, and a request's defaults - the feeds a block is denoised in,
#: which positions a feed decides, the confidence past which a position
#: is decided whatever the quota
DECODE_KEYS = ("block_length", "mask_token_id", "denoising_steps",
               "remasking", "confidence_threshold")

#: ``remasking``: a feed decides its quota's most confident positions
#: (``low_confidence_static``), or those and every other whose
#: confidence passes the threshold (``low_confidence_dynamic``)
REMASKING = ("low_confidence_static", "low_confidence_dynamic")


def _sdar_spec(spec):
    """SDAR's block (per-slot only) from ``sdar``, the published
    config's keys and the published generation procedure's
    (``SDAR_KEYS``), no bias anywhere: RMSNorm, ``_grouped_attention``
    without gate, window or ring and with rotary positions on every
    layer, softmax-routed experts on every layer (all held, the chosen
    weights normed where ``norm_topk_prob``), no shared expert, an
    unscaled embedding and an untied head - under one changed rule: a
    query attends every key up to the END of its own block of
    ``block_length`` positions (``attention_decode(block=)``). **The
    graph says how it decodes** (``decode_procedure``): a step is a
    block of ``block_length`` positions, some of them
    ``mask_token_id``, fed until every position is decided and then
    once more to keep its keys and values; row ``t`` of the logits
    scores the token AT position ``t``."""
    cfg = _given_keys(spec, "sdar", SDAR_KEYS)
    n_head, L = spec["n_head"], int(cfg["block_length"])
    if n_head % cfg["num_key_value_heads"] or cfg["head_dim"] % 2:
        raise MXNetError(
            f"block='sdar_moe': {n_head} query heads on "
            f"{cfg['num_key_value_heads']} K/V heads of "
            f"{cfg['head_dim']}: the K/V heads divide the query "
            "heads, and a head's width is even (rotary pairs)")
    if L < 1 or spec["T"] % L and spec["T"] != 1 or spec["capacity"] % L:
        raise MXNetError(
            f"block='sdar_moe': block_length {L} divides neither the "
            f"{spec['T']} rows a slot of this graph nor the capacity "
            f"{spec['capacity']}: every dispatch is whole blocks (the "
            "S = 1 graph is bound beside them and serves no request)")
    steps = int(cfg["denoising_steps"])
    if not 1 <= steps <= L or cfg["remasking"] not in REMASKING \
            or not 0 <= int(cfg["mask_token_id"]) < spec["vocab_size"]:
        raise MXNetError(
            f"block='sdar_moe': denoising_steps {steps} of 1..{L}, "
            f"remasking {cfg['remasking']!r} of {REMASKING}, "
            f"mask_token_id {cfg['mask_token_id']} inside the "
            f"vocabulary of {spec['vocab_size']}")
    cfg.update(layer_types=["full_attention"] * spec["n_layer"],
               gate=False, rope_full=True, block_length=L)
    return dict(
        spec, cfg=cfg, norm=_rms(spec), attention=_grouped_attention,
        bias=False, dense_layers=0, moe_fold="ffn_fold",
        moe=dict(step_len=spec["T"], num_experts=cfg["num_experts"],
                 num_hidden=cfg["moe_intermediate_size"],
                 top_k=cfg["num_experts_per_tok"],
                 norm_topk=bool(cfg["norm_topk_prob"])),
        fed=True, pos_embed="rotary", tie_head=False, embed_scale=False,
        procedure={k: cfg[k] for k in DECODE_KEYS})


def decode_procedure(symbol):
    """How a slot-pooled graph decodes where that is not "one token a
    step": ``DECODE_KEYS`` of a graph that decodes by blocks, as its
    builder wrote them on the output (``block="sdar_moe"``), None of
    any other graph. ``DecodeEngine`` reads it, the way ``slot_state``
    reads the state families."""
    node = symbol._outputs[0][0]
    if "__decode_block_length__" not in node._extra:
        return None
    kinds = dict(zip(DECODE_KEYS, (int, int, int, str, float)))
    return {k: kinds[k](node._extra[f"__decode_{k}__"]) for k in DECODE_KEYS}


#: the keys of Granite 4.0-H's published ``config.json`` (``model_type
#: granitemoehybrid``) that ``block="granite_hybrid"`` reads
#: (``get_decode_symbol(granite=...)``); ``layer_types`` one entry a
#: layer that is run, ``"mamba"`` or ``"attention"``;
#: ``intermediate_size`` is the width of one routed expert (read where
#: ``num_local_experts`` > 0); optionally ``held``, the (first, count)
#: of the routed experts this graph holds
GRANITE_KEYS = ("num_key_value_heads", "layer_types", "mamba_n_heads",
                "mamba_d_head", "mamba_d_state", "mamba_n_groups",
                "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
                "mamba_conv_bias", "mamba_proj_bias",
                "shared_intermediate_size", "num_local_experts",
                "num_experts_per_tok", "intermediate_size",
                "position_embedding_type", "embedding_multiplier",
                "residual_multiplier", "attention_multiplier",
                "logits_scaling")


def _granite_spec(spec):
    """Granite 4.0-H's block (per-slot only) from ``granite``, the
    published config's keys (``GRANITE_KEYS``), no bias but the
    convolution's: ``x = x + m Mixer(N(x))``, ``x = x + m FF(N(x))``
    with ``m = residual_multiplier``, RMSNorm, the embedding times
    ``embedding_multiplier``, a tied head whose logits are divided by
    ``logits_scaling``. **A mixer per layer** (``layer_types``):
    ``"mamba"`` is a Mamba-2 mixer whose state is a convolution's tail
    and one matrix a head, constant in the context (``_mamba_mixer``;
    families ``"conv"`` and ``"recurrent"``); ``"attention"`` is grouped
    attention without positions under ``attention_multiplier``
    (``_nope_attention``; ``"rows"``, the only pools that grow with
    ``capacity``). **The feed-forward of every layer** by
    ``num_local_experts``: 0 (Micro) is one dense gated SiLU of
    ``shared_intermediate_size``; above 0 (Small) it is that many
    routed experts of ``intermediate_size``, ``num_experts_per_tok`` a
    token under a softmax over the chosen logits, beside a shared
    gated SiLU of ``shared_intermediate_size`` that every token passes
    (``MoEFFN``: ``norm_topk`` over a softmax of all is the softmax
    over the chosen), of which this graph holds ``held`` (first, count;
    default all). What this graph does not build is refused: groups of
    B and C that do not divide the heads, a bias on the mixer's
    projections, positions,
    a choice of no expert or of more than the router has, a share
    outside the router's width."""
    cfg = _given_keys(spec, "granite", GRANITE_KEYS)
    kinds, n_layer, n_head = list(cfg["layer_types"]), spec["n_layer"], \
        spec["n_head"]
    if len(kinds) != n_layer or set(kinds) - {"mamba", "attention"}:
        raise MXNetError(
            f"block='granite_hybrid': layer_types {kinds} must name "
            f"'mamba' or 'attention' for each of {n_layer} layers")
    d_in = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    if cfg["mamba_n_groups"] < 1 \
            or cfg["mamba_n_heads"] % cfg["mamba_n_groups"] \
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"] \
            or cfg["position_embedding_type"] != "nope" \
            or d_in != cfg["mamba_expand"] * spec["d_model"]:
        raise MXNetError(
            "block='granite_hybrid' builds groups of B and C of whole "
            "heads, a convolution with a bias, projections without, no "
            "positions and mamba_n_heads x mamba_d_head = mamba_expand x "
            f"d_model (got {cfg})")
    if n_head % cfg["num_key_value_heads"] or spec["d_model"] % n_head:
        raise MXNetError(
            f"block='granite_hybrid': {n_head} query heads on "
            f"{cfg['num_key_value_heads']} K/V heads at d_model "
            f"{spec['d_model']}")
    cfg.update(layer_types=kinds, head_dim=spec["d_model"] // n_head)
    shared = int(cfg["shared_intermediate_size"])
    experts = int(cfg["num_local_experts"])
    if not experts:
        feed_forward = dict(dense=("gated", shared), dense_layers=n_layer)
    else:
        top_k = int(cfg["num_experts_per_tok"])
        first, count = map(int, cfg.get("held") or (0, experts))
        if not 1 <= top_k <= experts:
            raise MXNetError(
                f"block='granite_hybrid': num_experts_per_tok {top_k} of "
                f"num_local_experts {experts} routed experts")
        if first < 0 or count < 1 or first + count > experts:
            raise MXNetError(
                f"block='granite_hybrid': held experts {first}.."
                f"{first + count} of num_local_experts {experts}")
        cfg.update(held=(first, count))
        feed_forward = dict(
            dense_layers=0, moe_fold="ffn_fold",
            moe=dict(step_len=spec["T"], num_experts=experts,
                     num_hidden=int(cfg["intermediate_size"]), top_k=top_k,
                     norm_topk=True, held_first=first, held_count=count,
                     shared_hidden=shared))
    return dict(
        spec, cfg=cfg, norm=_rms(spec), attention=_hybrid_mixer, bias=False,
        fed=True, pos_embed="rotary", tie_head=True, **feed_forward,
        multipliers={"embedding": float(cfg["embedding_multiplier"]),
                     "residual": float(cfg["residual_multiplier"]),
                     "logits": float(cfg["logits_scaling"])})


#: the keys of Nemotron-H's published ``config.json`` (``model_type
#: nemotron_h``: NVIDIA-Nemotron-3-Nano) that ``block="nemotron_h"``
#: reads (``get_decode_symbol(nemotron_h=...)``);
#: ``hybrid_override_pattern`` one letter a layer that is run - ``M`` a
#: Mamba-2 mixer, ``*`` attention, ``E`` routed experts, each the
#: layer's only sub-layer -; optionally ``held``, the (first, count) of
#: the routed experts this graph holds
NEMOTRON_H_KEYS = ("hybrid_override_pattern", "num_key_value_heads",
                   "head_dim", "mamba_num_heads", "mamba_head_dim",
                   "ssm_state_size", "n_groups", "conv_kernel",
                   "chunk_size", "use_conv_bias", "mamba_proj_bias",
                   "mamba_hidden_act", "attention_bias", "mlp_bias",
                   "mlp_hidden_act", "n_routed_experts",
                   "num_experts_per_tok", "moe_intermediate_size",
                   "moe_shared_expert_intermediate_size", "n_shared_experts",
                   "n_group", "topk_group", "norm_topk_prob",
                   "routed_scaling_factor")


def _nemotron_h_spec(spec):
    """Nemotron-H's block (per-slot only) from ``nemotron_h``, the
    published config's keys (``NEMOTRON_H_KEYS``): **a layer is ONE
    sub-layer**, ``x = x + Mixer_i(RMSNorm(x))``, by the layer's letter
    of ``hybrid_override_pattern`` - no bias but the convolution's, an
    unscaled embedding, an untied head. ``M`` is a Mamba-2 mixer with B
    and C in ``n_groups`` groups and a gated norm whose statistic is a
    group's own (``_mamba_mixer``; ``mamba_num_heads x mamba_head_dim``
    need not be a multiple of ``d_model``); ``*`` is grouped attention
    of ``head_dim`` without positions under ``1 / sqrt(head_dim)``
    (``_nope_attention``); ``E`` is ``n_routed_experts`` ungated experts
    ``down(relu(up x) ** 2)`` of ``moe_intermediate_size``,
    ``num_experts_per_tok`` a token under the sigmoid router with a
    correction bias, normalised over the chosen and times
    ``routed_scaling_factor``, beside one shared expert of the same
    form (``MoEFFN(act="relu2")``), of which this graph holds ``held``
    (first, count; default all). What this graph does not build is
    refused: a letter that is none of the three (``-``, a dense
    feed-forward), a bias on a projection, another activation, a
    router that limits its choice to groups of experts, more or fewer
    shared experts than one, groups of B and C that do not divide the
    heads, a share outside the router's width."""
    cfg = _given_keys(spec, "nemotron_h", NEMOTRON_H_KEYS)
    pattern, n_layer, n_head = str(cfg["hybrid_override_pattern"]), \
        spec["n_layer"], spec["n_head"]
    if len(pattern) != n_layer or set(pattern) - set("M*E"):
        raise MXNetError(
            f"block='nemotron_h': hybrid_override_pattern {pattern!r} must "
            f"name 'M', '*' or 'E' for each of {n_layer} layers")
    heads, groups = int(cfg["mamba_num_heads"]), int(cfg["n_groups"])
    experts, top_k = int(cfg["n_routed_experts"]), \
        int(cfg["num_experts_per_tok"])
    first, count = map(int, cfg.get("held") or (0, experts))
    if groups < 1 or heads % groups or cfg["mamba_proj_bias"] \
            or cfg["attention_bias"] or cfg["mlp_bias"] \
            or not cfg["use_conv_bias"] \
            or cfg["mamba_hidden_act"] != "silu" \
            or cfg["mlp_hidden_act"] != "relu2" \
            or int(cfg["n_shared_experts"]) != 1 \
            or (int(cfg["n_group"]), int(cfg["topk_group"])) != (1, 1) \
            or not cfg["norm_topk_prob"]:
        raise MXNetError(
            "block='nemotron_h' builds groups of B and C of whole heads, "
            "a convolution with a bias and SiLU, projections without a "
            "bias, relu2 experts beside one shared expert, and a router "
            "that chooses among all experts (n_group 1, topk_group 1) and "
            f"normalises the chosen (got {cfg})")
    if n_head % cfg["num_key_value_heads"]:
        raise MXNetError(
            f"block='nemotron_h': {n_head} query heads on "
            f"{cfg['num_key_value_heads']} K/V heads")
    if not 1 <= top_k <= experts or first < 0 or count < 1 \
            or first + count > experts:
        raise MXNetError(
            f"block='nemotron_h': num_experts_per_tok {top_k} and held "
            f"experts {first}..{first + count} of n_routed_experts "
            f"{experts}")
    # the mixers read one vocabulary of keys: Granite's
    cfg.update(
        held=(first, count), mamba_n_heads=heads,
        mamba_d_head=int(cfg["mamba_head_dim"]),
        mamba_d_state=int(cfg["ssm_state_size"]), mamba_n_groups=groups,
        mamba_d_conv=int(cfg["conv_kernel"]),
        mamba_chunk_size=int(cfg["chunk_size"]),
        layer_types=[{"M": "mamba", "*": "attention", "E": "experts"}[c]
                     for c in pattern])
    return dict(
        spec, cfg=cfg, norm=_rms(spec), attention=_hybrid_mixer, bias=False,
        fed=True, pos_embed="rotary", tie_head=False, embed_scale=False,
        sub_layers=[("ffn",) if c == "E" else ("proj",) for c in pattern],
        dense_layers=0, moe_fold="ffn_fold",
        moe=dict(step_len=spec["T"], num_experts=experts,
                 num_hidden=int(cfg["moe_intermediate_size"]), top_k=top_k,
                 norm_topk=True, scoring="sigmoid", router_bias=True,
                 scaling=float(cfg["routed_scaling_factor"]),
                 held_first=first, held_count=count,
                 shared_hidden=int(
                     cfg["moe_shared_expert_intermediate_size"]),
                 act="relu2"))


#: the keys of Ling-3.0's published ``config.json`` (``model_type
#: bailing_hybrid``) that ``block="ling_hybrid"`` reads
#: (``get_decode_symbol(ling=...)``). ``layer_types`` and the two
#: ``*_swiglu_limit_list`` have one entry a layer that is run
#: (``"kda"`` or ``"mla"``; the published rule is ``"mla"`` where
#: ``(i + 1) % layer_group_size == 0``); optionally ``held`` and
#: ``kda_chunk`` (the rows of a trip of the chunked form, 64)
LING_KEYS = ("layer_types", "head_dim", "short_conv_kernel_size",
             "kda_lower_bound", "kda_safe_gate", "no_kda_lora",
             "use_kda_lora", "linear_silu", "group_norm_size",
             "num_kv_heads_for_linear_attn", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "rope_scaling", "gated_attention_proj_granularity_type",
             "use_mla_nope", "first_k_dense_replace", "intermediate_size",
             "moe_intermediate_size", "moe_shared_expert_intermediate_size",
             "num_experts", "num_experts_per_tok", "num_shared_experts",
             "routed_scaling_factor", "norm_topk_prob", "n_group",
             "topk_group", "moe_router_enable_expert_bias",
             "scale_router_input", "expert_swiglu_limit_list",
             "share_expert_swiglu_limit_list", "up_proj_norm", "value_norm",
             "use_nGPT")


def _ling_spec(spec):
    """Ling-3.0's block (per-slot only) from ``ling``, the published
    config's keys (``LING_KEYS``), no bias anywhere: pre-norm RMSNorm,
    **a mixer per layer** (``layer_types``) - ``"kda"`` is Kimi Delta
    Attention, whose state is three convolutions' tails and one matrix
    a head, constant in the context (``_kda_mixer``; families ``"conv"``
    and ``"recurrent"``), ``"mla"`` the latent block's attention over
    every earlier position under the plain rotary, its query one
    projection (``q_lora_rank`` None) and its heads' outputs gated by a
    sigmoid a head (``_latent_attention``; ``"rows"``, the only pool
    that grows with ``capacity``) -, and the latent block's
    feed-forward (``_latent_spec``): dense gated SiLU on the first
    ``first_k_dense_replace`` layers, then sigmoid-routed experts with a
    correction bias chosen inside ``topk_group`` of ``n_group`` groups,
    of which this graph holds ``held``, beside a shared one. What this
    graph does not build is refused, by key."""
    cfg = _given_keys(spec, "ling", LING_KEYS)
    kinds, n_layer = list(cfg["layer_types"]), spec["n_layer"]
    if len(kinds) != n_layer or set(kinds) - {"kda", "mla"}:
        raise MXNetError(
            f"block='ling_hybrid': layer_types {kinds} must name 'kda' or "
            f"'mla' for each of {n_layer} layers")
    limits = list(cfg["expert_swiglu_limit_list"]) \
        + list(cfg["share_expert_swiglu_limit_list"])
    refused = {
        "q_lora_rank": cfg["q_lora_rank"] is not None,
        "gated_attention_proj_granularity_type":
            cfg["gated_attention_proj_granularity_type"] != "head_wise",
        "expert_swiglu_limit_list / share_expert_swiglu_limit_list":
            len(limits) != 2 * n_layer or any(limits),
        "num_kv_heads_for_linear_attn": cfg["num_kv_heads_for_linear_attn"],
        "kda_safe_gate": not cfg["kda_safe_gate"],
        "no_kda_lora / use_kda_lora":
            not cfg["no_kda_lora"] or cfg["use_kda_lora"],
        "linear_silu": not cfg["linear_silu"],
        "group_norm_size": cfg["group_norm_size"] != 1,
        "moe_router_enable_expert_bias":
            not cfg["moe_router_enable_expert_bias"],
        "moe_shared_expert_intermediate_size":
            cfg["moe_shared_expert_intermediate_size"]
            != cfg["moe_intermediate_size"],
        **{k: cfg[k] for k in ("use_mla_nope", "scale_router_input",
                               "up_proj_norm", "value_norm", "use_nGPT")}}
    refused = [k for k, bad in refused.items() if bad]
    if refused:
        raise MXNetError(
            "block='ling_hybrid' builds the published block - a query "
            "projected directly beside a head-wise output gate, no swiglu "
            "clamp on a layer that is run (one entry a layer), KDA heads "
            "as the query's, the bounded decay with full projections, "
            "SiLU after the convolutions, one norm a head, a router with "
            "a correction bias, a shared expert of the routed width - and "
            f"not what {refused} ask(s) for (got "
            f"{ {k: cfg.get(k) for k in LING_KEYS} })")
    given = dict(cfg, n_routed_experts=cfg["num_experts"],
                 n_shared_experts=cfg["num_shared_experts"])
    latent = _latent_spec(
        spec, given, ["none"] * n_layer,
        {"router_bias": True, "n_group": int(cfg["n_group"]),
         "topk_group": int(cfg["topk_group"])},
        _yarn_rope(cfg, "ling_hybrid"))
    latent["cfg"].update(layer_types=kinds, head_gate=True,
                         kda_chunk=int(cfg.get("kda_chunk", 64)))
    return dict(latent, attention=_ling_mixer)


#: ``block=`` -> its spec constructor: the one place a block is chosen
#: by its name. A tenth architecture is one more entry and, only if
#: its attention is new, one more attention function
_SPECS = {"gpt2": _gpt2_spec, "olmoe": _olmoe_spec, "evabyte": _eva_spec,
          "glm_dsa": _glm_spec, "axk1": _axk1_spec, "afmoe": _afmoe_spec,
          "xing4": _xing4_spec, "granite_hybrid": _granite_spec,
          "ling_hybrid": _ling_spec, "sdar_moe": _sdar_spec,
          "nemotron_h": _nemotron_h_spec}


def _spec(given, decode):
    """The record that ``_layer`` and ``_logits`` walk, from the
    keywords ``get_symbol`` / ``get_decode_symbol`` were called with:
    those, ``decode``, ``T`` (the rows a slot or sequence has in this
    graph), the defaults of what most blocks do not have (no ``fed``
    input, a plain residual add at the compute width, one head, no
    dropout, no multipliers, both sub-layers in every layer) and what
    the block's own constructor
    (``_SPECS``) makes of them and checks."""
    make = _SPECS.get(given["block"])
    if make is None:
        raise MXNetError(f"block {given['block']!r}: "
                         + ", ".join(map(repr, _SPECS)))
    T = given["step_len"] if decode else given["seq_len"]
    capacity = (given["capacity"] or default_cache_capacity()) if decode \
        else None
    return make({
        # what one of the two graphs has no keyword for
        "dropout": 0.0, "per_slot": False, **given,
        "decode": decode, "T": T, "capacity": capacity,
        "cache_dtype": (given["cache_dtype"] or default_cache_dtype())
        if decode else None,
        "max_seq_len": given["max_seq_len"] or capacity or T,
        "rms_eps": float(given["rms_eps"]),
        # what most blocks do not have
        "fed": False, "residual": "plain", "heads": 1, "next_byte": False,
        "moe": None, "dense": None, "multipliers": None,
        "procedure": None, "sub_layers": None})


def _embedded(spec):
    """The head of a graph: ``(x, tok_w, fed, fed_rows)`` - the token
    embedding (scaled by sqrt(D), transformer convention, unless
    ``embed_scale=False``; by the block's own multiplier where it has
    one), plus the learned position table when
    ``pos_embed='learned'``, cast where the block's stream is float32,
    laid ``n`` times side by side where it is ``n`` copies (``"hyper"``).
    In a fed graph the tokens are embedded in the view the row-wise
    operations run in (``ops/rows.py``: ``(slots, S, D)``, or
    one block of the real rows under a budget), ``fed`` as the decode
    ops take it and ``fed_rows`` as that view's ``MoEFFN`` does. A
    decode graph with learned positions takes ``pos_ids``; per-slot
    they are shaped (B, S) - every slot at its own absolute position -
    so the looked-up table rows already align with ``x`` and add
    elementwise."""
    name, d_model = spec["name"], spec["d_model"]
    data, tok_w = sym.var("data"), sym.var(f"{name}_tok_embed_weight")
    fed = fed_rows = None
    if spec["fed"]:
        fed = sym.var("fed")
        data, fed_rows = sym.pack_rows(data, fed, name=f"{name}_rows")
    x = sym.Embedding(data=data, weight=tok_w, input_dim=spec["vocab_size"],
                      output_dim=d_model, name=f"{name}_tok_embed",
                      **({"scale": float(spec["multipliers"]["embedding"])}
                         if spec["multipliers"]
                         else {"scale": float(np.sqrt(d_model))}
                         if spec["embed_scale"] else {}))    # (B, T, D)
    if spec["pos_embed"] == "learned":
        pos_ids = sym.var("pos_ids") if spec["decode"] else sym._arange(
            start=0, stop=float(spec["T"]), name=f"{name}_pos_ids")
        if fed is not None:             # a row's position goes with it
            pos_ids = sym.pack_rows(pos_ids, fed,
                                    name=f"{name}_pos_rows")[0]
        pos = sym.Embedding(data=pos_ids,
                            weight=sym.var(f"{name}_pos_embed_weight"),
                            input_dim=spec["max_seq_len"],
                            output_dim=d_model,
                            name=f"{name}_pos_embed")        # (T, D) /
        if spec["per_slot"]:                                 # (B, S, D)
            x = x + pos
        else:
            pos = sym.expand_dims(pos, axis=0, name=f"{name}_pos_b")
            x = sym.broadcast_add(x, pos, name=f"{name}_add_pos")
    if spec["residual"] == "float32":
        x = sym.Cast(x, dtype="float32", name=f"{name}_embed_f32")
    elif spec["residual"] == "hyper":   # every copy starts as the row
        x = sym.tile(x, reps=(1, 1, spec["hyper"]["n"]),
                     name=f"{name}_embed_copies")
    return x, tok_w, fed, fed_rows


def _logits(spec):
    """Inputs -> embedding -> layers -> final norm -> head: ``(logits
    over the graph's rows, fed)`` of any block's graph."""
    x, tok_w, fed, fed_rows = _embedded(spec)
    carry = None
    for i in range(spec["n_layer"]):
        x, carry = _layer(x, fed, fed_rows, carry, i, spec)
    return _head(x, tok_w, spec), fed


def get_symbol(vocab_size=256, d_model=64, n_layer=2, n_head=4,
               seq_len=32, pos_embed="rotary", rope_base=10000.0,
               dropout=0.0, include_loss=True, normalization="batch",
               max_seq_len=None, name="lm", block="gpt2", n_expert=None,
               top_k=None, expert_width=None, norm_topk=False,
               rms_eps=1e-5, tie_head=True, embed_scale=True):
    """Training/full-sequence graph.

    data: ``(B, seq_len)`` token ids (bind the data iter with an int32
    ``DataDesc`` for vocabularies past bf16's exact-integer range);
    label: ``(B*seq_len,)`` next-token ids fed straight into the loss
    head (flat on purpose — the label variable keeps its exact dtype
    under mixed precision only when it feeds the loss slot directly).

    ``include_loss=False`` returns logits ``(B, seq_len, vocab)`` — the
    decode-parity reference the KV-cache gates compare against.

    ``block`` is ``"gpt2"`` (``_gpt2_spec``) or ``"olmoe"``
    (``_olmoe_spec``: ``n_expert`` experts of ``expert_width``,
    ``top_k`` a token, ``rms_eps``); the other blocks have no
    full-sequence graph (their attention is decode ops alone; the plain
    full forward is the benchmark's, ``chipbench/reference/``).
    """
    logits, _fed = _logits(_spec(dict(locals()), decode=False))
    if not include_loss:
        return sym.Reshape(logits, shape=(-1, seq_len, vocab_size),
                           name=f"{name}_logits_btv")
    return sym.SoftmaxOutput(logits, name="softmax",
                             normalization=normalization)


def get_decode_symbol(vocab_size=256, d_model=64, n_layer=2, n_head=4,
                      pos_embed="rotary", rope_base=10000.0,
                      capacity=None, step_len=1, max_seq_len=None,
                      per_slot=False, cache_dtype=None, name="lm",
                      block="gpt2", n_expert=None, top_k=None,
                      expert_width=None, norm_topk=False, rms_eps=1e-5,
                      tie_head=True, embed_scale=True, window=2048,
                      chunk=16, n_pred_heads=1, ffn_width=None,
                      multibyte=False, glm=None, afmoe=None,
                      max_step_len=None, axk1=None, xing4=None,
                      granite=None, ling=None, sdar=None, nemotron_h=None):
    """Incremental KV-cache decoder: ``(B, step_len)`` new token ids in,
    logits ``(B, step_len, vocab)`` out, per-layer K/V caches of
    ``capacity`` positions riding executor aux state. Parameter names
    match ``get_symbol``'s exactly, so a trained parameter set loads
    unchanged. ``pos_embed='learned'`` adds a ``pos_ids`` input
    (``(step_len,)`` absolute positions — ``KVCacheDecoder`` feeds it).

    ``per_slot=True`` builds the slot-pooled continuous-batching graph:
    every batch row is an independent decode slot with its own (B, 1)
    cache cursor, so one pinned program advances B sequences at B
    different positions per dispatch — ``BatchedKVCacheDecoder`` drives
    it, ``serve.decode`` schedules it. ``step_len`` > 1 builds the
    S-token *window* variant of the same graph (chunked prefill and
    speculative verify): each slot consumes S tokens starting at its own
    cursor, with within-window causal masking, and the logits row ``s``
    predicts the token after stream position ``cursor + s``. With
    learned positions the ``pos_ids`` input becomes ``(B, step_len)``
    per-slot absolute positions.

    ``cache_dtype='fp8'`` (or ``MXNET_LM_CACHE_DTYPE=fp8``) declares
    the per-layer K/V cache cells as ``float8_e4m3fn`` storage: rows
    quantize on write and dequantize on read inside the pinned decode
    program, quartering cache HBM traffic and footprint. The cursor
    stays int32 and the default (None) keeps compute-width cells.

    ``block``, ``n_expert``, ``top_k``, ``expert_width``, ``norm_topk``,
    ``rms_eps``, ``tie_head`` and ``embed_scale`` are ``get_symbol``'s.
    The blocks that are served and not trained (per-slot only; each
    described at its spec constructor): ``"evabyte"`` (``_eva_spec``:
    ``window``, ``chunk``, ``n_pred_heads``, ``ffn_width``,
    ``multibyte``), ``"glm_dsa"`` (``_glm_spec``: ``glm``, the published
    config's ``GLM_KEYS``), ``"axk1"`` (``_axk1_spec``: ``axk1``,
    ``AXK1_KEYS``), ``"xing4"`` (``_xing4_spec``: ``xing4``,
    ``XING4_KEYS``), ``"afmoe"`` (``_afmoe_spec``: ``afmoe``,
    ``AFMOE_KEYS``; ``max_step_len``), ``"granite_hybrid"``
    (``_granite_spec``: ``granite``, ``GRANITE_KEYS``),
    ``"ling_hybrid"`` (``_ling_spec``: ``ling``, ``LING_KEYS``),
    ``"sdar_moe"`` (``_sdar_spec``: ``sdar``, ``SDAR_KEYS``; its graph
    says that it decodes by blocks, ``decode_procedure``) and
    ``"nemotron_h"`` (``_nemotron_h_spec``: ``nemotron_h``,
    ``NEMOTRON_H_KEYS``; a layer is one sub-layer).

    Every slot-pooled graph (``per_slot=True``, whatever the block)
    takes one more input, ``fed`` ``(slots,)`` int32 - how many of each
    slot's ``step_len`` tokens are real - and advances a slot's state
    by exactly that: nothing runs ahead, nothing is rewound after a
    window. It passes between the rows its row-wise operations run over
    and ``(slots, step_len, .)`` through ``pack_rows`` / ``unpack_rows``
    (``ops/rows.py``), which keep all ``slots x step_len`` rows as built
    here; ``packed_window`` derives the form of a window graph that runs
    over a budget of real rows. The one-cursor graph (``per_slot=False``:
    ``KVCacheDecoder``'s) has no such input.
    """
    spec = _spec(dict(locals()), decode=True)
    logits, fed = _logits(spec)
    if fed is None:
        return sym.Reshape(logits, shape=(-1, step_len, vocab_size),
                           name=f"{name}_logits_bsv")
    if multibyte and spec["next_byte"]:
        return _slots(logits, fed, step_len, f"{name}_logits_bspv",
                      shape=(spec["heads"], vocab_size))
    if spec["next_byte"]:
        logits = sym.slice_axis(logits, axis=1, begin=0, end=vocab_size,
                                name=f"{name}_next_byte")
    out = _slots(logits, fed, step_len, f"{name}_logits_bsv")
    if spec["procedure"]:
        out._set_attr(**{f"__decode_{k}__": str(v)
                         for k, v in spec["procedure"].items()})
    return out


class SyntheticLMIter:
    """Synthetic next-token LM batches: data ``(B, T)`` int32 ids,
    label ``(B*T,)`` float ids (the shifted-by-one stream), matching
    ``get_symbol``'s flat-label loss contract."""

    def __init__(self, vocab_size, batch_size, seq_len, n_batches,
                 seed=0):
        from ..io import DataDesc
        from .. import ndarray as nd
        rs = np.random.RandomState(seed)
        stream = rs.randint(
            0, vocab_size,
            (n_batches * batch_size, seq_len + 1)).astype(np.int32)
        self._data = [nd.array(stream[i * batch_size:(i + 1) * batch_size,
                                      :seq_len])
                      for i in range(n_batches)]
        self._label = [nd.array(
            stream[i * batch_size:(i + 1) * batch_size, 1:]
            .reshape(-1).astype(np.float32)) for i in range(n_batches)]
        self.provide_data = [DataDesc("data", (batch_size, seq_len),
                                      np.int32)]
        self.provide_label = [DataDesc("softmax_label",
                                       (batch_size * seq_len,))]
        self.batch_size = batch_size
        self._i = 0

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        from ..io import DataBatch
        if self._i >= len(self._data):
            raise StopIteration
        b = DataBatch(data=[self._data[self._i]],
                      label=[self._label[self._i]],
                      provide_data=self.provide_data,
                      provide_label=self.provide_label)
        self._i += 1
        return b

    next = __next__


class KVCacheDecoder:
    """Host-side driver for a bound decode module.

    Owns what the jitted program cannot check: the absolute position
    cursor (capacity overflow raises HERE, before dynamic_update_slice
    would clamp the write), the ``pos_ids`` feed for learned positions,
    and cache reset between sequences. The module must be bound
    ``for_training=False`` over ``get_decode_symbol``'s graph.
    """

    def __init__(self, module, capacity, pos_embed="rotary"):
        self._mod = module
        self.capacity = int(capacity)
        self.pos_embed = pos_embed
        self.pos = 0
        exe = module._exec_group.executor
        if pos_embed == "learned" and exe._compute_dtype is not None \
                and np.issubdtype(exe.arg_dict["pos_ids"].dtype,
                                  np.floating):
            # a float cell is cast to the compute width at graph entry,
            # and bfloat16 holds no odd position past 256
            raise MXNetError(
                "a decode module that computes in "
                f"{np.dtype(exe._compute_dtype).name} takes its positions "
                "as whole numbers: bind pos_ids as an int32 DataDesc")
        self._new_session_trace()

    def _new_session_trace(self):
        """One trace per decode session (telemetry.trace): every step
        records a child span under the session root, so an N-token
        decode reconstructs to a single parented span tree keyed by
        ``self.trace.trace_id``."""
        from ..telemetry import trace as _trace
        self.trace = _trace.new_trace(session=True)
        self.trace.root = _trace.next_span_id()

    def reset(self):
        """Zero every decode cache (aux cells), rewind the cursor and
        rotate the session trace (a new sequence = a new trace)."""
        import jax.numpy as jnp
        exe = self._mod._exec_group.executor
        for nm, cell in exe.aux_dict.items():
            cell._set(jnp.zeros(cell.shape, cell.asjax().dtype))
        self.pos = 0
        self._new_session_trace()

    def step(self, tokens):
        """Decode one window: tokens ``(B, S)`` -> logits ``(B, S, V)``
        NDArray. Advances the device-side caches and the host cursor."""
        import time
        from .. import ndarray as nd
        from ..io import DataBatch
        from ..telemetry import trace as _trace
        tokens = np.asarray(tokens)
        if tokens.ndim == 1:
            tokens = tokens[:, None]
        S = tokens.shape[1]
        if self.pos + S > self.capacity:
            raise MXNetError(
                f"KV cache overflow: position {self.pos} + {S} new "
                f"tokens exceeds capacity {self.capacity}; reset() or "
                "re-bind with a larger capacity")
        data = [nd.array(tokens.astype(np.int32))]
        if self.pos_embed == "learned":
            data.append(nd.array(
                np.arange(self.pos, self.pos + S, dtype=np.int32)))
        t0 = time.perf_counter()
        if self.trace.start_s is None:
            self.trace.start_s = t0
        self._mod.forward(DataBatch(data=data, label=[]), is_train=False)
        self.pos += S
        t1 = time.perf_counter()
        _trace.record(self.trace, "lm.decode.step", t0, t1,
                      parent=self.trace.root, pos=self.pos - S, tokens=S)
        # the session root grows with every step: same span id, longer
        # duration — consumers dedupe keeping the last record
        _trace.record(self.trace, "lm.decode.session",
                      self.trace.start_s, t1, span_id=self.trace.root,
                      capacity=self.capacity, pos=self.pos)
        return self._mod.get_outputs()[0]


def _stateful_nodes(symbol):
    """``(node, its OpDef, [(aux name, aux variable's name)])`` of every
    node of a graph whose op declares per-slot state or what it reads
    (``OpDef.slot_state``, ``state_reads``), in graph order."""
    for node in symbol._topo_nodes():
        if node.is_variable:
            continue
        opdef = node.opdef()
        if opdef.slot_state or opdef.state_reads:
            aux = opdef.aux_names(node.attrs)
            yield node, opdef, [
                (nm, var.name) for (var, _), nm in zip(
                    node.inputs[len(node.inputs) - len(aux):], aux)]


def slot_state(symbol):
    """``{family: [aux cell names, in graph order]}`` of a slot-pooled
    decode graph's per-slot state, as its ops declare it
    (``OpDef.slot_state``): ``"cursor"`` cells, ``"rows"`` pools with a
    row per position, and whatever further families an op keeps
    (``ops/eva.py``: ``"window"``, ``"summary"``). A cell no op
    declares (``MoEFFN``'s counts) is no slot's state."""
    families = {}
    for _node, opdef, cells in _stateful_nodes(symbol):
        for nm, var in cells:
            if nm in opdef.slot_state:
                families.setdefault(opdef.slot_state[nm], []).append(var)
    return families


#: the families of per-slot state that something indexes: the cursor,
#: a row per position, a ring by the position's remainder, EVA's window
#: and summaries by the position's window and chunk. Any other family
#: (``ops/ssm.py``: a convolution's tail, a recurrence's state) is
#: carried from token to token and holds no position to go back to
_INDEXED_FAMILIES = frozenset(("cursor", "rows", "ring", "window",
                               "summary"))


def ridge_rows():
    """The rows a bfloat16 matmul carries for the price of reading its
    weights: up to peak / bandwidth rows (two operations and two bytes
    a weight and row) the product is bound by the weights' bytes, so a
    row more costs nothing. From the one table of peaks
    (``telemetry.mfu.PEAKS``), the entry of the chip the programs are
    written for whatever host builds the graph: the v5e's 197e12 /
    819e9 = 240, rounded up to the matmuls' tile: 256."""
    from ..telemetry.mfu import PEAKS
    chip = PEAKS["TPU v5e"]
    return -(-int(chip["bf16"] / chip["hbm"]) // 128) * 128


def packed_rows(slots, step_len):
    """The row budget R of a packed window program: one slot's whole
    prefill chunk and one token for every other slot, rounded up to the
    matmuls' tile (128 rows; 8 at sizes under that) - and never under
    the rows that cost nothing (``ridge_rows``; the whole window where
    that is the smaller): a budget under them would shorten no matmul
    and feed fewer prompt tokens a window. 8 x 64 packs to 256 and not
    to 72; a whole window of no more than the ridge (4 x 64) gets its
    own rows and so no packed form (``packed_window``); from 248 rows a
    slot on the chunk and the riders are the larger and nothing
    changes. Up to one tile the whole window (the tests' sizes) a chunk
    and the riders it is."""
    whole = int(slots) * int(step_len)
    rows = int(step_len) + int(slots)
    unit = 128 if rows >= 128 else 8
    rows = -(-rows // unit) * unit
    return rows if whole <= 128 else max(rows, min(ridge_rows(), whole))


def _head_entry(out):
    """Where the head of a graph whose output node is ``out`` starts:
    ``(node, k)``, the stream the ``k``-th input of ``node``. From the
    output back through every node that reads ONE computed value - the
    head's own: the copies' sum, the final norm, the fold, the product,
    its scale or slice, each beside parameters or ``fed`` alone and each
    a row's own affair - to the first that reads what a node of several
    computed inputs made: the last layer's join."""
    node = out
    while True:
        (k,) = [i for i, (src, _) in enumerate(node.inputs)
                if not src.is_variable]
        src = node.inputs[k][0]
        if sum(not s.is_variable for s, _ in src.inputs) != 1:
            return node, k
        node = src


def packed_window(symbol, slots):
    """``(graph, R)``: a fed window graph with its rows packed to the
    budget ``R = packed_rows(slots, S)`` - a copy of ``symbol`` whose
    ``pack_rows`` / ``unpack_rows`` nodes carry it (``ops/rows.py``), so
    that every row-wise operation runs over R rows and not ``slots x
    S`` - and its head over fewer still: ``last_rows`` stands in front
    of it (``_head_entry``), so the copies' sum, the final norm, the
    product with the vocabulary and what scales or slices it run over
    each slot's last fed row, the one row of a serving window that
    anybody reads, and the graph's output - the logits' ``unpack_rows``,
    here the reshape of ``slots`` rows - is ``(slots, 1, V)``
    (``(slots, 1, heads, V)`` with every head's logits): no array of a
    window's ``slots x S`` rows by the vocabulary exists in the
    program. None where the graph has no such nodes (a block without
    ``fed``) or packing would not halve the rows (S = 1, rung 1)."""
    steps = {int(n.attrs["step_len"]) for n in symbol._topo_nodes()
             if n.op == "unpack_rows"}
    if len(steps) != 1 or symbol._outputs[0][0].op != "unpack_rows":
        return None
    step_len = steps.pop()
    rows = packed_rows(slots, step_len)
    if slots * step_len < 2 * rows:
        return None
    packed = symbol._substitute({})
    out = packed._outputs[0][0]
    for node in packed._topo_nodes():
        if node.op in ("pack_rows", "unpack_rows") and node is not out:
            node.attrs["rows"] = rows
    out.attrs["step_len"] = 1           # a row a slot, as an S = 1 graph's
    node, k = _head_entry(out)
    node.inputs[k] = sym.last_rows(
        sym.Symbol([node.inputs[k]]), sym.Symbol([out.inputs[1]]),
        step_len=step_len, rows=rows, name=f"{out.name}_last")._outputs[0]
    return packed, rows


def copy_sites(symbol, step_len):
    """``(sites, static)`` of a window graph of ``step_len`` rows a
    slot: its ``pack_rows`` / ``unpack_rows`` nodes that carry a row
    budget - a copy of rows each, where a node without one is nothing or
    a reshape - and those of them that lower without a loop
    (``ops/rows.py``'s ``one_chunk``: all where a slot's rows are one
    chunk, none otherwise). A count of the graph."""
    sites = sum(node.op in ("pack_rows", "unpack_rows")
                and int(node.attrs.get("rows", 0)) > 0
                for node in symbol._topo_nodes())
    return sites, sites if one_chunk(int(step_len)) else 0


def row_programs(slots, block, shardings):
    """``(capture, restore)``: two jitted programs over the ``"rows"``
    pools of a ``slots``-slot driver, of the pools' shapes and ``block``
    rows whatever slot, position or length a call names, so each
    compiles once a driver. ``capture_rows_<slots>(pools, slot, start)``
    reads ``block`` rows of one slot from every pool;
    ``restore_rows_<slots>(pools, rows, slot, start)`` takes the pools
    over (donated), writes ``block`` rows into one slot of each in place
    and hands them back in their buffers (``shardings``): neither
    copies a pool."""
    import jax
    from jax import lax

    def capture_rows(pools, slot, start):
        return tuple(lax.dynamic_slice(
            p, (slot, 0, start, 0),
            (1, p.shape[1], block, p.shape[3]))[0] for p in pools)

    def restore_rows(pools, rows, slot, start):
        return tuple(lax.dynamic_update_slice(
            p, r[None].astype(p.dtype), (slot, 0, start, 0))
            for p, r in zip(pools, rows))

    capture_rows.__name__ = f"capture_rows_{slots}"
    restore_rows.__name__ = f"restore_rows_{slots}"
    return (jax.jit(capture_rows),
            jax.jit(restore_rows, donate_argnums=0,
                    out_shardings=tuple(shardings)))


def row_blocks(length, block, capacity):
    """``(start, skip, n)`` of each launch of ``row_programs`` that
    covers rows ``[0, length)``: it moves the pool's rows ``[start,
    start + block)``, of which ``[skip, skip + n)`` are wanted (a block
    that would pass the capacity starts earlier instead)."""
    for at in range(0, int(length), block):
        start = min(at, capacity - block)
        yield start, at - start, min(block - (at - start), int(length) - at)


class BatchedKVCacheDecoder:
    """Host-side driver for a bound SLOT-POOLED decode module.

    The module must be bound ``for_training=False`` over
    ``get_decode_symbol(per_slot=True)``'s graph at a fixed slot count
    (the batch dim). Each slot is an independent sequence: ``join``
    claims a slot (resets its device cursor), ``leave`` releases it
    host-side only (the program keeps advancing the retired row
    harmlessly — its writes stay inside its own slot and nothing
    attends them), and ``step`` advances EVERY slot by one token in one
    dispatch. Like ``KVCacheDecoder``, the driver owns what the pinned
    program cannot check: per-slot cursors (capacity overflow raises
    HERE, naming the offending slots, before the masked write would
    no-op) and the per-slot ``pos_ids`` feed for learned positions.

    Besides the steady-state S=1 program, a driver can carry *window*
    modules (``add_window``): same parameters, same shared aux cells,
    ``step_len=S`` graphs that advance every slot by S positions per
    dispatch — chunked prefill and speculative verify ride these.
    ``step`` dispatches on ``tokens.shape[1]``. ``rewind`` sets a
    slot's device cursor to an arbitrary position — the seam for padded
    final prefill chunks, prefix-cache joins at cursor C, and
    speculative rollback.

    Every cursor move (``join``, ``rewind``, ``rewind_many``) is ONE
    launch of one small jitted program over all layers' ``*cache_pos``
    cells (``_set_cursors``). Its shapes are the pool's, never the
    number of rows moved, so it compiles once per driver — at engine
    warm-up — and no cursor move compiles afterwards. ``name`` is the
    served model's label: a named driver counts its launches and the
    rows they moved in ``serve.decode.cursor.updates`` / ``.rows``, and
    at every ``step`` the bytes of state that the step program takes
    over and updates in place (``donated_bytes``: every aux array of a
    graph whose ops ask for it, ``OpDef.donate_aux``) in
    ``serve.decode.state.donated_bytes``.

    ``step`` hands back the whole ``(slots, S, V)`` logits as they lie
    on the device; ``release_outputs`` makes them the caller's alone (a
    scheduler calls it behind every step: a window's logits over a
    vocabulary of 131,072 are 2 GB, and every rung's programs would
    each keep their latest). A caller that samples one row a slot launches
    ``select_rows`` behind it: one small jitted program per step length
    that picks each slot's row and takes its argmax there, so that
    token ids cross to the host and not the logits.

    **Two kinds of state.** The graph's ops say which aux cells hold
    per-slot state and of what family (``slot_state``). A graph whose
    state is a cursor and ``"rows"`` pools alone is *positional*: any
    position below the cursor is a row that is still there, which is
    what ``rewind`` to an arbitrary position, ``capture_rows`` and
    ``restore_rows`` rest on. Every graph ``get_decode_symbol(per_slot=
    True)`` builds takes ``fed``, the number of real tokens of each
    slot, as ``step`` hands it, and the program advances each slot's
    state by exactly that, so nothing runs ahead and nothing needs
    rewinding after a window (a graph without the input is refused
    at construction). EVA attention
    (``block="evabyte"``) keeps a ring of the open window's exact rows
    beside one summary per closed chunk: not positional. For such a
    graph ``join`` and ``leave`` are unchanged
    (everything is read by the cursor, so a cursor at 0 is a clean
    slot); ``rewind``/``rewind_many`` accept 0 or a position whose
    window's rows are all still in the ring (no row of a later window
    written over them) and raise ``MXNetError`` otherwise;
    ``capture_rows``/``restore_rows`` raise; ``overflowing`` is about
    the context (cursor + S against capacity), whatever the pools
    hold; ``DecodeEngine.migrate`` copies every family.

    A graph with sliding layers (``block="afmoe"``) keeps their K and V
    in rings of the window and one dispatch's rows (family ``"ring"``)
    beside the full layers' ``"rows"``: not positional either, by the
    families and not by any cell's name. It is fed like the other; a
    cursor goes to 0 or back by as many positions as the ring holds
    beyond its window and a dispatch's rows (``ring_slack``);
    ``capture_rows``/``restore_rows`` raise, naming the families.
    ``state_bytes`` is the state's bytes by family.

    A graph with a recurrent mixer (``block="granite_hybrid"``,
    ``block="ling_hybrid"``) keeps,
    beside its attention layers' ``"rows"``, state that nothing indexes
    (families ``"conv"`` and ``"recurrent"``, ``ops/ssm.py``,
    ``ops/kda.py``: constant
    in the context, rewritten whole by every dispatch): every token
    since the slot joined is in it and none can be taken out, so a
    cursor goes to 0 - where the program reads the state as zeros - or
    stays where it is; ``capture_rows``/``restore_rows`` raise, naming
    the families; ``DecodeEngine.migrate`` copies it like any other.

    ``serve.decode.DecodeScheduler`` builds the continuous-batching
    front end (admission, retirement, streaming, rung ladder) on top of
    one of these per slot rung.
    """

    def __init__(self, module, capacity, slots=None, pos_embed="rotary",
                 name=None):
        self._mod = module
        self.capacity = int(capacity)
        self.pos_embed = pos_embed
        self.name = name
        if slots is None:
            slots = module.data_shapes[0].shape[0]
        self.slots = int(slots)
        self.pos = np.zeros(self.slots, np.int64)    # device-cursor mirror
        self._sent_back = None      # rewind_many(where=)'s rows, till kept
        self.active = np.zeros(self.slots, bool)
        self._windows = {}                           # step_len -> module
        # step_len -> how a step's host arrays reach that module's cells
        self._stagers = {1: module._exec_group.input_stager()}
        # step_len -> (module, its stager, R, its copy sites): the
        # window program over the real rows alone, where there is one
        # (``add_window``)
        self._packed = {}
        # rows the latest step's program ran its row-wise operations
        # over: slots x S, or R where it was the packed one; and those
        # its head ran over: the same, but a packed window's ``slots``
        self.last_program_rows = self.last_head_rows = None
        # the copies of rows in the latest step's program and those of
        # them that hold no loop (``copy_sites``): a packed window's
        # alone, (0, 0) of any other program
        self.last_copy_sites = None
        self._stepped = None          # the module the latest step ran
        self._cursor_program = None                  # built at first use
        self._row_progs = None                       # capture, restore
        self._select_programs = {}                   # step_len -> program
        self._merge_programs = {}                    # step_len -> program
        self._denoise_programs = {}                  # block length -> program
        self._block_programs = {}                    # block length -> program
        self._moe_program = None                     # built at first use
        exe = module._exec_group.executor
        # what every step program takes over and updates in place (the
        # window modules share these cells): 0 = the graph donates none
        self.donated_bytes = sum(
            cell.size * cell.dtype.itemsize
            for cell in exe.aux_arrays) if exe.donates_aux else 0
        # the per-slot state, by family, as the graph's ops declare it
        self._state = slot_state(module.symbol)
        self.positional = set(self._state) <= {"cursor", "rows"}
        # state carried from token to token, which no position indexes
        self._carried = sorted(set(self._state) - _INDEXED_FAMILIES)
        # a window of exact rows beside summaries (EVA attention)
        self.summarises = "summary" in self._state
        self.state_bytes = {
            family: sum(cell.size * cell.dtype.itemsize
                        for _nm, cell in self._cells(family))
            for family in self._state}
        if "fed" not in module.symbol.list_arguments():
            raise MXNetError(
                "a slot-pooled decode graph takes fed, the real tokens "
                "of each slot, beside its tokens, and this one has no "
                "such input: build it with get_decode_symbol("
                "per_slot=True)")
        if "fed" not in module.data_names:
            # bound as a parameter it would stay what it was set to,
            # and every step would advance the slots by that
            raise MXNetError(
                "this decode graph takes fed, the real tokens of each "
                "slot, beside its tokens: bind it as data (data_names "
                f"{list(module.data_names)}; a (slots,) int32 DataDesc)")
        # what a dispatch reads of the state, as the graph's ops say it
        # (``OpDef.state_reads``): ``read_counts`` names everything
        # they count, ``_reads`` holds one ``(executions, f(pos, fed))``
        # for each (op, attributes) the graph has, however many layers
        # (a Cerebras graph is one group of 24, GLM-5.2's two), and
        # ``last_reads`` is their sum for the latest dispatch:
        # ``{count: integer}``. An op that counts on the device
        # instead (``MoEFFN``) keeps a cell a layer, ``_moe_cells``,
        # of ``_moe_counts``' entries.
        self.read_counts, self._moe_cells, self._moe_counts = {}, [], ()
        self.last_reads = None
        groups, rings = {}, []
        for node, opdef, cells in _stateful_nodes(module.symbol):
            for nm, var in cells:
                if opdef.slot_state.get(nm) == "ring":
                    rings.append((int(exe.aux_dict[var].shape[2]),
                                  int(node.attrs["window"])))
            if opdef.state_reads is None:
                continue
            counts, reads = opdef.state_reads
            if callable(counts):
                counts = counts(node.attrs)
            self.read_counts.update(counts)
            if reads is None:
                self._moe_counts = tuple(counts)
                self._moe_cells += [exe.aux_dict[var] for nm, var in cells
                                    if nm not in opdef.slot_state]
                continue
            sources = {
                nm: src.attrs for nm, (src, _out) in zip(
                    opdef.input_names(node.attrs), node.inputs)
                if not src.is_variable and src.opdef().state_reads}
            key = (node.op, repr(sorted(node.attrs.items())),
                   repr(sorted(sources.items())))
            if key not in groups:
                groups[key] = [0, reads(node.attrs, self.capacity, sources)]
            groups[key][0] += 1
        self._reads = [tuple(group) for group in groups.values()]
        # rings: the shortest one's rows and the window it serves
        self._ring = min(rings, default=None)
        # seconds the latest ``step`` spent staging and launching and
        # the latest ``select_rows`` took, on the clock its caller
        # handed it (``now=``); None where the caller handed none
        self.last_stage = self.last_launch = self.last_select = None
        self._donated = _telemetry.metrics.held_counters(
            "serve.decode.state.donated_bytes", model=name)
        if self.summarises:
            self.window = int(
                exe.aux_dict[self._state["window"][0]].shape[2])

    def add_window(self, step_len, module, packed=None):
        """Register an S-token window module. It MUST have been bound
        with ``shared_module=`` this driver's S=1 module (or a module
        sharing its cells) so both programs advance the SAME device
        cache/cursor cells — the executor-group aux-sharing rule makes
        that automatic when slot count and capacity agree.

        ``packed`` is ``(module, R)``: a module bound the same way over
        ``packed_window``'s form of the same graph, whose row-wise
        operations run over R packed rows and whose output is each
        slot's last fed row alone. ``step`` launches it for a window
        whose slots are fed no more than R rows between them, and
        ``module`` for any other: the same state, and of the outputs
        the row that a serving window's caller reads."""
        self._windows[int(step_len)] = module
        self._stagers[int(step_len)] = module._exec_group.input_stager()
        if packed is not None:
            form, rows = packed
            self._packed[int(step_len)] = (
                form, form._exec_group.input_stager(), int(rows),
                copy_sites(form.symbol, step_len))

    def window_budget(self, step_len):
        """R, the rows that the slots of one ``step_len`` window may be
        fed between them and still take the packed program; None where
        the driver has none for that length."""
        packed = self._packed.get(int(step_len))
        return None if packed is None else packed[2]

    @property
    def window_lens(self):
        return sorted(self._windows)

    @property
    def ring_slack(self):
        """How far back of its cursor a slot of a graph with rings can
        be rewound: what the shortest ring holds beyond its window and
        the rows of the largest dispatch (whose pads may have been
        written behind the cursor), and one. None without a ring."""
        if self._ring is None:
            return None
        rows, window = self._ring
        return rows - window - max([1] + self.window_lens) + 1

    def _cells(self, family):
        """(name, cell) of every aux cell of one state family, in graph
        order."""
        aux = self._mod._exec_group.executor.aux_dict
        return [(nm, aux[nm]) for nm in self._state.get(family, ())]

    def slot_cells(self):
        """(name, cell) of every per-slot state cell, all families:
        what a slot that moves to another pool takes along."""
        return [nc for family in self._state for nc in self._cells(family)]

    def _cursor_cells(self):
        return [cell for _nm, cell in self._cells("cursor")]

    def _kv_cells(self):
        """(name, cell) for every layer's K and V cache, in graph
        order — the prefix store snapshots/restores these rows. Only a
        positional graph has them to give."""
        if not self.positional:
            raise MXNetError(
                "this decoder's state is not a row per position "
                f"(families {sorted(self._state)}): a prefix of it "
                "cannot be captured or restored by row copy")
        return self._cells("rows")

    @property
    def routed(self):
        """Does the graph route tokens to experts (``MoEFFN``)?"""
        return bool(self._moe_cells)

    def moe_stats_begin(self):
        """Start copying the latest dispatch's per-layer ``moe_stats``
        cells to the host (a few int32 a layer) and return what
        ``moe_stats`` reads; call it right after ``step`` so that the
        copy rides behind the program, beside the ids'. The cells go
        through one small program (``moe_stats_<slots>``: a stack, one
        array and one copy whatever the layers) because the next step
        takes every aux array over (``donates_aux``): what comes back
        here is the caller's, and outlives a step launched before it is
        read. None for a dense decoder."""
        if not self._moe_cells:
            return None
        if self._moe_program is None:
            import jax
            import jax.numpy as jnp

            def moe_stats(cells):
                return jnp.stack(cells)

            moe_stats.__name__ = f"moe_stats_{self.slots}"
            self._moe_program = jax.jit(moe_stats)
        stats = self._moe_program(tuple(c.asjax() for c in self._moe_cells))
        stats.copy_to_host_async()
        return stats

    def moe_stats(self, stats):
        """``{count: integer}`` (the op's ``state_reads`` names: layer
        executions, assignments, experts touched, the busiest expert's
        load, ...) summed over the layers of one dispatch (S=1 or
        window: the programs share the cells), from
        ``moe_stats_begin``'s array. Read it once the dispatch's ids
        are on the host: the program has then finished, and nothing
        further is waited for."""
        total = np.sum(np.asarray(stats, np.int64), axis=0)
        return dict(zip(self._moe_counts, total.tolist()))

    def free_slots(self):
        """Slot indices with no active sequence."""
        return [i for i in range(self.slots) if not self.active[i]]

    def _set_cursors(self, rows, positions, where=None):
        """Set the device cursor of every slot in ``rows`` to its entry
        of ``positions`` in every layer, and the host mirror with it:
        one launch of one program that takes all ``*cache_pos`` cells
        (donated), a (slots,) position vector and a (slots,) mask, and
        returns ``where(mask, position, cell)`` for each. Rows not named
        keep their value; every cell keeps its placement and dtype and
        gets a buffer of its own. A row named twice is refused.
        ``where`` (``rewind_many``) takes the mask's place as it lies on
        the device."""
        rows = np.asarray(rows, np.int64).reshape(-1)
        if not rows.size:
            return
        positions = np.asarray(positions, np.int32).reshape(-1)
        if positions.shape != rows.shape:
            raise MXNetError(f"{rows.size} cursor rows but "
                             f"{positions.size} positions")
        target = np.zeros(self.slots, np.int32)
        mask = np.zeros(self.slots, bool)
        target[rows] = positions
        mask[rows] = True
        if mask.sum() != rows.size:
            raise MXNetError(f"slot named twice in one cursor update: "
                             f"{rows.tolist()}")
        if where is not None and self._sent_back is not None:
            raise MXNetError(
                "rewind_many(where=) before kept() has said who of "
                f"slots {self._sent_back[0].tolist()} stayed")
        if self._ring is not None:
            cur = self.pos[rows]
            back = cur - positions
            bad = (positions != 0) & ((back < 0) | (back > self.ring_slack))
            if bad.any():
                raise MXNetError(
                    f"cursor of slot(s) {rows[bad].tolist()} cannot move "
                    f"from {cur[bad].tolist()} to "
                    f"{positions[bad].tolist()}: this decoder's sliding "
                    f"layers keep rings (families {sorted(self._state)}; "
                    f"{self._ring[0]} rows for a window of "
                    f"{self._ring[1]}), so a cursor goes to 0 or back by "
                    f"at most {self.ring_slack}, inside what the ring "
                    "still holds")
        if self._carried:
            cur = self.pos[rows]
            bad = (positions != 0) & (positions != cur)
            if bad.any():
                raise MXNetError(
                    f"cursor of slot(s) {rows[bad].tolist()} cannot move "
                    f"from {cur[bad].tolist()} to "
                    f"{positions[bad].tolist()}: this decoder carries "
                    f"state from token to token (families "
                    f"{self._carried}) that holds every token since the "
                    "slot joined and no position to go back to, so a "
                    "cursor goes to 0 or stays")
        if self.summarises:
            cur = self.pos[rows]
            ends = (positions // self.window + 1) * self.window
            bad = (positions != 0) & ((positions > cur) | (cur > ends))
            if bad.any():
                raise MXNetError(
                    f"cursor of slot(s) {rows[bad].tolist()} cannot move "
                    f"from {cur[bad].tolist()} to "
                    f"{positions[bad].tolist()}: this decoder keeps the "
                    f"open window's {self.window} exact rows and "
                    "summaries of everything older, so a cursor goes to "
                    "0 or back inside the window whose rows are still "
                    "there")
        cells = self._cursor_cells()
        arrays = tuple(cell.asjax() for cell in cells)
        if self._cursor_program is None:
            import jax
            import jax.numpy as jnp

            def cursor_update(cells, pos, mask):
                return tuple(
                    jnp.where(mask[:, None],
                              pos[:, None].astype(cell.dtype), cell)
                    for cell in cells)

            cursor_update.__name__ = f"cursor_update_{self.slots}"
            self._cursor_program = jax.jit(
                cursor_update, donate_argnums=0,
                out_shardings=tuple(a.sharding for a in arrays))
        for cell, new in zip(cells, self._cursor_program(
                arrays, target, mask if where is None else where)):
            cell._set(new)
        if where is not None:
            self._sent_back = rows, self.pos[rows].copy()
        self.pos[rows] = positions
        if self.name is not None:
            _telemetry.counter("serve.decode.cursor.updates",
                               model=self.name).inc()
            if where is None:       # else ``kept`` counts who moved
                _telemetry.counter("serve.decode.cursor.rows",
                                   model=self.name).inc(int(rows.size))

    def select_rows(self, out, idx, feed=None, now=None):
        """From a step's ``(slots, S, V)`` output as it lies on the
        device, ``rows = out[slot, idx[slot]]`` as ``(slots, V)`` (the
        bytes the host would have indexed, untouched; of an output of
        one row a slot - an S=1 step's, or a packed window's, which is
        each slot's last fed row already - that row, whatever row of
        the window ``idx`` names), ``ids =
        argmax(rows, -1)`` as ``(slots,)`` int32, the first maximum as
        ``np.argmax`` takes it, and ``tokens``, the same ids as the S=1
        program takes its token input (``(slots, 1)``, the data cell's
        dtype, where a step's batch lies, so that ``step(tokens)``
        puts nothing): one launch of ``select_rows_<slots>x<S>`` (its
        name in the trace), one program per step length whatever
        ``idx`` holds. All three stay on the device; the copy of
        ``ids`` to the host starts here, behind the step program.
        ``idx`` is (slots,) ints in ``[0, S)`` of an S-row output and
        not below 0 of any; ``feed`` (slots,) bools
        says whose id ``tokens`` carries (None: every slot's), and the
        others ride 0, as a row nobody owns does when the host builds
        the tokens. All of it is the annotation ``decode.select_rows``;
        ``now`` (a clock's read) makes ``last_select`` its seconds."""
        t0 = None if now is None else now()
        with _telemetry.span("decode.select_rows"):
            arr = out.asjax()
            S = arr.shape[1]
            idx = np.asarray(idx, np.int32).reshape(-1)
            if idx.shape != (self.slots,) or idx.min() < 0 \
                    or S > 1 and idx.max() >= S:
                raise MXNetError(
                    f"select_rows() wants ({self.slots},) row "
                    f"indices in [0, {S}), got {idx.tolist()}")
            if S == 1:
                idx = np.zeros(self.slots, np.int32)
            feed = np.ones(self.slots, bool) if feed is None \
                else np.asarray(feed, bool).reshape(-1)
            if feed.shape != (self.slots,):
                raise MXNetError(f"select_rows() wants ({self.slots},) "
                                 f"feed flags, got {feed.shape}")
            program = self._select_programs.get(S)
            if program is None:
                import jax
                import jax.numpy as jnp
                token_dtype = self._mod._exec_group.executor \
                    .arg_dict[self._mod.data_names[0]].dtype

                def select_rows(out, idx, feed):
                    rows = out[jnp.arange(out.shape[0]), idx]
                    ids = jnp.argmax(rows, axis=-1).astype(jnp.int32)
                    tokens = jnp.where(feed, ids, 0)[:, None]
                    return rows, ids, tokens.astype(token_dtype)

                select_rows.__name__ = f"select_rows_{self.slots}x{S}"
                program = self._select_programs[S] = jax.jit(select_rows)
            rows, ids, tokens = program(arr, idx, feed)
            ids.copy_to_host_async()
        self.last_select = None if now is None else now() - t0
        return rows, ids, tokens

    def denoise_select(self, out, ids, undecided, quota, threshold,
                       now=None):
        """What one feed of a block decides, on the device: from a
        step's ``(slots, L, V)`` output as it lies there, the block's
        ``ids`` ``(slots, L)`` as they were fed, which of its positions
        are ``undecided`` ``(slots, L)`` bools, and each slot's
        ``quota`` ``(slots,)`` ints and ``threshold`` ``(slots,)``
        floats: at every undecided position ``x0 = argmax`` (the first
        maximum) with confidence ``c = softmax(logits)[x0]`` in
        float32; the positions with ``c > threshold`` are decided, and
        the ``quota`` most confident whatever the threshold (the
        earlier position first among equals). ``ids`` and ``undecided``
        may be device arrays, ``merge_block``'s first two results,
        taken as they lie (the same program). Returns ``(2, slots,
        L)`` int32 on the device, its copy to the host started: the
        ids with ``x0`` at the positions decided, and 1 where a
        position is still undecided. A slot with nothing undecided (a
        feed that commits; a row nobody owns) comes back as it went
        in. One launch of ``denoise_select_<slots>x<L>`` (its name in
        the trace): **ids and a mask cross to the host, 8 x L bytes a
        slot, never logits**, as ``select_rows`` does for one row. The
        annotation is ``decode.denoise_select``; ``now`` makes
        ``last_select`` its seconds."""
        import jax
        t0 = None if now is None else now()
        with _telemetry.span("decode.denoise_select"):
            arr = out.asjax()
            L = arr.shape[1]
            if not isinstance(ids, jax.Array):
                ids = np.asarray(ids, np.int32)
            if not isinstance(undecided, jax.Array):
                undecided = np.asarray(undecided, bool)
            if arr.ndim != 3 or ids.shape != (self.slots, L) \
                    or undecided.shape != (self.slots, L):
                raise MXNetError(
                    f"denoise_select() wants ({self.slots}, L, V) rows "
                    f"with ({self.slots}, L) ids and flags, got "
                    f"{arr.shape}, {ids.shape}, {undecided.shape}")
            program = self._denoise_programs.get(L)
            if program is None:
                import jax.numpy as jnp

                def denoise_select(rows, ids, undecided, quota, threshold):
                    rows = rows.astype(jnp.float32)
                    x0 = jnp.argmax(rows, axis=-1).astype(jnp.int32)
                    conf = jnp.where(
                        undecided,
                        jnp.max(jax.nn.softmax(rows, axis=-1), axis=-1),
                        -jnp.inf)
                    # a position's place among its block's confidences
                    order = jnp.argsort(-conf, axis=-1, stable=True)
                    rank = jnp.argsort(order, axis=-1, stable=True)
                    decided = undecided & ((conf > threshold[:, None])
                                           | (rank < quota[:, None]))
                    return jnp.stack([
                        jnp.where(decided, x0, ids),
                        (undecided & ~decided).astype(jnp.int32)])

                denoise_select.__name__ = f"denoise_select_{self.slots}x{L}"
                program = self._denoise_programs[L] = jax.jit(denoise_select)
            state = program(arr, ids, undecided,
                            np.asarray(quota, np.int32).reshape(self.slots),
                            np.asarray(threshold, np.float32)
                            .reshape(self.slots))
            state.copy_to_host_async()
        self.last_select = None if now is None else now() - t0
        return state

    def merge_tokens(self, tokens, ids, chip):
        """The token input of a step that takes some slots' first token
        from the chip: the host's ``(slots, S)`` ``tokens`` with column
        0 of the slots that ``chip`` (``(slots,)`` bools) names taken
        from ``ids``, ``select_rows``' third result of the step before
        as it lies on the device. One launch of
        ``merge_tokens_<slots>x<S>`` (its name in the trace), one
        program a step length whatever ``chip`` holds; the result stays
        on the device, in the data cell's dtype and where a step's
        batch lies, so that ``step`` puts nothing and launches the
        program it launches for the host's tokens."""
        tokens = np.asarray(tokens)
        chip = np.asarray(chip, bool).reshape(-1)
        S = tokens.shape[-1]
        if tokens.shape != (self.slots, S) or \
                chip.shape != (self.slots,) or \
                ids.shape != (self.slots, 1):
            raise MXNetError(
                f"merge_tokens() wants ({self.slots}, S) tokens, "
                f"({self.slots}, 1) ids and ({self.slots},) flags, "
                f"got {tokens.shape}, {ids.shape}, {chip.shape}")
        program = self._merge_programs.get(S)
        if program is None:
            import jax
            import jax.numpy as jnp

            def merge_tokens(tokens, ids, chip):
                first = jnp.arange(tokens.shape[1]) == 0
                return jnp.where(chip[:, None] & first[None, :],
                                 ids, tokens.astype(ids.dtype))

            merge_tokens.__name__ = f"merge_tokens_{self.slots}x{S}"
            program = self._merge_programs[S] = jax.jit(merge_tokens)
        return program(tokens, ids, chip)

    def merge_block(self, tokens, undecided, state, chip):
        """What a feed of one block a slot takes where some slots'
        blocks are the chip's to give: the host's ``(slots, L)``
        ``tokens`` and ``undecided`` with the rows of the slots that
        ``chip`` (``(slots,)`` bools) names taken from ``state``,
        ``denoise_select``'s ``(2, slots, L)`` of the feed before as it
        lies on the device - its ids, and its mask of what is still
        undecided. Returns ``(ids, undecided, back)``, all on the
        device: the ids in the data cell's dtype and where a step's
        batch lies (``step`` puts nothing), the mask as
        ``denoise_select`` takes it, and ``back`` ``(slots,)`` bools,
        the slots whose block has a position undecided - whose feed
        keeps nothing, and whose cursor ``rewind_many(where=back)``
        puts back behind the step. One launch of
        ``merge_block_<slots>x<L>`` (its name in the trace), one
        program a rung whatever ``chip`` holds."""
        tokens = np.asarray(tokens)
        undecided = np.asarray(undecided, bool)
        chip = np.asarray(chip, bool).reshape(-1)
        L = tokens.shape[-1]
        if tokens.shape != (self.slots, L) or undecided.shape != tokens.shape \
                or chip.shape != (self.slots,) \
                or state.shape != (2, self.slots, L):
            raise MXNetError(
                f"merge_block() wants ({self.slots}, L) tokens and flags, "
                f"(2, {self.slots}, L) ids and mask and ({self.slots},) "
                f"flags, got {tokens.shape}, {undecided.shape}, "
                f"{state.shape}, {chip.shape}")
        program = self._block_programs.get(L)
        if program is None:
            import jax
            import jax.numpy as jnp
            token_dtype = self._mod._exec_group.executor \
                .arg_dict[self._mod.data_names[0]].dtype

            def merge_block(tokens, undecided, state, chip):
                chip = chip[:, None]
                ids = jnp.where(chip, state[0], tokens.astype(state.dtype))
                left = jnp.where(chip, state[1] != 0, undecided)
                return ids.astype(token_dtype), left, jnp.any(left, axis=-1)

            merge_block.__name__ = f"merge_block_{self.slots}x{L}"
            program = self._block_programs[L] = jax.jit(merge_block)
        return program(tokens, undecided, state, chip)

    def join(self, slot):
        """Claim ``slot`` for a new sequence: set its device cursor to
        0 in every layer (one launch of the cursor program — never a
        compile after warm-up) and mark it active. The cache rows are
        NOT zeroed: every position a fresh sequence attends is
        rewritten by it first, and masked positions carry exactly zero
        softmax weight, so reuse is bit-clean."""
        slot = int(slot)
        if self.active[slot]:
            raise MXNetError(f"slot {slot} already holds an active "
                             "sequence (leave() it first)")
        self._set_cursors([slot], [0])
        self.active[slot] = True
        return slot

    def leave(self, slot):
        """Release ``slot`` host-side. No device work: the retired row
        keeps advancing as a masked no-op until the next join."""
        self.active[int(slot)] = False

    def rewind(self, slot, pos):
        """Set ``slot``'s device cursor to ``pos`` in every layer (one
        launch of the cursor program, like ``join``). Used to discard
        the tail of a window after dispatch: padded final prefill
        chunks, rejected speculative proposals, and decoding slots
        riding a chunk dispatch all rewind to the stream position they
        actually reached. Cache rows past ``pos`` become garbage nobody
        attends (exp(-inf)-masked) and are rewritten before first read —
        the same bit-clean contract as ``join``."""
        self._set_cursors([slot], [pos])

    def rewind_many(self, slots, positions, where=None):
        """Batched ``rewind``: still ONE launch of the cursor program,
        for any number of distinct slots (the chunk-dispatch epilogue
        touches most of a rung); an empty list launches nothing.

        ``where`` - ``(slots,)`` bools as they lie on the device,
        ``merge_block``'s third result - is which of ``slots`` go back:
        it takes the mask's place in the same program, so it may name
        nobody outside ``slots``. The host cannot know who stayed: the
        mirror ``pos`` takes ``positions`` for every slot named until
        whoever fetches what ``where`` was computed from says who did
        (``kept``), before a step or a cursor update over them and
        before the next call with ``where``."""
        self._set_cursors(slots, positions, where=where)

    def kept(self, slots):
        """Who stayed of the slots that the last
        ``rewind_many(where=)`` named (``where`` read false there, as
        the caller has it on the host now): their mirror ``pos`` goes
        back to what it was before that call, as the device's cells
        stand; the others moved and are counted
        (``serve.decode.cursor.rows``)."""
        if self._sent_back is None:
            raise MXNetError("kept() with no rewind_many(where=) before")
        rows, before = self._sent_back
        stayed = np.isin(rows, np.asarray(slots, np.int64).reshape(-1))
        if stayed.sum() != np.size(slots):
            raise MXNetError(
                f"kept({list(slots)}) names a slot that the last "
                f"rewind_many(where=) did not: {rows.tolist()}")
        self._sent_back = None
        self.pos[rows[stayed]] = before[stayed]
        if self.name is not None:
            _telemetry.counter("serve.decode.cursor.rows", model=self.name) \
                .inc(int((~stayed).sum()))

    @property
    def row_block(self):
        """Rows a slot that one launch of the capture and restore
        programs moves: the largest window's, so that a join lands in
        as many launches as its prefill would have taken dispatches."""
        return min(self.capacity, max(self.window_lens or [256]))

    def _row_programs(self):
        """This driver's ``row_programs``, built at first use."""
        if self._row_progs is None:
            self._row_progs = row_programs(
                self.slots, self.row_block,
                [cell.asjax().sharding for _nm, cell in self._kv_cells()])
        return self._row_progs

    def _row_slot(self, slot):
        # the programs clamp an index they cannot reach: refuse it here
        if not 0 <= int(slot) < self.slots:
            raise MXNetError(f"slot {slot} of a pool of {self.slots}")
        return np.int32(slot)

    def capture_rows(self, slot, length):
        """Snapshot ``slot``'s first ``length`` cache positions across
        every layer: ``{cell_name: (heads, length, width) np.ndarray}``.
        The prefix store keeps these host-side under its byte budget.
        ``row_block`` rows a launch of one program, whatever ``slot``
        and ``length`` (no compile after ``warm_rows``); only the
        slot's rows are read and brought to the host, never a pool."""
        slot = self._row_slot(slot)
        if not 0 <= int(length) <= self.capacity:
            raise MXNetError(f"capture_rows: {length} rows of a capacity "
                             f"of {self.capacity}")
        cells = self._kv_cells()
        capture, _ = self._row_programs()
        pools = tuple(cell.asjax() for _nm, cell in cells)
        parts = []
        for start, skip, n in row_blocks(length, self.row_block,
                                         self.capacity):
            out = capture(pools, slot, np.int32(start))
            for arr in out:
                arr.copy_to_host_async()
            parts.append((out, skip, n))
        return {nm: np.concatenate(
            [np.asarray(out[i])[:, skip:skip + n]
             for out, skip, n in parts], axis=1)
            if parts else np.zeros(
                (cell.shape[1], 0, cell.shape[3]), str(cell.dtype))
            for i, (nm, cell) in enumerate(cells)}

    def restore_rows(self, slot, rows):
        """Write captured rows back into ``slot`` (prefix-cache join),
        bitwise the values ``capture_rows`` saw: ``row_block`` rows a
        launch of one donated program (``_row_programs``), the last
        block padded, so that a join of any length compiles nothing and
        copies no pool. Rows of the slot past the restored ones are
        don't-cares, as after a ``rewind``. The caller sets the cursor.
        Returns the bytes put to the device."""
        slot = self._row_slot(slot)
        cells = self._kv_cells()
        _, restore = self._row_programs()
        block = self.row_block
        length = {rows[nm].shape[1] for nm, _cell in cells}
        if len(length) != 1 or max(length) > self.capacity:
            raise MXNetError(f"restore_rows: rows of lengths "
                             f"{sorted(length)} for pools of "
                             f"{self.capacity}")
        put = 0
        host = [np.asarray(rows[nm], dtype=str(cell.dtype))
                for nm, cell in cells]
        for start, skip, n in row_blocks(length.pop(), block,
                                         self.capacity):
            at = start + skip
            chunk = []
            for src in host:
                part = src[:, start:at + n]
                if part.shape[1] < block:
                    part = np.concatenate([part, np.zeros(
                        (part.shape[0], block - part.shape[1],
                         part.shape[2]), part.dtype)], axis=1)
                chunk.append(part)
                put += part.nbytes
            pools = tuple(cell.asjax() for _nm, cell in cells)
            for (_nm, cell), new in zip(cells, restore(
                    pools, tuple(chunk), slot, np.int32(start))):
                cell._set(new)
        return put

    def warm_rows(self):
        """Compile ``capture_rows`` and ``restore_rows`` (one block of
        slot 0 out and back in: warm-up's slots are free)."""
        self.restore_rows(0, self.capture_rows(0, self.row_block))

    def release_outputs(self):
        """Make the latest ``step``'s outputs the caller's alone: the
        program that ran lets go of them (``Executor.release_outputs``),
        so their device memory goes when the caller drops what ``step``
        handed it, not at that program's next run."""
        if self._stepped is not None:
            self._stepped._exec_group.executor.release_outputs()
            self._stepped = None

    def overflowing(self, window=1):
        """Active slots whose next ``window``-token dispatch would pass
        capacity — the scheduler retires these (alone) before dispatch."""
        return [i for i in range(self.slots)
                if self.active[i] and self.pos[i] + window > self.capacity]

    def step(self, tokens, fed=None, now=None):
        """Advance every slot by one S-token window: ``tokens``
        (slots,) or (slots, S) int ids (retired slots ride any valid
        id, 0 by convention) -> logits (slots, S, V) NDArray, or of a
        packed window (below) (slots, 1, V). S=1 runs
        the steady-state decode program; S>1 dispatches the matching
        window module registered via ``add_window``. Raises per slot
        BEFORE dispatch when an active slot would overflow its cache —
        batchmates are untouched (nothing was dispatched).

        ``tokens`` may be a device array, ``select_rows``' third result:
        the ids of the step before as they lie on the chip, which the
        program takes as they are (no put) whether or not that step has
        run yet. Everything else a dispatch needs - positions, ``fed``,
        the overflow check, ``last_reads``, ``pos`` - comes from the
        host's cursor mirror and never from the ids.

        The program advances slot ``b`` by ``fed[b]`` of its S tokens
        (0..S; None feeds every slot all S) and leaves a slot with no
        room for S positions where it is. Where the window has a packed
        program (``add_window(packed=)``) and ``fed`` is given and sums to no
        more than its budget, that is the program launched: its
        row-wise operations run over the budget's rows, not ``slots x
        S`` (``last_program_rows`` says which ran; ``last_copy_sites``
        how many copies of rows it holds and how many of them without a
        loop), its head over each
        slot's last fed row (``last_head_rows``: ``slots``), and what
        it returns is that row alone, ``(slots, 1, V)``: row
        ``fed[b] - 1`` of slot ``b``'s window, the row a serving window
        samples from (for a slot fed nothing a finite row nobody
        reads). ``select_rows`` takes it as it takes an S=1 step's. Who
        wants every row of a window steps without ``fed``, or reads a
        driver that has no packed form.

        Two annotations: ``decode.step.stage`` (the checks, the host
        arrays and their puts, what the dispatch reads of the state)
        and ``decode.step.launch`` (``forward`` to ``get_outputs``: the
        jitted call). ``now`` (a clock's read, the scheduler's) makes
        ``last_stage`` and ``last_launch`` their seconds; without it no
        clock is read."""
        import jax
        from ..io import DataBatch
        t0 = None if now is None else now()
        with _telemetry.span("decode.step.stage"):
            if not isinstance(tokens, jax.Array):
                tokens = np.asarray(tokens)
            if tokens.ndim == 1:
                tokens = tokens[:, None]
            S = tokens.shape[1]
            if tokens.shape != (self.slots, S) or S < 1:
                raise MXNetError(f"step() wants ({self.slots}, S) tokens, "
                                 f"got {tokens.shape}")
            stage = self._stagers.get(S)
            self.last_program_rows = self.last_head_rows = self.slots * S
            self.last_copy_sites = (0, 0)
            if S == 1:
                mod = self._mod
            else:
                mod = self._windows.get(S)
                if mod is None:
                    raise MXNetError(
                        f"no window module for step_len={S} (have "
                        f"{self.window_lens}); add_window() it at engine "
                        "warmup — steady-state dispatch never compiles")
            over = self.overflowing(S)
            if over:
                raise MXNetError(
                    f"KV cache overflow in slot(s) {over}: position "
                    f"{[int(self.pos[i]) for i in over]} + {S} exceeds "
                    f"capacity {self.capacity}; retire the sequence(s) or "
                    "re-bind with a larger capacity")
            packed = None if fed is None else self._packed.get(S)
            fed = np.full(self.slots, S, np.int64) if fed is None \
                else np.asarray(fed, np.int64).reshape(-1)
            if fed.shape != (self.slots,) or fed.min() < 0 \
                    or fed.max() > S:
                raise MXNetError(
                    f"step() wants ({self.slots},) fed counts in "
                    f"[0, {S}], got {fed.tolist()}")
            # the program's own rule, mirrored: no room for S, nothing
            # fed
            fed = np.where(self.pos + S <= self.capacity, fed, 0)
            if packed is not None and fed.sum() <= packed[2]:
                mod, stage, self.last_program_rows, self.last_copy_sites \
                    = packed
                self.last_head_rows = self.slots
            self.last_reads = self._dispatch_reads(fed)
            if self.name is not None:    # the pools' bytes a dispatch
                self._donated()[0].inc(self.donated_bytes)
            hosts = [tokens]
            if self.pos_embed == "learned":
                pos = self.pos[:, None] + np.arange(S)[None, :]
                hosts.append(np.minimum(pos, self.capacity - 1))
            hosts.append(fed)
            data = stage(hosts)
        t1 = None if now is None else now()
        with _telemetry.span("decode.step.launch"):
            mod.forward(DataBatch(data=data, label=[]), is_train=False)
            out = mod.get_outputs()[0]
        self._stepped = mod
        self.pos += fed
        self.last_stage, self.last_launch = (None, None) if now is None \
            else (t1 - t0, now() - t1)
        return out

    def _dispatch_reads(self, fed):
        """What one dispatch that feeds ``fed`` tokens a slot reads of
        the state, from the cursors alone (no fetch): the sum over the
        graph's stateful layers of what each op declares
        (``OpDef.state_reads``), a few numpy operations for each kind
        of layer and none for each layer."""
        total = {}
        for executions, reads in self._reads:
            for count, value in reads(self.pos, fed).items():
                total[count] = total.get(count, 0) + executions * value
        return total
