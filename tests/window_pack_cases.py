"""The slot-pooled decoders at tiny sizes - GLM-5.2's block, A.X-K1's,
Xing4.0's, Trinity's, Granite 4.0-H's, Ling-3.0's and EvaByte's, which
are served alone (``BLOCKS``),
and the two that are trained too (``FUSED``: GPT-2's with learned and
with rotary positions, OLMoE's), fed like the others since ISSUE 47 -
for the tests of a window's packed rows (``tests/test_decode_pack.py``)
and of the text their programs lower to (``tests/test_chip_compile.py``):
graphs, parameters, bound drivers with the whole-window and the packed
program, and a program's lowered text."""
import numpy as np

import jax

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm

CAPACITY, WINDOW, SLOTS = 128, 16, 4            # WINDOW: the S > 1 program

_LATENT = {"q_lora_rank": 48, "kv_lora_rank": 64, "qk_nope_head_dim": 24,
           "qk_rope_head_dim": 16, "first_k_dense_replace": 1,
           "intermediate_size": 96, "moe_intermediate_size": 32,
           "num_experts_per_tok": 4, "n_shared_experts": 1,
           "routed_scaling_factor": 2.5, "norm_topk_prob": True}
_GLM = dict(_LATENT, v_head_dim=32, index_n_heads=16, index_head_dim=32,
            index_topk=16, indexer_types=["full", "full", "shared"],
            n_routed_experts=16, held=(4, 4))
_AXK1 = dict(_LATENT, v_head_dim=16, n_routed_experts=24,
             num_experts_per_tok=8, n_group=4, topk_group=2, held=(3, 3),
             rope_scaling={"type": "yarn", "factor": 8,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 4, "beta_slow": 1, "mscale": 1,
                           "mscale_all_dim": 1})
_XING4 = dict(_AXK1, n_routed_experts=16, num_experts_per_tok=4, n_group=1,
              topk_group=1, routed_scaling_factor=2.0, held=None,
              hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
              mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
_AFMOE = {"num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
          "layer_types": ["sliding_attention", "full_attention",
                          "sliding_attention"],
          "num_dense_layers": 1, "intermediate_size": 96,
          "moe_intermediate_size": 32, "num_experts": 16,
          "num_experts_per_tok": 4, "num_shared_experts": 1,
          "route_norm": True, "route_scale": 2.826}

_GRANITE = {"num_key_value_heads": 2,
            "layer_types": ["mamba", "attention", "mamba"],
            "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
            "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
            "mamba_chunk_size": 8, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "shared_intermediate_size": 96,
            "num_local_experts": 0, "num_experts_per_tok": 0,
            "intermediate_size": 96, "position_embedding_type": "nope",
            "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
            "attention_multiplier": 0.125, "logits_scaling": 8.0}

_LING = {"layer_types": ["kda", "kda", "mla"], "head_dim": 16,
         "short_conv_kernel_size": 4, "kda_lower_bound": -5,
         "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
         "linear_silu": True, "group_norm_size": 1,
         "num_kv_heads_for_linear_attn": 0, "q_lora_rank": None,
         "kv_lora_rank": 64, "qk_nope_head_dim": 24, "qk_rope_head_dim": 16,
         "v_head_dim": 16, "rope_scaling": None,
         "gated_attention_proj_granularity_type": "head_wise",
         "use_mla_nope": False, "first_k_dense_replace": 1,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "moe_shared_expert_intermediate_size": 32, "num_experts": 16,
         "num_experts_per_tok": 4, "num_shared_experts": 1,
         "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 4,
         "topk_group": 2, "moe_router_enable_expert_bias": True,
         "scale_router_input": False,
         "expert_swiglu_limit_list": [0, 0, 0],
         "share_expert_swiglu_limit_list": [0, 0, 0], "up_proj_norm": False,
         "value_norm": False, "use_nGPT": False, "held": (4, 8),
         "kda_chunk": 8}

#: block -> ``get_decode_symbol``'s arguments beside the step length
BLOCKS = {
    "glm_dsa": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                    rope_base=8e6, glm=_GLM),
    "axk1": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                 rope_base=1e4, rms_eps=1e-6, axk1=_AXK1),
    "xing4": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                  rope_base=1e4, rms_eps=1e-6, xing4=_XING4),
    "afmoe": dict(vocab_size=48, d_model=64, n_layer=3, n_head=8,
                  rope_base=1e4, afmoe=_AFMOE, max_step_len=WINDOW),
    "granite_hybrid": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                           granite=_GRANITE),
    "ling_hybrid": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                        rope_base=6e6, rms_eps=1e-6, ling=_LING),
    "evabyte": dict(vocab_size=40, d_model=32, n_layer=2, n_head=2,
                    rope_base=1e5, window=32, chunk=4, n_pred_heads=2,
                    ffn_width=48),
}

#: the blocks with a training form (``_fused_attention``), likewise:
#: every keyword of the graph (``block=`` where the case's name is not
#: the block's)
FUSED = {
    "gpt2": dict(vocab_size=48, d_model=32, n_layer=2, n_head=2,
                 pos_embed="learned", max_seq_len=CAPACITY),
    "gpt2_rotary": dict(block="gpt2", vocab_size=48, d_model=32, n_layer=2,
                        n_head=2, pos_embed="rotary", rope_base=1e4),
    "olmoe": dict(vocab_size=48, d_model=32, n_layer=2, n_head=2,
                  pos_embed="rotary", rope_base=1e4, n_expert=8, top_k=2,
                  expert_width=24, norm_topk=False, rms_eps=1e-5,
                  tie_head=False, embed_scale=False),
}

#: every case that takes ``fed``: all of them
FED = sorted(BLOCKS) + sorted(FUSED)


def config(case):
    """``get_decode_symbol``'s keywords of a case beside the step
    length, the capacity and ``per_slot``."""
    if case in FUSED:
        return dict({"block": case}, **FUSED[case])
    return dict(BLOCKS[case], block=case, pos_embed="rotary",
                tie_head=False, embed_scale=case == "afmoe")


def symbol(case, step_len):
    return tfm.get_decode_symbol(step_len=step_len, capacity=CAPACITY,
                                 per_slot=True, **config(case))


def unfed_symbol(case, step_len, **kw):
    """A slot-pooled graph built by hand, as ``get_decode_symbol`` built
    the ``FUSED`` blocks before ISSUE 47: no ``fed`` input, every slot
    advances by ``step_len`` and whoever drives it rewinds the slots
    that fed fewer. What the scheduler's and the driver's unfed
    branches still serve, and the reference that the fed graphs are
    held to. ``kw`` over the case's keywords."""
    import inspect
    given = {k: p.default for k, p in inspect.signature(
        tfm.get_decode_symbol).parameters.items()}
    given.update(config(case), step_len=step_len, capacity=CAPACITY,
                 per_slot=True)
    given.update(kw)
    spec = dict(tfm._spec(given, decode=True), fed=False)
    if spec["moe"]:
        spec["moe"] = {k: v for k, v in spec["moe"].items()
                       if k != "step_len"}
    logits, fed = tfm._logits(spec)
    assert fed is None
    return mx.sym.Reshape(logits, shape=(-1, step_len, given["vocab_size"]),
                          name=f"{given['name']}_logits_bsv")


def inputs(sym, slots, step_len):
    """The data descriptions of a decode graph, in the order the
    drivers stage them: tokens, learned positions, ``fed``."""
    shapes = {"data": ((slots, step_len), np.int32),
              "pos_ids": ((slots, step_len), np.float32),
              "fed": ((slots,), np.int32)}
    return [mx.io.DataDesc(nm, *shapes[nm]) for nm in shapes
            if nm in sym.list_arguments()]


def params(block, seed=5):
    sym = symbol(block, 1)
    given = {d.name: d.shape for d in inputs(sym, SLOTS, 1)}
    shapes, _, _ = sym.infer_shape(**given)
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in given:
            continue
        draw = rng.standard_normal(shape)
        gain = name.endswith(("_gamma", "_kv_norm_weight"))
        out[name] = ((0.3 * draw if block == "evabyte" else 1.0 + 0.3 * draw)
                     if gain else 0.25 * draw).astype(np.float32)
    return out


def bound(sym, step_len, shared=None, arg_params=None, slots=SLOTS):
    descs = inputs(sym, slots, step_len)
    mod = mx.mod.Module(sym, data_names=[d.name for d in descs],
                        label_names=[])
    mod.bind(descs, None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None, arg_params=dict(arg_params),
                        aux_params={}, allow_missing=True)
    return mod


def driver(block, packed=True, slots=SLOTS):
    """A ``slots``-slot driver of ``block`` with its window program of
    ``WINDOW`` rows a slot and, with ``packed``, the packed form of it
    beside (``tfm.packed_window``)."""
    base = bound(symbol(block, 1), 1, arg_params=params(block), slots=slots)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=slots,
                                    pos_embed=config(block)["pos_embed"])
    window = symbol(block, WINDOW)
    form = tfm.packed_window(window, slots) if packed else None
    drv.add_window(
        WINDOW, bound(window, WINDOW, shared=base, slots=slots),
        packed=form and (bound(form[0], WINDOW, shared=base, slots=slots),
                         form[1]))
    return drv


def unfed_driver(block, slots=SLOTS):
    """``driver`` over ``unfed_symbol``'s graphs: every slot advances
    by S, the caller rewinds."""
    base = bound(unfed_symbol(block, 1), 1, arg_params=params(block),
                 slots=slots)
    drv = tfm.BatchedKVCacheDecoder(base, CAPACITY, slots=slots,
                                    pos_embed=config(block)["pos_embed"])
    drv.add_window(WINDOW, bound(unfed_symbol(block, WINDOW), WINDOW,
                                 shared=base, slots=slots))
    return drv


def lowered_text(sym, slots, step_len):
    """The text that the inference program of ``sym`` bound at ``(slots,
    step_len)`` lowers to (the function ``Executor`` jits, on the CPU
    under whatever kernel tier is set)."""
    exe = bound(sym, step_len, arg_params={}, slots=slots) \
        ._exec_group.executor

    def prog(arg_vals, aux_vals, rng):
        return exe._runner(arg_vals, aux_vals, False, rng)

    return jax.jit(prog).lower(exe._arg_vals(), exe._aux_vals(),
                               jax.random.PRNGKey(0)).as_text()
