"""The slot-pooled decoders at tiny sizes, one table: GLM-5.2's block,
A.X-K1's, Xing4.0's, Trinity's, Granite 4.0-H's (dense and routed),
Ling-3.0's, SDAR's, Nemotron-H's and EvaByte's, which are served alone
(``BLOCKS``), and the
two that are trained too (``FUSED``: GPT-2's with learned and with
rotary positions, OLMoE's). A block's row holds what differs -
``get_decode_symbol``'s keywords, how its parameters are drawn
(``DRAWS``), its plain reference and the mapping to that reference's
``cfg`` (``REFERENCE``), its tolerance (``TOL``), its state families
(``FAMILIES``), what its builder refuses (``REFUSED``) and its own
schedules (``OWN_SCHEDULES``) - and the module what does not: graphs,
parameters, bound drivers with the whole-window and the packed program
under a kernel tier, a schedule of dispatches run through a driver, an
engine, a program's lowered text. ``tests/decode_block_suite.py`` runs
the behaviours every served block has over a row, each
``tests/test_<arch>.py`` imports it for its own; ``test_decode_pack.py``
and ``test_chip_compile.py`` read the same rows."""
import contextlib
import importlib
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:                # ``chipbench.reference``
    sys.path.insert(0, ROOT)

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
              hc_mult=4, hc_sinkhorn_iters=4, hc_eps=1e-6,
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

#: SDAR's: the published keys, and how the model decodes (blocks of 4
#: positions, the mask id the last row of the vocabulary, a position a
#: feed unless a confidence passes the threshold)
_SDAR = {"num_key_value_heads": 2, "head_dim": 16, "num_experts": 16,
         "num_experts_per_tok": 4, "moe_intermediate_size": 32,
         "norm_topk_prob": True, "block_length": 4, "mask_token_id": 47,
         "denoising_steps": 4, "remasking": "low_confidence_dynamic",
         "confidence_threshold": 0.9}

#: Nemotron-H's: a layer is one sub-layer (five of them: M E M * E),
#: B and C in two groups of four heads, a K/V head of 24 (not 64 / 4)
#: read by four query heads, ungated experts of which half are held
_NEMOTRON_H = {"hybrid_override_pattern": "MEM*E", "num_key_value_heads": 1,
               "head_dim": 24, "mamba_num_heads": 8, "mamba_head_dim": 8,
               "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4,
               "chunk_size": 8, "use_conv_bias": True,
               "mamba_proj_bias": False, "mamba_hidden_act": "silu",
               "attention_bias": False, "mlp_bias": False,
               "mlp_hidden_act": "relu2", "n_routed_experts": 8,
               "num_experts_per_tok": 3, "moe_intermediate_size": 24,
               "moe_shared_expert_intermediate_size": 40,
               "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
               "norm_topk_prob": True, "routed_scaling_factor": 2.5,
               "held": (2, 4)}

#: block -> ``get_decode_symbol``'s arguments beside the step length
BLOCKS = {
    "glm_dsa": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                    rope_base=8e6, glm=_GLM),
    "axk1": dict(vocab_size=48, d_model=64, n_layer=3, n_head=4,
                 rope_base=1e4, rms_eps=1e-6, axk1=_AXK1),
    # (two layers, dense and sparse, and 4 Sinkhorn rounds where 20 are
    # published: a program of three layers at 20 compiles in 9 s here)
    "xing4": dict(vocab_size=48, d_model=64, n_layer=2, n_head=4,
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
    "sdar_moe": dict(vocab_size=48, d_model=64, n_layer=3, n_head=8,
                     rope_base=1e6, rms_eps=1e-6, sdar=_SDAR),
    "nemotron_h": dict(vocab_size=48, d_model=64, n_layer=5, n_head=4,
                       nemotron_h=_NEMOTRON_H),
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

#: Granite 4.0-H Small's block: Micro's with routed experts beside the
#: shared feed-forward, half of them held (``block="granite_hybrid"``);
#: served by the suite, not among the packed-window cases
_GRANITE_MOE = dict(_GRANITE, num_key_value_heads=3, mamba_n_heads=12,
                    mamba_d_head=8, shared_intermediate_size=24,
                    num_local_experts=8, num_experts_per_tok=3,
                    intermediate_size=16, held=(0, 4))
VARIANTS = {
    "granite_moe_hybrid": dict(block="granite_hybrid", vocab_size=96,
                               d_model=48, n_layer=3, n_head=6,
                               granite=_GRANITE_MOE),
}

#: every case of the packed-window tests: all that takes ``fed``
FED = sorted(BLOCKS) + sorted(FUSED)

#: the keyword that holds a block's published keys
PUBLISHED = {"glm_dsa": "glm", "axk1": "axk1", "xing4": "xing4",
             "afmoe": "afmoe", "granite_hybrid": "granite",
             "ling_hybrid": "ling", "sdar_moe": "sdar",
             "nemotron_h": "nemotron_h"}

#: the positions a decode step of a block feeds and decides between
#: them, where that is not one: its dispatches are whole blocks from a
#: block's edge, and its tokens the reference's ``generate``'s
STEP = {"sdar_moe": _SDAR["block_length"]}


def config(case, **over):
    """``get_decode_symbol``'s keywords of a case beside the step
    length, the capacity and ``per_slot``. ``over`` replaces keywords; a
    dict over a dict (the block's published keys) is merged into it."""
    if case in FUSED:
        kw = dict({"block": case}, **FUSED[case])
    else:
        kw = dict({"block": case}, **{**BLOCKS, **VARIANTS}[case],
                  pos_embed="rotary", tie_head=False,
                  embed_scale=case == "afmoe")
    for key, value in over.items():
        kw[key] = dict(kw[key], **value) \
            if isinstance(value, dict) and isinstance(kw.get(key), dict) \
            else value
    return kw


def symbol(case, step_len, capacity=CAPACITY, **over):
    return tfm.get_decode_symbol(**{
        "step_len": step_len, "capacity": capacity, "per_slot": True,
        **config(case, **over)})


def inputs(sym, slots, step_len):
    """The data descriptions of a decode graph, in the order the
    drivers stage them: tokens, learned positions, ``fed``."""
    shapes = {"data": ((slots, step_len), np.int32),
              "pos_ids": ((slots, step_len), np.float32),
              "fed": ((slots,), np.int32)}
    return [mx.io.DataDesc(nm, *shapes[nm]) for nm in shapes
            if nm in sym.list_arguments()]


def _mamba_dt(draw, rng):
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), draw.shape))
    return dt + np.log(-np.expm1(-dt))          # the inverse softplus


_GAIN = lambda draw, rng: 1.0 + 0.3 * draw      # noqa: E731
_MAMBA = {"_A_log": lambda draw, rng: np.log(rng.uniform(1, 16, draw.shape)),
          "_dt_bias": _mamba_dt,
          "_mamba_D": lambda draw, rng: np.ones(draw.shape)}
#: how a block's parameters are drawn where that is not "matrices of
#: deviation 0.25, gains about 1": a name's ending -> what becomes of
#: its standard normal ``draw`` (``rng`` goes on where the draw was
#: taken, so a rule that draws again moves every later parameter)
DRAWS = {
    # EVA's feature maps at unit scale, gains about 0
    "evabyte": {"_phi": lambda draw, rng: draw,
                "_mu": lambda draw, rng: draw,
                "_gamma": lambda draw, rng: 0.3 * draw},
    # mapping weights of deviation 0.15 over 256 numbers of unit RMS:
    # logits of deviation 2.4, the published widths' under N(0, 0.02)
    "xing4": {"_mhc_scale": lambda draw, rng: 1.0 + 0.2 * draw,
              "_mhc_weight": lambda draw, rng: 0.15 * draw},
    # as Mamba-2 draws them: ``A_log = log U(1, 16)``, ``dt`` of 1e-3 to
    # 1e-1 through the inverse softplus, ``D`` 1
    "granite_hybrid": _MAMBA,
    "granite_moe_hybrid": _MAMBA,
    "nemotron_h": _MAMBA,
    # KDA's decays span the bound: ``A_log`` about 0, ``dt_bias`` of
    # U(-6, 3)
    "ling_hybrid": {"_kda_norm_weight": _GAIN,
                    "_kda_A_log": lambda draw, rng: 0.3 * draw,
                    "_kda_dt_bias":
                        lambda draw, rng: rng.uniform(-6.0, 3.0, draw.shape)},
}


def params(case, seed=5, draws=None, **over):
    """A parameter set of ``case``: matrices of deviation 0.25, gains
    about 1, the block's own by ``DRAWS``, and ``draws`` (of the same
    form) before either."""
    sym = symbol(case, 1, **over)
    given = {d.name: d.shape for d in inputs(sym, SLOTS, 1)}
    shapes, _, _ = sym.infer_shape(**given)
    rules = {**{"_gamma": _GAIN, "_kv_norm_weight": _GAIN},
             **DRAWS.get(case, {}), **(draws or {})}
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in given:
            continue
        draw = rng.standard_normal(shape)
        rule = next((rules[end] for end in rules if name.endswith(end)),
                    None)
        out[name] = (0.25 * draw if rule is None
                     else rule(draw, rng)).astype(np.float32)
    return out


def bound(sym, step_len, shared=None, arg_params=None, slots=SLOTS,
          dtype=None):
    descs = inputs(sym, slots, step_len)
    mod = mx.mod.Module(sym, data_names=[d.name for d in descs],
                        label_names=[], compute_dtype=dtype)
    mod.bind(descs, None, for_training=False, shared_module=shared)
    if shared is None:
        mod.init_params(initializer=None, arg_params=dict(arg_params),
                        aux_params={}, allow_missing=True)
    return mod


@contextlib.contextmanager
def tier(name):
    """``MXNET_KERNEL_TIER`` set to ``name`` (``"pallas"``: the kernels
    in interpret mode here) and put back."""
    old = os.environ.get("MXNET_KERNEL_TIER")
    os.environ["MXNET_KERNEL_TIER"] = name
    kernel_tier.clear()
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("MXNET_KERNEL_TIER", None)
        else:
            os.environ["MXNET_KERNEL_TIER"] = old
        kernel_tier.clear()


def driver(case, packed=True, slots=SLOTS, window=WINDOW, capacity=CAPACITY,
           arg_params=None, dtype=None, **over):
    """A ``slots``-slot driver of ``case`` with its window program of
    ``window`` rows a slot and, with ``packed``, the packed form of it
    beside (``tfm.packed_window``). The programs compile at their first
    step, under the kernel tier that is set then (``tier``)."""
    arg_params = params(case, **over) if arg_params is None else arg_params
    base = bound(symbol(case, 1, capacity, **over), 1, arg_params=arg_params,
                 slots=slots, dtype=dtype)
    drv = tfm.BatchedKVCacheDecoder(
        base, capacity, slots=slots,
        pos_embed=config(case, **over)["pos_embed"])
    whole = symbol(case, window, capacity, **over)
    form = tfm.packed_window(whole, slots) if packed else None
    drv.add_window(
        window, bound(whole, window, shared=base, slots=slots, dtype=dtype),
        packed=form and (bound(form[0], window, shared=base, slots=slots,
                               dtype=dtype), form[1]))
    if case in STEP and STEP[case] != window:   # a block's own program
        drv.add_window(STEP[case], bound(
            symbol(case, STEP[case], capacity, **over), STEP[case],
            shared=base, slots=slots, dtype=dtype))
    return drv


def lowered_text(sym, slots, step_len, debug_info=False):
    """The text that the inference program of ``sym`` bound at ``(slots,
    step_len)`` lowers to (the function ``Executor`` jits, on the CPU
    under whatever kernel tier is set); with ``debug_info`` the
    operations' locations too, which carry the named scopes."""
    exe = bound(sym, step_len, arg_params={}, slots=slots) \
        ._exec_group.executor

    def prog(arg_vals, aux_vals, rng):
        return exe._runner(arg_vals, aux_vals, False, rng)

    return jax.jit(prog).lower(exe._arg_vals(), exe._aux_vals(),
                               jax.random.PRNGKey(0)) \
        .as_text(**({"debug_info": True} if debug_info else {}))


# ----------------------------------------------------- the plain references
def _published(kw, **more):
    """The reference's ``cfg`` of a block built from published keys:
    those keys beside the model's own widths under their published
    names."""
    sub = dict(kw[PUBLISHED[kw["block"]]])
    held = sub.pop("held", None)
    cfg = dict(sub, vocab_size=kw["vocab_size"], hidden_size=kw["d_model"],
               num_attention_heads=kw["n_head"],
               num_hidden_layers=kw["n_layer"],
               rope_theta=kw.get("rope_base", 1e4),
               rms_norm_eps=kw.get("rms_eps", 1e-5), **more)
    if held:
        cfg.update(held_first=held[0])
        cfg[{"glm_dsa": "n_routed_experts_held", "axk1":
             "n_routed_experts_held"}.get(kw["block"], "num_experts_held")] \
            = held[1]
    return cfg


def _glm_cfg(kw):
    return _published(kw, rope_parameters={"rope_theta": kw["rope_base"]})


def _ling_cfg(kw):
    n = kw["n_layer"]
    types = kw["ling"]["layer_types"]
    return _published(kw, num_key_value_heads=kw["n_head"],
                      layers_run=list(range(n)),
                      layer_group_size=types.index("mla") + 1
                      if "mla" in types else 100)


def _granite_cfg(kw):
    return _published(kw, layers_run=list(range(kw["n_layer"])))


def _nemotron_cfg(kw):
    cfg = _published(kw, layers_run=list(range(kw["n_layer"])),
                     layer_norm_epsilon=kw.get("rms_eps", 1e-5))
    cfg["n_routed_experts_held"] = cfg.pop("num_experts_held")
    return cfg


def _evabyte_cfg(kw):
    return dict(vocab_size=kw["vocab_size"], hidden_size=kw["d_model"],
                num_attention_heads=kw["n_head"],
                num_hidden_layers=kw["n_layer"],
                intermediate_size=kw["ffn_width"],
                window_size=kw["window"], chunk_size=kw["chunk"],
                num_pred_heads=kw["n_pred_heads"],
                rope_theta=kw["rope_base"],
                rms_norm_eps=kw.get("rms_eps", 1e-5))


def _gpt2_cfg(kw):
    return dict(n_embd=kw["d_model"], n_head=kw["n_head"],
                n_layer=kw["n_layer"])


def _olmoe_cfg(kw):
    return dict(vocab_size=kw["vocab_size"], hidden_size=kw["d_model"],
                num_attention_heads=kw["n_head"],
                num_hidden_layers=kw["n_layer"],
                num_experts=kw["n_expert"],
                num_experts_per_tok=kw["top_k"],
                intermediate_size=kw["expert_width"],
                norm_topk_prob=kw["norm_topk"], rope_theta=kw["rope_base"],
                rms_norm_eps=kw["rms_eps"])


#: case -> (the module under ``chipbench.reference`` whose ``forward``
#: is the block's plain reference, the row's keywords as that module's
#: ``cfg``); ``gpt2_rotary`` has no such module and is held to the
#: model's own full forward (``get_symbol``'s graph, ``_own_forward``)
REFERENCE = {
    "glm_dsa": ("glm_dsa", _glm_cfg), "axk1": ("axk1", _published),
    "xing4": ("xing4", _published), "afmoe": ("afmoe", _published),
    "granite_hybrid": ("granite_hybrid", _granite_cfg),
    "granite_moe_hybrid": ("granite_moe_hybrid", _granite_cfg),
    "ling_hybrid": ("ling_hybrid", _ling_cfg),
    "evabyte": ("evabyte", _evabyte_cfg),
    "gpt2": ("gpt2", _gpt2_cfg), "olmoe": ("olmoe", _olmoe_cfg),
    "sdar_moe": ("sdar_moe", _published),
    "nemotron_h": ("nemotron_h", _nemotron_cfg),
}


def reference_cfg(case, **over):
    return REFERENCE[case][1](config(case, **over))


def _own_forward(case, seqs, arg_params, **over):
    """The logits of the model's training graph over whole sequences
    (one module a shape, bound once)."""
    kw = config(case, **over)
    key = (case, repr(sorted(over.items())), seqs.shape)
    if key not in _FORWARDS:
        sym = tfm.get_symbol(seq_len=seqs.shape[1], include_loss=False, **kw)
        mod = mx.mod.Module(sym, data_names=["data"], label_names=[])
        mod.bind([mx.io.DataDesc("data", seqs.shape, np.int32)], None,
                 for_training=False)
        _FORWARDS[key] = mod
    mod = _FORWARDS[key]
    mod.init_params(initializer=None, arg_params=dict(arg_params),
                    aux_params={}, allow_missing=True, force_init=True)
    mod.forward(mx.io.DataBatch(data=[mx.nd.array(seqs, dtype=np.int32)],
                                label=[]), is_train=False)
    return mod.get_outputs()[0].asnumpy().reshape(
        seqs.shape + (kw["vocab_size"],))


_PARAMS, _FORWARDS = {}, {}


def reference(case, seqs, arg_params=None, over=None, **kw):
    """The logits of every position of ``seqs`` (n, T) by the block's
    plain reference (one full forward in float32 ``jax.numpy``), under
    ``params(case)`` where no others are given; ``kw`` goes to the
    reference's ``forward``."""
    over = over or {}
    if arg_params is None:
        if case not in _PARAMS:
            _PARAMS[case] = params(case)
        arg_params = _PARAMS[case] if not over else params(case, **over)
    if case not in REFERENCE:
        return _own_forward(case, np.asarray(seqs), arg_params, **over)
    key = (case, repr(sorted(over.items())), repr(sorted(kw.items())))
    if key not in _FORWARDS:            # one trace a shape, not a call
        ref = importlib.import_module(
            f"chipbench.reference.{REFERENCE[case][0]}")
        cfg = reference_cfg(case, **over)
        _FORWARDS[key] = jax.jit(lambda p, t: ref.forward(p, t, cfg, **kw))
    return np.asarray(_FORWARDS[key](arg_params, jnp.asarray(seqs)))


def plain_greedy(case, prompt, max_new, T=64, **request):
    """The tokens a greedy request of ``prompt`` is served, by the
    reference: one full forward a token over the sequence so far, padded
    to ``T`` (a causal model: what follows a position moves nothing at
    it), so that one program serves every length; of a block that
    decodes by blocks (``STEP``) the reference's own ``generate`` under
    ``request``'s denoising parameters."""
    if case in STEP:
        ref = importlib.import_module(
            f"chipbench.reference.{REFERENCE[case][0]}")
        if case not in _PARAMS:
            _PARAMS[case] = params(case)
        return ref.generate(_PARAMS[case], prompt, max_new,
                            reference_cfg(case), **request)
    seq = np.zeros((1, T), np.int32)
    seq[0, :len(prompt)] = prompt
    out = []
    for at in range(len(prompt), len(prompt) + max_new):
        out.append(int(np.argmax(reference(case, seq)[0, at - 1])))
        seq[0, at] = out[-1]
    return out


#: float32 served against the float32 reference through the block's
#: layers, with where each number was measured
TOL = {
    # 2 layers, logits about 1: a few float32 ulps of the partial sums
    # (measured in tests/test_evabyte.py, PR 30: 4e-7 to 2e-6)
    "evabyte": 2e-5,
    # 3 layers, logits about 2 (tests/test_glm_dsa.py: 2e-6 to 6e-6;
    # tests/test_axk1.py: 2e-6 to 8e-6)
    "glm_dsa": 5e-5, "axk1": 5e-5,
    # 3 layers and 6 mappings, logits about 8 (tests/test_xing4.py:
    # 3e-5 to 2e-4; the mapping's exp and 20 Sinkhorn rounds carry a
    # rounding of the stream further than a plain residual add does)
    "xing4": 1e-3,
    # logits about 3 (tests/test_afmoe.py: 2e-6 to 5e-6)
    "afmoe": 5e-5,
    # logits about 1 (tests/test_granite_hybrid.py: 1e-6 to 2e-5,
    # test_granite_moe_hybrid.py and test_ling_hybrid.py to 3e-5; the
    # chunked form sums in another order than the recurrence, the
    # grouped matmuls in another than one expert at a time)
    "granite_hybrid": 2e-4, "granite_moe_hybrid": 2e-4, "ling_hybrid": 2e-4,
    # 5 sub-layers, logits about 2 (tests/test_nemotron_h.py, PR 63:
    # 1e-6 to 1e-5; the same chunked form and grouped matmuls)
    "nemotron_h": 2e-4,
    # 3 layers, logits about 3 (tests/test_sdar_moe.py, PR 60: 2e-6 to
    # 6e-6, Trinity's head geometry and OLMoE's router)
    "sdar_moe": 5e-5,
    # 2 layers, logits up to 0.8 (tests/test_moe.py's free routing: 1.5e-7
    # to 6e-7; the dense blocks measured with ISSUE 57's suite: under 1e-6)
    "olmoe": 1e-5, "gpt2": 1e-5, "gpt2_rotary": 1e-5,
}

_ROWS = ["cursor", "rows"]
#: the families of per-slot state a block's ops declare (``slot_state``);
#: a block whose state is a cursor and rows alone is positional: it
#: rewinds anywhere, and takes drafts and prefix stores
FAMILIES = {
    "gpt2": _ROWS, "gpt2_rotary": _ROWS, "olmoe": _ROWS, "glm_dsa": _ROWS,
    "axk1": _ROWS, "xing4": _ROWS, "sdar_moe": _ROWS,
    "afmoe": ["cursor", "ring", "rows"],
    "evabyte": ["cursor", "summary", "window"],
    "granite_hybrid": ["conv", "cursor", "recurrent", "rows"],
    "granite_moe_hybrid": ["conv", "cursor", "recurrent", "rows"],
    "ling_hybrid": ["conv", "cursor", "recurrent", "rows"],
    "nemotron_h": ["conv", "cursor", "recurrent", "rows"],
}


def positional(case):
    return FAMILIES[case] == _ROWS


_ROUTED = {"num_local_experts": 4, "num_experts_per_tok": 2,
           "intermediate_size": 16}
_LING_REFUSED = {
    "q_lora_rank": 24, "gated_attention_proj_granularity_type": "element",
    "expert_swiglu_limit_list": [0, 4, 4],
    "share_expert_swiglu_limit_list": [0, 5, 0],
    "num_kv_heads_for_linear_attn": 2, "use_mla_nope": True,
    "scale_router_input": True, "up_proj_norm": True, "value_norm": True,
    "use_nGPT": True, "kda_safe_gate": False, "use_kda_lora": True,
    "linear_silu": False, "group_norm_size": 4,
    "moe_router_enable_expert_bias": False,
    "moe_shared_expert_intermediate_size": 16}
#: what a block's builder refuses, ``{case: (keywords over the row's,
#: what the error says)}`` (``_granite_spec``, ``_ling_spec``,
#: ``_evabyte_spec``, ``_afmoe_spec``); with Granite's routed experts
#: on (ISSUE 54) a choice of no expert or of more than the router has,
#: and a held range outside the router's width
REFUSED = {
    "granite_hybrid": {
        case: ({"granite": over}, "granite_hybrid") for case, over in {
            "groups_that_split_a_head": {"mamba_n_groups": 3},
            "projection_bias": {"mamba_proj_bias": True},
            "positions": {"position_embedding_type": "rope"},
            "a_layer_short": {"layer_types": ["mamba", "attention"]},
            "a_layer_of_another_kind":
                {"layer_types": ["mamba", "mlp", "mamba"]},
            "no_expert_a_token": dict(_ROUTED, num_experts_per_tok=0),
            "more_experts_a_token_than_the_router_has":
                dict(_ROUTED, num_experts_per_tok=5),
            "held_past_the_router": dict(_ROUTED, held=(2, 3)),
            "held_before_the_router": dict(_ROUTED, held=(-1, 2)),
            "nothing_held": dict(_ROUTED, held=(0, 0)),
        }.items()},
    "ling_hybrid": {
        **{key: ({"ling": {key: value}}, rf"ling_hybrid.*{key}")
           for key, value in _LING_REFUSED.items()},
        "a_layer_short": ({"ling": {"layer_types": ["kda", "mla"]}},
                          "'kda' or 'mla'"),
        "a_layer_of_another_kind":
            ({"ling": {"layer_types": ["kda", "mamba", "mla"]}},
             "'kda' or 'mla'"),
        "no_published_keys": ({"ling": None}, "needs ling="),
    },
    "evabyte": {
        "one_cursor": ({"per_slot": False}, "per_slot"),
        "learned_positions": ({"pos_embed": "learned"}, "rotary"),
        "a_window_off_the_chunk": ({"window": 18}, "multiples of chunk"),
    },
    "afmoe": {
        "a_layer_short": ({"n_layer": 2}, "layer_types"),
        "fp8_pools": ({"cache_dtype": "fp8"}, "per_slot"),
    },
}
REFUSED["granite_hybrid"]["no_published_keys"] = ({"granite": None},
                                                  "needs granite=")
#: ``_nemotron_h_spec``: a letter it has no sub-layer for, a pattern of
#: another length, what the config could say and the block does not build
REFUSED["nemotron_h"] = {
    **{case: ({"nemotron_h": over}, "nemotron_h") for case, over in {
        "a_dense_feed_forward_layer": {"hybrid_override_pattern": "MEM-E"},
        "a_layer_short": {"hybrid_override_pattern": "MEM*"},
        "groups_that_split_a_head": {"n_groups": 3},
        "projection_bias": {"mamba_proj_bias": True},
        "attention_bias": {"attention_bias": True},
        "expert_bias": {"mlp_bias": True},
        "a_gated_activation": {"mlp_hidden_act": "silu"},
        "two_shared_experts": {"n_shared_experts": 2},
        "a_group_limited_router": {"n_group": 2, "topk_group": 1},
        "gates_not_normalised": {"norm_topk_prob": False},
        "more_experts_a_token_than_the_router_has":
            {"num_experts_per_tok": 9},
        "held_past_the_router": {"held": (6, 4)},
        "kv_heads_that_split_the_query_heads": {"num_key_value_heads": 3},
    }.items()},
    "no_published_keys": ({"nemotron_h": None}, "needs nemotron_h="),
}


# ------------------------------------------------- a schedule of dispatches
def window(*fed, slots=SLOTS, S=WINDOW, rider=1):
    """One window dispatch, ``(S, fed counts a slot)``: the slots past
    those named ride with one token (``rider``; 0: they wait)."""
    return (S, list(fed) + [rider] * (slots - len(fed)))


def steps(n, *fed, slots=SLOTS, step=1):
    """``n`` decode dispatches of ``step`` rows a slot (S = 1; a
    block's length where a block decodes by blocks): the slots past
    those named are fed, a named one ``step`` rows or none."""
    return [(step, [step * f for f in fed]
             + [step] * (slots - len(fed)))] * n


def schedules(W, slots, budget, step=1):
    """The dispatches every block is walked through, ``{name: [(S, fed
    counts a slot)]}``: whole windows of ``W`` rows, windows inside the
    packed program's ``budget`` with riders, ragged chunks, decode
    before the windows. Of a block that decodes ``step`` positions a
    step every count is whole blocks (rounded down, and a block at
    least), a decoding slot waits where it would ride a window, and a
    decode dispatch is one block a slot."""
    rider = 1 if step == 1 else 0

    def mix(*fed):
        return window(*[max(step, n // step * step) if n else 0
                        for n in fed], slots=slots, S=W, rider=rider)

    def ones(n, *fed):
        return steps(n, *fed, slots=slots, step=step)

    full = mix(*[W] * slots)
    packed = [mix(W), mix(W), mix(5, rider, W - 3),
              mix(rider, W - 5, W - 7), mix(rider, W, 0), mix(rider, 3)]
    assert all(sum(fed) <= budget for _S, fed in packed)
    return {
        # whole windows (the whole-window program, two chunks a slot a
        # dispatch where a block has chunks of 8), then S = 1 through
        # the state
        "whole_windows_then_decode": [full] * 3 + ones(6),
        # the packed program: a chunk and riders, a part of a chunk
        # beside another, a ragged last chunk, a slot fed nothing
        "packed_windows_with_riders": packed + ones(4),
        # two chunks in one dispatch with a ragged second (13 = 8 + 5)
        "two_chunks_and_a_ragged_last": [
            mix(W - 3, W, W - 7), mix(W, 7, W - 4), mix(2, 1, 3)]
        + ones(3, 1, 0) + [mix(W - 7, 2)],
        # decode first (the state starts by steps), then windows over it
        "decode_then_windows": ones(5) + [mix(W), mix(W - 6, W - 4, 2)]
        + ones(2),
    }


def _eva(*windows):
    return [window(*fed) for fed in windows]


#: a block's own schedules beside those: EvaByte's walk over the
#: boundaries of its window of 32 and its chunks of 4
OWN_SCHEDULES = {
    "evabyte": {
        # the third and fourth windows read the first 32 positions as
        # 8 summaries
        "window_reads_summaries": _eva(*[[WINDOW] * SLOTS] * 4),
        # decode walks over the boundaries at 32 and 64
        "decode_closes_a_window": _eva([WINDOW] * SLOTS) + steps(1) * 20
        + _eva([WINDOW] * SLOTS) + steps(1) * 14,
        # ragged windows: boundaries at 32, 64 and 96 fall inside
        # dispatches, at another row for each slot
        "window_closes_mid_dispatch": _eva([9, 13, 16], [16, 16, 11]) * 4,
        # slot 0 prefills while slot 1 rides with 1 token and slot 2
        # with 1 or none, from just before the boundary at 32 across it
        "riders_before_a_boundary": _eva(
            [16, 15, 15], [16, 15, 14], [16, 1, 0], [16, 1, 1], [16, 1, 1],
            [16, 1, 0], [3, 1, 1]),
        # lengths that are no multiple of the chunk of 4, then decode
        "lengths_off_the_chunk": _eva([13, 7, 16], [16, 10, 5], [9, 16, 14],
                                      [1, 2, 3]) + steps(1) * 5
        + steps(3, 1, 0, 1),
    },
}


def seqs(case, T, seed=1, slots=SLOTS, **over):
    return np.random.default_rng(seed).integers(
        0, config(case, **over)["vocab_size"], (slots, T)).astype(np.int32)


def run(drv, seqs, schedule, start=None):
    """Feed ``seqs`` (slots, T) through ``schedule``, a list of (S, fed
    counts a slot): the logits of every fed position that a dispatch
    hands back, (slots, T, ...) - all of an S = 1 step's and a
    whole-window program's, of a packed window's each slot's last fed
    row alone (ISSUE 51: the others stay NaN; ``err`` compares what is
    there) -, the cursors and the rows each dispatch's program ran
    over. Every slot joins fresh first, or goes on from ``start``; a
    pad is a junk token."""
    if start is None:
        for slot in range(drv.slots):
            if drv.active[slot]:
                drv.leave(slot)
            drv.join(slot)
        start = [0] * drv.slots
    got, at, rows = None, np.asarray(start), []
    for S, fed in schedule:
        tokens = np.full((drv.slots, S), 7, np.int32)
        for slot, n in enumerate(fed):
            tokens[slot, :n] = seqs[slot, at[slot]:at[slot] + n]
        out = drv.step(tokens, fed=fed).asnumpy()
        if got is None:
            got = np.full(seqs.shape + out.shape[2:], np.nan, np.float32)
        rows.append(drv.last_program_rows)
        assert out.shape[1] == (S if rows[-1] == drv.slots * S else 1)
        for slot, n in enumerate(fed):
            if out.shape[1] == S:
                got[slot, at[slot]:at[slot] + n] = out[slot, :n]
            elif n:
                got[slot, at[slot] + n - 1] = out[slot, 0]
        at = at + np.asarray(fed)
        assert list(drv.pos) == list(at)
    return got, at, rows


def err(got, want):
    """The largest difference over the positions that ``run`` holds
    logits of (at least one)."""
    held = ~np.isnan(got).reshape(got.shape[0], -1).any(axis=-1) \
        if got.ndim == 2 else ~np.isnan(got).any(axis=-1)
    assert held.any()
    return np.abs(got[held] - want[held]).max()


def reset(drv):
    """Every slot left and every cursor at 0."""
    drv.active[:] = False
    drv.rewind_many(list(range(drv.slots)), [0] * drv.slots)


# ----------------------------------------------------- engine and scheduler
def engine(case, name, ladder=(2, 4), windows=(WINDOW,), capacity=CAPACITY,
           arg_params=None, **over):
    """A ``DecodeEngine`` of ``case`` over ``ladder`` with a window
    program for each of ``windows``' rows a slot."""
    gen = lambda s: symbol(case, s, capacity, **over)       # noqa: E731
    return mx.serve.DecodeEngine(
        name, gen(1), params(case, **over) if arg_params is None
        else arg_params, capacity=capacity, ladder=list(ladder),
        symbol_gen=gen, window_lens=list(windows))


def served(sched, prompts, max_new):
    """The tokens of ``prompts`` submitted together and pumped to the
    end."""
    handles = [sched.submit(p, max_new_tokens=max_new) for p in prompts]
    sched.pump()
    return [[int(t) for t in h.result(timeout=5)] for h in handles]


def counted(case, engine, keys, lens=(45, 9, 30, 70), max_new=12):
    """Requests of ragged lengths (windows with riders, a rung switch)
    through a scheduler of its own over ``engine``: the prompts, what
    the counters ``keys`` grew by, and the ring's records of those
    iterations."""
    from mxnet_tpu.telemetry import flightrec
    rng = np.random.default_rng(8)
    vocab = config(case)["vocab_size"]
    prompts = [rng.integers(0, vocab, n).tolist() for n in lens]
    sched = mx.serve.DecodeScheduler(engine, clock=mx.serve.FakeClock(),
                                     prefill_chunk=WINDOW, prefix_store=None)
    before = {k: sched._counter(k).value for k in keys}
    flightrec.clear()           # another scheduler's records of the engine
    served(sched, prompts, max_new)
    grew = {k: sched._counter(k).value - v for k, v in before.items()}
    records = [r for r in flightrec.get_records()
               if r.get("kind") == "serve.decode.step"
               and r.get("model") == engine.name]
    return prompts, grew, records
