"""The Ling-3.0 architecture (archs/ling_hybrid.py,
reference/ling_hybrid.py, the configuration ling-3.0-flash, the traffic
mix longchat32-closed, the ``kda.*`` / ``kda_*`` metrics) on the CPU:
the interface, the configuration against the catalog and its
arithmetic, the costs against a count by hand, every new reader on a
synthetic ``obs``, the decays ``make_params`` draws - and the cell
rehearsed at a tiny size (tests/fixtures/ling_hybrid/) in a temporary
copy of the rehearsal manifest, traced and untraced: two CPU rehearsals
of half a minute each. All of it is part of tier-1 through
``tests/test_ling_hybrid.py``."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, traffic as traffic_mod  # noqa: E402
from chipbench.tests import scripted_trace  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal")
FIXTURE = os.path.join(HERE, "fixtures", "ling_hybrid")
CELL = "tiny-ling-longchat32"
REAL_CELL = "ling3flash-serve-longchat32-closed"
NEW_METRICS = ("kda.share_of_step", "kda_decode_roofline",
               "kda_window_roofline", "kda.real_share_of_chunk_rows")
#: architectures.jsonl, row Ling-3.0-flash: ``config``
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
    "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 512, "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts_held": 128, "vocab_size": 39296}


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-ling.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "ling_hybrid.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    assert sorted(k for k, v in CATALOG.items() if cfg.get(k) != v) \
        == sorted(k for k in REDUCED if k in CATALOG)
    assert cfg["reduced"] == list(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2,
        "num_experts_held": 512, "vocab_size": 157184}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "ling-3.0-flash")
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"]
    # the layers run: the leading dense layers once, then the second
    # group of six whole - five KDA layers and the latent one at 11
    assert cfg["layers_run"] == [0, 6, 7, 8, 9, 10, 11]
    from chipbench.reference import ling_hybrid as ref
    assert ref.layer_types(cfg) == ["kda"] * 6 + ["mla"]
    assert ref.layer_types(dict(cfg, layers_run=list(range(42)))) \
        .count("mla") == 7
    for lst in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(cfg[lst][i] for i in cfg["layers_run"])
    for key in ("reduced_detail", "assumed", "deployment", "env"):
        assert cfg[key], key
    assert sorted(cfg["reduced_detail"]) == sorted(
        list(REDUCED) + ["arithmetic", "clamp"])
    # one entry of ``assumed`` for each reading the config leaves open
    for key in ("layer_rule", "kda_heads", "kda_decay", "kda_norms",
                "kda_conv", "kda_output", "kda_beta", "mla_query",
                "mla_rotary", "mla_gate", "router", "clamp", "mtp",
                "precision", "state_layout", "kda_chunk", "weights",
                "ladder", "capacity", "prefill_chunk"):
        assert key in cfg["assumed"], key
    assert "float32" in cfg["assumed"]["precision"]
    assert "four" in cfg["deployment"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"],
            cfg["kda_chunk"]) == (16384, 256, [1, 8, 32], 64)
    # the arithmetic of reduced_detail, in millions of parameters
    D, V, H, dh = 2560, 39296, 32, 128
    expert = 3 * D * 768
    kda = D * (5 * H * dh + H) + H * dh * D + 3 * H * dh * 4 + H \
        + H * dh + dh
    mla = D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D + D * H + 512
    router, dense = (D + 1) * 512, 3 * D * 6144
    dense_layer = kda + dense + 2 * D
    sparse_kda = kda + 128 * expert + expert + router + 2 * D
    sparse_mla = mla + 128 * expert + expert + router + 2 * D
    assert [round(x / 1e6, 2) for x in (expert, kda, mla, router, dense)] \
        == [5.9, 63.05, 31.97, 1.31, 47.19]
    assert [round(x / 1e6, 1) for x in (dense_layer, sparse_kda,
                                        sparse_mla, 2.0 * V * D)] \
        == [110.2, 825.2, 794.2, 201.2]
    total = dense_layer + 5 * sparse_kda + sparse_mla + 2 * V * D + D
    assert round(total / 1e6) == 5232 and round(2 * total / 1e9, 2) == 10.46
    recurrent = 6 * 4 * (H * dh * dh + 3 * 3 * H * dh)
    latent = cfg["capacity"] * 1280
    assert round(recurrent / 1e6, 2) == 13.47 \
        and round(latent / 1e6, 2) == 20.97
    state = 41 * (recurrent + latent)
    assert round(state / 1e9, 2) == 1.41
    assert 0.73 < (2 * total + state) / 16e9 < 0.75


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "ling-3.0-flash"
    block = [tuple(p) for p in mix["block"]]
    assert block[:4] == [(300, 640), (2100, 448), (1430, 384), (560, 768)]
    assert "prefix" not in mix
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 32, 1, 12)
    assert mix["clients"] == max(cell.config["ladder"])
    assert traffic_mod.block_totals(mix) == (32, 103320, 13920)
    short = [(p, a) for p, a in block if p <= 1500]
    mid = [(p, a) for p, a in block if 2000 <= p <= 5000]
    long_ = [(p, a) for p, a in block if p == 12000]
    assert (len(short), len(mid), len(long_)) == (16, 12, 4)
    assert min(p for p, _ in short) == 300
    assert all(384 <= a <= 768 for _, a in short)
    assert all(192 <= a <= 512 for _, a in mid)
    assert all(128 <= a <= 256 for _, a in long_)
    assert not [p for p, _ in block if p % 256 == 0]
    assert max(p + a for p, a in block) == 12256 < cell.config["capacity"]
    # short and long interleaved: a long one in every run of eight
    assert [i % 8 for i, (p, _) in enumerate(block) if p == 12000] \
        == [7] * 4
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"sched.window_iter_share", "engine.fetch_ms_p50.chat",
            "sched.runahead_share_of_steps", "decode_program_roofline",
            "moe.experts_touched_per_layer_step", "moe.expert_share_of_step",
            "moe_expert_roofline", "moe.held_assignment_share",
            "mla.attn_share_of_step", "mla_dense_decode_roofline",
            "mla_dense_window_roofline",
            "sched.riding_share_of_window_slots",
            "engine.real_share_of_window_rows",
            "engine.head_share_of_window_rows"} <= names
    assert not [n for n in names if n.startswith(("ssm", "gqa_", "dsa",
                                                  "eva", "mhc"))]
    # the three metrics that read null since PR 46 are left alone
    assert not names & {"engine.launch_latency_ms_p50.chat",
                        "engine.wake_latency_ms_p50.chat",
                        "sched.turnaround_ms_p50.chat"}
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [REAL_CELL]
            assert (m["moves"], m["layer"]) == ("serve_tokens_per_s",
                                                "kernels")
    # (no count of the manifest's cells here: the next cell is appended
    # behind this one and is its own tests' business)


@pytest.mark.parametrize("step_len", [1, 16])
def test_the_architecture_file_has_the_interface_and_builds_the_block(
        step_len):
    arch, cfg = _arch(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"]:
        assert hasattr(arch, name), name
    sym = arch.decode_symbol(cfg, step_len)
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("kda_mixer_decode") == 3
    assert ops.count("mla_attention_decode") == 1
    assert ops.count("MoEFFN") == 3 and "dsa_index_select" not in ops
    args = sym.list_arguments()
    assert "fed" in args and "pos_ids" not in args
    assert {"lm_head_weight", "lm_l0_kda_in_weight", "lm_l0_kda_conv_weight",
            "lm_l0_kda_A_log", "lm_l0_kda_dt_bias", "lm_l0_kda_norm_weight",
            "lm_l0_ffn_gate_up_weight", "lm_l1_moe_router_bias",
            "lm_l3_q_weight", "lm_l3_gate_weight",
            "lm_l3_attn_kv_b_weight"} <= set(args)
    assert not [a for a in args if "q_a" in a or "q_b" in a]
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len),
                                                  "fed": (4,)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, tie_word_embeddings=True), step_len)
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, topk_method="none"), step_len)
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "ling_hybrid.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                  # prose may name them
    assert "mxnet_tpu" not in body and "pallas" not in body
    assert "chunk" not in body and "cache" not in body
    assert "lax.scan" in body                       # step by step
    assert 'default_matmul_precision("highest")' in text
    assert "reduce_precision" in body
    # every reading at the head of the reference too
    for word in ("layer_group_size", "the query's count", "D^-0.5",
                 "1e-6", "one number a head", "no swiglu clamp",
                 "drafter beside the model"):
        assert word in text.split('"""', 2)[1], word


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.latent_row_bytes(cfg) == 1152
    assert arch.moe_expert_bytes(cfg) == 11796480
    state, row = arch.kda_decode(cfg), arch.kda_window(cfg)
    assert state == {"flops": 6.0 * 32 * 128 * 128,
                     "bytes": 2 * 4 * (32 * 128 * 128 + 3 * 3 * 4096)}
    assert state["bytes"] == 4489216 and state["flops"] == 3145728.0
    assert row == {"flops": 3145728.0, "bytes": 49216}
    got = arch.costs(cfg, 32, 256, 4000.0)
    assert set(got) == {"decode_step", "window_step", "kda_decode",
                        "kda_window", "mla_window", "mla_row", "moe_expert",
                        "mla_pair_absorbed", "mla_pair_expanded",
                        "mla_key_expansion"}
    assert got["kda_decode"] == state and got["kda_window"] == row
    # an S = 1 step of 32 slots: about 51 of 128 experts a sparse layer
    touched = got["decode_step"]["held_experts_touched_per_layer"]
    assert 50 < touched < 52
    # what it reads: the weights outside the experts (1.51 GB), six
    # layers' touched experts (3.6 GB), 32 x 6 states read and written
    # (0.86 GB), one layer's latent rows
    outside = 110.2e6 - 47.19e6 + 47.19e6 + 5 * (63.05e6 + 5.9e6 + 1.31e6) \
        + 31.97e6 + 5.9e6 + 1.31e6 + 39296 * 2560
    want = 2 * outside + 6 * touched * 11796480 + 32 * 6 * 4489216 \
        + 32 * 4001 * 1152
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=0.01)
    assert 32 * 6 * 4489216 == pytest.approx(0.862e9, rel=0.005)
    assert 5.5e9 < got["decode_step"]["bytes"] < 6.1e9
    # the delta rule's own work is a fortieth of a KDA layer's matmuls
    assert 0.02 < row["flops"] / (2 * 63.05e6) < 0.03


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _decode_trace():
    """Chip 0: the S=1 program of the top rung runs twice for 1,000 us,
    inside each run four mixers' kda_update 100 us; the window program
    runs once for 2,000 us with four times kda_update 100 us and three
    trips of kda_chunk 20 us."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 2000):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x1(abc)", base,
                     1000))
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1(abd)",
                     base + 1000, 10))
        for layer in range(4):
            out.append(e(plane, "XLA Ops", f"kda_update.{layer}",
                         base + 200 * layer + 10, 100))
        out.append(e(plane, "XLA Ops", "fusion.1", base + 900, 10))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x256(abe)", 5000,
                 2000))
    for layer in range(4):
        out.append(e(plane, "XLA Ops", f"kda_update.{layer + 9}",
                     5020 + 400 * layer, 100))
        for trip in range(3):
            out.append(e(plane, "XLA Ops", f"kda_chunk.{layer + 9}",
                         5200 + 400 * layer + 30 * trip, 20))
    return out


def test_every_new_reader_on_a_synthetic_obs():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the operations, the counters and the ring's
    # fields (the parent): every reader finds nothing, and raises nothing
    for name in NEW_METRICS:
        assert read(name, _obs()) is None, name
        assert read(name, {}) is None, name
    ring = [{"kind": "serve.decode.step", "window": 1, "rung": 32,
             "kda_step_slots": 4 * n, "kda_chunk_slots": 0,
             "kda_real_rows": 0} for n in (30, 32, 32)] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 32,
         "kda_step_slots": 4 * 31, "kda_chunk_slots": 4,
         "kda_real_rows": 4 * rows} for rows in (353, 340, 200)] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 8,
         "kda_step_slots": 0, "kda_chunk_slots": 4 * 8,
         "kda_real_rows": 4 * 256}]
    cost = {"kda_decode": {"flops": 3145728.0, "bytes": 4489216},
            "kda_window": {"flops": 3145728.0, "bytes": 49216}}
    obs = _obs(events=_decode_trace(), ring=ring, cost=cost,
               counters={"serve.decode.kda.real_rows": 353 * 6,
                         "serve.decode.kda.chunk_rows": 384 * 6})
    # 4 x 100 us of kda_* in each 1,000 us run of the 32-slot one
    assert read("kda.share_of_step", obs) == pytest.approx(40.0)
    # median 128 states x 4,489,216 B at 819 GB/s = 702 us against
    # 400 us of kda_* a run: a scripted trace reads what it likes
    assert read("kda_decode_roofline", obs) == pytest.approx(
        100.0 * (128 * 4489216 / 819e9) / 400e-6, rel=1e-9)
    # the top rung's median window: 124 steps and 4 chunked slots'
    # states, 124 + 4 x 340 rows, against 640 us of kda_* a run of the
    # window program (the rung-8 record is not the top rung's)
    rows = 124 + 4 * 340
    assert read("kda_window_roofline", obs) == pytest.approx(
        100.0 * ((128 * 4489216 + rows * 49216) / 819e9) / 640e-6,
        rel=1e-9)
    # and operations where a row's are many
    dear = dict(cost, kda_window={"flops": 1e12, "bytes": 49216})
    assert read("kda_window_roofline", dict(obs, cost=dear)) \
        == pytest.approx(100.0 * (rows * 1e12 / 197e12) / 640e-6, rel=1e-9)
    assert read("kda.real_share_of_chunk_rows", obs) == pytest.approx(
        100.0 * 353 / 384)
    # an architecture that states no such cost: not these metrics'
    for name in NEW_METRICS[1:3]:
        assert read(name, dict(obs, cost={})) is None
    # a program whose ring lacks the fields (no op declares them)
    bare = [{k: v for k, v in r.items() if not k.startswith("kda_")}
            for r in ring]
    for name in NEW_METRICS[1:3]:
        assert read(name, dict(obs, ring=bare)) is None
    for name in NEW_METRICS:
        assert (metrics[name].reader or metrics[name].decl["reads"]) \
            is not None


def test_make_params_draws_decays_that_span_the_bound():
    """A channel's log decay at ``f = 0`` spans most of [-5, 0) - and
    ``make_params`` refuses parameters whose decays do not -, gains are
    1, the same seed draws the same parameters, and the reference's
    tail is its full forward's."""
    import numpy as np
    arch, cfg = _arch(), _tiny()
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    again = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(np.array_equal(params[n], again[n]) for n in params)
    other = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1),
                             3280000019, cfg)
    assert not np.array_equal(params["lm_head_weight"],
                              other["lm_head_weight"])
    f32 = lambda n: np.asarray(params[n], np.float32)       # noqa: E731
    for layer in (0, 1, 2):
        bias = f32(f"lm_l{layer}_kda_dt_bias")
        assert bias.shape == (32,) and bias.min() < -4 and bias.max() > 1
        rate = np.exp(f32(f"lm_l{layer}_kda_A_log"))
        assert (rate >= 0.49).all() and (rate <= 2.01).all()
        log_a = -5.0 / (1.0 + np.exp(-np.repeat(rate, 8) * bias))
        assert log_a.min() < -4.0 and log_a.max() > -0.01
        assert (f32(f"lm_l{layer}_kda_norm_weight") == 1).all()
        w = f32(f"lm_l{layer}_kda_conv_weight")
        assert w.shape == (96, 4) and 0.2 < w.std() < 0.35
    assert (f32("lm_l3_attn_kv_norm_weight") == 1).all()
    assert 0.01 < f32("lm_tok_embed_weight").std() < 0.03
    narrow = dict(arch.__dict__)
    try:
        arch._DT_BIAS = (-1.0, 1.0)
        arch._drawer.cache_clear()
        with pytest.raises(SystemExit, match="do not span"):
            arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    finally:
        arch._DT_BIAS = narrow["_DT_BIAS"]
        arch._drawer.cache_clear()
    import jax.numpy as jnp
    from chipbench.reference import ling_hybrid as ref
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    want = np.asarray(ref.forward(params, tokens, cfg, tail=32))
    assert want.shape == (2, 32, 64)
    np.testing.assert_allclose(
        want, np.asarray(ref.forward(params, tokens, cfg))[:, -32:],
        atol=1e-5, rtol=1e-5)
    # the controls are switches of the same forward
    controls = arch._controls(cfg)
    for _key, _what, switches in controls:
        low = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                     **switches))
        assert np.abs(low - want).max() > 0
    assert [k for k, _w, _s in controls] == [
        "fp8", "state_bf16", "state_none", "state_lost"]
    assert jnp.bfloat16 in [s.get("state_dtype") for _k, _w, s in controls]
    assert [s.get("state_every") for _k, _w, s in controls][2:] == [1, 16]
    same = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                  state_every=1000))
    assert np.array_equal(same, want)


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_ling(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-ling.json"),
                       ("traffic", "tiny-longchat32.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-ling", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-ling.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-ling", "traffic": "tiny-longchat32",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS:
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_ling(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_ling(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_ling_rehearses(copy_with_ling, trace):
    root = copy_with_ling
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", CELL, "--seed", "3280000019",
         "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    by = {l["chipbench"]: l for l in lines[:-1]}
    last = lines[-1]
    assert "chipbench" not in last          # the result is the last line
    assert last["correct"] and by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    for key in ("fp8", "state_bf16", "state_none", "state_lost"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.kda.step_slots",
                     "serve.decode.kda.chunk_rows",
                     "serve.decode.kda.real_rows",
                     "serve.decode.attn.live_rows"):
            assert counters[name] > 0, name
        assert counters["serve.decode.kda.chunk_rows"] \
            >= counters["serve.decode.kda.real_rows"]
        # the counter's metric reads on the CPU too; the CPU's trace has
        # no XLA Ops line, so the readers over the device trace find
        # nothing and the line leaves them out
        assert 0 < last["metrics"]["kda.real_share_of_chunk_rows"][
            "value"] <= 100
        for name in NEW_METRICS[:3]:
            assert name not in last["metrics"]
