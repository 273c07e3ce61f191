"""The Granite 4.0-H architecture with routed experts
(archs/granite_moe_hybrid.py, reference/granite_moe_hybrid.py, the
configuration granite-4.0-h-small, the traffic mix agent32-closed, the
metrics ``moe.held_load_imbalance`` and ``moe_window_roofline``) on the
CPU: the interface, the configuration against the catalog and its
arithmetic, the costs against a count by hand, the new readers on a
synthetic ``obs``, ``make_params`` and the controls - and the cell
rehearsed at a tiny size (tests/fixtures/granite_moe_hybrid/) in a
temporary copy of the rehearsal manifest, traced and untraced: two CPU
rehearsals of under a minute each. All of it is part of tier-1 through
``tests/test_chipbench_granite_moe_hybrid.py``."""
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
FIXTURE = os.path.join(HERE, "fixtures", "granite_moe_hybrid")
CELL = "tiny-granite-small-agent32"
REAL_CELL = "granite4hs-serve-agent32-closed"
NEW_METRICS = ("moe.held_load_imbalance", "moe_window_roofline")
#: architectures.jsonl, row granite-4.0-h-small: ``config``
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "num_experts_held": 36,
           "vocab_size": 50176}


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-small.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs",
                           "tiny-granite-small.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs",
                             "granite_moe_hybrid.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    assert sorted(k for k, v in CATALOG.items() if cfg.get(k) != v) \
        == sorted(k for k in REDUCED if k in CATALOG)
    assert cfg["reduced"] == list(REDUCED)
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_experts_held": 72,
                                "vocab_size": 100352}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"]
    # the layers run: one whole period, nine mamba layers and the
    # attention layer at 5; the router's width and the experts a token
    # as published, half the experts and half the vocabulary held
    from chipbench.reference import granite_moe_hybrid as ref
    assert cfg["layers_run"] == list(range(10))
    assert ref.layer_types(cfg) == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert (cfg["num_local_experts"], cfg["num_experts_per_tok"],
            cfg["held_first"]) == (72, 10, 0)
    assert 2 * cfg["vocab_size"] == CATALOG["vocab_size"]
    for key in ("reduced_detail", "assumed", "deployment", "env"):
        assert cfg[key], key
    assert sorted(cfg["reduced_detail"]) == sorted(
        list(REDUCED) + ["arithmetic"])
    # one entry of ``assumed`` for each reading the config leaves open
    for key in ("expert_width", "router", "experts", "shared_expert",
                "split_order", "convolution", "dt_limits", "gated_norm",
                "D", "multipliers", "positions", "precision",
                "state_layout", "kv_heads", "chunk", "weights", "ladder",
                "capacity", "prefill_chunk", "prefix_store"):
        assert key in cfg["assumed"], key
    assert "float32" in cfg["assumed"]["precision"]
    assert "two" in cfg["deployment"] and "half" in cfg["deployment"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"]) \
        == (8192, 256, [1, 8, 32])
    assert cfg["prefill_chunk"] == cfg["mamba_chunk_size"]
    # the arithmetic of reduced_detail, in millions of parameters
    D, V = 4096, 50176
    d_in, C = 128 * 64, 128 * 64 + 2 * 128
    expert = 3 * D * 768
    mamba = D * (d_in + C + 128) + d_in * D + C * 5 + 3 * 128 + d_in
    attn = D * (32 + 2 * 8) * 128 + 32 * 128 * D
    ffn = 3 * D * 1536 + 72 * D + 2 * D
    assert [round(x / 1e6, 2) for x in (expert, mamba, attn, 3 * D * 1536,
                                        72 * D, 36 * expert)] \
        == [9.44, 102.29, 41.94, 18.87, 0.29, 339.74]
    whole = 36 * mamba + 4 * attn + 40 * (ffn + 72 * expert) \
        + 100352 * D + D
    assert round(whole / 1e6) == 32207 and round(2 * whole / 1e9, 1) == 64.4
    here = 9 * (mamba + ffn + 36 * expert) + attn + ffn + 36 * expert \
        + V * D + D
    assert round(here / 1e6, 1) == 4757.2 and round(2 * here / 1e9, 2) == 9.51
    recurrent = 9 * 4 * (128 * 64 * 128 + 3 * C)
    kv = cfg["capacity"] * 4096
    assert round(recurrent / 1e6, 2) == 38.66 and round(kv / 1e6, 2) == 33.55
    state = 41 * (recurrent + kv)
    assert round(state / 1e9, 2) == 2.96
    assert 0.77 < (2 * here + state) / 16e9 < 0.79
    # one whole layer does not leave room for ten on a chip
    assert 10 * 2 * (mamba + ffn + 72 * expert) + 2 * V * D > 16e9


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "granite-4.0-h-small"
    block = [tuple(p) for p in mix["block"]]
    assert block[:8] == [(700, 448), (1500, 96), (1150, 320), (2250, 64),
                         (150, 512), (1900, 256), (3100, 128), (6000, 96)]
    assert block[-4:] == [(500, 320), (2000, 272), (3850, 120), (6000, 160)]
    assert "prefix" not in mix
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 32, 1, 12)
    assert mix["clients"] == max(cell.config["ladder"])
    assert traffic_mod.block_totals(mix) == (32, 76040, 7560)
    tools = [(p, a) for p, a in block if 1500 <= p <= 4100 and a <= 160]
    grounded = [(p, a) for p, a in block if 700 <= p <= 2300 and a >= 224]
    short = [(p, a) for p, a in block if p <= 500]
    docs = [(p, a) for p, a in block if p == 6000]
    assert (len(tools), len(grounded), len(short), len(docs)) \
        == (12, 12, 4, 4)
    assert all(64 <= a <= 160 for _, a in tools)
    assert all(224 <= a <= 512 for _, a in grounded)
    assert all(150 <= p and 320 <= a <= 512 for p, a in short)
    assert all(96 <= a <= 192 for _, a in docs)
    assert not [p for p, _ in block if p % 256 == 0]
    assert max(p + a for p, a in block) == 6192 < cell.config["capacity"]
    # interleaved: a long document closes every run of eight
    assert [i % 8 for i, (p, _) in enumerate(block) if p == 6000] == [7] * 4
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"sched.window_iter_share", "sched.tokens_per_iter",
            "engine.step_ms_p50", "engine.window_ms_p50.chat",
            "engine.fetch_ms_p50.chat", "sched.runahead_share_of_steps",
            "sched.runahead_share_of_windows", "decode_program_roofline",
            "moe.experts_touched_per_layer_step", "moe.expert_share_of_step",
            "moe_expert_roofline", "moe.held_assignment_share",
            "ssm.share_of_step", "ssm_decode_roofline",
            "ssm_window_roofline", "attn.read_share_of_step",
            "sched.riding_share_of_window_slots",
            "engine.real_share_of_window_rows",
            "engine.head_share_of_window_rows"} <= names
    assert not [n for n in names if n.startswith(("kda", "mla", "gqa_",
                                                  "dsa", "eva", "mhc"))]
    # OLMoE's scale of 64 is not this cell's
    assert "moe.load_imbalance" not in names
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
    assert ops.count("ssm_mixer_decode") == 2
    assert ops.count("attention_decode") == 1
    assert ops.count("MoEFFN") == 3
    moe = next(n for n in sym._topo_nodes() if n.op == "MoEFFN")
    assert (int(moe.attrs["num_experts"]), int(moe.attrs["held_count"]),
            int(moe.attrs["top_k"]), int(moe.attrs["shared_hidden"])) \
        == (8, 4, 3, 24)
    args = sym.list_arguments()
    assert "fed" in args and "pos_ids" not in args
    assert {"lm_tok_embed_weight", "lm_l0_mamba_in_weight",
            "lm_l0_moe_router_weight", "lm_l0_moe_gate_weight",
            "lm_l0_moe_shared_down_weight", "lm_l1_qkv_weight",
            "lm_l2_mamba_A_log"} <= set(args)
    assert not [a for a in args if "head_weight" in a or "ffn_" in a]
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len),
                                                  "fed": (4,)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, tie_word_embeddings=False), step_len)
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, num_local_experts=0), step_len)
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "granite_moe_hybrid.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                  # prose may name them
    assert "mxnet_tpu" not in body and "pallas" not in body
    assert "chunk" not in body and "cache" not in body
    assert "lax.scan" in body and "lax.top_k(logits" in body
    assert 'default_matmul_precision("highest")' in text
    # every reading at the head of the reference too
    for word in ("width of ONE routed expert", "top-k of the LOGITS",
                 "halves of one", "before ``residual_multiplier``",
                 "keeps its normalisation"):
        assert word in " ".join(text.split('"""', 2)[1].split()), word


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.moe_expert_bytes(cfg) == 18874368
    assert arch.moe_assignment(cfg) == {"flops": 6.0 * 4096 * 768,
                                        "bytes": 2 * 4096 * 2}
    assert arch.ssm_state_bytes(cfg) == 2 * 4 * (128 * 64 * 128 + 3 * 8448)
    assert arch.kv_row_bytes(cfg) == 4096
    got = arch.costs(cfg, 32, 256, 2000.0)
    assert set(got) == {"decode_step", "window_step", "ssm_state",
                        "ssm_row", "moe_expert", "moe_assignment"}
    assert got["ssm_state"]["bytes"] == 8591360
    # an S = 1 step of 32 slots touches nearly all 36 held experts
    touched = got["decode_step"]["held_experts_touched_per_layer"]
    assert 35.6 < touched < 35.8
    # what it reads: the weights outside the experts (2.72 GB: mixers
    # 1.93, shared feed-forward and router 0.38, half the head 0.41),
    # ten layers' touched experts (6.74 GB), 32 x 9 states read and
    # written (2.47 GB), one layer's K/V rows
    outside = 9 * 102.287e6 + 41.943e6 + 10 * (18.874e6 + 0.295e6 + 8192) \
        + 50176 * 4096
    assert got["decode_step"]["weights_outside_experts"] \
        == pytest.approx(outside, rel=1e-4)
    want = 2 * outside + 10 * touched * 18874368 + 32 * 9 * 8591360 \
        + 32 * 2001 * 4096 + 32 * 50176 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=0.005)
    assert 32 * 9 * 8591360 == pytest.approx(2.474e9, rel=0.001)
    assert 12.0e9 < got["decode_step"]["bytes"] < 12.6e9
    shares = {"experts": 10 * touched * 18874368,
              "state": 32 * 9 * 8591360,
              "mixers": 2 * (9 * 102.287e6 + 41.943e6)}
    for part, least in (("experts", 0.5), ("state", 0.19), ("mixers", 0.15)):
        assert shares[part] / got["decode_step"]["bytes"] > least, part
    # a window's experts: all 36, whatever its rows
    assert got["window_step"]["held_experts_touched_per_layer"] \
        == pytest.approx(36.0)


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _window_trace():
    """Chip 0: the window program of the top rung runs twice for 30,000
    us; inside each run ten layers' loops (``while``, 1,500 us) hold two
    trips of moe_gmm_gate_up 400 us and moe_gmm_down 200 us; the S=1
    program runs once with kernels of its own that are not a window's;
    a rung-8 window program runs beside them."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 40000):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x256(abc)", base,
                     30000))
        for layer in range(10):
            at = base + 100 + 2900 * layer
            out.append(e(plane, "XLA Ops", f"while.{layer}", at, 1500))
            for trip in range(2):
                out.append(e(plane, "XLA Ops", f"moe_gmm_gate_up.{layer}",
                             at + 700 * trip, 400))
                out.append(e(plane, "XLA Ops", f"moe_gmm_down.{layer}",
                             at + 700 * trip + 400, 200))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x1(abd)", 80000,
                 15000))
    out.append(e(plane, "XLA Ops", "moe_gmm_gate_up.77", 80100, 5000))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x256(abe)", 96000,
                 9000))
    out.append(e(plane, "XLA Ops", "moe_gmm_down.78", 96100, 3000))
    return out


def test_every_new_reader_on_a_synthetic_obs():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the ring's field and the counters (the parent):
    # every reader finds nothing, and raises nothing
    for name in NEW_METRICS:
        assert read(name, _obs()) is None, name
        assert read(name, {}) is None, name
    ring = [{"kind": "serve.decode.step", "window": 1, "rung": 32,
             "moe_touched": 357, "moe_held": 1600, "moe_layer_steps": 10}
            ] * 3 + [
        {"kind": "serve.decode.step", "window": 256, "rung": 32,
         "moe_touched": t, "moe_held": h, "moe_layer_steps": 10}
        for t, h in ((360, 19300), (358, 19100), (360, 19000))] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 8,
         "moe_touched": 100, "moe_held": 900, "moe_layer_steps": 10}]
    cost = {"moe_expert": {"flops": 0.0, "bytes": 18874368},
            "moe_assignment": {"flops": 6.0 * 4096 * 768, "bytes": 16384}}
    obs = _obs(events=_window_trace(), ring=ring, cost=cost,
               counters={"serve.decode.moe.max_expert_load": 700,
                         "serve.decode.moe.held_assignments": 19200,
                         "serve.decode.moe.assignments": 38400})
    # the busiest held expert's 700 over a mean of 19,200 / 36
    assert read("moe.held_load_imbalance", obs) == pytest.approx(
        36.0 * 700 / 19200)
    # the top rung's median window touched 360 experts: 6.79 GB at 819
    # GB/s = 8.30 ms against 10 x 2 x 600 us = 12 ms of moe_gmm* a run
    # (the S=1 program's kernels and the rung-8 program's are not read)
    assert read("moe_window_roofline", obs) == pytest.approx(
        100.0 * (360 * 18874368 / 819e9) / 12e-3, rel=1e-9)
    # and the operations where a window's assignments are many
    many = [dict(r, moe_held=10 * r["moe_held"]) for r in ring]
    assert read("moe_window_roofline", dict(obs, ring=many)) \
        == pytest.approx(100.0 * (191000 * 6.0 * 4096 * 768 / 197e12)
                         / 12e-3, rel=1e-9)
    # an architecture that states no such cost: not this metric's
    assert read("moe_window_roofline", dict(obs, cost={})) is None
    assert read("moe_window_roofline", dict(
        obs, cost={"moe_expert": cost["moe_expert"]})) is None
    # a program whose ring lacks moe_held (the parent's: the counter had
    # no ring field)
    bare = [{k: v for k, v in r.items() if k != "moe_held"} for r in ring]
    assert read("moe_window_roofline", dict(obs, ring=bare)) is None
    # a trace without a window program, or without the kernels in it
    assert read("moe_window_roofline", dict(obs, events=[])) is None
    for name in NEW_METRICS:
        assert (metrics[name].reader or metrics[name].decl["reads"]) \
            is not None


def test_make_params_is_seeded_and_the_controls_are_switches():
    """``make_params`` draws bfloat16 parameters from the seed (the same
    seed the same parameters, a large seed another set), the mixer's
    own as archs/granite_hybrid.py draws them; the reference's tail is
    its full forward's; every control is a switch of the same forward
    that moves the logits."""
    import numpy as np
    import jax.numpy as jnp
    arch, cfg = _arch(), _tiny()
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    again = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(np.array_equal(params[n], again[n]) for n in params)
    other = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1),
                             3280000019, cfg)
    assert not np.array_equal(params["lm_tok_embed_weight"],
                              other["lm_tok_embed_weight"])
    f32 = lambda n: np.asarray(params[n], np.float32)       # noqa: E731
    assert params["lm_l0_moe_gate_weight"].shape == (4, 48, 16)
    assert params["lm_l0_moe_router_weight"].shape == (8, 48)
    assert 0.01 < f32("lm_l0_moe_gate_weight").std() < 0.03
    assert (f32("lm_l0_ln2_gamma") == 1).all()
    assert (f32("lm_l0_mamba_D") == 1).all()
    rate = np.exp(f32("lm_l0_mamba_A_log"))
    assert (rate >= 0.99).all() and (rate <= 16.1).all()
    w = f32("lm_l0_mamba_conv_weight")
    assert w.shape == (128, 4) and 0.2 < w.std() < 0.35
    from chipbench.reference import granite_moe_hybrid as ref
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    want = np.asarray(ref.forward(params, tokens, cfg, tail=32))
    assert want.shape == (2, 32, 64)
    np.testing.assert_allclose(
        want, np.asarray(ref.forward(params, tokens, cfg))[:, -32:],
        atol=1e-5, rtol=1e-5)
    controls = arch._controls(cfg)
    assert [k for k, _w, _s in controls] == [
        "fp8", "experts_out", "gates_raw", "state_none", "state_lost",
        "state_bf16"]
    for _key, _what, switches in controls:
        low = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                     **switches))
        assert np.abs(low - want).max() > 0, _key
    assert jnp.bfloat16 in [s.get("state_dtype") for _k, _w, s in controls]
    assert [s.get("state_every") for _k, _w, s in controls][3:5] == [1, 16]
    same = np.asarray(ref.forward(params, tokens, cfg, tail=32,
                                  state_every=1000))
    assert np.array_equal(same, want)
    _logits, chosen = ref.forward(params, tokens, cfg, tail=32,
                                  return_chosen=True)
    assert chosen.shape == (3, 160, 3)
    assert float(ref.choice_flip_share(chosen, chosen)) == 0.0


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_small(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-granite-small.json"),
                       ("traffic", "tiny-agent32.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-granite-small", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-granite-small.json",
        "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-granite-small",
        "traffic": "tiny-agent32", "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS + ("moe.held_assignment_share",):
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_small(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_small(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_small_rehearses(copy_with_small, trace):
    root = copy_with_small
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
    for key in ("fp8", "experts_out", "gates_raw", "state_none",
                "state_lost", "state_bf16"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert 0.0 <= detail["choice_flip_share"] < 1.0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.moe.assignments",
                     "serve.decode.moe.held_assignments",
                     "serve.decode.moe.max_expert_load",
                     "serve.decode.ssm.rows",
                     "serve.decode.attn.live_rows"):
            assert counters[name] > 0, name
        assert counters["serve.decode.moe.assignments"] \
            > counters["serve.decode.moe.held_assignments"]
        # the counters' metrics read on the CPU too (the scale of 36 is
        # the published share's, so the tiny value is no imbalance); the
        # CPU's trace has no XLA Ops line, so the reader over the device
        # trace finds nothing and the line leaves it out
        assert last["metrics"]["moe.held_load_imbalance"]["value"] > 0
        assert 20 < last["metrics"]["moe.held_assignment_share"][
            "value"] < 80
        assert "moe_window_roofline" not in last["metrics"]
