"""The Nemotron-H architecture (ISSUE 63: archs/nemotron_h.py,
reference/nemotron_h.py, the configuration nemotron-3-nano-30b-a3b, the
traffic mix reason32-closed, the metric ``moe.rows_per_touched_expert``)
on the CPU: the interface, the configuration against the catalog and its
arithmetic, the costs against a count by hand, the new reader and the
accepted readers of the two new kernel forms on a synthetic ``obs``,
``make_params`` with its correction bias and the controls - and the cell
rehearsed at a tiny size (tests/fixtures/nemotron_h/) in a temporary
copy of the rehearsal manifest, traced and untraced: two CPU rehearsals
of under a minute each. All of it is part of tier-1 through
``tests/test_chipbench_nemotron_h.py``."""
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
FIXTURE = os.path.join(HERE, "fixtures", "nemotron_h")
CELL = "tiny-nemotron-reason32"
REAL_CELL = "nemotron3nano-serve-reason32-closed"
GRANITE_CELL = "granite4hs-serve-agent32-closed"
NEW_METRICS = ("moe.rows_per_touched_expert",)
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: architectures.jsonl, row NVIDIA-Nemotron-3-Nano-30B-A3B-BF16: ``config``
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 16, "n_routed_experts_held": 64,
           "vocab_size": 65536}
BLOCK = [
    (180, 2048), (1500, 1536), (420, 1536), (60, 1024), (120, 2560),
    (2200, 1024), (760, 1280), (6000, 640), (310, 1792), (3100, 896),
    (560, 2304), (140, 768), (900, 1024), (4000, 768), (240, 1920),
    (6000, 768), (650, 1408), (1800, 1408), (150, 2176), (220, 640),
    (480, 1664), (2700, 1152), (830, 1152), (6000, 896), (270, 2432),
    (3600, 832), (380, 1344), (300, 512), (700, 1888), (2400, 1280),
    (530, 1600), (6000, 704)]


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-nemotron.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "nemotron_h.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    assert [k for k, v in CATALOG.items() if cfg.get(k) != v] \
        == ["num_hidden_layers", "vocab_size"]
    assert cfg["reduced"] == list(REDUCED) \
        and {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts_held": 128,
                                "vocab_size": 131072}
    assert cfg["layers_run"] == list(range(16)) and cfg["held_first"] == 0
    run = PATTERN[:16]
    assert run == "MEMEM*EMEMEM*EME" and (
        run.count("M"), run.count("E"), run.count("*")) == (7, 7, 2)
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) \
        == (23, 23, 6) and [i for i, c in enumerate(PATTERN) if c == "*"] \
        == [5, 12, 19, 26, 33, 42]
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == cfg["reduced"] \
        and entry["source"] == cfg["source"] \
        == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-" \
           "BF16/blob/main/config.json"
    assert (cfg["kind"], cfg["arch"]) == ("serve", "nemotron_h")
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"]) \
        == (8192, 256, [1, 8, 32])
    assert cfg["prefill_chunk"] == 2 * cfg["chunk_size"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    assert (cfg["compute_dtype"], cfg["param_dtype"]) \
        == ("bfloat16", "bfloat16")
    for key in ("positions", "split_order", "gated_norm", "dt_limits",
                "router", "experts", "e_score_correction_bias",
                "state_layout", "precision", "capacity", "prefill_chunk",
                "ladder", "sampling", "kernel_tier", "weights",
                "mamba_width", "layers"):
        assert key in cfg["assumed"], key
    assert "modelling file is not on this machine" \
        in cfg["assumed"]["positions"]
    assert "43 %" in cfg["assumed"]["e_score_correction_bias"]
    assert "eight v5e chips" in cfg["deployment"] \
        and "not run" in cfg["deployment"]
    assert sorted(cfg["reduced_detail"]) == [
        "arithmetic", "n_routed_experts_held", "num_hidden_layers",
        "vocab_size"]
    # the arithmetic of reduced_detail, in millions of parameters
    D, V = 2688, 131072
    d_in, C = 64 * 64, 64 * 64 + 2 * 8 * 128
    assert (d_in, C, d_in + C + 64) == (4096, 6144, 10304)
    mamba = D * 10304 + d_in * D + C * 5 + 3 * 64 + d_in + D
    attn = D * (32 + 2 * 2) * 128 + 32 * 128 * D + D
    expert = 2 * D * 1856
    shared, router = 2 * D * 3712, 128 * D + 128
    layer = router + shared + 128 * expert + D
    held = router + shared + 64 * expert + D
    assert [round(x / 1e6, 2) for x in (mamba, attn, expert, shared, layer,
                                        held, 2 * V * D)] \
        == [38.74, 23.40, 9.98, 19.96, 1297.47, 658.89, 704.64]
    whole = 23 * mamba + 6 * attn + 23 * layer + 2 * V * D + D
    assert round(whole / 1e9, 2) == 31.58 and round(2 * whole / 1e9, 1) \
        == 63.2
    here = 7 * mamba + 7 * held + 2 * attn + 2 * 65536 * D + D
    assert round(2 * here / 1e9, 2) == 10.57
    state = 7 * (64 * 64 * 128 * 4 + 3 * C * 4) \
        + 2 * 2 * 128 * 2 * 2 * cfg["capacity"]
    assert round(state / 1e6, 2) == 31.97 and sum(cfg["ladder"]) == 41
    live = 2 * here + 41 * state
    assert 0.73 < live / 16e9 < 0.75
    # the whole-window program's logits at rung 32 fit beside it
    assert live + 32 * 256 * 65536 * 2 < 13.5e9


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 \
        and cell.config["name"] == "nemotron-3-nano-30b-a3b"
    assert [tuple(p) for p in mix["block"]] == BLOCK
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 32, 1, 12)
    assert mix["clients"] == max(cell.config["ladder"]) \
        and "prefix" not in mix
    assert traffic_mod.block_totals(mix) == (32, 53500, 42976)
    assert max(p + n for p, n in BLOCK) == 6896 < cell.config["capacity"]
    assert not [p for p, _ in BLOCK if p % 256 == 0]
    assert sorted(p for p, _ in BLOCK)[-4:] == [6000] * 4
    # answers longer than prompts for most: the ratio turned round
    assert sum(n > p for p, n in BLOCK) == 21
    # nine tenths decode: the gap between tokens is what its callers
    # feel most (seven runs of PR 63 spread by 0.1 % of a half bound of 0.8)
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "serve_tpot_p95_ms",
        "setup_s"}
    # every per-layer metric of Granite Small's cell reads here too, but
    # the one whose declaration scales by Granite's 36 held experts and
    # the one whose list PR 54's test pins to that cell alone; and the
    # S = 1 read of the two attention layers' K/V
    granite = {m.name for m in manifest.resolve(man, GRANITE_CELL).per_layer}
    mine = {m.name for m in cell.per_layer}
    assert granite - mine == {"moe.held_load_imbalance",
                              "moe_window_roofline"}
    assert mine - granite == {"gqa_decode_roofline"}
    new = next(m for m in man["per_layer"] if m["name"] == NEW_METRICS[0])
    assert new == {"name": "moe.rows_per_touched_expert", "unit": "rows",
                   "better": "higher", "source": "program_counter",
                   "layer": "kernels", "moves": "serve_tokens_per_s",
                   "workloads": [GRANITE_CELL, REAL_CELL]}
    assert man["per_layer"][-1] == new
    assert man["workloads"][-1]["name"] == REAL_CELL \
        and man["configs"][-1]["name"] == "nemotron-3-nano-30b-a3b"
    assert len(man["workloads"]) == 15 and len(man["configs"]) == 13


@pytest.mark.parametrize("step_len", [1, 16])
def test_the_architecture_file_has_the_interface_and_builds_the_block(
        step_len):
    arch, tiny = _arch(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"]:
        assert hasattr(arch, name), name
    assert not [n for n in manifest.ARCH_OPTIONAL["serve"]
                if hasattr(arch, n)]            # a token a step, no mask
    sym = arch.decode_symbol(tiny, step_len)
    assert sorted(arch.data_shapes(tiny, 4, step_len)) == ["data", "fed"]
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    # MEMEM*E: three mixers, three expert layers, one attention layer
    assert (ops.count("ssm_mixer_decode"), ops.count("MoEFFN"),
            ops.count("attention_decode")) == (3, 3, 1)
    args = sym.list_arguments()
    assert "lm_head_weight" in args and "lm_l1_moe_router_bias" in args
    assert not [a for a in args if "gate_weight" in a or "pos" in a]
    with pytest.raises(SystemExit, match="builds the published block"):
        arch.decode_symbol(dict(tiny, tie_word_embeddings=True), step_len)
    with pytest.raises(SystemExit, match="builds the published block"):
        arch.decode_symbol(dict(tiny, layers_run=[0, 1]), step_len)
    assert 0 < arch.LOGIT_TOL < 1 and arch._TAIL == 32


def test_a_tree_without_the_block_fails_at_once(monkeypatch):
    """On the parent commit ``models/transformer.py`` has no
    ``NEMOTRON_H_KEYS``: ``decode_symbol`` exits before a weight is
    drawn."""
    from mxnet_tpu.models import transformer as tfm
    arch, tiny = _arch(), _tiny()
    monkeypatch.delattr(tfm, "NEMOTRON_H_KEYS")
    with pytest.raises(SystemExit, match="builds no block 'nemotron_h'"):
        arch.decode_symbol(tiny, 1)


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    slots, live = 32, 1500.0
    cost = arch.costs(cfg, slots, 256, live)
    assert sorted(cost) == ["decode_step", "gqa_row", "moe_assignment",
                            "moe_expert", "ssm_row", "ssm_state",
                            "window_step"]
    assert cost["gqa_row"]["bytes"] == 1024
    # one expert is TWO matrices; a state and its tail, read and written
    assert cost["moe_expert"]["bytes"] == 2 * 2688 * 1856 * 2 == 19955712
    assert cost["moe_assignment"]["flops"] == 4.0 * 2688 * 1856
    assert cost["ssm_state"]["bytes"] == 2 * 4 * (
        64 * 64 * 128 + 3 * 6144) == 4341760
    assert cost["ssm_row"]["bytes"] == (6144 + 64 + 2 * 4096) * 2 == 28800
    assert cost["ssm_row"]["flops"] == 2.0 * 128 * 128 * 8 \
        + 2.0 * 128 * 64 * 64 + 4.0 * 64 * 128 * 64
    assert arch.kv_row_bytes(cfg) == 1024
    step = cost["decode_step"]
    touched = 64 * (1 - (1 - 6 / 128) ** 32)
    assert step["held_experts_touched_per_layer"] == pytest.approx(touched)
    assert round(touched, 1) == 50.2
    D, V = 2688, 65536
    mamba = D * 10304 + 4096 * D + 6144 * 5 + 3 * 64 + 4096
    attn = D * 36 * 128 + 4096 * D
    outside = 7 * mamba + 2 * attn + 7 * (2 * D * 3712 + 128 * D + 128) \
        + 16 * D + V * D + D
    assert step["weights_outside_experts"] == outside
    want_bytes = (2 * outside + 7 * touched * 19955712 + 32 * D * 2
                  + 32 * 7 * 4341760 + 32 * 7 * 28800
                  + 2 * (32 * (live + 1) + 32) * 1024 + 32 * V * 4)
    assert step["bytes"] == pytest.approx(want_bytes)
    want_flops = 2.0 * 32 * (outside + 7 * 3 * 2 * D * 1856) \
        + 32 * 7 * cost["ssm_row"]["flops"] \
        + 32 * 2 * (live + 0.5) * 4.0 * 4096
    assert step["flops"] == pytest.approx(want_flops)
    # the issue's arithmetic: about 9.4 GB a step, 11.5 ms at 819 GB/s,
    # the experts three quarters and the states a tenth; bytes bound
    assert 9.2e9 < step["bytes"] < 9.6e9
    assert 0.72 < 7 * touched * 19955712 / step["bytes"] < 0.78
    assert 0.09 < 32 * 7 * 4341760 / step["bytes"] < 0.12
    assert step["flops"] / 197e12 < 0.1 * step["bytes"] / 819e9
    window = cost["window_step"]
    assert window["held_experts_touched_per_layer"] == pytest.approx(64.0)
    assert window["flops"] > 200 * step["flops"]


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _step_trace():
    """Chip 0: the S=1 program of the top rung runs three times for
    14,000 us; inside each run seven E layers hold a ``moe_gmm_up`` of
    700 us and a ``moe_gmm_down`` of 600 us, seven M layers an
    ``ssm_update`` of 200 us, two attention layers a ``decode_attn`` of
    40 us; the window program runs once with kernels of its own."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 20000, 40000):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x1(abc)", base,
                     14000))
        for layer in range(7):
            at = base + 100 + 1900 * layer
            out.append(e(plane, "XLA Ops", f"ssm_update.{layer}", at, 200))
            out.append(e(plane, "XLA Ops", f"moe_gmm_up.{layer}", at + 300,
                         700))
            out.append(e(plane, "XLA Ops", f"moe_gmm_down.{layer}",
                         at + 1100, 600))
        for layer in range(2):
            out.append(e(plane, "XLA Ops", f"decode_attn.{layer}",
                         base + 13500 + 100 * layer, 40))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_32x256(abd)", 60000,
                 90000))
    out.append(e(plane, "XLA Ops", "moe_gmm_up.77", 60100, 9000))
    out.append(e(plane, "XLA Ops", "moe_gmm_down.77", 70100, 8000))
    out.append(e(plane, "XLA Ops", "ssm_scan.78", 80100, 3000))
    out.append(e(plane, "XLA Ops", "ssm_update.78", 84100, 1000))
    return out


def test_the_new_reader_and_the_new_kernel_forms_on_a_synthetic_obs():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer}
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    new = NEW_METRICS[0]
    # a program whose ring lacks the fields (the parent): nothing, no raise
    bare = [{"kind": "serve.decode.step", "window": 1, "rung": 32,
             "moe_touched": 350}]
    for obs in ({}, _obs(), _obs(ring=bare),
                _obs(ring=[dict(bare[0], moe_touched=0, moe_held=0)])):
        assert read(new, obs) is None
    ring = [{"kind": "serve.decode.step", "window": 1, "rung": 32,
             "moe_touched": t, "moe_held": h, "moe_layer_steps": 7,
             "ssm_touched": 224, "ssm_rows": 224}
            for t, h in ((350, 672), (352, 690), (340, 660))] + [
        {"kind": "serve.decode.step", "window": 256, "rung": 32,
         "moe_touched": 448, "moe_held": 8000, "moe_layer_steps": 7,
         "ssm_touched": 224, "ssm_rows": 2600}]
    arch, cfg = _arch(), _published()
    obs = _obs(events=_step_trace(), ring=ring,
               cost=arch.costs(cfg, 32, 256, 1500.0))
    # the median of the S=1 records' ratios (1.92, 1.96, 1.94); the
    # window's 17.9 is not read
    assert read(new, obs) == pytest.approx(660 / 340)
    assert metrics[new].reader is not None
    # the accepted readers find the new forms by their prefixes:
    # moe_gmm_up + moe_gmm_down are 7 x 1,300 us of 14,000
    assert read("moe.expert_share_of_step", obs) == pytest.approx(65.0)
    # 350 experts of two matrices: 6.98 GB, 8.53 ms at 819 GB/s
    assert read("moe_expert_roofline", obs) == pytest.approx(
        100.0 * (350 * 19955712 / 819e9) / 9.1e-3, rel=1e-9)
    assert 90 < read("moe_expert_roofline", obs) < 100
    assert read("ssm.share_of_step", obs) == pytest.approx(10.0)
    # 224 states of 4.34 MB: 1.19 ms against 7 x 200 us
    assert read("ssm_decode_roofline", obs) == pytest.approx(
        100.0 * (224 * 4341760 / 819e9) / 1.4e-3, rel=1e-9)
    assert read("attn.read_share_of_step", obs) == pytest.approx(
        100.0 * 80 / 14000)
    assert 0 < read("ssm_window_roofline", obs) < 100


def test_make_params_is_seeded_and_the_controls_are_switches():
    """``make_params`` draws bfloat16 parameters from the seed (the same
    seed the same parameters, a large seed another set), the correction
    bias of deviation 0.01 among them; the reference's tail is its full
    forward's; each control is a switch of the same forward that moves
    the logits."""
    import jax.numpy as jnp
    import numpy as np
    arch, tiny = _arch(), _tiny()
    from chipbench.reference import nemotron_h as ref
    sym = arch.decode_symbol(tiny, 1)
    shapes = arch.data_shapes(tiny, 4, 1)
    a = arch.make_params(sym, shapes, 3280000019, tiny)
    b = arch.make_params(sym, shapes, 3280000019, tiny)
    c = arch.make_params(sym, shapes, 7, tiny)
    assert sorted(a) == sorted(b) and all(
        np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    assert all(str(v.dtype) == "bfloat16" for v in a.values())
    f32 = lambda k: np.asarray(a[k], np.float32)            # noqa: E731
    assert float(f32("lm_l0_ln1_gamma").min()) == 1.0
    # D like a matrix, and the mixers' output projection at the plain
    # deviation where the experts' last matrix has the scaled one
    assert 0 < np.abs(f32("lm_l0_mamba_D")).max() < 0.1
    assert np.std(f32("lm_l0_proj_weight")) \
        > 3 * np.std(f32("lm_l1_moe_down_weight"))
    bias = f32("lm_l1_moe_router_bias")
    assert bias.shape == (8,) and 0 < np.abs(bias).max() < 0.05
    assert a["lm_l1_moe_up_weight"].shape == (4, 24, 64) \
        == a["lm_l1_moe_down_weight"].shape
    assert 0.2 < np.exp(-np.exp(f32("lm_l0_mamba_A_log")) * 1e-1).min()
    tokens = np.random.default_rng(0).integers(0, 64, (2, 48)).astype(
        np.int32)
    full = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny))
    tail = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny, tail=32))
    np.testing.assert_allclose(tail, full[:, -32:], atol=1e-6)
    controls = arch._controls(tiny)
    assert [k for k, _what, _sw in controls] == [
        "fp8", "experts_out", "relu", "gates_unscaled", "bias_out",
        "group_0", "one_statistic", "state_none"]
    assert arch._PER_RUN == ("fp8",)
    for key, _what, switches in controls:
        other = np.asarray(ref.forward(a, jnp.asarray(tokens), tiny,
                                       **switches))
        if key == "bias_out":       # may move no choice of so few tokens
            continue
        assert np.max(np.abs(other - full)) > 1e-5, key
    # the logits as check_reference reads them: the tail, zeros before
    out = arch.reference_logits(a, jnp.asarray(tokens), tiny)
    host = np.asarray(out)
    assert host.shape == (2, 48, 64) and not host[:, :16].any()
    np.testing.assert_allclose(host[:, 16:], full[:, 16:], atol=1e-6)


# ---------------------------------------------------- the cell, rehearsed
#: the accepted metrics listed for the tiny cell beside the new one
_LISTED = ("sched.tokens_per_iter", "sched.window_iter_share",
           "engine.step_ms_p50", "moe.held_assignment_share",
           "moe.experts_touched_per_layer_step")


def _add_tiny_nemotron(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-nemotron.json"),
                       ("traffic", "tiny-reason.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-nemotron", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-nemotron.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-nemotron", "traffic": "tiny-reason",
        "chips": 1, "why": "rehearsal"})
    have = {m["name"] for m in man["per_layer"]}
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms") \
                + _LISTED:
            m["workloads"].append(CELL)
    for m in manifest.load()["per_layer"]:
        if m["name"] in NEW_METRICS + _LISTED and m["name"] not in have:
            man["per_layer"].append(dict(m, workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_nemotron(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_nemotron(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_nemotron_rehearses(copy_with_nemotron, trace):
    root = copy_with_nemotron
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
    assert by["reference"]["fed_windows"]["packed"] == [True, True]
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    # a run prints the control that sets the limit and no other
    assert detail["fp8_control_max_abs_err"] > 0
    assert [k for k in detail if k.endswith("_control")] == ["fp8_control"]
    assert 0.0 <= detail["choice_flip_share"] < 1.0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.moe.assignments",
                     "serve.decode.moe.held_assignments",
                     "serve.decode.moe.layer_steps",
                     "serve.decode.ssm.rows", "serve.decode.ssm.touched",
                     "serve.decode.attn.live_rows"):
            assert counters[name] > 0, name
        assert counters["serve.decode.moe.assignments"] \
            > counters["serve.decode.moe.held_assignments"]
        # three E layers of three choices a real row, three M layers
        assert counters["serve.decode.moe.assignments"] \
            == 3 * counters["serve.decode.ssm.rows"]
        metrics = last["metrics"]
        assert 30 < metrics["moe.held_assignment_share"]["value"] < 70
        assert 1.0 <= metrics["moe.rows_per_touched_expert"]["value"] <= 3.0
        assert 0 < metrics["moe.experts_touched_per_layer_step"]["value"] \
            <= 4
        assert set(metrics) == set(NEW_METRICS + _LISTED)
