"""The Xing4.0 architecture (archs/xing4.py, reference/xing4.py, the
configuration xing4.0-29b-a4b, the traffic mix longreason-closed, the
``mhc.*`` / ``mhc_*`` metrics) on the CPU: the interface, the
configuration against the catalog and its arithmetic, the costs against
a count by hand, every new reader on a scripted trace, the controls
against the reference - quick, and part of tier-1 through
``tests/test_xing4.py`` - and the cell rehearsed at a tiny size
(tests/fixtures/xing4/) in a temporary copy of the rehearsal manifest,
traced and untraced - by hand, two CPU rehearsals of a minute each."""
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
FIXTURE = os.path.join(HERE, "fixtures", "xing4")
CELL = "tiny-xing4-longreason"
REAL_CELL = "xing4-serve-longreason-closed"
NEW_METRICS = ("mhc.share_of_step", "mhc_decode_roofline",
               "mhc_window_roofline")
#: architectures.jsonl, row Xing4.0-29B-A4B: ``config``
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-xing4.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "xing4.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    differs = sorted(k for k, v in CATALOG.items() if cfg.get(k) != v)
    assert differs == ["first_k_dense_replace", "num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "first_k_dense_replace": 2}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for key in ("reduced_detail", "assumed", "deployment", "env"):
        assert cfg[key], key
    # one entry of ``assumed`` for each choice the config leaves open
    for key in ("mhc_mappings", "mhc_clamp", "mhc_eps", "mhc_statistic",
                "mhc_stream_ends", "mtp", "topk_method"):
        assert key in cfg["assumed"], key
    assert "not served" in cfg["assumed"]["mtp"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas"}
    # the floors: five sparse layers behind the dense one (four is the
    # floor), every expert held, the whole vocabulary
    assert cfg["layers_run"] == [0, 2, 3, 4, 5, 6]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 5
    assert "n_routed_experts_held" not in cfg and cfg["ep_size"] == 1
    assert (cfg["capacity"], cfg["prefill_chunk"], cfg["ladder"]) \
        == (16384, 1024, [1, 4, 8])
    # the arithmetic of reduced_detail, in millions of parameters
    D, H = 3584, 32
    mla = D * 768 + 768 * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D
    expert = 3 * D * 1024
    mhc = 2 * (24 * 4 * D + 24 + 3)
    dense = mla + 3 * D * 9216 + mhc
    sparse = mla + 64 * expert + expert + D * 64 + mhc
    total = dense + 5 * sparse + 2 * 131072 * D
    assert [round(x / 1e6, 1) for x in (mla, expert, dense, sparse)] \
        == [28.4, 11.0, 128.2, 745.0]
    assert round(mhc / 1e6, 2) == 0.69
    assert round(total / 1e9, 2) == 4.79
    state = 13 * cfg["capacity"] * 6 * 1280
    assert round(state / 1e9, 2) == 1.64
    assert 0.69 < (2 * total + state) / 16e9 < 0.71
    # YaRN at factor 64: the softmax scale the issue states
    from chipbench.reference import axk1
    _inv, trig, scale, ramp = axk1.yarn(cfg)
    assert trig == 1.0 and ramp == (10, 23)
    assert round(scale, 5) == 0.14468


def test_the_traffic_is_the_issues():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "xing4.0-29b-a4b"
    assert mix["block"] == [[4200, 1024], [9800, 640], [3100, 1536],
                            [12200, 512], [6400, 1280], [7700, 768],
                            [5200, 1152], [11900, 896]]
    assert "prefix" not in mix
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 8, 1, 12)
    assert traffic_mod.block_totals(mix) == (8, 60500, 7808)
    assert sum(-(-p // 1024) for p, _ in mix["block"]) == 64
    assert max(p + a for p, a in mix["block"]) == 12796 \
        < cell.config["capacity"]
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"mla.attn_share_of_step", "mla_dense_decode_roofline",
            "moe_expert_roofline", "moe.load_imbalance",
            "decode_program_roofline",
            "engine.real_share_of_window_rows"} <= names
    assert not [n for n in names if "dsa" in n or "held" in n
                or "prefix" in n]
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}
    # the new metrics are this cell's alone, and nothing else was added
    for m in man["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [REAL_CELL]
            assert (m["moves"], m["source"], m["layer"]) == (
                "serve_tokens_per_s", "device_trace", "kernels")


@pytest.mark.parametrize("step_len", [1, 16])
def test_the_architecture_file_has_the_interface_and_builds_the_block(
        step_len):
    arch, cfg = _arch(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"]:
        assert hasattr(arch, name), name
    sym = arch.decode_symbol(cfg, step_len)
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("mla_attention_decode") == 3
    assert ops.count("mhc_pre") == ops.count("mhc_post") == 6
    assert ops.count("MoEFFN") == 2 and "dsa_index_select" not in ops
    args = sym.list_arguments()
    assert "fed" in args and "lm_head_weight" in args
    assert "lm_l1_moe_router_bias" in args
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len),
                                                  "fed": (4,)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, topk_method="none"), step_len)
    with open(os.path.join(ROOT, "chipbench", "reference",
                           "xing4.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                  # prose may name them
    assert "mxnet_tpu" not in body and "pallas" not in body
    assert 'default_matmul_precision("highest")' in text
    # every assumption at the head of the reference too
    for word in ("clamp acts on", "hc_eps", "copies of the embedding",
                 "sum of its rows", "not part of this forward"):
        assert word in text.split('"""', 2)[1], word


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.latent_row_bytes(cfg) == 1152
    assert arch.moe_expert_bytes(cfg) == 3 * 3584 * 1024 * 2
    assert arch.mhc_row_bytes(cfg) == 71680 == 10 * 3584 * 2
    got = arch.costs(cfg, 8, 1024, 8000.0)
    assert set(got) == {"decode_step", "window_step", "mla_window",
                        "mla_row", "mhc_row", "moe_expert",
                        "mla_pair_absorbed", "mla_pair_expanded",
                        "mla_key_expansion"}
    assert got["mhc_row"] == {"flops": 0.0, "bytes": 71680}
    assert got["mla_pair_absorbed"]["flops"] == 32 * 2176
    mla = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 4096 * 3584
    mapping = 24 * 14336
    outside = 6 * (mla + 2 * mapping) + 3 * 3584 * 9216 \
        + 5 * (3 * 3584 * 1024 + 3584 * 64) + 131072 * 3584
    # S = 1: 8 tokens' choices touch 64 (1 - (60/64)**8) = 25.8 experts
    # a layer, 2.8 GB of 5 layers' 7.0
    touched = 64 * (1 - (1 - 4 / 64) ** 8)
    assert touched == pytest.approx(25.8, abs=0.05)
    assert got["decode_step"]["experts_touched_per_layer"] == touched
    assert 5 * touched * 22020096 == pytest.approx(2.84e9, rel=0.01)
    att = 6 * 8 * 8001 * 1152 + 6 * 8 * 32 * 320 * 2 + 8 * 6 * 1152
    want = outside * 2 + 5 * touched * 22020096 + 8 * 3584 * 2 + att \
        + 8 * 12 * 71680 + 8 * 131072 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    # the issue's estimate of a step's reads: at least 4.5 GB
    assert 4.5e9 < got["decode_step"]["bytes"] < 5.5e9
    # a whole window (8 x 1,024 rows): 12 sub-layers' mixing is 7 GB of
    # the least traffic; the packed one's 1,152 rows 0.99 GB
    assert 1152 * 12 * 71680 == pytest.approx(0.99e9, rel=0.01)
    assert arch.attention(cfg, 8, 1, 8000.0)["form"] == "absorbed"


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _decode_trace():
    """Chip 0: the S=1 program of the top rung runs twice for 100 us,
    inside each run mhc_pre 2 us and mhc_post 3 us a sub-layer (four
    sub-layers); the window program runs once for 1,000 us with four
    times mhc_pre 20 us and mhc_post 30 us."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 200):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1(abc)", base,
                     100))
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_4x1(abd)",
                     base + 100, 10))
        for sub in range(4):
            out.append(e(plane, "XLA Ops", f"mhc_pre.{sub}",
                         base + 20 * sub, 2))
            out.append(e(plane, "XLA Ops", f"mhc_post.{sub}",
                         base + 20 * sub + 10, 3))
        out.append(e(plane, "XLA Ops", "fusion.1", base + 90, 10))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1024(abe)", 1000,
                 1000))
    for sub in range(4):
        out.append(e(plane, "XLA Ops", f"mhc_pre.{sub + 9}",
                     1000 + 100 * sub, 20))
        out.append(e(plane, "XLA Ops", f"mhc_post.{sub + 9}",
                     1050 + 100 * sub, 30))
    return out


def test_every_new_reader_on_a_scripted_trace():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the kernels and the ring field (the parent):
    # every reader finds nothing, and raises nothing
    for name in NEW_METRICS:
        assert read(name, _obs()) is None, name
        assert read(name, {}) is None, name
    ring = [{"kind": "serve.decode.step", "window": 1, "rung": 8,
             "mhc_rows": rows} for rows in (84, 96, 96)] + [
        {"kind": "serve.decode.step", "window": 1024, "rung": 8,
         "mhc_rows": 12 * rows} for rows in (1031, 1031, 400)] + [
        {"kind": "serve.decode.step", "window": 1024, "rung": 4,
         "mhc_rows": 12 * 1024}]
    cost = {"mhc_row": {"flops": 0.0, "bytes": 71680}}
    obs = _obs(events=_decode_trace(), ring=ring, cost=cost)
    # 4 x (2 + 3) us of mhc_* in each 100 us run of the 8-slot program
    assert read("mhc.share_of_step", obs) == pytest.approx(20.0)
    # median 96 row-sub-layers x 71,680 B at 819 GB/s = 8.4 us against
    # 20 us of mhc_* a run
    assert read("mhc_decode_roofline", obs) == pytest.approx(
        100.0 * (96 * 71680 / 819e9) / 20e-6, rel=1e-9)
    # the top rung's median window mixed 1,031 rows 12 times: 1.08 ms
    # against 200 us of mhc_* a run of the window program, scripted
    assert read("mhc_window_roofline", obs) == pytest.approx(
        100.0 * (12 * 1031 * 71680 / 819e9) / 200e-6, rel=1e-9)
    # an architecture that states no mhc_row: not these metrics' to read
    for name in NEW_METRICS[1:]:
        assert read(name, dict(obs, cost={})) is None
    # a program whose ring lacks the field (no op declares it)
    bare = [{k: v for k, v in r.items() if k != "mhc_rows"} for r in ring]
    for name in NEW_METRICS[1:]:
        assert read(name, dict(obs, ring=bare)) is None


def test_the_controls_are_further_than_the_emulation():
    """At a tiny size on the CPU, weights of real size: the reference
    with every matmul operand in float8, with the mappings' arithmetic
    in bfloat16 and without YaRN's factor of the softmax scale are each
    far from the reference; its bfloat16-operand emulation is nearer at
    the median position (a routing decision flipped by rounding moves a
    worst position)."""
    import jax.numpy as jnp
    import numpy as np
    arch, cfg = _arch(), _tiny()
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    assert np.asarray(params["lm_l0_ln1_gamma"], np.float32).all()
    assert (np.asarray(params["lm_l1_ffn_mhc_scale"], np.float32)
            == 1.0).all()
    assert params["lm_l1_moe_gate_weight"].shape == (16, 64, 32)
    assert params["lm_l1_moe_router_bias"].shape == (16,)
    assert params["lm_l0_proj_mhc_weight"].shape == (24, 256)
    again = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(np.array_equal(params[n], again[n]) for n in params)
    for name in params:            # weights of real size: see evabyte
        if name.endswith("_weight") and "norm" not in name:
            params[name] = (np.asarray(params[name], np.float32) * 10) \
                .astype(params[name].dtype)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    from chipbench.reference import xing4 as ref
    want = np.asarray(ref.forward(params, tokens, cfg, tail=32))
    assert want.shape == (2, 32, 64)
    np.testing.assert_allclose(
        want, np.asarray(ref.forward(params, tokens, cfg))[:, -32:],
        atol=1e-5, rtol=1e-5)
    per_position = lambda x: np.median(              # noqa: E731
        np.max(np.abs(x - want), axis=-1))
    emu = per_position(np.asarray(ref.forward(
        params, tokens, cfg, round_to=jnp.bfloat16, tail=32)))
    controls = {
        key: per_position(np.asarray(ref.forward(params, tokens, cfg,
                                                 tail=32, **switches)))
        for key, _what, switches in arch._CONTROLS}
    assert sorted(controls) == ["fp8", "mapping_bf16", "yarn_scale"]
    assert all(emu < c / 3 for c in (controls["fp8"],
                                     controls["yarn_scale"])), \
        (emu, controls)
    assert controls["mapping_bf16"] > emu / 3, (emu, controls)
    # what check_reference slices: through jax.jit, then np.asarray
    import jax
    full = np.asarray(jax.jit(lambda t: arch.TailLogits(t * 1.0, 80))(
        jnp.asarray(want)))
    assert full.shape == (2, 80, 64) and full.dtype == np.float32
    assert not full[:, :48].any()
    np.testing.assert_array_equal(full[:, 48:], want)


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_xing4(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-xing4.json"),
                       ("traffic", "tiny-longreason.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-xing4", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-xing4.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-xing4", "traffic": "tiny-longreason",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50", "decode_program_roofline"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS + ("moe.experts_touched_per_layer_step",):
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_xing4(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_xing4(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_xing4_rehearses(copy_with_xing4, trace):
    root = copy_with_xing4
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
    assert 0.0 <= detail["choice_flip_share"] <= 1.0
    for key in ("fp8", "mapping_bf16", "yarn_scale"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.mhc.rows",
                     "serve.decode.attn.attended_rows",
                     "serve.decode.moe.experts_touched"):
            assert counters[name] > 0, name
        # three layers: six sub-layers mix every real row of a dispatch
        real = counters["serve.decode.window.real_rows"]
        assert counters["serve.decode.mhc.rows"] >= 6 * real
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in NEW_METRICS:
            assert name not in last["metrics"]
