"""The A.X-K1 architecture (archs/axk1.py, reference/axk1.py, the
configuration a.x-k1, the traffic mix shareddoc-closed, the ``mla.*`` /
``mla_dense_*`` / ``prefix.*`` metrics) on the CPU: the interface, the
configuration's arithmetic against ``published``, the costs against a
count by hand, every new reader on a scripted trace, the controls
against the reference - quick, and part of tier-1 through
``tests/test_axk1.py`` - and the cell rehearsed at a tiny size
(tests/fixtures/axk1/) in a temporary copy of the rehearsal manifest,
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
FIXTURE = os.path.join(HERE, "fixtures", "axk1")
CELL = "tiny-axk1-shareddoc"
REAL_CELL = "axk1-serve-shareddoc-closed"
NEW_METRICS = ("mla.attn_share_of_step", "mla_dense_decode_roofline",
               "mla_dense_window_roofline",
               "prefix.joined_share_of_prompt_tokens", "prefix.join_ms_p50")
#: architectures.jsonl, row A.X-K1: ``config``
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs", "a.x-k1.json")) as f:
        return json.load(f)


def _tiny():
    with open(os.path.join(FIXTURE, "configs", "tiny-axk1.json")) as f:
        return json.load(f)


def _arch():
    return manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "axk1.py"))


# ------------------------------------------------------------ quick cases
def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    cfg = _published()
    differs = sorted(k for k, v in CATALOG.items() if cfg.get(k) != v)
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts_held",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts_held": 192,
                                "vocab_size": 163840}
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "a.x-k1")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for key in ("reduced_detail", "assumed", "deployment", "env"):
        assert cfg[key], key
    assert "topk_method" in cfg["assumed"]
    assert cfg["env"] == {"MXNET_KERNEL_TIER": "pallas",
                          "MXNET_SERVE_PREFIX_CACHE_MB": "512"}
    # the floors: four sparse layers behind the dense one, 12 >= 8
    # experts held - half a group -, an eighth of the vocabulary
    assert cfg["layers_run"] == [0, 1, 2, 3, 4]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] == 4
    per_group = cfg["n_routed_experts"] // cfg["n_group"]
    assert cfg["n_routed_experts_held"] * 2 == per_group == 24
    assert cfg["vocab_size"] * 8 == 163840
    # the arithmetic of reduced_detail, in millions of parameters
    D, H = 7168, 64
    mla = D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D
    expert = 3 * D * 2048
    dense = mla + 3 * D * 18432
    sparse = mla + expert + D * 192 + 12 * expert
    total = dense + 4 * sparse + 2 * 20480 * D
    assert [round(x / 1e6, 1) for x in (mla, expert, dense, sparse)] \
        == [101.1, 44.0, 497.5, 675.0]
    assert round(total / 1e9, 2) == 3.49
    state = 13 * cfg["capacity"] * 5 * 1280
    assert round(state / 1e9, 2) == 2.73
    assert 0.6 < (2 * total + state) / 16e9 < 0.62
    # a resident document's rows, and three of them inside the budget
    assert round(16384 * 5 * 1280 / 1e6) == 105
    assert 3 * 18400 * 5 * 1280 < 512 * (1 << 20)


def test_the_traffic_is_the_issues_and_shares_three_documents():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    mix = cell.traffic
    assert cell.chips == 1 and cell.config["name"] == "a.x-k1"
    assert mix["block"] == [[16700, 128], [17000, 256], [2300, 128],
                            [17400, 96], [17900, 192], [5200, 96],
                            [18400, 128], [16600, 256]]
    assert mix["prefix"] == {"count": 3, "len": 16384}
    assert (mix["kind"], mix["clients"], mix["lead_in_blocks"],
            mix["trace_seconds"]) == ("closed_loop", 8, 2, 12)
    assert traffic_mod.block_totals(mix) == (8, 111500, 1280)
    shared = [p for p, _ in mix["block"] if p > 16384]
    assert len(shared) == 6 and 16384 * 6 == 98304
    assert round(100 * 98304 / 111500) == 88
    assert max(p + a for p, a in mix["block"]) == 18528 \
        < cell.config["capacity"]
    # two blocks of the generator: the shared requests carry a
    # prefix_id and open with that document, the short ones do not;
    # over seeds 1-5 every document is asked for inside the lead-in
    for seed in (1, 2, 3, 4, 5, 2 ** 31 + 77):
        gen = traffic_mod.requests(mix, cell.config["vocab_size"], seed)
        reqs = [next(gen) for _ in range(16)]
        docs = {}
        for r in reqs:
            assert (r.prefix_id is not None) == (r.prompt_len > 16384)
            if r.prefix_id is not None:
                head = r.prompt[:16384].tobytes()
                assert docs.setdefault(r.prefix_id, head) == head
        assert len(docs) == 3, (seed, sorted(docs))
    names = {m.name for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert not [n for n in names if "dsa" in n]
    assert {m.name for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p90_ms", "setup_s"}


@pytest.mark.parametrize("step_len", [1, 16])
def test_the_architecture_file_has_the_interface_and_builds_the_block(
        step_len):
    arch, cfg = _arch(), _tiny()
    for name in manifest.ARCH_INTERFACE["serve"]:
        assert hasattr(arch, name), name
    sym = arch.decode_symbol(cfg, step_len)
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("mla_attention_decode") == 3
    assert ops.count("MoEFFN") == 2 and "dsa_index_select" not in ops
    args = sym.list_arguments()
    assert "fed" in args and "lm_head_weight" in args
    assert not [a for a in args if a.endswith(("_bias", "_beta"))]
    assert arch.data_shapes(cfg, 4, step_len) == {"data": (4, step_len),
                                                  "fed": (4,)}
    with pytest.raises(SystemExit, match="published block"):
        arch.decode_symbol(dict(cfg, topk_method="noaux_tc"), step_len)
    with open(os.path.join(ROOT, "chipbench", "reference", "axk1.py")) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                  # prose may name them
    assert "mxnet_tpu" not in body and "glm_dsa" not in body
    assert 'default_matmul_precision("highest")' in text


def test_costs_against_a_count_by_hand():
    arch, cfg = _arch(), _published()
    assert arch.latent_row_bytes(cfg) == 1152
    assert arch.moe_expert_bytes(cfg) == 3 * 7168 * 2048 * 2
    got = arch.costs(cfg, 8, 1024, 17000.0)
    assert set(got) == {"decode_step", "window_step", "mla_window",
                        "mla_row", "moe_expert", "mla_pair_absorbed",
                        "mla_pair_expanded", "mla_key_expansion"}
    assert got["mla_pair_absorbed"]["flops"] == 64 * 2176
    assert got["mla_pair_expanded"]["flops"] == 64 * 640
    assert got["mla_key_expansion"] == {"flops": 64 * 2 * 512 * 256,
                                        "bytes": 1152}
    mla = 7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256 \
        + 8192 * 7168
    outside = 5 * mla + 3 * 7168 * 18432 \
        + 4 * (3 * 7168 * 2048 + 7168 * 192) + 20480 * 7168
    # S = 1: 8 tokens' choices touch 12 * (1 - (23/24)**8) = 3.5 held
    # experts a layer; each slot's query attends its 17,000 rows (and
    # its own); absorbed is the cheaper form by far (an expansion of
    # 17,001 keys for one query a slot is not)
    touched = 12 * (1 - (1 - 8 / 192) ** 8)
    assert touched == pytest.approx(3.46, abs=0.01)
    att = 5 * 8 * 17001 * 1152 + 5 * 8 * 64 * 320 * 2 + 8 * 5 * 1152
    want = outside * 2 + 4 * touched * 88080384 + 8 * 7168 * 2 + att \
        + 8 * 20480 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    assert arch.attention(cfg, 8, 1, 17000.0)["form"] == "absorbed"
    assert arch.attention(cfg, 8, 1, 17000.0)["flops"] == pytest.approx(
        5 * 8 * 17000.5 * 64 * (2 * 576 + 2 * 512), rel=1e-12)
    # the window: 8,192 queries at 17,512 keys on average. Absorbed
    # 2,176 FLOPs a query, key and head; expanded 640 and the keys'
    # expansion once: the expanded form is the cheaper, and says so
    window = got["mla_window"]
    absorbed = 8192 * 17512.0 * 64 * 2176
    expanded = 8192 * 17512.0 * 64 * 640 + 8 * 18024 * 64 * 2 * 512 * 256
    assert window["form"] == "expanded" and expanded < absorbed / 2
    assert window["flops"] == pytest.approx(5 * expanded, rel=1e-12)
    assert 41e12 < window["flops"] < 42e12
    # at a short context one window's expansion outweighs its scores
    assert arch.attention(cfg, 8, 16, 100.0)["form"] == "absorbed"
    # the rest of a window: 21.6 TFLOP of matrix products over 8,192 rows
    assert 21e12 < got["window_step"]["flops"] - window["flops"] < 22e12


def _obs(**kw):
    obs = {"events": scripted_trace.events(), "device_kind": "TPU v5 lite",
           "chips": 1, "ring": [], "counters": {}, "cost": {}}
    obs.update(kw)
    return obs


def _decode_trace():
    """Chip 0: the S=1 program of the top rung runs twice for 100 us,
    inside each run mla_write 5 us and mla_attn_decode 20 us a layer
    (two layers); the window program runs once for 1,000 us with
    mla_attn_window 300 us and mla_write 20 us."""
    e = scripted_trace._e
    plane, out = "/device:TPU:0", []
    for base in (0, 200):
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1(abc)", base,
                     100))
        out.append(e(plane, "XLA Modules", "jit_fwd_infer_4x1(abd)",
                     base + 100, 10))
        for layer in range(2):
            out.append(e(plane, "XLA Ops", f"mla_write.{layer}",
                         base + 40 * layer, 5))
            out.append(e(plane, "XLA Ops", f"mla_attn_decode.{layer}",
                         base + 40 * layer + 10, 20))
        out.append(e(plane, "XLA Ops", "fusion.1", base + 90, 10))
    out.append(e(plane, "XLA Modules", "jit_fwd_infer_8x1024(abe)", 1000,
                 1000))
    out.append(e(plane, "XLA Ops", "mla_write.9", 1000, 20))
    out.append(e(plane, "XLA Ops", "mla_attn_window.9", 1100, 300))
    return out


def test_every_new_reader_on_a_scripted_trace():
    man = manifest.load()
    cell = manifest.resolve(man, REAL_CELL)
    metrics = {m.name: m for m in cell.per_layer if m.name in NEW_METRICS}
    assert sorted(metrics) == sorted(NEW_METRICS)
    from chipbench import readers
    read = lambda name, obs: readers.read(metrics[name], obs)  # noqa: E731
    # a program without the spans, counters and kernels (the parent):
    # every reader finds nothing, and raises nothing
    for name in NEW_METRICS:
        assert read(name, _obs()) is None, name
        assert read(name, {}) is None, name
    # three windows of the top rung: one slot prefills 1,024 rows at
    # 16,384 and seven ride at 17,000 (five layers); then two slots
    # prefill; then one feeds 10 rows. A window of a lower rung beside
    def window(rung, slots):
        return {"kind": "serve.decode.step", "window": 1024, "rung": rung,
                "mla_attended": 5 * sum(p + n for p, n in slots),
                "mla_pairs": 5 * sum(n * p + n * (n + 1) // 2
                                     for p, n in slots)}
    riding = [(17000, 1)] * 7
    windows = [window(8, [(16384, 1024)] + riding),
               window(8, [(16384, 1024), (16384, 1024)] + riding[:6]),
               window(8, [(16384, 10)] + riding),
               window(4, [(100, 1024)])]
    ring = [{"kind": "serve.decode.step", "window": 1, "mla_attended": rows,
             "mla_pairs": rows, "rung": 8}
            for rows in (500000, 711111, 900000)] + windows + [
        {"kind": "trace.span", "name": "serve.decode.prefix.join",
         "dur_us": 30000},
        {"kind": "trace.span", "name": "serve.decode.prefix.join",
         "dur_us": 50000},
        {"kind": "trace.span", "name": "serve.decode.prefix.join",
         "dur_us": 41000},
        {"kind": "trace.span", "name": "serve.decode.queue.wait",
         "dur_us": 7}]
    cost = {"mla_row": {"flops": 0.0, "bytes": 1152},
            **_arch().pair_costs(_published())}
    obs = _obs(events=_decode_trace(), ring=ring, cost=cost, counters={
        "serve.decode.prefix.joined_tokens": 98304 * 3,
        "serve.decode.prompt_tokens": 111500 * 3})
    # 2 x (5 + 20) us of mla_* in each 100 us run of the 8-slot program
    assert read("mla.attn_share_of_step", obs) == pytest.approx(50.0)
    # median 711,111 rows x 1,152 B at 819 GB/s = 1,000.2 us against
    # 40 us of mla_attn_decode a run: a scripted 2,500 %, unclipped
    assert read("mla_dense_decode_roofline", obs) == pytest.approx(
        100.0 * (711111 * 1152 / 819e9) / 40e-6, rel=1e-9)
    # the median window of the top rung is the first: 5 x 17.4 M pairs
    # and 5 x 136,415 keys - absorbed 12.1 TFLOP, expanded 3.6 + 11.4:
    # the absorbed form is the cheaper here (seven slots' keys would be
    # expanded for one query each) - at 197 TFLOP/s = 61.5 ms against
    # 320 us of mla_* a run of the window program, scripted
    pairs, keys = windows[0]["mla_pairs"], windows[0]["mla_attended"]
    assert pairs == 5 * (1024 * 16384 + 1024 * 1025 // 2 + 7 * 17001)
    assert pairs * 64 * 2176 < pairs * 64 * 640 + keys * 64 * 262144
    assert read("mla_dense_window_roofline", obs) == pytest.approx(
        100.0 * (pairs * 64 * 2176 / 197e12) / 320e-6, rel=1e-9)
    # with two slots prefilling the expanded form is the cheaper
    two = windows[1]
    assert two["mla_pairs"] * 64 * 2176 > two["mla_pairs"] * 64 * 640 \
        + two["mla_attended"] * 64 * 262144
    assert read("prefix.joined_share_of_prompt_tokens", obs) \
        == pytest.approx(88.165, abs=1e-3)
    assert read("prefix.join_ms_p50", obs) == pytest.approx(41.0)
    # an architecture that states no pair costs: not this metric's to read
    assert read("mla_dense_window_roofline",
                dict(obs, cost={"mla_row": cost["mla_row"]})) is None


def test_the_controls_are_further_than_the_emulation():
    """At a tiny size on the CPU, weights of real size: the reference
    with every matmul operand in float8, without YaRN's factor of the
    softmax scale, and with the plain rotary are each far from the
    reference; its bfloat16 emulation is near at the median position
    (a routing decision flipped by rounding moves a worst position)."""
    import jax.numpy as jnp
    import numpy as np
    arch, cfg = _arch(), _tiny()
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    assert np.asarray(params["lm_l0_ln1_gamma"], np.float32).all()
    assert params["lm_l1_moe_gate_weight"].shape == (3, 64, 32)
    assert params["lm_l1_moe_router_weight"].shape == (24, 64)
    assert not [n for n in params if n.endswith("router_bias")]
    again = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(np.array_equal(params[n], again[n]) for n in params)
    for name in params:            # weights of real size: see evabyte
        if name.endswith("_weight") and "norm" not in name:
            params[name] = (np.asarray(params[name], np.float32) * 10) \
                .astype(params[name].dtype)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    from chipbench.reference import axk1 as ref
    want = np.asarray(ref.forward(params, tokens, cfg))
    per_position = lambda x: np.median(              # noqa: E731
        np.max(np.abs(x - want[:, -32:]), axis=-1))
    emu = per_position(np.asarray(ref.forward(
        params, tokens, cfg, round_to=jnp.bfloat16, tail=32)))
    controls = {
        key: per_position(np.asarray(ref.forward(params, tokens, cfg,
                                                 tail=32, **switches)))
        for key, _what, switches in arch._CONTROLS}
    assert sorted(controls) == ["fp8", "plain_rotary", "yarn_scale"]
    assert all(emu < c / 3 for c in controls.values()), (emu, controls)


# ---------------------------------------------------- the cell, rehearsed
def _add_tiny_axk1(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-axk1.json"),
                       ("traffic", "tiny-shareddoc.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-axk1", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-axk1.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-axk1", "traffic": "tiny-shareddoc",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50", "decode_program_roofline"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW_METRICS + ("moe.held_assignment_share",):
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


@pytest.fixture(scope="module")
def copy_with_axk1(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    _add_tiny_axk1(root)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_axk1_rehearses(copy_with_axk1, trace):
    root = copy_with_axk1
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
    assert by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["positions_compared"] == 32
    assert 0.0 <= detail["choice_flip_share"] <= 1.0
    for key in ("fp8", "yarn_scale", "plain_rotary"):
        assert detail[f"{key}_control_max_abs_err"] > 0
    assert last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    assert not by["window"]["compiles_in_window"]
    if trace:
        counters = by["traced"]["counters"]
        for name in ("serve.decode.prefix.joined_tokens",
                     "serve.decode.prompt_tokens",
                     "serve.decode.attn.attended_rows",
                     "serve.decode.moe.held_assignments"):
            assert counters[name] > 0, name
        # prompts of 53, 61 and 70 join at 48 of them, 19 is computed
        share = last["metrics"]["prefix.joined_share_of_prompt_tokens"]
        assert 60.0 < share["value"] < 80.0
        assert last["metrics"]["prefix.join_ms_p50"]["value"] > 0
        # 3 of 24 experts held: an eighth of the assignments, roughly
        assert 4.0 < last["metrics"]["moe.held_assignment_share"][
            "value"] < 25.0
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in NEW_METRICS[:3]:
            assert name not in last["metrics"]
