"""The EvaByte architecture (archs/evabyte.py, reference/evabyte.py, the
``eva.*`` metrics) rehearsed on the CPU at a tiny size: a tiny
configuration and traffic mix (tests/fixtures/evabyte/) and a cell in a
temporary copy of the rehearsal manifest, traced and untraced; the
architecture's costs against a count by hand at one shape; the control
against ``LOGIT_TOL``. By hand, not part of tier-1 (two CPU rehearsals,
a minute or two)."""
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

from chipbench import manifest  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal")
FIXTURE = os.path.join(HERE, "fixtures", "evabyte")
CELL = "tiny-evabyte-longdoc"
EVA_COUNTERS = ("serve.decode.eva.layer_steps", "serve.decode.eva.exact_rows",
                "serve.decode.eva.summary_rows",
                "serve.decode.eva.chunks_summarised",
                "serve.decode.eva.windows_closed")
EVA_METRICS = ("eva.summary_share_of_keys", "eva.attn_share_of_step",
               "eva_decode_roofline", "eva_window_roofline")


def _add_tiny_evabyte(root):
    """The tiny configuration, its traffic mix and its cell into the
    copy under ``root``: two new files and manifest entries; the
    architecture, its reference and the metrics' readers are the
    benchmark's own."""
    inside = "chipbench/tests/rehearsal/"
    for kind, name in (("configs", "tiny-evabyte.json"),
                       ("traffic", "tiny-longdoc.json")):
        dest = root / inside / kind / name
        assert not dest.exists()
        shutil.copy(os.path.join(FIXTURE, kind, name), dest)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-evabyte", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-evabyte.json", "why": "rehearsal"})
    man["workloads"].append({
        "name": CELL, "config": "tiny-evabyte", "traffic": "tiny-longdoc",
        "chips": 1, "why": "rehearsal"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "sched.window_iter_share",
                         "engine.step_ms_p50", "decode_program_roofline"):
            m["workloads"].append(CELL)
    real = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in EVA_METRICS:        # as BENCHMARK.json declares them
        man["per_layer"].append(dict(real[name], workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _files(root):
    return {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def copy_with_evabyte(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    before = _files(root)
    _add_tiny_evabyte(root)
    return root, before


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_evabyte_rehearses(copy_with_evabyte, trace):
    root, before = copy_with_evabyte
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
    assert by["reference"]["ok"], by["reference"]
    # prefill 4 x 16 and 16 steps: the comparison crosses position 64,
    # the second window boundary, reading summaries on both paths
    assert by["reference"]["tokens"] == 80
    detail = by["reference_detail"]
    assert by["reference"]["tolerance"] == detail["tolerance"]   # its own
    assert detail["control_max_abs_err"] > \
        detail["bfloat16_emulation_max_abs_err"]
    assert last["correct"] and last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    if trace:
        counters = by["traced"]["counters"]
        for name in EVA_COUNTERS:
            assert counters[name] > 0, name
        # two layers an iteration; the window's edges fall inside one
        assert abs(counters["serve.decode.eva.layer_steps"]
                   - 2 * counters["serve.decode.iterations"]) <= 2
        share = last["metrics"]["eva.summary_share_of_keys"]["value"]
        assert 5.0 < share < 60.0
        # the CPU's trace has no XLA Ops line: the readers over the
        # device trace find nothing and the line leaves them out
        for name in EVA_METRICS[1:]:
            assert name not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s",
                                        "serve_ttft_p90_ms", "setup_s"}
    after = _files(root)
    assert all(after[p] == data for p, data in before.items())
    added = sorted(str(p.relative_to(root)) for p in set(after) - set(before))
    assert added == ["chipbench/tests/rehearsal/configs/tiny-evabyte.json",
                     "chipbench/tests/rehearsal/traffic/tiny-longdoc.json"]


def _published():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "evabyte-6.5b.json")) as f:
        return json.load(f)


def test_costs_against_a_count_by_hand():
    """At the published widths, 8 slots at context 5,000: a query reads
    905 exact rows (5000 mod 2048 = 904, and itself) and 2 x 128
    summaries."""
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "evabyte.py"))
    cfg = _published()
    assert arch.keys_at(cfg, 5000) == (905, 256)
    assert arch.keys_at(cfg, 2047) == (2048, 0)
    assert arch.keys_at(cfg, 2048) == (1, 128)
    assert arch.row_bytes(cfg) == 16384
    got = arch.costs(cfg, 8, 512, 5000.0)
    assert set(got) == {"decode_step", "window_step", "eva_window",
                        "eva_row"}
    d, L, F = 4096, 8, 11008
    weights = L * (4 * d * d + 3 * d * F + 2 * d) + 8 * 320 * d
    # S = 1: every weight once, 8 embedding rows, 1,161 rows of state a
    # slot a layer, q k v out and the new row with 1/16 of a summary
    state = L * 8 * (905 + 256) * 16384
    moved = L * 8 * (4 * d * 2 + 16384 * (1 + 1 / 16))
    want = weights * 2 + 8 * d * 2 + state + moved + 8 * 320 * 4
    assert got["decode_step"]["bytes"] == pytest.approx(want, rel=1e-12)
    matmul = 2 * 8 * (L * (4 * d * d + 3 * d * F) + 8 * 320 * d)
    attn = L * (4 * 8 * (905 + 256 + 0.5) * d + 6 * 8 * d)
    assert got["decode_step"]["flops"] == pytest.approx(matmul + attn,
                                                        rel=1e-12)
    # the window: 4,096 tokens; the matmuls are 13.3 TFLOP, the EVA
    # kernels a twentieth of that
    win = got["window_step"]["flops"]
    assert 13.0e12 < win < 14.5e12
    assert 0.03 < got["eva_window"]["flops"] / win < 0.08


def test_the_float8_control_fails_the_tolerance():
    """At a tiny size on the CPU: the reference with every matmul
    operand in float8 is outside ``LOGIT_TOL``; its bfloat16 emulation
    and the state-only control are not the control."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    arch = manifest._load_file(
        "arch", os.path.join(ROOT, "chipbench", "archs", "evabyte.py"))
    with open(os.path.join(FIXTURE, "configs", "tiny-evabyte.json")) as f:
        cfg = json.load(f)
    os.environ.setdefault("MXNET_KERNEL_TIER", "xla")
    symbol = arch.decode_symbol(cfg, 1)
    params = arch.make_params(symbol, arch.data_shapes(cfg, 2, 1), 7, cfg)
    assert all(str(a.dtype) == "bfloat16" for a in params.values())
    assert not np.asarray(params["lm_l0_ln1_gamma"], np.float32).any()
    phi = np.asarray(params["lm_l0_attn_phi"], np.float32)
    assert phi.shape == (4, 16) and np.abs(phi).max() <= 0.25 + 1e-3
    # a head of real size: the random logits of a 64-wide model are too
    # small for any rounding to matter against an absolute bound
    params["lm_head_weight"] = (np.asarray(params["lm_head_weight"],
                                           np.float32) * 40).astype(
        params["lm_head_weight"].dtype)
    tokens = np.random.default_rng(0).integers(0, 64, (2, 80)).astype("i4")
    from chipbench.reference import evabyte as ref
    want = np.asarray(ref.forward(params, tokens, cfg))
    ctrl = np.asarray(ref.forward(params, tokens, cfg,
                                  round_to=jnp.float8_e4m3fn))
    emu = np.asarray(ref.forward(params, tokens, cfg,
                                 round_to=jnp.bfloat16))
    bound = arch.LOGIT_TOL + arch.LOGIT_TOL * np.abs(want)
    assert np.max(np.abs(ctrl - want) / bound) > 1.0
    assert np.max(np.abs(emu - want) / bound) < 1.0
    assert ref.forward(params, tokens, cfg, all_heads=True).shape \
        == (2, 80, 8, 64)
