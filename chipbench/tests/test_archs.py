"""The architecture seam (README.md, "The architecture interface"):
every configuration names an architecture that exposes its kind's
interface, the two that were moved build and count what the runners
built and counted before, and a third enters a copy of the benchmark as
new files and manifest entries only. By hand, not part of tier-1; the
last test is slow (two CPU rehearsals)."""
import dataclasses
import functools
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

from chipbench import costs, manifest  # noqa: E402

REHEARSAL = os.path.join(HERE, "rehearsal")
FIXTURE = os.path.join(HERE, "fixtures", "rotary")


@functools.lru_cache(maxsize=None)
def _cells():
    """Every cell of the benchmark and of the rehearsal manifest."""
    out = []
    for path, root in ((None, ROOT),
                       (os.path.join(REHEARSAL, "BENCHMARK.json"), REHEARSAL)):
        man = manifest.load(path, root=root)
        out += [manifest.resolve(man, w["name"], root=root)
                for w in man["workloads"]]
    return out


def _cell(name):
    return next(c for c in _cells() if c.name == name)


def test_every_configuration_names_an_architecture_with_its_interface():
    cells = _cells()
    for cell in cells:
        arch = manifest.load_arch(cell)
        for name in manifest.ARCH_INTERFACE[cell.config["kind"]]:
            assert hasattr(arch, name), (cell.name, name)
    assert {os.path.basename(c.arch_file) for c in cells} >= {
        "gpt2.py", "resnet.py"}         # a later PR adds, none goes


def test_a_configuration_without_arch_is_an_error_that_names_the_key(
        tmp_path):
    man = manifest.load()
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    for c in man["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        del cfg["arch"]
        (tmp_path / c["file"]).write_text(json.dumps(cfg))
    with pytest.raises(manifest.ManifestError, match='"arch"'):
        manifest.resolve(man, man["workloads"][0]["name"],
                         root=str(tmp_path))
    with open(os.path.join(ROOT, man["configs"][0]["file"])) as f:
        cfg = json.load(f)
    (tmp_path / man["configs"][0]["file"]).write_text(
        json.dumps(dict(cfg, arch="no-such")))
    with pytest.raises(manifest.ManifestError, match="archs/no-such.py"):
        manifest.resolve(man, man["workloads"][0]["name"],
                         root=str(tmp_path))


def test_an_architecture_that_lacks_a_name_is_refused(tmp_path):
    (tmp_path / "half.py").write_text("LOGIT_TOL = 0.1\n")
    cell = dataclasses.replace(_cell("tiny-chat"),
                               arch_file=str(tmp_path / "half.py"))
    with pytest.raises(manifest.ManifestError, match="decode_symbol"):
        manifest.load_arch(cell)


@pytest.mark.parametrize("step_len", [1, 8])        # S=1, prefill chunk
def test_gpt2_builds_the_symbol_the_runner_built(step_len):
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as tfm
    cell = _cell("tiny-chat")
    cfg = cell.config
    assert cfg["prefill_chunk"] == 8
    arch = manifest.load_arch(cell)
    with mx.name.NameManager():     # auto names ("plus3") count from 0
        want = tfm.get_decode_symbol(   # serve_runner.py at PR 26, letter
            capacity=cfg["capacity"], per_slot=True, step_len=step_len,
            max_seq_len=cfg["n_positions"],
            **dict(vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
                   n_layer=cfg["n_layer"], n_head=cfg["n_head"],
                   pos_embed=cfg["position_embedding"]))
    with mx.name.NameManager():
        got = arch.decode_symbol(cfg, step_len)
    assert got.tojson() == want.tojson()
    assert arch.data_shapes(cfg, 4, step_len) == {
        "data": (4, step_len), "pos_ids": (4, step_len)}
    with pytest.raises(SystemExit, match="4 \\* n_embd"):
        arch.decode_symbol(dict(cfg, n_inner=96), step_len)


def test_gpt2_draws_the_weights_the_runner_drew():
    import jax
    import numpy as np
    cell = _cell("tiny-chat")
    cfg, arch = cell.config, manifest.load_arch(cell)
    sym = arch.decode_symbol(cfg, 1)
    shapes = arch.data_shapes(cfg, 4, 1)
    seed = 3_000_000_019
    got = arch.make_params(sym, shapes, seed, cfg)
    names = [n for n in sym.list_arguments() if n not in shapes]
    assert sorted(got) == sorted(names) and "pos_ids" not in got
    key = jax.random.PRNGKey(seed % (1 << 31))
    for i, name in enumerate(names):
        arr = got[name]
        assert arr.dtype == np.float32
        if name.endswith("_gamma"):
            assert (arr == 1).all()
        elif name.endswith(("_beta", "_bias")):
            assert (arr == 0).all()
        else:       # parameter i folds i in: PR 24's draw, bit for bit
            want = jax.jit(lambda k, i=i, shape=arr.shape: 0.02
                           * jax.random.normal(jax.random.fold_in(k, i),
                                               shape, np.float32))(key)
            assert np.array_equal(arr, np.asarray(want)), name


def test_the_moved_costs_are_the_yardsticks():
    gpt = _cell("cgpt1.3b-serve-chat-closed")
    got = manifest.load_arch(gpt).costs(gpt.config, 8, 64, 417.5)
    assert got == {
        "decode_step": costs.gpt_step(gpt.config, 8, 1, 417.5),
        "window_step": costs.gpt_step(gpt.config, 8, 64, 417.5)}
    fit = _cell("resnet50-fit-dp4")
    assert manifest.load_arch(fit).costs(fit.config, 1024) == {
        "train_step": costs.resnet_train_step(fit.config, 1024)}


def test_resnet_pool_is_seeded_and_shaped():
    import numpy as np
    cell = _cell("tiny-fit-1")
    arch = manifest.load_arch(cell)
    x, y = arch.pool(6, cell.config, 2**31 + 5)
    x2, y2 = arch.pool(6, cell.config, 2**31 + 5)
    assert x.shape == (6, 3, 64, 64) and x.dtype == np.float32
    assert y.shape == (6,) and y.dtype == np.float32
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert not np.array_equal(x, arch.pool(6, cell.config, 7)[0])
    assert arch.UPDATED_PARAM in arch.symbol(cell.config).list_arguments()


# ------------------------------------------------ a third architecture
def _add_rotary(root):
    """The rotary fixture into the copy under ``root``: an architecture,
    its plain reference, a configuration, a declared metric over a
    counter outside the ``window`` line's seven, a cell - new files and
    manifest entries, nothing else."""
    bench = root / "chipbench"
    for part, dest in (("archs", bench / "archs"),
                       ("reference", bench / "reference"),
                       ("layers", bench / "layers"),
                       ("configs", bench / "tests" / "rehearsal" / "configs")):
        for name in os.listdir(os.path.join(FIXTURE, part)):
            assert not (dest / name).exists()
            shutil.copy(os.path.join(FIXTURE, part, name), dest / name)
    man = manifest.load(os.path.join(REHEARSAL, "BENCHMARK.json"))
    inside = "chipbench/tests/rehearsal/"
    man["paths"] = [inside.rstrip("/")]
    for c in man["configs"]:
        c["file"] = inside + c["file"]
    man["configs"].append({
        "name": "tiny-rotary", "source": "fixture", "reduced": [],
        "file": inside + "configs/tiny-rotary.json", "why": "no-edit test"})
    man["workloads"].append({
        "name": "tiny-rotary-chat", "config": "tiny-rotary",
        "traffic": "tiny-chat", "chips": 1, "why": "no-edit test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                         "sched.tokens_per_iter", "engine.step_ms_p50"):
            m["workloads"].append("tiny-rotary-chat")
    man["per_layer"].append({
        "name": "sched.joins_per_iter", "unit": "joins/iter",
        "better": "higher", "source": "program_counter",
        "layer": "DecodeScheduler", "moves": "serve_tokens_per_s",
        "workloads": ["tiny-rotary-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _files(root):
    return {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def copy_with_rotary(tmp_path_factory):
    root = tmp_path_factory.mktemp("repo")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):       # the system under test
        os.symlink(os.path.join(ROOT, program), root / program)
    before = _files(root)
    _add_rotary(root)
    return root, before


@pytest.mark.parametrize("trace", [0, 1])
def test_a_new_architecture_enters_as_files_and_rehearses(
        copy_with_rotary, trace):
    root, before = copy_with_rotary
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"),
         "--workload", "tiny-rotary-chat", "--seed", "3000000019",
         "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    by = {l["chipbench"]: l for l in lines[:-1]}
    last = lines[-1]
    assert by["reference"]["ok"], by["reference"]
    assert by["reference"]["tolerance"] == 0.01         # its own
    assert last["correct"] and last["attempted"] > 0 and not last["failed"]
    assert by["window"]["counters"]["serve.decode.tokens"] > 0
    if trace:
        counters = by["traced"]["counters"]
        # the fixture's own counter, and one of the program's that the
        # window line does not list, both reach obs["counters"]
        assert counters["serve.decode.fixture.symbols"] == 0
        assert counters["serve.decode.joins"] > 0
        assert last["metrics"]["sched.joins_per_iter"]["value"] > 0
        assert last["metrics"]["sched.tokens_per_iter"]["value"] > 0
    else:
        assert set(last["metrics"]) == {"serve_tokens_per_s",
                                        "serve_ttft_p90_ms", "setup_s"}
    after = _files(root)
    assert all(after[p] == data for p, data in before.items())
    added = sorted(str(p.relative_to(root)) for p in set(after) - set(before))
    assert added == [
        "chipbench/archs/rotary.py",
        "chipbench/layers/sched.joins_per_iter.json",
        "chipbench/reference/rotary.py",
        "chipbench/tests/rehearsal/configs/tiny-rotary.json"]
