"""``serve_runner.check_reference`` under a scripted driver: which calls
it makes (the parent's, then two windows inside the budget with ``fed``
given and a second reference call), the decode step an architecture
states (``decode_step_len``, ``mask_token``), what it refuses by name,
and three controls that must come out ``ok: false``. The driver is a toy
decoder in numpy whose rows can be worked out by hand; the reference is
the same arithmetic in ``jax.numpy`` over the whole sequence. The last
test is slow (two CPU rehearsals of the rotary fixture at a decode step
of four tokens). By hand: ``python -m pytest
chipbench/tests/test_check_reference.py``."""
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, serve_runner  # noqa: E402
from chipbench.tests import test_archs  # noqa: E402

V, D, RUNG, S, CAPACITY, SEED = 32, 8, 4, 16, 256, 2**31 + 59
CFG = {"vocab_size": V}


def _tables():
    rng = np.random.default_rng(7)
    return {"embed": rng.normal(size=(V, D)).astype(np.float32),
            "head": rng.normal(size=(D, V)).astype(np.float32)}


def _visible(i, j, block):
    """Position ``i`` attends ``j``: every earlier block and its own
    block whole (``block`` 1 is the causal rule)."""
    return j // block <= i // block


def reference_logits(params, tokens, cfg, block=1):
    """Row ``i`` = (sum over visible ``j`` of embed[token j] /
    (1 + |i - j|)) @ head: a full forward, no cache."""
    import jax.numpy as jnp
    T = tokens.shape[1]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    w = jnp.where(_visible(i, j, block), 1.0 / (1.0 + jnp.abs(i - j)), 0.0)
    x = jnp.einsum("ij,bjd->bid", w, params["embed"][tokens])
    return x @ params["head"]


class _Out:
    def __init__(self, a):
        self._a = a

    def asnumpy(self):
        return self._a


class Driver:
    """``BatchedKVCacheDecoder`` as ``check_reference`` uses it: a slot
    is the ids written so far and a cursor; ``step`` writes each slot's
    fed ids at its cursor and returns the fed rows' logits - the whole
    window without ``fed``, each slot's last fed row alone with it (the
    packed form). Every call is recorded."""

    def __init__(self, window_lens=(S,), block=1, positional=True,
                 takes_back=True, reads_fed=True, packed=True):
        self.window_lens = list(window_lens)
        self.block, self.positional = block, positional
        self.takes_back, self.reads_fed, self.packed = \
            takes_back, reads_fed, packed
        self.slots = RUNG
        self.active = np.zeros(RUNG, bool)
        self.pos = np.zeros(RUNG, np.int64)
        self.ids = np.zeros((RUNG, CAPACITY), np.int64)
        self.tables = _tables()
        self.last_program_rows = None
        self.calls = []

    def join(self, slot):
        self.calls.append(("join", slot))
        self.pos[slot], self.active[slot] = 0, True

    def leave(self, slot):
        self.calls.append(("leave", slot))
        self.active[slot] = False

    def rewind_many(self, slots, positions):
        self.calls.append(("rewind_many", list(slots), list(positions)))
        for slot, pos in zip(slots, positions):
            if self.takes_back or pos == 0:
                self.pos[slot] = pos

    def release_outputs(self):
        pass

    def _row(self, slot, i, written):
        j = np.arange(written)
        w = np.where(_visible(i, j, self.block), 1.0 / (1.0 + np.abs(i - j)),
                     0.0).astype(np.float32)
        return (w @ self.tables["embed"][self.ids[slot, :written]]) \
            @ self.tables["head"]

    def step(self, tokens, fed=None):
        tokens = np.asarray(tokens)
        width = tokens.shape[1]
        self.calls.append(("step", tokens.copy(),
                           None if fed is None else list(map(int, fed))))
        given = fed is not None
        fed = np.full(RUNG, width) if fed is None else np.asarray(fed)
        if not self.reads_fed:          # a rider advances like a chunk
            fed = np.where(fed > 0, width, 0)
        packed = given and self.packed
        self.last_program_rows = width + RUNG if packed else RUNG * width
        out = np.zeros((RUNG, 1 if packed else width, V), np.float32)
        for slot in range(RUNG):
            n, at = int(fed[slot]), int(self.pos[slot])
            self.ids[slot, at:at + n] = tokens[slot, :n]
            for r in range(n):
                if packed and r != n - 1:
                    continue
                out[slot, 0 if packed else r] = self._row(slot, at + r,
                                                          at + n)
            self.pos[slot] += n
        return _Out(out)


def _engine(drv):
    return types.SimpleNamespace(
        ladder=types.SimpleNamespace(max=RUNG), capacity=CAPACITY,
        driver=lambda rung: drv)


def _arch(block=1, **optional):
    shapes = []

    def logits(params, tokens, cfg):
        shapes.append(tokens.shape)         # once a shape: it is jitted
        return reference_logits(params, tokens, cfg, block=block)
    return types.SimpleNamespace(
        reference_logits=logits, LOGIT_TOL=1e-4, shapes=shapes,
        **{name: (lambda cfg, v=value: v)
           for name, value in optional.items()})


def _check(drv, arch):
    return serve_runner.check_reference(_engine(drv), _tables(), CFG, SEED,
                                        arch)


def _steps(drv):
    return [c for c in drv.calls if c[0] == "step"]


# ------------------------------------------------------ the calls it makes
def test_without_the_optional_names_the_parents_calls_then_two_fed_windows():
    drv, arch = Driver(), _arch()
    ok, report = _check(drv, arch)
    assert ok, report
    P = 4 * S + 16
    kinds = [c[0] for c in drv.calls]
    assert kinds == (["rewind_many", "join", "join"] + ["step"] * (4 + 16 + 2)
                     + ["leave", "leave", "rewind_many"])
    steps = _steps(drv)
    # the parent's: four whole windows and sixteen S=1 steps, no fed
    assert [(c[1].shape, c[2]) for c in steps[:20]] == \
        [((RUNG, S), None)] * 4 + [((RUNG, 1), None)] * 16
    # its tokens are the parent's draw: the generator's first call
    rng = np.random.default_rng([SEED % (1 << 32), 11])
    parent = rng.integers(0, V, (2, P)).astype(np.int32)
    fed_ids = np.concatenate([c[1][:2] for c in steps[:20]], axis=1)
    assert np.array_equal(fed_ids, parent)
    assert not np.concatenate([c[1][2:] for c in steps], axis=1).any()
    # then a chunk and a rider in one dispatch, and the roles swapped
    assert [(c[1].shape, c[2]) for c in steps[20:]] == [
        ((RUNG, S), [S, 1, 0, 0]), ((RUNG, S), [1, S, 0, 0])]
    assert not steps[20][1][1, 1:].any() and not steps[21][1][0, 1:].any()
    assert drv.pos[:2].tolist() == [0, 0] and not drv.active.any()
    # two reference calls: the parent's, then the first sequence alone
    # at the second length
    assert arch.shapes == [(2, P), (1, P + S + 1)]
    assert report["tokens"] == P and report["positions_compared"] == 32
    assert report["decode_step_len"] == 1 and report["masked_feeds"] == 0
    assert "masked_max_err_over_bound" not in report
    assert "tokens_before_fed_windows" not in report
    assert report["fed_windows"]["program_rows"] == [S + RUNG] * 2
    assert report["fed_windows"]["packed"] == [True, True]
    assert report["fed_windows"]["rows_compared"] == 2
    assert report["fed_windows"]["max_err_over_bound"] < 0.1
    assert report["max_err_over_bound"] < 0.1


def test_a_rung_without_a_packed_form_is_read_at_row_fed_less_one():
    drv = Driver(packed=False)
    ok, report = _check(drv, _arch())
    assert ok, report
    assert report["fed_windows"]["packed"] == [False, False]
    assert report["fed_windows"]["program_rows"] == [RUNG * S] * 2


def test_a_decode_step_of_four_is_four_calls_of_rung_by_four():
    drv = Driver(window_lens=(4, S), block=4)
    arch = _arch(block=4, decode_step_len=4)
    ok, report = _check(drv, arch)
    assert ok, report
    steps = _steps(drv)
    assert [(c[1].shape, c[2]) for c in steps[4:8]] == \
        [((RUNG, 4), None)] * 4
    assert [c[2] for c in steps[8:]] == [[S, 4, 0, 0], [4, S, 0, 0]]
    assert report["decode_step_len"] == 4 and report["masked_feeds"] == 0
    assert report["positions_compared"] == 32
    assert arch.shapes == [(2, 4 * S + 16), (1, 4 * S + 16 + S + 4)]


def test_with_a_mask_token_each_step_is_masked_feed_rewind_clean_feed():
    mask = V - 1
    drv = Driver(window_lens=(4, S), block=4)
    ok, report = _check(drv, _arch(block=4, decode_step_len=4,
                                   mask_token=mask))
    assert ok, report
    assert report["masked_feeds"] == 4
    assert report["masked_max_err_over_bound"] < 0.1
    decode = drv.calls[3 + 4:3 + 4 + 12]
    assert [c[0] for c in decode] == ["step", "rewind_many", "step"] * 4
    for j in range(4):
        masked, rewind, clean = decode[3 * j:3 * j + 3]
        at = 4 * S + 4 * j
        assert rewind[1:] == ([0, 1], [at, at])     # where they stood
        hidden = masked[1][:2] != clean[1][:2]
        assert hidden.any() and (masked[1][:2][hidden] == mask).all()
        assert np.array_equal(hidden[0], hidden[1])
        assert masked[1].shape == clean[1].shape == (RUNG, 4)


@pytest.mark.parametrize("driver,optional,match", [
    (dict(window_lens=(4, S)), dict(decode_step_len=3),
     "decode_step_len 3: the engine has programs of"),
    (dict(window_lens=(6, S)), dict(decode_step_len=6),
     "decode_step_len 6 does not divide"),
    (dict(window_lens=(12, 24)), dict(decode_step_len=12),   # 24, not 16
     "decode_step_len 12 does not divide"),
    (dict(window_lens=(4, S), positional=False),
     dict(decode_step_len=4, mask_token=5), "not positional")])
def test_what_the_engine_cannot_be_held_to_is_refused_by_name(
        driver, optional, match):
    drv = Driver(**driver)
    with pytest.raises(SystemExit, match=match):
        _check(drv, _arch(**optional))
    assert not drv.calls            # before anything is fed


def test_an_engine_without_room_is_refused_by_name():
    engine = _engine(Driver())
    engine.capacity = 2 * S + 16
    with pytest.raises(SystemExit, match="no room"):
        serve_runner.check_reference(engine, _tables(), CFG, SEED, _arch())


def test_room_for_the_fed_windows_is_made_and_said():
    engine = _engine(Driver())
    engine.capacity = 4 * S + 16 + 2        # the parent's four windows fit
    ok, report = serve_runner.check_reference(engine, _tables(), CFG, SEED,
                                              _arch())
    assert ok, report
    assert report["tokens"] == 3 * S + 16
    assert report["tokens_before_fed_windows"] == 4 * S + 16


# ----------------------------------------- three controls: ``ok`` is false
@pytest.mark.parametrize("driver,arch,reading", [
    # a masked feed that is not taken back: the clean feed lands L late
    (dict(window_lens=(4, S), block=4, takes_back=False),
     dict(block=4, decode_step_len=4, mask_token=V - 1),
     "max_err_over_bound"),
    # fed windows that ignore ``fed``: the rider advances by S
    (dict(reads_fed=False), dict(), "fed_windows"),
    # a causal reference where the driver's rows are block-wise
    (dict(window_lens=(4, S), block=4), dict(block=1, decode_step_len=4),
     "max_err_over_bound")])
def test_the_three_controls_are_not_ok(driver, arch, reading, capsys):
    from chipbench import common
    ok, report = _check(Driver(**driver), _arch(**arch))
    common.say("reference", ok=ok, **report)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["ok"] is False
    found = line[reading]
    if reading == "fed_windows":
        found = found["max_err_over_bound"]
        assert line["max_err_over_bound"] < 0.1      # the parent's part passes
    assert found >= 2, line


# -------------------------------- the parent program at a step of four
@pytest.fixture(scope="module")
def copy_with_rotary4(tmp_path_factory):
    """``test_archs``' copy with the rotary fixture, and beside it the
    same architecture stating ``decode_step_len`` 4 over a
    ``prefill_chunk`` of 4: the causal decoder's own window program is
    the decode step."""
    root = tmp_path_factory.mktemp("repo4")
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for program in ("mxnet_tpu", "examples"):
        os.symlink(os.path.join(ROOT, program), root / program)
    test_archs._add_rotary(root)
    bench = root / "chipbench"
    text = (bench / "archs" / "rotary.py").read_text()
    (bench / "archs" / "rotary4.py").write_text(
        text + "\n\ndef decode_step_len(cfg):\n"
        "    return cfg[\"prefill_chunk\"]\n")
    configs = bench / "tests" / "rehearsal" / "configs"
    cfg = json.loads((configs / "tiny-rotary.json").read_text())
    (configs / "tiny-rotary4.json").write_text(json.dumps(dict(
        cfg, name="tiny-rotary4", arch="rotary4", prefill_chunk=4)))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(
        next(c for c in man["configs"] if c["name"] == "tiny-rotary"),
        name="tiny-rotary4",
        file="chipbench/tests/rehearsal/configs/tiny-rotary4.json"))
    man["workloads"].append({
        "name": "tiny-rotary4-chat", "config": "tiny-rotary4",
        "traffic": "tiny-chat", "chips": 1, "why": "decode step of four"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "tiny-rotary-chat" in m.get("workloads", ()):
            m["workloads"].append("tiny-rotary4-chat")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.mark.parametrize("workload,step_len", [
    ("tiny-rotary4-chat", 4), ("tiny-rotary-chat", 1)])
def test_the_parent_program_rehearses_at_a_decode_step_of_four(
        copy_with_rotary4, workload, step_len):
    root = copy_with_rotary4
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), "--rehearse",
         "--manifest", str(root / "BENCHMARK.json"), "--workload", workload,
         "--seed", "3000000059", "--seconds", "2", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    ref = next(l for l in lines if l.get("chipbench") == "reference")
    assert ref["ok"], ref
    assert ref["decode_step_len"] == step_len and ref["masked_feeds"] == 0
    assert ref["fed_windows"]["rows_compared"] == 2
    assert ref["fed_windows"]["max_err_over_bound"] <= 1.0
    last = lines[-1]
    assert last["correct"] and not last["failed"]
    # every number that decided ``correct`` beside its limit: the result
    # line's last key and the last lines on standard error
    assert list(last)[-1] == "compared"
    assert last["compared"]["fed_windows_err_over_bound"] == {
        "value": ref["fed_windows"]["max_err_over_bound"], "limit": 1.0}
    tail = proc.stderr.strip().splitlines()[-len(last["compared"]):]
    assert [t.split()[2].rstrip(":") for t in tail] == list(last["compared"])
    assert manifest.ARCH_OPTIONAL["serve"] == ("decode_step_len",
                                               "mask_token")


# ------------------------- a whole run with the timed path broken underneath
_BROKEN = '''
import sys
sys.path.insert(0, {root!r})
from mxnet_tpu.models import transformer
real = transformer.BatchedKVCacheDecoder.step


def step(self, tokens, fed=None, now=None):
    """The packed window program alone hands back other rows: what a
    serving window launches, and no whole-window or S=1 dispatch."""
    out = real(self, tokens, fed=fed, now=now)
    S = 1 if tokens.ndim == 1 else tokens.shape[1]
    packed = fed is not None and self.last_program_rows < self.slots * S
    return out * -1.0 if packed else out


transformer.BatchedKVCacheDecoder.step = step
from chipbench import run
run.main(sys.argv[1:])
'''


def test_a_run_whose_packed_program_is_broken_is_not_correct(tmp_path):
    """The rest of a run behind the look for a chip (``--rehearse``),
    with the logits of every packed window altered where they are
    produced: requests finish at their lengths, the whole-window prefill
    and the S=1 decode agree with the reference - the parent of PR 59
    printed ``correct: true`` over such a program - and the two fed
    windows say it is not."""
    script = tmp_path / "broken.py"
    script.write_text(_BROKEN.format(root=ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(script), "--rehearse", "--manifest",
         os.path.join(HERE, "rehearsal", "BENCHMARK.json"),
         "--workload", "tiny-chat", "--seed", "3000000061",
         "--seconds", "2", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is False
    assert last["attempted"] > 0 and last["failed"] == 0
    compared = last["compared"]
    assert compared["reference_err_over_bound"]["value"] <= 1.0
    assert compared["fed_windows_err_over_bound"]["value"] >= 2.0
    assert compared["wrong_length"]["value"] == 0
    assert "fed_windows_err_over_bound" in proc.stderr.splitlines()[-5]
