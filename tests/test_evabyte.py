"""EvaByte behind the serving path (ops/eva.py, block="evabyte" of
models/transformer.py, the fed contract of BatchedKVCacheDecoder and
serve/decode.py). What every served block does is
``tests/decode_block_suite.py``'s, over the row ``evabyte`` of
``tests/decode_blocks.py`` (its own schedules walk over the window's
boundaries) against the plain reference chipbench/reference/evabyte.py:
window 32 and chunk 4, so that window boundaries are cheap to cross; phi
and mu drawn at N(0, 1), so that the pooling is no mean. Below that the
block's own: every head's logits, a rider just before a boundary, the
op against the reference's layer, a slot without room, a cursor that
goes back inside the open window, what the scheduler counts of the
state, and the step programs that donate their pools."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.ops.registry import get_op

import decode_blocks as blocks
from decode_blocks import CAPACITY, SLOTS, WINDOW
from decode_block_suite import *  # noqa: F401,F403

from chipbench.reference import evabyte as ref  # noqa: E402

BLOCK = "evabyte"
KW = blocks.config(BLOCK)
W, C = KW["window"], KW["chunk"]
TOL = blocks.TOL[BLOCK]
_W = (WINDOW, [WINDOW] * SLOTS)


def test_every_heads_logits_match_the_reference():
    """``multibyte``: all 8 heads of every fed position against the
    plain reference's full forward, windows that close in mid-dispatch
    and S = 1 steps over a boundary."""
    over = {"multibyte": True, "n_pred_heads": 8}
    params = blocks.params(BLOCK, **over)
    with blocks.tier("xla"):
        drv = blocks.driver(BLOCK, packed=False, slots=3, arg_params=params,
                            **over)
        seqs = blocks.seqs(BLOCK, 120, seed=3, slots=3)
        got, at, _ = blocks.run(drv, seqs, [
            (WINDOW, [9, 13, 16]), (WINDOW, [16, 16, 11])] * 2
            + [(1, [1, 1, 1])] * 16)
    assert got.shape[2:] == (8, KW["vocab_size"]) and at.max() > W
    want = blocks.reference(BLOCK, seqs, params, over, all_heads=True)
    for slot, n in enumerate(at):
        assert np.abs(got[slot, :n] - want[slot, :n]).max() <= TOL, slot


def _slot_state(drv, slot):
    """Every state cell's share of one slot, by name."""
    return {name: np.asarray(cell.asjax())[slot]
            for name, cell in drv.slot_cells()}


def test_a_rider_just_before_the_boundary_decodes_as_if_alone(driver):
    """A slot at position W - 1 that rides a window dispatch with one
    real token and 15 pads closes its window on the real token alone:
    the logits of a slot that took the same steps at S = 1, and the
    state that slot wrote - every row of both rings, every summary
    there is, the cursor. The slot that the windows fed nothing keeps
    its state bit for bit."""
    seqs = blocks.seqs(BLOCK, 96, seed=9)
    seqs[2] = seqs[1]                   # slot 2 decodes slot 1's bytes alone
    idle = [0] * (SLOTS - 3)
    lead = [_W, (WINDOW, [16, 15, 15] + idle)]              # 1, 2 at 31
    blocks.run(driver, seqs, lead)
    waiting = _slot_state(driver, 2)
    rode, _, _ = blocks.run(driver, seqs,
                            lead + [(WINDOW, [16, 1, 0] + idle)] * 3)
    rider, waited = _slot_state(driver, 1), _slot_state(driver, 2)
    alone, _, _ = blocks.run(driver, seqs,
                             lead + [(1, [0, 0, 1] + idle)] * 3)
    np.testing.assert_allclose(rode[1, 33], alone[2, 33], rtol=0, atol=2e-6)
    assert np.abs(rode[1, 33]).max() > 0.1
    stepped = _slot_state(driver, 2)
    assert len(rider) == 5 * KW["n_layer"]
    for name, cell in rider.items():
        assert np.array_equal(waited[name], waiting[name]), name
        if name.endswith("cache_pos"):
            assert cell.tolist() == stepped[name].tolist() == [34], name
            continue
        # a summary past the cursor is whatever an earlier request left
        rows = 34 // C if "summary" in name else W
        assert np.abs(cell[:, :rows]).max() > 0.1, name
        np.testing.assert_allclose(cell[:, :rows], stepped[name][:, :rows],
                                   rtol=0, atol=5e-6, err_msg=name)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("S", [1, 6, 16])
def test_the_op_matches_the_references_layer(variant, S):
    """The op alone, both lowerings, against the reference's attention:
    ragged ``fed`` (0 included), garbage in the pads, boundaries inside
    dispatches."""
    B, H, d, T = 3, 2, 16, 90
    rng = np.random.default_rng(S)
    q, k, v = (rng.standard_normal((B, H, T, d)).astype("f")
               for _ in range(3))
    phi, mu = (rng.standard_normal((H, d)).astype("f") for _ in range(2))
    want = np.asarray(ref.eva_attention(
        ref.rope(jnp.asarray(q), 1e4), ref.rope(jnp.asarray(k), 1e4),
        jnp.asarray(v), phi, mu, 16, 4))
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs(
        {"capacity": 112, "window": 16, "chunk": 4, "rope_base": 1e4})
    fn = opdef.variant_fn(variant)
    run = jax.jit(lambda ins, aux: fn(attrs, ins, aux, False, None))
    aux = [jnp.zeros((B, H, 16, d))] * 2 + [jnp.zeros((B, H, 28, d))] * 2 \
        + [jnp.zeros((B, 1), jnp.int32)]
    got = np.zeros_like(want)
    at = np.zeros(B, int)
    step = 0
    while at.min() < T:
        fed = np.array([min(S, T - at[b], 1 + (step + b) % S)
                        for b in range(B)])
        if step % 3 == 1:
            fed[0] = 0
        new = [np.full((B, H, S, d), junk, "f") for junk in (7., -9., 5.)]
        for b, n in enumerate(fed):
            for dst, src in zip(new, (q, k, v)):
                dst[b, :, :n] = src[b, :, at[b]:at[b] + n]
        out, aux = run(new + [jnp.asarray(fed, jnp.int32), phi, mu], aux)
        for b, n in enumerate(fed):
            got[b, :, at[b]:at[b] + n] = np.asarray(out[0])[b, :, :n]
        at += fed
        step += 1
        assert list(np.asarray(aux[4])[:, 0]) == list(at)
    assert np.abs(got - want).max() <= 5e-6


#: one window dispatch of 32 rows a slot over a window of 256 and
#: chunks of 16 (two ring blocks of 128 in ``eva_write``, four blocks of
#: 16 summaries in ``eva_summarise``): each slot's cursor - inside the
#: second window, on a window's last row (a rider there closes a chunk
#: AND a window), on a chunk's last row, at 0, past a ring block's edge,
#: on the third window's last row - and by case the rows it is fed
_CURSORS = [300, 255, 47, 0, 390, 767]
_FED = {
    "whole_windows": [32, 32, 32, 32, 32, 32],
    "one_riding": [32, 1, 32, 32, 32, 32],
    "none_fed": [0, 0, 0, 0, 0, 0],
    "ragged_chunks": [7, 32, 17, 5, 2, 30],
    "all_riding": [1, 1, 1, 1, 1, 1],
    "riders_beside_a_chunk_and_dead_slots": [0, 1, 1, 32, 0, 1],
    "dead_slots_around_live_ones": [0, 0, 9, 1, 0, 0],
}


@pytest.fixture(scope="module")
def before_the_window():
    """The op's inputs and the state of six slots at ``_CURSORS``,
    written by the XLA composition, with the reference's attention of
    every position."""
    B, H, d, S, T = len(_CURSORS), 2, 16, 32, 800
    rng = np.random.default_rng(62)
    q, k, v = (rng.standard_normal((B, H, T, d)).astype("f")
               for _ in range(3))
    phi, mu = (rng.standard_normal((H, d)).astype("f") for _ in range(2))
    want = np.asarray(ref.eva_attention(
        ref.rope(jnp.asarray(q), 1e4), ref.rope(jnp.asarray(k), 1e4),
        jnp.asarray(v), phi, mu, 256, 16))
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs(
        {"capacity": 1024, "window": 256, "chunk": 16, "rope_base": 1e4})

    def window(at, fed):
        new = [np.full((B, H, S, d), junk, "f") for junk in (7., -9., 5.)]
        for b, n in enumerate(fed):
            for dst, src in zip(new, (q, k, v)):
                dst[b, :, :n] = src[b, :, at[b]:at[b] + n]
        return new + [jnp.asarray(fed, jnp.int32), phi, mu]

    def program(fn):
        return jax.jit(lambda ins, aux: fn(attrs, ins, aux, False, None))

    programs = {variant: program(opdef.variant_fn(variant))
                for variant in ("xla", "pallas")}
    aux = [jnp.zeros((B, H, 256, d))] * 2 + [jnp.zeros((B, H, 64, d))] * 2 \
        + [jnp.zeros((B, 1), jnp.int32)]
    at = np.zeros(B, int)
    while (at < _CURSORS).any():
        fed = np.minimum(S, np.asarray(_CURSORS) - at)
        _, aux = programs["xla"](window(at, fed), aux)
        at += fed
    return window, programs, [np.asarray(a) for a in aux], want


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(_FED))
def test_a_window_serves_each_slot_by_what_it_is_fed(
        before_the_window, variant, case):
    """Which form a slot takes is read from ``fed``: a window whose
    slots are fed a whole window, one row (riding), nothing, a ragged
    few. Both lowerings: every fed position against the reference's
    layer, every pool and cursor the XLA composition's, and the four
    pools of a slot fed nothing bit for bit what they were."""
    window, programs, aux, want = before_the_window
    fed = _FED[case]
    ins = window(_CURSORS, fed)
    (out,), new = programs[variant](ins, aux)
    _, plain = programs["xla"](ins, aux)
    for b, (at, n) in enumerate(zip(_CURSORS, fed)):
        assert np.abs(np.asarray(out)[b, :, :n]
                      - want[b, :, at:at + n]).max(initial=0) <= 5e-6, b
        for old, cell in zip(aux[:4], new[:4]):
            assert n or np.array_equal(old[b], np.asarray(cell)[b]), b
    assert np.asarray(new[4])[:, 0].tolist() \
        == [at + n for at, n in zip(_CURSORS, fed)]
    for cell, same in zip(new[:2], plain[:2]):          # the rings: copies
        assert np.array_equal(np.asarray(cell), np.asarray(same))
    for cell, same in zip(new[2:4], plain[2:4]):        # the summaries
        np.testing.assert_allclose(np.asarray(cell), np.asarray(same),
                                   rtol=0, atol=2e-6)
    assert np.isfinite(np.asarray(out)).all()


def test_a_slot_without_room_is_fed_nothing():
    """cursor + S past the capacity: the op leaves the slot where it
    is, and so does the driver's mirror."""
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs({"capacity": 32, "window": 16, "chunk": 4})
    x = jnp.ones((2, 1, 8, 8))
    aux = [jnp.zeros((2, 1, 16, 8))] * 2 + [jnp.zeros((2, 1, 8, 8))] * 2 \
        + [jnp.asarray([[20], [28]], jnp.int32)]
    _, new_aux = opdef.forward(
        attrs, [x, x, x, jnp.asarray([8, 8], jnp.int32),
                jnp.ones((1, 8)), jnp.ones((1, 8))], aux, False, None)
    assert np.asarray(new_aux[4]).ravel().tolist() == [28, 28]
    with pytest.raises(MXNetError, match="longer than the window"):
        opdef.forward(attrs, [jnp.ones((2, 1, 17, 8))] * 3
                      + [jnp.asarray([1, 1]), jnp.ones((1, 8)),
                         jnp.ones((1, 8))], aux, False, None)




# ------------------------------------------------------ the driver's contract
def test_the_driver_knows_the_window_and_the_cells_by_the_ops(driver):
    assert driver.window == W
    assert [n for n, _reads in driver._reads] == [2]    # two layers alike
    assert len(driver.slot_cells()) == 5 * KW["n_layer"]
    # the K/V decoder's families, by the same declaration
    sym = tfm.get_decode_symbol(vocab_size=16, d_model=16, n_layer=2,
                                n_head=2, capacity=8, per_slot=True)
    state = tfm.slot_state(sym)
    assert sorted(state) == ["cursor", "rows"]
    assert state["rows"] == ["lm_l0_attn_k_cache", "lm_l0_attn_v_cache",
                             "lm_l1_attn_k_cache", "lm_l1_attn_v_cache"]
    assert state["cursor"] == ["lm_l0_attn_cache_pos", "lm_l1_attn_cache_pos"]


def test_a_cursor_goes_back_inside_the_open_window(driver):
    """Behind a closed window there is nothing to go back to; inside
    the open one, and in the window that has just closed (its rows are
    all still there), a slot decodes on as the reference."""
    seqs = blocks.seqs(BLOCK, 80, seed=2)
    idle = [0] * (SLOTS - 3)
    blocks.run(driver, seqs, [_W] * 2 + [(WINDOW, [8, 0, 16] + idle)])
    with pytest.raises(MXNetError, match="cannot move"):
        driver.rewind(0, 31)            # behind the closed window
    with pytest.raises(MXNetError, match="cannot move"):
        driver.rewind_many([0, 2], [36, 20])
    assert list(driver.pos[:3]) == [40, 32, 48]  # a refusal moves nothing
    driver.rewind(0, 33)                # inside the open window
    driver.rewind(1, 30)                # the window that has just closed:
    driver.rewind(2, 0)                 # its rows are all still there
    start = [33, 30, 0] + list(driver.pos[3:])
    got, _, _ = blocks.run(driver, seqs, [(1, [1, 1, 1] + idle)] * 4,
                           start=start)
    want = blocks.reference(BLOCK, seqs)
    for slot, t0 in enumerate([33, 30, 0]):
        assert np.abs(got[slot, t0:t0 + 4]
                      - want[slot, t0:t0 + 4]).max() <= TOL
    with pytest.raises(MXNetError, match="row per position"):
        driver.capture_rows(0, 8)
    blocks.reset(driver)


def test_the_scheduler_counts_chunks_and_windows_by_arithmetic(engine):
    """Four requests of ragged lengths through the scheduler (windows
    closing while others prefill): the counters of what the state was
    asked for."""
    prompts, grew, steps = blocks.counted(BLOCK, engine, (
        "eva.layer_steps", "eva.exact_rows", "eva.summary_rows",
        "eva.chunks_summarised", "eva.windows_closed", "eva.window_slots",
        "eva.ride_slots", "eva.fed_slots", "window.fed_slots",
        "window.riding_slots"))
    layers = KW["n_layer"]
    # positions 0..n+10 of each request are fed (the last token is
    # sampled, not fed): chunks and windows by arithmetic
    fed = [len(p) + 11 for p in prompts]
    assert grew["eva.chunks_summarised"] == layers * sum(n // C for n in fed)
    assert grew["eva.windows_closed"] == layers * sum(n // W for n in fed)
    assert grew["eva.summary_rows"] > 0 and grew["eva.exact_rows"] > 0
    assert steps and all("eva_exact" in r and "eva_summary" in r
                         for r in steps)
    assert any(r["eva_summary"] > 0 and r["window"] == 1 for r in steps)
    assert mx.telemetry.get_metric("serve.decode.eva.layer_steps",
                                   model=engine.name).value > 0
    # which launches the windows' slots took, once a layer: the driver's
    # own count of a window's fed slots and of its riders (every window
    # of this script holds a chunk of more than one row)
    assert grew["eva.fed_slots"] == layers * grew["window.fed_slots"] > 0
    assert grew["eva.ride_slots"] == layers * grew["window.riding_slots"] > 0
    assert grew["eva.window_slots"] \
        == grew["eva.fed_slots"] - grew["eva.ride_slots"] > 0
    # and the benchmark's metric over them, as BENCHMARK.json declares it
    from chipbench import manifest, readers
    cell = manifest.resolve(manifest.load(),
                            "evabyte-6.5b-serve-longdoc-closed")
    metric, = (m for m in cell.per_layer
               if m.name == "eva.ride_share_of_window_slots")
    obs = {"counters": {f"serve.decode.{k}": v for k, v in grew.items()}}
    assert readers.read(metric, obs) == pytest.approx(
        100.0 * grew["eva.ride_slots"] / grew["eva.fed_slots"])
    assert readers.read(metric, {"counters": {}}) is None   # a parent's


def test_the_older_blocks_step_programs_take_no_fed_and_donate_their_pools():
    """GPT-2's decode graph (fed since ISSUE 47), a positional state;
    like this block's, its step program takes over
    every aux array (an array read from a cell before the step is
    deleted by it) and the cell holds the new one."""
    from chipbench import weights
    sym = tfm.get_decode_symbol(
        vocab_size=16, d_model=16, n_layer=1, n_head=2, capacity=8,
        pos_embed="rotary", rope_base=10000.0, per_slot=True)
    mod = blocks.bound(sym, 1, slots=2, arg_params=weights.normal_init(
        sym, {"data": (2, 1), "fed": (2,)}, 3))
    exe = mod._exec_group.executor
    drv = tfm.BatchedKVCacheDecoder(mod, 8, slots=2)
    assert drv.positional and exe.donates_aux
    assert drv.donated_bytes == 2 * (2 * 2 * 8 * 8 * 4) + 2 * 4
    pool = exe.aux_dict["lm_l0_attn_k_cache"]
    held = pool.asjax()
    drv.step(np.zeros((2, 1), np.int32))
    assert held.is_deleted() and not pool.asjax().is_deleted()
    assert np.asarray(pool.asjax())[:, :, 0].any()      # row 0 written
    assert not np.asarray(pool.asjax())[:, :, 1:].any()  # and no other
    eva_mod = blocks.bound(blocks.symbol(BLOCK, 1), 1, slots=1,
                           arg_params=blocks.params(BLOCK))
    ring = eva_mod._exec_group.executor.aux_dict["lm_l0_attn_singles_k"]
    held = ring.asjax()
    tfm.BatchedKVCacheDecoder(eva_mod, CAPACITY, slots=1).step(
        np.zeros((1, 1), np.int32))
    assert held.is_deleted() and not ring.asjax().is_deleted()


def test_eva_ride_check_rehearses(tmp_path):
    """``tools/eva_ride_check.py``, the op's by-hand check on the chip
    at the published sizes, at tiny sizes on the CPU: the window
    program fed a chunk, riders and nothing against the XLA composition
    and against the S = 1 program, and its times by what it is fed."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               MXNET_CRASH_DIR=str(tmp_path / "crash"))
    env.pop("MXNET_KERNEL_TIER", None)
    run = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "eva_ride_check.py"),
         "--rehearse", "--times", "1"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:] + run.stdout[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["riding_form"] and line["device"] == "cpu"
    assert line["mixed"]["fed"] == [32, 1, 1, 0, 10, 1]
    assert line["mixed"]["cursors"] == [330, 256, 129, 0, 402, 768]
    for case in ("mixed", "chunk_and_riders", "all_riding", "all_dead"):
        got = line[case]
        assert got["riders_row_equals_s1_program"], case
        assert got["riders_state_equals_s1_program"], case
        assert got["dead_slots_pools_untouched"], case
        assert got["rings_equal_xla"] and got["cursors_equal_xla"], case
        assert got["out_max_abs_err_vs_xla"] <= 5e-6, case
    assert set(line["window_program_ms_p50"]) >= {"all_dead", "chunk_alone"}
