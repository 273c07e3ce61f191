"""Transformer workload subsystem (ISSUE 13, ROADMAP 1).

Pins the tentpole end to end: the decoder-only LM trains through
``Module.fit(spmd=True)`` on a (data x seq) virtual-device mesh with
params matching the single-device unsharded run to float ulps at K=1
and K=4; the ``attention`` OpDef carries three gated lowerings (xla
composition / Pallas flash / sequence-sharded ring) selected by the
kernel tier + plan; and N incremental KV-cache decode steps reproduce
the length-N full-sequence forward (f32 and bf16), export through
``export_model`` as a stateful artifact, and serve through ``serve()``
with zero steady-state compiles. Satellites ride along: ring-attention
fwd/grad parity vs the full attention (the PR-0 dead code resurrected),
cost-table coverage, KV-cache bytes in the memory planner, and the
zero-false-positive lint gates (zoo membership is pinned in
tools/mxlint's corpus; the precision/memplan/SH6xx surfaces here).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.models import transformer as tfm
from mxnet_tpu.parallel import MeshConfig
from mxnet_tpu.parallel import spmd as spmd_mod
from mxnet_tpu.parallel.spmd import SpmdPlan
from mxnet_tpu.parallel.ring_attention import (attention as full_attention,
                                               ring_attention_sharded)
from mxnet_tpu import kernel_tier
from mxnet_tpu.ops.registry import get_op

pytestmark = pytest.mark.skipif(
    len(jax.devices("cpu")) < 8, reason="needs 8 virtual cpu devices")

V, D, L, H, T, B = 64, 32, 2, 4, 8, 4


def _qkv(seed=0, b=2, h=2, t=8, d=4, dtype=np.float32):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(b, h, t, d).astype(dtype))
                 for _ in range(3))


def _seq_plan(data=2, seq=4):
    return SpmdPlan(SpmdPlan.build_mesh_for(
        jax.devices("cpu")[:data * seq], MeshConfig(data=data, seq=seq)))


def _init(mod):
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=2))


# ===================================================== symbol structure
def test_symbol_shapes_and_tying():
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=H,
                         seq_len=T)
    args, outs, auxs = sym.infer_shape(data=(B, T),
                                       softmax_label=(B * T,))
    by_name = dict(zip(sym.list_arguments(), args))
    assert by_name["lm_tok_embed_weight"] == (V, D)
    assert outs == [(B * T, V)]
    assert sym.list_auxiliary_states() == []
    # tied head: exactly ONE embedding-sized weight in the graph
    assert sum(1 for n, s in by_name.items() if s == (V, D)) == 1
    # learned positions add the table
    sym2 = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=1, n_head=H,
                          seq_len=T, pos_embed="learned", max_seq_len=16)
    args2, _, _ = sym2.infer_shape(data=(B, T), softmax_label=(B * T,))
    by2 = dict(zip(sym2.list_arguments(), args2))
    assert by2["lm_pos_embed_weight"] == (16, D)


def test_synthetic_lm_iter_contract():
    it = tfm.SyntheticLMIter(V, B, T, n_batches=3, seed=0)
    assert it.provide_data[0].shape == (B, T)
    assert np.dtype(it.provide_data[0].dtype) == np.int32
    assert it.provide_label[0].shape == (B * T,)
    batches = list(it)
    assert len(batches) == 3
    d = batches[0].data[0].asnumpy()
    l = batches[0].label[0].asnumpy()
    assert d.dtype == np.int32 and d.shape == (B, T)
    # labels are the shifted-by-one stream, flattened row-major
    assert l.shape == (B * T,)
    assert (l.reshape(B, T)[:, :-1] == d[:, 1:]).all()


# ================================================== ring resurrection
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_parity_forward(causal):
    """Satellite: ring == full attention on a seq-axis mesh (the PR-0
    dead code, now gated for real against the attention contract)."""
    from mxnet_tpu.parallel.mesh import build_mesh
    q, k, v = _qkv(0, 2, 2, 8, 4)
    mesh = build_mesh(MeshConfig(seq=4), devices=jax.devices("cpu")[:4])
    got = jax.jit(lambda *a: ring_attention_sharded(
        *a, mesh, causal=causal))(q, k, v)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_parity_grad(causal):
    """Ring gradients == full-attention gradients (the training path
    differentiates through the ppermute ring), traced as one program
    as the training path traces it."""
    from mxnet_tpu.parallel.collectives import shard_map
    from mxnet_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P
    import functools

    q, k, v = _qkv(1, 2, 2, 8, 4)
    mesh = _seq_plan(1, 4).mesh
    spec = P(None, None, "seq", None)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    w = jnp.asarray(np.random.RandomState(2).randn(*q.shape)
                    .astype(np.float32))

    g_ring = jax.jit(jax.grad(lambda *a: jnp.sum(ring(*a) * w),
                              argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(
        lambda *a: jnp.sum(full_attention(*a, causal=causal) * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ============================================ three gated lowerings
def test_attention_has_three_gated_lowerings():
    opdef = get_op("attention")
    assert set(opdef.variants) == {"pallas", "ring"}  # + the xla forward
    shapes, dtypes = [(2, 2, 8, 4)] * 3, ["float32"] * 3
    # no plan: ring ineligible, CPU auto resolves to the composition
    assert not opdef.variant_eligible("ring", {}, shapes, dtypes)
    assert kernel_tier.resolve(opdef, {}, shapes, dtypes, True) == "xla"
    plan = _seq_plan(2, 4)
    with spmd_mod.plan_scope(plan):
        assert opdef.variant_eligible("ring", {}, shapes, dtypes)
        # indivisible T: never eligible
        assert not opdef.variant_eligible("ring", {}, [(2, 2, 6, 4)] * 3,
                                          dtypes)
    assert kernel_tier.resolve(opdef, {}, shapes, dtypes, True,
                               spmd_plan=plan) == "ring"
    assert any(d.get("variant") == "ring" and d.get("source") == "plan"
               for d in kernel_tier.decisions())


def test_attention_ring_numerics_gate():
    """The ring lowering passes the SAME numerics gate the flash kernel
    does, f32 and bf16."""
    opdef = get_op("attention")
    plan = _seq_plan(1, 4)
    for dt, tol in (("float32", None), ("bfloat16", None)):
        with spmd_mod.plan_scope(plan):
            ok, err = kernel_tier.numerics_gate(
                opdef, {"causal": True}, [(2, 2, 8, 4)] * 3, [dt] * 3,
                variant="ring", is_train=True, n_aux=0)
        assert ok, f"ring numerics gate failed at {dt}: {err}"


def test_attention_flash_numerics_gate():
    """The fused (flash) lowering stays gated too — interpret mode off
    TPU, same tolerance table."""
    opdef = get_op("attention")
    for dt in ("float32", "bfloat16"):
        ok, err = kernel_tier.numerics_gate(
            opdef, {"causal": True}, [(1, 2, 8, 4)] * 3, [dt] * 3,
            variant="pallas", is_train=False, n_aux=0)
        assert ok, f"flash numerics gate failed at {dt}: {err}"


def test_kernel_tier_xla_mode_overrides_ring(monkeypatch):
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    opdef = get_op("attention")
    assert kernel_tier.resolve(opdef, {}, [(2, 2, 8, 4)] * 3,
                               ["float32"] * 3, True,
                               spmd_plan=_seq_plan(2, 4)) == "xla"


# ========================================== (data x seq) spmd training
def _fit_lm(spmd, K=1, n_dev=1, mesh=None):
    mx.random.seed(7)
    sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L, n_head=H,
                         seq_len=T)
    it = tfm.SyntheticLMIter(V, B, T, n_batches=4, seed=0)
    mod = mx.mod.Module(sym, context=[mx.cpu(i) for i in range(n_dev)])
    accs = []
    mod.fit(it, num_epoch=2, spmd=spmd, mesh=mesh, steps_per_dispatch=K,
            optimizer="sgd",
            optimizer_params=(("learning_rate", 0.1), ("momentum", 0.9)),
            batch_end_callback=lambda p: accs.append(
                p.eval_metric.get()[1]),
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              magnitude=2))
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}, accs, mod


@pytest.mark.parametrize("K", [1, 4])
def test_spmd_seq_parallel_fit_parity(K):
    """Acceptance: fit(spmd=True) on the (data=2 x seq=2) mesh matches
    the single-device unsharded run — params to float ulps, per-batch
    metric trajectory exactly — at K=1 and under the K=4 scan, with the
    ring lowering actually selected."""
    kernel_tier.clear()
    p0, a0, _ = _fit_lm(False)
    p1, a1, mod = _fit_lm(True, K=K, n_dev=4,
                          mesh=MeshConfig(data=2, seq=2))
    assert mod._fused_armed
    if K > 1:
        assert mod._exec_group._scan_K == K
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    np.testing.assert_allclose(a0, a1, rtol=1e-6)
    assert any(d.get("variant") == "ring"
               for d in kernel_tier.decisions())
    plan = mod._exec_group._spmd_plan
    from jax.sharding import PartitionSpec as P
    assert plan.data_spec_for((B, T)) == P("data", "seq")
    # bound token batch really is (data x seq)-sharded
    sh = mod._exec_group.executor.arg_dict["data"].asjax().sharding
    assert sh.is_equivalent_to(plan.data_sharding_for((B, T)), 2)


def test_spmd_seq_parallel_lint_clean():
    """SH6xx stays quiet on the (data x seq) binding (zero-FP gate)."""
    from mxnet_tpu import analysis
    _, _, mod = _fit_lm(True, n_dev=4, mesh=MeshConfig(data=2, seq=2))
    report = analysis.run_passes(
        analysis.AnalysisContext(symbol=mod._symbol,
                                 executor=mod._exec_group.executor,
                                 exec_group=mod._exec_group, module=mod),
        passes=["sharding_checker"])
    assert len(report) == 0, [str(d) for d in report]


# ===================================================== KV-cache decode
def _trained_pair(compute_dtype=None, pos_embed="rotary", n_layer=L):
    full_sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=n_layer,
                              n_head=H, seq_len=T, include_loss=False,
                              pos_embed=pos_embed, max_seq_len=T)
    full = mx.mod.Module(full_sym, label_names=[],
                         compute_dtype=compute_dtype)
    full.bind([("data", (B, T))], None, for_training=False)
    _init(full)
    args, _ = full.get_params()

    dec_sym = tfm.get_decode_symbol(
        vocab_size=V, d_model=D, n_layer=n_layer, n_head=H, capacity=T,
        pos_embed=pos_embed, max_seq_len=T)
    data_names = ("data", "pos_ids") if pos_embed == "learned" \
        else ("data",)
    shapes = [("data", (B, 1))] + ([("pos_ids", (1,))]
                                   if pos_embed == "learned" else [])
    dec = mx.mod.Module(dec_sym, data_names=data_names, label_names=[],
                        compute_dtype=compute_dtype)
    dec.bind(shapes, None, for_training=False)
    dec.init_params(initializer=None, arg_params=args, aux_params={},
                    allow_missing=True)
    return full, dec, args


@pytest.mark.parametrize("compute_dtype,tol", [
    (None, 2e-6), ("bfloat16", 2e-2)])
def test_incremental_decode_matches_full_forward(compute_dtype, tol):
    """Acceptance: N single-token KV-cache steps == the length-N full
    forward, f32 (tight) and bf16 (kernel-tier tolerance)."""
    full, dec, _ = _trained_pair(compute_dtype)
    tokens = np.random.RandomState(3).randint(0, V, (B, T)).astype(
        np.int32)
    full.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)], label=[]),
                 is_train=False)
    ref = full.get_outputs()[0].asnumpy().astype(np.float32)

    drv = tfm.KVCacheDecoder(dec, capacity=T)
    got = np.concatenate(
        [drv.step(tokens[:, t:t + 1]).asnumpy().astype(np.float32)
         for t in range(T)], axis=1)
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)

    # reset rewinds to a bit-identical step 0
    drv.reset()
    again = drv.step(tokens[:, :1]).asnumpy().astype(np.float32)
    np.testing.assert_array_equal(again[:, 0], got[:, 0])


def test_decode_learned_positions():
    full, dec, _ = _trained_pair(pos_embed="learned", n_layer=1)
    tokens = np.random.RandomState(4).randint(0, V, (B, T)).astype(
        np.int32)
    full.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)], label=[]),
                 is_train=False)
    ref = full.get_outputs()[0].asnumpy()
    drv = tfm.KVCacheDecoder(dec, capacity=T, pos_embed="learned")
    got = np.concatenate([drv.step(tokens[:, t:t + 1]).asnumpy()
                          for t in range(T)], axis=1)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("bound,ok", [(np.float32, False), (np.int32, True)],
                         ids=["float32_refused", "int32_exact"])
def test_one_cursor_decoder_takes_whole_positions_at_bfloat16(bound, ok):
    """A float ``pos_ids`` cell is cast to bfloat16 at graph entry and
    no odd position past 256 survives that: ``KVCacheDecoder`` refuses
    such a binding, and with an int32 cell (what ``step`` feeds) position
    301 reads its own row of the table and not 300's."""
    capacity = 320
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1, n_head=H,
                                capacity=capacity, pos_embed="learned",
                                max_seq_len=capacity)
    dec = mx.mod.Module(sym, data_names=("data", "pos_ids"), label_names=[],
                        compute_dtype="bfloat16")
    dec.bind([mx.io.DataDesc("data", (1, 1), np.int32),
              mx.io.DataDesc("pos_ids", (1,), bound)], None,
             for_training=False)
    shapes, _, _ = sym.infer_shape(data=(1, 1), pos_ids=(1,))
    rs = np.random.RandomState(5)
    dec.init_params(initializer=None, aux_params={}, allow_missing=True,
                    arg_params={
                        nm: mx.nd.array(0.3 * rs.randn(*shape))
                        for nm, shape in zip(sym.list_arguments(), shapes)
                        if nm not in ("data", "pos_ids")})
    if not ok:
        with pytest.raises(mx.base.MXNetError, match="int32 DataDesc"):
            tfm.KVCacheDecoder(dec, capacity=capacity, pos_embed="learned")
        return
    drv = tfm.KVCacheDecoder(dec, capacity=capacity, pos_embed="learned")
    cell = dec._exec_group.executor.arg_dict["pos_ids"]
    rows = {}
    for pos in (300, 301):
        drv.reset()
        drv.pos = pos       # an empty cache: the row depends on the
        rows[pos] = drv.step(np.asarray([[3]])).asnumpy()   # position alone
        assert str(cell.dtype) == "int32" and int(cell.asnumpy()[0]) == pos
    assert np.abs(rows[300].astype(np.float32)
                  - rows[301].astype(np.float32)).max() > 0


def test_decode_cache_overflow_raises():
    _full, dec, _ = _trained_pair(n_layer=1)
    tokens = np.zeros((B, 1), np.int32)
    drv = tfm.KVCacheDecoder(dec, capacity=T)
    for _ in range(T):
        drv.step(tokens)
    with pytest.raises(mx.base.MXNetError, match="overflow"):
        drv.step(tokens)
    # eager op-level check too (concrete cursor at capacity)
    op = get_op("attention_decode")
    q = jnp.zeros((1, 1, 1, 4))
    cache = jnp.zeros((1, 1, 4, 4))
    with pytest.raises(mx.base.MXNetError, match="overflow"):
        op.forward({"capacity": 4}, [q, q, q],
                   [cache, cache, jnp.full((1,), 4, jnp.int32)],
                   False, None)


def test_decode_cache_cursor_binds_int32():
    """The declared aux dtype survives binding (and is therefore exempt
    from the bf16 entry cast — exact positions past 256)."""
    _full, dec, _ = _trained_pair(compute_dtype="bfloat16", n_layer=1)
    exe = dec._exec_group.executor
    cursors = [nm for nm in exe.aux_dict if nm.endswith("cache_pos")]
    assert cursors
    for nm in cursors:
        assert exe.aux_dict[nm].asjax().dtype == jnp.int32


def test_attention_decode_rejects_training():
    op = get_op("attention_decode")
    q = jnp.zeros((1, 1, 1, 4))
    cache = jnp.zeros((1, 1, 4, 4))
    with pytest.raises(mx.base.MXNetError, match="inference"):
        op.forward({"capacity": 4}, [q, q, q],
                   [cache, cache, jnp.zeros((1,), jnp.int32)],
                   True, None)


# ====================================== export + serve the decoder
def test_decode_export_serve_zero_compiles(tmp_path):
    """Acceptance: the exported KV-cache decoder is a stateful artifact
    (Predictor carries the cache), reproduces the module decode, and
    serves through serve() with compile_count() delta == 0 after
    warmup."""
    from mxnet_tpu import predict as predict_mod
    from mxnet_tpu import program_cache as pc

    full, dec, args = _trained_pair(n_layer=1)
    tokens = np.random.RandomState(5).randint(0, V, (B, T)).astype(
        np.int32)
    full.forward(mx.io.DataBatch(data=[mx.nd.array(tokens)], label=[]),
                 is_train=False)
    ref = full.get_outputs()[0].asnumpy()

    path = str(tmp_path / "lm_decode.mxp")
    predict_mod.export_model(
        path, tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=1,
                                    n_head=H, capacity=T),
        args, {}, {"data": (B, 1)}, data_dtypes={"data": np.int32})
    p = predict_mod.Predictor(path)
    assert p.stateful
    got = np.concatenate([p.forward(data=tokens[:, t:t + 1])[0].asnumpy()
                          for t in range(T)], axis=1)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=2e-6)
    p.reset_state()
    np.testing.assert_array_equal(
        p.forward(data=tokens[:, :1])[0].asnumpy()[:, 0], got[:, 0])

    p.reset_state()
    server = mx.serve.serve(p, name="lmdec")
    try:
        mark = pc.compile_count()
        outs = []
        for t in range(T):
            h = server.submit({"data": tokens[:, t:t + 1]},
                              model="lmdec")
            outs.append(np.asarray(h.result(timeout=60)[0].asnumpy()))
        assert pc.compile_count() - mark == 0
        assert server.stats()["compiles_since_warmup"] == 0
    finally:
        server.stop()
    np.testing.assert_allclose(np.concatenate(outs, axis=1), ref,
                               rtol=1e-5, atol=2e-6)


# ================================================= RoPE + cost/memplan
def test_rope_op_semantics():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 2, 4, 8)
                    .astype(np.float32))
    op = get_op("RoPE")
    (y0,), _ = op.forward({"offset": 0, "base": 10000.0}, [x], [], False,
                          None)
    # position 0 rotates by angle 0: first token unchanged
    np.testing.assert_allclose(np.asarray(y0[:, :, 0]),
                               np.asarray(x[:, :, 0]), rtol=1e-6)
    # offset semantics: RoPE(x, offset=k)[t] == RoPE(x', 0)[t+k]
    (y3,), _ = op.forward({"offset": 3, "base": 10000.0},
                          [x[:, :, :1]], [], False, None)
    (yfull,), _ = op.forward({"offset": 0, "base": 10000.0},
                             [jnp.concatenate([x] * 1, 2)], [], False,
                             None)
    big = jnp.concatenate([x, x], axis=2)      # position 3 holds x[:, :, 3]
    (yb,), _ = op.forward({"offset": 0, "base": 10000.0}, [big], [],
                          False, None)
    np.testing.assert_allclose(np.asarray(yb[:, :, 3]),
                               np.asarray(
                                   op.forward({"offset": 3,
                                               "base": 10000.0},
                                              [x[:, :, 3:4]], [], False,
                                              None)[0][0][:, :, 0]),
                               rtol=1e-5, atol=1e-6)
    # norm-preserving (rotation)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y0), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_costs_seeded_and_planner_kv_bytes():
    """Satellite: every new op carries BOTH cost estimators, and the
    memory planner charges the decoder's KV cache under
    attention_decode in the per-op byte table."""
    from mxnet_tpu.ops import cost
    assert cost.partial_cost_ops() == []
    for name in ("RoPE", "attention_decode", "attention"):
        assert get_op(name).has_cost(), name

    from mxnet_tpu.analysis import memplan
    cap = 16
    sym = tfm.get_decode_symbol(vocab_size=V, d_model=D, n_layer=L,
                                n_head=H, capacity=cap)
    plan = memplan.plan_symbol(sym, {"data": (B, 1)}, policy="none",
                               for_training=False)
    # two f32 cache arrays per layer + the int32 cursor
    expect = L * (2 * B * H * cap * (D // H) * 4 + 4)
    assert plan["kv_cache_bytes"] == expect
    assert plan["per_op_bytes"].get("attention_decode") == expect
    # aux accounting covers the cache (itemized into the peak)
    assert plan["aux_bytes"] >= expect

    # training-side plans run at none AND dots (zoo gate)
    train_sym = tfm.get_symbol(vocab_size=V, d_model=D, n_layer=L,
                               n_head=H, seq_len=T)
    shapes = {"data": (B, T), "softmax_label": (B * T,)}
    peaks = {}
    for policy in ("none", "dots"):
        p = memplan.plan_symbol(train_sym, shapes, policy=policy)
        assert p["peak_bytes_per_device"] > 0
        peaks[policy] = p["peak_bytes_per_device"]
    assert peaks["dots"] <= peaks["none"]
    # ME801 trips at a toy capacity
    found = memplan.plan_findings(
        memplan.plan_symbol(train_sym, shapes, policy="none"),
        capacity_bytes=1024)
    assert any(d.rule == "ME801" for d in found)


def test_precision_flow_clean_f32_bf16():
    """Satellite: the transformer binds clean under the precision-flow
    pass at f32 and bf16 (the f32 loss head stays exempt)."""
    from mxnet_tpu import analysis
    for cd in (None, "bfloat16"):
        report = analysis.run_passes(analysis.AnalysisContext(
            symbol=tfm.get_symbol(vocab_size=V, d_model=D, n_layer=1,
                                  n_head=H, seq_len=T),
            known_shapes={"data": (B, T)}, compute_dtype=cd),
            passes=["precision_flow"])
        assert len(report) == 0, [str(d) for d in report]


def test_mxlint_zoo_includes_transformer():
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import mxlint
    names = [t[0] for t in mxlint._check_corpus()]
    assert "models/transformer" in names
    assert "models/transformer_decode" in names
