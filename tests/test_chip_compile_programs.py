"""The chip rehearsals that cost no chip time (guide on-chip-measurement
section 2), kept as tests: whole served programs - a packed window, a
routed layer's share with the grouped kernels, a prefix join - compiled
by the TPU compiler for a described (not attached) ``v5e:2x2`` device at
the published widths; the kernels and ops one by one, and the pins of
every block's lowered text, are ``tests/test_chip_compile.py``'s (a
file of its own so that two workers take them: ROADMAP D22)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.ops.registry import get_op


#: two layers of the Cerebras and the OLMoE configuration at their
#: published widths, as the doc and chat cells serve them (8 slots,
#: windows of 64): the keywords, and the shape of a result that only the
#: row-wise operations of a window compute - the first feed-forward
#: product, the experts' gated rows (8 assignments a row)
_SERVED_WIDTHS = {
    "gpt2": (dict(vocab_size=50257, d_model=2048, n_layer=2, n_head=16,
                  pos_embed="learned", max_seq_len=2048, capacity=2048),
             lambda rows: f"{rows},8192"),
    "olmoe": (dict(block="olmoe", vocab_size=50304, d_model=2048, n_layer=2,
                   n_head=16, pos_embed="rotary", rope_base=1e4,
                   capacity=4096, n_expert=64, top_k=8, expert_width=1024,
                   tie_head=False, embed_scale=False),
              lambda rows: f"{rows * 8},1024"),
}


def _compiled_window(symbol, B, S, v5e):
    """The text that the window program of ``symbol`` at ``(B, S)``
    compiles to for the described chip, bfloat16, its state donated."""
    from mxnet_tpu.executor import _build_graph_runner
    runner, arg_names, aux_names, _ = _build_graph_runner(
        symbol, compute_dtype="bfloat16")
    given = {nm: (B, S) for nm in ("data", "pos_ids") if nm in arg_names}
    arg_shapes, _, aux_shapes = symbol.infer_shape(fed=(B,), **given)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=v5e)

    args = {nm: sds(s, jnp.int32 if nm in ("data", "fed") else jnp.bfloat16)
            for nm, s in zip(arg_names, arg_shapes)}
    aux = {nm: sds(s, jnp.int32 if len(s) < 4 else jnp.bfloat16)
           for nm, s in zip(aux_names, aux_shapes)}

    def prog(arg_vals, aux_vals):
        outs, new_aux = runner(arg_vals, aux_vals, False, None)
        return outs, {**aux_vals, **new_aux}

    return jax.jit(prog, donate_argnums=(1,)).lower(args, aux).compile() \
        .as_text()


#: the scopes of a fed graph's ``pack_rows`` / ``unpack_rows`` nodes
#: (``models/transformer.py``: the tokens, the learned positions, a
#: layer's split into heads and its merge), as an operation's
#: ``op_name`` or location carries them in front of what they lower to
_ROW_COPY_LOOP = r"(_rows|_split|_pack|_unfold)/while\b"


@pytest.mark.parametrize("block", sorted(_SERVED_WIDTHS))
def test_a_window_of_one_chunk_a_slot_compiles_for_v5e_without_a_copy_loop(
        block, v5e, monkeypatch):
    """ISSUE 65: the packed window program of the Cerebras and the OLMoE
    block at 8 x 64 / 256 rows, compiled for the chip: a slot's 64 rows
    are one chunk, so no ``while`` of it is a copy of rows (what is left
    are the compiler's own around the kernels' grids and the embedding
    reads) where the parent held one a site - the tokens, the
    positions, a layer's split into heads and its merge: 6 and 9 in
    these two layers, 50 in the doc cell's 24 - and the sites are
    counted as static, every one."""
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    kw, _row_wise = _SERVED_WIDTHS[block]
    B, S = 8, 64
    packed, R = tfm.packed_window(
        tfm.get_decode_symbol(step_len=S, per_slot=True, **kw), B)
    assert R == 256
    sites = {"gpt2": 2 * 2 + 2, "olmoe": 4 * 2 + 1}[block]
    assert tfm.copy_sites(packed, S) == (sites, sites)
    try:
        text = _compiled_window(packed, B, S, v5e)
    finally:
        kernel_tier.clear()
    for kernel in ("decode_attn", "cache_write"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), kernel
    assert not [line for line in text.splitlines()
                if " while(" in line and re.search(_ROW_COPY_LOOP, line)]
    # the copies are there, under the sites' names: a gather of rows
    # where the graph packs, a slice a slot where it unpacks
    assert re.search(r' gather\(.*op_name="[^"]*(_rows|_pack)/', text)
    assert re.search(r' dynamic-slice\(.*op_name="[^"]*_split/', text)


def test_a_window_of_several_chunks_a_slot_keeps_its_copy_loops(monkeypatch):
    """The other branch of the same predicate: Granite's block at its
    tiny widths with 256 rows a slot (two chunks of 128; 8 slots pack
    to 384) lowers every copy site of its packed window to the loop
    whose trips follow ``fed``, one ``while`` a site beside the mixers'
    own, and counts none of them static."""
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    B, S = 8, 256
    try:
        packed, R = tfm.packed_window(
            cases.symbol("granite_hybrid", S, capacity=2 * S), B)
        text = cases.lowered_text(packed, B, S, debug_info=True)
    finally:
        kernel_tier.clear()
    assert R == 384
    sites, static = tfm.copy_sites(packed, S)
    assert (sites, static) == (5, 0)    # the tokens, q, k, v, the merge
    loops = re.findall(r'loc\("([^"]*/while)"', text)
    assert len(loops) == text.count("stablehlo.while") == sites + 2
    assert len([nm for nm in loops if re.search(_ROW_COPY_LOOP, nm)]) \
        == sites
    assert not re.search(r'loc\("[^"]*(_rows|_split|_pack)/gather', text)


@pytest.mark.parametrize("block", sorted(_SERVED_WIDTHS))
def test_fused_blocks_pack_a_window_of_8x64_to_the_ridge_on_v5e(
        block, v5e, monkeypatch):
    """ISSUE 47: the slot-pooled GPT-2 and OLMoE graphs take ``fed``, so
    their window of 8 x 64 has a packed form, over the 256 rows a
    weight-bound matmul carries for free. It compiles for the chip with
    the block's kernels, its row-wise operations run over 256 rows and
    none over the whole window's 512, and the whole-window form of the
    same graph runs them over 512."""
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    kw, row_wise = _SERVED_WIDTHS[block]
    B, S = 8, 64
    whole = tfm.get_decode_symbol(step_len=S, per_slot=True, **kw)
    packed, R = tfm.packed_window(whole, B)
    assert R == tfm.ridge_rows() == 256
    assert tfm.packed_window(whole, 4) is None      # 4 x 64: free as it is
    texts = {}
    try:
        for rows, symbol in ((R, packed), (B * S, whole)):
            texts[rows] = _compiled_window(symbol, B, S, v5e)
    finally:
        kernel_tier.clear()
    for text in texts.values():
        for kernel in ("decode_attn", "cache_write") + (
                ("moe_gmm_gate_up", "moe_gmm_down") if block == "olmoe"
                else ()):
            assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), \
                kernel
    def computes(rows, text):
        return re.search(rf"= bf16\[{row_wise(rows)}\]\S* "
                         r"(fusion|convolution|custom-call)\(", text)

    assert computes(R, texts[R]) and not computes(B * S, texts[R])
    assert computes(B * S, texts[B * S])


@pytest.mark.parametrize("rows", [8, 1152, 8192],
                         ids=["decode", "packed", "whole"])
@pytest.mark.parametrize("op", ["mhc_pre", "mhc_post"])
def test_hyper_connection_kernels_compile_for_v5e(op, rows, v5e):
    """``ops/mhc.py``'s two kernels at Xing4.0's published sizes (four
    copies of 3,584, bfloat16) over the rows of the top rung's three
    programs: the S = 1 step's 8 (the mapping down the sublanes), the
    packed window's 1,152 and the whole window's 8,192 (whole tiles,
    along the lanes). One Mosaic kernel each, and nothing beside it that
    passes over the stream: no fusion reads or writes ``(rows,
    14336)``."""
    import re
    opdef = get_op(op)
    attrs = opdef.normalize_attrs({"n": 4})
    n, C = 4, 3584

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((1, rows, n * C)), sds((24, n * C)), sds((24,)), sds((3,))] \
        if op == "mhc_pre" else \
        [sds((1, rows, n * C)), sds((rows, C)), sds((rows, 4), jnp.float32),
         sds((rows, 16), jnp.float32)]
    assert opdef.variant_eligible("pallas", attrs, [i.shape for i in ins],
                                  [i.dtype for i in ins])
    fn = opdef.variant_fn("pallas")
    text = jax.jit(lambda r: fn(attrs, list(r), [], False, None)[0]) \
        .lower(ins).compile().as_text()
    assert len(re.findall(rf"{op}[.\w]* = .*tpu_custom_call", text)) == 1
    assert not re.findall(rf"bf16\[(1,)?{rows},{n * C}\]\S* fusion\(", text)


@pytest.mark.parametrize("op", ["pack", "unpack"])
def test_packing_compiles_for_v5e_to_block_copies_in_place(op, v5e):
    """``ops/rows.py`` at GLM-5.2's widest operand (8 slots of 1,024
    rows of 64 x 256 queries, a budget of 1,152): packing and unpacking
    are one loop each of ``dynamic-slice`` / ``dynamic-update-slice``
    over one buffer - no gather, no scatter, and the 268 MB block of all the
    slots' rows is neither copied nor laid out anew."""
    import re
    from mxnet_tpu.ops import rows
    B, S, R, n = 8, 1024, 1152, 16384

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if op == "pack":
        # a computed operand, as the attention's result is
        fn = lambda x, fed, w: jnp.dot(             # noqa: E731
            rows.pack(x * 2, fed, R)[0][0], w)
        args = (sds((B, S, n)), sds((B,), jnp.int32), sds((n, 128)))
    else:
        fn = lambda x, fed, w: rows.unpack(         # noqa: E731
            jnp.dot(x, w), fed, S, R, (n,))
        args = (sds((R, 128)), sds((B,), jnp.int32), sds((128, n)))
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert " gather(" not in text and " scatter(" not in text
    assert len(re.findall(r" while\(", text)) == 1
    assert "dynamic-update-slice(" in text and "dynamic-slice(" in text
    assert not re.findall(r"= bf16\[[\d,]+\]\S* copy\(", text)
    # nothing beside the operand (pack: 268 MB) or the result (unpack)
    block = B * S * n * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < (block if op == "pack" else 0) + (8 << 20)


def test_packed_window_program_compiles_for_v5e_and_copies_no_pool(
        v5e, monkeypatch):
    """One layer of GLM-5.2 at the published widths (an indexer, latent
    attention under its selection, 16 held experts beside a shared one;
    8 slots, windows of 1,024, a capacity of 32,768, bfloat16) in the
    packed form of its window program: it compiles for the chip, its
    dense products run over the budget's 1,152 rows, nothing that packs
    or unpacks is a gather or a scatter, and with the aux arrays donated
    both pools come back in the buffers they came in."""
    import re
    from mxnet_tpu.executor import _build_graph_runner
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    B, S, C, D, V = 8, 1024, 32768, 6144, 2048
    glm = dict(q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
               qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32,
               index_head_dim=128, index_topk=2048, indexer_types=["full"],
               first_k_dense_replace=0, intermediate_size=12288,
               moe_intermediate_size=2048, n_routed_experts=256,
               num_experts_per_tok=8, n_shared_experts=1,
               routed_scaling_factor=2.5, norm_topk_prob=True, held=(0, 16))
    whole = tfm.get_decode_symbol(
        vocab_size=V, d_model=D, n_layer=1, n_head=64, rope_base=8e6,
        capacity=C, step_len=S, per_slot=True, block="glm_dsa",
        tie_head=False, embed_scale=False, glm=glm)
    symbol, R = tfm.packed_window(whole, B)
    assert R == 1152
    try:
        runner, arg_names, aux_names, _ = _build_graph_runner(
            symbol, compute_dtype="bfloat16")
        arg_shapes, _, aux_shapes = symbol.infer_shape(data=(B, S),
                                                       fed=(B,))

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=v5e)

        args = {nm: sds(s, jnp.int32 if nm in ("data", "fed")
                        else jnp.bfloat16)
                for nm, s in zip(arg_names, arg_shapes)}
        aux = {nm: sds(s, jnp.int32 if len(s) < 4 else jnp.bfloat16)
               for nm, s in zip(aux_names, aux_shapes)}

        def prog(arg_vals, aux_vals):
            outs, new_aux = runner(arg_vals, aux_vals, False, None)
            return outs, {**aux_vals, **new_aux}

        compiled = jax.jit(prog, donate_argnums=(1,)).lower(args, aux) \
            .compile()
    finally:
        kernel_tier.clear()
    text = compiled.as_text()
    for kernel in ("mla_attn_window", "mla_attn_ride", "dsa_index_scores",
                   "moe_gmm_gate_up"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), kernel
    # the projections' products are R rows tall, none is slots x S
    assert re.search(rf"= bf16\[{R},\d+\]\S* (fusion|convolution)\(", text)
    assert not re.search(rf"= bf16\[{B * S},{D}\]", text)
    # packing and unpacking: the nodes' names are the operations' scopes
    moved = [line for line in text.splitlines()
             if re.search(r'op_name="[^"]*(_pack|_unfold|_split|_rows|'
                          r'logits_bsv)/', line)]
    assert moved and not [line for line in moved
                          if " gather(" in line or " scatter(" in line]
    pools = [s for s in aux_shapes if len(s) == 4]
    assert sorted(p[-1] for p in pools) == [128, 640]
    for p in pools:
        pool = rf"= bf16\[{','.join(map(str, p))}\]\S* "
        assert not re.findall(pool + r"(copy|fusion)\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        2 * int(np.prod(p)) for p in pools)


@pytest.mark.parametrize("S", [1, 1024], ids=["decode", "window"])
def test_group_limited_share_compiles_for_v5e_with_the_grouped_kernels(S, v5e):
    """A.X-K1's ``MoEFFN`` at the published sizes (8 slots, rows of
    7,168, 192 experts in 8 groups, 12 held of width 2,048 beside a
    shared expert): rows of 7,168 are inside the grouped kernels' widths,
    so the Pallas lowering is eligible and both ``moe_gmm_*`` kernels
    compile for the chip."""
    import re
    B, D, F, E, held = 8, 7168, 2048, 192, 12
    opdef = get_op("MoEFFN")
    attrs = opdef.normalize_attrs(dict(
        num_experts=E, num_hidden=F, top_k=8, norm_topk=True,
        scoring="sigmoid", scaling=2.5, held_first=0, held_count=held,
        shared_hidden=F, step_len=S, n_group=8, topk_group=4))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((B * S, D)), sds((B,), jnp.int32), sds((E, D)),
           sds((held, D, F)), sds((held, D, F)), sds((held, F, D)),
           sds((D, F)), sds((D, F)), sds((F, D))]
    assert opdef.input_names(attrs) == [
        "data", "fed", "router_weight", "gate_weight", "up_weight",
        "down_weight", "shared_gate_weight", "shared_up_weight",
        "shared_down_weight"]
    aux = [sds((5,), jnp.int32)]
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    compiled = jax.jit(lambda r, a: fn(attrs, r, a, False, None)) \
        .lower(ins, aux).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_gate_up", "moe_gmm_down"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), kernel


@pytest.mark.parametrize("step_len,rows,slots", [
    (1, 32, 32), (256, 384, 1), (256, 8192, 32)],
    ids=["decode", "packed_window", "whole_window"])
def test_softmax_share_compiles_for_v5e_with_the_grouped_kernels(
        step_len, rows, slots, v5e):
    """Granite 4.0-H Small's ``MoEFFN`` at the published sizes (rows of
    4,096, a softmax router over 72 experts, 10 a token, 36 held of
    width 768 beside a shared feed-forward of 1,536; ISSUE 54): the
    softmax branch of the share - ``moe_route`` under ``held_first`` /
    ``held_count`` - is eligible for the Pallas lowering and both
    ``moe_gmm_*`` kernels compile for the chip inside the loop over the
    held rows' segments, over an S = 1 step's 32 rows, a packed
    window's 384 (one count for all of them) and the whole window's
    8,192 that the benchmark's ``check_reference`` runs."""
    import re
    D, F, Fs, E, held = 4096, 768, 1536, 72, 36
    opdef = get_op("MoEFFN")
    attrs = opdef.normalize_attrs(dict(
        num_experts=E, num_hidden=F, top_k=10, norm_topk=True,
        held_first=0, held_count=held, shared_hidden=Fs,
        step_len=step_len))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((rows, D)), sds((slots,), jnp.int32), sds((E, D)),
           sds((held, D, F)), sds((held, D, F)), sds((held, F, D)),
           sds((D, Fs)), sds((D, Fs)), sds((Fs, D))]
    assert opdef.input_names(attrs) == [
        "data", "fed", "router_weight", "gate_weight", "up_weight",
        "down_weight", "shared_gate_weight", "shared_up_weight",
        "shared_down_weight"]
    aux = [sds((5,), jnp.int32)]
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    compiled = jax.jit(lambda r, a: fn(attrs, r, a, False, None)) \
        .lower(ins, aux).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_gate_up", "moe_gmm_down"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), kernel


@pytest.mark.parametrize("step_len,rows,slots", [
    (1, 32, 32), (256, 384, 1), (256, 8192, 32)],
    ids=["decode", "packed_window", "whole_window"])
def test_ungated_share_compiles_for_v5e_and_copies_no_expert(
        step_len, rows, slots, v5e):
    """Nemotron-H's ``MoEFFN(act="relu2")`` at the published sizes (rows
    of 2,688, a sigmoid router with a bias over 128 experts, 6 a token
    times 2.5, 64 held of width 1,856 - 14.5 x 128 lanes - beside a
    shared expert of 3,712; ISSUE 63): the ungated form is eligible for
    the Pallas lowering, ``moe_gmm_up`` and ``moe_gmm_down`` compile for
    the chip inside the loop over the held rows' segments - no
    ``moe_gmm_gate_up`` -, and neither stack of 64 matrices, each with
    the model's width last, is copied or laid out anew in front of a
    kernel (a ``(2,688, 1,856)`` matrix would be: the chip holds it
    transposed)."""
    import re
    D, F, Fs, E, held = 2688, 1856, 3712, 128, 64
    opdef = get_op("MoEFFN")
    attrs = opdef.normalize_attrs(dict(
        num_experts=E, num_hidden=F, top_k=6, norm_topk=True,
        scoring="sigmoid", router_bias=True, scaling=2.5, held_first=0,
        held_count=held, shared_hidden=Fs, step_len=step_len, act="relu2"))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((rows, D)), sds((slots,), jnp.int32), sds((E, D)), sds((E,)),
           sds((held, F, D)), sds((held, F, D)), sds((D, Fs)), sds((Fs, D))]
    assert opdef.input_names(attrs) == [
        "data", "fed", "router_weight", "router_bias", "up_weight",
        "down_weight", "shared_up_weight", "shared_down_weight"]
    aux = [sds((5,), jnp.int32)]
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    # an expert's width off the sublanes is not offered to the kernels
    off = [a.shape for a in ins + aux]
    off[4] = off[5] = (held, 1850, D)
    assert not opdef.variant_eligible("pallas", attrs, off,
                                      [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    compiled = jax.jit(lambda r, a: fn(attrs, r, a, False, None)) \
        .lower(ins, aux).compile()
    text = compiled.as_text()
    for kernel in ("moe_gmm_up", "moe_gmm_down"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", text), kernel
    assert "moe_gmm_gate_up" not in text
    stack = rf"= bf16\[{held},{F},{D}\]\S* "
    assert not re.findall(stack + r"(copy|fusion|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 600 << 20


@pytest.mark.parametrize("form", ["plain", "held"])
def test_two_expert_layers_lower_the_grouped_kernels_once(form, v5e,
                                                          monkeypatch):
    """A graph of two ``MoEFFN`` layers of equal shapes, lowered for the
    chip: ``grouped_expert_ffn`` is a jitted function, so the program
    holds one body of ``moe_gmm_gate_up`` and one of ``moe_gmm_down``
    and calls them from both layers (inside the held experts' loop over
    the segments too) - what a bind pays to turn the kernels into text
    does not grow with the depth."""
    import re
    from mxnet_tpu.executor import _build_graph_runner
    monkeypatch.setenv("MXNET_KERNEL_TIER", "pallas")
    kernel_tier.clear()
    T, D, F, E = 256, 256, 128, 8
    kw = dict(num_experts=E, num_hidden=F, top_k=2)
    if form == "held":
        kw.update(scoring="sigmoid", norm_topk=True, held_first=2,
                  held_count=4)
    x = mx.sym.var("data")
    for layer in range(2):
        x = mx.sym.MoEFFN(x, name=f"moe{layer}", **kw)
    try:
        runner, arg_names, aux_names, _ = _build_graph_runner(
            x, compute_dtype="bfloat16")
        arg_shapes, _, aux_shapes = x.infer_shape(data=(T, D))
        args = {nm: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=v5e)
                for nm, s in zip(arg_names, arg_shapes)}
        aux = {nm: jax.ShapeDtypeStruct(s, jnp.int32, sharding=v5e)
               for nm, s in zip(aux_names, aux_shapes)}
        lowered = jax.jit(lambda a, st: runner(a, st, False, None)) \
            .lower(args, aux)
    finally:
        kernel_tier.clear()
    text = lowered.as_text()
    assert text.count("@tpu_custom_call") == 2
    for kernel in ("moe_gmm_gate_up", "moe_gmm_down"):
        assert len(re.findall(kernel, text)) == 1, kernel
    assert len(re.findall(r"call @_grouped_expert_ffn", text)) == 2
    # and the chip's compiler takes both calls of the one body
    compiled = lowered.compile().as_text()
    for kernel in ("moe_gmm_gate_up", "moe_gmm_down"):
        assert re.search(rf"{kernel}[.\w]* = .*tpu_custom_call", compiled)


def test_a_prefix_join_compiles_for_v5e_and_copies_no_pool(v5e):
    """``BatchedKVCacheDecoder``'s row programs at A.X-K1's sizes (five
    latent pools of 8 x 32,768 rows of 640 lanes, 1,024 rows a launch):
    ``restore_rows`` takes the pools over and hands every one back in
    its buffer - a dynamic-update-slice in place, no copy of 335 MB -
    and ``capture_rows`` reads 1,024 rows of one slot, not a pool."""
    import re
    from mxnet_tpu.models.transformer import row_blocks, row_programs
    B, C, W, L, block = 8, 32768, 640, 5, 1024

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    assert list(row_blocks(16384 + 5, block, C)) == [
        (i * block, 0, block) for i in range(16)] + [(16384, 0, 5)]
    assert list(row_blocks(C, block, C))[-1] == (C - block, 0, block)
    capture, restore = row_programs(B, block, [v5e] * L)
    pools = tuple(sds((B, 1, C, W)) for _ in range(L))
    rows = tuple(sds((1, block, W)) for _ in range(L))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e)
    compiled = restore.lower(pools, rows, scalar, scalar).compile()
    text = compiled.as_text()
    pool = rf"= bf16\[{B},1,{C},{W}\]\S* "
    assert not re.findall(pool + r"copy\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= L * B * C * W * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    compiled = capture.lower(pools, scalar, scalar).compile()
    assert not re.findall(pool + r"copy\(", compiled.as_text())
    assert L * block * W * 2 \
        <= compiled.memory_analysis().output_size_in_bytes \
        < L * block * W * 2 + 4096
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
