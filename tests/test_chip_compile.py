"""The chip rehearsals that cost no chip time (guide on-chip-measurement
section 2), kept as tests:

* every kernel site of ``chip_smoke.py``'s *kernels* phase, and every
  decode op, compiled by the TPU compiler for a described (not attached)
  ``v5e:2x2`` device at the smoke models' and the published widths -
  what interpret mode cannot show (tile alignment, unlowerable
  primitives, scoped-VMEM overruns);
* the pins: the text every block's programs lower to and the graphs the
  builder makes, at the tiny sizes of ``tests/decode_blocks.py``.

Whole served programs compiled for the v5e are
``tests/test_chip_compile_programs.py``'s, ``chip_smoke.py`` without a
chip ``tests/test_chip_smoke_bodies.py``'s (files of their own so that
three workers take them: ROADMAP D22).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import kernel_tier
from mxnet_tpu.ops.registry import get_op

import chip_smoke

# ------------------------------------------------ compile for a v5e chip
_SITES = chip_smoke.kernel_sites(chip_smoke.SMOKE_WIDTHS)
#: sites whose backward is a hand-written kernel too
_HAND_BACKWARD = {"softmax_ce", "layernorm", "bias_gelu"}


@pytest.mark.parametrize("site", _SITES, ids=[s[0] for s in _SITES])
def test_kernel_compiles_for_v5e(site, v5e):
    name, op, raw_attrs, shapes, dtypes, is_train, _inputs = site
    opdef = get_op(op)
    attrs = opdef.normalize_attrs(raw_attrs)
    n_aux = len(opdef.aux_names(attrs))
    structs = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=v5e)
               for s, d in zip(shapes, dtypes)]
    regular = structs[:len(structs) - n_aux] if n_aux else structs
    aux = structs[len(structs) - n_aux:] if n_aux else []
    fn = opdef.variant_fn("pallas")

    def forward(r, x):
        outs, new_aux = fn(attrs, list(r), list(x), is_train,
                           jax.random.PRNGKey(0))
        return list(outs), list(new_aux)

    def backward(r, x):
        def loss(first):
            outs, _ = fn(attrs, [first] + list(r[1:]), list(x), True,
                         jax.random.PRNGKey(0))
            return outs[0].astype(jnp.float32).sum()
        return jax.grad(loss)(r[0])

    programs = [forward] + ([backward] if name in _HAND_BACKWARD else [])
    for prog in programs:
        text = jax.jit(prog).lower(regular, aux).compile().as_text()
        assert "tpu_custom_call" in text, \
            f"{name}: no Mosaic kernel in the compiled {prog.__name__}"


def _eva_published(S, v5e, layers=1):
    """``eva_attention_decode`` at the published sizes (8 slots, 32
    heads of 128, a window of 2,048 rows, 2,048 summaries, bfloat16) as
    a program of ``layers`` layers with their aux arrays donated, for
    the described chip: the jitted function and its arguments."""
    opdef = get_op("eva_attention_decode")
    attrs = opdef.normalize_attrs({"capacity": 32768, "window": 2048,
                                   "chunk": 16, "rope_base": 1e5})
    B, H, d = 8, 32, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    x = sds((B, H, S, d))
    ins = [x, x, x, sds((B,), jnp.int32), sds((H, d)), sds((H, d))]
    aux = [sds((B, H, 2048, d))] * 4 + [sds((B, 1), jnp.int32)]
    fn = opdef.variant_fn("pallas")
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])

    def program(r, a):
        state = []
        for layer in range(layers):
            (out,), new = fn(attrs, r, a[5 * layer:5 * layer + 5], False,
                             None)
            r = [out] + r[1:]
            state += new
        return out, state

    return jax.jit(program, donate_argnums=(1,)), ins, aux * layers


#: the launches of the op's Pallas lowering, by program: the three
#: kernels, and in a window program the riding slots' three behind them
_EVA_LAUNCHES = {
    1: ("eva_summarise", "eva_attn_decode", "eva_write"),
    512: ("eva_summarise", "eva_attn_window", "eva_write",
          "eva_summarise_ride", "eva_attn_ride", "eva_write_ride"),
}


@pytest.mark.parametrize("S", [1, 512], ids=["decode", "window"])
def test_eva_attention_compiles_for_v5e_and_copies_no_pool(S, v5e):
    """EvaByte's attention at the published sizes (8 slots, 32 heads of
    128, a window of 2,048 rows, 2,048 summaries, bfloat16): the op's
    Pallas lowering compiles for the chip, every pool access is a
    kernel's - a window program's six launches: the slots fed a chunk
    and the slots that ride (ISSUE 62) -, and with the aux arrays
    donated no pool is copied or re-laid - the four pools (512 MB) come
    back in the buffers they came in."""
    program, ins, aux = _eva_published(S, v5e)
    compiled = program.lower(ins, aux).compile()
    text = compiled.as_text()
    for kernel in _EVA_LAUNCHES[S]:
        assert re.search(rf"%{kernel}[.\d]* = .*tpu_custom_call", text), \
            kernel
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) \
        == len(_EVA_LAUNCHES[S])
    assert not re.findall(r"= bf16\[8,32,2048,128\]\S* copy\(", text)
    assert " scatter(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * (
        8 * 32 * 2048 * 128 * 2)


def _mosaic_bodies(text):
    """The Mosaic module of every kernel in a lowered program's text,
    printed without locations (a kernel's serialised body carries the
    lines of its source)."""
    import base64
    import json
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    bodies = []
    for config in re.findall(r'backend_config = "(.*?)"[,}]', text, re.S):
        body = json.loads(config.replace("\\22", '"'))["custom_call_config"]
        ctx = ir.Context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # the attribute 'stable_mosaic'
        with ctx:
            bodies.append(ir.Module.parse(base64.b64decode(body["body"]))
                          .operation.get_asm(enable_debug_info=False))
    return bodies


#: the first 16 hex digits of the sha256 of the three kernels of the
#: S = 1 program at the published sizes, as Mosaic modules without
#: locations: ISSUE 62's parent's (04bb371), computed on a copy of it.
#: ISSUE 62 put the kernel-calling functions behind ``jax.jit`` and gave
#: the launches of a window program a grid over the slots they are fed;
#: the S = 1 program calls the same kernels through a call boundary
_PARENT_EVA_S1_KERNELS = {"3956dc910fa03186", "fad848bb0f66eae8",
                          "e648953d01fd1f67"}


@pytest.mark.parametrize("S", [1, 512], ids=["decode", "window"])
def test_eva_programs_hold_each_kernel_once_whatever_their_layers(S, v5e):
    """A two-layer program lowers every launch once and calls it from
    both layers (each kernel-calling function of ``ops/eva.py`` is a
    jitted function of its own: a kernel lowered bare is lowered anew
    in every layer, and ``setup_s`` pays it), and the S = 1 program's
    three kernels are the parent's, body for body."""
    import hashlib
    program, ins, aux = _eva_published(S, v5e, layers=2)
    text = program.lower(ins, aux).as_text()
    bodies = _mosaic_bodies(text)
    assert len(bodies) == len(set(bodies)) == len(_EVA_LAUNCHES[S])
    for function in ("summarise", "attend", "write_rows"):
        defined = re.findall(rf"func\.func private @{function}(?:_\d+)?\(",
                             text)
        called = re.findall(rf"call @{function}(?:_\d+)?\(", text)
        assert len(defined) == len(_EVA_LAUNCHES[S]) // 3, function
        assert len(called) == 2 * len(defined), function
    if S == 1:
        assert {hashlib.sha256(body.encode()).hexdigest()[:16]
                for body in bodies} == _PARENT_EVA_S1_KERNELS


@pytest.mark.parametrize("S,rows", [(1, 32), (256, 384)],
                         ids=["decode", "packed_window"])
@pytest.mark.parametrize("H,groups,chunk", [(64, 1, 256), (128, 1, 256),
                                            (64, 8, 128)],
                         ids=["micro", "small", "nemotron"])
def test_ssm_mixer_compiles_for_v5e_and_copies_no_state(H, groups, chunk, S,
                                                        rows, v5e):
    """Granite 4.0-H's mixer at the published sizes (32 slots, 64 heads
    of 64 - Micro - or 128 - Small, ISSUE 54: 64 lane groups, rows of
    8,448 channels -, a state of 128, a convolution over 4, chunks of
    256; the S = 1 program's 32 rows and the packed window's 384) and
    Nemotron-H's (ISSUE 63: 64 heads of 64 with B and C in 8 groups,
    rows of 6,144 channels, chunks of 128 - four lane groups read one
    group, its B and C stood up as columns in the kernel): the Pallas
    lowering compiles for the chip - the step's kernel, and in a window
    the chunk's inside XLA's loop over the trips -, and with the aux
    arrays donated the 67 MB (134 MB) state comes back in the buffer it
    came in, never copied."""
    import re
    opdef = get_op("ssm_mixer_decode")
    P, N, K = 64, 128, 4
    d_in, C = H * P, H * P + 2 * groups * N
    attrs = opdef.normalize_attrs(dict(
        heads=H, head_dim=P, d_state=N, d_conv=K, chunk=chunk, step_len=S,
        capacity=4096, **({"groups": groups} if groups > 1 else {})))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    ins = [sds((rows, d_in + C + H)), sds((32,), "int32"),
           sds((C, K)), sds((C,)), sds((H,)), sds((H,)), sds((H,))]
    aux = [sds((32, K - 1, C), "float32"),
           sds((32, d_in // 128, N, 128), "float32"), sds((32, 1), "int32")]
    assert opdef.donate_aux
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    compiled = jax.jit(lambda r, a: fn(attrs, r, a, False, None),
                       donate_argnums=(1,)).lower(ins, aux).compile()
    text = compiled.as_text()
    kernels = ["ssm_update"] + (["ssm_scan"] if S > 1 else [])
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\w]* = .*tpu_custom_call", text), \
            kernel
    assert len(re.findall("tpu_custom_call", text)) == len(kernels)
    assert not re.findall(rf"= f32\[32,{H // 2},128,128\]\S* copy\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        32 * d_in * N * 4


@pytest.mark.parametrize("S,rows", [(1, 32), (256, 384)],
                         ids=["decode", "packed_window"])
def test_kda_mixer_compiles_for_v5e_and_copies_no_state(S, rows, v5e):
    """Ling-3.0-flash's KDA mixer at the published sizes (32 slots, 32
    heads of 128 x 128, convolutions over 4, chunks of 64; the S = 1
    program's 32 rows and the packed window's 384): the Pallas lowering
    compiles for the chip - the step's kernel, and in a window the
    chunk's inside XLA's loop over the trips -, and with the aux arrays
    donated the 67 MB state comes back in the buffer it came in, never
    copied."""
    import re
    opdef = get_op("kda_mixer_decode")
    H, D, K = 32, 128, 4
    HD = H * D
    attrs = opdef.normalize_attrs(dict(
        heads=H, head_dim=D, d_conv=K, chunk=64, step_len=S,
        capacity=16384, lower_bound=-5.0, rms_eps=1e-6))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=v5e)

    ins = [sds((rows, 5 * HD + H)), sds((32,), "int32"), sds((3 * HD, K)),
           sds((H,)), sds((HD,)), sds((D,))]
    aux = [sds((32, K - 1, 3 * HD), "float32"),
           sds((32, H, D, D), "float32"), sds((32, 1), "int32")]
    assert opdef.donate_aux
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    compiled = jax.jit(lambda r, a: fn(attrs, r, a, False, None),
                       donate_argnums=(1,)).lower(ins, aux).compile()
    text = compiled.as_text()
    kernels = ["kda_update"] + (["kda_chunk"] if S > 1 else [])
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\w]* = .*tpu_custom_call", text), \
            kernel
    assert len(re.findall("tpu_custom_call", text)) == len(kernels)
    assert not re.findall(r"= f32\[32,32,128,128\]\S* copy\(", text)
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        32 * H * D * D * 4


@pytest.mark.parametrize("S", [1, 64], ids=["decode", "window"])
@pytest.mark.parametrize("capacity", [2048, 4096],
                         ids=["cerebras", "olmoe"])
def test_attention_decode_compiles_for_v5e_and_writes_in_place(capacity, S,
                                                               v5e):
    """``attention_decode`` at the two serving configurations' shapes (8
    slots, 16 heads of 128, bfloat16; capacity 2,048 with learned
    positions, 4,096 with RoPE): with the aux arrays donated the cache
    write moves S rows a slot and nothing else - no pool is copied,
    re-laid for a scatter or rebuilt by a fusion, both pools come back
    in the buffers they came in, and the read is the kernel: two layers
    hold one lowering of ``decode_attn`` and call it twice, it takes
    the pools as they lie and nothing of a pool's shape comes out of
    it or is made for it."""
    import re
    opdef = get_op("attention_decode")
    attrs = opdef.normalize_attrs({"capacity": capacity, "per_slot": True,
                                   "rope": capacity == 4096})
    B, H, d = 8, 16, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((B, H, S, d))] * 3
    aux = [sds((B, H, capacity, d))] * 2 + [sds((B, 1), jnp.int32)]
    fn = opdef.variant_fn("pallas")
    assert opdef.donate_aux
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])

    def two_layers(r, a1, a2):
        o1, n1 = fn(attrs, r, a1, False, None)
        o2, n2 = fn(attrs, [o1[0], r[1], r[2]], a2, False, None)
        return o2, n1, n2

    lowered = jax.jit(two_layers, donate_argnums=(1, 2)).lower(ins, aux, aux)
    assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == [
        "cache_write", "decode_attn"]
    compiled = lowered.compile()
    text = compiled.as_text()
    reads = re.findall(r"%decode_attn[.\w]* = (\S+) custom-call\(([^)]*)\)",
                       text)
    assert len(reads) == 2
    pool_shape = f"bf16[{B},{H},{capacity},{d}]"
    for out, operands in reads:
        assert out.startswith(f"f32[{B},{H},{S},{d}]")
        # K and V go in as the write handed them over, nothing between
        assert len(re.findall(r"%get-tuple-element[.\w]*", operands)) == 2
    pool = rf"= bf16\[{B},{H},{capacity},{d}\]\S* "
    assert not re.findall(pool + r"copy\(", text)
    assert not re.findall(pool + r"fusion\(", text)
    assert not re.findall(pool + r"(reshape|bitcast|transpose)\(", text)
    assert " scatter(" not in text
    assert pool_shape in text
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * (
        B * H * capacity * d * 2)


#: sha256 of the lowered text, the kernels' bodies printed without their
#: source locations, of two layers of ``attention_decode``'s Pallas
#: lowering at the Cerebras and OLMoE serving shapes (capacity, S): the
#: parent's (2800d77), computed there. A graph with as many K/V heads
#: as query heads and no window lowers to the text it had; a Mosaic
#: body as serialised also carries the file's path and line numbers,
#: which move whenever a line is added above a kernel, so the bytes of
#: the whole text are not what is compared.
_PARENT_TEXT_SHA256 = {
    (2048, 1): "b72cf2bf51649865",
    (2048, 64): "0713e04f0f954bfb",
    (4096, 1): "59376acb9cab38bf",
    (4096, 64): "e4d1f6bf71e96f49",
}


def _text_without_locations(text):
    import base64
    import re
    from jax._src.lib.mlir import ir
    bodies = []
    for body in re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    assert len(bodies) == 2                 # cache_write, decode_attn
    return "".join(bodies) + re.sub(
        r'body\\22: \\22([A-Za-z0-9+/=]+)\\22', "BODY", text)


@pytest.mark.parametrize("capacity,S", sorted(_PARENT_TEXT_SHA256))
def test_ungrouped_unwindowed_graphs_lower_to_the_parents_text(capacity, S,
                                                               v5e):
    import hashlib
    opdef = get_op("attention_decode")
    attrs = opdef.normalize_attrs({"capacity": capacity, "per_slot": True,
                                   "rope": capacity == 4096})
    assert not {"kv_heads", "window", "ring", "fed"} & set(attrs)
    B, H, d = 8, 16, 128

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((B, H, S, d))] * 3
    aux = [sds((B, H, capacity, d))] * 2 + [sds((B, 1), jnp.int32)]
    fn = opdef.variant_fn("pallas")

    def two_layers(r, a1, a2):
        o1, n1 = fn(attrs, r, a1, False, None)
        o2, n2 = fn(attrs, [o1[0], r[1], r[2]], a2, False, None)
        return o2, n1, n2

    text = jax.jit(two_layers, donate_argnums=(1, 2)).lower(
        ins, aux, aux).as_text()
    digest = hashlib.sha256(
        _text_without_locations(text).encode()).hexdigest()
    assert digest[:16] == _PARENT_TEXT_SHA256[(capacity, S)]


#: ``attention_decode`` as the served graphs give it (``fed`` an input)
#: at the published sizes: (attributes, slots, query heads, K/V heads,
#: rows of a pool, rows of a long window). Trinity-Mini's full layers
#: (pools of a row per position) and sliding layers (rings of 2,048 +
#: 1,024 rows, rotary), 32 query heads on 4 K/V heads of 128; Granite
#: 4.0-H Small's (32 on 8) and Micro's (32 heads of 64 on 8, two to a
#: row of 128: 4 rows) attention layers, unrotated, at the published
#: multipliers; Cerebras-GPT's and OLMoE's, a K/V head a query head
_FED_GRAPHS = {
    "full": (dict(capacity=32768, kv_heads=4), 8, 32, 4, 32768, 1024),
    "sliding": (dict(capacity=32768, kv_heads=4, rope=True, window=2048,
                     ring=3072), 8, 32, 4, 3072, 1024),
    "granite_small": (dict(capacity=8192, kv_heads=8, scale=0.0078125),
                      32, 32, 8, 8192, 256),
    "granite_micro": (dict(capacity=4096, kv_heads=4, scale=0.015625),
                      32, 32, 4, 4096, 256),
    "cerebras": (dict(capacity=2048), 8, 16, 16, 2048, 64),
    "olmoe": (dict(capacity=4096, rope=True), 8, 16, 16, 4096, 64),
    # SDAR's (ISSUE 60): Trinity's head geometry, rotary, and the mask's
    # upper edge the end of the query's block of 4
    "sdar": (dict(capacity=8192, kv_heads=4, rope=True, block=4),
             8, 32, 4, 8192, 512),
    # Nemotron-H's (ISSUE 63): 16 query heads a K/V head, unrotated,
    # the default scale
    "nemotron": (dict(capacity=8192, kv_heads=2), 32, 32, 2, 8192, 256),
}


def _fed_graph(kind, S, v5e):
    """``(attrs, inputs, aux)`` of ``_FED_GRAPHS[kind]`` at ``S`` rows a
    slot, as shapes on the described chip."""
    raw, B, H, Hkv, rows, _ = _FED_GRAPHS[kind]
    d = 128
    opdef = get_op("attention_decode")
    attrs = opdef.normalize_attrs(dict(raw, per_slot=True, fed=True))

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    ins = [sds((B, H, S, d)), sds((B, Hkv, S, d)), sds((B, Hkv, S, d)),
           sds((B,), jnp.int32)]
    aux = [sds((B, Hkv, rows, d))] * 2 + [sds((B, 1), jnp.int32)]
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    return attrs, ins, aux


@pytest.mark.parametrize("S", [1, 0], ids=["decode", "window"])
@pytest.mark.parametrize("kind", ["full", "sliding", "granite_small",
                                  "granite_micro", "nemotron"])
def test_grouped_window_attention_compiles_for_v5e_and_copies_no_pool(kind, S,
                                                                      v5e):
    """Trinity-Mini's, the two Granites' and Nemotron-H's
    ``attention_decode`` at the published sizes (``_FED_GRAPHS``), in
    the S = 1 program and the window program of 1,024 or 256 rows a
    slot. The write is ``cache_write``; the read ``decode_attn`` (4, 8
    or - Nemotron-H, inside ``_DECODE_ROWS`` 64 - 16 rows a K/V head)
    or, in a window, ``window_attn`` for the slots that prefill and
    ``window_attn_ride`` for those fed one row (ISSUE 58), whose row 0
    is laid into the window's result where it lies; two layers hold one
    lowering of each and call it twice, and with the aux arrays donated
    every pool comes back in the buffer it came in, neither copied nor
    re-laid."""
    from mxnet_tpu.models.transformer import ring_rows
    assert ring_rows(2048, 1024) == _FED_GRAPHS["sliding"][0]["ring"]
    _, B, H, Hkv, rows, window = _FED_GRAPHS[kind]
    S = S or window
    attrs, ins, aux = _fed_graph(kind, S, v5e)
    opdef = get_op("attention_decode")
    assert opdef.aux_names(attrs)[0] == (
        "k_ring" if kind == "sliding" else "k_cache")
    fn = opdef.variant_fn("pallas")

    def two_layers(r, a1, a2):
        o1, n1 = fn(attrs, r, a1, False, None)
        o2, n2 = fn(attrs, [o1[0]] + r[1:], a2, False, None)
        return o2, n1, n2

    lowered = jax.jit(two_layers, donate_argnums=(1, 2)).lower(ins, aux, aux)
    kernels = ["cache_write", "decode_attn"] if S == 1 else [
        "cache_write", "window_attn", "window_attn_ride"]
    assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == kernels
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in kernels:
        assert len(re.findall(rf"%{kernel}[.\d]* = .* custom-call\(",
                              text)) == 2, kernel
    pool = rf"= bf16\[{B},{Hkv},{rows},128\]\S* "
    assert not re.findall(pool + r"copy\(", text)
    assert not re.findall(pool + r"fusion\(", text)
    assert " scatter(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        4 * B * Hkv * rows * 128 * 2
    if S > 1:
        # the ride reads every slot's first query against the pools as
        # the write handed them over, and gives a row a slot
        rides = re.findall(
            r"%window_attn_ride[.\w]* = (\S+) custom-call\(([^)]*)\)", text)
        for out, operands in rides:
            assert out.startswith(f"f32[{B},{Hkv},{H // Hkv},128]")
            assert all(x.startswith("%get-tuple-element")
                       for x in operands.split(", ")[-2:])


@pytest.mark.parametrize("S", [1, 4, 512], ids=["s1", "block", "window"])
def test_block_attention_compiles_for_v5e_and_copies_no_pool(S, v5e):
    """SDAR's ``attention_decode(block=4)`` at the published sizes (8
    slots, 32 query heads on 4 K/V heads of 128, capacity 8,192) in the
    S = 1 program, the program of one block (``decode_attn``: 8 heads x
    4 rows of a K/V head) and the prefill window of 512 rows a slot
    (``window_attn`` alone: nobody rides a window of a graph that
    decodes by blocks with one row); two layers hold one lowering of
    each and call it twice, and every pool comes back in the buffer it
    came in."""
    _, B, H, Hkv, rows, _ = _FED_GRAPHS["sdar"]
    attrs, ins, aux = _fed_graph("sdar", S, v5e)
    assert attrs["block"] == 4
    fn = get_op("attention_decode").variant_fn("pallas")

    def two_layers(r, a1, a2):
        o1, n1 = fn(attrs, r, a1, False, None)
        o2, n2 = fn(attrs, [o1[0]] + r[1:], a2, False, None)
        return o2, n1, n2

    lowered = jax.jit(two_layers, donate_argnums=(1, 2)).lower(ins, aux, aux)
    kernels = ["cache_write", "decode_attn" if S < 512 else "window_attn"]
    assert re.findall(r'kernel_name = "(\w+)"', lowered.as_text()) == kernels
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in kernels:
        assert len(re.findall(rf"%{kernel}[.\d]* = .* custom-call\(",
                              text)) == 2, kernel
    pool = rf"= bf16\[{B},{Hkv},{rows},128\]\S* "
    assert not re.findall(pool + r"copy\(", text)
    assert not re.findall(pool + r"fusion\(", text)
    assert " scatter(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        4 * B * Hkv * rows * 128 * 2


#: the first 16 hex digits of the sha256 of the text that ONE layer of
#: ``attention_decode``'s Pallas lowering lowers to where ISSUE 58 adds
#: no second read, Mosaic bodies without their source locations
#: (``_text_without_locations``): the S = 1 program of every served
#: graph of ``_FED_GRAPHS`` and the 8 x 64 windows of the Cerebras and
#: OLMoE graphs (64 query rows a K/V head: ``decode_attn``'s). PR 58's
#: parent's (e049b28), computed on a copy of it
_PARENT_FED_TEXT_SHA256 = {
    ("cerebras", 1): "c6311d42d107a145",
    ("cerebras", 64): "cf10b18aa45f4ec0",
    ("olmoe", 1): "47963a8c5f731692",
    ("olmoe", 64): "74c601dd27d91082",
    ("full", 1): "3fcd589773f3ff23",
    ("sliding", 1): "99409f889c43bfe4",
    ("granite_small", 1): "c020b71179ae8e83",
    ("granite_micro", 1): "e4c575084307e085",
}


@pytest.mark.parametrize("kind,S", sorted(_PARENT_FED_TEXT_SHA256))
def test_programs_without_a_long_window_lower_to_the_parents_text(kind, S,
                                                                  v5e):
    """A rider is taken out of ``window_attn`` inside a long window's
    program alone: the S = 1 programs, and the windows of at most 64
    query rows a K/V head, lower to the text they had."""
    import hashlib
    attrs, ins, aux = _fed_graph(kind, S, v5e)
    fn = get_op("attention_decode").variant_fn("pallas")
    text = jax.jit(lambda r, a: fn(attrs, r, a, False, None),
                   donate_argnums=(1,)).lower(ins, aux).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', text) == [
        "cache_write", "decode_attn"]
    digest = hashlib.sha256(
        _text_without_locations(text).encode()).hexdigest()
    assert digest[:16] == _PARENT_FED_TEXT_SHA256[(kind, S)]


def _mla_published(selected, B, C, S, sds):
    """``mla_attention_decode``'s attributes and inputs at the published
    sizes: GLM-5.2's under a selection, A.X-K1's (YaRN of factor 32,
    values of 128) without one."""
    if selected:
        return (dict(capacity=C, n_heads=64, nope_dim=192, rope_dim=64,
                     v_dim=256, kv_rank=512, rope_base=8e6),
                [sds((B, S, 64 * 256)), sds((B, S, 576)),
                 sds((B, S, C), jnp.int8), sds((B,), jnp.int32),
                 sds((512,)), sds((64 * 448, 512))])
    return (dict(capacity=C, n_heads=64, nope_dim=128, rope_dim=64,
                 v_dim=128, kv_rank=512, rope_base=1e4, rms_eps=1e-6,
                 selected=False, rope_factor=32.0,
                 rope_original_positions=4096, rope_beta_fast=32.0,
                 rope_beta_slow=1.0, rope_mscale=1.0,
                 rope_mscale_all_dim=1.0),
            [sds((B, S, 64 * 192)), sds((B, S, 576)),
             sds((B,), jnp.int32), sds((512,)), sds((64 * 256, 512))])


@pytest.mark.parametrize("S", [1, 1024], ids=["decode", "window"])
@pytest.mark.parametrize("op", ["dsa_index_select", "mla_attention_decode",
                                "mla_attention_decode_unselected"])
def test_latent_attention_compiles_for_v5e_and_copies_no_pool(op, S, v5e):
    """GLM-5.2's two decode ops at the published sizes (8 slots, a
    capacity of 32,768, bfloat16; 32 index heads of 128 over 128-wide
    keys, 64 heads over latent rows of 512 + 64 in 640 lanes): the
    Pallas lowerings compile for the chip, every pool access is a
    kernel's, and with the aux arrays donated the pool of either state
    family - 67 MB of index keys, 336 MB of latent rows - comes back in
    the buffer it came in, neither copied nor re-laid. And A.X-K1's
    ``mla_attention_decode`` at its published sizes (64 heads of 128 +
    64 with values of 128, YaRN of factor 32): no selection, so no mask
    operand of 268 MB a window - the kernels take the pool, the queries
    and two cursors."""
    import re
    B, C = 8, 32768
    bf16 = jnp.bfloat16

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    opdef = get_op(op.replace("_unselected", ""))
    if op == "dsa_index_select":
        attrs = dict(capacity=C, n_heads=32, head_dim=128, rope_dim=64,
                     topk=2048, rope_base=8e6)
        ins = [sds((B, S, 32 * 128)), sds((B, S, 128)), sds((B, S, 32)),
               sds((B,), jnp.int32)]
        width, kernels = 128, ("dsa_write", "dsa_index_scores", "dsa_topk")
    else:
        attrs, ins = _mla_published(not op.endswith("_unselected"),
                                    B, C, S, sds)
        width = 640
        kernels = ("mla_write",) + (("mla_attn_decode",) if S == 1 else
                                    ("mla_attn_window", "mla_attn_ride"))
    attrs = opdef.normalize_attrs(attrs)
    aux = [sds((B, 1, C, width)), sds((B, 1), jnp.int32)]
    assert opdef.donate_aux and set(opdef.slot_state.values()) == {
        "rows", "cursor"}
    assert opdef.variant_eligible("pallas", attrs,
                                  [a.shape for a in ins + aux],
                                  [str(a.dtype) for a in ins + aux])
    fn = opdef.variant_fn("pallas")
    lowered = jax.jit(lambda r, a: fn(attrs, r, a, False, None),
                      donate_argnums=(1,)).lower(ins, aux)
    # what every mla_* and dsa_* reader of the benchmark finds them by
    assert tuple(re.findall(r'kernel_name = "(\w+)"',
                            lowered.as_text())) == kernels
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\w]* = .*tpu_custom_call", text), \
            kernel
    pool = rf"= bf16\[{B},1,{C},{width}\]\S* "
    assert not re.findall(pool + r"copy\(", text)
    assert not re.findall(pool + r"fusion\(", text)
    assert " scatter(" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        B * C * width * 2
    if op.endswith("_unselected"):
        assert "s8[" not in text            # no mask anywhere
    if "mla_attn_ride" in kernels:
        # the riding slots' rows go into the window's result where it
        # lies; the window form attends in the expanded widths, so
        # nothing of slots x S rows is as wide as a latent row: no
        # product of q with W_kb over them (671 MB), none of the latent
        # sums with W_vb (537 MB) - the absorbed arrays are a row a slot
        assert re.search(r"dynamic-update-slice\(%mla_attn_window", text)
        for lanes in (512, 640):
            assert not re.findall(rf"\[{B},64,{S},{lanes}\]", text)
            assert re.findall(rf"bf16\[{B},64,1,{lanes}\]", text)


#: the first 16 hex digits of the sha256 of the S = 1 lowering of
#: ``mla_attention_decode`` at the published sizes, Mosaic bodies without
#: their source locations: the parent's (aec2c22), computed there
_PARENT_MLA_S1_SHA256 = {
    "selected": "c1ceba25c83b862c",
    "unselected": "7ecaf31ef216901f",
}


@pytest.mark.parametrize("lowering", sorted(_PARENT_MLA_S1_SHA256))
def test_the_s1_latent_programs_lower_to_the_parents_text(lowering, v5e):
    """A slot fed one row in a window takes the S = 1 form of the
    kernel; the S = 1 programs themselves - GLM-5.2's under a selection,
    A.X-K1's without - lower to the text they had."""
    import hashlib
    B, C, S = 8, 32768, 1

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    opdef = get_op("mla_attention_decode")
    attrs, ins = _mla_published(lowering == "selected", B, C, S, sds)
    attrs = opdef.normalize_attrs(attrs)
    aux = [sds((B, 1, C, 640)), sds((B, 1), jnp.int32)]
    fn = opdef.variant_fn("pallas")
    text = jax.jit(lambda r, a: fn(attrs, r, a, False, None),
                   donate_argnums=(1,)).lower(ins, aux).as_text()
    digest = hashlib.sha256(
        _text_without_locations(text).encode()).hexdigest()
    assert digest[:16] == _PARENT_MLA_S1_SHA256[lowering]


#: the first 16 hex digits of the sha256 of the text that the whole
#: inference program of each block lowers to at the tiny sizes of
#: ``tests/decode_blocks.py`` (4 slots; S = 1 and the window program
#: of 16 rows a slot; the XLA compositions, on the CPU). The fed blocks'
#: S = 1 and whole-window digests are PR 43's parent's (7112c03),
#: computed on a copy of it: those graphs pass through ``pack_rows`` /
#: ``unpack_rows``, which without a row budget lower to nothing and to
#: the reshape that stood there. Their packed forms (``packed_window``)
#: and the two blocks without ``fed`` are PR 44's parent's (7b8989b),
#: computed on it before ``models/transformer.py`` built every block
#: from one record: the builder changed, no program did. Since ISSUE 47
#: the slot-pooled GPT-2 and OLMoE graphs take ``fed``: their digests
#: - S = 1, whole window, packed - were recorded on ISSUE 47's tree
#: (``gpt2_rotary`` joined then). ISSUE 51 changed the
#: packed forms and nothing else: ``packed_window`` selects each slot's
#: last fed row in front of the head and the program returns ``(slots,
#: 1, V)``, so the seven ``"packed"`` digests were recorded anew on its
#: tree; every S = 1 and whole-window digest stands as it was.
#: ISSUE 65 changed the packed forms again and nothing else: at the 16
#: rows a slot of these programs a slot's rows are one chunk, where
#: ``pack_rows`` / ``unpack_rows`` under a budget lower to a gather of
#: rows and to a slice a slot and no longer to a loop (``ops/rows.py``),
#: so the nine ``"packed"`` digests were recorded anew on its tree;
#: every S = 1 and whole-window digest stands as it was.
#: ``granite_hybrid`` (``num_local_experts`` 0, Micro's dense block)
#: joined with ISSUE 54, which taught ``_granite_spec`` the routed
#: layer: its three digests are PR 54's parent's (60de662), computed on
#: a copy of it.
_PARENT_PROGRAM_SHA256 = {
    ("granite_hybrid", 1, "whole"): "e339ed008af6b332",
    ("granite_hybrid", 16, "whole"): "c603e7f61d202afb",
    ("granite_hybrid", 16, "packed"): "2e96e86a9b0e1824",
    ("glm_dsa", 1, "whole"): "9461136fdd6eaa09",
    ("glm_dsa", 16, "whole"): "96061a4da742fd99",
    ("axk1", 1, "whole"): "2927901ccdca78df",
    ("axk1", 16, "whole"): "785026fe1e56d2a1",
    ("afmoe", 1, "whole"): "aaabd06ad6dfea04",
    ("afmoe", 16, "whole"): "005db05c189522c6",
    ("evabyte", 1, "whole"): "3507d30cfe287fb6",
    ("evabyte", 16, "whole"): "88305a7d0fb20b40",
    ("glm_dsa", 16, "packed"): "fa885957a7552a6f",
    ("axk1", 16, "packed"): "6ac093ffd8a8e037",
    ("afmoe", 16, "packed"): "a85cd904c7c6245c",
    ("evabyte", 16, "packed"): "69f18e2f99666c0b",
    ("gpt2", 1, "whole"): "21ee45bab5a96eb9",
    ("gpt2", 16, "whole"): "450794b962ca88af",
    ("gpt2", 16, "packed"): "725c081618075ec3",
    ("gpt2_rotary", 1, "whole"): "27a812ab4885915c",
    ("gpt2_rotary", 16, "whole"): "10f9b19f6dbac22d",
    ("gpt2_rotary", 16, "packed"): "6a8a616ec76139f9",
    ("olmoe", 1, "whole"): "afab9133b8a82585",
    ("olmoe", 16, "whole"): "c801f808ba5a429b",
    ("olmoe", 16, "packed"): "bf69baefc4282d35",
    # ISSUE 60's own tree: the block that decodes by blocks - its S = 1
    # program, the program of one block, the prefill window and its
    # packed form join the rest
    ("sdar_moe", 1, "whole"): "0a898f651820b8d4",
    ("sdar_moe", 4, "whole"): "11ef4bec07b8939e",
    ("sdar_moe", 16, "whole"): "3d1bbf14ec4620a1",
    ("sdar_moe", 16, "packed"): "c5b2be53345c6ff1",
}


@pytest.mark.parametrize("block,S,form", sorted(_PARENT_PROGRAM_SHA256))
def test_decode_programs_lower_to_the_parents_text(block, S, form,
                                                   monkeypatch):
    """The S = 1 program of every block, its whole-window program (for
    a block that takes ``fed``: what ``step`` without ``fed`` and
    ``check_reference`` run) and the packed form of that (what a
    serving window runs) lower to the text they had."""
    import hashlib
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    try:
        sym = cases.symbol(block, S)
        if form == "packed":
            sym, budget = tfm.packed_window(sym, cases.SLOTS)
            assert budget == 24
        text = cases.lowered_text(sym, cases.SLOTS, S)
    finally:
        kernel_tier.clear()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_PROGRAM_SHA256[(block, S, form)]


@pytest.mark.parametrize("block,parents", [("gpt2", 6), ("olmoe", 9)])
def test_a_packed_window_of_8x64_lowers_without_a_loop(block, parents,
                                                       monkeypatch):
    """ISSUE 65, from the lowered text: the rung-8 packed form of the
    Cerebras and the OLMoE block's window of 64 rows a slot (256 rows;
    two layers at the tests' widths) holds no ``while`` at all. The
    parent's held one a copy site - the tokens, the learned positions,
    a layer's split into heads (OLMoE: q, k and v apart) and its merge:
    ``parents`` here, 50 in the doc cell's 24 layers - and nothing
    else of these graphs loops under the XLA compositions."""
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    try:
        packed, budget = tfm.packed_window(
            cases.symbol(block, 64, capacity=256), 8)
        text = cases.lowered_text(packed, 8, 64)
    finally:
        kernel_tier.clear()
    assert budget == 256
    assert tfm.copy_sites(packed, 64) == (parents, parents)
    assert "stablehlo.while" not in text


@pytest.mark.parametrize("block", [
    "afmoe", "axk1", "evabyte", "evabyte_multibyte", "glm_dsa", "gpt2",
    "gpt2_rotary", "granite_hybrid", "olmoe", "xing4"])
def test_a_packed_window_program_holds_no_window_of_logits(block,
                                                           monkeypatch):
    """ISSUE 51, from the lowered text: the packed window program of
    every block computes its head over ``slots`` rows - no array of the
    vocabulary's width is as long as the window (``slots x S`` rows, or
    ``(slots, S, V)``) or as the budget (R rows), as an output or in
    between - and returns ``(slots, 1, V)``; the whole-window program
    of the same graph holds the window's logits as it did. At 5 slots
    of 16 rows (80 a whole window, a budget of 24) and a vocabulary of
    53, numbers that no width of the tiny blocks shares."""
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    slots, S, V = 5, 16, 53
    kw = dict(cases.config(block.split("_multibyte")[0]), vocab_size=V)
    heads = 1
    if block.endswith("_multibyte"):
        kw.update(multibyte=True)
        heads = kw["n_pred_heads"]
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    try:
        whole = tfm.get_decode_symbol(step_len=S, capacity=cases.CAPACITY,
                                      per_slot=True, **kw)
        packed, budget = tfm.packed_window(whole, slots)
        assert budget == 24
        texts = {form: cases.lowered_text(sym, slots, S)
                 for form, sym in (("whole", whole), ("packed", packed))}
    finally:
        kernel_tier.clear()

    def wide(text):
        """The shapes of every array as wide as the vocabulary (or as
        all heads' vocabularies side by side), as tuples."""
        shapes = {tuple(map(int, dims.split("x")))
                  for dims in re.findall(r"tensor<((?:\d+x)*\d+)x[a-z]", text)}
        return {shape for shape in shapes
                if shape[-1] in (V, heads * V)
                or shape[-2:] == (heads, V)}

    def rows(shape):
        lead = shape[:-2] if shape[-2:] == (heads, V) and heads > 1 \
            else shape[:-1]
        return int(np.prod(lead))

    out = (slots, 1, heads, V) if heads > 1 else (slots, 1, V)
    assert out in wide(texts["packed"])
    # the head's product, the logits and whatever scales or slices
    # them are a row a slot; nothing of that width is as long as the
    # window or the budget (what else is as wide is a weight, read as
    # d_model rows: 64 or 32)
    lengths = set(map(rows, wide(texts["packed"])))
    assert slots in lengths and not lengths & {slots * S, budget}
    window = (slots, S, heads, V) if heads > 1 else (slots, S, V)
    assert window in wide(texts["whole"])
    assert slots * S in set(map(rows, wide(texts["whole"])))


#: the first 16 hex digits of the sha256 of ``Symbol.tojson()`` - every
#: node's name, operation, attributes and inputs - of each block's decode
#: graph at the same sizes, and of the two training graphs there are
#: (``get_symbol``: with its loss, and ``include_loss=False``), of
#: ``KVCacheDecoder``'s graph (one cursor for the batch, rotary), of a
#: graph with fp8 pools and of EvaByte's with every head's logits:
#: PR 44's parent's (7b8989b), computed on it. The nodes that ``+`` and ``*``
#: make are named from a counter, so each graph is built under a name
#: manager of its own. The slot-pooled GPT-2 and OLMoE graphs as above:
#: recorded on ISSUE 47's tree (``fp8_cache`` is a slot-pooled graph
#: too); the training and one-cursor graphs are the parent's still.
_PARENT_GRAPH_SHA256 = {
    ("granite_hybrid", 1): "98ba507ff8be6d3b",      # PR 54's parent's
    ("granite_hybrid", 16): "c66f2e97f9e26b0b",
    ("glm_dsa", 1): "8562f0a3f8b916d7",
    ("glm_dsa", 16): "29129fe7d994fe2c",
    ("axk1", 1): "64a8301c7b5af164",
    ("axk1", 16): "9fcc866dd738526e",
    ("afmoe", 1): "e1061652c1bf8d38",
    ("afmoe", 16): "17df5a2e3b2f960c",
    ("evabyte", 1): "0e294d777b975dbf",
    ("evabyte", 16): "988ab43155750348",
    ("gpt2", 1): "e13693f6a86a2957",
    ("gpt2", 16): "c8cf5a65db6de372",
    ("gpt2_rotary", 1): "942fe74ea9b1182b",
    ("gpt2_rotary", 16): "ba38ab3f681ca623",
    ("olmoe", 1): "e2aeb2264db3a8f5",
    ("olmoe", 16): "2b430acdadc4d962",
    ("sdar_moe", 1): "20d23c640e7c2682",                           # ISSUE 60's own tree
    ("sdar_moe", 4): "35fb89b50c5d5e98",
    ("sdar_moe", 16): "86c9b0fa92012e11",
    ("gpt2", "loss"): "f3eafb9ae1e1305d",
    ("gpt2", "logits"): "454a7d008d7a8bd4",
    ("olmoe", "loss"): "bce42d5f0c8d936c",
    ("olmoe", "logits"): "8b27129cb4c804ab",
    ("gpt2", "scalar_cursor"): "7f8953d42678de28",
    ("gpt2", "fp8_cache"): "43cf3716b98942f8",
    ("olmoe", "scalar_cursor"): "e3acb29dc5352a82",
    ("evabyte", "multibyte"): "a016ce6b612f26cc",
}


def _pinned_graph(block, form):
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    with mx.name.NameManager():
        if isinstance(form, int):
            return cases.symbol(block, form)
        if form == "scalar_cursor":         # ``KVCacheDecoder``'s graph
            return tfm.get_decode_symbol(
                block=block, step_len=4, capacity=cases.CAPACITY,
                **dict(cases.FUSED[block], pos_embed="rotary"))
        if form == "fp8_cache":
            return tfm.get_decode_symbol(
                block=block, capacity=cases.CAPACITY, per_slot=True,
                cache_dtype="fp8", **cases.FUSED[block])
        if form == "multibyte":
            return tfm.get_decode_symbol(
                block=block, step_len=16, capacity=cases.CAPACITY,
                per_slot=True, tie_head=False, embed_scale=False,
                multibyte=True, **cases.BLOCKS[block])
        kw = {k: v for k, v in cases.FUSED[block].items()
              if k != "max_seq_len"}
        return tfm.get_symbol(block=block, seq_len=16, dropout=0.1,
                              include_loss=form == "loss", **kw)


@pytest.mark.parametrize("block,form", sorted(_PARENT_GRAPH_SHA256, key=str))
def test_every_block_builds_the_parents_graph(block, form):
    import hashlib
    text = _pinned_graph(block, form).tojson()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_GRAPH_SHA256[(block, form)]


@pytest.mark.parametrize("S,form", [(1, "whole"), (16, "whole"),
                                    (16, "packed")])
def test_hyper_connected_programs_lower_at_tiny_sizes(S, form, monkeypatch):
    """Xing4.0's block (``residual="hyper"``): its S = 1 program, its
    whole-window program and the packed form of that lower, the stream
    as rows of four copies (256 numbers) over the rows the program
    runs - 4 slots, 4 x 16 of a whole window, the budget's 24 - and
    never as ``(rows, 4, 64)``."""
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    try:
        sym = cases.symbol("xing4", S)
        rows = cases.SLOTS * S
        if form == "packed":
            sym, rows = tfm.packed_window(sym, cases.SLOTS)
            assert rows == 24
        text = cases.lowered_text(sym, cases.SLOTS, S)
    finally:
        kernel_tier.clear()
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("mhc_pre") == ops.count("mhc_post") \
        == 2 * cases.config("xing4")["n_layer"]
    assert f"tensor<{rows}x256xf32>" in text        # the stream's rows
    assert f"tensor<{rows}x16xf32>" in text         # Hres, 16 a row
    assert f"tensor<{rows}x4x64xf32>" not in text


@pytest.mark.parametrize("S, form", [(1, "whole"), (16, "whole"),
                                     (16, "packed")])
def test_granite_hybrid_programs_lower_over_the_rows_they_run(
        S, form, monkeypatch):
    """Granite 4.0-H's tiny graph (``decode_blocks``: mamba,
    attention, mamba) lowers as its S = 1, whole-window and packed
    programs: two recurrent mixers whose state keeps its shape whatever
    the rows, the mixer's projection over the rows the program runs -
    4 slots, 4 x 16 of a whole window, the budget's 24 - and the
    attention layer's K/V heads paired in one row."""
    import decode_blocks as cases
    from mxnet_tpu.models import transformer as tfm
    monkeypatch.setenv("MXNET_KERNEL_TIER", "xla")
    kernel_tier.clear()
    try:
        sym = cases.symbol("granite_hybrid", S)
        rows = cases.SLOTS * S
        if form == "packed":
            sym, rows = tfm.packed_window(sym, cases.SLOTS)
            assert rows == 24
        text = cases.lowered_text(sym, cases.SLOTS, S)
    finally:
        kernel_tier.clear()
    ops = [n.op for n in sym._topo_nodes() if not n.is_variable]
    assert ops.count("ssm_mixer_decode") == 2
    assert ops.count("attention_decode") == 1
    # [z | xBC | dt] = 128 + 160 + 8 numbers a row, over the rows alone
    assert f"tensor<{rows}x296xf32>" in text
    # the state (4 slots, one lane group, 16 down, 128 across) and the
    # convolution's tail, float32 in and out
    assert text.count("tensor<4x1x16x128xf32>") >= 4
    assert "tensor<4x3x160xf32>" in text
    # two K/V heads of 16 in one row of 32, 128 positions
    assert "tensor<4x1x128x32xf32>" in text
    if S > 1:       # the chunks' loop; an S = 1 program has none
        assert "stablehlo.while" in text
    families = tfm.slot_state(sym)
    assert sorted(families) == ["conv", "cursor", "recurrent", "rows"]
