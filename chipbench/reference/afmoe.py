"""Plain reference: the Trinity (``model_type afmoe``) decoder in
float32 jax.numpy.

The published description: arcee-ai/Trinity-Mini's ``config.json``
(catalog row ``Trinity-Mini`` of model-configs/architectures.jsonl) as
computed by the published model code (``transformers``'
``models/afmoe/modeling_afmoe.py``). What the config's keys do not
carry is that code's and is marked (A); the configuration lists each
under ``assumed``:

    x      = E[token] * sqrt(hidden_size)              mup_enabled; (A)
    layer l, RMSNorm n1..n4 (eps, a gain, no bias; four a layer (A)):
    a      = n1(x)
    q      = qn(Wq a) per head, k = kn(Wk a) per head, v = Wv a,
             g = Wg a                                            (A)
             32 query heads and 4 K/V heads of 128; qn, kn RMSNorm
             over a head's 128 (A); rotary (rotate-half pairs
             (i, i + 64), theta ``rope_theta``, no scaling) on the
             layers ``layer_types`` marks ``sliding_attention`` and
             NONE on ``full_attention`` (A)
    o      = softmax(q . k / sqrt(128)) v, query head i on K/V head
             i // 8; the query at t attends j <= t, and on a sliding
             layer only j > t - sliding_window
    h      = x + n2(Wo (concat(heads o) * sigmoid(g)))           (A)
    m      = n3(h)
    l < num_dense_layers:  f = Wdown(silu(Wgate m) * (Wup m))
    else:  s = sigmoid(float32(Wr m)) over all experts
           S = top-k of s + b     (b: the stored ``expert_bias`` (A);
                                   it steers the choice, never a weight)
           w = s[S] / (sum s[S] + 1e-20) * route_scale   (route_norm)
           f = shared(m) + sum_{e in S} w_e expert_e(m), every expert and
               the shared one a gated SiLU of ``moe_intermediate_size``
    x      = h + n4(f)
    logits = Whead nf(x), Whead untied, no scale

``n_group``, ``topk_group``, ``num_expert_groups`` and
``num_limited_groups`` are 1 and read by nothing.

No kernels, no cache, no rings, no batching of heads into groups, no
sorting of tokens by expert: one full-sequence forward that computes
EVERY expert's output for every token and masks it by the top-k
weights (one expert at a time, upcast inside the ``lax.scan``), with
attention over blocks of 128 queries against all keys so that 4,112
positions fit beside a live engine.

Departures from the published code - parameter LAYOUT only, each
following mxnet_tpu/models/transformer.py (the program under test),
none changing the mathematics:
  * ``q_proj``, ``k_proj``, ``v_proj`` and ``gate_proj`` are the four
    row blocks, in that order, of one ``*_qkvg_weight``
    (2 * (heads + kv_heads) * head_dim, hidden);
  * a dense layer's ``gate_proj`` and ``up_proj`` are the two row
    blocks of ``*_ffn_gate_up_weight``;
  * the experts are stacked on a leading axis and stored transposed,
    K-major (``*_moe_gate_weight`` (E, hidden, inter) is
    ``gate_proj.weight.T``, ...), the shared expert's likewise;
  * the router's weight is ``*_moe_router_weight``, its
    ``expert_bias`` ``*_moe_router_bias``;
  * the head holds the rows of the vocabulary that the configuration
    keeps (the first ``vocab_size``).

The switches of the comparison: ``round_to`` rounds every matmul
operand (weights and activations) to that dtype first - the control of
lower precision; ``window=False`` lets the sliding layers attend every
``j <= t`` (the control: a program without the window); ``rope_full``
rotates the full layers too (the control: a program that rotates every
layer); ``tail`` computes the head for the last ``tail`` positions
alone; ``return_routing`` also hands back the experts chosen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, gamma, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(gamma)


def _rope(x, theta):
    """x (B, H, T, dh): rotate the pair (i, i + dh/2) of position t by
    t * theta**(-2i/dh) (rotate-half)."""
    dh = x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _rounder(round_to):
    if round_to is None:
        return lambda x: x
    return lambda x: x.astype(round_to).astype(jnp.float32)


def attention(q, k, v, window, rd, block):
    """q (B, H, T, dh), k and v (B, H_kv, T, dh) -> (B, H, T, dh):
    query head i on K/V head ``i // (H // H_kv)``, the query at t
    attending ``j <= t`` and, with a ``window``, only ``j > t -
    window``; ``block`` queries at a time against all keys."""
    B, H, T, dh = q.shape
    G = H // k.shape[1]
    k, v = (jnp.repeat(x, G, axis=1) for x in (k, v))
    n = -(-T // block)
    qb = jnp.pad(q, [(0, 0), (0, 0), (0, n * block - T), (0, 0)])
    qb = jnp.moveaxis(qb.reshape(B, H, n, block, dh), 2, 0)
    j = jnp.arange(T)[None, :]

    def rows(args):
        t0, q_blk = args
        t = t0 + jnp.arange(block)[:, None]
        mask = j <= t
        if window:
            mask = mask & (j > t - window)
        s = jnp.einsum("bhqd,bhkd->bhqk", rd(q_blk), rd(k)) \
            / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          rd(jax.nn.softmax(s, axis=-1)), rd(v))

    out = jax.lax.map(rows, (jnp.arange(n) * block, qb))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, n * block, dh)[:, :, :T]


def _gated(m, gate, up, down, rd):
    h = jax.nn.silu(rd(m) @ rd(_f32(gate))) * (rd(m) @ rd(_f32(up)))
    return rd(h) @ rd(_f32(down))


def expert_layer(m, p, params, cfg, rd):
    """The sparse feed-forward of rows ``m`` (N, D): ``(routed + shared
    (N, D), chosen (N, k))``, every expert computed for every row, one
    at a time, and masked by the top-k weights."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    sc = jax.nn.sigmoid(rd(m) @ rd(_f32(params[f"{p}_moe_router_weight"])).T)
    _, chosen = jax.lax.top_k(sc + _f32(params[f"{p}_moe_router_bias"]), k)
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)
    weight = jnp.where(picked, sc, 0.0)
    if cfg["route_norm"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    weight = weight * cfg["route_scale"]

    def one_expert(acc, xs):
        gate, up, down, w = xs
        return acc + w[:, None] * _gated(m, gate, up, down, rd), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (params[f"{p}_moe_gate_weight"], params[f"{p}_moe_up_weight"],
         params[f"{p}_moe_down_weight"], weight.T))
    shared = _gated(m, params[f"{p}_moe_shared_gate_weight"],
                    params[f"{p}_moe_shared_up_weight"],
                    params[f"{p}_moe_shared_down_weight"], rd)
    return routed + shared, chosen.astype(jnp.int32)


def forward(params, tokens, config, name="lm", round_to=None, window=True,
            rope_full=False, tail=None, return_routing=False, block=128):
    """Logits (B, T, vocab held) of ``tokens`` (B, T) int32 under
    ``params`` ({program name: array}), float32 at the highest matmul
    precision (module docstring for the switches). ``config``'s
    ``layer_types`` has one entry for each layer that is run."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    D, H = config["hidden_size"], config["num_attention_heads"]
    Hkv, dh = config["num_key_value_heads"], config["head_dim"]
    B, T = tokens.shape
    rd = _rounder(round_to)
    block = min(block, T)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens] \
            * jnp.sqrt(jnp.float32(D))
        for i, kind in enumerate(config["layer_types"]):
            p = f"{name}_l{i}"
            sliding = kind == "sliding_attention"
            a = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            wide = rd(a) @ rd(_f32(params[f"{p}_qkvg_weight"])).T
            q, k, v, g = jnp.split(
                wide, [H * dh, (H + Hkv) * dh, (H + 2 * Hkv) * dh], axis=-1)
            q = _rms_norm(q.reshape(B, T, H, dh),
                          params[f"{p}_q_norm_gamma"], eps)
            k = _rms_norm(k.reshape(B, T, Hkv, dh),
                          params[f"{p}_k_norm_gamma"], eps)
            q, k, v = (t.transpose(0, 2, 1, 3)
                       for t in (q, k, v.reshape(B, T, Hkv, dh)))
            if sliding or rope_full:
                q, k = _rope(q, theta), _rope(k, theta)
            att = attention(
                q, k, v, config["sliding_window"] if sliding and window
                else 0, rd, block)
            att = att.transpose(0, 2, 1, 3).reshape(B, T, H * dh) \
                * jax.nn.sigmoid(g)
            x = x + _rms_norm(
                rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T,
                params[f"{p}_post_attn_ln_gamma"], eps)
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            if i < config["num_dense_layers"]:
                F = config["intermediate_size"]
                w = _f32(params[f"{p}_ffn_gate_up_weight"])
                f = _gated(m, w[:F].T, w[F:].T,
                           _f32(params[f"{p}_ffn_down_weight"]).T, rd)
            else:
                f, sel = expert_layer(m.reshape(B * T, D), p, params,
                                      config, rd)
                f = f.reshape(B, T, D)
                chosen.append(sel.reshape(B, T, -1))
            x = x + _rms_norm(f, params[f"{p}_post_ffn_ln_gamma"], eps)
        if tail is not None:
            x = x[:, T - tail:]
        x = _rms_norm(x, params[f"{name}_ln_f_gamma"], eps)
        logits = rd(x) @ rd(_f32(params[f"{name}_head_weight"])).T
    if return_routing:
        return logits, jnp.stack(chosen)
    return logits


def routing_flip_share(ours, theirs):
    """Share of (layer, sequence, position) decisions in which the two
    sides chose different SETS of experts."""
    a = jnp.sort(jnp.asarray(ours), axis=-1)
    b = jnp.sort(jnp.asarray(theirs), axis=-1)
    return jnp.mean(jnp.any(a != b, axis=-1).astype(jnp.float32))
