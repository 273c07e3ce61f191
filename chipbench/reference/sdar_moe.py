"""Plain reference: the SDAR (``model_type sdar_moe``) decoder in float32
jax.numpy, and the generation procedure it is served by.

The published description: JetLM/SDAR-30B-A3B-Chat's ``config.json``
(catalog row ``SDAR-30B-A3B-Chat`` of model-configs/architectures.jsonl)
as computed by the published model code (``modeling_sdar_moe.py``) and
generated from by the published ``block_diffusion_generate``. What the
config's keys do not carry is that code's and is marked (A); the
configuration lists each under ``assumed``.

Layer ``i``, input ``x`` (T, hidden), positions ``t = 0..T-1``, block
length ``L`` (A):

    n   = RMSNorm(x)
    q   = W_q n (heads of head_dim), k = W_k n, v = W_v n (K/V heads)
          q and k RMS-normed PER HEAD over head_dim, one gain each a
          layer (A), then rotate-half rotary at t (pairs (i, i +
          head_dim/2)), base ``rope_theta``, no scaling
    s   = q . k / sqrt(head_dim); query head h reads K/V head
          h // (heads // kv_heads)
          **query t attends key j iff j < (t // L + 1) * L**: blocks
          counted from position 0; inside a block every position sees
          every other, across blocks the mask is causal
    h1  = x + W_o softmax_float32(s) v
    m   = RMSNorm(h1)
    p   = softmax_float32(W_r m) over all experts; the
          ``num_experts_per_tok`` largest, weights p_e / sum of those
          (``norm_topk_prob``)
    y   = h1 + sum_e w_e W_down,e (silu(W_gate,e m) * W_up,e m)
          experts of ``moe_intermediate_size``; every layer sparse
          (``decoder_sparse_step`` 1, ``mlp_only_layers`` []), no shared
          expert
    logits = W_head RMSNorm(y_last), W_head untied; the embedding is not
          scaled. **Row t of the logits scores the token AT position t**
          (no shift) (A).

``generate``: the prompt's whole blocks, ``floor(P / L) * L`` tokens,
are the context; then block by block - the block starts as the ``P mod
L`` prompt tokens it still holds (first block only) and the mask id
elsewhere; repeat {one forward over everything up to the block's end;
at every undecided position ``x0 = argmax``, confidence ``c =
softmax(logits)[x0]`` in float32; decide the positions with ``c >
confidence_threshold`` (``low_confidence_dynamic`` alone) and, whatever
the threshold, the step's quota's most confident (``L /
denoising_steps`` a step, the remainder to the first steps; the earlier
position first among equals); a decided position is never undecided
again} until none is undecided. A request of ``P`` prompt and ``N`` new
tokens takes ``ceil((P + N) / L) - floor(P / L)`` blocks; positions past
``P + N`` of the last block are denoised and not delivered. The noise
schedule is training's; nothing here reads it.

No kernels, no cache, no sorting of tokens by expert: one full-sequence
forward that computes EVERY expert's output for every token and masks
it by the top-k weights (one expert at a time, upcast inside the
``lax.scan``), attention over blocks of 128 queries against all keys.
Independent of ``mxnet_tpu``.

Departures from the published code - parameter LAYOUT only, each
following mxnet_tpu/models/transformer.py (the program under test),
none changing the mathematics:
  * ``q_proj``, ``k_proj`` and ``v_proj`` are the three row blocks, in
    that order, of one ``*_qkv_weight`` ((heads + 2 kv_heads) *
    head_dim, hidden);
  * the experts are stacked on a leading axis and stored transposed,
    K-major (``*_moe_gate_weight`` (E, hidden, inter) is
    ``gate_proj.weight.T``, ...); the router's weight is
    ``*_moe_router_weight``;
  * ``generate`` runs one forward over the whole sequence a feed where
    the published loop keeps keys and values of the committed blocks:
    the same numbers, since a committed block's rows depend on nothing
    after it.

The switches of the comparison: ``round_to`` rounds every matmul operand
(weights and activations) to that dtype first - the control of lower
precision; ``causal=True`` puts the causal mask in place of the block
mask (the control: a program that decodes one token a step); ``tail``
computes the head for the last ``tail`` positions alone;
``return_routing`` also hands back the experts chosen.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


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


def attention(q, k, v, L, rd, rows):
    """q (B, H, T, dh), k and v (B, H_kv, T, dh) -> (B, H, T, dh):
    query head i on K/V head ``i // (H // H_kv)``, the query at t
    attending ``j < (t // L + 1) * L`` (``L`` 1: ``j <= t``, the causal
    mask); ``rows`` queries at a time against all keys."""
    B, H, T, dh = q.shape
    G = H // k.shape[1]
    k, v = (jnp.repeat(x, G, axis=1) for x in (k, v))
    n = -(-T // rows)
    qb = jnp.pad(q, [(0, 0), (0, 0), (0, n * rows - T), (0, 0)])
    qb = jnp.moveaxis(qb.reshape(B, H, n, rows, dh), 2, 0)
    j = jnp.arange(T)[None, :]

    def part(args):
        t0, q_blk = args
        t = t0 + jnp.arange(rows)[:, None]
        mask = j < (t // L + 1) * L
        s = jnp.einsum("bhqd,bhkd->bhqk", rd(q_blk), rd(k)) \
            / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd",
                          rd(jax.nn.softmax(s, axis=-1)), rd(v))

    out = jax.lax.map(part, (jnp.arange(n) * rows, qb))
    return jnp.moveaxis(out, 0, 2).reshape(B, H, n * rows, dh)[:, :, :T]


def expert_layer(m, p, params, cfg, rd):
    """The sparse feed-forward of rows ``m`` (N, D): ``(output (N, D),
    chosen (N, k))``, every expert computed for every row, one at a
    time, and masked by the top-k weights."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(
        rd(m) @ rd(_f32(params[f"{p}_moe_router_weight"])).T, axis=-1)
    _, chosen = jax.lax.top_k(probs, k)
    picked = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :],
                     axis=1)
    weight = jnp.where(picked, probs, 0.0)
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one_expert(acc, xs):
        gate, up, down, w = xs          # this expert's matrices, upcast
        h = jax.nn.silu(rd(m) @ rd(_f32(gate))) * (rd(m) @ rd(_f32(up)))
        return acc + w[:, None] * (rd(h) @ rd(_f32(down))), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (params[f"{p}_moe_gate_weight"], params[f"{p}_moe_up_weight"],
         params[f"{p}_moe_down_weight"], weight.T))
    return out, chosen.astype(jnp.int32)


def forward(params, tokens, config, name="lm", round_to=None, causal=False,
            tail=None, return_routing=False, rows=128):
    """Logits (B, T, vocab) of ``tokens`` (B, T) int32 under ``params``
    ({program name: array}), float32 at the highest matmul precision
    (module docstring for the switches): row ``t`` scores the token AT
    position ``t``. ``config``: the published keys and
    ``block_length``."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    D, H = config["hidden_size"], config["num_attention_heads"]
    Hkv, dh = config["num_key_value_heads"], config["head_dim"]
    L = 1 if causal else int(config["block_length"])
    B, T = tokens.shape
    rd = _rounder(round_to)
    rows = min(rows, T)
    chosen = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params[f"{name}_tok_embed_weight"])[tokens]
        for i in range(config["num_hidden_layers"]):
            p = f"{name}_l{i}"
            a = _rms_norm(x, params[f"{p}_ln1_gamma"], eps)
            wide = rd(a) @ rd(_f32(params[f"{p}_qkv_weight"])).T
            q, k, v = jnp.split(wide, [H * dh, (H + Hkv) * dh], axis=-1)
            q = _rms_norm(q.reshape(B, T, H, dh),
                          params[f"{p}_q_norm_gamma"], eps)
            k = _rms_norm(k.reshape(B, T, Hkv, dh),
                          params[f"{p}_k_norm_gamma"], eps)
            q, k, v = (t.transpose(0, 2, 1, 3)
                       for t in (q, k, v.reshape(B, T, Hkv, dh)))
            q, k = _rope(q, theta), _rope(k, theta)
            att = attention(q, k, v, L, rd, rows)
            att = att.transpose(0, 2, 1, 3).reshape(B, T, H * dh)
            x = x + rd(att) @ rd(_f32(params[f"{p}_proj_weight"])).T
            m = _rms_norm(x, params[f"{p}_ln2_gamma"], eps)
            y, sel = expert_layer(m.reshape(B * T, D), p, params, config, rd)
            chosen.append(sel.reshape(B, T, -1))
            x = x + y.reshape(B, T, D)
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


def quotas(L, steps):
    """The positions each of ``steps`` feeds decides at least: ``L /
    steps``, the remainder to the first feeds."""
    return [L // steps + (i < L % steps) for i in range(steps)]


def decide(logits, undecided, quota, threshold):
    """One feed's decisions over a block's ``(L, V)`` logits: ``(x0,
    decided)`` - the argmax of every position and which of the
    ``undecided`` ones are decided now: those whose confidence
    ``softmax(logits)[x0]`` (float32) passes ``threshold`` and the
    ``quota`` most confident, the earlier position first among
    equals."""
    logits = np.asarray(logits, np.float32)
    x0 = np.argmax(logits, axis=-1)
    conf = np.asarray(jnp.max(jax.nn.softmax(jnp.asarray(logits), axis=-1),
                              axis=-1))
    conf = np.where(undecided, conf, -np.inf)
    order = np.argsort(-conf, kind="stable")
    decided = np.zeros(len(conf), bool)
    decided[order[:quota]] = True
    return x0, undecided & (decided | (conf > threshold))


def generate(params, prompt, max_new, config, eos_id=None,
             denoising_steps=None, remasking=None, confidence_threshold=None,
             trace=None):
    """The tokens a request of ``prompt`` and ``max_new`` new tokens is
    served, by the published loop (module docstring) over ``forward``:
    a list of at most ``max_new`` ids (fewer where ``eos_id`` was
    decided: nothing from it on is delivered). The denoising parameters
    default to ``config``'s (``denoising_steps``, ``remasking``,
    ``confidence_threshold``). ``trace``, a list, takes ``(block start,
    feed, ids fed, positions decided)`` of every feed that decided
    something."""
    L, mask = int(config["block_length"]), int(config["mask_token_id"])
    steps = int(denoising_steps or config["denoising_steps"])
    remasking = remasking or config["remasking"]
    threshold = config["confidence_threshold"] \
        if confidence_threshold is None else confidence_threshold
    if remasking != "low_confidence_dynamic":
        threshold = np.inf
    prompt = [int(t) for t in prompt]
    P = len(prompt)
    end = -(-(P + max_new) // L) * L
    fwd = _jitted(_freeze(config))
    seq = np.full((1, end), mask, np.int32)     # what lies past a block
    seq[0, :P] = prompt                         # moves nothing before it
    for start in range(P // L * L, end, L):
        undecided = np.arange(start, start + L) >= P
        for feed in range(L + 1):
            if not undecided.any():
                break
            logits = np.asarray(fwd(params, jnp.asarray(seq)))[0]
            quota = quotas(L, steps)[feed] if feed < steps else L
            x0, decided = decide(logits[start:start + L], undecided,
                                 quota, threshold)
            if trace is not None:
                trace.append((start, feed, seq[0, start:start + L].copy(),
                              decided.copy()))
            seq[0, start:start + L] = np.where(decided, x0,
                                               seq[0, start:start + L])
            undecided = undecided & ~decided
    out = [int(t) for t in seq[0, P:P + max_new]]
    if eos_id is not None and eos_id in out:
        out = out[:out.index(eos_id)]
    return out


def _freeze(config):
    return tuple(sorted((k, v) for k, v in config.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=8)
def _jitted(frozen):
    config = dict(frozen)
    return jax.jit(lambda params, tokens: forward(params, tokens, config))
