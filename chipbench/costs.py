"""Operations and bytes from shapes, and the roofline over the peaks
table. The yardstick: the program's own cost registry (ops/cost.py,
telemetry/mfu.py) is not consulted, so a PR that edits it moves nothing
here.

Every function returns ``{"flops": .., "bytes": ..}`` for ONE call of
the program it names, counting what the algorithm needs at the compute
dtype the configuration states - not what the program happens to move
(float32 master weights re-cast every step, a whole-capacity cache
read): those show up as a low roofline share.
"""
from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json "
                       f"({sorted(k for k in table if k[0] != '_')})")
    return table[device_kind]


def roofline(cost, device_kind, chips=1):
    """Least seconds for ``cost`` on ``chips`` chips, and which bound
    it is."""
    pk = peaks(device_kind)
    t_flops = cost["flops"] / (pk["bf16_flops_per_s"] * chips)
    t_bytes = cost["bytes"] / (pk["hbm_bytes_per_s"] * chips)
    return (max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "bandwidth")


# ------------------------------------------------------------- ResNet-50
def resnet_convs(config):
    """(cin, cout, k, stride, out_hw) of every convolution of the
    ImageNet bottleneck ResNet the configuration describes, plus the
    classifier as a 1x1 'conv' on a 1x1 map."""
    filt, units = config["filter_list"], config["units"]
    hw = config["image_shape"][1] // 2             # conv0: 7x7 / 2
    layers = [(config["image_shape"][0], filt[0], 7, 2, hw)]
    hw //= 2                                       # 3x3 max pool / 2
    cin = filt[0]
    for stage, n in enumerate(units):
        cout = filt[stage + 1]
        for unit in range(n):
            stride = 2 if (unit == 0 and stage > 0) else 1
            out = hw // stride
            layers.append((cin, cout // 4, 1, 1, hw))          # conv1
            layers.append((cout // 4, cout // 4, 3, stride, out))  # conv2
            layers.append((cout // 4, cout, 1, 1, out))        # conv3
            if unit == 0:
                layers.append((cin, cout, 1, stride, out))     # shortcut
            cin, hw = cout, out
    layers.append((cin, config["num_classes"], 1, 1, 1))       # fc1
    return layers


def resnet_train_step(config, global_batch):
    """One SGD step: forward + backward (2x the forward: input and
    weight gradients) of every convolution; the bytes are each
    convolution's output written forward and read backward and its
    gradient written and read, at the compute width, plus float32
    weights, gradients and momentum read and written once."""
    macs = params = acts = 0
    for cin, cout, k, _stride, hw in resnet_convs(config):
        macs += cin * cout * k * k * hw * hw
        params += cin * cout * k * k
        acts += cout * hw * hw
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    return {"flops": 3 * 2 * macs * global_batch,
            "bytes": 4 * acts * width * global_batch + 5 * 4 * params,
            "macs_per_sample": macs, "params": params}


# ------------------------------------------------------- GPT-2-style LM
def gpt_params(config):
    d, inner = config["n_embd"], config["n_inner"]
    per_layer = 3 * d * d + d * d + 2 * d * inner     # qkv, proj, ffn
    return {"layers": config["n_layer"] * per_layer,
            "embedding": config["vocab_size"] * d}


def gpt_step(config, slots, step_len, live_rows):
    """One dispatch of a slot-pooled decode program: ``slots`` rows of
    ``step_len`` tokens, each slot attending ``live_rows`` cached
    positions (mean over slots). Pads count as tokens: the program's
    contract is (slots, step_len, vocab) logits."""
    d, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    p = gpt_params(config)
    tokens = slots * step_len
    width = 2 if config["compute_dtype"] == "bfloat16" else 4
    matmul = 2 * tokens * (p["layers"] + p["embedding"])     # + tied head
    attn = 4 * tokens * (live_rows + step_len / 2.0) * d * L  # QK^T + PV
    kv_row = 2 * d * L * width                # K and V, every layer
    return {"flops": matmul + attn,
            "bytes": (p["layers"] + p["embedding"]) * width   # weights
            + slots * live_rows * kv_row                      # cache read
            + tokens * kv_row                                 # cache write
            + tokens * V * 4}                                 # logits out
