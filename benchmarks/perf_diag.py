"""Perf diagnosis: structural diff of our fused train step vs the flax
referent's, on the compiled TPU executables.

Dumps both optimized-HLO texts, counts the op classes that explain
schedule/fusion gaps (transposes, dtype converts, copies, fusions,
all-reduce), and times targeted program variants (e.g. the fused step
WITHOUT gradient outputs) to attribute the wall-clock difference.

    python benchmarks/perf_diag.py          # needs the TPU (one process!)
"""
from __future__ import annotations

import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

BATCH = 256
NUM_CLASSES = 1000
LR, MOMENTUM = 0.1, 0.9


def hlo_stats(text):
    ops = re.findall(r"^\s*(?:ROOT )?%?[\w.-]+ = [\w\[\]{}, ]* (\w+)\(",
                     text, re.M)
    from collections import Counter
    c = Counter(ops)
    interesting = {k: c[k] for k in
                   ("transpose", "convert", "copy", "fusion", "convolution",
                    "dot", "reduce", "custom-call", "bitcast",
                    "dynamic-update-slice", "all-reduce") if c.get(k)}
    # transposes/converts inside fusions don't show at top level; count
    # them anywhere in the text too
    interesting["transpose_any"] = len(re.findall(r"transpose\(", text))
    interesting["convert_any"] = len(re.findall(r"convert\(", text))
    interesting["copy_any"] = len(re.findall(r"copy\(", text))
    interesting["total_top_level"] = sum(c.values())
    return interesting


def _force(x):
    """Force execution: device_get of a value completes only after the
    program producing it does."""
    import jax
    return float(np.asarray(jax.device_get(x)).ravel()[0])


def time_program(fn, reps=10):
    """Median seconds of ``reps`` forced runs after one warm run."""
    _force(fn())
    laps = []
    for _ in range(reps):
        tic = time.perf_counter()
        _force(fn())
        laps.append(time.perf_counter() - tic)
    return statistics.median(laps)


def setup_ours():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet

    rng = np.random.RandomState(0)
    imgs = rng.rand(BATCH, 3, 224, 224).astype(np.float32)
    labels = (rng.rand(BATCH) * NUM_CLASSES).astype(np.float32)
    sym = resnet.get_symbol(num_classes=NUM_CLASSES, num_layers=50,
                            image_shape="3,224,224")
    it = mx.io.NDArrayIter(imgs, labels, batch_size=BATCH)
    mod = mx.mod.Module(sym, context=mx.tpu(), compute_dtype=jnp.bfloat16)
    mod.fit(it, num_epoch=1, initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": LR, "momentum": MOMENTUM})
    assert mod._fused_armed
    eg = mod._exec_group
    exe = eg.executor
    arg_vals = exe._arg_vals()
    w = {nm: arg_vals.pop(nm) for nm in eg._fused_watched}
    lrs, wds = mod._fused_lr_wd()
    lr_arr = jnp.asarray([lrs[nm] for nm in eg._fused_watched],
                         jnp.float32)
    wd_arr = jnp.asarray([wds[nm] for nm in eg._fused_watched],
                         jnp.float32)
    args = (w, arg_vals, exe._aux_vals(), jax.random.PRNGKey(0),
            eg._fused_states, lr_arr, wd_arr)
    return mod, eg, exe, args


def main():
    import jax
    import jax.numpy as jnp

    out = {}
    mod, eg, exe, args = setup_ours()
    w, arg_vals, aux_vals, rng_key, states, lr_arr, wd_arr = args

    # ---- full fused program (donation disabled so we can re-run) ----
    runner = exe._runner
    loss_mask = exe._loss_mask
    watched = eg._fused_watched
    plan_init, plan_update = mod._optimizer.fused_plan()

    def step_full(w, rest, aux_vals, rng, states, lr_arr, wd_arr):
        def f(wv):
            return runner({**rest, **wv}, aux_vals, True, rng)
        outs, vjp_fn, new_aux = jax.vjp(f, w, has_aux=True)
        heads = [jnp.ones(o.shape, o.dtype) if is_loss
                 else jnp.zeros(o.shape, o.dtype)
                 for o, is_loss in zip(outs, loss_mask)]
        (grads,) = vjp_fn(heads)
        new_w, new_states = {}, {}
        for i, nm in enumerate(watched):
            nw, ns = plan_update(w[nm], grads[nm].astype(w[nm].dtype),
                                 states[nm], lr_arr[i], wd_arr[i])
            new_w[nm] = nw
            new_states[nm] = ns
        return outs, new_aux, new_w, new_states, grads

    def step_nograds(w, rest, aux_vals, rng, states, lr_arr, wd_arr):
        outs, new_aux, new_w, new_states, _ = step_full(
            w, rest, aux_vals, rng, states, lr_arr, wd_arr)
        return outs, new_aux, new_w, new_states

    def step_lossonly(w, rest, aux_vals, rng, states, lr_arr, wd_arr):
        outs, new_aux, new_w, new_states, _ = step_full(
            w, rest, aux_vals, rng, states, lr_arr, wd_arr)
        return [jnp.sum(o) for o in outs], new_aux, new_w, new_states

    variants = {}
    for name, fn in (("full", step_full), ("nograds", step_nograds),
                     ("lossonly", step_lossonly)):
        jitted = jax.jit(fn)
        print(f"[diag] compiling ours/{name}", file=sys.stderr, flush=True)
        compiled = jitted.lower(*args).compile()
        if name == "full":
            with open("/tmp/hlo_ours.txt", "w") as f:
                f.write(compiled.as_text())
            out["hlo_ours"] = hlo_stats(compiled.as_text())
        t = time_program(lambda j=jitted: j(*args)[0][0])
        variants[name] = round(t * 1e3, 1)
    out["ours_ms"] = variants

    # ---- flax referent ----
    from benchmarks.flax_resnet50 import make_train_step
    step, init = make_train_step(BATCH, LR, MOMENTUM, NUM_CLASSES)
    state = init(jax.random.PRNGKey(0))
    rngnp = np.random.RandomState(0)
    x = jax.device_put(rngnp.rand(BATCH, 224, 224, 3).astype(np.float32))
    y = jax.device_put((rngnp.rand(BATCH) * NUM_CLASSES).astype(np.int32))
    print("[diag] compiling flax", file=sys.stderr, flush=True)
    compiled = step.lower(state, x, y).compile()
    with open("/tmp/hlo_flax.txt", "w") as f:
        f.write(compiled.as_text())
    out["hlo_flax"] = hlo_stats(compiled.as_text())

    state_box = [state]

    def flax_once():
        state_box[0], loss = step(state_box[0], x, y)
        return loss

    out["flax_ms"] = round(time_program(flax_once) * 1e3, 1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
