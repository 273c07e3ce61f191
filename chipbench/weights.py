"""Parameters from the seed, for architectures whose published
initialisation is GPT-2's: the default behind an architecture's
``make_params`` (README.md, "The architecture interface")."""
from __future__ import annotations

import numpy as np


def normal_init(symbol, data_shapes, seed, dtype="float32"):
    """Every parameter of ``symbol`` from the seed: N(0, 0.02) matrices
    and embeddings, zero biases, unit norm gains (GPT-2's
    initialisation), drawn in float32 and held in ``dtype``. Made on
    the default device in ONE jitted call, then handed over as host
    arrays - what a loaded checkpoint is - and freed on the device:
    ``Module`` keeps its own copy there, and two do not fit beside the
    KV pools. Parameter ``i`` of ``symbol.list_arguments()`` less the
    data inputs draws from ``fold_in(key, i)``, so the same seed gives
    the same weights whatever else is added here."""
    import jax
    import jax.numpy as jnp
    names = symbol.list_arguments()
    shapes, _, _ = symbol.infer_shape(**data_shapes)
    todo = [(n, tuple(s)) for n, s in zip(names, shapes)
            if n not in data_shapes]
    dtype = jnp.dtype(dtype)

    def gen(key):
        out = {}
        for i, (name, shape) in enumerate(todo):
            if name.endswith("_gamma"):
                out[name] = jnp.ones(shape, dtype)
            elif name.endswith(("_beta", "_bias")):
                out[name] = jnp.zeros(shape, dtype)
            else:
                out[name] = (0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                ).astype(dtype)
        return out

    arrays = jax.jit(gen)(jax.random.PRNGKey(int(seed) % (1 << 31)))
    host = {}
    for name in list(arrays):
        arr = arrays.pop(name)
        host[name] = np.asarray(arr)
        arr.delete()
    return host
