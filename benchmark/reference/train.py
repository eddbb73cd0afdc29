"""Drive a plain reference through its first optimizer steps and take the
numbers the comparison holds the program to. Imports nothing of the
program; takes the sizes, the optimizer's settings, a key and batches.

A batch here is ``{name: array[shards, rows, ...], "seed": int32[shards]}``:
each shard is what one chip of a data-parallel step sees; the loss and
the gradient of a step are the means over the shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

FAULTS = ("half_batch", "no_exchange", "state_unchanged")


def leaf_axes(name, x):
    """Axes one tensor's norm runs over: all, but for the leading layer
    axis of a stacked leaf."""
    first = 1 if name.startswith("layers/") else 0
    return tuple(range(first, x.ndim))


def run(mod, sizes, optimizer: dict, key, batches, masks, *,
        precision: str = "fp32", fault: str | None = None, probe=None):
    """Train ``len(batches)`` steps from ``init_weights(sizes, key)``.

    Returns host numpy: ``loss`` per step, ``grad`` (per-tensor norm of
    the first step's gradient as the optimizer gets it, before any
    clipping inside the optimizer) and ``change`` (per-tensor norm of
    the parameters' change over all the steps).

    ``fault`` plants one of the faults a training step can have, for the
    readings the limits are set from: ``half_batch`` leaves the second
    half of each shard's rows out and takes the mean over the rest,
    ``no_exchange`` updates from the first shard's gradient alone,
    ``state_unchanged`` returns the state as it got it. ``probe(step,
    norms)`` is handed the per-tensor norms of the change and of both
    moments after every step (for a look by hand)."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    opt = dict(optimizer)
    step_fn = C.OPTIMIZERS[opt.pop("name")]

    def model_copy(w):
        """What the forward pass reads under amp O2: a bfloat16 copy of
        every tensor but the LayerNorm parameters; the masters stay
        float32 (the gradient comes back through the same rounding)."""
        return {n: (x if mod.keeps_float32(n) else C.round_bf16(x))
                for n, x in w.items()}

    @jax.jit
    def shard_grad(w, shard, seed):
        rows = None
        if fault == "half_batch":
            rows = max(jax.tree.leaves(shard)[0].shape[0] // 2, 1)
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda masters: mod.loss(
                model_copy(masters), shard, seed, sizes, masks, precision,
                rows))(w)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(w, g, m, v, step):
        return step_fn(w, g, m, v, step, leaf_axes=leaf_axes, **opt)

    @jax.jit
    def norms(tree):
        return C.leaf_norms(tree, leaf_axes)

    @jax.jit
    def change_norms(w, key):
        w0 = mod.init_weights(sizes, key)
        return C.leaf_norms({n: w[n] - w0[n] for n in w}, leaf_axes)

    w = jax.jit(lambda k: mod.init_weights(sizes, k))(key)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad = [], None
    for i, batch in enumerate(batches):
        seeds = np.asarray(batch["seed"]).reshape(-1)
        shards = len(seeds)
        used = 1 if fault == "no_exchange" else shards
        total, g = 0.0, None
        for s in range(used):
            shard = {k: jnp.asarray(np.asarray(a)[s])
                     for k, a in batch.items() if k != "seed"}
            loss_s, g_s = shard_grad(w, shard, jnp.int32(seeds[s]))
            total = total + loss_s / used
            g = (jax.tree.map(lambda a: a / used, g_s) if g is None else
                 jax.tree.map(lambda a, b: a + b / used, g, g_s))
        losses.append(float(total))
        if i == 0:
            grad = jax.device_get(norms(g))
        if fault == "state_unchanged":
            continue
        w, m, v = update(w, g, m, v, jnp.float32(i + 1))
        if probe is not None:
            probe(i, {"change": jax.device_get(change_norms(w, key)),
                      "m": jax.device_get(norms(m)),
                      "v": jax.device_get(norms(v))})
    change = jax.device_get(change_norms(w, key))
    return {"loss": np.asarray(losses, np.float64),
            "grad": {n: np.asarray(a, np.float64) for n, a in grad.items()},
            "change": {n: np.asarray(a, np.float64)
                       for n, a in change.items()}}
