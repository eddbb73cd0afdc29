"""What the family builders share: the system under test assembled
through its normal entry points (``amp.initialize(O2)`` + a fused
optimizer + ``build_train_step(donate=True)``), its state born on the
device in one jitted call from the benchmark's own weights, and the small
reductions the comparison reads from that state.

This is the only part of the benchmark that imports the program.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Built(NamedTuple):
    step: Any                  # apex_tpu.train.TrainStep
    state: Any                 # TrainState on the device(s)
    place: Callable            # host program-batch -> device batch
    program_batch: Callable    # traffic batch -> host program-batch
    reference_batch: Callable  # traffic batch -> reference batch
    feed: Callable | None      # (corpus, rows, seed) -> iterator of ids
    grad_norms: Callable       # state after step 1 -> finish(step 1's metrics)
    change_norms: Callable     # (state, key) -> device tree of norms
    new_state: Callable        # key -> a fresh state from that key's weights
    to_reference: Callable     # program tree of scalars -> {ref name: array}
    optimizer: dict            # the reference optimizer's settings
    n_params: int


def nest(flat: dict) -> dict:
    """{path tuple: leaf} -> nested dicts."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return out


class LeafMap:
    """Names of the reference's tensors against the program's parameter
    paths: ``top`` {ref name: path}, ``per_layer`` {ref name without the
    ``layers/`` prefix: path under a layer}, ``layer(i)`` the path of
    layer ``i``."""

    def __init__(self, top: dict, per_layer: dict, layer: Callable,
                 n_layers: int):
        self.top, self.per_layer = top, per_layer
        self.layer, self.n_layers = layer, n_layers

    def to_program(self, weights: dict) -> dict:
        flat = {path: weights[name] for name, path in self.top.items()}
        for name, tail in self.per_layer.items():
            stacked = weights["layers/" + name]
            for i in range(self.n_layers):
                flat[self.layer(i) + tail] = stacked[i]
        missing = set(weights) - set(self.top) - {
            "layers/" + n for n in self.per_layer}
        if missing:
            raise ValueError(f"weights the program has no place for: "
                             f"{sorted(missing)}")
        return nest(flat)

    def to_reference(self, tree) -> dict:
        def at(path):
            node = tree
            for name in path:
                node = node[name]
            return np.asarray(node, np.float64)

        out = {name: at(path) for name, path in self.top.items()}
        for name, tail in self.per_layer.items():
            out["layers/" + name] = np.stack(
                [at(self.layer(i) + tail) for i in range(self.n_layers)])
        return out


def check_same_structure(made, expected) -> None:
    """The benchmark's weights must fill exactly the tree the program's
    own ``init`` would make."""
    a = {jax.tree_util.keystr(p): (x.shape, jnp.dtype(x.dtype))
         for p, x in jax.tree_util.tree_flatten_with_path(made)[0]}
    b = {jax.tree_util.keystr(p): (x.shape, jnp.dtype(x.dtype))
         for p, x in jax.tree_util.tree_flatten_with_path(expected)[0]}
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff}")


def assemble(*, loss_fn, optimizer, opt_settings: dict, leaf_map: LeafMap,
             init_weights: Callable, expected_tree: Callable, key,
             mesh, ddp, clip_by_metric: bool, with_grad_norm: bool,
             program_batch, reference_batch, feed=None,
             abstract_on=None) -> Built:
    """Trainer through the normal entry points, with its state made on
    the device in ONE jitted call from ``key``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import apex_tpu.amp as amp
    from apex_tpu.train import build_train_step

    made = {}

    def make_state(key):
        params = leaf_map.to_program(init_weights(key))
        check_same_structure(params, expected_tree())
        params, opt, handle = amp.initialize(
            params, optimizer, opt_level="O2", verbosity=0)
        made["step"] = build_train_step(
            loss_fn, opt, amp=handle, ddp=ddp, mesh=mesh, accum_steps=1,
            donate=True, with_grad_norm=with_grad_norm)
        return made["step"].init(params)

    if abstract_on is not None:
        # compile-only use on described devices: shapes, nothing placed
        new_state = None
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=abstract_on),
            jax.eval_shape(make_state, key))
        place = None
    elif mesh is None:
        new_state = jax.jit(make_state)
        state = new_state(key)
        place = lambda hb: jax.tree.map(jnp.asarray, hb)  # noqa: E731
    else:
        # replicated over the mesh as it is made: a state left on one
        # chip would be copied by the first step, and the copy donated
        new_state = jax.jit(make_state,
                            out_shardings=NamedSharding(mesh, P()))
        state = new_state(key)
        sharding = NamedSharding(mesh, P(None, ddp.axis_name))
        place = lambda hb: jax.device_put(hb, sharding)  # noqa: E731

    def sq_norms(tree):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree)

    moment_norms = jax.jit(lambda opt_state: sq_norms(opt_state.exp_avg))
    b1 = opt_settings.get("b1", 0.9)

    def grad_norms(state):
        """Reduce the first moment's norms after step 1 (on the device,
        nothing fetched yet); the returned ``finish(metrics of step 1)``
        fetches them and undoes the moment and the clipping."""
        m1 = moment_norms(state.opt_state)

        def finish(metrics):
            clip = 1.0
            if clip_by_metric:
                gn = float(metrics["grad_norm"])
                cap = opt_settings["max_grad_norm"]
                clip = cap / gn if gn > cap else 1.0
            tree = jax.tree.map(lambda x: float(x) / ((1.0 - b1) * clip),
                                jax.device_get(m1))
            return leaf_map.to_reference(tree)

        return finish

    @jax.jit
    def change_norms(master, key):
        start = leaf_map.to_program(init_weights(key))
        return sq_norms(jax.tree.map(lambda a, b: a - b, master, start))

    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(state.params))
    return Built(
        step=made["step"], state=state, place=place,
        program_batch=program_batch, reference_batch=reference_batch,
        feed=feed, grad_norms=grad_norms,
        change_norms=lambda st, k: change_norms(st.opt_state.master, k),
        new_state=new_state,
        to_reference=lambda tree: leaf_map.to_reference(
            jax.device_get(tree)),
        optimizer=opt_settings, n_params=n_params)
