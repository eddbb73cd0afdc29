"""The trainer of a family whose model keeps tensors of its own float32
under amp O2 and reports step counters beside its loss, which
``common.assemble`` passes on neither: ``amp.initialize(O2,
keep_fp32_filter)`` + ``FusedAdam`` (AdamW) + ``build_train_step(donate=True,
has_aux=True)``, fed by the program's own ``CausalLMBatchLoader``, its
state born on the device in one jitted call; and the leaf map of a stack
whose layers are of several kinds.

``builders/nemotron_h.py``, ``lfm2.py``, ``sdar.py`` and ``afmoe.py`` hold
the same assembly each in a copy of their own; ``builders/deepseek_v3.py``
is assembled here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common


class KindLeafMap:
    """``layers/<kind>/<tensor>[j]`` of the reference <-> the program's
    ``model/layers_<i>/...`` where layer ``i`` is the ``j``-th that has a
    part of that kind. ``top`` is ``{reference name: program path}`` of
    the tensors outside the layers, ``per_kind`` ``{kind: {tensor: path
    under a layer}}``, ``kinds`` the reference's ``[(kinds of layer i)]``."""

    def __init__(self, top: dict, per_kind: dict, kinds):
        self.top, self.per_kind = top, per_kind
        self.layers = {kind: [i for i, pair in enumerate(kinds)
                              if kind in pair] for kind in per_kind}

    def _leaves(self):
        for kind, where in self.layers.items():
            for name, tail in self.per_kind[kind].items():
                if where:
                    yield f"layers/{kind}/{name}", where, tail

    def to_program(self, weights: dict) -> dict:
        flat = {path: weights[name] for name, path in self.top.items()}
        known = set(self.top)
        for name, where, tail in self._leaves():
            known.add(name)
            for j, i in enumerate(where):
                flat[("model", f"layers_{i}") + tail] = weights[name][j]
        if set(weights) != known:
            raise ValueError(f"weights and program disagree on: "
                             f"{sorted(set(weights) ^ known)}")
        return common.nest(flat)

    def to_reference(self, tree) -> dict:
        def at(path):
            node = tree
            for name in path:
                node = node[name]
            return np.asarray(node, np.float64)

        out = {name: at(path) for name, path in self.top.items()}
        for name, where, tail in self._leaves():
            out[name] = np.stack([at(("model", f"layers_{i}") + tail)
                                  for i in where])
        return out


def assemble(config: dict, *, model, keep_fp32_filter, leaf_map,
             init_weights, key, abstract_on=None) -> common.Built:
    """``model.loss(ids) -> (loss, counters)`` trained on one chip as the
    configuration's ``optimizer`` (AdamW) says, from ``init_weights(key)``
    (the reference's weights) put through ``leaf_map``."""
    import apex_tpu.amp as amp
    from apex_tpu.data import CausalLMBatchLoader
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.train import build_train_step

    opt = dict(config["optimizer"])
    if opt.pop("name") != "adamw":
        raise ValueError("this assembly trains with FusedAdam (AdamW)")
    optimizer = FusedAdam(lr=opt["lr"], weight_decay=opt["wd"],
                          betas=(opt["b1"], opt["b2"]), eps=opt["eps"],
                          adam_w_mode=True)

    def loss_fn(params, mb):
        return model.apply({"params": params}, mb["ids"], method="loss")

    made = {}

    def make_state(key):
        params = leaf_map.to_program(init_weights(key))
        ids = jnp.zeros((1, 8), jnp.int32)
        common.check_same_structure(params, jax.eval_shape(
            lambda k: model.init(k, ids)["params"], jax.random.PRNGKey(0)))
        params, fused, handle = amp.initialize(
            params, optimizer, opt_level="O2", verbosity=0,
            keep_fp32_filter=keep_fp32_filter)
        made["step"] = build_train_step(
            loss_fn, fused, amp=handle, accum_steps=1, donate=True,
            has_aux=True)
        return made["step"].init(params)

    if abstract_on is not None:
        new_state = place = None
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=abstract_on),
            jax.eval_shape(make_state, key))
    else:
        new_state = jax.jit(make_state)
        state = new_state(key)
        place = lambda hb: jax.tree.map(jnp.asarray, hb)  # noqa: E731

    def sq_norms(tree):
        return jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            tree)

    moment_norms = jax.jit(lambda opt_state: sq_norms(opt_state.exp_avg))

    def grad_norms(state):
        m1 = moment_norms(state.opt_state)

        def finish(metrics):
            return leaf_map.to_reference(jax.tree.map(
                lambda x: float(x) / (1.0 - opt["b1"]), jax.device_get(m1)))

        return finish

    @jax.jit
    def change_norms(master, key):
        start = leaf_map.to_program(init_weights(key))
        return sq_norms(jax.tree.map(lambda a, b: a - b, master, start))

    return common.Built(
        step=made["step"], state=state, place=place,
        program_batch=lambda tb: {"ids": tb["ids"][None]},
        reference_batch=lambda tb: {
            "ids": tb["ids"][None],
            "seed": np.asarray(tb["seed"], np.int32).reshape(1)},
        feed=lambda corpus, rows, loader_seed, prefetch: CausalLMBatchLoader(
            corpus, batch_size=rows, seed=loader_seed, prefetch=prefetch),
        grad_norms=grad_norms,
        change_norms=lambda st, k: change_norms(st.opt_state.master, k),
        new_state=new_state,
        to_reference=lambda tree: leaf_map.to_reference(
            jax.device_get(tree)),
        optimizer=dict(config["optimizer"]),
        n_params=sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(state.params)))
