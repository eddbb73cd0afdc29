"""Builder of the ``nemotron_h`` family: a configuration file of HF
``nemotron_h`` ``config.json`` keys (cut as its ``reduced`` says) ->
``NemotronHLMHeadModel.loss`` under amp O2 (``keep_fp32_filter`` of the
model) + ``FusedAdam`` (AdamW) + ``build_train_step(donate=True,
has_aux=True)``, fed by the program's own ``CausalLMBatchLoader``.

Its leaf map is its own: the reference stacks a tensor over the layers
of ONE kind (``layers/M/in_proj``: the Mamba blocks in stack order), the
program names blocks by position (``backbone/layers_<i>/mixer/...``).

It assembles the trainer itself and not through ``common.assemble``,
which passes neither ``keep_fp32_filter`` nor ``has_aux`` on; what the
comparison reads (first-moment norms after step 1, the masters' change)
is taken the same way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

REFERENCE = "nemotron_h"

_TOP = {"embed": ("backbone", "embedding"), "head": ("lm_head",),
        "norm_f": ("backbone", "norm_f", "scale")}
_PER_KIND = {
    "M": {"norm": ("norm", "scale"),
          "in_proj": ("mixer", "in_proj", "kernel"),
          "conv_w": ("mixer", "conv_kernel"),
          "conv_b": ("mixer", "conv_bias"),
          "dt_bias": ("mixer", "dt_bias"), "A_log": ("mixer", "A_log"),
          "D": ("mixer", "D"), "gate_norm": ("mixer", "norm_scale"),
          "out_proj": ("mixer", "out_proj", "kernel")},
    "E": {"norm": ("norm", "scale"),
          "router": ("mixer", "experts", "router"),
          "w_up": ("mixer", "experts", "w_up"),
          "w_down": ("mixer", "experts", "w_down"),
          "shared_up": ("mixer", "shared_up", "kernel"),
          "shared_down": ("mixer", "shared_down", "kernel")},
    "*": {"norm": ("norm", "scale"), "q": ("mixer", "q", "kernel"),
          "k": ("mixer", "k", "kernel"), "v": ("mixer", "v", "kernel"),
          "out": ("mixer", "out", "kernel")},
}
_NAME_OF = {"M": "M", "E": "E", "*": "A"}


class KindLeafMap:
    """``layers/<kind>/<tensor>[j]`` of the reference <-> the program's
    ``backbone/layers_<i>/...`` where block ``i`` is the ``j``-th of its
    kind in ``pattern``."""

    def __init__(self, pattern: str):
        self.blocks = {kind: [i for i, k in enumerate(pattern) if k == kind]
                       for kind in _PER_KIND}

    def _leaves(self):
        for kind, where in self.blocks.items():
            for name, tail in _PER_KIND[kind].items():
                if where:
                    yield (f"layers/{_NAME_OF[kind]}/{name}", where, tail)

    def to_program(self, weights: dict) -> dict:
        flat = {path: weights[name] for name, path in _TOP.items()}
        known = set(_TOP)
        for name, where, tail in self._leaves():
            known.add(name)
            for j, i in enumerate(where):
                flat[("backbone", f"layers_{i}") + tail] = weights[name][j]
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

        out = {name: at(path) for name, path in _TOP.items()}
        for name, where, tail in self._leaves():
            out[name] = np.stack([at(("backbone", f"layers_{i}") + tail)
                                  for i in where])
        return out


def model_config(config: dict):
    """The program's ``NemotronHConfig`` of a configuration file."""
    from apex_tpu.models.nemotron_h import NemotronHConfig

    deployment = config["deployment"]
    return NemotronHConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        pattern=config["hybrid_override_pattern"][
            :config["num_hidden_layers"]],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"], n_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        time_step_limit=tuple(config["time_step_limit"]),
        n_routed_experts=deployment["n_routed_experts_published"],
        experts_held=config["n_routed_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"], norm_eps=config["norm_eps"],
        dtype=jnp.bfloat16, fused_kernels=True,
        **config.get("builder_options", {}))


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    import apex_tpu.amp as amp
    from apex_tpu.data import CausalLMBatchLoader
    from apex_tpu.models.nemotron_h import (NemotronHLMHeadModel,
                                            keep_fp32_filter)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.train import build_train_step

    if mesh is not None or ddp is not None:
        raise ValueError("the nemotron_h builder builds one chip's share")
    cfg = model_config(config)
    model = NemotronHLMHeadModel(cfg)
    leaf_map = KindLeafMap(cfg.pattern)
    opt = dict(config["optimizer"])
    if opt.pop("name") != "adamw":
        raise ValueError("the nemotron_h builder trains with FusedAdam "
                         "(AdamW)")
    optimizer = FusedAdam(lr=opt["lr"], weight_decay=opt["wd"],
                          betas=(opt["b1"], opt["b2"]), eps=opt["eps"],
                          adam_w_mode=True)

    def loss_fn(params, mb):
        return model.apply({"params": params}, mb["ids"], method="loss")

    def init_weights(key):
        return reference.init_weights(config, key)

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
