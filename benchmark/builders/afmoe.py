"""Builder of the ``afmoe`` family: a configuration file of HF ``afmoe``
``config.json`` keys (cut as its ``reduced`` says) ->
``AfmoeLMHeadModel.loss`` under amp O2 (``keep_fp32_filter`` of the model)
+ ``FusedAdam`` (AdamW) + ``build_train_step(donate=True, has_aux=True)``,
fed by the program's own ``CausalLMBatchLoader``.

Its leaf map is its own: every layer has attention and a feed-forward of
one kind, and the reference stacks a tensor over the layers that have its
kind (``layers/attn/q``: every layer; ``layers/moe/router``: the sparse
layers), where the program names layers by position
(``model/layers_<i>/...``).

It assembles the trainer itself, as ``builders/lfm2.py`` does and for its
reason: ``common.assemble`` passes neither ``keep_fp32_filter`` nor
``has_aux`` on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

REFERENCE = "afmoe"

_TOP = {"embed": ("model", "embed_tokens"),
        "norm_f": ("model", "norm", "scale"),
        "lm_head": ("lm_head",)}
_PER_KIND = {
    "attn": {"ln_in": ("input_layernorm", "scale"),
             "q": ("self_attn", "q_proj", "kernel"),
             "k": ("self_attn", "k_proj", "kernel"),
             "v": ("self_attn", "v_proj", "kernel"),
             "gate": ("self_attn", "gate_proj", "kernel"),
             "q_norm": ("self_attn", "q_norm"),
             "k_norm": ("self_attn", "k_norm"),
             "o": ("self_attn", "o_proj", "kernel"),
             "ln_post": ("post_attention_layernorm", "scale")},
    "dense": {"ln_pre": ("pre_mlp_layernorm", "scale"),
              "gate_up": ("mlp", "gate_up", "kernel"),
              "down": ("mlp", "down", "kernel"),
              "ln_post": ("post_mlp_layernorm", "scale")},
    "moe": {"ln_pre": ("pre_mlp_layernorm", "scale"),
            "router": ("moe", "experts", "router"),
            "expert_bias": ("moe", "expert_bias"),
            "w_gate_up": ("moe", "experts", "w_gate_up"),
            "w_down": ("moe", "experts", "w_down"),
            "shared_gate_up": ("moe", "shared", "gate_up", "kernel"),
            "shared_down": ("moe", "shared", "down", "kernel"),
            "ln_post": ("post_mlp_layernorm", "scale")},
}


class KindLeafMap:
    """``layers/<kind>/<tensor>[j]`` of the reference <-> the program's
    ``model/layers_<i>/...`` where layer ``i`` is the ``j``-th that has a
    part of that kind. ``kinds`` is the reference's ``[("attn",
    feed-forward kind)]``."""

    def __init__(self, kinds):
        self.layers = {kind: [i for i, pair in enumerate(kinds)
                              if kind in pair] for kind in _PER_KIND}

    def _leaves(self):
        for kind, where in self.layers.items():
            for name, tail in _PER_KIND[kind].items():
                if where:
                    yield f"layers/{kind}/{name}", where, tail

    def to_program(self, weights: dict) -> dict:
        flat = {path: weights[name] for name, path in _TOP.items()}
        known = set(_TOP)
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

        out = {name: at(path) for name, path in _TOP.items()}
        for name, where, tail in self._leaves():
            out[name] = np.stack([at(("model", f"layers_{i}") + tail)
                                  for i in where])
        return out


def model_config(config: dict):
    """The program's ``AfmoeConfig`` of a configuration file."""
    from apex_tpu.models.afmoe import AfmoeConfig

    deployment = config["deployment"]
    if config["score_func"] != "sigmoid":
        raise ValueError("the afmoe builder routes by sigmoid scores")
    return AfmoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], sliding_window=config["sliding_window"],
        rope_theta=float(config["rope_theta"]),
        num_experts=deployment["num_experts_published"],
        experts_held=config["num_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["num_shared_experts"],
        route_scale=config["route_scale"], route_norm=config["route_norm"],
        rms_norm_eps=config["rms_norm_eps"],
        mup_enabled=config["mup_enabled"], dtype=jnp.bfloat16,
        fused_kernels=True)


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    import apex_tpu.amp as amp
    from apex_tpu.data import CausalLMBatchLoader
    from apex_tpu.models.afmoe import AfmoeLMHeadModel, keep_fp32_filter
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.train import build_train_step

    if mesh is not None or ddp is not None:
        raise ValueError("the afmoe builder builds one chip's share")
    if len(config["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not have num_hidden_layers "
                         "entries")
    model = AfmoeLMHeadModel(model_config(config))
    leaf_map = KindLeafMap(reference.kinds(config))
    opt = dict(config["optimizer"])
    if opt.pop("name") != "adamw":
        raise ValueError("the afmoe builder trains with FusedAdam (AdamW)")
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
