"""Builder of the ``deepseek_v3`` family: a configuration file of HF
``deepseek_v3`` ``config.json`` keys (cut as its ``reduced`` says) ->
``DeepseekV3LMHeadModel.loss`` under amp O2 (``keep_fp32_filter`` of the
model) + ``FusedAdam`` (AdamW) + ``build_train_step(donate=True,
has_aux=True)``, fed by the program's own ``CausalLMBatchLoader``
(``builders/with_aux.py``).

Its leaf map is by kind: every layer has latent attention and a
feed-forward of one kind, and the reference stacks a tensor over the
layers that have its kind (``layers/attn/kv_b``: every layer;
``layers/moe/router``: the sparse layers), where the program names layers
by position (``model/layers_<i>/...``).
"""

from __future__ import annotations

import jax.numpy as jnp

from . import common, with_aux

REFERENCE = "deepseek_v3"

_TOP = {"embed": ("model", "embed_tokens"),
        "norm_f": ("model", "norm", "scale"),
        "lm_head": ("lm_head",)}
_PER_KIND = {
    "attn": {"ln_in": ("input_layernorm", "scale"),
             "q": ("self_attn", "q_proj", "kernel"),
             "kv_a": ("self_attn", "kv_a_proj_with_mqa", "kernel"),
             "kv_norm": ("self_attn", "kv_a_layernorm", "scale"),
             "kv_b": ("self_attn", "kv_b_proj", "kernel"),
             "o": ("self_attn", "o_proj", "kernel")},
    "dense": {"ln_pre": ("post_attention_layernorm", "scale"),
              "gate_up": ("mlp", "gate_up", "kernel"),
              "down": ("mlp", "down", "kernel")},
    "moe": {"ln_pre": ("post_attention_layernorm", "scale"),
            "router": ("mlp", "experts", "router"),
            "expert_bias": ("mlp", "expert_bias"),
            "w_gate_up": ("mlp", "experts", "w_gate_up"),
            "w_down": ("mlp", "experts", "w_down"),
            "shared_gate_up": ("mlp", "shared", "gate_up", "kernel"),
            "shared_down": ("mlp", "shared", "down", "kernel")},
}


def leaf_map(kinds) -> with_aux.KindLeafMap:
    return with_aux.KindLeafMap(_TOP, _PER_KIND, kinds)


def model_config(config: dict):
    """The program's ``DeepseekV3Config`` of a configuration file."""
    from apex_tpu.models.deepseek_v3 import DeepseekV3Config

    deployment = config["deployment"]
    if config["scoring_func"] != "sigmoid":
        raise ValueError("the deepseek_v3 builder routes by sigmoid scores")
    if config["q_lora_rank"] is not None:
        raise ValueError("the deepseek_v3 builder has no query latent")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("the deepseek_v3 builder has no group-limited "
                         "routing")
    if not config["rope_interleave"] or config["rope_scaling"] is not None:
        raise ValueError("the deepseek_v3 builder turns interleaved rotary "
                         "pairs, unscaled")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if config["moe_layer_freq"] != 1:
        raise ValueError("the deepseek_v3 builder puts experts in every "
                         "layer after the dense ones")
    return DeepseekV3Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        num_experts=deployment["n_routed_experts_published"],
        experts_held=config["n_routed_experts"],
        expert_offset=deployment["expert_offset"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["n_shared_experts"],
        route_scale=config["routed_scaling_factor"],
        route_norm=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"], dtype=jnp.bfloat16,
        fused_kernels=True)


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    from apex_tpu.models.deepseek_v3 import (DeepseekV3LMHeadModel,
                                             keep_fp32_filter)

    if mesh is not None or ddp is not None:
        raise ValueError("the deepseek_v3 builder builds one chip's share")
    return with_aux.assemble(
        config, model=DeepseekV3LMHeadModel(model_config(config)),
        keep_fp32_filter=keep_fp32_filter,
        leaf_map=leaf_map(reference.kinds(config)),
        init_weights=lambda k: reference.init_weights(config, k), key=key,
        abstract_on=abstract_on)
