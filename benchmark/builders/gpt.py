"""Builder of the GPT-2 family: a configuration file of HF GPT-2
``config.json`` keys -> ``GPTLMHeadModel`` + ``lm_loss`` under amp O2 +
``FusedAdam`` (decoupled weight decay), fed by the program's own
``CausalLMBatchLoader``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

REFERENCE = "gpt"


def leaf_map(n_layers: int) -> common.LeafMap:
    t = ("transformer",)
    top = {"wte": t + ("wte",), "wpe": t + ("wpe",),
           "ln_f/weight": t + ("ln_f", "scale"),
           "ln_f/bias": t + ("ln_f", "bias")}
    per_layer = {}
    for n in ("attn_q", "attn_k", "attn_v", "attn_out", "mlp_in", "mlp_out"):
        per_layer[n + "/kernel"], per_layer[n + "/bias"] = \
            (n, "kernel"), (n, "bias")
    for n in ("ln_1", "ln_2"):
        per_layer[n + "/weight"], per_layer[n + "/bias"] = \
            (n, "scale"), (n, "bias")
    return common.LeafMap(top, per_layer,
                          lambda i: ("transformer", f"h_{i}"), n_layers)


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    from apex_tpu.data import CausalLMBatchLoader
    from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
    from apex_tpu.optimizers import FusedAdam

    rates = {config[k] for k in ("attn_pdrop", "embd_pdrop", "resid_pdrop")}
    if len(rates) != 1:
        raise ValueError("the program's GPT has one dropout rate")
    cfg = GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        max_position_embeddings=config["n_positions"], dropout=rates.pop(),
        layernorm_eps=config["layer_norm_epsilon"], dtype=jnp.bfloat16,
        fused_kernels=True, **config.get("builder_options", {}))
    model = GPTLMHeadModel(cfg)
    opt = dict(config["optimizer"])
    if opt.pop("name") != "adamw":
        raise ValueError("the gpt builder trains with FusedAdam (AdamW)")
    shards = 1 if mesh is None else mesh.devices.size

    def loss_fn(params, mb):
        key = jax.random.PRNGKey(mb["seed"][0])
        logits = model.apply({"params": params}, mb["ids"],
                             deterministic=False, rngs={"dropout": key})
        return lm_loss(logits, mb["ids"])

    def expected_tree():
        ids = jnp.zeros((1, 8), jnp.int32)
        return jax.eval_shape(lambda k: model.init(k, ids)["params"],
                              jax.random.PRNGKey(0))

    def program_batch(tb):
        return {"ids": tb["ids"][None],
                "seed": np.asarray(tb["seed"], np.int32).reshape(1, shards)}

    def reference_batch(tb):
        return {"ids": tb["ids"].reshape(shards, -1, tb["ids"].shape[1]),
                "seed": np.asarray(tb["seed"], np.int32).reshape(shards)}

    def feed(corpus, rows, loader_seed, prefetch):
        return CausalLMBatchLoader(corpus, batch_size=rows,
                                   seed=loader_seed, prefetch=prefetch)

    return common.assemble(
        loss_fn=loss_fn,
        optimizer=FusedAdam(lr=opt["lr"], weight_decay=opt["wd"],
                            betas=(opt["b1"], opt["b2"]), eps=opt["eps"],
                            adam_w_mode=True),
        opt_settings=dict(config["optimizer"]),
        leaf_map=leaf_map(cfg.num_layers),
        init_weights=lambda key: reference.init_weights(config, key),
        expected_tree=expected_tree, key=key, mesh=mesh, ddp=ddp,
        clip_by_metric=False, with_grad_norm=False,
        program_batch=program_batch, reference_batch=reference_batch,
        feed=feed, abstract_on=abstract_on)
