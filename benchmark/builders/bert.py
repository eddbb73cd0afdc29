"""Builder of the BERT family: a configuration file of google-research
``bert_config.json`` keys -> ``BertForPreTraining`` + ``pretraining_loss``
under amp O2 + ``FusedLAMB``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common

REFERENCE = "bert"


def leaf_map(n_layers: int) -> common.LeafMap:
    emb = ("bert", "embeddings")
    top = {
        "word_embeddings": emb + ("word_embeddings", "embedding"),
        "position_embeddings": emb + ("position_embeddings",),
        "token_type_embeddings": emb + ("token_type_embeddings", "embedding"),
        "embeddings_ln/weight": emb + ("ln", "scale"),
        "embeddings_ln/bias": emb + ("ln", "bias"),
        "pooler/kernel": ("bert", "pooler", "kernel"),
        "pooler/bias": ("bert", "pooler", "bias"),
        "mlm_ln/weight": ("mlm_ln", "scale"),
        "mlm_ln/bias": ("mlm_ln", "bias"),
    }
    for n in ("mlm_transform", "mlm_decoder", "nsp"):
        top[n + "/kernel"], top[n + "/bias"] = (n, "kernel"), (n, "bias")
    per_layer = {}
    for n in ("q", "k", "v", "out"):
        per_layer[n + "/kernel"] = ("attention", n, "kernel")
        per_layer[n + "/bias"] = ("attention", n, "bias")
    for n in ("mlp_in", "mlp_out"):
        per_layer[n + "/kernel"], per_layer[n + "/bias"] = \
            (n, "kernel"), (n, "bias")
    for n in ("attention_ln", "output_ln"):
        per_layer[n + "/weight"], per_layer[n + "/bias"] = \
            (n, "scale"), (n, "bias")
    return common.LeafMap(top, per_layer,
                          lambda i: ("bert", f"layer_{i}"), n_layers)


def build(config: dict, traffic: dict, reference, *, seed: int, key, mesh=None,
          ddp=None, abstract_on=None) -> common.Built:
    from apex_tpu.models import BertConfig, BertForPreTraining, \
        pretraining_loss
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_dropout=config["hidden_dropout_prob"],
        attention_dropout=config["attention_probs_dropout_prob"],
        layernorm_eps=config["layer_norm_eps"],
        dtype=jnp.bfloat16, fused_kernels=True,
        **config.get("builder_options", {}))
    model = BertForPreTraining(cfg)
    opt = dict(config["optimizer"])
    if opt.pop("name") != "lamb":
        raise ValueError("the bert builder trains with FusedLAMB")
    shards = 1 if mesh is None else mesh.devices.size

    def loss_fn(params, mb):
        # the dropout stream comes from the batch, never from a constant
        key = jax.random.PRNGKey(mb["seed"][0])
        mlm, nsp = model.apply(
            {"params": params}, mb["ids"], mb["types"], mb["attn"],
            deterministic=False, rngs={"dropout": key},
            masked_positions=mb["positions"])
        return pretraining_loss(mlm, nsp, mb["mlm_labels"],
                                mb["nsp_labels"], mb["mlm_weights"])

    def expected_tree():
        ids = jnp.zeros((1, 8), jnp.int32)
        return jax.eval_shape(
            lambda k: model.init(k, ids, ids, ids)["params"],
            jax.random.PRNGKey(0))

    fields = ("ids", "types", "attn", "positions", "mlm_labels",
              "mlm_weights", "nsp_labels")

    def program_batch(tb):
        out = {k: tb[k][None] for k in fields}
        out["seed"] = np.asarray(tb["seed"], np.int32).reshape(1, shards)
        return out

    def reference_batch(tb):
        out = {k: tb[k].reshape(shards, -1, *tb[k].shape[1:])
               for k in fields}
        out["seed"] = np.asarray(tb["seed"], np.int32).reshape(shards)
        return out

    return common.assemble(
        loss_fn=loss_fn,
        optimizer=FusedLAMB(lr=opt["lr"], weight_decay=opt["wd"],
                            betas=(opt["b1"], opt["b2"]), eps=opt["eps"],
                            max_grad_norm=opt["max_grad_norm"]),
        opt_settings=dict(config["optimizer"]),
        leaf_map=leaf_map(cfg.num_layers),
        init_weights=lambda key: reference.init_weights(config, key),
        expected_tree=expected_tree, key=key, mesh=mesh, ddp=ddp,
        clip_by_metric=True, with_grad_norm=True,
        program_batch=program_batch, reference_batch=reference_batch,
        abstract_on=abstract_on)
