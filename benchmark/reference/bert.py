"""Plain reference of BERT pretraining (masked LM + next sentence) as the
``bert_large`` configuration states it: post-LN encoder, learned position
and token-type embeddings, MLM head over gathered positions, NSP head on
the pooled first token. float32 ``jax.numpy``; imports nothing of the
program.

Departures of the configuration from google-research/bert, followed here
because they are what the configuration states (see its ``departures``):
tanh-approximated GELU, an MLM decoder that is not tied to the word
embeddings, no weight-decay exclusions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

LAYER = "layers/"     # leaves under this prefix are stacked over layers


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: LayerNorm."""
    return "ln" in name.split("/")[-2:][0] and "/" in name


def init_weights(sizes, key):
    """The configuration's weights from a key: N(0, 0.02) matrices and
    embeddings (rounded to bfloat16, see ``common.normal_bf16``), zero
    biases, unit LayerNorm gains."""
    H, I, L = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["num_hidden_layers"]
    V, P, T = sizes["vocab_size"], sizes["max_position_embeddings"], \
        sizes["type_vocab_size"]
    mats = {
        "word_embeddings": (V, H), "position_embeddings": (P, H),
        "token_type_embeddings": (T, H),
        LAYER + "q/kernel": (L, H, H), LAYER + "k/kernel": (L, H, H),
        LAYER + "v/kernel": (L, H, H), LAYER + "out/kernel": (L, H, H),
        LAYER + "mlp_in/kernel": (L, H, I),
        LAYER + "mlp_out/kernel": (L, I, H),
        "pooler/kernel": (H, H), "mlm_transform/kernel": (H, H),
        "mlm_decoder/kernel": (H, V), "nsp/kernel": (H, 2),
    }
    keys = C.named_keys(key, sorted(mats))
    w = {n: C.normal_bf16(keys[n], s) for n, s in mats.items()}
    for n in ("q", "k", "v", "out"):
        w[LAYER + n + "/bias"] = jnp.zeros((L, H), jnp.float32)
    w[LAYER + "mlp_in/bias"] = jnp.zeros((L, I), jnp.float32)
    w[LAYER + "mlp_out/bias"] = jnp.zeros((L, H), jnp.float32)
    for n in ("attention_ln", "output_ln"):
        w[LAYER + n + "/weight"] = jnp.ones((L, H), jnp.float32)
        w[LAYER + n + "/bias"] = jnp.zeros((L, H), jnp.float32)
    for n in ("embeddings_ln", "mlm_ln"):
        w[n + "/weight"] = jnp.ones((H,), jnp.float32)
        w[n + "/bias"] = jnp.zeros((H,), jnp.float32)
    w["pooler/bias"] = jnp.zeros((H,), jnp.float32)
    w["mlm_transform/bias"] = jnp.zeros((H,), jnp.float32)
    w["mlm_decoder/bias"] = jnp.zeros((V,), jnp.float32)
    w["nsp/bias"] = jnp.zeros((2,), jnp.float32)
    return w


def site_seeds(sizes, root_key):
    """Seeds of the dropout sites of one forward pass, in the order the
    configuration's ``dropout_sites`` names them."""
    L = sizes["num_hidden_layers"]

    def per_layer(*tail):
        return jnp.stack([C.site_seed(root_key, ("bert", f"layer_{i}") + tail)
                          for i in range(L)])

    return {
        "embeddings": C.site_seed(root_key,
                                  ("bert", "embeddings", "TPDropout_0")),
        "attention": per_layer("attention"),
        "attention_out": per_layer("TPDropout_0"),
        "mlp_out": per_layer("TPDropout_1"),
    }


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """Pretraining loss of one shard of rows. ``batch`` holds ``ids``,
    ``types``, ``attn`` (B, S), ``positions``, ``mlm_labels``,
    ``mlm_weights`` (B, P) and ``nsp_labels`` (B,); ``seed`` is the int32
    the dropout stream of this shard is rooted in. ``rows`` keeps only
    the first rows in the loss (the half-batch fault)."""
    heads, eps = sizes["num_attention_heads"], sizes["layer_norm_eps"]
    p_hid, p_att = sizes["hidden_dropout_prob"], \
        sizes["attention_probs_dropout_prob"]
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    seeds = site_seeds(sizes, jax.random.PRNGKey(seed))
    ids = batch["ids"]
    B, S = ids.shape
    H = sizes["hidden_size"]

    x = (w["word_embeddings"][ids] + w["position_embeddings"][:S][None]
         + w["token_type_embeddings"][batch["types"]])
    x = C.layer_norm(x, w["embeddings_ln/weight"], w["embeddings_ln/bias"],
                     eps)
    x = C.dropout(x, masks.elementwise_keep(seeds["embeddings"], (B, S, H),
                                            p_hid), p_hid)
    key_mask = batch["attn"] == 0

    def layer(x, lw):
        q = mm(x, lw["q/kernel"]) + lw["q/bias"]
        k = mm(x, lw["k/kernel"]) + lw["k/bias"]
        v = mm(x, lw["v/kernel"]) + lw["v/bias"]
        keep = masks.attention_keep(lw["seed_attention"], B, heads, S, p_att)
        ctx = C.attention(q, k, v, heads, key_mask=key_mask, keep=keep,
                          rate=p_att, precision=precision)
        a = mm(ctx, lw["out/kernel"]) + lw["out/bias"]
        a = C.dropout(a, masks.elementwise_keep(
            lw["seed_attention_out"], (B, S, H), p_hid), p_hid)
        x = C.layer_norm(x + a, lw["attention_ln/weight"],
                         lw["attention_ln/bias"], eps)
        h = C.gelu_tanh(mm(x, lw["mlp_in/kernel"]) + lw["mlp_in/bias"])
        y = mm(h, lw["mlp_out/kernel"]) + lw["mlp_out/bias"]
        y = C.dropout(y, masks.elementwise_keep(
            lw["seed_mlp_out"], (B, S, H), p_hid), p_hid)
        x = C.layer_norm(x + y, lw["output_ln/weight"], lw["output_ln/bias"],
                         eps)
        return x, None

    stacked = {n[len(LAYER):]: a for n, a in w.items() if n.startswith(LAYER)}
    for n in ("attention", "attention_out", "mlp_out"):
        stacked["seed_" + n] = seeds[n]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)

    pooled = jnp.tanh(mm(x[:, 0], w["pooler/kernel"]) + w["pooler/bias"])
    g = jnp.take_along_axis(x, batch["positions"][..., None], axis=1)
    h = C.gelu_tanh(mm(g, w["mlm_transform/kernel"])
                    + w["mlm_transform/bias"])
    h = C.layer_norm(h, w["mlm_ln/weight"], w["mlm_ln/bias"], eps)
    mlm_logits = mm(h, w["mlm_decoder/kernel"]) + w["mlm_decoder/bias"]
    nsp_logits = mm(pooled, w["nsp/kernel"]) + w["nsp/bias"]

    weights = batch["mlm_weights"]
    per_token = C.cross_entropy(mlm_logits, batch["mlm_labels"])
    nsp = C.cross_entropy(nsp_logits, batch["nsp_labels"])
    if rows is not None:
        weights, per_token, nsp = weights[:rows], per_token[:rows], nsp[:rows]
    mlm = jnp.sum(per_token * weights) / jnp.maximum(jnp.sum(weights), 1.0)
    return mlm + jnp.mean(nsp)
