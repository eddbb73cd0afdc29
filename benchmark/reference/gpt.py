"""Plain reference of GPT-2 language-model training as the
``gpt2_medium`` configuration states it: learned position embeddings,
pre-LN blocks, tanh GELU (``gelu_new``), a final LayerNorm and a head
tied to the token embeddings; next-token cross-entropy over every
position but the last. float32 ``jax.numpy``; imports nothing of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

LAYER = "layers/"


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: LayerNorm."""
    return "ln" in name.split("/")[-2:][0] and "/" in name


def init_weights(sizes, key):
    H, L = sizes["n_embd"], sizes["n_layer"]
    V, P = sizes["vocab_size"], sizes["n_positions"]
    mats = {
        "wte": (V, H), "wpe": (P, H),
        LAYER + "attn_q/kernel": (L, H, H), LAYER + "attn_k/kernel": (L, H, H),
        LAYER + "attn_v/kernel": (L, H, H),
        LAYER + "attn_out/kernel": (L, H, H),
        LAYER + "mlp_in/kernel": (L, H, 4 * H),
        LAYER + "mlp_out/kernel": (L, 4 * H, H),
    }
    keys = C.named_keys(key, sorted(mats))
    w = {n: C.normal_bf16(keys[n], s) for n, s in mats.items()}
    for n in ("attn_q", "attn_k", "attn_v", "attn_out", "mlp_out"):
        w[LAYER + n + "/bias"] = jnp.zeros((L, H), jnp.float32)
    w[LAYER + "mlp_in/bias"] = jnp.zeros((L, 4 * H), jnp.float32)
    for n in ("ln_1", "ln_2"):
        w[LAYER + n + "/weight"] = jnp.ones((L, H), jnp.float32)
        w[LAYER + n + "/bias"] = jnp.zeros((L, H), jnp.float32)
    w["ln_f/weight"] = jnp.ones((H,), jnp.float32)
    w["ln_f/bias"] = jnp.zeros((H,), jnp.float32)
    return w


def site_seeds(sizes, root_key):
    L = sizes["n_layer"]

    def per_layer(*tail):
        return jnp.stack([C.site_seed(root_key,
                                      ("transformer", f"h_{i}") + tail)
                          for i in range(L)])

    return {
        "embeddings": C.site_seed(root_key, ("transformer", "TPDropout_0")),
        "attention": per_layer(),
        "attention_out": per_layer("TPDropout_0"),
        "mlp_out": per_layer("TPDropout_1"),
    }


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """Next-token loss of one shard of rows; ``batch["ids"]`` is (B, S)."""
    heads, eps = sizes["n_head"], sizes["layer_norm_epsilon"]
    rate = sizes["resid_pdrop"]
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    seeds = site_seeds(sizes, jax.random.PRNGKey(seed))
    ids = batch["ids"]
    B, S = ids.shape
    H = sizes["n_embd"]

    x = w["wte"][ids] + w["wpe"][:S][None]
    x = C.dropout(x, masks.elementwise_keep(seeds["embeddings"], (B, S, H),
                                            sizes["embd_pdrop"]),
                  sizes["embd_pdrop"])

    def block(x, lw):
        y = C.layer_norm(x, lw["ln_1/weight"], lw["ln_1/bias"], eps)
        q = mm(y, lw["attn_q/kernel"]) + lw["attn_q/bias"]
        k = mm(y, lw["attn_k/kernel"]) + lw["attn_k/bias"]
        v = mm(y, lw["attn_v/kernel"]) + lw["attn_v/bias"]
        keep = masks.attention_keep(lw["seed_attention"], B, heads, S,
                                    sizes["attn_pdrop"])
        ctx = C.attention(q, k, v, heads, causal=True, keep=keep,
                          rate=sizes["attn_pdrop"], precision=precision)
        a = mm(ctx, lw["attn_out/kernel"]) + lw["attn_out/bias"]
        x = x + C.dropout(a, masks.elementwise_keep(
            lw["seed_attention_out"], (B, S, H), rate), rate)
        y = C.layer_norm(x, lw["ln_2/weight"], lw["ln_2/bias"], eps)
        y = C.gelu_tanh(mm(y, lw["mlp_in/kernel"]) + lw["mlp_in/bias"])
        y = mm(y, lw["mlp_out/kernel"]) + lw["mlp_out/bias"]
        x = x + C.dropout(y, masks.elementwise_keep(
            lw["seed_mlp_out"], (B, S, H), rate), rate)
        return x, None

    stacked = {n[len(LAYER):]: a for n, a in w.items() if n.startswith(LAYER)}
    for n in ("attention", "attention_out", "mlp_out"):
        stacked["seed_" + n] = seeds[n]
    x, _ = jax.lax.scan(jax.checkpoint(block), x, stacked)
    x = C.layer_norm(x, w["ln_f/weight"], w["ln_f/bias"], eps)

    def row_loss(xr, ir):
        # one row at a time: a (S, V) float32 logits block, not (B, S, V)
        logits = mm(xr[:-1], w["wte"].T)
        return jnp.sum(C.cross_entropy(logits, ir[1:]))

    if rows is not None:
        x, ids, B = x[:rows], ids[:rows], rows
    total = jnp.sum(jax.lax.map(lambda a: jax.checkpoint(row_loss)(*a),
                                (x, ids)))
    return total / (B * (S - 1))
