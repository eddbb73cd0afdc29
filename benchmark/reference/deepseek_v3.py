"""Plain reference of next-token training of a ``deepseek_v3`` stack as the
``kanana_2_30b_a3b`` configuration states it. float32 ``jax.numpy``;
imports nothing of the program.

A layer is ``x + MLA(RMSNorm(x))`` then ``x + F(RMSNorm(x))``. Multi-head
latent attention, in its EXPANDED form (no absorption of ``W_kvb`` into
the query or the output): ``q = x W_q`` over ``n`` heads of ``d_n + d_r``
(no query latent: ``q_lora_rank`` null); ``[c, k_r] = x W_kva``; ``c =
RMSNorm(c)`` (a gain of ``kv_lora_rank``, eps 1e-6); ``[k_n, v] = c
W_kvb`` over ``n`` heads of ``d_n + d_v``; rotary positions on ``q_r`` and
on the ONE head ``k_r`` with the pairs ``(t_2i, t_2i+1)`` turned in place
(``rope_interleave``); every head's key is ``[k_n, k_r]``; ``softmax(q
k^T / sqrt(d_n + d_r))`` under the causal mask mixes the values of ``d_v``;
``o W_o``. The feed-forward is a dense SwiGLU in the first
``first_k_dense_replace`` layers; after them ``shared(h) + sum over the
chosen experts held here of w_i E_i(h)`` with ``s = sigmoid(h W_r)`` over
all the published experts, the ``num_experts_per_tok`` largest of ``s +
bias`` (``e_score_correction_bias``, held at zero), ``w_i =
routed_scaling_factor s_i / (sum of the chosen s + 1e-20)``; the
``n_shared_experts`` shared experts are one SwiGLU of their summed width.
One RMSNorm after the last layer; an untied head over the vocabulary slice
held here.

Departures from the family's modelling code (transformers
``models/deepseek_v3``), none of which changes a number the comparison
reads: the rotary pairs are turned where they lie, where the family first
moves each pair's elements to the half-split order (the same dot products
of q with k); the shared experts are written as one SwiGLU of width
``n_shared_experts x moe_intermediate_size``, as the family builds them;
the expert bias has no update rule (``departures`` of the configuration
file); ``n_group`` / ``topk_group`` 1 are no group limit and are not
written out.

What is cut is cut here exactly as in the program: ``n_routed_experts``
experts HELD (``deployment.expert_offset`` onward) of the
``deployment.n_routed_experts_published`` the router scores, so the layer
adds its own experts' part and leaves the absent experts' part out; the
shared experts are every token's and count once.

Memory: every layer is checkpointed and runs one sequence at a time
inside, attention in blocks of queries, the held experts one at a time,
the head and loss per sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .lfm2 import gated as swiglu, rms_norm

LAYER = "layers/"
# every layer has attention; a feed-forward is dense or sparse, and a
# stacked tensor runs over the layers that have its kind, in stack order
KINDS = {"attn": ("ln_in", "q", "kv_a", "kv_norm", "kv_b", "o"),
         "dense": ("ln_pre", "gate_up", "down"),
         "moe": ("ln_pre", "router", "expert_bias", "w_gate_up", "w_down",
                 "shared_gate_up", "shared_down")}
FP32 = {"ln_in", "ln_pre", "kv_norm", "norm_f", "router", "expert_bias"}
LATENT_EPS = 1e-6      # kv_a_layernorm: the family's RMSNorm default
QUERY_BLOCK = 128      # queries of an attention block


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: every
    RMSNorm gain (the latent's too), the router and the expert bias."""
    return name.rsplit("/", 1)[-1] in FP32


def kinds(sizes) -> list:
    """``[("attn", feed-forward kind)]`` of the layers held."""
    return [("attn", "dense" if i < sizes["first_k_dense_replace"]
             else "moe") for i in range(sizes["num_hidden_layers"])]


def dims(sizes) -> dict:
    return {"H": sizes["hidden_size"], "I": sizes["intermediate_size"],
            "F": sizes["moe_intermediate_size"],
            "S": sizes["moe_intermediate_size"] * sizes["n_shared_experts"],
            "held": sizes["n_routed_experts"],
            "experts": sizes["deployment"]["n_routed_experts_published"],
            "first": sizes["deployment"]["expert_offset"],
            "n": sizes["num_attention_heads"], "r": sizes["kv_lora_rank"],
            "dn": sizes["qk_nope_head_dim"], "dr": sizes["qk_rope_head_dim"],
            "dv": sizes["v_head_dim"]}


def init_weights(sizes, key):
    """N(0, 0.02) rounded to bfloat16 for every matrix; gains 1; the
    expert bias 0."""
    d, ks = dims(sizes), kinds(sizes)
    n = {kind: sum(kind in pair for pair in ks) for kind in KINDS}
    H, V = d["H"], sizes["vocab_size"]
    per_layer = {
        "attn": {"q": (H, d["n"] * (d["dn"] + d["dr"])),
                 "kv_a": (H, d["r"] + d["dr"]),
                 "kv_b": (d["r"], d["n"] * (d["dn"] + d["dv"])),
                 "o": (d["n"] * d["dv"], H)},
        "dense": {"gate_up": (H, 2 * d["I"]), "down": (d["I"], H)},
        "moe": {"router": (H, d["experts"]),
                "w_gate_up": (d["held"], H, 2 * d["F"]),
                "w_down": (d["held"], d["F"], H),
                "shared_gate_up": (H, 2 * d["S"]),
                "shared_down": (d["S"], H)}}
    mats = {"embed": (V, H), "lm_head": (H, V)}
    for kind, shapes in per_layer.items():
        if n[kind]:
            mats.update({f"{LAYER}{kind}/{name}": (n[kind],) + shape
                         for name, shape in shapes.items()})
    keys = C.named_keys(key, sorted(mats))
    w = {name: C.normal_bf16(keys[name], s) for name, s in mats.items()}
    w["norm_f"] = jnp.ones((H,), jnp.float32)
    for kind, count in n.items():
        for name in KINDS[kind]:
            if count and name.startswith("ln_"):
                w[f"{LAYER}{kind}/{name}"] = jnp.ones((count, H), jnp.float32)
    w[f"{LAYER}attn/kv_norm"] = jnp.ones((n["attn"], d["r"]), jnp.float32)
    if n["moe"]:
        w[f"{LAYER}moe/expert_bias"] = jnp.zeros((n["moe"], d["experts"]),
                                                 jnp.float32)
    return w


# -- attention and the feed-forwards, on one sequence (l, H) --------------------
# (RMSNorm and SwiGLU with [W_gate | W_up] side by side: the lfm2
# reference's own)

def rotary_interleaved(t, theta):
    """Rotary positions on ``t`` ``(l, heads, d)``: position ``p`` turns
    the pair ``(t_2i, t_2i+1)`` by ``p * theta ** (-2 i / d)``, in place
    (the pairing of DeepSeek-V3's own inference code, which reads a pair
    as one complex number)."""
    l, d = t.shape[0], t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = t[..., 0::2], t[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(t.shape)


def attention(x, lw, sizes, mm):
    """Causal latent attention, expanded to ``n`` heads; whole rows of the
    score matrix for a block of queries at a time."""
    d = dims(sizes)
    l = x.shape[0]
    n, dn, dr, dv, r = d["n"], d["dn"], d["dr"], d["dv"], d["r"]
    theta = float(sizes["rope_theta"])
    q = mm(x, lw["q"]).reshape(l, n, dn + dr)
    ckr = mm(x, lw["kv_a"])
    c = rms_norm(ckr[:, :r], lw["kv_norm"], LATENT_EPS)
    kv = mm(c, lw["kv_b"]).reshape(l, n, dn + dv)
    k_r = rotary_interleaved(ckr[:, None, r:], theta)            # l 1 dr
    q = jnp.concatenate([q[..., :dn], rotary_interleaved(q[..., dn:], theta)],
                        -1).transpose(1, 0, 2)                   # n l dq
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (l, n, dr))],
                        -1).transpose(1, 0, 2)                   # n l dq
    v = kv[..., dn:].transpose(1, 0, 2)                          # n l dv
    bq = min(QUERY_BLOCK, l)
    pad = -l % bq
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(n, -1, bq, dn + dr).transpose(1, 0, 2, 3)
    starts = jnp.arange(qb.shape[0]) * bq

    def block(args):
        qi, start = args                                         # n bq dq
        s = mm(qi, jnp.swapaxes(k, -1, -2)) * (dn + dr) ** -0.5  # n bq l
        row = start + jnp.arange(bq)[:, None]
        s = jnp.where(row >= jnp.arange(l)[None, :], s, C.FILL)
        return mm(jax.nn.softmax(s, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(block), (qb, starts))       # nb n bq dv
    ctx = ctx.transpose(0, 2, 1, 3).reshape(-1, n * dv)[:l]
    return mm(ctx, lw["o"])


def dense_mlp(x, lw, sizes, mm):
    return swiglu(x, lw["gate_up"], lw["down"], mm)


def experts(x, lw, sizes, mm):
    d = dims(sizes)
    scores = jax.nn.sigmoid(jnp.matmul(x, lw["router"],
                                       precision=C.HIGHEST))
    # the selection bias is a buffer with no gradient, held at zero
    # (`departures`: the trainer's update rule is not run here)
    by = scores + jax.lax.stop_gradient(lw["expert_bias"])
    _, chosen = jax.lax.top_k(by, sizes["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, -1)
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * sizes["routed_scaling_factor"]

    def one(total, args):
        index, gate_up, down = args
        mine = jnp.sum(jnp.where(chosen == index, weight, 0.0), -1)
        return total + mine[:, None] * swiglu(x, gate_up, down, mm), None

    held = d["first"] + jnp.arange(d["held"])
    routed, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                             (held, lw["w_gate_up"], lw["w_down"]))
    return swiglu(x, lw["shared_gate_up"], lw["shared_down"], mm) + routed


FFN = {"dense": dense_mlp, "moe": experts}


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """Mean next-token loss of one shard of rows; ``batch["ids"]`` is
    (B, S). No dropout: ``seed`` and ``masks`` are not used."""
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    eps = sizes["rms_norm_eps"]
    ids = batch["ids"]
    if rows is not None:
        ids = ids[:rows]
    B, S = ids.shape
    # one split per stacked tensor: its transpose is one concatenate
    apart = {n: [t[0] for t in jnp.split(a, a.shape[0])]
             for n, a in w.items() if n.startswith(LAYER)}
    seen = {kind: 0 for kind in KINDS}

    def take(kind):
        lw = {n: apart[f"{LAYER}{kind}/{n}"][seen[kind]]
              for n in KINDS[kind]}
        seen[kind] += 1
        return lw

    def by_row(fn, *per_row):
        """``fn`` on one sequence at a time, recomputed in the backward
        pass; the weights ``fn`` closes over are one layer's."""
        return jax.lax.map(lambda args: jax.checkpoint(fn)(*args), per_row)

    x = w["embed"][ids]                                   # (B, S, H)
    for _, ffn in kinds(sizes):
        parts = ((lambda h, lw: attention(h, lw, sizes, mm), "attn",
                  "ln_in"),
                 (lambda h, lw, f=ffn: FFN[f](h, lw, sizes, mm), ffn,
                  "ln_pre"))
        for fn, kind, norm_in in parts:
            def part(x, lw, fn=fn, norm_in=norm_in):
                return x + by_row(lambda row: fn(
                    rms_norm(row, lw[norm_in], eps), lw), x)
            x = jax.checkpoint(part)(x, take(kind))

    def row_loss(row, row_ids):
        logits = mm(rms_norm(row, w["norm_f"], eps)[:-1], w["lm_head"])
        return jnp.sum(C.cross_entropy(logits, row_ids[1:]))

    total = jnp.sum(by_row(row_loss, x, ids))
    return total / (B * (S - 1))
