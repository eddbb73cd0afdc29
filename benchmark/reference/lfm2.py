"""Plain reference of next-token training of an ``lfm2_moe`` stack as the
``lfm2_24b_a2b`` configuration states it: a layer is ``x + mixer(
RMSNorm(x))`` then ``x + ffn(RMSNorm(x))``; the mixer is a gated short
convolution or grouped-query attention with per-head RMSNorm of q and k
and rotary positions, by ``layer_types``; the feed-forward is a dense
gated MLP in the first ``num_dense_layers`` layers and gated sparse
experts after; one RMSNorm after the last layer; the head is the
embedding (tied), over the vocabulary slice held here. float32
``jax.numpy``; imports nothing of the program.

What is cut is cut here exactly as in the program: ``layer_types`` and
``num_dense_layers`` as the file gives them; ``num_experts`` experts HELD
(``deployment.expert_offset`` onward) of the
``deployment.num_experts_published`` the router scores, so the layer adds
its own experts' part and leaves the absent experts' part out.

Memory: every layer is checkpointed and runs one sequence at a time
inside, attention in blocks of queries, the held experts one at a time,
the head and loss per sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

LAYER = "layers/"
# a layer is one mixer kind and one feed-forward kind; a stacked tensor
# runs over the layers that have its kind, in stack order
KINDS = {"conv": ("norm", "in_proj", "conv_w", "out_proj"),
         "attn": ("norm", "q", "k", "v", "q_norm", "k_norm", "out"),
         "dense": ("norm", "gate_up", "down"),
         "moe": ("norm", "router", "expert_bias", "w_gate_up", "w_down")}
MIXER_OF = {"conv": "conv", "full_attention": "attn"}
FP32 = {"norm", "norm_f", "q_norm", "k_norm", "router", "expert_bias",
        "conv_w"}
QUERY_BLOCK = 128      # queries of an attention block


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: every
    RMSNorm gain (q's and k's too), the router, the expert bias and the
    convolution's taps."""
    return name.rsplit("/", 1)[-1] in FP32


def kinds(sizes) -> list:
    """``[(mixer kind, feed-forward kind)]`` of the layers held."""
    return [(MIXER_OF[t], "dense" if i < sizes["num_dense_layers"] else "moe")
            for i, t in enumerate(sizes["layer_types"])]


def dims(sizes) -> dict:
    nq = sizes["num_attention_heads"]
    return {"H": sizes["hidden_size"], "I": sizes["intermediate_size"],
            "F": sizes["moe_intermediate_size"],
            "held": sizes["num_experts"],
            "experts": sizes["deployment"]["num_experts_published"],
            "first": sizes["deployment"]["expert_offset"],
            "nq": nq, "nkv": sizes["num_key_value_heads"],
            "d": sizes["hidden_size"] // nq, "K": sizes["conv_L_cache"]}


def init_weights(sizes, key):
    """N(0, 0.02) rounded to bfloat16 for every matrix and for the
    convolution's taps; gains 1; the expert bias 0."""
    d, ks = dims(sizes), kinds(sizes)
    n = {kind: sum(kind in pair for pair in ks) for kind in KINDS}
    H, V = d["H"], sizes["vocab_size"]
    per_layer = {
        "conv": {"in_proj": (H, 3 * H), "conv_w": (d["K"], H),
                 "out_proj": (H, H)},
        "attn": {"q": (H, d["nq"] * d["d"]), "k": (H, d["nkv"] * d["d"]),
                 "v": (H, d["nkv"] * d["d"]), "out": (d["nq"] * d["d"], H)},
        "dense": {"gate_up": (H, 2 * d["I"]), "down": (d["I"], H)},
        "moe": {"router": (H, d["experts"]),
                "w_gate_up": (d["held"], H, 2 * d["F"]),
                "w_down": (d["held"], d["F"], H)}}
    mats = {"embed": (V, H)}
    for kind, shapes in per_layer.items():
        if n[kind]:
            mats.update({f"{LAYER}{kind}/{name}": (n[kind],) + shape
                         for name, shape in shapes.items()})
    keys = C.named_keys(key, sorted(mats))
    w = {name: C.normal_bf16(keys[name], s) for name, s in mats.items()}
    w["norm_f"] = jnp.ones((H,), jnp.float32)
    for kind, count in n.items():
        if count:
            w[f"{LAYER}{kind}/norm"] = jnp.ones((count, H), jnp.float32)
    if n["attn"]:
        for name in ("q_norm", "k_norm"):
            w[f"{LAYER}attn/{name}"] = jnp.ones((n["attn"], d["d"]),
                                                jnp.float32)
    if n["moe"]:
        w[f"{LAYER}moe/expert_bias"] = jnp.zeros((n["moe"], d["experts"]),
                                                 jnp.float32)
    return w


# -- the mixers and feed-forwards, on one sequence (l, H) -----------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def short_conv(x, lw, sizes, mm):
    """``[B | C | x] = in_proj(h)``; ``u = B * x``; the causal taps as
    shifted multiplies (tap ``j`` reads token ``t - (K - 1) + j``, zeros
    before the start, no bias, no activation); ``y = C * v``;
    ``out_proj``."""
    l, H = x.shape
    K = lw["conv_w"].shape[0]
    proj = mm(x, lw["in_proj"])
    B, Cg, xs = proj[:, :H], proj[:, H:2 * H], proj[:, 2 * H:]
    padded = jnp.pad(B * xs, ((K - 1, 0), (0, 0)))
    v = sum(padded[j:j + l] * lw["conv_w"][j] for j in range(K))
    return mm(Cg * v, lw["out_proj"])


def rope_tables(l, hd, theta):
    """cos and sin of ``position * theta ** (-2 i / hd)``, each (l, hd)
    with the ``hd / 2`` angles twice (the half-split pairing)."""
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(l, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def rotate_half(t):
    half = t.shape[-1] // 2
    return jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)


def attention(x, lw, sizes, mm):
    """Causal softmax attention, ``nq`` query heads on ``nkv`` key/value
    heads (query head ``h`` reads head ``h // (nq // nkv)`` of k and v,
    repeated per group here); q and k normed over each head and turned by
    their position first; whole rows of the score matrix for a block of
    queries at a time."""
    d = dims(sizes)
    l = x.shape[0]
    nq, nkv, hd = d["nq"], d["nkv"], d["d"]
    eps = sizes["norm_eps"]
    cos, sin = rope_tables(l, hd, sizes["rope_parameters"]["rope_theta"])
    cos, sin = cos[:, None, :], sin[:, None, :]
    q = rms_norm(mm(x, lw["q"]).reshape(l, nq, hd), lw["q_norm"], eps)
    k = rms_norm(mm(x, lw["k"]).reshape(l, nkv, hd), lw["k_norm"], eps)
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    v = mm(x, lw["v"]).reshape(l, nkv, hd)
    q = q.transpose(1, 0, 2)                                   # nq l hd
    k = jnp.repeat(k.transpose(1, 0, 2), nq // nkv, axis=0)    # nq l hd
    v = jnp.repeat(v.transpose(1, 0, 2), nq // nkv, axis=0)
    bq = min(QUERY_BLOCK, l)
    pad = -l % bq
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(nq, -1, bq, hd).transpose(1, 0, 2, 3)
    starts = jnp.arange(qb.shape[0]) * bq

    def block(args):
        qi, start = args                                       # nq bq hd
        s = mm(qi, jnp.swapaxes(k, -1, -2)) * hd ** -0.5       # nq bq l
        row = start + jnp.arange(bq)[:, None]
        s = jnp.where(row >= jnp.arange(l)[None, :], s, C.FILL)
        return mm(jax.nn.softmax(s, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(block), (qb, starts))     # nb nq bq hd
    ctx = ctx.transpose(0, 2, 1, 3).reshape(-1, nq * hd)[:l]
    return mm(ctx, lw["out"])


def gated(x, w_gate_up, w_down, mm):
    """``W2 (silu(W1 h) * W3 h)`` with ``[W1 | W3]`` side by side."""
    F = w_down.shape[0]
    gu = mm(x, w_gate_up)
    return mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_down)


def dense_mlp(x, lw, sizes, mm):
    return gated(x, lw["gate_up"], lw["down"], mm)


def experts(x, lw, sizes, mm):
    d = dims(sizes)
    scores = jax.nn.sigmoid(jnp.matmul(x, lw["router"],
                                       precision=C.HIGHEST))
    # the selection bias is a buffer with no gradient, held at zero
    # (`departures`: its update rule is not in the file)
    by = scores
    if sizes["use_expert_bias"]:
        by = scores + jax.lax.stop_gradient(lw["expert_bias"])
    _, chosen = jax.lax.top_k(by, sizes["num_experts_per_tok"])
    weight = jnp.take_along_axis(scores, chosen, -1)
    if sizes["norm_topk_prob"]:
        # the 1e-6 as the family's modelling code has it (`assumed`)
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
    weight = weight * sizes["routed_scaling_factor"]

    def one(total, args):
        index, gate_up, down = args
        mine = jnp.sum(jnp.where(chosen == index, weight, 0.0), -1)
        return total + mine[:, None] * gated(x, gate_up, down, mm), None

    held = d["first"] + jnp.arange(d["held"])
    routed, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                             (held, lw["w_gate_up"], lw["w_down"]))
    return routed


PART = {"conv": short_conv, "attn": attention, "dense": dense_mlp,
        "moe": experts}


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """Mean next-token loss of one shard of rows; ``batch["ids"]`` is
    (B, S). No dropout: ``seed`` and ``masks`` are not used."""
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    eps = sizes["norm_eps"]
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
    for pair in kinds(sizes):
        for kind in pair:                 # the mixer, then the feed-forward
            def part(x, lw, kind=kind):
                return x + by_row(lambda row: PART[kind](
                    rms_norm(row, lw["norm"], eps), lw, sizes, mm), x)
            x = jax.checkpoint(part)(x, take(kind))

    def row_loss(row, row_ids):
        # the head is the embedding (tied: `assumed`)
        logits = mm(rms_norm(row, w["norm_f"], eps)[:-1], w["embed"].T)
        return jnp.sum(C.cross_entropy(logits, row_ids[1:]))

    total = jnp.sum(by_row(row_loss, x, ids))
    return total / (B * (S - 1))
