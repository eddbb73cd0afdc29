"""Plain reference of next-token training of an ``afmoe`` stack as the
``trinity_mini`` configuration states it. float32 ``jax.numpy``; imports
nothing of the program.

``x0 = E[ids] sqrt(H)``. A layer is ``x + RMSNorm(attention(RMSNorm(x)))``
then ``x + RMSNorm(ffn(RMSNorm(x)))`` (four norms: in, post-attention,
pre-feed-forward, post-feed-forward). Attention: ``nq`` query heads on
``nkv`` key/value heads of ``d``; q and k RMS-normed over each head; a
WINDOW layer (``sliding_attention``) turns q and k by their position
(rotary, rotate_half pairing) and lets the query at ``i`` see the key at
``j`` iff ``0 <= i - j < sliding_window``; a GLOBAL layer
(``full_attention``) has no position term and is causal; the context is
gated, ``o * sigmoid(x W_g)``, before the output projection. The
feed-forward is a dense SwiGLU in the first ``num_dense_layers`` layers;
after them ``shared(h) + sum over the chosen experts held here of w_i
E_i(h)`` with ``s = sigmoid(h W_r)`` over all the published experts, the
``num_experts_per_tok`` largest of ``s + bias`` (the bias held at zero),
``w_i = route_scale s_i / (sum of the chosen s + 1e-20)``. One RMSNorm
after the last layer; an untied head over the vocabulary slice held here.

What is cut is cut here exactly as in the program: ``layer_types`` and
``num_dense_layers`` as the file gives them; ``num_experts`` experts HELD
(``deployment.expert_offset`` onward) of the
``deployment.num_experts_published`` the router scores, so the layer adds
its own experts' part and leaves the absent experts' part out; the shared
expert is every token's and counts once.

Memory: every layer is checkpointed and runs one sequence at a time
inside, attention in blocks of queries, the held experts one at a time,
the head and loss per sequence.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .lfm2 import gated as swiglu, rms_norm, rope_tables, rotate_half

LAYER = "layers/"
# every layer has attention; a feed-forward is dense or sparse, and a
# stacked tensor runs over the layers that have its kind, in stack order
KINDS = {"attn": ("ln_in", "q", "k", "v", "gate", "q_norm", "k_norm", "o",
                  "ln_post"),
         "dense": ("ln_pre", "gate_up", "down", "ln_post"),
         "moe": ("ln_pre", "router", "expert_bias", "w_gate_up", "w_down",
                 "shared_gate_up", "shared_down", "ln_post")}
FP32 = {"ln_in", "ln_post", "ln_pre", "norm_f", "q_norm", "k_norm", "router",
        "expert_bias"}
WINDOW, GLOBAL = "sliding_attention", "full_attention"
QUERY_BLOCK = 128      # queries of an attention block


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: every
    RMSNorm gain (q's and k's too), the router and the expert bias."""
    return name.rsplit("/", 1)[-1] in FP32


def kinds(sizes) -> list:
    """``[("attn", feed-forward kind)]`` of the layers held."""
    return [("attn", "dense" if i < sizes["num_dense_layers"] else "moe")
            for i in range(len(sizes["layer_types"]))]


def dims(sizes) -> dict:
    return {"H": sizes["hidden_size"], "I": sizes["intermediate_size"],
            "F": sizes["moe_intermediate_size"],
            "S": sizes["moe_intermediate_size"] * sizes["num_shared_experts"],
            "held": sizes["num_experts"],
            "experts": sizes["deployment"]["num_experts_published"],
            "first": sizes["deployment"]["expert_offset"],
            "nq": sizes["num_attention_heads"],
            "nkv": sizes["num_key_value_heads"], "d": sizes["head_dim"]}


def init_weights(sizes, key):
    """N(0, 0.02) rounded to bfloat16 for every matrix; gains 1; the
    expert bias 0."""
    d, ks = dims(sizes), kinds(sizes)
    n = {kind: sum(kind in pair for pair in ks) for kind in KINDS}
    H, V = d["H"], sizes["vocab_size"]
    qw, kvw = d["nq"] * d["d"], d["nkv"] * d["d"]
    per_layer = {
        "attn": {"q": (H, qw), "k": (H, kvw), "v": (H, kvw), "gate": (H, qw),
                 "o": (qw, H)},
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
    for name in ("q_norm", "k_norm"):
        w[f"{LAYER}attn/{name}"] = jnp.ones((n["attn"], d["d"]), jnp.float32)
    if n["moe"]:
        w[f"{LAYER}moe/expert_bias"] = jnp.zeros((n["moe"], d["experts"]),
                                                 jnp.float32)
    return w


# -- attention and the feed-forwards, on one sequence (l, H) --------------------
# (RMSNorm, the rotary tables and rotate_half, SwiGLU with [W_gate | W_up]
# side by side: the lfm2 reference's own)

def visible(i, j, window):
    """Does the query at ``i`` see the key at ``j``: causal, and within
    ``window`` positions where a window is given (None: a global layer)."""
    seen = i >= j
    if window is not None:
        seen = seen & (i - j < window)
    return seen


def attention(x, lw, sizes, mm, window_layer):
    """Gated softmax attention, ``nq`` query heads on ``nkv`` key/value
    heads (query head ``h`` reads head ``h // (nq // nkv)`` of k and v,
    repeated per group here); q and k normed over each head, turned by
    their position in a window layer; whole rows of the score matrix for a
    block of queries at a time."""
    d = dims(sizes)
    l = x.shape[0]
    nq, nkv, hd = d["nq"], d["nkv"], d["d"]
    eps = sizes["rms_norm_eps"]
    q = rms_norm(mm(x, lw["q"]).reshape(l, nq, hd), lw["q_norm"], eps)
    k = rms_norm(mm(x, lw["k"]).reshape(l, nkv, hd), lw["k_norm"], eps)
    window = None
    if window_layer:
        cos, sin = rope_tables(l, hd, sizes["rope_theta"])
        cos, sin = cos[:, None, :], sin[:, None, :]
        q = q * cos + rotate_half(q) * sin
        k = k * cos + rotate_half(k) * sin
        window = sizes["sliding_window"]
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
        s = jnp.where(visible(row, jnp.arange(l)[None, :], window), s,
                      C.FILL)
        return mm(jax.nn.softmax(s, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(block), (qb, starts))     # nb nq bq hd
    ctx = ctx.transpose(0, 2, 1, 3).reshape(-1, nq * hd)[:l]
    return mm(gated_output(ctx, mm(x, lw["gate"])), lw["o"])


def gated_output(ctx, g):
    """The output gate: ``o * sigmoid(x W_g)``."""
    return ctx * jax.nn.sigmoid(g)


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
    if sizes["route_norm"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * sizes["route_scale"]

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
    if sizes["mup_enabled"]:
        x = x * sizes["hidden_size"] ** 0.5
    for layer_type, (_, ffn) in zip(sizes["layer_types"], kinds(sizes)):
        parts = ((lambda h, lw, lt=layer_type: attention(
                      h, lw, sizes, mm, lt == WINDOW), "attn", "ln_in"),
                 (lambda h, lw, f=ffn: FFN[f](h, lw, sizes, mm), ffn,
                  "ln_pre"))
        for fn, kind, norm_in in parts:
            def part(x, lw, fn=fn, norm_in=norm_in):
                return x + by_row(lambda row: rms_norm(fn(
                    rms_norm(row, lw[norm_in], eps), lw), lw["ln_post"],
                    eps), x)
            x = jax.checkpoint(part)(x, take(kind))

    def row_loss(row, row_ids):
        logits = mm(rms_norm(row, w["norm_f"], eps)[:-1], w["lm_head"])
        return jnp.sum(C.cross_entropy(logits, row_ids[1:]))

    total = jnp.sum(by_row(row_loss, x, ids))
    return total / (B * (S - 1))
