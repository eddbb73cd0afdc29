"""Plain reference of block-diffusion training of an ``sdar_moe`` stack as
the ``sdar_30b_a3b_chat`` configuration states it. float32 ``jax.numpy``;
imports nothing of the program.

The objective. A row ``x0`` of ``L`` ids in blocks of ``g =
block_length`` positions, ``b(i) = i // g``. Block ``b`` draws ``t_b ~
U(0, 1)``, ``p_b = (1 - floor) t_b + floor``; position ``i`` is masked
with probability ``p_b(i)``; ``xt = where(masked, MASK, x0)`` (:func:`noise`,
the formula of the configuration's ``assumed``). The stack reads ``[xt ;
x0]``, ``2 L`` positions, both copies at rotary positions ``0 .. L - 1``;
the query at ``r`` sees the key at ``c`` iff (both noised and ``b(r) ==
b(c)``) or (``r`` noised, ``c`` clean, ``b(c) < b(r)``) or (both clean and
``b(c) <= b(r)``) (:func:`visible`, built densely for a block of queries
at a time). The logits at noised position ``i`` predict ``x0[i]``; ``loss =
(1 / (rows x L)) sum over masked i of CE_i / p_b(i)``.

A layer is ``x + attention(RMSNorm(x))`` then ``x + experts(RMSNorm(x))``:
grouped-query attention with per-head RMSNorm of q and k and rotary
positions; gated SiLU experts scored by softmax over all the published
experts, the ``num_experts_per_tok`` largest, weights normalised over the
chosen; one RMSNorm after the last layer; an untied head over the
vocabulary slice held here. What is cut is cut here exactly as in the
program: ``num_experts`` experts HELD (``deployment.expert_offset``
onward) of the ``deployment.num_experts_published`` the router scores, so
the layer adds its own experts' part and leaves the absent experts' out.
The last layer is computed whole (its clean rows feed nothing and get no
gradient; the program may skip them).

Memory: every layer is checkpointed and runs one row at a time inside,
attention in blocks of queries, the held experts one at a time, the head
and loss per row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

LAYER = "layers/"
PER_LAYER = ("ln1", "q", "k", "v", "q_norm", "k_norm", "o", "ln2", "router",
             "w_gate_up", "w_down")
FP32 = {"ln1", "ln2", "norm_f", "q_norm", "k_norm", "router"}
QUERY_BLOCK = 128      # queries of an attention block


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: every
    RMSNorm gain (q's and k's too) and the router."""
    return name.rsplit("/", 1)[-1] in FP32


def dims(sizes) -> dict:
    return {"H": sizes["hidden_size"], "F": sizes["moe_intermediate_size"],
            "held": sizes["num_experts"],
            "experts": sizes["deployment"]["num_experts_published"],
            "first": sizes["deployment"]["expert_offset"],
            "nq": sizes["num_attention_heads"],
            "nkv": sizes["num_key_value_heads"], "d": sizes["head_dim"],
            "n": sizes["num_hidden_layers"], "V": sizes["vocab_size"]}


def init_weights(sizes, key):
    """N(0, 0.02) rounded to bfloat16 for every matrix; gains 1."""
    d = dims(sizes)
    H, n = d["H"], d["n"]
    mats = {"embed": (d["V"], H), "lm_head": (H, d["V"]),
            LAYER + "q": (n, H, d["nq"] * d["d"]),
            LAYER + "k": (n, H, d["nkv"] * d["d"]),
            LAYER + "v": (n, H, d["nkv"] * d["d"]),
            LAYER + "o": (n, d["nq"] * d["d"], H),
            LAYER + "router": (n, H, d["experts"]),
            LAYER + "w_gate_up": (n, d["held"], H, 2 * d["F"]),
            LAYER + "w_down": (n, d["held"], d["F"], H)}
    keys = C.named_keys(key, sorted(mats))
    w = {name: C.normal_bf16(keys[name], s) for name, s in mats.items()}
    w["norm_f"] = jnp.ones((H,), jnp.float32)
    for name in ("ln1", "ln2"):
        w[LAYER + name] = jnp.ones((n, H), jnp.float32)
    for name in ("q_norm", "k_norm"):
        w[LAYER + name] = jnp.ones((n, d["d"]), jnp.float32)
    return w


# -- the objective ---------------------------------------------------------------

def noise(rows, L, seed, g, floor):
    """``(masked bool (rows, L), p float32 (rows, L))`` of a step: row
    ``r`` draws from ``fold_in(PRNGKey(seed), r)`` split in two, ``t =
    uniform((L // g,))`` and ``u = uniform((L,))``, float32 (threefry);
    ``p = (1 - floor) t + floor`` once a block; ``masked = u < p``."""
    root = jax.random.PRNGKey(seed, impl="threefry2x32")
    masked, p = [], []
    for r in range(rows):
        k_t, k_u = jax.random.split(jax.random.fold_in(root, r))
        t = jax.random.uniform(k_t, (L // g,), jnp.float32)
        u = jax.random.uniform(k_u, (L,), jnp.float32)
        p_r = jnp.repeat((1.0 - floor) * t + floor, g)
        masked.append(u < p_r)
        p.append(p_r)
    return jnp.stack(masked), jnp.stack(p)


def visible(r, c, L, g):
    """Does the query at ``r`` see the key at ``c`` (positions among the
    ``2 L`` of ``[noised ; clean]``; arrays that broadcast)."""
    r_noised, c_noised = r < L, c < L
    rb, cb = (r % L) // g, (c % L) // g
    return ((r_noised & c_noised & (rb == cb))
            | (r_noised & ~c_noised & (cb < rb))
            | (~r_noised & ~c_noised & (cb <= rb)))


# -- the layer, on one row's two copies (2L, H) ------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def rope_tables(L, hd, theta):
    """cos and sin of ``position * theta ** (-2 i / hd)``, each (2 L, hd):
    positions ``0 .. L - 1`` for the noised copy and again for the clean
    one, the ``hd / 2`` angles twice (the half-split pairing)."""
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    angle = jnp.concatenate([angle, angle], axis=0)
    return jnp.cos(angle), jnp.sin(angle)


def rotate_half(t):
    half = t.shape[-1] // 2
    return jnp.concatenate([-t[..., half:], t[..., :half]], axis=-1)


def attention(x, lw, sizes, mm):
    """Softmax attention under the block-diffusion mask, ``nq`` query
    heads on ``nkv`` key/value heads (query head ``h`` reads head ``h //
    (nq // nkv)`` of k and v, repeated per group here); q and k normed
    over each head and turned by their position first; whole rows of the
    score matrix, and of the mask, for a block of queries at a time."""
    d = dims(sizes)
    two_l = x.shape[0]
    L, g = two_l // 2, sizes["block_length"]
    nq, nkv, hd = d["nq"], d["nkv"], d["d"]
    eps = sizes["rms_norm_eps"]
    cos, sin = rope_tables(L, hd, sizes["rope_theta"])
    cos, sin = cos[:, None, :], sin[:, None, :]
    q = rms_norm(mm(x, lw["q"]).reshape(two_l, nq, hd), lw["q_norm"], eps)
    k = rms_norm(mm(x, lw["k"]).reshape(two_l, nkv, hd), lw["k_norm"], eps)
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    v = mm(x, lw["v"]).reshape(two_l, nkv, hd)
    q = q.transpose(1, 0, 2)                                   # nq 2L hd
    k = jnp.repeat(k.transpose(1, 0, 2), nq // nkv, axis=0)    # nq 2L hd
    v = jnp.repeat(v.transpose(1, 0, 2), nq // nkv, axis=0)
    bq = min(QUERY_BLOCK, two_l)
    pad = -two_l % bq
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(nq, -1, bq, hd).transpose(1, 0, 2, 3)
    starts = jnp.arange(qb.shape[0]) * bq

    def block(args):
        qi, start = args                                       # nq bq hd
        s = mm(qi, jnp.swapaxes(k, -1, -2)) * hd ** -0.5       # nq bq 2L
        # padded query rows (beyond 2 L) read as clean rows of late blocks
        row = jnp.minimum(start + jnp.arange(bq), two_l - 1)[:, None]
        s = jnp.where(visible(row, jnp.arange(two_l)[None, :], L, g), s,
                      C.FILL)
        return mm(jax.nn.softmax(s, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(block), (qb, starts))     # nb nq bq hd
    ctx = ctx.transpose(0, 2, 1, 3).reshape(-1, nq * hd)[:two_l]
    return mm(ctx, lw["o"])


def gated(x, w_gate_up, w_down, mm):
    """``W_down (silu(W_gate h) * W_up h)`` with ``[W_gate | W_up]`` side
    by side."""
    F = w_down.shape[0]
    gu = mm(x, w_gate_up)
    return mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_down)


def experts(x, lw, sizes, mm):
    d = dims(sizes)
    scores = jax.nn.softmax(jnp.matmul(x, lw["router"],
                                       precision=C.HIGHEST), axis=-1)
    weight, chosen = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)

    def one(total, args):
        index, gate_up, down = args
        mine = jnp.sum(jnp.where(chosen == index, weight, 0.0), -1)
        return total + mine[:, None] * gated(x, gate_up, down, mm), None

    held = d["first"] + jnp.arange(d["held"])
    routed, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                             (held, lw["w_gate_up"], lw["w_down"]))
    return routed


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """The block-diffusion loss of one shard of rows; ``batch["ids"]`` is
    (B, L) clean rows, ``seed`` the step's (the noise is drawn from it).
    No dropout: ``masks`` is not used."""
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    eps = sizes["rms_norm_eps"]
    ids = batch["ids"]
    if rows is not None:
        ids = ids[:rows]
    B, L = ids.shape
    masked, p = noise(B, L, seed, sizes["block_length"],
                      sizes["noise"]["floor"])
    xt = jnp.where(masked, sizes["mask_token_id"], ids)
    # one split per stacked tensor: its transpose is one concatenate
    apart = {n: [t[0] for t in jnp.split(a, a.shape[0])]
             for n, a in w.items() if n.startswith(LAYER)}

    def by_row(fn, *per_row):
        """``fn`` on one row at a time, recomputed in the backward pass;
        the weights ``fn`` closes over are one layer's."""
        return jax.lax.map(lambda args: jax.checkpoint(fn)(*args), per_row)

    x = w["embed"][jnp.concatenate([xt, ids], axis=1)]       # (B, 2L, H)
    for i in range(sizes["num_hidden_layers"]):
        lw = {n: apart[LAYER + n][i] for n in PER_LAYER}
        for norm, part in (("ln1", attention), ("ln2", experts)):
            def residual(x, lw, norm=norm, part=part):
                return x + by_row(lambda row: part(
                    rms_norm(row, lw[norm], eps), lw, sizes, mm), x)
            x = jax.checkpoint(residual)(x, lw)

    def row_loss(row, row_ids, row_weight):
        # the noised copy's positions; position i predicts x0[i]
        logits = mm(rms_norm(row[:L], w["norm_f"], eps), w["lm_head"])
        return jnp.sum(C.cross_entropy(logits, row_ids) * row_weight)

    total = jnp.sum(by_row(row_loss, x, ids, jnp.where(masked, 1.0 / p, 0.0)))
    return total / (B * L)
