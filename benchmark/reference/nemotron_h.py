"""Plain reference of next-token training of a ``nemotron_h`` stack as
the ``nemotron_twotower_30b_a3b`` configuration states it: blocks
``x + mixer(RMSNorm(x))`` by the letters of ``hybrid_override_pattern``
(``M`` Mamba-2, ``E`` experts, ``*`` grouped-query attention), no
position term, an untied head over the vocabulary slice held here.
float32 ``jax.numpy``; imports nothing of the program.

What is cut is cut here exactly as in the program: the first
``num_hidden_layers`` letters of the pattern; ``n_routed_experts`` experts
HELD (``deployment.expert_offset`` onward) of the
``deployment.n_routed_experts_published`` the router scores, so the layer
adds its own experts' part and leaves the absent experts' part out.

Memory: every block is checkpointed and runs one sequence at a time
inside (so the loop over sequences carries the gradient of ONE block's
weights, never of the model's), attention in blocks of queries, the held
experts one at a time, the head and loss per sequence.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C

LAYER = "layers/"
KINDS = {"M": ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log",
               "D", "gate_norm", "out_proj"),
         "E": ("norm", "router", "w_up", "w_down", "shared_up",
               "shared_down"),
         "*": ("norm", "q", "k", "v", "out")}
NAME_OF = {"M": "M", "E": "E", "*": "A"}        # layers/<kind>/<tensor>
FP32 = {"norm", "norm_f", "gate_norm", "router", "A_log", "D", "dt_bias"}
QUERY_BLOCK = 128      # queries of an attention block


def keeps_float32(name: str) -> bool:
    """Tensors amp O2 leaves out of the bfloat16 model copy: every
    RMSNorm gain, the router, ``A_log``, ``D``, ``dt_bias``."""
    return name.rsplit("/", 1)[-1] in FP32


def pattern(sizes) -> str:
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def dims(sizes) -> dict:
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    g, n = sizes["n_groups"], sizes["ssm_state_size"]
    return {"H": sizes["hidden_size"], "inner": heads * p, "heads": heads,
            "P": p, "G": g, "N": n, "conv": heads * p + 2 * g * n,
            "proj": 2 * heads * p + 2 * g * n + heads,
            "held": sizes["n_routed_experts"],
            "experts": sizes["deployment"]["n_routed_experts_published"],
            "first": sizes["deployment"]["expert_offset"],
            "F": sizes["moe_intermediate_size"],
            "Fs": sizes["moe_shared_expert_intermediate_size"],
            "nq": sizes["num_attention_heads"],
            "nkv": sizes["num_key_value_heads"], "d": sizes["head_dim"]}


def init_weights(sizes, key):
    """N(0, 0.02) rounded to bfloat16 for every matrix; ``A_log`` =
    log(uniform[1, 16]); ``dt_bias`` = inverse softplus of a log-uniform
    step in [``time_step_min``, ``time_step_max``]; ``D`` and gains 1;
    conv bias 0."""
    d, pat = dims(sizes), pattern(sizes)
    n = {k: pat.count(k) for k in "ME*"}
    H, V = d["H"], sizes["vocab_size"]
    mats = {"embed": (V, H), "head": (H, V)}
    if n["M"]:
        m = LAYER + "M/"
        mats.update({m + "in_proj": (n["M"], H, d["proj"]),
                     m + "conv_w": (n["M"], sizes["conv_kernel"], d["conv"]),
                     m + "out_proj": (n["M"], d["inner"], H)})
    if n["E"]:
        e = LAYER + "E/"
        mats.update({e + "router": (n["E"], H, d["experts"]),
                     e + "w_up": (n["E"], d["held"], H, d["F"]),
                     e + "w_down": (n["E"], d["held"], d["F"], H),
                     e + "shared_up": (n["E"], H, d["Fs"]),
                     e + "shared_down": (n["E"], d["Fs"], H)})
    if n["*"]:
        a = LAYER + "A/"
        mats.update({a + "q": (n["*"], H, d["nq"] * d["d"]),
                     a + "k": (n["*"], H, d["nkv"] * d["d"]),
                     a + "v": (n["*"], H, d["nkv"] * d["d"]),
                     a + "out": (n["*"], d["nq"] * d["d"], H)})
    keys = C.named_keys(key, sorted(mats) + ["A_log", "dt"])
    w = {name: C.normal_bf16(keys[name], s) for name, s in mats.items()}
    w["norm_f"] = jnp.ones((H,), jnp.float32)
    for kind, count in n.items():
        if count:
            w[LAYER + NAME_OF[kind] + "/norm"] = jnp.ones((count, H),
                                                          jnp.float32)
    if n["M"]:
        m, shape = LAYER + "M/", (n["M"], d["heads"])
        w[m + "conv_b"] = jnp.zeros((n["M"], d["conv"]), jnp.float32)
        w[m + "A_log"] = jnp.log(jax.random.uniform(
            keys["A_log"], shape, jnp.float32, 1.0, 16.0))
        lo, hi = math.log(sizes["time_step_min"]), math.log(
            sizes["time_step_max"])
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            keys["dt"], shape, jnp.float32) * (hi - lo) + lo),
            sizes["time_step_floor"])
        w[m + "dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
        w[m + "D"] = jnp.ones(shape, jnp.float32)
        w[m + "gate_norm"] = jnp.ones((n["M"], d["inner"]), jnp.float32)
    return w


# -- the three mixers, on one sequence (l, H) -----------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def decay(to, frm, strict=False):
    """``exp(to[..., t] - frm[..., s])`` where ``t >= s`` (``t > s`` if
    ``strict``), else 0, of two running sums of log-decays; (..., L, L)."""
    L = to.shape[-1]
    keep = jnp.tril(jnp.ones((L, L), bool), -1 if strict else 0)
    return jnp.exp(jnp.where(keep, to[..., :, None] - frm[..., None, :],
                             -jnp.inf))


def chunked_scan(x, dt, A, B, Cm, chunk, mm):
    """The state-space recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t`` of the ``R`` heads that share one ``B``, ``C``,
    in chunks, as the Mamba-2 paper's minimal listing does it: the chunk's
    own tokens by a masked matmul, one state per chunk carried on. ``x``
    (l, R, P), ``dt`` (l, R), ``A`` (R,), ``B``, ``Cm`` (l, N)."""
    l, R, P = x.shape
    N = B.shape[-1]
    pad = -l % chunk
    if pad:      # a padded token has dt = 0: no decay, nothing added
        x, dt, B, Cm = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                        for t in (x, dt, B, Cm))
    c = (l + pad) // chunk
    x = x.reshape(c, chunk, R, P).transpose(0, 2, 1, 3)         # c R L P
    dtc = dt.reshape(c, chunk, R).transpose(0, 2, 1)            # c R L
    Bc = jnp.broadcast_to(B.reshape(c, 1, chunk, N), (c, R, chunk, N))
    Cc = jnp.broadcast_to(Cm.reshape(c, 1, chunk, N), (c, R, chunk, N))
    cs = jnp.cumsum(dtc * A[:, None], -1)      # running log-decay in a chunk
    xdt = x * dtc[..., None]
    # 1. inside each chunk: ((C B^T) * decay) (dt x)
    y = mm(mm(Cc, jnp.swapaxes(Bc, -1, -2)) * decay(cs, cs), xdt)
    # 2. the state each chunk adds, decayed to the chunk's end
    to_end = jnp.exp(cs[..., -1:] - cs)
    added = mm(jnp.swapaxes(Bc, -1, -2), xdt * to_end[..., None])  # c R N P
    # 3. the state entering chunk z: chunk k < z's, decayed by the whole
    #    chunks between
    run = jnp.cumsum(cs[..., -1].T, -1)                         # R c
    before = jnp.pad(run, ((0, 0), (1, 0)))[:, :-1]
    entering = mm(decay(before, run, strict=True),
                  added.transpose(1, 0, 2, 3).reshape(R, c, N * P))
    entering = entering.reshape(R, c, N, P).transpose(1, 0, 2, 3)
    # 4. what the entering state gives inside the chunk
    y = y + mm(Cc, entering) * jnp.exp(cs)[..., None]
    return y.transpose(0, 2, 1, 3).reshape(c * chunk, R, P)[:l]


def mamba(x, lw, sizes, mm):
    """The mixer a group at a time: group ``g`` of ``B`` and ``C``, its
    ``R`` heads of ``x``, ``z`` and ``dt`` and its group of the gated norm
    meet the other groups only in ``out_proj``, so each runs from its own
    columns of ``in_proj`` and of the convolution (one group's
    intermediates are live, an eighth of the mixer's)."""
    d = dims(sizes)
    l, H = x.shape
    inner, G, N, P = d["inner"], d["G"], d["N"], d["P"]
    R, wide = d["heads"] // G, d["inner"] // G

    def by_group(t, parts):
        """Columns ``[z | x | B | C | dt]`` (those named in ``parts``) of
        a tensor, regrouped as (G, ..., a group's columns)."""
        sizes_of = {"z": (inner, wide), "x": (inner, wide), "B": (G * N, N),
                    "C": (G * N, N), "dt": (d["heads"], R)}
        out, at = [], 0
        for name in parts:
            total, mine = sizes_of[name]
            cols = t[..., at:at + total]
            out.append(jnp.moveaxis(
                cols.reshape(cols.shape[:-1] + (G, mine)), -2, 0))
            at += total
        return jnp.concatenate(out, axis=-1)

    lo, hi = sizes["time_step_limit"]
    K = lw["conv_w"].shape[0]

    def group(args):
        w_in, conv_w, conv_b, dt_bias, a_log, skip, gain = args
        proj = mm(x, w_in)
        z, xbc, dt = (proj[:, :wide], proj[:, wide:2 * wide + 2 * N],
                      proj[:, 2 * wide + 2 * N:])
        padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[j:j + l] * conv_w[j]
                              for j in range(K)) + conv_b)
        xs = xbc[:, :wide].reshape(l, R, P)
        B, Cm = xbc[:, wide:wide + N], xbc[:, wide + N:]
        dt = jnp.clip(jax.nn.softplus(dt + dt_bias), lo, hi)
        y = chunked_scan(xs, dt, -jnp.exp(a_log), B, Cm,
                         sizes["chunk_size"], mm)
        y = (y + xs * skip[:, None]).reshape(l, wide) * jax.nn.silu(z)
        return rms_norm(y, gain, sizes["norm_eps"])

    y = jax.lax.map(jax.checkpoint(group), (
        by_group(lw["in_proj"], ("z", "x", "B", "C", "dt")),
        by_group(lw["conv_w"], ("x", "B", "C")),
        by_group(lw["conv_b"], ("x", "B", "C")),
        lw["dt_bias"].reshape(G, R), lw["A_log"].reshape(G, R),
        lw["D"].reshape(G, R), lw["gate_norm"].reshape(G, wide)))
    return mm(y.transpose(1, 0, 2).reshape(l, inner), lw["out_proj"])


def attention(x, lw, sizes, mm):
    """Causal softmax attention, ``nq`` query heads on ``nkv`` key/value
    heads (query head ``h`` reads group ``h // (nq // nkv)``), whole rows
    of the score matrix for a block of queries at a time."""
    d = dims(sizes)
    l = x.shape[0]
    nq, nkv, hd = d["nq"], d["nkv"], d["d"]
    per = nq // nkv
    q = mm(x, lw["q"]).reshape(l, nkv, per, hd).transpose(1, 2, 0, 3)
    k = mm(x, lw["k"]).reshape(l, nkv, hd).transpose(1, 0, 2)
    v = mm(x, lw["v"]).reshape(l, nkv, hd).transpose(1, 0, 2)
    bq = min(QUERY_BLOCK, l)
    pad = -l % bq
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(nkv, per, -1, bq, hd).transpose(2, 0, 1, 3, 4)
    starts = jnp.arange(qb.shape[0]) * bq

    def block(args):
        qi, start = args                                 # (nkv, per, bq, hd)
        s = mm(qi.reshape(nkv, per * bq, hd),
               jnp.swapaxes(k, -1, -2)) * hd ** -0.5     # (nkv, per * bq, l)
        row = start + jnp.tile(jnp.arange(bq), per)[:, None]
        s = jnp.where(row >= jnp.arange(l)[None, :], s, C.FILL)
        return mm(jax.nn.softmax(s, axis=-1), v).reshape(nkv, per, bq, hd)

    ctx = jax.lax.map(jax.checkpoint(block), (qb, starts))
    ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(-1, nq * hd)[:l]
    return mm(ctx, lw["out"])


def experts(x, lw, sizes, mm):
    d = dims(sizes)
    k = sizes["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(x, lw["router"],
                                       precision=C.HIGHEST))
    # the selection bias is a buffer held at zero (`departures`)
    _, chosen = jax.lax.top_k(scores, k)
    weight = jnp.take_along_axis(scores, chosen, -1)
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    weight = weight * sizes["routed_scaling_factor"]

    def one(total, args):
        index, up, down = args
        mine = jnp.sum(jnp.where(chosen == index, weight, 0.0), -1)
        return total + mine[:, None] * mm(relu2(mm(x, up)), down), None

    held = d["first"] + jnp.arange(d["held"])
    routed, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                             (held, lw["w_up"], lw["w_down"]))
    return routed + mm(relu2(mm(x, lw["shared_up"])), lw["shared_down"])


MIXER = {"M": mamba, "E": experts, "*": attention}


def loss(w, batch, seed, sizes, masks, precision="fp32", rows=None):
    """Mean next-token loss of one shard of rows; ``batch["ids"]`` is
    (B, S). No dropout: ``seed`` and ``masks`` are not used."""
    mm = lambda a, b: C.matmul(a, b, precision)  # noqa: E731
    pat, eps = pattern(sizes), sizes["norm_eps"]
    ids = batch["ids"]
    if rows is not None:
        ids = ids[:rows]
    B, S = ids.shape
    # one split per stacked tensor: its transpose is one concatenate
    apart = {n: [t[0] for t in jnp.split(a, a.shape[0])]
             for n, a in w.items() if n.startswith(LAYER)}
    layers, seen = [], {k: 0 for k in KINDS}
    for kind in pat:
        prefix = LAYER + NAME_OF[kind] + "/"
        layers.append((kind, {n: apart[prefix + n][seen[kind]]
                              for n in KINDS[kind]}))
        seen[kind] += 1

    def by_row(fn, *per_row):
        """``fn`` on one sequence at a time, recomputed in the backward
        pass; the weights ``fn`` closes over are one block's."""
        return jax.lax.map(lambda args: jax.checkpoint(fn)(*args), per_row)

    x = w["embed"][ids]                                   # (B, S, H)
    for kind, lw in layers:
        def block(x, lw, kind=kind):
            return x + by_row(lambda row: MIXER[kind](
                rms_norm(row, lw["norm"], eps), lw, sizes, mm), x)
        x = jax.checkpoint(block)(x, lw)

    def row_loss(row, row_ids):
        logits = mm(rms_norm(row, w["norm_f"], eps)[:-1], w["head"])
        return jnp.sum(C.cross_entropy(logits, row_ids[1:]))

    total = jnp.sum(by_row(row_loss, x, ids))
    return total / (B * (S - 1))
