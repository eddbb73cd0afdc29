"""What the algorithm of the ``deepseek_v3`` family needs, from its
configuration's own keys (HF ``deepseek_v3`` ``config.json``, cut as the
file's ``reduced`` says): the matmul operations of one forward pass, which
layers are of which kind, and the work of one latent-attention call (at
the harness's rules for a call: ``harness/flops.py: ATTENTION_CALLS``).
Imports nothing of the program.

Latent attention's q and k heads are ``qk_nope_head_dim +
qk_rope_head_dim`` wide (192) and its v heads ``v_head_dim`` (128): QK^T
runs at the one width and PV at the other, and q, k, dq, dk cross HBM at
the one width and v, o, do, dv at the other. ``harness/flops.py:
attention_call`` counts a call at ONE head size, so :func:`attention_shape`
states no call it could count, and the call's work is
:func:`mla_attention_call`'s."""

from benchmark.harness.flops import BF16

# kind of call (``harness/flops.py: ATTENTION_CALLS``'s names): (pairs of
# matmuls, one at the q/k width and one at the v width; tensors crossing
# HBM at the q/k width, at the v width)
MLA_CALLS = {
    "attention_forward": (1, 2, 2),        # q, k | v in, o out
    "attention_backward": (2, 4, 4),       # q, k, dq, dk | v, o, do, dv
    "attention_backward_dq": (1, 3, 2),    # q, k in, dq out | v, do in
    "attention_backward_dkv": (1, 3, 3),   # q, k in, dk out | v, do, dv
}


def pattern(config: dict) -> list:
    """``[("attn", feed-forward kind)]`` of the layers held: the
    feed-forward is ``"dense"`` or ``"moe"``."""
    return [("attn", "dense" if i < config["first_k_dense_replace"]
             else "moe") for i in range(config["num_hidden_layers"])]


def _heads(config: dict):
    """(heads, q/k head size, v head size)."""
    return (config["num_attention_heads"],
            config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def mla_attention_call(config: dict, rows: int, seq: int, kind: str):
    """(operations, bytes) of ONE causal latent-attention call over
    ``rows`` sequences of ``seq`` tokens, at half (causal). The forward is
    QK^T at the q/k width and PV at the v width; ``dq`` is dP (at the v
    width) and dQ (at the q/k width), ``dkv`` dV (v) and dK (q/k), a fused
    backward all four; the scores recomputed inside a backward call are
    not counted. Bytes are what must cross HBM once when no score tensor
    is written, bfloat16: q, k, dq, dk at the q/k width; v, o, do, dv at
    the v width."""
    n, dqk, dv = _heads(config)
    pairs, at_qk, at_v = MLA_CALLS[kind]
    pair = 2 * rows * (seq * seq // 2) * n * (dqk + dv)
    return pairs * pair, rows * seq * n * (at_qk * dqk + at_v * dv) * BF16


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Every matmul of the layers and the head over ``rows`` sequences:
    latent attention's q projection, the kv latent and shared rotary key
    (``kv_a_proj_with_mqa``), the per-head keys and values from the latent
    (``kv_b_proj``) and the output projection; its causal core at half,
    QK^T at the q/k head width and PV at the v head width; the dense
    SwiGLU; a sparse layer's router over all the published experts, the
    shared experts on every token and the routed experts at the
    EXPECTATION of uniform routing (``num_experts_per_tok x held /
    published`` assignments a token); the untied head over the vocabulary
    held."""
    seq = traffic["seq"]
    tokens = rows * seq
    h, r = config["hidden_size"], config["kv_lora_rank"]
    n, dqk, dv = _heads(config)
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    published = config["deployment"]["n_routed_experts_published"]
    per_token = (config["num_experts_per_tok"] * config["n_routed_experts"]
                 / published)
    f = config["moe_intermediate_size"]
    attn = (2 * tokens * (h * n * dqk              # q
                          + h * (r + dr)           # [c | k_r]
                          + r * n * (dn + dv)      # [k_n | v]
                          + n * dv * h)            # o
            + 2 * rows * (seq * seq // 2) * n * (dqk + dv))
    part = {
        "attn": attn,
        "dense": 3 * 2 * tokens * h * config["intermediate_size"],
        "moe": (2 * tokens * h * published                       # router
                + 3 * 2 * tokens * h * f
                * config["n_shared_experts"]                     # shared
                + 3 * 2 * tokens * per_token * h * f),           # routed
    }
    head = 2 * rows * (seq - 1) * h * config["vocab_size"]
    return int(sum(part[kind] for pair in pattern(config) for kind in pair)
               + head)


def attention_shape(config: dict):
    """None: a latent-attention call has two head sizes, which the
    harness's rule for a call (one size) cannot count; the call's work is
    :func:`mla_attention_call`'s."""
    return None
