"""What the algorithm of the ``afmoe`` family needs, from its
configuration's own keys (HF ``afmoe`` ``config.json``, cut as the file's
``reduced`` says): the matmul operations of one forward pass, the shape of
its attention calls, which layers are of which kind, and the work of one
window-masked attention call (at the harness's rules for a call:
``harness/flops.py: ATTENTION_CALLS``). Imports nothing of the program.

A WINDOW layer (``sliding_attention``) lets the query at ``i`` see the
keys ``max(0, i - W + 1) .. i`` (``W = sliding_window``): ``sum_i min(i +
1, W)`` pairs a head over a sequence, not "causal at half". A GLOBAL layer
(``full_attention``) is causal and counted by the harness's rule, at
half."""

from benchmark.harness.flops import ATTENTION_CALLS, BF16


def pattern(config: dict) -> list:
    """``[(attention kind, feed-forward kind)]`` of the layers held: the
    attention is ``"window"`` or ``"global"``, the feed-forward
    ``"dense"`` or ``"moe"``."""
    kind = {"sliding_attention": "window", "full_attention": "global"}
    return [(kind[t], "dense" if i < config["num_dense_layers"] else "moe")
            for i, t in enumerate(config["layer_types"])]


def window_pairs(seq: int, window: int) -> int:
    """Visible (query, key) pairs of one head over one sequence under the
    sliding-window causal mask: ``sum over i of min(i + 1, window)``."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _widths(config: dict):
    d = config["head_dim"]
    return config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d


def window_attention_call(config: dict, rows: int, seq: int, kind: str):
    """(operations, bytes) of ONE window-masked attention call over
    ``rows`` sequences of ``seq`` tokens. Operations at the band's visible
    pairs alone: the forward is one pair of matmuls (QK^T and PV), ``dq``
    one (dP and dQ), ``dkv`` one (dV and dK), a fused backward both; the
    scores recomputed inside a backward call are not counted. Bytes are
    what must cross HBM once when no score tensor is written, bfloat16: q,
    o and their gradients at the query width, k, v and theirs at the
    key/value width."""
    q_width, kv_width = _widths(config)
    pair = 2 * 2 * rows * window_pairs(seq, config["sliding_window"]) \
        * q_width
    pairs, at_q, at_kv = ATTENTION_CALLS[kind]
    return pairs * pair, rows * seq * (at_q * q_width
                                       + at_kv * kv_width) * BF16


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Every matmul of the layers and the head over ``rows`` sequences:
    the q, gate and output projections at the query width, k and v at the
    key/value width; a window layer's attention at its visible pairs, a
    global layer's causal at half; the dense SwiGLU; a sparse layer's
    router over all the published experts, the shared expert on every
    token and the routed experts at the EXPECTATION of uniform routing
    (``num_experts_per_tok x held / published`` assignments a token); the
    untied head over the vocabulary held."""
    seq = traffic["seq"]
    tokens = rows * seq
    h = config["hidden_size"]
    q_width, kv_width = _widths(config)
    published = config["deployment"]["num_experts_published"]
    per_token = config["num_experts_per_tok"] * config["num_experts"] / published
    f = config["moe_intermediate_size"]
    projections = 2 * tokens * h * (3 * q_width + 2 * kv_width)
    part = {
        "window": projections + 2 * 2 * rows * window_pairs(
            seq, config["sliding_window"]) * q_width,
        "global": projections + 2 * 2 * rows * seq * seq * q_width // 2,
        "dense": 3 * 2 * tokens * h * config["intermediate_size"],
        "moe": (2 * tokens * h * published                       # router
                + 3 * 2 * tokens * h * f
                * config["num_shared_experts"]                   # shared
                + 3 * 2 * tokens * per_token * h * f),           # routed
    }
    head = 2 * rows * (seq - 1) * h * config["vocab_size"]
    return int(sum(part[kind] for pair in pattern(config) for kind in pair)
               + head)


def attention_shape(config: dict) -> dict:
    """The heads of the attention calls. The global layers' calls are
    causal; the window layers' work is ``window_attention_call``'s, not
    ``harness/flops.py: attention_call``'s."""
    return {"query_heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_size": config["head_dim"], "causal": True}
