"""What the algorithm of the ``lfm2`` family needs, from its
configuration's own keys (HF ``lfm2_moe`` ``config.json``, cut as the
file's ``reduced`` says): the matmul operations of one forward pass, the
shape of its attention calls, which layers are of which kind, and the
work of one call of the gated grouped matmuls and of the short
convolution's gate. Imports nothing of the program."""

BF16 = 2   # bytes


def pattern(config: dict) -> list:
    """``[(mixer, feed-forward)]`` of the layers held: the mixer is
    ``"conv"`` or ``"attn"``, the feed-forward ``"dense"`` or ``"moe"``."""
    mixer = {"conv": "conv", "full_attention": "attn"}
    return [(mixer[t], "dense" if i < config["num_dense_layers"] else "moe")
            for i, t in enumerate(config["layer_types"])]


def layers_of(config: dict, kind: str) -> list:
    """Positions of the layers that have a part of ``kind``."""
    return [i for i, pair in enumerate(pattern(config)) if kind in pair]


def short_conv_call(config: dict, tokens: int, kind: str):
    """(operations, bytes) of one call of the gate ``C * conv(B * x)``
    over ``tokens`` tokens. ``short_conv_forward``: a multiply for each
    gate and a multiply-add for each tap a channel; ``[B | C | x]`` in and
    ``y`` out, bfloat16. ``short_conv_backward``: the forward's arithmetic
    again and its transpose; ``[B | C | x]`` and the cotangent of ``y``
    in, the cotangent of ``[B | C | x]`` out (the taps' own gradient is
    ``K x width`` numbers)."""
    h, k = config["hidden_size"], config["conv_L_cache"]
    forward = tokens * h * (2 + 2 * k)
    if kind == "short_conv_forward":
        return forward, tokens * 4 * h * BF16
    if kind == "short_conv_backward":
        return 3 * forward, tokens * 7 * h * BF16
    raise KeyError(kind)


def grouped_mm_call(config: dict, assignments: float, kind: str):
    """(operations, bytes) of the grouped matmuls one expert layer runs
    in one pass over ``assignments`` token-expert pairs held here.
    ``experts_forward``: the gate and up projections as ONE grouped matmul
    of ``assignments x hidden x 2 width`` and the down projection of
    ``assignments x width x hidden``; ``experts_backward``: their four
    transposes (rows' and weights' gradients), twice the forward. Bytes:
    each grouped matmul reads or writes the rows at both of its widths and
    the held experts' matrix once, bfloat16."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["num_experts"]
    ops = 2 * assignments * h * 2 * f + 2 * assignments * f * h
    nbytes = ((assignments * (h + 2 * f) + held * h * 2 * f)
              + (assignments * (f + h) + held * f * h)) * BF16
    factor = {"experts_forward": 1, "experts_backward": 2}[kind]
    return factor * ops, factor * nbytes


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Every matmul of the layers and the head over ``rows`` sequences:
    the routed experts at the EXPECTATION of uniform routing
    (``num_experts_per_tok x held / published`` assignments a token),
    causal attention at half, the tied head over the vocabulary held."""
    seq = traffic["seq"]
    tokens = rows * seq
    h = config["hidden_size"]
    nq, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = h // nq
    published = config["deployment"]["num_experts_published"]
    per_token = config["num_experts_per_tok"] * config["num_experts"] / published
    part = {
        "conv": 2 * tokens * h * 3 * h + 2 * tokens * h * h,
        "attn": (2 * tokens * h * (2 * nq * d + 2 * nkv * d)
                 + 2 * 2 * rows * seq * seq * nq * d // 2),
        "dense": 3 * 2 * tokens * h * config["intermediate_size"],
        "moe": (2 * tokens * h * published                       # router
                + 3 * 2 * tokens * per_token * h
                * config["moe_intermediate_size"]),
    }
    head = 2 * rows * (seq - 1) * h * config["vocab_size"]
    return int(sum(part[kind] for pair in pattern(config) for kind in pair)
               + head)


def attention_shape(config: dict) -> dict:
    nq = config["num_attention_heads"]
    return {"query_heads": nq, "kv_heads": config["num_key_value_heads"],
            "head_size": config["hidden_size"] // nq, "causal": True}
