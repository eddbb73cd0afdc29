"""What the algorithm of the ``sdar`` family needs, from its
configuration's own keys (HF ``sdar_moe`` ``config.json``, cut as the
file's ``reduced`` says, and the objective's ``block_length``): the matmul
operations of one forward pass of the block-diffusion objective, the shape
of its attention calls, and the work of one block-masked attention call.
Imports nothing of the program.

The objective feeds a row of ``L`` tokens as two copies, ``2 L`` positions,
under a mask that lets the query at ``r`` see ``L^2 + g L`` of the ``4
L^2`` pairs a head has (``g = block_length``): the ``L g`` pairs inside
the noised blocks, the ``L (L - g) / 2`` pairs of noised queries on
strictly earlier clean blocks, the ``L (L + g) / 2`` block-causal pairs of
the clean copy. In the last layer held the clean copy's queries feed
nothing (the head reads the noised positions alone): its clean-copy
query projection, attention rows, output projection and feed-forward are
left out of the count whether or not the program skips them, so that
``step.mfu`` can only be understated."""

BF16 = 2   # bytes


def live_pairs(config: dict, seq: int, clean_queries: bool = True) -> int:
    """Visible (query, key) pairs of one head over one row of ``seq``
    tokens fed as two copies; without the clean copy's queries, the noised
    queries' alone."""
    g = config["block_length"]
    noised = seq * g + seq * (seq - g) // 2
    return noised + (seq * (seq + g) // 2 if clean_queries else 0)


def _widths(config: dict):
    d = config["head_dim"]
    return config["num_attention_heads"] * d, \
        config["num_key_value_heads"] * d


def blockdiff_attention_call(config: dict, rows: int, seq: int, kind: str,
                             clean_queries: bool = True):
    """(operations, bytes) of ONE block-masked attention call over
    ``rows`` rows of ``seq`` tokens (``2 seq`` keys; ``2 seq`` queries, or
    ``seq`` where ``clean_queries`` is False: the last layer's call).
    Operations at the live pairs alone: the forward is one pair of matmuls
    (QK^T and PV), ``dq`` one (dP and dQ), ``dkv`` one (dV and dK), a fused
    backward both; the scores recomputed inside a backward call are not
    counted. Bytes are what must cross HBM once when no score tensor is
    written, bfloat16: q, o and their gradients at the query width, k, v
    and theirs at the key/value width."""
    q_width, kv_width = _widths(config)
    pair = 2 * 2 * rows * live_pairs(config, seq, clean_queries) * q_width
    pairs, at_q, at_kv = {
        "attention_forward": (1, 2, 2),        # q in, o out; k, v in
        "attention_backward": (2, 4, 4),       # q, o, do in, dq out; k, v in, dk, dv out
        "attention_backward_dq": (1, 3, 2),    # q, do in, dq out; k, v in
        "attention_backward_dkv": (1, 2, 4),   # q, do in; k, v in, dk, dv out
    }[kind]
    q_tokens = rows * seq * (2 if clean_queries else 1)
    return pairs * pair, (q_tokens * at_q * q_width
                          + rows * 2 * seq * at_kv * kv_width) * BF16


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Every matmul of one forward pass over ``rows`` rows: ``2 seq``
    positions a row through the projections, the router and the routed
    experts (at the EXPECTATION of uniform routing,
    ``num_experts_per_tok x held / published`` assignments a position),
    attention at its live pairs, the head over the ``seq`` noised
    positions; the last layer without its clean copy's query side."""
    seq = traffic["seq"]
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    q_width, kv_width = _widths(config)
    published = config["deployment"]["num_experts_published"]
    per_token = config["num_experts_per_tok"] * config["num_experts"] / published

    def layer(clean_queries):
        q_tokens = rows * seq * (2 if clean_queries else 1)
        return (2 * q_tokens * h * q_width * 2                 # q, o
                + 2 * rows * 2 * seq * h * kv_width * 2        # k, v
                + 2 * 2 * rows * live_pairs(config, seq, clean_queries)
                * q_width                                      # QK^T, PV
                + 2 * q_tokens * h * published                 # router
                + 3 * 2 * q_tokens * per_token * h * f)        # experts

    n = config["num_hidden_layers"]
    head = 2 * rows * seq * h * config["vocab_size"]
    return int((n - 1) * layer(True) + layer(False) + head)


def attention_shape(config: dict) -> dict:
    """The heads of the attention calls. The calls are block-masked, not
    causal: their work is ``blockdiff_attention_call``'s, not
    ``harness/flops.py: attention_call``'s."""
    return {"query_heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_size": config["head_dim"], "causal": False}
