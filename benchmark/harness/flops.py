"""Operations and bytes the ALGORITHM needs, from shapes: matmul by
matmul, forward plus backward, recomputation not counted, causal
attention at half. ``work(config, traffic)`` dispatches on the
configuration's ``builder``.

A matmul of ``m x k`` by ``k x n`` is ``2 m k n`` operations; the backward
pass of a matmul is two matmuls of that size, so a trained step is three
times its forward matmuls. Embedding lookups, LayerNorm, softmax, GELU
and the optimizer are not matmuls and are not counted as operations.
"""

from __future__ import annotations

BF16 = 2   # bytes


def _encoder_layer(rows, seq, hidden, inter, causal):
    tokens = rows * seq
    proj = 4 * 2 * tokens * hidden * hidden          # q, k, v, out
    mlp = 2 * 2 * tokens * hidden * inter
    attn = 2 * 2 * rows * seq * seq * hidden          # QK^T and PV
    if causal:
        attn //= 2
    return proj + mlp + attn


def bert_forward_flops(c: dict, rows: int, seq: int, predictions: int) -> int:
    h, v = c["hidden_size"], c["vocab_size"]
    layers = c["num_hidden_layers"] * _encoder_layer(
        rows, seq, h, c["intermediate_size"], causal=False)
    picked = rows * predictions
    head = 2 * picked * h * h + 2 * picked * h * v     # transform, decoder
    pooled = 2 * rows * h * h + 2 * rows * h * 2       # pooler, NSP
    return layers + head + pooled


def gpt_forward_flops(c: dict, rows: int, seq: int) -> int:
    h = c["n_embd"]
    layers = c["n_layer"] * _encoder_layer(rows, seq, h, 4 * h, causal=True)
    head = 2 * rows * (seq - 1) * h * c["vocab_size"]  # tied head
    return layers + head


def step_flops(config: dict, traffic: dict, chips: int) -> int:
    """Model operations of one global step (all chips), forward and
    backward."""
    rows, seq = traffic["rows_per_chip"] * chips, traffic["seq"]
    if config["builder"] == "bert":
        fwd = bert_forward_flops(config, rows, seq,
                                 traffic["mlm"]["max_predictions"])
    elif config["builder"] == "gpt":
        fwd = gpt_forward_flops(config, rows, seq)
    else:
        raise KeyError(f"no operation count for builder "
                       f"{config['builder']!r}: add one to flops.py's "
                       f"successor file")
    return 3 * fwd


def sizes(config: dict):
    """(hidden, heads, causal) of a configuration."""
    if config["builder"] == "bert":
        return config["hidden_size"], config["num_attention_heads"], False
    return config["n_embd"], config["n_head"], True


ATTENTION_CALLS = {
    # kind of call: (matmuls of the forward's two counted, tensors moved)
    "attention_forward": (1, 4),        # q, k, v in; o out
    "attention_backward": (2, 8),       # q, k, v, o, do in; dq, dk, dv out
    "attention_backward_dq": (1, 5),    # q, k, v, do in; dq out
    "attention_backward_dkv": (1, 6),   # q, k, v, do in; dk, dv out
}


def attention_call(config: dict, rows: int, seq: int, kind: str):
    """(operations, bytes) of ONE attention call over ``rows`` sequences.
    Forward is QK^T and PV. Backward is the four matmuls the gradient
    needs (dP, dV, dQ, dK): all four in a fused backward call, two each
    in the split ``dq`` and ``dkv`` calls; the recomputation of the scores
    inside a flash backward is not counted. Bytes are what must cross HBM
    when no score tensor is written, in bfloat16."""
    hidden, _, causal = sizes(config)
    pair = 2 * 2 * rows * seq * seq * hidden      # two matmuls
    if causal:
        pair //= 2
    pairs, tensors = ATTENTION_CALLS[kind]
    return pairs * pair, tensors * rows * seq * hidden * BF16
