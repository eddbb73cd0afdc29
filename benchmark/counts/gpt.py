"""What the algorithm of the GPT-2 family needs, from its configuration's
own keys (HF GPT-2 ``config.json``): the matmul operations of one forward
pass, and the shape of its attention calls. Imports nothing of the
program."""

from benchmark.harness.flops import encoder_layer


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Pre-LN blocks with a 4x MLP and causal attention, and the tied
    head over every position but the last."""
    h, seq = config["n_embd"], traffic["seq"]
    layers = config["n_layer"] * encoder_layer(rows, seq, h, 4 * h,
                                               causal=True)
    head = 2 * rows * (seq - 1) * h * config["vocab_size"]
    return layers + head


def attention_shape(config: dict) -> dict:
    heads = config["n_head"]
    return {"query_heads": heads, "kv_heads": heads,
            "head_size": config["n_embd"] // heads, "causal": True}
