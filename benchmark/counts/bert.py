"""What the algorithm of the BERT family needs, from its configuration's
own keys (google-research ``bert_config.json``): the matmul operations of
one forward pass, and the shape of its attention calls. Imports nothing
of the program."""

from benchmark.harness.flops import encoder_layer


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """24 post-LN encoder layers over ``rows`` sequences, the MLM head
    over the gathered positions only, the pooler and the NSP head over
    one row each."""
    h, v = config["hidden_size"], config["vocab_size"]
    layers = config["num_hidden_layers"] * encoder_layer(
        rows, traffic["seq"], h, config["intermediate_size"], causal=False)
    picked = rows * traffic["mlm"]["max_predictions"]
    head = 2 * picked * h * h + 2 * picked * h * v     # transform, decoder
    pooled = 2 * rows * h * h + 2 * rows * h * 2       # pooler, NSP
    return layers + head + pooled


def attention_shape(config: dict) -> dict:
    heads = config["num_attention_heads"]
    return {"query_heads": heads, "kv_heads": heads,
            "head_size": config["hidden_size"] // heads, "causal": False}
