"""What the algorithm of the ``nemotron_h`` family needs, from its
configuration's own keys (HF ``nemotron_h`` ``config.json``, cut as the
file's ``reduced`` says): the matmul operations of one forward pass, the
shape of its attention calls, and the work of one grouped-matmul call and
of one scan call. Imports nothing of the program."""

BF16 = 2   # bytes


def pattern(config: dict) -> str:
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def mamba_sizes(config: dict) -> dict:
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    return {"heads": heads, "P": p, "G": g, "N": n, "inner": heads * p,
            "proj": 2 * heads * p + 2 * g * n + heads,
            "chunk": config["chunk_size"]}


def scan_flops(config: dict, tokens: int) -> int:
    """Matmul operations of ONE forward scan over ``tokens`` tokens: per
    chunk of L tokens, C B^T once a group (L x N x L), then per head the
    masked scores times the inputs (L x L x P), the state the chunk adds
    (N x L x P) and what the entering state gives (L x N x P). The
    recurrence across chunks (a matmul over the chunks of a sequence) is
    left out: it depends on the sequence and is under 3% of the rest."""
    m = mamba_sizes(config)
    L = m["chunk"]
    chunks = -(-tokens // L)
    per_chunk = (m["G"] * 2 * L * L * m["N"]
                 + m["heads"] * (2 * L * L * m["P"]
                                 + 2 * 2 * L * m["N"] * m["P"]))
    return chunks * per_chunk


def scan_call(config: dict, tokens: int, kind: str):
    """(operations, bytes) of one scan call: ``scan_forward`` or
    ``scan_backward`` (every matmul of the forward has two in the
    backward). Bytes are what must cross HBM in bfloat16: x in and y out
    at the inner width, B and C, and dt in float32; the backward reads
    them and the cotangent of y and writes their gradients."""
    m = mamba_sizes(config)
    row = (2 * m["inner"] + 2 * m["G"] * m["N"]) * BF16 + m["heads"] * 4
    factor = {"scan_forward": 1, "scan_backward": 2}[kind]
    return factor * scan_flops(config, tokens), factor * tokens * row


def grouped_mm_call(config: dict, assignments: float, kind: str):
    """(operations, bytes) of the grouped matmuls one expert layer runs
    in one pass over ``assignments`` token-expert pairs held here:
    ``experts_forward`` is the up and the down projection (two grouped
    matmuls of ``assignments x hidden x width``), ``experts_backward``
    their four transposes. Bytes: each grouped matmul reads or writes the
    rows at both widths and the held experts' matrix once, bfloat16."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    held = config["n_routed_experts"]
    one_ops = 2 * assignments * h * f
    one_bytes = (assignments * (h + f) + held * h * f) * BF16
    calls = {"experts_forward": 2, "experts_backward": 4}[kind]
    return calls * one_ops, calls * one_bytes


def forward_flops(config: dict, traffic: dict, rows: int) -> int:
    """Every matmul of the blocks and the head over ``rows`` sequences:
    the routed experts at the EXPECTATION of uniform routing
    (``num_experts_per_tok x held / published`` assignments a token),
    the scan's chunk matmuls as matmuls, causal attention at half."""
    seq = traffic["seq"]
    tokens = rows * seq
    h = config["hidden_size"]
    m = mamba_sizes(config)
    nq, nkv, d = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    published = config["deployment"]["n_routed_experts_published"]
    per_token = (config["num_experts_per_tok"] * config["n_routed_experts"]
                 / published)
    mamba = (2 * tokens * h * m["proj"] + 2 * tokens * m["inner"] * h
             + rows * scan_flops(config, seq))
    attention = (2 * tokens * h * (2 * nq * d + 2 * nkv * d)
                 + 2 * 2 * rows * seq * seq * nq * d // 2)
    experts = (2 * tokens * h * published                       # router
               + 2 * 2 * tokens * h
               * config["moe_shared_expert_intermediate_size"]
               + 2 * 2 * tokens * per_token * h
               * config["moe_intermediate_size"])
    pat = pattern(config)
    head = 2 * rows * (seq - 1) * h * config["vocab_size"]
    return int(pat.count("M") * mamba + pat.count("*") * attention
               + pat.count("E") * experts + head)


def attention_shape(config: dict) -> dict:
    return {"query_heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_size": config["head_dim"], "causal": True}
