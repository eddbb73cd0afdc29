"""Operations and bytes the ALGORITHM needs, from shapes: matmul by
matmul, forward plus backward, recomputation not counted, causal
attention at half.

What one model family needs is that family's own file, found by the
configuration's ``builder`` key: ``benchmark/counts/<builder>.py`` with

* ``forward_flops(config, traffic, rows) -> int``: the matmul operations
  of ONE forward pass over ``rows`` sequences of ``traffic["seq"]`` (the
  whole traffic is handed over: a head over gathered positions needs
  more of it than the length; an expert layer needs only its
  configuration: experts held, experts per token);
* ``attention_shape(config) -> dict or None``: ``query_heads``,
  ``kv_heads``, ``head_size``, ``causal`` of the attention calls, or None
  for a family with no attention kernel.

The rules stay here, the same for every family. A matmul of ``m x k`` by
``k x n`` is ``2 m k n`` operations; the backward pass of a matmul is two
matmuls of that size, so a trained step is three times its forward
matmuls. Embedding lookups, LayerNorm, softmax, GELU and the optimizer
are not matmuls and are not counted as operations.
"""

from __future__ import annotations

import functools

from .manifest import BENCH_DIR, load_module

BF16 = 2   # bytes


def counts(config: dict):
    """The count file of a configuration's family. A family without one
    is an error that names the file to add (never a default count)."""
    return _count_file(config["builder"])


@functools.lru_cache(maxsize=None)
def _count_file(builder: str):
    path = BENCH_DIR / "counts" / f"{builder}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"no operation count for builder {builder!r}: add "
            f"benchmark/counts/{builder}.py with forward_flops(config, "
            f"traffic, rows) and attention_shape(config)")
    return load_module(path, f"benchmark_counts_{builder}")


def encoder_layer(rows, seq, hidden, inter, causal):
    """Forward matmul operations of one transformer layer whose attention
    is as wide as its hidden size: four projections, a two-matmul MLP,
    QK^T and PV."""
    tokens = rows * seq
    proj = 4 * 2 * tokens * hidden * hidden          # q, k, v, out
    mlp = 2 * 2 * tokens * hidden * inter
    attn = 2 * 2 * rows * seq * seq * hidden          # QK^T and PV
    if causal:
        attn //= 2
    return proj + mlp + attn


def step_flops(config: dict, traffic: dict, chips: int) -> int:
    """Model operations of one global step (all chips), forward and
    backward."""
    rows = traffic["rows_per_chip"] * chips
    return 3 * counts(config).forward_flops(config, traffic, rows)


ATTENTION_CALLS = {
    # kind of call: (matmul pairs of the forward's one counted,
    #                tensors moved at the query width, at the key/value width)
    "attention_forward": (1, 2, 2),        # q in, o out; k, v in
    "attention_backward": (2, 4, 4),       # q, o, do in, dq out; k, v in, dk, dv out
    "attention_backward_dq": (1, 3, 2),    # q, do in, dq out; k, v in
    "attention_backward_dkv": (1, 2, 4),   # q, do in; k, v in, dk, dv out
}


def attention_call(config: dict, rows: int, seq: int, kind: str):
    """(operations, bytes) of ONE attention call over ``rows`` sequences.
    Forward is QK^T and PV. Backward is the four matmuls the gradient
    needs (dP, dV, dQ, dK): all four in a fused backward call, two each
    in the split ``dq`` and ``dkv`` calls; the recomputation of the scores
    inside a flash backward is not counted. Operations go by the query
    heads (grouping keys and values saves none). Bytes are what must
    cross HBM when no score tensor is written, in bfloat16: q, o and
    their gradients at the query width, k, v and theirs at the key/value
    width."""
    shape = counts(config).attention_shape(config)
    if shape is None:
        raise LookupError(f"builder {config['builder']!r} states no "
                          f"attention call (attention_shape is None)")
    q_width = shape["query_heads"] * shape["head_size"]
    kv_width = shape["kv_heads"] * shape["head_size"]
    pair = 2 * 2 * rows * seq * seq * q_width      # two matmuls
    if shape["causal"]:
        pair //= 2
    pairs, at_q, at_kv = ATTENTION_CALLS[kind]
    return pairs * pair, rows * seq * (at_q * q_width + at_kv * kv_width) * BF16
