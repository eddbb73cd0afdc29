"""Device time a step of the recomputed forward (``rematted_computation``
in the op's path: what activation checkpointing costs), heads, amp and
optimizer excluded, over the traced slice."""

from benchmark.harness import scopes


def read(ctx):
    return scopes.phase_ms(ctx, "recompute")
