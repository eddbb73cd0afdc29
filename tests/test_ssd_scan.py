"""The chunked selective scan (``apex_tpu.ops.ssd_scan``) against the
recurrence token by token, forward and backward."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops.ssd_scan import ssd_scan, ssd_scan_reference


def _inputs(l, H=8, P=16, G=2, N=32, b=2, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (b, l, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(k[3], (b, l, G, N), dtype)
    C = jax.random.normal(k[4], (b, l, G, N), dtype)
    D = 1.0 + 0.1 * jax.random.normal(k[5], (H,))
    return x, dt, A, B, C, D


def _rel(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                 / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-30))


# l = 300 and 45 are no multiple of their chunk: the tail chunk is padded
@pytest.mark.parametrize("l,chunk", [(256, 128), (300, 128), (45, 16),
                                     (64, 64), (16, 128)])
def test_forward_matches_the_recurrence(l, chunk):
    args = _inputs(l)
    with jax.default_matmul_precision("highest"):
        y = ssd_scan(*args, chunk=chunk)
        ref = ssd_scan_reference(*args)
    assert y.shape == ref.shape == args[0].shape
    # float32 throughout: the chunked sums differ from the running state
    # by summation order only
    assert _rel(y, ref) < 1e-5


@pytest.mark.parametrize("l,chunk", [(300, 128), (45, 16), (128, 32)])
def test_backward_matches_the_recurrence(l, chunk):
    args = _inputs(l, seed=1)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(scalar(lambda *a: ssd_scan(*a, chunk=chunk)),
                       argnums=tuple(range(6)))(*args)
        ref = jax.grad(scalar(ssd_scan_reference),
                       argnums=tuple(range(6)))(*args)
    for name, a, b in zip("x dt A B C D".split(), got, ref):
        # dt and A pass through exp(cumsum): 1e-4 of the largest entry
        assert _rel(a, b) < 2e-4, name


def test_groups_share_b_and_c_among_their_heads():
    """One group for all heads equals every head given the same B, C."""
    x, dt, A, B, C, D = _inputs(96, G=1)
    wide = (jnp.repeat(B, 8, axis=2), jnp.repeat(C, 8, axis=2))
    with jax.default_matmul_precision("highest"):
        one = ssd_scan(x, dt, A, B, C, D, chunk=32)
        each = ssd_scan(x, dt, A, *wide, D, chunk=32)
    assert _rel(one, each) < 1e-6
    with pytest.raises(ValueError, match="not a multiple"):
        ssd_scan(x, dt, A, B[:, :, :, :].repeat(3, axis=2),
                 C.repeat(3, axis=2), D)


def test_bfloat16_inputs_keep_float32_decays():
    """bf16 activations: operands round, the decays and the carried state
    do not; the result stays within bf16's rounding of the float32 one."""
    args = _inputs(256, seed=2)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    y = ssd_scan(*low, chunk=128)
    assert y.dtype == jnp.bfloat16
    ref = ssd_scan_reference(*(a.astype(jnp.float32) for a in low))
    assert _rel(y, ref) < 2e-2


def test_no_state_per_token_is_kept_for_the_backward_pass():
    """The residuals of the forward pass hold per-CHUNK states (c of them
    a head), none of shape (tokens, P, N)."""
    b, l, H, P, G, N, chunk = 1, 512, 4, 8, 1, 16, 64
    args = _inputs(l, H=H, P=P, G=G, N=N, b=b)
    _, vjp = jax.vjp(lambda *a: ssd_scan(*a, chunk=chunk), *args)
    sizes = [x.size for x in jax.tree.leaves(vjp)]
    assert max(sizes) < b * l * H * P * N       # a state per token
    assert max(sizes) <= b * l * H * max(chunk, P, N) * 2
