"""Gated short convolution: ``y = C * conv(B * x)``.

The mixer of the ``lfm2`` family's ``conv`` layers
(``apex_tpu/models/lfm2.py``). Its input projection gives three parts of
equal width, ``B``, ``C`` and ``x``; ``u = B * x`` is convolved depthwise
and causally over ``K`` taps (``v_t = sum_j w_j u_{t - (K - 1) + j}``,
zeros before the sequence's start, no bias, no activation) and gated
again, ``y = C * v``.

Two Pallas kernels, ``short_conv_fwd`` and ``short_conv_bwd``, each ONE
pass over its operands in float32 arithmetic over the storage dtype: the
forward reads ``B``, ``C``, ``x`` and writes ``y``; the backward reads
them and the cotangent of ``y``, does ``u`` and ``v`` again on chip and
writes the three cotangents (the taps' own gradient leaves as one small
partial sum a block). A block of tokens reads the few rows it needs of
its neighbour (the last rows before it for the causal taps, the first
after it for their transpose) as a second, 16-row window on the same
array. Nothing of the block's width is kept for the backward pass but
``B``, ``C``, ``x`` themselves. (XLA's own fusion of the three-shift form
writes ``u`` to HBM in float32 and reads it back three times: 12 widths
a token forward where the kernel moves 4, and the whole step of the
``lfm2_24b_a2b.lm8192`` cell ran 1.7% slower with it on the chip;
``PERF.md`` section 6, PR 34.)

The kernels are picked by the platform the program is lowered for
(``jax.lax.platform_dependent``: compiled for a TPU, interpreted anywhere
else), so a compile for a described chip gets the kernel the chip runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import profiler

_HALO = 16            # rows of the neighbouring block a block may read
_FWD_ROWS, _BWD_ROWS, _LANES = 512, 256, 512


def gated_short_conv_reference(b, c, x, taps):
    """The three-shift form in plain ``jax.numpy``, for autodiff to
    differentiate: what :func:`gated_short_conv` is held to."""
    K, l = taps.shape[0], x.shape[1]
    u = b.astype(jnp.float32) * x.astype(jnp.float32)
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    v = sum(padded[:, j:j + l] * taps[j].astype(jnp.float32)
            for j in range(K))
    return (c.astype(jnp.float32) * v).astype(x.dtype)


def _rows_before(u, halo, steps):
    """``u`` delayed by ``steps`` rows; the rows that come in are the last
    of ``halo``."""
    if not steps:
        return u
    rolled = pltpu.roll(u, steps, 0)
    first = jax.lax.broadcasted_iota(jnp.int32, halo.shape, 0) < steps
    head = jnp.where(first, pltpu.roll(halo, steps, 0), rolled[:_HALO])
    if u.shape[0] == _HALO:
        return head
    return jnp.concatenate([head, rolled[_HALO:]], axis=0)


def _rows_after(g, halo, steps):
    """``g`` advanced by ``steps`` rows; the rows that come in are the
    first of ``halo``."""
    if not steps:
        return g
    rows = g.shape[0]
    rolled = pltpu.roll(g, rows - steps, 0)
    last = jax.lax.broadcasted_iota(jnp.int32, halo.shape, 0) >= (
        _HALO - steps)
    tail = jnp.where(last, pltpu.roll(halo, _HALO - steps, 0),
                     rolled[rows - _HALO:])
    if rows == _HALO:
        return tail
    return jnp.concatenate([rolled[:rows - _HALO], tail], axis=0)


def _product(a_ref, b_ref, live=None):
    """``a * b`` of two blocks in float32; zero where ``live`` says the
    block lies outside the sequence."""
    p = a_ref[0].astype(jnp.float32) * b_ref[0].astype(jnp.float32)
    return p if live is None else jnp.where(live, p, 0.0)


def _fwd_kernel(w_ref, b_ref, c_ref, x_ref, b_before, x_before, y_ref, *, K):
    u = _product(b_ref, x_ref)
    before = _product(b_before, x_before, pl.program_id(2) > 0)
    v = sum(_rows_before(u, before, K - 1 - j) * w_ref[j:j + 1, :]
            for j in range(K))
    y_ref[0] = (c_ref[0].astype(jnp.float32) * v).astype(y_ref.dtype)


def _bwd_kernel(w_ref, b_ref, c_ref, x_ref, dy_ref, b_before, x_before,
                c_after, dy_after, db_ref, dc_ref, dx_ref, dw_ref, *, K):
    i = pl.program_id(2)
    u = _product(b_ref, x_ref)
    before = _product(b_before, x_before, i > 0)
    dv = _product(dy_ref, c_ref)
    after = _product(dy_after, c_after, i < pl.num_programs(2) - 1)
    delayed = [_rows_before(u, before, K - 1 - j) for j in range(K)]
    v = sum(delayed[j] * w_ref[j:j + 1, :] for j in range(K))
    du = sum(_rows_after(dv, after, K - 1 - j) * w_ref[j:j + 1, :]
             for j in range(K))
    db_ref[0] = (du * x_ref[0].astype(jnp.float32)).astype(db_ref.dtype)
    dx_ref[0] = (du * b_ref[0].astype(jnp.float32)).astype(dx_ref.dtype)
    dc_ref[0] = (dy_ref[0].astype(jnp.float32) * v).astype(dc_ref.dtype)
    for j in range(K):
        dw_ref[0, 0, j:j + 1, :] = jnp.sum(dv * delayed[j], axis=0,
                                           keepdims=True)


def _blocks(l, width, rows):
    """(padded tokens, rows of a block, lanes of a block)."""
    rows = min(rows, -(-l // _HALO) * _HALO)
    lanes = _LANES if width % _LANES == 0 else width
    return -(-l // rows) * rows, rows, lanes


def _padded(t, tokens):
    return jnp.pad(t, ((0, 0), (0, tokens - t.shape[1]), (0, 0)))


def _specs(rows, lanes, n_blocks):
    per = rows // _HALO
    block = pl.BlockSpec((1, rows, lanes), lambda n, ch, i: (n, i, ch))
    before = pl.BlockSpec(
        (1, _HALO, lanes),
        lambda n, ch, i: (n, jnp.maximum(i * per - 1, 0), ch))
    after = pl.BlockSpec(
        (1, _HALO, lanes),
        lambda n, ch, i: (n, jnp.minimum((i + 1) * per,
                                         n_blocks * per - 1), ch))
    return block, before, after


def _forward(b, c, x, taps, interpret):
    n, l, width = x.shape
    K = taps.shape[0]
    tokens, rows, lanes = _blocks(l, width, _FWD_ROWS)
    b, c, x = (_padded(t, tokens) for t in (b, c, x))
    grid = (n, width // lanes, tokens // rows)
    block, before, _ = _specs(rows, lanes, grid[2])
    taps_spec = pl.BlockSpec((K, lanes), lambda n, ch, i: (0, ch))
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K), grid=grid,
        in_specs=[taps_spec, block, block, block, before, before],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret,
        name="short_conv_fwd",
    )(taps.astype(jnp.float32), b, c, x, b, x)
    return y[:, :l]


def _backward(b, c, x, taps, dy, interpret):
    n, l, width = x.shape
    K = taps.shape[0]
    tokens, rows, lanes = _blocks(l, width, _BWD_ROWS)
    b, c, x, dy = (_padded(t, tokens) for t in (b, c, x, dy))
    grid = (n, width // lanes, tokens // rows)
    block, before, after = _specs(rows, lanes, grid[2])
    taps_spec = pl.BlockSpec((K, lanes), lambda n, ch, i: (0, ch))
    like = jax.ShapeDtypeStruct(x.shape, x.dtype)
    db, dc, dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K), grid=grid,
        in_specs=[taps_spec, block, block, block, block, before, before,
                  after, after],
        out_specs=[block, block, block,
                   pl.BlockSpec((1, 1, K, lanes),
                                lambda n, ch, i: (n, i, 0, ch))],
        out_shape=[like, like, like,
                   jax.ShapeDtypeStruct((n, grid[2], K, width),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret,
        name="short_conv_bwd",
    )(taps.astype(jnp.float32), b, c, x, dy, b, x, c, dy)
    return (db[:, :l], dc[:, :l], dx[:, :l],
            jnp.sum(dw, axis=(0, 1)).astype(taps.dtype))


def _by_platform(fn, *args):
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(fn, interpret=False),
        default=functools.partial(fn, interpret=True))


@jax.custom_vjp
def gated_short_conv(b, c, x, taps):
    """``b``, ``c``, ``x`` ``(batch, tokens, width)``, ``taps`` ``(K,
    width)`` float32 with ``K <= 17`` -> ``c * conv(b * x)``,
    ``(batch, tokens, width)`` in ``x``'s dtype."""
    if taps.shape[0] > _HALO + 1:
        raise ValueError(f"{taps.shape[0]} taps reach past the "
                         f"{_HALO} rows a block reads of its neighbour")
    with jax.named_scope(profiler.CONV_GATE):
        return _by_platform(_forward, b, c, x, taps)


def _fwd(b, c, x, taps):
    return gated_short_conv(b, c, x, taps), (b, c, x, taps)


def _bwd(res, dy):
    with jax.named_scope(profiler.CONV_GATE):
        return _by_platform(_backward, *res, dy)


gated_short_conv.defvjp(_fwd, _bwd)
