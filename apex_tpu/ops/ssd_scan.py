"""Mamba-2's selective state-space recurrence, computed in chunks (the
"state-space duality" algorithm of Dao & Gu, "Transformers are SSMs",
2024, section 6).

Per head ``h`` (group ``g = h // (H // G)`` of ``B``, ``C``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: (P, N)
    y_t = S_t C_t + D x_t

A sequence is cut into chunks of ``chunk`` tokens. Inside a chunk the
recurrence is the masked matmul ``((C B^T) * L) (dt x)`` with
``L[t, s] = exp(sum_{s < r <= t} dt_r A)``; across chunks one ``(P, N)``
state per chunk and head is passed on. Cost is linear in the sequence;
everything heavy is a matmul of 128-sized tiles. The backward pass is
the reverse-mode derivative of the same chunked program: it keeps one
state per CHUNK, never one per token.

The scan is XLA einsums (operands in the activation dtype, accumulation,
decays and states in float32). It runs under the ``ssm_scan`` scope of
:mod:`apex_tpu.profiler`, so a Pallas kernel that replaces it later is
read by the same per-layer metric.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu import profiler


def _decay(to, frm, strict: bool = False):
    """``exp(to[..., t] - frm[..., s])`` where ``t >= s`` (``t > s`` if
    ``strict``), 0 elsewhere; both (..., L) float32 running sums of
    log-decays. (..., L, L)."""
    L = to.shape[-1]
    keep = jnp.tril(jnp.ones((L, L), bool), -1 if strict else 0)
    return jnp.exp(jnp.where(keep, to[..., :, None] - frm[..., None, :],
                             -jnp.inf))


@jax.named_scope(profiler.SSM_SCAN)
def ssd_scan(x, dt, A, B, C, D, chunk: int = 128):
    """Chunked selective scan.

    Args:
      x:  (b, l, H, P) inputs per head.
      dt: (b, l, H) float32 step sizes (after softplus and clipping).
      A:  (H,) float32, negative.
      B, C: (b, l, G, N) input / output projections, ``H % G == 0``.
      D:  (H,) skip gain.
      chunk: tokens per chunk; ``l`` is padded up to a multiple of it
        (a padded token has ``dt = 0``: it neither decays nor feeds the
        state).

    Returns ``y``: (b, l, H, P) in ``x``'s dtype.
    """
    b, l, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    if H % G:
        raise ValueError(f"{H} heads are not a multiple of {G} groups")
    R = H // G
    pad = -l % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    c = (l + pad) // chunk
    dtype = x.dtype
    f32 = jnp.float32

    # (b, c, L, G, R, ...): heads as (group, head in group)
    xc = x.reshape(b, c, chunk, G, R, P)
    dtc = dt.astype(f32).reshape(b, c, chunk, G, R)
    Bc = B.reshape(b, c, chunk, G, N)
    Cc = C.reshape(b, c, chunk, G, N)
    a = dtc * A.astype(f32).reshape(G, R)                  # log-decay a token
    a = a.transpose(0, 1, 3, 4, 2)                         # (b, c, G, R, L)
    cs = jnp.cumsum(a, axis=-1)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)  # dt_t x_t

    # inside a chunk: ((C B^T) * L) (dt x); C B^T once per group
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=f32)
    scores = (cb[:, :, :, None] * _decay(cs, cs)).astype(dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, xdt,
                   preferred_element_type=f32)

    # the state each chunk adds: sum_s exp(a_{s+1..L}) B_s (dt x)_s^T
    to_end = jnp.exp(cs[..., -1:] - cs)                    # (b, c, G, R, L)
    xw = (xdt.astype(f32)
          * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(dtype)
    added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc, xw,
                       preferred_element_type=f32)

    # across chunks: the state entering chunk z is sum_{k < z} of chunk k's
    # added state decayed by the whole chunks between, exp(sum_{k<j<z} total_j)
    total = cs[..., -1].transpose(0, 2, 3, 1)              # (b, G, R, c)
    run = jnp.cumsum(total, axis=-1)
    before = jnp.pad(run, ((0, 0),) * 3 + ((1, 0),))[..., :-1]    # run[z-1]
    entering = jnp.einsum("bgrzk,bkgrpn->bzgrpn",
                          _decay(before, run, strict=True), added,
                          preferred_element_type=f32)

    # what the entering state gives inside the chunk: exp(a_{1..t}) C_t S
    decay_in = jnp.exp(cs).transpose(0, 1, 4, 2, 3)        # (b, c, L, G, R)
    y_off = jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, entering.astype(dtype),
                       preferred_element_type=f32)
    y = y + y_off * decay_in[..., None]
    y = y + xc.astype(f32) * D.astype(f32).reshape(G, R)[:, :, None]
    return y.astype(dtype).reshape(b, c * chunk, H, P)[:, :l]


def ssd_scan_reference(x, dt, A, B, C, D):
    """The recurrence token by token in float32 (tests only)."""
    b, l, H, P = x.shape
    G = B.shape[2]
    R = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(B.astype(f32), R, axis=2)
    Ch = jnp.repeat(C.astype(f32), R, axis=2)

    def step(S, t):
        xt, dtt, Bt, Ct = t
        decay = jnp.exp(dtt * A.astype(f32))                       # (b, H)
        S = S * decay[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xt * dtt[..., None], Bt)
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct)

    S0 = jnp.zeros((b, H, P, B.shape[3]), f32)
    xs = (x.astype(f32).transpose(1, 0, 2, 3), dt.astype(f32).transpose(1, 0, 2),
          Bh.transpose(1, 0, 2, 3), Ch.transpose(1, 0, 2, 3))
    _, y = jax.lax.scan(step, S0, xs)
    y = y.transpose(1, 0, 2, 3) + x.astype(f32) * D.astype(f32)[:, None]
    return y.astype(x.dtype)
