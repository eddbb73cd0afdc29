"""Flash attention parity vs the composed-softmax reference (pattern:
the reference's fused-vs-composed kernel tests, SURVEY.md §4; component:
contrib fmha / fast_multihead_attn)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Interpret-mode Pallas kernels on CPU are the suite's dominant cost
# (~5 min for this tier alone); fast CI runs -m "not slow", the full
# run and the on-TPU tier keep the coverage. The causal multi-tile
# cases at the end of the file are NOT slow-marked: they run in tier 1.
slow = pytest.mark.slow

from apex_tpu.ops.flash_attention import flash_attention, mha_reference


def _mk(B, H, Sq, Sk, D, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, H, Sq, D), dtype),
            jax.random.normal(ks[1], (B, H, Sk, D), dtype),
            jax.random.normal(ks[2], (B, H, Sk, D), dtype))


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


@pytest.mark.parametrize("shape,causal,use_mask", [
    ((2, 4, 128, 64), False, False),
    ((2, 4, 128, 64), False, True),
    ((1, 2, 256, 64), True, False),
    ((2, 2, 100, 64), False, True),      # unaligned seq
    ((1, 1, 37, 32), True, False),       # unaligned seq + head dim
    ((1, 2, 640, 64), False, True),      # multi-block online softmax
])
@slow
def test_parity_fwd_bwd(shape, causal, use_mask):
    B, H, S, D = shape
    q, k, v = _mk(B, H, S, S, D)
    km = ((jax.random.uniform(jax.random.PRNGKey(9), (B, S)) < 0.3)
          if use_mask else None)
    scale = 1.0 / np.sqrt(D)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, km, causal, scale))(
        q, k, v)
    ref = mha_reference(q, k, v, km, causal, scale)
    assert _max_err(out, ref) < 2e-5

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, km, causal, scale) * 1.3)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, km, causal, scale) * 1.3)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, gr):
        assert _max_err(a, b) < 3e-4


@slow
@pytest.mark.parametrize("S", [128, 100, 37])
def test_fully_masked_rows_are_finite(S):
    """All keys masked -> uniform distribution (finite), matching the
    reference's -30000 fill semantics, not NaN. Unaligned S regression:
    wrapper-padded keys must NOT count toward the uniform denominator
    (an Sk=100 row block pads to 128; the old code returned outputs
    scaled by 100/128)."""
    q, k, v = _mk(1, 1, S, S, 64)
    km = jnp.ones((1, S), bool)
    out = flash_attention(q, k, v, km, False, 0.125)
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = mha_reference(q, k, v, km, False, 0.125)
    assert _max_err(out, ref) < 2e-5


@slow
def test_bf16_io_fp32_accumulation():
    q, k, v = _mk(2, 2, 256, 256, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, None, False, 0.125)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), None, False, 0.125)
    assert _max_err(out, ref) < 0.02


@slow
def test_bert_model_flash_matches_composed():
    """Model-level: BertModel with the flash path forced on vs off."""
    from apex_tpu.models import BertConfig, BertForPreTraining

    rng = np.random.RandomState(0)
    B, S = 2, 64
    kw = dict(hidden_dropout=0.0, attention_dropout=0.0,
              max_position_embeddings=S, num_layers=2)
    cfg_flash = BertConfig.tiny(flash_min_seq=1, **kw)
    cfg_comp = BertConfig.tiny(flash_attention=False, **kw)

    ids = jnp.asarray(rng.randint(0, cfg_flash.vocab_size, (B, S)))
    types = jnp.zeros((B, S), jnp.int32)
    attn = jnp.asarray((rng.rand(B, S) > 0.2).astype(np.int32))

    m1 = BertForPreTraining(cfg_flash)
    m2 = BertForPreTraining(cfg_comp)
    params = m1.init(jax.random.PRNGKey(0), ids, types, attn)["params"]

    mlm1, nsp1 = m1.apply({"params": params}, ids, types, attn)
    mlm2, nsp2 = m2.apply({"params": params}, ids, types, attn)
    assert _max_err(mlm1, mlm2) < 5e-4
    assert _max_err(nsp1, nsp2) < 5e-4

    def loss1(p):
        a, b = m1.apply({"params": p}, ids, types, attn)
        return jnp.sum(a.astype(jnp.float32)) * 1e-3 + jnp.sum(b)

    def loss2(p):
        a, b = m2.apply({"params": p}, ids, types, attn)
        return jnp.sum(a.astype(jnp.float32)) * 1e-3 + jnp.sum(b)

    g1 = jax.grad(loss1)(params)
    g2 = jax.grad(loss2)(params)
    errs = jax.tree.map(_max_err, g1, g2)
    assert max(jax.tree.leaves(errs)) < 5e-3


@slow
def test_flash_attention_with_lse_fwd_bwd():
    """(out, lse) variant: lse matches composed logsumexp, and grads are
    correct INCLUDING a live lse cotangent (the ring-merge consumer)."""
    from apex_tpu.ops.flash_attention import (
        _with_lse_reference,
        flash_attention_with_lse,
    )

    q, k, v = _mk(1, 2, 100, 100, 64, seed=5)
    out, lse = flash_attention_with_lse(q, k, v, None, True, 0.125)
    ref_out, ref_lse = _with_lse_reference(q, k, v, None, True, 0.125)
    assert lse.shape == (1, 2, 1, 100)
    assert _max_err(out, ref_out) < 2e-5
    assert _max_err(lse, ref_lse) < 2e-5

    def loss_k(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, None, True, 0.125)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(l))

    def loss_r(q, k, v):
        o, l = _with_lse_reference(q, k, v, None, True, 0.125)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(l))

    gk = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        assert _max_err(a, b) < 3e-4


# ------------------------------------------------------------- dropout

@slow
def test_dropout_parity_with_extracted_mask():
    """Fused dropout == composed attention using the kernel's OWN
    keep-mask (flash_dropout_keep_mask reproduces the in-kernel bits
    exactly on either backend), fwd and bwd."""
    from apex_tpu.ops.flash_attention import (
        flash_dropout_keep_mask,
        mha_with_mask_reference,
    )

    B, H, S, D = 2, 3, 128, 64
    rate, seed = 0.1, 1234
    q, k, v = _mk(B, H, S, S, D)
    km = jax.random.uniform(jax.random.PRNGKey(9), (B, S)) < 0.2
    scale = 1.0 / np.sqrt(D)

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, km, False, scale, rate, seed))(q, k, v)
    keep = flash_dropout_keep_mask(B, H, S, S, rate, seed)
    ref = mha_with_mask_reference(q, k, v, keep, km, False, scale, rate)
    assert _max_err(out, ref) < 2e-5

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, km, False, scale,
                                       rate, seed) * 1.3)

    def loss_ref(q, k, v):
        return jnp.sum(mha_with_mask_reference(q, k, v, keep, km, False,
                                               scale, rate) * 1.3)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, gr):
        assert _max_err(a, b) < 3e-4


@slow
def test_dropout_parity_unaligned_multiblock():
    """Dropout mask replay across tile boundaries: unaligned S forces
    padding, S=640 forces the multi-block online-softmax recurrence."""
    from apex_tpu.ops.flash_attention import (
        flash_dropout_keep_mask,
        mha_with_mask_reference,
    )

    for (S, causal) in [(100, False), (640, True)]:
        B, H, D = 1, 2, 64
        rate, seed = 0.15, 77
        q, k, v = _mk(B, H, S, S, D, seed=3)
        scale = 1.0 / np.sqrt(D)
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, None, causal, scale, rate, seed))(q, k, v)
        keep = flash_dropout_keep_mask(B, H, S, S, rate, seed)
        ref = mha_with_mask_reference(q, k, v, keep, None, causal, scale,
                                      rate)
        assert _max_err(out, ref) < 2e-5

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, causal, scale,
                                           rate, seed))

        def loss_ref(q, k, v):
            return jnp.sum(mha_with_mask_reference(q, k, v, keep, None,
                                                   causal, scale, rate))

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g, gr):
            assert _max_err(a, b) < 3e-4


@slow
def test_dropout_mask_statistics_and_seed_sensitivity():
    """Keep-rate ~= 1-rate; different seeds give different masks; the
    same seed is deterministic."""
    from apex_tpu.ops.flash_attention import flash_dropout_keep_mask

    B, H, S = 2, 4, 256
    rate = 0.1
    m1 = np.asarray(flash_dropout_keep_mask(B, H, S, S, rate, 5))
    m2 = np.asarray(flash_dropout_keep_mask(B, H, S, S, rate, 5))
    m3 = np.asarray(flash_dropout_keep_mask(B, H, S, S, rate, 6))
    assert (m1 == m2).all()
    assert (m1 != m3).any()
    keep_frac = m1.mean()
    assert abs(keep_frac - (1 - rate)) < 0.01


@slow
def test_dropout_zero_rate_matches_no_dropout():
    B, H, S, D = 1, 2, 128, 64
    q, k, v = _mk(B, H, S, S, D)
    a = flash_attention(q, k, v, None, False, 0.125)
    b = flash_attention(q, k, v, None, False, 0.125, 0.0, 3)
    assert _max_err(a, b) == 0.0


@slow
def test_dropout_requires_seed():
    B, H, S, D = 1, 1, 128, 64
    q, k, v = _mk(B, H, S, S, D)
    with pytest.raises(ValueError, match="dropout_seed"):
        jax.jit(lambda q, k, v: flash_attention(
            q, k, v, None, False, 1.0, 0.1, None))(q, k, v)


# ------------------------------------------------ (B, S, NH*D) bsh entry

def _bsh_ref(q, k, v, NH, causal, scale, rate=0.0, seed=None, km=None):
    """Transposed-entry reference for the flat layout."""
    from apex_tpu.ops.flash_attention import flash_attention

    B, S, H = q.shape
    D = H // NH

    def split(t):
        return t.reshape(B, S, NH, D).transpose(0, 2, 1, 3)

    out = flash_attention(split(q), split(k), split(v), km, causal, scale,
                          rate, seed)
    return out.transpose(0, 2, 1, 3).reshape(B, S, H)


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("rate,seed", [(0.0, None), (0.1, 42)])
def test_bsh_entry_matches_transposed(causal, rate, seed):
    """flash_attention_bsh (head-group kernels on flat activations) is
    bitwise the transposed entry — outputs AND gradients, with and
    without fused dropout (identical per-head PRNG tile ids)."""
    from apex_tpu.ops.flash_attention import flash_attention_bsh

    B, S, NH, D = 2, 128, 4, 64
    H = NH * D
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H), jnp.float32) for kk in ks)
    out = flash_attention_bsh(q, k, v, None, NH, causal, 0.125, rate, seed)
    ref = _bsh_ref(q, k, v, NH, causal, 0.125, rate, seed)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    g = jax.grad(loss(lambda a, b, c: flash_attention_bsh(
        a, b, c, None, NH, causal, 0.125, rate, seed)), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(loss(lambda a, b, c: _bsh_ref(
        a, b, c, NH, causal, 0.125, rate, seed)), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@slow
def test_bsh_entry_unaligned_seq_and_mask():
    from apex_tpu.ops.flash_attention import flash_attention_bsh

    B, S, NH, D = 2, 100, 4, 64  # S pads 100 -> 128 in-entry
    H = NH * D
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H), jnp.float32) for kk in ks)
    km = jnp.asarray(np.random.RandomState(2).rand(B, S) < 0.2)
    out = flash_attention_bsh(q, k, v, km, NH, False, 0.125)
    ref = _bsh_ref(q, k, v, NH, False, 0.125, km=km)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@slow
def test_bsh_entry_fallback_paths():
    """Configs the head-group kernels can't take (odd NH at D=64, or a
    multi-tile sequence) must transparently fall back to the transposed
    entry with identical semantics."""
    from apex_tpu.ops.flash_attention import flash_attention_bsh

    # odd NH=3 at D=64: no valid 128-lane grouping
    B, S, NH, D = 1, 128, 3, 64
    H = NH * D
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H), jnp.float32) for kk in ks)
    out = flash_attention_bsh(q, k, v, None, NH, False, 0.125)
    ref = _bsh_ref(q, k, v, NH, False, 0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)

    # S=640: beyond the single-tile regime
    B, S, NH, D = 1, 640, 4, 64
    H = NH * D
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H), jnp.float32) for kk in ks)
    out = flash_attention_bsh(q, k, v, None, NH, True, 0.125, 0.1, 7)
    ref = _bsh_ref(q, k, v, NH, True, 0.125, 0.1, 7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ------------------------------------- causal tile classes (multi-tile)

def _no_tile_skipping(monkeypatch):
    """The parent's kernels: every tile computed under the causal mask,
    every block fetched. Reached by patching the predicate the kernel
    bodies and the index maps consult - not an option of the program.
    jit caches traces by function, so this hands back a wrapper that is
    traced anew, under the patched predicate."""
    import importlib

    # by module path: ``apex_tpu.ops`` re-exports the function under the
    # module's name
    mod = importlib.import_module("apex_tpu.ops.flash_attention")
    monkeypatch.setattr(mod, "_causal_dead", lambda iq, ik, bq, bk: False)
    return lambda f: jax.jit(lambda *args: f(*args))


def _causal_case(Sq, Sk, use_mask, rate, masked_keys=()):
    from apex_tpu.ops.flash_attention import flash_attention_with_lse

    B, H, D = 1, 1, 64
    q, k, v = _mk(B, H, Sq, Sk, D, seed=11)
    g = jax.random.normal(jax.random.PRNGKey(12), q.shape, q.dtype)
    km = None
    if use_mask:
        # key 0 stays visible: every row keeps a causally visible key
        km = (jax.random.uniform(jax.random.PRNGKey(9), (B, Sk)) < 0.3
              ).at[:, 0].set(False)
    if masked_keys:
        km = jnp.zeros((B, Sk), bool).at[:, jnp.asarray(masked_keys)].set(
            True)
    seed = 77 if rate else None
    scale = 1.0 / np.sqrt(D)

    def kernel_all(q, k, v):
        """(out, lse, dq, dk, dv) with a live lse cotangent."""
        (o, lse), vjp = jax.vjp(
            lambda q, k, v: flash_attention_with_lse(
                q, k, v, km, True, scale, rate, seed), q, k, v)
        return (o, lse) + vjp((g, jnp.cos(lse)))

    return (B, H, D, q, k, v, g, km, seed, scale), kernel_all


@pytest.mark.parametrize("rate", [0.0, 0.1])
@pytest.mark.parametrize("use_mask", [False, True])
@pytest.mark.parametrize("Sq,Sk", [
    (1024, 1024),   # (512, 512) blocks: 1 dead, 2 diagonal, 1 full
    (640, 640),     # pads to 768 at 384-blocks: 1 dead, 2 diagonal, 1 full
    (1024, 512),    # Sq > Sk: no dead tile, the second row block is full
    (512, 1024),    # Sq < Sk: the second key block has no live tile
])
def test_causal_multi_tile_parity_and_bit_equality(Sq, Sk, use_mask, rate,
                                                   monkeypatch):
    """Causal attention past one tile: dead tiles skipped (no compute, no
    fetch), no key-mask selects without a mask - forward, lse and the
    three gradients against the composed reference, and bit-equal to the
    parent's kernels."""
    from apex_tpu.ops.flash_attention import (
        _with_lse_reference,
        flash_dropout_keep_mask,
        mha_with_mask_reference,
    )

    (B, H, D, q, k, v, g, km, seed, scale), kernel_all = _causal_case(
        Sq, Sk, use_mask, rate)
    got = jax.jit(kernel_all)(q, k, v)

    keep = (flash_dropout_keep_mask(B, H, Sq, Sk, rate, seed) if rate
            else jnp.ones((B, H, Sq, Sk), bool))

    def ref_out(q, k, v):
        if not rate:
            return mha_reference(q, k, v, km, True, scale)
        return mha_with_mask_reference(q, k, v, keep, km, True, scale, rate)

    assert _max_err(got[0], ref_out(q, k, v)) < 2e-5
    _, ref_lse = _with_lse_reference(q, k, v, km, True, scale)
    assert got[1].shape == (B, H, 1, Sq)
    assert _max_err(got[1], ref_lse) < 2e-5

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * 1.3)

    gk = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, km, True, scale, rate, seed)), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(ref_out), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gk, gr):
        assert _max_err(a, b) < 3e-4

    parent = _no_tile_skipping(monkeypatch)(kernel_all)(q, k, v)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, parent):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_causal_row_with_no_visible_key_is_uniform_over_its_live_tiles(
        monkeypatch):
    """Keys 0..9 user-masked under causal=True: rows 0..9 see no key.
    They degrade to uniform over the keys of their LIVE tiles (key block
    0 of two) - the composed reference's uniform runs over all 1024 keys,
    future ones included, as the parent's kernels did (which also shows
    that ``_no_tile_skipping`` reaches them); every other row matches it."""
    S = 1024
    (_, _, _, q, k, v, _, km, _, scale), kernel_all = _causal_case(
        S, S, False, 0.0, masked_keys=range(10))
    out = jax.jit(kernel_all)(q, k, v)[0]
    assert bool(jnp.all(jnp.isfinite(out)))
    ref = mha_reference(q, k, v, km, True, scale)
    assert _max_err(out[:, :, 10:], ref[:, :, 10:]) < 2e-5
    uniform = jnp.mean(v[:, :, :512], axis=2, keepdims=True)
    assert _max_err(out[:, :, :10], jnp.broadcast_to(
        uniform, out[:, :, :10].shape)) < 2e-5
    parent = _no_tile_skipping(monkeypatch)(kernel_all)(q, k, v)[0]
    assert _max_err(parent, ref) < 2e-5
    assert _max_err(parent[:, :, :10], out[:, :, :10]) > 1e-3


@pytest.mark.parametrize("Sq,Sk,bq,bk,expected", [
    (1024, 1024, 512, 512, (1, 2, 1)),
    (2048, 2048, 512, 512, (6, 4, 6)),
    (640, 640, 384, 384, (1, 2, 1)),      # 640 pads to 768
    (1024, 512, 512, 512, (0, 1, 1)),
    (512, 1024, 512, 512, (1, 1, 0)),
    (1024, 1024, 256, 512, None),
    (1536, 1024, 384, 512, None),
    (768, 1280, 384, 256, None),
    (1024, 1024, 512, 128, None),
])
def test_causal_tile_classes_match_brute_force(Sq, Sk, bq, bk, expected):
    """The predicates (the kernels skip on ``dead``), counted over the
    grid, against a classification read off the ``row >= col`` matrix."""
    from apex_tpu.ops.flash_attention import tile_classes

    nq, nk = -(-Sq // bq), -(-Sk // bk)
    visible = np.arange(nq * bq)[:, None] >= np.arange(nk * bk)[None, :]
    tiles = visible.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    dead = int((~tiles.any(axis=(2, 3))).sum())
    full = int(tiles.all(axis=(2, 3)).sum())
    brute = (dead, nq * nk - dead - full, full)
    assert tile_classes(Sq, Sk, bq, bk, causal=True) == brute
    if expected is not None:
        assert brute == expected


# ------------------------------------------- grouped-query attention

def _gqa_inputs(S, H, Hkv, dtype=jnp.float32, seed=0, B=2, D=64):
    ks = jax.random.split(jax.random.PRNGKey(seed + S + Hkv), 4)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype)
    do = jax.random.normal(ks[3], (B, H, S, D), dtype)
    return q, k, v, do


# S = 256: the single-tile kernels (fused backward); S = 1024: the
# multi-tile path with its split backward and skipped causal tiles
@pytest.mark.parametrize("S", [256, 1024])
@pytest.mark.parametrize("Hkv", [1, 2])
def test_grouped_query_matches_reference_on_repeated_kv(S, Hkv):
    """4 query heads on 1 and on 2 key/value heads, forward and backward,
    against ``mha_reference`` on k, v repeated to 4 heads (whose gradient
    sums over each group)."""
    H = 4
    q, k, v, do = _gqa_inputs(S, H, Hkv)

    def rep(t):
        return jnp.repeat(t, H // Hkv, axis=1)

    def flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, True, 0.125) * do)

    def composed(q, k, v):
        return jnp.sum(mha_reference(q, rep(k), rep(v), None, True, 0.125)
                       * do)

    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, None, True, 0.125)
        ref = mha_reference(q, rep(k), rep(v), None, True, 0.125)
        got = jax.grad(flash, (0, 1, 2))(q, k, v)
        want = jax.grad(composed, (0, 1, 2))(q, k, v)
    assert out.shape == q.shape
    assert _max_err(out, ref) < 2e-5
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name          # dk, dv at Hkv heads
        assert _max_err(a, b) < 1e-4, name


def test_grouped_query_dropout_and_bf16():
    """bf16 with in-kernel dropout: dk, dv are summed over the group in
    float32 before the cast; the mask is per query head."""
    from apex_tpu.ops.flash_attention import (flash_dropout_keep_mask,
                                              mha_with_mask_reference)

    H, Hkv, S = 4, 2, 640
    q, k, v, do = _gqa_inputs(S, H, Hkv, jnp.bfloat16, seed=3, B=1)
    keep = flash_dropout_keep_mask(1, H, S, S, 0.1, 11)

    def flash(q, k, v):
        return jnp.sum((flash_attention(q, k, v, None, True, 0.125, 0.1, 11)
                        * do).astype(jnp.float32))

    def composed(q, k, v):
        rep = lambda t: jnp.repeat(t, 2, axis=1)  # noqa: E731
        return jnp.sum((mha_with_mask_reference(
            q, rep(k), rep(v), keep, None, True, 0.125, 0.1) * do
        ).astype(jnp.float32))

    got = jax.grad(flash, (0, 1, 2))(q, k, v)
    want = jax.grad(composed, (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _max_err(a, b) < 0.05 * float(jnp.max(jnp.abs(
            b.astype(jnp.float32)))), name


def test_heads_must_be_a_multiple_of_groups():
    q, k, v, _ = _gqa_inputs(128, 4, 3)
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, k, v, None, True, 0.125)


@pytest.mark.parametrize("S,causal,rate", [(256, True, 0.0), (512, False, 0.1),
                                           (1024, True, 0.1), (640, True, 0.0)])
def test_multi_head_is_bit_identical_to_the_parent(monkeypatch, S, causal,
                                                   rate):
    """With as many key/value heads as query heads the kernels are the
    parent's: its index maps (the identity on heads) and its dk, dv in
    k's dtype with no sum outside. The parent is reached by putting its
    two pieces back - ``_kv_head`` always the identity, ``_sum_groups`` a
    no-op - and the multi-head result must not differ in one bit. (PR 30
    also held the results against the parent's module file itself, out
    and three gradients, float32 and bfloat16, 10 cases: bit-identical.)"""
    import importlib

    # the package exports a function of the module's own name
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    q, k, v, do = _gqa_inputs(S, 4, 4, jnp.bfloat16, seed=5, B=1)
    seed = 7 if rate else None

    def both():
        def loss(q, k, v):
            return jnp.sum((fa.flash_attention(q, k, v, None, causal, 0.125,
                                               rate, seed) * do
                            ).astype(jnp.float32))

        return (fa.flash_attention(q, k, v, None, causal, 0.125, rate, seed),
                ) + jax.grad(loss, (0, 1, 2))(q, k, v)

    new = both()
    monkeypatch.setattr(fa, "_kv_head", lambda q, k: (lambda h: h))
    monkeypatch.setattr(fa, "_sum_groups", lambda dk, k: dk)
    parent = both()
    for name, a, b in zip(("out", "dq", "dk", "dv"), new, parent):
        assert a.dtype == b.dtype == jnp.bfloat16, name
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32))), name


# ------------------------------------- a mask by function: block diffusion

def _dense_blockdiff(L, g, clean_queries=True):
    """The block-diffusion mask written out from its definition (the
    issue's, not the kernel's arithmetic): rows and columns are ``[noised ;
    clean]``, ``b(i) = (i mod L) // g``."""
    r = np.arange(2 * L if clean_queries else L)[:, None]
    c = np.arange(2 * L)[None, :]
    rn, cn, rb, cb = r < L, c < L, (r % L) // g, (c % L) // g
    return ((rn & cn & (rb == cb)) | (rn & ~cn & (cb < rb))
            | (~rn & ~cn & (cb <= rb)))


@pytest.mark.parametrize("L,g,clean_queries", [
    (64, 4, True),      # one tile (128 x 128)
    (192, 32, True),    # one tile of 384, L no multiple of a tile
    (320, 1, True),     # 640 -> 768 at 384-blocks: L straddles a tile
    (512, 4, True),     # 1024 at 512-blocks: 8 live tiles of 16
    (576, 4, False),    # the last layer's call: L queries on 2 L keys
    (512, 32, False),
    (640, 4, False),    # 768 x 1536 in tiles of 384 x 512: bq != bk
])
def test_block_diffusion_mask_matches_the_dense_mask(L, g, clean_queries):
    """The block-masked kernels (past one tile a grid over the list of
    live tiles, which are masked from iotas; 4 query heads on 2 key/value
    heads): forward and
    the three gradients against composed attention under the mask built
    densely from its definition; ``mha_reference`` takes the same
    description and builds the same mask."""
    from apex_tpu.ops.flash_attention import (FILL, BlockDiffusionMask,
                                              _block_sizes)

    mask = BlockDiffusionMask(L, g, clean_queries)
    H, Hkv, D, scale = 4, 2, 32, 32 ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(L + g), 4)
    q = jax.random.normal(ks[0], (1, H, mask.q_len, D))
    k = jax.random.normal(ks[1], (1, Hkv, 2 * L, D))
    v = jax.random.normal(ks[2], (1, Hkv, 2 * L, D))
    go = jax.random.normal(ks[3], q.shape)
    dense = jnp.asarray(_dense_blockdiff(L, g, clean_queries))
    np.testing.assert_array_equal(
        np.asarray(mask.visible(np.arange(mask.q_len)[:, None],
                                np.arange(2 * L)[None, :])),
        np.asarray(dense))

    def composed(q, k, v):
        kk, vv = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
        p = jax.nn.softmax(jnp.where(dense, s, FILL), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    def kernel(q, k, v):
        return flash_attention(q, k, v, None, False, scale, score_mask=mask)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * go)

    want = composed(q, k, v)
    assert _max_err(kernel(q, k, v), want) < 2e-6
    assert _max_err(mha_reference(q, k, v, None, False, scale,
                                  score_mask=mask), want) < 2e-6
    got = jax.grad(scalar(kernel), (0, 1, 2))(q, k, v)
    ref = jax.grad(scalar(composed), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        assert _max_err(a, b) < 1e-5, name
    # the sizes above are one tile and past one tile, square tiles and not
    assert (_block_sizes(mask.q_len, 2 * L) == (mask.q_len, 2 * L)) == (
        L in (64, 192))
    assert (len(set(_block_sizes(mask.q_len, 2 * L))) == 2) == (L == 640)


def _brute_tile_classes(L, g, bq, bk, clean_queries):
    """``(live, (dead, partial, full))`` of the block-diffusion mask's
    tiles at (bq, bk), read off the dense mask; padding past the call's
    lengths is not counted."""
    Sq, Sk = (2 * L if clean_queries else L), 2 * L
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    dense = np.zeros((nq * bq, nk * bk), bool)
    dense[:Sq, :Sk] = _dense_blockdiff(L, g, clean_queries)
    real = np.zeros_like(dense)
    real[:Sq, :Sk] = True
    tiles = dense.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    real = real.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    full = (tiles | ~real).all(axis=(2, 3)) & live
    return live, (int((~live).sum()), int((live & ~full).sum()),
                  int(full.sum()))


@pytest.mark.parametrize("L,g,bq,bk,clean_queries,expected", [
    (1024, 4, 512, 512, True, (8, 6, 2)),         # 8 live of 16
    (8192, 4, 512, 512, True, (736, 48, 240)),    # 288 live of 1,024
    (8192, 4, 512, 512, False, (360, 32, 120)),   # the last layer's call
    (320, 1, 384, 384, True, None),               # L straddles a tile
    (192, 32, 128, 256, True, None),
    (96, 3, 64, 32, True, None),                  # blocks across tile edges
    (96, 8, 32, 64, False, None),
])
def test_block_diffusion_tile_classes_and_tables(L, g, bq, bk, clean_queries,
                                                 expected):
    """``tile_classes`` under the description against a classification
    read off the dense mask; the two prefetched tile lists hold every live
    tile once and no dead one, a row (k-major: a column) as one run of
    steps in ascending order with its ends flagged, so that every output
    block is opened, accumulated in the dense walk's order and closed
    once."""
    from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                              _mask_tables, tile_classes)

    mask = BlockDiffusionMask(L, g, clean_queries)
    Sq, Sk = mask.q_len, mask.k_len
    live, brute = _brute_tile_classes(L, g, bq, bk, clean_queries)
    assert tile_classes(Sq, Sk, bq, bk, score_mask=mask) == brute
    if expected is not None:
        assert brute == expected
    with pytest.raises(ValueError, match="one of the two"):
        tile_classes(Sq, Sk, bq, bk, causal=True, score_mask=mask)
    q_major, k_major = _mask_tables(mask, bq, bk)
    for tiles, alive, outer, inner in ((q_major, live, "iq", "ik"),
                                       (k_major, live.T, "ik", "iq")):
        assert all(a.dtype == np.int32 and a.shape == (live.sum(),)
                   for a in tiles)
        outer, inner = getattr(tiles, outer), getattr(tiles, inner)
        # every live tile once, no dead one: in row-major order
        want_outer, want_inner = np.nonzero(alive)
        np.testing.assert_array_equal(outer, want_outer)
        np.testing.assert_array_equal(inner, want_inner)
        # every row (column) is there, one run of steps, flagged at its
        # two ends and nowhere else
        assert set(outer) == set(range(alive.shape[0]))
        starts = np.flatnonzero(np.append(True, np.diff(outer) != 0))
        assert len(starts) == alive.shape[0]
        np.testing.assert_array_equal(np.flatnonzero(tiles.first), starts)
        np.testing.assert_array_equal(
            np.flatnonzero(tiles.last),
            np.append(starts[1:], len(outer)) - 1)
        assert set(tiles.first) | set(tiles.last) <= {0, 1}


@pytest.mark.parametrize("Sq,Sk,bq,bk,kind,expected", [
    (1024, 1024, 512, 512, "causal", 4),         # every tile, dead or not
    (8192, 8192, 512, 512, "causal", 256),
    (640, 640, 384, 384, "causal", 4),           # 640 -> 768
    (1024, 512, 512, 512, None, 2),
    (16384, 16384, 512, 512, (8192, 4, True), 288),    # was 1,024
    (8192, 16384, 512, 512, (8192, 4, False), 152),    # was 512
    (640, 640, 384, 384, (320, 1, True), None),  # L straddles a tile
    (192, 192, 64, 32, (96, 3, True), None),
    (96, 192, 32, 64, (96, 8, False), None),
    (640, 1280, 384, 512, (640, 4, False), None),
])
def test_grid_steps_are_every_tile_or_the_live_ones(Sq, Sk, bq, bk, kind,
                                                    expected):
    """The grid steps a head of each multi-tile kernel: ``nq * nk`` with
    or without ``causal`` (a dead causal tile is still a step), the live
    tiles alone under a description."""
    from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                              grid_steps, tile_classes)

    if not isinstance(kind, tuple):
        steps = grid_steps(Sq, Sk, bq, bk, causal=kind == "causal")
        assert steps == -(-Sq // bq) * -(-Sk // bk) == expected
        return
    mask = BlockDiffusionMask(*kind)
    steps = grid_steps(Sq, Sk, bq, bk, score_mask=mask)
    live, (dead, partial, full) = _brute_tile_classes(kind[0], kind[1], bq,
                                                      bk, kind[2])
    assert steps == live.sum() == partial + full
    assert steps == sum(tile_classes(Sq, Sk, bq, bk, score_mask=mask)[1:])
    assert expected in (None, steps)
    with pytest.raises(ValueError, match="not both"):
        grid_steps(Sq, Sk, bq, bk, causal=True, score_mask=mask)


@pytest.mark.parametrize("empty", ["row", "column"])
def test_a_description_with_an_empty_row_or_column_raises(empty):
    """A block of queries (of keys) with no live tile would be a block of
    ``o`` (of ``dk`` / ``dv``) that no grid step writes: the list builder
    refuses the description."""
    import dataclasses

    from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                              _mask_tables, grid_steps)

    @dataclasses.dataclass(frozen=True)
    class Holed(BlockDiffusionMask):
        def tile_class(self, r0, r1, c0, c1):
            first = (r0 if empty == "row" else c0) == 0
            return "dead" if first else super().tile_class(r0, r1, c0, c1)

    assert grid_steps(256, 256, 64, 64, score_mask=BlockDiffusionMask(
        128, 4)) == 8
    with pytest.raises(ValueError, match="no live tile"):
        _mask_tables(Holed(128, 4), 64, 64)
    with pytest.raises(ValueError, match="no live tile"):
        grid_steps(256, 256, 64, 64, score_mask=Holed(128, 4))


def test_without_a_description_the_kernels_hold_no_mask_operation():
    """"No score mask" is static, as "no key mask" is: a call with
    ``causal=True/False`` and no description builds the kernels it built
    before - no table operand, no scalar prefetch, none of the
    description's integer arithmetic - and a described call does."""
    from apex_tpu.ops.flash_attention import BlockDiffusionMask

    q, k, v = _mk(1, 2, 1024, 1024, 64)

    def kernels(causal, score_mask=None):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, None, causal, 0.125,
                                           score_mask=score_mask))

        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
        return str(jaxpr), _pallas_grids(jaxpr.jaxpr)

    for causal in (False, True):
        text, grids = kernels(causal)
        assert text.count("pallas_call") == 3
        assert "blockdiff" not in text and "shift_right" not in text
        # every tile is a grid step, (B, H, 2, 2), and nothing is
        # prefetched
        assert grids == [((1, 2, 2, 2), 0)] * 3, grids
    text, grids = kernels(False, BlockDiffusionMask(512, 4))
    assert text.count("pallas_call") == 3
    for name in ("flash_blockdiff_fwd", "flash_blockdiff_bwd_dq",
                 "flash_blockdiff_bwd_dkv"):
        assert name in text
    assert "shift_right" in text
    # the described call walks its 3 live tiles of 4, (B, H, live tiles),
    # named by the four prefetched lists
    assert grids == [((1, 2, 3), 4)] * 3, grids


def _pallas_grids(jaxpr):
    """``(grid, scalar-prefetch operands)`` of every ``pallas_call`` in a
    jaxpr, nested ones too, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            found.append((tuple(mapping.grid), mapping.num_index_operands))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_grids(sub)
    return found


def test_a_description_stands_alone_and_fits_its_call():
    from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                              flash_attention_bsh)

    mask = BlockDiffusionMask(64, 4)
    q, k, v = _mk(1, 2, 128, 128, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, None, True, 1.0, score_mask=mask)
    with pytest.raises(ValueError, match="key_mask"):
        flash_attention(q, k, v, jnp.zeros((1, 128), bool), False, 1.0,
                        score_mask=mask)
    with pytest.raises(ValueError, match="dropout"):
        flash_attention(q, k, v, None, False, 1.0, 0.1, 3, score_mask=mask)
    with pytest.raises(ValueError, match="64 queries"):
        flash_attention(q[:, :, :64], k, v, None, False, 1.0,
                        score_mask=mask)
    with pytest.raises(ValueError, match="multiple"):
        BlockDiffusionMask(66, 4)
    with pytest.raises(TypeError):       # the _bsh layout takes none
        flash_attention_bsh(q[:, 0], k[:, 0], v[:, 0], None, 1, False, 1.0,
                            0.0, None, mask)


# ------------------------------------- a mask by function: a sliding window

def _dense_window(S, W):
    """The sliding-window causal mask written out from its definition:
    the query at ``i`` sees the key at ``j`` iff ``0 <= i - j < W``."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    return (i >= j) & (i - j < W)


@pytest.mark.parametrize("S,W", [
    (128, 40),       # one tile
    (1024, 300),     # 512-blocks, a window no multiple of the tile
    (640, 200),      # 640 -> 768 at 384-blocks: padded keys and queries
    (1024, 5000),    # a window past the sequence: the causal mask
])
def test_sliding_window_mask_matches_the_dense_mask(S, W):
    """The window-masked kernels (past one tile a grid over the list of
    the band's live tiles, masked from iotas; 4 query heads on 2
    key/value heads): forward and the three gradients against composed
    attention under the band built densely from its definition;
    ``mha_reference`` takes the same description. A window of the whole
    sequence or more is the causal call, kernel for kernel."""
    from apex_tpu.ops.flash_attention import FILL, SlidingWindowMask

    mask = SlidingWindowMask(S, W)
    H, Hkv, D, scale = 4, 2, 32, 32 ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(S + W), 4)
    q = jax.random.normal(ks[0], (1, H, S, D))
    k = jax.random.normal(ks[1], (1, Hkv, S, D))
    v = jax.random.normal(ks[2], (1, Hkv, S, D))
    go = jax.random.normal(ks[3], q.shape)
    dense = jnp.asarray(_dense_window(S, W))
    np.testing.assert_array_equal(
        np.asarray(mask.visible(np.arange(S)[:, None],
                                np.arange(S)[None, :])), np.asarray(dense))

    def composed(q, k, v):
        kk, vv = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
        p = jax.nn.softmax(jnp.where(dense, s, FILL), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    def kernel(q, k, v):
        return flash_attention(q, k, v, None, False, scale, score_mask=mask)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * go)

    want = composed(q, k, v)
    assert _max_err(kernel(q, k, v), want) < 2e-6
    assert _max_err(mha_reference(q, k, v, None, False, scale,
                                  score_mask=mask), want) < 2e-6
    got = jax.grad(scalar(kernel), (0, 1, 2))(q, k, v)
    ref = jax.grad(scalar(composed), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        assert _max_err(a, b) < 1e-5, name
    if W >= S:
        def causal(q, k, v):
            return flash_attention(q, k, v, None, True, scale)

        assert _max_err(kernel(q, k, v), causal(q, k, v)) < 1e-6
        for name, a, b in zip(("dq", "dk", "dv"), got, jax.grad(
                scalar(causal), (0, 1, 2))(q, k, v)):
            assert _max_err(a, b) < 1e-6, name


@pytest.mark.parametrize("S,W,bq,bk,expected", [
    (8192, 2048, 512, 512, (186, 28, 42)),   # 70 live of 256
    (8192, 8192, 512, 512, (120, 16, 120)),  # the causal mask's classes
    (1024, 300, 512, 512, None),
    (640, 200, 384, 384, None),              # padding past S not counted
    (192, 50, 64, 32, None),                 # bq != bk
    (96, 7, 32, 64, None),
])
def test_sliding_window_tile_classes_and_tables(S, W, bq, bk, expected):
    """``tile_classes`` under the description (a closed form over the
    band) against a classification read off the dense mask; the grid is
    the live tiles alone, every row and column of tiles has one, and a
    window of the whole sequence classifies as ``causal=True`` does."""
    from apex_tpu.ops.flash_attention import (SlidingWindowMask,
                                              _mask_tables, grid_steps,
                                              tile_classes)

    mask = SlidingWindowMask(S, W)
    nq, nk = -(-S // bq), -(-S // bk)
    dense = np.zeros((nq * bq, nk * bk), bool)
    dense[:S, :S] = _dense_window(S, W)
    real = np.zeros_like(dense)
    real[:S, :S] = True
    tiles = dense.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    real = real.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    full = (tiles | ~real).all(axis=(2, 3)) & live
    brute = (int((~live).sum()), int((live & ~full).sum()), int(full.sum()))
    assert tile_classes(S, S, bq, bk, score_mask=mask) == brute
    assert expected in (None, brute)
    assert grid_steps(S, S, bq, bk, score_mask=mask) == live.sum()
    q_major, k_major = _mask_tables(mask, bq, bk)
    np.testing.assert_array_equal(np.stack([q_major.iq, q_major.ik]),
                                  np.stack(np.nonzero(live)))
    np.testing.assert_array_equal(np.stack([k_major.ik, k_major.iq]),
                                  np.stack(np.nonzero(live.T)))
    if W >= S and bq == bk:
        assert brute == tile_classes(S, S, bq, bk, causal=True)
    if expected is not None and W < S:
        assert grid_steps(S, S, bq, bk, score_mask=mask) == 70
    with pytest.raises(ValueError, match="positive"):
        SlidingWindowMask(S, 0)
    with pytest.raises(ValueError, match="queries"):
        flash_attention(*_mk(1, 1, 64, 64, 32), None, False, 1.0,
                        score_mask=SlidingWindowMask(128, W))


# ----------------------------------- the multi-tile kernels' sub-blocks

def _kernel_jaxprs(jaxpr, found=None):
    """``{kind: jaxpr}`` of the flash kernels in a jaxpr, nested ones
    searched too: the first ``flash_<kind>`` or ``flash_<tag>_<kind>`` of
    each kind (``fwd``, ``bwd_dq``, ``bwd_dkv``; ``bwd`` single-tile)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            kind = next(k for k in ("bwd_dkv", "bwd_dq", "fwd", "bwd")
                        if eqn.params["name"].endswith("_" + k))
            found.setdefault(kind, eqn.params["jaxpr"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_jaxprs(sub, found)
    return found


def _ops_in_order(jaxpr, names):
    """Primitive names of ``jaxpr`` among ``names``, nested jaxprs (the
    live-tile ``cond``) in place, in program order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _ops_in_order(sub, names)
    return found


def _split_rows(monkeypatch, rows):
    """Make the multi-tile forward and dq kernels split a tile step's query
    rows into sub-blocks of ``rows`` (all of them where ``rows`` does not
    divide the block): patched, not an option of the program. Returns a
    jit that is traced anew under the patch."""
    import importlib

    mod = importlib.import_module("apex_tpu.ops.flash_attention")
    monkeypatch.setattr(mod, "_FWD_ROWS", rows)
    monkeypatch.setattr(mod, "_DQ_ROWS", rows)
    return lambda f: jax.jit(lambda *args: f(*args))


def _traced(f, *args):
    """``f``'s jaxpr, traced anew (a jaxpr is cached by function)."""
    return jax.make_jaxpr(lambda *a: f(*a))(*args).jaxpr


_SPLIT_CASES = {
    # causal at 512-blocks with dropout and a key mask: the sub-blocks'
    # row offsets, the slices of the tile's one keep mask
    "causal_dropout_mask": dict(Sq=1024, Sk=1024, causal=True, rate=0.1,
                                key_mask=True, dtype=jnp.float32),
    # 640 pads to 768 at 384-blocks: three sub-blocks of 128
    "causal_384": dict(Sq=640, Sk=640, causal=True, rate=0.0,
                       key_mask=False, dtype=jnp.bfloat16),
    # no mask function past one tile, Sq > Sk, 2 query heads a group
    "plain_gqa": dict(Sq=1024, Sk=512, causal=False, rate=0.0,
                      key_mask=False, dtype=jnp.bfloat16, Hkv=1),
    # the block-diffusion mask: every row of a sub-block keeps its own
    # noised / clean arithmetic; and the last layer's noised queries
    "blockdiff": dict(L=512, g=4, clean_queries=True, dtype=jnp.bfloat16),
    "blockdiff_noised": dict(L=512, g=4, clean_queries=False,
                             dtype=jnp.float32),
    # the sliding window: a band of live tiles, the element mask by row
    # offset
    "window": dict(S=1536, W=512, dtype=jnp.bfloat16),
    # one tile: the single-tile kernels, which are never split
    "single_tile": dict(Sq=512, Sk=512, causal=True, rate=0.1,
                        key_mask=True, dtype=jnp.float32),
}


def _split_case(name):
    """``(args, f)``: inputs and a function of them that gives the
    forward's output and every gradient (and lse where the entry has
    one)."""
    from apex_tpu.ops.flash_attention import (BlockDiffusionMask,
                                              SlidingWindowMask,
                                              flash_attention_with_lse)

    c = _SPLIT_CASES[name]
    H, D, dtype = 2, 64, c["dtype"]
    if "L" in c:
        mask = BlockDiffusionMask(c["L"], c["g"], c["clean_queries"])
        Sq, Sk, Hkv = mask.q_len, mask.k_len, 1
    elif "W" in c:
        mask = SlidingWindowMask(c["S"], c["W"])
        Sq, Sk, Hkv = mask.q_len, mask.k_len, 1
    else:
        mask, Sq, Sk, Hkv = None, c["Sq"], c["Sk"], c.get("Hkv", H)
    ks = jax.random.split(jax.random.PRNGKey(Sq + Sk), 4)
    q = jax.random.normal(ks[0], (1, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (1, Hkv, Sk, D), dtype)
    v = jax.random.normal(ks[2], (1, Hkv, Sk, D), dtype)
    g = jax.random.normal(ks[3], q.shape, dtype)
    if mask is not None:
        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, None, False, 0.125, score_mask=mask), q, k, v)
            return (out,) + vjp(g)
        return (q, k, v), f
    km = (jnp.zeros((1, Sk), bool).at[:, 7].set(True) if c["key_mask"]
          else None)
    seed = 29 if c["rate"] else None

    def f(q, k, v):
        (out, lse), vjp = jax.vjp(lambda q, k, v: flash_attention_with_lse(
            q, k, v, km, c["causal"], 0.125, c["rate"], seed), q, k, v)
        return (out, lse) + vjp((g, jnp.cos(lse)))
    return (q, k, v), f


@pytest.mark.parametrize("rows", [256, 128])
@pytest.mark.parametrize("name", sorted(_SPLIT_CASES))
def test_forward_row_sub_blocks_are_bit_identical_to_one_block(
        monkeypatch, name, rows):
    """The forward's and dq's tile steps with their query rows in
    sub-blocks of 256 or 128 give the output, lse and the three gradients
    of the unsplit steps (sub-blocks of all ``bq`` rows: the kernels
    before the split) in every bit: the forward's statistics and a row of
    dq are per row. Each kernel holds the split it was asked for - two
    matmuls a sub-block in the forward, three in dq, dkv's four whole (its
    split measured slower on the v5e: PERF.md section 6), a single-tile
    call never split - and a sub-block of the whole block traces to the
    unsplit kernel, text for text."""
    from apex_tpu.ops.flash_attention import _block_sizes

    args, f = _split_case(name)
    Sq, Sk = args[0].shape[2], args[1].shape[2]
    bq, bk = _block_sizes(Sq, Sk)
    parent = _split_rows(monkeypatch, 1 << 20)(f)(*args)
    whole = _kernel_jaxprs(_traced(f, *args))
    _split_rows(monkeypatch, bq)
    assert ({k: str(j) for k, j in _kernel_jaxprs(_traced(f, *args)).items()}
            == {k: str(j) for k, j in whole.items()})
    split = _split_rows(monkeypatch, rows)(f)(*args)
    parts = _kernel_jaxprs(_traced(f, *args))

    def matmuls(kernels):
        return {kind: _ops_in_order(j, {"dot_general"}).count("dot_general")
                for kind, j in kernels.items()}

    if Sq <= bq and Sk <= bk:
        assert matmuls(whole) == matmuls(parts) == {"fwd": 2, "bwd": 5}
    else:
        n = bq // rows if bq % rows == 0 else 1
        assert matmuls(whole) == {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}
        assert matmuls(parts) == {"fwd": 2 * n, "bwd_dq": 3 * n,
                                  "bwd_dkv": 4}
    for i, (a, b) in enumerate(zip(split, parent)):
        assert a.dtype == b.dtype, i
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)),
                                      err_msg=f"{name}: output {i}")


def test_each_sub_block_is_its_own_chain_of_matmul_softmax_matmul(
        monkeypatch):
    """What the tile step's program gives the scheduler: four sub-blocks
    of 128 rows in a 512-row tile, each its own chain of matmuls and
    softmax (its ``exp``), sharing no value with another - the chains it
    may interleave: q k^T, softmax, p v in the forward; q k^T, softmax,
    do v^T, ds k in dq. (Issuing the forward's next q k^T before this
    sub-block's softmax measured slower on the v5e: docs/kernels.md.)"""
    args, f = _split_case("causal_dropout_mask")
    _split_rows(monkeypatch, 128)
    kernels = _kernel_jaxprs(_traced(f, *args))
    mm, exp = "dot_general", "exp"
    for kind, chain in (("fwd", [mm, exp, exp, mm]),
                        ("bwd_dq", [mm, exp, mm, mm])):
        got = _ops_in_order(kernels[kind], {mm, exp})
        assert got == chain * 4, (kind, got)
