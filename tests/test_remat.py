"""What a rematerialised block keeps (``apex_tpu/transformer/remat.py``).

``"selective"`` keeps the matmul outputs and the flash kernel's outputs and
recomputes the elementwise ops; ``"full"`` recomputes the whole block. The
two and ``remat=False`` are the same mathematics: equal losses and
gradients, with dropout on too (the masks are regenerated from the same
seeds). What differs is what the differentiated step does twice, which the
jaxpr shows."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import profiler
from apex_tpu.models.bert import BertConfig, BertForPreTraining
from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel
from apex_tpu.transformer import remat

# the module: ``apex_tpu.ops`` exports the function under the same name
fa = importlib.import_module("apex_tpu.ops.flash_attention")

LAYERS, SEQ = 2, 128


def _bert(dropout=0.0, wide_heads=False, **kw):
    """(loss(params), params). ``wide_heads``: heads of 64, which take
    the (B, S, H) flash entry; the tiny default's heads of 16 take the
    transposed one."""
    if wide_heads:
        kw.update(hidden_size=128, num_heads=2)
    cfg = BertConfig.tiny(max_position_embeddings=SEQ, flash_min_seq=SEQ,
                          hidden_dropout=dropout, attention_dropout=dropout,
                          **kw)
    model = BertForPreTraining(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    mask = jnp.ones((2, SEQ), jnp.int32).at[:, -5:].set(0)
    params = model.init(jax.random.PRNGKey(0), ids, None, mask)

    def loss(p):
        mlm, nsp = model.apply(p, ids, None, mask, deterministic=False,
                               rngs={"dropout": jax.random.PRNGKey(1)})
        return jnp.mean(jnp.square(mlm)) + jnp.mean(nsp)

    return loss, params


def _gpt(dropout=0.0, **kw):
    cfg = GPTConfig.tiny(dropout=dropout, **kw)
    model = GPTLMHeadModel(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, SEQ)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)

    def loss(p):
        logits = model.apply(p, ids, deterministic=False,
                             rngs={"dropout": jax.random.PRNGKey(1)},
                             mutable=["losses"])[0]
        return jnp.mean(jnp.square(logits))

    return loss, params


MAKE = {"bert": _bert, "gpt": _gpt}


def _ops(loss, params):
    """{(primitive or kernel name, recomputed?, flax module or None): count}
    over the differentiated step's jaxpr. JAX puts ``rematted_computation``
    into the name stack of what a checkpoint's backward does again."""
    counts = {}

    def walk(jaxpr, recomputed, layer=None):
        outer = layer
        for eqn in jaxpr.eqns:
            stack = str(eqn.source_info.name_stack)
            again = recomputed or "rematted_computation" in stack
            name = eqn.primitive.name
            if name == "pallas_call":      # a kernel counts as one op
                name = eqn.params["name"]
            names = [name]
            # a matmul with no batch dimensions: what "selective" keeps
            if name == "dot_general" and not any(
                    eqn.params["dimension_numbers"][1]):
                names.append("dense_dot")
            # an inner jaxpr's name stacks start at its call site
            layer = next((part for part in stack.split("/")
                          if part.startswith(("layer_", "layers_", "h_"))),
                         outer)
            for name in names:
                for key in ((name, again), (name, again, layer)):
                    counts[key] = counts.get(key, 0) + 1
            if eqn.primitive.name == "pallas_call":
                continue
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, again, layer)

    walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, False)
    return counts


# -- the same mathematics -------------------------------------------------------

def _same_loss_and_gradients(loss, other, params):
    """Holds ``loss`` to ``other`` at ``params``: value and every gradient
    to 1e-6. Returns each gradient's largest entry."""
    want_loss, want = jax.jit(jax.value_and_grad(other))(params)
    got_loss, got = jax.jit(jax.value_and_grad(loss))(params)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-6
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    return [float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(got)]


@pytest.mark.parametrize("other", [dict(remat_policy="full"),
                                   dict(remat=False)],
                         ids=["full", "no_remat"])
@pytest.mark.parametrize("dropout", [0.0, 0.1])
@pytest.mark.parametrize("model", ["bert", "gpt"])
def test_selective_is_the_same_mathematics(model, dropout, other):
    loss, params = MAKE[model](dropout)            # the class default
    peaks = _same_loss_and_gradients(
        loss, MAKE[model](dropout, **other)[0], params)
    assert any(peak > 0 for peak in peaks)


def test_selective_is_the_default_of_both_configs():
    assert BertConfig().remat and BertConfig().remat_policy == "selective"
    assert GPTConfig().remat and GPTConfig().remat_policy == "selective"
    assert remat.POLICIES == ("selective", "full")


# -- what is done twice ----------------------------------------------------------

# (model, its options, a block's matmuls and hidden dropouts that "full"
# does twice: GPT's block ends in ``mlp_out`` + dropout, whose outputs no
# backward op reads). The tiny BERT's heads of 16 and the tiny GPT take
# the transposed flash entry, heads of 64 the (B, S, H) entry
STACKS = [("bert", dict(), 6, 2), ("bert", dict(wide_heads=True), 6, 2),
          ("gpt", dict(), 5, 1)]


@pytest.mark.parametrize("model,options,dots,drops", STACKS,
                         ids=["bert-bhsd", "bert-bsh", "gpt-bhsd"])
def test_selective_recomputes_no_matmul_and_no_flash(model, options, dots,
                                                     drops):
    full = _ops(*MAKE[model](0.1, remat_policy="full", **options))
    kept = _ops(*MAKE[model](0.1, **options))
    none = _ops(*MAKE[model](0.1, remat=False, **options))
    # "full": every block's matmuls and its flash forward run twice
    assert full["dot_general", True] == dots * LAYERS
    assert full["flash_fwd", True] == LAYERS
    assert full["flash_fwd", False] == LAYERS
    # "selective": each layer's flash forward once, no matmul again ...
    assert ("dot_general", True) not in kept
    assert ("flash_fwd", True) not in kept
    assert kept["flash_fwd", False] == LAYERS
    assert kept["dot_general", False] == none["dot_general", False]
    # ... while the elementwise ops are (the hidden dropouts' kernel)
    assert kept["dropout_apply", True] == drops * LAYERS
    assert ("dropout_apply", True) not in none
    # the backward kernels are the same in all three
    backward = [k for k in full if k[0].startswith("flash_bwd")
                and len(k) == 2]
    assert backward and all(full[k] == kept[k] == none[k] for k in backward)


def test_dots_alone_would_recompute_flash(monkeypatch):
    """The ``"dots"`` policy offered before PR 31 kept the matmuls but not
    the Pallas call's outputs: the counts above tell the two apart."""
    import flax.linen as nn
    from apex_tpu.models import bert

    dots = nn.remat(
        bert.BertLayer, static_argnums=(3,),
        policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    monkeypatch.setattr(bert, "remat_block", lambda *a: dots)
    ops = _ops(*_bert(0.1))
    assert ("dot_general", True) not in ops
    assert ops["flash_fwd", True] == LAYERS


# -- a block that routes keeps full recomputation ---------------------------------

@pytest.mark.parametrize("policy", ["selective", "full"])
def test_expert_block_recomputes_everything_under_either_policy(policy):
    # h_0 routes, h_1 is dense
    ops = _ops(*_gpt(0.0, remat_policy=policy, num_experts=4,
                     moe_layer_freq=2))
    assert ops["dot_general", True, "h_0"] == 8    # router + experts too
    assert ops["flash_fwd", True, "h_0"] == 1
    assert ops.get(("dot_general", True, "h_1"), 0) == (
        5 if policy == "full" else 0)
    assert ops.get(("flash_fwd", True, "h_1"), 0) == (
        1 if policy == "full" else 0)


# -- a block whose expert layer names its routing keeps it, with its rows ------

def _nemotron(pattern, seq=32, **kw):
    """(loss(params), params) of a tiny ``nemotron_h`` stack."""
    from apex_tpu.models.nemotron_h import (NemotronHConfig,
                                            NemotronHLMHeadModel)

    cfg = NemotronHConfig.tiny(pattern=pattern, **kw)
    model = NemotronHLMHeadModel(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, seq)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return (lambda p: model.apply({"params": p}, ids, method="loss")[0],
            params)


def test_nemotron_expert_block_keeps_its_routing_and_rows():
    """``models/nemotron_h.py`` wraps an ``E`` block so that the names of
    ``profiler.MOE_RESIDUALS`` are kept: the backward pass runs no grouped
    matmul, no ``top_k``, no sort and no scatter again, and of the block's
    matmuls the router's scores and the shared expert's up projection;
    ``M`` and ``*`` blocks keep their dense matmul outputs (PR 35) and do
    again only the scan's batched einsums and the composed attention's."""
    kept = _ops(*_nemotron("ME*", fused_kernels=False))
    none = _ops(*_nemotron("ME*", fused_kernels=False, remat=False))
    # off the TPU a grouped matmul is ``ragged_dot``: up and down forward,
    # two each backward, in both
    for counts in (kept, none):
        assert counts["ragged_dot_general", False, "layers_1"] == 6
    for name in ("ragged_dot_general", "gmm", "tgmm", "top_k", "sort",
                 "scatter"):
        assert (name, True, "layers_1") not in kept, name
    assert kept["top_k", False, "layers_1"] == 1
    # the sort by expert, its inverse, the weights into that order and back
    assert kept["sort", False, "layers_1"] == 4
    # the router's scores and the shared expert's up projection
    assert kept["dot_general", True, "layers_1"] == 2
    # the Mamba and the attention block do no projection again: what is
    # left is the scan's five einsums and the composed attention's two,
    # all with batch dimensions
    assert kept["dot_general", True, "layers_0"] == 5
    assert kept["dot_general", True, "layers_2"] == 2
    for layer in ("layers_0", "layers_2"):
        assert ("dense_dot", True, layer) not in kept
    # (the loss recomputes a row's head matmul either way, in no layer)
    assert not [k for k in none if k[1] and k[2:] not in ((), (None,))]


# -- the Mamba and attention blocks of ``nemotron_h`` keep their matmuls --------

# seq 128: one flash tile; the tiny config's chunk of 16 gives 8 chunks
def _mixer_stacks(test):
    test = pytest.mark.parametrize("fused", [False, True],
                                   ids=["composed", "fused"])(test)
    return pytest.mark.parametrize("pattern", ["M", "*", "M*M"])(test)


@_mixer_stacks
def test_nemotron_mixer_blocks_are_the_same_mathematics(pattern, fused):
    loss, params = _nemotron(pattern, seq=SEQ, fused_kernels=fused)
    peaks = _same_loss_and_gradients(
        loss, _nemotron(pattern, seq=SEQ, fused_kernels=fused,
                        remat=False)[0], params)
    assert all(peak > 0 for peak in peaks)


@_mixer_stacks
def test_nemotron_mixer_blocks_recompute_no_matmul_and_no_flash(pattern,
                                                                fused):
    kept = _ops(*_nemotron(pattern, seq=SEQ, fused_kernels=fused))
    none = _ops(*_nemotron(pattern, seq=SEQ, fused_kernels=fused,
                           remat=False))
    for i, kind in enumerate(pattern):
        layer = f"layers_{i}"
        # no projection (in_proj, out_proj; q, k, v, out) runs again ...
        assert ("dense_dot", True, layer) not in kept
        assert (kept["dense_dot", False, layer]
                == none["dense_dot", False, layer])
        if kind == "*" and fused:
            # ... and no flash forward: once, and the backward kernels as
            # without recomputation
            assert ("flash_fwd", True, layer) not in kept
            flash = [k for k in none if k[0].startswith("flash_")
                     and k[2:] == (layer,)]
            assert len(flash) >= 2 and all(kept[k] == none[k] == 1
                                           for k in flash), flash
        if kind == "M":
            # ... while the chunked scan does: its einsums carry batch
            # dimensions and ``ssd_scan`` names nothing (ROADMAP.md S6)
            assert kept["dot_general", True, layer] == 5
    # the elementwise ops are done again: a block's RMSNorm at the least
    assert any(k[1] and k[2:] == ("layers_0",) for k in kept)
    assert not [k for k in none if k[1] and k[2:] not in ((), (None,))]


# -- names ---------------------------------------------------------------------

@pytest.mark.parametrize("name", ["dots", "none", "Selective", ""])
@pytest.mark.parametrize("model", ["bert", "gpt"])
def test_unknown_policy_raises(model, name):
    with pytest.raises(ValueError, match="remat_policy"):
        MAKE[model](remat_policy=name)
    with pytest.raises(ValueError, match="remat_policy"):
        remat.remat_block(object, (), name)


def test_residual_names_are_one_vocabulary():
    names = profiler.FLASH_RESIDUALS + profiler.MOE_RESIDUALS
    assert len(set(names)) == len(names)
    assert not set(names) & set(profiler.SCOPES + profiler.LAYER_SCOPES
                                + profiler.KERNEL_NAMES
                                + profiler.STEP_COUNTERS)
    for name in names:
        assert name in profiler.__doc__


# -- the tags are the identity outside a remat ------------------------------------

def _qkv(shape, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(3)]


def _entry_transposed(q, k, v):
    return fa.flash_attention(q, k, v, None, True, 0.25, 0.1, jnp.int32(7))


def _entry_with_lse(q, k, v):
    out, lse = fa.flash_attention_with_lse(q, k, v, None, False, 0.25, 0.1,
                                           jnp.int32(7))
    return out * jnp.exp(-lse).transpose(0, 1, 3, 2)


def _entry_bsh(q, k, v):
    return fa.flash_attention_bsh(q, k, v, None, 2, True, 0.125, 0.1,
                                  jnp.int32(7))


ENTRIES = {"flash_attention": (_entry_transposed, (2, 2, 256, 16)),
           "flash_attention_with_lse": (_entry_with_lse, (2, 2, 256, 16)),
           "flash_attention_bsh": (_entry_bsh, (2, 128, 128))}


@pytest.mark.parametrize("entry", sorted(ENTRIES))
def test_residual_tags_change_nothing_outside_remat(entry, monkeypatch):
    fn, shape = ENTRIES[entry]
    q, k, v = _qkv(shape)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v)))

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(grad)(q, k, v))
    for name in profiler.FLASH_RESIDUALS:
        assert f"name[name={name}]" in text
    tagged = jax.jit(grad)(q, k, v)
    # the parent's rules: the same code with no tag (JAX caches a
    # custom_vjp's traced forward rule by function and shapes)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    jax.clear_caches()
    try:
        assert "name[" not in str(jax.make_jaxpr(grad)(q, k, v))
        plain = jax.jit(grad)(q, k, v)
    finally:
        jax.clear_caches()
    for a, b in zip(jax.tree.leaves(tagged), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
