"""Checkpoint round-trip, per-step metrics, host overflow line, and the
same-seed determinism regression (SURVEY.md §5 auxiliary subsystems;
VERDICT round-1 item 9)."""

import io
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.amp import LossScaler
from apex_tpu.optimizers import FusedAdam
from apex_tpu.utils.checkpoint import (
    latest_step,
    load_checkpoint,
    save_checkpoint,
)


def _train_state():
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4, 4).astype("float32")),
              "b": jnp.asarray(rng.randn(4).astype("float32"))}
    opt = FusedAdam(lr=1e-2)
    state = opt.init(params)
    # advance a step so moments are nonzero
    grads = jax.tree.map(jnp.ones_like, params)
    params, state = opt.step(grads, state, params)
    scaler = LossScaler("dynamic")
    sstate = scaler.init()
    return params, opt, state, scaler, sstate


def test_checkpoint_roundtrip_bitwise(tmp_path):
    params, opt, state, scaler, sstate = _train_state()
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, params=params, opt_state=state,
                    scaler_state=sstate)
    assert latest_step(d) == 7

    restored = load_checkpoint(
        d, template=dict(params=params, opt_state=state,
                         scaler_state=sstate))
    assert restored["_step"] == 7
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # NamedTuple containers restored via template
    assert type(restored["opt_state"]).__name__ == "AdamState"
    assert int(restored["opt_state"].step) == 1
    for a, b in zip(jax.tree.leaves(restored["opt_state"]),
                    jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(restored["scaler_state"].loss_scale) == 2.0 ** 16

    # resume: stepping from the restored state matches stepping the live one
    grads = jax.tree.map(jnp.ones_like, params)
    p1, s1 = opt.step(grads, restored["opt_state"], restored["params"])
    p2, s2 = opt.step(grads, state, params)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_latest_and_missing(tmp_path):
    d = str(tmp_path / "ckpt")
    assert latest_step(d) is None
    with pytest.raises(FileNotFoundError):
        load_checkpoint(d)
    params = {"w": jnp.ones((2,))}
    save_checkpoint(d, 1, params=params)
    save_checkpoint(d, 5, params=jax.tree.map(lambda x: x * 5, params))
    assert latest_step(d) == 5
    got = load_checkpoint(d)
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  [5.0, 5.0])


def test_metrics_dict():
    scaler = LossScaler("dynamic")
    st = scaler.init()
    m = LossScaler.metrics(st, grad_norm=jnp.float32(3.5),
                           loss=jnp.float32(1.25))
    assert set(m) == {"loss_scale", "unskipped", "steps_skipped",
                      "grad_norm", "loss"}
    assert float(m["loss_scale"]) == 2.0 ** 16
    assert float(m["grad_norm"]) == 3.5


def test_host_overflow_report_prints_contract_line(capsys):
    from apex_tpu.amp import set_ingraph_logging, set_verbosity
    from apex_tpu.amp._amp_state import get_verbosity

    # earlier tests may have initialized amp with verbosity=0
    prev_verbosity = get_verbosity()
    set_verbosity(1)
    # in-graph logging off (the accelerator default): host fallback must print
    set_ingraph_logging(False)
    try:
        scaler = LossScaler("dynamic")
        st = scaler.init()
        bad = {"g": jnp.asarray([jnp.inf, 1.0])}
        _, found = scaler.unscale(bad, st)
        st2 = scaler.update(st, found)

        skipped = scaler.host_overflow_report(st, st2)
        assert skipped
        out = capsys.readouterr().out  # stdout, where scripts grep
        assert ("Gradient overflow.  Skipping step, loss scaler 0 "
                "reducing loss scale to 32768.0") in out

        # clean step: no line
        good = {"g": jnp.asarray([1.0, 1.0])}
        _, found = scaler.unscale(good, st2)
        st3 = scaler.update(st2, found)
        assert not scaler.host_overflow_report(st2, st3)
    finally:
        set_ingraph_logging(None)
        set_verbosity(prev_verbosity)


def test_no_double_overflow_line_when_ingraph_active(capsys):
    """On callback-capable runtimes the in-graph path prints the line;
    the host fallback must then NOT print it again (grep-and-count)."""
    from apex_tpu.amp import set_ingraph_logging, set_verbosity
    from apex_tpu.amp._amp_state import get_verbosity

    prev_verbosity = get_verbosity()
    set_verbosity(1)
    set_ingraph_logging(True)
    try:
        scaler = LossScaler("dynamic")
        st = scaler.init()
        bad = {"g": jnp.asarray([jnp.inf, 1.0])}
        _, found = scaler.unscale(bad, st)
        st2 = scaler.update(st, found)
        jax.effects_barrier()
        assert scaler.host_overflow_report(st, st2)  # True, but no print
        out = capsys.readouterr().out
        assert out.count("Gradient overflow.") == 1
    finally:
        set_ingraph_logging(None)
        set_verbosity(prev_verbosity)


@pytest.mark.slow
def test_same_seed_bitwise_determinism():
    """SURVEY.md §5 race/determinism row: two runs from the same seed are
    bitwise identical — params, losses, and dropout behavior included."""
    from apex_tpu.models import BertConfig, BertForPreTraining
    from apex_tpu.models.bert import pretraining_loss

    def run():
        cfg = BertConfig.tiny(hidden_dropout=0.1, attention_dropout=0.1)
        model = BertForPreTraining(cfg)
        rng = np.random.RandomState(42)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
        labels = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
        nsp = jnp.asarray(rng.randint(0, 2, (2,)))
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)}, ids, None, None)
        opt = FusedAdam(lr=1e-3)
        state = opt.init(params)

        @jax.jit
        def step(params, state, key):
            def loss_fn(p):
                mlm, nspl = model.apply(p, ids, None, None,
                                        deterministic=False,
                                        rngs={"dropout": key})
                return pretraining_loss(mlm, nspl, labels, nsp)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params2, state2 = opt.step(grads, state, params)
            return params2, state2, loss

        losses = []
        for i in range(3):
            params, state, loss = step(params, state,
                                       jax.random.PRNGKey(100 + i))
            losses.append(np.asarray(loss))
        return losses, params

    l1, p1 = run()
    l2, p2 = run()
    for a, b in zip(l1, l2):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qkv_checkpoint_remap_roundtrip():
    """Layout portability between the TP fused-qkv attention params and
    the non-TP split q/k/v layout (advisor r4): split/merge are exact
    inverses and preserve the Megatron [q | k | v] output-axis order."""
    import numpy as np

    from apex_tpu.utils.checkpoint import merge_split_qkv, split_fused_qkv

    rng = np.random.RandomState(0)
    kq, kk, kv = (rng.randn(8, 8).astype("f4") for _ in range(3))
    fused = {
        "layer_0": {
            "qkv": {"kernel": np.concatenate([kq, kk, kv], axis=-1),
                    "bias": np.arange(24, dtype="f4")},
            "out": {"kernel": rng.randn(8, 8).astype("f4")},
        },
        "layer_1": {
            "attn_qkv": {"kernel": np.concatenate([kq, kk, kv], axis=-1)},
        },
    }
    split = split_fused_qkv(fused)
    np.testing.assert_array_equal(split["layer_0"]["q"]["kernel"], kq)
    np.testing.assert_array_equal(split["layer_0"]["k"]["kernel"], kk)
    np.testing.assert_array_equal(split["layer_0"]["v"]["kernel"], kv)
    np.testing.assert_array_equal(split["layer_0"]["q"]["bias"],
                                  np.arange(8, dtype="f4"))
    assert "qkv" not in split["layer_0"]
    # untouched siblings pass through
    np.testing.assert_array_equal(split["layer_0"]["out"]["kernel"],
                                  fused["layer_0"]["out"]["kernel"])
    # GPT naming handled by the default map
    np.testing.assert_array_equal(split["layer_1"]["attn_q"]["kernel"], kq)

    merged = merge_split_qkv(split)
    jax.tree.map(np.testing.assert_array_equal, merged, fused)


def test_qkv_remap_projection_equivalence():
    """The remapped layouts compute the SAME attention projections: a
    fused qkv matmul + 3-way split equals the three split projections."""
    import numpy as np

    from apex_tpu.utils.checkpoint import split_fused_qkv

    rng = np.random.RandomState(1)
    Wqkv = rng.randn(6, 18).astype("f4")
    x = rng.randn(4, 6).astype("f4")
    split = split_fused_qkv({"qkv": {"kernel": Wqkv}})
    q_f, k_f, v_f = np.split(x @ Wqkv, 3, axis=-1)
    np.testing.assert_allclose(x @ split["q"]["kernel"], q_f, rtol=1e-6)
    np.testing.assert_allclose(x @ split["k"]["kernel"], k_f, rtol=1e-6)
    np.testing.assert_allclose(x @ split["v"]["kernel"], v_f, rtol=1e-6)
