#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py                # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips   # the data-parallel path on 4 chips

One process, no children. With no arguments it needs exactly the chip it
finds and, on it:

1. reports whether the native batch loader built its C library;
2. checks every Pallas kernel of the BERT-large train path against its
   jnp reference at the real widths (reference at ``highest`` matmul
   precision);
3. trains BERT-large (24 x 1024, 16 heads, vocab 30522, dropout 0.1/0.1;
   S=512 with 76 gathered MLM positions, B=16) for 8 steps on one
   repeated batch through the entry points a user calls —
   ``amp.initialize(opt_level="O2")`` + ``FusedLAMB`` +
   ``build_train_step(donate=True)`` + ``TrainLoop`` — and checks the
   losses, the single compilation, the compiled-in kernels, the
   donation and the overflow skip.

``--four-chips`` runs ONLY the data-parallel phase: the same model
through ``build_train_step(ddp=DistributedDataParallel(...), mesh=...)``
at per-chip batch 4 against the same global batch accumulated on one of
the four chips (``accum_steps=4``).

Any failed check raises and no failed dispatch is retried, so the exit
code is non-zero and the last line is not the result. The last line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Without a TPU the script exits 1 and prints no result; there is no
CPU mode. ``tests/test_chip_smoke.py`` rehearses the phase functions at
tiny shapes on the CPU instead. Times printed here are smoke readings on
the host clock, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, NamedTuple

import numpy as np

BATCH, SEQ, N_PRED = 16, 512, 76   # the headline shape (MLPerf: 76 at S=512)
N_STEPS = 8
DDP_STEPS, DDP_WORLD = 4, 4
LR = 1e-3
SEED = 0

# Stated tolerances. Kernel outputs are bf16 (8 mantissa bits, ulp
# 2^-8 relative) computed with single-pass bf16 MXU matmuls; the
# references run in fp32 at ``highest`` precision. Errors are max|a-b|
# over max|b|.
KERNEL_TOL = 2e-2
KEEP_SHARE_TOL = 1e-3       # kept share of a 0.1-rate mask vs 0.9 ...
DDP_LOSS_RTOL = 1e-2        # DDP-on-4 vs accumulate-on-1 loss, each step
# ... and the share of the optimizer's total movement (fp32 masters,
# final minus initial, all leaves) on which the two runs disagree. An
# unsynchronised replica moves along its own shard's gradient signs and
# disagrees on O(1) of it.
DDP_UPDATE_RTOL = 0.25


def keep_share_tol(n: int) -> float:
    """... or five standard deviations of a Bernoulli(0.9) share over
    ``n`` draws, where that is wider (the tiny CPU rehearsal)."""
    return max(KEEP_SHARE_TOL, 5.0 * (0.09 / n) ** 0.5)


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: FAILED: {what}")
    log(f"  ok: {what}")


def device_record() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# the model, the data, the step
# ---------------------------------------------------------------------------


def bert_large_config():
    import jax.numpy as jnp

    from apex_tpu.models import BertConfig

    return BertConfig.bert_large(dtype=jnp.bfloat16, fused_kernels=True)


def make_batch(cfg, batch: int, seq: int, n_pred: int, *, accum: int = 1,
               shards: int = 1, seed: int = SEED) -> dict:
    """One global batch as host numpy, leaves ``[accum, batch // accum,
    ...]``: token ids and MLM labels from the native MLM loader over a
    seeded corpus, the labels re-packed as the MLPerf gathered-positions
    format (``n_pred`` slots per row with weights). ``seed`` carries one
    dropout seed per (microbatch, shard), so that shard ``i`` of a
    data-parallel step and microbatch ``i`` of an accumulated step draw
    the same masks."""
    from apex_tpu.data import MLMBatchLoader

    rng = np.random.RandomState(seed)
    corpus = rng.randint(5, cfg.vocab_size, (batch, seq)).astype(np.int32)
    corpus[:, 0] = 1  # [CLS]-slot analog, never masked
    loader = MLMBatchLoader(corpus, batch_size=batch,
                            vocab_size=cfg.vocab_size, mask_id=4,
                            special_ids=[0, 1, 2, 3, 4], seed=seed,
                            prefetch=0)
    (ids, labels), = list(loader)
    positions = np.zeros((batch, n_pred), np.int32)
    mlm_labels = np.zeros((batch, n_pred), np.int32)
    mlm_weights = np.zeros((batch, n_pred), np.float32)
    for b in range(batch):
        chosen = np.flatnonzero(labels[b] >= 0)[:n_pred]
        positions[b, :len(chosen)] = chosen
        mlm_labels[b, :len(chosen)] = labels[b, chosen]
        mlm_weights[b, :len(chosen)] = 1.0
    # a padded tail on some rows, so the key mask of the attention
    # kernel is exercised
    attn = np.ones((batch, seq), np.int32)
    attn[batch // 2:, seq - seq // 8:] = 0
    mlm_weights *= np.take_along_axis(attn, positions, axis=1)
    flat = {
        "ids": ids.astype(np.int32),
        "types": np.zeros((batch, seq), np.int32),
        "attn": attn,
        "positions": positions,
        "mlm_labels": mlm_labels,
        "mlm_weights": mlm_weights,
        "nsp_labels": rng.randint(0, 2, (batch,)).astype(np.int32),
    }
    out = {k: v.reshape(accum, batch // accum, *v.shape[1:])
           for k, v in flat.items()}
    out["seed"] = (1 + np.arange(accum * shards, dtype=np.int32)
                   ).reshape(accum, shards)
    return out


class Trainer(NamedTuple):
    step: Any          # apex_tpu.train.TrainStep
    state: Any         # TrainState (arrays, or ShapeDtypeStructs)
    batch: Any         # dict of arrays (or ShapeDtypeStructs)
    n_params: int
    traces: list       # one entry per trace of the loss function


def build_bert_trainer(cfg, *, batch: int, seq: int, n_pred: int = N_PRED,
                       donate: bool = True, accum_steps: int = 1,
                       ddp=None, mesh=None, abstract_on=None) -> Trainer:
    """BERT pretraining through the normal entry points:
    ``amp.initialize(O2)`` + ``FusedLAMB`` + ``build_train_step``.

    State and batch go to JAX's default device, or with ``mesh`` are
    replicated / split over it. ``abstract_on`` (the state's sharding on
    described devices) builds nothing on any device: state and batch
    come back as ``ShapeDtypeStruct``s, for compile-only use."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import apex_tpu.amp as amp
    from apex_tpu.models import BertForPreTraining, pretraining_loss
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.train import build_train_step

    model = BertForPreTraining(cfg)
    world = 1 if mesh is None else mesh.devices.size
    host_batch = make_batch(cfg, batch, seq, n_pred, accum=accum_steps,
                            shards=world)
    traces = []

    def loss_fn(params, mb):
        traces.append(1)
        # the dropout stream comes from the batch, never from a constant
        # closed over on the host
        key = jax.random.PRNGKey(mb["seed"][0])
        mlm, nsp = model.apply(
            {"params": params}, mb["ids"], mb["types"], mb["attn"],
            deterministic=False, rngs={"dropout": key},
            masked_positions=mb["positions"])
        return pretraining_loss(mlm, nsp, mb["mlm_labels"],
                                mb["nsp_labels"], mb["mlm_weights"])

    sample = {k: host_batch[k][0][:1] for k in ("ids", "types", "attn")}

    def init_params(key):
        return model.init(key, sample["ids"], sample["types"],
                          sample["attn"])["params"]

    made = {}

    def make_state(params):
        params, opt, handle = amp.initialize(
            params, FusedLAMB(lr=LR, weight_decay=0.01), opt_level="O2",
            verbosity=0)
        made["step"] = build_train_step(
            loss_fn, opt, amp=handle, ddp=ddp, mesh=mesh,
            accum_steps=accum_steps, donate=donate)
        return made["step"].init(params)

    key = jax.random.PRNGKey(SEED)
    batch_sharding = abstract_on
    if mesh is not None:
        batch_sharding = NamedSharding(mesh, P(None, ddp.axis_name))
    if abstract_on is not None:
        def abstract(tree, sharding):
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    jnp.shape(x), jnp.result_type(x), sharding=sharding),
                tree)

        state = abstract(
            jax.eval_shape(lambda k: make_state(init_params(k)), key),
            abstract_on)
        dev_batch = abstract(host_batch, batch_sharding)
    else:
        # params are born under jit (one program, not an op-by-op walk
        # over 400 leaves); amp.initialize and the optimizer init then
        # run eagerly, as a user's script would
        state = make_state(jax.jit(init_params)(key))
        dev_batch = jax.tree.map(jnp.asarray, host_batch)
        if mesh is not None:
            # replicate the state over the mesh up front: a state left on
            # one chip would be copied by the first step, and the copy,
            # not the original, donated
            state = jax.device_put(state, NamedSharding(mesh, P()))
            dev_batch = jax.device_put(dev_batch, batch_sharding)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(state.params))
    return Trainer(made["step"], state, dev_batch, n_params, traces)


# ---------------------------------------------------------------------------
# phase: the native batch loader
# ---------------------------------------------------------------------------


def phase_loader() -> None:
    from apex_tpu.data import native_available

    log("[loader] native batch loader: "
        + ("C library built (csrc/dataloader.c)" if native_available()
           else "no C compiler here, numpy fallback"))


# ---------------------------------------------------------------------------
# phase: each kernel against its reference
# ---------------------------------------------------------------------------


def _err(got, ref) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def _check_close(name: str, got, ref, tol: float = KERNEL_TOL) -> None:
    import jax

    errs = [_err(g, r) for g, r in zip(jax.tree.leaves(got),
                                       jax.tree.leaves(ref))]
    check(all(np.isfinite(errs)) and max(errs) <= tol,
          f"{name}: max err/max|ref| per output "
          f"{[float(f'{e:.2e}') for e in errs]} <= {tol}")


def phase_kernels(B: int = BATCH, NH: int = 16, S: int = SEQ, D: int = 64
                  ) -> None:
    """LayerNorm, flash attention (both layouts, with key mask and
    replayed hardware-PRNG dropout), scale-mask softmax and fused
    dropout, forward and backward, against jnp references."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.dropout import fused_dropout
    from apex_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_bsh,
        flash_dropout_keep_mask,
        mha_with_mask_reference,
    )
    from apex_tpu.ops.layer_norm import (
        fused_layer_norm_affine,
        layer_norm_reference,
    )
    from apex_tpu.ops.softmax import scaled_masked_softmax, softmax_reference

    H, rows, rate = NH * D, B * S, 0.1
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    f32 = jnp.float32
    log(f"[kernels] B={B} heads={NH} S={S} D={D} (rows={rows}, hidden={H})")

    def fwd_bwd(fn, n_diff):
        """jit of ``(cot, *args) -> (out, grads wrt args[:n_diff])``.
        Every array is an argument: a mask or cotangent closed over
        would be baked into the executable as a constant. The cotangent
        is random, so that a backward error cannot hide in a uniform
        one."""
        def run(cot, *args):
            rest = args[n_diff:]
            out, vjp = jax.vjp(lambda *d: fn(*d, *rest), *args[:n_diff])
            return out, vjp(cot.astype(out.dtype))
        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        # -- LayerNorm (rows, hidden) ---------------------------------------
        x = jax.random.normal(keys[0], (rows, H), jnp.bfloat16)
        w = 1.0 + 0.1 * jax.random.normal(keys[1], (H,), f32)
        b = 0.1 * jax.random.normal(keys[2], (H,), f32)
        cot = jax.random.normal(keys[3], (rows, H), f32)
        got = fwd_bwd(lambda x, w, b: fused_layer_norm_affine(
            x, w, b, 1e-12), 3)(cot, x, w, b)
        ref = fwd_bwd(lambda x, w, b: layer_norm_reference(
            x, w, b, 1e-12), 3)(cot, x.astype(f32), w, b)
        _check_close("layer_norm fwd+bwd", got, ref)

        # -- flash attention, key mask + dropout 0.1 ------------------------
        q, k, v = (jax.random.normal(kk, (B, NH, S, D), jnp.bfloat16)
                   for kk in keys[4:7])
        key_mask = jnp.zeros((B, S), bool).at[B // 2:, S - S // 8:].set(True)
        seed = jnp.int32(1234)
        scale = D ** -0.5
        cot = jax.random.normal(keys[7], (B, NH, S, D), f32)
        keep = jax.jit(lambda s: flash_dropout_keep_mask(
            B, NH, S, S, rate, s))(seed)
        share = float(jnp.mean(keep.astype(f32)))
        check(abs(share - (1 - rate)) <= keep_share_tol(keep.size),
              f"flash dropout mask keeps {share:.5f} of {keep.size} "
              f"(0.9 +- {keep_share_tol(keep.size):.1e})")
        ref = fwd_bwd(lambda q, k, v, keep, mask: mha_with_mask_reference(
            q, k, v, keep, mask, False, scale, rate), 3)(
            cot, q.astype(f32), k.astype(f32), v.astype(f32), keep, key_mask)
        got = fwd_bwd(lambda q, k, v, mask, seed: flash_attention(
            q, k, v, mask, False, scale, rate, seed), 3)(
            cot, q, k, v, key_mask, seed)
        _check_close("flash_attention fwd+bwd (mask replayed in bwd)",
                     got, ref)

        # the layout the model runs: (B, S, heads*D), same mask stream
        def to_bsh(t):
            return t.transpose(0, 2, 1, 3).reshape(B, S, H)

        got = fwd_bwd(lambda q, k, v, mask, seed: flash_attention_bsh(
            q, k, v, mask, NH, False, scale, rate, seed), 3)(
            to_bsh(cot), to_bsh(q), to_bsh(k), to_bsh(v), key_mask, seed)
        _check_close("flash_attention_bsh fwd+bwd (mask replayed in bwd)",
                     got, jax.tree.map(to_bsh, ref))
        del keep, ref, got

        # -- scale-mask softmax (B, heads, S, S) ----------------------------
        sc = jax.random.normal(keys[4], (B, NH, S, S), jnp.bfloat16) * 4.0
        pad = key_mask[:, None, None, :]
        cot = jax.random.normal(keys[5], (B, NH, S, S), f32)
        got = fwd_bwd(lambda t, m: scaled_masked_softmax(t, m, scale), 1)(
            cot, sc, pad)
        ref = fwd_bwd(lambda t, m: softmax_reference(t, m, scale), 1)(
            cot, sc.astype(f32), pad)
        _check_close("scaled_masked_softmax fwd+bwd", got, ref)
        del sc, cot, got, ref

        # -- fused dropout (rows, hidden) -----------------------------------
        # inputs bounded away from zero: "kept" is read off y != 0
        x = (jnp.abs(x.astype(f32)) + 0.5).astype(jnp.bfloat16)
        y, (gx,) = fwd_bwd(lambda t, s: fused_dropout(t, rate, s), 1)(
            jnp.ones((rows, H), f32), x, seed)
        kept = y != 0
        share = float(jnp.mean(kept.astype(f32)))
        check(abs(share - (1 - rate)) <= keep_share_tol(y.size),
              f"fused_dropout keeps {share:.5f} of {y.size} "
              f"(0.9 +- {keep_share_tol(y.size):.1e})")
        _check_close("fused_dropout kept values",
                     y, jnp.where(kept, x.astype(f32) / (1 - rate), 0.0))
        check(bool(jnp.array_equal(gx != 0, kept)),
              "fused_dropout backward mask equals forward mask")


# ---------------------------------------------------------------------------
# phase: BERT-large training on one chip
# ---------------------------------------------------------------------------


def _poisoned(batch: dict, row: int, value) -> dict:
    """``batch`` with one non-finite MLM weight in ``row`` of microbatch
    0, placed like the original."""
    import jax

    weights = np.array(batch["mlm_weights"])
    weights[0, row, 0] = value
    return dict(batch, mlm_weights=jax.device_put(
        weights, batch["mlm_weights"].sharding))


def _peak_bytes() -> int:
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def live_bytes(mem) -> int:
    """Bytes a compiled program holds at once: arguments and outputs,
    less what donation aliases, plus its temporaries."""
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def phase_train(cfg=None, batch: int = BATCH, seq: int = SEQ,
                n_pred: int = N_PRED, n_steps: int = N_STEPS,
                expect_kernels: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from apex_tpu.train import TrainLoop

    cfg = bert_large_config() if cfg is None else cfg
    log(f"[train] BERT {cfg.num_layers} x {cfg.hidden_size}, "
        f"{cfg.num_heads} heads, vocab {cfg.vocab_size}, dropout "
        f"{cfg.hidden_dropout}/{cfg.attention_dropout}; B={batch} "
        f"S={seq} P={n_pred}; amp O2 + FusedLAMB(lr={LR}) + "
        f"build_train_step(donate=True) + TrainLoop")
    t0 = time.perf_counter()
    run = build_bert_trainer(cfg, batch=batch, seq=seq, n_pred=n_pred,
                             donate=True)
    jax.block_until_ready(run.state)
    log(f"  {run.n_params / 1e6:.1f}M params; state built in "
        f"{time.perf_counter() - t0:.1f} s")

    # the compiled program, ahead of the first step: kernels, memory; the
    # program's compile record splits its set-up by stage
    from apex_tpu import profiler

    record = profiler.compile_record()
    seen = len(record.spans())
    compiled = run.step.lower(run.state, run.batch).compile()
    split = record.split(record.spans()[seen:])
    text = compiled.as_text()
    n_kernels = text.count("tpu_custom_call")
    mem = compiled.memory_analysis()
    log(f"  the step ahead of time, by the compile record: trace "
        f"{split['trace']:.1f} s, lower {split['lower']:.1f} s, compile "
        f"{split['compile']:.1f} s (persistent cache: "
        f"{', '.join(split['cache']) or 'not asked'}); "
        f"tpu_custom_call x{n_kernels}; program memory: arguments "
        f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, aliased "
        f"{mem.alias_size_in_bytes / 2**30:.2f}, temp "
        f"{mem.temp_size_in_bytes / 2**30:.2f}, live "
        f"{live_bytes(mem) / 2**30:.2f}")
    if expect_kernels:
        check(n_kernels > 0, f"Pallas kernels are compiled into the step "
                             f"(tpu_custom_call x{n_kernels})")
    check(mem.alias_size_in_bytes > 0, "the compiled step aliases its "
                                       "donated state")
    del compiled, text

    loop = TrainLoop(run.step, run.state, max_retries=0)
    donated_leaf = jax.tree.leaves(run.state.params)[0]
    metrics, times = [], []
    traces_after_first = None
    for i in range(n_steps):
        t0 = time.perf_counter()
        m = loop.step(run.batch)
        jax.block_until_ready(loop.state)
        times.append(time.perf_counter() - t0)
        if m is not None:
            metrics.append(m)
        if i == 0:
            traces_after_first = len(run.traces)
            log(f"  first step (jit compile through the cache + run): "
                f"{times[0]:.1f} s")
    metrics.append(loop.drain())
    losses = [m["loss"] for m in metrics]
    log("  losses: " + " ".join(f"{x:.4f}" for x in losses))
    warm = times[2:] or times[1:]
    log(f"  smoke reading, not a benchmark: {np.median(warm) * 1e3:.1f} "
        f"ms/step median of steps {n_steps - len(warm) + 1}-{n_steps} "
        f"(host clock around block_until_ready)")
    log(f"  peak_bytes_in_use: {_peak_bytes() / 2**30:.2f} GiB")
    check(len(losses) == n_steps and all(np.isfinite(losses)),
          f"{n_steps} finite losses")
    check(not any(m["skipped"] for m in metrics), "no step skipped")
    check(losses[-1] < losses[0],
          f"loss fell on the repeated batch ({losses[0]:.4f} -> "
          f"{losses[-1]:.4f})")
    check(len(run.traces) == traces_after_first,
          "the step was traced for the first call only (no retrace)")
    check(donated_leaf.is_deleted(),
          "the donated input state was consumed (is_deleted)")

    # one overflow step: skipped, params bit-unchanged, scale halved
    before = jax.tree.map(jnp.copy, loop.state.params)
    scale_before = float(loop.state.scaler_state.loss_scale)
    loop.step(_poisoned(run.batch, 0, jnp.inf))
    m = loop.drain()
    same = jax.jit(lambda a, b: jnp.all(jnp.stack(
        [jnp.array_equal(x, y) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b))])))(
        before, loop.state.params)
    scale_after = float(loop.state.scaler_state.loss_scale)
    check(bool(m["skipped"]), "overflow step: skipped")
    check(bool(same), "overflow step: params bit-unchanged")
    check(scale_after == scale_before / 2,
          f"overflow step: loss scale halved ({scale_before:g} -> "
          f"{scale_after:g})")


# ---------------------------------------------------------------------------
# phase: data-parallel on four chips vs accumulation on one
# ---------------------------------------------------------------------------


def _shards_equal(tree) -> bool:
    import jax

    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        first = np.asarray(shards[0].data)
        if not all(np.array_equal(first, np.asarray(s.data))
                   for s in shards[1:]):
            return False
    return True


def phase_ddp(cfg=None, per_chip_batch: int = 4, seq: int = SEQ,
              n_pred: int = N_PRED, n_steps: int = DDP_STEPS,
              world: int = DDP_WORLD, expect_kernels: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.train import TrainLoop
    from apex_tpu.utils.hlo_audit import collective_stats

    cfg = bert_large_config() if cfg is None else cfg
    devices = jax.devices()[:world]
    check(len(devices) == world, f"{world} devices present")
    # the comparison runs where JAX places by default: on the first of them
    batch = per_chip_batch * world
    log(f"[ddp] BERT {cfg.num_layers} x {cfg.hidden_size}, S={seq}: "
        f"DistributedDataParallel(delay_allreduce=True) over {world} "
        f"chips at per-chip batch {per_chip_batch}, vs accum_steps="
        f"{world} of the same global batch on one chip; {n_steps} steps")

    # -- the comparison: the same mean of `world` microbatch gradients ------
    ref = build_bert_trainer(cfg, batch=batch, seq=seq, n_pred=n_pred,
                             accum_steps=world)
    masters0 = jax.tree.map(np.asarray, ref.state.opt_state.master)
    ref_loop = TrainLoop(ref.step, ref.state, max_retries=0)
    ref_metrics = ref_loop.run([ref.batch] * n_steps)
    ref_losses = [m["loss"] for m in ref_metrics]
    ref_masters = jax.tree.map(np.asarray, ref_loop.state.opt_state.master)
    log("  accumulate-on-1 losses: "
        + " ".join(f"{x:.4f}" for x in ref_losses))
    del ref_loop, ref

    # -- the path under test --------------------------------------------------
    mesh = jax.make_mesh((world,), ("data",), devices=devices)
    ddp = DistributedDataParallel("data", delay_allreduce=True)
    run = build_bert_trainer(cfg, batch=batch, seq=seq, n_pred=n_pred,
                             ddp=ddp, mesh=mesh)
    t0 = time.perf_counter()
    text = run.step.lower(run.state, run.batch).compile().as_text()
    log(f"  compile (lower + compile, AOT): "
        f"{time.perf_counter() - t0:.1f} s")
    stats = collective_stats(text)
    grad_bytes = 4 * run.n_params  # the fp32 accumulators DDP reduces
    n_kernels = text.count("tpu_custom_call")
    log(f"  all-reduce: {stats['all-reduce']['ops']} ops, "
        f"{stats['all-reduce']['bytes']} bytes (gradient bytes "
        f"{grad_bytes}); tpu_custom_call x{n_kernels} inside shard_map")
    check(stats["all-reduce"]["bytes"] >= grad_bytes,
          "all-reduce bytes cover the gradient bytes")
    if expect_kernels:
        check(n_kernels > 0, "Pallas kernels are compiled inside shard_map")
    del text

    loop = TrainLoop(run.step, run.state, max_retries=0)
    metrics = loop.run([run.batch] * n_steps)
    losses = [m["loss"] for m in metrics]
    log("  ddp-on-4 losses:        " + " ".join(f"{x:.4f}" for x in losses))
    check(all(np.isfinite(losses)) and not any(
        m["skipped"] for m in metrics), f"{n_steps} finite, unskipped steps")
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    check(worst <= DDP_LOSS_RTOL,
          f"losses agree each step (worst relative difference "
          f"{worst:.2e} <= {DDP_LOSS_RTOL})")

    placed = {s.device for s in
              jax.tree.leaves(loop.state.params)[0].addressable_shards}
    check(len(placed) == world,
          f"parameters sit on {world} distinct devices")
    check(_shards_equal(loop.state.params),
          "parameters are equal across the devices after the last step")

    masters = jax.tree.map(np.asarray, loop.state.opt_state.master)

    def sq(a, b):
        return sum(float(np.sum((x.astype(np.float64) - y) ** 2))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    moved = np.sqrt(sq(ref_masters, masters0))
    apart = np.sqrt(sq(masters, ref_masters))
    check(moved > 0 and apart / moved <= DDP_UPDATE_RTOL,
          f"final fp32 masters agree: |ddp - accum| / |accum - init| = "
          f"{apart / moved:.3e} <= {DDP_UPDATE_RTOL}")

    # -- a NaN in ONE chip's shard: all four skip in lockstep ---------------
    before = jax.tree.map(np.asarray, loop.state.params)
    steps_skipped = int(loop.state.scaler_state.steps_skipped)
    # the first row of chip 1's shard
    loop.step(_poisoned(run.batch, per_chip_batch, jnp.nan))
    m = loop.drain()
    check(bool(m["skipped"]) and int(
        loop.state.scaler_state.steps_skipped) == steps_skipped + 1,
        "one-shard NaN: the step was skipped")
    check(_shards_equal(loop.state.params) and all(
        np.array_equal(a, np.asarray(b)) for a, b in zip(
            jax.tree.leaves(before), jax.tree.leaves(loop.state.params))),
        f"one-shard NaN: all {world} replicas skipped in lockstep "
        f"(params unchanged and equal on every device)")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel phase, on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU here (JAX found {dev['platform']} "
              f"{dev['kind']!r} x{dev['count']}); this script has no CPU "
              f"mode", file=sys.stderr)
        return 1

    from apex_tpu.utils.compile_cache import enable_compile_cache

    log(f"[device] {dev['platform']} {dev['kind']!r} x{dev['count']}; "
        f"jax {jax.__version__}; compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_ddp()
    else:
        phase_loader()
        phase_kernels()
        phase_train()
    log(f"[done] all phases passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
