"""BERT pretraining through the flagship stack: amp O2 (bf16 + fp32
masters) + FusedLAMB + Pallas fused kernels (+ optional data-parallel
mesh) — the BASELINE configs[4] workload at selectable size.

The rebuild's analog of the reference's MLPerf-BERT harness entry point
(SURVEY.md §6). Synthetic token data (no network in the sandbox); the
data flow, kernels, and amp/optimizer machinery are the real thing.

Run::

    python examples/train_bert.py --config tiny --steps 10
    python examples/train_bert.py --config large --batch-size 8 --seq 128
    python examples/train_bert.py --config tiny --data-parallel  # dp mesh

Works on CPU (tiny) and a TPU chip (tiny/base/large) unchanged.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import BertConfig, BertForPreTraining
from apex_tpu.models.bert import pretraining_loss
from apex_tpu.optimizers import FusedLAMB


def synthetic_batch(cfg, batch, seq, seed):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq))
    labels = np.where(rng.rand(batch, seq) < 0.15,
                      rng.randint(0, cfg.vocab_size, (batch, seq)), -1)
    nsp = rng.randint(0, 2, (batch,))
    mask = np.ones((batch, seq), np.int32)
    return (jnp.asarray(ids), jnp.asarray(labels), jnp.asarray(nsp),
            jnp.asarray(mask))


def make_loader(cfg, batch, seq, steps):
    """Real input pipeline over a synthetic corpus: C-path shuffle +
    row gather + MLM masking with background prefetch
    (apex_tpu.data.MLMBatchLoader)."""
    from apex_tpu.data import MLMBatchLoader

    # fixed-size corpus cycled over epochs (set_epoch reshuffles+remasks)
    # — constant host memory no matter how many steps
    n_rows = min(max(batch * steps, batch), max(batch, 4096))
    rng = np.random.RandomState(1234)
    corpus = rng.randint(5, cfg.vocab_size, (n_rows, seq)).astype(np.int32)
    corpus[:, 0] = 1  # [CLS]-slot analog, never masked
    return MLMBatchLoader(corpus, batch_size=batch,
                          vocab_size=cfg.vocab_size, mask_id=4,
                          special_ids=[0, 1, 2, 3, 4], prefetch=2)


def main():
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="tiny",
                    choices=["tiny", "base", "large"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--data-parallel", action="store_true",
                    help="shard the batch over all devices (dp mesh)")
    args = ap.parse_args()

    maker = {"tiny": BertConfig.tiny, "base": BertConfig.bert_base,
             "large": BertConfig.bert_large}[args.config]
    cfg = maker(dtype=jnp.bfloat16, hidden_dropout=0.0,
                attention_dropout=0.0,
                max_position_embeddings=max(args.seq, 512))
    model = BertForPreTraining(cfg)
    print(f"backend={jax.default_backend()} config={args.config} "
          f"B={args.batch_size} S={args.seq} dp={args.data_parallel}")

    ids, labels, nsp, mask = synthetic_batch(
        cfg, args.batch_size, args.seq, 0)
    params = model.init(jax.random.PRNGKey(0), ids, None, mask)

    # O2: bf16 model, fp32 masters inside FusedLAMB, dynamic scaler
    params, optimizer, handle = amp.initialize(
        params, FusedLAMB(lr=args.lr), opt_level="O2",
        cast_model_type=jnp.bfloat16)

    def build_step():
        def step(params, opt_state, scaler_state, ids, labels, nsp, mask):
            def loss_fn(p):
                mlm, nspl = model.apply(p, ids, None, mask)
                return pretraining_loss(mlm, nspl, labels, nsp)

            vg = handle.value_and_grad(loss_fn, scaler_state)
            (loss, found_inf), grads = vg(params)
            if args.data_parallel:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, "data"), grads)
                found_inf = jax.lax.pmax(
                    found_inf.astype(jnp.int32), "data").astype(bool)
            new_params, new_opt = optimizer.step(
                grads, opt_state, params, skip_if=found_inf)
            new_scaler = handle.update_scale(scaler_state, found_inf)
            if args.data_parallel:
                loss = jax.lax.pmean(loss, "data")
            return new_params, new_opt, new_scaler, loss

        return step

    opt_state = optimizer.init(params)
    scaler_state = handle.init_state()
    step_fn = build_step()

    if args.data_parallel:
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        data_specs = (P("data"), P("data"), P("data"), P("data"))
        step_fn = jax.shard_map(
            step_fn, mesh=mesh,
            in_specs=(P(), P(), P()) + data_specs,
            out_specs=(P(), P(), P(), P()))
    # params and optimizer state are donated: updated in place, held
    # once. The scaler state (four scalars) is not: the loop reads the
    # previous one after the step for the overflow report.
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    loader = make_loader(cfg, args.batch_size, args.seq, args.steps)
    nsp_rng = np.random.RandomState(99)
    batches = iter(loader)

    def next_batch():
        nonlocal batches
        try:
            return next(batches)
        except StopIteration:  # epoch boundary: reshuffle + remask
            loader.set_epoch(loader.epoch + 1)
            batches = iter(loader)
            return next(batches)

    t0 = time.perf_counter()
    for i in range(args.steps):
        # prefetched host batch (C-path gather + MLM mask); NSP labels
        # are synthetic — the corpus has no sentence-pair structure
        ids_np, labels_np = next_batch()
        b = (jnp.asarray(ids_np), jnp.asarray(labels_np),
             jnp.asarray(nsp_rng.randint(0, 2, (args.batch_size,))),
             jnp.ones((args.batch_size, args.seq), jnp.int32))
        prev = scaler_state
        params, opt_state, scaler_state, loss = step_fn(
            params, opt_state, scaler_state, *b)
        handle.scalers[0].host_overflow_report(prev, scaler_state)
        if i == 0:
            jax.block_until_ready(loss)
            t0 = time.perf_counter()  # exclude compile
            print(f"step 0 loss {float(loss):.4f} (compiled)")
        elif i == args.steps - 1 or i % 5 == 0:
            print(f"step {i} loss {float(loss):.4f} "
                  f"scale {float(scaler_state.loss_scale):.0f}")
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    steps_timed = max(args.steps - 1, 1)
    sps = args.batch_size * steps_timed / dt
    print(f"{steps_timed} steps in {dt:.2f}s = "
          f"{1000 * dt / steps_timed:.1f} ms/step, {sps:.1f} samples/s")


if __name__ == "__main__":
    main()
