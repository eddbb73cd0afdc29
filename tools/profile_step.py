"""Component-level on-chip profile of the BERT-large headline step.

Chained-carry timing (bench.marginal_time): state evolves through every
call, one value fetch per window, inputs from a fixed seed. Each
component is timed fwd+bwd in isolation so the step decomposes into an
actionable budget (attention kernels / encoder matmuls / MLM tail /
optimizer). Needs the chip: utilization is computed against the
attached chip's published peak (apex_tpu/utils/chip_peaks.py), and an
unknown chip is an error.

Usage:  python tools/profile_step.py [component ...]
        components: attn encoder tail matmul embed opt step
                    dequant_gemm train_sharded
        (default: all)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

_SEED = 0

B, S, H, NH, D, L, I = 16, 512, 1024, 16, 64, 24, 4096
V = 30522


def _peak():
    from apex_tpu.utils.chip_peaks import chip_peaks

    return chip_peaks(jax.devices()[0].device_kind).bf16_flops


def _chain(step, state, iters=8, warmup=2, windows=2):
    """Delegates to bench.marginal_time — ONE timing methodology for
    the whole repo (value-fetch barrier + positive-marginal guard)."""
    import bench

    for _ in range(warmup):
        state = step(*state)
    bench._fetch(state)
    box = [state]

    def advance(n):
        for _ in range(n):
            box[0] = step(*box[0])

    return bench.marginal_time(advance, lambda: bench._fetch(box[0]),
                               iters, windows=windows)


def _reset():
    import gc

    gc.collect()
    jax.clear_caches()
    gc.collect()


def prof_attention():
    """24 layers of flash attention (B, NH, S, D) fwd+bwd, dropout 0.1."""
    from apex_tpu.ops.flash_attention import flash_attention

    # fp32 carry: a bf16 carry with a tiny update rounds back to the
    # IDENTICAL input and the runtime memoizer serves the whole step
    # from cache (observed: 0.02 ms "measurement")
    q = jax.random.normal(jax.random.PRNGKey(_SEED), (B, NH, S, D),
                          jnp.float32)

    def loss(qc):
        x = qc.astype(jnp.bfloat16)
        for i in range(L):
            x = flash_attention(x, x, x, None, False, 0.125, 0.1,
                                _SEED + i)
        return jnp.sum(x.astype(jnp.float32) ** 2)

    @jax.jit
    def step(q):
        dq = jax.grad(loss)(q)
        return (0.999 * q - 1e-3 * jnp.tanh(dq),)

    dt = _chain(step, (q,))
    # useful flops: 4*B*S^2*H per layer fwd, 3 matmuls of same size in
    # bwd (recompute s + dq/dk/dv/dp makes it 5+2 kernel matmuls, but
    # the MFU convention counts fwd 2 + bwd 4 matmul-equivalents)
    flops = 12.0 * L * B * S * S * H
    print(f"attention x{L} fwd+bwd (dropout .1): {dt*1e3:7.2f} ms  "
          f"({flops/dt/1e12:5.1f} TFLOP/s conv, {flops/dt/_peak():.3f} MFU; "
          f"kernel does 7/6 of counted matmuls)")
    return dt


def prof_encoder():
    """Encoder-only (BertModel, no heads/loss/optimizer) fwd+bwd at the
    true dropout config."""
    from apex_tpu.models import BertConfig, BertModel

    cfg = BertConfig.bert_large(dtype=jnp.bfloat16, remat=False)
    model = BertModel(cfg)
    rng = np.random.RandomState(_SEED)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))
    types = jnp.zeros((B, S), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, types, mask)["params"]

    def loss(p, key):
        x, pooled = model.apply({"params": p}, ids, types, mask,
                                deterministic=False,
                                rngs={"dropout": key})
        return jnp.sum(x.astype(jnp.float32) ** 2) * 1e-6

    @jax.jit
    def step(p, key):
        key, sub = jax.random.split(key)
        g = jax.grad(loss)(p, sub)
        # bounded but bf16/f32-visible update: keeps inputs fresh for
        # the memoizer without blowing up over the timing loop
        p2 = jax.tree.map(
            lambda a, b: 0.9995 * a - 1e-4 * jnp.tanh(b.astype(jnp.float32)
                                                      ).astype(a.dtype),
            p, g)
        return p2, key

    dt = _chain(step, (params, jax.random.PRNGKey(_SEED)))
    enc_params = sum(x.size for x in jax.tree.leaves(params))
    flops = 6.0 * enc_params * B * S + 12.0 * L * B * S * S * H
    print(f"encoder-only fwd+bwd (dropout .1):  {dt*1e3:7.2f} ms  "
          f"({flops/dt/1e12:5.1f} TFLOP/s, {flops/dt/_peak():.3f} MFU, "
          f"{enc_params/1e6:.0f}M params)")
    return dt


def prof_tail():
    """MLM head + loss tail alone: transform -> gelu -> LN -> decoder ->
    logsumexp loss (+ NSP head), fwd+bwd from a (B, S, H) activation."""
    from apex_tpu.models.bert import pretraining_loss
    from apex_tpu.normalization import FusedLayerNorm
    import flax.linen as nn

    rng = np.random.RandomState(_SEED)
    x = jnp.asarray(rng.randn(B, S, H).astype("f4") * 0.1)  # f32 carry
    labels = jnp.asarray(
        np.where(rng.rand(B, S) < 0.15, rng.randint(0, V, (B, S)), -1))
    nsp = jnp.asarray(rng.randint(0, 2, (B,)))

    class Tail(nn.Module):
        @nn.compact
        def __call__(self, x):
            h = nn.Dense(H, dtype=jnp.bfloat16, param_dtype=jnp.float32,
                         name="mlm_transform")(x)
            h = nn.gelu(h)
            h = FusedLayerNorm(H, name="mlm_ln")(h)
            mlm = nn.Dense(V, dtype=jnp.bfloat16, param_dtype=jnp.float32,
                           name="mlm_decoder")(h)
            nspl = nn.Dense(2, dtype=jnp.bfloat16, param_dtype=jnp.float32,
                            name="nsp")(x[:, 0])
            return mlm, nspl

    tail = Tail()
    params = tail.init(jax.random.PRNGKey(0), x)["params"]

    def loss(p, x):
        mlm, nspl = tail.apply({"params": p}, x.astype(jnp.bfloat16))
        return pretraining_loss(mlm, nspl, labels, nsp)

    @jax.jit
    def step(p, x):
        l, (g, gx) = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
        p2 = jax.tree.map(
            lambda a, b: 0.9995 * a - 1e-4 * jnp.tanh(b.astype(jnp.float32)
                                                      ).astype(a.dtype),
            p, g)
        return p2, 0.999 * x - 1e-3 * jnp.tanh(gx)

    dt = _chain(step, (params, x))
    flops = 6.0 * (H * V + H * H) * B * S
    print(f"MLM tail fwd+bwd:                   {dt*1e3:7.2f} ms  "
          f"(matmul-ideal {flops/_peak()*1e3:.1f} ms)")
    return dt


def prof_matmul():
    """Matmul-chain ceiling at the encoder shape."""
    a = jax.random.normal(jax.random.PRNGKey(_SEED), (B * S, H),
                          jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (H, I), jnp.bfloat16)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (I, H), jnp.bfloat16)

    @jax.jit
    def step(a):
        # all-bf16 chain (no fp32 intermediate stores). Normalize by RMS
        # instead of a fixed 0.01 scale: the fixed scale decays the carry
        # to exact zeros in a few steps, after which every call has
        # IDENTICAL inputs and the runtime memoizer serves it instantly
        # (observed: negative marginal times).
        for _ in range(8):
            a = jax.lax.dot(jax.lax.dot(a, w1), w2)
            a = (a * jax.lax.rsqrt(jnp.mean(a.astype(jnp.float32) ** 2)
                                   + 1e-6).astype(a.dtype))
        return (a,)

    dt = _chain(step, (a,), iters=8)
    flops = 8 * 2 * 2.0 * B * S * H * I
    print(f"matmul chain ceiling:               {dt*1e3:7.2f} ms  "
          f"({flops/dt/1e12:5.1f} TFLOP/s = {flops/dt/_peak():.2f} of peak)")
    return dt


def prof_dequant_gemm():
    """Quantized-weight matmul chain at the encoder shape: the XLA
    dequant-then-matmul reference vs the fused Pallas dequant-GEMM
    (apex_tpu.ops.dequant_gemm) vs the fp matmul floor — the decode
    weight-read path docs/serving.md's weight_quantization knob buys.
    Same RMS-normalized carry as prof_matmul (defeats the runtime
    memoizer)."""
    from apex_tpu.models.gpt import quantize_dense_kernel
    from apex_tpu.ops import dequant_gemm as dg

    a = jax.random.normal(jax.random.PRNGKey(_SEED), (B * S, H),
                          jnp.float32)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (H, I), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(2), (I, H), jnp.float32)
    q1, s1 = quantize_dense_kernel(w1, "int8")
    q2, s2 = quantize_dense_kernel(w2, "int8")
    flops = 8 * 2 * 2.0 * B * S * H * I
    results = {}

    def norm(a):
        return a * jax.lax.rsqrt(
            jnp.mean(a.astype(jnp.float32) ** 2) + 1e-6).astype(a.dtype)

    for label, mm in (
            ("fp32 matmul floor", lambda x, w, q, s: jnp.dot(x, w)),
            ("XLA dequant chain",
             lambda x, w, q, s: dg.dequant_matmul_reference(x, q, s)),
            ("fused dequant-GEMM",
             lambda x, w, q, s: dg.dequant_matmul(x, q, s,
                                                  use_pallas=True))):

        @jax.jit
        def step(a, mm=mm):
            for _ in range(8):
                a = norm(mm(mm(a, w1, q1, s1), w2, q2, s2))
            return (a,)

        dt = _chain(step, (a,), iters=8)
        results[label] = dt
        print(f"dequant_gemm {label:<22s} {dt*1e3:7.2f} ms  "
              f"({flops/dt/1e12:5.1f} TFLOP/s)")
    return results


def prof_step():
    """Full headline step via bench._measure (same session)."""
    sys.path.insert(0, "/root/repo")
    import bench

    dt, _, mfu = bench._measure(B, S, iters=8, with_baseline=False,
                                remat=False)
    return dt


def prof_embed():
    """BertEmbeddings fwd+bwd alone: vocab gather + pos/type add + LN +
    dropout forward; the backward's cost center is the scatter-add of
    (B*S, H) token grads into the (30522, H) embedding table."""
    from apex_tpu.models import BertConfig
    from apex_tpu.models.bert import BertEmbeddings

    cfg = BertConfig.bert_large(dtype=jnp.bfloat16)
    emb = BertEmbeddings(cfg)
    rng = np.random.RandomState(_SEED)
    ids = jnp.asarray(rng.randint(0, V, (B, S)))
    types = jnp.zeros((B, S), jnp.int32)
    params = emb.init(jax.random.PRNGKey(0), ids, types)["params"]

    def loss(p, key):
        x = emb.apply({"params": p}, ids, types, deterministic=False,
                      rngs={"dropout": key})
        return jnp.sum(x.astype(jnp.float32) ** 2) * 1e-6

    @jax.jit
    def step(p, key):
        key, sub = jax.random.split(key)
        g = jax.grad(loss)(p, sub)
        p2 = jax.tree.map(
            lambda a, b: 0.9995 * a - 1e-4 * jnp.tanh(b.astype(jnp.float32)
                                                      ).astype(a.dtype),
            p, g)
        return p2, key

    dt = _chain(step, (params, jax.random.PRNGKey(_SEED)))
    print(f"embeddings fwd+bwd:                 {dt*1e3:7.2f} ms")
    return dt


def prof_opt():
    """Full-size FusedLAMB O2 step alone (367M params, fp32 masters +
    both moments): state traffic is ~11 GB/step, so the bandwidth
    roofline is ~13 ms — this measures how close the fused update runs
    to it."""
    import apex_tpu.amp as amp
    from apex_tpu.models import BertConfig, BertForPreTraining
    from apex_tpu.optimizers import FusedLAMB

    cfg = BertConfig.bert_large(dtype=jnp.bfloat16)
    model = BertForPreTraining(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, None,
                        jnp.ones((1, 8), jnp.int32))["params"]
    opt = FusedLAMB(lr=1e-4, weight_decay=0.01)
    params, opt, handle = amp.initialize(params, opt, opt_level="O2",
                                         verbosity=0)
    ost = opt.init(params)
    grads = jax.tree.map(lambda p: (p * 1e-3).astype(p.dtype), params)

    @jax.jit
    def step(params, ost, c):
        p2, ost2, found = opt.step(
            jax.tree.map(lambda g: g * (1.0 + c * 1e-6), grads), ost,
            params, grad_scale=jnp.float32(65536.0))
        return p2, ost2, c + 1.0

    # _chain does warmup + fetch before timing, so the compile lands
    # outside every timed window
    dt = _chain(step, (params, ost, jnp.float32(_SEED)))
    print(f"optimizer (FusedLAMB O2 367M):      {dt*1e3:7.2f} ms"
          f"  (state-traffic roofline ~13 ms)")
    return dt


def prof_train_sharded():
    """GPT-tiny 3D-parallel fused train step (docs/training.md
    "Sharded training") on the largest (batch, model) mesh this host's
    devices allow, chained-carry timed like every other component;
    also prints the AOT-audited per-step collective totals so the
    wall-clock attributes to the ZeRO/TP legs, not to guesswork."""
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
    from apex_tpu.serving.mesh import build_mesh
    from apex_tpu.train import build_train_step

    n = jax.device_count()
    shape = (2, 2) if n >= 4 else ((1, 2) if n >= 2 else (1, 1))
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 4, 16)))
    params = model.init(jax.random.PRNGKey(0), tokens[0])["params"]

    def loss_fn(p, mb):
        return lm_loss(model.apply({"params": p}, mb), mb)

    ts = build_train_step(
        loss_fn, DistributedFusedAdam(lr=1e-3, flat_mode="global"),
        accum_steps=2, mesh=build_mesh(shape), num_heads=cfg.num_heads)
    state = ts.init(params)
    audit = ts.audit_collectives(state, tokens)
    total = audit["collectives"]["total"]["ops"]

    def step(st):
        st2, _ = ts.step(st, tokens)
        return (st2,)

    dt = _chain(step, (state,))
    print(f"train-sharded GPT-tiny @ mesh{shape}: {dt*1e3:7.2f} ms/step "
          f"({1.0/dt:5.2f} steps/s; {total} collectives/step, donation "
          f"aliases {audit['alias']['pairs']} covering "
          f"{audit['sharded_leaves']} sharded leaves)")
    return dt


COMPONENTS = {"attn": prof_attention, "encoder": prof_encoder,
              "tail": prof_tail, "matmul": prof_matmul,
              "embed": prof_embed, "opt": prof_opt, "step": prof_step,
              "dequant_gemm": prof_dequant_gemm,
              "train_sharded": prof_train_sharded}


def main():
    want = [a for a in sys.argv[1:] if a in COMPONENTS] or list(COMPONENTS)
    for name in want:
        _reset()
        COMPONENTS[name]()
        _reset()


if __name__ == "__main__":
    main()
