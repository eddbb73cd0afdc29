"""Headline benchmark: BERT-large pretraining step throughput, one chip.

BASELINE.json configs[4]: amp O2 (bf16 + fp32 masters) + FusedLAMB with
the Pallas fused LayerNorm / scale-mask-softmax / flash-attention
kernels, at the TRUE pretraining config — hidden and attention dropout
0.1, attention dropout fused into the flash kernel (hardware PRNG).
The reference publishes no numbers (BASELINE.md), so ``vs_baseline`` is
measured in-run against the unfused fp32 recipe (stock flax LayerNorm +
jnp softmax + materialized-score attention, fp32 params, same LAMB math,
same dropout) — i.e. the speedup this framework's mixed-precision +
fused-kernel path delivers over the naive one, which is exactly the
value apex adds over eager torch.

Prints ONE JSON line (on TPU — the BASELINE seq-512-class shape):
  {"metric": "bert_large_pretrain_s512_samples_per_sec_per_chip",
   "value": <optimized samples/sec/chip>, "unit": "samples/sec",
   "vs_baseline": <optimized / fp32-unfused>}
Off-TPU the flow runs as a tiny-model smoke and the metric is named
"bert_tiny_smoke_samples_per_sec" so nothing records it as a real
bert-large number.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


# Every benchmark input comes from this fixed seed: a re-run measures
# the same data.
_SEED = 0


def build_step(cfg_kwargs, opt_level, batch, seq):
    import apex_tpu.amp as amp
    from apex_tpu.models import BertConfig, BertForPreTraining, pretraining_loss
    from apex_tpu.optimizers import FusedLAMB

    maker = (BertConfig.bert_large if jax.default_backend() == "tpu"
             else BertConfig.tiny)  # off-TPU smoke: shape-check the flow
    # class-default dropouts (0.1/0.1): the real pretraining config
    cfg = maker(**cfg_kwargs)
    model = BertForPreTraining(cfg)

    rng = np.random.RandomState(_SEED)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)))
    types = jnp.zeros((batch, seq), jnp.int32)
    attn = jnp.ones((batch, seq), jnp.int32)
    # MLPerf input format (round 4): masked positions as a dense (B, P)
    # list with per-slot weights, P = max_predictions_per_seq (76 at
    # S=512, the MLPerf value) — the MLM head computes ONLY these
    # positions, exactly like the reference harness. Round 3 ran the
    # vocab decoder over all S positions, work the reference never does.
    n_pred = max(int(seq * 0.15), 2)
    if seq == 512:
        n_pred = 76
    pos_np = np.zeros((batch, n_pred), np.int32)
    lab_np = np.zeros((batch, n_pred), np.int32)
    wgt_np = np.zeros((batch, n_pred), np.float32)
    for b in range(batch):
        chosen = rng.choice(seq, size=rng.randint(max(n_pred // 2, 1),
                                                  n_pred + 1),
                            replace=False)
        chosen.sort()
        pos_np[b, :len(chosen)] = chosen
        lab_np[b, :len(chosen)] = rng.randint(0, cfg.vocab_size,
                                              len(chosen))
        wgt_np[b, :len(chosen)] = 1.0
    positions = jnp.asarray(pos_np)
    mlm_labels = jnp.asarray(lab_np)
    mlm_weights = jnp.asarray(wgt_np)
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch,)))

    params = model.init(jax.random.PRNGKey(0), ids, types, attn)["params"]
    # APEX_BENCH_MOMENTS selects the LAMB moment dtype for the O2 arm
    # (bf16 = the round-5 low-HBM tier: stochastically-rounded bf16 m/v
    # + recompute-update stage 2). Default stays f32: the bf16 arm has
    # not been measured against it on the current machine. The
    # fp32-unfused baseline arm always keeps fp32 moments (the naive
    # recipe it represents).
    knob = os.environ.get("APEX_BENCH_MOMENTS", "f32")
    if knob in ("f32", "fp32", "float32"):
        moments = "float32"
    elif knob in ("bf16", "bfloat16"):
        moments = "bfloat16"
    else:
        raise ValueError(f"APEX_BENCH_MOMENTS={knob!r}: use f32 or bf16")
    if opt_level != "O2":
        moments = "float32"
    opt = FusedLAMB(lr=1e-4, weight_decay=0.01, moments_dtype=moments)
    params, opt, handle = amp.initialize(
        params, opt, opt_level=opt_level, verbosity=0)
    ost = opt.init(params)
    sst = handle.init_state()

    # The "fp32 unfused" baseline must do true fp32 matmul math: on TPU the
    # default matmul precision computes fp32 matmuls on the MXU in bf16
    # passes, which would silently hand the baseline the optimized path's
    # main speed advantage (this is the eager-fp32-torch analog the
    # reference's value-add is measured against).
    precision = "highest" if opt_level == "O0" else "default"

    def step(params, ost, sst, key):
        key, sub = jax.random.split(key)
        with jax.default_matmul_precision(precision):
            def loss_fn(p):
                mlm, nsp = model.apply({"params": p}, ids, types, attn,
                                       deterministic=False,
                                       rngs={"dropout": sub},
                                       masked_positions=positions)
                return pretraining_loss(mlm, nsp, mlm_labels, nsp_labels,
                                        mlm_weights)

            if opt_level == "O2":
                # fused tail: scaled grads go straight into LAMB, which
                # unscales inside its own reads and overflow-checks via
                # its global-norm reduction (one fewer full pass over
                # the gradient tree than unscale-then-step)
                loss, grads = handle.scaled_value_and_grad(loss_fn, sst)(
                    params)
                p2, ost2, found = opt.step(grads, ost, params,
                                           grad_scale=sst.loss_scale)
            else:
                (loss, found), grads = handle.value_and_grad(loss_fn, sst)(
                    params)
                p2, ost2 = opt.step(grads, ost, params, skip_if=found)
            return p2, ost2, handle.scalers[0].update(sst, found), loss, key

    # params, optimizer state and scaler state are donated: the update
    # is in place and the state is held once, not twice, at the B=16 cap
    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    model_info = dict(
        n_params=sum(x.size for x in jax.tree.leaves(params)),
        n_layers=cfg.num_layers, hidden=cfg.hidden_size,
        n_pred=n_pred, vocab=cfg.vocab_size)
    # The state is returned in a single-element list so time_steps can POP
    # it and own the only reference to the donated buffers.
    return jitted, [(params, ost, sst, jax.random.PRNGKey(_SEED))], model_info


def marginal_time(advance, fetch, iters, windows=2):
    """Marginal timing — THE one timing primitive here; time_steps,
    _chain_time, and tools/profile_step.py all delegate to it so a
    methodology fix lands once.

    Time two windows of chained steps of different lengths, each ended
    by a value fetch (a barrier that cannot return before the work is
    done), and report the MARGINAL cost
    (T_big - T_small) / (n_big - n_small). Whatever a window costs once
    — dispatch ramp-up, the fetch itself — cancels; what remains is the
    sustained per-step cost a real training loop pays (it blocks
    rarely, so the sustained rate IS the marginal rate).

    Noise guard: a host-side latency spike landing in a small window
    can push the marginal non-positive; non-positive marginals are
    DISCARDED, and if every window pair is corrupted the fallback is
    the big window's mean (a conservative upper bound, never negative).

    Args:
      advance: ``advance(n)`` runs n chained steps (state evolves
        through every call).
      fetch: value-fetch barrier returning a float that depends on the
        full step output.
      iters: big-window length; the small window is ``max(iters//4, 1)``.
    """
    n_small = max(iters // 4, 1)
    if iters <= n_small:  # degenerate window pair (iters=1): no marginal
        t0 = time.perf_counter()
        advance(iters)
        fetch()
        return (time.perf_counter() - t0) / iters
    marginals = []
    t_big_last = None
    for _ in range(windows):
        t0 = time.perf_counter()
        advance(n_small)
        fetch()
        t_small = time.perf_counter() - t0
        t0 = time.perf_counter()
        advance(iters)
        fetch()
        t_big = time.perf_counter() - t0
        t_big_last = t_big
        dt = (t_big - t_small) / (iters - n_small)
        if dt > 0:
            marginals.append(dt)
    if not marginals:  # every pair noise-corrupted: conservative bound
        marginals.append(t_big_last / iters)
    return min(marginals)


def time_steps(jitted, state_box, warmup=2, iters=8, windows=3):
    """Headline-step timing via :func:`marginal_time` (best-of-3 window
    pairs: the headline is the round's recorded number, so it gets one
    more chance against latency spikes than the microbenches; each
    extra pair costs ~2 s)."""
    params, ost, sst, key = state_box.pop()  # take ownership; see build_step
    loss = None
    for _ in range(warmup):
        params, ost, sst, loss, key = jitted(params, ost, sst, key)
    float(loss)  # value fetch: the execution barrier

    def advance(n):
        nonlocal params, ost, sst, key, loss
        for _ in range(n):
            params, ost, sst, loss, key = jitted(params, ost, sst, key)

    dt = marginal_time(advance, lambda: float(loss), iters,
                       windows=windows)
    return dt, float(loss)


def model_flops_per_step(n_params, batch, seq, n_layers, hidden,
                         n_pred=None, vocab=None):
    """Approximate model FLOPs for one fwd+bwd step: 6*N per token for the
    matmul-dominated path plus the attention score/context term
    (12 * L * B * S^2 * H, fwd+bwd).

    ``n_pred``/``vocab``: with the MLPerf gathered-predictions head the
    MLM transform+decoder run on B*P rows, not B*S — their FLOPs are
    counted at the rows actually computed (honest MFU accounting: the
    gather makes the step FASTER without inflating the utilization
    number)."""
    matmul = 6.0 * n_params * batch * seq
    if n_pred is not None:
        tail_params = hidden * hidden + hidden * vocab  # transform+decoder
        matmul -= 6.0 * tail_params * batch * (seq - n_pred)
    attn = 12.0 * n_layers * batch * seq * seq * hidden
    return matmul + attn


def _reset():
    """Free the previous config's executables + live buffers: at S=512
    the fp32 baseline only fits on the 16 GB chip if the optimized
    config's state is truly gone (no donation on this runtime)."""
    import gc

    gc.collect()
    jax.clear_caches()
    gc.collect()


# every record this invocation printed (metric lines + section
# records), so the end-of-run bench_diff report can compare THIS run
# against the newest recorded BENCH_*.json without waiting for the
# driver to write the new artifact
_RUN_RECORDS = []


def _print_record(rec):
    """Print one JSON record line AND remember it for the end-of-run
    bench_diff report."""
    print(json.dumps(rec))
    _RUN_RECORDS.append(rec)


def _emit_section_record(name, status, wall_s, error=None):
    """One `{"section": ...}` JSON line per bench section: wall time +
    exit status, emitted whether the section lived or died. A section
    that crashes must not simply leave NOTHING in the artifact — a dead
    section must be a visible record
    ("status": "failed" + the error), not an absence someone has to
    diff against the previous round to notice."""
    rec = {"section": name, "status": status,
           "wall_time_s": round(wall_s, 3)}
    if error is not None:
        rec["error"] = error
    _print_record(rec)


def _print_bench_diff_report():
    """End-of-full-run satellite (round 15): compare THIS run's records
    against the newest recorded ``BENCH_*.json`` with
    ``tools/bench_diff.py`` and PRINT the report (stderr, so the
    stdout record stream stays machine-parseable). The comparer landed
    in round 13 but nothing invoked it — a section that quietly
    vanished still read as a clean round to a human eyeballing metric
    lines. Strictly informational here: a perf round must record its
    numbers even when they regressed (the verdict line says which),
    so this NEVER fails the run."""
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sys.path.insert(0, here)
        from tools.bench_diff import diff, parse_artifact

        priors = sorted(f for f in os.listdir(here)
                        if f.startswith("BENCH_") and f.endswith(".json"))
        if not priors:
            return
        newest = os.path.join(here, priors[-1])
        current = {"rc": None, "metrics": {}, "sections": {}}
        for rec in _RUN_RECORDS:
            if "metric" in rec:
                current["metrics"][str(rec["metric"])] = rec
            elif "section" in rec:
                current["sections"][str(rec["section"])] = rec
        rc, lines = diff(parse_artifact(newest), current)
        print(f"== bench diff vs {priors[-1]} (informational — never "
              "fails the run) ==", file=sys.stderr)
        for line in lines:
            print(line, file=sys.stderr)
        print(f"== bench diff verdict: "
              f"{'REGRESSIONS FLAGGED' if rc else 'ok'} ==",
              file=sys.stderr)
    except Exception as e:  # the report must never kill a perf round
        print(f"# bench_diff report skipped: {type(e).__name__}: {e}",
              file=sys.stderr)


def _run_section(name, fn):
    """Run one bench section, print its JSON result, and ALWAYS follow
    with the section record. A section that dies is recorded as
    ``failed`` and then re-raised: the run ends there, with rc != 0."""
    t0 = time.perf_counter()
    try:
        _print_record(fn())
    except Exception as e:
        _emit_section_record(name, "failed", time.perf_counter() - t0,
                             error=f"{type(e).__name__}: {e}")
        raise
    _emit_section_record(name, "ok", time.perf_counter() - t0)


def _measure(batch, seq, iters, with_baseline=True, remat=True):
    """(optimized dt, baseline dt or None, mfu) at one shape."""
    _reset()
    jitted, state, info = build_step(
        dict(dtype=jnp.bfloat16, fused_kernels=True, remat=remat),
        "O2", batch, seq)
    dt_opt, loss_opt = time_steps(jitted, state, warmup=2, iters=iters)
    del jitted, state
    _reset()

    dt_base = loss_base = None
    if with_baseline:
        jitted, state, _ = build_step(
            dict(dtype=jnp.float32, fused_kernels=False), "O0", batch, seq)
        dt_base, loss_base = time_steps(jitted, state, warmup=2,
                                        iters=max(iters // 2, 2))
        del jitted, state
        _reset()

    # the attached chip's published peak; an unknown chip raises
    from apex_tpu.utils.chip_peaks import chip_peaks

    mfu = model_flops_per_step(
        info["n_params"], batch, seq, info["n_layers"], info["hidden"],
        n_pred=info["n_pred"], vocab=info["vocab"],
    ) / dt_opt / chip_peaks(jax.devices()[0].device_kind).bf16_flops
    base_txt = ("" if dt_base is None else
                f" | baseline(fp32 unfused) {dt_base*1e3:.1f} ms/step "
                f"(loss {loss_base:.3f})")
    print(
        f"# B={batch} S={seq}: optimized(bf16 O2+fused) "
        f"{dt_opt*1e3:.1f} ms/step = {batch/dt_opt:.1f} samples/s "
        f"MFU={mfu:.3f} (loss {loss_opt:.3f}){base_txt} | "
        f"params={info['n_params']/1e6:.0f}M "
        f"backend={jax.default_backend()}",
        file=sys.stderr,
    )
    return dt_opt, dt_base, mfu


def _fetch(state):
    """Value fetch of one element: the only reliable execution barrier
    on this runtime (block_until_ready/is_ready return early for some
    chained programs — see marginal_time)."""
    leaf = jax.tree.leaves(state)[0]
    return float(jnp.sum(leaf))


def _chain_time_stateful(step, state, iters, warmup=2, windows=2):
    """(marginal dt, evolved state): the state keeps evolving through
    warmup and every timed window."""
    for _ in range(warmup):
        state = step(*state)
    _fetch(state)
    box = [state]

    def advance(n):
        for _ in range(n):
            box[0] = step(*box[0])

    dt = marginal_time(advance, lambda: _fetch(box[0]), iters,
                       windows=windows)
    return dt, box[0]


def _chain_time(step, state, iters, warmup=2, windows=2):
    """Microbench timing via :func:`marginal_time`: state evolves
    through every call."""
    dt, _ = _chain_time_stateful(step, state, iters, warmup, windows)
    return dt


def _ab_chain_time(step_a, step_b, state, iters, rounds=3):
    """INTERLEAVED A/B timing for ratio metrics: alternate the two arms
    round-robin and report each arm's best marginal.

    Timing arm A fully and then arm B exposes the RATIO to whatever
    drifts on the host between the two measurement periods.
    Alternating rounds puts both arms through the same drift, and
    min-per-arm discards the contended rounds.

    Each arm's state THREADS ACROSS ROUNDS (round 2 continues from
    round 1's evolved carry)."""
    t_a, t_b = [], []
    s_a = s_b = state
    for _ in range(rounds):
        dt, s_a = _chain_time_stateful(step_a, s_a, iters)
        t_a.append(dt)
        dt, s_b = _chain_time_stateful(step_b, s_b, iters)
        t_b.append(dt)
    return min(t_a), min(t_b)


def bench_layer_norm(fast=False):
    """BASELINE configs[1]: FusedLayerNorm (training dispatch: XLA-fused
    fwd + Pallas bwd) vs stock-XLA LN, fwd+bwd at the shape the
    dispatcher serves — LN between GEMMs (the pre-LN transformer-block
    context), 16 block applications per timed call at the BERT-large
    (8192, 1024) activation shape. Value = speedup (x).

    Post-mortem of the round-4 regression: the old
    microbench chained 64 BARE LN+residual applications — a shape where
    XLA fuses each LN into the neighboring adds across the whole chain,
    while every standalone Pallas kernel is an HBM fusion barrier; it
    also (until round 4) only differentiated x, so the stock arm never
    computed dgamma/dbeta at all. At that shape the all-Pallas pair
    honestly loses ~10% — but it is not the shape the mode dispatcher
    serves. Measured at THIS shape (v5e, marginal timing, 2026-07-31):
    stock 7.01 ms/call, all-Pallas 7.23, hybrid 5.19 — the round-5
    dispatch (jnp fwd so XLA fuses LN into the GEMM that consumes it;
    Pallas bwd for the one-pass dx + in-kernel dgamma/dbeta) wins
    ~1.35x, which is the honest kernel-tier claim. Gradients flow to
    x, the LN affine params, AND the GEMM weights (the training
    contract; dgamma/dbeta work is paid by both arms)."""
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine
    from apex_tpu.ops.layer_norm import layer_norm_reference as stock_ln

    # Off-TPU this is a flow smoke, not a measurement: the GEMM-sandwich
    # shape is ~1.6 TFLOP per timed call at the real size, far beyond a
    # CI core's budget (the round-4 bare-LN chain was bandwidth-light;
    # this one is deliberately matmul-bound — see docstring)
    on_tpu = jax.default_backend() == "tpu" and not fast
    N, H = (16 * 512, 1024) if on_tpu else (128, 64)
    n_apps = 16 if on_tpu else 2
    ks = jax.random.split(jax.random.PRNGKey(_SEED), 4)
    x0 = jax.random.normal(ks[0], (N, H), jnp.float32)
    w0 = jnp.ones((H,), jnp.float32)
    b0 = jnp.zeros((H,), jnp.float32)
    W1 = jax.random.normal(ks[1], (H, H), jnp.float32) * 0.03
    W2 = jax.random.normal(ks[2], (H, H), jnp.float32) * 0.03

    def mk(fn):
        def block(xb, w, b, W1b, W2b):
            h = jnp.dot(fn(xb, w, b), W1b)
            return jnp.dot(jax.nn.gelu(h), W2b) + xb

        @jax.jit
        def step(x, w, b, W1, W2):
            # W1/W2 are ARGUMENTS inside argnums: as closure constants
            # their cotangent matmuls and saved-activation traffic would
            # be dead-code-eliminated — the same DCE understatement the
            # round-4 post-mortem above describes for dgamma/dbeta
            def loss(x, w, b, W1, W2):
                xb = x.astype(jnp.bfloat16)
                W1b, W2b = W1.astype(jnp.bfloat16), W2.astype(jnp.bfloat16)
                for _ in range(n_apps):
                    xb = block(xb, w, b, W1b, W2b)
                return jnp.sum(xb.astype(jnp.float32) ** 2) / N
            dx, dw, db, dW1, dW2 = jax.grad(
                loss, argnums=(0, 1, 2, 3, 4))(x, w, b, W1, W2)
            # f32 carries with bounded f32-visible updates: a bf16 carry
            # with a tiny step would round back to the identical input
            return (0.999 * x - 1e-3 * jnp.tanh(dx),
                    w - 1e-4 * jnp.tanh(dw), b - 1e-4 * jnp.tanh(db),
                    W1 - 1e-4 * jnp.tanh(dW1), W2 - 1e-4 * jnp.tanh(dW2))
        return step

    state = (x0, w0, b0, W1, W2)
    dt_fused, dt_stock = _ab_chain_time(
        mk(fused_layer_norm_affine), mk(stock_ln), state,
        iters=4 if fast else 8, rounds=1 if fast else 3)
    return {
        "metric": "fused_layer_norm_fwdbwd_speedup_vs_xla",
        "value": round(dt_stock / dt_fused, 3),
        "unit": "x",
        "vs_baseline": round(dt_stock / dt_fused, 3),
    }


def bench_fused_lamb(fast=False):
    """BASELINE configs[2]: FusedLAMB (multi_tensor flat-fusion step)
    vs a per-leaf unfused update chain, on a ResNet-50-class param set
    (~25.6M params, 161 leaves; ``fast=True`` shrinks the set for the
    tier-1 smoke). Value = speedup (x)."""
    from apex_tpu.optimizers import FusedLAMB

    rng = np.random.RandomState(_SEED)
    n_conv, n_bn = (5, 10) if fast else (53, 106)
    leaves = {}
    # ResNet-50-ish spectrum: many small conv/bn leaves + a few big ones
    for i in range(n_conv):
        leaves[f"conv{i}"] = jnp.asarray(
            rng.randn(*(3, 3, 128, 256 if i % 3 else 512)).astype("f4") * .01)
    for i in range(n_bn):
        leaves[f"bn{i}"] = jnp.asarray(rng.randn(512).astype("f4"))
    leaves["fc"] = jnp.asarray(
        rng.randn(128 if fast else 2048, 1000).astype("f4") * .01)
    grads = jax.tree.map(lambda p: p * 0.01, leaves)
    n = sum(l.size for l in jax.tree.leaves(leaves))

    opt = FusedLAMB(lr=1e-3)

    # 8 chained optimizer steps per timed call: one step is ~1-2 ms,
    # below the runtime's window-noise floor (same sizing rationale as
    # bench_layer_norm)
    @jax.jit
    def fused_step(params, ost):
        for _ in range(8):
            params, ost = opt.step(grads, ost, params)
        return params, ost

    def eager_one(params, m, v, step):
        # per-leaf unfused chain: the torch-eager per-param analog of
        # the SAME optimizer — including the global-grad-norm clip
        # FusedLAMB performs (max_grad_norm=1.0 default). Round-4 audit:
        # without this the baseline ran strictly less work (no stage-0
        # pass over the gradients) and the "speedup" compared different
        # optimizers (measured 0.84x for that unfair framing).
        step = step + 1
        gn = jnp.sqrt(sum(jnp.sum(grads[k].astype(jnp.float32) ** 2)
                          for k in params))
        clip = jnp.where(gn > 1.0, 1.0 / gn, 1.0)
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            g = grads[k] * clip
            m_k = 0.9 * m[k] + 0.1 * g
            v_k = 0.999 * v[k] + 0.001 * g * g
            mh = m_k / (1 - 0.9 ** step)
            vh = v_k / (1 - 0.999 ** step)
            upd = mh / (jnp.sqrt(vh) + 1e-6) + 0.01 * params[k]
            tn = jnp.linalg.norm(params[k])
            un = jnp.linalg.norm(upd)
            trust = jnp.where((tn > 0) & (un > 0), tn / un, 1.0)
            new_p[k] = params[k] - 1e-3 * trust * upd
            new_m[k], new_v[k] = m_k, v_k
        return new_p, new_m, new_v, step

    @jax.jit
    def eager_step(params, m, v, step):
        for _ in range(8):  # same 8-step chaining as fused_step
            params, m, v, step = eager_one(params, m, v, step)
        return params, m, v, step

    ost0 = opt.init(leaves)
    iters = 4 if fast else 20
    dt_fused = _chain_time(fused_step, (leaves, ost0), iters=iters)
    zeros = jax.tree.map(jnp.zeros_like, leaves)
    dt_eager = _chain_time(eager_step,
                           (leaves, zeros, zeros, jnp.int32(0)),
                           iters=iters)
    return {
        "metric": "fused_lamb_step_speedup_vs_per_leaf_eager",
        "value": round(dt_eager / dt_fused, 3),
        "unit": "x",
        "vs_baseline": round(dt_eager / dt_fused, 3),
        "n_params": n,
    }


def count_allreduce_bytes(hlo_text):
    """(op_count, total_bytes) of all-reduce collectives in compiled HLO
    text. Round 5: thin wrapper over the general
    :mod:`apex_tpu.utils.hlo_audit` (which also counts all-gather /
    reduce-scatter / all-to-all / collective-permute, so a grad sync
    that silently migrated from all-reduce to a reduce-scatter +
    all-gather pair is caught by the companion ``other_bytes`` field of
    the ddp metric rather than reading as an improvement)."""
    from apex_tpu.utils.hlo_audit import collective_stats

    s = collective_stats(hlo_text)["all-reduce"]
    return s["ops"], s["bytes"]


_DDP_SCALING_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

dp = int(sys.argv[1])
sync = sys.argv[2] == "sync"  # nosync: same step minus the grad allreduce
sys.path.insert(0, sys.argv[3])
import apex_tpu  # noqa: F401
from apex_tpu.parallel import DistributedDataParallel, SyncBatchNorm
from apex_tpu.utils.collectives import compat_shard_map
import flax.linen as nn

class Net(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        for i in range(4):
            x = nn.Conv(32, (3, 3), use_bias=False)(x)
            x = SyncBatchNorm(num_features=32, axis_name="data",
                              channel_last=True)(
                x, use_running_average=not train)
            x = nn.relu(x)
        return jnp.mean(x, axis=(1, 2)) @ jnp.ones((32, 1))

net = Net()
ddp = DistributedDataParallel(axis_name="data")
mesh = jax.make_mesh((dp,), ("data",), devices=jax.devices()[:dp])
rng = np.random.RandomState(0)
xb = jnp.asarray(rng.randn(dp * 8, 16, 16, 3).astype("f4"))
yb = jnp.asarray(rng.randn(dp * 8, 1).astype("f4"))

def init_fn(x):
    return net.init(jax.random.PRNGKey(0), x[:1], train=False)

def train_step(variables, x, y):
    def loss_fn(p):
        out, mut = net.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"])
        return jnp.mean((out - y) ** 2), mut
    (loss, mut), g = jax.value_and_grad(loss_fn, has_aux=True)(
        variables["params"])
    if sync:
        g = ddp.allreduce_grads(g)
    p2 = jax.tree.map(lambda p, gg: p - 1e-3 * gg, variables["params"], g)
    return {"params": p2, "batch_stats": mut["batch_stats"]}

variables = jax.jit(compat_shard_map(
    init_fn, mesh=mesh, in_specs=P("data"), out_specs=P()))(xb)
step = jax.jit(compat_shard_map(
    train_step, mesh=mesh, in_specs=(P(), P("data"), P("data")),
    out_specs=P()))
hlo = step.lower(variables, xb, yb).compile().as_text()
grad_bytes = sum(l.size * 4 for l in jax.tree.leaves(variables["params"]))
from apex_tpu.utils.hlo_audit import collective_stats
st = collective_stats(hlo)
other = {k: v for k, v in st.items()
         if k not in ("all-reduce", "total") and v["ops"]}
print(json.dumps({"ops": st["all-reduce"]["ops"],
                  "bytes": st["all-reduce"]["bytes"],
                  "other_ops": sum(v["ops"] for v in other.values()),
                  "other_bytes": sum(v["bytes"] for v in other.values()),
                  "grad_bytes": grad_bytes}))
"""


def bench_ddp_scaling():
    """BASELINE configs[3] (virtual-device proxy for the 8->64->256 pod
    sweep, which needs hardware this harness doesn't have): the
    framework-attributable DDP+SyncBN synchronization traffic at dp=8,
    measured from the compiled HLO — all-reduce bytes per step over the
    ideal one-pass-over-the-gradients bytes. Ideal is slightly above
    1.0 (SyncBN's welford-triple psums ride on top of the grad sync);
    a regression that syncs twice, syncs in a wider dtype, or adds
    per-layer collectives moves the ratio — unlike the round-3
    wall-clock ratio, which sat pinned at its 1.0 clamp because the
    sync cost of this net is below CPU-sim timing noise.

    The step runs under ``compat_shard_map`` (``check_vma=False``):
    autodiff inserts no boundary psum there, so the gradient all-reduce
    counted here is the one ``DistributedDataParallel.allreduce_grads``
    issues. The deliberate-regression demonstration (doubled sync moves
    the metric) lives in tests/test_bench_metrics.py."""
    import os
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    here = os.path.dirname(os.path.abspath(__file__))

    def run(mode, dp=8):
        out = subprocess.run(
            [sys.executable, "-c", _DDP_SCALING_CHILD, str(dp), mode, here],
            capture_output=True, text=True, timeout=600, env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-500:])
        return json.loads(out.stdout.strip().splitlines()[-1])

    stats = run("sync")
    ratio = stats["bytes"] / stats["grad_bytes"]
    print(f"# ddp collective audit: {stats['ops']} all-reduces "
          f"({stats['bytes']} B) vs grad bytes {stats['grad_bytes']}; "
          f"other collectives: {stats['other_ops']} op "
          f"({stats['other_bytes']} B)",
          file=sys.stderr)
    return {
        "metric": "ddp_syncbn_allreduce_bytes_over_grad_bytes_8dev",
        "value": round(ratio, 3),
        "unit": "ratio",
        "vs_baseline": round(ratio, 3),
        "allreduce_ops": stats["ops"],
        # grad traffic migrated to reduce-scatter/all-gather/all-to-all
        # would land HERE instead of lowering the headline ratio
        # (advisor r4 #3); expected ~0 for this all-reduce-only step
        "other_collective_bytes": stats["other_bytes"],
    }


def bench_scaled_masked_softmax():
    """FusedScaleMaskSoftmax kernel tier vs stock jnp softmax at the
    BERT-shaped (B, H, S, S) = (16, 16, 512, 512) attention-score
    tensor, fwd+bwd with a padding mask (the softmax tier was
    justified on speed but had no perf row). This is
    the tier the composed-attention path uses when flash is off — the
    reference justifies ``scaled_masked_softmax_cuda`` purely on this
    comparison (SURVEY §2.2). 4 chained applications/call keep the
    workload above the window-noise floor (each app is a ~268 MB bf16
    tensor fwd+bwd). Interleaved A/B + min-per-arm, like the LN row."""
    from apex_tpu.ops.softmax import scaled_masked_softmax, softmax_reference

    B, H, S = 16, 16, 512
    x0 = jax.random.normal(jax.random.PRNGKey(_SEED), (B, H, S, S),
                           jnp.float32)
    mask = (jax.random.uniform(jax.random.PRNGKey(1), (B, 1, 1, S))
            > 0.9)  # ~10% padded keys

    def mk(fn):
        def many(xb):
            for _ in range(4):
                xb = fn(xb, mask, 0.125) + 0.5 * xb
            return xb

        @jax.jit
        def step(x):
            def loss(x):
                return jnp.sum(many(x.astype(jnp.bfloat16))
                               .astype(jnp.float32) ** 2)
            dx = jax.grad(loss)(x)
            return (0.999 * x - 1e-3 * jnp.tanh(dx),)
        return step

    dt_fused, dt_stock = _ab_chain_time(
        mk(scaled_masked_softmax),
        mk(lambda x, m, s: softmax_reference(x, m, s)), (x0,), iters=6)
    return {
        "metric": "scaled_masked_softmax_fwdbwd_speedup_vs_xla",
        "value": round(dt_stock / dt_fused, 3),
        "unit": "x",
        "vs_baseline": round(dt_stock / dt_fused, 3),
    }


def bench_long_context(seq=4096):
    """Long-context attention on-chip (SURVEY §5 long-context row): GPT-
    medium-class attention (NH=16, D=64) fwd+bwd at S=4096, flash kernel
    vs composed (materialized-score) attention. This records the
    measured basis for the docs' claim that flash "wins outright at
    longer S" — at S=512 the two tie and flash's win is the O(S*D)
    memory; here the (1, 16, S, S) fp32 score tensor alone is ~1 GB and
    the composed path pays it in bandwidth. Dropout 0 in both arms (a
    composed S=4096 dropout mask tensor would not fit; the flash
    dropout path is timed by the headline)."""
    from apex_tpu.ops.flash_attention import flash_attention, mha_reference

    # L=1 at S>=8192: the composed arm materializes an L x 4.3 GB fp32
    # score tensor through fwd+bwd; two layers would not leave room for
    # the backward on the 16 GB chip
    B, NH, D, L = 1, 16, 64, (1 if seq >= 8192 else 2)
    q0 = jax.random.normal(jax.random.PRNGKey(_SEED), (B, NH, seq, D),
                           jnp.float32)

    def mk(attn):
        def loss(qc):
            x = qc.astype(jnp.bfloat16)
            for _ in range(L):
                x = attn(x)
            return jnp.sum(x.astype(jnp.float32) ** 2)

        @jax.jit
        def step(q):
            dq = jax.grad(loss)(q)
            return (0.999 * q - 1e-3 * jnp.tanh(dq),)
        return step

    flash_step = mk(lambda x: flash_attention(x, x, x, None, True, 0.125))
    comp_step = mk(lambda x: mha_reference(x, x, x, None, True, 0.125))
    # Interleaved A/B: sequential arms let host drift land entirely on
    # one side of the ratio.
    dt_flash, dt_comp = _ab_chain_time(flash_step, comp_step, (q0,),
                                       iters=4)
    return {
        "metric": f"long_context_attn_s{seq}_flash_speedup_vs_composed",
        "value": round(dt_comp / dt_flash, 3),
        "unit": "x",
        "vs_baseline": round(dt_comp / dt_flash, 3),
        "flash_ms_per_call": round(dt_flash * 1e3, 2),
    }


def bench_serving(fast=False):
    """Serving section (round 6): the continuous-batching engine
    (apex_tpu.serving) driving GPT decode with the paged KV-cache —
    prefill tokens/s, decode steps/s (one step = one token for every
    active slot), and peak cache-slot utilization. Two phases so the
    numbers don't contaminate each other: a max_new_tokens=1 drain is
    ~pure prefill; a drain with every slot busy is decode-dominated.
    On TPU this runs a GPT-2-small-class config; off-TPU the tiny smoke
    config (flow check, metric named accordingly). ``fast=True`` is the
    tier-1 smoke shape (smallest workload, same code paths)."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.observability import flatten_stats as _flatten_stats
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ecfg = EngineConfig(max_batch=16, block_size=32, num_blocks=512,
                            max_prefill_len=256, max_seq_len=512,
                            kv_dtype=jnp.bfloat16)
        n_req, max_new, prompt_len = 16, 64, 128
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ecfg = EngineConfig(max_batch=4, block_size=8, num_blocks=64,
                            max_prefill_len=16, max_seq_len=48)
        n_req, max_new, prompt_len = (3, 4, 12) if fast else (6, 8, 12)
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))
    engine = InferenceEngine(model, params, ecfg)

    def requests(tag, new_tokens):
        return [
            Request(uid=f"{tag}-{i}",
                    prompt=list(rng.randint(0, cfg.vocab_size, prompt_len)),
                    max_new_tokens=new_tokens,
                    sampling=SamplingParams(temperature=1.0, top_k=40))
            for i in range(n_req)
        ]

    # warmup: compile the two programs (prefill + decode)
    for r in requests("warm", 2):
        engine.add_request(r)
    engine.run()

    # phase 1 — prefill throughput (max_new_tokens=1: no decode steps)
    reqs = requests("pre", 1)
    tokens = sum(len(r.prompt) for r in reqs)
    t0 = time.perf_counter()
    for r in reqs:
        engine.add_request(r)
    engine.run()
    prefill_tok_s = tokens / max(time.perf_counter() - t0, 1e-9)

    # phase 2 — decode throughput + peak slot utilization
    s0 = engine.stats()
    util_peak = 0.0
    t0 = time.perf_counter()
    for r in requests("dec", max_new):
        engine.add_request(r)
    while engine.has_work:
        engine.step()
        util_peak = max(util_peak, engine.allocator.utilization)
    dt = time.perf_counter() - t0
    decode_steps = (engine.stats()["num_decode_dispatches"]
                    - s0["num_decode_dispatches"])
    decode_tokens = (engine.stats()["num_tokens_decoded"]
                     - s0["num_tokens_decoded"])
    stats = engine.stats()

    # phase 3 — prefix caching (round 6): decode tokens/s over a fresh
    # prefix-caching engine at 0% prompt overlap (every prompt distinct:
    # pure overhead measurement) vs ~90% overlap (a shared system-prompt
    # head fronting every request — the dominant real-traffic shape,
    # where block sharing skips most prefill work and most prompt-block
    # allocations). Same request count/budgets in both arms.
    import dataclasses as _dc

    # round the shared head DOWN to a block multiple: prefix matching
    # is full-block only, so an unaligned head would cap the achievable
    # hit rate below what the arm's "~90%" label claims
    bs = ecfg.block_size
    shared_len = max(bs * (int(prompt_len * 0.9) // bs), bs)
    shared_head = list(rng.randint(0, cfg.vocab_size, shared_len))

    def _overlap_arm(tag, shared):
        eng = InferenceEngine(model, params,
                              _dc.replace(ecfg, enable_prefix_caching=True))
        for r in requests(f"{tag}-warm", 1):    # compile outside the clock
            eng.add_request(r)
        eng.run()
        s_before = eng.stats()
        tt0 = time.perf_counter()
        for i in range(n_req):
            tail = list(rng.randint(0, cfg.vocab_size,
                                    prompt_len - len(shared)))
            eng.add_request(Request(
                uid=f"{tag}-{i}", prompt=list(shared) + tail,
                max_new_tokens=max_new,
                sampling=SamplingParams(temperature=1.0, top_k=40)))
            eng.step()   # staggered arrivals (continuous traffic), so
            # later requests see the head request's registered blocks
        eng.run()
        tdt = time.perf_counter() - tt0
        s_after = eng.stats()
        toks = n_req * max_new
        d_hits = (s_after["prefix_hit_blocks"]
                  - s_before["prefix_hit_blocks"])
        d_lookups = (s_after["prefix_lookup_blocks"]
                     - s_before["prefix_lookup_blocks"])
        return {
            "decode_tokens_per_sec": round(toks / max(tdt, 1e-9), 3),
            # this arm's hit rate, not the engine-lifetime rate (which
            # the warmup phase's guaranteed misses would dilute)
            "prefix_cache_hit_rate": round(
                d_hits / max(d_lookups, 1), 3),
            "prefill_chunks": int(s_after["num_prefill_chunks"]
                                  - s_before["num_prefill_chunks"]),
            "prompt_blocks_allocated": int(
                s_after["prompt_blocks_allocated"]
                - s_before["prompt_blocks_allocated"]),
        }, s_after

    arm0, _ = _overlap_arm("p0", shared=[])
    arm90, s90 = _overlap_arm("p90", shared=shared_head)

    print(f"# serving: prefill {prefill_tok_s:.1f} tok/s | "
          f"{decode_steps} decode steps in {dt:.3f}s | peak slot "
          f"utilization {util_peak:.3f} | compilations "
          f"{stats['prefill_compilations']}+{stats['decode_compilations']} | "
          f"prefix-cache decode tok/s "
          f"{arm0['decode_tokens_per_sec']:.1f} (0% overlap) -> "
          f"{arm90['decode_tokens_per_sec']:.1f} (~90%, arm hit rate "
          f"{arm90['prefix_cache_hit_rate']:.2f})",
          file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_decode_steps_per_sec" if on_tpu
                   else "serving_tiny_smoke_decode_steps_per_sec"),
        "value": round(decode_steps / max(dt, 1e-9), 3),
        "unit": "steps/sec",
        # no reference arm for serving yet — recorded against itself
        "vs_baseline": 1.0,
        "prefill_tokens_per_sec": round(prefill_tok_s, 1),
        "decode_tokens_per_sec": round(decode_tokens / max(dt, 1e-9), 3),
        "cache_slot_utilization_peak": round(util_peak, 3),
        "jit_programs": int(stats["prefill_compilations"]
                            + stats["decode_compilations"]),
        "prefix_overlap_0pct": arm0,
        "prefix_overlap_90pct": arm90,
        "scheduler_stats": {
            # the sanctioned flattener (docs/observability.md); the
            # nested per-tenant ledger is excluded — it has its own arm;
            # non-numeric entries (the quantization mode strings/None)
            # pass through as-is
            k: (round(v, 4) if isinstance(v, float)
                else int(v) if isinstance(v, (int, bool)) else v)
            for k, v in _flatten_stats(s90, exclude=("tenants",)).items()
        },
    }


def bench_serving_multistep(fast=False):
    """Multi-step fused decode sweep: the same decode-dominated
    workload served at ``decode_steps`` (K) in {1, 4, 8} — K scanned
    decode iterations per dispatch, so one scheduler tick (host table /
    sampling-array work, dispatch, fetch) is amortized over K tokens
    per lane. Reports decode tokens/sec per arm plus the dispatch vs
    token counters that make the amortization observable, and ASSERTS
    the outputs are bit-identical across K (the per-request/per-token
    PRNG keying contract — a throughput knob must never change what
    gets generated). ``vs_baseline`` is the K=max / K=1 tokens/sec
    ratio: the multi-step speedup itself. ``fast=True`` is the tier-1
    smoke shape (smaller sweep + workload, same code path)."""
    import dataclasses as _dc

    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ecfg = EngineConfig(max_batch=16, block_size=32, num_blocks=512,
                            max_prefill_len=256, max_seq_len=512,
                            kv_dtype=jnp.bfloat16)
        n_req, max_new, prompt_len = 16, 64, 32
        ks = (1, 4, 8)
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ecfg = EngineConfig(max_batch=4, block_size=8, num_blocks=64,
                            max_prefill_len=16, max_seq_len=48)
        n_req, max_new, prompt_len = (4, 12, 8) if fast else (8, 24, 8)
        ks = (1, 4) if fast else (1, 4, 8)
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED + 1)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))
    # mixed greedy / sampled lanes, fixed across arms (the bit-identity
    # check is only meaningful when every arm serves the same stream)
    prompts = [list(rng.randint(0, cfg.vocab_size, prompt_len))
               for _ in range(n_req)]

    def requests(tag):
        return [
            Request(uid=f"{tag}-{i}", prompt=prompts[i],
                    max_new_tokens=max_new,
                    sampling=(SamplingParams() if i % 2 == 0 else
                              SamplingParams(temperature=1.0, top_k=40)))
            for i in range(n_req)
        ]

    sweep, outputs = {}, {}
    for k in ks:
        eng = InferenceEngine(model, params,
                              _dc.replace(ecfg, decode_steps=k))
        for r in requests("warm")[:2]:      # compile outside the clock
            eng.add_request(r)
        eng.run()
        s0 = eng.stats()
        t0 = time.perf_counter()
        for r in requests(f"k{k}"):
            eng.add_request(r)
        out = eng.run()
        tdt = time.perf_counter() - t0
        s1 = eng.stats()
        toks = s1["num_tokens_decoded"] - s0["num_tokens_decoded"]
        sweep[f"k{k}"] = {
            "decode_tokens_per_sec": round(toks / max(tdt, 1e-9), 3),
            "num_decode_dispatches": int(s1["num_decode_dispatches"]
                                         - s0["num_decode_dispatches"]),
            "num_tokens_decoded": int(toks),
            "decode_table_rebuilds": int(s1["decode_table_rebuilds"]
                                         - s0["decode_table_rebuilds"]),
            "decode_compilations": int(s1["decode_compilations"]),
        }
        outputs[k] = {u.split("-", 1)[1]: v for u, v in out.items()}

    identical = all(outputs[k] == outputs[ks[0]] for k in ks)
    ratio = (sweep[f"k{ks[-1]}"]["decode_tokens_per_sec"]
             / max(sweep["k1"]["decode_tokens_per_sec"], 1e-9))
    print("# serving multistep: " + " | ".join(
        f"K={k} {sweep[f'k{k}']['decode_tokens_per_sec']:.1f} tok/s "
        f"({sweep[f'k{k}']['num_decode_dispatches']} dispatches)"
        for k in ks) + f" | K{ks[-1]}/K1 {ratio:.2f}x | "
        f"bit-identical {identical}", file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_multistep_decode_tokens_per_sec"
                   if on_tpu else
                   "serving_tiny_smoke_multistep_decode_tokens_per_sec"),
        "value": sweep[f"k{ks[-1]}"]["decode_tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),     # K=max vs K=1, same workload
        "decode_steps_swept": list(ks),
        "outputs_bit_identical_across_k": bool(identical),
        "sweep": sweep,
    }


def bench_serving_speculative(fast=False):
    """Speculative decoding (round 7): the same decode-dominated
    workload served by the non-speculative K-step scan baseline vs
    draft-and-verify (``spec_tokens``, n-gram prompt-lookup drafter) on
    a REPETITIVE/structured-prompt arm — the traffic speculation
    targets (templated output, code, multi-turn echoes), where the
    drafter's guesses actually get accepted. Reports decode tokens/sec
    per arm, the acceptance rate, and accepted tokens per dispatch
    (tokens-per-target-forward is the whole speculative win), ASSERTS
    greedy output bit-identical between the arms (the certification
    bar: a throughput knob must never change what gets generated) and
    that the drafter accepted a nonzero number of tokens — so a
    regression that silently stops speculating fails the smoke run
    instead of surfacing as a quiet perf loss. ``vs_baseline`` is the
    speculative / non-speculative tokens/sec ratio. ``fast=True`` is
    the tier-1 smoke shape (same code path, smallest workload)."""
    import dataclasses as _dc

    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import EngineConfig, InferenceEngine, Request

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ecfg = EngineConfig(max_batch=16, block_size=32, num_blocks=512,
                            max_prefill_len=256, max_seq_len=512,
                            kv_dtype=jnp.bfloat16)
        n_req, max_new, prompt_len, k_base, spec = 16, 96, 64, 8, 12
    elif fast:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ecfg = EngineConfig(max_batch=4, block_size=8, num_blocks=96,
                            max_prefill_len=16, max_seq_len=96)
        n_req, max_new, prompt_len, k_base, spec = 4, 12, 16, 4, 4
    else:
        # decode-dominated CPU arm at a REAL context length: the
        # speculative win on CPU is gather dominance — the K-step scan
        # gathers the full paged context K times per dispatch, the
        # verify forward once — so the context must be long enough for
        # the gather to be the cost (tok/s is flat vs the scan at
        # context ~16, 1.5-1.7x at 256). spec > K is deliberate: a
        # high-acceptance drafter sustains spans longer than the scan's
        # guaranteed K, the lever the scan itself does not have.
        cfg = GPTConfig.tiny(dropout=0.0, remat=False,
                             max_position_embeddings=512)
        ecfg = EngineConfig(max_batch=4, block_size=16, num_blocks=256,
                            max_prefill_len=256, max_seq_len=448)
        n_req, max_new, prompt_len, k_base, spec = 8, 160, 256, 8, 12
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED + 2)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))
    # structured prompts: a short random pattern repeated, so the
    # prompt itself seeds the n-gram index; greedy lanes only (greedy
    # repetition attractors are exactly the accept-friendly regime, and
    # greedy is the regime the bit-identity certification covers)
    prompts = []
    for _ in range(n_req):
        pat = list(rng.randint(0, cfg.vocab_size, 4))
        prompts.append((pat * (prompt_len // 4 + 1))[:prompt_len])

    def requests(tag):
        return [Request(uid=f"{tag}-{i}", prompt=prompts[i],
                        max_new_tokens=max_new)
                for i in range(n_req)]

    # interleaved A/B, best-of-reps: each rep times one round of BOTH
    # arms back to back, so machine-load drift lands on both, and the
    # best round per arm is reported — CPU wall clocks are noisy at
    # these sub-second rounds
    reps = 1 if fast else 5
    specs = (("baseline_k", dict(decode_steps=k_base)),
             ("speculative", dict(spec_tokens=spec)))
    engines, arms, outputs = {}, {}, {}
    for name, kw in specs:
        eng = InferenceEngine(model, params, _dc.replace(ecfg, **kw))
        for r in requests("warm")[:2]:      # compile outside the clock
            eng.add_request(r)
        eng.run()
        engines[name] = (eng, eng.stats())
    best = {name: None for name, _ in specs}
    for rep in range(reps):
        for name, _ in specs:
            eng, _ = engines[name]
            t0 = time.perf_counter()
            for r in requests(f"{name}{rep}"):
                eng.add_request(r)
            out = eng.run()
            tdt = time.perf_counter() - t0
            if best[name] is None or tdt < best[name]:
                best[name] = tdt
            outputs[name] = {u.split("-", 1)[1]: v
                             for u, v in out.items()}
    for name, kw in specs:
        eng, s0 = engines[name]
        s1 = eng.stats()
        toks = (s1["num_tokens_decoded"]
                - s0["num_tokens_decoded"]) // reps
        disp = (s1["num_decode_dispatches"]
                - s0["num_decode_dispatches"]) / reps
        arms[name] = {
            "decode_tokens_per_sec": round(
                toks / max(best[name], 1e-9), 3),
            "num_decode_dispatches": round(disp, 1),
            "num_tokens_decoded": int(toks),
            "tokens_per_dispatch": round(toks / max(disp, 1), 3),
            "decode_compilations": int(s1["decode_compilations"]),
        }
        if kw.get("spec_tokens"):
            drafted = (s1["num_draft_tokens"]
                       - s0["num_draft_tokens"]) // reps
            accepted = (s1["num_accepted_tokens"]
                        - s0["num_accepted_tokens"]) // reps
            arms[name].update({
                "num_draft_tokens": int(drafted),
                "num_accepted_tokens": int(accepted),
                "acceptance_rate": round(accepted / max(drafted, 1), 4),
                "accepted_per_dispatch": round(
                    accepted / max(disp, 1), 3),
                "spec_blocks_rolled_back": int(
                    (s1["num_spec_blocks_rolled_back"]
                     - s0["num_spec_blocks_rolled_back"]) // reps),
            })

    identical = outputs["speculative"] == outputs["baseline_k"]
    assert identical, "speculative greedy output diverged from baseline"
    spec_arm = arms["speculative"]
    assert spec_arm["num_accepted_tokens"] > 0, (
        "the n-gram drafter accepted nothing on the structured arm — "
        "speculation is silently off")
    ratio = (spec_arm["decode_tokens_per_sec"]
             / max(arms["baseline_k"]["decode_tokens_per_sec"], 1e-9))
    print(f"# serving speculative: baseline K={k_base} "
          f"{arms['baseline_k']['decode_tokens_per_sec']:.1f} tok/s | "
          f"spec={spec} "
          f"{spec_arm['decode_tokens_per_sec']:.1f} tok/s "
          f"({ratio:.2f}x) | acceptance "
          f"{spec_arm['acceptance_rate']:.2f} | "
          f"{spec_arm['tokens_per_dispatch']:.2f} tok/dispatch | "
          f"bit-identical {identical}", file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_speculative_decode_tokens_per_sec"
                   if on_tpu else
                   "serving_tiny_speculative_decode_tokens_per_sec"),
        "value": spec_arm["decode_tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(ratio, 3),     # spec vs K-scan, same stream
        "spec_tokens": spec,
        "baseline_decode_steps": k_base,
        "prompt_len": prompt_len,
        "acceptance_rate": spec_arm["acceptance_rate"],
        "accepted_per_dispatch": spec_arm["accepted_per_dispatch"],
        "outputs_bit_identical": bool(identical),
        "arms": arms,
    }


def _poisson_burst_trace(rng, ticks, base_rate, make_request,
                         burst_start=None, burst_end=None,
                         burst_factor=1):
    """The shared seeded trace builder for the serving stress arms
    (overload, multitenant): per tick, ``Poisson(base_rate)`` arrivals
    — ``burst_factor`` x inside ``[burst_start, burst_end)`` — each
    materialized by ``make_request(tick, k)`` (``k`` = the arrival's
    index within the trace). One generator, one rng, so traces stay
    seeded and COMPARABLE across arms: the same (rng state, rates)
    always yields the same burst."""
    trace, k = [], 0
    for tick in range(ticks):
        burst = (burst_start is not None
                 and burst_start <= tick < burst_end)
        rate = base_rate * (burst_factor if burst else 1)
        for _ in range(int(rng.poisson(rate))):
            trace.append((tick, make_request(tick, k)))
            k += 1
    return trace


def bench_serving_overload(fast=False):
    """Overload / tail-latency arm (round 8): a seeded bursty trace —
    Poisson-ish arrivals with a 4x burst phase in the middle, mixed
    prompt/output lengths, mixed priorities and deadlines — driven
    tick-by-tick through an engine with the full overload-protection
    stack on: bounded queue (``try_add`` sheds at the door), admit-time
    feasibility gate, and degradation-ladder watermarks. Reports
    p50/p99 TTFT (submit -> first host-visible token), p50/p99
    inter-token latency (host-visible gaps; tokens surfacing in the
    same drain batch count as 0), goodput (SLO-attained tokens/s:
    tokens of requests that FINISHED — shed/timed-out requests
    contribute zero) alongside raw generated tokens/s, the shed/timeout
    counts, ladder transitions, and the queue high-water mark — and
    ASSERTS zero engine stalls and a bounded queue, so an overload
    regression fails the bench instead of doubling p99 silently.
    ``vs_baseline`` is goodput / raw throughput (the SLO-attainment
    fraction). ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ecfg = EngineConfig(max_batch=16, block_size=32, num_blocks=512,
                            max_prefill_len=256, max_seq_len=512,
                            kv_dtype=jnp.bfloat16, max_waiting=64,
                            queue_high_watermark=32,
                            free_block_low_watermark=0.125,
                            degrade_patience=2)
        base_rate, phase_ticks = 1.0, 40
        prompt_lens, max_news = (64, 128, 192), (16, 32, 64)
        deadlines = (None, None, 0.05, 2.0, 6.0)
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ecfg = EngineConfig(max_batch=4, block_size=8, num_blocks=64,
                            max_prefill_len=16, max_seq_len=48,
                            max_waiting=8, queue_high_watermark=5,
                            free_block_low_watermark=0.125,
                            degrade_patience=2)
        base_rate = 0.3 if fast else 0.4
        phase_ticks = 8 if fast else 24
        prompt_lens, max_news = (6, 10, 14), (3, 5, 8)
        # the 0.02 s class is the feasibility-gate bait: once the EWMAs
        # see real dispatch times it is shed at admission, not timed out
        deadlines = (None, None, 0.02, 1.5, 5.0)
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED + 3)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))
    engine = InferenceEngine(model, params, ecfg)

    # warmup: compile the two programs outside the clock
    for i in range(2):
        engine.add_request(Request(
            uid=f"warm-{i}",
            prompt=list(rng.randint(0, cfg.vocab_size, prompt_lens[0])),
            max_new_tokens=2))
    engine.run()

    # the trace, built up front (seeded => the same burst every round):
    # arrivals-per-tick ~ Poisson(rate); the middle phase runs at 4x
    def make_request(tick, uid):
        dl = deadlines[int(rng.randint(len(deadlines)))]
        return Request(
            uid=f"o{uid}",
            prompt=list(rng.randint(
                0, cfg.vocab_size,
                int(rng.choice(prompt_lens)))),
            max_new_tokens=int(rng.choice(max_news)),
            priority=int(rng.choice((0, 1, 2), p=(0.3, 0.5, 0.2))),
            deadline_s=dl,
            sampling=(SamplingParams() if uid % 2 == 0 else
                      SamplingParams(temperature=1.0, top_k=40)))

    trace = _poisson_burst_trace(
        rng, ticks=3 * phase_ticks, base_rate=base_rate,
        make_request=make_request, burst_start=phase_ticks,
        burst_end=2 * phase_ticks, burst_factor=4)

    submit_t, first_tok_t, last_obs_t, last_counts = {}, {}, {}, {}
    ttfts, gaps = [], []
    shed_at_door = stalls = 0

    def observe(now):
        # host-visible token counts for every request still owned by
        # the engine (finished-but-undrained, resident, or requeued)
        counts = {u: len(t) for u, t in engine.finished.items()}
        for s in engine.slots:
            if s is not None:
                counts[s.request.uid] = (len(s.generated) if s.started
                                         else len(s.entry.generated))
        for e in engine.waiting:
            counts[e.request.uid] = len(e.generated)
        for u, n in counts.items():
            prev = last_counts.get(u, 0)
            if n <= prev or u not in submit_t:
                continue
            if u not in first_tok_t:
                first_tok_t[u] = now
                ttfts.append(now - submit_t[u])
                if n > 1:   # surfaced in the same drain batch
                    gaps.extend([0.0] * (n - 1))
            else:
                gaps.extend([(now - last_obs_t[u]) / (n - prev)]
                            * (n - prev))
            last_obs_t[u] = now
            last_counts[u] = n

    t0 = time.perf_counter()
    i = tick = 0
    while i < len(trace) or engine.has_work:
        while i < len(trace) and trace[i][0] <= tick:
            req = trace[i][1]
            submit_t[req.uid] = time.perf_counter()
            if not engine.try_add(req):      # bounded queue: shed at
                shed_at_door += 1            # the door, explicitly
                submit_t.pop(req.uid, None)
            i += 1
        had_work = engine.has_work
        progressed = engine.step()
        if had_work and not progressed:
            stalls += 1
        observe(time.perf_counter())
        tick += 1
    wall = time.perf_counter() - t0

    results = engine.run(return_status=True)   # drain terminal maps
    status_counts = {}
    for r in results.values():
        status_counts[r.status] = status_counts.get(r.status, 0) + 1
    raw_tokens = sum(len(r.tokens) for r in results.values())
    good_tokens = sum(len(r.tokens) for r in results.values()
                      if r.status == "finished")
    goodput = good_tokens / max(wall, 1e-9)
    raw_tps = raw_tokens / max(wall, 1e-9)
    stats = engine.stats()

    assert stalls == 0, f"{stalls} no-progress ticks with work remaining"
    # client adds are bounded by max_waiting; preemption/recovery
    # requeues of residents can push past it by at most max_batch
    assert (stats["queue_depth_peak"]
            <= ecfg.max_waiting + ecfg.max_batch), stats
    assert status_counts.get("finished", 0) > 0, status_counts

    # the ONE shared percentile helper (linear interpolation, same
    # rule as StepTimer and the obs histograms — docs/observability.md)
    from apex_tpu.observability import percentile

    def pct(xs, q):
        return percentile(xs, q) if xs else 0.0

    print(f"# serving overload: {len(trace)} offered "
          f"({shed_at_door} shed at door) over {tick} ticks | "
          f"goodput {goodput:.1f} of {raw_tps:.1f} tok/s | TTFT p50 "
          f"{pct(ttfts, 50) * 1e3:.1f}ms p99 {pct(ttfts, 99) * 1e3:.1f}ms"
          f" | ITL p50 {pct(gaps, 50) * 1e3:.2f}ms p99 "
          f"{pct(gaps, 99) * 1e3:.2f}ms | queue peak "
          f"{int(stats['queue_depth_peak'])}/{ecfg.max_waiting} | "
          f"rejected {int(stats['num_rejected_infeasible'])} infeasible"
          f" + {int(stats['num_rejected_queue_full'])} full | ladder "
          f"down {int(stats['num_degrade_steps_down'])} / up "
          f"{int(stats['num_degrade_steps_up'])}", file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_overload_goodput_tokens_per_sec"
                   if on_tpu else
                   "serving_tiny_overload_goodput_tokens_per_sec"),
        "value": round(goodput, 3),
        "unit": "tokens/sec",
        # the SLO-attainment fraction: how much of the raw token
        # stream belonged to requests that actually finished
        "vs_baseline": round(goodput / max(raw_tps, 1e-9), 4),
        "burst_factor": 4,
        "num_requests_offered": len(trace),
        "num_requests_admitted": len(results),
        "num_shed_at_door": shed_at_door,
        "status_counts": status_counts,
        "p50_ttft_s": round(pct(ttfts, 50), 6),
        "p99_ttft_s": round(pct(ttfts, 99), 6),
        "p50_itl_s": round(pct(gaps, 50), 6),
        "p99_itl_s": round(pct(gaps, 99), 6),
        "goodput_tokens_per_sec": round(goodput, 3),
        "decode_tokens_per_sec": round(raw_tps, 3),
        "slo_attainment": round(good_tokens / max(raw_tokens, 1), 4),
        "num_stalls": stalls,
        "max_waiting": int(ecfg.max_waiting),
        "max_batch": int(ecfg.max_batch),
        "queue_depth_peak": int(stats["queue_depth_peak"]),
        "num_rejected_queue_full": int(stats["num_rejected_queue_full"]),
        "num_rejected_infeasible": int(stats["num_rejected_infeasible"]),
        "num_timeouts": int(stats["num_timeouts"]),
        "num_preemptions": int(stats["num_preemptions"]),
        "degrade_steps_down": int(stats["num_degrade_steps_down"]),
        "degrade_steps_up": int(stats["num_degrade_steps_up"]),
        "queue_wait_mean_s": round(float(stats["queue_wait_mean_s"]), 6),
        "queue_wait_max_s": round(float(stats["queue_wait_max_s"]), 6),
    }


def bench_serving_multitenant(fast=False):
    """Multi-tenant isolation arm (round 10): one ADVERSARIAL flood
    tenant against two well-behaved tenants with deadlines, all
    sharing a prefix-cached pool under the tenancy stack — weighted
    DRR admission, per-tenant quotas (waiting cap + resident-block
    ceiling + token-rate budget on the flood), streaming delivery.

    Three phases: (1) the victims run SOLO (their exact seeded traces,
    no flood) to baseline per-tenant p99 TTFT; (2) the same victim
    traces run against the flood — the arm reports per-tenant goodput
    and p99 TTFT and ASSERTS the flood is the only tenant ever shed or
    throttled and the victims' p99 TTFT (in scheduler ticks — the
    deterministic unit) stays within its bound of the solo baseline;
    (3) a chaos engine mixes aborts, quota sheds, injected
    prefill/decode faults, and degradation-ladder steps over the same
    trace shape, then must pass ``check_allocator_integrity`` (the
    per-tenant refcount split certified exactly) with every accepted
    request terminal. ``vs_baseline`` is combined victim goodput /
    solo victim goodput. ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams, TenantQuota)
    from apex_tpu.utils.faults import FaultPlan, FaultSpec

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ekw = dict(max_batch=16, block_size=32, num_blocks=512,
                   max_prefill_len=256, max_seq_len=512,
                   kv_dtype=jnp.bfloat16, max_waiting=64,
                   enable_prefix_caching=True)
        victim_rate, flood_rate, ticks = 0.5, 4.0, 80
        prompt_lens, max_news = (64, 128), (16, 32)
        flood_quota = TenantQuota(max_waiting=8, max_resident_blocks=24,
                                  tokens_per_s=2000.0)
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ekw = dict(max_batch=4, block_size=8, num_blocks=64,
                   max_prefill_len=16, max_seq_len=48, max_waiting=24,
                   enable_prefix_caching=True)
        victim_rate = 0.25 if fast else 0.35
        flood_rate = 1.5
        ticks = 24 if fast else 48
        prompt_lens, max_news = (6, 10), (3, 5)
        flood_quota = TenantQuota(max_waiting=4, max_resident_blocks=5,
                                  tokens_per_s=5000.0)
    tenancy = dict(
        tenant_weights={"acme": 4, "bolt": 4, "flood": 1},
        tenant_quotas={"flood": flood_quota},
        drr_quantum=16)
    model = GPTLMHeadModel(cfg)
    # FIXED seeds: this arm asserts on shed attribution,
    # tail-latency bounds, and chaos-path coverage — the trace must be
    # the same every round or the asserts flake
    init_rng = np.random.RandomState(1789)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(init_rng.randint(0, cfg.vocab_size, (1, 8))))

    def victim_trace():
        # victims get their OWN rng so the solo and combined runs see
        # byte-identical victim traffic
        rng = np.random.RandomState(1790)

        def make(tick, k):
            tenant = ("acme", "bolt")[k % 2]
            return Request(
                uid=f"{tenant}-{k}",
                prompt=list(rng.randint(0, cfg.vocab_size,
                                        int(rng.choice(prompt_lens)))),
                max_new_tokens=int(rng.choice(max_news)),
                tenant=tenant, deadline_s=30.0,
                sampling=(SamplingParams() if k % 2 == 0 else
                          SamplingParams(temperature=1.0, top_k=40)))

        return _poisson_burst_trace(rng, ticks=ticks,
                                    base_rate=victim_rate,
                                    make_request=make)

    def flood_trace():
        rng = np.random.RandomState(1791)
        shared = list(rng.randint(0, cfg.vocab_size, prompt_lens[-1]))

        def make(tick, k):
            # the adversary: high rate, no deadlines, identical
            # prompts (it also tries to squat on the prefix cache)
            return Request(uid=f"flood-{k}", prompt=list(shared),
                           max_new_tokens=int(max_news[-1]),
                           tenant="flood")

        return _poisson_burst_trace(rng, ticks=ticks,
                                    base_rate=flood_rate,
                                    make_request=make)

    def drive(engine, trace, abort_every=None):
        """Tick the engine through the trace; per-uid submit tick and
        first-token tick (host-visible, via the streaming API), door
        sheds per tenant, optional every-Nth-accepted abort schedule.
        Returns (ttft_ticks per uid, door_sheds per tenant, aborted
        uids, wall seconds, stalls)."""
        submit, first = {}, {}
        sheds, aborted, accepted = {}, [], []
        stalls = 0
        t0 = time.perf_counter()
        i = tick = 0
        while i < len(trace) or engine.has_work:
            while i < len(trace) and trace[i][0] <= tick:
                req = trace[i][1]
                if engine.try_add(req):
                    submit[req.uid] = tick
                    accepted.append(req.uid)
                    if (abort_every
                            and len(accepted) % abort_every == 0):
                        aborted.append(req.uid)
                else:
                    t = req.tenant
                    sheds[t] = sheds.get(t, 0) + 1
                i += 1
            for uid in aborted[:]:
                if engine.abort(uid):
                    aborted.remove(uid)
                    aborted.append("done:" + uid)
            had = engine.has_work
            progressed = engine.step()
            if had and not progressed:
                stalls += 1
            for uid, tok, last in engine.pop_stream_events():
                if tok >= 0 and uid not in first and uid in submit:
                    first[uid] = tick
            tick += 1
        wall = time.perf_counter() - t0
        ttft = {u: first[u] - submit[u] for u in first}
        return ttft, sheds, aborted, wall, stalls

    from apex_tpu.observability import percentile

    def pct(xs, q):
        return percentile(xs, q) if xs else 0.0

    victims = victim_trace()

    # phase 1: victims solo — the baseline each tenant is entitled to
    engine = InferenceEngine(model, params, EngineConfig(**ekw, **tenancy))
    ttft_solo, _, _, wall_solo, stalls0 = drive(engine, victims)
    solo_res = engine.run(return_status=True)
    solo_good = {t: sum(len(r.tokens) for u, r in solo_res.items()
                        if r.status == "finished" and u.startswith(t))
                 for t in ("acme", "bolt")}
    solo_p99 = {t: pct([v for u, v in ttft_solo.items()
                        if u.startswith(t)], 99)
                for t in ("acme", "bolt")}

    # phase 2: the same victim traffic + the flood
    combined = sorted(victims + flood_trace(), key=lambda x: x[0])
    engine = InferenceEngine(model, params, EngineConfig(**ekw, **tenancy))
    ttft_mix, sheds, _, wall_mix, stalls1 = drive(engine, combined)
    mix_res = engine.run(return_status=True)
    stats = engine.stats()
    tstats = stats["tenants"]
    good = {t: sum(len(r.tokens) for u, r in mix_res.items()
                   if r.status == "finished" and u.startswith(t))
            for t in ("acme", "bolt", "flood")}
    mix_p99 = {t: pct([v for u, v in ttft_mix.items()
                       if u.startswith(t)], 99)
               for t in ("acme", "bolt")}
    bad_status = {u: r.status for u, r in mix_res.items()
                  if r.status in ("throttled", "rejected")}

    assert stalls0 == stalls1 == 0, (stalls0, stalls1)
    # isolation bar 1: the flood is the ONLY tenant ever shed at the
    # door or throttled by quota — victims never pay for it
    assert all(t == "flood" for t in sheds), sheds
    assert all(u.startswith("flood") for u in bad_status), bad_status
    assert stats["num_throttled"] > 0 or sheds, (
        "the flood was never shed — the arm is not exercising quotas")
    # isolation bar 2: victim tail latency holds within its bound of
    # the solo baseline (ticks — the deterministic scheduler unit)
    for t in ("acme", "bolt"):
        bound = 3.0 * solo_p99[t] + 12.0
        assert mix_p99[t] <= bound, (
            f"victim {t}: p99 TTFT {mix_p99[t]} ticks vs solo "
            f"{solo_p99[t]} (bound {bound})")
        assert good[t] > 0, good

    # phase 3: chaos — aborts + quota sheds + faults + ladder steps,
    # then the allocator must account for every block exactly
    faults = FaultPlan([
        FaultSpec(site="prefill", kind="transient", every=11),
        FaultSpec(site="decode", kind="transient", every=13),
    ], seed=1792)
    engine = InferenceEngine(
        model, params,
        EngineConfig(**{**ekw, "max_waiting": 8}, **tenancy,
                     # low watermarks: the chaos phase must actually
                     # walk the ladder (the flood quota caps its queue
                     # share at 4, so 4 is the reachable pressure mark)
                     queue_high_watermark=4,
                     free_block_low_watermark=0.25,
                     degrade_patience=1, max_dispatch_retries=3),
        faults=faults)
    _, chaos_sheds, chaos_aborts, _, chaos_stalls = drive(
        engine, combined, abort_every=5)
    chaos_res = engine.run(return_status=True)
    engine.check_allocator_integrity()
    cstats = engine.stats()
    assert chaos_stalls == 0
    assert cstats["num_cancelled"] > 0, "chaos fired no aborts"
    assert cstats["num_dispatch_retries"] > 0, "chaos fired no faults"
    assert (cstats["num_throttled"] > 0 or chaos_sheds), \
        "chaos fired no quota sheds"
    assert cstats["num_degrade_steps_down"] > 0, \
        "chaos never stepped the ladder"

    victim_good = (good["acme"] + good["bolt"]) / max(wall_mix, 1e-9)
    solo_victim_good = ((solo_good["acme"] + solo_good["bolt"])
                        / max(wall_solo, 1e-9))
    print(f"# serving multitenant: victims solo p99 TTFT "
          f"{solo_p99['acme']:.0f}/{solo_p99['bolt']:.0f} ticks -> "
          f"vs flood {mix_p99['acme']:.0f}/{mix_p99['bolt']:.0f} | "
          f"victim goodput {victim_good:.1f} (solo "
          f"{solo_victim_good:.1f}) tok/s | flood finished "
          f"{good['flood']} tok, shed {sheds.get('flood', 0)} door + "
          f"{int(stats['num_throttled'])} throttled | chaos: "
          f"{int(cstats['num_cancelled'])} aborts, "
          f"{int(cstats['num_dispatch_retries'])} retries, ladder down "
          f"{int(cstats['num_degrade_steps_down'])}, integrity OK",
          file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_multitenant_victim_goodput_tok_per_sec"
                   if on_tpu else
                   "serving_tiny_multitenant_victim_goodput_tok_per_sec"),
        "value": round(victim_good, 3),
        "unit": "tokens/sec",
        # isolation quality: combined-run victim goodput vs their solo
        # entitlement (1.0 = the flood cost the victims nothing)
        "vs_baseline": round(victim_good / max(solo_victim_good, 1e-9),
                             4),
        "per_tenant": {
            t: {"goodput_tokens": good[t],
                "p99_ttft_ticks": mix_p99.get(t),
                "solo_p99_ttft_ticks": solo_p99.get(t),
                "door_sheds": sheds.get(t, 0),
                "throttled": int(tstats.get(t, {}).get(
                    "statuses", {}).get("throttled", 0))}
            for t in ("acme", "bolt", "flood")},
        "num_offered": len(combined),
        "flood_only_shed": True,
        "chaos_aborts": int(cstats["num_cancelled"]),
        "chaos_retries": int(cstats["num_dispatch_retries"]),
        "chaos_ladder_steps_down": int(cstats["num_degrade_steps_down"]),
        "chaos_throttled": int(cstats["num_throttled"]),
        "allocator_integrity_ok": True,
    }


def bench_serving_kv_memory(fast=False):
    """Memory scale-up arm (round 11, docs/serving.md memory tiers):
    the capacity story of quantized KV blocks and the host-RAM spill
    tier, measured where it matters — concurrent residents under a
    FIXED device byte budget, and recompute avoided on a re-serve.

    Phase 1 (capacity): the same seeded bursty trace served by two
    engines whose pools hold the SAME number of KV bytes — one storing
    full-precision (fp32) blocks, one int8-with-scales blocks (so the
    int8 pool holds ~2.7x the block count). Reports peak concurrent
    residents and decode tokens/s per arm and ASSERTS the int8 pool
    sustains >= 1.5x the fp peak (the acceptance bar: quantization
    must buy real concurrency, not just smaller numbers). Both arms
    replay identical prompts/arrivals, and ``vs_baseline`` is the
    residents ratio.

    Phase 2 (spill): an int8 + prefix-caching engine with the host
    spill tier serves distinct prompts, takes a full rung-2-style
    flush (every evictable block spilled to host RAM), then RE-SERVES
    the same prompts — prefix hits now re-admit by device upload
    instead of recompute. Reports the spill hit rate (asserted
    nonzero) and asserts the re-serve outputs are token-identical to
    the first pass (greedy + deterministic engine: the upload path
    must not perturb a single token). ``fast=True`` is the tier-1
    smoke shape."""
    import dataclasses as _dc

    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import (EngineConfig, InferenceEngine,
                                  Request, kv_block_bytes)

    # FIXED seeds: this arm asserts (like the multitenant
    # arm), so the workload must be the workload the asserts were
    # designed against
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (1, 8))))
    bs, hd = 8, cfg.hidden_size // cfg.num_heads
    fp_block = kv_block_bytes(cfg.num_layers, bs, cfg.num_heads, hd,
                              dtype=jnp.float32)
    q_block = kv_block_bytes(cfg.num_layers, bs, cfg.num_heads, hd,
                             quantization="int8")
    fp_blocks = 10
    budget = fp_blocks * fp_block
    int8_blocks = budget // q_block
    plen, new = 16, 16          # 32 tokens = 4 blocks per resident
    ticks = 6 if fast else 12
    base_rate = 1.5 if fast else 2.0

    def capacity_arm(quant, num_blocks):
        ecfg = EngineConfig(max_batch=8, block_size=bs,
                            num_blocks=int(num_blocks),
                            max_prefill_len=16, max_seq_len=32,
                            decode_steps=4, kv_dtype=jnp.float32,
                            kv_quantization=quant)
        eng = InferenceEngine(model, params, ecfg)
        eng.add_request(Request(uid="warm", prompt=[1] * plen,
                                max_new_tokens=2))
        eng.run()               # compile outside the clock
        rr = np.random.RandomState(1)

        def make(tick, k):
            return Request(
                uid=f"m{k}",
                prompt=list(rr.randint(0, cfg.vocab_size, plen)),
                max_new_tokens=new)

        trace = _poisson_burst_trace(
            np.random.RandomState(2), ticks=ticks,
            base_rate=base_rate, make_request=make,
            burst_start=ticks // 3, burst_end=2 * ticks // 3,
            burst_factor=2)
        s0 = eng.stats()
        peak = 0
        t0 = time.perf_counter()
        ti = 0
        for tick in range(ticks):
            while ti < len(trace) and trace[ti][0] <= tick:
                eng.add_request(trace[ti][1])
                ti += 1
            eng.step()
            peak = max(peak, int(eng.stats()["active_slots"]))
        while eng.has_work:
            eng.step()
            peak = max(peak, int(eng.stats()["active_slots"]))
        dt = time.perf_counter() - t0
        s1 = eng.stats()
        toks = s1["num_tokens_decoded"] - s0["num_tokens_decoded"]
        return {
            "num_blocks": int(num_blocks),
            "block_bytes": int(fp_block if quant is None else q_block),
            "peak_residents": peak,
            "decode_tokens_per_sec": round(toks / max(dt, 1e-9), 3),
            "decode_tokens": int(toks),
            "preemptions": int(s1["num_preemptions"]),
            "wall_s": round(dt, 4),
        }, len(trace)

    fp_arm, offered = capacity_arm(None, fp_blocks)
    int8_arm, _ = capacity_arm("int8", int8_blocks)
    ratio = int8_arm["peak_residents"] / max(fp_arm["peak_residents"], 1)
    assert ratio >= 1.5, (
        f"int8 storage must sustain >= 1.5x the fp concurrent "
        f"residents under an equal byte budget "
        f"(got {int8_arm['peak_residents']} vs "
        f"{fp_arm['peak_residents']})")
    # both arms served the identical trace; token counts must agree
    # (no EOS in play — a divergence means an arm silently dropped
    # work, which would invalidate the tokens/s comparison)
    assert int8_arm["decode_tokens"] == fp_arm["decode_tokens"], (
        fp_arm, int8_arm)

    # phase 2: spill tier hit rate on a re-serve pass
    scfg = EngineConfig(max_batch=2, block_size=bs, num_blocks=8,
                        max_prefill_len=16, max_seq_len=32,
                        kv_dtype=jnp.float32, kv_quantization="int8",
                        enable_prefix_caching=True,
                        spill_max_bytes=64 * q_block)
    eng = InferenceEngine(model, params, scfg)
    rr = np.random.RandomState(3)
    prompts = [list(rr.randint(0, cfg.vocab_size, plen))
               for _ in range(3 if fast else 6)]

    def serve(tag):
        for i, p in enumerate(prompts):
            eng.add_request(Request(uid=f"{tag}{i}", prompt=p,
                                    max_new_tokens=4))
        return eng.run()

    first = serve("a")
    eng.allocator.flush_evictable()   # the rung-2 flush: all -> spill
    second = serve("b")
    sstats = eng.stats()
    eng.check_allocator_integrity()
    reserve_identical = all(
        second[f"b{i}"] == first[f"a{i}"]
        for i in range(len(prompts)))
    assert sstats["spill_hits"] > 0 and sstats["spill_hit_rate"] > 0, \
        sstats
    assert reserve_identical, "spill re-admit perturbed tokens"

    print(f"# kv-memory: budget {budget} B -> fp {fp_blocks} blocks "
          f"(peak {fp_arm['peak_residents']} residents, "
          f"{fp_arm['decode_tokens_per_sec']:.1f} tok/s) vs int8 "
          f"{int8_blocks} blocks (peak {int8_arm['peak_residents']}, "
          f"{int8_arm['decode_tokens_per_sec']:.1f} tok/s) = "
          f"{ratio:.2f}x residents | spill hit rate "
          f"{sstats['spill_hit_rate']:.2f} "
          f"({sstats['spill_hits']} uploads)", file=sys.stderr)
    return {
        "metric": "serving_tiny_kv_memory_int8_decode_tokens_per_sec",
        "value": int8_arm["decode_tokens_per_sec"],
        "unit": "tokens/sec",
        # the capacity headline: concurrent residents at int8 vs fp
        # under the same byte budget
        "vs_baseline": round(ratio, 3),
        "residents_ratio": round(ratio, 3),
        "byte_budget": int(budget),
        "num_offered": int(offered),
        "fp": fp_arm,
        "int8": int8_arm,
        "spill": {
            "hits": int(sstats["spill_hits"]),
            "misses": int(sstats["spill_misses"]),
            "hit_rate": round(float(sstats["spill_hit_rate"]), 4),
            "blocks_spilled": int(sstats["num_blocks_spilled"]),
            "bytes": int(sstats["spill_bytes"]),
            "reserve_token_identical": bool(reserve_identical),
        },
    }


def bench_weight_quant(fast=False):
    """Weight-quantization arm (round 19, docs/serving.md memory
    tiers): the capacity + speed story of int8 weight storage with the
    dequant-GEMM read path, measured the PR 11 way — equal-byte-budget
    arms.

    Phase 1 (capacity): the model's device param bytes at fp32 vs
    int8-with-scales storage (``gpt_param_bytes`` over the exact trees
    the engine serves). Under a FIXED HBM budget the quantized
    representation serves ``fp_bytes / q_bytes`` x the model bytes per
    chip — equivalently that many more concurrent model residents
    (multi-model serving) or a model that many times bigger. ASSERTS
    the ratio >= 1.8x (the acceptance bar: int8+scale overhead must
    not eat the 4x dtype win down to marginal).

    Phase 2 (speed + certification): the same seeded greedy trace
    served by an fp engine and a weight_quantization="int8" engine at
    equal model/config. Reports decode tokens/s per arm and ASSERTS
    the outputs are token-identical — the greedy-decode certification
    of the quantized logits, riding the bench so a numerics regression
    fails the smoke run, not just tier-1. ``fast=True`` is the tier-1
    smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.models.gpt import gpt_param_bytes, quantize_gpt_model
    from apex_tpu.serving import EngineConfig, InferenceEngine, Request

    # FIXED seeds: this arm asserts (token identity), so
    # the workload must be the workload the asserts were designed
    # against
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (1, 8))))

    # phase 1: model bytes per chip at an equal HBM budget
    fp_bytes = gpt_param_bytes(params)
    _, qparams = quantize_gpt_model(model, params, "int8")
    q_bytes = gpt_param_bytes(qparams)
    bytes_ratio = fp_bytes / q_bytes
    budget = 4 * fp_bytes           # a budget that fits 4 fp residents
    fp_residents = budget // fp_bytes
    q_residents = budget // q_bytes
    assert bytes_ratio >= 1.8, (
        f"int8 weight storage must serve >= 1.8x the model bytes per "
        f"chip at an equal HBM budget (got {fp_bytes} fp -> {q_bytes} "
        f"quantized = {bytes_ratio:.2f}x)")

    # phase 2: decode tok/s fp vs int8 at equal model, token-identity
    # asserted (greedy + deterministic engine)
    rr = np.random.RandomState(1)
    n_req, plen, new = (3, 12, 8) if fast else (6, 16, 16)
    prompts = [list(rr.randint(0, cfg.vocab_size, plen))
               for _ in range(n_req)]

    def speed_arm(mode):
        ecfg = EngineConfig(max_batch=4, block_size=8,
                            num_blocks=32, max_prefill_len=16,
                            max_seq_len=48, decode_steps=4,
                            weight_quantization=mode)
        eng = InferenceEngine(model, params, ecfg)
        eng.add_request(Request(uid="warm", prompt=[1] * plen,
                                max_new_tokens=2))
        eng.run()               # compile outside the clock
        for i, p in enumerate(prompts):
            eng.add_request(Request(uid=f"r{i}", prompt=p,
                                    max_new_tokens=new))
        s0 = eng.stats()
        t0 = time.perf_counter()
        outs = eng.run()
        dt = time.perf_counter() - t0
        toks = (eng.stats()["num_tokens_decoded"]
                - s0["num_tokens_decoded"])
        return outs, {
            "decode_tokens_per_sec": round(toks / max(dt, 1e-9), 3),
            "decode_tokens": int(toks),
            "wall_s": round(dt, 4),
        }

    fp_outs, fp_arm = speed_arm(None)
    q_outs, q_arm = speed_arm("int8")
    assert q_outs == fp_outs, (
        "int8 weight storage must decode token-identical to fp on the "
        "greedy certification trace")

    print(f"# weight-quant: {fp_bytes} fp param bytes -> {q_bytes} "
          f"int8 = {bytes_ratio:.2f}x model bytes/chip "
          f"({q_residents} vs {fp_residents} residents at a "
          f"{budget} B budget) | decode "
          f"{fp_arm['decode_tokens_per_sec']:.1f} tok/s fp vs "
          f"{q_arm['decode_tokens_per_sec']:.1f} tok/s int8, "
          f"token-identical", file=sys.stderr)
    return {
        "metric": "serving_tiny_weight_quant_int8_decode_tokens_per_sec",
        "value": q_arm["decode_tokens_per_sec"],
        "unit": "tokens/sec",
        # the capacity headline: model bytes served per chip at an
        # equal HBM budget, int8 vs fp
        "vs_baseline": round(bytes_ratio, 3),
        "bytes_ratio": round(bytes_ratio, 3),
        "fp_param_bytes": int(fp_bytes),
        "int8_param_bytes": int(q_bytes),
        "byte_budget": int(budget),
        "fp_residents": int(fp_residents),
        "int8_residents": int(q_residents),
        "greedy_token_identical": bool(q_outs == fp_outs),
        "fp": fp_arm,
        "int8": q_arm,
    }


def bench_serving_fleet(fast=False):
    """Fleet chaos arm (round 12, docs/fleet.md): the crash-tolerance
    story of the multi-replica router, certified where it matters —
    a replica KILLED mid-burst under seeded faults.

    Three phases: (0) identity — a 1-replica fleet must be
    BIT-IDENTICAL to the bare engine (outputs, terminal statuses, and
    the engine's full ``stats()`` dict, schedule counters included);
    (1) a 3-replica fleet serves a seeded Poisson-burst trace with
    shared-prefix groups (the affinity bait) kill-free, for the
    baseline p99 TTFT and goodput; (2) the SAME trace runs with
    seeded transient faults on every replica, a ``drain_replica``
    migration mid-run, and one replica hard-killed mid-burst
    (``kill_replica`` — recovery from the last periodic checkpoint
    alone) — the arm asserts ZERO lost accepted requests (every
    accepted uid terminal exactly once, ``num_lost_requests == 0``),
    at least one failover and one migration actually fired, and the
    kill-run victims' p99 TTFT (scheduler ticks, the deterministic
    unit) holds within its bound of the no-kill baseline.
    ``vs_baseline`` is kill-run goodput / no-kill goodput.
    ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.observability import percentile
    from apex_tpu.serving import (EngineConfig, FleetConfig, FleetRouter,
                                  InferenceEngine, Request,
                                  SamplingParams)
    from apex_tpu.utils.faults import FaultPlan, FaultSpec

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ekw = dict(max_batch=8, block_size=32, num_blocks=256,
                   max_prefill_len=128, max_seq_len=384,
                   kv_dtype=jnp.bfloat16, enable_prefix_caching=True,
                   snapshot_interval_ticks=2, max_waiting=64, seed=11)
        ticks, rate = 60, 0.8
        prompt_lens, max_news = (48, 96), (12, 24)
        kill_tick, drain_tick = 24, 36
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ekw = dict(max_batch=4, block_size=8, num_blocks=64,
                   max_prefill_len=16, max_seq_len=48,
                   enable_prefix_caching=True,
                   snapshot_interval_ticks=2, max_waiting=32, seed=11)
        ticks = 16 if fast else 28
        rate = 0.5 if fast else 0.7
        prompt_lens, max_news = (8, 14), (4, 6)
        kill_tick = 6 if fast else 10
        drain_tick = 10 if fast else 16
    model = GPTLMHeadModel(cfg)
    # FIXED seeds: the arm asserts on zero-lost, failover
    # coverage, and a tail-latency bound — the trace must be the same
    # every round or the asserts flake
    init_rng = np.random.RandomState(1812)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(init_rng.randint(0, cfg.vocab_size, (1, 8))))

    # shared-prefix groups: requests within a group open with the same
    # block-aligned head, so affinity routing has something to win
    prefix_rng = np.random.RandomState(1813)
    prefixes = [list(prefix_rng.randint(0, cfg.vocab_size,
                                        prompt_lens[0]))
                for _ in range(3)]

    def make_trace():
        rng = np.random.RandomState(1814)

        def make(tick, k):
            head = prefixes[k % len(prefixes)]
            tail_len = int(rng.choice(prompt_lens)) - len(head) // 2
            prompt = head + list(rng.randint(0, cfg.vocab_size,
                                             max(1, tail_len)))
            prompt = prompt[:prompt_lens[-1]]
            samp = (SamplingParams() if k % 2 else
                    SamplingParams(temperature=1.0, top_k=40))
            new = int(rng.choice(max_news))
            # a FACTORY per arrival: each drive builds fresh Request
            # objects (engines write the terminal status onto them)
            return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                                   max_new_tokens=new, sampling=samp)

        return _poisson_burst_trace(
            rng, ticks=ticks, base_rate=rate, make_request=make,
            burst_start=ticks // 3, burst_end=2 * ticks // 3,
            burst_factor=3)

    def drive(router, trace, kill_at=None, kill_idx=None,
              drain_at=None, drain_idx=None):
        """Tick the fleet through the trace; per-uid submit/first-token
        ticks via the stream feed, the kill/drain chaos moves at their
        scheduled ticks (victims = the killed replica's owners at the
        kill). Returns (ttft_ticks, accepted, victims, wall_s)."""
        submit, first = {}, {}
        accepted, victims = [], None
        t0 = time.perf_counter()
        i = tick = 0
        while i < len(trace) or router.has_work:
            while i < len(trace) and trace[i][0] <= tick:
                req = trace[i][1]()
                if router.try_add(req):
                    submit[req.uid] = tick
                    accepted.append(req.uid)
                i += 1
            if (kill_at is not None and tick == kill_at
                    and router.replicas[kill_idx].alive):
                victims = [u for u, o in router.owners().items()
                           if o == kill_idx]
                router.kill_replica(kill_idx)
            if (drain_at is not None and tick == drain_at
                    and router.replicas[drain_idx].alive):
                router.drain_replica(drain_idx)
            router.step()
            for uid, tok, last in router.pop_stream_events():
                if tok >= 0 and uid not in first and uid in submit:
                    first[uid] = tick
            tick += 1
        wall = time.perf_counter() - t0
        ttft = {u: first[u] - submit[u] for u in first}
        return ttft, accepted, victims, wall

    def pct(xs, q):
        return percentile(xs, q) if xs else 0.0

    # -- phase 0: the 1-replica identity cert (constant clock: every
    # time-derived stat equal by construction, so the FULL stats dict
    # compares) --
    ident = make_trace()[:8]
    bare = InferenceEngine(model, params, EngineConfig(**ekw),
                           clock=lambda: 0.0)
    for _, mk in ident:
        bare.add_request(mk())
    bare_res = bare.run(return_status=True)
    bare_stats = bare.stats()
    fleet1 = FleetRouter(model, params, EngineConfig(**ekw),
                         FleetConfig(num_replicas=1),
                         clock=lambda: 0.0)
    for _, mk in ident:
        fleet1.add_request(mk())
    one_res = fleet1.run(return_status=True)
    identity_ok = (
        {u: (r.tokens, r.status) for u, r in bare_res.items()}
        == {u: (r.tokens, r.status) for u, r in one_res.items()}
        and fleet1.replicas[0].engine.stats() == bare_stats)
    assert identity_ok, "1-replica fleet diverged from the bare engine"

    # -- phase 1: 3 replicas, no kill — the baseline --
    trace = make_trace()
    router = FleetRouter(model, params, EngineConfig(**ekw),
                         FleetConfig(num_replicas=3))
    ttft_base, accepted_base, _, wall_base = drive(router, trace)
    base_res = router.run(return_status=True)
    base_stats = router.stats()
    assert set(base_res) >= set(accepted_base), "baseline lost requests"
    assert base_stats["num_lost_requests"] == 0
    base_good = sum(len(r.tokens) for r in base_res.values()
                    if r.status == "finished") / max(wall_base, 1e-9)
    p99_base = pct(list(ttft_base.values()), 99)

    # -- phase 2: same trace + seeded transient faults on every
    # replica + a drain-and-migrate + one replica hard-killed
    # mid-burst --
    faults = [FaultPlan([FaultSpec(site="prefill", kind="transient",
                                   every=9)], seed=1815),
              FaultPlan([FaultSpec(site="decode", kind="transient",
                                   every=11)], seed=1816),
              FaultPlan([FaultSpec(site="decode", kind="transient",
                                   every=13)], seed=1817)]
    router = FleetRouter(model, params,
                         EngineConfig(**ekw, max_dispatch_retries=3),
                         FleetConfig(num_replicas=3),
                         faults=faults)
    ttft_kill, accepted, victims, wall_kill = drive(
        router, trace, kill_at=kill_tick, kill_idx=1,
        drain_at=drain_tick, drain_idx=2)
    kill_res = router.run(return_status=True)
    stats = router.stats()
    # the headline asserts: zero lost accepted requests, exactly one
    # terminal per accepted uid, the chaos actually fired
    missing = set(accepted) - set(kill_res)
    assert not missing, f"lost accepted requests: {sorted(missing)}"
    assert stats["num_lost_requests"] == 0, stats["num_lost_requests"]
    assert len(set(accepted)) == len(accepted)
    assert stats["num_failovers"] >= 1, "the kill never fired"
    assert stats["num_migrations"] >= 1, "the drain never migrated"
    for rep in router.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()
    n_finished = sum(r.status == "finished" for r in kill_res.values())
    assert n_finished > 0
    # victim tail latency: bounded vs the no-kill baseline (ticks —
    # the deterministic unit; victims pay the failover re-prefill)
    victims = victims or []
    victim_ttft = [ttft_kill[u] for u in victims if u in ttft_kill]
    p99_victim = pct(victim_ttft, 99)
    victim_bound = 4.0 * p99_base + 16.0
    assert p99_victim <= victim_bound, (
        f"victim p99 TTFT {p99_victim} ticks vs baseline {p99_base} "
        f"(bound {victim_bound})")
    kill_good = sum(len(r.tokens) for r in kill_res.values()
                    if r.status == "finished") / max(wall_kill, 1e-9)

    print(f"# serving fleet: identity OK | baseline p99 TTFT "
          f"{p99_base:.0f} ticks, goodput {base_good:.1f} tok/s | "
          f"kill@{kill_tick} (victims {len(victims)}) p99 "
          f"{p99_victim:.0f} ticks (bound {victim_bound:.0f}), "
          f"goodput {kill_good:.1f} tok/s | failovers "
          f"{stats['num_failovers']}, migrations "
          f"{stats['num_migrated_requests']} req, reinjected "
          f"{stats['num_reinjected_requests']}, duplicates dropped "
          f"{stats['num_duplicate_results']}, lost "
          f"{stats['num_lost_requests']}", file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_fleet_kill_goodput_tok_per_sec"
                   if on_tpu else
                   "serving_tiny_fleet_kill_goodput_tok_per_sec"),
        "value": round(kill_good, 3),
        "unit": "tokens/sec",
        # crash-tolerance quality: goodput under a replica kill vs the
        # kill-free fleet (1.0 = the kill cost nothing)
        "vs_baseline": round(kill_good / max(base_good, 1e-9), 4),
        "identity_ok": True,
        "zero_lost": True,
        "num_offered": len(trace),
        "num_accepted": len(accepted),
        "num_victims": len(victims),
        "victim_p99_ttft_ticks": round(float(p99_victim), 2),
        "victim_p99_bound_ticks": round(float(victim_bound), 2),
        "baseline_p99_ttft_ticks": round(float(p99_base), 2),
        "num_failovers": int(stats["num_failovers"]),
        "num_migrations": int(stats["num_migrations"]),
        "num_migrated_requests": int(stats["num_migrated_requests"]),
        "num_reinjected_requests":
            int(stats["num_reinjected_requests"]),
        "num_duplicate_results": int(stats["num_duplicate_results"]),
        "num_lost_requests": int(stats["num_lost_requests"]),
        "num_affinity_hits": int(stats["num_affinity_hits"]),
        "status_counts": {
            s: sum(r.status == s for r in kill_res.values())
            for s in {r.status for r in kill_res.values()}},
        "allocator_integrity_ok": True,
    }


def bench_serving_integrity(fast=False):
    """Data-integrity chaos arm (round 13, docs/robustness.md "Data
    integrity"): the end-to-end corruption story, certified where it
    matters — seeded "corrupt" faults at EVERY checksum point, and a
    silently-wrong-compute replica caught by the fleet's determinism
    cross-check.

    Three phases: (0) identity — integrity machinery fully disabled
    (``verify_artifacts=False``, no scrub, no cross-check) must be
    BIT-IDENTICAL to checksums-on, bare engine AND 1-replica fleet
    (outputs, statuses, the full stats dict): verification is pure
    detection, and enabling checksums alone changes no served token.
    (1) artifact chaos — an engine whose spill tier rots under a
    seeded plan must serve the identical tokens by recompute, and a
    2-replica fleet under corrupt plans covering
    spill_put/spill_get/checkpoint/export/import, with a migration and
    a hard kill mid-run, must finish with ZERO lost accepted requests,
    every accepted uid terminal exactly once, and every fired
    corruption caught (refused imports / corrupt checkpoints / spill
    discards all counted). (2) SDC — a 3-replica fleet with a
    ``"corrupt"`` decode fault on replica 0 and the cross-check on
    must detect the diverging replica, retire it, and lose nothing;
    DETECTION LATENCY (router ticks from the first corrupt token to
    the suspect verdict) is the reported metric. ``vs_baseline`` is
    SDC-phase goodput over the clean phase-0 fleet goodput (the price
    of serving through a corrupting replica + its retirement).
    ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.serving import (EngineConfig, FleetConfig, FleetRouter,
                                  InferenceEngine, Request,
                                  SamplingParams)
    from apex_tpu.utils.faults import FaultPlan, FaultSpec

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        cfg = GPTConfig.gpt2_small(dropout=0.0, remat=False,
                                   dtype=jnp.bfloat16)
        ekw = dict(max_batch=8, block_size=32, num_blocks=96,
                   max_prefill_len=128, max_seq_len=384,
                   kv_dtype=jnp.bfloat16, enable_prefix_caching=True,
                   spill_max_bytes=64 << 20,
                   snapshot_interval_ticks=2, seed=13)
        n_req, new_tokens = 24, 16
    else:
        cfg = GPTConfig.tiny(dropout=0.0, remat=False)
        ekw = dict(max_batch=2, block_size=4, num_blocks=10,
                   max_prefill_len=8, max_seq_len=32,
                   enable_prefix_caching=True, spill_max_bytes=1 << 20,
                   snapshot_interval_ticks=2, seed=13)
        n_req, new_tokens = (8 if fast else 12), 4
    model = GPTLMHeadModel(cfg)
    init_rng = np.random.RandomState(1905)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(init_rng.randint(0, cfg.vocab_size, (1, 8))))
    # FIXED seeds: every phase asserts — the trace must not drift
    rng = np.random.RandomState(1906)
    prompts = [list(rng.randint(1, cfg.vocab_size, 8))
               for _ in range(6)]

    def requests(prefix):
        out = []
        for k in range(n_req):
            samp = (SamplingParams(temperature=1.0, top_k=20)
                    if k % 2 else SamplingParams())
            out.append(Request(f"{prefix}{k}",
                               list(prompts[k % len(prompts)]),
                               max_new_tokens=new_tokens,
                               sampling=samp))
        return out

    def resdict(res):
        return {u: (tuple(r.tokens), r.status) for u, r in res.items()}

    # -- phase 0: integrity-off bit-identity (constant clock so the
    # full stats dict compares) --
    def engine_run(verify):
        eng = InferenceEngine(
            model, params, EngineConfig(**ekw, verify_artifacts=verify),
            clock=lambda: 0.0)
        for r in requests("i"):
            eng.add_request(r)
        return resdict(eng.run(return_status=True)), eng.stats()

    off_res, off_stats = engine_run(False)
    on_res, on_stats = engine_run(True)
    assert off_res == on_res, "checksums changed served tokens"
    assert off_stats == on_stats, "checksums changed schedule counters"

    def fleet_run(verify):
        t0 = time.perf_counter()
        fl = FleetRouter(model, params,
                         EngineConfig(**ekw, verify_artifacts=verify),
                         FleetConfig(num_replicas=1),
                         clock=lambda: 0.0)
        for r in requests("f"):
            fl.add_request(r)
        res = resdict(fl.run(return_status=True))
        return res, fl.replicas[0].engine.stats(), \
            time.perf_counter() - t0

    f_off, fs_off, _ = fleet_run(False)
    f_on, fs_on, wall_clean = fleet_run(True)
    assert f_off == f_on and fs_off == fs_on, \
        "1-replica fleet diverged across verify_artifacts"
    identity_ok = True
    clean_tokens = sum(len(t) for t, _ in f_on.values())
    clean_good = clean_tokens / max(wall_clean, 1e-9)

    # -- phase 1a: spill rot served by recompute, token-identically --
    def spill_serve(plan):
        eng = InferenceEngine(model, params, EngineConfig(**ekw),
                              faults=plan, clock=lambda: 0.0)
        outs = {}
        for wave in range(2):
            for k, p in enumerate(prompts):
                eng.add_request(Request(f"s{wave}.{k}", list(p),
                                        max_new_tokens=new_tokens))
                outs.update(eng.run())
        return outs, eng.stats()

    clean_spill, clean_sst = spill_serve(None)
    rot_plan = FaultPlan([FaultSpec(site="spill_put", kind="corrupt",
                                    every=2)], seed=1907)
    rot_spill, rot_sst = spill_serve(rot_plan)
    assert rot_spill == clean_spill, "corrupt spill changed tokens"
    spill_discards = int(rot_sst["num_spill_corrupt_discards"])
    assert spill_discards > 0, "the spill rot never fired"

    # -- phase 1b: fleet-wide artifact chaos + migrate + kill --
    def chaos_plan(seed):
        return FaultPlan([
            FaultSpec(site="spill_put", kind="corrupt", every=3),
            FaultSpec(site="spill_get", kind="corrupt", every=4),
            FaultSpec(site="checkpoint", kind="corrupt", every=2),
            FaultSpec(site="export", kind="corrupt", every=2),
            FaultSpec(site="import", kind="corrupt", every=2),
        ], seed=seed)

    fl = FleetRouter(model, params,
                     EngineConfig(**ekw, scrub_interval_ticks=3),
                     FleetConfig(num_replicas=2, respawn=True),
                     faults=[chaos_plan(1908), chaos_plan(1909)])
    accepted = []
    for r in requests("a"):
        if fl.try_add(r):
            accepted.append(r.uid)
    for _ in range(3):
        fl.step()
    owners = fl.owners()
    if owners:
        u = sorted(owners)[0]
        fl.migrate([u], owners[u])
    fl.step()
    fl.kill_replica(0)
    chaos_res = fl.run(return_status=True)
    chaos_stats = fl.stats()
    missing = set(accepted) - set(chaos_res)
    assert not missing, f"lost accepted requests: {sorted(missing)}"
    assert chaos_stats["num_lost_requests"] == 0
    chaos_detections = (
        chaos_stats["num_refused_imports"]
        + chaos_stats["num_corrupt_checkpoints"]
        + sum(rep.engine.stats()["num_corruptions_detected"]
              for rep in fl.replicas
              if rep.alive and rep.engine is not None))
    assert chaos_detections > 0, "artifact chaos never detected"

    # -- phase 2: the SDC cross-check --
    sdc_plan = FaultPlan([FaultSpec(site="decode", kind="corrupt",
                                    every=3)], seed=1910)
    fl = FleetRouter(model, params, EngineConfig(**ekw),
                     FleetConfig(num_replicas=3,
                                 sdc_check_interval_ticks=2),
                     faults=[sdc_plan, None, None])
    sdc_accepted = []
    for r in requests("d"):
        if fl.try_add(r):
            sdc_accepted.append(r.uid)
    first_corrupt_tick = suspect_tick = None
    tick = 0
    t0 = time.perf_counter()
    while fl.has_work:
        fl.step()
        tick += 1
        if first_corrupt_tick is None and any(
                kind == "corrupt" for _, kind, _ in sdc_plan.fired):
            first_corrupt_tick = tick
        if (suspect_tick is None
                and fl.stats()["num_sdc_suspects"] >= 1):
            suspect_tick = tick
    wall_sdc = time.perf_counter() - t0
    sdc_res = fl.run(return_status=True)
    sdc_stats = fl.stats()
    assert first_corrupt_tick is not None, "the SDC fault never fired"
    assert suspect_tick is not None, \
        "the cross-check never caught the corrupt replica"
    assert not fl.replicas[0].alive
    assert sdc_stats["num_lost_requests"] == 0
    assert set(sdc_res) == set(sdc_accepted), "terminals not exactly-once"
    detection_latency = suspect_tick - first_corrupt_tick
    sdc_tokens = sum(len(r.tokens) for r in sdc_res.values())
    sdc_good = sdc_tokens / max(wall_sdc, 1e-9)

    print(f"# serving integrity: identity OK | spill rot "
          f"{spill_discards} discards served token-identically | "
          f"artifact chaos {chaos_detections} detections, lost "
          f"{chaos_stats['num_lost_requests']} | SDC caught in "
          f"{detection_latency} ticks (corrupt@{first_corrupt_tick} -> "
          f"suspect@{suspect_tick}), checks "
          f"{sdc_stats['num_sdc_checks']}, goodput {sdc_good:.1f} "
          f"tok/s vs clean {clean_good:.1f}", file=sys.stderr)
    return {
        "metric": ("serving_gpt2s_integrity_sdc_detection_latency_ticks"
                   if on_tpu else
                   "serving_tiny_integrity_sdc_detection_latency_ticks"),
        "value": float(detection_latency),
        "unit": "ticks",
        # the cost of serving through a corrupting replica + its
        # retirement, relative to the clean 1-replica fleet
        "vs_baseline": round(sdc_good / max(clean_good, 1e-9), 4),
        "identity_ok": identity_ok,
        "spill_corrupt_discards": spill_discards,
        "spill_served_token_identical": True,
        "chaos_detections": int(chaos_detections),
        "chaos_refused_imports":
            int(chaos_stats["num_refused_imports"]),
        "chaos_corrupt_checkpoints":
            int(chaos_stats["num_corrupt_checkpoints"]),
        "chaos_zero_lost": True,
        "sdc_checks": int(sdc_stats["num_sdc_checks"]),
        "sdc_suspects": int(sdc_stats["num_sdc_suspects"]),
        "sdc_first_corrupt_tick": int(first_corrupt_tick),
        "sdc_suspect_tick": int(suspect_tick),
        "sdc_zero_lost": True,
        "sdc_exactly_once": True,
        "sdc_goodput_tok_per_sec": round(sdc_good, 3),
    }


_MESH_SERVING_CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
fast = sys.argv[2] == "1"
import jax, jax.numpy as jnp, numpy as np
from apex_tpu.models import GPTConfig, GPTLMHeadModel
from apex_tpu.serving import EngineConfig, InferenceEngine, Request
from apex_tpu.serving import mesh as mesh_lib

cfg = GPTConfig.tiny(dropout=0.0, remat=False)
model = GPTLMHeadModel(cfg)
params = model.init(jax.random.PRNGKey(0),
                    jnp.asarray(np.random.RandomState(0).randint(
                        0, cfg.vocab_size, (1, 8))))
n_req, plen, new = (8, 16, 12) if fast else (24, 32, 24)

def make_reqs():
    # greedy traffic: the cross-mesh token-identity assertion is
    # certified for argmax lanes (fixed seeds; the sampled story is
    # the tier-1 matrix's)
    rr = np.random.RandomState(4)
    return [Request(uid=f"m{i}",
                    prompt=list(rr.randint(0, cfg.vocab_size, plen)),
                    max_new_tokens=new) for i in range(n_req)]

def econf(mesh_shape):
    return EngineConfig(max_batch=8, block_size=8, num_blocks=64,
                        max_prefill_len=16, max_seq_len=64,
                        decode_steps=4, mesh_shape=mesh_shape, seed=9)

def serve(eng, reqs):
    for r in reqs:
        eng.add_request(r)
    return eng.run(return_status=True)

# phase 0: mesh (1,1) bit-identity to the PRE-MESH engine (the mesh
# layer neutered = the byte-identical old path), constant clock so the
# full stats() dict is comparable
CONST = lambda: 0.0
mesh_eng = InferenceEngine(model, params, econf((1, 1)), clock=CONST)
mesh_res = serve(mesh_eng, make_reqs())
saved = (mesh_lib.shard_params, mesh_lib.shard_cache,
         mesh_lib.program_out_shardings)
mesh_lib.shard_params = lambda mesh, params, pspec_fn=None: params
mesh_lib.shard_cache = lambda mesh, cache: cache
mesh_lib.program_out_shardings = lambda mesh, cache: None
try:
    plain_eng = InferenceEngine(model, params, econf((1, 1)), clock=CONST)
    plain_res = serve(plain_eng, make_reqs())
finally:
    (mesh_lib.shard_params, mesh_lib.shard_cache,
     mesh_lib.program_out_shardings) = saved
assert {u: (r.tokens, r.status) for u, r in mesh_res.items()} \
    == {u: (r.tokens, r.status) for u, r in plain_res.items()}, \
    "mesh (1,1) is not token/status-identical to the pre-mesh engine"
assert mesh_eng.stats() == plain_eng.stats(), \
    "mesh (1,1) perturbed the stats() dict"

# phase 1: the same seeded greedy trace timed at (1,1) vs (1,2)
def arm(mesh_shape):
    eng = InferenceEngine(model, params, econf(mesh_shape))
    eng.add_request(Request(uid="warm", prompt=[1] * 8, max_new_tokens=2))
    eng.run()                       # compile outside the clock
    reqs = make_reqs()
    s0 = eng.stats()
    t0 = time.perf_counter()
    res = serve(eng, reqs)
    dt = time.perf_counter() - t0
    s1 = eng.stats()
    toks = s1["num_tokens_decoded"] - s0["num_tokens_decoded"]
    audit = eng.audit_collectives()     # raises on contract violation
    return {
        "mesh_shape": list(mesh_shape),
        "decode_tokens_per_sec": round(toks / max(dt, 1e-9), 3),
        "decode_tokens": int(toks),
        "wall_s": round(dt, 4),
        "prefill_compilations": int(s1["prefill_compilations"]),
        "decode_compilations": int(s1["decode_compilations"]),
        "collective_ops": {prog: int(st["total"]["ops"])
                           for prog, st in audit.items()},
        "allreduce_ops": {prog: int(st["all-reduce"]["ops"])
                          for prog, st in audit.items()},
        # the spelling-agnostic reduction count (hlo_audit's round-5
        # lesson: XLA may lower one all-reduce as a reduce-scatter +
        # all-gather pair; the raw all-reduce count is reported above
        # as observed truth but never asserted on)
        "reduction_ops": {
            prog: int(st["all-reduce"]["ops"]
                      + st["reduce-scatter"]["ops"])
            for prog, st in audit.items()},
    }, {u: r.tokens for u, r in res.items()}

arm11, out11 = arm((1, 1))
arm12, out12 = arm((1, 2))
assert out11 == out12, \
    "greedy request outputs diverged across mesh shapes"
assert arm11["prefill_compilations"] == 1 \
    and arm11["decode_compilations"] == 1, arm11
assert arm12["prefill_compilations"] == 1 \
    and arm12["decode_compilations"] == 1, arm12
assert all(v == 0 for v in arm11["collective_ops"].values()), arm11
assert all(v >= 1 for v in arm12["reduction_ops"].values()), arm12

print(json.dumps({
    "mesh11_bit_identical": True,
    "cross_mesh_token_identical": True,
    "num_requests": n_req,
    "mesh_1x1": arm11,
    "mesh_1x2": arm12,
}))
"""


def bench_serving_mesh(fast=False):
    """Pod-scale serving arm (round 15, docs/serving.md "Mesh
    sharding"): the GSPMD mesh promotion, certified where it matters —
    the SAME seeded greedy trace served at mesh (1, 1) and (1, 2).

    Runs in a child process with TWO forced CPU host devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=2`` must be
    set before JAX initializes, and the parent's backend is already
    up), and asserts in-child: mesh (1, 1) BIT-identical to the
    pre-mesh engine (outputs, statuses, full constant-clock stats —
    the mesh layer neutered as the baseline), token-identity of every
    request's output across mesh shapes, compile counts pinned at one
    per program under both meshes, and the hlo_audit collective
    contract (zero collectives at (1, 1); every program shows
    all-reduce traffic at (1, 2) and the contract forbids
    all-to-all). Reports decode tok/s per arm — on a shared-core
    virtual mesh the (1, 2) arm pays the all-reduces without real
    parallel compute, so ``vs_baseline`` (the (1,2)/(1,1) ratio) is
    the honest collective-overhead number, not a speedup claim; on
    real multi-chip hardware the same record becomes the scale-up
    curve. ``fast=True`` is the tier-1 smoke shape."""
    import subprocess

    env = {k: v for k, v in os.environ.items()
           # the pallas read flag would make the child's (1,2) engine
           # refuse construction (the kernel is single-device) — an
           # operator exercising it on the OTHER serving sections must
           # not kill the mesh arm
           if k != "APEX_PAGED_ATTENTION_PALLAS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, "-c", _MESH_SERVING_CHILD, here,
         "1" if fast else "0"],
        capture_output=True, text=True, timeout=600, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-800:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["mesh11_bit_identical"] is True
    assert rec["cross_mesh_token_identical"] is True
    a11, a12 = rec["mesh_1x1"], rec["mesh_1x2"]
    ratio = (a12["decode_tokens_per_sec"]
             / max(a11["decode_tokens_per_sec"], 1e-9))
    print(f"# serving-mesh: {rec['num_requests']} greedy requests, "
          f"(1,1) {a11['decode_tokens_per_sec']:.1f} tok/s vs (1,2) "
          f"{a12['decode_tokens_per_sec']:.1f} tok/s ({ratio:.2f}x); "
          f"collectives (1,1) {a11['collective_ops']} -> (1,2) "
          f"reductions {a12['reduction_ops']}; bit-identity + "
          f"cross-mesh token identity held", file=sys.stderr)
    return {
        "metric": "serving_tiny_mesh_decode_tokens_per_sec",
        "value": a12["decode_tokens_per_sec"],
        "unit": "tokens/sec",
        # the honest cross-arm number on a virtual mesh: collective
        # overhead, not parallel speedup (see docstring)
        "vs_baseline": round(ratio, 3),
        "mesh11_bit_identical": True,
        "cross_mesh_token_identical": True,
        "num_requests": int(rec["num_requests"]),
        "arms": {"mesh_1x1": a11, "mesh_1x2": a12},
    }


def bench_train_step(fast=False):
    """Fused train step (apex_tpu.train): the whole global optimizer
    step — amp O2 scaled forward/backward, ``accum_steps`` scanned
    microbatches with fp32 on-device accumulation, in-graph overflow
    skip, fused-LAMB update — as ONE donated-buffer dispatch, swept
    over ``accum_steps`` in {1, 4, 8} against the hand-wired
    per-microbatch dispatch loop (``build_reference_loop``: one
    dispatch per microbatch + an apply dispatch, the pre-builder
    wiring). Reports steps/sec per arm, ASSERTS bit-identical final
    params (fused vs loop, every arm — the training analog of the
    serving bench's cross-K certification), and audits the compiled
    program's input-output aliasing so a silently-dropped donation
    reads as a regression, not a warning. ``vs_baseline`` is the
    loop/fused time ratio at the largest accum: the dispatch
    amortization itself. ``fast=True`` is the tier-1 smoke shape."""
    import flax.linen as nn

    import apex_tpu.amp as amp
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.train import build_reference_loop, build_train_step

    on_tpu = jax.default_backend() == "tpu" and not fast
    if on_tpu:
        hidden, depth, feat, classes, mb = 2048, 4, 512, 1024, 64
        accums = (1, 4, 8)
        ident_steps, iters = 8, 8
    else:
        hidden, depth, feat, classes, mb = 256, 2, 64, 16, 32
        accums = (1, 4) if fast else (1, 4, 8)
        ident_steps, iters = (4, 4) if fast else (8, 8)

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            for _ in range(depth):
                x = nn.Dense(hidden, param_dtype=jnp.float32)(x)
                x = nn.relu(x)
            return nn.Dense(classes, param_dtype=jnp.float32)(x)

    model = Net()
    rng = np.random.RandomState(_SEED + 2)
    max_acc = max(accums)
    xs_all = jnp.asarray(rng.randn(max_acc, mb, feat).astype("f4"))
    ys_all = jnp.asarray(rng.randint(0, classes, (max_acc, mb)))

    p0 = model.init(jax.random.PRNGKey(0), xs_all[0])["params"]
    p0, opt, handle = amp.initialize(
        p0, FusedLAMB(lr=1e-3, weight_decay=0.01), opt_level="O2",
        verbosity=0)
    n_param_leaves = len(jax.tree.leaves(p0))

    def loss_fn(p, batch):
        x, y = batch
        logits = model.apply({"params": p}, x).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

    def fresh(builder):
        return builder.init(jax.tree.map(jnp.copy, p0))

    def ab_time(stepper_a, state_a, stepper_b, state_b, batch,
                rounds=3):
        """Interleaved A/B marginal timing (the _ab_chain_time
        methodology restated for (state, batch) steppers whose two arms
        carry different state types): alternate arms round-robin so
        both ride the same load drift, keep the min marginal per arm.
        Each arm's state threads across rounds (donating steps consume
        it)."""
        arms = [[stepper_a, state_a, None], [stepper_b, state_b, None]]
        mins = [None, None]
        for arm in arms:                 # compile outside the clock
            arm[1], m = arm[0](arm[1], batch)
            arm[2] = m["loss"]
            float(np.asarray(arm[2]))
        for _ in range(rounds):
            for i, arm in enumerate(arms):
                def advance(n, arm=arm):
                    for _ in range(n):
                        arm[1], m = arm[0](arm[1], batch)
                        arm[2] = m["loss"]

                dt = marginal_time(
                    advance, lambda arm=arm: float(np.asarray(arm[2])),
                    iters)
                mins[i] = dt if mins[i] is None else min(mins[i], dt)
        return mins

    sweep, all_identical, alias_pairs = {}, True, None
    for a in accums:
        batch = (xs_all[:a], ys_all[:a])
        ts = build_train_step(loss_fn, opt, amp=handle, accum_steps=a,
                              donate=True)
        ref = build_reference_loop(loss_fn, opt, amp=handle,
                                   accum_steps=a)
        if a == max_acc:                # donation audit on the big arm
            alias_pairs = ts.alias_stats(fresh(ts), batch)["pairs"]
        # bit-identity certification: same init, same stream, T steps
        sA, sB = fresh(ts), fresh(ref)
        for _ in range(ident_steps):
            sA, _m = ts.step(sA, batch)
            sB, _m = ref.step(sB, batch)
        ident = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves((sA.params, sA.opt_state)),
                            jax.tree.leaves((sB.params, sB.opt_state))))
        all_identical = all_identical and ident
        dt_fused, dt_loop = ab_time(ts.step, fresh(ts), ref.step,
                                    fresh(ref), batch,
                                    rounds=1 if fast else 3)
        sweep[f"accum{a}"] = {
            "fused_steps_per_sec": round(1.0 / dt_fused, 3),
            "loop_steps_per_sec": round(1.0 / dt_loop, 3),
            "speedup": round(dt_loop / dt_fused, 3),
            "bit_identical": bool(ident),
        }

    if not all_identical:
        # the certification is the point: a fused-vs-loop bit mismatch
        # must fail the section loudly (missing record in the round),
        # never record rc=0 with a quietly-false JSON field
        raise AssertionError(
            "fused-scan vs per-microbatch loop params NOT bit-identical: "
            + json.dumps({k: v["bit_identical"] for k, v in sweep.items()}))
    top = sweep[f"accum{max_acc}"]
    print("# train step: " + " | ".join(
        f"accum={a} fused {sweep[f'accum{a}']['fused_steps_per_sec']:.1f} "
        f"vs loop {sweep[f'accum{a}']['loop_steps_per_sec']:.1f} steps/s "
        f"({sweep[f'accum{a}']['speedup']:.2f}x)" for a in accums)
        + f" | bit-identical {all_identical} | donated alias pairs "
        f"{alias_pairs}/{n_param_leaves} param leaves", file=sys.stderr)
    return {
        "metric": ("train_step_fused_accum8_steps_per_sec" if on_tpu
                   else "train_step_tiny_smoke_fused_steps_per_sec"),
        "value": top["fused_steps_per_sec"],
        "unit": "steps/sec",
        # the fused-vs-per-microbatch-dispatch amortization at max accum
        "vs_baseline": top["speedup"],
        "accum_steps_swept": list(accums),
        "final_params_bit_identical": bool(all_identical),
        "donated": True,
        "donated_alias_pairs": int(alias_pairs),
        "param_leaves": int(n_param_leaves),
        "sweep": sweep,
    }


_TRAIN_SHARDED_CHILD = r"""
import json, sys, time
sys.path.insert(0, sys.argv[1])
fast = sys.argv[2] == "1"
import jax, jax.numpy as jnp, numpy as np
from apex_tpu.models.gpt import GPTConfig, GPTLMHeadModel, lm_loss
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.serving.mesh import build_mesh
from apex_tpu.train import build_train_step

cfg = GPTConfig.tiny(dropout=0.0, remat=False)
model = GPTLMHeadModel(cfg)
ACCUM, B, S = 2, 4, 16
tokens = jnp.asarray(np.random.RandomState(7).randint(
    0, cfg.vocab_size, (ACCUM, B, S)))
params = jax.device_get(
    model.init(jax.random.PRNGKey(0), tokens[0])["params"])

def loss_fn(p, mb):
    return lm_loss(model.apply({"params": p}, mb), mb)

arms, order = {}, ["meshless", "mesh_1x2", "mesh_2x2"]
for name, shape in zip(order, [None, (1, 2), (2, 2)]):
    opt = DistributedFusedAdam(lr=1e-3, flat_mode="global")
    kw = dict(accum_steps=ACCUM)
    if shape is not None:
        kw.update(mesh=build_mesh(shape), num_heads=cfg.num_heads)
    ts = build_train_step(loss_fn, opt, **kw)
    st = ts.init(jax.tree.map(jnp.asarray, params))
    st, m = ts.step(st, tokens)  # compile outside the clock
    arms[name] = {"ts": ts, "st": st,
                  "loss1": float(jax.device_get(m["loss"]))}

# certification: every mesh arm's first optimizer step lands on the
# meshless loss (the tier-1 matrix holds the bit-level story; here the
# cross-partitioning fp32 drift bound is the gate)
ref = arms["meshless"]["loss1"]
for name in order[1:]:
    got = arms[name]["loss1"]
    assert abs(got - ref) <= 1e-3 * abs(ref) + 1e-5, (name, got, ref)

# interleaved A/B: round-robin the arms so every arm rides the same
# host-load drift; min-of-rounds marginal seconds per global step
iters, rounds = (2, 2) if fast else (4, 3)
best = {n: None for n in order}
for _ in range(rounds):
    for n in order:
        a = arms[n]
        t0 = time.perf_counter()
        for _ in range(iters):
            a["st"], m = a["ts"].step(a["st"], tokens)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / iters
        best[n] = dt if best[n] is None else min(best[n], dt)

out = {"arms": {}, "loss_certified": True}
for n in order:
    a, ts = arms[n], arms[n]["ts"]
    rec = {"steps_per_sec": round(1.0 / best[n], 3),
           "compiles": int(ts._jitted._cache_size()),
           "opt_state_bytes_per_shard":
               int(ts._core.optimizer.stats()["opt_state_bytes_per_shard"]),
           "flat_world": int(ts._core.optimizer.stats()["flat_world"])}
    assert rec["compiles"] == 1, (n, rec["compiles"])
    if ts.mesh_shape is not None:
        # raises on any per-mesh contract violation (forbidden
        # all-to-all, missing TP all-reduces, missing ZeRO leg)
        audit = ts.audit_collectives(a["st"], tokens)
        rec["collective_ops"] = {
            k: int(v["ops"]) for k, v in audit["collectives"].items()}
        rec["alias_pairs"] = int(audit["alias"]["pairs"])
        rec["sharded_leaves"] = int(audit["sharded_leaves"])
    out["arms"][n] = rec
print(json.dumps(out))
"""


def bench_train_sharded(fast=False):
    """3D-parallel training arm (round 20, docs/training.md "Sharded
    training"): the GSPMD ``build_train_step(mesh=...)`` promotion —
    scanned accumulation + ZeRO flat-shard optimizer update + tensor-
    parallel activations in ONE donated dispatch — A/B'd against the
    meshless fused step on the same tiny GPT.

    Runs in a child process with FOUR forced CPU host devices (the
    ``XLA_FLAGS`` must land before JAX initializes; the parent backend
    is already up), interleaves the meshless / (1,2) / (2,2) arms
    round-robin so all share the host-load drift, and asserts
    in-child: every mesh arm's loss certified against meshless, the
    compile count pinned at ONE per arm (the spec-canonicalization
    regression gate), and the AOT hlo_audit collective contract per
    mesh shape (all-to-all forbidden; TP all-reduces and the ZeRO
    reduce+gather leg required where the geometry demands them). On a
    shared-core virtual mesh the sharded arms pay the collectives
    without real parallel compute, so ``vs_baseline`` (the
    (2,2)/meshless steps/s ratio) is the honest overhead number, not
    a speedup claim; ``opt_state_bytes_per_shard`` falling from the
    world-1 arms to (2,2) is the ZeRO memory story that survives the
    virtual mesh. ``fast=True`` is the tier-1 smoke shape."""
    import subprocess

    env = {k: v for k, v in os.environ.items()
           # single-device pallas knobs must not leak into the mesh
           # child (same hygiene as bench_serving_mesh)
           if k != "APEX_PAGED_ATTENTION_PALLAS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, "-c", _TRAIN_SHARDED_CHILD, here,
         "1" if fast else "0"],
        capture_output=True, text=True, timeout=600, env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-800:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    arms = rec["arms"]
    assert rec["loss_certified"] is True
    for n in ("mesh_1x2", "mesh_2x2"):
        assert arms[n]["compiles"] == 1
        assert arms[n]["collective_ops"].get("all-to-all", 0) == 0
        assert arms[n]["alias_pairs"] >= arms[n]["sharded_leaves"] > 0
    # the ZeRO shard: (2,2) has flat_world=2, so each rank holds half
    # the fp32 master/m/v bytes of the world-1 arms (modulo padding)
    assert (arms["mesh_2x2"]["opt_state_bytes_per_shard"]
            < arms["mesh_1x2"]["opt_state_bytes_per_shard"])
    base = arms["meshless"]["steps_per_sec"]
    top = arms["mesh_2x2"]
    ratio = top["steps_per_sec"] / max(base, 1e-9)
    zero_ratio = (arms["meshless"]["opt_state_bytes_per_shard"]
                  / max(top["opt_state_bytes_per_shard"], 1))
    print(f"# train-sharded: meshless {base:.2f} steps/s vs (2,2) "
          f"{top['steps_per_sec']:.2f} steps/s ({ratio:.2f}x); (2,2) "
          f"collectives {top['collective_ops']}; opt-state bytes/shard "
          f"{arms['meshless']['opt_state_bytes_per_shard']} -> "
          f"{top['opt_state_bytes_per_shard']} ({zero_ratio:.2f}x "
          f"ZeRO shrink); loss certified, compiles pinned at 1",
          file=sys.stderr)
    return {
        "metric": "train_tiny_sharded_steps_per_sec",
        "value": top["steps_per_sec"],
        "unit": "steps/sec",
        # the honest cross-arm number on a virtual mesh: collective
        # overhead, not parallel speedup (see docstring)
        "vs_baseline": round(ratio, 3),
        "loss_certified": True,
        "opt_state_bytes_ratio": round(zero_ratio, 3),
        "arms": arms,
    }


def bench_serving_process(fast=False):
    """Out-of-process replica arm (round 16, docs/fleet.md "Process
    replicas" + "Autoscaler"): the child-process serving runtime and
    the elastic autoscaler, certified where they matter — a child
    SIGKILLED for real mid-burst, and a fleet that grows and shrinks
    without flapping.

    Three phases: (0) identity — a 1-process-replica fleet (the engine
    in a CHILD OS process behind the framed stdio RPC) must be
    BIT-IDENTICAL to the in-process 1-replica fleet: outputs, terminal
    statuses, and the full constant-clock fleet ``stats()`` (only the
    per-replica ``mode`` tag differs, popped before compare); (1) a
    2-process-replica fleet serves a seeded Poisson burst while one
    child is ``os.kill``-SIGKILLED mid-burst with respawn on — ZERO
    lost accepted requests, every accepted uid terminal exactly once,
    at least one failover, a FRESH child pid in the victim slot, and
    the victims' p99 TTFT (scheduler ticks) bounded vs the kill-free
    in-process baseline on the same trace; (2) the autoscaler rides a
    burst-then-drain ramp in-process (the control loop is
    mode-agnostic; in-process keeps the phase child-free): the fleet
    grows under load, shrinks back to min when drained, spawn/retire
    counts balance, and an idle tail of ticks shows zero flapping.

    Always the tiny host shape: process replicas are a HOST runtime
    mechanism (device kernels untouched), and two processes cannot
    share one TPU — on a TPU parent the children are forced to
    ``JAX_PLATFORMS=cpu`` and the parent arms pin to the CPU backend
    so phase 0 compares like with like. ``fast=True`` is the tier-1
    smoke shape."""
    import contextlib
    import signal as _signal

    from apex_tpu.models import GPTConfig
    from apex_tpu.observability import percentile
    from apex_tpu.serving import (EngineConfig, FleetConfig, FleetRouter,
                                  Request, SamplingParams)
    from apex_tpu.serving.process_replica import (build_model_from_spec,
                                                  gpt_model_spec)

    backend = jax.default_backend()
    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    spec = gpt_model_spec(cfg)
    ekw = dict(max_batch=4, block_size=8, num_blocks=64,
               max_prefill_len=16, max_seq_len=48,
               enable_prefix_caching=True,
               snapshot_interval_ticks=2, max_waiting=32, seed=11)
    ticks = 10 if fast else 16
    rate = 0.5 if fast else 0.7
    prompt_lens, max_news = (8, 14), (4, 6)
    kill_tick = 4 if fast else 6

    stack = contextlib.ExitStack()
    prev_platforms = os.environ.get("JAX_PLATFORMS")
    if backend != "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        stack.enter_context(jax.default_device(jax.devices("cpu")[0]))
    try:
        # the parent builds (model, params) FROM the spec — the same
        # deterministic init the children replay, so the boot
        # checksum handshake passes by construction
        model, params = build_model_from_spec(spec)

        def make_trace():
            rng = np.random.RandomState(1914)

            def make(tick, k):
                prompt = list(rng.randint(1, cfg.vocab_size,
                                          int(rng.choice(prompt_lens))))
                samp = (SamplingParams() if k % 2 else
                        SamplingParams(temperature=1.0, top_k=40))
                new = int(rng.choice(max_news))
                return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                                       max_new_tokens=new, sampling=samp)

            return _poisson_burst_trace(
                rng, ticks=ticks, base_rate=rate, make_request=make,
                burst_start=ticks // 3, burst_end=2 * ticks // 3,
                burst_factor=3)

        def drive(router, trace, kill_at=None, kill_idx=None):
            """Tick through the trace; the kill is a REAL ``os.kill``
            SIGKILL on the child pid (no cooperation — the parent
            discovers the corpse through the RPC layer). Returns
            (ttft_ticks, accepted, victims, wall_s)."""
            submit, first = {}, {}
            accepted, victims = [], None
            t0 = time.perf_counter()
            i = tick = 0
            while i < len(trace) or router.has_work:
                while i < len(trace) and trace[i][0] <= tick:
                    req = trace[i][1]()
                    if router.try_add(req):
                        submit[req.uid] = tick
                        accepted.append(req.uid)
                    i += 1
                if (kill_at is not None and tick == kill_at
                        and router.replicas[kill_idx].alive):
                    victims = [u for u, o in router.owners().items()
                               if o == kill_idx]
                    os.kill(router.replicas[kill_idx].engine.child_pid,
                            _signal.SIGKILL)
                router.step()
                for uid, tok, last in router.pop_stream_events():
                    if tok >= 0 and uid not in first and uid in submit:
                        first[uid] = tick
                tick += 1
            wall = time.perf_counter() - t0
            ttft = {u: first[u] - submit[u] for u in first}
            return ttft, accepted, victims, wall

        def pct(xs, q):
            return percentile(xs, q) if xs else 0.0

        proc_kw = dict(model_spec=spec,
                       child_clock={"kind": "constant", "t": 0.0})

        # -- phase 0: the 1-process-replica identity cert (constant
        # clocks both sides: every time-derived stat equal by
        # construction, so the FULL fleet stats dict compares) --
        ident = make_trace()[:6]

        def run_one(mode):
            kw = proc_kw if mode == "process" else {}
            fleet = FleetRouter(model, params, EngineConfig(**ekw),
                                FleetConfig(num_replicas=1,
                                            replica_mode=mode),
                                clock=lambda: 0.0, **kw)
            try:
                for _, mk in ident:
                    fleet.add_request(mk())
                res = fleet.run(return_status=True)
                stats = json.loads(json.dumps(fleet.stats(),
                                              sort_keys=True,
                                              default=str))
                for row in stats["replicas"].values():
                    row.pop("mode")
                return ({u: (tuple(r.tokens), r.status)
                         for u, r in res.items()}, stats)
            finally:
                fleet.close()

        in_res, in_stats = run_one("in_process")
        pr_res, pr_stats = run_one("process")
        assert pr_res == in_res, \
            "process fleet outputs diverged from in-process"
        assert pr_stats == in_stats, \
            "process fleet stats diverged from in-process"

        # -- phase 1: kill-free in-process baseline, then the same
        # trace on a 2-process-replica fleet with a mid-burst SIGKILL
        # on one child --
        trace = make_trace()
        base = FleetRouter(model, params, EngineConfig(**ekw),
                           FleetConfig(num_replicas=2))
        ttft_base, accepted_base, _, wall_base = drive(base, trace)
        base_res = base.run(return_status=True)
        assert base.stats()["num_lost_requests"] == 0
        base_good = sum(len(r.tokens) for r in base_res.values()
                        if r.status == "finished") / max(wall_base, 1e-9)
        p99_base = pct(list(ttft_base.values()), 99)

        router = FleetRouter(model, params, EngineConfig(**ekw),
                             FleetConfig(num_replicas=2,
                                         replica_mode="process",
                                         respawn=True),
                             **proc_kw)
        try:
            pid0 = router.replicas[0].engine.child_pid
            ttft_kill, accepted, victims, wall_kill = drive(
                router, trace, kill_at=kill_tick, kill_idx=0)
            kill_res = router.run(return_status=True)
            stats = router.stats()
            missing = set(accepted) - set(kill_res)
            assert not missing, \
                f"lost accepted requests: {sorted(missing)}"
            assert stats["num_lost_requests"] == 0
            assert len(set(accepted)) == len(accepted)
            assert stats["num_failovers"] >= 1, "the kill never fired"
            assert stats["num_respawns"] >= 1, "no respawn after kill"
            fresh = router.replicas[0].engine
            pids_fresh = fresh is not None and fresh.child_pid != pid0
            assert pids_fresh, "victim slot did not get a fresh child"
        finally:
            router.close()
        victims = victims or []
        victim_ttft = [ttft_kill[u] for u in victims if u in ttft_kill]
        p99_victim = pct(victim_ttft, 99)
        victim_bound = 4.0 * p99_base + 16.0
        assert p99_victim <= victim_bound, (
            f"victim p99 TTFT {p99_victim} ticks vs baseline "
            f"{p99_base} (bound {victim_bound})")
        kill_good = sum(len(r.tokens) for r in kill_res.values()
                        if r.status == "finished") / max(wall_kill, 1e-9)

        # -- phase 2: the autoscale ramp, in-process (child-free) --
        ramp = FleetRouter(
            model, params,
            EngineConfig(**{**ekw, "max_batch": 1}),
            FleetConfig(num_replicas=1,
                        autoscale_high_watermark=1.0,
                        autoscale_low_watermark=0.5,
                        autoscale_patience=2,
                        autoscale_max_replicas=3))
        n_ramp = 8 if fast else 12
        rng = np.random.RandomState(1915)
        for k in range(n_ramp):
            ramp.add_request(Request(
                uid=f"r{k}", prompt=list(rng.randint(1, cfg.vocab_size,
                                                     6)),
                max_new_tokens=12, sampling=SamplingParams()))
        sizes = []
        while ramp.has_work:
            ramp.step()
            sizes.append(len(ramp._alive()))
        rs = ramp.stats()
        assert max(sizes) > 1, "the ramp never triggered a spawn"
        assert sizes[-1] == 1, "the drained fleet did not shrink to min"
        assert max(sizes) <= 3 and min(sizes) >= 1
        assert rs["num_spawned"] == rs["num_retired"] >= 1
        assert rs["num_lost_requests"] == 0
        assert len(ramp.run()) == n_ramp
        before = (rs["num_spawned"], rs["num_retired"])
        for _ in range(8):                      # idle tail: no flapping
            ramp.step()
        after = ramp.stats()
        flap_free = (after["num_spawned"], after["num_retired"]) == before
        assert flap_free, "the idle fleet flapped"
    finally:
        stack.close()
        if backend != "cpu":
            if prev_platforms is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = prev_platforms

    print(f"# serving process: identity OK | baseline p99 TTFT "
          f"{p99_base:.0f} ticks, goodput {base_good:.1f} tok/s | "
          f"SIGKILL@{kill_tick} (victims {len(victims)}) p99 "
          f"{p99_victim:.0f} ticks (bound {victim_bound:.0f}), "
          f"goodput {kill_good:.1f} tok/s | failovers "
          f"{stats['num_failovers']}, respawns {stats['num_respawns']}, "
          f"rpc retries {stats['num_rpc_retries']}, rpc timeouts "
          f"{stats['num_rpc_timeouts']} | ramp peak {max(sizes)} "
          f"replicas, spawned {after['num_spawned']}, retired "
          f"{after['num_retired']}", file=sys.stderr)
    return {
        "metric": "serving_tiny_process_kill_goodput_tok_per_sec",
        "value": round(kill_good, 3),
        "unit": "tokens/sec",
        # SIGKILL-tolerance quality: goodput with a child killed
        # mid-burst vs the kill-free in-process fleet (wall-clock, so
        # the respawn boot cost shows here, not in ticks)
        "vs_baseline": round(kill_good / max(base_good, 1e-9), 4),
        "identity_ok": True,
        "zero_lost": True,
        "child_pid_fresh": True,
        "num_offered": len(trace),
        "num_accepted": len(accepted),
        "num_victims": len(victims),
        "victim_p99_ttft_ticks": round(float(p99_victim), 2),
        "victim_p99_bound_ticks": round(float(victim_bound), 2),
        "baseline_p99_ttft_ticks": round(float(p99_base), 2),
        "num_failovers": int(stats["num_failovers"]),
        "num_respawns": int(stats["num_respawns"]),
        "num_rpc_retries": int(stats["num_rpc_retries"]),
        "num_rpc_timeouts": int(stats["num_rpc_timeouts"]),
        "num_lost_requests": int(stats["num_lost_requests"]),
        "autoscale_peak_replicas": int(max(sizes)),
        "autoscale_num_spawned": int(after["num_spawned"]),
        "autoscale_num_retired": int(after["num_retired"]),
        "autoscale_flap_free": True,
        "status_counts": {
            s: sum(r.status == s for r in kill_res.values())
            for s in {r.status for r in kill_res.values()}},
    }


def bench_serving_disagg(fast=False):
    """Disaggregated prefill/decode arm (round 17, docs/fleet.md
    "Disaggregated roles"): specialist replicas vs the colocated fleet
    at EQUAL device count, on a trace built to expose the interference
    disaggregation removes — long-decode requests pin a colocated
    replica's lanes for their whole decode, so a newcomer's prefill
    waits out someone else's generation, and every prefill chunk that
    does run lands its latency on the resident decodes sharing the
    tick.

    Three phases: (1) colocated baseline — 2 role-less replicas serve
    a seeded Poisson mix of long-decode and latency-sensitive
    short-prompt requests; TTFT p99 (scheduler ticks), decode goodput
    (wall), and the interference quantified directly: ticks where a
    replica ran a prefill chunk AND stepped live decode lanes
    (chunk-over-decode), plus lane-wait implied by the TTFT tail; (2)
    the SAME trace on a {1 prefill + 1 decode} specialist fleet —
    prefill lanes recycle at handoff instead of being held through
    decode, so the arm asserts the disaggregated TTFT p99 is LOWER
    than colocated, decode specialists never prefilled a fresh
    prompt (their chunk count is bounded by their handoff imports —
    only sub-block tail resumes), the handoff counters moved real
    requests/bytes, and nothing was lost;
    (3) chaos — the prefill specialist is hard-killed mid-trace:
    role fallback + checkpoint failover must finish every accepted
    request with ``num_lost_requests == 0``. ``vs_baseline`` is
    disaggregated p99 / colocated p99 (< 1 = disaggregation pays).
    ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.observability import percentile
    from apex_tpu.serving import (EngineConfig, FleetConfig, FleetRouter,
                                  Request, SamplingParams)

    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    # lanes are the contended resource: few of them, long decodes
    ekw = dict(max_batch=2, block_size=8, num_blocks=96,
               max_prefill_len=8, max_seq_len=64,
               enable_prefix_caching=True, spill_max_bytes=1 << 20,
               snapshot_interval_ticks=2, max_waiting=64, seed=11)
    ticks = 14 if fast else 28
    rate = 1.0 if fast else 0.9
    heavy_new = 16 if fast else 24
    kill_tick = 5 if fast else 9
    model = GPTLMHeadModel(cfg)
    # FIXED seeds: the arm asserts a latency ORDERING
    # between two fleets on one trace — the trace must be the same
    # every round or the assert flakes
    init_rng = np.random.RandomState(1712)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(init_rng.randint(0, cfg.vocab_size, (1, 8))))

    def make_trace():
        rng = np.random.RandomState(1713)

        def make(tick, k):
            heavy = (k % 3) != 2
            # single-chunk prompts: the contended resource is the
            # LANE a long decode pins, not prefill chunk bandwidth
            plen = int(rng.randint(6, 9) if heavy
                       else rng.randint(4, 7))
            prompt = list(rng.randint(0, cfg.vocab_size, plen))
            new = (heavy_new + int(rng.randint(0, 4)) if heavy
                   else int(rng.randint(2, 5)))
            samp = (SamplingParams() if k % 2 else
                    SamplingParams(temperature=1.0, top_k=40))
            return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                                   max_new_tokens=new, sampling=samp)

        return _poisson_burst_trace(
            rng, ticks=ticks, base_rate=rate, make_request=make,
            burst_start=ticks // 3, burst_end=2 * ticks // 3,
            burst_factor=2)

    def drive(router, trace, kill_at=None, kill_idx=None):
        """Tick through the trace; per-uid submit/first-token ticks
        via the stream feed. Interference probe per tick: a replica
        that both chunked a prefill and stepped decode lanes charged
        that chunk's latency to the residents (chunk-over-decode).
        Returns (ttft, accepted, contended_ticks, chunks_by_rep,
        wall_s)."""
        submit, first, accepted = {}, {}, []
        contended = 0
        t0 = time.perf_counter()
        i = tick = 0

        def counters():
            out = {}
            for idx, rep in enumerate(router.replicas):
                if rep.alive and rep.engine is not None:
                    s = rep.engine.stats()
                    out[idx] = (int(s["num_prefill_chunks"]),
                                int(s["num_decode_steps"]))
            return out

        before = counters()
        while i < len(trace) or router.has_work:
            while i < len(trace) and trace[i][0] <= tick:
                req = trace[i][1]()
                if router.try_add(req):
                    submit[req.uid] = tick
                    accepted.append(req.uid)
                i += 1
            if (kill_at is not None and tick == kill_at
                    and router.replicas[kill_idx].alive):
                router.kill_replica(kill_idx)
            router.step()
            after = counters()
            for idx in after:
                b = before.get(idx, (0, 0))
                if (after[idx][0] > b[0] and after[idx][1] > b[1]):
                    contended += 1
            before = after
            for uid, tok, last in router.pop_stream_events():
                if tok >= 0 and uid not in first and uid in submit:
                    first[uid] = tick
            tick += 1
        wall = time.perf_counter() - t0
        chunks = {idx: c for idx, (c, _) in before.items()}
        ttft = {u: first[u] - submit[u] for u in first}
        return ttft, accepted, contended, chunks, wall

    def pct(xs, q):
        return percentile(xs, q) if xs else 0.0

    def goodput(res, wall):
        return sum(len(r.tokens) for r in res.values()
                   if r.status == "finished") / max(wall, 1e-9)

    # -- phase 1: the colocated baseline (2 role-less replicas) --
    trace = make_trace()
    colo = FleetRouter(model, params, EngineConfig(**ekw),
                       FleetConfig(num_replicas=2))
    ttft_colo, acc_colo, contended_colo, _, wall_colo = drive(
        colo, trace)
    colo_res = colo.run(return_status=True)
    colo_stats = colo.stats()
    assert not (set(acc_colo) - set(colo_res)), "colocated lost requests"
    assert colo_stats["num_lost_requests"] == 0
    p99_colo = pct(list(ttft_colo.values()), 99)
    good_colo = goodput(colo_res, wall_colo)

    # -- phase 2: the same trace, disaggregated at equal device
    # count ({1 prefill + 1 decode} vs the 2 colocated) --
    disagg = FleetRouter(model, params, EngineConfig(**ekw),
                         FleetConfig(num_replicas=2,
                                     replica_roles=("prefill",
                                                    "decode")))
    ttft_dis, acc_dis, contended_dis, chunks_dis, wall_dis = drive(
        disagg, trace)
    dis_res = disagg.run(return_status=True)
    dis_stats = disagg.stats()
    assert not (set(acc_dis) - set(dis_res)), "disagg lost requests"
    assert dis_stats["num_lost_requests"] == 0
    assert dis_stats["num_handoffs"] >= 1, "no handoff sweep fired"
    assert dis_stats["num_handoff_requests"] >= 1
    assert dis_stats["num_handoff_bytes"] > 0
    decode_rows = {idx: dis_stats["replicas"][str(idx)]
                   for idx in chunks_dis
                   if dis_stats["replicas"][str(idx)]["role"]
                   == "decode"}
    decode_chunks = sum(chunks_dis[idx] for idx in decode_rows)
    decode_imports = sum(int(r["num_migrated_in"])
                         for r in decode_rows.values())
    # a decode specialist never prefills a FRESH prompt: its only
    # chunks are the sub-block tail resumes of handed-off requests
    # (the prefix-cache transport moves full blocks; the tail is
    # shorter than one chunk), so chunks are bounded by imports
    assert decode_chunks <= decode_imports, (
        f"decode specialists ran {decode_chunks} prefill chunks for "
        f"only {decode_imports} handoff imports — fresh prompts "
        f"leaked onto the decode pool")
    p99_dis = pct(list(ttft_dis.values()), 99)
    good_dis = goodput(dis_res, wall_dis)
    # the headline ordering: specialist prefill lanes recycle at the
    # handoff instead of being held hostage through a long decode
    assert p99_dis < p99_colo, (
        f"disaggregated TTFT p99 {p99_dis} ticks did not beat "
        f"colocated {p99_colo}")

    # -- phase 3: the prefill specialist hard-killed mid-trace --
    chaos = FleetRouter(model, params, EngineConfig(**ekw),
                        FleetConfig(num_replicas=2,
                                    replica_roles=("prefill",
                                                   "decode")))
    _, acc_kill, _, _, _ = drive(chaos, trace, kill_at=kill_tick,
                                 kill_idx=0)
    kill_res = chaos.run(return_status=True)
    kill_stats = chaos.stats()
    missing = set(acc_kill) - set(kill_res)
    assert not missing, f"lost accepted requests: {sorted(missing)}"
    assert kill_stats["num_lost_requests"] == 0
    assert kill_stats["num_failovers"] >= 1, "the kill never fired"
    for rep in chaos.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()

    print(f"# serving disagg: colocated p99 TTFT {p99_colo:.0f} ticks "
          f"(chunk-over-decode {contended_colo} ticks), goodput "
          f"{good_colo:.1f} tok/s | disagg p99 {p99_dis:.0f} ticks "
          f"(contended {contended_dis}), goodput {good_dis:.1f} tok/s "
          f"| handoffs {dis_stats['num_handoffs']} sweeps / "
          f"{dis_stats['num_handoff_requests']} req / "
          f"{dis_stats['num_handoff_bytes']} B, probes skipped "
          f"{dis_stats['num_affinity_probes_skipped']} | prefill-kill: "
          f"failovers {kill_stats['num_failovers']}, lost "
          f"{kill_stats['num_lost_requests']}", file=sys.stderr)
    return {
        "metric": "serving_tiny_disagg_ttft_p99_ticks",
        "value": round(float(p99_dis), 2),
        "unit": "ticks",
        # the disaggregation win: specialist TTFT p99 over colocated
        # TTFT p99 on the interference trace (< 1 = disagg pays)
        "vs_baseline": round(float(p99_dis) / max(float(p99_colo),
                                                  1e-9), 4),
        "colocated_ttft_p99_ticks": round(float(p99_colo), 2),
        "colocated_goodput_tok_per_sec": round(good_colo, 3),
        "disagg_goodput_tok_per_sec": round(good_dis, 3),
        "colocated_chunk_over_decode_ticks": int(contended_colo),
        "disagg_chunk_over_decode_ticks": int(contended_dis),
        "decode_specialist_prefill_chunks": int(decode_chunks),
        "decode_specialist_imports": int(decode_imports),
        "num_offered": len(trace),
        "num_accepted_colocated": len(acc_colo),
        "num_accepted_disagg": len(acc_dis),
        "num_handoffs": int(dis_stats["num_handoffs"]),
        "num_handoff_requests": int(dis_stats["num_handoff_requests"]),
        "num_handoff_bytes": int(dis_stats["num_handoff_bytes"]),
        "num_affinity_probes_skipped":
            int(dis_stats["num_affinity_probes_skipped"]),
        "kill_num_failovers": int(kill_stats["num_failovers"]),
        "kill_num_lost_requests":
            int(kill_stats["num_lost_requests"]),
        "zero_lost": True,
        "status_counts": {
            s: sum(r.status == s for r in dis_res.values())
            for s in {r.status for r in dis_res.values()}},
        "allocator_integrity_ok": True,
    }


def bench_serving_shared_prefix(fast=False):
    """Fleet-global shared prefix tier arm (round 18, docs/fleet.md
    "Shared prefix tier"): one router-owned, refcount-deduped,
    byte-budgeted KV tier vs per-replica spill at EQUAL device count
    and EQUAL total spill bytes, on an affinity-blind shared-prefix
    trace built to expose what private tiers cannot hold — an ODD
    number of rotating shared prefixes (odd so paired placement can't
    accidentally partition them by replica parity: BOTH replicas see
    EVERY prefix, the affinity-blind worst case) whose deduped
    working set fits the shared budget while the duplicated
    per-replica demand overflows each local LRU.

    Three phases: (1) per-replica baseline — 2 replicas with
    ``affinity_weight=0`` and the whole byte budget split into two
    local spill tiers, each smaller than the full prefix set it must
    hold privately, so steady state keeps missing; (2) the SAME trace
    on the shared arm — local tiers just big enough to land a seeded
    run, the rest of the budget as ``shared_prefix_bytes`` holding
    the DEDUPED set once — asserting the fleet-wide prefix hit rate
    ((hit+spilled-in blocks)/looked-up blocks, summed over replicas)
    BEATS the per-replica arm, steady-state TTFT p99 (scheduler
    ticks, cold warmup excluded) strictly improves, publishes/dedupe/
    hits all moved, and outputs are token-identical across arms (the
    tier is an optimization, never a token source; the trace is
    greedy so each prefix's generated suffix chain dedupes too —
    sampled/int8/spec coverage lives in tests/test_shared_prefix.py);
    (3) chaos — a replica is hard-killed mid-trace with the tier on:
    failover must finish every accepted request with
    ``num_lost_requests == 0`` (the shared tier holds no request
    state, only re-derivable KV bytes). ``vs_baseline`` is
    per-replica hit rate / shared hit rate (< 1 = the shared tier
    pays). ``fast=True`` is the tier-1 smoke shape."""
    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.observability import percentile
    from apex_tpu.serving import (EngineConfig, FleetConfig, FleetRouter,
                                  Request, SamplingParams)

    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    # a SMALL device pool: prefix blocks must be evicted into the
    # spill tiers for either arm to have anything to serve
    ekw = dict(max_batch=2, block_size=4, num_blocks=8,
               max_prefill_len=8, max_seq_len=32,
               enable_prefix_caching=True, snapshot_interval_ticks=2,
               max_waiting=64, seed=11)
    # one 4-token block of fp32 K+V under GPTConfig.tiny (n_embd=128):
    # 2 * 4 * 128 * 4 B — the unit both arms' byte budgets are set in
    blk = 4096
    npref = 7          # ODD (see docstring); 7-block (28-token) heads
    n_reqs = 28 if fast else 56   # 4 / 8 visits per prefix
    kill_pair = 4 if fast else 10
    # EQUAL total spill bytes. Each finished sequence is 8 blocks (28
    # prompt + 4 generated), so the deduped greedy working set is
    # 7 x 8 = 56 blocks. Shared arm: 8-block local tiers (a seeded
    # 7-block run must FIT the landing tier or the import evicts its
    # own head) + a 60-block shared tier holding the set once.
    # Per-replica arm: the same 76-block total split into two 38-block
    # local tiers — each replica needs all 56 blocks privately, so
    # its LRU cycles and steady state keeps missing.
    local_small, shared_bytes = 8 * blk, 60 * blk
    per_replica_local = (2 * local_small + shared_bytes) // 2
    model = GPTLMHeadModel(cfg)
    # FIXED seeds: the arm asserts a hit-rate ORDERING
    # between two fleets on one trace — the trace must be the same
    # every round or the assert flakes
    init_rng = np.random.RandomState(1712)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(init_rng.randint(0, cfg.vocab_size, (1, 8))))

    def make_trace():
        rng = np.random.RandomState(1713)
        prefixes = [list(rng.randint(0, cfg.vocab_size, 28))
                    for _ in range(npref)]

        def make(k):
            prompt = prefixes[k % npref]
            return lambda: Request(uid=f"q{k}", prompt=list(prompt),
                                   max_new_tokens=4,
                                   sampling=SamplingParams())

        return [make(k) for k in range(n_reqs)]

    def drive(router, trace, kill_pair_at=None, kill_idx=None):
        """Submit in PAIRS (backlog spreads a pair across replicas —
        load-only ties would otherwise pile onto slot 0), DRAINING
        between pairs: publishes need device churn to evict blocks
        into the tiers before the next placement probes them, and a
        drained queue keeps the placement-time shared-tier seed
        adjacent to its admission. Per-uid submit/first-token ticks
        via the stream feed."""
        submit, first, accepted = {}, {}, []
        t0 = time.perf_counter()
        tick = 0
        for i in range(0, len(trace), 2):
            if (kill_pair_at is not None and i // 2 == kill_pair_at
                    and router.replicas[kill_idx].alive):
                router.kill_replica(kill_idx)
            for k in (i, i + 1):
                if k < len(trace):
                    req = trace[k]()
                    if router.try_add(req):
                        submit[req.uid] = tick
                        accepted.append(req.uid)
            while router.has_work:
                router.step()
                for uid, tok, _last in router.pop_stream_events():
                    if tok >= 0 and uid not in first and uid in submit:
                        first[uid] = tick
                tick += 1
        wall = time.perf_counter() - t0
        ttft = {u: first[u] - submit[u] for u in first}
        return ttft, accepted, wall

    def fleet_hit_rate(router):
        """(prefix hits + spill/shared re-admissions) / lookups, in
        BLOCKS, summed over alive replicas — shared-tier seeds land
        in the chosen replica's local spill and re-admit through the
        same upload path, so ``spill_hits`` is the one re-admission
        unit both arms share."""
        hit = lookups = 0
        for rep in router.replicas:
            if rep.alive and rep.engine is not None:
                s = rep.engine.stats()
                hit += int(s["prefix_hit_blocks"]) + int(s["spill_hits"])
                lookups += int(s["prefix_lookup_blocks"])
        return hit / max(lookups, 1)

    def pct(xs, q):
        return percentile(xs, q) if xs else 0.0

    def steady(ttft):
        # the steady-state window: the trace's second half, every
        # prefix long since first-seen — cold compulsory misses
        # (identical in both arms) would otherwise drown the tail
        return [ttft[f"q{k}"] for k in range(n_reqs // 2, n_reqs)
                if f"q{k}" in ttft]

    # -- phase 1: per-replica baseline (whole budget split local) --
    trace = make_trace()
    perrep = FleetRouter(
        model, params,
        EngineConfig(spill_max_bytes=per_replica_local, **ekw),
        FleetConfig(num_replicas=2, affinity_weight=0.0))
    ttft_pr, acc_pr, wall_pr = drive(perrep, trace)
    pr_res = perrep.run(return_status=True)
    pr_stats = perrep.stats()
    rate_pr = fleet_hit_rate(perrep)
    assert not (set(acc_pr) - set(pr_res)), "per-replica arm lost requests"
    assert pr_stats["num_lost_requests"] == 0
    p99_pr = pct(steady(ttft_pr), 99)

    # -- phase 2: the same trace, shared tier at equal total bytes --
    shared = FleetRouter(
        model, params,
        EngineConfig(spill_max_bytes=local_small, **ekw),
        FleetConfig(num_replicas=2, affinity_weight=0.0,
                    shared_prefix_bytes=shared_bytes))
    ttft_sh, acc_sh, wall_sh = drive(shared, trace)
    sh_res = shared.run(return_status=True)
    sh_stats = shared.stats()
    rate_sh = fleet_hit_rate(shared)
    assert not (set(acc_sh) - set(sh_res)), "shared arm lost requests"
    assert sh_stats["num_lost_requests"] == 0
    assert sh_stats["num_shared_publishes"] >= 1, "nothing published"
    assert sh_stats["num_shared_dedupe"] >= 1, (
        "no dedupe: both replicas' evictions of one prefix should "
        "collide in the shared tier")
    assert sh_stats["shared_tier_hits"] >= 1, "no shared-tier hit"
    # the tier is an optimization, never a token source: both arms
    # produce the SAME tokens for every request
    assert set(pr_res) == set(sh_res)
    for uid in pr_res:
        assert list(pr_res[uid].tokens) == list(sh_res[uid].tokens), (
            f"{uid}: shared-tier tokens diverged from per-replica")
    p99_sh = pct(steady(ttft_sh), 99)
    # the headline ordering: ONE deduped copy reachable by every
    # replica beats N private copies that each overflow
    assert rate_sh > rate_pr, (
        f"shared-tier fleet hit rate {rate_sh:.3f} did not beat "
        f"per-replica {rate_pr:.3f} at equal total spill bytes")
    assert p99_sh < p99_pr, (
        f"steady-state TTFT p99 {p99_sh} ticks (shared) did not beat "
        f"per-replica {p99_pr}")

    # -- phase 3: a replica hard-killed mid-trace, tier on --
    chaos = FleetRouter(
        model, params,
        EngineConfig(spill_max_bytes=local_small, **ekw),
        FleetConfig(num_replicas=2, affinity_weight=0.0,
                    shared_prefix_bytes=shared_bytes, respawn=True))
    _, acc_kill, _ = drive(chaos, trace, kill_pair_at=kill_pair,
                           kill_idx=0)
    kill_res = chaos.run(return_status=True)
    kill_stats = chaos.stats()
    missing = set(acc_kill) - set(kill_res)
    assert not missing, f"lost accepted requests: {sorted(missing)}"
    assert kill_stats["num_lost_requests"] == 0
    assert kill_stats["num_failovers"] >= 1, "the kill never fired"
    for rep in chaos.replicas:
        if rep.alive and rep.engine is not None:
            rep.engine.check_allocator_integrity()

    print(f"# serving shared prefix: per-replica hit rate "
          f"{rate_pr:.3f} (steady p99 TTFT {p99_pr:.0f} ticks) | "
          f"shared {rate_sh:.3f} (steady p99 {p99_sh:.0f}), "
          f"{sh_stats['num_shared_publishes']} published / "
          f"{sh_stats['num_shared_dedupe']} deduped / "
          f"{sh_stats['shared_tier_hits']} hits / "
          f"{sh_stats['num_shared_evictions']} evictions, tier "
          f"{sh_stats['shared_tier_blocks']} blocks "
          f"{sh_stats['shared_tier_bytes']} B | kill: failovers "
          f"{kill_stats['num_failovers']}, lost "
          f"{kill_stats['num_lost_requests']}", file=sys.stderr)
    return {
        "metric": "serving_tiny_shared_prefix_fleet_hit_rate",
        "value": round(float(rate_sh), 4),
        "unit": "hit_fraction",
        # the dedupe win: per-replica hit rate over shared hit rate
        # at equal total spill bytes (< 1 = the shared tier pays)
        "vs_baseline": round(float(rate_pr) / max(float(rate_sh),
                                                  1e-9), 4),
        "per_replica_hit_rate": round(float(rate_pr), 4),
        "shared_steady_ttft_p99_ticks": round(float(p99_sh), 2),
        "per_replica_steady_ttft_p99_ticks": round(float(p99_pr), 2),
        "total_spill_bytes_per_arm": 2 * local_small + shared_bytes,
        "num_offered": len(trace),
        "num_accepted_shared": len(acc_sh),
        "num_shared_publishes": int(sh_stats["num_shared_publishes"]),
        "num_shared_dedupe": int(sh_stats["num_shared_dedupe"]),
        "shared_tier_hits": int(sh_stats["shared_tier_hits"]),
        "num_shared_evictions": int(sh_stats["num_shared_evictions"]),
        "shared_tier_blocks": int(sh_stats["shared_tier_blocks"]),
        "shared_tier_bytes": int(sh_stats["shared_tier_bytes"]),
        "tokens_identical_across_arms": True,
        "kill_num_failovers": int(kill_stats["num_failovers"]),
        "kill_num_lost_requests": int(kill_stats["num_lost_requests"]),
        "zero_lost": True,
        "status_counts": {
            s: sum(r.status == s for r in sh_res.values())
            for s in {r.status for r in sh_res.values()}},
        "allocator_integrity_ok": True,
    }


def bench_obs_pipeline(fast=False):
    """Observability pipeline certification (docs/observability.md):
    drive a small engine with the full observer attached (tracer +
    flight recorder + metrics), write the dump, and run
    tools/trace_summary.py over it end to end — so the post-mortem
    tooling a dead round depends on is proven by every smoke run, not
    first exercised at the incident. Also re-certifies the
    zero-perturbation contract on this workload: the observed engine's
    outputs must be bit-identical to an unobserved twin's. Value =
    requests summarized; the section FAILS if the dump does not
    round-trip, the summary misses a request, or bit-identity breaks."""
    import importlib.util
    import os as _os
    import tempfile

    from apex_tpu.models import GPTConfig, GPTLMHeadModel
    from apex_tpu.observability import Observability
    from apex_tpu.serving import (EngineConfig, InferenceEngine, Request,
                                  SamplingParams)

    cfg = GPTConfig.tiny(dropout=0.0, remat=False)
    model = GPTLMHeadModel(cfg)
    rng = np.random.RandomState(_SEED + 7)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 8))))
    # a pool tight enough to preempt, so the trace exercises the
    # requeue/resume path too
    ekw = dict(max_batch=3, block_size=8, num_blocks=6,
               max_prefill_len=8, max_seq_len=32, seed=3)
    n_req = 3 if fast else 5
    reqs = [Request(uid=f"o{i}",
                    prompt=list(rng.randint(0, cfg.vocab_size, 6 + i)),
                    max_new_tokens=12,
                    sampling=(SamplingParams(temperature=1.0, top_k=16)
                              if i % 2 else SamplingParams()))
            for i in range(n_req)]

    def serve(obs):
        # request objects are reusable across engines: add_request
        # starts a fresh lifecycle (resets the engine-owned status)
        eng = InferenceEngine(model, params, EngineConfig(**ekw),
                              obs=obs)
        for r in reqs:
            eng.add_request(r)
        return eng.run(return_status=True)

    t0 = time.perf_counter()
    plain = serve(None)
    obs = Observability()
    observed = serve(obs)
    identical = ({u: (tuple(r.tokens), r.status)
                  for u, r in plain.items()}
                 == {u: (tuple(r.tokens), r.status)
                     for u, r in observed.items()})
    if not identical:
        raise AssertionError(
            "observability perturbed engine output (tracing on != off)")

    with tempfile.TemporaryDirectory() as td:
        dump_path = obs.dump_to(_os.path.join(td, "dump.json"))
        spec = importlib.util.spec_from_file_location(
            "_trace_summary",
            _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                          "tools", "trace_summary.py"))
        ts = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ts)
        report = ts.summarize_file(dump_path)
    dt = time.perf_counter() - t0
    missing = [r.uid for r in reqs if f"{r.uid}:" not in report]
    if missing:
        raise AssertionError(
            f"trace summary missed requests {missing}:\n{report}")
    deep = obs.deep_stats()
    print("# obs pipeline: " + report.splitlines()[1]
          + f" | bit-identical {identical}", file=sys.stderr)
    return {
        "metric": "obs_pipeline_smoke_requests_summarized",
        "value": n_req,
        "unit": "requests",
        "vs_baseline": 1.0,
        "bit_identical_with_observer": bool(identical),
        "trace_events": int(deep["trace_events"]),
        "recorder_events": int(deep["recorder_events"]),
        "ttft_observed": int(deep["metrics"]["serving_ttft_s"]["count"]),
        "summary_lines": len(report.splitlines()),
        "wall_s": round(dt, 3),
    }


def main():
    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    if "--smoke" in sys.argv:
        # tier-1 guard mode (tests/test_train_step.py): every section in
        # its fastest shape, one JSON line each, rc != 0 if ANY section
        # dies — so a change that would blank a future bench round
        # fails CI instead of surfacing in a lost perf round.
        for name, fn in (
            ("bench_layer_norm", lambda: bench_layer_norm(fast=True)),
            ("bench_fused_lamb", lambda: bench_fused_lamb(fast=True)),
            ("bench_ddp_scaling", bench_ddp_scaling),
            ("bench_serving", lambda: bench_serving(fast=True)),
            ("bench_serving_multistep",
             lambda: bench_serving_multistep(fast=True)),
            ("bench_serving_speculative",
             lambda: bench_serving_speculative(fast=True)),
            ("bench_serving_overload",
             lambda: bench_serving_overload(fast=True)),
            ("bench_serving_multitenant",
             lambda: bench_serving_multitenant(fast=True)),
            ("bench_serving_kv_memory",
             lambda: bench_serving_kv_memory(fast=True)),
            ("bench_weight_quant",
             lambda: bench_weight_quant(fast=True)),
            ("bench_serving_fleet",
             lambda: bench_serving_fleet(fast=True)),
            ("bench_serving_integrity",
             lambda: bench_serving_integrity(fast=True)),
            ("bench_serving_mesh",
             lambda: bench_serving_mesh(fast=True)),
            ("bench_serving_process",
             lambda: bench_serving_process(fast=True)),
            ("bench_serving_disagg",
             lambda: bench_serving_disagg(fast=True)),
            ("bench_serving_shared_prefix",
             lambda: bench_serving_shared_prefix(fast=True)),
            ("bench_train_step", lambda: bench_train_step(fast=True)),
            ("bench_train_sharded",
             lambda: bench_train_sharded(fast=True)),
            ("bench_obs_pipeline", lambda: bench_obs_pipeline(fast=True)),
        ):
            _run_section(name, fn)
            _reset()
        return
    # Headline: the BASELINE seq-512-class pretraining shape. With the
    # logsumexp MLM loss, B=16 WITHOUT per-layer remat fits the 16 GB
    # chip and beats every remat'd batch (no recompute tax). Round-4
    # re-sweep (marginal timing, same session): B=20 no-remat now TIES
    # B=16 (107.7 vs 105.4 samples/s — round 3 had it 7% behind), and
    # the gathered MLM tail frees enough activation memory that B=24
    # and B=32 now FIT no-remat — but run SLOWER per sample (99.9 /
    # 101.9 samples/s). B=16 stays the recorded peak. The fp32
    # baseline keeps remat (its fp32 activations would not fit
    # otherwise).
    batch, seq = (16, 512) if on_tpu else (2, 32)
    t_headline = time.perf_counter()
    try:
        dt_opt, dt_base, mfu = _measure(batch, seq, iters=8,
                                        remat=not on_tpu)
    except Exception as e:
        # the record of the death IS the artifact here: the re-raise
        # ends the run, so write the section line first
        _emit_section_record("headline", "failed",
                             time.perf_counter() - t_headline,
                             error=f"{type(e).__name__}: {e}")
        raise
    if on_tpu and "--all-shapes" in sys.argv:
        # secondary shape for comparison with earlier rounds' S=128 runs
        # (off by default: each extra config costs a slow fresh compile
        # and the driver runs this file under a time budget)
        _measure(64, 128, iters=6, with_baseline=False)

    result = {
        "metric": ("bert_large_pretrain_s512_samples_per_sec_per_chip"
                   if on_tpu else "bert_tiny_smoke_samples_per_sec"),
        "value": round(batch / dt_opt, 3),
        "unit": "samples/sec",
        "vs_baseline": round(dt_base / dt_opt, 3),
    }
    _print_record(result)
    _emit_section_record("headline", "ok",
                         time.perf_counter() - t_headline)
    # BASELINE configs[1]-[3] + the serving section (round 6) + the
    # long-context attention record (S=4096 on TPU by default; add
    # S=2048 with --long-context)
    secondary = [bench_layer_norm, bench_fused_lamb, bench_ddp_scaling,
                 bench_serving, bench_serving_multistep,
                 bench_serving_speculative, bench_serving_overload,
                 bench_serving_multitenant, bench_serving_kv_memory,
                 bench_weight_quant,
                 bench_serving_fleet, bench_serving_integrity,
                 bench_serving_mesh, bench_serving_process,
                 bench_serving_disagg, bench_serving_shared_prefix,
                 bench_train_step, bench_train_sharded,
                 bench_obs_pipeline]
    if on_tpu:
        secondary.append(bench_scaled_masked_softmax)
        secondary.append(bench_long_context)

        def bench_long_context_s8192():
            # S=8192 row (round 5): the composed baseline's (1,16,S,S)
            # fp32 score tensor is ~4 GB here — the shape where the
            # flash kernel's O(S*D) memory stops being a luxury
            return bench_long_context(seq=8192)
        secondary.append(bench_long_context_s8192)
        if "--long-context" in sys.argv:
            def bench_long_context_s2048():
                return bench_long_context(seq=2048)
            secondary.append(bench_long_context_s2048)
    _reset()
    for bench_fn in secondary:
        _run_section(bench_fn.__name__, bench_fn)
        _reset()
    # the round-13 comparer, finally closing its own loop: diff THIS
    # run against the newest recorded round (report only, stderr)
    _print_bench_diff_report()


if __name__ == "__main__":
    main()
