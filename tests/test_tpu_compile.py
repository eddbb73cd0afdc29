"""Compile-only checks against a DESCRIBED TPU v5e (no chip attached).

The TPU compiler is installed in the CPU sandbox and compiles for a
topology that is described, not attached. These tests hand it the
training path's Pallas kernels at BERT-large widths (B=16, S=512,
hidden 1024, 16 heads x 64) and assert each one compiles to a Mosaic
``tpu_custom_call`` — what interpret mode can never show: fast-memory
limits, tile alignment, head-group blocking, and whether a kernel can
sit inside ``shard_map`` over a four-chip mesh.

Nothing runs, so nothing here is a result or a time. The kernels pick
``interpret`` from ``jax.default_backend()`` at trace time (the CPU
here), so the ``compiled_kernels`` fixture steers ``_interpret`` of each
kernel module to False for the duration of a test — in the test, not
through an option of the program.

All of it lives in ONE file, and the topology is described inside a
module-scoped fixture: only the xdist worker that is handed this file
loads libtpu (one process at a time may), every worker collects the
same tests, and a topology that cannot be described skips instead of
failing collection.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

B, S, NH, D = 16, 512, 16, 64
HID = NH * D
ROWS = B * S


def _hbm_bytes():
    from apex_tpu.utils.chip_peaks import chip_peaks

    return chip_peaks("TPU v5 lite").hbm_bytes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described-device executable is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / lock held by another process
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


def _steer_kernels(patch):
    """Trace the Pallas kernels as the chip would: ``interpret=False``."""
    import importlib

    # by module path: ``apex_tpu.ops`` re-exports functions under the
    # same names as its submodules
    for name in ("dropout", "flash_attention", "layer_norm", "softmax"):
        mod = importlib.import_module(f"apex_tpu.ops.{name}")
        patch.setattr(mod, "_interpret", lambda: False)


@pytest.fixture
def compiled_kernels(monkeypatch):
    _steer_kernels(monkeypatch)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    return compiled, compiled.as_text()


def _n_kernels(text):
    return text.count("tpu_custom_call")


# -- each kernel of the BERT-large train path, forward + backward ----------


def _ln_case(sh):
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine

    def f(x, w, b):
        return jax.value_and_grad(
            lambda x, w, b: fused_layer_norm_affine(x, w, b, 1e-12)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(x, w, b)

    # one kernel: under autodiff the forward is XLA-fused by design
    # (ops/layer_norm.py ``_ln_fwd_mode``), the backward is Pallas
    return f, (_spec((ROWS, HID), jnp.bfloat16, sh),
               _spec((HID,), jnp.float32, sh),
               _spec((HID,), jnp.float32, sh)), 1


def _flash_case(sh):
    from apex_tpu.ops.flash_attention import flash_attention

    def f(q, k, v, mask, seed):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(
                q, k, v, mask, False, D ** -0.5, 0.1, seed)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    qkv = _spec((B, NH, S, D), jnp.bfloat16, sh)
    return f, (qkv, qkv, qkv, _spec((B, S), jnp.bool_, sh),
               _spec((), jnp.int32, sh)), 2


def _flash_causal_1024_case(sh):
    """GPT-2-medium's attention call (``gpt2_medium.lm1024``): causal,
    no key mask, past one tile — forward + the split dq / dkv backward,
    each with its dead tiles skipped and their DMA clamped away."""
    from apex_tpu.ops.flash_attention import flash_attention

    def f(q, k, v, seed):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(
                q, k, v, None, True, D ** -0.5, 0.1, seed)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    qkv = _spec((8, NH, 1024, D), jnp.bfloat16, sh)
    return f, (qkv, qkv, qkv, _spec((), jnp.int32, sh)), 3


def _flash_bsh_case(sh):
    from apex_tpu.ops.flash_attention import flash_attention_bsh

    def f(q, k, v, mask, seed):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention_bsh(
                q, k, v, mask, NH, False, D ** -0.5, 0.1, seed)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    qkv = _spec((B, S, HID), jnp.bfloat16, sh)
    return f, (qkv, qkv, qkv, _spec((B, S), jnp.bool_, sh),
               _spec((), jnp.int32, sh)), 2


def _keep_mask_case(sh):
    from apex_tpu.ops.flash_attention import flash_dropout_keep_mask

    def f(seed):
        return flash_dropout_keep_mask(B, NH, S, S, 0.1, seed)

    return f, (_spec((), jnp.int32, sh),), 1


def _softmax_case(sh):
    from apex_tpu.ops.softmax import scaled_masked_softmax

    def f(x, mask):
        return jax.value_and_grad(
            lambda x: scaled_masked_softmax(x, mask, D ** -0.5)
            .astype(jnp.float32).sum())(x)

    return f, (_spec((B, NH, S, S), jnp.bfloat16, sh),
               _spec((B, 1, 1, S), jnp.bool_, sh)), 2


def _dropout_case(sh):
    from apex_tpu.ops.dropout import fused_dropout

    def f(x, seed):
        return jax.value_and_grad(
            lambda x: fused_dropout(x, 0.1, seed)
            .astype(jnp.float32).sum())(x)

    return f, (_spec((ROWS, HID), jnp.bfloat16, sh),
               _spec((), jnp.int32, sh)), 2


def _flash_gqa_8192_case(sh):
    """The ``nemotron_h`` attention call (``nemotron_twotower_30b_a3b.
    lm8192``): 32 query heads on 2 key/value heads of 128, causal, 16 x 16
    tiles; k and v go in at their own head count (no repeated copy), dk
    and dv come out per query head in float32."""
    from apex_tpu.ops.flash_attention import flash_attention

    def f(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v, None, True, 128 ** -0.5)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return f, (_spec((2, 32, 8192, 128), jnp.bfloat16, sh),
               _spec((2, 2, 8192, 128), jnp.bfloat16, sh),
               _spec((2, 2, 8192, 128), jnp.bfloat16, sh)), 3


def _grouped_matmul_case(sh):
    """The held experts of one expert layer of the same cell: 8 experts of
    2688 x 1856 over the 98,304 assignment slots of 16,384 tokens; up,
    squared ReLU, down, forward and backward (gmm x 4 + tgmm x 2)."""
    from apex_tpu.transformer.moe import grouped_matmul, squared_relu

    def f(rows, up, down, sizes):
        def loss(rows, up, down):
            h = squared_relu(grouped_matmul(rows, up, sizes))
            return grouped_matmul(h, down, sizes).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(rows, up, down)

    return f, (_spec((98304, 2688), jnp.bfloat16, sh),
               _spec((8, 2688, 1856), jnp.bfloat16, sh),
               _spec((8, 1856, 2688), jnp.bfloat16, sh),
               _spec((8,), jnp.int32, sh)), 5


def _ssd_scan_case(sh):
    """The Mamba-2 scan of the same cell (64 heads of 64, 8 groups, state
    128, chunks of 128, 2 x 8192 tokens): XLA einsums, no Pallas kernel -
    the case holds its temporaries under the chip's memory."""
    from apex_tpu.ops.ssd_scan import ssd_scan

    def f(x, dt, A, B, C, D):
        return jax.value_and_grad(
            lambda x, dt, B, C: ssd_scan(x, dt, A, B, C, D, chunk=128)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(x, dt, B, C)

    bc = _spec((2, 8192, 8, 128), jnp.bfloat16, sh)
    return f, (_spec((2, 8192, 64, 64), jnp.bfloat16, sh),
               _spec((2, 8192, 64), jnp.float32, sh),
               _spec((64,), jnp.float32, sh), bc, bc,
               _spec((64,), jnp.float32, sh)), 0


def _flash_gqa64_8192_case(sh):
    """The ``lfm2`` attention call (``lfm2_24b_a2b.lm8192``): 32 query
    heads on 8 key/value heads of 64, causal, 2 x 8192 tokens."""
    from apex_tpu.ops.flash_attention import flash_attention

    def f(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: flash_attention(q, k, v, None, True, 64 ** -0.5)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return f, (_spec((2, 32, 8192, 64), jnp.bfloat16, sh),
               _spec((2, 8, 8192, 64), jnp.bfloat16, sh),
               _spec((2, 8, 8192, 64), jnp.bfloat16, sh)), 3


def _gated_grouped_matmul_case(sh):
    """The held gated experts of one expert layer of the same cell: 8
    experts of 2048 x (2 x 1536) and 1536 x 2048 over the 65,536 slots of
    16,384 tokens; gate and up in ONE grouped matmul, SiLU times the gate,
    down; forward and backward (gmm x 4 + tgmm x 2)."""
    from apex_tpu.transformer.moe import grouped_matmul

    def f(rows, gate_up, down, sizes):
        def loss(rows, gate_up, down):
            h = grouped_matmul(rows, gate_up, sizes).astype(jnp.float32)
            a = (jax.nn.silu(h[:, :1536]) * h[:, 1536:]).astype(rows.dtype)
            return grouped_matmul(a, down, sizes).astype(jnp.float32).sum()

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(rows, gate_up,
                                                           down)

    return f, (_spec((65536, 2048), jnp.bfloat16, sh),
               _spec((8, 2048, 3072), jnp.bfloat16, sh),
               _spec((8, 1536, 2048), jnp.bfloat16, sh),
               _spec((8,), jnp.int32, sh)), 5


def _short_conv_case(sh):
    """The gated short convolution of the same cell (width 2048, 3 taps, 2
    x 8192 tokens): ``short_conv_fwd`` and ``short_conv_bwd``, picked by
    the platform the program is lowered for (nothing to steer)."""
    from apex_tpu.ops.short_conv import gated_short_conv

    def f(b, c, x, taps):
        return jax.value_and_grad(
            lambda b, c, x, taps: gated_short_conv(b, c, x, taps)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))(b, c, x, taps)

    part = _spec((2, 8192, 2048), jnp.bfloat16, sh)
    return f, (part, part, part, _spec((3, 2048), jnp.float32, sh)), 2


_CASES = {
    "flash_attention_gqa64_8192": _flash_gqa64_8192_case,
    "gated_grouped_matmul": _gated_grouped_matmul_case,
    "gated_short_conv": _short_conv_case,
    "layer_norm": _ln_case,
    "flash_attention": _flash_case,
    "flash_attention_causal_1024": _flash_causal_1024_case,
    "flash_attention_bsh": _flash_bsh_case,
    "flash_dropout_keep_mask": _keep_mask_case,
    "scaled_masked_softmax": _softmax_case,
    "fused_dropout": _dropout_case,
    "flash_attention_gqa_8192": _flash_gqa_8192_case,
    "grouped_matmul": _grouped_matmul_case,
    "ssd_scan": _ssd_scan_case,
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, compiled_kernels):
    fn, specs, min_kernels = _CASES[name](one_chip)
    compiled, text = _compile(fn, *specs)
    assert _n_kernels(text) >= min_kernels, (
        f"{name}: expected >= {min_kernels} Mosaic kernels (fwd + bwd) "
        f"in the v5e program, found {_n_kernels(text)}")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < _hbm_bytes()


@pytest.mark.parametrize("name", ["layer_norm", "flash_attention_bsh",
                                  "fused_dropout"])
def test_kernel_compiles_inside_shard_map_on_four_chips(
        name, mesh4, compiled_kernels):
    """The data-parallel step runs these kernels INSIDE ``shard_map``;
    on the CPU that context always took the jnp references, so this is
    the only place the compiled kernels meet the partitioner."""
    from apex_tpu.utils.collectives import compat_shard_map

    fn, specs, min_kernels = _CASES[name](None)
    n_grads = len(jax.tree.leaves(jax.eval_shape(fn, *specs))) - 1
    rep, split = NamedSharding(mesh4, P()), NamedSharding(mesh4, P("data"))
    # activations (leading dim = batch or rows) shard over the mesh,
    # weights and seeds replicate
    in_specs = tuple(P("data") if len(s.shape) >= 2 else P() for s in specs)
    specs = tuple(
        _spec(s.shape, s.dtype, split if len(s.shape) >= 2 else rep)
        for s in specs)

    def body(*args):
        # (value, grads wrt the leading args); the value and a
        # replicated weight's grad are device-local under
        # check_vma=False, so they are summed here
        return tuple(g if g.ndim >= 2 else jax.lax.psum(g, "data")
                     for g in jax.tree.leaves(fn(*args)))

    sm = compat_shard_map(body, mesh4, in_specs=in_specs,
                          out_specs=(P(),) + in_specs[:n_grads])
    _, text = _compile(sm, *specs)
    assert _n_kernels(text) >= min_kernels
    if name == "layer_norm":
        assert "all-reduce" in text  # the dw/db psum over the four chips


# -- the whole step: does BERT-large B=16 S=512 fit one chip? --------------


# what the class default of the block stacks ("selective" recomputation:
# the matmul and flash outputs kept) may hold live at 8,192 tokens a chip
SELECTIVE_LIVE_BYTES = 12 * 2 ** 30


def _compile_bert_large_step(remat_policy, **trainer_options):
    """(trainer, memory analysis, HLO text) of ``chip_smoke.py``'s
    BERT-large step at S=512 under a recomputation policy."""
    import chip_smoke

    cfg = dataclasses.replace(chip_smoke.bert_large_config(),
                              remat_policy=remat_policy)
    run = chip_smoke.build_bert_trainer(cfg, seq=S, **trainer_options)
    compiled = run.step.lower(run.state, run.batch).compile()
    return run, compiled.memory_analysis(), compiled.as_text()


@pytest.mark.slow
@pytest.mark.parametrize("donate", [True, False])
def test_bert_large_train_step_fits_one_v5e(donate, one_chip,
                                            compiled_kernels):
    """The headline step of ``chip_smoke.py`` (24 x 1024, S=512, B=16,
    amp O2 + FusedLAMB through ``build_train_step``) compiled from
    shapes for one described chip: its kernels are compiled in and,
    with donation, its memory fits 16 GB - under 12 GiB with the class
    default's kept activations, and with 24 kernels fewer than under
    ``remat_policy="full"`` (no ``flash_fwd`` runs twice). Slow (minutes):
    run by hand before a chip call, not in tier 1."""
    import chip_smoke

    options = dict(batch=B, donate=donate, abstract_on=one_chip)
    _, mem, text = _compile_bert_large_step("selective", **options)
    assert _n_kernels(text) > 0
    live = chip_smoke.live_bytes(mem)
    print(f"donate={donate}: args {mem.argument_size_in_bytes / 2**30:.2f} "
          f"GiB, out {mem.output_size_in_bytes / 2**30:.2f}, alias "
          f"{mem.alias_size_in_bytes / 2**30:.2f}, temp "
          f"{mem.temp_size_in_bytes / 2**30:.2f}, live {live / 2**30:.2f}; "
          f"tpu_custom_call x{_n_kernels(text)}")
    if donate:
        assert mem.alias_size_in_bytes > 0
        assert live < SELECTIVE_LIVE_BYTES < _hbm_bytes()
        _, full_mem, full_text = _compile_bert_large_step("full", **options)
        print(f"remat_policy='full': live "
              f"{chip_smoke.live_bytes(full_mem) / 2**30:.2f} GiB, "
              f"tpu_custom_call x{_n_kernels(full_text)}")
        assert _n_kernels(full_text) - _n_kernels(text) == 24
        assert chip_smoke.live_bytes(full_mem) < live


@pytest.mark.slow
def test_bert_large_ddp_step_compiles_for_four_v5e(mesh4, compiled_kernels):
    """The four-chip cell's step (``bert_large.phase2_ddp4``: BERT-large
    through ``build_train_step(ddp=..., mesh=...)`` at 16 rows a chip),
    compiled for the four described chips. The kernels sit inside
    ``shard_map``, one flat all-reduce carries the fp32 gradient bytes,
    and each chip's share stays under 12 GiB with the class default's
    kept activations, with 24 kernels fewer than under
    ``remat_policy="full"``."""
    import chip_smoke
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.utils.hlo_audit import collective_stats

    options = dict(batch=4 * B,
                   ddp=DistributedDataParallel("data", delay_allreduce=True),
                   mesh=mesh4, abstract_on=NamedSharding(mesh4, P()))
    run, mem, text = _compile_bert_large_step("selective", **options)
    stats = collective_stats(text)
    live = chip_smoke.live_bytes(mem)
    print(f"ddp x4: all-reduce {stats['all-reduce']}, tpu_custom_call "
          f"x{_n_kernels(text)}, live per chip {live / 2**30:.2f} GiB")
    assert _n_kernels(text) > 0
    assert stats["all-reduce"]["bytes"] >= 4 * run.n_params
    assert live < SELECTIVE_LIVE_BYTES < _hbm_bytes()
    _, full_mem, full_text = _compile_bert_large_step("full", **options)
    print(f"remat_policy='full': live per chip "
          f"{chip_smoke.live_bytes(full_mem) / 2**30:.2f} GiB, "
          f"tpu_custom_call x{_n_kernels(full_text)}")
    assert _n_kernels(full_text) - _n_kernels(text) == 24
    assert collective_stats(full_text)["all-reduce"] == stats["all-reduce"]


# -- a cell's whole step, as its builder builds it ---------------------------

def _cell_step(cell, one_chip):
    """The train step of a benchmark cell compiled from shapes for one
    described chip (call with the kernels steered): ``(builder, reference,
    config, built, paths, live bytes)``, where ``paths(regex)`` gives the
    ``op_name`` of every instruction whose line matches (or what another
    ``want`` captures there)."""
    import re

    import chip_smoke
    from benchmark.harness import runner
    from benchmark.harness.manifest import Manifest

    manifest = Manifest()
    config = manifest.config(manifest.cell(cell)["config"])
    traffic = manifest.traffic(cell)
    builder, reference = runner.family(config)
    built = builder.build(config, traffic, reference, seed=0,
                          key=runner.weights_key(0), abstract_on=one_chip)
    ids = np.zeros((traffic["rows_per_chip"], traffic["seq"]), np.int32)
    batch = jax.tree.map(
        lambda x: _spec(np.shape(x), np.asarray(x).dtype, one_chip),
        built.program_batch({"ids": ids, "seed": [1]}))
    compiled = built.step.lower(built.state, batch).compile()
    lines = compiled.as_text().splitlines()

    def paths(pattern, want=r'op_name="([^"]*)"'):
        return [m.group(1) for line in lines if re.search(pattern, line)
                for m in [re.search(want, line)] if m]

    return (builder, reference, config, built, paths,
            chip_smoke.live_bytes(compiled.memory_analysis()))


# -- the fourth cell's step: what each kind of block does twice -------------

# what the ``nemotron_h`` step may hold live at 2 x 8,192 tokens a chip with
# the expert blocks' routing and hidden rows, the Mamba blocks' ``in_proj``
# output and the attention block's q, k, v and flash residuals kept
# (13.07 GiB by this compile; 11.94 with the Mamba and attention blocks
# recomputed in full, PERF.md section 6, PR 35)
NEMOTRON_LIVE_BYTES = int(14.0 * 2 ** 30)
NEMOTRON_CELL = "nemotron_twotower_30b_a3b.lm8192"


@pytest.fixture(scope="module")
def nemotron_step(one_chip):
    """The whole train step of ``nemotron_twotower_30b_a3b.lm8192`` (seven
    blocks ``MEMEM*E`` at the published widths, amp O2 + FusedAdam through
    ``build_train_step``, as the cell builds it), compiled ONCE for the
    tests below: ``(paths, pattern, live bytes)``."""
    with pytest.MonkeyPatch.context() as patch:
        _steer_kernels(patch)
        builder, _, config, _, paths, live = _cell_step(NEMOTRON_CELL,
                                                        one_chip)
    return paths, builder.model_config(config).pattern, live


def test_nemotron_step_runs_no_grouped_matmul_and_no_sort_twice(
        nemotron_step):
    """An expert block keeps its routing and its hidden rows under
    recomputation: six grouped-matmul calls a layer (``gmm`` up and down
    forward; two ``gmm`` and two ``tgmm`` backward), none of them and no
    sort (``top_k``, the sort by expert and the ones that follow it) on a
    recomputed path; the attention block keeps flash's residuals, so no
    kernel at all runs on a recomputed path; inside the memory the kept
    tensors were promised."""
    paths, pattern, live = nemotron_step
    kernels = paths(r'custom_call_target="tpu_custom_call"')
    layers = [i for i, kind in enumerate(pattern) if kind == "E"]
    assert len(layers) == 3
    for i in layers:
        mine = [p for p in kernels if f"/layers_{i}/" in p]
        assert len(mine) == 6, mine
        assert sum("jit(tgmm)" in p for p in mine) == 2
        assert sum("jit(gmm)" in p for p in mine) == 4
        assert all("/moe_experts/" in p for p in mine)
    # the attention block's three flash calls: forward once, dq, dkv
    assert len(kernels) == 6 * len(layers) + 3
    flash = sorted(p.rsplit("/", 2)[-2] for p in kernels
                   if "/gqa_attention/" in p)
    assert flash == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], flash
    assert not [p for p in kernels if "rematted_computation" in p]
    sorts = paths(r" sort\(")
    assert not [p for p in sorts if "rematted_computation" in p], sorts
    sorts = [p for p in sorts if "/experts/" in p]
    # top_k; the sort by expert, its inverse, the weights there and back
    assert len(sorts) == 5 * len(layers), sorts
    print(f"{NEMOTRON_CELL}: tpu_custom_call x{len(kernels)}, live "
          f"{live / 2 ** 30:.2f} GiB")
    assert live <= NEMOTRON_LIVE_BYTES < _hbm_bytes()


def test_nemotron_step_recomputes_no_projection_but_the_scan(nemotron_step):
    """A Mamba block keeps ``in_proj``'s output and the attention block its
    q, k and v (``remat_block(..., "selective")``): none of those matmuls
    lies on a recomputed path of the compiled step, where under a bare
    ``nn.remat`` each ran twice. The chunked scan DOES run again in every
    Mamba block: its einsums carry batch dimensions, so the policy keeps
    none of them and ``ssd_scan`` names nothing. That is what is left for
    the scan's own kernel (``ROADMAP.md`` S6): a PR that keeps or fuses
    its residuals sees this assertion go."""
    paths, pattern, _ = nemotron_step
    again = [p for p in paths(r" (convolution|dot)\(")
             if "rematted_computation" in p]
    mixers = [i for i, kind in enumerate(pattern) if kind in "M*"]
    assert len(mixers) == 4
    for i in mixers:
        mine = [p for p in again if f"/layers_{i}/" in p]
        for dense in ("/in_proj/", "/q/", "/k/", "/v/", "/out_proj/",
                      "/out/"):
            assert not [p for p in mine if dense in p], (i, dense, mine)
        scans = [p for p in mine if "/ssm_scan/" in p]
        assert bool(scans) == (pattern[i] == "M"), (i, mine)


# -- the fifth cell's step: gated experts, short convolutions, rotary GQA ----

# what the ``lfm2`` step may hold live at 2 x 8,192 tokens a chip with every
# layer's matmul outputs, flash residuals, routing and expert rows kept
# (11.64 GiB by this compile; PERF.md section 4, PR 34)
LFM2_LIVE_BYTES = int(12.5 * 2 ** 30)


def test_lfm2_step_runs_no_grouped_matmul_no_sort_and_no_flash_twice(
        one_chip, compiled_kernels):
    """The whole train step of ``lfm2_24b_a2b.lm8192`` (five layers at the
    published widths, amp O2 + FusedAdam through ``build_train_step``, as
    the cell builds it) compiled from shapes for one described chip. A
    gated expert layer keeps its routing and its ``(slots, 2F)`` rows: six
    grouped-matmul calls a layer (``gmm`` gate-up and down forward; two
    ``gmm`` and two ``tgmm`` backward), none of them, no sort and no flash
    call on a recomputed path; a conv mixer runs ``short_conv_fwd`` in the
    forward and the recomputed pass and ``short_conv_bwd`` once; well under
    the chip's memory."""
    cell = "lfm2_24b_a2b.lm8192"
    _, reference, config, built, paths, live = _cell_step(cell, one_chip)
    assert built.n_params == 469_285_248
    kernels = paths(r'custom_call_target="tpu_custom_call"')
    kinds = reference.kinds(config)
    experts = [i for i, pair in enumerate(kinds) if "moe" in pair]
    convs = [i for i, pair in enumerate(kinds) if "conv" in pair]
    assert experts == [1, 2, 3, 4] and convs == [0, 2, 3, 4]
    for i in experts:
        mine = [p for p in kernels if f"/layers_{i}/expert_ffn/" in p]
        assert len(mine) == 6, mine
        assert sum("jit(tgmm)" in p for p in mine) == 2
        assert sum("jit(gmm)" in p for p in mine) == 4
        assert all("/moe_experts/" in p for p in mine)
        assert not any("rematted_computation" in p for p in mine)
    for i in convs:
        mine = [p for p in kernels if f"/layers_{i}/conv/" in p]
        assert all("/conv_gate/" in p for p in mine)
        assert sum("short_conv_fwd" in p for p in mine) == 2
        assert sum("short_conv_bwd" in p for p in mine) == 1
        assert len(mine) == 3
    flash = [p for p in kernels if "/gqa_attention/" in p]
    assert sorted(p.rsplit("/", 2)[-2] for p in flash) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert not any("rematted_computation" in p for p in flash)
    # the rest: the RMSNorm backward of two norms a layer and the last
    norms = [p for p in kernels if "layer_norm_bwd" in p]
    assert len(norms) == 2 * len(kinds) + 1
    assert len(kernels) == (6 * len(experts) + 3 * len(convs) + 3
                            + len(norms))
    sorts = paths(r" sort\(")
    assert not [p for p in sorts if "rematted_computation" in p], sorts
    sorts = [p for p in sorts if "/experts/" in p]
    # top_k; the sort by expert, its inverse, the weights there and back
    assert len(sorts) == 5 * len(experts), sorts
    print(f"{cell}: tpu_custom_call x{len(kernels)}, live "
          f"{live / 2 ** 30:.2f} GiB")
    assert live <= LFM2_LIVE_BYTES < 15 * 2 ** 30 < _hbm_bytes()


# -- the sixth cell's step: block diffusion over two copies of a row ----------

def _mosaic_text(body):
    """The Mosaic module of a compiled Pallas kernel as text, from the
    ``body`` of its custom call (base64 of the serialized module)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    tpu.register_dialect(context)
    context.allow_unregistered_dialects = True
    with context:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm()


def _mosaic_grid(body):
    """``(grid, scalar-prefetch operands)`` of a compiled Pallas kernel,
    from its body (whose function carries both as attributes)."""
    import re

    text = _mosaic_text(body)
    bounds = re.search(r"iteration_bounds = array<i64: ([0-9, ]+)>", text)
    prefetch = re.search(r"scalar_prefetch = (\d+)", text)
    return (tuple(int(n) for n in bounds.group(1).split(",")),
            int(prefetch.group(1)) if prefetch else 0)


# what the ``sdar`` step may hold live at 1 x 8,192 tokens read as 16,384
# positions with every layer's matmul outputs, flash residuals, routing and
# expert rows kept (12.16 GiB by this compile; PERF.md section 4, PR 36)
SDAR_LIVE_BYTES = int(13.0 * 2 ** 30)


def test_sdar_step_runs_no_flash_call_and_no_grouped_matmul_twice(
        one_chip, compiled_kernels):
    """The whole train step of ``sdar_30b_a3b_chat.bd8192`` (four layers at
    the published widths, the block-diffusion loss, amp O2 + FusedAdam
    through ``build_train_step``, as the cell builds it) compiled from
    shapes for one described chip: every layer makes ONE block-masked
    flash call over the two copies (forward once, ``dq``, ``dkv``: the
    block keeps ``o`` and ``lse``) and six grouped-matmul calls, none of
    them and no sort on a recomputed path; each flash kernel's grid is (1
    row, 32 heads, the mask's live tiles: 288 of 1,024, and 152 of 512 in
    the last layer, whose queries are the noised copy alone) under four
    prefetched lists, the forward's tile step in four sub-blocks of 128
    query rows and ``dq``'s in two of 256; no causal flash kernel is in the
    step; under the chip's memory."""
    from apex_tpu.ops.flash_attention import BlockDiffusionMask, grid_steps

    cell = "sdar_30b_a3b_chat.bd8192"
    _, _, config, built, paths, live = _cell_step(cell, one_chip)
    assert built.n_params == 456_346_624
    called = r'custom_call_target="tpu_custom_call"'
    kernels = paths(called)
    bodies = paths(called, want=r'\\?"body\\?":\s*\\?"([A-Za-z0-9+/=]+)')
    assert len(bodies) == len(kernels)
    grids = {p: _mosaic_grid(body) for p, body in zip(kernels, bodies)
             if "/blockdiff_attention/" in p}
    matmuls = {p: _mosaic_text(body).count("tpu.matmul")
               for p, body in zip(kernels, bodies)
               if "/blockdiff_attention/" in p}
    layers = range(config["num_hidden_layers"])
    L, heads = 8192, config["num_attention_heads"]
    live_tiles = [grid_steps((1 + (i < layers[-1])) * L, 2 * L, 512, 512,
                             score_mask=BlockDiffusionMask(
                                 L, config["block_length"], i < layers[-1]))
                  for i in layers]
    assert live_tiles == [288, 288, 288, 152]
    for i in layers:
        mine = [p for p in kernels if f"/layers_{i}/expert_ffn/" in p]
        assert len(mine) == 6, mine
        assert sum("jit(tgmm)" in p for p in mine) == 2
        assert sum("jit(gmm)" in p for p in mine) == 4
        assert all("/moe_experts/" in p for p in mine)
        flash = [p for p in kernels if f"/layers_{i}/self_attn/" in p]
        assert all("/blockdiff_attention/" in p for p in flash)
        assert sorted(p.rsplit("/", 2)[-2] for p in flash) == [
            "flash_blockdiff_bwd_dkv", "flash_blockdiff_bwd_dq",
            "flash_blockdiff_fwd"], flash
        assert [grids[p] for p in flash] == [
            ((1, heads, live_tiles[i]), 4)] * 3, [grids[p] for p in flash]
        # the forward's tile step in four sub-blocks of 128 query rows, two
        # matmuls each; dq's in two of 256, three each; dkv's four whole
        assert sorted((p.rsplit("/", 2)[-2], matmuls[p]) for p in flash) == [
            ("flash_blockdiff_bwd_dkv", 4), ("flash_blockdiff_bwd_dq", 6),
            ("flash_blockdiff_fwd", 8)], flash
    assert not any("rematted_computation" in p for p in kernels)
    assert not [p for p in kernels
                if re.search(r"/flash_(fwd|bwd|bwd_dq|bwd_dkv)(/|$)", p)]
    # the rest: the RMSNorm backward of two norms a layer and the last
    norms = [p for p in kernels if "layer_norm_bwd" in p]
    assert len(norms) == 2 * len(layers) + 1
    assert len(kernels) == (6 + 3) * len(layers) + len(norms)
    sorts = paths(r" sort\(")
    assert not [p for p in sorts if "rematted_computation" in p], sorts
    sorts = [p for p in sorts if "/experts/" in p]
    assert len(sorts) == 5 * len(layers), sorts
    print(f"{cell}: tpu_custom_call x{len(kernels)}, live "
          f"{live / 2 ** 30:.2f} GiB")
    assert live <= SDAR_LIVE_BYTES < 15 * 2 ** 30 < _hbm_bytes()


# -- the seventh cell's step: window and global layers, gated experts ---------

# what the ``afmoe`` step may hold live at 2 x 8,192 tokens a chip with every
# layer's matmul outputs, flash residuals, routing and expert rows kept
# (14.63 GiB by this compile; PERF.md section 4)
TRINITY_LIVE_BYTES = int(15.0 * 2 ** 30)


def test_trinity_step_runs_no_flash_call_and_no_grouped_matmul_twice(
        one_chip, compiled_kernels):
    """The whole train step of ``trinity_mini.lm8192`` (five layers at the
    published widths, amp O2 + FusedAdam through ``build_train_step``, as
    the cell builds it) compiled from shapes for one described chip: a
    window layer makes ONE window-masked flash call a pass (forward once,
    ``dq``, ``dkv``: the layer keeps ``o`` and ``lse``), each on a grid of
    (2 rows, 32 heads, the band's 70 live tiles of 256) under four
    prefetched lists; the global layer makes the causal calls; a sparse
    layer six grouped-matmul calls; none of them and no sort on a
    recomputed path; under the chip's memory."""
    from apex_tpu.ops.flash_attention import SlidingWindowMask, grid_steps

    cell = "trinity_mini.lm8192"
    _, reference, config, built, paths, live = _cell_step(cell, one_chip)
    assert built.n_params == 504_147_712
    called = r'custom_call_target="tpu_custom_call"'
    kernels = paths(called)
    bodies = paths(called, want=r'\\?"body\\?":\s*\\?"([A-Za-z0-9+/=]+)')
    assert len(bodies) == len(kernels)
    grids = {p: _mosaic_grid(body) for p, body in zip(kernels, bodies)
             if "/window_attention/" in p}
    S, W, heads = 8192, config["sliding_window"], config["num_attention_heads"]
    live_tiles = grid_steps(S, S, 512, 512,
                            score_mask=SlidingWindowMask(S, W))
    assert live_tiles == 70
    kinds = reference.kinds(config)
    for i, (layer_type, (_, ffn)) in enumerate(zip(config["layer_types"],
                                                   kinds)):
        flash = [p for p in kernels if f"/layers_{i}/self_attn/" in p
                 and "/flash_" in p]
        names = sorted(p.rsplit("/", 2)[-2] for p in flash)
        if layer_type == "sliding_attention":
            assert all("/window_attention/" in p for p in flash)
            assert names == ["flash_window_bwd_dkv", "flash_window_bwd_dq",
                             "flash_window_fwd"], flash
            assert [grids[p] for p in flash] == [
                ((2, heads, live_tiles), 4)] * 3, [grids[p] for p in flash]
        else:
            assert all("/global_attention/" in p for p in flash)
            assert names == ["flash_bwd_dkv", "flash_bwd_dq",
                             "flash_fwd"], flash
        mine = [p for p in kernels if f"/layers_{i}/moe/" in p]
        assert len(mine) == (6 if ffn == "moe" else 0), mine
        assert sum("jit(tgmm)" in p for p in mine) == len(mine) // 3
        assert all("/moe_experts/" in p for p in mine)
    assert not any("rematted_computation" in p for p in kernels)
    # the rest: the RMSNorm backward of four norms a layer and the last
    norms = [p for p in kernels if "layer_norm_bwd" in p]
    assert len(norms) == 4 * len(kinds) + 1
    sparse = sum(ffn == "moe" for _, ffn in kinds)
    assert len(kernels) == 3 * len(kinds) + 6 * sparse + len(norms), [
        p for p in kernels if "/flash_" not in p and "gmm" not in p
        and "layer_norm_bwd" not in p]
    sorts = paths(r" sort\(")
    assert not [p for p in sorts if "rematted_computation" in p], sorts
    sorts = [p for p in sorts if "/experts/" in p]
    assert len(sorts) == 5 * sparse, sorts
    print(f"{cell}: tpu_custom_call x{len(kernels)}, live "
          f"{live / 2 ** 30:.2f} GiB")
    assert live <= TRINITY_LIVE_BYTES < 15.5 * 2 ** 30 < _hbm_bytes()
