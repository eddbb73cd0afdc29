"""The whole train step of ``kanana_2_30b_a3b.lm8192`` (the ``deepseek_v3``
family, ``tests/test_deepseek_v3.py``) compiled ONCE for a described v5e:
what it holds live, and what it runs twice. A file of its own, beside
``tests/test_tpu_compile.py`` (whose described chip and readers it uses),
so that the two compile-heavy files run on two workers."""

import pytest

from apex_tpu import profiler
from benchmark.reference import deepseek_v3 as reference
# the described chip of the compile-only tests, and their readers
from test_tpu_compile import (_cell_step, _hbm_bytes, _mosaic_grid,  # noqa
                              _steer_kernels, one_chip, topo)

CELL = "kanana_2_30b_a3b.lm8192"

# what the step may hold live at 2 x 8,192 tokens a chip with every layer's
# matmul outputs, flash residuals, routing and expert rows kept (13.98 GiB
# by this compile; PERF.md section 4)
LIVE_BYTES = int(14.5 * 2 ** 30)


def test_the_step_fits_and_runs_no_flash_call_and_no_grouped_matmul_twice(
        one_chip):
    """The whole train step of ``kanana_2_30b_a3b.lm8192`` (five layers at
    the published widths, amp O2 + FusedAdam through ``build_train_step``,
    as the cell builds it) compiled from shapes for one described chip: a
    layer makes ONE causal latent-attention flash call a pass
    (``flash_mla_fwd`` once, ``dq``, ``dkv``: the layer keeps ``o`` and
    ``lse``) on the grid (2 rows, 32 heads, 16 x 16 tiles), a sparse layer
    six grouped-matmul calls; none of them, no sort and no matmul of a
    layer on a recomputed path (the head's, recomputed a row at a time by
    design, is the one); under the chip's memory."""
    with pytest.MonkeyPatch.context() as patch:
        _steer_kernels(patch)
        _, _, config, built, paths, live = _cell_step(CELL, one_chip)
    assert built.n_params == config["n_params"] == 424_961_024
    called = r'custom_call_target="tpu_custom_call"'
    kernels = paths(called)
    bodies = paths(called, want=r'\\?"body\\?":\s*\\?"([A-Za-z0-9+/=]+)')
    assert len(bodies) == len(kernels)
    kinds = reference.kinds(config)
    for i, (_, ffn) in enumerate(kinds):
        flash = [(p, b) for p, b in zip(kernels, bodies)
                 if f"/layers_{i}/self_attn/" in p and "/flash_" in p]
        assert all(f"/{profiler.MLA_CORE}/" in p for p, _ in flash)
        assert sorted(p.rsplit("/", 2)[-2] for p, _ in flash) == [
            "flash_mla_bwd_dkv", "flash_mla_bwd_dq", "flash_mla_fwd"], flash
        assert [_mosaic_grid(b) for _, b in flash] == [
            ((2, 32, 16, 16), 0)] * 3
        mine = [p for p in kernels if f"/layers_{i}/mlp/" in p]
        assert len(mine) == (6 if ffn == "moe" else 0), mine
        assert sum("jit(tgmm)" in p for p in mine) == len(mine) // 3
    assert not any("rematted_computation" in p for p in kernels)
    # the rest: the RMSNorm backward of three norms a layer and the last
    norms = [p for p in kernels if "layer_norm_bwd" in p]
    assert len(norms) == 3 * len(kinds) + 1
    sparse = sum(ffn == "moe" for _, ffn in kinds)
    assert len(kernels) == 3 * len(kinds) + 6 * sparse + len(norms)
    sorts = paths(r" sort\(")
    assert not [p for p in sorts if "rematted_computation" in p], sorts
    dots = [p for p in paths(r"= \S+ (convolution|dot)\(")
            if "rematted_computation" in p]
    assert all(f"/{profiler.LM_HEAD}/" in p for p in dots), dots
    print(f"{CELL}: tpu_custom_call x{len(kernels)}, live "
          f"{live / 2 ** 30:.2f} GiB")
    assert live <= LIVE_BYTES < 15 * 2 ** 30 < _hbm_bytes()
