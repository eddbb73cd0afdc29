"""Rehearsal of ``chip_smoke.py`` on the CPU at tiny shapes.

The script itself has no CPU mode (without a TPU it exits 1 and prints
no result). These tests drive its phase functions — the same code the
chip runs — on the virtual CPU devices, with the Pallas kernels in
interpret mode, so that a wrong path, argument or control flow is found
here and not on the chip. Nothing measured here is a device number.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _tiny():
    from apex_tpu.models import BertConfig

    return BertConfig.tiny(dtype=jnp.bfloat16, fused_kernels=True)


def test_refuses_without_a_tpu():
    """With JAX held to the CPU the script exits non-zero and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU here" in proc.stderr


def test_batch_is_seeded_and_shaped():
    cfg = _tiny()
    a = chip_smoke.make_batch(cfg, 8, 32, 4, accum=2, shards=4)
    b = chip_smoke.make_batch(cfg, 8, 32, 4, accum=2, shards=4)
    assert all((a[k] == b[k]).all() for k in a)
    assert a["ids"].shape == (2, 4, 32) and a["positions"].shape == (2, 4, 4)
    assert a["seed"].shape == (2, 4) and len(set(a["seed"].ravel())) == 8
    assert a["mlm_weights"].sum() > 0 and (a["attn"] == 0).any()
    # every counted position is a real (unpadded) masked token
    assert ((a["mlm_weights"] == 0) | (a["mlm_labels"] >= 0)).all()


def test_kernel_phase_rehearsal(capsys):
    chip_smoke.phase_kernels(B=2, NH=2, S=128, D=64)
    out = capsys.readouterr().out
    assert out.count("  ok: ") == 8 and "FAILED" not in out


def test_train_phase_rehearsal(capsys):
    chip_smoke.phase_train(_tiny(), batch=4, seq=32, n_pred=4,
                           expect_kernels=False)
    out = capsys.readouterr().out
    assert "overflow step: loss scale halved" in out
    assert "the donated input state was consumed" in out


def test_ddp_phase_rehearsal(capsys):
    chip_smoke.phase_ddp(_tiny(), per_chip_batch=1, seq=32, n_pred=4,
                         expect_kernels=False)
    out = capsys.readouterr().out
    assert "all 4 replicas skipped in lockstep" in out
    assert "all-reduce bytes cover the gradient bytes" in out


def test_last_line_contract(monkeypatch, capsys):
    """On a (pretended) TPU with the phases stubbed out, the last line
    is exactly the contract's JSON object and ``--four-chips`` runs the
    data-parallel phase and no other."""
    ran = []
    monkeypatch.setattr(chip_smoke, "device_record", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4})
    for name in ("phase_loader", "phase_kernels", "phase_train",
                 "phase_ddp"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda name=name: ran.append(name))
    import apex_tpu.utils.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "stub")
    assert chip_smoke.main(["--four-chips"]) == 0
    assert ran == ["phase_ddp"]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
    ran.clear()
    assert chip_smoke.main([]) == 0
    assert ran == ["phase_loader", "phase_kernels", "phase_train"]


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the helper sets nothing (JAX
    reads the variable itself). Unset: the fixed ``.jax_cache/`` of the
    checkout — never a temporary name."""
    import jax

    from apex_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_unknown_chip_has_no_peak():
    from apex_tpu.utils.chip_peaks import chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")
