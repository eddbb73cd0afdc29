"""Tier-1 guards of how the benchmark finds a model family (mirrored from
``benchmark/tests/``, which tier 1 does not run): a family comes in by
added files alone, a family without a count file is an error that names
the file, the counting rules hold for grouped-query attention, the
harness names no family - and the ``nemotron_h`` family's own count and
its cell's rehearsal."""

import importlib.util
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.harness import flops
from benchmark.harness.manifest import ROOT, Manifest

M = Manifest()
CELL = "nemotron_twotower_30b_a3b.lm8192"
CONFIG = "nemotron_twotower_30b_a3b"


def _mirrored(name):
    """A test module of ``benchmark/tests`` loaded by path (it is no
    package)."""
    path = ROOT / "benchmark" / "tests" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"mirrored_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_by_files = _mirrored("test_family_by_files")
grown = _by_files.grown


def test_a_new_family_runs_by_added_files_alone(grown):
    _by_files.test_a_new_family_runs_by_added_files_alone(grown)


def test_a_family_without_a_count_file_fails_the_rehearsal_too(grown):
    _by_files.test_a_family_without_a_count_file_fails_the_rehearsal_too(
        grown)


# -- the dispatch cases of benchmark/tests/test_flops.py, as they stand there ------

_flops = _mirrored("test_flops")
test_grouped_query_moves_k_and_v_at_their_own_width = \
    _flops.test_grouped_query_moves_k_and_v_at_their_own_width
test_a_family_without_an_attention_kernel_has_no_attention_call = \
    _flops.test_a_family_without_an_attention_kernel_has_no_attention_call
test_no_count_file_is_an_error_that_names_the_file = \
    _flops.test_no_count_file_is_an_error_that_names_the_file
test_step_flops_returns_the_parents_integer = \
    _flops.test_step_flops_returns_the_parents_integer
test_attention_call_returns_the_parents_integers = \
    _flops.test_attention_call_returns_the_parents_integers
test_the_harness_names_no_family = _flops.test_the_harness_names_no_family


def test_the_harness_does_not_name_the_new_family_either():
    harness = Path(flops.__file__).parent
    for path in harness.glob("*.py"):
        code = "\n".join(line.split("#")[0]
                         for line in path.read_text().splitlines())
        assert not re.search(r"""["'](nemotron_h|hybrid_override_pattern)["']""",
                             code), path.name


# -- the nemotron_h family's own count ----------------------------------------------

def test_nemotron_h_forward_by_hand():
    c, t = M.config(CONFIG), M.traffic(CELL)
    counts = flops.counts(c)
    assert counts.pattern(c) == "MEMEM*E"
    rows, S, H, V = 2, 8192, 2688, 16384
    T = rows * S
    inner, proj = 64 * 64, 2 * 64 * 64 + 2 * 8 * 128 + 64
    chunks, L, N, P = T // 128, 128, 128, 64
    scan = chunks * (8 * 2 * L * L * N
                     + 64 * (2 * L * L * P + 2 * 2 * L * N * P))
    mamba = 2 * T * H * proj + 2 * T * inner * H + scan
    attention = (2 * T * H * (2 * 32 * 128 + 2 * 2 * 128)
                 + 2 * 2 * rows * S * S * 32 * 128 // 2)
    experts = (2 * T * H * 128 + 2 * 2 * T * H * 3712
               + 2 * 2 * T * (6 * 8 / 128) * H * 1856)
    head = 2 * rows * (S - 1) * H * V
    want = int(3 * mamba + attention + 3 * experts + head)
    assert counts.forward_flops(c, t, rows) == want
    assert flops.step_flops(c, t, 1) == 3 * want
    assert flops.step_flops(c, t, 1) == pytest.approx(2.894e13, rel=1e-3)
    assert counts.attention_shape(c) == {
        "query_heads": 32, "kv_heads": 2, "head_size": 128, "causal": True}


def test_nemotron_h_calls_by_hand():
    c = M.config(CONFIG)
    counts = flops.counts(c)
    ops, nbytes = counts.scan_call(c, 16384, "scan_forward")
    assert ops == 128 * (8 * 2 * 128 ** 3 + 64 * 6 * 128 * 128 * 64)
    assert nbytes == 16384 * ((2 * 4096 + 2 * 1024) * 2 + 64 * 4)
    assert counts.scan_call(c, 16384, "scan_backward") == (2 * ops,
                                                           2 * nbytes)
    ops, nbytes = counts.grouped_mm_call(c, 1000.0, "experts_forward")
    assert ops == 2 * 2 * 1000 * 2688 * 1856
    assert nbytes == 2 * (1000 * (2688 + 1856) + 8 * 2688 * 1856) * 2
    assert counts.grouped_mm_call(c, 1000.0, "experts_backward") == (
        2 * ops, 2 * nbytes)


# -- the sdar family's own count (block diffusion: live pairs, not "causal at half") ---

SDAR_CONFIG, SDAR_CELL = "sdar_30b_a3b_chat", "sdar_30b_a3b_chat.bd8192"


def test_sdar_forward_by_hand():
    c, t = M.config(SDAR_CONFIG), M.traffic(SDAR_CELL)
    counts = flops.counts(c)
    rows, L, g, H, V = 1, 8192, 4, 2048, 18992
    qw, kvw, F = 32 * 128, 4 * 128, 768
    # the mask's live pairs of a head, by hand and against the kernel's
    # own helper (the count file imports nothing of the program): a noised
    # block sees itself, the clean copy of strictly earlier blocks; the
    # clean copy is block-causal
    noised = L * g + L * (L - g) // 2
    clean = L * (L + g) // 2
    assert noised + clean == L * L + g * L
    assert counts.live_pairs(c, L) == L * L + g * L
    assert counts.live_pairs(c, L, clean_queries=False) == noised
    from apex_tpu.ops.flash_attention import BlockDiffusionMask, tile_classes
    for clean_queries, pairs in ((True, noised + clean), (False, noised)):
        mask = BlockDiffusionMask(64, g, clean_queries)
        dead, partial, full = tile_classes(mask.q_len, 128, 1, 1,
                                           score_mask=mask)
        assert partial == 0 and full == counts.live_pairs(
            {"block_length": g}, 64, clean_queries)

    def layer(positions, pairs):       # positions: the query side's
        return (2 * positions * H * qw * 2 + 2 * 2 * L * H * kvw * 2
                + 2 * 2 * pairs * qw + 2 * positions * H * 128
                + 3 * 2 * positions * (8 * 16 / 128) * H * F)

    want = int(3 * layer(2 * L, noised + clean) + layer(L, noised)
               + 2 * L * H * V)
    assert counts.forward_flops(c, t, rows) == want
    assert flops.step_flops(c, t, 1) == 3 * want
    assert flops.step_flops(c, t, 1) == pytest.approx(2.177e13, rel=1e-3)
    assert counts.attention_shape(c) == {
        "query_heads": 32, "kv_heads": 4, "head_size": 128, "causal": False}


def test_sdar_calls_by_hand():
    c = M.config(SDAR_CONFIG)
    counts = flops.counts(c)
    L, g, qw, kvw = 8192, 4, 4096, 512
    pair = 2 * 2 * (L * L + g * L) * qw
    ops, nbytes = counts.blockdiff_attention_call(c, 1, L,
                                                  "attention_forward")
    assert ops == pair
    assert nbytes == (2 * L * 2 * qw + 2 * L * 2 * kvw) * 2
    for kind, pairs, at_q, at_kv in (("attention_backward_dq", 1, 3, 2),
                                     ("attention_backward_dkv", 1, 2, 4),
                                     ("attention_backward", 2, 4, 4)):
        assert counts.blockdiff_attention_call(c, 1, L, kind) == (
            pairs * pair, (2 * L * at_q * qw + 2 * L * at_kv * kvw) * 2)
    # the last layer's call: the noised copy's queries on the same keys
    ops, nbytes = counts.blockdiff_attention_call(
        c, 1, L, "attention_forward", clean_queries=False)
    assert ops == 2 * 2 * (L * g + L * (L - g) // 2) * qw
    assert nbytes == (L * 2 * qw + 2 * L * 2 * kvw) * 2
    # a forward call's 288 live tiles of 512 x 512 hold its live pairs
    assert 288 * 512 * 512 >= L * L + g * L > 240 * 512 * 512


def test_the_configuration_file_states_its_cut():
    entry = M._entry("configs", CONFIG)
    c = M.config(CONFIG)
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    catalog = ROOT / "benchmark" / "configs" / f"{CONFIG}.json"
    assert entry["file"] == str(catalog.relative_to(ROOT))
    # every width is the published one
    assert (c["hidden_size"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"], c["head_dim"],
            c["mamba_head_dim"], c["ssm_state_size"],
            c["num_experts_per_tok"]) == (2688, 1856, 3712, 128, 64, 128, 6)
    assert c["deployment"]["n_routed_experts_published"] == 128
    assert c["deployment"]["chips_sharing_a_layer"] == 16
    assert len(c["departures"]) == 2 and "denoiser" in c["departures"][0]
    cell = M.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    t = M.traffic(CELL)
    assert (t["seq"], t["rows_per_chip"], t["feed"], t["corpus_rows"],
            t["prefetch"]) == (8192, 2, "loader", 512, 2)
    mine = [m for m in M.doc["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 9
    for m in mine:
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").exists()


def test_the_new_cell_rehearses_to_correct():
    """``benchmark/run.py --rehearse`` of the new cell, traced, in a child
    process: correct, the counters read, no compilation in the window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "3000000021", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    shape = json.loads(done.stdout.split(
        "REHEARSAL on the CPU, not a result: ")[1].splitlines()[0])
    assert shape["correct"] is True
    assert {"moe.assignments_held", "moe.load_max_over_mean",
            "step.live_gib", "amp.steps_skipped"} <= set(shape["metrics"])
    assert 'compared compilations_in_window: {"value": 0' in done.stderr
