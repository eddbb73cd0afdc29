"""The rest of a run, driven without the look for a chip, at the
rehearsal's tiny size on the CPU: a sound program comes out correct, and
each fault a training cell can have, planted underneath the timed path,
comes out NOT correct under the cell's own limits. Also the control: the
plain reference in float8 in the program's place fails the comparison.

Slow (each run traces and interprets the kernels): about half a minute a
case.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import check, masks, runner
from benchmark.harness.manifest import Manifest

M = Manifest()


def drive(cell, sabotage=None, seed=11):
    return runner.run_cell(
        M, cell, seed=seed, seconds=0.5, trace=False,
        t0=time.perf_counter(), devices=jax.devices(), rehearsal=True,
        sabotage=sabotage)


class _Wrapped:
    """A TrainStep whose call is replaced; everything else passes on."""

    def __init__(self, step, call):
        self._step, self._call = step, call

    def __call__(self, state, batch):
        return self._call(self._step, state, batch)

    def __getattr__(self, name):
        return getattr(self._step, name)


def state_unchanged(stage, built):
    if stage != "after_build":
        return built

    def call(step, state, batch):
        kept = jax.tree.map(jnp.copy, state)
        _, metrics = step(state, batch)
        return kept, metrics

    return built._replace(step=_Wrapped(built.step, call))


def half_batch(stage, built):
    """The second half of every chip's rows never reaches the step: the
    first half stands in its place, so every mean is taken over the
    first half."""
    if stage != "after_build":
        return built

    def place(batch):
        shards = batch["seed"].shape[1]

        def fold(x):
            x = np.asarray(x)
            per = x.reshape(x.shape[0], shards, -1, *x.shape[2:])
            half = per.shape[2] // 2
            return np.concatenate([per[:, :, :half], per[:, :, :half]],
                                  axis=2).reshape(x.shape)

        return built.place({k: (v if k == "seed" else fold(v))
                            for k, v in batch.items()})

    return built._replace(place=place)


_UNDO = []      # what a sabotage patched, put back after each test


@pytest.fixture(autouse=True)
def undo_patches():
    yield
    while _UNDO:
        _UNDO.pop()()


def no_exchange(stage, built):
    """The gradient exchange between the chips is left out: every chip
    updates from its own shard's gradient. (Patched for the whole run:
    the step is traced at its first call, after the build.)"""
    if stage == "before_build":
        from apex_tpu.parallel import DistributedDataParallel as DDP

        def alone(self, acc, accum_steps=1):
            return jax.tree.map(lambda a: a / accum_steps, acc)

        saved = DDP.allreduce_accumulated
        _UNDO.append(lambda: setattr(DDP, "allreduce_accumulated", saved))
        DDP.allreduce_accumulated = alone
    return built


CASES = [
    ("bert_large.phase2", None, True),
    ("bert_large.phase2", state_unchanged, False),
    ("bert_large.phase2", half_batch, False),
    ("gpt2_medium.lm1024", None, True),
    ("gpt2_medium.lm1024", state_unchanged, False),
    ("gpt2_medium.lm1024", half_batch, False),
    ("bert_large.phase2_ddp4", None, True),
    ("bert_large.phase2_ddp4", no_exchange, False),
    ("bert_large.phase2_ddp4", half_batch, False),
    ("bert_large.phase2_ddp4", state_unchanged, False),
]


@pytest.mark.parametrize(
    "cell,fault,expected", CASES,
    ids=[f"{c}-{'sound' if f is None else f.__name__}" for c, f, _ in CASES])
def test_fault_under_the_timed_path_reads_not_correct(cell, fault, expected):
    if cell not in [w["name"] for w in M.doc["workloads"]]:
        pytest.skip(f"{cell} is not a cell of this benchmark")
    result = drive(cell, fault)
    assert result["correct"] is expected, result["compared"]
    assert result["attempted"] > 0 and "metrics" in result
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell", ["bert_large.phase2", "gpt2_medium.lm1024",
                                  "bert_large.phase2_ddp4"])
def test_control_in_float8_fails_the_comparison(cell):
    if cell not in [w["name"] for w in M.doc["workloads"]]:
        pytest.skip(f"{cell} is not a cell of this benchmark")
    from benchmark import control
    from benchmark.reference import train

    config, params = runner._apply_rehearsal(
        M.config(M.cell(cell)["config"]), M.traffic(cell))
    _, reference = runner.family(config)
    batches = control.first_batches(config, params, 5, M.cell(cell)["chips"],
                                    runner.FIRST_STEPS)
    key = runner.weights_key(5)
    ref = train.run(reference, config, config["optimizer"], key, batches,
                    masks)
    low = train.run(reference, config, config["optimizer"], key, batches,
                    masks, precision="fp8")
    assert not check.compare(low, ref, params["limits"])["correct"]
    same = train.run(reference, config, config["optimizer"], key, batches,
                     masks)
    assert check.compare(same, ref, params["limits"])["correct"]
