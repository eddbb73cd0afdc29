"""The comparison that decides ``correct``, on hand-made numbers."""

import numpy as np
import pytest

from benchmark.harness import check


def side(loss, grad, change):
    return {"loss": loss, "grad": grad, "change": change}


REF = side([10.0, 9.0, 8.0],
           {"a": np.array([1.0, 2.0]), "b": 4.0, "dead": 1e-9, "c": 3.0},
           {"a": np.array([0.1, 0.2]), "b": 0.4, "dead": 1e-7, "c": 0.3})


def test_gap_of_norms_against_leaf_or_median_whichever_is_larger():
    prog = side([10.0, 9.0, 8.0],
                {"a": np.array([1.0, 2.2]), "b": 4.0, "dead": 0.5, "c": 3.0},
                dict(REF["change"]))
    out = check.compare(prog, REF, {})["numbers"]
    # "dead" is far under the median tensor (2.0): its gap is measured
    # against the median, 0.5 / 2.0, and it is the worst
    assert out["grad_worst_leaf"]["leaf"] == "dead"
    assert out["grad_worst_leaf"]["value"] == pytest.approx(0.25)
    assert out["grad_median_leaf"]["value"] == pytest.approx(0.0)


def test_dead_gradient_leaves_are_left_out_of_the_change_only():
    prog = side([10.0, 9.0, 8.0], dict(REF["grad"]),
                {"a": np.array([0.1, 0.2]), "b": 0.4, "dead": 0.2, "c": 0.33})
    out = check.compare(prog, REF, {})["numbers"]
    assert out["change_worst_leaf"]["leaves_left_out"] == 1
    assert out["change_worst_leaf"]["leaf"] == "c"
    assert out["change_worst_leaf"]["value"] == pytest.approx(0.1)


def test_limits_decide_and_a_number_without_a_limit_is_not_held():
    prog = side([10.001, 9.0, 8.0], dict(REF["grad"]), dict(REF["change"]))
    assert check.compare(prog, REF, {"loss_1": 1e-3})["correct"]
    verdict = check.compare(prog, REF, {"loss_1": 1e-5})
    assert not verdict["correct"]
    assert verdict["numbers"]["loss_1"]["value"] == pytest.approx(1e-4)
    assert verdict["numbers"]["loss_2"]["limit"] is None
    nan = side([float("nan"), 9.0, 8.0], dict(REF["grad"]),
               dict(REF["change"]))
    assert not check.compare(nan, REF, {"loss_1": 1.0})["correct"]
    brief = check.brief(verdict["numbers"])
    assert set(brief["loss_1"]) == {"value", "limit"}


def test_unchanged_state_reads_one():
    still = side([10.0, 9.0, 8.0], dict(REF["grad"]),
                 {"a": np.array([0.0, 0.0]), "b": 0.0, "dead": 0.0, "c": 0.0})
    out = check.compare(still, REF, {"change_median_leaf": 0.5})
    assert out["numbers"]["change_median_leaf"]["value"] == pytest.approx(1.0)
    assert not out["correct"]


def test_different_tensors_on_the_two_sides_raise():
    prog = side([10.0, 9.0, 8.0], {"a": 1.0}, {"a": 1.0})
    with pytest.raises(ValueError, match="different tensors"):
        check.compare(prog, REF, {})
