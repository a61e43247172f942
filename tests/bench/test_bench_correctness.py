"""The comparison that decides ``correct``, at a small size on the CPU: a
sound run compares exactly, the control (the reference in the program's
place, computed in bfloat16) and each fault a served cell can have
(``bench/faults.py``) come out not correct."""
from __future__ import annotations

import pytest

import bench_checkout as bc


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return bc.make_checkout(str(tmp_path_factory.mktemp("checkout")))


def _control(checkout, seed, dtype):
    import control
    import run

    c = run.cell(run.benchmark(checkout), bc.CELL, checkout)
    with bc.jax_settings_kept():
        return control.control_run(c.config, c.traffic, seed, 2.0, dtype)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_bfloat16_is_not_correct(checkout, seed):
    out = _control(checkout, seed, "bfloat16")
    assert out["info"]["scored_decisions"] > 50
    assert not out["correct"], out["checks"]
    assert out["checks"]["exact_mismatches"]["value"] == 0


def test_reference_in_its_own_place_compares_exactly(checkout):
    out = _control(checkout, 4, "float64")
    assert out["correct"]
    assert out["checks"]["decision_gap"]["value"] == 0.0


def test_sound_run_is_correct(checkout):
    code, result = bc.run_small(checkout, seed=21)
    assert code == 0 and result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_of_each_batch",
                                   "answer_altered", "always_reject"])
def test_fault_is_not_correct(checkout, fault):
    import faults

    code, result = bc.run_small(checkout, seed=21,
                                engine_hook=faults.FAULTS[fault])
    assert code == 0
    assert not result["correct"], result["checks"]
