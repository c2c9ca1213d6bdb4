"""The control comes out not correct: answers from the labelling's Eq.-3
bound alone (BiBFS capped at zero waves) break exactness, so the
comparison with the plain reference counts wrong answers. The same runs
without the control pass, on the same seeds."""
import pytest

from tinycell import run_tiny, tiny_cell

SEEDS = (2 ** 31 + 3, 7, 4_000_000_017)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails(no_disk_cache, seed):
    res = run_tiny(tiny_cell(), "--control", seed=seed)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes(no_disk_cache, seed):
    res = run_tiny(tiny_cell(), seed=seed)
    assert res["correct"], res["checks"]
