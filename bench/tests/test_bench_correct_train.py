"""The training cell's comparison, run end to end on the CPU at a tiny
size (its numbers, its sizes cut): a sound run is correct; each fault
planted underneath the timed path, and the control, are not.

The limits are this size's own, set between the readings of sound runs
(loss 1.4e-5, grad 1.4e-3, change 5.4e-3 at most) and of the control
(loss 1.9e-4, grad 2.3e-2); the faults read grad or change 0.46 and
more."""
import time

import pytest

from bench import check, faults, harness
from bench.tests import tiny

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.write_root(tmp_path_factory.mktemp("root"), {tiny.TRAIN: {
        "loss_gap": 5e-5, "grad_gap": 6e-3, "change_gap": 2e-2}})
    return harness.load_cell(tiny.TRAIN, root)


def _run(cell):
    import jax
    return harness.run_cell(cell, SEED, 0.3, False, jax.devices()[:1],
                            time.time())


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 3 and r["failed"] == 0
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "no_exchange"])
def test_fault_is_caught(cell, fault):
    with faults.FAULTS[fault]():
        r = _run(cell)
    assert not r["correct"], (fault, r["checks"])


def test_control_is_caught(cell):
    """The reference itself in the program's place, in float8."""
    import jax
    from bench.jobs.train import Swarm
    sw = Swarm(cell, jax.devices()[:1], SEED)
    sw.reseed(SEED)
    sw.free()
    numbers = check.train_numbers(sw.reference("fp8"), sw.reference())
    ok, checks = check.judge(numbers, cell.limits, 0)
    assert not ok, checks
