"""The serving cell's comparison, run end to end on the CPU at a tiny size
(its number, its sizes cut): a sound run is correct; a token altered where
decode produces it, a program compiled inside the window, and the
control, are not.

The limit is this size's own, set between sound runs' widest gaps (under
1e-3) and the control's (2.5e-2); an altered token reads 0.7 and more."""
import contextlib
import time

import pytest

from bench import check, faults, harness
from bench.tests import tiny

SEED = 2 ** 31 + 23


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.write_root(tmp_path_factory.mktemp("root"),
                           {tiny.SERVE: {"logit_gap": 5e-3}})
    return harness.load_cell(tiny.SERVE, root)


def _run(cell):
    import jax
    return harness.run_cell(cell, SEED, 1.0, False, jax.devices()[:1],
                            time.time())


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    m = r["metrics"]
    assert m["serve_tokens_per_s"]["value"] > 0
    assert m["itl_p95_ms"]["value"] > 0 and m["setup_s"]["value"] > 0


def test_altered_token_is_caught(cell):
    with faults.altered_token():
        r = _run(cell)
    assert not r["correct"], r["checks"]


@contextlib.contextmanager
def _recompiling_step():
    """Every engine step also compiles a program of a new shape, as a
    hot path that recompiles would."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    real = ServeEngine.step
    n = [0]

    def step(self):
        n[0] += 1
        jax.jit(lambda x: x + 1)(jnp.zeros((n[0],))).block_until_ready()
        return real(self)
    ServeEngine.step = step
    try:
        yield
    finally:
        ServeEngine.step = real


def test_compile_in_window_is_caught(cell):
    with _recompiling_step():
        r = _run(cell)
    assert r["checks"]["compiles_in_window"]["value"] > 0
    assert r["checks"]["compiles_in_window"]["limit"] == 0
    assert not r["correct"], r["checks"]


def test_control_is_caught(cell):
    """The float8 reference's own first choices, judged by the float32
    reference, on the same served requests."""
    import jax
    from bench.jobs.serve import (Server, sample_for_check, served_gaps)
    from bench.traffic import chat_requests
    tr = cell.traffic
    srv = Server(cell, jax.devices()[0], SEED)
    srv.prewarm()
    d = srv.drive(chat_requests(tr, srv.cfg.vocab_size,
                                [tr["warmup_s"], 1.0, 1.0], SEED),
                  tr["warmup_s"], 1.0, tr["drain_s"])
    srv.free()
    sample = sample_for_check(d, SEED, cell.config["reference"]
                              ["check_tokens"])
    g = served_gaps(cell.reference, cell.config["model"], srv.weights(),
                    sample, d, srv.ecfg.kv_capacity, srv.ecfg.max_new_tokens,
                    "fp8")
    ok, checks = check.judge({"logit_gap": float(g.max())}, cell.limits, 0)
    assert not ok, checks
