"""A cell's reference is the module its configuration names
(`reference.module`), found beside the configurations and used by both
jobs for the weights and for the replay or the reference supersteps; a
configuration's `model` may hold nested sub-config keys and a `pattern`
list."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from bench import harness
from bench.harness import BENCH, ROOT
from bench.tests import tiny

SEED = 2 ** 31 + 41
SERVE_LIMITS = {"logit_gap": 5e-3}
# test_bench_correct_train's limits for the tiny training cell, and the
# seed they were read at
TRAIN_LIMITS = {"loss_gap": 5e-5, "grad_gap": 6e-3, "change_gap": 2e-2}
TRAIN_SEED = 2 ** 31 + 11

RECORDER = '''"""Records each call with its `model`, then hands it on to the
transformer reference."""
from bench.reference import transformer as T

CALLS = []


def _recorded(name):
    def call(*args, **kw):
        CALLS.append((name, None if name == "init_params" else args[0]))
        return getattr(T, name)(*args, **kw)
    return call


init_params, hidden, logits, loss_and_grad = map(
    _recorded, ("init_params", "hidden", "logits", "loss_and_grad"))
'''


# ---------------------------------------------------------------------------
# Nested model keys
# ---------------------------------------------------------------------------

def test_model_config_applies_nested_keys_and_a_pattern_list():
    from repro.configs import MoEConfig, SSMConfig, get_config
    conf = {"arch": "jamba-1.5-large-398b", "model": {
        "n_layers": 4, "d_model": 64,
        "pattern": [["mamba", "moe"], ["attn", "dense"]],
        "moe": {"n_experts": 8, "top_k": 2, "d_ff": 32},
        "ssm": {"d_state": 16, "chunk": 8}}}
    cfg = harness.model_config(conf)
    base = get_config("jamba-1.5-large-398b")
    assert cfg.pattern == (("mamba", "moe"), ("attn", "dense"))
    assert isinstance(cfg.moe, MoEConfig) and isinstance(cfg.ssm, SSMConfig)
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2, d_ff=32,
                                expert_shard_axis=base.moe.expert_shard_axis)
    assert cfg.ssm == SSMConfig(d_state=16, head_dim=base.ssm.head_dim,
                                chunk=8)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads) == (4, 64, base.n_heads)
    # an architecture with no sub-config gets one built from the dict
    olmo = harness.model_config({"arch": "olmo-1b", "model": {
        "moe": {"n_experts": 4, "top_k": 1, "d_ff": 16}}})
    assert olmo.moe == MoEConfig(n_experts=4, top_k=1, d_ff=16)


@pytest.mark.parametrize("model,named", [
    ({"n_layer": 2}, "'n_layer'"),
    ({"moe": {"n_expert": 4}}, "moe.'n_expert'"),
    ({"ssm": {"d_state": 16, "state": 4}}, "ssm.'state'"),
    ({"frontend": {"kind": "vision", "patches": 4}}, "frontend.'patches'"),
])
def test_model_config_rejects_an_unknown_key_by_name(model, named):
    with pytest.raises(ValueError) as e:
        harness.model_config({"arch": "jamba-1.5-large-398b", "model": model})
    assert named in str(e.value)


def test_published_configurations_run_as_registered():
    from repro.configs import get_config
    for name in ("olmo-1b", "transformer-wmt"):
        conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        assert harness.model_config(conf) == get_config(conf["arch"])


def test_gap_fn_caches_a_nested_model_and_hands_it_on_as_it_was():
    import jax.numpy as jnp
    from bench.reference.serve import gap_fn
    seen = []

    def hidden(model, params, tokens, prec):
        seen.append(model)
        return params["h"][tokens]

    def logits(model, params, h, prec):
        seen.append(model)
        return h @ params["w"]
    ref = types.ModuleType("stub")
    ref.hidden, ref.logits = hidden, logits
    model = {"d_model": 4, "pattern": [["mamba", "moe"], ["attn", "moe"]],
             "moe": {"n_experts": 4, "top_k": 2}}
    fn = gap_fn(ref, model, 6, 3, "f32")
    assert gap_fn(ref, copy.deepcopy(model), 6, 3, "f32") is fn
    assert gap_fn(ref, dict(model, d_model=8), 6, 3, "f32") is not fn
    params = {"h": jnp.eye(6, 4), "w": jnp.arange(20.0).reshape(4, 5)}
    # rows 1-3 of w are the logits of positions 1-3
    g = fn(params, jnp.arange(6), 1, jnp.array([4, 3, 0]))
    assert np.allclose(np.asarray(g), [0.0, 1.0, 4.0])
    assert seen and all(m == model for m in seen)
    assert isinstance(seen[0]["pattern"], list)


# ---------------------------------------------------------------------------
# The lookup
# ---------------------------------------------------------------------------

def _checkout(tmp_path, module: str, body=None):
    """A checkout whose olmo-1b configuration names reference `module`
    (written with `body` where given); the program is linked in."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    path = tmp_path / "bench" / "configs" / "olmo-1b.json"
    conf = json.loads(path.read_text())
    conf["reference"]["module"] = module
    path.write_text(json.dumps(conf))
    if body is not None:
        (tmp_path / "bench" / "reference" / f"{module}.py").write_text(body)
    return tmp_path


@pytest.mark.parametrize("module,body,said", [
    ("nope", None, "no reference 'nope'"),
    ("lacking", "from bench.reference.transformer import init_params, "
     "hidden\n", "reference 'lacking' lacks ['logits']"),
])
def test_a_missing_reference_stops_the_run_at_load(tmp_path, module, body,
                                                   said):
    """Exit 2 before set-up: no result line, no compilation cache made."""
    root = _checkout(tmp_path, module, body)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-serve-chat",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 2, p.stderr
    assert said in p.stderr
    assert not p.stdout.strip()
    assert not (root / ".jax_cache").exists()


def _drive(cell, seed):
    import jax
    from bench.jobs.serve import Server, sample_for_check
    from bench.traffic import chat_requests
    tr = cell.traffic
    srv = Server(cell, jax.devices()[0], seed)
    srv.prewarm()
    d = srv.drive(chat_requests(tr, srv.cfg.vocab_size,
                                [tr["warmup_s"], 1.0, 1.0], seed),
                  tr["warmup_s"], 1.0, tr["drain_s"])
    srv.free()
    return srv, d, sample_for_check(d, seed,
                                    cell.config["reference"]["check_tokens"])


@pytest.mark.parametrize("published_std", [False, True])
def test_olmo_weights_and_logit_gap_are_those_of_a_direct_import(
        tmp_path, published_std):
    """The tiny olmo-serve-chat cell, through the configuration's module
    and through `bench.reference.transformer` imported directly: the same
    weights bit for bit, the same gaps; at the tiny cell's embedding
    scale, which is transformer's default, and at olmo-1b's own."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    from bench.jobs.serve import served_gaps
    from bench.reference import transformer as T
    from bench.traffic import seed32
    name, cells = tiny.SERVE, []
    if published_std:
        olmo = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
        name = "olmo-serve-chat-std"
        cells = [tiny.Tiny(name, "olmo-1b", "chat-poisson", tiny.merged(
            tiny.SERVE_CUT, {"weights": olmo["weights"]}))]
    cell = harness.load_cell(name, tiny.write_root(
        tmp_path, {name: SERVE_LIMITS}, cells))
    assert cell.reference is not T
    assert cell.reference.__file__.endswith("reference/transformer.py")
    srv, d, sample = _drive(cell, SEED)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                srv.cfg))
    direct = jax.jit(lambda k: T.init_params(
        k, shapes, jnp.dtype(srv.cfg.dtype),
        cell.config["weights"]["embed_std"]))(
            jax.random.PRNGKey(seed32(SEED)))
    w = srv.weights()
    for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(direct)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a).view(np.uint16),
                              np.asarray(b).view(np.uint16))
    model, rows = cell.config["model"], srv.ecfg.max_new_tokens
    for prec in ("f32", "fp8"):
        got = served_gaps(cell.reference, model, w, sample, d,
                          srv.ecfg.kv_capacity, rows, prec)
        want = served_gaps(T, model, direct, sample, d,
                           srv.ecfg.kv_capacity, rows, prec)
        assert got.size >= 40 and np.array_equal(got, want), prec


@pytest.mark.parametrize("base,limits,seed,calls", [
    (tiny.SERVE, SERVE_LIMITS, SEED, {"init_params", "hidden", "logits"}),
    (tiny.TRAIN, TRAIN_LIMITS, TRAIN_SEED, {"init_params", "loss_and_grad"}),
])
def test_the_named_module_makes_the_weights_and_the_reference(
        tmp_path, base, limits, seed, calls):
    """A configuration that names a stub reference, one that records its
    calls and hands them to `transformer`: the run's weights and its
    replay (serving) or reference supersteps (training) go through it,
    with the configuration's `model`, and the run is correct."""
    import jax
    spec = next(c for c in tiny.CELLS if c.name == base)
    stub = tiny.Tiny(f"{base}-stub", spec.config, spec.traffic,
                     tiny.merged(spec.cut, {"reference": {
                         "module": "recorder"}}))
    root = tiny.write_root(tmp_path, {stub.name: limits}, [stub])
    (root / "bench" / "reference" / "recorder.py").write_text(RECORDER)
    cell = harness.load_cell(stub.name, root)
    assert cell.config["reference"]["module"] == "recorder"
    r = harness.run_cell(cell, seed, 0.3 if base == tiny.TRAIN else 1.0,
                         False, jax.devices()[:1], time.time())
    assert r["correct"], r["checks"]
    assert {name for name, _ in cell.reference.CALLS} == calls
    assert all(m == cell.config["model"] for name, m in cell.reference.CALLS
               if name != "init_params")
