"""Non-blocking gossip pipeline (DESIGN.md §Pipeline).

Simulator↔engine parity oracle: the SPMD engine trajectory must match the
sequential numpy oracle (`core/simulator.py::run_superstep_oracle`)
step-for-step to fp32 tolerance — exact mode, fixed H, complete graph,
seeded matchings — for blocking, plain non-blocking, and the overlapped
(double-buffered) non-blocking mode, on all three transports. Plus the
pipeline's structural invariants: primed/drained state, bitwise equivalence
of overlap vs plain non-blocking, and the dispatch-before-local-steps /
permute-only-collective claims (jaxpr inspection on a multi-device
subprocess).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SwarmConfig, make_graph, make_swarm_step,
                        pipeline_epilogue, pipeline_prologue,
                        sample_matching, swarm_init)
from repro.core.simulator import run_superstep_oracle
from repro.core.swarm import make_matching_pool
from repro.launch.mesh import auto_mesh
from repro.optim import make_optimizer

N, D, H, B, T = 8, 12, 2, 4, 10
LR = 0.05


def _data(T, seed=42):
    r = np.random.default_rng(seed)
    X = r.normal(size=(T, N, H, B, D)).astype(np.float32)
    Y = r.normal(size=(T, N, H, B)).astype(np.float32)
    return X, Y


def _lin_loss(p, mb):
    x, y = mb
    return 0.5 * jnp.mean((x @ p["w"] - y) ** 2)


def _make_engine(scfg, **kw):
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    state = swarm_init(jax.random.PRNGKey(0), scfg,
                       lambda k: {"w": jax.random.normal(k, (D,)) * 0.3},
                       opt.init, same_init=False)
    step = jax.jit(make_swarm_step(scfg, _lin_loss, opt.update,
                                   lambda s: LR, **kw))
    return step, state


def _run_engine(step, state, X, Y, perms):
    traj = []
    key = jax.random.PRNGKey(7)
    h = jnp.full((N,), H, jnp.int32)
    for t, perm in enumerate(perms):
        key, sub = jax.random.split(key)
        state, _ = step(state, (jnp.asarray(X[t]), jnp.asarray(Y[t])),
                        jnp.asarray(perm), h, sub)
        traj.append(np.asarray(state.params["w"], np.float32))
    return np.stack(traj), state


def _oracle(x0, X, Y, perms, nonblocking):
    def grad_fn(w, i, t, q):
        x, y = X[t, i, q], Y[t, i, q]
        return x.T @ ((x @ w - y) / np.float32(B))
    return run_superstep_oracle(x0, grad_fn, perms, H, LR,
                                nonblocking=nonblocking)


@pytest.mark.parametrize("mode,nonblocking", [
    ("blocking", False),
    ("nonblocking", True),
    ("overlap", True),
])
def test_engine_matches_superstep_oracle(mode, nonblocking):
    """Parity oracle: exact mode, fixed H, complete graph, seeded
    matchings — engine trajectory == sequential oracle, step for step."""
    X, Y = _data(T)
    g = make_graph("complete", N)
    perms = [sample_matching(g, np.random.default_rng(123)) for _ in range(T)]
    scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=nonblocking,
                       overlap=(mode == "overlap"), gossip_impl="gather",
                       track_potential=False)
    step, state = _make_engine(scfg)
    x0 = np.asarray(state.params["w"], np.float32)
    traj, _ = _run_engine(step, state, X, Y, perms)
    ref = _oracle(x0, X, Y, perms, nonblocking)
    np.testing.assert_allclose(traj, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ppermute", "ppermute_pool"])
def test_overlap_parity_all_transports(impl):
    """The pipelined superstep gives the SAME trajectory (and the same
    oracle parity) through the shard_map transports as through gather."""
    X, Y = _data(T)
    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=4, seed=0)
    idx_rng = np.random.default_rng(5)
    idxs = [int(idx_rng.integers(len(pool))) for _ in range(T)]
    mesh = auto_mesh((1,), ("node",))
    if impl == "ppermute":
        # one static matching every superstep
        pairs = [(int(pool[1][d]), d) for d in range(N) if pool[1][d] != d]
        kw = dict(mesh=mesh, node_axes=(), static_pairs=pairs)
        perms_in = [pool[1]] * T
        perms_oracle = [pool[1]] * T
    else:
        kw = dict(mesh=mesh, node_axes=(), matching_pool=pool)
        perms_in = [np.full((N,), i, np.int32) for i in idxs]
        perms_oracle = [pool[i] for i in idxs]
    scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=True, overlap=True,
                       gossip_impl=impl, track_potential=False)
    step, state = _make_engine(scfg, **kw)
    x0 = np.asarray(state.params["w"], np.float32)
    traj, _ = _run_engine(step, state, X, Y, perms_in)
    ref = _oracle(x0, X, Y, perms_oracle, nonblocking=True)
    np.testing.assert_allclose(traj, ref, rtol=2e-5, atol=2e-5)


def test_overlap_bitwise_equals_plain_nonblocking():
    """In exact mode the double-buffered pipeline is a pure re-scheduling:
    bit-identical states to the plain non-blocking superstep."""
    X, Y = _data(T)
    g = make_graph("complete", N)
    perms = [sample_matching(g, np.random.default_rng(9)) for _ in range(T)]

    def run(overlap):
        scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=True, overlap=overlap,
                           gossip_impl="gather", track_potential=False)
        step, state = _make_engine(scfg)
        return _run_engine(step, state, X, Y, perms)[0]

    np.testing.assert_array_equal(run(False), run(True))


def test_pipeline_prologue_steady_epilogue():
    """swarm_init primes the in-flight payload (prologue); the steady-state
    superstep keeps it primed; the epilogue drains it; re-priming resumes
    the exact trajectory (exact mode: bitwise)."""
    X, Y = _data(6)
    g = make_graph("complete", N)
    perms = [sample_matching(g, np.random.default_rng(17)) for _ in range(6)]
    scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=True, overlap=True,
                       gossip_impl="gather", track_potential=False)
    step, state = _make_engine(scfg)
    assert state.inflight is not None and "sbuf" in state.inflight
    assert state.prev is None  # the comm copy lives packed in inflight

    full, _ = _run_engine(step, state, X, Y, perms)
    # interrupted run: drain after 3 supersteps, re-prime, finish
    half, mid = _run_engine(step, state, X[:3], Y[:3], perms[:3])
    drained = pipeline_epilogue(scfg, mid)
    assert drained.inflight is None
    resumed = pipeline_prologue(scfg, drained, jax.random.PRNGKey(3))
    assert resumed.inflight is not None
    rest, _ = _run_engine(step, resumed, X[3:], Y[3:], perms[3:])
    np.testing.assert_array_equal(full, np.concatenate([half, rest]))


def test_quantized_epilogue_preserves_comm_copy():
    """Regression: draining a QUANTIZED pipelined run must carry the packed
    comm copy back into `prev`, and re-priming must restore it — otherwise
    the post-resume encode's distance proxy collapses to zero (scale →
    min_scale) and the first decode after resume wraps."""
    X, Y = _data(5)
    g = make_graph("complete", N)
    perms = [sample_matching(g, np.random.default_rng(23)) for _ in range(5)]
    scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=True, overlap=True,
                       quantize=True, gossip_impl="gather",
                       track_potential=False)
    step, state = _make_engine(scfg)
    _, mid = _run_engine(step, state, X, Y, perms)
    drained = pipeline_epilogue(scfg, mid)
    assert drained.prev is not None  # comm copy survives the drain
    resumed = pipeline_prologue(scfg, drained, jax.random.PRNGKey(5))
    # the proxy buffer round-trips exactly (fp32 params)
    np.testing.assert_array_equal(np.asarray(resumed.inflight["prev"]),
                                  np.asarray(mid.inflight["prev"]))
    # ... and is NOT the degenerate self-proxy: the models have moved
    assert float(jnp.max(jnp.abs(resumed.inflight["prev"] -
                                 resumed.inflight["sbuf"]))) > 0


def test_overlap_quantized_tracks_exact():
    """Quantized overlap stays within the quantization error envelope of
    the exact overlapped trajectory (models start concentrated, so the
    distance criterion holds)."""
    X, Y = _data(T)
    g = make_graph("complete", N)
    perms = [sample_matching(g, np.random.default_rng(31)) for _ in range(T)]

    def run(quantize):
        scfg = SwarmConfig(n_nodes=N, H=H, nonblocking=True, overlap=True,
                           quantize=quantize, gossip_impl="gather",
                           track_potential=False)
        opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
        state = swarm_init(jax.random.PRNGKey(0), scfg,
                           lambda k: {"w": jax.random.normal(k, (D,)) * 0.3},
                           opt.init, same_init=True)
        step = jax.jit(make_swarm_step(scfg, _lin_loss, opt.update,
                                       lambda s: 0.01))
        return _run_engine(step, state, X, Y, perms)[0]

    exact, quant = run(False), run(True)
    assert float(np.max(np.abs(exact - quant))) < 0.05


def test_overlap_requires_nonblocking_and_flat():
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    with pytest.raises(AssertionError):
        make_swarm_step(SwarmConfig(n_nodes=N, overlap=True),
                        _lin_loss, opt.update, lambda s: LR)
    with pytest.raises(AssertionError):
        make_swarm_step(SwarmConfig(n_nodes=N, overlap=True, nonblocking=True,
                                    gossip_impl="gather_legacy"),
                        _lin_loss, opt.update, lambda s: LR)


_PIPELINE_JAXPR_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.swarm import SwarmConfig, make_swarm_step, swarm_init
    from repro.launch.mesh import auto_mesh
    from repro.optim import make_optimizer

    N = 8
    mesh = auto_mesh((N,), ("node",))
    pairs = [(0, 1), (1, 0), (2, 3), (3, 2)]
    scfg = SwarmConfig(n_nodes=N, H=2, nonblocking=True, overlap=True,
                       quantize=True, gossip_impl="ppermute",
                       track_potential=False)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.0)

    def tiny_init(rng):
        return {"w": jax.random.normal(rng, (300,)) * 0.1}

    def tiny_loss(p, mb):
        return jnp.mean((mb @ p["w"]) ** 2)

    state = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init)
    step = make_swarm_step(scfg, tiny_loss, opt.update, lambda s: 0.1,
                           mesh=mesh, node_axes=("node",),
                           static_pairs=pairs)
    batch = jnp.zeros((N, 2, 4, 300), jnp.float32)
    perm = jnp.asarray([1, 0, 3, 2, 4, 5, 6, 7], jnp.int32)
    h = jnp.full((N,), 2, jnp.int32)
    with mesh:
        txt = str(jax.make_jaxpr(step)(state, batch, perm, h,
                                       jax.random.PRNGKey(1)))
    i_pp = txt.find("ppermute")
    # the H-step fori_loop lowers to scan (static bounds) or while
    i_loop = min(i for i in (txt.find("while"), txt.find("scan"))
                 if i >= 0)
    print("n_ppermute", txt.count("ppermute"))
    print("dispatch_before_local_loop", 0 <= i_pp < i_loop)
""")


def test_pipelined_superstep_dispatches_before_local_loop():
    """Structural pipelining claims, quantized ppermute on an 8-fake-device
    mesh: (a) exactly TWO collectives per superstep (uint8 q + fp32 scales
    — the in-flight payload tensors; encode/decode are NOT re-issued per
    collective), and (b) the collectives are dispatched before the
    local-step `while` loop in program order, so they carry no data
    dependence on the local compute and latency-hiding scheduling can
    overlap the wire exchange with it."""
    out = subprocess.run([sys.executable, "-c", _PIPELINE_JAXPR_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    got = dict(line.split() for line in out.stdout.strip().splitlines())
    assert got["n_ppermute"] == "2"
    assert got["dispatch_before_local_loop"] == "True"


def test_ppermute_perm_input_matches_compiled_pairs():
    """Regression: for the plain ppermute transport the collective's pairs
    are compiled in (static), so sample_gossip_perm must feed the engine
    that SAME matching every superstep — a fresh draw would make the
    matched mask disagree with the actual data movement. The ppermute
    trajectory must therefore equal gather driven by the static matching."""
    from repro.configs import get_config, reduced
    from repro.data import DataConfig, SyntheticLMDataset, make_node_batches
    from repro.launch.train import (build_trainer, sample_gossip_perm,
                                    static_ppermute_matching)
    from repro.core.swarm import sample_h_counts

    cfg = reduced(get_config("transformer-wmt"), n_layers=1, d_model=64)
    seed = 3

    def run(impl):
        step, state, scfg, graph = build_trainer(
            cfg, "swarm", 4, 2, lr=0.05, seed=seed, gossip_impl=impl)
        static = static_ppermute_matching(graph, seed)
        ds = SyntheticLMDataset(DataConfig(cfg.vocab_size, 32, seed=0), 4)
        rng_np = np.random.default_rng(0)
        key = jax.random.PRNGKey(1)
        for t in range(4):
            nb = make_node_batches(ds, t, 2 * scfg.H)
            b = {k: jnp.asarray(v.reshape(4, scfg.H, 2, 32))
                 for k, v in nb.items()}
            perm = sample_gossip_perm(scfg, graph, rng_np, seed) \
                if impl == "ppermute" else static
            if impl == "ppermute":
                np.testing.assert_array_equal(perm, static)
            key, sub = jax.random.split(key)
            state, _ = step(state, b, jnp.asarray(perm),
                            jnp.asarray(sample_h_counts(scfg, rng_np)), sub)
        return state

    a, b = run("ppermute"), run("gather")
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_build_trainer_overlap_end_to_end():
    """launch/train.py plumbing: --overlap/--gossip_impl/--pool_size reach
    the engine and the driver trains (3 supersteps, finite loss/gamma)."""
    from repro.configs import get_config, reduced
    from repro.data import DataConfig, SyntheticLMDataset, make_node_batches
    from repro.launch.train import build_trainer, sample_gossip_perm
    from repro.core.swarm import sample_h_counts

    cfg = reduced(get_config("transformer-wmt"), n_layers=1, d_model=64)
    step, state, scfg, graph = build_trainer(
        cfg, "swarm", 4, 2, lr=0.05, quantize=True, overlap=True,
        gossip_impl="ppermute_pool", pool_size=3)
    assert scfg.overlap and scfg.nonblocking and scfg.pool_size == 3
    assert state.inflight is not None
    ds = SyntheticLMDataset(DataConfig(cfg.vocab_size, 32, seed=0), 4)
    rng_np = np.random.default_rng(0)
    key = jax.random.PRNGKey(1)
    for t in range(3):
        nb = make_node_batches(ds, t, 2 * scfg.H)
        b = {k: jnp.asarray(v.reshape(4, scfg.H, 2, 32))
             for k, v in nb.items()}
        perm = jnp.asarray(sample_gossip_perm(scfg, graph, rng_np))
        h = jnp.asarray(sample_h_counts(scfg, rng_np))
        key, sub = jax.random.split(key)
        state, m = step(state, b, perm, h, sub)
    assert np.isfinite(float(m["loss"])) and np.isfinite(float(m["gamma"]))
