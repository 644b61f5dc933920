"""Simulator↔engine parity on HETEROGENEOUS traces (DESIGN.md §Sched).

Three layers of evidence that the bridge (sched/bridge.py) executes the
paper's asynchronous process faithfully:

1. binning is exact: the binned superstep oracle equals the sequential
   one-event-at-a-time replay (`run_events_oracle`) bitwise — events in a
   bin are node-disjoint, so they commute;
2. the SPMD engine matches the binned superstep oracle within fp32
   tolerance for blocking / non-blocking / overlap on all three transports
   (gather dynamic matchings; ppermute static-matching restriction;
   ppermute_pool pool restriction with per-bin pool indices);
3. the synchronous uniform trace drives the engine to the SAME trajectory
   as the plain (unscheduled) driver — bit-exactly.

The trace profile follows REPRO_RATE_PROFILE: unset, parity runs on
uniform-rate clocks (straggler slowdown still makes the h-schedule
heterogeneous); the CI scheduler-path job sets `lognormal` to run the
SAME parity suite over heterogeneous clocks."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SwarmConfig, make_graph, make_swarm_step, swarm_init
from repro.core.simulator import run_events_oracle, run_superstep_oracle
from repro.core.swarm import make_matching_pool
from repro.launch.mesh import auto_mesh
from repro.optim import make_optimizer
from repro.sched import (RateProfile, StragglerConfig, bin_trace,
                         engine_inputs, generate_trace, pool_edges,
                         synchronous_trace)

N, D, H_MEAN, H_MAX, B = 8, 12, 2, 4, 4
LR = 0.05
_ENV_PROFILE = os.environ.get("REPRO_RATE_PROFILE", "uniform")
PROFILE = RateProfile(_ENV_PROFILE if _ENV_PROFILE in ("uniform", "lognormal")
                      else "lognormal", sigma=0.8)
STRAGGLER = StragglerConfig(fraction=0.25, slowdown=4.0)


def _trace_and_schedule(impl, n_events=40, seed=13):
    g = make_graph("complete", N)
    if impl == "ppermute_pool":
        pool = make_matching_pool(g, K=4, seed=0)
        tr = generate_trace(g, PROFILE, n_events, H=H_MEAN, h_max=H_MAX,
                            seed=seed, straggler=STRAGGLER,
                            edges=pool_edges(pool))
        return tr, bin_trace(tr, pool=pool), pool, None
    if impl == "ppermute":
        pairs = [(1, 0), (0, 1), (3, 2), (2, 3), (5, 4), (4, 5),
                 (7, 6), (6, 7)]
        static = np.asarray([1, 0, 3, 2, 5, 4, 7, 6], np.int32)
        edges = np.asarray([(0, 1), (2, 3), (4, 5), (6, 7)], np.int64)
        tr = generate_trace(g, PROFILE, n_events, H=H_MEAN, h_max=H_MAX,
                            seed=seed, straggler=STRAGGLER, edges=edges)
        return tr, bin_trace(tr, static_pairs=pairs), None, (pairs, static)
    tr = generate_trace(g, PROFILE, n_events, H=H_MEAN, h_max=H_MAX,
                        seed=seed, straggler=STRAGGLER)
    return tr, bin_trace(tr), None, None


def _data(S, seed=21):
    r = np.random.default_rng(seed)
    X = r.normal(size=(S, N, H_MAX, B, D)).astype(np.float32)
    Y = r.normal(size=(S, N, H_MAX, B)).astype(np.float32)
    return X, Y


def _lin_loss(p, mb):
    x, y = mb
    return 0.5 * jnp.mean((x @ p["w"] - y) ** 2)


def _grad_fn(X, Y):
    def grad(w, i, t, q):
        x, y = X[t, i, q], Y[t, i, q]
        return x.T @ ((x @ w - y) / np.float32(B))
    return grad


def _make_engine(scfg, **kw):
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    state = swarm_init(jax.random.PRNGKey(0), scfg,
                       lambda k: {"w": jax.random.normal(k, (D,)) * 0.3},
                       opt.init, same_init=False)
    step = jax.jit(make_swarm_step(scfg, _lin_loss, opt.update,
                                   lambda s: LR, **kw))
    return step, state


def test_binned_equals_sequential_event_replay():
    """Bridge-semantics ground truth: the binned superstep oracle computes
    exactly (bitwise) what the one-event-at-a-time replay computes, in both
    blocking and non-blocking semantics — binning is a reordering of
    commuting operations, not an approximation."""
    tr, sched, _, _ = _trace_and_schedule("gather", n_events=60)
    S = sched.n_supersteps
    X, Y = _data(S)
    grad = _grad_fn(X, Y)
    x0 = np.random.default_rng(3).normal(size=(N, D)).astype(np.float32)
    for nonblocking in (False, True):
        binned = run_superstep_oracle(
            x0, grad, sched.perms, H_MEAN, LR, nonblocking=nonblocking,
            h_schedule=sched.h, masks=sched.mask)
        seq = run_events_oracle(x0, grad, tr.pairs, tr.h, sched.event_bin,
                                LR, nonblocking=nonblocking)
        # compare at each node's final state (the sequential replay logs
        # per event; bin boundaries align at the end of each bin)
        np.testing.assert_array_equal(binned[-1], seq[-1])
        # and at every bin boundary
        for s in range(S):
            last_e = int(np.nonzero(sched.event_bin == s)[0][-1])
            np.testing.assert_array_equal(binned[s], seq[last_e])


@pytest.mark.parametrize("mode,nonblocking,overlap", [
    ("blocking", False, False),
    ("nonblocking", True, False),
    ("overlap", True, True),
])
@pytest.mark.parametrize("impl", ["gather", "ppermute", "ppermute_pool"])
def test_bridged_engine_matches_oracle(impl, mode, nonblocking, overlap):
    """Acceptance: bridged heterogeneous-trace execution matches the
    sequential oracle within fp32 tolerance for all modes × transports."""
    tr, sched, pool, static = _trace_and_schedule(impl)
    S = sched.n_supersteps
    X, Y = _data(S)
    scfg = SwarmConfig(n_nodes=N, H=H_MEAN, h_mode="trace", h_max=H_MAX,
                       nonblocking=nonblocking, overlap=overlap,
                       gossip_impl=impl, track_potential=False)
    kw = {}
    if impl == "ppermute":
        kw = dict(mesh=auto_mesh((1,), ("node",)), node_axes=(),
                  static_pairs=static[0])
    elif impl == "ppermute_pool":
        kw = dict(mesh=auto_mesh((1,), ("node",)), node_axes=(),
                  matching_pool=pool)
    step, state = _make_engine(scfg, **kw)
    x0 = np.asarray(state.params["w"], np.float32)
    key = jax.random.PRNGKey(7)
    traj = []
    for s in range(S):
        perm, h, mask = engine_inputs(sched, s, impl)
        key, sub = jax.random.split(key)
        state, m = step(state, (jnp.asarray(X[s]), jnp.asarray(Y[s])),
                        jnp.asarray(perm), jnp.asarray(h), sub,
                        jnp.asarray(mask))
        traj.append(np.asarray(state.params["w"], np.float32))
    ref = run_superstep_oracle(x0, _grad_fn(X, Y), sched.perms, H_MEAN, LR,
                               nonblocking=nonblocking, h_schedule=sched.h,
                               masks=sched.mask)
    np.testing.assert_allclose(np.stack(traj), ref, rtol=2e-5, atol=2e-5)
    # participation sanity: the engine reports the bin's matched fraction
    assert float(m["matched_frac"]) == pytest.approx(
        sched.mask[S - 1].mean(), abs=1e-6)


def test_overlap_bitwise_equals_nonblocking_on_heterogeneous_trace():
    """The pipelined superstep stays a pure re-scheduling under partial
    participation: bit-identical to plain non-blocking on the same trace."""
    tr, sched, _, _ = _trace_and_schedule("gather")
    S = sched.n_supersteps
    X, Y = _data(S)

    def run(overlap):
        scfg = SwarmConfig(n_nodes=N, H=H_MEAN, h_mode="trace", h_max=H_MAX,
                           nonblocking=True, overlap=overlap,
                           gossip_impl="gather", track_potential=False)
        step, state = _make_engine(scfg)
        key = jax.random.PRNGKey(7)
        out = []
        for s in range(S):
            perm, h, mask = engine_inputs(sched, s, "gather")
            key, sub = jax.random.split(key)
            state, _ = step(state, (jnp.asarray(X[s]), jnp.asarray(Y[s])),
                            jnp.asarray(perm), jnp.asarray(h), sub,
                            jnp.asarray(mask))
            out.append(np.asarray(state.params["w"], np.float32))
        return np.stack(out)

    np.testing.assert_array_equal(run(False), run(True))


def test_quantized_bridged_run_tracks_exact():
    """Quantized gossip on a heterogeneous trace stays inside the
    quantization error envelope of the exact bridged run."""
    tr, sched, _, _ = _trace_and_schedule("gather", n_events=30)
    S = sched.n_supersteps
    X, Y = _data(S)

    def run(quantize):
        scfg = SwarmConfig(n_nodes=N, H=H_MEAN, h_mode="trace", h_max=H_MAX,
                           nonblocking=True, quantize=quantize,
                           gossip_impl="gather", track_potential=False)
        opt = make_optimizer("sgd", lr=0.01, momentum=0.0)
        state = swarm_init(jax.random.PRNGKey(0), scfg,
                           lambda k: {"w": jax.random.normal(k, (D,)) * 0.3},
                           opt.init, same_init=True)
        step = jax.jit(make_swarm_step(scfg, _lin_loss, opt.update,
                                       lambda s: 0.01))
        key = jax.random.PRNGKey(7)
        out = []
        for s in range(S):
            perm, h, mask = engine_inputs(sched, s, "gather")
            key, sub = jax.random.split(key)
            state, _ = step(state, (jnp.asarray(X[s]), jnp.asarray(Y[s])),
                            jnp.asarray(perm), jnp.asarray(h), sub,
                            jnp.asarray(mask))
            out.append(np.asarray(state.params["w"], np.float32))
        return np.stack(out)

    exact, quant = run(False), run(True)
    assert float(np.max(np.abs(exact - quant))) < 0.05


def test_uniform_sync_trace_reproduces_plain_engine_bit_exactly():
    """Acceptance: the uniform-rate (synchronous) profile drives the engine
    to today's unscheduled superstep trajectory BIT-EXACTLY — scheduling is
    a strict generalization, not a behavior change."""
    from repro.core import sample_matching
    g = make_graph("complete", N)
    T = 6
    X, Y = _data(T)
    tr = synchronous_trace(g, T, H=H_MEAN, rng=np.random.default_rng(5))
    sched = bin_trace(tr)
    scfg = SwarmConfig(n_nodes=N, H=H_MEAN, gossip_impl="gather",
                       track_potential=False)
    step, state0 = _make_engine(scfg)

    # plain driver: fresh matchings from the same stream, no mask
    key = jax.random.PRNGKey(7)
    state = state0
    rng = np.random.default_rng(5)
    plain = []
    h = jnp.full((N,), H_MEAN, jnp.int32)
    for t in range(T):
        key, sub = jax.random.split(key)
        state, _ = step(state, (jnp.asarray(X[t][:, :H_MEAN]),
                                jnp.asarray(Y[t][:, :H_MEAN])),
                        jnp.asarray(sample_matching(g, rng)), h, sub)
        plain.append(np.asarray(state.params["w"], np.float32))

    key = jax.random.PRNGKey(7)
    state = state0
    bridged = []
    for s in range(sched.n_supersteps):
        perm, hh, mask = engine_inputs(sched, s, "gather")
        key, sub = jax.random.split(key)
        state, _ = step(state, (jnp.asarray(X[s][:, :H_MEAN]),
                                jnp.asarray(Y[s][:, :H_MEAN])),
                        jnp.asarray(perm), jnp.asarray(hh), sub,
                        jnp.asarray(mask))
        bridged.append(np.asarray(state.params["w"], np.float32))

    np.testing.assert_array_equal(np.stack(plain), np.stack(bridged))
