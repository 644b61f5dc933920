"""Baseline ↔ transport parity suite (DESIGN.md §Baselines).

Every baseline now routes its exchange through the unified flat-buffer
transport (core/exchange.py). Three layers of evidence it is faithful:

1. flat == legacy: for each algorithm, the flat-transport trajectory is
   bitwise (fp32 matmul mixing: tolerance) identical to the retained
   ``*_legacy`` per-leaf oracle, across blocking/non-blocking x
   masked/unmasked;
2. bridged == sequential: a masked AD-PSGD run driven by the scheduler
   bridge equals the one-event-at-a-time replay (`run_events_oracle`);
3. the uniform factory: `make_algorithm("swarm")` routes to the swarm
   superstep (same trajectory as direct `make_swarm_step` construction),
   and the capability matrix rejects unsupported combinations at config
   time.

Plus the SGP + q8 regression: push-sum's (X, w) rides the payload as an
extra row group, so `state.prev` is a clean comm copy for the quantizer's
lattice scale proxy — quantized SGP tracks fp32 instead of decoding
against a colliding {"w": ...} tree (the historical bug).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import (CAPABILITIES, make_algorithm,
                              validate_run_config)
from repro.algorithms.sgp import sgp_init_state
from repro.core import GossipTransport, SwarmConfig, make_graph, \
    sample_matching, swarm_init
from repro.core.exchange import make_matching_pool
from repro.optim import make_optimizer
from repro.quant.schemes import ModularQuantConfig

N, D, HID = 8, 6, 16
STEPS, H, B = 6, 2, 4
LR = 0.05


def tiny_init(rng):
    k1, k2 = jax.random.split(rng)
    return {"w1": jax.random.normal(k1, (D, HID)) * 0.3,
            "w2": jax.random.normal(k2, (HID, 1)) * 0.3}


def tiny_loss(p, mb):
    x, y = mb
    return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)


def _data(t, h_slots):
    r = np.random.default_rng(100 + t)
    x = jnp.asarray(r.normal(size=(N, h_slots, B, D)).astype(np.float32))
    y = (x.sum(-1, keepdims=True) > 0).astype(jnp.float32)
    return (x, y)


def _masks(steps, seed=7):
    r = np.random.default_rng(seed)
    return [r.random(N) < 0.6 for _ in range(steps)]


def _build(algo, impl, *, quantize=False, nonblocking=False, seed=0,
           pool=None, quant=None, same_init=False, codec=None):
    from repro.quant.codecs import make_codec
    g = make_graph("complete", N)
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    tr_kw = {}
    if pool is not None:
        from repro.launch.mesh import auto_mesh
        tr_kw = dict(mesh=auto_mesh((1,), ("node",)), node_axes=(),
                     matching_pool=pool)
    if codec is not None:
        tr_kw["codec"] = make_codec(codec, quant)
    tr = GossipTransport(impl, N, quant=quant, **tr_kw)
    kw = dict(loss_fn=tiny_loss, opt_update=opt.update, lr_fn=lambda s: LR,
              n_nodes=N, transport=tr)
    if algo == "localsgd":
        kw["H"] = H
    if algo == "dpsgd":
        kw["graph"] = g
    if algo == "adpsgd":
        kw.update(quantize=quantize, nonblocking=nonblocking)
    if algo == "sgp":
        kw["quantize"] = quantize
    step = jax.jit(make_algorithm(algo, **kw))
    scfg = SwarmConfig(n_nodes=N, H=H, quantize=quantize,
                       nonblocking=nonblocking)
    state = swarm_init(jax.random.PRNGKey(seed), scfg, tiny_init, opt.init,
                       same_init=same_init)
    if algo == "sgp":
        state = sgp_init_state(state, N, quantize)
    return step, state, g


def _run(algo, impl, *, masked=False, quantize=False, nonblocking=False,
         pool=None, quant=None, perms=None, same_init=False, codec=None):
    step, state, g = _build(algo, impl, quantize=quantize,
                            nonblocking=nonblocking, pool=pool, quant=quant,
                            same_init=same_init, codec=codec)
    rng_np = np.random.default_rng(3)
    masks = _masks(STEPS) if masked else [None] * STEPS
    h_slots = H if algo in ("swarm", "localsgd") else 1
    h = jnp.full((N,), h_slots, jnp.int32)
    traj = []
    for t in range(STEPS):
        perm = jnp.asarray(perms[t] if perms is not None
                           else sample_matching(g, rng_np))
        batch = _data(t, h_slots)
        key = jax.random.PRNGKey(1000 + t)
        if masks[t] is None:
            state, m = step(state, batch, perm, h, key)
        else:
            state, m = step(state, batch, perm, h, key,
                            jnp.asarray(masks[t]))
        p = state.params["model"] if algo == "sgp" else state.params
        traj.append(np.concatenate(
            [np.asarray(x, np.float32).reshape(N, -1)
             for x in jax.tree.leaves(p)], axis=1))
        assert np.isfinite(float(m["loss"]))
    return np.stack(traj), state


BASELINES = ["adpsgd", "sgp", "localsgd", "dpsgd", "allreduce"]


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
@pytest.mark.parametrize("algo", BASELINES)
def test_flat_matches_legacy_oracle(algo, masked):
    """The flat-buffer baseline trajectory equals the per-leaf legacy
    oracle — bitwise for the gather/mean exchanges, fp32 tolerance for
    D-PSGD's dense matmul mixing (different contraction order)."""
    flat, _ = _run(algo, "gather", masked=masked)
    legacy, _ = _run(algo, "gather_legacy", masked=masked)
    if algo == "dpsgd":
        np.testing.assert_allclose(flat, legacy, rtol=2e-6, atol=2e-6)
    else:
        np.testing.assert_array_equal(flat, legacy)


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_adpsgd_nonblocking_flat_matches_legacy(masked):
    """Algorithm-2-style stale AD-PSGD: flat == legacy across masks."""
    flat, _ = _run("adpsgd", "gather", masked=masked, nonblocking=True)
    legacy, _ = _run("adpsgd", "gather_legacy", masked=masked,
                     nonblocking=True)
    np.testing.assert_array_equal(flat, legacy)


def test_adpsgd_pool_transport_matches_gather():
    """AD-PSGD on the production ppermute_pool transport (lax.switch over
    static matchings) equals the gather transport fed the same matchings."""
    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=4, seed=0)
    r = np.random.default_rng(5)
    idxs = [int(r.integers(len(pool))) for _ in range(STEPS)]
    pool_perms = [np.full((N,), i, np.int32) for i in idxs]
    gather_perms = [pool[i] for i in idxs]
    a, _ = _run("adpsgd", "ppermute_pool", pool=pool, perms=pool_perms)
    b, _ = _run("adpsgd", "gather", perms=gather_perms)
    np.testing.assert_array_equal(a, b)


def test_adpsgd_quantized_tracks_fp32():
    # common init: the modular scheme's distance criterion assumes the
    # swarm stays concentrated (the paper's protocol starts from consensus)
    qcfg = ModularQuantConfig(safety=16.0)
    fp, _ = _run("adpsgd", "gather", same_init=True)
    q8, _ = _run("adpsgd", "gather", quantize=True, quant=qcfg,
                 same_init=True)
    assert np.isfinite(q8).all()
    assert float(np.max(np.abs(fp - q8))) < 0.05


# ---------------------------------------------------------------------------
# SGP + q8: the state.prev collision regression (push-sum w rides the
# payload; prev is a clean payload-shaped comm copy for the quant proxy)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_sgp_quantized_tracks_fp32(masked):
    qcfg = ModularQuantConfig(safety=16.0)
    fp, sf = _run("sgp", "gather", masked=masked, same_init=True)
    q8, sq = _run("sgp", "gather", masked=masked, quantize=True, quant=qcfg,
                  same_init=True)
    assert np.isfinite(q8).all()
    assert float(np.max(np.abs(fp - q8))) < 0.05
    # push-sum weights stay positive and near 1 through the quantizer
    w = np.asarray(sq.params["w"])
    assert (w > 0.5).all() and (w < 2.0).all()
    # the comm copy is the PAYLOAD tree — w included — not a bare {"w": ...}
    assert set(sq.prev.keys()) == {"model", "w"}


def test_sgp_quantized_prev_is_payload_shaped():
    _, state = _run("sgp", "gather", quantize=True, same_init=True,
                    quant=ModularQuantConfig(safety=16.0))
    flat_params = jax.tree.structure(state.params)
    flat_prev = jax.tree.structure(state.prev)
    assert flat_params == flat_prev


def test_masked_metropolis_doubly_stochastic():
    """Regression: the mask-gated Metropolis matrix must stay symmetric
    doubly stochastic for EVERY mask (dropped edge mass folds back onto
    the diagonal — a leaky W_eff would shrink active nodes' parameters
    every masked round), and equal W at the all-True mask."""
    from repro.algorithms.dpsgd import masked_metropolis, metropolis_weights
    W = jnp.asarray(metropolis_weights(make_graph("complete", N)),
                    jnp.float32)
    r = np.random.default_rng(0)
    for trial in range(8):
        mask = jnp.asarray(r.random(N) < 0.5)
        We = np.asarray(masked_metropolis(W, mask), np.float64)
        np.testing.assert_allclose(We.sum(0), 1.0, atol=1e-6)
        np.testing.assert_allclose(We.sum(1), 1.0, atol=1e-6)
        np.testing.assert_allclose(We, We.T, atol=1e-7)
        assert (We >= -1e-7).all()
        # inactive rows are exactly identity
        for i in np.nonzero(~np.asarray(mask))[0]:
            np.testing.assert_allclose(We[i], np.eye(N)[i], atol=1e-7)
    full = np.asarray(masked_metropolis(W, jnp.ones((N,), bool)))
    np.testing.assert_allclose(full, np.asarray(W), atol=1e-6)


def test_masked_dpsgd_preserves_mean_of_active():
    """The masked mixing round is mass-preserving: the node-axis mean of
    the model is unchanged by the mixing (doubly stochastic W_eff)."""
    from repro.algorithms.dpsgd import masked_metropolis, metropolis_weights
    W = jnp.asarray(metropolis_weights(make_graph("complete", N)),
                    jnp.float32)
    r = np.random.default_rng(1)
    X = jnp.asarray(r.normal(size=(N, 5)).astype(np.float32))
    mask = jnp.asarray([True, True, False, True, False, False, True, True])
    Xm = masked_metropolis(W, mask) @ X
    np.testing.assert_allclose(np.asarray(Xm.mean(0)),
                               np.asarray(X.mean(0)), atol=1e-5)


# ---------------------------------------------------------------------------
# Default-codec (q8) bitwise identity through the codec layer: selecting
# the default codec EXPLICITLY must not perturb a single bit of any
# quantized trajectory, across every algorithm and execution mode the
# matrix allows (the pre-refactor golden for the raw flat gossip lives in
# tests/test_codecs.py::test_q8_flat_gossip_matches_pre_refactor_golden)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
@pytest.mark.parametrize("algo,nonblocking", [
    ("adpsgd", False), ("adpsgd", True), ("sgp", False)])
def test_default_codec_q8_bitwise_baselines(algo, nonblocking, masked):
    qcfg = ModularQuantConfig(safety=16.0)
    kw = dict(masked=masked, quantize=True, quant=qcfg, same_init=True)
    if algo == "adpsgd":
        kw["nonblocking"] = nonblocking
    a, _ = _run(algo, "gather", **kw)
    b, _ = _run(algo, "gather", codec="q8", **kw)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
@pytest.mark.parametrize("mode", ["blocking", "nonblocking", "overlap"])
def test_default_codec_q8_bitwise_swarm(mode, masked):
    from repro.core import make_swarm_step

    def run(codec):
        scfg = SwarmConfig(n_nodes=N, H=H, quantize=True,
                           quant=ModularQuantConfig(safety=16.0),
                           codec=codec, nonblocking=(mode != "blocking"),
                           overlap=(mode == "overlap"),
                           gossip_impl="gather", track_potential=False)
        opt = make_optimizer("sgd", lr=LR, momentum=0.0)
        step = jax.jit(make_swarm_step(scfg, tiny_loss, opt.update,
                                       lambda s: LR))
        state = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init,
                           same_init=True)
        g = make_graph("complete", N)
        rng_np = np.random.default_rng(3)
        masks = _masks(STEPS) if masked else [None] * STEPS
        h = jnp.full((N,), H, jnp.int32)
        traj = []
        for t in range(STEPS):
            perm = jnp.asarray(sample_matching(g, rng_np))
            batch = _data(t, H)
            key = jax.random.PRNGKey(1000 + t)
            args = (state, batch, perm, h, key) + \
                (() if masks[t] is None else (jnp.asarray(masks[t]),))
            state, _ = step(*args)
            traj.append(np.concatenate(
                [np.asarray(x, np.float32).reshape(N, -1)
                 for x in jax.tree.leaves(state.params)], axis=1))
        return np.stack(traj)

    np.testing.assert_array_equal(run(None), run("q8"))


# ---------------------------------------------------------------------------
# Bridged baseline == sequential event replay (scheduler semantics)
# ---------------------------------------------------------------------------


def test_bridged_adpsgd_matches_event_oracle():
    """AD-PSGD driven by the scheduler bridge's (perm, h, mask) equals the
    one-event-at-a-time sequential replay — the baseline inherits the
    bridge's exactness (events in a bin are node-disjoint)."""
    from repro.core.simulator import run_events_oracle
    from repro.sched import RateProfile, StragglerConfig, bin_trace, \
        engine_inputs, generate_trace

    Dlin = 12
    g = make_graph("complete", N)
    tr = generate_trace(g, RateProfile("lognormal", sigma=0.8), 30, H=1,
                        h_max=1, seed=11,
                        straggler=StragglerConfig(fraction=0.25, slowdown=4.0))
    sched = bin_trace(tr)
    S = sched.n_supersteps
    r = np.random.default_rng(21)
    X = r.normal(size=(S, N, 1, B, Dlin)).astype(np.float32)
    Y = r.normal(size=(S, N, 1, B)).astype(np.float32)

    def lin_loss(p, mb):
        x, y = mb
        return 0.5 * jnp.mean((x @ p["w"] - y) ** 2)

    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    step = jax.jit(make_algorithm(
        "adpsgd", loss_fn=lin_loss, opt_update=opt.update,
        lr_fn=lambda s: LR, n_nodes=N,
        transport=GossipTransport("gather", N)))
    scfg = SwarmConfig(n_nodes=N, H=1)
    state = swarm_init(jax.random.PRNGKey(0), scfg,
                       lambda k: {"w": jax.random.normal(k, (Dlin,)) * 0.3},
                       opt.init, same_init=False)
    x0 = np.asarray(state.params["w"], np.float32)
    traj = []
    for s in range(S):
        perm, h, mask = engine_inputs(sched, s, "gather")
        state, _ = step(state, (jnp.asarray(X[s]), jnp.asarray(Y[s])),
                        jnp.asarray(perm), jnp.asarray(h),
                        jax.random.PRNGKey(7 + s), jnp.asarray(mask))
        traj.append(np.asarray(state.params["w"], np.float32))

    def grad(w, i, t, q):
        x, y = X[t, i, q], Y[t, i, q]
        return x.T @ ((x @ w - y) / np.float32(B))

    seq = run_events_oracle(x0, grad, tr.pairs, tr.h, sched.event_bin, LR)
    for s in range(S):
        last_e = int(np.nonzero(sched.event_bin == s)[0][-1])
        np.testing.assert_allclose(traj[s], seq[last_e], rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# Registry: uniform factory + capability matrix
# ---------------------------------------------------------------------------


def test_make_algorithm_routes_swarm():
    """Satellite: make_algorithm('swarm') builds the swarm superstep via
    the same factory signature — identical trajectory to direct
    make_swarm_step construction."""
    from repro.core import make_swarm_step
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    scfg = SwarmConfig(n_nodes=N, H=H, gossip_impl="gather")
    kw = dict(loss_fn=tiny_loss, opt_update=opt.update, lr_fn=lambda s: LR)
    via_registry = jax.jit(make_algorithm("swarm", n_nodes=N, scfg=scfg,
                                          **kw))
    direct = jax.jit(make_swarm_step(scfg, tiny_loss, opt.update,
                                     lambda s: LR))
    g = make_graph("complete", N)
    rng_np = np.random.default_rng(0)
    s1 = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init)
    s2 = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init)
    for t in range(3):
        perm = jnp.asarray(sample_matching(g, rng_np))
        h = jnp.full((N,), H, jnp.int32)
        batch = _data(t, H)
        key = jax.random.PRNGKey(t)
        s1, m1 = via_registry(s1, batch, perm, h, key)
        s2, m2 = direct(s2, batch, perm, h, key)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_algorithm_swarm_from_fields():
    """SwarmConfig fields pass straight through the factory."""
    opt = make_optimizer("sgd", lr=LR, momentum=0.0)
    step = make_algorithm("swarm", loss_fn=tiny_loss, opt_update=opt.update,
                          lr_fn=lambda s: LR, n_nodes=N, H=3,
                          nonblocking=True, gossip_impl="gather")
    assert callable(step)
    with pytest.raises(TypeError):
        make_algorithm("swarm", loss_fn=tiny_loss, opt_update=opt.update,
                       lr_fn=lambda s: LR, n_nodes=N,
                       scfg=SwarmConfig(n_nodes=N), H=3, nonblocking=True)


def test_unknown_algorithm_rejected():
    with pytest.raises(ValueError, match="unknown algorithm"):
        make_algorithm("sgd-3000")


@pytest.mark.parametrize("algo,kw", [
    ("sgp", dict(gossip_impl="ppermute")),
    ("localsgd", dict(quantize=True)),
    ("dpsgd", dict(gossip_impl="ppermute_pool")),
    ("allreduce", dict(nonblocking=True)),
    ("adpsgd", dict(overlap=True)),
])
def test_capability_matrix_rejects(algo, kw):
    with pytest.raises(ValueError, match="DESIGN.md"):
        validate_run_config(algo, **kw)


def test_capability_matrix_covers_registry():
    from repro.algorithms import ALGORITHMS
    assert set(CAPABILITIES) == set(ALGORITHMS)
    for algo, caps in CAPABILITIES.items():
        # every baseline accepts a scheduler trace (the acceptance bar:
        # no second-class citizens under --rate-profile)
        assert caps.sched, algo
        assert "gather" in caps.transports, algo
