"""Scan-driven superstep driver (core/scan.py; DESIGN.md §Fusion).

The chunked `lax.scan` driver must be a pure re-packaging of the per-step
driver — bitwise identical trajectories and metrics for every
(mode × transport × codec) combination the engine supports, including the
scheduler bridge's masked partial-participation supersteps. Plus the
donation contract (the chunk jit actually aliases the SwarmState/key
buffers, and donation does not corrupt the codec checkpoint state) and
mid-run chunk-boundary checkpoint/resume bit-exactness for the stateful
codecs (q8 comm copy, top-k error-feedback residual).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core import (SwarmConfig, make_graph, make_superstep_scan,
                        make_swarm_step, sample_matching, swarm_init)
from repro.core.swarm import (codec_checkpoint_tree, make_matching_pool,
                              restore_codec_state)
from repro.launch.mesh import auto_mesh
from repro.optim import make_optimizer
from repro.quant.schemes import ModularQuantConfig

N, D, H, B, T = 8, 12, 2, 4, 6
LR = 0.05
QCFG = ModularQuantConfig(safety=16.0)


def donation_alias_count(lowered) -> int:
    """How many input buffers a lowered computation actually aliases to
    outputs (donation applied, not just requested). StableHLO marks inputs
    aliased to a fixed output `tf.aliasing_output` and donors whose
    aliasing is decided at compile time `jax.buffer_donor` — count both."""
    txt = lowered.as_text()
    return txt.count("tf.aliasing_output") + txt.count("jax.buffer_donor")


def _data(S, seed=42, h_slots=H):
    r = np.random.default_rng(seed)
    X = r.normal(size=(S, N, h_slots, B, D)).astype(np.float32)
    Y = r.normal(size=(S, N, h_slots, B)).astype(np.float32)
    return X, Y


def _lin_loss(p, mb):
    x, y = mb
    return 0.5 * jnp.mean((x @ p["w"] - y) ** 2)


def _make_engine(scfg, momentum=0.9, **kw):
    opt = make_optimizer("sgd", lr=LR, momentum=momentum)
    state = swarm_init(jax.random.PRNGKey(0), scfg,
                       lambda k: {"w": jax.random.normal(k, (D,)) * 0.3},
                       opt.init, same_init=False)
    step = jax.jit(make_swarm_step(scfg, _lin_loss, opt.update,
                                   lambda s: LR, **kw))
    return step, state


def _run_per_step(step, state, X, Y, perms, hs, masks=None,
                  key=None):
    """The per-step driver's host loop, verbatim: eager key split, one
    dispatch per superstep."""
    key = jax.random.PRNGKey(7) if key is None else key
    metrics = []
    for t in range(len(perms)):
        key, sub = jax.random.split(key)
        args = (state, (jnp.asarray(X[t]), jnp.asarray(Y[t])),
                jnp.asarray(perms[t]), jnp.asarray(hs[t]), sub)
        if masks is not None:
            state, m = step(*args, jnp.asarray(masks[t]))
        else:
            state, m = step(*args)
        metrics.append(jax.device_get(m))
    return state, metrics


def _run_scan(step, state, X, Y, perms, hs, masks=None, chunks=(T,),
              donate=True, key=None):
    chunk_fn = make_superstep_scan(step, with_mask=masks is not None,
                                   donate=donate)
    key = jax.random.PRNGKey(7) if key is None else key
    ms_all, t = [], 0
    for K in chunks:
        args = (state, key,
                (jnp.asarray(X[t:t + K]), jnp.asarray(Y[t:t + K])),
                jnp.asarray(np.asarray(perms[t:t + K])),
                jnp.asarray(np.asarray(hs[t:t + K])))
        if masks is not None:
            args += (jnp.asarray(np.asarray(masks[t:t + K])),)
        state, key, ms = chunk_fn(*args)
        ms_all.append(jax.device_get(ms))
        t += K
    assert t == len(perms)
    stacked = {k: np.concatenate([m[k] if np.ndim(m[k]) else m[k][None]
                                  for m in ms_all])
               for k in ms_all[0]}
    return state, stacked, key


def _assert_states_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for name in ("prev", "residual", "opt", "inflight"):
        xa, xb = getattr(a, name), getattr(b, name)
        assert (xa is None) == (xb is None), name
        for x, y in zip(jax.tree.leaves(xa), jax.tree.leaves(xb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _gather_inputs(S, seed=123):
    g = make_graph("complete", N)
    r = np.random.default_rng(seed)
    perms = np.stack([sample_matching(g, r) for _ in range(S)])
    hs = np.full((S, N), H, np.int32)
    return perms, hs


COMBOS = [
    ("blocking_fp32_gather", dict(), None),
    ("nonblocking_fp32_gather", dict(nonblocking=True), None),
    ("blocking_q8_gather", dict(quantize=True), None),
    ("nonblocking_q4_gather",
     dict(nonblocking=True, quantize=True, codec="q4"), None),
    ("nonblocking_topk_gather",
     dict(nonblocking=True, quantize=True, codec="topk:0.25"), None),
    ("overlap_q8_gather",
     dict(nonblocking=True, overlap=True, quantize=True), None),
    ("blocking_q8_ppermute", dict(quantize=True), "ppermute"),
    ("blocking_q8_ppermute_pool", dict(quantize=True), "ppermute_pool"),
]


@pytest.mark.parametrize("name,skw,impl",
                         COMBOS, ids=[c[0] for c in COMBOS])
def test_scan_bitwise_matches_per_step(name, skw, impl):
    """The tentpole guardrail: scan driver == per-step driver, bitwise, on
    final state AND per-superstep metrics, for every mode × transport ×
    codec — chunked unevenly (4+2) to cover the partial-last-chunk
    recompile."""
    X, Y = _data(T)
    g = make_graph("complete", N)
    kw = {}
    if impl == "ppermute":
        pool = make_matching_pool(g, K=4, seed=0)
        static = np.asarray(pool[1], np.int32)
        pairs = [(int(static[d]), d) for d in range(N) if static[d] != d]
        kw = dict(mesh=auto_mesh((1,), ("node",)), node_axes=(),
                  static_pairs=pairs)
        perms = np.stack([static] * T)
        hs = np.full((T, N), H, np.int32)
    elif impl == "ppermute_pool":
        pool = make_matching_pool(g, K=4, seed=0)
        kw = dict(mesh=auto_mesh((1,), ("node",)), node_axes=(),
                  matching_pool=pool)
        r = np.random.default_rng(5)
        perms = np.stack([np.full((N,), int(r.integers(len(pool))), np.int32)
                          for _ in range(T)])
        hs = np.full((T, N), H, np.int32)
    else:
        impl = "gather"
        perms, hs = _gather_inputs(T)
    scfg = SwarmConfig(n_nodes=N, H=H, gossip_impl=impl, quant=QCFG,
                       track_potential=False, **skw)

    step, state = _make_engine(scfg, **kw)
    ref_state, ref_ms = _run_per_step(step, state, X, Y, perms, hs)

    step2, state2 = _make_engine(scfg, **kw)
    scan_state, scan_ms, _ = _run_scan(step2, state2, X, Y, perms, hs,
                                       chunks=(4, 2))

    _assert_states_bitwise(ref_state, scan_state)
    for t in range(T):
        for k in ("loss", "matched_frac"):
            np.testing.assert_array_equal(np.float32(ref_ms[t][k]),
                                          np.float32(scan_ms[k][t]))


def test_scan_sched_masked_bitwise():
    """Scheduler-bridge case: heterogeneous trace, masked partial
    supersteps, variable per-node h — stacked_engine_inputs rows must
    equal engine_inputs per step, and the scan driver must reproduce the
    per-step bridged trajectory bitwise."""
    from repro.sched import (RateProfile, bin_trace, engine_inputs,
                             generate_trace, stacked_engine_inputs)
    g = make_graph("complete", N)
    h_max = 4
    tr = generate_trace(g, RateProfile("lognormal", sigma=0.8), 40,
                        H=H, h_max=h_max, h_mode="rate", seed=13)
    sched = bin_trace(tr)
    S = sched.n_supersteps
    perms, hs, masks = stacked_engine_inputs(sched, 0, S, "gather")
    for s in range(S):
        p, h, m = engine_inputs(sched, s, "gather")
        np.testing.assert_array_equal(perms[s], p)
        np.testing.assert_array_equal(hs[s], h)
        np.testing.assert_array_equal(masks[s], m)

    X, Y = _data(S, seed=21, h_slots=h_max)
    scfg = SwarmConfig(n_nodes=N, H=H, h_mode="trace", h_max=h_max,
                       nonblocking=True, quantize=True, quant=QCFG,
                       gossip_impl="gather", track_potential=False)
    step, state = _make_engine(scfg)
    ref_state, ref_ms = _run_per_step(step, state, X, Y, perms, hs,
                                      masks=masks)
    step2, state2 = _make_engine(scfg)
    scan_state, scan_ms, _ = _run_scan(step2, state2, X, Y, perms, hs,
                                       masks=masks, chunks=(S // 2,
                                                            S - S // 2))
    _assert_states_bitwise(ref_state, scan_state)
    for t in range(S):
        np.testing.assert_array_equal(np.float32(ref_ms[t]["loss"]),
                                      np.float32(scan_ms["loss"][t]))


def test_stacked_engine_inputs_pool_broadcast():
    """Pool-transport schedules stack the broadcast pool index as perm —
    row t of the stack == engine_inputs(sched, t)."""
    from repro.sched import (RateProfile, bin_trace, engine_inputs,
                             generate_trace, pool_edges,
                             stacked_engine_inputs)
    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=4, seed=0)
    tr = generate_trace(g, RateProfile("lognormal", sigma=0.8), 30,
                        H=H, h_max=4, h_mode="rate", seed=11,
                        edges=pool_edges(pool))
    sched = bin_trace(tr, pool=pool)
    perms, hs, masks = stacked_engine_inputs(sched, 0, None,
                                             "ppermute_pool")
    assert perms.shape == (sched.n_supersteps, N)
    for s in range(sched.n_supersteps):
        p, h, m = engine_inputs(sched, s, "ppermute_pool")
        np.testing.assert_array_equal(perms[s], p)
        np.testing.assert_array_equal(hs[s], h)
        np.testing.assert_array_equal(masks[s], m)


def test_chunk_donation_actually_aliases():
    """Donation regression (satellite): the chunk jit must alias the
    donated SwarmState/key input buffers to outputs — asserted on the
    lowered module's aliasing markers, with the compiled memory stats
    cross-checked. And the donated inputs must actually die."""
    X, Y = _data(4)
    perms, hs = _gather_inputs(4)
    scfg = SwarmConfig(n_nodes=N, H=H, quantize=True, quant=QCFG,
                       gossip_impl="gather", track_potential=False)
    step, state = _make_engine(scfg)
    chunk_fn = make_superstep_scan(step, with_mask=False, donate=True)
    key = jax.random.PRNGKey(7)
    args = (state, key, (jnp.asarray(X), jnp.asarray(Y)),
            jnp.asarray(perms), jnp.asarray(hs))
    lowered = chunk_fn.lower(*args)
    n_donated = len(jax.tree.leaves(state)) + 1   # + the rng key
    assert donation_alias_count(lowered) >= n_donated, \
        "donated superstep inputs are not aliased in the lowered module"
    assert lowered.compile().memory_analysis().alias_size_in_bytes > 0

    new_state, new_key, _ = chunk_fn(*args)
    for x in jax.tree.leaves(state):
        if hasattr(x, "is_deleted"):
            assert x.is_deleted(), "donated input buffer still alive"
    # the undonated variant must NOT invalidate its inputs
    step2, state2 = _make_engine(scfg)
    chunk_nd = make_superstep_scan(step2, with_mask=False, donate=False)
    nd_state, _, _ = chunk_nd(state2, jax.random.PRNGKey(7),
                              (jnp.asarray(X), jnp.asarray(Y)),
                              jnp.asarray(perms), jnp.asarray(hs))
    assert all(not (hasattr(x, "is_deleted") and x.is_deleted())
               for x in jax.tree.leaves(state2))
    # donation is a pure memory optimization: same values out
    _assert_states_bitwise(new_state, nd_state)


def test_donation_does_not_corrupt_codec_checkpoint(tmp_path):
    """codec_checkpoint_tree read off a donated-chunk output must
    round-trip through save/load bit-exactly (the donated INPUT buffers
    are dead, but the output state is fresh and persistable)."""
    X, Y = _data(4)
    perms, hs = _gather_inputs(4)
    scfg = SwarmConfig(n_nodes=N, H=H, quantize=True, quant=QCFG,
                       codec="topk:0.25", gossip_impl="gather",
                       track_potential=False)
    step, state = _make_engine(scfg, momentum=0.0)
    state, _, _ = _run_scan(step, state, X, Y, perms, hs, chunks=(4,),
                            donate=True)
    tree = codec_checkpoint_tree(state)
    assert set(tree) == {"params", "prev", "residual"}
    ck = str(tmp_path / "donated_ck")
    save_checkpoint(ck, jax.device_get(tree), {"codec": "topk:0.25"})
    loaded = load_checkpoint(ck, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("codec", ["q8", "topk:0.25"])
def test_chunked_scan_checkpoint_resume_bitexact(codec, tmp_path):
    """Chunk boundaries are exact checkpoint points: save (codec state +
    rng key) after chunk 1, restore into a fresh engine, continue — the
    resumed run equals the unbroken run bit for bit (the top-k residual
    rides the scan carry and must survive the round trip)."""
    X, Y = _data(4, seed=77)
    perms, hs = _gather_inputs(4, seed=31)
    scfg = SwarmConfig(n_nodes=N, H=H, quantize=True, quant=QCFG,
                       codec=codec, gossip_impl="gather",
                       track_potential=False)

    step, state = _make_engine(scfg, momentum=0.0)
    full_state, _, _ = _run_scan(step, state, X, Y, perms, hs,
                                 chunks=(2, 2))

    step2, s0 = _make_engine(scfg, momentum=0.0)
    mid_state, _, mid_key = _run_scan(step2, s0, X[:2], Y[:2], perms[:2],
                                      hs[:2], chunks=(2,))
    tree = codec_checkpoint_tree(mid_state)
    tree["rng_key"] = np.asarray(jax.device_get(mid_key))
    ck = str(tmp_path / f"scan_ck_{codec.replace(':', '_')}")
    save_checkpoint(ck, jax.device_get(tree), {"codec": codec})

    step3, fresh = _make_engine(scfg, momentum=0.0)
    loaded = load_checkpoint(ck, tree)
    key = jnp.asarray(loaded.pop("rng_key"))
    restored = restore_codec_state(fresh, loaded)
    resumed_state, _, _ = _run_scan(step3, restored, X[2:], Y[2:],
                                    perms[2:], hs[2:], chunks=(2,),
                                    key=key)
    for a, b in zip(jax.tree.leaves(full_state.params),
                    jax.tree.leaves(resumed_state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if full_state.residual is not None:
        np.testing.assert_array_equal(np.asarray(full_state.residual),
                                      np.asarray(resumed_state.residual))
