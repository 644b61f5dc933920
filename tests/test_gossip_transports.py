"""Gossip transports agree: gather (naive GSPMD), ppermute (shard_map), and
ppermute_pool (lax.switch over static matchings) produce identical averaging
on the same matching; the pool honors its masks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_graph
from repro.core.swarm import (SwarmConfig, SwarmState, gossip_exact,
                              gossip_ppermute, gossip_ppermute_pool,
                              make_matching_pool, make_swarm_step, swarm_init)
from repro.optim import make_optimizer

N = 4


def _mesh():
    # single CPU device: trivial 1x1 mesh — shard_map still exercises the
    # ppermute code path (self-permutes)
    from repro.launch.mesh import auto_mesh
    return auto_mesh((1, 1), ("data", "model"))


def test_matching_pool_valid():
    g = make_graph("complete", 8)
    pool = make_matching_pool(g, K=6, seed=1)
    assert len(pool) == 6
    for p in pool:
        assert (p[p] == np.arange(8)).all()


def test_pool_switch_matches_gather():
    from jax.sharding import PartitionSpec as P
    mesh = _mesh()
    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=3, seed=0)
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(N, 8)), jnp.float32)}
    specs = {"w": P(None, None)}
    with mesh:
        for idx in range(3):
            out_pool = gossip_ppermute_pool(
                params, specs, mesh, (), pool, jnp.asarray(idx))
            perm = jnp.asarray(pool[idx])
            out_ref = gossip_exact(params, perm, perm != jnp.arange(N))
            np.testing.assert_allclose(np.asarray(out_pool["w"]),
                                       np.asarray(out_ref["w"]), atol=1e-6)


def test_pool_superstep_trains():
    mesh = _mesh()
    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=4, seed=0)
    from jax.sharding import PartitionSpec as P

    def tiny_init(rng):
        return {"w": jax.random.normal(rng, (6, 1)) * 0.3}

    def tiny_loss(p, mb):
        x, y = mb
        return jnp.mean((x @ p["w"] - y) ** 2)

    opt = make_optimizer("sgd", lr=0.1, momentum=0.0)
    scfg = SwarmConfig(n_nodes=N, H=2, gossip_impl="ppermute_pool")
    specs = jax.tree.map(lambda _: P(None, None, None),
                         {"w": 0})
    with mesh:
        step = make_swarm_step(scfg, tiny_loss, opt.update, lambda s: 0.1,
                               mesh=mesh, param_specs=specs, node_axes=(),
                               matching_pool=pool)
        state = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init)
        step = jax.jit(step)
        losses = []
        for t in range(25):
            r = np.random.default_rng(t)
            x = jnp.asarray(r.normal(size=(N, 2, 8, 6)).astype(np.float32))
            y = x.sum(-1, keepdims=True)
            idx = jnp.asarray([t % 4] * N, jnp.int32)  # pool index rides perm
            h = jnp.full((N,), 2, jnp.int32)
            state, m = step(state, (x, y), idx, h, jax.random.PRNGKey(t))
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.5 * losses[0]
