"""Production-scale hier lowering (DESIGN.md §Hierarchy, ISSUE satellites).

Two subprocess suites (device count locks at jax import, so each runs with
its own XLA_FLAGS fake-device count):

* a 1024-node hier:32 swarm with the codec-compressed comm copy LOWERS on
  a simulated 512-device mesh from ShapeDtypeStructs alone — with a
  per-device state-byte budget assert and the >= 2x resident-prev
  reduction the q8 wire format buys;
* the jaxpr collective counts extend to the hier transports: ONE ppermute
  per wire row group for an inter-group exchange (two quantized: codes +
  scales), and exactly pool_entries x per-branch collectives for the
  two-tier lax.switch pool.
"""
import os
import subprocess
import sys
import textwrap

_DRYRUN_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import bucket as B
    from repro.core.hier import parse_topology
    from repro.core.swarm import SwarmConfig, SwarmState, make_swarm_step
    from repro.optim import make_optimizer
    from repro.quant.codecs import make_codec
    from repro.quant.schemes import ModularQuantConfig

    NN, D, NDEV = 1024, 4096, 512
    assert len(jax.devices()) == NDEV
    mesh = jax.make_mesh((NDEV,), ("node",))
    topo = parse_topology("hier:32", NN)
    scfg = SwarmConfig(n_nodes=NN, H=2, quantize=True, codec="q8",
                       compress_state=True, topology="hier:32",
                       track_potential=False)
    opt = make_optimizer("sgd", lr=0.05, momentum=0.9)

    def loss(p, mb):
        x, y = mb
        return 0.5 * jnp.mean((x @ p["w"] - y) ** 2)

    step = make_swarm_step(scfg, loss, opt.update, lambda s: 0.05)

    codec = make_codec("q8", ModularQuantConfig())
    psds = {"w": jax.ShapeDtypeStruct((NN, D), jnp.float32)}
    layout = B.build_layout(psds, block=codec.block)
    rows = NN * layout.rows_per_node
    prev_sds = codec.wire_layout().wire_sds(rows)
    msds = {"m": {"w": jax.ShapeDtypeStruct((NN, D), jnp.float32)}}
    state_sds = SwarmState(psds, msds, prev_sds,
                           jax.ShapeDtypeStruct((), jnp.int32))
    node = NamedSharding(mesh, P("node"))
    repl = NamedSharding(mesh, P())
    state_sh = SwarmState({"w": node}, {"m": {"w": node}},
                          tuple(node for _ in prev_sds), repl)
    batch_sds = (jax.ShapeDtypeStruct((NN, 2, 1, D), jnp.float32),
                 jax.ShapeDtypeStruct((NN, 2, 1), jnp.float32))
    jitted = jax.jit(step, in_shardings=(state_sh, (node, node),
                                         repl, repl, repl))
    lowered = jitted.lower(state_sds, batch_sds,
                           jax.ShapeDtypeStruct((NN,), jnp.int32),
                           jax.ShapeDtypeStruct((NN,), jnp.int32),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert lowered is not None

    def nbytes(sds_tree):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(sds_tree))

    dense_prev = NN * layout.n_padded * 4
    wire_prev = nbytes(prev_sds)
    total = nbytes(state_sds)
    per_dev = total // NDEV
    print("n_groups", topo.n_groups)
    print("dense_prev", dense_prev)
    print("wire_prev", wire_prev)
    print("per_dev", per_dev)
    # budget: params + momentum + compressed prev, evenly sharded, with
    # <= 35% headroom over the two dense fp32 copies per device
    budget = int((2 * NN * D * 4 / NDEV) * 1.35)
    print("budget", budget)
    print("ok", int(wire_prev * 2 <= dense_prev and per_dev <= budget))
""")


_HIER_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import bucket as B
    from repro.core.hier import parse_topology
    from repro.quant.schemes import ModularQuantConfig

    N = 8
    mesh = jax.make_mesh((N,), ("node",))
    topo = parse_topology("hier:4", N)
    rng = np.random.default_rng(0)
    tree = {"a": jnp.asarray(rng.normal(size=(N, 6, 16)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(N, 7)), jnp.float32)}
    lay = B.build_layout(tree)
    buf = B.pack(lay, tree)
    qcfg = ModularQuantConfig()

    # one INTER-group exchange: lane-aligned cross-group involution
    iperm = topo.inter_group_perm(np.random.default_rng(1))
    ipairs = [(int(iperm[d]), d) for d in range(N) if iperm[d] != d]
    assert (topo.tier_of_pairs(np.asarray(ipairs)) == 1).all()
    with mesh:
        jx = jax.make_jaxpr(lambda b: B.gossip_flat_ppermute(
            b, mesh, ("node",), ipairs))(buf)
        jq = jax.make_jaxpr(lambda b, pb, k: B.gossip_flat_ppermute(
            b, mesh, ("node",), ipairs, quant=qcfg, prev_buf=pb,
            rng=k))(buf, buf, jax.random.PRNGKey(0))
    print("inter_exact", str(jx).count("ppermute"))
    print("inter_quant", str(jq).count("ppermute"))

    # the two-tier pool: P intra matchings + Q inter perms in ONE switch
    pool, tiers = topo.matching_pool(4, seed=3)
    print("pool_entries", len(pool), "n_inter", int((tiers == 1).sum()))
    idx = jnp.zeros((), jnp.int32)
    with mesh:
        jp = jax.make_jaxpr(lambda b, i: B.gossip_flat_ppermute_pool(
            b, mesh, ("node",), pool, i))(buf, idx)
        jpq = jax.make_jaxpr(lambda b, i, pb, k: B.gossip_flat_ppermute_pool(
            b, mesh, ("node",), pool, i, quant=qcfg, prev_buf=pb,
            rng=k))(buf, idx, buf, jax.random.PRNGKey(0))
    print("pool_exact", str(jp).count("ppermute"))
    print("pool_quant", str(jpq).count("ppermute"))
""")


def _run(script):
    # a host-device rehearsal: never reach for an accelerator
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=600,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    pairs = []
    for line in out.stdout.strip().splitlines():
        toks = line.split()
        pairs += list(zip(toks[::2], toks[1::2]))
    return dict(pairs)


def test_1024_node_hier_lowering_on_512_devices():
    """The tentpole's memory claim, proven by lowering: a 1024-node
    hier:32 swarm with the q8-compressed comm copy lowers on a 512-device
    mesh from SDS alone, the wire-format prev is >= 2x smaller than the
    fp32 copy it replaces, and per-device resident state fits the
    two-dense-copies + headroom budget."""
    vals = _run(_DRYRUN_SCRIPT)
    assert vals["n_groups"] == "32"
    assert int(vals["wire_prev"]) * 2 <= int(vals["dense_prev"])
    assert int(vals["per_dev"]) <= int(vals["budget"])
    assert vals["ok"] == "1"


def test_hier_collective_counts():
    """PR 1/PR 5's one-collective-per-wire-row-group guarantee extends to
    the hier primitives: an inter-group exchange is ONE ppermute (two
    quantized: codes + scales), and the two-tier pool switch holds exactly
    pool_entries x per-branch collectives — no hidden extra collective for
    the slow tier."""
    vals = _run(_HIER_COLLECTIVE_SCRIPT)
    assert vals["inter_exact"] == "1"
    assert vals["inter_quant"] == "2"
    entries = int(vals["pool_entries"])
    assert entries == 5 and int(vals["n_inter"]) == 1  # 4 intra + 1 inter
    assert int(vals["pool_exact"]) == entries
    assert int(vals["pool_quant"]) == 2 * entries
