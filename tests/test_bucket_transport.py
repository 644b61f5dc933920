"""Flat-buffer gossip transport (core/bucket.py): pack/unpack roundtrip,
flat ≡ legacy per-leaf gossip (bit-for-bit exact / tolerance quantized),
payload-byte accounting, and the one-collective-per-payload-tensor claim
(jaxpr inspection on a multi-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucket as B
from repro.core import make_graph, make_swarm_step, sample_matching, swarm_init
from repro.core.swarm import (SwarmConfig, gossip_exact, gossip_quantized,
                              sample_h_counts)
from repro.optim import make_optimizer
from repro.quant.schemes import ModularQuantConfig, payload_bytes

N = 8


def _mixed_tree(rng, n=N, spread=0.01):
    """Node-stacked tree, mixed dtypes/shapes, nodes concentrated (small Γ)
    so the quantized decode distance criterion holds."""
    base = {"emb": rng.normal(size=(33, 16)),
            "w": {"in": rng.normal(size=(6, 16)),
                  "out": rng.normal(size=(16, 1))},
            "scale": rng.normal(size=(5,))}
    noise = lambda v: v[None] + spread * rng.normal(size=(n,) + v.shape)  # noqa: E731
    return {"emb": jnp.asarray(noise(base["emb"]), jnp.bfloat16),
            "w": {"in": jnp.asarray(noise(base["w"]["in"]), jnp.float32),
                  "out": jnp.asarray(noise(base["w"]["out"]), jnp.float32)},
            "scale": jnp.asarray(noise(base["scale"]), jnp.float32)}


def test_roundtrip_identity_mixed_dtypes():
    tree = _mixed_tree(np.random.default_rng(0))
    layout = B.build_layout(tree)
    back = B.unpack(layout, B.pack(layout, tree))
    for (pa, a), (pb, b) in zip(jax.tree_util.tree_leaves_with_path(tree),
                                jax.tree_util.tree_leaves_with_path(back)):
        assert a.dtype == b.dtype and a.shape == b.shape, pa
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=str(pa))


def test_layout_alignment_and_cache():
    tree = _mixed_tree(np.random.default_rng(1))
    layout = B.build_layout(tree)
    assert layout.n_padded % (layout.block * layout.tile_rows) == 0
    for off, seg in zip(layout.offsets, layout.seg_sizes):
        assert off % layout.block == 0 and seg % layout.block == 0
    assert B.build_layout(tree) is layout  # cached per structure


def test_flat_exact_matches_legacy_bitwise():
    tree = _mixed_tree(np.random.default_rng(2))
    layout = B.build_layout(tree)
    perm = jnp.asarray([1, 0, 3, 2, 4, 5, 7, 6])
    matched = perm != jnp.arange(N)
    flat = B.unpack(layout, B.gossip_flat_exact(B.pack(layout, tree), perm,
                                                matched))
    ref = gossip_exact(tree, perm, matched)
    for a, b in zip(jax.tree.leaves(flat), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_flat_quantized_matches_legacy_within_tolerance():
    rng = np.random.default_rng(3)
    tree = _mixed_tree(rng)
    prev = jax.tree.map(
        lambda x: (x.astype(jnp.float32) +
                   0.005 * jnp.asarray(rng.normal(size=x.shape),
                                       jnp.float32)).astype(x.dtype), tree)
    qcfg = ModularQuantConfig(safety=16.0)
    layout = B.build_layout(tree, block=qcfg.block)
    perm = jnp.asarray([1, 0, 3, 2, 6, 7, 4, 5])
    matched = perm != jnp.arange(N)
    key = jax.random.PRNGKey(0)
    flat = B.unpack(layout, B.gossip_flat_quantized(
        qcfg, B.pack(layout, tree), B.pack(layout, prev), perm, matched, key))
    leg = gossip_quantized(qcfg, tree, prev, perm, matched, key)
    exact = gossip_exact(tree, perm, matched)
    # both transports land within the quantization error bound of the exact
    # average (they use different stochastic-rounding draws, so compare each
    # to the exact oracle, not to each other)
    for f, l, e in zip(jax.tree.leaves(flat), jax.tree.leaves(leg),
                       jax.tree.leaves(exact)):
        f, l, e = (np.asarray(a, np.float32) for a in (f, l, e))
        tol = 0.05  # ~ safety * max|x - prev| / 2^(bits-1) headroom
        assert np.abs(f - e).max() < tol
        assert np.abs(l - e).max() < tol


def test_payload_bytes_matches_packed_arrays():
    tree = _mixed_tree(np.random.default_rng(4))
    qcfg = ModularQuantConfig()
    layout = B.build_layout(tree, block=qcfg.block)
    buf = B.pack(layout, tree)
    # exact mode: fp32 buffer per node
    assert buf.nbytes // layout.n_nodes == layout.payload_num_bytes()
    # quantized mode: uint8 q + fp32 scales per node == the analytic formula
    q, s = B.encode_flat(qcfg, buf, buf, jax.random.PRNGKey(0))
    per_node = (q.nbytes + s.nbytes) // layout.n_nodes
    assert per_node == layout.payload_num_bytes(qcfg)
    assert per_node == payload_bytes(qcfg, layout.n_padded)


def test_superstep_flat_matches_legacy_end_to_end():
    """Default (flat) and *_legacy supersteps produce bit-identical states
    in exact mode over several supersteps."""
    def tiny_init(rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (6, 16)) * 0.3,
                "w2": jax.random.normal(k2, (16, 1)) * 0.3}

    def tiny_loss(p, mb):
        x, y = mb
        return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)

    def make_batch(t, h=2, b=8):
        r = np.random.default_rng(t)
        x = r.normal(size=(N, h, b, 6)).astype(np.float32)
        y = (x.sum(-1, keepdims=True) > 0).astype(np.float32)
        return jnp.asarray(x), jnp.asarray(y)

    def run(impl):
        g = make_graph("complete", N)
        opt = make_optimizer("sgd", lr=0.05, momentum=0.0)
        scfg = SwarmConfig(n_nodes=N, H=2, gossip_impl=impl)
        state = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init, opt.init)
        step = jax.jit(make_swarm_step(scfg, tiny_loss, opt.update,
                                       lambda s: 0.05))
        rng_np = np.random.default_rng(0)
        key = jax.random.PRNGKey(2)
        for t in range(8):
            key, sub = jax.random.split(key)
            state, _ = step(state, make_batch(t),
                            jnp.asarray(sample_matching(g, rng_np)),
                            jnp.asarray(sample_h_counts(scfg, rng_np)), sub)
        return state

    flat, leg = run("gather"), run("gather_legacy")
    for a, b in zip(jax.tree.leaves(flat.params), jax.tree.leaves(leg.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_PPERMUTE_COUNT_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import bucket as B
    from repro.core.swarm import gossip_ppermute
    from repro.quant.schemes import ModularQuantConfig

    N = 8
    mesh = jax.make_mesh((N,), ("node",))
    rng = np.random.default_rng(0)
    tree = {"a": jnp.asarray(rng.normal(size=(N, 6, 16)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(N, 7)), jnp.float32),
            "c": jnp.asarray(rng.normal(size=(N, 3, 5)), jnp.float32)}
    lay = B.build_layout(tree)
    buf = B.pack(lay, tree)
    pairs = [(0, 1), (1, 0), (2, 3), (3, 2)]
    qcfg = ModularQuantConfig()
    with mesh:
        jx = jax.make_jaxpr(lambda b: B.gossip_flat_ppermute(
            b, mesh, ("node",), pairs))(buf)
        jq = jax.make_jaxpr(lambda b, pb, k: B.gossip_flat_ppermute(
            b, mesh, ("node",), pairs, quant=qcfg, prev_buf=pb, rng=k))(
            buf, buf, jax.random.PRNGKey(0))
        specs = {k: P(*((None,) * tree[k].ndim)) for k in tree}
        jl = jax.make_jaxpr(lambda t: gossip_ppermute(
            t, specs, mesh, ("node",), pairs))(tree)
    print("flat_exact", str(jx).count("ppermute"))
    print("flat_quant", str(jq).count("ppermute"))
    print("legacy_exact", str(jl).count("ppermute"))
""")


def test_single_ppermute_per_payload_tensor():
    """The flat transport issues EXACTLY ONE ppermute per payload tensor
    (1 exact: the fp32 buffer; 2 quantized: uint8 q + fp32 scales) while the
    per-leaf legacy path issues one per leaf. Counted in the jaxpr on an
    8-fake-device subprocess (device count is locked at jax import)."""
    out = subprocess.run([sys.executable, "-c", _PPERMUTE_COUNT_SCRIPT],
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    counts = dict(line.split() for line in out.stdout.strip().splitlines())
    assert counts["flat_exact"] == "1"
    assert counts["flat_quant"] == "2"
    assert counts["legacy_exact"] == "3"  # one per leaf


def test_pool_average_momentum_uses_actual_partners():
    """In ppermute_pool mode `perm` carries the pool index; momentum
    averaging must still pair each node with its ACTUAL matched partner
    (regression: it used to index momenta by the pool index itself)."""
    from jax.sharding import PartitionSpec as P
    from repro.core.swarm import make_matching_pool
    from repro.launch.mesh import auto_mesh

    def tiny_init(rng):
        return {"w": jax.random.normal(rng, (4, 3)) * 0.3}

    def tiny_loss(p, mb):
        x, y = mb
        return jnp.mean((x @ p["w"] - y) ** 2)

    def batch(t):
        r = np.random.default_rng(t)
        x = jnp.asarray(r.normal(size=(N, 2, 8, 4)), jnp.float32)
        return x, x.sum(-1, keepdims=True)

    g = make_graph("complete", N)
    pool = make_matching_pool(g, K=3, seed=0)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.9)
    mesh = auto_mesh((1,), ("node",))
    idx = 1

    def run(impl):
        kw = {}
        if impl == "ppermute_pool":
            kw = dict(mesh=mesh, param_specs={"w": P(None, None, None)},
                      node_axes=(), matching_pool=pool)
            perm = jnp.asarray([idx] * N, jnp.int32)   # pool index rides perm
        else:
            perm = jnp.asarray(pool[idx])              # the same matching
        scfg = SwarmConfig(n_nodes=N, H=2, gossip_impl=impl,
                           average_momentum=True)
        with mesh:
            step = jax.jit(make_swarm_step(scfg, tiny_loss, opt.update,
                                           lambda s: 0.1, **kw))
            state = swarm_init(jax.random.PRNGKey(0), scfg, tiny_init,
                               opt.init)
            for t in range(3):
                state, _ = step(state, batch(t), perm,
                                jnp.full((N,), 2, jnp.int32),
                                jax.random.PRNGKey(t))
        return state

    a, b = run("ppermute_pool"), run("gather")
    for x, y in zip(jax.tree.leaves(a.opt), jax.tree.leaves(b.opt)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_quantized_flat_default_runs_through_kernel_ops(monkeypatch):
    """The default quantized gossip path must call the kernels/ops.py
    wrappers (quantize_mod encode, decode_avg fused decode+avg)."""
    import repro.kernels.ops as K
    calls = []
    orig_q, orig_d = K.quantize_mod, K.decode_avg
    monkeypatch.setattr(K, "quantize_mod",
                        lambda *a, **k: calls.append("q") or orig_q(*a, **k))
    monkeypatch.setattr(K, "decode_avg",
                        lambda *a, **k: calls.append("d") or orig_d(*a, **k))
    rng = np.random.default_rng(5)
    tree = _mixed_tree(rng)
    qcfg = ModularQuantConfig(safety=16.0)
    layout = B.build_layout(tree, block=qcfg.block)
    buf = B.pack(layout, tree)
    perm = jnp.asarray([1, 0, 3, 2, 4, 5, 7, 6])
    B.gossip_flat_quantized(qcfg, buf, buf, perm, perm != jnp.arange(N),
                            jax.random.PRNGKey(0))
    assert calls == ["q", "d"]


def test_decode_avg_matched_mask_fused():
    """decode_avg(matched=...) returns y untouched on masked-out rows, for
    both the ref oracle and the Pallas interpreter backend."""
    from repro.kernels import decode_avg, quantize_mod
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(16, 256)), jnp.float32)
    y = x + jnp.asarray(0.01 * rng.normal(size=x.shape), jnp.float32)
    u = jnp.asarray(rng.uniform(size=x.shape), jnp.float32)
    q, s, _ = quantize_mod(x, y, u, backend="ref")
    matched = jnp.asarray(rng.integers(0, 2, size=(16,)).astype(bool))
    for backend in ("ref", "interpret"):
        out = decode_avg(q, s, y, matched=matched, backend=backend)
        out = np.asarray(out)
        ym = np.asarray(y)
        np.testing.assert_array_equal(out[~np.asarray(matched)],
                                      ym[~np.asarray(matched)])
        avg = np.asarray(decode_avg(q, s, y, backend=backend))
        np.testing.assert_allclose(out[np.asarray(matched)],
                                   avg[np.asarray(matched)], atol=1e-6)
