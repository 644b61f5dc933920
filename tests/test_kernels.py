"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
ref.py oracle, swept over shapes/dtypes with hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, strategies as st
except ImportError:
    # only the @given property tests need hypothesis — keep the direct
    # Pallas-vs-optim and block-alignment tests running without it
    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _AnyStrategy()

    def given(*a, **k):
        return lambda f: pytest.mark.skip(
            reason="hypothesis not installed")(f)

from repro.kernels import decode_avg, quantize_mod, sgd_fused_update
from repro.kernels.ref import decode_avg_ref, quantize_mod_ref, sgd_update_ref

SIZES = st.integers(min_value=1, max_value=5000)
DTYPES = st.sampled_from([jnp.float32, jnp.bfloat16])


def _rand(rng, n, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=(n,)) * scale).astype(dtype)


@given(n=SIZES, seed=st.integers(0, 2**31 - 1))
def test_quantize_interpret_matches_ref(n, seed):
    rng = np.random.default_rng(seed)
    x = _rand(rng, n)
    ref = x + _rand(rng, n, scale=0.01)
    u = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    q1, s1, _ = quantize_mod(x, ref, u, backend="ref")
    q2, s2, _ = quantize_mod(x, ref, u, backend="interpret")
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@given(n=SIZES, seed=st.integers(0, 2**31 - 1), dtype=DTYPES)
def test_decode_avg_interpret_matches_ref(n, seed, dtype):
    rng = np.random.default_rng(seed)
    x = _rand(rng, n, dtype)
    y = (x.astype(jnp.float32) + _rand(rng, n, scale=0.01)).astype(dtype)
    u = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    q, s, _ = quantize_mod(x, y, u, backend="ref")
    o1 = decode_avg(q, s, y, backend="ref")
    o2 = decode_avg(q, s, y, backend="interpret")
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=1e-6)


@given(n=SIZES, seed=st.integers(0, 2**31 - 1),
       mu=st.floats(0.0, 0.99), wd=st.floats(0.0, 0.1),
       nesterov=st.booleans())
def test_sgd_interpret_matches_ref(n, seed, mu, wd, nesterov):
    rng = np.random.default_rng(seed)
    p, g, m = _rand(rng, n), _rand(rng, n), _rand(rng, n, scale=0.1)
    a = sgd_fused_update(p, g, m, lr=0.1, mu=mu, wd=wd, nesterov=nesterov,
                         backend="ref")
    b = sgd_fused_update(p, g, m, lr=0.1, mu=mu, wd=wd, nesterov=nesterov,
                         backend="interpret")
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


def test_sgd_kernel_matches_optim_module():
    """The fused kernel implements exactly optim.sgd's reference update."""
    from repro.optim.sgd import SGDConfig, sgd_init, sgd_update
    rng = np.random.default_rng(0)
    p = {"a": _rand(rng, 300), "b": _rand(rng, 77)}
    g = {"a": _rand(rng, 300), "b": _rand(rng, 77)}
    cfg = SGDConfig(lr=0.2, momentum=0.9, weight_decay=0.01)
    st0 = sgd_init(cfg, p)
    p_ref, st_ref = sgd_update(cfg, p, g, st0)
    for key in p:
        pk, mk = sgd_fused_update(p[key], g[key], st0["m"][key], lr=0.2,
                                  mu=0.9, wd=0.01, backend="interpret")
        np.testing.assert_allclose(np.asarray(pk), np.asarray(p_ref[key]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(mk), np.asarray(st_ref["m"][key]),
                                   atol=1e-6)


def test_sgd_kernel_traced_lr():
    """The engines drive lr from lr_fn(state.step) INSIDE jit — the kernel
    must accept a traced scalar (SMEM operand on the Pallas path), not a
    baked-in Python float, and agree with the concrete-lr result."""
    rng = np.random.default_rng(3)
    p, g, m = _rand(rng, 1000), _rand(rng, 1000), _rand(rng, 1000, scale=0.1)
    for backend in ("ref", "interpret"):
        # compare jit-vs-jit (the engine always runs jitted; eager op-by-op
        # dispatch differs by FMA contraction, which is not the contract)
        want = jax.jit(lambda b=backend: sgd_fused_update(
            p, g, m, lr=0.07, mu=0.9, wd=0.01, backend=b))()
        f = jax.jit(lambda lr, b=backend: sgd_fused_update(
            p, g, m, lr=lr, mu=0.9, wd=0.01, backend=b))
        got = f(jnp.float32(0.07))
        for x, y in zip(want, got):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=1e-6)


def test_fused_optimizer_path_bitwise_golden():
    """Satellite guardrail: optim.sgd's fused flat-buffer path (the hot
    path, SGDConfig.fused=True default) is BITWISE identical to the
    per-leaf tree-map oracle at the default config — including under
    jit+vmap with a traced lr, i.e. exactly how the engine calls it."""
    import dataclasses

    from repro.optim.sgd import SGDConfig, sgd_init, sgd_update
    rng = np.random.default_rng(0)
    p = {"a": _rand(rng, 300), "b": {"c": _rand(rng, 77).reshape(7, 11),
                                     "d": _rand(rng, 1)[0]}}
    g = jax.tree.map(lambda x: jnp.asarray(
        rng.normal(size=x.shape), jnp.float32), p)
    for kw in (dict(), dict(nesterov=True, weight_decay=0.01)):
        cfg = SGDConfig(lr=0.2, momentum=0.9, **kw)
        st = sgd_init(cfg, p)
        st = {"m": jax.tree.map(lambda x: jnp.asarray(
            rng.normal(size=x.shape) * 0.1, jnp.float32), p)}
        unfused = dataclasses.replace(cfg, fused=False)
        run = lambda c: jax.jit(jax.vmap(  # noqa: E731
            lambda pp, gg, mm, lr: sgd_update(c, pp, gg, {"m": mm}, lr),
            in_axes=(0, 0, 0, None)))(
                jax.tree.map(lambda x: jnp.stack([x, x * 1.5]), p),
                jax.tree.map(lambda x: jnp.stack([x, x * 0.5]), g),
                jax.tree.map(lambda x: jnp.stack([x, x * 2.0]), st["m"]),
                jnp.float32(0.033))
        a, b = run(cfg), run(unfused)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("shape", [(8, 256), (16, 512), (64, 128)])
def test_kernel_block_shapes_aligned(shape):
    """BlockSpec tiling stays 128-lane / 8-sublane aligned for arbitrary
    padded inputs (the ops.py wrapper guarantees this)."""
    n = shape[0] * shape[1] - 13  # force padding
    rng = np.random.default_rng(0)
    x = _rand(rng, n)
    u = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    q, s, pad = quantize_mod(x, x, u, block=shape[1], backend="interpret")
    assert q.shape[1] % 128 == 0 and q.shape[0] % 8 == 0
    out = decode_avg(q, s, x, block=shape[1], backend="interpret")
    assert out.shape == x.shape


@pytest.mark.parametrize("matched", [False, True], ids=["plain", "matched"])
@pytest.mark.parametrize("bits,pack4", [(2, False), (4, True), (8, False),
                                        (16, False)],
                         ids=["q2", "q4-pack4", "q8", "q16"])
def test_codec_kernels_interpret_bitwise_ref(bits, pack4, matched):
    """Every codec variant's Pallas body (int32 code arithmetic, narrowed
    only at the store) reproduces the jnp oracle bit for bit: wire codes,
    scales and the decoded average."""
    rng = np.random.default_rng(bits)
    n = 24 * 256 - 5
    x = _rand(rng, n)
    ref = x + _rand(rng, n, scale=0.01)
    u = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    m = jnp.asarray(rng.random(24) < 0.5) if matched else None
    out = {}
    for backend in ("ref", "interpret"):
        q, s, _ = jax.jit(lambda a, b, c, be=backend: quantize_mod(
            a, b, c, bits=bits, pack4=pack4, backend=be))(x, ref, u)
        d = jax.jit(lambda q, s, y, mm, be=backend: decode_avg(
            q, s, y, bits=bits, matched=mm, pack4=pack4, backend=be))(
                q, s, ref, m)
        out[backend] = (q, s, d)
    for a, b in zip(out["ref"], out["interpret"]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
